"""Kernels: the windowed flash attention's share of its roofline in the traced stretch's prefills.
The least time the window layers' attention can take for the prompts admitted in the stretch (the
configuration's family counts it, ``window_flash_least``: q read and the output written once, k and
v read once, in every window layer; a score and a weighted sum in every query head for each (query,
key) pair INSIDE the window, 4 x heads x head_dim FLOPs a pair; the larger of bytes over HBM
bandwidth and FLOPs over peak), over the device time the trace gives the kernel's calls: the
operations named ``window_flash_attention*`` (the kernel's ``name=``). The pairs come from the
program's flight log: ``swa_pairs`` of the stretch's admitting steps, summed over the window layers
at the prompts' TRUE lengths: the count is of the pairs the MATHEMATICS needs, whatever tiles the
kernel visits (a tile on the diagonal or on the window's edge is computed whole and half of it is
masked; padding to the bucket is in the time, not in the least). A family without such a count, a
program whose log lacks the field or whose prefill runs no such kernel, or a stretch that admitted
nothing: nothing to read."""

from benchmark import common, flight


def read(obs):
    trace = (obs.get("worker") or {}).get("trace") or {}
    if not trace.get("trace_host") or "peaks" not in obs:
        return None
    log = flight.records(obs)
    secs = sum(v[1] for k, v in (trace.get("ops") or {}).items() if k.startswith("window_flash_attention"))
    least = getattr(common.load_family(obs["config"]["family"]), "window_flash_least", None)
    if log is None or least is None or not secs:
        return None
    a, b = trace["trace_host"]
    steps = [s for s in log["steps"] if a <= s["t"] < b and s.get("swa_pairs")]
    if not steps:
        return None
    need = least(obs["config"], pairs=sum(s["swa_pairs"] for s in steps), tokens=sum(s["prefill_tokens"] for s in steps))
    return 100.0 * max(need["bytes"] / obs["peaks"]["hbm_bytes_per_s"], need["flops"] / obs["peaks"]["bf16_flops"]) / secs
