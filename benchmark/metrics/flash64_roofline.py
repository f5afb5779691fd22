"""Kernels: the flash attention kernel's share of its roofline at heads 64 wide, in the traced
stretch's prefills. The least time the attention layers' attention can take for the prompts admitted
in the stretch at the heads' TRUE width (the configuration's family counts it, ``flash64_least``: q
read and the output written once, k and v read once, in every attention layer; a score and a
weighted sum in every query head for each causal (query, key) pair, 4 x heads x 64 FLOPs a pair;
the larger of bytes over HBM bandwidth and FLOPs over peak), over the device time the trace gives
the kernel's calls: the operations with ``_fwd_pallas`` in their name (every flash call of such a
configuration is 64 wide). The pairs come from the program's flight log: ``narrow_pairs`` of the
stretch's admitting steps, summed over the attention layers at the prompts' TRUE lengths: the count
is of the pairs the MATHEMATICS needs at the width it needs (a kernel that multiplies a 64-wide head
on a 128-wide array does half of that a cycle, a tile on the diagonal is computed whole and half of
it masked, padding to the bucket is in the time, not in the least). A family without such a count,
a program whose log lacks the field or whose prefill runs no such kernel, or a stretch that
admitted nothing: nothing to read."""

from benchmark import common, flight


def read(obs):
    trace = (obs.get("worker") or {}).get("trace") or {}
    if not trace.get("trace_host") or "peaks" not in obs:
        return None
    log = flight.records(obs)
    secs = sum(v[1] for k, v in (trace.get("ops") or {}).items() if "_fwd_pallas" in k)
    least = getattr(common.load_family(obs["config"]["family"]), "flash64_least", None)
    if log is None or least is None or not secs:
        return None
    a, b = trace["trace_host"]
    steps = [s for s in log["steps"] if a <= s["t"] < b and s.get("narrow_pairs")]
    if not steps:
        return None
    need = least(obs["config"], pairs=sum(s["narrow_pairs"] for s in steps), tokens=sum(s["prefill_tokens"] for s in steps))
    return 100.0 * max(need["bytes"] / obs["peaks"]["hbm_bytes_per_s"], need["flops"] / obs["peaks"]["bf16_flops"]) / secs
