"""Step programs: the least time the traced stretch's prefills can take on the chip's published
peaks, over the device time the trace gives the programs with ``prefill`` in their name. The least
time of ONE admitting step is the larger of bytes over HBM bandwidth and FLOPs over peak, counted
by the configuration's family (``prefill_least``: every weight outside the routed experts once,
the held experts that got a pair once, FLOPs at the TRUE prompt lengths) from what the program's
flight log says of that step: the prompts it took in (the requests whose admit stamp lies in the
step; their true lengths), ``prefill_moe_pairs_local`` and ``prefill_experts_hit``. A step that
prefilled in several programs (several buckets) is counted as if it were one call, which can
only lower the bound. A family without such a count, a program whose log lacks the fields (the
parent of PR 34), or a stretch without an admitting step: nothing to read."""

from benchmark import common, flight


def read(obs):
    trace = (obs.get("worker") or {}).get("trace") or {}
    log = flight.records(obs)
    if not trace.get("trace_host") or "peaks" not in obs or log is None:
        return None
    least = getattr(common.load_family(obs["config"]["family"]), "prefill_least", None)
    a, b = trace["trace_host"]
    rows = [s for s in log["steps"] if a <= s["t"] < b and s.get("prefill_tokens")]
    secs = sum(v[1] for k, v in (trace.get("programs") or {}).items() if "prefill" in k)
    if least is None or not rows or not secs:
        return None
    least_s = 0.0
    for s in rows:
        lengths = [r["prompt_tokens"] for r in log["requests"].values() if s["t0"] <= (r.get("admit_t") or 0.0) <= s["t"]]
        if sum(lengths) != s["prefill_tokens"]:
            # the stamps do not tell the prompts apart: as many equal prompts as the step admitted have the least causal attention of any split
            lengths = [s["prefill_tokens"] / s["admitted"]] * s["admitted"]
        need = least(obs["config"], lengths=lengths, pairs_local=s["prefill_moe_pairs_local"], experts_hit=s["prefill_experts_hit"])
        least_s += max(need["bytes"] / obs["peaks"]["hbm_bytes_per_s"], need["flops"] / obs["peaks"]["bf16_flops"])
    return 100.0 * least_s / secs
