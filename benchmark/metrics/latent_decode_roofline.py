"""Kernels: the latent decode attention's share of its roofline in the traced stretch. The least
time one call could take on the chip's published peaks (the configuration's family counts it,
``latent_attention_least``: the latent rows of the step's live blocks read once, and for each
head a score over the row and a weighted sum over its latent part; the larger of bytes over HBM
bandwidth and FLOPs over peak) over the device time the trace gives the kernel's calls: the
operations named ``latent_decode_attention*`` (the kernel's ``name=``, so one inside the step's
layer scan is seen by name). The live rows of a step come from the program's flight log:
``attn_blocks_read`` (the blocks the step's lanes hold, over all layers) times the positions in a
block (the cache's positions over ``attn_blocks_total``), a mean over the stretch's decode steps;
a call is one layer's share of a step. A family without such a count, a program whose log lacks
the fields or whose step runs no such kernel (the parent of PR 36, the XLA form), or a stretch
without a decode step: nothing to read."""

from statistics import fmean

from benchmark import common, flight


def read(obs):
    trace = (obs.get("worker") or {}).get("trace") or {}
    if not trace.get("trace_host") or "peaks" not in obs:
        return None
    log = flight.records(obs)
    calls = [v for k, v in (trace.get("ops") or {}).items() if k.startswith("latent_decode_attention")]
    if log is None or not calls:
        return None
    least = getattr(common.load_family(obs["config"]["family"]), "latent_attention_least", None)
    a, b = trace["trace_host"]
    steps = [s for s in log["steps"] if a <= s["t"] < b and s.get("attn_blocks_read")]
    secs = sum(v[1] for v in calls)
    if least is None or not steps or not secs:
        return None
    kv, layers = obs["worker"]["kv"], obs["config"]["num_hidden_layers"]
    positions_a_block = kv["allocated_bytes"] / kv["bytes_per_token"] * layers / steps[0]["attn_blocks_total"]
    need = least(obs["config"], rows=fmean(s["attn_blocks_read"] for s in steps) * positions_a_block / layers)
    one_call_s = max(need["bytes"] / obs["peaks"]["hbm_bytes_per_s"], need["flops"] / obs["peaks"]["bf16_flops"])
    return 100.0 * one_call_s * sum(v[0] for v in calls) / secs
