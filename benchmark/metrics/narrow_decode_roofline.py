"""Kernels: the decode step's attention over heads 64 wide, its share of its roofline in the traced
stretch. The least time one call could take on the chip's published peaks (the configuration's
family counts it, ``narrow_decode_least``: a key and a value by head, at the heads' TRUE width, for
every position the step's bound lanes hold, read once; every query head's score and weighted sum
over them; the larger of bytes over HBM bandwidth and FLOPs over peak) over the device time the
trace gives the kernel's calls: the operations named ``slot_decode_attention_narrow*`` (the
live-block kernel's ``name=`` over rows that hold two heads each, so one inside the step is seen by
name and told from ``slot_decode_attention*`` at heads 128 wide). The positions of a step come from
the program's flight log: ``narrow_rows_read`` (over all attention layers), a mean over the
stretch's decode steps; a call is one layer's share of a step. A family without such a count, a
program whose log lacks the field or whose step runs no such kernel (the XLA form), or a stretch
without a decode step: nothing to read."""

from statistics import fmean

from benchmark import common, flight


def read(obs):
    trace = (obs.get("worker") or {}).get("trace") or {}
    if not trace.get("trace_host") or "peaks" not in obs:
        return None
    log = flight.records(obs)
    calls = [v for k, v in (trace.get("ops") or {}).items() if k.startswith("slot_decode_attention_narrow")]
    if log is None or not calls:
        return None
    family = common.load_family(obs["config"]["family"])
    least = getattr(family, "narrow_decode_least", None)
    a, b = trace["trace_host"]
    steps = [s for s in log["steps"] if a <= s["t"] < b and s.get("narrow_rows_read")]
    secs = sum(v[1] for v in calls)
    if least is None or not steps or not secs:
        return None
    need = least(obs["config"], rows=fmean(s["narrow_rows_read"] for s in steps) / family.count(obs["config"], "full_attention"))
    one_call_s = max(need["bytes"] / obs["peaks"]["hbm_bytes_per_s"], need["flops"] / obs["peaks"]["bf16_flops"])
    return 100.0 * one_call_s * sum(v[0] for v in calls) / secs
