"""Kernels: the sparse decode attention's share of its roofline in the traced stretch. The least
time one call could take on the chip's published peaks (the configuration's family counts it,
``sparse_decode_least``: the keys and values of the blocks the step's lanes CHOSE, a key-value
head's share each, read once; 16 heads' scores and weighted sums over them; the larger of bytes
over HBM bandwidth and FLOPs over peak) over the device time the trace gives the kernel's calls:
the operations named ``sparse_decode_attention*`` (the kernel's ``name=``, so one inside the step
is seen by name). The blocks of a step come from the program's flight log: ``sparse_blocks_read``
(over all sparse layers), a mean over the stretch's decode steps; a call is one layer's share of a
step. The kernel fetches every key-value head's rows of a block for each head's table (twice the
least at two heads), so one that streams at the HBM's peak reads 50 here, not 100. A family without
such a count, a program whose log lacks the field or whose step runs no such kernel (the XLA form),
or a stretch without a decode step: nothing to read."""

from statistics import fmean

from benchmark import common, flight


def read(obs):
    trace = (obs.get("worker") or {}).get("trace") or {}
    if not trace.get("trace_host") or "peaks" not in obs:
        return None
    log = flight.records(obs)
    calls = [v for k, v in (trace.get("ops") or {}).items() if k.startswith("sparse_decode_attention")]
    if log is None or not calls:
        return None
    family = common.load_family(obs["config"]["family"])
    least = getattr(family, "sparse_decode_least", None)
    a, b = trace["trace_host"]
    steps = [s for s in log["steps"] if a <= s["t"] < b and s.get("sparse_blocks_read")]
    secs = sum(v[1] for v in calls)
    if least is None or not steps or not secs:
        return None
    need = least(obs["config"], blocks=fmean(s["sparse_blocks_read"] for s in steps) / family.kinds(obs["config"]).count("S"))
    one_call_s = max(need["bytes"] / obs["peaks"]["hbm_bytes_per_s"], need["flops"] / obs["peaks"]["bf16_flops"])
    return 100.0 * one_call_s * sum(v[0] for v in calls) / secs
