"""Kernels: the flash attention kernels' share of their roofline in the traced steps. The least
time the attention calls could take (``benchmark/flops.py``: each forward call the trace shows x
the forward bound, plus one backward pass per layer and traced step x the backward bound, each
bound the larger of FLOPs over peak and bytes over peak) over the device time the trace gives
the kernels (``_fwd_pallas*`` and ``_bwd_pallas*`` custom calls). A backward pass counts once
however many kernels it is split into; a forward recomputed by remat is a call like any other."""

from benchmark.flops import flash_roofline


def read(obs):
    train = obs.get("train")
    trace = (obs.get("worker") or {}).get("trace") or {}
    if not train or not trace.get("ops") or "peaks" not in obs or not trace.get("traced_steps"):
        return None
    fwd = [v for k, v in trace["ops"].items() if "_fwd_pallas" in k]
    bwd = [v for k, v in trace["ops"].items() if "_bwd_pallas" in k]
    secs = sum(v[1] for v in fwd + bwd)
    if not fwd or not bwd or not secs:
        return None
    mix, chips, c = obs["mix"], obs["device"]["count"], obs["config"]
    bound = flash_roofline(c, int(mix["global_batch"]) // chips, int(mix["seq_len"]), obs["peaks"])
    n_bwd = c["num_hidden_layers"] * trace["traced_steps"]
    least = sum(v[0] for v in fwd) * bound["fwd"]["min_s"] + n_bwd * bound["bwd"]["min_s"]
    return 100.0 * least / secs
