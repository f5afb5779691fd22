"""Kernels: the decode step's attention under the learned index, its share of its roofline in the
traced stretch. The least time one step's indexed attention could take on the chip's published
peaks (the configuration's family counts one layer's, ``indexed_decode_least``: the CHOSEN rows of
``k`` and ``v`` of the step's lanes read once plus their ``k_idx`` rows once; 16 indexer heads'
products a scored row, 32 heads' scores and weighted sums a chosen row; the larger of bytes over HBM
bandwidth and FLOPs over peak), over the device time a call of the program with ``fused`` in its
name spends under the scopes ``indexed.score``, ``indexed.select`` and ``indexed.attend``
(``benchmark/scopes.py``): scoring the lane's rows, choosing, and attending to the chosen ones,
whatever runs them (a gather of single rows today). The rows of a step come from the program's
flight log: ``rows_scored`` and ``rows_chosen`` (over all layers), means over the stretch's decode
steps. A family without such a count, a program whose log lacks the fields or without the scopes, or
a stretch without a decode step: nothing to read."""

from statistics import fmean

from benchmark import common, flight, scopes


def read(obs):
    s = scopes.summary(obs)
    log = flight.records(obs)
    if not s or "peaks" not in obs or log is None:
        return None
    least = getattr(common.load_family(obs["config"]["family"]), "indexed_decode_least", None)
    secs = sum(scopes.scope_seconds(s, "fused", kind) for kind in ("indexed.score", "indexed.select", "indexed.attend"))
    calls = sum(r["calls"] for n, r in s["programs"].items() if "fused" in n)
    if least is None or not secs or not calls:
        return None
    a, b = obs["worker"]["trace"]["trace_host"]
    steps = [r for r in log["steps"] if a <= r["t"] < b and r.get("rows_scored")]
    if not steps:
        return None
    layers = obs["config"]["num_hidden_layers"]
    need = least(obs["config"], rows_scored=fmean(r["rows_scored"] for r in steps) / layers, rows_chosen=fmean(r["rows_chosen"] for r in steps) / layers)
    one_step_s = layers * max(need["bytes"] / obs["peaks"]["hbm_bytes_per_s"], need["flops"] / obs["peaks"]["bf16_flops"])
    return 100.0 * one_step_s * calls / secs
