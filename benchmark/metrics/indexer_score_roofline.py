"""Kernels: the indexer's share of its roofline in the traced stretch's prefills. The least time
the index scores can take for the prompts admitted in the stretch at their TRUE lengths, in every
layer held (the configuration's family counts one layer's, ``indexer_score_least``: the indexer's
queries, weights and keys moved once, a 64-deep product in each of 16 heads for every causal (query,
position) pair; the larger of bytes over HBM bandwidth and FLOPs over peak), over the device time
under the scopes ``indexed.score`` and ``indexed.select`` in the programs with ``prefill`` in their
name (``benchmark/scopes.py``): the scoring AND the choice, because a form that finds a query's
threshold where it computes its scores has one time for both; the choice's own work (a threshold,
a sort) is in the time and not in the least. The pairs come from the program's flight log:
``pairs_scored`` of the stretch's admitting steps, summed over the layers. A family without such a
count, a program whose log lacks the field or without the scopes, or a stretch that admitted nothing:
nothing to read."""

from benchmark import common, flight, scopes


def read(obs, least_name="indexer_score_least", counter="pairs_scored", kinds=("indexed.score", "indexed.select")):
    s = scopes.summary(obs)
    log = flight.records(obs)
    if not s or "peaks" not in obs or log is None:
        return None
    least = getattr(common.load_family(obs["config"]["family"]), least_name, None)
    secs = sum(scopes.scope_seconds(s, "prefill", kind) for kind in kinds)
    if least is None or not secs:
        return None
    a, b = obs["worker"]["trace"]["trace_host"]
    steps = [r for r in log["steps"] if a <= r["t"] < b and r.get(counter)]
    if not steps:
        return None
    layers = obs["config"]["num_hidden_layers"]
    need = least(obs["config"], pairs=sum(r[counter] for r in steps) / layers, tokens=sum(r["prefill_tokens"] for r in steps))
    return 100.0 * layers * max(need["bytes"] / obs["peaks"]["hbm_bytes_per_s"], need["flops"] / obs["peaks"]["bf16_flops"]) / secs
