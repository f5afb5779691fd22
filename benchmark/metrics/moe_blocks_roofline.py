"""Kernels: the routed experts' blocks' share of their roofline in the traced stretch's prefills.
The least time the held experts' blocks of the stretch's admitting steps can take on the chip's
published peaks (the configuration's family counts it, ``moe_blocks_least``, for one expert layer
of one call: the three matrices of every held expert that got a pair read ONCE, each held pair's
row read and its output written, 2 x 3 x F x H FLOPs a pair; the larger of bytes over HBM bandwidth
and FLOPs over peak), over the device time under the scope ``moe.blocks`` in the programs with
``prefill`` in their name (``benchmark/scopes.py``). It reads the same work whatever runs the blocks
under that scope: the XLA loop, which fetches an expert's matrices anew for every block, or the
kernel of ``ops/grouped_experts.py``, which holds them for a run of blocks. The experts hit and the
pairs held come from the program's flight log: ``prefill_experts_hit`` (a mean over the expert
layers and over the step's prefill programs, so times the programs, one for each group of the
step's ``prefill_dispatch_t``) and ``prefill_moe_pairs_local`` (a mean over the expert layers,
summed over the programs) of the stretch's admitting steps, times the expert layers held. A step
that prefilled in several programs is counted as one call, which can only lower the bound; the
padding of an expert's run to whole blocks is in the time, not in the least. A family without such
a count, a program without the scope or whose log lacks the fields, or a stretch that admitted
nothing: nothing to read."""

from benchmark import common, flight, scopes


def read(obs):
    s = scopes.summary(obs)
    log = flight.records(obs)
    if not s or log is None or "peaks" not in obs:
        return None
    family = common.load_family(obs["config"]["family"])
    least = getattr(family, "moe_blocks_least", None)
    secs = scopes.scope_seconds(s, "prefill", "moe.blocks")
    if least is None or not secs:
        return None
    a, b = obs["worker"]["trace"]["trace_host"]
    c = obs["config"]
    layers = c["num_hidden_layers"] - c["num_dense_layers"]
    least_s = 0.0
    for row in (r for r in log["steps"] if a <= r["t"] < b and r.get("prefill_moe_pairs_local")):
        need = least(c, experts_hit=row["prefill_experts_hit"] * len(row.get("prefill_dispatch_t") or [0]), pairs_local=row["prefill_moe_pairs_local"])
        least_s += layers * max(need["bytes"] / obs["peaks"]["hbm_bytes_per_s"], need["flops"] / obs["peaks"]["bf16_flops"])
    return 100.0 * least_s / secs if least_s else None
