"""Kernels: the block-sparse attention's share of its roofline in the traced stretch's prefills.
The least time steps 1-5 can take for the prompts admitted in the stretch at their TRUE lengths, in
every sparse layer held (the configuration's family counts one layer's, ``sparse_attend_least``:
queries, keys, values and the output moved once, every query head's scores against the usable
compressed keys, attention over the CHOSEN blocks only; the larger of bytes over HBM bandwidth and
FLOPs over peak), over the device time under the scopes ``sparse.select`` and ``sparse.attend`` in
the programs with ``prefill`` in their name (``benchmark/scopes.py``). It reads the same work
whatever runs it under those two scopes: XLA's masked tiles today, a kernel that skips the other
blocks later. Padding to the bucket and to a power of two of prompts is in the time, not in the
least. A family without such a count, a program without the scopes, or a stretch that admitted
nothing: nothing to read."""

from benchmark import common, scopes


def read(obs):
    s = scopes.summary(obs)
    if not s or "peaks" not in obs:
        return None
    family = common.load_family(obs["config"]["family"])
    least = getattr(family, "sparse_attend_least", None)
    secs = scopes.scope_seconds(s, "prefill", "sparse.select") + scopes.scope_seconds(s, "prefill", "sparse.attend")
    if least is None or not secs:
        return None
    a, b = obs["worker"]["trace"]["trace_host"]
    lengths = [r["prompt_tokens"] for r in (obs["worker"].get("requests") or {}).values() if a <= (r["admit_t"] or 0) < b]
    if not lengths:
        return None
    need = least(obs["config"], lengths=lengths)
    one_layer_s = max(need["bytes"] / obs["peaks"]["hbm_bytes_per_s"], need["flops"] / obs["peaks"]["bf16_flops"])
    return 100.0 * family.kinds(obs["config"]).count("S") * one_layer_s / secs
