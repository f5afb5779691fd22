"""Kernels: the Lightning rule's share of its roofline in the traced stretch's prefills. The least
time the rule can take for the prompt tokens admitted in the stretch, in every Lightning layer held
(the configuration's family counts one layer's, ``lightning_chunk_least``: q, k and v read and the
output written once, a sequence's state written once, the recurrence's own FLOPs with no term for a
chunk size; the larger of bytes over HBM bandwidth and FLOPs over peak), over the device time under
the scope ``lightning.chunk`` in the programs with ``prefill`` in their name
(``benchmark/scopes.py``). It reads the same work whatever runs the rule under that scope: XLA's
chunked form today, a kernel later. Padding is in the time, not in the least. A family without such
a count, a program without the scope, or a stretch that admitted nothing: nothing to read."""

from benchmark import common, scopes


def read(obs):
    s = scopes.summary(obs)
    if not s or "peaks" not in obs:
        return None
    family = common.load_family(obs["config"]["family"])
    least = getattr(family, "lightning_chunk_least", None)
    secs = scopes.scope_seconds(s, "prefill", "lightning.chunk")
    if least is None or not secs:
        return None
    a, b = obs["worker"]["trace"]["trace_host"]
    lengths = [r["prompt_tokens"] for r in (obs["worker"].get("requests") or {}).values() if a <= (r["admit_t"] or 0) < b]
    if not lengths:
        return None
    need = least(obs["config"], tokens=sum(lengths), sequences=len(lengths))
    one_layer_s = max(need["bytes"] / obs["peaks"]["hbm_bytes_per_s"], need["flops"] / obs["peaks"]["bf16_flops"])
    return 100.0 * family.kinds(obs["config"]).count("L") * one_layer_s / secs
