"""Step programs (prefill), by the program's own names: device time, in the traced stretch, of the
operations under scope ``sparse`` and all its sub-scopes (a block-sparse attention layer whole: its
projections, norms and gate, ``sparse.select`` and ``sparse.attend``) in the programs with
``prefill`` in their name, per 1,000 prompt tokens admitted in that stretch
(``prefill_ms_per_ktok``'s denominator): the part of ``prefill_mixer_ms_per_ktok`` that is this
mixer's. ``benchmark/scopes.py`` says where the seconds come from. A program without the scope, or
a stretch that admitted nothing: nothing to read."""

from benchmark import scopes


def read(obs, kind="sparse"):
    s = scopes.summary(obs)
    secs = scopes.scope_seconds(s, "prefill", kind) if s else 0.0
    if not secs:
        return None
    a, b = obs["worker"]["trace"]["trace_host"]
    tokens = sum(r["prompt_tokens"] for r in (obs["worker"].get("requests") or {}).values() if a <= (r["admit_t"] or 0) < b)
    return secs * 1e3 / (tokens / 1000.0) if tokens else None
