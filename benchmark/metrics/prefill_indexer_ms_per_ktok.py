"""``prefill_sparse_ms_per_ktok`` (see that reader) for the scopes ``indexed.score`` and
``indexed.select`` together: the indexer's projections, its scores of every earlier position and
the choice of the best of them: what the model pays in a prefill to attend to less. Attention
under the choice (``indexed.attend``) is not in it."""

from benchmark.common import load_reader


def read(obs):
    per = load_reader("prefill_sparse_ms_per_ktok")
    parts = [per(obs, kind=kind) for kind in ("indexed.score", "indexed.select")]
    return sum(p for p in parts if p is not None) if any(p is not None for p in parts) else None
