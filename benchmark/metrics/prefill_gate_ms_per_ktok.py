"""``prefill_sparse_ms_per_ktok`` (see that reader) for the scopes ``swa.gate`` and ``attn.gate`` together (an
attention layer's output gate: the projection ``h W_g``, as wide as the queries, and the product
``o * sigmoid(g)`` before the output projection): the part of ``prefill_mixer_ms_per_ktok`` that the gate
costs. A program without the scopes, or a stretch that admitted nothing: nothing to read."""

from benchmark.common import load_reader


def read(obs):
    by_kind = load_reader("prefill_sparse_ms_per_ktok")
    found = [ms for ms in (by_kind(obs, kind=kind) for kind in ("swa.gate", "attn.gate")) if ms is not None]
    return sum(found) if found else None
