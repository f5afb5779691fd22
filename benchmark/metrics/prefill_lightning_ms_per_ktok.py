"""``prefill_sparse_ms_per_ktok`` (see that reader) for scope ``lightning`` and its sub-scopes (a
Lightning linear-attention layer whole: its projections, norms, rotation and gate, and
``lightning.chunk``, the recurrence in chunks)."""

from benchmark.common import load_reader


def read(obs):
    return load_reader("prefill_sparse_ms_per_ktok")(obs, kind="lightning")
