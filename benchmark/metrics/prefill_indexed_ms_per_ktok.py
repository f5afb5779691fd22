"""``prefill_sparse_ms_per_ktok`` (see that reader) for scope ``indexed`` (an attention layer under a
learned index whole: its projections, norms and rotation, the indexer's projections ``indexed.score``,
the choice ``indexed.select``, attention under it ``indexed.attend``, the output projection)."""

from benchmark.common import load_reader


def read(obs):
    return load_reader("prefill_sparse_ms_per_ktok")(obs, kind="indexed")
