"""``prefill_sparse_ms_per_ktok`` (see that reader) for scope ``swa`` (a sliding-window attention
layer whole: its projections, the rotation, the flash kernel with a window, the output projection,
and the router that reads the same normed stream, which keeps its own deeper name ``moe.route`` and
so counts for the expert layer, not here)."""

from benchmark.common import load_reader


def read(obs):
    return load_reader("prefill_sparse_ms_per_ktok")(obs, kind="swa")
