"""``prefill_sparse_ms_per_ktok`` (see that reader) for scope ``shortconv`` (a gated short-convolution
layer whole: its input projection and gates, the depthwise convolution ``shortconv.conv`` and the
output projection)."""

from benchmark.common import load_reader


def read(obs):
    return load_reader("prefill_sparse_ms_per_ktok")(obs, kind="shortconv")
