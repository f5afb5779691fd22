"""``prefill_sparse_ms_per_ktok`` (see that reader) for scope ``mamba1`` (a Mamba-1 selective state-space
layer whole: its input projection, the depthwise convolution ``mamba1.conv``, the projections to the
step, B and C with their norms, the recurrence ``mamba1.scan``, the gate and the output projection)."""

from benchmark.common import load_reader


def read(obs):
    return load_reader("prefill_sparse_ms_per_ktok")(obs, kind="mamba1")
