"""``prefill_sparse_ms_per_ktok`` (see that reader) for scope ``mamba1.scan``: the selective scan alone,
from the convolution's output, the step, B and C to ``y`` before the gate (the kernel
``selective_scan*`` and what XLA puts around it, or the XLA form where the kernel is refused): the part
of ``prefill_mamba1_ms_per_ktok`` that no matmul does."""

from benchmark.common import load_reader


def read(obs):
    return load_reader("prefill_sparse_ms_per_ktok")(obs, kind="mamba1.scan")
