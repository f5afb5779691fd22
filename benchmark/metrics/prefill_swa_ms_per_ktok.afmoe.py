"""``prefill_swa_ms_per_ktok`` (see that reader: device time under scope ``swa`` and its sub-scopes in
the prefill programs per 1,000 prompt tokens) for the AFMoE description's window layers, whose scope
also holds the head norms, the output gate (``swa.gate``) and the sandwich's second norm. An entry of
its own because ``tests/benchmark/test_smallthinker_family.py`` holds the first entry's ``workloads``
to the cell that brought it."""

from benchmark.common import load_reader

read = load_reader("prefill_swa_ms_per_ktok")
