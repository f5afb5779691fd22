"""``window_flash_roofline`` (see that reader: the windowed flash attention's share of its roofline in
the traced stretch's prefills, by the family's ``window_flash_least``) at the AFMoE description's 48
query heads over 8 key-value heads. An entry of its own because
``tests/benchmark/test_smallthinker_family.py`` holds the first entry's ``workloads`` to the cell that
brought it."""

from benchmark.common import load_reader

read = load_reader("window_flash_roofline")
