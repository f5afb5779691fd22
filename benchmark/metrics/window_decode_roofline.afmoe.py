"""``window_decode_roofline`` (see that reader: the ring layers' decode attention's share of its
roofline in the traced stretch, by the family's ``window_decode_least`` and ``kinds``) at the AFMoE
description's 48 query heads over 8 key-value heads, three tiles of query rows a lane. An entry of its
own because ``tests/benchmark/test_smallthinker_family.py`` holds the first entry's ``workloads`` to
the cell that brought it."""

from benchmark.common import load_reader

read = load_reader("window_decode_roofline")
