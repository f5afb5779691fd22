"""ray_tpu/_config.py: the flag registry holds only flags the program reads."""

import dataclasses
import os
import re

import ray_tpu
from ray_tpu._config import Config

PKG = os.path.dirname(os.path.abspath(ray_tpu.__file__))


def test_every_flag_is_read_somewhere_in_the_package():
    """A flag that no line of ray_tpu/ reads is a promise to whoever sets
    RT_<NAME>: they are told nothing and get nothing."""
    source = []
    for d, _, names in os.walk(PKG):
        for n in names:
            if n.endswith(".py") and os.path.join(d, n) != os.path.join(PKG, "_config.py"):
                with open(os.path.join(d, n), encoding="utf-8") as f:
                    source.append(f.read())
    source = "\n".join(source)
    # `extra` is not a flag: it is where update() keeps the keys it does not know
    flags = [f.name for f in dataclasses.fields(Config) if f.name != "extra"]
    unread = [n for n in flags if not re.search(rf"\b{n}\b", source)]
    assert unread == [], f"Config declares flags nothing under ray_tpu/ reads: {unread}"
