"""Step programs (decode), by the program's own names: device time a call of the program with
``fused`` in its name under a scope of role ``mixer`` (a recurrent state's read-decay-write has a
role of its own, ``state``, and is not in it). ``benchmark/scopes.py`` says where the seconds come from."""

from benchmark import scopes


def read(obs):
    return scopes.fused_role_ms(obs, "mixer")
