"""Step programs (decode): ``decode_mixer_ms`` (see that reader) for the scopes of role ``state``
(``mamba2.state``, ``gdn.state``: a recurrent state read, decayed, written and read out, once a
layer that keeps one). A model without such a layer: nothing to read."""

from benchmark import scopes


def read(obs):
    return scopes.fused_role_ms(obs, "state")
