"""Step programs (decode): ``decode_mixer_ms`` (see that reader) for the scopes of role ``ffn``
(``mlp``, ``ffn``, ``moe`` and its sub-scopes: router, placement, the hit experts, the shared expert)."""

from benchmark import scopes


def read(obs):
    return scopes.fused_role_ms(obs, "ffn")
