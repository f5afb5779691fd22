"""Step programs (prefill): ``prefill_mixer_ms_per_ktok`` (see that reader) for the scopes of role
``ffn`` (whatever acts on a position alone: ``mlp``, ``ffn``, ``moe`` and its sub-scopes)."""

from benchmark import scopes


def read(obs):
    return scopes.prefill_role_ms_per_ktok(obs, "ffn")
