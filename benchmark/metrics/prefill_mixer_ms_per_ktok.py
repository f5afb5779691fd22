"""Step programs (prefill), by the program's own names: device time, in the traced stretch, of
the operations under a scope of role ``mixer`` (whatever mixes along the sequence: ``attn``,
``gated_attn``, ``gdn``, ``mamba2``, ``mla`` and their sub-scopes; ``ray_tpu/util/profiling.SCOPES``)
in the programs with ``prefill`` in their name, per 1,000 prompt tokens admitted in that stretch
(``prefill_ms_per_ktok``'s denominator). ``benchmark/scopes.py`` says where the seconds come from."""

from benchmark import scopes


def read(obs):
    return scopes.prefill_role_ms_per_ktok(obs, "mixer")
