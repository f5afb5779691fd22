"""Step programs (prefill): what of an expert layer is the grouped matmul. Device time under scope
``moe.blocks`` over the time under ``moe`` and all its sub-scopes, in the programs with ``prefill``
in their name, in percent; the rest is routing (``moe.route``), placement and the gathers into and
out of the blocks (``moe.place``) and the shared expert (``moe.shared``)."""

from benchmark import scopes


def read(obs):
    return scopes.moe_blocks_share(obs)
