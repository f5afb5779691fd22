"""Per-sequence state beside the slot KV rows.

A layer that keeps one fixed-size state per sequence (a recurrent
layer's state, a short convolution's window) has no positions to index:
its cache is ``[layers of that kind, slots, *shape]``, a decode step
overwrites a slot's entry and an admission replaces it whole with what
the prefill computed at the prompt's true length. That replacement IS
the reset of a recycled slot: nothing of the previous sequence survives
it. What is kept, by layer kind, comes from the model's description
(``config.cache_spec()``: entries marked ``"sequence"``); entries marked
``"position"`` are the KV rows of ``llm/kv_cache.py``.

The state cache is donated through the fused step and updated in place,
like the KV rows; its arrays live in one flat dict (entry names are
unique across kinds).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def sequence_entries(config) -> dict:
    """name -> (layers, shape, dtype) of every per-sequence entry of the model's cache."""
    return {name: (config.count(kind), shape, dtype)
            for kind, spec in config.cache_spec().items()
            for name, (shape, dtype, per) in spec.items() if per == "sequence"}


def alloc(config, num_slots: int) -> dict:
    return {name: jnp.zeros((layers, num_slots) + tuple(shape), jnp.dtype(dtype))
            for name, (layers, shape, dtype) in sequence_entries(config).items()}


def bytes_per_slot(config) -> int:
    return sum(layers * math.prod(shape) * jnp.dtype(dtype).itemsize
               for layers, shape, dtype in sequence_entries(config).values())


def insert_state(state: dict, slot, row, new: dict) -> dict:
    """Replace ``slot``'s state with row ``row`` of a prefill's batched outputs
    (``new[name]``: [layers, batch, *shape]). slot/row are traced scalars: one compiled program
    per prefill batch size serves every slot."""
    zero = jnp.zeros((), jnp.int32)
    out = {}
    for name, arr in state.items():
        one = jax.lax.dynamic_index_in_dim(new[name], jnp.asarray(row, jnp.int32), 1, keepdims=True)
        start = (zero, jnp.asarray(slot, jnp.int32)) + (zero,) * (arr.ndim - 2)
        out[name] = jax.lax.dynamic_update_slice(arr, one.astype(arr.dtype), start)
    return out
