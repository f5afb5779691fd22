"""Slot-based KV cache for continuous-batching decode.

Static-shaped by design: XLA compiles the decode step once for the whole
serving lifetime. The cache is a pytree of stacked per-layer arrays

    k, v: [L, slots, max_seq_len, kv_heads, head_dim]
    length: [slots] int32   (tokens currently valid per slot; 0 = empty)

``k`` and ``v`` are the per-position ENTRIES of a model whose attention keeps keys and values by
head. A hybrid description names its own (``HybridDescription.position_entries()``: ``k`` and ``v``
again, or a latent layer's ``c_kv`` [r] and ``k_r`` [rope]); the cache is then one stacked array
``[layers that keep it, slots, max_seq_len, *shape]`` for each, allocated, inserted into and counted
by the same functions (``alloc_entries``, ``insert_entries``, ``entry_bytes_per_token``).
Not every entry spans ``max_seq_len``: an entry the description names a RING
(``HybridDescription.ring_entries()``: a sliding-window layer's keys and values, window W) is
``[layers, slots, min(W, max_seq_len), *shape]`` and holds a sequence's LAST W positions, position
p at row p mod W: a prompt's last min(length, W) positions are placed at their rows by
``insert_entries``, a decode step writes at ``length mod W`` (``models/hybrid.LayerCache``) and
attends over min(length + 1, W) rows, in whatever order they lie. A ring's bytes follow the
window and not the sequence; what it cannot do (a page table, a handoff, a prefix hit, a copy of
"the first T positions") the engine refuses by name for every description alike.

A "slot" is one concurrent sequence. Admission = prefill writes a new
sequence's K/V into a free slot at offset 0; decode appends one token per
active slot per step via per-slot dynamic_update_slice. This is the
TPU-native answer to vLLM's paged KV blocks (ref capability:
python/ray/llm/_internal/serve/engines/vllm/vllm_models.py:215-228):
on TPU, static shapes + donation beat dynamic paging because XLA aliases
the cache in-place and the MXU sees one fixed program.

Sizing (from shapes, 1.1B-param llama, bf16 cache): cache HBM is exactly
linear in slots x max_seq_len — 0.69 GiB at 8x2048, 2.75 GiB at 8x8192 or
32x2048. Against ~16 GiB HBM minus ~2.2 GiB weights, the static design
holds 8 slots to ~32K tokens or 32 slots to ~8K; past that working set
(e.g. 32 slots x 32K = 11 GiB + activations) is where block paging or
prefix sharing becomes necessary rather than merely nice. How step time
scales with slots is not measured on today's code (PERF.md).

Int8 cache (``dtype="int8"``, llm/kv_quant.py) moves that threshold by
``2*hd/(hd+4)``: per token per layer the cache stores ``2*kv*(hd + 4)``
bytes (int8 values + one f32 per-head scale) instead of ``2*kv*hd*2``
bf16 bytes — 1.94x fewer at hd=128. The 11 GiB 32x32K working set above
drops to ~5.7 GiB, so the same ~13.8 GiB budget that capped bf16 at 32
slots x 8K holds int8 at 32 slots to ~16K or ~62 slots at 8K — and since
decode is HBM-bandwidth-bound, the bytes each step streams shrink by the
same factor. Quantization happens on append inside the fused step;
attention dequantizes on read (scale layout [L, B, kv, S]: position axis
last, so scale tiles waste nothing — see kv_quant.py).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ray_tpu.llm.kv_quant import dequantize, is_int8, quantize_heads


@dataclass(frozen=True)
class CacheConfig:
    num_layers: int
    num_slots: int
    max_seq_len: int
    num_kv_heads: int
    head_dim: int
    dtype: str = "bfloat16"  # bf16/f32 variants, or "int8" (kv_quant.py)


def alloc_entries(entries: dict, num_slots: int, max_seq_len: int, rings: dict | None = None) -> dict:
    """The slot cache of ``entries``: name -> (layers, shape of one position, dtype). ``rings``:
    name -> W for the entries that hold a sequence's last W positions only (W rows a slot)."""
    cache = {name: jnp.zeros((layers, num_slots, min((rings or {}).get(name, max_seq_len), max_seq_len)) + tuple(shape), jnp.dtype(dtype))
             for name, (layers, shape, dtype) in entries.items()}
    return {**cache, "length": jnp.zeros((num_slots,), dtype=jnp.int32)}


def entry_bytes_per_token(entries: dict) -> int:
    """Bytes one position of one sequence takes in the cache of ``entries``, over all its layers."""
    return sum(layers * math.prod(shape) * jnp.dtype(dtype).itemsize for layers, shape, dtype in entries.values())


def insert_entries(cache: dict, slot, new: dict, length, rings: frozenset = frozenset()) -> dict:
    """Write a prefilled sequence's entries into ``slot`` at offset 0. ``new[name]``: [layers,
    T_pad, *shape] (the padded tail is garbage and stays masked by ``length``). slot/length:
    traced scalars, so one compiled program serves every slot and every prefill bucket. An entry of
    ``rings`` with fewer rows W than T_pad takes the prompt's LAST min(length, W) positions, each
    at its row p mod W: a gather of W of the T_pad positions by the true length (row r holds
    position base + (r - base) mod W with base = max(length - W, 0); while length <= W that is
    position r itself, and the rows from ``length`` on are garbage that ``length`` masks)."""
    zero = jnp.zeros((), dtype=jnp.int32)
    out = {}
    for name, arr in new.items():
        W = cache[name].shape[2]
        if name in rings and arr.shape[1] > W:
            base = jnp.maximum(jnp.asarray(length, jnp.int32) - W, 0)
            arr = jnp.take(arr, base + (jnp.arange(W, dtype=jnp.int32) - base) % W, axis=1)
        start = (zero, jnp.asarray(slot, jnp.int32)) + (zero,) * (arr.ndim - 1)
        out[name] = jax.lax.dynamic_update_slice(cache[name], arr[:, None].astype(cache[name].dtype), start)
    return {**out, "length": cache["length"].at[slot].set(jnp.asarray(length, jnp.int32))}


def alloc(cfg: CacheConfig) -> dict:
    shape = (cfg.num_layers, cfg.num_slots, cfg.max_seq_len, cfg.num_kv_heads, cfg.head_dim)
    if is_int8(cfg.dtype):
        # per-head scales with the position axis LAST ([L, B, kv, S]) so
        # the trailing dims stay on (8,128) tile multiples (kv_quant.py)
        sshape = (cfg.num_layers, cfg.num_slots, cfg.num_kv_heads, cfg.max_seq_len)
        return {
            "k": jnp.zeros(shape, dtype=jnp.int8),
            "v": jnp.zeros(shape, dtype=jnp.int8),
            "k_scale": jnp.zeros(sshape, dtype=jnp.float32),
            "v_scale": jnp.zeros(sshape, dtype=jnp.float32),
            "length": jnp.zeros((cfg.num_slots,), dtype=jnp.int32),
        }
    one = (cfg.num_layers, (cfg.num_kv_heads, cfg.head_dim), cfg.dtype)
    return alloc_entries({"k": one, "v": one}, cfg.num_slots, cfg.max_seq_len)


def insert_sequence(cache: dict, slot, k_new, v_new, length, k_scale=None, v_scale=None):
    """Write a prefilled sequence into `slot` at offset 0.

    k_new/v_new: [L, T_pad, kv_heads, head_dim] (padded tail is garbage and
    stays masked by `length`). slot/length: traced scalars — one compiled
    program serves every slot and every prefill bucket.

    Dtype adaptation is transparent in all four directions: fp block into
    an int8 cache quantizes here (prefill writes quantized blocks); an
    int8 block (+ ``k_scale``/``v_scale`` [L, kv, T_pad], the handoff wire
    layout) into an int8 cache copies bytes; int8 into an fp cache
    dequantizes; fp into fp is the original path.
    """
    zero = jnp.zeros((), dtype=jnp.int32)
    start = (zero, jnp.asarray(slot, jnp.int32), zero, zero, zero)
    quant = "k_scale" in cache
    if not quant and k_scale is not None:  # int8 block -> fp cache
        k_new = dequantize(k_new, k_scale.transpose(0, 2, 1))
        v_new = dequantize(v_new, v_scale.transpose(0, 2, 1))
        k_scale = v_scale = None
    if not quant:
        return insert_entries(cache, slot, {"k": k_new, "v": v_new}, length)
    if k_scale is None:  # fp block -> quantize on insert
        k_new, sk = quantize_heads(k_new)  # sk: [L, T, kv]
        v_new, sv = quantize_heads(v_new)
        k_scale, v_scale = sk.transpose(0, 2, 1), sv.transpose(0, 2, 1)
    s_start = (zero, jnp.asarray(slot, jnp.int32), zero, zero)
    k_sc = jax.lax.dynamic_update_slice(cache["k_scale"], k_scale[:, None].astype(jnp.float32), s_start)
    v_sc = jax.lax.dynamic_update_slice(cache["v_scale"], v_scale[:, None].astype(jnp.float32), s_start)
    k = jax.lax.dynamic_update_slice(cache["k"], k_new[:, None].astype(cache["k"].dtype), start)
    v = jax.lax.dynamic_update_slice(cache["v"], v_new[:, None].astype(cache["v"].dtype), start)
    lens = cache["length"].at[slot].set(jnp.asarray(length, jnp.int32))
    return {"k": k, "v": v, "k_scale": k_sc, "v_scale": v_sc, "length": lens}


def extract_sequence(cache: dict, slot, T: int):
    """Read one slot's first ``T`` cached positions as a contiguous block.

    Inverse of insert_sequence: returns (k [L, T, kv, hd], v same) — the
    disaggregated-prefill extract primitive (llm/disagg/) — plus, for an
    int8 cache, (k_scale [L, kv, T], v_scale same): the handoff wire
    layout, so quantized blocks ship self-describing at ~half the bytes.
    ``T`` is static (one compiled program per prefill bucket, like
    insert); ``slot`` is a traced scalar. Positions past the slot's real
    length are garbage the consumer masks by length, exactly as
    prefill's padded tail."""
    zero = jnp.zeros((), dtype=jnp.int32)
    start = (zero, jnp.asarray(slot, jnp.int32), zero, zero, zero)
    L, _, _, kv, hd = cache["k"].shape
    size = (L, 1, T, kv, hd)
    k = jax.lax.dynamic_slice(cache["k"], start, size)[:, 0]
    v = jax.lax.dynamic_slice(cache["v"], start, size)[:, 0]
    if "k_scale" in cache:
        s_start = (zero, jnp.asarray(slot, jnp.int32), zero, zero)
        s_size = (L, 1, kv, T)
        k_sc = jax.lax.dynamic_slice(cache["k_scale"], s_start, s_size)[:, 0]
        v_sc = jax.lax.dynamic_slice(cache["v_scale"], s_start, s_size)[:, 0]
        return k, v, k_sc, v_sc
    return k, v


def free_slot(cache: dict, slot: int) -> dict:
    """Mark a slot empty (host-side bookkeeping mirrors this)."""
    return {**cache, "length": cache["length"].at[slot].set(0)}
