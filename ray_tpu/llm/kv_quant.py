"""Int8 KV-cache quantization: per-head amax scales, applied on append.

Decode is HBM-bandwidth-bound, and the
cache — not the weights — is the binding HBM constraint past the
threshold kv_cache.py documents, so halving cache bytes both doubles
servable concurrency at fixed HBM and shrinks the bytes every decode
step must stream. EQuARX (arxiv 2506.17615, PAPERS.md) is the TPU
precedent that aggressive quantization of bandwidth-bound tensors holds
up accuracy-wise.

Scheme: symmetric int8 with one float32 scale per (layer, position,
kv-head) — ``scale = amax(|x|, head_dim) / 127`` computed from the
exact K/V vector being appended, so no calibration pass exists and a
freshly written token is immediately self-describing. Quantization
happens INSIDE the fused append (prefill insert, decode append, spec
block append, disagg scatter-in); attention dequantizes on read at the
f32 compute dtype the score/value einsums already use, so the convert
never lands on a flops-dominant dot (the JXC003 trap — regression-locked
in tests/test_lint_rules.py).

Overhead: 4 scale bytes per head per position next to ``head_dim`` int8
bytes — cache bytes shrink by ``2*hd / (hd + 4)`` vs bf16 (1.94x at
hd=128), and the scales ride every wire format (disagg handoffs ship
int8 values + scales, halving object-plane bytes too).

Layout convention: value tensors keep their fp layout with dtype int8;
scale tensors put the POSITION axis last (``[..., kv_heads, S]``) so
their trailing dims land on (8, 128) tile multiples instead of wasting
15/16 of every tile the way a kv-heads-minor layout would (JXC006).

Quantization is idempotent at the byte level: re-quantizing a
dequantized block reproduces the same bytes (amax maps back to 127), so
a requant hop — e.g. an int8 handoff admitted by an fp consumer that
later re-prefills — cannot compound error.
"""

from __future__ import annotations

import jax.numpy as jnp

INT8_MAX = 127.0

# cache_dtype values LLMEngine accepts, normalized (anything else is a
# ValueError at engine construction, never a silent passthrough)
CACHE_DTYPES = {
    "bfloat16": "bfloat16",
    "bf16": "bfloat16",
    "float32": "float32",
    "f32": "float32",
    "int8": "int8",
}


def is_int8(dtype) -> bool:
    return str(dtype) == "int8"


def normalize_cache_dtype(dtype: str) -> str:
    """Validated, canonical cache dtype string (raises ValueError)."""
    try:
        return CACHE_DTYPES[str(dtype).lower()]
    except KeyError:
        raise ValueError(
            f"cache_dtype must be one of {sorted(set(CACHE_DTYPES))}, got {dtype!r}"
        ) from None


def quantize_heads(x):
    """Quantize over the trailing head_dim axis.

    x: [..., hd] float. Returns (q int8 [..., hd], scale f32 [...]) with
    ``scale = amax/127``; all-zero vectors (padded garbage, zeroed
    attention) quantize to q=0, scale=0 and dequantize back to exact 0.
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = amax / INT8_MAX
    inv = jnp.where(amax > 0.0, INT8_MAX / jnp.maximum(amax, 1e-30), 0.0)
    q = jnp.clip(jnp.round(xf * inv[..., None]), -INT8_MAX, INT8_MAX).astype(jnp.int8)
    return q, scale


def dequantize(q, scale):
    """q int8 [..., hd] * scale f32 broadcast over hd -> f32 [..., hd]."""
    return q.astype(jnp.float32) * scale[..., None]


def bytes_per_token(num_layers: int, num_kv_heads: int, head_dim: int, dtype: str) -> int:
    """K+V cache bytes one token occupies, scales included — the honest
    per-token figure kv_cache_stats() and the bench roofline report."""
    if is_int8(dtype):
        return 2 * num_layers * num_kv_heads * (head_dim + 4)
    return 2 * num_layers * num_kv_heads * head_dim * jnp.dtype(dtype).itemsize
