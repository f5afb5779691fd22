"""Elementwise / norm / embedding ops. XLA fuses these into surrounding
matmuls; the Pallas fused rmsnorm is used standalone where no producer
matmul exists to fuse with."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def rms_norm(x, weight, eps: float = 1e-6):
    """RMSNorm in f32 with cast back (llama convention)."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("eps",))
def rms_norm_pallas(x, weight, eps: float = 1e-6):
    """Fused RMSNorm Pallas kernel: one HBM round trip for [rows, d]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    orig_shape = x.shape
    d = x.shape[-1]
    rows = x.size // d
    x2 = x.reshape(rows, d)
    block_rows = min(256, rows)

    def kernel(x_ref, w_ref, o_ref):
        xf = x_ref[:].astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        o_ref[:] = (xf * jax.lax.rsqrt(var + eps) * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)

    out = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(rows, block_rows),),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((d,), lambda i: (0,), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
    )(x2, weight)
    return out.reshape(orig_shape)


def rotary_embedding(positions, head_dim: int, theta: float = 10000.0, dtype=jnp.float32):
    """RoPE cos/sin tables for integer positions [.., T]."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., T, half]
    return jnp.cos(angles).astype(dtype), jnp.sin(angles).astype(dtype)


def apply_rope(x, cos, sin):
    """x: [B, H, T, D]; cos/sin: [B, T, D/2] or [T, D/2] (split-half rope)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:
        cos = cos[None, None]
        sin = sin[None, None]
    else:
        cos = cos[:, None]
        sin = sin[:, None]
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)


def swiglu(x, w_gate, w_up, w_down):
    """SwiGLU MLP: down( silu(x@gate) * (x@up) )."""
    g = jnp.dot(x, w_gate)
    u = jnp.dot(x, w_up)
    return jnp.dot(jax.nn.silu(g) * u, w_down)


# positions of one slab of ``live_slabs``. On a v5e 512 rows keep a product at the whole bucket's rate (2048 x 8192:
# 89-96 us a slab against 91 for as many rows of the plain form; 256 rows lose a fifth of it) and round a prompt
# of 2,500 up by 2% where 1,024 round it up by 23%: PERF.md section 6, PR 54, has what 256, 1,024 and 2,048 read
LIVE_SLAB = 512


def live_slabs(fn, x, lengths, w, stacked=None):
    """``fn(x, w)`` for a ``fn`` that acts on a position alone ([.., H] -> [.., H'], ``w`` its weights),
    on the rows of x [B,T,H] under their sequence's true length ``lengths`` [B] and no others: slab
    after slab of ``LIVE_SLAB`` positions, over the slabs that START under their row's length, the
    slabs of all rows in ONE loop whose length is data. Every other position comes out as zeros. A
    serving prefill's, where nobody reads a padded position's output: a loop of data length has no
    backward pass and no reduction over a mesh, so the caller hands ``lengths`` only where neither
    follows (``SeqCtx.skippable``).
    ``stacked`` = (arrays stacked over layers, this layer's index): a slab then reads ``w`` where it
    lies, layer i of each of them; a layer sliced out first would be copied once a layer to become
    the loop's operand (100 MB at 2048 x 8192 x 3: a fifth of a millisecond, an eighth of that MLP
    over 2,500 positions).
    The plain form, ``fn(x, w)``, where there is nothing to skip, by the shape alone: no lengths, a
    bucket of one slab, or one that is no whole number of them."""
    S = LIVE_SLAB
    if lengths is None or x.shape[1] <= S or x.shape[1] % S:
        return fn(x, w)
    B, T, H = x.shape
    slabs_of = (jnp.clip(lengths, 0, T).astype(jnp.int32) + S - 1) // S
    last_slab = jnp.cumsum(slabs_of)  # one past each row's last slab
    like = jax.eval_shape(fn, jax.ShapeDtypeStruct((S, H), x.dtype), w)

    def one_slab(j, out):
        b = jnp.sum(last_slab <= j).astype(jnp.int32)
        at = (j - (last_slab[b] - slabs_of[b])) * S
        if stacked is None:
            mine = w
        else:
            # the layer's index behind a barrier with the trip's: what keeps the compiler from hoisting the slices
            # out of the loop, where each would be a copy of the layer's matrix
            arrays, i = stacked
            i, _ = jax.lax.optimization_barrier((i, j))
            mine = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), arrays)
        y = fn(jax.lax.dynamic_slice(x, (b, at, 0), (1, S, H))[0], mine)
        return jax.lax.dynamic_update_slice(out, y[None], (b, at, 0))

    return jax.lax.fori_loop(0, last_slab[-1], one_slab, jnp.zeros((B, T) + like.shape[1:], like.dtype))


def live_rows(T: int, lengths) -> int:
    """Host arithmetic for a step's row of the flight log (``prefill_rows_live``): the positions
    that ``live_slabs`` runs ``fn`` on in ONE prefill program over ``T`` padded positions and rows
    of the true ``lengths`` (a padding row's among them); every position where the shape has the
    plain form run."""
    S = LIVE_SLAB
    if T <= S or T % S:
        return len(lengths) * T
    return sum(-(-min(int(n), T) // S) * S for n in lengths)


def cross_entropy_loss(logits, labels, mask=None, z_loss: float = 0.0):
    """Token cross entropy in f32; labels -100 or mask==0 are ignored."""
    logits = logits.astype(jnp.float32)
    valid = labels >= 0 if mask is None else mask > 0
    safe_labels = jnp.where(valid, labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, safe_labels[..., None], axis=-1)[..., 0]
    loss = (lse - ll) * valid
    if z_loss > 0.0:
        loss = loss + z_loss * (lse * valid) ** 2
    denom = jnp.maximum(valid.sum(), 1)
    return loss.sum() / denom


def embedding_lookup(table, ids):
    return jnp.take(table, ids, axis=0)
