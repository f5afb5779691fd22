"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference has NO sequence-parallel implementation (SURVEY.md §5.7:
grep for ulysses/ring_attention/context_parallel over python/ray + rllib is
empty; long sequences are delegated to engines). Here it is first-class and
TPU-native:

- ring_attention: blockwise attention with online-softmax merging while
  K/V shards rotate around the `sp` mesh axis via `lax.ppermute` (ICI
  neighbor exchange — the ring topology IS the TPU interconnect). Memory
  per chip: O(T/sp * chunk), never O((T/sp)^2): each ring step runs the
  Pallas flash kernel (TPU) or a chunked-XLA blockwise scan (CPU), both
  returning (o, lse) without materializing local score matrices.
- custom VJP: the backward is a second ring pass in which (k, v, dk, dv)
  rotate together — every device adds its gradient contribution to the
  visiting shard, and after n hops dk/dv arrive back at their owner.
  Residuals are O(T/sp): (q, k, v, o, lse). No [Tl, Tl] buffers anywhere.
- ulysses_attention: all-to-all head<->sequence reshard over `sp` (each
  chip sees the full sequence for H/sp heads), full local attention, then
  the inverse all-to-all. One collective round instead of sp ring steps —
  better when heads >= sp and ICI all-to-all bandwidth is plentiful.

Both are called INSIDE shard_map over the mesh (see sp_attention entry
point) so XLA lowers the permutes onto ICI.

Causal schedule: with K/V rotating ring-wise, device `my` holding shard
`src` needs: full attention if src < my, diagonal-causal if src == my,
nothing if src > my. The diagonal step always runs first (it initializes
the online-softmax carry with a finite lse — every query attends at least
to itself), then n-1 (rotate, switch{skip|full}) steps. Skipped steps cost
one ppermute but no FLOPs (lax.switch executes one branch).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.ops.flash_attention import (
    _bwd_pallas_with_delta,
    _fwd_pallas,
    _use_pallas,
    chunked_attention_bwd,
    chunked_attention_fwd,
)

_NEG_INF = -1e30  # finite sentinel: exp(_NEG_INF - finite) underflows to 0.0


def _local_fwd(q, k, v, causal, scale, impl, chunk):
    """One ring step's local attention -> (o f32, lse f32), no [Tl,Tl]."""
    if _use_pallas(q, impl):
        o, lse = _fwd_pallas(q, k, v, causal=causal, scale=scale)
        return o.astype(jnp.float32), lse
    return chunked_attention_fwd(q, k, v, causal=causal, scale=scale, chunk=chunk)


def _local_bwd(q, k, v, g, lse, delta, causal, scale, impl, chunk):
    """One ring step's local backward -> (dq, dk, dv) f32."""
    if _use_pallas(q, impl):
        dq, dk, dv = _bwd_pallas_with_delta(
            q, k, v, g.astype(q.dtype), lse, delta, causal=causal, scale=scale
        )
        return dq.astype(jnp.float32), dk.astype(jnp.float32), dv.astype(jnp.float32)
    return chunked_attention_bwd(q, k, v, g, lse, delta, causal=causal, scale=scale, chunk=chunk)


def ring_attention_local(q, k, v, axis_name: str = "sp", causal: bool = True, scale: float | None = None, impl: str = "auto", chunk: int = 1024):
    """Runs inside shard_map: q,k,v are the local sequence shards
    [B, H, T/sp, D]. Returns the local output shard [B, H, T/sp, D]."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return _ring_attn(q, k, v, axis_name, causal, float(scale), impl, chunk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ring_attn(q, k, v, axis_name, causal, scale, impl, chunk):
    out, _ = _ring_attn_fwd(q, k, v, axis_name, causal, scale, impl, chunk)
    return out


def _ring_attn_fwd(q, k, v, axis_name, causal, scale, impl, chunk):
    n = lax.psum(1, axis_name)  # static: shard_map axis size
    my = lax.axis_index(axis_name)
    perm = [(j, (j + 1) % n) for j in range(n)]
    rotate = lambda t: lax.ppermute(t, axis_name, perm)

    # step 0: the diagonal shard (src == my) — always computed, so the
    # online-softmax carry starts finite for every query row
    o_acc, lse_acc = _local_fwd(q, k, v, causal, scale, impl, chunk)

    if n > 1:
        def full_step(k_i, v_i):
            return _local_fwd(q, k_i, v_i, False, scale, impl, chunk)

        def skip_step(k_i, v_i):
            # zeros DERIVED from q/k_i so they inherit the region's varying
            # manual axes (vma): fresh jnp.zeros would be unvarying and
            # lax.switch rejects branch-type mismatch when this runs inside
            # a wider manual region (e.g. pp x sp in parallel/pipeline.py)
            zero_o = (q * 0 + k_i[..., :1, :] * 0).astype(jnp.float32)
            return zero_o, jnp.full_like(zero_o[..., 0], _NEG_INF)

        def step(carry, i):
            (o, lse), kv = carry
            kv = jax.tree.map(rotate, kv)  # neighbor exchange on ICI
            k_i, v_i = kv
            src = (my - i) % n
            use = (src < my).astype(jnp.int32) if causal else jnp.int32(1)
            o_i, lse_i = lax.switch(use, [skip_step, full_step], k_i, v_i)
            # merge two normalized partials: weights exp(lse - m) / w, w >= 1
            m = jnp.maximum(lse, lse_i)
            alpha = jnp.exp(lse - m)
            beta = jnp.exp(lse_i - m)
            w = alpha + beta
            o = (o * alpha[..., None] + o_i * beta[..., None]) / w[..., None]
            return ((o, m + jnp.log(w)), kv), None

        ((o_acc, lse_acc), _), _ = lax.scan(step, ((o_acc, lse_acc), (k, v)), jnp.arange(1, n))

    out = o_acc.astype(q.dtype)
    return out, (q, k, v, out, lse_acc)


def _ring_attn_bwd(axis_name, causal, scale, impl, chunk, res, g):
    q, k, v, o, lse = res
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    perm = [(j, (j + 1) % n) for j in range(n)]
    rotate = lambda t: lax.ppermute(t, axis_name, perm)
    g32 = g.astype(jnp.float32)
    delta = jnp.sum(g32 * o.astype(jnp.float32), axis=-1)  # [B,H,Tl] f32

    # step 0: diagonal — gradient contribution to our own kv shard
    dq_acc, dk0, dv0 = _local_bwd(q, k, v, g32, lse, delta, causal, scale, impl, chunk)

    if n == 1:
        return dq_acc.astype(q.dtype), dk0.astype(k.dtype), dv0.astype(v.dtype)

    def full_step(k_i, v_i):
        return _local_bwd(q, k_i, v_i, g32, lse, delta, False, scale, impl, chunk)

    def skip_step(k_i, v_i):
        # vma-inheriting zeros (see forward skip_step)
        z = (q * 0).astype(jnp.float32)
        return z, (k_i * 0).astype(jnp.float32), (v_i * 0).astype(jnp.float32)

    def step(carry, i):
        dq, pkg = carry
        pkg = jax.tree.map(rotate, pkg)  # (k_s, v_s, dk_s, dv_s) travel together
        k_i, v_i, dk_i, dv_i = pkg
        src = (my - i) % n
        use = (src < my).astype(jnp.int32) if causal else jnp.int32(1)
        dq_c, dk_c, dv_c = lax.switch(use, [skip_step, full_step], k_i, v_i)
        return (dq + dq_c, (k_i, v_i, dk_i + dk_c, dv_i + dv_c)), None

    (dq_acc, (_, _, dk_acc, dv_acc)), _ = lax.scan(
        step, (dq_acc, (k, v, dk0, dv0)), jnp.arange(1, n)
    )
    # one final hop brings each shard's accumulated dk/dv home to its owner
    dk_acc = rotate(dk_acc)
    dv_acc = rotate(dv_acc)
    return dq_acc.astype(q.dtype), dk_acc.astype(k.dtype), dv_acc.astype(v.dtype)


_ring_attn.defvjp(_ring_attn_fwd, _ring_attn_bwd)


def ulysses_attention_local(q, k, v, axis_name: str = "sp", causal: bool = True, scale: float | None = None, attn_fn=None):
    """Runs inside shard_map: all-to-all so each chip gets full sequence for
    H/sp heads, local full attention, inverse all-to-all."""
    n = lax.psum(1, axis_name)
    # [B, H, Tl, D] -> [B, H/n, T, D]
    q2 = lax.all_to_all(q, axis_name, split_axis=1, concat_axis=2, tiled=True)
    k2 = lax.all_to_all(k, axis_name, split_axis=1, concat_axis=2, tiled=True)
    v2 = lax.all_to_all(v, axis_name, split_axis=1, concat_axis=2, tiled=True)
    if attn_fn is None:
        from ray_tpu.ops.flash_attention import flash_attention

        attn_fn = lambda a, b, c: flash_attention(a, b, c, causal, scale)
    o2 = attn_fn(q2, k2, v2)
    # [B, H/n, T, D] -> [B, H, Tl, D]
    return lax.all_to_all(o2, axis_name, split_axis=2, concat_axis=1, tiled=True)


def sp_attention(q, k, v, mesh: Mesh, impl: str = "ring", causal: bool = True):
    """Top-level entry: q,k,v globally [B, H, T, D] sharded over sp on T.
    Wraps the local kernels in shard_map over the full mesh."""
    if "sp" not in mesh.axis_names:
        from ray_tpu.ops.flash_attention import flash_attention

        return flash_attention(q, k, v, causal, None)
    batch_ax = tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names) or None
    spec = P(batch_ax, None, "sp", None)
    local = ring_attention_local if impl == "ring" else ulysses_attention_local

    fn = jax.shard_map(
        functools.partial(local, axis_name="sp", causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
