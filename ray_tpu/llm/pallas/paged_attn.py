"""Pallas paged-attention decode kernel: gather -> dequant -> attend fused.

The XLA paged path (paged_kv._paged_attn_batch/_paged_attn_seq) scans the
page axis and each step GATHERS one page per lane into a fresh buffer
before attending — on a real chip that materialization is an extra
HBM round trip per page (read pool -> write gathered copy -> read copy
into the attention dot), and the int8 cache adds a separate dequant pass
over the gathered pages. This kernel deletes the materialization: a grid
over (lanes x KV pages) whose BlockSpec index map reads the device page
table directly (scalar-prefetch), so each page streams HBM -> VMEM
exactly once, dequantizes IN REGISTERS with the exact kv_quant recipe
(int8 * f32 per-head amax scale at the f32 compute dtype), and folds
into a flash-style online-softmax carry (m/l/acc). Paged decode becomes
HBM-roofline-bound on the bytes that must move — the pool pages — and
nothing else.

Scope and contracts:

- The kernel computes the PAGE-PREFIX softmax partials only: positions
  ``0..bound[b]-1`` read from the pool. The current token's K/V (decode)
  and the causal in-register chunk (spec verify / chunked prefill) are
  folded OUTSIDE the kernel by the same ``_combine`` math the XLA path
  uses — the kernel never reads the position being written this step,
  which is the third leg of the gather/scatter aliasing contract
  documented on ``decode_attn_paged`` (the attention program must stay
  read-only over the pool). ``tests/test_llm_pallas.py`` poisons the
  write target to regression-lock this.
- Math mirrors the XLA scan op-for-op (same masks, same ``_NEG``
  surrogate, same combine order), so interpret mode on CPU is
  token-identical to the XLA oracle — the equivalence tier-1 asserts.
- ``interpret=True`` (automatic off-TPU) runs the kernel through the
  Pallas interpreter: slow, but the SAME kernel body TPU compiles, so
  CPU CI exercises the real code path.

The XLA path remains the default; engines opt in with
``attn_kernel="pallas"`` (llm/engine.py validates, and refuses with
AttnKernelUnavailableError when ``kernel_supported`` says no).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.lint import jaxcheck
from ray_tpu.llm.paged_kv import _NEG


def _interpret_default() -> bool:
    """Interpret off-TPU: the kernel body is executed by the Pallas
    interpreter as plain jax ops (slow, exact); on TPU it compiles."""
    return jax.default_backend() != "tpu"


# f32 bytes of one dequantized page block, padded to (8, 128) tiles, that
# the kernel has been compiled with for a v5e (tests/test_chip_compile.py
# and PERF.md Findings, PR 21): kv 32 x page 128 x hd 128, kv 8 x page 512
# x hd 128. The body holds a handful of such blocks in VMEM; larger ones
# have not been shown to fit, so they are not promised.
_MAX_BLOCK_F32_BYTES = 2 << 20


def kernel_supported(page_size: int, num_kv_heads: int, head_dim: int, quantized: bool = False):
    """(ok, why_not) for this config on this backend. CPU always works
    (interpret mode). On TPU the K/V block ``(1, page, kvh, hd)`` spans
    the pool's full trailing dims, so Mosaic accepts any (kvh, hd) tile —
    compiled for v5e at kv 1-32, hd 64-256, page 16-512, fp and int8 —
    and the gate is the VMEM the dequantized block takes. This decision
    is taken ONCE at engine construction and the engine turns a False
    into AttnKernelUnavailableError, so it must be strict enough that a
    promised kernel never fails to compile later."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True, ""
    if backend == "tpu":
        block = page_size * -(-num_kv_heads // 8) * 8 * -(-head_dim // 128) * 128 * 4
        if block > _MAX_BLOCK_F32_BYTES:
            return False, (
                f"a page block of {page_size} x {num_kv_heads} x {head_dim} takes {block} f32 bytes "
                f"in VMEM, over the {_MAX_BLOCK_F32_BYTES} the kernel has been compiled with"
            )
        return True, ""
    return False, f"no pallas paged-attention path for backend {backend!r}"


def _partials_kernel(tables_ref, bound_ref, q_ref, k_ref, v_ref, *rest,
                     page: int, rows: int, quant: bool):
    """One (lane b, page j) grid step: stream page ``tables[b, j]`` from
    HBM, dequantize in registers (int8 pools), fold into the lane's
    online-softmax carry. The carry lives in the output refs — the page
    grid dim revisits the same output block, the canonical reduction.

    The body keeps the pool's own ``[page, kv, hd]`` tiling — one
    (kv, hd) vreg tile per position — so nothing is relaid out, in HBM
    or in VMEM. Per query row the score is a broadcast multiply and a
    lane reduction that KEEPS its axis (``[page, kv, 1]``); the softmax
    max/sum and the P·V fold reduce over the leading page axis, which is
    elementwise across tiles. These are the forms Mosaic lowers; the
    einsum / squeezed-reduction form this replaces was refused
    ("Offset change" on the row reduction). A decode query is 1-10 rows,
    so the MXU would idle anyway; the kernel is bound by the page
    stream."""
    if quant:
        k_sc_ref, v_sc_ref, m_ref, l_ref, acc_ref = rest
    else:
        m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kp = k_ref[0].astype(jnp.float32)  # [page, kv, hd]
    vp = v_ref[0].astype(jnp.float32)
    if quant:
        # the exact kv_quant dequant the XLA path applies to gathered
        # pages, at the f32 compute dtype. The scale plane is
        # position-LAST ([kv, page]); a diagonal select + lane reduction
        # moves it to [page, kv, 1] exactly (it only ever adds zeros)
        kvh = kp.shape[1]
        diag = (jax.lax.broadcasted_iota(jnp.int32, (page, kvh, page), 0)
                == jax.lax.broadcasted_iota(jnp.int32, (page, kvh, page), 2))
        kp = kp * jnp.where(diag, k_sc_ref[0][None], 0.0).sum(axis=-1, keepdims=True)
        vp = vp * jnp.where(diag, v_sc_ref[0][None], 0.0).sum(axis=-1, keepdims=True)
    pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (page, 1, 1), 0)
    ok = pos < bound_ref[b]  # strictly pre-existing positions only

    def row(r, carry):
        qf = q_ref[0, r]  # [kv, hd], f32, pre-scaled by the caller
        s = (kp * qf[None]).sum(axis=-1, keepdims=True)  # [page, kv, 1]
        s = jnp.where(ok, s, _NEG)
        m_prev = m_ref[0, r]  # [kv, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=0))
        alpha = jnp.exp(m_prev - m_new)
        pexp = jnp.exp(s - m_new[None])
        m_ref[0, r] = m_new
        l_ref[0, r] = l_ref[0, r] * alpha + pexp.sum(axis=0)
        acc_ref[0, r] = acc_ref[0, r] * alpha + (pexp * vp).sum(axis=0)  # [kv, hd]
        return carry

    jax.lax.fori_loop(0, rows, row, None, unroll=rows <= 16)


def paged_attn_partials(qf, pool_k_l, pool_v_l, tables, bound,
                        k_scale_l=None, v_scale_l=None, *, interpret: bool | None = None):
    """Online-softmax partials of ``qf`` over each lane's paged prefix.

    qf: [B, nkv, rep, T, hd] float32, already scaled by 1/sqrt(hd);
    pool_*_l: [P, page, kv, hd] (one layer; fp or int8);
    tables: [B, max_pg] int32 device page table (padding rows point at
    the trash page — masked by ``bound``); bound: [B] int32 — attend to
    pool positions ``0 .. bound[b]-1`` ONLY (lengths for decode, the
    prefix start for wide-block verify/extend). The position being
    written this step is >= bound by contract and must reach attention
    in registers via the caller's self/chunk fold, never from the pool.
    k_scale_l/v_scale_l: [P, kv, page] f32 for int8 pools.

    Returns (m [B, nkv, rep, T], l same, acc [B, nkv, rep, T, hd]) f32 —
    the same partials the XLA page scan carries, ready for the shared
    ``_combine`` + normalize tail.
    """
    B, nkv, rep, T, hd = qf.shape
    page = pool_k_l.shape[1]
    kvh = pool_k_l.shape[2]
    R = rep * T
    max_pg = tables.shape[1]
    quant = k_scale_l is not None
    if interpret is None:
        interpret = _interpret_default()

    kernel = functools.partial(_partials_kernel, page=page, rows=R, quant=quant)
    lane = lambda b, j, tbl, bnd: (b, 0, 0, 0)  # noqa: E731
    # the fused gather: the index map IS the page-table read, so the
    # pipeline DMAs exactly one pool page per grid step HBM -> VMEM
    page_blk = lambda b, j, tbl, bnd: (tbl[b, j], 0, 0, 0)  # noqa: E731
    in_specs = [
        pl.BlockSpec((1, R, nkv, hd), lane),
        pl.BlockSpec((1, page, kvh, hd), page_blk),
        pl.BlockSpec((1, page, kvh, hd), page_blk),
    ]
    # query rows lead so each row is one (kv, hd) tile matching a pool
    # position's tile (a transpose of the tiny query, never of the pool)
    rows_first = lambda x: x.reshape(B, nkv, R, -1).transpose(0, 2, 1, 3)  # noqa: E731
    args = [tables, bound, rows_first(qf), pool_k_l, pool_v_l]
    if quant:
        in_specs += [
            pl.BlockSpec((1, kvh, page), lambda b, j, tbl, bnd: (tbl[b, j], 0, 0)),
            pl.BlockSpec((1, kvh, page), lambda b, j, tbl, bnd: (tbl[b, j], 0, 0)),
        ]
        args += [k_scale_l, v_scale_l]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # tables + bound ride SMEM ahead of the body
        grid=(B, max_pg),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, R, nkv, 1), lane),
            pl.BlockSpec((1, R, nkv, 1), lane),
            pl.BlockSpec((1, R, nkv, hd), lane),
        ],
    )
    out_shape = [
        jax.ShapeDtypeStruct((B, R, nkv, 1), jnp.float32),
        jax.ShapeDtypeStruct((B, R, nkv, 1), jnp.float32),
        jax.ShapeDtypeStruct((B, R, nkv, hd), jnp.float32),
    ]
    kw = {}
    if not interpret:
        # lanes are independent; the page dim carries the m/l/acc
        # reduction and must stay sequential
        kw["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        )
    m, l, acc = pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape, interpret=interpret,
        name="paged_attn_partials", **kw
    )(*args)
    heads_first = lambda x: x.transpose(0, 2, 1, 3).reshape(B, nkv, rep, T, -1)  # noqa: E731
    return heads_first(m)[..., 0], heads_first(l)[..., 0], heads_first(acc)


# ---------------------------------------------------------------------------
# jaxcheck entries: the kernel traced over interpret-mode buckets (this is
# how the static pass sees the program on TPU-less CI; the pallas_call
# abstract shapes are identical either way). Shapes mirror model_runner's
# _trace_cfg pools: nkv=8, hd=128, page=16 — tile-true trailing dims so
# JXC006's (8,128) math stays meaningful. The fp entry carries both the
# decode (T=1) and wide-block (T=5, spec verify's k+1) buckets.
# ---------------------------------------------------------------------------
def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _bucket_partials(B=8, pages=64, page=16, kv=8, hd=128, T=1, quant=False):
    qf = _sds((B, kv, 1, T, hd), jnp.float32)
    pool = _sds((pages, page, kv, hd), jnp.int8 if quant else jnp.float32)
    tables = _sds((B, 8), jnp.int32)
    bound = _sds((B,), jnp.int32)
    args = (qf, pool, pool, tables, bound)
    if quant:
        sc = _sds((pages, kv, page), jnp.float32)
        args += (sc, sc)
    return args, {}


@jaxcheck.entry(
    name="llm.paged_attn_pallas",
    shapes={
        "b8_t1_interp": _bucket_partials,
        "b8_t5_interp": lambda: _bucket_partials(T=5),
    },
)
def paged_attn_pallas(qf, pool_k_l, pool_v_l, tables, bound):
    """Registry twin of the fp kernel call (decode + wide-block buckets).
    Nothing donates: the partials feed the caller's self/chunk fold and
    qf/pool stay live past the call by design."""
    return paged_attn_partials(qf, pool_k_l, pool_v_l, tables, bound, interpret=True)


@jaxcheck.entry(
    name="llm.paged_attn_pallas_int8",
    shapes={
        "b8_t1_interp": lambda: _bucket_partials(quant=True),
        "b8_t5_interp": lambda: _bucket_partials(T=5, quant=True),
    },
)
def paged_attn_pallas_int8(qf, pool_k_l, pool_v_l, tables, bound, k_scale_l, v_scale_l):
    """Int8-pool twin: in-register dequant rides the same kernel body
    (the scale planes stream with their pages through the index map)."""
    return paged_attn_partials(
        qf, pool_k_l, pool_v_l, tables, bound, k_scale_l, v_scale_l, interpret=True
    )
