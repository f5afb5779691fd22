"""Flash attention: Pallas TPU kernels (fwd + bwd) with XLA fallback.

The hot op of the framework (the reference delegates attention to
torch/vLLM CUDA kernels; here it is TPU-native). Forward and backward are
Pallas kernels tiled for the MXU: online softmax with f32 accumulation in
VMEM scratch across the kv grid dimension; backward never materializes the
[T, T] probability matrix (dq kernel iterates kv blocks, dk/dv kernel
iterates q blocks). O(T) residuals: output + logsumexp.

Layout: [batch, num_heads, seq, head_dim] (GQA: kv heads broadcast).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def _default_blocks(head_dim: int) -> tuple[int, int]:
    """Flash tile sizes by head width: 1024x1024 measured fastest on v5e for hd<=128
    (0.595 vs 0.568 MFU at 512x512 on the bench model); larger head dims
    fall back to 512: the backward kernels need that to stay inside VMEM (four
    float32 score tiles live at once), the forward kernel does not where a call
    is long (``_fwd_blocks``)."""
    return (1024, 1024) if head_dim <= 128 else (512, 512)


def _fwd_blocks(head_dim: int, T: int) -> tuple[int, int]:
    """The forward kernel's tiles for a call over ``T`` query positions, the shape's alone:
    ``_default_blocks``', and 1,024 x 1,024 at heads up to 256 wide too where the call holds
    eight such query tiles or more. A tile twice as tall and as wide pays a grid step's fixed
    cost a quarter as often and computes its diagonal tiles whole, and skips a row's padding
    by 1,024 positions where it skipped by 512: 20 heads x 256 x 16,384 take 19.9 ms for 24.5
    and 9.3 for 13.3 at a true length of 10,000, and a prompt of 2,500 in a bucket of 4,096
    computes six tile pairs of 1,024 for fifteen of 512, 60% more (PERF.md section 6, PR 61:
    a cell of such buckets lost half to one percent at the larger tile). Everything that
    asks for a call's query tile asks ``_query_tile``, which asks here."""
    bq, bk = _default_blocks(head_dim)
    if head_dim <= 256 and T >= 8 * 1024:
        bq, bk = max(bq, 1024), max(bk, 1024)
    return bq, bk


# ----------------------------------------------------------------------
# reference / fallback implementation (XLA; used on CPU)
# ----------------------------------------------------------------------
def attention_xla(q, k, v, causal: bool = True, scale: float | None = None, segment_ids=None, window: int | None = None):
    """Plain XLA attention, f32 softmax. q,k,v: [B, H, T, D]. ``window``: a query at position i
    reads the keys j with ``i - window < j <= i`` (its own among them) and no earlier one."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    logits = _apply_masks(logits, causal, segment_ids, window)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def _apply_masks(logits, causal, segment_ids, window=None):
    B, H, Tq, Tk = logits.shape
    if causal:
        qi = jax.lax.broadcasted_iota(jnp.int32, (Tq, Tk), 0)
        ki = jax.lax.broadcasted_iota(jnp.int32, (Tq, Tk), 1)
        logits = jnp.where((ki <= qi)[None, None], logits, _NEG_INF)
        if window is not None:
            logits = jnp.where((ki > qi - window)[None, None], logits, _NEG_INF)
    if segment_ids is not None:
        same = segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
        logits = jnp.where(same, logits, _NEG_INF)
    return logits


# ----------------------------------------------------------------------
# pallas forward kernel
# ----------------------------------------------------------------------
def _fwd_kernel(*refs, scale, causal, block_q, block_k, window=None, lengths=False):
    """``window`` (causal only): a query at position i reads keys ``i - window < j <= i``. A tile
    wholly before the window of its first query is skipped as a tile above the diagonal is (and not
    fetched: ``_fwd_pallas``'s index map holds the tile before it), the edge tiles are masked.
    ``lengths``: the first ref is the prefetched true length of each grid row, and a query tile that
    starts at or past its row's is skipped whole: ``_init`` and ``_finalize`` make zeros of it.
    A row's running max ``m_scr`` and sum ``l_scr`` are [block_q, 1] columns. Held in every lane
    of a [block_q, 128] tile they were the same bits and 1-5% of this kernel at its tiles, one head
    a grid step beside a score tile of 512 x 512 or more, and nothing a cell showed (PERF.md
    section 6, PR 61): not kept."""
    from jax.experimental import pallas as pl

    lens_ref, (q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr) = (refs[0], refs[1:]) if lengths else (None, refs)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
            if window is not None:
                inside = k_pos > q_pos - window
                s = jnp.where(inside, s, _NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if causal and window is not None:
            # a row whose window starts after this tile has read no key yet: exp(_NEG_INF - _NEG_INF) is 1, not 0
            p = jnp.where(inside, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = alpha * l_scr[:] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[:] = m_new

    runs = None  # where this (query tile, key tile) has anything to compute; None: everywhere
    if causal:
        runs = ki * block_k <= qi * block_q + block_q - 1
        if window is not None:
            runs = runs & (ki * block_k + block_k - 1 > qi * block_q - window)
    if lengths:
        runs = runs & (qi * block_q < lens_ref[pl.program_id(0)])
    if runs is None:
        _compute()
    else:
        pl.when(runs)(_compute)

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[:] + jnp.log(l))[:, 0]


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q", "block_k", "window"))
def _fwd_pallas(q, k, v, causal=True, scale=None, block_q=None, block_k=None, window=None, lengths=None):
    """``lengths`` [B] int32 (causal only, forward only): row b's true length. A query tile that
    starts at or past it is neither computed nor fetched, and comes out as zeros; the tile that
    holds position ``length - 1`` and every tile before it are what they are without ``lengths``,
    bit for bit. None is the call as it was: no prefetched scalar, the same specs, and so is a
    call of ONE query tile, which has nothing to skip (``_skippable``)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, T, D = q.shape
    Tk = k.shape[2]
    if scale is None:
        scale = D**-0.5
    if not causal and (window is not None or lengths is not None):
        raise ValueError("a window and true lengths are causal: keys i - window < j <= i < length")  # tpulint: disable=ERR002 — a programmer's error at trace time
    lengths = _skippable(lengths, T, D, block_q)
    dq, dk = _fwd_blocks(D, T)
    block_q = min(block_q or dq, T)
    block_k = min(block_k or dk, Tk)
    grid = (B * H, pl.cdiv(T, block_q), pl.cdiv(Tk, block_k))
    qs, ks, vs = (x.reshape(B * H, x.shape[2], D) for x in (q, k, v))

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal, block_q=block_q, block_k=block_k)
    named = {}
    if window is not None:
        kernel = functools.partial(kernel, window=window)
        named = {"name": "window_flash_attention"}  # the name tells the windowed calls apart in a trace

    # a tile the kernel skips is not fetched either: its index is that of the nearest tile the query tile reads (a
    # window's first, the diagonal's), which the pipeline holds already; past a row's true length (``lens``: the
    # prefetched lengths, where the call has them) that of the last tile its last live query tile read
    def last_live(b, lens):
        return jnp.maximum(pl.cdiv(lens[b], block_q) - 1, 0)

    def queries(b, i, j, *lens):
        return (b, jnp.minimum(i, last_live(b, *lens)) if lens else i, 0)

    def keys(b, i, j, *lens):
        if lens or window is not None:
            at = jnp.minimum(i, last_live(b, *lens)) if lens else i
            first = 0 if window is None else jnp.maximum(at * block_q - window + 1, 0) // block_k
            diagonal = (at * block_q + block_q - 1) // block_k
            j = jnp.clip(j, first, diagonal)
            if lens:
                j = jnp.where(i > at, diagonal, j)
        return (b, j, 0)

    specs = dict(
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), queries, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, D), keys, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, D), keys, memory_space=pltpu.VMEM),
        ],
        # every output tile is written, a skipped one as zeros: rows of memory nobody wrote could hold a NaN
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j, *_: (b, i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j, *_: (b, 0, i), memory_space=pltpu.VMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
    )
    prefetched = ()
    if lengths is not None:
        kernel = functools.partial(kernel, lengths=True)
        specs = {"grid_spec": pltpu.PrefetchScalarGridSpec(num_scalar_prefetch=1, **specs)}
        prefetched = (jnp.repeat(lengths.astype(jnp.int32), H),)  # the grid's first axis is B * H
    o, lse = pl.pallas_call(
        kernel,
        **specs,
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, 1, T), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=4 * B * H * T * Tk * D,
            bytes_accessed=(qs.size + ks.size + vs.size) * 2,
            transcendentals=B * H * T * Tk,
        ),
        **named,
    )(*prefetched, qs, ks, vs)
    return o.reshape(B, H, T, D), lse.reshape(B, H, T)


# ----------------------------------------------------------------------
# pallas backward kernels
# ----------------------------------------------------------------------
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr, *, scale, causal, block_q, block_k):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0][:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0][:, None]) * scale
        dq_scr[:] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    if causal:
        @pl.when(ki * block_k <= qi * block_q + block_q - 1)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(ki == n_k - 1)
    def _fin():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal, block_q, block_k):
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    qi = pl.program_id(2)
    n_q = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0][:, None])  # [bq, bk]
        dv_scr[:] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0][:, None]) * scale
        dk_scr[:] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    if causal:
        # skip q blocks entirely before this kv block
        @pl.when(qi * block_q + block_q - 1 >= ki * block_k)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(qi == n_q - 1)
    def _fin():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q", "block_k"))
def _bwd_pallas(q, k, v, o, lse, g, causal=True, scale=None, block_q=None, block_k=None):
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    return _bwd_pallas_with_delta(q, k, v, g, lse, delta, causal=causal, scale=scale, block_q=block_q, block_k=block_k)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q", "block_k"))
def _bwd_pallas_with_delta(q, k, v, g, lse, delta, causal=True, scale=None, block_q=None, block_k=None):
    """Backward kernels with a caller-supplied delta = sum(dO * O, -1).

    Ring attention computes delta once from the globally-merged output and
    reuses it for every ring step's local backward (delta: [B, H, T] f32).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, T, D = q.shape
    Tk = k.shape[2]
    if scale is None:
        scale = D**-0.5
    dbq, dbk = _default_blocks(D)
    block_q = min(block_q or dbq, T)
    block_k = min(block_k or dbk, Tk)
    qs, ks, vs, dos = (x.reshape(B * H, x.shape[2], D) for x in (q, k, v, g))
    lse3 = lse.reshape(B * H, 1, T)
    delta = delta.reshape(B * H, 1, T)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal, block_q=block_q, block_k=block_k),
        grid=(B * H, pl.cdiv(T, block_q), pl.cdiv(Tk, block_k)),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B * H, T, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary", "arbitrary")),
    )(qs, ks, vs, dos, lse3, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal, block_q=block_q, block_k=block_k),
        grid=(B * H, pl.cdiv(Tk, block_k), pl.cdiv(T, block_q)),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, i), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tk, D), k.dtype),
            jax.ShapeDtypeStruct((B * H, Tk, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary", "arbitrary")),
    )(qs, ks, vs, dos, lse3, delta)

    return (
        dq.reshape(B, H, T, D),
        dk.reshape(B, H, Tk, D),
        dv.reshape(B, H, Tk, D),
    )


# ----------------------------------------------------------------------
# custom VJP
# ----------------------------------------------------------------------
def flash_attention(q, k, v, causal: bool = True, scale: float | None = None, impl: str = "auto", window: int | None = None, lengths=None):
    """Flash attention with GQA support. q: [B,H,T,D]; k,v: [B,Hkv,T,D].

    impl: "auto" (pallas on TPU when head_dim tiles), "pallas", or "xla".
    window: a query at position i reads the keys ``i - window < j <= i`` only (causal; forward
    kernel and both XLA passes; the backward KERNELS have no window and refuse one).
    lengths [B] int32: the true lengths of right-padded sequences (causal, forward only: no
    backward pass knows them, and a differentiated call that takes them refuses). Rows below a
    sequence's length are what they are without it; the query tiles (``query_tiles``) wholly at
    or past it come out as zeros, and the kernel neither computes nor fetches them. A call of
    ONE query tile does not take them (``_skippable``): it is the call without them, and so is
    the program around it.
    """
    if lengths is not None and not causal:
        raise ValueError("true lengths are causal: keys j <= i < length")  # tpulint: disable=ERR002 — a programmer's error at trace time
    return _flash(q, k, v, causal, scale, impl, window, _skippable(lengths, q.shape[2], q.shape[-1]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, scale, impl, window, lengths):
    out, _ = _flash_fwd(q, k, v, causal, scale, impl, window, lengths)
    return out


def flash_attention_on_mesh(q, k, v, mesh, impl: str = "auto", scale: float | None = None, window: int | None = None, lengths=None):
    """Causal flash_attention over [B, H, T, D] inside a GSPMD program.
    GSPMD cannot partition a Mosaic kernel on its own ("wrap the call in
    a shard_map"), so where the Pallas kernel is selected on a mesh of
    several devices it runs under shard_map: batch over dp/fsdp, heads
    over tp — attention is independent across both, so each device runs
    the kernel on its own block and no collective is needed. ``mesh`` is
    None (or one device, or the XLA path) for the plain call. ``lengths``
    [B] (``flash_attention``) are sharded as the batch is."""
    if (mesh is None or mesh.size == 1 or not set(mesh.axis_names) <= {"dp", "fsdp", "tp"}
            or not _use_pallas(q, impl)):
        return flash_attention(q, k, v, True, scale, impl, window, lengths)
    from jax.sharding import PartitionSpec as P

    batch = tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names) or None
    spec = P(batch, "tp" if "tp" in mesh.axis_names else None, None, None)
    attn = functools.partial(flash_attention, causal=True, scale=scale, impl=impl, window=window)
    lengths = _skippable(lengths, q.shape[2], q.shape[-1])  # the sequence is whole on every device: the shape's rule is the call's
    if lengths is None:
        return jax.shard_map(attn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)(q, k, v)
    return jax.shard_map(lambda q, k, v, n: attn(q, k, v, lengths=n), mesh=mesh, in_specs=(spec, spec, spec, P(batch)),
                         out_specs=spec, check_vma=False)(q, k, v, lengths)


def _broadcast_kv(q, k, v):
    H, Hkv = q.shape[1], k.shape[1]
    if H != Hkv:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    return k, v


def _query_tile(head_dim: int, T: int, block_q: int | None = None) -> int:
    """Positions of one query tile of a call over ``T`` positions (at the forward kernel's blocks, unless ``block_q`` says)."""
    return min(block_q or _fwd_blocks(head_dim, T)[0], T)


def _skippable(lengths, T: int, head_dim: int, block_q: int | None = None):
    """THE rule, the shape's alone: a call of ONE query tile (``T`` positions in a tile of at least
    as many) has nothing to skip and never can, so it does not learn the lengths. It is then the
    call without them on every backend, and the program around it never holds them (asked where
    a call enters, before ``custom_vjp`` or ``shard_map`` would make an operand of them, and by
    ``_fwd_pallas`` for who calls the kernel itself); any other call keeps them."""
    return None if lengths is None or T <= _query_tile(head_dim, T, block_q) else lengths


def query_tiles(calls: dict, T: int, lengths) -> dict:
    """Host arithmetic for a step's row of the flight log: ``attn_q_tiles``, the query tiles that
    ``calls`` ({head width: calls}, a description's ``flash_calls``) over ``T`` padded positions
    have by their shape, and ``attn_q_tiles_live``, those that start under a true length of
    ``lengths`` (the program's, a padding row's among them): the ones a call with ``lengths`` runs.
    Summed over calls and batch rows; every head of a row has its row's. A call of one query
    tile (``_skippable``) runs it whatever the length: it counts once in both."""
    tiles = live = 0
    for head_dim, n_calls in calls.items():
        block = _query_tile(head_dim, T)
        tiles += n_calls * len(lengths) * -(-T // block)
        live += n_calls * (len(lengths) if T <= block else sum(-(-min(int(n), T) // block) for n in lengths))
    return {"attn_q_tiles": tiles, "attn_q_tiles_live": live}


def _flash_fwd(q, k, v, causal, scale, impl="auto", window=None, lengths=None):
    kb, vb = _broadcast_kv(q, k, v)
    if _use_pallas(q, impl):
        o, lse = _fwd_pallas(q, kb, vb, causal=causal, scale=scale, window=window, lengths=lengths)
    else:
        o, lse = _fwd_xla_with_lse(q, kb, vb, causal, scale, window)
        if lengths is not None:
            # the kernel's contract, so that a program is the same function of its input on every backend
            T = q.shape[2]
            block = _query_tile(q.shape[-1], T)
            skipped = jnp.arange(T)[None, :] // block * block >= lengths[:, None]
            o = jnp.where(skipped[:, None, :, None], 0, o)
    return o, (q, k, v, o, lse, lengths)


def _fwd_xla_with_lse(q, k, v, causal, scale, window=None):
    if scale is None:
        scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    logits = _apply_masks(logits, causal, None, window)
    lse = jax.nn.logsumexp(logits, axis=-1)
    probs = jnp.exp(logits - lse[..., None]).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v), lse


def _flash_bwd(causal, scale, impl, window, residuals, g):
    q, k, v, o, lse, lengths = residuals
    if lengths is not None:
        raise NotImplementedError("no backward pass knows a sequence's true length: differentiate the call without lengths")  # tpulint: disable=ERR002 — a programmer's error at trace time
    kb, vb = _broadcast_kv(q, k, v)
    if _use_pallas(q, impl):
        if window is not None:
            raise NotImplementedError("the flash backward kernels have no window (ROADMAP B10): train a windowed layer with impl='xla'")  # tpulint: disable=ERR002 — a programmer's error at trace time
        dq, dk, dv = _bwd_pallas(q, kb, vb, o, lse, g, causal=causal, scale=scale)
    else:
        dq, dk, dv = _bwd_xla(q, kb, vb, o, lse, g, causal, scale, window)
    H, Hkv = q.shape[1], k.shape[1]
    if H != Hkv:
        rep = H // Hkv
        dk = dk.reshape(dk.shape[0], Hkv, rep, *dk.shape[2:]).sum(axis=2).astype(k.dtype)
        dv = dv.reshape(dv.shape[0], Hkv, rep, *dv.shape[2:]).sum(axis=2).astype(v.dtype)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), None


def _bwd_xla(q, k, v, o, lse, g, causal, scale, window=None):
    if scale is None:
        scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    logits = _apply_masks(logits, causal, None, window)
    p = jnp.exp(logits - lse[..., None])
    g32 = g.astype(jnp.float32)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, g32)
    delta = jnp.sum(g32 * o.astype(jnp.float32), axis=-1, keepdims=True)
    dp = jnp.einsum("bhqd,bhkd->bhqk", g32, v.astype(jnp.float32))
    ds = p * (dp - delta)
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, k.astype(jnp.float32)) * scale
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, q.astype(jnp.float32)) * scale
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)

# kept for callers/tests that used the older name
_flash_fwd_pallas = _fwd_pallas


# ----------------------------------------------------------------------
# chunked (blockwise) XLA attention: O(T * chunk) memory, no pallas.
# The non-pallas path of ring attention (parallel/ring_attention.py) — a
# lax.scan over kv chunks with an online-softmax carry, so the full
# [Tq, Tk] score matrix never exists.
# ----------------------------------------------------------------------
def _pick_chunk(T: int, target: int) -> int:
    if T <= target:
        return T
    for c in range(target, 0, -1):
        if T % c == 0:
            return c
    return T


def chunked_attention_fwd(q, k, v, causal: bool, scale: float, chunk: int = 1024):
    """Returns (o [B,H,Tq,D] f32, lse [B,H,Tq] f32). kv is consumed in
    chunks of `chunk`; the first chunk initializes the online-softmax carry
    (for causal it always contains key 0, so no -inf max to guard)."""
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    q32 = q.astype(jnp.float32)
    C = _pick_chunk(Tk, chunk)
    nk = Tk // C

    def attend_chunk(k_c, v_c, k_off):
        s = jnp.einsum("bhqd,bhkd->bhqk", q32, k_c.astype(jnp.float32), preferred_element_type=jnp.float32) * scale
        if causal:
            qp = jax.lax.broadcasted_iota(jnp.int32, (Tq, C), 0)
            kp = k_off + jax.lax.broadcasted_iota(jnp.int32, (Tq, C), 1)
            s = jnp.where((kp <= qp)[None, None], s, _NEG_INF)
        m = jnp.max(s, axis=-1)
        p = jnp.exp(s - m[..., None])
        return m, jnp.sum(p, axis=-1), jnp.einsum("bhqk,bhkd->bhqd", p, v_c.astype(jnp.float32))

    m0, l0, acc0 = attend_chunk(k[:, :, :C], v[:, :, :C], 0)
    if nk > 1:
        ks = jnp.moveaxis(k[:, :, C:].reshape(B, H, nk - 1, C, D), 2, 0)
        vs = jnp.moveaxis(v[:, :, C:].reshape(B, H, nk - 1, C, D), 2, 0)

        def body(carry, xs):
            m, l, acc = carry
            k_c, v_c, j = xs
            m_b, l_b, acc_b = attend_chunk(k_c, v_c, (j + 1) * C)
            m_new = jnp.maximum(m, m_b)
            alpha = jnp.exp(m - m_new)
            beta = jnp.exp(m_b - m_new)
            return (m_new, alpha * l + beta * l_b, acc * alpha[..., None] + acc_b * beta[..., None]), None

        (m0, l0, acc0), _ = jax.lax.scan(body, (m0, l0, acc0), (ks, vs, jnp.arange(nk - 1)))
    l_safe = jnp.maximum(l0, 1e-30)
    return acc0 / l_safe[..., None], m0 + jnp.log(l_safe)


def chunked_attention_bwd(q, k, v, g, lse, delta, causal: bool, scale: float, chunk: int = 1024):
    """Chunked backward given the (globally merged, in the ring case) lse
    and delta = sum(dO*O, -1). Returns (dq, dk, dv) in f32.

    dq scans kv chunks ([Tq, C] live at a time); dk/dv scan q chunks
    ([Cq, Tk] live at a time) — mirrors the pallas kernel split."""
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    q32, k32, v32, g32 = (x.astype(jnp.float32) for x in (q, k, v, g))

    Ck = _pick_chunk(Tk, chunk)
    nk = Tk // Ck
    ks = jnp.moveaxis(k32.reshape(B, H, nk, Ck, D), 2, 0)
    vs = jnp.moveaxis(v32.reshape(B, H, nk, Ck, D), 2, 0)

    def dq_body(dq, xs):
        k_c, v_c, j = xs
        s = jnp.einsum("bhqd,bhkd->bhqk", q32, k_c, preferred_element_type=jnp.float32) * scale
        if causal:
            qp = jax.lax.broadcasted_iota(jnp.int32, (Tq, Ck), 0)
            kp = j * Ck + jax.lax.broadcasted_iota(jnp.int32, (Tq, Ck), 1)
            s = jnp.where((kp <= qp)[None, None], s, _NEG_INF)
        p = jnp.exp(s - lse[..., None])
        dp = jnp.einsum("bhqd,bhkd->bhqk", g32, v_c, preferred_element_type=jnp.float32)
        ds = p * (dp - delta[..., None]) * scale
        return dq + jnp.einsum("bhqk,bhkd->bhqd", ds, k_c), None

    # carry zeros derive from q so they inherit any varying manual axes
    # (vma) when this runs inside a shard_map region (e.g. ring attention
    # under the pp x sp pipeline) — fresh jnp.zeros would be unvarying
    # and lax.scan rejects the carry-type mismatch
    dq, _ = jax.lax.scan(dq_body, (q32 * 0).astype(jnp.float32), (ks, vs, jnp.arange(nk)))

    Cq = _pick_chunk(Tq, chunk)
    nq = Tq // Cq
    qs = jnp.moveaxis(q32.reshape(B, H, nq, Cq, D), 2, 0)
    gs = jnp.moveaxis(g32.reshape(B, H, nq, Cq, D), 2, 0)
    lses = jnp.moveaxis(lse.reshape(B, H, nq, Cq), 2, 0)
    deltas = jnp.moveaxis(delta.reshape(B, H, nq, Cq), 2, 0)

    def dkv_body(carry, xs):
        dk, dv = carry
        q_c, g_c, lse_c, delta_c, i = xs
        s = jnp.einsum("bhqd,bhkd->bhqk", q_c, k32, preferred_element_type=jnp.float32) * scale
        if causal:
            qp = i * Cq + jax.lax.broadcasted_iota(jnp.int32, (Cq, Tk), 0)
            kp = jax.lax.broadcasted_iota(jnp.int32, (Cq, Tk), 1)
            s = jnp.where((kp <= qp)[None, None], s, _NEG_INF)
        p = jnp.exp(s - lse_c[..., None])
        dv_c = jnp.einsum("bhqk,bhqd->bhkd", p, g_c)
        dp = jnp.einsum("bhqd,bhkd->bhqk", g_c, v32, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_c[..., None]) * scale
        dk_c = jnp.einsum("bhqk,bhqd->bhkd", ds, q_c)
        return (dk + dk_c, dv + dv_c), None

    (dk, dv), _ = jax.lax.scan(
        dkv_body,
        ((k32 * 0).astype(jnp.float32), (v32 * 0).astype(jnp.float32)),  # vma-inheriting zeros
        (qs, gs, lses, deltas, jnp.arange(nq)),
    )
    return dq, dk, dv


def _use_pallas(q, impl: str = "auto") -> bool:
    import os

    if impl == "auto":
        impl = os.environ.get("RT_ATTENTION_IMPL", "auto")
    if impl == "xla":
        return False
    if impl == "pallas":
        return True
    return q.shape[-1] in (64, 128, 256) and jax.default_backend() == "tpu"
