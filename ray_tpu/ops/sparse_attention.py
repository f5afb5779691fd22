"""Block-sparse attention with a learned-free selection (InfLLM v2, the ``minicpm4`` mixer).

A query at position ``t`` of a sequence longer than ``dense_len`` attends, in each group of query
heads that share a key-value head, to at most ``topk`` BLOCKS of ``block`` positions, chosen by the
query itself (``SparseConfig`` holds the numbers; nothing here is trained):

1. compressed keys: ``Kc_j = mean(k[stride j : stride j + kernel])``, one row for every ``stride``
   positions, usable by a query once all ``kernel`` positions lie at or before it;
2. ``r[t, h, j] = softmax_j(q[t, h] . Kc_j / sqrt(hd))`` over the usable rows, summed over the
   heads of the group: ``R[t, g, j]``;
3. a block's score is the largest ``R`` among the compressed keys whose window overlaps it;
4. forced: the first ``init_blocks`` blocks and the ``window / block`` blocks that end with the
   query's own; chosen: the ``topk`` highest among the blocks at or before the query's, the forced
   ones counted among them (ties to the earlier block, as ``jax.lax.top_k`` breaks them);
5. causal softmax attention over the positions at or before ``t`` of the chosen blocks, with the
   uncompressed keys and values.

Steps 1-4 (``compress_keys``, ``block_scores``, ``choose_blocks``) are shared by the sequence form
below and by the decode step (``models/minicpm_sala.py``, whose attention over the chosen blocks is
``ops/slot_attention.attend_blocks``). The scores of steps 2-3 are accumulated in float32 from
the operands as they are held (the cache's dtype). ``sparse_attention_seq`` is the sequence form:
queries a tile at a time against ALL keys with the chosen blocks as a mask, which computes what a
kernel that skips the other blocks would: positions outside a query's blocks contribute nothing.
A sequence of at most ``dense_len`` positions attends densely (every block at or before the
query's), decided by ITS length, so a batch may hold both.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.util.profiling import scope

_BIG = 1e9  # a forced block's score; minus it, a block past the query's
_NEG = -1e30  # finite stand-in for -inf inside the kernel
# the kernel's tiles: queries a grid step takes (each with all the heads of its group), and keys
_TILE_Q, _TILE_K = 256, 512


class SparseConfig(NamedTuple):
    kernel: int = 32  # positions a compressed key is the mean of
    stride: int = 16  # positions between two compressed keys
    block: int = 64  # positions in a block
    topk: int = 64  # blocks a query reads, the forced ones among them
    window: int = 2048  # positions before the query that are always read (whole blocks)
    init_blocks: int = 1  # leading blocks that are always read
    dense_len: int = 8192  # a sequence of at most so many positions attends densely

    def check(self, positions: int) -> None:
        if self.kernel % self.stride or self.block % self.stride or self.window % self.block:
            raise ValueError("sparse attention: stride must divide kernel and block, and block the window")
        if positions % self.block:
            raise ValueError(f"sparse attention: {positions} positions are not whole blocks of {self.block}")


def _dot(spec, a, b):
    """Float32 accumulation of the operands as they are; float32 operands at ``highest`` precision."""
    exact = a.dtype == jnp.float32 and b.dtype == jnp.float32
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32, precision=jax.lax.Precision.HIGHEST if exact else None)


def compress_keys(k, lengths, sp: SparseConfig):
    """k [B,T,G,hd], lengths [B] -> the compressed keys [B, T // stride, G, hd] in k's dtype (means
    accumulated in float32): row j is the mean of positions ``stride j .. stride j + kernel - 1``,
    zeros where that window is not whole inside the sequence's true length (no row holds padding)."""
    B, T, G, hd = k.shape
    J, span = T // sp.stride, sp.kernel // sp.stride
    parts = jnp.sum(k[:, :J * sp.stride].astype(jnp.float32).reshape(B, J, sp.stride, G, hd), axis=2)
    parts = jnp.pad(parts, ((0, 0), (0, span - 1), (0, 0), (0, 0)))
    kc = sum(parts[:, i:i + J] for i in range(span)) / sp.kernel
    whole = (jnp.arange(J) * sp.stride + sp.kernel)[None, :] <= lengths[:, None]
    return jnp.where(whole[..., None, None], kc, 0.0).astype(k.dtype)


def block_scores(q, kc, t, sp: SparseConfig):
    """Steps 2-3. q [B,Q,G,R,hd] (a group's R query heads), kc [B,J,G,hd], t [B,Q] int32 the
    queries' positions -> float32 [B,Q,G,J // (block // stride)]: each block's score, -1 where no
    compressed key that overlaps the block is usable yet (real scores are sums of probabilities)."""
    J, hd = kc.shape[1], q.shape[-1]
    ratio, span = sp.block // sp.stride, sp.kernel // sp.stride
    s = _dot("bqgrh,bjgh->bqgrj", q, kc) * hd ** -0.5
    usable = ((jnp.arange(J) * sp.stride + sp.kernel)[None, None, :] <= (t + 1)[..., None])[:, :, None, None]  # [B,Q,1,1,J]
    top = jnp.max(jnp.where(usable, s, -jnp.inf), axis=-1, keepdims=True)
    e = jnp.where(usable, jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0)), 0.0)
    total = jnp.sum(e, axis=-1, keepdims=True)
    R = jnp.sum(e / jnp.where(total > 0.0, total, 1.0), axis=3)  # [B,Q,G,J]: summed over the group's heads
    R = jnp.where(usable[:, :, :, 0], R, -1.0)
    R = jnp.pad(R, ((0, 0), (0, 0), (0, 0), (span - 1, 0)), constant_values=-1.0)  # index j + span - 1 holds row j
    nb = J // ratio
    # block b overlaps rows ratio b - (span - 1) .. ratio b + ratio - 1
    return jnp.max(jnp.stack([jax.lax.slice_in_dim(R, d, d + ratio * (nb - 1) + 1, ratio, axis=-1) for d in range(ratio + span - 1)]), axis=0)


def choose_blocks(scores, t, sp: SparseConfig):
    """Step 4. scores [B,Q,G,nb] float32, t [B,Q] -> (blocks [B,Q,G,n] int32, ok [B,Q,G,n] bool),
    n = min(topk, nb): the chosen blocks' indices, ``ok`` false where fewer than n blocks lie at or
    before the query's own (the entry then names a block that must not be read)."""
    nb = scores.shape[-1]
    b, own = jnp.arange(nb), (t // sp.block)[..., None]  # [B,Q,1]
    forced = (b < sp.init_blocks) | (b > own - sp.window // sp.block)
    ranked = jnp.where((b <= own)[:, :, None], jnp.where(forced[:, :, None], _BIG, scores), -_BIG)
    top, blocks = jax.lax.top_k(ranked, min(sp.topk, nb))
    return blocks.astype(jnp.int32), top > -_BIG / 2


def chosen_mask(blocks, ok, nb: int):
    """(blocks, ok) of ``choose_blocks`` -> bool [B,Q,G,nb]: which blocks each query reads."""
    return jnp.any((blocks[..., None] == jnp.arange(nb)) & ok[..., None], axis=-2)


# --------------------------------------------------------------------------- the kernel of step 5
def refusal(dtype, head_dim: int, positions: int, block: int, mesh=None) -> str | None:
    """Why step 5 does NOT run as the kernel (the XLA form of masked tiles then does), or None. Off
    the TPU the answer is always a reason, as ``ops/slot_attention.refusal`` says of its own; on it
    the shapes let through are the ones compiled for a v5e in ``tests/test_chip_compile.py``."""
    if jax.default_backend() != "tpu":
        return f"backend {jax.default_backend()!r}: the kernel is compiled for the TPU only"
    if mesh is not None and mesh.size > 1:
        return "a mesh of several devices: a Mosaic kernel is not partitioned, and no cell runs it"
    if jnp.dtype(dtype) != jnp.bfloat16:
        return f"{jnp.dtype(dtype).name} operands: the kernel has been compiled for bfloat16 only"
    if head_dim != 128:
        return f"head_dim {head_dim}: compiled at 128"
    if positions % _TILE_Q or positions % _TILE_K or _TILE_K % block:
        return f"{positions} positions in blocks of {block}: not whole tiles of {_TILE_Q} queries and {_TILE_K} keys"
    return None


def _attend_kernel(q_ref, k_ref, v_ref, read_ref, o_ref, m_scr, l_scr, acc_scr, *, scale: float, heads: int):
    """Grid step (sequence, group, tile of queries i, tile of keys j): fold the tile's keys into
    the running max, sum and weighted values of every query of the tile, for each of the group's
    ``heads`` query heads in turn; ``read_ref`` [queries, keys] says which key each query may read
    (its chosen blocks, at or before it), the same for all the group's heads."""
    i, j = pl.program_id(2), pl.program_id(3)
    tq, tk = read_ref.shape

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j * tk <= i * tq + tq - 1)  # a tile of keys after every query of the tile: not fetched (the index maps), not computed
    def _fold():
        k, v = k_ref[...], v_ref[...]
        allowed = read_ref[...] != 0
        for h in range(heads):
            s = jax.lax.dot_general(q_ref[h], k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
            s = jnp.where(allowed, s, _NEG)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(allowed, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            m_scr[h] = m_new

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        for h in range(heads):
            l = l_scr[h]
            o_ref[h] = (acc_scr[h] / jnp.where(l > 0.0, l, 1.0)).astype(o_ref.dtype)


def attend_chosen(q, k, v, read, block: int, *, tile_q: int = _TILE_Q, tile_k: int = _TILE_K, interpret: bool = False):
    """Step 5 as one kernel. q [B,T,nh,hd], k, v [B,T,G,hd], read [B,T,G,T // block] bool: the
    blocks each query and group reads -> o [B,T,nh,hd] in q's dtype: causal softmax attention over
    the positions at or before each query of its blocks. The table goes in as [B,G,T,T] int8 (a
    position a byte: 151 MB a group at 12,288, written and read once; expanding blocks to positions
    inside the kernel is left for when it shows); tiles of keys after a tile's last query are
    neither fetched nor computed; a tile that no query of the tile chose is fetched and masked."""
    B, T, nh, hd = q.shape
    G = k.shape[2]
    R, tq, tk = nh // G, min(tile_q, T), min(tile_k, T)
    at = jnp.arange(T, dtype=jnp.int32)
    table = (jnp.repeat(read.transpose(0, 2, 1, 3), block, axis=-1) & (at[None, :] <= at[:, None])).astype(jnp.int8)  # [B,G,T,T]
    qg = q.reshape(B, T, G, R, hd).transpose(0, 2, 3, 1, 4)  # [B,G,R,T,hd]
    kg, vg = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)  # [B,G,T,hd]
    last = lambda i: (i * tq + tq - 1) // tk  # noqa: E731 - the last tile of keys a tile of queries reads
    keys = lambda b, g, i, j: (b, g, jnp.minimum(j, last(i)), 0)  # noqa: E731 - past it the index repeats: nothing is fetched
    o = pl.pallas_call(
        functools.partial(_attend_kernel, scale=hd ** -0.5, heads=R),
        grid=(B, G, T // tq, T // tk),
        in_specs=[pl.BlockSpec((None, None, R, tq, hd), lambda b, g, i, j: (b, g, 0, i, 0)),
                  pl.BlockSpec((None, None, tk, hd), keys), pl.BlockSpec((None, None, tk, hd), keys),
                  pl.BlockSpec((None, None, tq, tk), lambda b, g, i, j: (b, g, i, jnp.minimum(j, last(i))))],
        out_specs=pl.BlockSpec((None, None, R, tq, hd), lambda b, g, i, j: (b, g, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, G, R, T, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((R, tq, 1), jnp.float32), pltpu.VMEM((R, tq, 1), jnp.float32), pltpu.VMEM((R, tq, hd), jnp.float32)],
        interpret=interpret,
        name="sparse_prefill_attention",
        **({} if interpret else {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"), vmem_limit_bytes=64 << 20)}),
    )(qg, kg, vg, table)
    return o.transpose(0, 3, 1, 2, 4).reshape(B, T, nh, hd)


# --------------------------------------------------------------------------- the sequence form
def sparse_attention_seq(q, k, v, lengths, sp: SparseConfig, tile: int = 128, mesh=None):
    """The sequence form. q [B,T,nh,hd], k, v [B,T,G,hd] (nh a multiple of G), lengths [B]: true
    lengths of the right-padded sequences -> (o [B,T,nh,hd], kc [B, T // stride, G, hd]: the
    compressed keys as the cache keeps them). Steps 1-4 go ``tile`` queries at a time (the float32
    scores against the compressed keys are what they hold at once); a sequence of at most
    ``dense_len`` positions reads every block at or before the query's. Step 5 is ONE kernel over
    the table of chosen blocks (``attend_chosen``) unless ``refusal`` gives a reason; then it runs
    in the same tiles, against ALL keys with the chosen blocks as a mask (32 heads x 128 x 12,288
    float32 scores are 201 MB a sequence, written and read several times: what the kernel is for)."""
    B, true_T, nh, hd = q.shape
    G = k.shape[2]
    Q = min(tile, -(-true_T // sp.block) * sp.block)
    T = -(-true_T // Q) * Q  # whole blocks and whole tiles: what is padded lies after every real position and is cut off
    sp.check(T)
    if T != true_T:
        q, k, v = (jnp.pad(a, ((0, 0), (0, T - true_T), (0, 0), (0, 0))) for a in (q, k, v))
    nb = T // sp.block
    with scope("sparse.select"):
        kc = compress_keys(k, lengths, sp)  # T is whole blocks: a row for every stride, block // stride rows a block
    qg = q.reshape(B, T, G, nh // G, hd)
    dense = (lengths <= sp.dense_len)[:, None, None, None]
    at = jnp.arange(T, dtype=jnp.int32)
    as_kernel = refusal(q.dtype, hd, T, sp.block, mesh) is None

    def one_tile(first):
        qt = jax.lax.dynamic_slice_in_dim(qg, first, Q, axis=1)
        t = jnp.broadcast_to(first + jnp.arange(Q, dtype=jnp.int32), (B, Q))
        with scope("sparse.select"):
            blocks, ok = choose_blocks(block_scores(qt, kc, t, sp), t, sp)
            read = chosen_mask(blocks, ok, nb) | (dense & (jnp.arange(nb) <= (t // sp.block)[..., None])[:, :, None])
        if as_kernel:
            return read
        with scope("sparse.attend"):
            s = _dot("bqgrh,bsgh->bgrqs", qt, k) * hd ** -0.5
            allowed = jnp.repeat(read, sp.block, axis=-1) & (at <= t[..., None])[:, :, None]  # [B,Q,G,T]
            p = jax.nn.softmax(jnp.where(allowed.transpose(0, 2, 1, 3)[:, :, None], s, -jnp.inf), axis=-1)
            return _dot("bgrqs,bsgh->bqgrh", p.astype(v.dtype), v)  # every query reads its own position: no empty row

    out = jax.lax.map(one_tile, jnp.arange(0, T, Q, dtype=jnp.int32))  # [T/Q, B, Q, ...]
    out = jnp.moveaxis(out, 0, 1)
    if as_kernel:
        with scope("sparse.attend"):  # off the TPU only a test gets here (it swaps ``refusal``), and runs the same body interpreted
            o = attend_chosen(q, k, v, out.reshape(B, T, G, nb), sp.block, interpret=jax.default_backend() != "tpu")
    else:
        o = out.reshape(B, T, nh, hd)
    return o[:, :true_T], kc[:, :true_T // sp.stride]
