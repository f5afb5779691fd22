"""Attention of a slot decode step: one new token a lane against the stacked slot cache.

The slot layout keeps every layer's keys and values in two arrays ``[L, slots, S, kv, hd]`` that
ride the layer loop's carry (``llm/model_runner.py``, ``models/hybrid.py``). A decode step's
attention needs, of layer ``i``, the positions each lane holds: ``0 .. lengths[b]``, the last of
them the token the layer has just written. ``attend`` is that op, in two forms:

- the XLA form (``attend_rows`` over layer ``i``'s rows sliced out of the stack): the oracle, and
  what runs off the TPU, on an int8 cache and inside a ``shard_map`` body. It reads all ``S``
  positions of all lanes and masks: 7.5 of a 17.8 ms step went to the slice alone at 14 x 4096
  InternLM2 slots, and from 15 slots the slice no longer fitted the chip's fast memory (PERF.md
  section 6, PR 35).
- the Pallas kernel: a grid over (lane, block of positions) whose index map reads the layer index
  and the lanes' bounds from SMEM, so that a block streams HBM -> VMEM from where it lies in the
  stack, once, and ONLY IF it holds a live position. A block past a lane's bound repeats the index
  of the block before it, which the pipeline does not fetch again, and its body is skipped; a lane
  bound to no sequence repeats the previous live lane's last block and so reads nothing. No
  layer's rows are materialised, so nothing depends on the slot count.

Inside the kernel the stack is seen as ``[L, slots, S * kv, hd]`` (the same bytes: a position's
``(kv, hd)`` tile is ``kv`` rows), so a block is a plain 2-D ``[blk * kv, hd]`` matrix whatever
``kv`` is. Scores for ALL heads against ALL rows are one MXU product ``[nh, hd] x [hd, blk * kv]``
in the cache's dtype with float32 accumulation (exact products, as the XLA form's); the columns of
the other kv heads are masked out (the MXU idles in a decode step anyway). Softmax runs in float32
with the usual running max and sum. The probabilities stay float32: they are split into three
bfloat16 terms (8 + 8 + 8 mantissa bits) that multiply the bfloat16 values exactly, so the second
product accumulates what a float32 x float32 one would.

Heads NARROWER than the 128 lanes (64 wide: ``models/lfm2.py``) lie ``128 // hd`` to a row: the
cache keeps a position's ``(kv, hd)`` keys as ``(kv * hd / 128, 128)`` (``position_tile``), because a
stack whose last dimension is 64 takes 128 lanes a row on the chip anyway (twice the bytes) and
reaches the kernel only through a copy. The kernel is the same one: it sees rows of 128 as it would
``kv * hd / 128`` heads of 128, each query placed in its own head's columns of its row with zeros
in the others (so a row's product with it is its own head's score), the output's own columns cut
out afterwards; the MXU multiplies the zeros (it idles in a decode step anyway) and the bytes
streamed are the keys' and values' own (``slot_decode_attention_narrow`` in a trace).

A LATENT layer (``attend_latent``; ``models/glm4_moe_lite.py``) keeps no heads: a position is one
latent row ``c_kv`` [r] and one rotated key ``k_r`` [rope, in whole 128-lane tiles: the model file
says why], two stacked arrays ``[L, slots, S, r]`` and ``[L, slots, S, rope]``, and every query head
reads the SAME row, as key (all r + rope columns) and, in ``c_kv``, as value. Its kernel
(``latent_decode_attention`` in a trace) is the same grid over (lane, block), the same bounds and
index tables in SMEM and the same fold; a block of ``c_kv`` is streamed once and used twice, scores
are ``q_lat . c_kv + q_rope . k_r`` (two MXU products), and the query heads are padded to whole
bfloat16 tiles of 16 rows. Its XLA form is ``attend_rows`` with keys wider than values.

A SPARSE layer (``attend_blocks``; ``models/minicpm_sala.py``) reads, for each lane and key-value
head, the blocks of 64 positions that a TABLE computed in the same step names, not ``0 .. last``:
its kernel (``sparse_decode_attention`` in a trace) is a grid over (lane, place of the table) whose
index maps read the table from SMEM, one fetch a key-value head and place, and the same fold; its
XLA form gathers the blocks and masks.

Which form runs is decided by what the code can see (``refusal``, ``refusal_blocks``): the backend, the cache's dtype,
whether the caller is a ``shard_map`` body, and the tile's shape. There is no knob.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30  # finite stand-in for -inf: exp(_NEG - m) is 0 and (_NEG - _NEG) is not NaN

# bytes of one K (or V) block; the pipeline holds two of each. 14 x 4096 InternLM2 slots timed on a
# v5e (PERF.md section 6, PR 35) chose between 1 MiB (512 positions of 8 x 128) and 2 MiB
_BLOCK_BYTES = 1 << 20


# --------------------------------------------------------------------------- the XLA form
def attend_rows(q, k_rows, v_rows, lengths, num_kv_heads: int, scale: float | None = None):
    """One token a lane (its query q [B,nh,hd]) against a layer's rows k_rows [B,S,kv,hd] and
    v_rows [B,S,kv,vd] (vd = hd, but for a latent layer), in which the new token's key and value
    already sit at index lengths[b]: grouped-query softmax attention over the positions held,
    scores over sqrt(hd) unless ``scale`` says otherwise. -> [B, nh*vd] float32."""
    B, S = k_rows.shape[:2]
    nh, hd = q.shape[1:]
    qg = q.reshape(B, num_kv_heads, nh // num_kv_heads, hd)
    scores = jnp.einsum("bgrh,bsgh->bgrs", qg, k_rows, preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(hd) if scale is None else scores * scale
    ok = (jnp.arange(S, dtype=jnp.int32)[None, :] <= lengths[:, None])[:, None, None]
    probs = jax.nn.softmax(jnp.where(ok, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bgrs,bsgh->bgrh", probs, v_rows.astype(jnp.float32)).reshape(B, nh * v_rows.shape[-1])


def attend_blocks_rows(q, k_stack, v_stack, layer, lengths, blocks, ok, block: int):
    """One token a lane (its query q [B,nh,hd]) against the BLOCKS of ``block`` positions that a
    table names: ``blocks`` [B,G,N] int32, for each lane and key-value head the indices of the
    blocks its group of query heads reads in layer ``layer`` of the stacked slot cache k/v_stack
    [L,B,S,G,hd] (``ok`` [B,G,N] bool: false where the entry names none), the new token's key and
    value already at index lengths[b]: grouped-query softmax attention over the positions at or
    before lengths[b] of those blocks. The XLA form of ``attend_blocks`` (gather the blocks, mask):
    the oracle, and what runs off the TPU. The chip's compiler copies each stack whole to see it
    in blocks (4 x 0.2 GB a step at 2 layers x 16 x 12,288, 2.4 of a 13.9 ms step: my chip run,
    PR 45), which is what the kernel is for. -> [B, nh*hd] float32."""
    L, B, S, G, hd = k_stack.shape
    nh, N = q.shape[1], blocks.shape[-1]
    lanes, heads = jnp.arange(B)[:, None, None], jnp.arange(G)[None, :, None]
    # the blocks where they lie in the stack: [B,G,N,block,hd], nothing of the layer's other rows
    kb, vb = (a.reshape(L, B, S // block, block, G, hd)[layer, lanes, blocks, :, heads] for a in (k_stack, v_stack))
    scores = jnp.einsum("bgrh,bgnph->bgrnp", q.reshape(B, G, nh // G, hd), kb, preferred_element_type=jnp.float32) / math.sqrt(hd)
    at = blocks[..., None] * block + jnp.arange(block, dtype=jnp.int32)  # [B,G,N,block]
    allowed = (ok[..., None] & (at <= lengths[:, None, None, None]))[:, :, None]
    probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf).reshape(B, G, nh // G, N * block), axis=-1)
    probs = jnp.where(jnp.any(allowed, axis=(-2, -1)).reshape(B, G, 1, 1), probs, 0.0)  # a table that names nothing (an unbound lane): zeros, as the kernels give
    return jnp.einsum("bgrs,bgsh->bgrh", probs, vb.astype(jnp.float32).reshape(B, G, N * block, hd)).reshape(B, nh * hd)


def layer_of(stacked, i):
    """Layer ``i`` of a stacked cache leaf ``[L, ...]`` (i: a traced index inside the layer loop)."""
    return jax.lax.dynamic_index_in_dim(stacked, i, 0, keepdims=False)


# --------------------------------------------------------------------------- which form
def block_positions(S: int, num_kv_heads: int, head_dim: int, itemsize: int, block_bytes: int = _BLOCK_BYTES) -> int:
    """Positions in one block: the largest power of two that divides ``S`` and whose K rows take
    at most ``block_bytes``. kv 8 x hd 128 in bfloat16 -> 512, kv 2 x hd 128 -> 2048, kv 2 x hd 256
    -> 1024: one kernel, tiles by the bytes a position takes."""
    cap = max(block_bytes // (num_kv_heads * head_dim * itemsize), 1)
    return math.gcd(S, 1 << (cap.bit_length() - 1))


KERNEL = "slot_decode_attention"  # the live-block kernel's name in a trace, where a caller gives it no other
KERNEL_NARROW = "slot_decode_attention_narrow"  # and over rows that hold several heads narrower than the 128 lanes
LANES = 128


def position_tile(num_kv_heads: int, head_dim: int) -> tuple:
    """The shape a position's keys (or values) take in the stacked cache: ``(kv, hd)``, or, for heads
    narrower than the 128 lanes that fill whole rows of them, ``(kv * hd / 128, 128)``: ``128 // hd``
    heads side by side a row, head g in row ``g // (128 // hd)``. The same bytes in the same order."""
    if head_dim < LANES and LANES % head_dim == 0 and (num_kv_heads * head_dim) % LANES == 0:
        return num_kv_heads * head_dim // LANES, LANES
    return num_kv_heads, head_dim


def padded_heads(num_heads: int, num_kv_heads: int) -> int:
    """Query rows the kernel takes for ``num_heads`` heads: every key-value head's group grown by
    rows of zeros until the rows are whole bfloat16 tiles of 16 (28 heads over 4: 7 a group go as
    8, 32 rows). A padded row reads what its group reads and is cut off."""
    rep = num_heads // num_kv_heads
    while (rep * num_kv_heads) % 16:
        rep += 1
    return rep * num_kv_heads


def refusal(cache_dtype, num_heads: int, num_kv_heads: int, head_dim: int, S: int, *,
            quantized: bool = False, sharded: bool = False, value_dim: int | None = None) -> str | None:
    """Why the kernel does NOT serve this call (the XLA form then does), or None. Off the TPU the
    answer is always a reason: tier-1 runs on the CPU and the Pallas interpreter under every engine
    test would cost the suite minutes; tests run the interpreter by asking for it. On the TPU the
    shapes let through are the ones compiled for a v5e in ``tests/test_chip_compile.py``.
    ``value_dim`` narrower than ``head_dim``: a latent layer's tile, one row a position whose first
    ``value_dim`` columns (``c_kv``) are also the value and whose rest (``k_r``) is key only."""
    if jax.default_backend() != "tpu":
        return f"backend {jax.default_backend()!r}: the kernel is compiled for the TPU only"
    if sharded:
        return "inside a shard_map body (tensor parallel): a Mosaic kernel is not partitioned, and no cell runs it"
    if quantized:
        return "an int8 cache: the scales are not streamed by this kernel, and no cell runs it"
    dt = jnp.dtype(cache_dtype)
    if dt != jnp.bfloat16:
        return f"a {dt.name} cache: the kernel has been compiled for bfloat16 rows only"
    if value_dim is not None and value_dim != head_dim:
        rope = head_dim - value_dim
        if num_kv_heads != 1 or value_dim % 128 or value_dim > 512 or rope != 128:
            return (f"a latent row of {value_dim} + {rope} on {num_kv_heads} kv head(s): compiled at 512 + 128 on one (whole "
                    "128-lane tiles: a narrower rotated key reaches the kernel only through a copy of its whole stack)")
        if num_heads > 32:
            return f"{num_heads} query heads: compiled at 20 and 32 (padded to at most two bfloat16 tiles of 16 rows)"
        if block_positions(S, 1, value_dim, dt.itemsize) < 512:
            return f"{S} positions a slot: no block of at least 512 positions divides it"
        return None
    if head_dim == 64:
        # two heads a row of 128 lanes (``position_tile``): the kernel sees kv / 2 heads of 128, whose every group of
        # query rows is two key-value heads' groups, whole
        if num_kv_heads % 2 or num_heads % num_kv_heads or num_heads % 16:
            return (f"{num_heads} query heads over {num_kv_heads} kv heads x head_dim 64: compiled at 32 over 8 (pairs of "
                    "key-value heads a 128-lane row, whole bfloat16 tiles of 16 query rows)")
        return refusal(cache_dtype, num_heads, num_kv_heads // 2, 128, S)
    if head_dim % 128 or head_dim > 256:
        return f"head_dim {head_dim}: compiled at 64 (two heads a row), 128 and 256 (a multiple of the 128 lanes)"
    if num_kv_heads & (num_kv_heads - 1) or num_kv_heads > 8:
        return f"{num_kv_heads} kv heads: compiled at 2 and 8 (a power of two, at most 8)"
    if head_dim != 128 and num_kv_heads != 8:
        return (f"{num_kv_heads} kv heads x head_dim {head_dim}: a position's heads lie in ({num_kv_heads}, 128) tiles, which "
                "are [S * kv, hd] rows only after a copy of the whole cache (402 MB of temporaries at 3 x 16 x 4096 x 2 x 256, "
                "compiled for a v5e in PR 35)")
    if num_heads < 16 or padded_heads(num_heads, num_kv_heads) > 48:
        return (f"{num_heads} query heads over {num_kv_heads} kv heads: compiled at 16, 32 and 48 (whole bfloat16 tiles of 16 rows), "
                "and at 28 over 4, whose groups of 7 go as 8")
    blk = block_positions(S, num_kv_heads, head_dim, dt.itemsize)
    if blk * num_kv_heads < 512:
        return f"{S} positions a slot: no block of at least {512 // num_kv_heads} positions divides it"
    return None


# --------------------------------------------------------------------------- the kernel
def _start(j, m_scr, l_scr, o_ref):
    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        o_ref[...] = jnp.zeros_like(o_ref)


def _fold_block(s, ok, v, m_scr, l_scr, o_ref):
    """Fold one block's scores s [nh, cols] (float32; ``ok``: the columns a row may read) and
    values v [cols, vd] into the lane's running max (m), sum (l) and weighted values (o_ref)."""
    nh = s.shape[0]
    s = jnp.where(ok, s, _NEG)
    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))  # every row has position j*blk: real
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)  # masked columns: exp(_NEG - m) == 0
    l_scr[...] = jnp.broadcast_to(l_scr[:, :1] * alpha + p.sum(axis=-1, keepdims=True), l_scr.shape)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    if v.dtype == jnp.bfloat16:
        # float32 probabilities as three bfloat16 terms, stacked so that V is loaded once
        hi = p.astype(jnp.bfloat16)
        r1 = p - hi.astype(jnp.float32)
        mid = r1.astype(jnp.bfloat16)
        lo = (r1 - mid.astype(jnp.float32)).astype(jnp.bfloat16)
        pv = jnp.dot(jnp.concatenate([hi, mid, lo], axis=0), v, preferred_element_type=jnp.float32)
        pv = (pv[:nh] + pv[nh:2 * nh]) + pv[2 * nh:]
    else:
        pv = jnp.dot(p, v.astype(jnp.float32), preferred_element_type=jnp.float32,
                     precision=jax.lax.Precision.HIGHEST)
    o_ref[...] = o_ref[...] * alpha + pv


def _finish(j, l_scr, o_ref):
    @pl.when(j == pl.num_programs(1) - 1)
    def _normalise():
        l = l_scr[:, :1]
        o_ref[...] = o_ref[...] / jnp.where(l > 0.0, l, 1.0)  # a lane with no position folded nothing: zeros


def _kernel(layer_ref, bound_ref, src_ref, last_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, *,
            blk: int, kv: int, rep: int, scale: float):
    """Grid step (lane b, block j): fold the block's positions below ``bound[b]`` into the lane's
    running max (m), sum (l) and weighted values (o_ref, which the block axis revisits)."""
    del layer_ref, src_ref, last_ref  # the index maps' business
    b, j = pl.program_id(0), pl.program_id(1)
    bound = bound_ref[b]
    _start(j, m_scr, l_scr, o_ref)

    @pl.when(j * blk < bound)  # a block with no live position: not fetched (the index map), not computed
    def _fold():
        q, k, v = q_ref[...], k_ref[...], v_ref[...]  # [nh, hd], [blk*kv, hd] x 2
        nh, cols = q.shape[0], k.shape[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
        row = jax.lax.broadcasted_iota(jnp.int32, (nh, cols), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (nh, cols), 1)
        # column c is kv head c % kv of position c // kv; query head h reads kv head h // rep
        ok = (col % kv == row // rep) & (j * blk + col // kv < bound)
        _fold_block(s, ok, v, m_scr, l_scr, o_ref)

    _finish(j, l_scr, o_ref)


def _latent_kernel(layer_ref, bound_ref, src_ref, last_ref, ql_ref, qr_ref, c_ref, r_ref, o_ref, m_scr, l_scr, *,
                   blk: int, scale: float):
    """``_kernel`` for a latent layer: the block of latent rows c [blk, r] is the key, with the
    rotated keys r [blk, rope] beside it, AND the value; every (padded) query head reads every row."""
    del layer_ref, src_ref, last_ref
    b, j = pl.program_id(0), pl.program_id(1)
    bound = bound_ref[b]
    _start(j, m_scr, l_scr, o_ref)

    @pl.when(j * blk < bound)
    def _fold():
        c = c_ref[...]
        nt = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(ql_ref[...], c, nt, preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr_ref[...], r_ref[...], nt, preferred_element_type=jnp.float32)) * scale
        ok = j * blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) < bound
        _fold_block(s, ok, c, m_scr, l_scr, o_ref)

    _finish(j, l_scr, o_ref)


def _launch(kernel, name: str, layer, bound, queries, stacks, blk_rows: int, blk: int, out_width: int, interpret: bool):
    """The grid over (lane, block of ``blk`` positions) that both forms share. ``queries``: arrays
    [B, nh, w], one tile a lane; ``stacks``: arrays [L, B, rows, w] read ``blk_rows`` rows at a time
    from layer ``layer``; bound [B] int32: lane b attends positions 0 .. bound[b]-1 (0: the lane is
    bound to no sequence, reads nothing and gets zeros). -> [B, nh, out_width] float32."""
    B, nh = queries[0].shape[:2]
    S = stacks[0].shape[2] * blk // blk_rows
    nblk = S // blk
    if S % blk:
        raise ValueError(f"a block of {blk} positions does not divide {S}")  # tpulint: disable=ERR002 — a programmer's error at trace time
    bound = jnp.clip(bound.astype(jnp.int32), 0, S)
    lane = jnp.arange(B, dtype=jnp.int32)
    live = bound > 0
    # where an empty lane's steps point: the live lane before it, at its last block (the index the
    # step before had, so nothing is fetched); before the first live lane, that lane's block 0
    before = jax.lax.cummax(jnp.where(live, lane, -1))
    src = jnp.where(before >= 0, before, jnp.argmax(live).astype(jnp.int32))
    last = jnp.where(before >= 0, (jnp.maximum(bound, 1) - 1)[src] // blk, 0)

    def rows(b, j, layer_ref, bound_ref, src_ref, last_ref):
        return layer_ref[0], src_ref[b], jnp.minimum(jnp.where(bound_ref[b] > 0, j, nblk), last_ref[b]), 0

    per_lane = lambda b, j, *_: (b, 0, 0)  # noqa: E731
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, nblk),
            in_specs=[pl.BlockSpec((None, nh, q.shape[2]), per_lane) for q in queries]
            + [pl.BlockSpec((None, None, blk_rows, a.shape[3]), rows) for a in stacks],
            out_specs=pl.BlockSpec((None, nh, out_width), per_lane),
            scratch_shapes=[pltpu.VMEM((nh, 128), jnp.float32), pltpu.VMEM((nh, 128), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, nh, out_width), jnp.float32),
        interpret=interpret,
        name=name,
        # an empty lane's steps lean on the step before them: the grid runs in order
        **({} if interpret else {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=48 << 20)}),
    )(jnp.asarray(layer, jnp.int32).reshape(1), bound, src, last, *queries, *stacks)


def attend_kernel(q, k_stack, v_stack, layer, bound, *, block: int | None = None, interpret: bool = False,
                  name: str = KERNEL, scale: float | None = None):
    """The kernel form. q [B,nh,hd]; k/v_stack [L,B,S,kv,hd]; layer: int32 scalar (traced or not);
    bound [B] int32: lane b attends rows 0 .. bound[b]-1 of layer ``layer`` (0: the lane is
    bound to no sequence, reads nothing and gets zeros). ``name``: the kernel's in a trace (a ring
    layer's calls carry their own). ``scale``: on the scores, where it is not hd^-1/2.
    -> [B, nh*hd] float32."""
    B, nh, hd = q.shape
    L, _, S, kv, _ = k_stack.shape
    blk = block or block_positions(S, kv, hd, k_stack.dtype.itemsize)
    rows = padded_heads(nh, kv)
    if rows != nh:  # each group's rows of zeros at its end: head h stays row (h // rep) * rep' + h % rep
        q = jnp.pad(q.reshape(B, kv, nh // kv, hd), ((0, 0), (0, 0), (0, (rows - nh) // kv), (0, 0))).reshape(B, rows, hd)
    kernel = functools.partial(_kernel, blk=blk, kv=kv, rep=rows // kv, scale=1.0 / math.sqrt(hd) if scale is None else scale)
    out = _launch(kernel, name, layer, bound, [q],
                  [k_stack.reshape(L, B, S * kv, hd), v_stack.reshape(L, B, S * kv, hd)], blk * kv, blk, hd, interpret)
    if rows != nh:
        out = out.reshape(B, kv, rows // kv, hd)[:, :, :nh // kv]
    return out.reshape(B, nh * hd)


def attend_narrow_kernel(q, k_stack, v_stack, layer, bound, num_kv_heads: int, *, block: int | None = None, interpret: bool = False):
    """``attend_kernel`` for heads narrower than the 128 lanes: q [B,nh,hd]; k/v_stack
    [L,B,S,rows,128] with ``128 // hd`` key-value heads side by side a row (``position_tile``).
    Query head h reads key-value head g = h // (nh / kv), which lies in row ``g // pack`` at columns
    ``(g % pack) * hd``: the query goes to those columns of a 128-wide row of its own and zeros to
    the rest, the kernel then takes the rows for heads of 128 (a row's score is the head's own,
    scaled by hd^-1/2), and of its 128 output columns a head keeps its own. -> [B, nh*hd] float32."""
    B, nh, hd = q.shape
    pack = LANES // hd
    at = (jnp.arange(nh) // (nh // num_kv_heads)) % pack  # [nh]: which of a row's heads is head h's
    own = at[:, None] == jnp.arange(pack)[None, :]  # [nh, pack]
    wide = jnp.where(own[None, :, :, None], q[:, :, None, :], jnp.zeros((), q.dtype)).reshape(B, nh, LANES)
    out = attend_kernel(wide, k_stack, v_stack, layer, bound, block=block, interpret=interpret, name=KERNEL_NARROW, scale=hd ** -0.5)
    return jnp.sum(jnp.where(own[None, :, :, None], out.reshape(B, nh, pack, hd), 0.0), axis=2).reshape(B, nh * hd)


def attend_latent_kernel(q_lat, q_rope, c_stack, r_stack, layer, bound, scale: float, *, block: int | None = None,
                         interpret: bool = False):
    """The latent kernel form. q_lat [B,nh,r], q_rope [B,nh,rope]; c_stack [L,B,S,r], r_stack
    [L,B,S,rope]; bound as in ``attend_kernel``. -> [B, nh*r] float32: each head's attention-
    weighted mean of the latent rows, for the value projection that follows."""
    B, nh, r = q_lat.shape
    S = c_stack.shape[2]
    blk = block or block_positions(S, 1, r, c_stack.dtype.itemsize)
    pad = ((0, 0), (0, -nh % 16), (0, 0))  # whole bfloat16 tiles of query rows; a padded row reads what every row reads and is cut off
    q_lat, q_rope = jnp.pad(q_lat.astype(c_stack.dtype), pad), jnp.pad(q_rope.astype(c_stack.dtype), pad)
    out = _launch(functools.partial(_latent_kernel, blk=blk, scale=scale), "latent_decode_attention", layer, bound,
                  [q_lat, q_rope], [c_stack, r_stack], blk, blk, r, interpret)
    return out[:, :nh].reshape(B, nh * r)


def _blocks_kernel(layer_ref, count_ref, pos_ref, table_ref, q_ref, *refs, block: int, kv: int, rep: int, places: int, scale: float):
    """Grid step (lane b, place j of its table): fold, for EVERY key-value head g, the block that
    the table names for (b, g, j) into the lane's running max, sum and weighted values. ``refs``:
    kv blocks of keys (one a key-value head, each fetched by its own head's table), kv blocks of
    values, the output and the two scratch arrays; a block is ``block * kv`` rows of the stack seen
    as [S * kv, hd] (every head's rows of its positions), of which head g's group reads its own."""
    del layer_ref
    k_refs, v_refs, (o_ref, m_scr, l_scr) = refs[:kv], refs[kv:2 * kv], refs[2 * kv:]
    b, j = pl.program_id(0), pl.program_id(1)
    _start(j, m_scr, l_scr, o_ref)

    @pl.when(j < count_ref[b])  # a place that names nothing: not fetched (the index map repeats the place before it), not computed
    def _fold():
        q = q_ref[...]
        nh, cols = q.shape[0], block * kv
        nt = (((1,), (1,)), ((), ()))
        s = jnp.concatenate([jax.lax.dot_general(q, k_ref[...], nt, preferred_element_type=jnp.float32) for k_ref in k_refs], axis=1) * scale
        row = jax.lax.broadcasted_iota(jnp.int32, (nh, cols), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (nh, cols), 1)
        # of head g's block, column c is kv head c % kv of position block * table + c // kv; query head h reads kv head h // rep
        ok = jnp.concatenate([(row // rep == g) & (col % kv == g) & (table_ref[(b * kv + g) * places + j] * block + col // kv <= pos_ref[b])
                              for g in range(kv)], axis=1)
        _fold_block(s, ok, jnp.concatenate([v_ref[...] for v_ref in v_refs], axis=0), m_scr, l_scr, o_ref)

    _finish(j, l_scr, o_ref)


def attend_blocks_kernel(q, k_stack, v_stack, layer, pos, blocks, count, block: int, *, interpret: bool = False):
    """The kernel form of ``attend_blocks``. q [B,nh,hd]; k/v_stack [L,B,S,kv,hd]; blocks [B,kv,N]
    int32 with each lane's first ``count[b]`` places naming blocks (0: the lane reads nothing and
    gets zeros); pos [B]: the last position a lane may read. A grid over (lane, place) whose index
    maps read the table from SMEM: a place streams, for each key-value head, the block its group
    chose from where it lies in the stack. It shares the live-block form's fold (``_fold_block``).
    -> [B, nh*hd] float32."""
    B, nh, hd = q.shape
    L, _, S, kv, _ = k_stack.shape
    N = blocks.shape[-1]
    count = jnp.clip(count.astype(jnp.int32), 0, N)
    # past a lane's count every place repeats its last live one: the pipeline does not fetch the same block again
    place = jnp.minimum(jnp.arange(N, dtype=jnp.int32)[None, None, :], jnp.maximum(count, 1)[:, None, None] - 1)
    table = jnp.take_along_axis(blocks.astype(jnp.int32), place, axis=-1).reshape(B * kv * N)

    def rows(g):
        return lambda b, j, layer_ref, count_ref, pos_ref, table_ref: (layer_ref[0], b, table_ref[(b * kv + g) * N + j], 0)

    per_lane = lambda b, j, *_: (b, 0, 0)  # noqa: E731
    stacks = [a.reshape(L, B, S * kv, hd) for a in (k_stack, v_stack)]
    kernel = functools.partial(_blocks_kernel, block=block, kv=kv, rep=nh // kv, places=N, scale=1.0 / math.sqrt(hd))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, N),
            in_specs=[pl.BlockSpec((None, nh, hd), per_lane)]
            + [pl.BlockSpec((None, None, block * kv, hd), rows(g)) for _ in stacks for g in range(kv)],
            out_specs=pl.BlockSpec((None, nh, hd), per_lane),
            scratch_shapes=[pltpu.VMEM((nh, 128), jnp.float32), pltpu.VMEM((nh, 128), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, nh, hd), jnp.float32),
        interpret=interpret,
        name="sparse_decode_attention",
        **({} if interpret else {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=48 << 20)}),
    )(jnp.asarray(layer, jnp.int32).reshape(1), count, pos.astype(jnp.int32), table, q, *[a for a in stacks for _ in range(kv)])
    return out.reshape(B, nh * hd)


def refusal_blocks(cache_dtype, num_heads: int, num_kv_heads: int, head_dim: int, block: int) -> str | None:
    """Why ``attend_blocks`` does NOT run as the kernel (its XLA form then does), or None: the
    answer of ``refusal`` to the same questions, for a table of blocks of ``block`` positions."""
    if jax.default_backend() != "tpu":
        return f"backend {jax.default_backend()!r}: the kernel is compiled for the TPU only"
    dt = jnp.dtype(cache_dtype)
    if dt != jnp.bfloat16:
        return f"a {dt.name} cache: the kernel has been compiled for bfloat16 rows only"
    if head_dim != 128 or num_kv_heads & (num_kv_heads - 1) or num_kv_heads > 4:
        return f"{num_kv_heads} kv heads x head_dim {head_dim}: compiled at 2 x 128 (a power of two of heads, at most 4, 128 lanes)"
    if num_heads % 16 or num_heads > 32:
        return f"{num_heads} query heads: compiled at 32 (whole bfloat16 tiles of 16 rows)"
    if (block * num_kv_heads) % 16:
        return f"blocks of {block} positions x {num_kv_heads} kv heads: not whole bfloat16 tiles of 16 rows"
    return None


# --------------------------------------------------------------------------- the op
def attend(q, k_stack, v_stack, layer, lengths, num_kv_heads: int, *, live=None, k_scale=None, v_scale=None,
           sharded: bool = False, name: str = KERNEL):
    """One token a lane (its query q [B,nh,hd]) against layer ``layer`` of the stacked slot cache
    k/v_stack [L,B,S,kv,hd], in which the new token's key and value already sit at index
    lengths[b]. ``live`` [B] bool, where the caller knows it: lanes bound to a sequence (the kernel
    reads nothing for the others; the XLA form computes what nobody reads, as it always has).
    k/v_scale [L,B,kv,S]: an int8 cache's scales. ``sharded``: the caller is a shard_map body.
    A stack of S rows that is a RING of a window of S positions (``llm/kv_cache.py``) is read the
    same way: a lane at ``lengths`` holds min(lengths + 1, S) live rows, its first that many, which
    is this op's bound for any stack; ``name`` is then the kernel's own name in a trace.
    Heads narrower than the stack's rows (q's hd under the stack's 128): the stack holds several
    heads a row (``position_tile``), and the kernel that reads it is ``KERNEL_NARROW`` in a trace.
    -> [B, nh*hd] float32."""
    S, narrow = k_stack.shape[2], k_stack.shape[-1] != q.shape[2]
    why = refusal(k_stack.dtype, q.shape[1], num_kv_heads, q.shape[2], S, quantized=k_scale is not None, sharded=sharded)
    if why is None:
        bound = jnp.minimum(lengths, S - 1) + 1
        bound = bound if live is None else jnp.where(live, bound, 0)
        # off the TPU only a test gets here (it swaps ``refusal``), and runs the same body interpreted
        if narrow:
            return attend_narrow_kernel(q, k_stack, v_stack, layer, bound, num_kv_heads, interpret=jax.default_backend() != "tpu")
        return attend_kernel(q, k_stack, v_stack, layer, bound, interpret=jax.default_backend() != "tpu", name=name)
    k_rows, v_rows = layer_of(k_stack, layer), layer_of(v_stack, layer)
    if narrow:  # the rows as heads again: the same bytes
        k_rows, v_rows = (a.reshape(a.shape[:2] + (num_kv_heads, q.shape[2])) for a in (k_rows, v_rows))
    if k_scale is not None:  # dequantize at the float32 the products already accumulate in
        k_rows = k_rows.astype(jnp.float32) * layer_of(k_scale, layer).transpose(0, 2, 1)[..., None]
        v_rows = v_rows.astype(jnp.float32) * layer_of(v_scale, layer).transpose(0, 2, 1)[..., None]
    return attend_rows(q, k_rows, v_rows, lengths, num_kv_heads)


def attend_blocks(q, k_stack, v_stack, layer, lengths, blocks, ok, block: int, *, live=None):
    """One token a lane (its query q [B,nh,hd]) against the BLOCKS of ``block`` positions that a
    table names: ``blocks`` [B,G,N] int32, for each lane and key-value head the blocks its group of
    query heads reads in layer ``layer`` of the stacked slot cache k/v_stack [L,B,S,G,hd], ``ok``
    [B,G,N] bool true for the places that name one (a lane's first places, as many in every group:
    ``ops/sparse_attention.choose_blocks`` hands them out so), the new token's key and value already
    at index lengths[b]: grouped-query softmax attention over the positions at or before lengths[b]
    of those blocks. ``live`` [B] bool, where the caller knows it: the lanes whose output is read
    (the kernel reads nothing for the others; the XLA form computes what nobody reads).
    -> [B, nh*hd] float32."""
    if refusal_blocks(k_stack.dtype, q.shape[1], k_stack.shape[3], q.shape[2], block) is None:
        count = jnp.sum(ok[:, 0], axis=-1)
        # off the TPU only a test gets here (it swaps ``refusal_blocks``), and runs the same body interpreted
        return attend_blocks_kernel(q, k_stack, v_stack, layer, lengths, blocks, count if live is None else jnp.where(live, count, 0),
                                    block, interpret=jax.default_backend() != "tpu")
    return attend_blocks_rows(q, k_stack, v_stack, layer, lengths, blocks, ok, block)


def attend_latent(q_lat, q_rope, c_stack, r_stack, layer, lengths, *, scale: float, live=None, sharded: bool = False):
    """``attend`` for a latent layer: one token a lane, its absorbed queries q_lat [B,nh,r] and
    rotated q_rope [B,nh,<=rope], against layer ``layer`` of the stacked latent rows c_stack
    [L,B,S,r] and rotated keys r_stack [L,B,S,rope] (zeros after the key's own columns), in which
    the new token's already sit at index lengths[b]: ``softmax((q_lat . c_kv + q_rope . k_r) *
    scale)`` over the positions held, times the latent rows themselves. -> [B, nh*r] float32."""
    S, r = c_stack.shape[2], c_stack.shape[-1]
    q_rope = jnp.pad(q_rope, ((0, 0), (0, 0), (0, r_stack.shape[-1] - q_rope.shape[-1])))
    why = refusal(c_stack.dtype, q_lat.shape[1], 1, r + r_stack.shape[-1], S, sharded=sharded, value_dim=r)
    if why is None:
        bound = jnp.minimum(lengths, S - 1) + 1
        return attend_latent_kernel(q_lat, q_rope, c_stack, r_stack, layer, bound if live is None else jnp.where(live, bound, 0),
                                    scale, interpret=jax.default_backend() != "tpu")
    c_rows, r_rows = layer_of(c_stack, layer), layer_of(r_stack, layer)
    q = jnp.concatenate([q_lat, q_rope], axis=-1).astype(c_rows.dtype)
    return attend_rows(q, jnp.concatenate([c_rows, r_rows], axis=-1)[:, :, None], c_rows[:, :, None], lengths, 1, scale)
