"""Attention under a LEARNED index (a DeepSeek-Sparse-Attention indexer over grouped-query heads: the
``indexed`` mixer of ``models/keye_vl.py``).

A query at position ``t`` attends, in ALL its heads, to the ``topk`` positions ``s <= t`` of largest

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        (float32; J heads of d channels, ONE key a position)

and to every ``s <= t`` while ``t + 1 <= topk``; ties go to the earlier position. The indexer's
projections are the model file's; what is here is shared by the sequence form and the decode step:

1. ``index_keys``: the scores of a tile of queries against a tile of positions, as int32 KEYS whose
   signed order is the scores' (``sort_keys``), the heads summed one after another in one order
   wherever they are computed (the XLA form, both kernels), so that a pair's key is the same bits
   whichever pass computes it;
2. the choice as a THRESHOLD: the ``topk``-th largest key of a query's candidates, found by
   bisection over the key's 32 bits (32 counting passes: ``jax.lax.top_k`` at k = 2,048 of 24,576
   sorts), and, where several candidates hold exactly that key, the position up to which they are
   taken (a second bisection over the positions; skipped where no row of a tile has such a tie).
   ``threshold`` is the XLA form over keys that are held whole; ``thresholds_kernel`` computes a
   tile of queries' keys into fast memory, bisects there, and hands on the CHOICE, a bit a pair
   (``choice_words``: 75.5 MB a sequence at 24,576), so no [T, T] scores, keys or bytes ever exist
   in HBM and a pair's index score is computed once;
3. attention under the choice: ``attend_indexed_kernel`` is a flash pass over every causal tile of
   keys that READS the tile's bits (a block of words a tile of queries and eight tiles of keys) and
   does attention's own work alone: a masked score is ``-inf`` under a running maximum that starts
   at a finite floor, so its weight is exactly 0; ``indexed_attention_seq`` runs it, or, where
   ``refusal`` gives a reason, the same steps a tile of queries at a time in XLA with a mask.

The decode step (``indexed_attention_step``) scores a lane's ``k_idx`` rows, takes the ``topk`` best
(``jax.lax.top_k``: one row of at most ``max_seq_len`` keys a lane, ties to the lower index) and
attends to those rows of ``k`` and ``v`` GATHERED from where they lie in the stacked slot cache.
PERF.md section 6 (PR 58) has the measurements that chose a masked pass for prefill and gathered
rows for decode.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import slot_attention
from ray_tpu.util.profiling import scope

INT_MIN = -(1 << 31)  # the key of a position that is no candidate; no finite score's key
_NEG = -1e30
_TILE_Q, _TILE_K = 256, 512  # queries a grid step takes (with all their heads), and positions a tile of keys
_LANES = 128
_WORD = 32  # positions an int32 word of the choice table holds, a bit each


# --------------------------------------------------------------------------- scores and keys
def sort_keys(scores):
    """float32 -> int32 whose signed order is the floats' (-0.0 counted as 0.0: a head's zero times a
    negative weight is no smaller than another's zero)."""
    b = jax.lax.bitcast_convert_type(scores + 0.0, jnp.int32)
    return b ^ ((b >> 31) & 0x7FFFFFFF)


def _dot_nt(a, b):
    """a [Q,d] . b [S,d]^T -> float32 [Q,S]: exact products of the operands as held (float32 operands at ``highest``)."""
    exact = a.dtype == jnp.float32 and b.dtype == jnp.float32
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
                               precision=jax.lax.Precision.HIGHEST if exact else None)


def index_keys(dots, w, heads: int):
    """The index keys of a tile: ``dots(j)`` -> float32 [Q,S], head j's products ``qI[t, j] . kI[s]``;
    w [Q,J] float32 -> int32 [Q,S]. The heads are summed in their order, one after another: ONE
    body for the XLA forms and both kernels, so that a pair's key is the same bits in the pass that
    finds a query's thresholds and in the pass that applies them."""
    s = None
    for j in range(heads):
        term = w[:, j:j + 1] * jnp.maximum(dots(j), 0.0)
        s = term if s is None else s + term
    return sort_keys(s)


def _kth_key(count_ge, topk: int, rows):
    """The largest int32 ``c`` with ``count_ge(c) >= topk`` for every row, built bit by bit from the
    sign down (``INT_MIN`` where a row has fewer than ``topk`` keys over it); ``rows``: int32 zeros
    of the rows' shape. The one bisection of the XLA form and the kernel: each brings its own count."""
    thr = jnp.where(count_ge(rows) >= topk, 0, INT_MIN).astype(jnp.int32)

    def bit(n, thr):
        cand = thr | jnp.left_shift(jnp.int32(1), 30 - n)
        return jnp.where(count_ge(cand) >= topk, cand, thr)

    return jax.lax.fori_loop(0, 31, bit, thr)


def _tie_cut(count_tied_before, need, bits: int, rows):
    """The largest position ``p < 2^bits`` with fewer than ``need`` of a row's tied keys at positions
    before it (``count_tied_before(p)``): the tied keys at or before it are the ``need`` earliest."""
    def bit(n, cut):
        cand = cut | jnp.left_shift(jnp.int32(1), bits - 1 - n)
        return jnp.where(count_tied_before(cand) < need, cand, cut)

    return jax.lax.fori_loop(0, bits, bit, rows)


def threshold(keys, topk: int):
    """keys int32 [.., S] (``INT_MIN`` where a position is no candidate) -> (thr, cut) int32 [..]:
    a row's chosen positions are its candidates with ``key > thr``, and with ``key == thr`` at a
    position ``<= cut`` (``chosen``): the ``topk`` largest, ties to the earlier position; all of
    them where a row has at most ``topk`` candidates. Two bisections, 32 passes over the keys'
    bits and one a bit of a position; the XLA form, for rows held whole (a test's, the oracle's)."""
    S = keys.shape[-1]
    rows, at = jnp.zeros(keys.shape[:-1], jnp.int32), jnp.arange(S, dtype=jnp.int32)
    count = lambda held: jnp.sum(held, axis=-1, dtype=jnp.int32)  # noqa: E731
    thr = _kth_key(lambda c: count(keys >= c[..., None]), topk, rows)
    tied = keys == thr[..., None]
    cut = _tie_cut(lambda p: count(tied & (at < p[..., None])), topk - count(keys > thr[..., None]), max(S - 1, 1).bit_length(), rows)
    return thr, cut


def chosen(keys, thr, cut):
    """bool [.., S]: the candidates that ``threshold``'s (thr, cut) choose."""
    at = jnp.arange(keys.shape[-1], dtype=jnp.int32)
    return (keys > INT_MIN) & ((keys > thr[..., None]) | ((keys == thr[..., None]) & (at <= cut[..., None])))


# --------------------------------------------------------------------------- which form
def refusal(dtype, head_dim: int, index_dim: int, positions: int, mesh=None) -> str | None:
    """Why the sequence form does NOT run as the two kernels (the XLA form of masked tiles then
    does), or None. Off the TPU the answer is always a reason, as ``ops/slot_attention.refusal``
    says of its own; on it the shapes let through are the ones compiled for a v5e in
    ``tests/test_chip_compile.py``."""
    if jax.default_backend() != "tpu":
        return f"backend {jax.default_backend()!r}: the kernels are compiled for the TPU only"
    if mesh is not None and mesh.size > 1:
        return "a mesh of several devices: a Mosaic kernel is not partitioned, and no cell runs it"
    if jnp.dtype(dtype) != jnp.bfloat16:
        return f"{jnp.dtype(dtype).name} operands: the kernels have been compiled for bfloat16 only"
    if head_dim != 128 or index_dim != 64:
        return f"heads of {head_dim} under an index of {index_dim}: compiled at 128 under 64"
    if positions % min(_TILE_Q, positions) or positions % min(_TILE_K, positions) or positions % _LANES:
        return f"{positions} positions: not whole tiles of {_TILE_Q} queries and {_TILE_K} keys"
    if positions % (_WORD * _LANES):
        return f"{positions} positions: not whole groups of {_WORD * _LANES}, which the choice table packs (a bit a pair, {_WORD} chunks of {_LANES} lanes a word)"
    return None


# --------------------------------------------------------------------------- kernel 1: the thresholds, and the choice a bit a pair
def choice_words(positions: int) -> int:
    """int32 words a query's row of the choice table has: ``_WORD`` positions a word, in whole groups of
    ``_WORD * _LANES`` positions. Bit ``c`` of lane ``l`` of a group's ``_LANES`` words is the group's
    position ``c * _LANES + l``: a group is packed from ``_WORD`` chunks of lanes as they lie, shifted,
    and unpacked the same way, with no movement across lanes."""
    return -(-positions // (_WORD * _LANES)) * _LANES


def _thresholds_kernel(len_ref, qi_ref, w_ref, ki_ref, table_ref, keys_scr, *, topk: int, tq: int, tk: int, heads: int, bits: int):
    """Grid step (sequence b, tile of queries i): the tile's index keys against every position at
    or before its last query into ``keys_scr`` [tq, groups of positions], the two bisections over
    them, a chunk of ``tk`` positions at a time, then ``chosen`` of the keys and the thresholds,
    packed (``choice_words``) -> table [tq, words]; zeros for a tile of padding, and after a query."""
    b, i = pl.program_id(0), pl.program_id(1)
    group = _WORD * _LANES
    chunks = ((i + 1) * tq + tk - 1) // tk  # the chunks of positions that hold a candidate of some query of the tile
    groups = (chunks * tk + group - 1) // group  # and the groups of the table: the rest of the last one is filled with ``INT_MIN``
    table_ref[...] = jnp.zeros_like(table_ref)  # a tile of padding, and the groups after the tile's last query: no word is undefined

    @pl.when(i * tq < len_ref[b])
    def _tile():
        w = w_ref[...]
        row = i * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)

        def fill(c, _):
            first = pl.multiple_of(c * tk, tk)
            kt = ki_ref[pl.ds(first, tk), :]
            keys = index_keys(lambda j: _dot_nt(qi_ref[j], kt), w, heads)
            col = first + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
            keys_scr[:, pl.ds(first, tk)] = jnp.where(col <= row, keys, INT_MIN)
            return 0

        def blank(c, _):
            keys_scr[:, pl.ds(pl.multiple_of(c * tk, tk), tk)] = jnp.full((tq, tk), INT_MIN, jnp.int32)
            return 0

        jax.lax.fori_loop(0, chunks, fill, 0)
        jax.lax.fori_loop(chunks, groups * (group // tk), blank, 0)

        def count(pred):
            """[tq, 1]: how many of a row's keys ``pred(keys [tq,128], first position)`` holds of."""
            def chunk(c, acc):
                for u in range(tk // _LANES):
                    first = pl.multiple_of(c * tk + u * _LANES, _LANES)
                    acc = acc + pred(keys_scr[:, pl.ds(first, _LANES)], first).astype(jnp.int32)
                return acc
            return jnp.sum(jax.lax.fori_loop(0, chunks, chunk, jnp.zeros((tq, _LANES), jnp.int32)), axis=-1, keepdims=True)

        def pack(allowed):
            """The table's live groups from ``allowed(keys [tq,128], first position)``: one pass over the keys."""
            def one(g, _):
                word = jnp.zeros((tq, _LANES), jnp.int32)
                for c in range(_WORD):
                    first = pl.multiple_of(g * group + c * _LANES, _LANES)
                    word = word | jnp.where(allowed(keys_scr[:, pl.ds(first, _LANES)], first), jnp.int32(1 << c if c < 31 else INT_MIN), 0)
                table_ref[:, pl.ds(pl.multiple_of(g * _LANES, _LANES), _LANES)] = word
                return 0
            jax.lax.fori_loop(0, groups, one, 0)

        rows = jnp.zeros((tq, 1), jnp.int32)
        ge = lambda c: count(lambda keys, _: keys >= c)  # noqa: E731
        thr = _kth_key(ge, topk, rows)
        ties = jnp.max(ge(thr)) > topk  # some row holds its threshold's key more than once (or has fewer candidates than topk)

        @pl.when(jnp.logical_not(ties))
        def _plain():
            least = jnp.maximum(thr, INT_MIN + 1)  # a row with fewer candidates than topk takes them all, and no position that is none
            pack(lambda keys, _: keys >= least)

        @pl.when(ties)
        def _ties():
            lane = jax.lax.broadcasted_iota(jnp.int32, (tq, _LANES), 1)
            tied_before = lambda p: count(lambda keys, first: (keys == thr) & (first + lane < p))  # noqa: E731
            cut = _tie_cut(tied_before, topk - count(lambda keys, _: keys > thr), bits, rows)
            cut = jnp.where(thr > INT_MIN, cut, -1)  # where ``INT_MIN`` is the threshold it is the key of what is no candidate
            pack(lambda keys, first: (keys > thr) | ((keys == thr) & (first + lane <= cut)))


def _tiles(T: int) -> tuple:
    return min(_TILE_Q, T), min(_TILE_K, T)


def thresholds_kernel(qi, w, ki, lengths, topk: int, *, interpret: bool = False):
    """qi [B,J,T,d], w [B,T,J] float32, ki [B,T,d], lengths [B] -> int32 [B,T,``choice_words(T)``]:
    every query's choice among the positions at or before it (``chosen`` of its keys and its
    ``threshold``), a bit a position; tiles of queries past a sequence's true length are all zeros."""
    B, J, T, d = qi.shape
    tq, tk = _tiles(T)
    bits = max(T - 1, 1).bit_length()
    words = choice_words(T)
    rows = lambda b, i, *_: (b, i, 0)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_thresholds_kernel, topk=topk, tq=tq, tk=tk, heads=J, bits=bits),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, T // tq),
            in_specs=[pl.BlockSpec((None, J, tq, d), lambda b, i, *_: (b, 0, i, 0)), pl.BlockSpec((None, tq, J), rows),
                      pl.BlockSpec((None, T, d), lambda b, i, *_: (b, 0, 0))],
            out_specs=pl.BlockSpec((None, tq, words), rows),
            scratch_shapes=[pltpu.VMEM((tq, words * _WORD), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((B, T, words), jnp.int32), interpret=interpret, name="indexer_thresholds",
        **({} if interpret else {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"), vmem_limit_bytes=100 << 20)}),
    )(lengths.astype(jnp.int32), qi, w, ki)


# --------------------------------------------------------------------------- kernel 2: attention under the choice
def _attend_kernel(len_ref, q_ref, k_ref, v_ref, table_ref, o_ref, m_scr, l_scr, acc_scr, *, scale: float, tq: int, tk: int, heads: int, group: int):
    """Grid step (sequence b, tile of queries i, tile of keys j): the choice of the tile's pairs
    from the words of the group that holds the keys, and the tile's keys folded into the running
    max, sum and weighted values of every query, one query head after another; the mask is the
    same for all of them. A pair that is not chosen scores ``-inf`` under a maximum that is never
    below ``_NEG``: its weight is ``exp(-inf)``, and a row that has met no chosen pair yet keeps
    its zeros (``alpha`` is ``exp(0)``). A row's running max and sum are held in EVERY lane of
    [tq, ``_LANES``]: a column of one lane a row costs the pass as much again (PERF.md section 6, PR 59)."""
    b, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    hd = acc_scr.shape[-1]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when((j * tk <= i * tq + tq - 1) & (i * tq < len_ref[b]))  # a tile of keys after every query of the tile, or a tile of padding: not fetched, not computed
    def _fold():
        word = table_ref[...]
        at = (j * tk) % (_WORD * _LANES) // _LANES  # the tile's first chunk of lanes in its group: the bit its positions have in a word
        allowed = jnp.concatenate([(word & jnp.left_shift(jnp.int32(1), at + u)) != 0 for u in range(tk // _LANES)], axis=1)  # causal as it comes: no later position is chosen
        for h in range(heads):
            k, v = k_ref[h // group], v_ref[h // group]
            s = jax.lax.dot_general(q_ref[h], k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
            s = jnp.where(allowed, s, -jnp.inf)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - pltpu.repeat(m_new, tk // _LANES, axis=1))
            alpha = jnp.exp(m_prev - m_new)
            l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha[:, :hd] + jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            m_scr[h] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        for h in range(heads):
            l = l_scr[h][:, :hd]
            o_ref[h] = (acc_scr[h] / jnp.where(l > 0.0, l, 1.0)).astype(o_ref.dtype)


def attend_indexed_kernel(q, k, v, table, lengths, *, interpret: bool = False):
    """q [B,nh,T,hd], k, v [B,G,T,hd]; table [B,T,``choice_words(T)``] (``thresholds_kernel``);
    lengths [B] -> o [B,nh,T,hd] in q's dtype: softmax attention of every query over the positions
    its bits choose, zeros for the tiles of queries past a sequence's true length."""
    B, nh, T, hd = q.shape
    G = k.shape[1]
    tq, tk = _tiles(T)
    last = lambda i: (i * tq + tq - 1) // tk  # noqa: E731 - the last tile of keys a tile of queries reads
    queries = lambda b, i, j, *_: (b, 0, i, 0)  # noqa: E731
    keys = lambda b, i, j, *_: (b, 0, jnp.minimum(j, last(i)), 0)  # noqa: E731 - past it the index repeats: nothing is fetched
    words = lambda b, i, j, *_: (b, i, jnp.minimum(j, last(i)) * tk // (_WORD * _LANES))  # noqa: E731 - one block of words serves a group's tiles of keys
    return pl.pallas_call(
        functools.partial(_attend_kernel, scale=hd ** -0.5, tq=tq, tk=tk, heads=nh, group=nh // G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, T // tq, T // tk),
            in_specs=[pl.BlockSpec((None, nh, tq, hd), queries), pl.BlockSpec((None, G, tk, hd), keys), pl.BlockSpec((None, G, tk, hd), keys),
                      pl.BlockSpec((None, tq, _LANES), words)],
            out_specs=pl.BlockSpec((None, nh, tq, hd), queries),
            scratch_shapes=[pltpu.VMEM((nh, tq, _LANES), jnp.float32), pltpu.VMEM((nh, tq, _LANES), jnp.float32), pltpu.VMEM((nh, tq, hd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, nh, T, hd), q.dtype), interpret=interpret, name="indexed_prefill_attention",
        **({} if interpret else {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=100 << 20)}),
    )(lengths.astype(jnp.int32), q, k, v, table)


# --------------------------------------------------------------------------- the sequence form
def indexed_attention_seq(q, k, v, qi, w, ki, lengths, topk: int, tile: int = 128, mesh=None):
    """The sequence form. q [B,nh,T,hd], k, v [B,G,T,hd] (nh a multiple of G); the indexer's qi
    [B,J,T,d], w [B,T,J] float32 and ki [B,T,d]; lengths [B]: true lengths of the right-padded
    sequences -> o [B,nh,T,hd]: every query's softmax attention over the ``topk`` positions at or
    before it that its index scores highest (all of them while it has at most ``topk``). Two
    kernels (thresholds and the choice a bit a pair under ``indexed.select``, attention under the
    bits under ``indexed.attend``) unless ``refusal`` gives a reason; then ``tile`` queries
    at a time against ALL positions in XLA: keys, thresholds, a mask."""
    B, nh, T, hd = q.shape
    G, J = k.shape[1], qi.shape[1]
    if refusal(q.dtype, hd, qi.shape[-1], T, mesh) is None:
        interpret = jax.default_backend() != "tpu"  # off the TPU only a test gets here (it swaps ``refusal``), and runs the same bodies interpreted
        with scope("indexed.select"):
            table = thresholds_kernel(qi, w, ki, lengths, topk, interpret=interpret)
        with scope("indexed.attend"):
            return attend_indexed_kernel(q, k, v, table, lengths, interpret=interpret)
    Q = min(tile, T)
    pad = -T % Q
    if pad:  # what is padded lies after every real position and is cut off
        q, qi = (jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0))) for a in (q, qi))
        w = jnp.pad(w, ((0, 0), (0, pad), (0, 0)))
    at = jnp.arange(T, dtype=jnp.int32)
    precision = jax.lax.Precision.HIGHEST if q.dtype == jnp.float32 else None

    def one_tile(first):
        t = first + jnp.arange(Q, dtype=jnp.int32)
        with scope("indexed.score"):
            qt, wt = jax.lax.dynamic_slice_in_dim(qi, first, Q, axis=2), jax.lax.dynamic_slice_in_dim(w, first, Q, axis=1)
            keys = jax.vmap(lambda qb, wb, kb: index_keys(lambda j: _dot_nt(qb[j], kb), wb, J))(qt, wt, ki)  # [B,Q,T]
            keys = jnp.where(at[None, None, :] <= t[None, :, None], keys, INT_MIN)
        with scope("indexed.select"):
            allowed = chosen(keys, *threshold(keys, topk))
        with scope("indexed.attend"):
            qg = jax.lax.dynamic_slice_in_dim(q, first, Q, axis=2).reshape(B, G, nh // G, Q, hd)
            s = jnp.einsum("bgrqh,bgsh->bgrqs", qg, k, preferred_element_type=jnp.float32, precision=precision) * hd ** -0.5
            p = jax.nn.softmax(jnp.where(allowed[:, None, None], s, -jnp.inf), axis=-1)  # every query reads its own position: no empty row
            return jnp.einsum("bgrqs,bgsh->bgrqh", p.astype(v.dtype), v, preferred_element_type=jnp.float32, precision=precision).astype(q.dtype)

    out = jax.lax.map(one_tile, jnp.arange(0, T + pad, Q, dtype=jnp.int32))  # [tiles, B, G, R, Q, hd]
    return jnp.moveaxis(out, 0, 3).reshape(B, nh, T + pad, hd)[:, :, :T]


# --------------------------------------------------------------------------- the decode step
def indexed_attention_step(q, qi, w, k_stack, v_stack, ki_stack, layer, pos, topk: int):
    """One token a lane: its query q [B,nh,hd] and its indexer's qi [B,J,d], w [B,J] float32
    against layer ``layer`` of the stacked slot cache: k/v_stack [L,B,S,G,hd] and the indexer's keys
    ki_stack [L,B,S,d], the new token's rows already at index pos[b]. Scores of the lane's ``ki``
    rows (``indexed.score``), the ``topk`` best of the positions ``<= pos[b]`` (``indexed.select``:
    ``jax.lax.top_k``, ties to the earlier position; a lane that holds at most ``topk`` positions
    takes them all), softmax attention over those rows of ``k`` and ``v``, gathered
    (``indexed.attend``). One form for every lane. -> [B, nh*hd] float32."""
    B, nh, hd = q.shape
    S, G, J = k_stack.shape[2], k_stack.shape[3], qi.shape[1]
    n = min(topk, S)
    lanes = jnp.arange(B)[:, None]
    with scope("indexed.score"):
        rows = slot_attention.layer_of(ki_stack, layer)  # [B,S,d]
        exact = qi.dtype == jnp.float32 and rows.dtype == jnp.float32
        dots = jnp.einsum("bjd,bsd->bjs", qi, rows, preferred_element_type=jnp.float32, precision=jax.lax.Precision.HIGHEST if exact else None)
        keys = index_keys(lambda j: dots[:, j], w, J)  # [B,S]
        keys = jnp.where(jnp.arange(S, dtype=jnp.int32)[None, :] <= pos[:, None], keys, INT_MIN)
    with scope("indexed.select"):
        best, idx = jax.lax.top_k(keys, n)
        ok = best > INT_MIN
    with scope("indexed.attend"):
        kb, vb = k_stack[layer, lanes, idx], v_stack[layer, lanes, idx]  # [B,n,G,hd]: the chosen rows from where they lie
        s = jnp.einsum("bgrh,bngh->bgrn", q.reshape(B, G, nh // G, hd), kb, preferred_element_type=jnp.float32) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(ok[:, None, None], s, -jnp.inf), axis=-1)  # every lane holds its own position: no empty row
        return jnp.einsum("bgrn,bngh->bgrh", p, vb.astype(jnp.float32)).reshape(B, nh * hd)
