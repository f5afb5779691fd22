"""The delta rule over a padded batch of sequences from a zero state, as one kernel that keeps a
chunk and the state in fast memory (``models/qwen3_next.delta_rule_chunked`` says what the rule is
and how a chunk of it is solved; this is its lines, for either gate: one for each of a head's key
channels, Kimi Delta Attention, or one a head, Gated DeltaNet).

The XLA form builds, for all chunks at once, the ``C x C`` pairs of a chunk (``kk``, ``qk``), ``A``,
``(I + A)^-1``, ``w_v``, ``w_k``, ``q_in`` and ``k_out`` as a dozen fusions that each write a
float32 array of a chunk's square or a chunk times a head to HBM for the next to read back, then
scans the chunks reading six of them (5.4 us a chunk and head on a v5e, PR 43). Here ``q``, ``k``,
``v``, the gate and ``beta`` of a chunk come in once, where they lie (position-major: a head's
``K`` columns of a row are one 128-lane tile, so nothing is transposed on the way in or out),
``o`` goes out once, everything between lives in VMEM, and the state ``S [K, V]`` float32 stays
there from a sequence's first chunk to its last and is written once. The grid walks (sequence,
head) in parallel and a sequence's chunks in order, a few pairs of chunks a step.

What sets the pace is not a count of operations but products that wait for each other: a chunk is
a chain of a dozen small matmuls (the cumulative gate, the pairs across sub-blocks, six factors of
the inverse, ``w``, two for the state), each a fraction of a microsecond of latency on one of four
MXUs that the others leave idle, and the compiler overlaps independent chains only where their
stages lie side by side in ONE block of straight-line code. So (measured on a v5e, PR 43: 2.6 us a
chunk and head for the plain transcription, 1.4 for this): TWO chunks share every square
(``_solve``), a factor of the inverse is one product and not two, every line of ``_solve`` is a
line for all the pairs of chunks of a step (a batch axis: their products lie side by side), and
the loops are unrolled (a rolled loop is a wall the scheduler does not look over: 2.1 us).

What it costs to START matters as much: a serving cell warms 15 prefill programs, each traces and
lowers this body again in every process whatever the compile cache holds, and its host does that
three times slower than this sandbox. The same mathematics written as Python loops over lists of
pairs (3,000 equations) ran 1.25 us a chunk and head and added 58 s to the cell's warm set-up;
batched and with ``fori_loop`` bodies traced once it is 200 equations. Keep the body small.

ONE body for both gates; the gate's rank picks how ``_solve`` builds a chunk's ``C x C`` pairs. A
gate by channel has to stand INSIDE a pair's sum over the channels, which costs sub-blocks
(``_pairs_by_channel``: a matmul a sub-block row, then 16 steps on the vector unit). One gate a
head is a scalar that goes onto the products AFTER the matmul: no sub-blocks, ``kk`` and ``qk`` one
product each, and the gate comes in as ``beta`` does, ``[B, T, heads]``, not broadcast over a
head's channels in HBM. Everything else (two chunks a square, the inverse, ``pass_on``, the grid,
the specs, ``refusal``) is shared. A trace lists ``delta_rule_by_channel`` or ``delta_rule_by_head``.

Precision is the XLA form's: gates, their cumulative sum, every exponent, the pairs inside a
sub-block, ``A`` and its inverse (the same product of ``log2(C)`` factors) in float32; every other
product with its operands in ``operand_dtype`` accumulated in float32; the state float32.

``refusal`` says why the kernel does not serve a call (the XLA lines then do); off the TPU that is
always the backend, and a test that wants the kernel asks for it and gets the same body interpreted.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# positions in a sub-block of a chunk, for a gate by key channel (``models/qwen3_next._pairs_by_channel`` says why there are sub-blocks)
SUB_BLOCK = 16
_HI = jax.lax.Precision.HIGHEST
_NN, _NT = (((2,), (1,)), ((0,), (0,))), (((2,), (2,)), ((0,), (0,)))  # a @ b, a @ b^T: a pair of chunks a batch
_MM, _TN = (((1,), (0,)), ((), ())), (((0,), (0,)), ((), ()))  # a @ b, a^T @ b
# pairs of chunks a step of the grid at most (512 positions: a quarter of a MB a block)
_AT_ONCE = 4


def refusal(operand_dtype, K: int, V: int, chunk: int, *, mesh=None) -> str | None:
    """Why the kernel does NOT serve this call, or None (see the module docstring). ``chunk`` is the
    chunk as run (``min(chunk_size, T)``)."""
    if jax.default_backend() != "tpu":
        return f"backend {jax.default_backend()!r}: the kernel is compiled for the TPU only"
    if mesh is not None and mesh.size > 1:
        return "a program over a mesh: a Mosaic kernel is not partitioned (PR 21), and no cell runs the rule on one"
    if operand_dtype is None or jnp.dtype(operand_dtype) != jnp.bfloat16:
        return f"{'float32' if operand_dtype is None else jnp.dtype(operand_dtype).name} operands: the kernel has been compiled for bfloat16 operands only"
    if K != 128 or V != 128:
        return f"a head of {K} key and {V} value channels: compiled at 128 and 128 (one 128-lane tile each)"
    if chunk != 64:
        return f"a chunk of {chunk} positions: compiled at 64 (four sub-blocks of 16)"
    return None


def counters(name: str, layers: int, batch: int, length: int, chunk_size: int, dtype: str, K: int, V: int) -> dict:
    """What a description's ``prefill_counters`` says of the rule in ONE prefill program of ``batch`` x
    ``length`` positions (as padded), from its shape alone: ``<name>_chunks``, the chunks of the rule
    over its ``layers``, and ``<name>_kernel_chunks``, how many of them the kernel ran (all, or none
    where ``refusal`` speaks). ``dtype``: the model's, whose matmul operands the rule takes."""
    chunk = min(chunk_size, length)
    chunks = layers * batch * -(-length // chunk)
    refused = refusal(None if dtype == "float32" else dtype, K, V, chunk)
    return {f"{name}_chunks": chunks, f"{name}_kernel_chunks": 0 if refused else chunks}


def _pairs_by_channel(q, k, gc, k_at, gc_at, same, row, col, *, C: int, sub: int, mm):
    """``kk``, ``qk`` [P,2C,2C] for a gate by key channel (``models/qwen3_next._pairs_by_channel`` says why
    there are sub-blocks). ``k_at``, ``gc_at``: scratch [sub-blocks, sub, K], for a sub-block's j-th row at a
    j that a loop counts."""
    P, _, K = k.shape
    I, f32 = C // sub, jnp.float32
    # the pairs of two DIFFERENT sub-blocks: relative to the decay as the later sub-block starts
    across = [jnp.zeros((P, 4 * sub, 2 * C), f32)]
    for i in range(1, I):
        starts = [h * C + i * sub for h in (0, 1)]
        firsts = []
        for at in starts:
            inward = jnp.exp(gc[:, at:at + sub] - gc[:, at - 1:at])
            firsts += [k[:, at:at + sub] * inward, q[:, at:at + sub] * inward]
        started = jnp.concatenate([jnp.broadcast_to(gc[:, at - 1:at], (P, C, K)) for at in starts], axis=1)
        across.append(mm(jnp.concatenate(firsts, axis=1), k * jnp.exp(jnp.minimum(started - gc, 0.0)), _NT))  # [P, 4 sub, 2C]: a chunk's k rows, its q rows, then the other's
    kk, qk = (jnp.concatenate([a[:, (2 * h + of) * sub:(2 * h + of + 1) * sub] for h in (0, 1) for a in across], axis=1) for of in (0, 1))
    earlier_block = same & (row // sub > col // sub)
    kk, qk = jnp.where(earlier_block, kk, 0.0), jnp.where(earlier_block, qk, 0.0)
    # the pairs inside ONE sub-block: each pair's own exponent, summed over the channels
    inside, place = same & (col // sub == row // sub) & (col <= row), col % sub
    blocks = lambda a: a.reshape(P * 2 * I, sub, K)  # noqa: E731
    q_b, k_b, gc_b = blocks(q), blocks(k), blocks(gc)
    k_at[...], gc_at[...] = k_b, gc_b

    def against(j, pairs):  # a sub-block's j-th position against every t of the sub-block
        k_in = k_at[:, pl.ds(j, 1), :] * jnp.exp(jnp.minimum(gc_b - gc_at[:, pl.ds(j, 1), :], 0.0))
        here = inside & (place == j)
        return tuple(jax.lax.select(here, jnp.broadcast_to(jnp.sum(a * k_in, axis=2).reshape(P, 2 * C, 1), x.shape), x) for a, x in zip((k_b, q_b), pairs))

    return jax.lax.fori_loop(0, sub, against, (kk, qk), unroll=True)


def _solve(q, k, v, g, beta, k_at, gc_at, *, C: int, sub: int, mm, exact):
    """What pairs of chunks need that does not depend on the state they start from, for ``P``
    pairs at once: q, k, v [P,2C,K|V], beta [P,2C,1] and g [P,2C,K], a gate by key channel, or
    [P,2C,1], one gate a head (``k_at``, ``gc_at`` None: it has no sub-blocks) -> (``w_v`` [P,2C,V],
    ``w_k`` [P,2C,K], the pairs ``qk`` [P,2C,2C], ``gc`` [P,2C,K]). The two chunks of a pair (they
    follow each other) stand on the diagonal of ONE ``2C x 2C`` square (128 x 128 at C = 64: the
    registers one chunk's would take, and one product where there were two); what lies off the
    diagonal is never a pair. Every line is a line for all P pairs: their products lie side by side."""
    P, _, K = k.shape
    f32 = jnp.float32
    row, col = (jax.lax.broadcasted_iota(jnp.int32, (P, 2 * C, 2 * C), d) for d in (1, 2))
    same, row, col = row // C == col // C, row % C, col % C  # inside one chunk, and where in it
    # one gate a head: beside its K equal lanes, a column s of the gates after s (what sums to the log of the decay from s to t)
    by_channel = k_at is not None
    rest = g if by_channel else jnp.concatenate([jnp.broadcast_to(g, k.shape), jnp.where(same & (col < row), g, 0.0)], axis=2)
    # the log of the decay since a chunk's start, <= 0, falling: g's three bfloat16 parts (their sum is g, exactly) under a triangle of ones
    ones, gc = (same & (col <= row)).astype(jnp.bfloat16), jnp.zeros_like(rest)
    for _ in range(3):
        part = rest.astype(jnp.bfloat16)
        gc, rest = gc + jax.lax.dot_general(ones, part, _NN, preferred_element_type=f32), rest - part.astype(f32)
    if by_channel:  # the decay stands INSIDE a pair's sum over the channels
        kk, qk = _pairs_by_channel(q, k, gc, k_at, gc_at, same, row, col, C=C, sub=sub, mm=mm)
    else:  # a scalar goes onto the products AFTER the matmul, <= 1 under the triangle
        gc, decay = gc[:, :, :K], jnp.where(same & (col <= row), jnp.exp(gc[:, :, K:]), 0.0)
        kk, qk = decay * mm(k, k, _NT), decay * mm(q, k, _NT)
    A = jnp.where(same & (col < row), beta * kk, 0.0)
    # (I + A)^-1, float32: with B = -A the product (I + B)(I + B^2)(I + B^4)..., which ends after log2(C)
    # factors (B^C = 0). The power stands ON the diagonal and the product so far beside it, OFF the
    # diagonal, so that a factor costs ONE product for both: power @ [power | product] (every
    # factor is a polynomial in A: they commute)
    both = jax.lax.fori_loop(0, math.ceil(math.log2(C)), lambda _, both: jnp.where(same, 0.0, both) + exact(jnp.where(same, both, 0.0), both),
                             jnp.where(same, -A, (col == row).astype(f32)), unroll=True)
    inv = jnp.where(same, 0.0, both)  # a chunk's inverse beside its square: times the OTHER chunk's rows
    swap = lambda x: jnp.concatenate([x[:, C:], x[:, :C]], axis=1)  # noqa: E731
    return mm(inv, swap(v * beta)), mm(inv, swap(k * (beta * jnp.exp(gc)))), jnp.where(same & (col <= row), qk, 0.0), gc


def _kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, S_ref, *scratch, C: int, sub: int, operand_dtype):
    *sub_blocks, w_v_ref, w_k_ref, qk_ref, gc_ref = scratch  # one gate a head brings no scratch for sub-blocks: it has none
    f32 = jnp.float32
    K = k_ref.shape[1]
    P = k_ref.shape[0] // (2 * C)
    if operand_dtype is None:
        def mm(a, b, dims=_NN):
            return jax.lax.dot_general(a, b, dims, precision=_HI, preferred_element_type=f32)
    else:
        def mm(a, b, dims=_NN):
            return jax.lax.dot_general(a.astype(operand_dtype), b.astype(operand_dtype), dims, preferred_element_type=f32)

    def exact(a, b):
        return jax.lax.dot_general(a, b, _NN, precision=_HI, preferred_element_type=f32)

    @pl.when(pl.program_id(2) == 0)
    def _from_zero():
        S_ref[...] = jnp.zeros_like(S_ref)

    def mine(heads):  # [positions, N] -> [positions, 1]: this head's column, picked without indexing a lane
        return jnp.sum(jnp.where(jax.lax.broadcasted_iota(jnp.int32, heads.shape, 1) == pl.program_id(1), heads, 0.0), axis=1, keepdims=True)

    betas = mine(beta_ref[...])
    pairs = lambda a: a.reshape(P, 2 * C, a.shape[-1])  # noqa: E731
    solved = _solve(*(pairs(a[...]) for a in (q_ref, k_ref, v_ref)), pairs(g_ref[...] if sub_blocks else mine(g_ref[...])), pairs(betas),
                    *(sub_blocks or (None, None)), C=C, sub=sub, mm=mm, exact=exact)
    for ref, a in zip((w_v_ref, w_k_ref, qk_ref, gc_ref), solved):
        ref[...] = a.reshape(ref.shape)

    def pass_on(c, S):
        """The state through one chunk: two products that wait for each other."""
        at = pl.ds(pl.multiple_of(c * C, C), C)
        gc = gc_ref[at]
        reads = mm(jnp.concatenate([w_k_ref[at], q_ref[at] * jnp.exp(gc)], axis=0), S, _MM)  # [2C, V]: what w_k and the decayed q read of the state
        u = w_v_ref[at] - reads[:C]
        o_ref[at] = reads[C:] + mm(qk_ref[at], jnp.concatenate([u, u], axis=0), _MM)  # the other chunk's columns of these rows are zeros
        last = gc[C - 1:C]
        whole = jnp.exp(jnp.broadcast_to(last, (8, K))).T[:, :1]  # [K, 1]: the chunk's whole decay, a key channel a row
        return S * whole + mm(k_ref[at] * jnp.exp(last - gc), u, _TN)

    S_ref[...] = jax.lax.fori_loop(0, 2 * P, pass_on, S_ref[...], unroll=True)


def delta_rule(q, k, v, g, beta, chunk: int, operand_dtype=None, *, interpret: bool = False):
    """q, k [B,T,G,K], v [B,T,G,R,V], beta [B,T,G,R] and the log-decay g, [B,T,G,R,K] (a gate by key
    channel) or [B,T,G,R] (one gate a head), float32 -> (o [B,T,G,R,V], the state after position T-1
    [B,G,R,K,V]), float32: what ``delta_rule_chunked`` gives. Positions past a true length hold
    ``beta`` = 0 and ``g`` = 0: they write nothing and decay nothing. A trace lists the kernel as
    ``delta_rule_by_channel`` or ``delta_rule_by_head``."""
    B, T, G, K = q.shape
    R, V = v.shape[-2:]
    N, C = G * R, min(chunk, T)
    by_head = g.ndim == beta.ndim
    sub = math.gcd(C, SUB_BLOCK)
    pad = -T % (2 * C)  # whole pairs of chunks
    flat = lambda a: jnp.pad(a.reshape(B, T, -1), ((0, 0), (0, pad), (0, 0)))  # noqa: E731 — a position's heads side by side: no copy but the padding's
    pairs = (T + pad) // (2 * C)
    at_once = math.gcd(pairs, _AT_ONCE)  # pairs of chunks a step of the grid
    step = at_once * 2 * C
    key_head = pl.BlockSpec((None, step, K), lambda b, n, c: (b, c, n // R))
    head = lambda width: pl.BlockSpec((None, step, width), lambda b, n, c: (b, c, n))  # noqa: E731
    heads = pl.BlockSpec((None, step, N), lambda b, n, c: (b, c, 0))  # a number a head: a position's heads side by side, the head's column picked inside
    o, S = pl.pallas_call(
        functools.partial(_kernel, C=C, sub=sub, operand_dtype=operand_dtype),
        grid=(B, N, pairs // at_once),
        in_specs=[key_head, key_head, head(V), heads if by_head else head(K), heads],
        out_specs=[head(V), pl.BlockSpec((None, None, K, V), lambda b, n, c: (b, n, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, T + pad, N * V), jnp.float32), jax.ShapeDtypeStruct((B, N, K, V), jnp.float32)],
        scratch_shapes=([] if by_head else [pltpu.VMEM((step // sub, sub, K), jnp.float32)] * 2)
        + [pltpu.VMEM((step, width), jnp.float32) for width in (V, K, 2 * C, K)],
        interpret=interpret,
        name="delta_rule_by_head" if by_head else "delta_rule_by_channel",
        **({} if interpret else {"compiler_params": pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"))}),
    )(flat(q), flat(k), flat(v), flat(g), flat(beta))
    return o[:, :T].reshape(B, T, G, R, V), S.reshape(B, G, R, K, V)
