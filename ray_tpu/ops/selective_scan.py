"""The selective scan of a Mamba-1 layer over a padded batch of sequences from a zero state:

    h_t[n, d] = exp(dt_t[d] A[n, d]) h_{t-1}[n, d] + dt_t[d] x_t[d] B_t[n]
    y_t[d]    = sum_n C_t[n] h_t[n, d] + D[d] x_t[d],      dt_t = softplus(s_t + b)

with a decay of its own for every (state, channel) pair. There is no matmul form of it: where one
scalar a head decays a whole head (Mamba-2) a chunk's positions meet in a masked product
(``models/nemotron_h.ssd_chunked``); here every pair would need a ``[Q, Q]`` matrix of its own. It is
``N x D`` multiply-adds and as many exponentials a position, on the vector unit, each position
waiting for the one before it.

Written as ``lax.scan`` over positions it is one small program step a position and layer (273,000
a 10,500-token prompt of Jamba2-3B); as an associative scan or a cumulative form it writes the
expansion ``[T, N, D]`` float32 to HBM several times over (4 GB a layer at 12,288 x 16 x 5,120), and
the cumulative form overflows. The kernel keeps the expansion in registers: the grid walks
(sequence, block of ``BLOCK`` positions, the blocks of one sequence in order), the state
``[N, D]`` float32 (channels on the 128 lanes, the states on the sublanes) stays in VMEM from a
sequence's first block to its last and goes out once, ``x``, ``s`` and ``y`` cross HBM once at the
stream's width. Inside a block the channels go ``CHANNELS`` at a time (the state of one such run is
8 registers and rides the loop over the block's positions as a value), positions ``GROUP`` at a
time: a group's ``dt`` is one dense softplus, then its positions one after another, each row
broadcast over the states' sublanes.

``B_t`` and ``C_t`` are 16 numbers a position that have to stand on the SUBLANES, the same in every
lane. The kernel takes them broadcast over one row of 128 lanes (``[T, N, 128]``, 4 KB a position and
operand in bfloat16, made by XLA: a fifth more traffic than the 21 KB a position the scan must move)
because a column read at a lane that a loop counts is not something Mosaic lowers, and a
``[T * N, 1]`` array is padded to 128 lanes in HBM whatever its shape says.

A position at or past a sequence's true length has ``dt = 0``: it decays nothing and writes
nothing, so the state that goes out is the state AT the true length. Blocks that start past it
are skipped and their ``y`` is zeros.

Precision: ``x``, ``s``, ``B``, ``C`` and ``y`` in the stream's dtype (bfloat16 as published, which is
what the published kernel takes them in), everything between in float32, the state float32.

``refusal`` says why the kernel does not serve a call (``scan_xla`` then does: the CPU tests' form);
off the TPU that is always the backend, and a test that wants the kernel asks for it
(``selective_scan(..., interpret=True)``, or swaps ``refusal``) and gets the same body interpreted.
A trace lists the kernel as ``selective_scan``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL = "selective_scan"
LANES = 128
BLOCK = 128  # positions a step of the grid: x, s and y of 5,120 channels are 1.3 MB each in bfloat16, twice buffered
GROUP = 16  # positions whose rows are one tile of a bfloat16 array
CHANNELS = 512  # channels whose state rides the positions' loop in registers: 16 x 512 float32 are 8 of 64


def refusal(dtype, channels: int, states: int, *, mesh=None) -> str | None:
    """Why the kernel does NOT serve this call, or None (see the module docstring)."""
    if jax.default_backend() != "tpu":
        return f"backend {jax.default_backend()!r}: the kernel is compiled for the TPU only"
    if mesh is not None and mesh.size > 1:
        return "a program over a mesh: a Mosaic kernel is not partitioned (PR 21), and no cell runs the scan on one"
    if jnp.dtype(dtype) != jnp.bfloat16:
        return f"{jnp.dtype(dtype).name} operands: the kernel has been compiled for bfloat16 operands only"
    if states != 16:
        return f"{states} states a channel: compiled at 16 (two groups of 8 sublanes)"
    if channels % CHANNELS:
        return f"{channels} channels: compiled for whole runs of {CHANNELS}"
    return None


def counters(layers: int, batch: int, length: int, dtype: str, channels: int, states: int) -> dict:
    """What a description's ``prefill_counters`` says of the scan in ONE prefill program of ``batch`` x
    ``length`` positions (as padded), from its shape alone: ``selscan_positions``, the positions
    scanned over its ``layers``, and ``selscan_kernel_positions``, how many of them the kernel ran
    (all, or none where ``refusal`` speaks)."""
    positions = layers * batch * length
    return {"selscan_positions": positions, "selscan_kernel_positions": 0 if refusal(dtype, channels, states) else positions}


def _softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


def scan_xla(x, s, A, Bm, Cm, D, bias, lengths):
    """The recurrence as plain XLA, one position a step of a ``lax.scan``: x, s [B,T,D], A [D,N]
    float32 (negative), Bm, Cm [B,T,N], D, bias [D] float32, lengths [B] -> (y [B,T,D] in x's dtype,
    the state AT each true length [B,N,D] float32)."""
    f32 = jnp.float32
    T = x.shape[1]
    dt = _softplus(s.astype(f32) + bias)
    dt = jnp.where(jnp.arange(T)[None, :, None] < lengths[:, None, None], dt, 0.0)
    At = A.T.astype(f32)  # [N, D]

    def one_position(h, inp):
        x_t, dt_t, B_t, C_t = inp  # [B,D], [B,D], [B,N], [B,N]
        h = jnp.exp(dt_t[:, None, :] * At) * h + (dt_t * x_t)[:, None, :] * B_t[:, :, None]
        return h, jnp.sum(h * C_t[:, :, None], axis=1)

    xs = tuple(jnp.moveaxis(a.astype(f32), 1, 0) for a in (x, dt, Bm, Cm))
    h, y = jax.lax.scan(one_position, jnp.zeros((x.shape[0], A.shape[1], x.shape[2]), f32), xs)
    return (jnp.moveaxis(y, 0, 1) + D * x.astype(f32)).astype(x.dtype), h


def step(h, x, s, A, Bm, Cm, D, bias):
    """ONE position of the same recurrence for every lane: h [B,N,D] float32, x, s [B,D], Bm, Cm [B,N]
    -> (y [B,D] float32, h)."""
    f32 = jnp.float32
    x, dt = x.astype(f32), _softplus(s.astype(f32) + bias)
    h = jnp.exp(dt[:, None, :] * A.T.astype(f32)) * h + (dt * x)[:, None, :] * Bm.astype(f32)[:, :, None]
    return jnp.sum(h * Cm.astype(f32)[:, :, None], axis=1) + D * x, h


def _kernel(len_ref, x_ref, s_ref, b_ref, c_ref, a_ref, d_ref, bias_ref, y_ref, h_out_ref, h_ref, dt_ref, dtx_ref, *, channels: int):
    f32 = jnp.float32
    block, width = x_ref.shape
    states = a_ref.shape[0]
    start = pl.program_id(1) * block
    length = len_ref[pl.program_id(0)]

    @pl.when(pl.program_id(1) == 0)
    def _from_zero():
        h_ref[...] = jnp.zeros_like(h_ref)

    @pl.when(start >= length)
    def _past_the_sequence():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(start < length)
    def _scan():
        rows = jax.lax.broadcasted_iota(jnp.int32, (GROUP, channels), 0)
        for lo in range(0, width, channels):  # a run of channels: its state in registers over the block's positions
            run = slice(lo, lo + channels)
            A, skip, bias = a_ref[:, run], d_ref[:, run], bias_ref[:, run]

            def group(g, h, run=run, A=A, skip=skip, bias=bias):
                at = pl.ds(pl.multiple_of(g * GROUP, GROUP), GROUP)
                x = x_ref[at, run].astype(f32)
                dt = jnp.where(start + g * GROUP + rows < length, _softplus(s_ref[at, run].astype(f32) + bias), 0.0)
                dt_ref[...], dtx_ref[...] = dt, dt * x
                ys = jnp.zeros((GROUP, channels), f32)
                for i in range(GROUP):  # the positions of a group, each waiting for the one before it
                    t = g * GROUP + i
                    Bt, Ct = (jnp.concatenate([r[t].astype(f32)] * (channels // LANES), axis=1) for r in (b_ref, c_ref))  # [N, channels]: a row of lanes beside itself
                    h = jnp.exp(jnp.broadcast_to(dt_ref[i:i + 1, :], (states, channels)) * A) * h + jnp.broadcast_to(dtx_ref[i:i + 1, :], (states, channels)) * Bt
                    ys = jnp.where(rows == i, jnp.sum(h * Ct, axis=0, keepdims=True), ys)
                y_ref[at, run] = (ys + skip * x).astype(y_ref.dtype)
                return h

            h_ref[:, run] = jax.lax.fori_loop(0, block // GROUP, group, h_ref[:, run])

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _hand_out():
        h_out_ref[...] = h_ref[...]


def scan_kernel(x, s, A, Bm, Cm, D, bias, lengths, *, interpret: bool = False):
    """``scan_xla``'s arguments and results, by the kernel (see the module docstring)."""
    B, T, W = x.shape
    N = A.shape[1]
    block = min(BLOCK, -(-T // GROUP) * GROUP)
    pad = -T % block
    channels = next(c for c in (CHANNELS, 256, LANES, W) if W % c == 0)
    rows = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0)))  # noqa: E731
    over_lanes = lambda a: jnp.broadcast_to(rows(a)[..., None], (B, T + pad, N, LANES))  # noqa: E731 — a position's N numbers on the sublanes, the same in every lane
    stream = pl.BlockSpec((None, block, W), lambda b, t, n: (b, t, 0))
    states = pl.BlockSpec((None, block, N, LANES), lambda b, t, n: (b, t, 0, 0))
    whole = lambda r: pl.BlockSpec((r, W), lambda b, t, n: (0, 0))  # noqa: E731
    f32 = jnp.float32
    y, h = pl.pallas_call(
        functools.partial(_kernel, channels=channels),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, (T + pad) // block),
            in_specs=[stream, stream, states, states, whole(N), whole(1), whole(1)],
            out_specs=[stream, pl.BlockSpec((None, N, W), lambda b, t, n: (b, 0, 0))],
            scratch_shapes=[pltpu.VMEM((N, W), f32), pltpu.VMEM((GROUP, channels), f32), pltpu.VMEM((GROUP, channels), f32)]),
        out_shape=[jax.ShapeDtypeStruct((B, T + pad, W), x.dtype), jax.ShapeDtypeStruct((B, N, W), f32)],
        interpret=interpret, name=KERNEL,
        **({} if interpret else {"compiler_params": pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=48 << 20)}),
    )(lengths.astype(jnp.int32), rows(x), rows(s.astype(x.dtype)), over_lanes(Bm.astype(x.dtype)), over_lanes(Cm.astype(x.dtype)),
      A.T.astype(f32), D.astype(f32)[None], bias.astype(f32)[None])
    return y[:, :T], h


def selective_scan(x, s, A, Bm, Cm, D, bias, lengths, *, mesh=None, interpret: bool = False):
    """x (the convolution's output), s (the step before its bias and softplus) [B,T,D], A [D,N] float32,
    Bm, Cm [B,T,N], D, bias [D] float32, lengths [B] -> (y [B,T,D] in x's dtype, the state AT each true
    length [B,N,D] float32): by the kernel where ``refusal`` lets it (or a test asks), else by XLA."""
    if interpret or refusal(x.dtype, x.shape[2], A.shape[1], mesh=mesh) is None:
        return scan_kernel(x, s, A, Bm, Cm, D, bias, lengths, interpret=interpret or jax.default_backend() != "tpu")
    return scan_xla(x, s, A, Bm, Cm, D, bias, lengths)
