"""The routed experts of a decode step, read where they lie: one kernel whose grid
walks the held experts that a bound lane chose (``models/experts.experts_step``).

A decode step's rows are its lanes, a handful against experts of 2-10 MB a
matrix: the step's cost is reading weights. The dense form reads every held
expert; a plain XLA loop over the experts hit reads only those, but every
product is a fusion that starts its own DMA and nothing overlaps between two
of them (on a v5e 32 us an expert of Nemotron's where the bytes take 24, and
slower than the dense form once five sixths of the held experts are hit: PR 37).
Here the hit experts' ids, compacted to the front, are scalar-prefetched and a
step of the grid is one tile of ``F`` rows of one hit expert's matrices, taken
from the arrays STACKED over the expert layers at ``(layer, ids[j])``: the
pipeline fetches the next expert's tile while this one's products run, so the
cost is the bytes hit at the bandwidth the dense form reaches, and never more
than the dense form's. A step past the last hit expert points at the tile the
step before it had (nothing is fetched) and computes nothing.

``refusal`` says why the kernel does not serve a call (the XLA loop of
``experts_step`` then does); off the TPU that is always the backend, and a test
that wants the kernel asks for it and gets the same body interpreted.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# one buffer of the matrices' tiles; the pipeline holds two. An expert of the three hybrid cells
# (20.0, 6.3 and 18.9 MB) goes through whole
_TILE_BYTES = 24 << 20


def tile_rows(F: int, H: int, matrices: int, itemsize: int) -> int:
    """Rows of ``F`` in one tile: all of them where an expert's matrices fit ``_TILE_BYTES``, else
    the largest divisor of ``F`` in whole 16-row tiles that does (0: there is none)."""
    fits = _TILE_BYTES // (matrices * H * itemsize)
    return F if F <= fits else max([t for t in range(16, fits + 1, 16) if F % t == 0], default=0)


def refusal(dtype, H: int, F: int, matrices: int) -> str | None:
    """Why the kernel does NOT serve this call, or None (see the module docstring)."""
    if jax.default_backend() != "tpu":
        return f"backend {jax.default_backend()!r}: the kernel is compiled for the TPU only"
    dt = jnp.dtype(dtype)
    if dt != jnp.bfloat16:
        return f"{dt.name} experts: the kernel has been compiled for bfloat16 matrices only"
    if H % 128:
        return f"a residual width of {H}: not whole 128-lane tiles"
    if not tile_rows(F, H, matrices, dt.itemsize):
        return f"no tile of whole 16-row blocks divides an expert's {F} rows and fits {_TILE_BYTES >> 20} MiB"
    return None


def _kernel(layer_ref, ids_ref, n_ref, x_ref, comb_ref, *refs, act: str):
    del layer_ref
    *mat_refs, o_ref = refs
    j = pl.program_id(0)

    @pl.when((j == 0) & (pl.program_id(1) == 0))
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(j < n_ref[0])
    def _one_tile():
        x = x_ref[...]
        nt = (((1,), (1,)), ((), ()))  # a matrix is stored [F, H]
        rounded = lambda a: a.astype(x.dtype).astype(jnp.float32)  # noqa: E731 — where the dense form's products round to the operands' dtype
        up = rounded(jax.lax.dot_general(x, mat_refs[-2][...], nt, preferred_element_type=jnp.float32))
        if act == "relu2":
            a = rounded(jnp.square(jnp.maximum(up, 0.0)))
        else:
            gate = rounded(jax.lax.dot_general(x, mat_refs[0][...], nt, preferred_element_type=jnp.float32))
            a = rounded((jnp.maximum(gate, 0.0) if act == "reglu" else rounded(gate * jax.nn.sigmoid(gate))) * up)
        comb = comb_ref[...]  # [B, held]: this expert's column, picked without indexing a lane
        col = jnp.sum(jnp.where(jax.lax.broadcasted_iota(jnp.int32, comb.shape, 1) == ids_ref[j], comb, 0.0), axis=1, keepdims=True)
        o_ref[...] += jnp.dot((a * col).astype(x.dtype), mat_refs[-1][...], preferred_element_type=jnp.float32)


def hit_experts(mats, layer, x, comb, ids, n_hit, act: str, *, interpret: bool = False):
    """x [B,H], in the matrices' dtype, against the experts ``ids[:n_hit]`` of layer ``layer`` of
    ``mats`` (each [L, held, F, H]: gate where the form has one, up, down), times ``comb``
    [B, held] float32 -> [B,H] float32: ``sum_e comb[:, e] * down_e(act(up_e x))`` over them."""
    B, H = x.shape
    held, F = mats[0].shape[1:3]
    tf = tile_rows(F, H, len(mats), mats[0].dtype.itemsize)
    nf = F // tf
    pad = -B % 16  # whole tiles of rows; a padded row is zeros, chose nothing, and is cut off
    x, comb = jnp.pad(x, ((0, pad), (0, 0))), jnp.pad(comb, ((0, pad), (0, 0)))
    # a step past the last hit expert points where the step before it did: nothing is fetched
    ids = jnp.where(jnp.arange(held) < n_hit, ids, ids[jnp.maximum(n_hit - 1, 0)])

    def tile_of(j, f, layer_ref, ids_ref, n_ref):
        return layer_ref[0], ids_ref[j], jnp.where(j < n_ref[0], f, nf - 1), 0

    whole = lambda j, f, *_: (0, 0)  # noqa: E731
    out = pl.pallas_call(
        functools.partial(_kernel, act=act),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(held, nf),
            in_specs=[pl.BlockSpec((B + pad, H), whole), pl.BlockSpec((B + pad, held), whole)]
            + [pl.BlockSpec((None, None, tf, H), tile_of) for _ in mats],
            out_specs=pl.BlockSpec((B + pad, H), whole),
        ),
        out_shape=jax.ShapeDtypeStruct((B + pad, H), jnp.float32),
        interpret=interpret,
        name="step_experts",
        # the output is one block that every step adds to, and an idle step leans on the one before it
        **({} if interpret else {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=2 * _TILE_BYTES + (16 << 20))}),
    )(jnp.asarray(layer, jnp.int32).reshape(1), ids, n_hit.reshape(1), x, comb, *mats)
    return out[:B]
