"""The blocks of a prefill's grouped expert matmul, where an expert is small and is expected to get
less than two blocks' rows: one kernel whose grid walks the blocks in use (``models/experts._grouped``).

A block of the grouped matmul is ``block`` rows of the layout against ONE expert's matrices, 6-19 MiB
of them. Where an expert gets a block or two of a call the weights' bytes are the floor, and the
plain XLA loop is at half of it or less where the expert is small: each of a block's three products
is a fusion that starts its own fetch and nothing overlaps between two of them, a fixed cost of
about 2.3 us a product (on a v5e 14.7 us a 128-row block of Qwen3-Next's where an expert's 6.3 MB
take 7.7: PERF.md section 6, PR 56). Here the blocks' expert ids and row
offsets are scalar-prefetched and a step of the grid is one block against its expert's matrices,
taken from the arrays STACKED over the expert layers at ``(layer, e)``: the pipeline fetches the
next block's matrices while this block's products run, consecutive blocks of one expert fetch
nothing, and a step past the last block in use points where the step before it did and computes
nothing. The rows lie as ``_grouped`` lays them out (dense by expert, a run from a multiple of 16
rows, a row's output ``block`` rows before its input in the SAME array), so a block's rows come in
and go out by DMAs at a row offset, the next block's read and this block's write under the
products; a block's write starts only when the block before it has landed, because the two overlap
where a run ends inside a block and the later one has to win, as in the loop.

The mathematics and the roundings are ``one_block``'s AS THE CHIP'S COMPILER FUSES IT: operands in
the matrices' dtype, float32 accumulation, the hidden activation rounded to that dtype, the block's
product scaled by the pair's weight in float32 and rounded once; of a gated expert's two products
the one that the compiler leaves standing alone is rounded to the operands' dtype (the gate's under
SiLU, the up product's under ReLU) and the other goes into the activation in float32, and SiLU's
sigmoid is the approximate reciprocal. With these the kernel's rows are the loop's bit for bit at
the Qwen3-Next, Kimi, SmallThinker and GLM shapes on a v5e (PERF.md section 6, PR 56).

``refusal`` says why the kernel does not serve a call (the loop of ``_grouped`` then does), from the
call's shapes and dtype alone. Two rules decide between calls the kernel could run. The ROWS: an
expert that expects two blocks' rows or more fills its blocks, and the loop runs full blocks at the
MXU's pace. The expert's SIZE (``_EXPERT_BYTES``): what the kernel wins is the loop's fixed cost a
product, so its share falls as the expert's own fetch grows (-59% of ``moe.blocks`` at 6.0 MiB an
expert, -45% at 13.5, -18% at 19.0, where a program with the kernel in it also costs more warm
set-up than a cell that prefills chat prompts gets back: PERF.md section 6, PRs 56 and 57). Off the
TPU the backend is always the reason, and a test that wants the kernel asks for it and gets the
same body interpreted.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the largest expert the kernel serves; its matrices go through whole, two of them held at a time. Two readings set it
# (a v5e, PERF.md section 6, PRs 56 and 57). Of ``moe.blocks`` the kernel took -59% at Qwen3-Next's 6.0 MiB an expert, -45% at
# Kimi's 13.5 and -18% at Nemotron's 19.0 (29 us a block against the loop's 32-35, where the bytes take 24): past 16 MiB the
# loop is within a third of the bytes' floor, and the kernel is no longer the loop bit for bit. And two experts in flight then
# take at most a quarter of the chip's 128 MiB of fast memory: above that the compiler lost the room in which it kept the
# 2,048-row layouts of Kimi's and Nemotron's prefills resident
_EXPERT_BYTES = 16 << 20
# lanes of the array that hands a row's weight to the kernel: a row's weight stands in every lane of its row
LANES = 128


def refusal(dtype, H: int, F: int, matrices: int, expects: int, block: int) -> str | None:
    """Why the kernel does NOT serve this call, or None (see the module docstring). ``expects``: the
    rows of the call that an expert is expected to get (pairs over the router's width). What the
    matrices say comes before what the call's rows say, so an expert that is never served says so
    at every number of rows."""
    if jax.default_backend() != "tpu":
        return f"backend {jax.default_backend()!r}: the kernel is compiled for the TPU only"
    dt = jnp.dtype(dtype)
    if dt != jnp.bfloat16:
        return f"{dt.name} experts: the kernel has been compiled for bfloat16 matrices only"
    if H % 128 or F % 16:
        return f"matrices of {F} x {H}: not whole tiles of 16 rows and 128 lanes"
    if matrices * F * H * dt.itemsize > _EXPERT_BYTES:
        return (f"an expert of {matrices * F * H * dt.itemsize / 2**20:.2f} MiB, over {_EXPERT_BYTES >> 20} MiB: "
                "its own fetch is most of a block's time in the loop too")
    if expects >= 2 * block:
        return f"an expert expects {expects} rows, two blocks of {block} or more: full blocks run at the MXU's pace in the loop"
    return None


def _kernel(layer_ref, expert_ref, at_ref, n_ref, rows_ref, scale_ref, *refs, act: str, block: int, align: int):
    del layer_ref, expert_ref
    *mat_refs, out_ref, x_buf, s_buf, y_buf, sems = refs
    b, n = pl.program_id(0), n_ref[0]

    def row(i):
        return pl.multiple_of(at_ref[i] * align, align)

    def copies_in(i, slot):  # block i's inputs lie ``block`` rows behind where its outputs go
        return (pltpu.make_async_copy(rows_ref.at[pl.ds(block + row(i), block)], x_buf.at[slot], sems.at[0, slot]),
                pltpu.make_async_copy(scale_ref.at[pl.ds(row(i), block)], s_buf.at[slot], sems.at[1, slot]))

    def copy_out(i, slot):
        return pltpu.make_async_copy(y_buf.at[slot], out_ref.at[pl.ds(row(i), block)], sems.at[2, slot])

    @pl.when((b == 0) & (n > 0))
    def _first():
        for c in copies_in(0, 0):
            c.start()

    @pl.when(b < n)
    def _one_block():
        slot = b % 2

        @pl.when(b + 1 < n)
        def _next():
            for c in copies_in(b + 1, 1 - slot):
                c.start()

        for c in copies_in(b, slot):
            c.wait()
        x = x_buf[slot]
        nt = (((1,), (1,)), ((), ()))  # a matrix is stored [F, H]
        product = lambda m: jax.lax.dot_general(x, m[...], nt, preferred_element_type=jnp.float32)  # noqa: E731
        rounded = lambda a: a.astype(x.dtype).astype(jnp.float32)  # noqa: E731 — where the loop's values round to the operands' dtype
        if act == "relu2":
            a = jnp.square(jnp.maximum(product(mat_refs[0]), 0.0))
        elif act == "reglu":
            a = jnp.maximum(product(mat_refs[0]), 0.0) * rounded(product(mat_refs[1]))
        else:  # SiLU as the chip's compiler expands it for the dtype the kernel serves: the approximate reciprocal (a test's float32 gets the exact one)
            gate = rounded(product(mat_refs[0]))
            a = gate * pl.reciprocal(1.0 + jnp.exp(-gate), approx=x.dtype != jnp.float32) * product(mat_refs[1])
        y = jnp.dot(a.astype(x.dtype), mat_refs[-1][...], preferred_element_type=jnp.float32)
        y_buf[slot] = (y * s_buf[slot][:, :1]).astype(y_buf.dtype)

        @pl.when(b > 0)
        def _landed():  # the block before may overlap this one's rows, and this one's have to win
            copy_out(b - 1, 1 - slot).wait()

        copy_out(b, slot).start()

        @pl.when(b == n - 1)
        def _last():
            copy_out(b, slot).wait()


def blocks(mats, layer, rows, row_scale, expert_of, at, n_blocks, act: str, block: int, align: int, *, interpret: bool = False):
    """The blocks in use, in ascending order of row: block ``b < n_blocks`` is ``block`` rows of
    ``rows`` [block + R, H] (inputs from row ``block + at[b] * align``, outputs to row ``at[b] * align``)
    against expert ``expert_of[b]`` of layer ``layer`` of ``mats`` (each [L, held, F, H]: gate where the
    form has one, up, down), a row's product times ``row_scale`` [R, LANES] float32 (the row's weight in
    every lane) -> ``rows`` with the outputs written, in place."""
    H = rows.shape[1]
    F = mats[0].shape[2]
    steps = expert_of.shape[0]
    # a step past the last block in use points where the step before it did: nothing is fetched
    expert_of = jnp.where(jnp.arange(steps) < n_blocks, expert_of, expert_of[jnp.maximum(n_blocks - 1, 0)])
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    expert_bytes = len(mats) * F * H * mats[0].dtype.itemsize
    return pl.pallas_call(
        functools.partial(_kernel, act=act, block=block, align=align),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(steps,),
            in_specs=[anywhere, anywhere] + [pl.BlockSpec((None, None, F, H), lambda b, layer_ref, expert_ref, *_: (layer_ref[0], expert_ref[b], 0, 0)) for _ in mats],
            out_specs=anywhere,
            scratch_shapes=[pltpu.VMEM((2, block, H), rows.dtype), pltpu.VMEM((2, block, LANES), jnp.float32), pltpu.VMEM((2, block, H), rows.dtype),
                            pltpu.SemaphoreType.DMA((3, 2))],
        ),
        out_shape=jax.ShapeDtypeStruct(rows.shape, rows.dtype),
        input_output_aliases={4: 0},  # ``rows``, behind the four prefetched scalars
        interpret=interpret,
        name="grouped_experts",
        # a block leans on the reads the block before it started, and an idle step on the one before it
        **({} if interpret else {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=2 * expert_bytes + (24 << 20))}),
    )(jnp.asarray(layer, jnp.int32).reshape(1), expert_of, at, n_blocks.reshape(1), rows, row_scale, *mats)
