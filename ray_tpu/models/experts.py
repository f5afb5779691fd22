"""One routed-expert layer that every hybrid description configures (ROADMAP C1).

What varies between published expert layers is a handful of choices, and a
description states them as an ``ExpertLayer`` (its ``expert_layer`` property):
how the router scores (``sigmoid`` or ``softmax`` over ALL published experts),
whether a correction bias joins the scores for the choice of the top k, whether
the chosen scores are normalised and by what they are scaled, the expert's form
(``relu2``: two matrices, ``W_down relu(W_up x)^2``; ``swiglu``: three,
``W_down (SiLU(W_gate x) * W_up x)``; ``reglu``: three, ``W_down (relu(W_gate x) * W_up x)``),
whether there is a shared expert at all and whether its output is gated by
``sigmoid(x . w_sg)``. A layer whose routing is decided elsewhere (by a router that reads
another sub-block's input) is handed it (``routing=`` of ``moe_seq`` / ``moe_step``) and holds
no router. Everything else is one code path:

- ``route``: float32 on whatever the norm hands it, whatever the stream's dtype.
- ``experts_dense``: every held expert over every row: the form that has a
  backward pass (training, the plain forward), and what the tests hold the two
  others to. Its cost is reading every held expert's weights.
- ``experts_grouped``: a grouped matmul over the (row, expert) pairs routed
  HERE, for prefill: what it places, gathers and multiplies follows the pairs
  held here, not the pairs the router made; no pair is dropped whatever one
  expert's load. How its blocks run follows what the call's shapes say
  (``blocks_plan``): the rows an expert is EXPECTED to get, ``N k / num_experts``,
  and the expert's SIZE. Tall blocks of 256 rows where the call has
  ``TALL_FROM`` pairs and an expert expects 256 rows or more, blocks of 128
  where it expects fewer. The plain XLA loop fetches an expert's matrices anew
  for every block and exposes the fetch; on a TPU one kernel runs the blocks
  instead (``ops/grouped_experts.py``: an expert's matrices come in once a run
  of its blocks, the next expert's under this run's products) where an expert
  expects two blocks' rows or more, whatever its size (FULL blocks: the
  products are the floor, and the loop is at half of it), and where it expects
  less than two short blocks' rows and is of 16 MiB or less (LOW fill: the
  bytes are the floor, and the loop adds a fixed cost a product to them); the
  loop everywhere else: tall blocks of which an expert expects one and a few
  rows of a second, and a LARGE expert's low-fill calls (an expert of 54 MiB
  that expects 164-192 rows of a 12,288-row call, ``models/afmoe.py``'s: the
  loop fetches its matrices once a block, 2.3 times a call by the cell's
  ``moe_fetches_per_expert``, where the experts' bytes once are the floor).
- ``experts_step``: a decode step's form: the held experts that a BOUND lane
  chose, one after another, every lane against one expert's matrices read
  straight out of the stacked weights (a kernel on a TPU, ``ops/step_experts.py``;
  a loop elsewhere). Its cost is reading the experts hit, and no others.
- ``moe_seq`` / ``moe_step``: the layer over a padded sequence and for one token
  a lane, each with the routing counters the flight log carries.

In a profile the layer's parts stand under sub-scopes of the layer's own (``moe``):
``moe.route`` the router and its top k, ``moe.place`` laying the pairs out by expert
(in prefill its three parts by name: ``moe.place.count`` the pairs held here compacted
and each given its row, ``moe.place.into`` their inputs gathered into the layout,
``moe.place.out`` their outputs gathered out of it and summed by token), ``moe.blocks``
the experts' matmuls (the grouped matmul's loop, a step's hit experts), ``moe.shared``
the shared expert.

The layer is told which experts this chip holds (``expert_start``,
``local_experts``): the router scores all published experts, this chip computes
what its own give for the tokens routed to them and adds the shared expert; a
token whose choice lives on another chip gets nothing from that choice here
(expert parallelism without its exchange). Every expert matrix is stored
[F, H], the residual width last (``models/nemotron_h._shapes`` says why).

Weights of one layer, by name: ``router`` [H, E], ``router_bias`` [E] (where
``bias``), ``w_up`` / ``w_down`` (and ``w_gate`` for ``swiglu``) [El, F, H],
``shared_up`` [H, Fs] (and ``shared_gate``), ``shared_down`` [Fs, H],
``shared_sg`` [H] (where ``shared_gated``).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import grouped_experts, step_experts
from ray_tpu.util.profiling import scope, scoped

# rows of one block of the grouped matmul. A call takes blocks twice as tall where it has ``TALL_FROM`` pairs or more
# AND an expert is expected to get a tall block's rows of them (``blocks_plan``): the pairs spread over every PUBLISHED
# expert, so a chip that holds a quarter of them fills a tall block a quarter (Qwen3-Next's 4,096 rows x 10 choices
# over 512 experts are 80 rows an expert; a 256-row block took 7.5 us a product where a 128-row block takes 4.9, and
# the layer alone 4.89 -> 4.08 ms at 4,096 rows, Kimi's 3.95 -> 3.58: PERF.md section 6, PR 56). The rule only takes
# tall blocks away: a call that was short stays short
BLOCK, TALL_FROM = 128, 32768
# the grouped matmul's buffers grow with the rows handed to it (a row of the residual width for
# every pair that COULD be held here): beyond this many rows a sequence batch goes through in slabs
SLAB_ROWS = 8192
# the placement walks the pairs HELD here: ``SLAB`` of them at a time where it ranks them and gathers
# their inputs, and a tile of ``TILE`` tokens at a time where it gathers and sums their outputs, in
# trips whose rows ``out_plan`` reads off the layer's description
SLAB, TILE = 1024, 128
# the most rows a trip of the gather out takes (``out_plan``): on a v5e a trip, the loop's own turn counted, is 9 us and 24 ns an entry at rows
# 2,048 wide (16 and 37 at 2,560, 26 and 34 at 3,072), filled or not, up to 896 entries, and one of 1,024 reads 8-9 us over that line at all three
TRIP_MOST = 768
# an expert's run starts at a multiple of this many rows of the layout, a whole tile of a bfloat16
# array: a block's matmuls read and write 20% slower at an offset the compiler cannot see aligned
ALIGN = 16


@dataclass(frozen=True)
class ExpertLayer:
    num_experts: int  # the router's width: every published expert
    top_k: int
    expert_start: int = 0
    local_experts: int | None = None  # None: all of them
    score: str = "softmax"  # softmax | sigmoid
    bias: bool = False  # a correction bias joins the scores for the CHOICE (never the weights)
    norm_topk: bool = True
    scale: float = 1.0
    act: str = "swiglu"  # swiglu | reglu (w_gate, w_up, w_down) | relu2 (w_up, w_down)
    shared_gated: bool = False
    shared: bool = True  # False: routed experts alone, no expert that every token passes
    norm_eps: float = 1e-20  # what the sum of the chosen scores is raised by where they are normalised (as published: 1e-20, or 1e-6)

    def __post_init__(self):
        if self.score not in ("softmax", "sigmoid") or self.act not in ("swiglu", "reglu", "relu2"):
            raise ValueError(f"an expert layer scores by softmax or sigmoid and acts by swiglu, reglu or relu2, not {self.score}/{self.act}")
        if not 0 <= self.expert_start <= self.expert_start + self.held <= self.num_experts:
            raise ValueError("the experts held must lie inside the router's width")

    @property
    def held(self) -> int:
        return self.num_experts if self.local_experts is None else self.local_experts

    @property
    def matrices(self) -> tuple:
        return ("w_up", "w_down") if self.act == "relu2" else ("w_gate", "w_up", "w_down")


def _relu2(h):
    return jnp.square(jax.nn.relu(h))


def _gate(s: ExpertLayer):
    """What a gated expert applies to its gate: ReLU (``reglu``) or SiLU (``swiglu``)."""
    return jax.nn.relu if s.act == "reglu" else jax.nn.silu


def _hidden(s: ExpertLayer, x, up, gate, spec: str):
    """An expert's hidden activation from its input: ``spec`` contracts x with a matrix stored [.., F, H]."""
    if s.act == "relu2":
        return _relu2(jnp.einsum(spec, x, up))
    return _gate(s)(jnp.einsum(spec, x, gate)) * jnp.einsum(spec, x, up)


def route(w, x, c):
    """The published router, in float32 whatever the stream's dtype: scores over ALL experts,
    the top k (of score + correction bias where the layer has one), their own scores as
    weights, normalised and scaled. x [N,H] -> (expert ids [N,k] int32, weights [N,k] f32)."""
    s = c.expert_layer
    logits = jnp.dot(x.astype(jnp.float32), w["router"].astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits) if s.score == "sigmoid" else jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(scores + w["router_bias"] if s.bias else scores, s.top_k)
    wt = jnp.take_along_axis(scores, idx, axis=-1)
    if s.norm_topk:
        wt = wt / (jnp.sum(wt, axis=-1, keepdims=True) + s.norm_eps)
    return idx.astype(jnp.int32), wt * s.scale if s.scale != 1.0 else wt


def shared_expert(w, x, s: ExpertLayer):
    """The expert every token passes, of the routed experts' form; gated per token where published."""
    h = _relu2(jnp.dot(x, w["shared_up"])) if s.act == "relu2" else _gate(s)(jnp.dot(x, w["shared_gate"])) * jnp.dot(x, w["shared_up"])
    y = jnp.dot(h, w["shared_down"])
    if s.shared_gated:
        g = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32), w["shared_sg"].astype(jnp.float32), precision=jax.lax.Precision.HIGHEST))
        y = (y * g[..., None]).astype(y.dtype)
    return y


def experts_dense(w, x, idx, wt, c):
    """Every held expert over every row, whatever the rows chose: the form with a backward pass.
    A choice held elsewhere has no column here and adds nothing."""
    s = c.expert_layer
    comb = jnp.einsum("nke,nk->en", jax.nn.one_hot(idx - s.expert_start, s.held, dtype=jnp.float32), wt)
    a = _hidden(s, x, w["w_up"], w.get("w_gate"), "nh,efh->enf")
    return jnp.einsum("enf,efh->nh", (a * comb[..., None]).astype(x.dtype), w["w_down"])


def _count_before(flags):
    """How many of a 1-D array of flags are set before each place, and in all: rows of 128 against
    a strict triangle on the MXU (0/1 operands, float32 sums: exact) and the rows' totals running.
    No ``reduce_window`` over the whole length, and no sort."""
    n = flags.shape[0]
    f = jnp.pad(flags, (0, -n % 128)).reshape(-1, 128).astype(jnp.bfloat16)
    within = jnp.dot(f, jnp.triu(jnp.ones((128, 128), jnp.bfloat16), 1), preferred_element_type=jnp.float32)
    totals = jnp.sum(f, axis=1, dtype=jnp.float32)
    before = within + (jnp.cumsum(totals) - totals)[:, None]
    return before.reshape(-1)[:n].astype(jnp.int32), jnp.sum(totals).astype(jnp.int32)


def blocks_plan(s: ExpertLayer, N: int, mats) -> tuple:
    """How a call of ``_grouped`` over N rows runs its blocks, from its shapes alone: (rows of a block,
    whether the kernel runs them). An expert is expected to get ``N k / num_experts`` rows (the router's
    width: with expert parallelism the pairs split over all published experts, and each expert held
    here expects that many). Tall blocks only where the call has ``TALL_FROM`` pairs AND an expert
    expects a tall block's rows. The kernel (``ops/grouped_experts.py``; its ``refusal`` reads the rows,
    the block's height, the expert's bytes, the dtype and the tiles) in two regimes. FULL blocks, an
    expert that expects two blocks' rows or more of the call's own height, at any expert's size: the
    kernel holds an expert's matrices for its whole run of blocks where the loop fetches them for each,
    -46% to -56% of ``moe.blocks`` and -27% to -37% of the layer at the 512-1,152 rows an expert of the
    GLM, Keye, LFM2 and SmallThinker cells' calls (8,192 and 12,288 rows; one layer alone on a v5e,
    ``scripts/experts_layer_bench.py``, PERF.md section 6, PR 63). LOW fill, less than two short blocks'
    rows an expert of 16 MiB or less (PR 57). The loop keeps a large expert's low-fill calls and the
    calls between the two regimes: tall blocks of which an expert expects one and less than a second."""
    M = N * s.top_k
    tall = M >= max(TALL_FROM, 2 * BLOCK * s.num_experts)
    block = 2 * BLOCK if tall else BLOCK
    F, H = mats[0].shape[2:]
    return block, grouped_experts.refusal(mats[0].dtype, H, F, len(mats), M // s.num_experts, block, tall) is None


def out_plan(s: ExpertLayer) -> int:
    """Rows of one trip of the gather out of the blocks (``moe.place.out``), from the layer's description alone:
    the pairs that a tile of ``TILE`` tokens is EXPECTED to hold here, so that a tile is one trip and a trip has
    no entry to spare. Where every published expert is held a tile holds ``TILE k`` exactly; where a share is
    held, that share of it and a third more (the pairs are the router's to spread: a tile that holds more takes
    a second trip, the trips' count is data). More than ``TRIP_MOST`` go in equal trips. In whole ``TILE``s up to
    one, in whole pairs of them above. One layer alone on a v5e (``scripts/experts_layer_bench.py --trips``;
    PERF.md section 6, PR 65, has the table): an entry of a trip costs what a row costs whether a pair fills it
    or not (24-37 ns), a trip costs 9-26 us beside its entries, more the wider the rows, and trips of 384, 640
    and 896 entries read 1.4-3.9 us over the line through 128, 256, 512 and 768. So at six choices a token one
    trip of 768 and not two of 512, the second half empty (SmallThinker: ``moe.place.out`` -33%, the layer
    -14%); at eight, two of 512 as before (Keye: one of 1,024 reads the layer the same to half a percent); 128
    and not 512 where a chip holds an eighth of the experts at four choices (Trinity: -36%, the layer -11%);
    and the 512 they had for GLM and LFM2 (512 exactly), Qwen3-Next (320 expected), Nemotron (384) and Kimi
    (256: trips of 256 / 384 / 512 read the layer within 1.5% of each other there). The outputs are the same
    bit for bit at every trip size: a token's k products are added in one float32 pass either way."""
    pairs = TILE * s.top_k
    if s.held < s.num_experts:
        expected = pairs * s.held // s.num_experts
        pairs = min(pairs, expected + expected // 3)
    trips = -(-pairs // TRIP_MOST)
    rows = -(-pairs // trips)
    unit = TILE if rows <= TILE else 2 * TILE
    return -(-rows // unit) * unit


def _grouped(stacked, layer, x, idx, wt, valid, c):
    """``experts_grouped`` and what it did: -> (out [N,H], pairs at each held expert [El] int32,
    rows of the blocks in use, int32)."""
    s = c.expert_layer
    N, k = idx.shape
    M, El, H = N * k, s.held, x.shape[-1]
    mats = [stacked[n] for n in s.matrices]
    block, kernel = blocks_plan(s, N, mats)
    trip = out_plan(s)
    slabs, tiles = -(-M // SLAB), -(-N // TILE)
    n_pairs = slabs * SLAB + trip  # every pair could be held here; a trip may hang over the end
    n_rows = -(-(M + El * ALIGN) // SLAB) * SLAB + block  # and every run start on a whole tile of the layout; a slab or a block may hang over
    i32 = jnp.int32
    with scope("moe.place"), scope("moe.place.count"):
        local = (idx - s.expert_start).reshape(-1)
        mine = (local >= 0) & (local < El) & jnp.repeat(valid, k)
        scale = jnp.where(mine, wt.reshape(-1), 0.0)
        ids = jnp.arange(El, dtype=i32)
        sizes = jnp.sum(mine[:, None] & (local[:, None] == ids[None, :]), axis=0, dtype=i32)
        blocks_of = (sizes + block - 1) // block
        last_block = jnp.cumsum(blocks_of)  # one past each expert's last block
        # the runs lie DENSE by expert, each from a multiple of ``ALIGN`` rows: no padding to a whole block
        tiles_of = (sizes + ALIGN - 1) // ALIGN
        start = jnp.cumsum(tiles_of) - tiles_of  # in units of ``ALIGN`` rows, so that the compiler sees every offset aligned
        # the pairs held here, compacted in token order (a running count, no sort): what follows
        # walks these, ``held`` of them, and never the M that the router made
        before, held = _count_before(mine)
        by_token = jnp.full((n_pairs,), M, i32).at[jnp.where(mine, before, n_pairs)].set(jnp.arange(M, dtype=i32), mode="drop")
        tri = jnp.tril(jnp.ones((SLAB, SLAB), jnp.bfloat16))
        first_row = (start * ALIGN).astype(jnp.float32)

        def place_slab(j, carry):
            # a slab of held pairs: each one's row is its expert's first row plus its rank among that
            # expert's pairs (those of earlier slabs, ``seen``, and a triangle against this slab's one-hot)
            seen, row_of, pair_at = carry
            pair = jax.lax.dynamic_slice_in_dim(by_token, j * SLAB, SLAB)
            ok = pair < M
            hot = ok[:, None] & (local[jnp.minimum(pair, M - 1)][:, None] == ids[None, :])
            upto = jnp.dot(tri, hot.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
            row = jnp.sum(jnp.where(hot, upto + (first_row + seen)[None, :], 0.0), axis=1).astype(i32) - 1
            row = jnp.where(ok, row, n_rows)
            return seen + upto[-1], jax.lax.dynamic_update_slice_in_dim(row_of, row, j * SLAB, 0), pair_at.at[row].set(pair, mode="drop")

        _, row_of, pair_at = jax.lax.fori_loop(0, (held + SLAB - 1) // SLAB, place_slab, (
            jnp.zeros((El,), jnp.float32), jnp.zeros((n_pairs,), i32), jnp.full((n_rows,), M, i32)))
        # a tile of tokens owns a stretch of ``by_token``; it is summed ``trip`` pairs a trip
        edges = jnp.append(before, held)[np.minimum(np.arange(tiles + 1) * TILE * k, M)]
        trips_of = (edges[1:] - edges[:-1] + trip - 1) // trip
        last_trip = jnp.cumsum(trips_of)

    def fill_slab(j, rows):
        pair = jax.lax.dynamic_slice_in_dim(pair_at, j * SLAB, SLAB)
        return jax.lax.dynamic_update_slice_in_dim(rows, jnp.take(x, jnp.minimum(pair, M - 1) // k, axis=0), block + j * SLAB, 0)

    def fill_slab_and_weights(j, filled):
        # for the kernel: each row's weight beside it, in every lane of a row of its own (a block's come in by one DMA). What this costs
        # over ``fill_slab`` is the gather of the weights, 8 ns a scalar, and not the 512 bytes a row (PERF.md section 6, PR 65)
        weight = scale[jnp.minimum(jax.lax.dynamic_slice_in_dim(pair_at, j * SLAB, SLAB), M - 1)]
        return fill_slab(j, filled[0]), jax.lax.dynamic_update_slice_in_dim(filled[1], jnp.broadcast_to(weight[:, None], (SLAB, grouped_experts.LANES)), j * SLAB, 0)

    with scope("moe.place"), scope("moe.place.into"):  # the held pairs' rows of x, gathered into the layout a slab at a time
        slabs_in_use, rows = ((start[-1] + tiles_of[-1]) * ALIGN + SLAB - 1) // SLAB, jnp.zeros((block + n_rows, H), x.dtype)
        if kernel:
            rows, row_scale = jax.lax.fori_loop(0, slabs_in_use, fill_slab_and_weights, (rows, jnp.zeros((n_rows, grouped_experts.LANES), jnp.float32)))
        else:
            rows = jax.lax.fori_loop(0, slabs_in_use, fill_slab, rows)

    def one_block(b, rows):
        # ONE array holds a row's input ``block`` rows behind where its output goes, and the blocks
        # run in ascending order of row: what a block overwrites, its overhang past the end of its run
        # included, are inputs already read, and what it leaves past its run the next run's block rewrites
        e = jnp.sum(last_block <= b).astype(i32)
        done = b - (last_block[e] - blocks_of[e])  # whole blocks of this run before this one
        at = (start[e] + done * (block // ALIGN)).astype(jnp.uint32) * jnp.uint32(ALIGN)  # unsigned: no wrap of a negative index hides the factor
        xb = jax.lax.dynamic_slice_in_dim(rows, block + at, block)
        *gate, up, down = (jax.lax.dynamic_slice(a, (layer, e, 0, 0), (1, 1) + a.shape[2:])[0, 0] for a in mats)
        yb = jnp.dot(_hidden(s, xb, up, gate[0] if gate else None, "bh,fh->bf"), down)
        pair = jnp.minimum(jax.lax.dynamic_slice_in_dim(pair_at, at, block), M - 1)
        yb = (yb * scale[pair][:, None]).astype(rows.dtype)
        return jax.lax.dynamic_update_slice_in_dim(rows, yb, at, 0)

    with scope("moe.blocks"):
        if kernel:
            # every block's expert and offset at once (``one_block``'s own lines): the kernel's grid has a step for
            # every block the call COULD need; off the TPU only a test gets here (it swaps ``refusal``), and runs the same body interpreted
            b = jnp.arange(-(-M // block) + El, dtype=i32)
            e = jnp.minimum(jnp.sum(last_block[None, :] <= b[:, None], axis=1).astype(i32), El - 1)
            at = start[e] + (b - (last_block[e] - blocks_of[e])) * (block // ALIGN)
            rows = grouped_experts.blocks(mats, layer, rows, row_scale, e, at, last_block[-1], s.act, block, ALIGN, interpret=jax.default_backend() != "tpu")
        else:
            rows = jax.lax.fori_loop(0, last_block[-1], one_block, rows)

    def sum_rows(t, out):
        # ``trip`` of a tile's pairs (all of them, where the tile holds what ``out_plan`` expected): their rows gathered, and
        # added to their tokens by a 0/1 [TILE, trip] matrix on the MXU, which sums in float32 as a reduction over k would
        i = jnp.sum(last_trip <= t).astype(i32)
        q = edges[i] + (t - (last_trip[i] - trips_of[i])) * trip
        ok = jnp.arange(trip, dtype=i32) < edges[i + 1] - q
        yb = jnp.take(rows, jnp.where(ok, jax.lax.dynamic_slice_in_dim(row_of, q, trip), 0), axis=0)
        token = jax.lax.dynamic_slice_in_dim(by_token, q, trip) // k - i * TILE
        pick = ok[None, :] & (token[None, :] == jnp.arange(TILE, dtype=i32)[:, None])
        add = jnp.dot(pick.astype(yb.dtype), jnp.where(ok[:, None], yb, 0), preferred_element_type=jnp.float32, precision=jax.lax.Precision.HIGHEST)
        return jax.lax.dynamic_update_slice_in_dim(out, jax.lax.dynamic_slice_in_dim(out, i * TILE, TILE) + add, i * TILE, 0)

    with scope("moe.place"), scope("moe.place.out"):  # the gather out of the blocks and the sum over a token's pairs
        out = jax.lax.fori_loop(0, last_trip[-1], sum_rows, jnp.zeros((tiles * TILE, H), jnp.float32))[:N].astype(x.dtype)
    return out, sizes, last_block[-1] * block


def experts_grouped(stacked, layer, x, idx, wt, valid, c):
    """A grouped matmul over the (row, expert) pairs routed HERE, whose number is
    data: every loop below turns as often as those pairs need, none as often as the router's
    N x k. The held pairs are compacted in token order by a running count (no sort), ``SLAB`` of
    them at a time get their rows (an expert's first row plus the pair's rank among that expert's
    pairs: a triangle against the slab's one-hot on the MXU), and the layout is DENSE by expert: no
    padding between the runs but to a multiple of ``ALIGN`` rows. The pairs' inputs are gathered
    into it a slab at a time; one loop over the blocks IN USE takes ``block`` rows at its run's
    offset against its expert's matrices, read straight from the stacked weights, and writes the
    outputs into the SAME array ``block`` rows before the inputs (``one_block`` says why that is safe;
    where an expert expects two blocks' rows or more, or a small expert less than two short blocks', the
    kernel of ``ops/grouped_experts.py`` walks the same blocks in the same order with ``one_block``'s
    mathematics, ``blocks_plan``);
    then a tile of ``TILE`` tokens gathers its pairs' rows, in the trips ``out_plan`` sizes (one,
    where the tile holds the pairs expected of it and they are 768 or fewer), and a 0/1 matrix sums
    them by token in float32. A pair's product is scaled by its weight in float32 and rounded
    once, a token's pairs are summed in float32 and rounded once; no pair is dropped, whatever the
    load on one expert. ``valid`` [N] keeps padding out of every group. The loops' lengths are
    data, so this path has no backward pass (training uses ``experts_dense``).
    ``stacked[name]`` are the arrays STACKED over the expert layers, and the loop reads expert e
    of layer ``layer`` from them: a layer's experts, sliced out first, would be copied once a
    layer to become the loop's operand."""
    return _grouped(stacked, layer, x, idx, wt, valid, c)[0]


def _call_rows(N: int) -> int:
    """Rows of one call of ``_grouped`` for a batch of N: a slab's, where the batch goes through in slabs."""
    return SLAB_ROWS if N > SLAB_ROWS and N % SLAB_ROWS == 0 else N


def seq_counters(c, group, N: int) -> int:
    """How many counters ``moe_seq`` hands back for a batch of N rows through the stacked weights ``group``:
    three, and a fourth in the programs whose blocks the kernel runs (the others keep the text they had)."""
    s = c.expert_layer
    return 3 + blocks_plan(s, _call_rows(N), [group[n] for n in s.matrices])[1]


def moe_seq(w, xn, lengths, c, stacked=None, routing=None):
    """xn [B,T,H] -> ([B,T,H], counters): routed experts held here plus the shared expert where
    the layer has one. ``routing`` = (expert ids [B,T,k] int32, weights [B,T,k] f32) made elsewhere
    takes the place of this layer's own router.
    ``stacked`` = (the expert layers' stacked weights, this layer's index): the serving path's
    grouped matmul, in slabs of ``SLAB_ROWS`` rows where the batch is larger; without it every
    held expert over every token, which has a backward pass. The counters, float32 [3], are of
    the grouped matmul (zeros without it): held experts that got a pair, pairs served here, rows
    of the blocks in use; and where the kernel runs the blocks a fourth, the rows of the blocks
    it ran (``seq_counters`` says how many there are, from the shapes)."""
    s = c.expert_layer
    B, T, H = xn.shape
    N = B * T
    if routing is None:
        idx, wt = scoped("moe.route", route)(w, xn.reshape(N, H), c)  # on the norm as it comes
    else:
        idx, wt = (a.reshape(N, s.top_k) for a in routing)
    x = xn.reshape(N, H).astype(w["w_up"].dtype)
    valid = (jnp.arange(T)[None, :] < lengths[:, None]).reshape(-1)
    if stacked is None:
        routed = scoped("moe.blocks", experts_dense)(w, x, idx, jnp.where(valid[:, None], wt, 0.0), c)
        counters = jnp.zeros((3,), jnp.float32)
    else:
        if _call_rows(N) < N:
            slabs = jax.tree.map(lambda a: a.reshape((N // SLAB_ROWS, SLAB_ROWS) + a.shape[1:]), (x, idx, wt, valid))
            routed, sizes, rows = jax.lax.map(lambda a: _grouped(*stacked, *a, c), slabs)
            routed, sizes, rows = routed.reshape(N, H), jnp.sum(sizes, axis=0), jnp.sum(rows)
        else:
            routed, sizes, rows = _grouped(*stacked, x, idx, wt, valid, c)
        # the blocks in use are the blocks the kernel ran, in the programs where it runs them
        counters = jnp.stack([jnp.sum(sizes > 0), jnp.sum(sizes), rows, rows][:seq_counters(c, stacked[0], N)]).astype(jnp.float32)
    if s.shared:
        routed = routed + scoped("moe.shared", shared_expert)(w, x, s)
    return routed.reshape(B, T, H), counters


def experts_step(stacked, layer, x, idx, wt, active, c):
    """A decode step's routed experts: x [B,H], one row a lane -> (out [B,H], held experts read,
    int32). ``experts_dense``'s mathematics (the same operands, float32 accumulation, every
    chosen expert held here contributes) over the held experts that a lane of ``active`` chose,
    and no others: an expert's matrices are read where they lie in the arrays STACKED over the
    expert layers, at ``(layer, e)`` (``experts_grouped`` says why not from a layer sliced out),
    all B lanes go against them, and what a lane did not choose is multiplied by a combine weight
    of zero. A lane that is not bound pulls no expert in, whatever garbage it routes. On a TPU one
    kernel walks the hit experts' ids (``ops/step_experts.py``: the next expert is fetched under
    this one's products); elsewhere, and where the kernel refuses the shapes, a loop whose length
    is data does, one expert an iteration."""
    s = c.expert_layer
    El = s.held
    with scope("moe.place"):
        hot = jax.nn.one_hot(idx - s.expert_start, El, dtype=jnp.float32) * active[:, None, None]
        comb = jnp.einsum("nke,nk->ne", hot, wt)
        hit = jnp.sum(hot, axis=(0, 1)) > 0
        n_hit = jnp.sum(hit, dtype=jnp.int32)
        # the hit experts' ids, compacted to the front by a running count (no sort)
        ids = jnp.zeros((El,), jnp.int32).at[jnp.where(hit, jnp.cumsum(hit) - 1, El)].set(jnp.arange(El, dtype=jnp.int32), mode="drop")
    mats = [stacked[n] for n in s.matrices]
    if step_experts.refusal(mats[0].dtype, x.shape[1], mats[0].shape[2], len(mats)) is None:
        # off the TPU only a test gets here (it swaps ``refusal``), and runs the same body interpreted
        with scope("moe.blocks"):
            return step_experts.hit_experts(mats, layer, x, comb, ids, n_hit, s.act, interpret=jax.default_backend() != "tpu").astype(x.dtype), n_hit

    def one_expert(j, acc):
        e = ids[j]
        *gate, up, down = (jax.lax.dynamic_slice(a, (layer, e, 0, 0), (1, 1) + a.shape[2:])[0, 0] for a in mats)
        a = _hidden(s, x, up, gate[0] if gate else None, "bh,fh->bf")
        a = (a * jax.lax.dynamic_slice_in_dim(comb, e, 1, axis=1)).astype(x.dtype)
        return acc + jnp.dot(a, down, preferred_element_type=jnp.float32)

    with scope("moe.blocks"):
        out = jax.lax.fori_loop(0, n_hit, one_expert, jnp.zeros(x.shape, jnp.float32))
        return out.astype(x.dtype), n_hit


def moe_step(w, xn, active, c, stacked, routing=None):
    """One token a lane: xn [B,H], active [B] bool, ``stacked`` = (the expert layers' stacked
    weights, this layer's index) -> (out [B,H], [held experts that got a token, pairs served
    here, most tokens at one expert, held experts whose weights the step read] over the active
    lanes, float32). ``routing`` = (expert ids [B,k], weights [B,k]) made elsewhere, as in ``moe_seq``."""
    s = c.expert_layer
    idx, wt = scoped("moe.route", route)(w, xn, c) if routing is None else routing  # on the norm as it comes
    xn = xn.astype(w["w_up"].dtype)
    hot = jax.nn.one_hot(idx - s.expert_start, s.held, dtype=jnp.float32) * active[:, None, None]
    load = jnp.sum(hot, axis=(0, 1))
    routed, read = experts_step(*stacked, xn, idx, wt, active, c)
    stats = jnp.stack([jnp.sum(load > 0).astype(jnp.float32), jnp.sum(load), jnp.max(load), read.astype(jnp.float32)])
    return (routed + scoped("moe.shared", shared_expert)(w, xn, s) if s.shared else routed), stats
