"""One routed-expert layer that every hybrid description configures (ROADMAP C1).

What varies between published expert layers is a handful of choices, and a
description states them as an ``ExpertLayer`` (its ``expert_layer`` property):
how the router scores (``sigmoid`` or ``softmax`` over ALL published experts),
whether a correction bias joins the scores for the choice of the top k, whether
the chosen scores are normalised and by what they are scaled, the expert's form
(``relu2``: two matrices, ``W_down relu(W_up x)^2``; ``swiglu``: three,
``W_down (SiLU(W_gate x) * W_up x)``), and whether the shared expert's output is
gated by ``sigmoid(x . w_sg)``. Everything else is one code path:

- ``route``: float32 on whatever the norm hands it, whatever the stream's dtype.
- ``experts_dense``: every held expert over every row: the form that has a
  backward pass (training, the plain forward), and what the tests hold the two
  others to. Its cost is reading every held expert's weights.
- ``experts_grouped``: a grouped matmul in plain XLA over the (row, expert)
  pairs routed HERE, for prefill; no pair is dropped whatever one expert's load.
- ``experts_step``: a decode step's form: the held experts that a BOUND lane
  chose, one after another, every lane against one expert's matrices read
  straight out of the stacked weights (a kernel on a TPU, ``ops/step_experts.py``;
  a loop elsewhere). Its cost is reading the experts hit, and no others.
- ``moe_seq`` / ``moe_step``: the layer over a padded sequence and for one token
  a lane, each with the routing counters the flight log carries.

In a profile the layer's parts stand under sub-scopes of the layer's own (``moe``):
``moe.route`` the router and its top k, ``moe.place`` laying the pairs out by expert
and the gathers into and out of the blocks, ``moe.blocks`` the experts' matmuls (the
grouped matmul's loop, a step's hit experts), ``moe.shared`` the shared expert.

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

from ray_tpu.ops import step_experts
from ray_tpu.util.profiling import scope, scoped

# rows of one block of the grouped matmul; pairs beyond this many take blocks twice as tall
BLOCK, TALL_FROM = 128, 32768
# the grouped matmul's buffers grow with the rows handed to it (a row of the residual width for
# every pair that COULD be held here): beyond this many rows a sequence batch goes through in slabs
SLAB_ROWS = 8192


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
    act: str = "swiglu"  # swiglu (w_gate, w_up, w_down) | relu2 (w_up, w_down)
    shared_gated: bool = False

    def __post_init__(self):
        if self.score not in ("softmax", "sigmoid") or self.act not in ("swiglu", "relu2"):
            raise ValueError(f"an expert layer scores by softmax or sigmoid and acts by swiglu or relu2, not {self.score}/{self.act}")
        if not 0 <= self.expert_start <= self.expert_start + self.held <= self.num_experts:
            raise ValueError("the experts held must lie inside the router's width")

    @property
    def held(self) -> int:
        return self.num_experts if self.local_experts is None else self.local_experts

    @property
    def matrices(self) -> tuple:
        return ("w_gate", "w_up", "w_down") if self.act == "swiglu" else ("w_up", "w_down")


def _relu2(h):
    return jnp.square(jax.nn.relu(h))


def _hidden(s: ExpertLayer, x, up, gate, spec: str):
    """An expert's hidden activation from its input: ``spec`` contracts x with a matrix stored [.., F, H]."""
    if s.act == "relu2":
        return _relu2(jnp.einsum(spec, x, up))
    return jax.nn.silu(jnp.einsum(spec, x, gate)) * jnp.einsum(spec, x, up)


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
        wt = wt / (jnp.sum(wt, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), wt * s.scale if s.scale != 1.0 else wt


def shared_expert(w, x, s: ExpertLayer):
    """The expert every token passes, of the routed experts' form; gated per token where published."""
    h = _relu2(jnp.dot(x, w["shared_up"])) if s.act == "relu2" else jax.nn.silu(jnp.dot(x, w["shared_gate"])) * jnp.dot(x, w["shared_up"])
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


def _grouped(stacked, layer, x, idx, wt, valid, c):
    """``experts_grouped`` and what it did: -> (out [N,H], pairs at each held expert [El] int32,
    rows of the blocks in use, int32)."""
    s = c.expert_layer
    N, k = idx.shape
    M, El, H = N * k, s.held, x.shape[-1]
    block = 2 * BLOCK if M >= TALL_FROM else BLOCK
    n_rows = (-(-M // block) + El) * block  # the most that padding to whole blocks can need
    with scope("moe.place"):
        local = (idx - s.expert_start).reshape(-1)
        mine = (local >= 0) & (local < El) & jnp.repeat(valid, k)
        # a pair's place: its rank among the pairs of its expert (a running count, no sort), after
        # the blocks of the experts before it; what is not ours goes to a spare row that stays zero
        hot = mine[:, None] & (local[:, None] == jnp.arange(El, dtype=jnp.int32)[None, :])
        count = jnp.cumsum(hot.astype(jnp.int32), axis=0)
        sizes = count[-1]
        blocks_of = (sizes + block - 1) // block
        last_block = jnp.cumsum(blocks_of)  # one past each expert's last block
        e_of = jnp.clip(local, 0, El - 1)
        rank = jnp.take_along_axis(count, e_of[:, None], axis=1)[:, 0] - 1
        place = jnp.where(mine, (last_block[e_of] - blocks_of[e_of]) * block + rank, n_rows)
        pair_at = jnp.full((n_rows + 1,), M, jnp.int32).at[place].set(jnp.arange(M, dtype=jnp.int32))
        scale = jnp.where(mine, wt.reshape(-1), 0.0)
    mats = [stacked[n] for n in s.matrices]

    def one_block(b, ys):
        e = jnp.sum(last_block <= b).astype(jnp.int32)
        with scope("moe.place"):  # the gather into the block
            pair = jax.lax.dynamic_slice_in_dim(pair_at, b * block, block)
            ok = pair < M  # the padding at the end of an expert's run holds no pair
            pair = jnp.minimum(pair, M - 1)
            xb = jnp.where(ok[:, None], jnp.take(x, pair // k, axis=0), 0)
        *gate, up, down = (jax.lax.dynamic_slice(a, (layer, e, 0, 0), (1, 1) + a.shape[2:])[0, 0] for a in mats)
        yb = jnp.dot(_hidden(s, xb, up, gate[0] if gate else None, "bh,fh->bf"), down)
        yb = (yb * jnp.where(ok, scale[pair], 0.0)[:, None]).astype(ys.dtype)
        return jax.lax.dynamic_update_slice(ys, yb, (b * block, jnp.zeros((), jnp.int32)))

    with scope("moe.blocks"):
        ys = jax.lax.fori_loop(0, last_block[-1], one_block, jnp.zeros((n_rows + 1, H), x.dtype))
    with scope("moe.place"):  # and the gather out of them
        out = jnp.sum(jnp.take(ys, place, axis=0).reshape(N, k, H), axis=1, dtype=jnp.float32).astype(x.dtype)
    return out, sizes, last_block[-1] * block


def experts_grouped(stacked, layer, x, idx, wt, valid, c):
    """A grouped matmul in plain XLA: the (row, expert) pairs routed here, laid out by expert, each
    expert's run padded to whole blocks of rows, and one loop over the blocks IN USE: a block's
    rows against its expert's matrices, read straight from the stacked weights. The work
    follows the pairs (plus at most a block an expert), not experts x rows; no pair is dropped,
    whatever the load on one expert. ``valid`` [N] keeps padding out of every group. The loop's
    length is data, so this path has no backward pass (training uses ``experts_dense``).
    ``stacked[name]`` are the arrays STACKED over the expert layers, and the loop reads expert e
    of layer ``layer`` from them: a layer's experts, sliced out first, would be copied once a
    layer to become the loop's operand."""
    return _grouped(stacked, layer, x, idx, wt, valid, c)[0]


def moe_seq(w, xn, lengths, c, stacked=None):
    """xn [B,T,H] -> ([B,T,H], counters): routed experts held here plus the shared expert.
    ``stacked`` = (the expert layers' stacked weights, this layer's index): the serving path's
    grouped matmul, in slabs of ``SLAB_ROWS`` rows where the batch is larger; without it every
    held expert over every token, which has a backward pass. The counters, float32 [3], are of
    the grouped matmul (zeros without it): held experts that got a pair, pairs served here, rows
    of the blocks in use."""
    s = c.expert_layer
    B, T, H = xn.shape
    N = B * T
    idx, wt = scoped("moe.route", route)(w, xn.reshape(N, H), c)  # on the norm as it comes
    x = xn.reshape(N, H).astype(w["w_up"].dtype)
    valid = (jnp.arange(T)[None, :] < lengths[:, None]).reshape(-1)
    if stacked is None:
        routed = scoped("moe.blocks", experts_dense)(w, x, idx, jnp.where(valid[:, None], wt, 0.0), c)
        counters = jnp.zeros((3,), jnp.float32)
    else:
        if N > SLAB_ROWS and N % SLAB_ROWS == 0:
            slabs = jax.tree.map(lambda a: a.reshape((N // SLAB_ROWS, SLAB_ROWS) + a.shape[1:]), (x, idx, wt, valid))
            routed, sizes, rows = jax.lax.map(lambda a: _grouped(*stacked, *a, c), slabs)
            routed, sizes, rows = routed.reshape(N, H), jnp.sum(sizes, axis=0), jnp.sum(rows)
        else:
            routed, sizes, rows = _grouped(*stacked, x, idx, wt, valid, c)
        counters = jnp.stack([jnp.sum(sizes > 0), jnp.sum(sizes), rows]).astype(jnp.float32)
    return (routed + scoped("moe.shared", shared_expert)(w, x, s)).reshape(B, T, H), counters


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


def moe_step(w, xn, active, c, stacked):
    """One token a lane: xn [B,H], active [B] bool, ``stacked`` = (the expert layers' stacked
    weights, this layer's index) -> (out [B,H], [held experts that got a token, pairs served
    here, most tokens at one expert, held experts whose weights the step read] over the active
    lanes, float32)."""
    s = c.expert_layer
    idx, wt = scoped("moe.route", route)(w, xn, c)  # on the norm as it comes
    xn = xn.astype(w["w_up"].dtype)
    hot = jax.nn.one_hot(idx - s.expert_start, s.held, dtype=jnp.float32) * active[:, None, None]
    load = jnp.sum(hot, axis=(0, 1))
    routed, read = experts_step(*stacked, xn, idx, wt, active, c)
    stats = jnp.stack([jnp.sum(load > 0).astype(jnp.float32), jnp.sum(load), jnp.max(load), read.astype(jnp.float32)])
    return routed + scoped("moe.shared", shared_expert)(w, xn, s), stats
