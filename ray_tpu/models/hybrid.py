"""The layer loop of a hybrid decoder: one walk, any DESCRIPTION of layers (ROADMAP C1).

A hybrid model is a stack of sub-blocks ``x = x + mixer(norm(x))`` whose mixers
are of several kinds, each keeping its own kind of state per sequence. A model
file states WHAT its layers are; this file walks them. A description
(``HybridDescription``, mixed into the model's config dataclass) says:

- ``layer_kinds``: the kind of every sub-block, in order;
- ``mixers``: kind -> ``Mixer``: the named scope of that kind in a profile (a name of
  ``util/profiling.SCOPES``, which says what role the kind plays; a name outside it raises
  where the program is traced), its
  two forms (``seq`` over a padded sequence with true lengths; ``step`` for one
  token a lane against cached state), and whether it routes tokens to experts;
- ``cache_spec()``: kind -> {name: (shape, dtype, "position" | "sequence")}: what
  ONE layer of that kind keeps, per position of a sequence (the slot cache's
  rows: ``k`` and ``v`` of an attention layer with heads, ``c_kv`` and ``k_r`` of
  a latent one) or once per sequence (the state cache);
- ``ring_entries()``: name -> W for the per-position entries that keep a sequence's
  LAST W positions only (a sliding-window layer's keys and values): such an entry is
  a ring of W rows a slot, position p at row p mod W, beside the entries that span
  ``max_seq_len``; none by default;
- ``handed``: name -> (shape, dtype) of what one sub-block hands the NEXT for every
  token (a routing decided before attention, for the expert layer after it); the
  mixers that hand something on say so (``Mixer.hands``); nothing by default, and
  the loops then carry nothing more than the stream;
- ``norm(x, w)``: the pre-norm of every sub-block and the final norm;
- ``stream_dtype``, ``init_params``, ``num_params()``.

Parameters are stacked by layer kind (``params[kind][name]``: [layers of that
kind, ...]) so that the loops can index them; ``embed``, ``unembed`` and
``final_norm`` stand beside the kinds (a model whose head is TIED to its embedding holds no
``unembed``: ``head`` then multiplies by the table itself).

Two loops over one description:

- ``scan_layers`` for a sequence (prefill and training): one scan whose body
  switches on the layer's kind, so a program's size follows the kinds and not the
  depth; what the layers keep for the cache leaves the loop with one row for each
  layer that keeps it.
- ``run_layers`` for a decode step: a scan over the repeated period of the pattern
  (``layer_plan``; what stands before and after it is unrolled), with the caches in
  its carry, updated in place through a ``LayerCache``. An attention layer's keys and values are read from the stacked rows
  where they lie (``attend_slot`` -> ``ops/slot_attention.attend``, the op the Llama
  decode step calls too): a lane's live blocks on a TPU, the layer's rows sliced out
  and masked elsewhere.

Neither loop, nor the step programs of ``llm/hybrid_runner.py`` that call them,
names a model or a kind of layer. A uniform model is the special case of one kind
and a period of one. Ten descriptions stand over these loops today
(``nemotron_h``, ``qwen3_next``, ``glm4_moe_lite``, ``kimi_linear``, ``minicpm_sala``,
``smallthinker``, ``lfm2``, ``keye_vl``, ``jamba``, ``afmoe``, each a file beside this one; the eighth
and the ninth needed no line of ``llm/engine.py`` or ``llm/hybrid_runner.py``: a third per-position
entry, the indexer's key, and a state of another shape, ``[states, channels]`` with no heads, are
each one more row of ``cache_spec()``; the tenth, whose every sub-block norms its OUTPUT too, keeps
that second norm inside its mixers, so the loops' ``x + mixer(norm(x))`` stands, and needed one
host line of the engine: ``routed_counters``, what a description reads off a prefill program's
routing counters once they are back), and the benchmark has a family file for each and an
eleventh for ``llama``, which the runner still writes out itself (``benchmark/families/``).
"""

from __future__ import annotations

from collections import Counter
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from ray_tpu.models import experts
from ray_tpu.ops import slot_attention
from ray_tpu.ops.layers import cross_entropy_loss, live_rows
from ray_tpu.util.profiling import scope

# what a routing layer's sequence form reports beside its output, as one more thing it "keeps":
# float32 [3], or [4] in the programs whose blocks the kernel runs: see ``models/experts.moe_seq``
ROUTING = "routing"


class Mixer(NamedTuple):
    """One kind of layer, as the loops and the step programs see it."""

    scope: str  # the named scope around every layer of this kind, in every program
    seq: Callable  # (w, xn [B,T,H], SeqCtx) -> (y [B,T,H], kept {name: one layer's entry})
    step: Callable  # (w, xn [B,H], LayerCache, StepCtx) -> (y [B,H], routing counters [4] or None)
    routes: bool = False  # its sequence form keeps ROUTING, its step form hands back counters
    hands: bool = False  # both forms return one thing more, last: what the next sub-block finds as ``ctx.handed`` (``HybridDescription.handed``)


class SeqCtx(NamedTuple):
    lengths: Any  # [B] int32: true lengths of the right-padded sequences
    mesh: Any  # the mesh a kernel has to be told about, or None
    stacked: Any  # the serving path: (the kind's stacked weights, this layer's index); else None
    handed: Any = None  # {name: [B,T,*shape]}: what the last sub-block that hands something on handed (``HybridDescription.handed``)

    @property
    def skippable(self):
        """``lengths`` for a kernel or a loop that skips what lies past them and has no backward pass
        (``ops/flash_attention``, ``ops/layers.live_slabs``): the serving path's, which has no mesh
        either (``hybrid_runner.refuse``); None where a backward pass may follow."""
        return None if self.stacked is None else self.lengths


class StepCtx(NamedTuple):
    lengths: Any  # [B] int32: positions already held = the new token's position
    active: Any  # [B] bool: lanes bound to a live sequence
    stacked: Any  # (the kind's stacked weights, this layer's index), as in ``SeqCtx``
    handed: Any = None  # {name: [B,*shape]}, as in ``SeqCtx``


class LayerPlan(NamedTuple):
    """How ``run_layers`` walks a pattern: ``head``, then ``period`` x ``repeats``, then ``tail``.
    The head stands last among the fields: readers of a plan from before it had one index the
    first three."""

    period: tuple
    repeats: int
    tail: tuple
    head: tuple = ()


class HybridDescription:
    """What the loops, the engine and the cache manager read off a model's config. The config
    dataclass that mixes this in provides ``layer_kinds``, ``mixers``, ``cache_spec()``,
    ``norm``, ``stream_dtype``, ``init_params`` and ``num_params``."""

    @property
    def num_layers(self) -> int:
        return len(self.layer_kinds)

    def count(self, kind: str) -> int:
        return self.layer_kinds.count(kind)

    def keeping(self, name: str) -> tuple:
        """Indices, among all layers, of the layers that keep ``name`` (a cache entry or ROUTING)."""
        kinds = {k for k, spec in self.cache_spec().items() if name in spec}
        if name == ROUTING:
            kinds = {k for k, m in self.mixers.items() if m.routes}
        return tuple(n for n, k in enumerate(self.layer_kinds) if k in kinds)

    def position_entries(self) -> dict:
        """name -> (layers that keep it, shape, dtype) of every per-position entry of the cache:
        what the slot cache (``llm/kv_cache.py``) is allocated from."""
        return {name: (self.count(kind), tuple(shape), dtype)
                for kind, spec in self.cache_spec().items()
                for name, (shape, dtype, per) in spec.items() if per == "position"}

    def ring_entries(self) -> dict:
        """name -> W of the per-position entries that are RINGS: the slot cache keeps a sequence's
        last W positions of them, position p at row p mod W (``llm/kv_cache.py``), where every other
        per-position entry spans ``max_seq_len``. None by default."""
        return {}

    @property
    def handed(self) -> dict:
        """name -> (shape, dtype) of what a sub-block hands the next for every token. Nothing by default."""
        return {}

    @property
    def num_kv_layers(self) -> int:
        """Layers that keep something per position (keys and values, or a latent)."""
        return max([layers for layers, _, _ in self.position_entries().values()], default=0)

    @property
    def slot_attention_tile(self) -> dict:
        """What ``ops/slot_attention.refusal`` is asked about the decode step's attention: here
        the tile of a layer that keeps ``k`` and ``v`` by head."""
        return dict(num_heads=self.num_heads, num_kv_heads=self.num_kv_heads, head_dim=self.hd)

    def prefill_counters(self, batch: int, length: int, lengths=()) -> dict:
        """What ONE prefill program of ``batch`` x ``length`` positions (as padded) over prompts of
        the true ``lengths`` runs that can be counted on the host from those alone, by a name of
        ``llm/telemetry.PREFILL_COUNTERS``: summed over an admitting step's programs onto that step's
        row of the flight log. None by default."""
        return {}

    def routed_counters(self, rows: int, routing) -> dict:
        """What ONE prefill program over ``rows`` positions (as padded) did that follows, on the host,
        from its routing counters as they came back (``routing``: ``llm/hybrid_runner.PREFILL_STATS``,
        means over the routing layers) and its shapes, by a name of ``llm/telemetry.PREFILL_COUNTERS``:
        no value more leaves the device program for it. None by default."""
        return {}

    def prefill_rows_live(self, length: int, lengths) -> int:
        """The positions that the position-wise sub-blocks run in ONE prefill program over ``length``
        padded positions and rows of the true ``lengths`` (a padding row's among them), for an admitting
        step's ``prefill_rows_live``: whole slabs under each row's length where the description has a
        dense layer (the kind ``ffn``, which is ``glm4_moe_lite.ffn`` over ``ops/layers.live_slabs`` in
        every description that has one), every position where it has none."""
        return live_rows(length, lengths) if "ffn" in self.layer_kinds else len(lengths) * length

    def flash_calls(self, length: int) -> dict:
        """{head width: calls} of ``ops/flash_attention`` in ONE prefill program over ``length``
        padded positions: what ``flash_attention.query_tiles`` counts an admitting step's
        ``attn_q_tiles`` and ``attn_q_tiles_live`` from. None by default."""
        return {}

    def decode_counters(self, positions) -> dict:
        """What ONE decode step reads for lanes that hold ``positions`` (a list, the new token's
        among them) that can be counted on the host from those alone, by a name of
        ``llm/telemetry.DECODE_COUNTERS``: onto that step's row of the flight log. None by default."""
        return {}

    @property
    def stream_scales(self) -> tuple:
        """Constants (on the embedding, on every residual branch ``x + a * y``, on the normed stream
        before the head) of a model that scales its stream by its width and depth; 1.0 scales nothing
        and leaves the program as it was."""
        return 1.0, 1.0, 1.0

    @property
    def routing_layers(self) -> int:
        return len(self.keeping(ROUTING))

    @property
    def kinds_held(self) -> str:
        """What a refusal says this model is made of."""
        return ", ".join(f"{self.count(k)} x {k}" for k in dict.fromkeys(self.layer_kinds))

    @property
    def layer_plan(self) -> LayerPlan:
        """The stretch of the pattern that is a block repeated at least twice and covers the most
        layers, with the kinds before it (``head``: a leading dense layer) and after it (``tail``).
        ``run_layers`` scans over the repeats and unrolls the rest. Of two stretches that cover
        as many layers, the one that starts earlier, then the shorter period."""
        kinds, best = tuple(self.layer_kinds), LayerPlan((), 0, ())
        for h in range(len(kinds)):
            rest = kinds[h:]
            for p in range(1, len(rest) // 2 + 1):
                r = 1
                while rest[r * p:(r + 1) * p] == rest[:p]:
                    r += 1
                if r >= 2 and r * p > best.repeats * len(best.period):
                    best = LayerPlan(rest[:p], r, rest[r * p:], kinds[:h])
        return best if best.repeats else LayerPlan((), 0, kinds)


# --------------------------------------------------------------- the layer loops
def _layer_weights(params, kind, i):
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), params[kind])


def run_layers(config, params, x, carry, layer_fn):
    """Walk the layer pattern with LARGE state in the carry (a decode step's caches, updated in
    place): ``layer_fn(kind, w, i, x, carry) -> (x, carry)`` with ``w`` one layer's weights and
    ``i`` its index among the layers of its kind (traced inside the scan over the repeated
    period, a plain int in the head and the tail). The program holds one body per layer of the
    head, of the period and of the tail. Why not one body per kind (``scan_layers``): a
    conditional's branch hands back every carried array, and the chip's compiler copies the ones
    a branch did not touch, 8 GB a step for 0.75 GB of caches (compiled for a described v5e, PR 29)."""
    period, repeats, tail, head = config.layer_plan
    per, seen = Counter(period), Counter()

    def apply(kind, i, x, carry):
        with scope(config.mixers[kind].scope):
            return layer_fn(kind, _layer_weights(params, kind, i), i, x, carry)

    def unrolled(kinds, x, carry):
        for kind in kinds:
            x, carry = apply(kind, seen[kind], x, carry)
            seen[kind] += 1
        return x, carry

    def block(xc, r):
        x, carry = xc
        at = Counter(first)
        for kind in period:
            x, carry = apply(kind, r * per[kind] + at[kind], x, carry)
            at[kind] += 1
        return (x, carry), None

    x, carry = unrolled(head, x, carry)
    first = dict(seen)  # the layers of each kind that stand before the period
    if repeats:
        with scope("cache"):  # the loop's own work is on its carry, the caches; a layer's stands under its kind's scope
            (x, carry), _ = jax.lax.scan(block, (x, carry), jnp.arange(repeats, dtype=jnp.int32))
    seen.update({k: repeats * n for k, n in per.items()})
    return unrolled(tail, x, carry)


def scan_layers(config, params, x, layer_fn, empty):
    """Walk the layer pattern over a SEQUENCE in one scan whose body switches on the layer's
    kind: ``layer_fn(kind, w, i, x) -> (x, kept)`` with ``kept`` what that layer keeps, a dict
    with some of ``empty``'s entries (``empty``: name -> zeros of one layer's entry).
    -> (x, {name: [layers that keep it, ...]}). One body per KIND, so a prefill program's size
    and compile time follow the kinds and not the depth: 7 s a program against 17 s for
    ``run_layers``' nine bodies at 16 layers (compiled for a described v5e, PR 29), and a serving
    replica warms some twenty. What is kept rides the carry and is written OUTSIDE the switch, at
    the layer's row among the layers that keep that entry (a layer that does not writes zeros to
    a spare last row): stacked as the scan's output it would hold a row for EVERY layer, 1.6 GB of
    zeros beside 0.2 GB of keys and values at 24 sub-blocks x 8 x 4096 positions."""
    kinds = sorted(set(config.layer_kinds))
    which = jnp.asarray([kinds.index(k) for k in config.layer_kinds], jnp.int32)
    among = jnp.asarray([config.layer_kinds[:n].count(k) for n, k in enumerate(config.layer_kinds)], jnp.int32)
    keepers = {name: config.keeping(name) for name in empty}
    row = {name: jnp.asarray([keepers[name].index(n) if n in keepers[name] else len(keepers[name])
                              for n in range(config.num_layers)], jnp.int32) for name in empty}

    def branch(kind):
        def run(i, x):
            with scope(config.mixers[kind].scope):
                x, kept = layer_fn(kind, _layer_weights(params, kind, i), i, x)
            return x, {n: kept[n].astype(z.dtype) if n in kept else z for n, z in empty.items()}
        return run

    branches = [branch(k) for k in kinds]

    def body(xo, ki):
        x, out = xo
        x, kept = jax.lax.switch(ki[0], branches, ki[1], x)
        with scope("cache"):
            return (x, {n: jax.lax.dynamic_update_index_in_dim(out[n], kept[n], ki[2][n], 0) for n in out}), None

    out = {n: jnp.zeros((len(keepers[n]) + 1,) + z.shape, z.dtype) for n, z in empty.items()}
    with scope("cache"):  # the loop's own work (what it copies of its carry is what the layers keep); a layer's stands under its kind's scope
        (x, out), _ = jax.lax.scan(jax.checkpoint(body) if config.remat else body, (x, out), (which, among, row))
    return x, {n: a[:-1] for n, a in out.items()}


class LayerCache:
    """One layer's window onto the caches that ride ``run_layers``' carry, for a mixer's step
    form: ``read(name)`` is that layer's entry for every lane ([B, *shape]: a recurrent state),
    ``write(name, value)`` overwrites a per-sequence entry or puts ONE position's value at each
    lane's current position, ``stacked(name)`` hands out the whole array and the layer's index,
    for a per-position entry whose rows [B, S, *shape] an op reads in part (``attend_slot``;
    ``read`` would slice all S positions of every lane out). All act on the stacked arrays in
    place (a dynamic slice of, a scatter or an update into the donated carry); the arrays as
    they stand afterwards are ``arrays``. A position's write is scoped ``cache``; a per-sequence
    entry's stays in its caller's scope (a recurrent state's in ``<kind>.state``). An entry of
    ``rings`` holds as many rows as its window: position p goes to row p mod rows."""

    def __init__(self, arrays: dict, per_position: frozenset, i, lanes, pos, rings: frozenset = frozenset()):
        self.arrays, self._per_position, self._i, self._lanes, self._pos, self._rings = dict(arrays), per_position, i, lanes, pos, rings

    def read(self, name: str):
        return jax.lax.dynamic_index_in_dim(self.arrays[name], self._i, 0, keepdims=False)

    def stacked(self, name: str):
        """(the whole stacked array, this layer's index in it): for an op that reads a part of
        the layer's entry from where it lies, where ``read`` would slice all of it out."""
        return self.arrays[name], self._i

    def write(self, name: str, value) -> None:
        a = self.arrays[name]
        if name in self._per_position:
            with scope("cache"):
                pos = self._pos % a.shape[2] if name in self._rings else self._pos
                self.arrays[name] = a.at[self._i, self._lanes, pos].set(value.astype(a.dtype))
        else:
            self.arrays[name] = jax.lax.dynamic_update_index_in_dim(a, value.astype(a.dtype), self._i, 0)


# ---------------------------------------------------- what descriptions share
def init_stacked(groups: dict, count, keys, dt) -> dict:
    """Weights stacked by layer kind from ``groups``: kind -> {name: (shape of one layer, fan_in
    or fill)}: a float fills, an int draws N(0, fan_in^-1/2) in float32 and casts to ``dt``, a
    layer at a time (the float32 draw of all the experts at once would not fit the chip).
    ``count(kind)`` layers of each kind that has any; ``keys`` an iterator of PRNG keys."""
    def fill(shape, how, n):
        if isinstance(how, float):
            return jnp.full((n,) + shape, how, dt)
        return jax.lax.map(lambda k: (jax.random.normal(k, shape, jnp.float32) * how ** -0.5).astype(dt),
                           jax.random.split(next(keys), n))

    return {g: {name: fill(shape, how, count(g)) for name, (shape, how) in group.items()}
            for g, group in groups.items() if count(g)}


def attend_slot(q, cache: LayerCache, ctx: StepCtx, num_kv_heads: int, entries: tuple = ("k", "v"), name: str = slot_attention.KERNEL):
    """One token a lane (its query q [B,nh,hd]) against the positions its lane holds in THIS
    layer's keys and values (``entries``: their names in the cache), the new token's among them
    (written through ``cache`` before the call): ``ops/slot_attention.attend`` on the stacked rows
    where they lie. Entries that are a ring of W rows hold the lane's last min(length + 1, W)
    positions in its first that many rows while it is young and in all W once it has wrapped,
    which is what the op reads of ANY stack of W rows for a lane at ``lengths``: keys are cached
    after their rotation, so the order of the rows is immaterial to the softmax. ``name``: the
    kernel's name in a trace, where it is not the op's own. -> [B, nh*hd] f32."""
    (k, i), (v, _) = (cache.stacked(n) for n in entries)
    return slot_attention.attend(q, k, v, i, ctx.lengths, num_kv_heads, live=ctx.active, name=name)


# ------------------------------------------------------------- the stream's ends and its branches
def embed_tokens(params, tokens, c):
    """The stream as it starts: the tokens' embedding rows, times the description's constant if it has one."""
    x = jnp.take(params["embed"], tokens, axis=0)
    scale = c.stream_scales[0]
    return x.astype(c.stream_dtype) if scale == 1.0 else (x.astype(jnp.float32) * scale).astype(c.stream_dtype)


def add_branch(x, y, c):
    """``x + a * y``: a sub-block's output onto the stream (``a`` = 1 for every model that does not scale its branches)."""
    a = c.stream_scales[1]
    return x + y.astype(x.dtype) if a == 1.0 else x + (y.astype(jnp.float32) * a).astype(x.dtype)


def head(x, params):
    """x [.., H] -> logits [.., vocab] float32: through ``unembed`` [H, V], or, where the weights hold
    none, through the embedding table [V, H] itself (a tied head: contracted over its columns)."""
    return jnp.dot(x, params["unembed"] if "unembed" in params else params["embed"].T, preferred_element_type=jnp.float32)


def before_head(x, params, c):
    """The final norm, in the weights' dtype, times the description's constant if it has one: what the head multiplies."""
    x, scale = c.norm(x, params["final_norm"]), c.stream_scales[2]
    return (x if scale == 1.0 else x.astype(jnp.float32) * scale).astype(params["embed"].dtype)


# ------------------------------------------------------------- sequence forward
def forward_hidden(params, tokens, lengths, config, mesh=None, collect: bool = False):
    """tokens [B,T] right-padded, lengths [B] -> the final-norm'd stream [B,T,H] and, with
    ``collect`` (the serving prefill; its routing layers run the grouped matmul, which has no
    backward pass), what each layer keeps, by entry name: per-position entries [layers, B, T,
    *shape], per-sequence entries [layers, B, *shape] AT each sequence's true length, and
    ``ROUTING`` [routing layers, 3]."""
    c = config
    B, T = tokens.shape
    assert not collect or mesh is None, "the collecting pass is the serving path's, which takes no mesh (hybrid_runner.refuse)"
    with scope("embed"):
        x = embed_tokens(params, tokens, c)
    empty = {}
    if collect:
        for spec in c.cache_spec().values():
            for name, (shape, dtype, per) in spec.items():
                empty[name] = jnp.zeros(((B, T) if per == "position" else (B,)) + tuple(shape), jnp.dtype(dtype))
        if c.routing_layers:
            group = next(params[kind] for kind, m in c.mixers.items() if m.routes)
            empty[ROUTING] = jnp.zeros((experts.seq_counters(c, group, B * T),), jnp.float32)

    def layer(kind, w, i, riding):
        # what sub-blocks hand on rides the loop beside the stream (nothing, for a description that hands nothing on: no array more)
        x, handed = riding
        ctx = SeqCtx(lengths, mesh, (params[kind], i) if collect else None, handed)
        y, kept, *more = c.mixers[kind].seq(w, c.norm(x, w["norm"]), ctx)
        return (add_branch(x, y, c), more[0] if c.mixers[kind].hands else handed), kept if collect else {}

    handed = {n: jnp.zeros((B, T) + tuple(shape), jnp.dtype(dt)) for n, (shape, dt) in c.handed.items()}
    (x, _), out = scan_layers(c, params, (x, handed), layer, empty)
    with scope("head"):
        return before_head(x, params, c), out


def forward(params, tokens, config, mesh=None):
    """tokens [B,T] -> logits [B,T,vocab] f32, every position real."""
    lengths = jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32)
    x, _ = forward_hidden(params, tokens, lengths, config, mesh)
    with scope("head"):
        return head(x, params)


def loss_fn(params, batch, config, mesh=None):
    """batch: {tokens [B,T], targets [B,T] (-100 = ignore)} -> scalar loss."""
    logits = forward(params, batch["tokens"], config, mesh=mesh)
    with scope("head"):
        return cross_entropy_loss(logits, batch["targets"])


def trace_description():
    """A description at tile-true widths that traces in seconds: what ``lint/jaxcheck`` runs the
    step programs of ``llm/hybrid_runner.py`` over, so that the runner itself names no model."""
    from ray_tpu.models.nemotron_h import NemotronHConfig

    return NemotronHConfig(
        vocab_size=32256, hidden_size=1024, layer_pattern="ME*ME*ME", mamba_num_heads=16, mamba_head_dim=64,
        n_groups=8, ssm_state_size=128, n_routed_experts=16, expert_start=0, num_local_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=1024, moe_shared_expert_intermediate_size=2048,
        num_heads=8, num_kv_heads=8, head_dim=128, max_seq_len=512)
