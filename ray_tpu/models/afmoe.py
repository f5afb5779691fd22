"""Arcee AFMoE decoder (``model_type`` ``afmoe``; Trinity-Large-Preview): gated, query-key-normed
attention with a window and RoPE three layers in four and full attention WITHOUT positions the
fourth, every sub-block's output normed before it joins the stream (a SANDWICH), leading dense
layers, then sigmoid-routed experts behind a shared one; the embedding scaled at entry.

A tenth DESCRIPTION over the one layer loop (``models/hybrid.py``) and the one expert layer
(``models/experts.py``). Every published decoder layer is two residual sub-blocks over
``N(x) = w * x / sqrt(mean(x²) + eps)`` in float32, no bias anywhere:

1. ``x_0 = sqrt(hidden_size) * E[token]`` (``mup_enabled``: the first of ``stream_scales``).
2. ``h = N_1(x)``; ``q = h W_q`` (``num_heads`` of ``head_dim``), ``k = h W_k``, ``v = h W_v``
   (``num_kv_heads``), ``g = h W_g`` (as wide as q). ``q <- N_q(q)``, ``k <- N_k(k)``: an RMSNorm over
   a head's dimensions with ONE weight [head_dim] for all heads, BEFORE any rotation. A WINDOW
   layer (``layer_types[l] == "sliding_attention"``; kind ``swa``) rotates q and k (rotate-half over
   all of a head, ``rope_theta``) and query i reads keys ``i - W < j <= i``; a FULL layer (kind
   ``attn``) rotates nothing and reads every ``j <= i``. ``a = concat(heads) * sigmoid(g)``;
   ``x' = x + N_2(a W_o)``.
3. ``u = N_3(x')``. Layers ``< num_dense_layers`` (kind ``mlp``): ``f = W_down (silu(W_gate u) * W_up u)``.
   The others (kind ``moe``): ``s = sigmoid(u W_r)`` over ALL published experts in float32, the top k
   of ``s + b`` (``b``: the expert bias, a buffer; ``n_group`` = ``topk_group`` = 1, no group limit),
   weights ``route_scale * s_m / (sum of the chosen s + 1e-20)`` from ``s`` WITHOUT ``b``
   (``route_norm``), SwiGLU experts and one plain shared expert: ``experts.route`` and
   ``ExpertLayer`` as they stand. ``x'' = x' + N_4(f)``.
4. ``logits = N_f(x_L) W_head``, untied.

So the loop walks ``2 x num_hidden_layers`` sub-blocks of FOUR kinds, ``swa | attn`` then
``mlp | moe``. The sandwich's second norm (``post_norm``) lives INSIDE each mixer, its weight among
the mixer's: the loops (``hybrid.forward_hidden``, ``hybrid_runner.decode_step``) stay
``x + mixer(norm(x))`` to the letter, no other description's program is touched, and in a profile the
second norm stands under its sub-block's own scope. The alternative, an optional post-norm that
the loops apply, is a branch in two loops for one description. What is kept per position: a full
layer's ``k`` and ``v`` for every position; a window layer's ``k_w`` and ``v_w`` in a RING of the last
W positions (``ring_entries``; ``models/smallthinker.py``'s names, its decode kernel's name in a
trace, its counters). Keys are cached after their norm and their rotation.

The gate and the head norms are written HERE and not shared with ``models/qwen3_next.py``'s
``gated_attn_qkv``: there the gate is half of the query projection's own columns (one matrix
[H, 2 x heads x head_dim], a head's ``[q | gate]``: one matmul, which no scope can take apart), its
norm adds one to its weight and its rotation is partial; here ``W_g`` is a matrix of its own whose
projection and product stand under ``swa.gate`` / ``attn.gate``, so that a trace says what the gate
costs. Qwen3-Next's program text stays byte for byte.

The layer is told which experts this chip holds (``expert_start``, ``num_local_experts``): the
router keeps its published width and its k; a choice held elsewhere adds nothing here.

Precision: weights, stream, caches and matmul operands in the weights' dtype (bfloat16 as
published), accumulation float32; norms, the router, the gate's sigmoid and the softmaxes float32.

Initialisation (weights are random from a seed): matrices N(0, fan_in^-1/2); pre-norms, head norms
and the final norm 1; every POST-norm ``residual_rescale_layers^-1/2`` (the "depth-scaled" sandwich:
behind a post-norm the scale of ``W_o`` or ``W_down`` is immaterial, so the 1/sqrt(N) that other
descriptions draw into those matrices is drawn into the norm's gain, and the stream keeps the scale
it starts with); the embedding N(0, 1/hidden_size), so that the stream starts at unit scale under
the ``sqrt(hidden_size)`` factor; the expert bias N(0, ``router_bias_init``²), small and NOT zero (a
program that chose by ``s`` alone, or weighted by ``s + b``, is then another program).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ray_tpu.models import experts
from ray_tpu.models.experts import ExpertLayer
from ray_tpu.models.glm4_moe_lite import ffn
from ray_tpu.models.hybrid import ROUTING, HybridDescription, Mixer, attend_slot, forward, init_stacked, loss_fn  # noqa: F401 - the shared forward and loss, as the harness's family asks for them
from ray_tpu.models.nemotron_h import _anchor_routing  # one orthogonal matrix for all expert layers' routers: the same scoring, the same reason
from ray_tpu.models.smallthinker import DECODE_KERNEL, ENTRIES  # a window layer's entries in the cache and its decode kernel's name in a trace: the readers go by them
from ray_tpu.ops.flash_attention import flash_attention_on_mesh
from ray_tpu.ops.layers import apply_rope, live_rows, rms_norm, rotary_embedding
from ray_tpu.util.profiling import scope

KINDS = {"sliding_attention": "swa", "full_attention": "attn"}


@dataclass(frozen=True)
class AfmoeConfig(HybridDescription):
    vocab_size: int = 200192  # rows of the embedding and head held here
    hidden_size: int = 3072
    num_hidden_layers: int = 60  # decoder layers HELD: each an attention sub-block and a feed-forward sub-block
    num_dense_layers: int = 6  # the first layers' feed-forward is dense
    layer_types: tuple = ("sliding_attention", "sliding_attention", "sliding_attention", "full_attention") * 15  # one a held layer
    sliding_window: int = 4096
    num_heads: int = 48
    num_kv_heads: int = 8
    head_dim: int = 128
    rope_theta: float = 10000.0
    intermediate_size: int = 12288
    # moe: the router is num_experts wide whatever is held here
    num_experts: int = 256
    expert_start: int = 0
    num_local_experts: int | None = None  # None: all of them
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 3072
    num_shared_experts: int = 1
    route_norm: bool = True
    route_scale: float = 2.448
    mup_enabled: bool = True  # the embedding times sqrt(hidden_size) at entry
    rms_eps: float = 1e-5
    # init only: every post-norm's gain is this^-1/2 (the published sub-blocks: depth-scaled); 1 leaves them 1
    residual_rescale_layers: int = 120
    # init only: > 0 anchors every token id to its own top-k experts in every expert layer by this
    # margin in the router's logits (``models/nemotron_h._anchor_routing``)
    router_anchor: float = 0.0
    # init only: the expert bias is drawn N(0, this²)
    router_bias_init: float = 0.01
    max_seq_len: int = 16384
    dtype: str = "bfloat16"
    remat: bool = False
    attention_impl: str = "auto"  # auto | pallas | xla

    def __post_init__(self):
        if len(self.layer_types) != self.num_hidden_layers or set(self.layer_types) - set(KINDS):
            raise ValueError(f"layer_types names every held layer, each one of {sorted(KINDS)}")
        if not 0 <= self.num_dense_layers <= self.num_hidden_layers:
            raise ValueError("num_dense_layers counts some of the num_hidden_layers")
        if self.num_heads % self.num_kv_heads or self.head_dim % 2:
            raise ValueError("key-value heads divide the query heads, and a head is rotated in pairs")
        if self.num_shared_experts != 1:
            raise ValueError("the expert layer has one shared expert")
        _ = self.expert_layer  # raises where the experts held do not lie inside the router's width

    # ---- the description the layer loop, the engine and the cache manager read
    def init_params(self, key):
        return init_params(self, key)

    @property
    def layer_kinds(self) -> tuple:
        return tuple(kind for l, t in enumerate(self.layer_types) for kind in (KINDS[t], "mlp" if l < self.num_dense_layers else "moe"))

    @property
    def mixers(self) -> dict:
        """kind -> its scope in a profile and its two forms (``models/hybrid.Mixer``), each with the
        sandwich's second norm on its output."""
        dt = jnp.dtype(self.dtype)

        def post(w, y):
            return post_norm(self, w, y)

        def attention(kind):
            def seq(w, xn, ctx):
                y, k, v = attn_seq(w, xn.astype(dt), self, kind, ctx.mesh, ctx.skippable)
                return post(w, y), dict(zip(ENTRIES[kind], (k, v)))

            return Mixer(kind, seq, lambda w, xn, cache, ctx: (post(w, attn_step(w, xn.astype(dt), cache, ctx, self, kind)), None))

        def experts_seq(w, xn, ctx):
            y, counters = experts.moe_seq(w, xn, ctx.lengths, self, stacked=ctx.stacked)
            return post(w, y), {ROUTING: counters}

        def experts_step(w, xn, cache, ctx):
            y, stats = experts.moe_step(w, xn, ctx.active, self, ctx.stacked)
            return post(w, y), stats

        return {"swa": attention("swa"), "attn": attention("attn"),
                "mlp": Mixer("mlp", lambda w, xn, ctx: (post(w, ffn(w, xn.astype(dt), ctx.skippable, ctx.stacked)), {}),
                             lambda w, xn, cache, ctx: (post(w, ffn(w, xn.astype(dt))), None)),
                "moe": Mixer("moe", experts_seq, experts_step, True)}

    def norm(self, x, w):
        return rms_norm(x, w, self.rms_eps)

    @property
    def expert_layer(self) -> ExpertLayer:
        return ExpertLayer(num_experts=self.num_experts, top_k=self.num_experts_per_tok, expert_start=self.expert_start,
                           local_experts=self.num_local_experts, score="sigmoid", bias=True, norm_topk=self.route_norm,
                           scale=self.route_scale, act="swiglu", shared_gated=False)

    @property
    def stream_scales(self) -> tuple:
        return (math.sqrt(self.hidden_size) if self.mup_enabled else 1.0), 1.0, 1.0

    @property
    def hd(self) -> int:
        return self.head_dim

    @property
    def n_routed_experts(self) -> int:
        """The router's width, under the name ``_anchor_routing`` reads it by."""
        return self.num_experts

    @property
    def local_experts(self) -> int:
        return self.expert_layer.held

    def flash_calls(self, length: int) -> dict:
        return {self.hd: self.count("attn") + self.count("swa")}

    @property
    def stream_dtype(self):
        return jnp.dtype(self.dtype)

    def cache_spec(self) -> dict:
        """kind -> {name: (shape, dtype, "position" | "sequence")}: keys and values by head in every
        attention layer, under names of their own in a window layer (``ring_entries``)."""
        kv = (self.num_kv_heads, self.hd)
        return {kind: {name: (kv, self.dtype, "position") for name in names} for kind, names in ENTRIES.items()} | {"mlp": {}, "moe": {}}

    def ring_entries(self) -> dict:
        return {name: self.sliding_window for name in ENTRIES["swa"]} if self.count("swa") else {}

    def _window_pairs(self, n: int) -> int:
        """(query, key) pairs inside the window over a sequence of ``n`` positions: sum of min(i + 1, W)."""
        W = min(self.sliding_window, n)
        return W * (W + 1) // 2 + (n - W) * W

    def prefill_counters(self, batch: int, length: int, lengths=()) -> dict:
        """(query, key) pairs that the window layers' mathematics needs for prompts of the TRUE ``lengths``."""
        return {"swa_pairs": self.count("swa") * sum(self._window_pairs(int(n)) for n in lengths)}

    def prefill_rows_live(self, length: int, lengths) -> int:
        """The dense layer is ``glm4_moe_lite.ffn`` over ``ops/layers.live_slabs`` under the kind ``mlp``."""
        return live_rows(length, lengths) if self.count("mlp") else len(lengths) * length

    def routed_counters(self, rows: int, routing) -> dict:
        """``moe_expert_fetches`` of ONE prefill program over ``rows`` positions (as padded), from its
        routing counters on the host (``routing``: ``hybrid_runner.PREFILL_STATS``, means over the
        expert layers) and its shapes: how many times a held expert's matrices were brought in. The
        loop (``experts._grouped.one_block``) fetches them for every block in use,
        ``moe_rows_computed`` over the block's height; the kernel (``ops/grouped_experts.py``) once a
        run of an expert's blocks, ``experts_hit`` (of a batch that goes through in slabs the hit
        experts are counted once for all slabs: a floor there)."""
        if not self.count("moe"):
            return {}
        shape = jax.ShapeDtypeStruct((self.count("moe"),) + _shapes(self)["moe"]["w_up"][0], jnp.dtype(self.dtype))
        block, kernel = experts.blocks_plan(self.expert_layer, experts._call_rows(rows), [shape] * 3)
        return {"moe_expert_fetches": round(float(routing[0] if kernel else routing[2] / block), 3)}

    def decode_counters(self, positions) -> dict:
        """Rows of their rings that a decode step's window layers read for lanes holding
        ``positions`` (the new token's among them): min(position + 1, W) a lane and layer."""
        return {"swa_rows_read": self.count("swa") * sum(min(int(n), self.sliding_window) for n in positions)}

    def num_params(self) -> int:
        """Parameters held here (the chip's share of experts and vocabulary)."""
        n = 2 * self.vocab_size * self.hidden_size + self.hidden_size
        for g, group in _shapes(self).items():
            n += self.count(g) * sum(math.prod(shape) for shape, _ in group.values())
        return n + self.count("moe") * self.num_experts  # the expert bias

    @staticmethod
    def tiny(**kw):
        base = dict(
            vocab_size=256, hidden_size=64, num_hidden_layers=5, num_dense_layers=1,
            layer_types=("sliding_attention",) * 4 + ("full_attention",), sliding_window=16, num_heads=6, num_kv_heads=2, head_dim=16,
            intermediate_size=96, num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32, residual_rescale_layers=10,
            router_bias_init=0.1, max_seq_len=128, dtype="float32",
        )
        return AfmoeConfig(**{**base, **kw})


# ------------------------------------------------------------------ parameters
def _shapes(c: AfmoeConfig) -> dict:
    """group -> {name: (shape of one layer, fan_in or fill)}: matrices are N(0, fan_in^-1/2), the
    norms before a sub-block and on the heads 1, the norms after one 1/sqrt(N). An expert's three
    matrices are stored [F, H]."""
    H, q, kv, g = c.hidden_size, c.num_heads * c.hd, c.num_kv_heads * c.hd, float(c.residual_rescale_layers) ** -0.5
    F, Fm, E, El = c.intermediate_size, c.moe_intermediate_size, c.num_experts, c.local_experts
    attention = {"norm": ((H,), 1.0), "wq": ((H, q), H), "wk": ((H, kv), H), "wv": ((H, kv), H), "wg": ((H, q), H), "wo": ((q, H), q),
                 "q_norm": ((c.hd,), 1.0), "k_norm": ((c.hd,), 1.0), "post_norm": ((H,), g)}
    return {
        "swa": attention, "attn": attention,
        "mlp": {"norm": ((H,), 1.0), "w_gate": ((H, F), H), "w_up": ((H, F), H), "w_down": ((F, H), F), "post_norm": ((H,), g)},
        "moe": {"norm": ((H,), 1.0), "router": ((H, E), H), "w_gate": ((El, Fm, H), H), "w_up": ((El, Fm, H), H),
                "w_down": ((El, Fm, H), Fm), "shared_gate": ((H, Fm), H), "shared_up": ((H, Fm), H),
                "shared_down": ((Fm, H), Fm), "post_norm": ((H,), g)},
    }


def init_params(config: AfmoeConfig, key):
    """Weights from a seed, stacked by layer kind (the module's docstring says what is drawn)."""
    c, dt = config, jnp.dtype(config.dtype)
    keys = iter(jax.random.split(key, 64))
    params = init_stacked(_shapes(c), c.count, keys, dt)
    embed = jax.random.normal(next(keys), (c.vocab_size, c.hidden_size), jnp.float32)
    if c.count("moe"):
        params["moe"]["router_bias"] = c.router_bias_init * jax.random.normal(next(keys), (c.count("moe"), c.num_experts), jnp.float32)
        if c.router_anchor:
            params["moe"]["router"], embed = _anchor_routing(c, next(keys), embed, dt)
    params["embed"] = (embed / c.stream_scales[0]).astype(dt)
    params["unembed"] = (jax.random.normal(next(keys), (c.hidden_size, c.vocab_size), jnp.float32)
                         * c.hidden_size ** -0.5).astype(dt)
    params["final_norm"] = jnp.ones((c.hidden_size,), dt)
    return params


def param_logical_axes(config: AfmoeConfig):
    """Logical axes for ``parallel/mesh.ShardingRules`` (vocabulary, experts and heads are the
    axes a mesh could split; the serving engine refuses a mesh for this model today)."""
    attention = {"norm": (None,), "wq": ("embed", "heads"), "wk": ("embed", "kv_heads"), "wv": ("embed", "kv_heads"), "wg": ("embed", "heads"),
                 "wo": ("heads", "embed"), "q_norm": (None,), "k_norm": (None,), "post_norm": (None,)}
    lead = {"swa": attention, "attn": attention,
            "mlp": {"norm": (None,), "w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"), "w_down": ("mlp", "embed"), "post_norm": (None,)},
            "moe": {"norm": (None,), "router": ("embed", None), "router_bias": (None,), "w_gate": ("expert", "mlp", "embed"),
                    "w_up": ("expert", "mlp", "embed"), "w_down": ("expert", "mlp", "embed"), "shared_gate": ("embed", "mlp"),
                    "shared_up": ("embed", "mlp"), "shared_down": ("mlp", "embed"), "post_norm": (None,)}}
    axes = {g: {n: (None,) + a for n, a in group.items()} for g, group in lead.items() if config.count(g)}
    axes.update(embed=("vocab", "embed"), unembed=("embed", "vocab"), final_norm=(None,))
    return axes


# ------------------------------------------------------------------ the sandwich's second norm
def post_norm(c: AfmoeConfig, w, y):
    """A sub-block's output y [.., H] normed before it joins the stream, under the sub-block's own scope."""
    return c.norm(y, w["post_norm"])


# ------------------------------------------------------------------ attention: window and full, normed and gated
def head_norms(w, q, k, c: AfmoeConfig):
    """q [.., nh, hd] and k [.., kv, hd] normed over a head's dimensions, ONE weight [hd] each for all heads."""
    return c.norm(q, w["q_norm"]), c.norm(k, w["k_norm"])


def qkvg(w, xn, positions, c: AfmoeConfig, kind: str):
    """xn [B,T,H], positions [T] or [B,T] -> q [B,nh,T,hd], k, v [B,kv,T,hd] and the gate's
    logits [B,T,nh*hd]: q and k normed over a head (one weight for all heads), then rotated
    (rotate-half over all of a head) where the layer has a window."""
    B, T, _ = xn.shape
    q = jnp.dot(xn, w["wq"]).reshape(B, T, c.num_heads, c.hd)
    k = jnp.dot(xn, w["wk"]).reshape(B, T, c.num_kv_heads, c.hd)
    v = jnp.dot(xn, w["wv"]).reshape(B, T, c.num_kv_heads, c.hd).transpose(0, 2, 1, 3)
    q, k = (a.transpose(0, 2, 1, 3) for a in head_norms(w, q, k, c))
    if kind == "swa":
        cos, sin = rotary_embedding(positions, c.hd, c.rope_theta)
        q, k = (apply_rope(a.astype(jnp.float32), cos, sin).astype(a.dtype) for a in (q, k))
    with scope(kind + ".gate"):
        gate = jnp.dot(xn, w["wg"])
    return q, k, v, gate


def gated_out(w, o, gate, kind: str, dtype):
    """``(o * sigmoid(gate)) W_o``: the product in float32 under the gate's own scope, o [.., nh*hd]."""
    with scope(kind + ".gate"):
        a = (o.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(dtype)
    return jnp.dot(a, w["wo"])


def attn_seq(w, xn, c: AfmoeConfig, kind: str, mesh=None, lengths=None):
    """Causal grouped-query attention over a padded sequence, positions 0..T-1; a window layer
    rotates and reads the last ``sliding_window`` keys. -> (out [B,T,H] before its post-norm, k, v
    [B,T,kv,hd] as the cache keeps them: k normed and rotated). ``lengths`` [B]: the true lengths,
    where the kernel may skip what lies past them (``SeqCtx.skippable``)."""
    B, T, _ = xn.shape
    q, k, v, gate = qkvg(w, xn, jnp.arange(T, dtype=jnp.int32), c, kind)
    o = flash_attention_on_mesh(q, k, v, mesh, c.attention_impl, window=c.sliding_window if kind == "swa" else None, lengths=lengths)
    o = o.transpose(0, 2, 1, 3).reshape(B, T, c.num_heads * c.hd)
    return gated_out(w, o, gate, kind, xn.dtype), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)


def attn_step(w, xn, cache, ctx, c: AfmoeConfig, kind: str):
    """One token a lane: xn [B,H] against what its lane holds in this layer: every position of a
    full layer, the last ``sliding_window`` of a window layer, in its ring."""
    names = ENTRIES[kind]
    q, k, v, gate = qkvg(w, xn[:, None], ctx.lengths[:, None], c, kind)
    cache.write(names[0], k[:, :, 0])
    cache.write(names[1], v[:, :, 0])
    o = attend_slot(q[:, :, 0], cache, ctx, c.num_kv_heads, names, DECODE_KERNEL[kind])
    return gated_out(w, o, gate[:, 0], kind, xn.dtype)
