"""SmallThinker decoder (``model_name`` ``smallthinker_*``): sliding-window attention with RoPE
three layers in four, full attention WITHOUT positions the fourth, and in every layer ReGLU
experts with no shared one, chosen by a router that reads the stream BEFORE attention.

A sixth DESCRIPTION over the one layer loop (``models/hybrid.py``) and the one expert layer
(``models/experts.py``). Every published decoder layer is two residual sub-blocks over
``N(x) = w * x / sqrt(mean(x²) + eps)`` in float32, no bias and no query-key norm anywhere:

1. ``h = N_1(x)``; the ROUTING of the layer's experts is decided here, on ``h``:
   ``logits = h W_r`` in float32, the top k logits, ``p = softmax`` over those k
   (``moe_primary_router_apply_softmax``). That equals a softmax over ALL experts renormalised
   over the chosen k, ``exp(l_m) / Z`` over ``sum_chosen exp(l) / Z``: the ``Z`` cancels, and the
   top k of a softmax are the top k of its logits. So ``experts.route`` with
   ``score="softmax", norm_topk=True`` IS the published router, and is what runs
   (``tests/test_smallthinker.py`` holds the identity).
2. attention on the same ``h``: ``num_heads`` query heads over ``num_kv_heads`` key-value heads.
   A WINDOW layer (``sliding_window_layout[l] == 1``; kind ``swa``) rotates q and k (rotate-half
   over all of a head's dimensions, ``rope_theta``) and query i reads keys ``i - W < j <= i``; a
   GLOBAL layer (kind ``attn``) rotates nothing and reads every ``j <= i``: position reaches it
   through the window layers alone. ``x' = x + W_o concat(heads)``.
3. ``u = N_2(x')``; ``x'' = x' + sum_m p_m W_down[e_m] (relu(W_gate[e_m] u) * W_up[e_m] u)`` with
   the routing of step 1: the experts read ``u``, the router read ``h``.

So the loop walks ``2 x num_hidden_layers`` sub-blocks of three kinds, ``attn | swa`` then ``moe``
for each layer, and an attention sub-block HANDS the routing it made to the expert sub-block
after it (``HybridDescription.handed``, ``Mixer.hands``): expert ids and weights, [.., k] int32 and
float32 a token. The router's weights stand with the attention kinds (where it runs), under the
scope ``moe.route`` (whose it is). What is kept per position: a global layer's ``k`` and ``v`` for
every position; a window layer's ``k_w`` and ``v_w`` in a RING of the last W positions
(``ring_entries``; ``llm/kv_cache.py`` says how it is filled, ``hybrid.attend_slot`` how it is
read). Keys are cached after their rotation.

Precision: weights, stream, caches and matmul operands in the weights' dtype (bfloat16 as
published), accumulation float32; norms, the router and the softmaxes float32. Not here: the
"secondary experts" the family's description mentions (the published config carries primary
experts only).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ray_tpu.models import experts
from ray_tpu.models.experts import ExpertLayer
from ray_tpu.models.hybrid import ROUTING, HybridDescription, Mixer, attend_slot, forward, init_stacked, loss_fn  # noqa: F401 - the shared forward and loss, as the harness's family asks for them
from ray_tpu.models.nemotron_h import _anchor_routing  # one orthogonal matrix for all layers' routers: the same scoring of a token id, the same reason
from ray_tpu.ops import slot_attention
from ray_tpu.ops.flash_attention import flash_attention_on_mesh
from ray_tpu.ops.layers import apply_rope, rms_norm, rotary_embedding
from ray_tpu.util.profiling import scoped

# by attention kind: the entries of a layer's keys and values in the cache, and the decode kernel's name in a trace
ENTRIES = {"attn": ("k", "v"), "swa": ("k_w", "v_w")}
DECODE_KERNEL = {"attn": slot_attention.KERNEL, "swa": "window_decode_attention"}


@dataclass(frozen=True)
class SmallThinkerConfig(HybridDescription):
    vocab_size: int = 151936
    hidden_size: int = 2560
    num_hidden_layers: int = 52  # decoder layers HELD: each an attention sub-block and an expert sub-block
    sliding_window_layout: tuple = (0, 1, 1, 1) * 13  # a held layer's 1: a window layer; 0: global
    rope_layout: tuple = (0, 1, 1, 1) * 13  # a held layer's 1: q and k are rotated
    sliding_window_size: int = 4096
    num_heads: int = 28
    num_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1.5e6
    # moe: every expert is held, none is shared
    n_routed_experts: int = 64
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 768
    rms_eps: float = 1e-6
    # init only: every sub-block's projection back onto the stream is drawn 1/sqrt(this) smaller; 1 turns it off
    residual_rescale_layers: int = 104
    # init only: > 0 anchors every token id to its own top-k experts in every layer by this margin
    # in the router's logits (``models/nemotron_h._anchor_routing``)
    router_anchor: float = 0.0
    max_seq_len: int = 16384
    dtype: str = "bfloat16"
    remat: bool = False
    attention_impl: str = "auto"  # auto | pallas | xla

    def __post_init__(self):
        if len(self.sliding_window_layout) != self.num_hidden_layers or tuple(self.rope_layout) != tuple(self.sliding_window_layout):
            raise ValueError("sliding_window_layout and rope_layout name every held layer, and a layer rotates where it has a window, as published")
        if self.num_heads % self.num_kv_heads or self.head_dim % 2:
            raise ValueError("key-value heads divide the query heads, and a head is rotated in pairs")

    # ---- the description the layer loop, the engine and the cache manager read
    def init_params(self, key):
        return init_params(self, key)

    @property
    def layer_kinds(self) -> tuple:
        return tuple(kind for window in self.sliding_window_layout for kind in ("swa" if window else "attn", "moe"))

    @property
    def handed(self) -> dict:
        """What an attention sub-block hands the expert sub-block after it, a token: the routing."""
        k = self.num_experts_per_tok
        return {"experts": ((k,), "int32"), "weights": ((k,), "float32")}

    @property
    def mixers(self) -> dict:
        """kind -> its scope in a profile and its two forms (``models/hybrid.Mixer``)."""
        dt = jnp.dtype(self.dtype)

        def attention(kind):
            def seq(w, xn, ctx):
                y, k, v = attn_seq(w, xn.astype(dt), self, kind == "swa", ctx.mesh, ctx.skippable)
                return y, dict(zip(ENTRIES[kind], (k, v))), routing(w, xn, self)

            def step(w, xn, cache, ctx):
                return attn_step(w, xn.astype(dt), cache, ctx, self, kind), None, routing(w, xn, self)

            return Mixer(kind, seq, step, hands=True)

        def experts_seq(w, xn, ctx):
            y, counters = experts.moe_seq(w, xn, ctx.lengths, self, stacked=ctx.stacked, routing=(ctx.handed["experts"], ctx.handed["weights"]))
            return y, {ROUTING: counters}

        def experts_step(w, xn, cache, ctx):
            return experts.moe_step(w, xn, ctx.active, self, ctx.stacked, routing=(ctx.handed["experts"], ctx.handed["weights"]))

        return {"attn": attention("attn"), "swa": attention("swa"), "moe": Mixer("moe", experts_seq, experts_step, True)}

    def norm(self, x, w):
        return rms_norm(x, w, self.rms_eps)

    @property
    def expert_layer(self) -> ExpertLayer:
        return ExpertLayer(num_experts=self.n_routed_experts, top_k=self.num_experts_per_tok, score="softmax", norm_topk=True,
                           act="reglu", shared=False)

    @property
    def hd(self) -> int:
        return self.head_dim

    def flash_calls(self, length: int) -> dict:
        return {self.hd: self.count("attn") + self.count("swa")}

    @property
    def stream_dtype(self):
        return jnp.dtype(self.dtype)

    def cache_spec(self) -> dict:
        """kind -> {name: (shape, dtype, "position" | "sequence")}: keys and values by head in every
        attention layer, under names of their own in a window layer (``ring_entries``)."""
        kv = (self.num_kv_heads, self.hd)
        return {kind: {name: (kv, self.dtype, "position") for name in names} for kind, names in ENTRIES.items()} | {"moe": {}}

    def ring_entries(self) -> dict:
        return {name: self.sliding_window_size for name in ENTRIES["swa"]} if self.count("swa") else {}

    def _window_pairs(self, n: int) -> int:
        """(query, key) pairs inside the window over a sequence of ``n`` positions: sum of min(i + 1, W)."""
        W = min(self.sliding_window_size, n)
        return W * (W + 1) // 2 + (n - W) * W

    def prefill_counters(self, batch: int, length: int, lengths=()) -> dict:
        """(query, key) pairs that the window layers' mathematics needs for prompts of the TRUE ``lengths``."""
        return {"swa_pairs": self.count("swa") * sum(self._window_pairs(int(n)) for n in lengths)}

    def decode_counters(self, positions) -> dict:
        """Rows of their rings that a decode step's window layers read for lanes holding
        ``positions`` (the new token's among them): min(position + 1, W) a lane and layer."""
        return {"swa_rows_read": self.count("swa") * sum(min(int(n), self.sliding_window_size) for n in positions)}

    def num_params(self) -> int:
        """Parameters held here."""
        n = 2 * self.vocab_size * self.hidden_size + self.hidden_size
        for g, group in _shapes(self).items():
            n += self.count(g) * sum(math.prod(shape) for shape, _ in group.values())
        return n

    @staticmethod
    def tiny(**kw):
        base = dict(
            vocab_size=256, hidden_size=64, num_hidden_layers=8, sliding_window_layout=(0, 1, 1, 1) * 2, rope_layout=(0, 1, 1, 1) * 2,
            sliding_window_size=16, num_heads=6, num_kv_heads=2, head_dim=16, n_routed_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=32, residual_rescale_layers=16, max_seq_len=128, dtype="float32",
        )
        return SmallThinkerConfig(**{**base, **kw})


# ------------------------------------------------------------------ parameters
def _shapes(c: SmallThinkerConfig) -> dict:
    """group -> {name: (shape of one layer, fan_in or fill)}: matrices are N(0, fan_in^-1/2), the
    projections back onto the residual stream 1/sqrt(N) smaller, norms 1. An expert's three
    matrices are stored [F, H]; a layer's router stands with its attention sub-block, which runs it."""
    H, N, q, kv = c.hidden_size, c.residual_rescale_layers, c.num_heads * c.hd, c.num_kv_heads * c.hd
    F, E = c.moe_intermediate_size, c.n_routed_experts
    attention = {"norm": ((H,), 1.0), "wq": ((H, q), H), "wk": ((H, kv), H), "wv": ((H, kv), H), "wo": ((q, H), q * N), "router": ((H, E), H)}
    return {"attn": attention, "swa": attention,
            "moe": {"norm": ((H,), 1.0), "w_gate": ((E, F, H), H), "w_up": ((E, F, H), H), "w_down": ((E, F, H), F * N)}}


def init_params(config: SmallThinkerConfig, key):
    """Weights from a seed, stacked by layer kind."""
    c, dt = config, jnp.dtype(config.dtype)
    keys = iter(jax.random.split(key, 64))
    params = init_stacked(_shapes(c), c.count, keys, dt)
    embed = jax.random.normal(next(keys), (c.vocab_size, c.hidden_size), jnp.float32)
    if c.router_anchor:
        routers, embed = _anchor_routing(c, next(keys), embed, dt)  # [layers, H, E], in the layers' order
        for kind in ("attn", "swa"):
            if c.count(kind):
                params[kind]["router"] = routers[jnp.asarray([l for l, w in enumerate(c.sliding_window_layout) if bool(w) == (kind == "swa")])]
    params["embed"] = embed.astype(dt)
    params["unembed"] = (jax.random.normal(next(keys), (c.hidden_size, c.vocab_size), jnp.float32)
                         * c.hidden_size ** -0.5).astype(dt)
    params["final_norm"] = jnp.ones((c.hidden_size,), dt)
    return params


def param_logical_axes(config: SmallThinkerConfig):
    """Logical axes for ``parallel/mesh.ShardingRules`` (vocabulary, experts and heads are the
    axes a mesh could split; the serving engine refuses a mesh for this model today)."""
    attention = {"norm": (None,), "wq": ("embed", "heads"), "wk": ("embed", "kv_heads"), "wv": ("embed", "kv_heads"),
                 "wo": ("heads", "embed"), "router": ("embed", None)}
    lead = {"attn": attention, "swa": attention,
            "moe": {"norm": (None,), "w_gate": ("expert", "mlp", "embed"), "w_up": ("expert", "mlp", "embed"), "w_down": ("expert", "mlp", "embed")}}
    axes = {g: {n: (None,) + a for n, a in group.items()} for g, group in lead.items() if config.count(g)}
    axes.update(embed=("vocab", "embed"), unembed=("embed", "vocab"), final_norm=(None,))
    return axes


# ------------------------------------------------------------------ the router, before attention
def routing(w, xn, c: SmallThinkerConfig) -> dict:
    """The layer's routing from the attention sub-block's normed input xn [.., H], as it comes:
    what the sub-block hands on (``SmallThinkerConfig.handed``)."""
    idx, wt = scoped("moe.route", experts.route)(w, xn.reshape(-1, xn.shape[-1]), c)
    return {"experts": idx.reshape(xn.shape[:-1] + idx.shape[-1:]), "weights": wt.reshape(xn.shape[:-1] + wt.shape[-1:])}


# ------------------------------------------------------------------ attention: window and global
def qkv(w, xn, positions, c: SmallThinkerConfig, rotates: bool):
    """xn [B,T,H], positions [T] or [B,T] -> q [B,nh,T,hd], k, v [B,kv,T,hd]; q and k rotated
    (rotate-half over all of a head) where the layer rotates."""
    B, T, _ = xn.shape
    q = jnp.dot(xn, w["wq"]).reshape(B, T, c.num_heads, c.hd).transpose(0, 2, 1, 3)
    k = jnp.dot(xn, w["wk"]).reshape(B, T, c.num_kv_heads, c.hd).transpose(0, 2, 1, 3)
    v = jnp.dot(xn, w["wv"]).reshape(B, T, c.num_kv_heads, c.hd).transpose(0, 2, 1, 3)
    if rotates:
        cos, sin = rotary_embedding(positions, c.hd, c.rope_theta)
        q, k = (apply_rope(a.astype(jnp.float32), cos, sin).astype(a.dtype) for a in (q, k))
    return q, k, v


def attn_seq(w, xn, c: SmallThinkerConfig, window: bool, mesh=None, lengths=None):
    """Causal grouped-query attention over a padded sequence, positions 0..T-1; a window layer
    rotates and reads the last ``sliding_window_size`` keys. -> (out [B,T,H], k, v [B,T,kv,hd] as
    the cache keeps them: k rotated). ``lengths`` [B]: the true lengths, where the kernel may skip
    what lies past them (``SeqCtx.skippable``)."""
    B, T, _ = xn.shape
    q, k, v = qkv(w, xn, jnp.arange(T, dtype=jnp.int32), c, window)
    o = flash_attention_on_mesh(q, k, v, mesh, c.attention_impl, window=c.sliding_window_size if window else None, lengths=lengths)
    return jnp.dot(o.transpose(0, 2, 1, 3).reshape(B, T, c.num_heads * c.hd), w["wo"]), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)


def attn_step(w, xn, cache, ctx, c: SmallThinkerConfig, kind: str):
    """One token a lane: xn [B,H] against what its lane holds in this layer: every position of a
    global layer, the last ``sliding_window_size`` of a window layer, in its ring."""
    names = ENTRIES[kind]
    q, k, v = qkv(w, xn[:, None], ctx.lengths[:, None], c, kind == "swa")
    cache.write(names[0], k[:, :, 0])
    cache.write(names[1], v[:, :, 0])
    o = attend_slot(q[:, :, 0], cache, ctx, c.num_kv_heads, names, DECODE_KERNEL[kind])
    return jnp.dot(o.astype(xn.dtype), w["wo"])
