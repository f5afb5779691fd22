"""LFM2 decoder with experts (``model_type`` ``lfm2_moe``): gated short convolutions three layers
in four, grouped-query attention with query-key norms at heads 64 wide the fourth, a dense SwiGLU
in the first layers and sigmoid-routed experts chosen with a bias, none shared, in the rest.

A seventh DESCRIPTION over the one layer loop (``models/hybrid.py``) and the one expert layer
(``models/experts.py``). Every published decoder layer is two residual sub-blocks over
``N(x) = w * x / sqrt(mean(x²) + eps)`` in float32, no bias anywhere:
``x' = x + mixer(N_op(x))``, ``x'' = x' + ffn(N_ffn(x'))``; after the last layer one more ``N`` (the
published ``embedding_norm``), then the head, which is the embedding table itself (tied: the
weights hold no ``unembed``, ``hybrid.head``). So the loop walks ``2 x num_hidden_layers``
sub-blocks of four kinds, ``shortconv | attn`` then ``ffn | moe`` for each layer:

- ``shortconv`` (``layer_types[l] == "conv"``; scope ``shortconv``): ``[B, C, u] = h W_in`` (H -> 3H,
  split in that order); ``z_t = sum_j w_j (B * u)_{t-j}`` by channel (depthwise, causal,
  ``conv_L_cache`` taps, no activation; scope ``shortconv.conv``); ``y = (C * z) W_out``. What a
  sequence keeps of such a layer is the convolution's WINDOW alone, its last ``conv_L_cache - 1``
  products ``B * u`` (``short_conv_seq`` / ``short_conv_step`` of ``models/qwen3_next.py``, which
  there serve inside a delta-rule layer beside its matrix state): nothing per position, 8 KB a
  layer and sequence at the published width. The step reads and writes it under ``shortconv.state``.
- ``attn`` (``"full_attention"``; scope ``attn``): ``num_heads`` query heads over ``num_kv_heads``
  key-value heads of ``head_dim`` 64; ``N`` over the 64 of every query head and every key head (one
  weight vector each, shared by the heads), then rotate-half RoPE over all 64, causal softmax scaled
  by 64^-1/2, ``W_o``. Heads of 64 are half a row of the chip's 128 lanes, so the cache keeps a
  position's keys (and values) as ``slot_attention.position_tile`` lays them, two heads a row
  (``(4, 128)`` for 8 x 64: 1,024 B, not the 2,048 B of eight half-empty rows), and the decode step
  reads them with the live-block kernel where they lie (``slot_decode_attention_narrow`` in a trace).
- ``ffn`` (the first ``num_dense_layers`` layers; scope ``ffn``): SwiGLU at ``intermediate_size``.
- ``moe`` (scope ``moe``): ``s = sigmoid(h W_r)`` in float32, the top k of ``s + b`` (``b`` one
  float32 an expert: it chooses and does not weigh), weights ``s_i / (sum of the k + 1e-6)`` times
  ``routed_scaling_factor``; SwiGLU experts, no shared one. ``experts.route`` as GLM and Kimi use it.

Precision: weights, stream, caches and matmul operands in the weights' dtype (bfloat16 as
published), accumulation float32; norms, the router, the convolution's sum and the softmaxes
float32; the products ``B * u`` in the weights' dtype, as the window keeps them.

Initialisation (weights are random from a seed): matrices N(0, fan_in^-1/2), every projection
back onto the stream 1/sqrt(``residual_rescale_layers``) smaller, norms 1, with three exceptions.
``router_anchor`` > 0 anchors every token id to k + 1 experts of its own in every expert layer
(``models/nemotron_h._anchor_routing``, asked for one expert more than a token takes) and the
selection bias is then what chooses the k among them: ``b`` is ``router_bias_range`` times a random
permutation of 0 .. 1 over a layer's experts, all distinct, so that the k + 1 own experts (whose
scores lie within 1e-3 of each other, saturated) are told apart by steps of ``range / (E - 1)``
and the choice by ``s + b`` differs from the choice by ``s`` wherever the token's lowest-biased
own expert is not also its lowest-scored: four times in five. And the final norm's weight is
``+-head_scale / sqrt(mean |embedding row|²)`` with random signs, not 1: under a TIED head the stream,
which still holds its token's embedding row e, would otherwise give that token's own id the logit
sqrt(H) |e| against O(|e|) for every other, every served log-probability would read 0 against the
reference's 0, and no comparison would have teeth. Signs of a norm's weight are a relabelling
of the stream's channels that the embedding table does not share, so the head stays the table and
its logits come out with the spread ``head_scale``.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ray_tpu.models import experts
from ray_tpu.models.experts import ExpertLayer
from ray_tpu.models.glm4_moe_lite import ffn
from ray_tpu.models.hybrid import ROUTING, HybridDescription, Mixer, attend_slot, forward, init_stacked, loss_fn  # noqa: F401 - the shared forward and loss, as the harness's family asks for them
from ray_tpu.models.nemotron_h import _anchor_routing  # one orthogonal matrix for all expert layers' routers: the same scoring, the same reason
from ray_tpu.models.qwen3_next import a_few_at_a_time, short_conv_seq, short_conv_step
from ray_tpu.ops import slot_attention
from ray_tpu.ops.flash_attention import flash_attention_on_mesh
from ray_tpu.ops.layers import apply_rope, rms_norm, rotary_embedding
from ray_tpu.util.profiling import scope

MIXER = {"conv": "shortconv", "full_attention": "attn"}  # a published layer type's mixer kind


@dataclass(frozen=True)
class Lfm2Config(HybridDescription):
    vocab_size: int = 65536
    hidden_size: int = 2048
    num_hidden_layers: int = 40  # decoder layers HELD: each a mixer sub-block and an ffn sub-block
    layer_types: tuple = ("conv", "conv", "full_attention", "conv") * 10  # a held layer's mixer
    num_dense_layers: int = 2  # the first layers' ffn is dense
    intermediate_size: int = 11776
    conv_L_cache: int = 3  # taps of the short convolution; its window is one fewer
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    rope_theta: float = 1e6
    # moe: every expert is held, none is shared
    n_routed_experts: int = 64
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1536
    use_expert_bias: bool = True
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rms_eps: float = 1e-5
    # init only: every sub-block's projection back onto the stream is drawn 1/sqrt(this) smaller; 1 turns it off
    residual_rescale_layers: int = 80
    # init only: > 0 anchors every token id to k + 1 experts of its own in every expert layer by this margin in the
    # router's logits, and the selection bias (all distinct, 0 .. router_bias_range) chooses the k among them
    router_anchor: float = 0.0
    router_bias_range: float = 0.0
    head_scale: float = 1.4  # init only: the spread of the tied head's logits, through the final norm's weight
    max_seq_len: int = 16384
    dtype: str = "bfloat16"
    remat: bool = False
    attention_impl: str = "auto"  # auto | pallas | xla

    def __post_init__(self):
        if len(self.layer_types) != self.num_hidden_layers or set(self.layer_types) - set(MIXER):
            raise ValueError(f"layer_types names every held layer's mixer, one of {sorted(MIXER)}")
        if not 0 <= self.num_dense_layers <= self.num_hidden_layers:
            raise ValueError("num_dense_layers counts some of the num_hidden_layers")
        if self.num_heads % self.num_kv_heads or self.head_dim % 2:
            raise ValueError("key-value heads divide the query heads, and a head is rotated in pairs")

    # ---- the description the layer loop, the engine and the cache manager read
    def init_params(self, key):
        return init_params(self, key)

    @property
    def layer_kinds(self) -> tuple:
        return tuple(kind for l, t in enumerate(self.layer_types) for kind in (MIXER[t], "ffn" if l < self.num_dense_layers else "moe"))

    @property
    def mixers(self) -> dict:
        """kind -> its scope in a profile and its two forms (``models/hybrid.Mixer``)."""
        dt = jnp.dtype(self.dtype)

        def conv_seq(w, xn, ctx):
            y, window = a_few_at_a_time(lambda xn, lengths: shortconv_seq(w, xn, lengths), xn.astype(dt), ctx.lengths)
            return y, {"conv": window}

        def conv_step(w, xn, cache, ctx):
            with scope("shortconv.state"):
                window = cache.read("conv")
            y, window = shortconv_step(w, xn.astype(dt), window)
            with scope("shortconv.state"):
                cache.write("conv", window)
            return y, None

        def attention_seq(w, xn, ctx):
            y, k, v = attn_seq(w, xn.astype(dt), self, ctx.mesh, ctx.skippable)
            return y, {"k": k, "v": v}

        def experts_seq(w, xn, ctx):
            y, counters = experts.moe_seq(w, xn, ctx.lengths, self, stacked=ctx.stacked)
            return y, {ROUTING: counters}

        return {"shortconv": Mixer("shortconv", conv_seq, conv_step),
                "attn": Mixer("attn", attention_seq, lambda w, xn, cache, ctx: (attn_step(w, xn.astype(dt), cache, ctx, self), None)),
                "ffn": Mixer("ffn", lambda w, xn, ctx: (ffn(w, xn.astype(dt), ctx.skippable, ctx.stacked), {}), lambda w, xn, cache, ctx: (ffn(w, xn.astype(dt)), None)),
                "moe": Mixer("moe", experts_seq, lambda w, xn, cache, ctx: experts.moe_step(w, xn, ctx.active, self, ctx.stacked), True)}

    def norm(self, x, w):
        return rms_norm(x, w, self.rms_eps)

    @property
    def expert_layer(self) -> ExpertLayer:
        return ExpertLayer(num_experts=self.n_routed_experts, top_k=self.num_experts_per_tok, score="sigmoid", bias=self.use_expert_bias,
                           norm_topk=self.norm_topk_prob, scale=self.routed_scaling_factor, act="swiglu", shared=False, norm_eps=1e-6)

    @property
    def hd(self) -> int:
        return self.head_dim

    @property
    def stream_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def kv_tile(self) -> tuple:
        """A position's keys (or values) as the cache keeps them: two 64-wide heads a row of 128 lanes."""
        return slot_attention.position_tile(self.num_kv_heads, self.hd)

    def cache_spec(self) -> dict:
        """kind -> {name: (shape, dtype, "position" | "sequence")}: keys and values of every position
        in an attention layer, the convolution's window of a sequence in a convolution layer."""
        return {"attn": {"k": (self.kv_tile, self.dtype, "position"), "v": (self.kv_tile, self.dtype, "position")},
                "shortconv": {"conv": ((self.conv_L_cache - 1, self.hidden_size), self.dtype, "sequence")},
                "ffn": {}, "moe": {}}

    @property
    def flash_width(self) -> int:
        """Columns of ``attn_seq``'s queries, keys and values in the flash call: a head narrower than the 128 lanes
        goes with zeros beside it. The kernel takes a 64-wide operand only through the compiler's copy of it into
        128-lane tiles, and on the chip those copies stood the core idle 4 ms apiece, thirteen a 12,288-position
        prefill (PERF.md section 6, PR 53); padded where it is made, a head's row is written once."""
        return max(self.hd, slot_attention.LANES)

    def flash_calls(self, length: int) -> dict:
        return {self.flash_width: self.count("attn")}

    @property
    def _narrow(self) -> bool:
        return self.kv_tile != (self.num_kv_heads, self.hd)

    def prefill_counters(self, batch: int, length: int, lengths=()) -> dict:
        """Causal (query, key) pairs of the attention layers for prompts of the TRUE ``lengths``, where their heads are narrower than a row."""
        return {"narrow_pairs": self.count("attn") * sum(int(n) * (int(n) + 1) // 2 for n in lengths)} if self._narrow else {}

    def decode_counters(self, positions) -> dict:
        """Positions whose keys and values a decode step's attention layers read for lanes holding ``positions`` (the new token's among them)."""
        return {"narrow_rows_read": self.count("attn") * sum(int(n) for n in positions)} if self._narrow else {}

    def num_params(self) -> int:
        """Parameters held here: the embedding table counts once, the head is the table."""
        n = self.vocab_size * self.hidden_size + self.hidden_size
        for g, group in _shapes(self).items():
            n += self.count(g) * sum(math.prod(shape) for shape, _ in group.values())
        return n + self.count("moe") * self.n_routed_experts * self.use_expert_bias  # the selection bias

    @staticmethod
    def tiny(**kw):
        base = dict(
            vocab_size=256, hidden_size=64, num_hidden_layers=10, layer_types=("conv", "conv", "full_attention", "conv") * 2 + ("conv", "conv"),
            num_dense_layers=2, intermediate_size=96, num_heads=4, num_kv_heads=2, head_dim=64, n_routed_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=32, residual_rescale_layers=20, max_seq_len=128, dtype="float32",
        )
        return Lfm2Config(**{**base, **kw})


# ------------------------------------------------------------------ parameters
def _shapes(c: Lfm2Config) -> dict:
    """group -> {name: (shape of one layer, fan_in or fill)}: matrices are N(0, fan_in^-1/2), the
    projections back onto the residual stream 1/sqrt(N) smaller, norms 1. An expert's three
    matrices are stored [F, H]; a convolution's taps [taps, H], the oldest input's first, the current input's last."""
    H, N, q, kv = c.hidden_size, c.residual_rescale_layers, c.num_heads * c.hd, c.num_kv_heads * c.hd
    F, Fm, E, K = c.intermediate_size, c.moe_intermediate_size, c.n_routed_experts, c.conv_L_cache
    return {
        "shortconv": {"norm": ((H,), 1.0), "in_proj": ((H, 3 * H), H), "conv_w": ((K, H), K), "out_proj": ((H, H), H * N)},
        "attn": {"norm": ((H,), 1.0), "wq": ((H, q), H), "wk": ((H, kv), H), "wv": ((H, kv), H),
                 "q_norm": ((c.hd,), 1.0), "k_norm": ((c.hd,), 1.0), "wo": ((q, H), q * N)},
        "ffn": {"norm": ((H,), 1.0), "w_gate": ((H, F), H), "w_up": ((H, F), H), "w_down": ((F, H), F * N)},
        "moe": {"norm": ((H,), 1.0), "router": ((H, E), H), "w_gate": ((E, Fm, H), H), "w_up": ((E, Fm, H), H), "w_down": ((E, Fm, H), Fm * N)},
    }


def init_params(config: Lfm2Config, key):
    """Weights from a seed, stacked by layer kind: no ``unembed`` (the head is tied); the selection
    bias in float32; the module docstring says what the anchor, the bias and the final norm's weight are."""
    c, dt = config, jnp.dtype(config.dtype)
    keys = iter(jax.random.split(key, 64))
    params = init_stacked(_shapes(c), c.count, keys, dt)
    embed = jax.random.normal(next(keys), (c.vocab_size, c.hidden_size), jnp.float32)
    L, E = c.count("moe"), c.n_routed_experts
    if L and c.use_expert_bias:
        ranks = jax.vmap(lambda k: jax.random.permutation(k, E))(jax.random.split(next(keys), L))
        params["moe"]["router_bias"] = c.router_bias_range * ranks.astype(jnp.float32) / max(E - 1, 1)
    if L and c.router_anchor:
        one_more = dataclasses.replace(c, num_experts_per_tok=c.num_experts_per_tok + 1)
        params["moe"]["router"], embed = _anchor_routing(one_more, next(keys), embed, dt)
    params["embed"] = embed.astype(dt)
    signs = jnp.where(jax.random.bernoulli(next(keys), 0.5, (c.hidden_size,)), 1.0, -1.0)
    row = jnp.sqrt(jnp.mean(jnp.sum(jnp.square(embed), axis=-1)))  # the root mean square length of an embedding row
    params["final_norm"] = (signs * c.head_scale / row).astype(dt)
    return params


def param_logical_axes(config: Lfm2Config):
    """Logical axes for ``parallel/mesh.ShardingRules`` (vocabulary, experts and heads are the
    axes a mesh could split; the serving engine refuses a mesh for this model today)."""
    lead = {"shortconv": {"norm": (None,), "in_proj": ("embed", None), "conv_w": (None, None), "out_proj": (None, "embed")},
            "attn": {"norm": (None,), "wq": ("embed", "heads"), "wk": ("embed", "kv_heads"), "wv": ("embed", "kv_heads"),
                     "q_norm": (None,), "k_norm": (None,), "wo": ("heads", "embed")},
            "ffn": {"norm": (None,), "w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"), "w_down": ("mlp", "embed")},
            "moe": {"norm": (None,), "router": ("embed", None), "router_bias": (None,), "w_gate": ("expert", "mlp", "embed"),
                    "w_up": ("expert", "mlp", "embed"), "w_down": ("expert", "mlp", "embed")}}
    if not config.use_expert_bias:
        del lead["moe"]["router_bias"]
    axes = {g: {n: (None,) + a for n, a in group.items()} for g, group in lead.items() if config.count(g)}
    axes.update(embed=("vocab", "embed"), final_norm=(None,))
    return axes


# ------------------------------------------------------- shortconv: the gated short convolution
def _gates(w, xn):
    """``[B, C, u] = h W_in`` -> (the convolution's input ``B * u``, the output gate ``C``), each [.., H] in the weights' dtype."""
    b, c, u = jnp.split(jnp.dot(xn, w["in_proj"]), 3, axis=-1)
    return b * u, c


def shortconv_seq(w, xn, lengths):
    """xn [B,T,H], lengths [B] -> (out [B,T,H], the window [B,taps-1,H]: the convolution's last inputs AT each true length)."""
    bu, c = _gates(w, xn)
    with scope("shortconv.conv"):
        z, window = short_conv_seq(bu, w["conv_w"], lengths)
        gated = (c.astype(jnp.float32) * z).astype(xn.dtype)
    return jnp.dot(gated, w["out_proj"]), window


def shortconv_step(w, xn, window):
    """One token: xn [B,H], window [B,taps-1,H] -> (out [B,H], the window moved on by one)."""
    bu, c = _gates(w, xn)
    with scope("shortconv.conv"):
        z, window = short_conv_step(window, bu, w["conv_w"])
        gated = (c.astype(jnp.float32) * z).astype(xn.dtype)
    return jnp.dot(gated, w["out_proj"]), window


# ------------------------------------------------------------------ attn: heads of 64, normed and rotated
def qkv(w, xn, positions, c: Lfm2Config):
    """xn [B,T,H], positions [T] or [B,T] -> q [B,nh,T,hd], k, v [B,kv,T,hd]; q and k normalised over
    a head (``N`` with one weight vector for all query heads, one for all key heads), then rotated."""
    B, T, _ = xn.shape
    q = c.norm(jnp.dot(xn, w["wq"]).reshape(B, T, c.num_heads, c.hd), w["q_norm"]).transpose(0, 2, 1, 3)
    k = c.norm(jnp.dot(xn, w["wk"]).reshape(B, T, c.num_kv_heads, c.hd), w["k_norm"]).transpose(0, 2, 1, 3)
    v = jnp.dot(xn, w["wv"]).reshape(B, T, c.num_kv_heads, c.hd).transpose(0, 2, 1, 3)
    cos, sin = rotary_embedding(positions, c.hd, c.rope_theta)
    q, k = (apply_rope(a.astype(jnp.float32), cos, sin).astype(a.dtype) for a in (q, k))
    return q, k, v


def attn_seq(w, xn, c: Lfm2Config, mesh=None, lengths=None):
    """Causal grouped-query attention over a padded sequence, positions 0..T-1 -> (out [B,T,H], k, v
    [B,T,*kv_tile] as the cache keeps them: k normed and rotated, two heads a row). ``lengths`` [B]:
    the true lengths, where the kernel may skip what lies past them (``SeqCtx.skippable``)."""
    B, T, _ = xn.shape
    q, k, v = qkv(w, xn, jnp.arange(T, dtype=jnp.int32), c)
    kept = (a.transpose(0, 2, 1, 3).reshape((B, T) + c.kv_tile) for a in (k, v))
    if c.flash_width != c.hd:  # zeros beside every head: no score changes, and the output's own columns are cut out again
        q, k, v = (jnp.pad(a, ((0, 0), (0, 0), (0, 0), (0, c.flash_width - c.hd))) for a in (q, k, v))
    o = flash_attention_on_mesh(q, k, v, mesh, c.attention_impl, scale=c.hd ** -0.5, lengths=lengths)[..., :c.hd]
    return (jnp.dot(o.transpose(0, 2, 1, 3).reshape(B, T, c.num_heads * c.hd), w["wo"]), *kept)


def attn_step(w, xn, cache, ctx, c: Lfm2Config):
    """One token a lane: xn [B,H] against every position its lane holds in this layer."""
    q, k, v = qkv(w, xn[:, None], ctx.lengths[:, None], c)
    cache.write("k", k[:, :, 0].reshape((-1,) + c.kv_tile))
    cache.write("v", v[:, :, 0].reshape((-1,) + c.kv_tile))
    return jnp.dot(attend_slot(q[:, :, 0], cache, ctx, c.num_kv_heads).astype(xn.dtype), w["wo"])
