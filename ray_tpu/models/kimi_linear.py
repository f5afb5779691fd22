"""Kimi Linear decoder (``model_type`` ``kimi_linear``): Kimi Delta Attention three layers in
four, NoPE multi-head latent attention the fourth, one dense SwiGLU layer, then 256 sigmoid-routed
experts behind a shared one.

A fourth DESCRIPTION over the one layer loop (``models/hybrid.py``) and the one expert layer
(``models/experts.py``). Every published decoder layer is two residual sub-blocks,
``x = x + mixer(N(x))`` then ``x = x + mlp(N(x))``, ``N(x) = w * x / sqrt(mean(x²) + eps)`` in
float32, no bias anywhere. Layer ``i`` (1-indexed, as the published ``linear_attn_config`` counts)
mixes by latent attention where ``i`` is in ``full_attn_layers`` and by Kimi Delta Attention
otherwise; its MLP is dense for the first ``first_k_dense_replace`` layers and routed after. So
the loop walks ``2 x num_hidden_layers`` sub-blocks of four kinds:

- ``kda`` (scope ``kda``), Kimi Delta Attention. One projection gives q, k, v (``kda_num_heads``
  heads of ``kda_head_dim``, no grouping), a second, narrow one gives the inputs of the two
  low-rank gates and b. (q, k, v) pass a causal depthwise convolution of width 4 without bias,
  then SiLU; q and k are L2-normalised per head, q times ``dk^-1/2``. The forget gate is one value
  for EACH key channel of a head: ``g = -exp(A_log[h]) * softplus(W_fb (W_fa x) + dt_bias)``,
  ``beta = sigmoid(W_b x)`` one a head, float32. Per head a state ``S`` [dk, dv] in float32:
  ``S' = Diag(exp(g_t)) S_{t-1}``; ``S_t = S' + beta_t k_t (v_t - S'^T k_t)^T``; ``o_t = S_t^T q_t``.
  Then ``w * (o / sqrt(mean(o²) + eps)) * sigmoid(W_gb (W_ga x))`` per head (a second low-rank
  pair, one gate a value channel) and the output projection. Kept per sequence: ``S`` and the
  convolution's last three inputs. The Gated DeltaNet of ``models/qwen3_next.py`` with a gate that
  is a vector: prefill runs THAT file's chunked rule (``delta_rule_chunked`` says what a gate by
  channel changes), decode its one-position rule.
- ``mla`` (scope ``mla``), latent attention WITHOUT position (``mla_use_nope``): the functions of
  ``models/glm4_moe_lite.py`` with no query latent (``q_lora_rank`` None: ``q = x W_q``) and no
  rotation of the shared key or of any query column: position reaches these layers through the
  ``kda`` layers alone. Kept per position: ``c_kv`` after its norm and the shared key ``k_r`` as
  projected (in whole 128-lane tiles, as that file says). Prefill expands (keys 128 + 64 wide,
  values 128: both padded to the flash kernel's 256), decode absorbs.
- ``ffn`` (scope ``ffn``): SwiGLU at ``intermediate_size``; keeps nothing.
- ``moe`` (scope ``moe``): ``models/experts.py`` with a sigmoid router over all published
  experts, the top k of score + correction bias (one group: no group limit), their own scores
  renormalised and times ``routed_scaling_factor``, SwiGLU experts and one plain shared expert;
  told which experts this chip holds (``expert_start``, ``num_local_experts``).

Precision: as the two files this one borrows from: weights, stream, latent cache and matmul
operands in the weights' dtype (bfloat16 as published), accumulation float32; norms, the router,
the gates, beta, the L2 norms, the state ``S`` and the triangular inverse inside a chunk float32.

Columns of the two KDA projections are laid out flat, ``[q | k | v]`` and ``[f_a | g_a | b]``
(the published ``q_proj``, ``k_proj``, ``v_proj`` and ``f_a_proj``, ``g_a_proj``, ``b_proj`` side
by side, and their three convolutions as one of three times the width): a relabelling that random
weights cannot tell apart.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ray_tpu.models import experts
from ray_tpu.models.experts import ExpertLayer
from ray_tpu.models.glm4_moe_lite import LatentAttention, ffn, mla_seq, mla_step
from ray_tpu.models.hybrid import ROUTING, HybridDescription, Mixer, forward, init_stacked, loss_fn  # noqa: F401 - the shared forward and loss, as the harness's family asks for them
from ray_tpu.models.nemotron_h import _anchor_routing  # one orthogonal matrix for all expert layers' routers: 8 x 256 columns fit 2,304 dimensions
from ray_tpu.models.qwen3_next import a_few_at_a_time, delta_rule_chunked, delta_rule_step, init_decay, short_conv_seq, short_conv_step
from ray_tpu.ops import delta_rule
from ray_tpu.ops.layers import rms_norm
from ray_tpu.util.profiling import scope


@dataclass(frozen=True)
class KimiLinearConfig(LatentAttention, HybridDescription):
    vocab_size: int = 163840  # rows of the embedding and head held here
    hidden_size: int = 2304
    num_hidden_layers: int = 27  # published decoder layers: each a mixer sub-block and an MLP sub-block
    first_k_dense_replace: int = 1  # the first layers' MLP is dense
    intermediate_size: int = 9216
    # which layers (1-indexed, as published) mix by latent attention; the others by KDA
    full_attn_layers: tuple = (4, 8, 12, 16, 20, 24, 27)
    # kda: Kimi Delta Attention
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    conv_kernel: int = 4
    chunk_size: int = 64  # how the rule is blocked over a sequence: not mathematics
    time_step_min: float = 0.001  # init only
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # mla: no query latent, no rotation
    num_heads: int = 32
    head_dim: int = 72  # hidden_size / num_heads, as published; no layer is that wide
    q_lora_rank: int | None = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_use_nope: bool = True
    rope_theta: float = 10000.0  # read only where ``mla_use_nope`` is off
    # moe: the router is num_experts wide whatever is held here
    num_experts: int = 256
    expert_start: int = 0
    num_local_experts: int | None = None  # None: all of them
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 1024
    num_shared_experts: int = 1
    moe_renormalize: bool = True
    routed_scaling_factor: float = 2.446
    rms_eps: float = 1e-5
    # init only: every sub-block's projection back onto the stream is drawn 1/sqrt(this) smaller; 1 turns it off
    residual_rescale_layers: int = 54
    # init only: > 0 anchors every token id to its own top-k experts in every expert layer by this
    # margin in the router's logits (``models/nemotron_h._anchor_routing``)
    router_anchor: float = 0.0
    max_seq_len: int = 4096
    dtype: str = "bfloat16"
    remat: bool = False
    attention_impl: str = "auto"  # auto | pallas | xla

    def __post_init__(self):
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace counts some of the num_hidden_layers")
        if self.num_shared_experts != 1:
            raise ValueError("the expert layer has one shared expert")
        _ = self.expert_layer  # raises where the experts held do not lie inside the router's width

    # ---- the description the layer loop, the engine and the cache manager read
    def init_params(self, key):
        return init_params(self, key)

    @property
    def layer_kinds(self) -> tuple:
        return tuple(kind for i in range(1, self.num_hidden_layers + 1)
                     for kind in ("mla" if i in self.full_attn_layers else "kda", "ffn" if i <= self.first_k_dense_replace else "moe"))

    @property
    def mixers(self) -> dict:
        """kind -> its scope in a profile and its two forms (``models/hybrid.Mixer``)."""
        dt = jnp.dtype(self.dtype)

        def rule_seq(w, xn, ctx):
            y, S, conv = kda_seq(w, xn.astype(dt), ctx.lengths, self, ctx.mesh)
            return y, {"S": S, "conv": conv}

        def rule_step(w, xn, cache, ctx):
            with scope("kda.state"):  # the state's read here, its decay and write in ``kda_step``, its way back below
                S = cache.read("S")
            y, S, conv = kda_step(w, xn.astype(dt), S, cache.read("conv"), self)
            with scope("kda.state"):
                cache.write("S", S)
            cache.write("conv", conv)
            return y, None

        def attention_seq(w, xn, ctx):
            y, c_kv, k_r = mla_seq(w, xn.astype(dt), self, ctx.mesh, ctx.skippable)
            return y, {"c_kv": c_kv, "k_r": k_r}

        def experts_seq(w, xn, ctx):
            y, counters = experts.moe_seq(w, xn, ctx.lengths, self, stacked=ctx.stacked)
            return y, {ROUTING: counters}

        return {"kda": Mixer("kda", rule_seq, rule_step),
                "mla": Mixer("mla", attention_seq, lambda w, xn, cache, ctx: (mla_step(w, xn.astype(dt), cache, ctx, self), None)),
                "ffn": Mixer("ffn", lambda w, xn, ctx: (ffn(w, xn.astype(dt), ctx.skippable, ctx.stacked), {}),
                             lambda w, xn, cache, ctx: (ffn(w, xn.astype(dt)), None)),
                "moe": Mixer("moe", experts_seq, lambda w, xn, cache, ctx: experts.moe_step(w, xn, ctx.active, self, ctx.stacked), True)}

    def norm(self, x, w):
        return rms_norm(x, w, self.rms_eps)

    @property
    def expert_layer(self) -> ExpertLayer:
        return ExpertLayer(num_experts=self.num_experts, top_k=self.num_experts_per_tok, expert_start=self.expert_start,
                           local_experts=self.num_local_experts, score="sigmoid", bias=True, norm_topk=self.moe_renormalize,
                           scale=self.routed_scaling_factor, act="swiglu", shared_gated=False)

    @property
    def hd(self) -> int:
        return self.head_dim

    @property
    def mla_rotates(self) -> bool:
        return not self.mla_use_nope

    @property
    def n_routed_experts(self) -> int:
        """The router's width, under the name ``_anchor_routing`` reads it by."""
        return self.num_experts

    @property
    def local_experts(self) -> int:
        return self.expert_layer.held

    @property
    def kda_dim(self) -> int:
        return self.kda_num_heads * self.kda_head_dim

    @property
    def gate_rank(self) -> int:
        """Width of the bottleneck of the two low-rank gates: the published modules take a head's."""
        return self.kda_head_dim

    @property
    def stream_dtype(self):
        return jnp.dtype(self.dtype)

    def cache_spec(self) -> dict:
        """kind -> {name: (shape, dtype, "position" | "sequence")}: a KDA layer keeps a state a head
        and its convolutions' window per sequence, a latent layer the normed latent and the shared
        key of every position."""
        nh, d = self.kda_num_heads, self.kda_head_dim
        return {"kda": {"S": ((nh, d, d), "float32", "sequence"), "conv": ((self.conv_kernel - 1, 3 * self.kda_dim), self.dtype, "sequence")},
                "mla": self.latent_entries(), "ffn": {}, "moe": {}}

    @property
    def slot_attention_tile(self) -> dict:
        """What ``ops/slot_attention.refusal`` is asked about this description's decode attention."""
        return self.latent_tile

    def prefill_counters(self, batch: int, length: int, lengths=()) -> dict:
        """``kda_chunks`` and ``kda_kernel_chunks`` of one prefill program (``ops/delta_rule.counters``), over the KDA layers."""
        return delta_rule.counters("kda", self.count("kda"), batch, length, self.chunk_size, self.dtype, self.kda_head_dim, self.kda_head_dim)

    def num_params(self) -> int:
        """Parameters held here (the chip's share of experts and vocabulary)."""
        n = 2 * self.vocab_size * self.hidden_size + self.hidden_size
        for g, group in _shapes(self).items():
            n += self.count(g) * sum(math.prod(shape) for shape, _ in group.values())
        # A_log a head and dt_bias a channel; the routers' correction bias
        return n + self.count("kda") * (self.kda_num_heads + self.kda_dim) + self.count("moe") * self.num_experts

    @staticmethod
    def tiny(**kw):
        base = dict(
            vocab_size=256, hidden_size=64, num_hidden_layers=9, intermediate_size=96, kda_num_heads=4, kda_head_dim=8,
            chunk_size=8, num_heads=4, head_dim=16, kv_lora_rank=32, qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=8,
            num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32, residual_rescale_layers=18, max_seq_len=128, dtype="float32",
        )
        return KimiLinearConfig(**{**base, **kw})


# ------------------------------------------------------------------ parameters
def _shapes(c: KimiLinearConfig) -> dict:
    """group -> {name: (shape of one layer, fan_in or fill)}: matrices are N(0, fan_in^-1/2), the
    projections back onto the residual stream 1/sqrt(N) smaller, norms 1. An expert's three
    matrices are stored [F, H]."""
    H, D, r, N = c.hidden_size, c.kda_dim, c.gate_rank, c.residual_rescale_layers
    F, Fm, E, El = c.intermediate_size, c.moe_intermediate_size, c.num_experts, c.local_experts
    return {
        "kda": {"norm": ((H,), 1.0), "in_qkv": ((H, 3 * D), H), "in_low": ((H, 2 * r + c.kda_num_heads), H),
                "conv_w": ((c.conv_kernel, 3 * D), c.conv_kernel), "f_b": ((r, D), r), "g_b": ((r, D), r),
                "gate_norm": ((c.kda_head_dim,), 1.0), "out_proj": ((D, H), D * N)},
        "mla": c.latent_shapes(N),
        "ffn": {"norm": ((H,), 1.0), "w_gate": ((H, F), H), "w_up": ((H, F), H), "w_down": ((F, H), F * N)},
        "moe": {"norm": ((H,), 1.0), "router": ((H, E), H), "w_gate": ((El, Fm, H), H), "w_up": ((El, Fm, H), H),
                "w_down": ((El, Fm, H), Fm * N), "shared_gate": ((H, Fm), H), "shared_up": ((H, Fm), H),
                "shared_down": ((Fm, H), Fm * N)},
    }


def init_params(config: KimiLinearConfig, key):
    """Weights from a seed, stacked by layer kind. ``A_log = log U(1, 16)`` a head and ``dt_bias``
    a channel, the inverse softplus of a log-uniform step in [time_step_min, time_step_max], both
    float32 (the scheme ``models/qwen3_next.init_params`` states: a channel forgets over 1 to 1000
    positions); the router's correction bias 0, in float32."""
    c, dt = config, jnp.dtype(config.dtype)
    keys = iter(jax.random.split(key, 64))
    params = init_stacked(_shapes(c), c.count, keys, dt)
    n = c.count("kda")
    if n:
        params["kda"]["dt_bias"], params["kda"]["A_log"] = init_decay(c, next(keys), next(keys), (n, c.kda_dim), (n, c.kda_num_heads))
    embed = jax.random.normal(next(keys), (c.vocab_size, c.hidden_size), jnp.float32)
    if c.count("moe"):
        params["moe"]["router_bias"] = jnp.zeros((c.count("moe"), c.num_experts), jnp.float32)
        if c.router_anchor:
            params["moe"]["router"], embed = _anchor_routing(c, next(keys), embed, dt)
    params["embed"] = embed.astype(dt)
    params["unembed"] = (jax.random.normal(next(keys), (c.hidden_size, c.vocab_size), jnp.float32)
                         * c.hidden_size ** -0.5).astype(dt)
    params["final_norm"] = jnp.ones((c.hidden_size,), dt)
    return params


def param_logical_axes(config: KimiLinearConfig):
    """Logical axes for ``parallel/mesh.ShardingRules`` (vocabulary, experts and heads are the
    axes a mesh could split; the serving engine refuses a mesh for this model today)."""
    lead = {"kda": {"norm": (None,), "in_qkv": ("embed", None), "in_low": ("embed", None), "conv_w": (None, None), "f_b": (None, None),
                    "g_b": (None, None), "gate_norm": (None,), "out_proj": (None, "embed"), "dt_bias": (None,), "A_log": (None,)},
            "mla": config.latent_axes(),
            "ffn": {"norm": (None,), "w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"), "w_down": ("mlp", "embed")},
            "moe": {"norm": (None,), "router": ("embed", None), "router_bias": (None,), "w_gate": ("expert", "mlp", "embed"),
                    "w_up": ("expert", "mlp", "embed"), "w_down": ("expert", "mlp", "embed"), "shared_gate": ("embed", "mlp"),
                    "shared_up": ("embed", "mlp"), "shared_down": ("mlp", "embed")}}
    axes = {g: {n: (None,) + a for n, a in group.items()} for g, group in lead.items() if config.count(g)}
    axes.update(embed=("vocab", "embed"), unembed=("embed", "vocab"), final_norm=(None,))
    return axes


# --------------------------------------------------------- kda: Kimi Delta Attention
def _kda_inputs(w, conv, low, c: KimiLinearConfig):
    """After the convolution, all float32: q, k [.., nh, dk] L2-normalised (q times dk^-1/2),
    v [.., nh, dk], beta = sigmoid(b) [.., nh] and the log-decay by key channel
    g = -exp(A_log) softplus(W_fb f_a + dt_bias) [.., nh, dk]; ``low`` = [f_a | g_a | b]."""
    nh, dk, r = c.kda_num_heads, c.kda_head_dim, c.gate_rank
    x = jax.nn.silu(conv.astype(jnp.float32))
    lead = x.shape[:-1]
    q, k, v = (x[..., i * c.kda_dim:(i + 1) * c.kda_dim].reshape(*lead, nh, dk) for i in range(3))
    q, k = (a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6) for a in (q, k))
    f = jnp.dot(low[..., :r], w["f_b"], preferred_element_type=jnp.float32) + w["dt_bias"]
    g = -jnp.exp(w["A_log"])[:, None] * jax.nn.softplus(f.reshape(*lead, nh, dk))
    return q * dk ** -0.5, k, v, jax.nn.sigmoid(low[..., 2 * r:].astype(jnp.float32)), g


def _kda_out(w, o, low, c: KimiLinearConfig, dtype):
    """Per head ``w * (o / sqrt(mean(o²) + eps)) * sigmoid(W_gb g_a)`` in float32, then the output projection."""
    r = c.gate_rank
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + c.rms_eps) * w["gate_norm"].astype(jnp.float32)
    gate = jnp.dot(low[..., r:2 * r], w["g_b"], preferred_element_type=jnp.float32)
    y = o * jax.nn.sigmoid(gate.reshape(o.shape))
    return jnp.dot(y.reshape(*y.shape[:-2], c.kda_dim).astype(dtype), w["out_proj"])


def kda_seq(w, xn, lengths, c: KimiLinearConfig, mesh=None):
    """xn [B,T,H], lengths [B] -> (out [B,T,H], S [B,nh,dk,dk] f32, conv [B,K-1,3*nh*dk]): the state
    and the convolutions' window AT each sequence's true length. A batch of more than
    ``qwen3_next.RULE_POSITIONS`` positions goes through a few sequences at a time. ``mesh``: the
    mesh the program runs over, if any (the rule's kernel has to be told)."""
    T = xn.shape[1]
    operand = None if xn.dtype == jnp.float32 else xn.dtype

    def some(xn, lengths):
        low = jnp.dot(xn, w["in_low"])
        conv, window = short_conv_seq(jnp.dot(xn, w["in_qkv"]), w["conv_w"], lengths)
        q, k, v, beta, g = _kda_inputs(w, conv, low, c)
        real = (jnp.arange(T)[None, :] < lengths[:, None])[..., None]
        beta, g = jnp.where(real, beta, 0.0), jnp.where(real[..., None], g, 0.0)  # padding writes nothing and decays nothing
        o, S = delta_rule_chunked(q, k, v[:, :, :, None], g[:, :, :, None], beta[..., None], c.chunk_size, operand, name="kda", mesh=mesh)
        return _kda_out(w, o[:, :, :, 0], low, c, xn.dtype), S[:, :, 0], window

    return a_few_at_a_time(some, xn, lengths)


def kda_step(w, xn, S, conv, c: KimiLinearConfig):
    """One token: xn [B,H], S [B,nh,dk,dk] f32, conv [B,K-1,3*nh*dk] -> (out [B,H], S, conv)."""
    low = jnp.dot(xn, w["in_low"])
    out, window = short_conv_step(conv, jnp.dot(xn, w["in_qkv"]), w["conv_w"])
    q, k, v, beta, g = _kda_inputs(w, out, low, c)
    with scope("kda.state"):
        o, S = delta_rule_step(S, q, k, v, g, beta)
    return _kda_out(w, o, low, c, xn.dtype), S, window
