"""Qwen3-Next hybrid decoder: Gated DeltaNet, gated attention and a 512-expert block.

A second DESCRIPTION over the one layer loop (``models/hybrid.py``) and the one
expert layer (``models/experts.py``). Every published decoder layer is two
residual sub-blocks, ``x = x + mixer(N(x))`` then ``x = x + experts(N(x))``, with
``N(x) = x / sqrt(mean(x²) + eps) * (1 + w)`` in float32 (this family's norm adds
one to its weight); layer ``i`` mixes by full attention when
``(i + 1) % full_attention_interval == 0`` and by Gated DeltaNet otherwise. So the
loop walks ``2 x num_hidden_layers`` sub-blocks of three kinds:

- ``gdn`` (scope ``gdn``), Gated DeltaNet: one projection gives q, k (key heads),
  v and an output gate z (value heads; a key head serves ``nv / nk`` value heads),
  a second gives b and a per value head. (q, k, v) pass a causal depthwise
  convolution of width 4 without bias, then SiLU. ``beta = sigmoid(b)``,
  ``g = -exp(A_log) * softplus(a + dt_bias)``, ``alpha = exp(g)``, in float32.
  q and k are L2-normalised per head, q times ``dk^-1/2``. Per value head a state
  ``S`` [dk, dv] in float32: ``S' = alpha_t S_{t-1}``;
  ``S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T``; ``o_t = S_t^T q_t``. Then
  ``w * (o / sqrt(mean(o²) + eps)) * SiLU(z)`` per head (a plain weight) and the
  output projection. Kept per sequence: ``S`` and the convolution's last three
  inputs. Prefill runs the rule in chunks (``delta_rule_chunked``: on a TPU one
  kernel, ``ops/delta_rule.py``, elsewhere its lines in XLA); decode one
  position at a time, elementwise in float32.
- ``attn`` (scope ``gated_attn``): per head a query and an output gate; q and k
  normalised per head with ``N``; rotate-half RoPE on the FIRST
  ``partial_rotary_factor`` of each head's dimensions; causal softmax attention,
  grouped; ``o * sigmoid(gate)``; output projection. No bias anywhere. Kept per
  position: k (normalised and rotated) and v.
- ``moe`` (scope ``moe``): ``models/experts.py`` with a softmax router over all
  published experts, the top k normalised, SwiGLU experts of three matrices and
  one shared expert gated by ``sigmoid(x . w_sg)``.

Precision: weights, residual stream and matmul operands are the weights' dtype
(bfloat16 as published), accumulation float32; the norms, the router, the decay,
beta, the L2 norms, the state ``S`` and the triangular inverse inside a chunk are
float32. The chunked rule's matmuls take their operands in the weights' dtype
and accumulate in float32, as the published kernels do; in a float32 model they
are float32 at ``highest`` precision throughout.

Columns of the two DeltaNet projections are laid out flat, ``[q | k | v | z]`` and
``[b | a]``, heads in order: a relabelling of the published per-key-head
interleaving, which random weights cannot tell apart. Not here: the checkpoint's
multi-token-prediction head (the published config has no key for it).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ray_tpu.models import experts
from ray_tpu.models.experts import ExpertLayer
from ray_tpu.models.hybrid import ROUTING, HybridDescription, Mixer, attend_slot, forward, init_stacked, loss_fn  # noqa: F401 - the shared forward and loss, as the harness's family asks for them
from ray_tpu.ops import delta_rule
from ray_tpu.ops.flash_attention import flash_attention_on_mesh
from ray_tpu.ops.layers import apply_rope, rotary_embedding
from ray_tpu.util.profiling import scope

SCOPES = {"gdn": "gdn", "attn": "gated_attn", "moe": "moe"}
# positions (batch x padded length) a DeltaNet layer takes through the chunked rule at once: a
# larger prefill goes through a few sequences at a time, so that its float32 temporaries (a dozen
# arrays of 4 KB a position and value-head set) stay under a gigabyte beside the weights
RULE_POSITIONS = 8192
# positions in a sub-block of a chunk, for a gate that differs by key channel (``_pairs_by_channel``)
SUB_BLOCK = delta_rule.SUB_BLOCK


@dataclass(frozen=True)
class Qwen3NextConfig(HybridDescription):
    vocab_size: int = 151936  # rows of the embedding and head held here
    hidden_size: int = 2048
    num_hidden_layers: int = 48  # published decoder layers: each a mixer sub-block and an expert sub-block
    full_attention_interval: int = 4
    # gdn: Gated DeltaNet
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    conv_kernel: int = 4
    chunk_size: int = 64  # how the rule is blocked over a sequence: not mathematics
    time_step_min: float = 0.001  # init only
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # moe: the router is num_experts wide whatever is held here
    num_experts: int = 512
    expert_start: int = 0
    num_local_experts: int | None = None  # None: all of them
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    # attn: gated attention
    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    rms_eps: float = 1e-6
    # init only: every sub-block's projection back onto the stream is drawn 1/sqrt(this) smaller
    # (two sub-blocks a published layer); 1 turns it off
    residual_rescale_layers: int = 96
    # init only: > 0 anchors every token id to its own top-k experts in every expert layer by this
    # margin in the router's logits (``_anchor_routing``)
    router_anchor: float = 0.0
    max_seq_len: int = 4096
    dtype: str = "bfloat16"
    remat: bool = False
    attention_impl: str = "auto"  # auto | pallas | xla

    def __post_init__(self):
        if self.linear_num_value_heads % self.linear_num_key_heads or self.num_heads % self.num_kv_heads:
            raise ValueError("key heads must divide value heads, and KV heads the query heads")
        if self.rot_dim % 2 or not 0 < self.rot_dim <= self.head_dim:
            raise ValueError("partial_rotary_factor must leave an even, non-empty part of a head to rotate")
        _ = self.expert_layer  # raises where the experts held do not lie inside the router's width

    # ---- the description the layer loop, the engine and the cache manager read
    def init_params(self, key):
        return init_params(self, key)

    @property
    def layer_kinds(self) -> tuple:
        return tuple(kind for i in range(self.num_hidden_layers)
                     for kind in ("attn" if (i + 1) % self.full_attention_interval == 0 else "gdn", "moe"))

    @property
    def mixers(self) -> dict:
        """kind -> its scope in a profile and its two forms (``models/hybrid.Mixer``)."""
        dt = jnp.dtype(self.dtype)

        def rule_seq(w, xn, ctx):
            y, S, conv = gdn_seq(w, xn.astype(dt), ctx.lengths, self)
            return y, {"S": S, "conv": conv}

        def rule_step(w, xn, cache, ctx):
            with scope("gdn.state"):  # the state's read here, its decay and write in ``gdn_step``, its way back below
                S = cache.read("S")
            y, S, conv = gdn_step(w, xn.astype(dt), S, cache.read("conv"), self)
            with scope("gdn.state"):
                cache.write("S", S)
            cache.write("conv", conv)
            return y, None

        def attention_seq(w, xn, ctx):
            y, k, v = gated_attn_seq(w, xn.astype(dt), self, ctx.mesh, ctx.skippable)
            return y, {"k": k, "v": v}

        def attention_step(w, xn, cache, ctx):
            q, gate, k, v = gated_attn_qkv(w, xn.astype(dt)[:, None], ctx.lengths[:, None], self)
            cache.write("k", k[:, 0])
            cache.write("v", v[:, 0])
            o = attend_slot(q[:, 0], cache, ctx, self.num_kv_heads)
            return _gated_out(w, o, gate[:, 0], dt), None

        def experts_seq(w, xn, ctx):
            y, counters = experts.moe_seq(w, xn, ctx.lengths, self, stacked=ctx.stacked)
            return y, {ROUTING: counters}

        forms = {"gdn": (rule_seq, rule_step, False), "attn": (attention_seq, attention_step, False),
                 "moe": (experts_seq, lambda w, xn, cache, ctx: experts.moe_step(w, xn, ctx.active, self, ctx.stacked), True)}
        return {kind: Mixer(SCOPES[kind], *forms[kind]) for kind in forms}

    def norm(self, x, w):
        return rms_norm_1p(x, w, self.rms_eps)

    @property
    def expert_layer(self) -> ExpertLayer:
        return ExpertLayer(num_experts=self.num_experts, top_k=self.num_experts_per_tok, expert_start=self.expert_start,
                           local_experts=self.num_local_experts, score="softmax", bias=False, norm_topk=self.norm_topk_prob,
                           scale=1.0, act="swiglu", shared_gated=True)

    @property
    def hd(self) -> int:
        return self.head_dim

    def flash_calls(self, length: int) -> dict:
        return {self.hd: self.count("attn")}

    @property
    def rot_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def stream_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self) -> int:
        return 2 * self.key_dim + self.value_dim

    @property
    def local_experts(self) -> int:
        return self.expert_layer.held

    def cache_spec(self) -> dict:
        """kind -> {name: (shape, dtype, "position" | "sequence")}: what ONE layer of that kind
        keeps in the cache, per position of a sequence or once per sequence."""
        return {
            "attn": {"k": ((self.num_kv_heads, self.hd), self.dtype, "position"),
                     "v": ((self.num_kv_heads, self.hd), self.dtype, "position")},
            "gdn": {"S": ((self.linear_num_value_heads, self.linear_key_head_dim, self.linear_value_head_dim), "float32", "sequence"),
                    "conv": ((self.conv_kernel - 1, self.conv_dim), self.dtype, "sequence")},
            "moe": {},
        }

    def prefill_counters(self, batch: int, length: int, lengths=()) -> dict:
        """``gdn_chunks`` and ``gdn_kernel_chunks`` of one prefill program (``ops/delta_rule.counters``), over the DeltaNet layers."""
        return delta_rule.counters("gdn", self.count("gdn"), batch, length, self.chunk_size, self.dtype, self.linear_key_head_dim, self.linear_value_head_dim)

    def num_params(self) -> int:
        """Parameters held here (the chip's share of experts and vocabulary)."""
        n = 2 * self.vocab_size * self.hidden_size + self.hidden_size
        for g, group in _shapes(self).items():
            n += self.count(g) * sum(math.prod(shape) for shape, _ in group.values())
        return n + self.count("gdn") * 2 * self.linear_num_value_heads

    @staticmethod
    def tiny(**kw):
        base = dict(
            vocab_size=256, hidden_size=64, num_hidden_layers=5, full_attention_interval=2, linear_num_key_heads=2,
            linear_num_value_heads=4, linear_key_head_dim=8, linear_value_head_dim=8, chunk_size=8, num_experts=8,
            num_experts_per_tok=2, moe_intermediate_size=32, shared_expert_intermediate_size=32, num_heads=4,
            num_kv_heads=2, head_dim=16, max_seq_len=128, dtype="float32",
        )
        return Qwen3NextConfig(**{**base, **kw})


# ------------------------------------------------------------------ parameters
def _shapes(c: Qwen3NextConfig) -> dict:
    """group -> {name: (shape of one layer, fan_in or fill)}: matrices are N(0, fan_in^-1/2), the
    projections back onto the residual stream 1/sqrt(N) smaller; the ``1 + w`` norms start at 0,
    the DeltaNet's plain head norm at 1. An expert's three matrices are stored [F, H]."""
    H, kd, vd, nv, C = c.hidden_size, c.key_dim, c.value_dim, c.linear_num_value_heads, c.conv_dim
    F, Fs, E, El = c.moe_intermediate_size, c.shared_expert_intermediate_size, c.num_experts, c.local_experts
    q, kv, N = c.num_heads * c.hd, c.num_kv_heads * c.hd, c.residual_rescale_layers
    return {
        "gdn": {"norm": ((H,), 0.0), "in_qkvz": ((H, 2 * kd + 2 * vd), H), "in_ba": ((H, 2 * nv), H),
                "conv_w": ((c.conv_kernel, C), c.conv_kernel), "gate_norm": ((c.linear_value_head_dim,), 1.0),
                "out_proj": ((vd, H), vd * N)},
        "attn": {"norm": ((H,), 0.0), "wq": ((H, 2 * q), H), "wk": ((H, kv), H), "wv": ((H, kv), H),
                 "q_norm": ((c.hd,), 0.0), "k_norm": ((c.hd,), 0.0), "wo": ((q, H), q * N)},
        "moe": {"norm": ((H,), 0.0), "router": ((H, E), H), "w_gate": ((El, F, H), H), "w_up": ((El, F, H), H),
                "w_down": ((El, F, H), F * N), "shared_gate": ((H, Fs), H), "shared_up": ((H, Fs), H),
                "shared_down": ((Fs, H), Fs * N), "shared_sg": ((H,), H)},
    }


def init_params(config: Qwen3NextConfig, key):
    """Weights from a seed, stacked by layer kind. ``A_log = log U(1, 16)`` and ``dt_bias`` the
    inverse softplus of a log-uniform step in [time_step_min, time_step_max], both float32 (the
    Mamba-2 scheme, which the linear-attention libraries use for this layer too: a head forgets
    over 1 to 1000 positions)."""
    c, dt = config, jnp.dtype(config.dtype)
    keys = iter(jax.random.split(key, 64))
    params = init_stacked(_shapes(c), c.count, keys, dt)
    n, nv = c.count("gdn"), c.linear_num_value_heads
    if n:
        params["gdn"]["dt_bias"], params["gdn"]["A_log"] = init_decay(c, next(keys), next(keys), (n, nv), (n, nv))
    embed = jax.random.normal(next(keys), (c.vocab_size, c.hidden_size), jnp.float32)
    if c.router_anchor:
        params["moe"]["router"], embed = _anchor_routing(c, next(keys), embed, dt)
    params["embed"] = embed.astype(dt)
    params["unembed"] = (jax.random.normal(next(keys), (c.hidden_size, c.vocab_size), jnp.float32)
                         * c.hidden_size ** -0.5).astype(dt)
    params["final_norm"] = jnp.zeros((c.hidden_size,), dt)
    return params


def init_decay(c, k_step, k_A, steps: tuple, heads: tuple):
    """-> (``dt_bias`` of shape ``steps``: the inverse softplus of a log-uniform step in
    [c.time_step_min, c.time_step_max]; ``A_log`` of shape ``heads``: log U(1, 16)), float32."""
    step = jnp.exp(jax.random.uniform(k_step, steps) * (math.log(c.time_step_max) - math.log(c.time_step_min)) + math.log(c.time_step_min))
    step = jnp.maximum(step, c.time_step_floor)
    return step + jnp.log(-jnp.expm1(-step)), jnp.log(jax.random.uniform(k_A, heads, minval=1.0, maxval=16.0))


def _anchor_routing(c: Qwen3NextConfig, key, embed, dt):
    """Routers and an embedding table under which every token id has ITS OWN top-k experts in
    every expert layer, ``router_anchor`` ahead of the rest in the router's logits: what
    ``models/nemotron_h._anchor_routing`` gives, and why, for a model whose expert layers
    together have more experts than the stream has dimensions (12 x 512 against 2048), so that
    their columns cannot all be orthogonal. Here ONE set of ``num_experts`` orthonormal columns
    serves every layer, each layer under its own random permutation: a token id draws k of the
    columns, its embedding row is its N(0, 1) draw plus ``router_anchor`` times their sum, and in
    layer l it is routed to the experts whose columns those are THERE. No cross-talk between
    layers (one orthonormal set), and which experts a token meets is uniform and independent from
    layer to layer. -> (routers [L, H, E] in ``dt``, embed f32)."""
    L, E, H, k = c.count("moe"), c.num_experts, c.hidden_size, c.num_experts_per_tok
    if E > H:
        raise ValueError(f"router_anchor needs {E} orthogonal router columns in {H} dimensions")
    k_q, k_perm, k_pref = jax.random.split(key, 3)
    q, _ = jnp.linalg.qr(jax.random.normal(k_q, (H, E), jnp.float32))
    cols = q.T.astype(dt)  # [E, H]: the code is built from the columns as they are stored
    perms = jax.vmap(lambda kk: jax.random.permutation(kk, E))(jax.random.split(k_perm, L))  # layer l's expert e has column perms[l, e]
    _, pref = jax.lax.top_k(jax.random.uniform(k_pref, (c.vocab_size, E)), k)
    chosen = jnp.sum(jax.nn.one_hot(pref, E, dtype=jnp.float32), axis=1)  # [V, E], k ones a row
    code = jnp.dot(chosen, cols.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
    return jnp.take(cols, perms, axis=0).transpose(0, 2, 1), embed + c.router_anchor * code


def param_logical_axes(config: Qwen3NextConfig):
    """Logical axes for ``parallel/mesh.ShardingRules`` (vocabulary, experts and heads are the
    axes a mesh could split; the serving engine refuses a mesh for this model today)."""
    lead = {"gdn": {"norm": (None,), "in_qkvz": ("embed", None), "in_ba": ("embed", None), "conv_w": (None, None),
                    "gate_norm": (None,), "out_proj": (None, "embed"), "dt_bias": (None,), "A_log": (None,)},
            "attn": {"norm": (None,), "wq": ("embed", "heads"), "wk": ("embed", "kv_heads"), "wv": ("embed", "kv_heads"),
                     "q_norm": (None,), "k_norm": (None,), "wo": ("heads", "embed")},
            "moe": {"norm": (None,), "router": ("embed", None), "w_gate": ("expert", "mlp", "embed"),
                    "w_up": ("expert", "mlp", "embed"), "w_down": ("expert", "mlp", "embed"), "shared_gate": ("embed", "mlp"),
                    "shared_up": ("embed", "mlp"), "shared_down": ("mlp", "embed"), "shared_sg": (None,)}}
    axes = {g: {n: (None,) + a for n, a in group.items()} for g, group in lead.items() if config.count(g)}
    axes.update(embed=("vocab", "embed"), unembed=("embed", "vocab"), final_norm=(None,))
    return axes


def rms_norm_1p(x, w, eps: float):
    """``x / sqrt(mean(x²) + eps) * (1 + w)`` in float32, handed back in x's dtype."""
    x32 = x.astype(jnp.float32)
    out = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (out * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


# ------------------------------------------------------------ gdn: Gated DeltaNet
def _gdn_split(w, xn, c: Qwen3NextConfig):
    """The two projections -> the convolution's input (q, k, v), the output gate z, and (b, a)."""
    qkvz, ba = jnp.dot(xn, w["in_qkvz"]), jnp.dot(xn, w["in_ba"])
    return qkvz[..., :c.conv_dim], qkvz[..., c.conv_dim:], ba


def _gdn_inputs(w, conv, ba, c: Qwen3NextConfig):
    """After the convolution, all float32: q, k [.., nk, dk] L2-normalised (q times dk^-1/2),
    v [.., nv, dv], beta = sigmoid(b) and the log-decay g = -exp(A_log) softplus(a + dt_bias) [.., nv]."""
    nk, nv, dk, dv = c.linear_num_key_heads, c.linear_num_value_heads, c.linear_key_head_dim, c.linear_value_head_dim
    x = jax.nn.silu(conv.astype(jnp.float32))
    lead = x.shape[:-1]
    q, k = (x[..., i * c.key_dim:(i + 1) * c.key_dim].reshape(*lead, nk, dk) for i in (0, 1))
    q, k = (a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6) for a in (q, k))
    v = x[..., 2 * c.key_dim:].reshape(*lead, nv, dv)
    ba = ba.astype(jnp.float32)
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(ba[..., nv:] + w["dt_bias"])
    return q * dk ** -0.5, k, v, jax.nn.sigmoid(ba[..., :nv]), g


def _gdn_out(w, o, z, c: Qwen3NextConfig, dtype):
    """Per head ``w * (o / sqrt(mean(o²) + eps)) * SiLU(z)`` in float32, then the output projection."""
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + c.rms_eps) * w["gate_norm"].astype(jnp.float32)
    y = o * jax.nn.silu(z.astype(jnp.float32).reshape(o.shape))
    return jnp.dot(y.reshape(*y.shape[:-2], c.value_dim).astype(dtype), w["out_proj"])


def _pairs_by_channel(firsts, k, gc, sub: int, es):
    """For each ``a`` of ``firsts``: ``sum_c a_t[c] k_s[c] exp(gc_t[c] - gc_s[c])`` for s <= t inside
    a chunk (0 above the diagonal), for a decay that differs by key channel. a, k [B,nc,C,G,K]; gc
    [B,nc,C,G,R,K] the log of the decay since the chunk's start, falling along C
    -> a tuple of [B,nc,G,R,t,s] float32.

    The decay cannot be applied to the C x C products after the matmul as a scalar one can: it
    has to go INTO the operands, ``a_t exp(gc_t)`` and ``k_s exp(-gc_s)``, and ``exp(-gc_s)``
    leaves float32 inside one chunk (a channel that forgets in one position: 64 x 1.6 = 102 > 88).
    So the chunk is cut into sub-blocks of ``sub`` positions. A pair in two DIFFERENT sub-blocks
    takes its exponents relative to the decay at the later sub-block's start, ``exp(gc_t - ref)``
    and ``exp(ref - gc_s)``: both are <= 1 whatever the gate (gc falls), and the product is one
    matmul a sub-block row. A pair inside ONE sub-block is summed channel by channel with its own
    exponent ``gc_t - gc_s <= 0`` (sub x sub x K elementwise products a sub-block, no matmul): no
    reference point inside a sub-block is safe for every gate."""
    B, nc, C, G, K = k.shape
    R, I = gc.shape[4], C // sub
    blocks = lambda x: x.reshape(B, nc, I, sub, *x.shape[3:])  # noqa: E731
    k_b, gc_b = blocks(k)[:, :, :, :, :, None], blocks(gc)  # [B,nc,I,sub,G,1|R,K]
    # across sub-blocks: relative to the decay as sub-block i starts (0 for the first, which has nothing before it)
    ref = jnp.concatenate([jnp.zeros_like(gc_b[:, :, :1, -1]), gc_b[:, :, :-1, -1]], axis=2)  # [B,nc,I,G,R,K]
    inward = jnp.exp(gc_b - ref[:, :, :, None])
    k_out = k[:, :, None, :, :, None] * jnp.exp(jnp.minimum(ref[:, :, :, None] - gc[:, :, None], 0.0))  # [B,nc,I,C,G,R,K]
    earlier_block = (jnp.arange(C) // sub)[:, None] > (jnp.arange(C) // sub)[None, :]
    # inside a sub-block: each pair's own exponent, summed over the channels where it stands
    k_in = k_b[:, :, :, None, :] * jnp.exp(jnp.minimum(gc_b[:, :, :, :, None] - gc_b[:, :, :, None, :], 0.0))  # [B,nc,I,t,s,G,R,K]
    at_or_before = jnp.tril(jnp.ones((C, C), bool))
    same_block = jnp.eye(I, dtype=jnp.float32)[:, None, :, None]

    def pairs(a):
        a_b = blocks(a)[:, :, :, :, :, None]
        across = es("bcitgrk,bcisgrk->bcgrits", a_b * inward, k_out).reshape(B, nc, G, R, C, C)
        inside = jnp.moveaxis(jnp.sum(a_b[:, :, :, :, None] * k_in, axis=-1), (2, 3, 4), (4, 5, 6))  # [B,nc,G,R,I,t,s]
        inside = (inside[:, :, :, :, :, :, None] * same_block).reshape(B, nc, G, R, C, C)
        return jnp.where(earlier_block, across, 0.0) + jnp.where(at_or_before, inside, 0.0)

    return tuple(pairs(a) for a in firsts)


def delta_rule_chunked(q, k, v, g, beta, chunk: int, operand_dtype=None, name: str = "gdn", mesh=None):
    """The gated delta rule over a sequence from a zero state, blocked in chunks. q, k [B,T,G,K]
    (key heads), v [B,T,G,R,V] (a key head's R value heads), beta [B,T,G,R] and g, the log-decay
    (<= 0): [B,T,G,R], one gate a head (Gated DeltaNet), or [B,T,G,R,K], one for each of a head's
    key channels (Kimi Delta Attention: ``S <- Diag(exp(g)) S`` in place of ``exp(g) S``), float32
    -> (o [B,T,G,R,V], the state after position T-1 [B,G,R,K,V]). ``name``: whose scopes the two
    stretches stand under (``<name>.chunk``, ``<name>.scan``). ``mesh``: the mesh the program runs
    over, if any.

    Inside a chunk, with gamma_t the decay since the chunk's start and S_0 the state there, the
    rule's written values ``u_t = beta_t (v_t - S'_t^T k_t)`` solve the unit lower-triangular
    system ``(I + A) U = beta V - (beta gamma K) S_0``, ``A[t,s] = beta_t (gamma_t / gamma_s)
    k_t.k_s`` for s < t. ``(I + A)^-1`` does not depend on S_0: it is found for every chunk at
    once, in float32, as the product (I - A)(I + A²)(I + A⁴)... (A is nilpotent), and applied
    to both right-hand sides. A short scan then passes the state from chunk to chunk:
    ``U = W_v - W_k S_0``, ``O = (gamma Q) S_0 + (M * Q K^T) U``, ``S_C = gamma_C S_0 +
    (gamma_C / gamma * K)^T U``. A position with beta = 0 and g = 0 writes nothing and decays
    nothing, which is how padding is kept out. The matmuls (but the inverse) take their operands
    in ``operand_dtype`` and accumulate in float32, as the published kernels do with bfloat16;
    without it they are float32 at ``highest`` precision throughout.

    One body for both gates: everything above holds with gamma a vector over the key channels
    (``gamma_t / gamma_s`` then stands INSIDE ``k_t.k_s`` and ``q_t.k_s``), and only those two
    C x C sets of pairs are built differently (``_pairs_by_channel``, in sub-blocks of ``SUB_BLOCK``
    positions). Either gate runs as ONE kernel that keeps a chunk and the state in fast memory
    (``ops/delta_rule.py``: the same lines at the same precision, the form of the pairs picked by the
    gate's rank, all of it under ``<name>.chunk``) unless its ``refusal`` gives a reason; then the
    lines below run."""
    if delta_rule.refusal(operand_dtype, q.shape[-1], v.shape[-1], min(chunk, q.shape[1]), mesh=mesh) is None:
        with scope(f"{name}.chunk"):  # off the TPU only a test gets here (it swaps ``refusal``), and runs the same body interpreted
            return delta_rule.delta_rule(q, k, v, g, beta, chunk, operand_dtype, interpret=jax.default_backend() != "tpu")
    hi = jax.lax.Precision.HIGHEST
    if operand_dtype is None or jnp.dtype(operand_dtype) == jnp.float32:
        def es(spec, a, b):
            return jnp.einsum(spec, a, b, precision=hi)
    else:
        def es(spec, a, b):
            return jnp.einsum(spec, a.astype(operand_dtype), b.astype(operand_dtype), preferred_element_type=jnp.float32)
    B, T, G, K = q.shape
    R, V = v.shape[-2:]
    C = min(chunk, T)
    pad = -T % C
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)) for a in (q, k, v, g, beta))
    nc = (T + pad) // C
    q, k = q.reshape(B, nc, C, G, K), k.reshape(B, nc, C, G, K)
    by_channel = g.ndim == beta.ndim + 1
    v, g, beta = v.reshape(B, nc, C, G, R, V), g.reshape(B, nc, C, G, R, *g.shape[4:]), beta.reshape(B, nc, C, G, R)
    with scope(f"{name}.chunk"):  # what runs on all chunks at once, the triangular inverse included
        gc = jnp.cumsum(g, axis=2)  # log of the decay since the chunk's start, <= 0
        beta_t = jnp.moveaxis(beta, 2, 4)  # [B,nc,G,R,t]
        before = jnp.tril(jnp.ones((C, C), bool), -1)
        if by_channel:
            kk, qk = _pairs_by_channel((k, q), k, gc, math.gcd(C, SUB_BLOCK), es)  # [B,nc,G,R,t,s], s <= t
            A = jnp.where(before, beta_t[..., None] * kk, 0.0)
            gc_k = gc  # [B,nc,C,G,R,K]
        else:
            seg = jnp.moveaxis(gc[:, :, :, None] - gc[:, :, None, :], (2, 3), (4, 5))  # [B,nc,G,R,t,s]: log gamma_t / gamma_s
            at_or_before = jnp.tril(jnp.ones((C, C), bool))
            decay = jnp.where(at_or_before, jnp.exp(jnp.where(at_or_before, seg, 0.0)), 0.0)
            kk = es("bctgk,bcsgk->bcgts", k, k)[:, :, :, None]  # [B,nc,G,1,t,s]
            A = jnp.where(before, beta_t[..., None] * decay * kk, 0.0)
            qk = es("bctgk,bcsgk->bcgts", q, k)[:, :, :, None] * decay  # [B,nc,G,R,t,s], s <= t
            gc_k = gc[..., None]  # one gate for all of a head's key channels
        # (I + A)^-1, float32: A^C = 0, so the product below ends after log2(C) factors
        inv, power = jnp.eye(C, dtype=jnp.float32) - A, A
        for _ in range(max(0, math.ceil(math.log2(C)) - 1)):
            power = jnp.einsum("...ts,...su->...tu", power, power, precision=hi)
            inv = inv + jnp.einsum("...ts,...su->...tu", inv, power, precision=hi)
        w_v = es("bcgrts,bcsgrv->bcgrtv", inv, v * beta[..., None])
        w_k = es("bcgrts,bcsgrk->bcgrtk", inv, k[:, :, :, :, None] * (beta[..., None] * jnp.exp(gc_k)))
        q_in = q[:, :, :, :, None] * jnp.exp(gc_k)  # [B,nc,C,G,R,K]: gamma_t q_t
        k_out = k[:, :, :, :, None] * jnp.exp(gc_k[:, :, -1:] - gc_k)  # gamma_C / gamma_s k_s
        whole = jnp.exp(gc_k[:, :, -1])  # [B,nc,G,R,K or 1]

    def pass_on(S, chunk_):
        w_v_c, w_k_c, qk_c, q_c, k_c, whole_c = chunk_
        u = w_v_c - es("bgrtk,bgrkv->bgrtv", w_k_c, S)
        o = es("btgrk,bgrkv->btgrv", q_c, S) + es("bgrts,bgrsv->btgrv", qk_c, u)
        return S * whole_c[..., None] + es("bsgrk,bgrsv->bgrkv", k_c, u), o

    with scope(f"{name}.scan"):  # the state passed from chunk to chunk
        per_chunk = tuple(jnp.moveaxis(a, 1, 0) for a in (w_v, w_k, qk, q_in, k_out, whole))
        S_end, o = jax.lax.scan(pass_on, jnp.zeros((B, G, R, K, V), jnp.float32), per_chunk)
    return jnp.moveaxis(o, 0, 1).reshape(B, nc * C, G, R, V)[:, :T], S_end


def delta_rule_step(S, q, k, v, g, beta):
    """The rule for one position, elementwise in float32: S [B,N,K,V], q, k [B,N,K], v [B,N,V],
    beta [B,N] and the log-decay g [B,N] (one gate a head) or [B,N,K] (one a key channel)
    -> (o [B,N,V], S): decay, read ``S'^T k``, write ``k (beta (v - S'^T k))^T``, read out."""
    q, k = q[..., None], k[..., None]
    S = S * (jnp.exp(g)[..., None, None] if g.ndim == beta.ndim else jnp.exp(g)[..., None])
    u = beta[..., None] * (v - jnp.sum(S * k, axis=-2))
    S = S + k * u[..., None, :]
    return jnp.sum(S * q, axis=-2), S


def short_conv_seq(mixed, taps, lengths):
    """The causal depthwise convolution without bias over a padded sequence: mixed [B,T,C], taps
    [K,C] -> (float32 [B,T,C], the window [B,K-1,C] of its last inputs AT each true length)."""
    K, T = taps.shape[0], mixed.shape[1]
    padded = jnp.pad(mixed, ((0, 0), (K - 1, 0), (0, 0)))  # index j holds position j - (K-1)
    taps = taps.astype(jnp.float32)
    conv = sum(padded[:, j:j + T].astype(jnp.float32) * taps[j] for j in range(K))
    return conv, jax.vmap(lambda p, n: jax.lax.dynamic_slice_in_dim(p, n, K - 1, 0))(padded, lengths)


def short_conv_step(window, mixed, taps):
    """One position of ``short_conv_seq``: window [B,K-1,C] of the inputs before it, mixed [B,C]
    -> (float32 [B,C], the window moved on by one)."""
    window = jnp.concatenate([window, mixed[:, None].astype(window.dtype)], axis=1)  # [B,K,C]
    return jnp.sum(window.astype(jnp.float32) * taps.astype(jnp.float32), axis=1), window[:, 1:]


def a_few_at_a_time(some, xn, lengths):
    """``some(xn [b,T,H], lengths [b])`` over a batch [B,T,H]: whole where it has at most
    ``RULE_POSITIONS`` positions, else a few sequences at a time."""
    B, T, _ = xn.shape
    at_once = max(1, RULE_POSITIONS // T)
    if B <= at_once or B % at_once:
        return some(xn, lengths)
    parts = jax.lax.map(lambda a: some(*a), (xn.reshape(B // at_once, at_once, T, -1), lengths.reshape(B // at_once, at_once)))
    return jax.tree.map(lambda a: a.reshape((B,) + a.shape[2:]), parts)


def gdn_seq(w, xn, lengths, c: Qwen3NextConfig):
    """xn [B,T,H], lengths [B] -> (out [B,T,H], S [B,nv,dk,dv] f32, conv [B,K-1,C]): the state
    and the convolution's window AT each sequence's true length. A batch of more than
    ``RULE_POSITIONS`` positions goes through a few sequences at a time."""
    T = xn.shape[1]
    nk, nv = c.linear_num_key_heads, c.linear_num_value_heads
    operand = None if xn.dtype == jnp.float32 else xn.dtype

    def some(xn, lengths):
        b = xn.shape[0]
        mixed, z, ba = _gdn_split(w, xn, c)
        conv, window = short_conv_seq(mixed, w["conv_w"], lengths)
        q, k, v, beta, g = _gdn_inputs(w, conv, ba, c)
        real = (jnp.arange(T)[None, :] < lengths[:, None])[..., None]
        beta, g = jnp.where(real, beta, 0.0), jnp.where(real, g, 0.0)  # padding writes nothing and decays nothing
        grouped = (b, T, nk, nv // nk)
        o, S = delta_rule_chunked(q, k, v.reshape(*grouped, -1), g.reshape(grouped), beta.reshape(grouped), c.chunk_size, operand)
        y = _gdn_out(w, o.reshape(b, T, nv, -1), z.reshape(b, T, nv, -1), c, xn.dtype)
        return y, S.reshape(b, nv, *S.shape[-2:]), window

    return a_few_at_a_time(some, xn, lengths)


def gdn_step(w, xn, S, conv, c: Qwen3NextConfig):
    """One token: xn [B,H], S [B,nv,dk,dv] f32, conv [B,K-1,C] -> (out [B,H], S, conv)."""
    R = c.linear_num_value_heads // c.linear_num_key_heads
    mixed, z, ba = _gdn_split(w, xn, c)
    out, window = short_conv_step(conv, mixed, w["conv_w"])
    q, k, v, beta, g = _gdn_inputs(w, out, ba, c)
    with scope("gdn.state"):
        q, k = (jnp.repeat(a, R, axis=1) for a in (q, k))  # [B,nv,dk]: a key head serves R value heads
        o, S = delta_rule_step(S, q, k, v, g, beta)
    return _gdn_out(w, o, z.reshape(o.shape), c, xn.dtype), S, window


# --------------------------------------------------------- attn: gated attention
def gated_attn_qkv(w, xn, positions, c):
    """xn [B,T,H], positions [B,T] or [T] -> q [B,T,nh,hd], its output gate [B,T,nh*hd],
    k, v [B,T,kv,hd]: q and k normalised per head with the model's ``N`` (``c.norm``) and rotated
    (rotate-half) in their first ``rot_dim`` dimensions, the rest passing (0: nothing is rotated).
    ``c``: whatever has ``num_heads``, ``num_kv_heads``, ``hd``, ``rot_dim``, ``rope_theta`` and
    ``norm``: this file's config, or one mixer's view of another model's (``models/minicpm_sala.py``)."""
    B, T, _ = xn.shape
    qg = jnp.dot(xn, w["wq"]).reshape(B, T, c.num_heads, 2 * c.hd)
    q, gate = qg[..., :c.hd], qg[..., c.hd:].reshape(B, T, c.num_heads * c.hd)
    k = jnp.dot(xn, w["wk"]).reshape(B, T, c.num_kv_heads, c.hd)
    v = jnp.dot(xn, w["wv"]).reshape(B, T, c.num_kv_heads, c.hd)
    q, k = c.norm(q, w["q_norm"]), c.norm(k, w["k_norm"])
    if not c.rot_dim:
        return q, gate, k, v
    cos, sin = rotary_embedding(positions, c.rot_dim, c.rope_theta)

    def rotate(x):  # [B,T,heads,hd]
        turned = apply_rope(x[..., :c.rot_dim].astype(jnp.float32).transpose(0, 2, 1, 3), cos, sin).transpose(0, 2, 1, 3)
        return jnp.concatenate([turned.astype(x.dtype), x[..., c.rot_dim:]], axis=-1)

    return rotate(q), gate, rotate(k), v


def _gated_out(w, o, gate, dtype):
    """``o * sigmoid(gate)`` in float32, then the output projection."""
    return jnp.dot((o.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(dtype), w["wo"])


def gated_attn_seq(w, xn, c: Qwen3NextConfig, mesh=None, lengths=None):
    """Causal grouped-query attention over a padded sequence, positions 0..T-1.
    -> (out, k, v [B,T,kv,hd]) with k as the cache keeps it: normalised and rotated. ``lengths`` [B]:
    the true lengths, where the kernel may skip what lies past them (``SeqCtx.skippable``)."""
    B, T, _ = xn.shape
    q, gate, k, v = gated_attn_qkv(w, xn, jnp.arange(T, dtype=jnp.int32), c)
    o = flash_attention_on_mesh(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                                mesh, c.attention_impl, lengths=lengths)
    return _gated_out(w, o.transpose(0, 2, 1, 3).reshape(B, T, c.num_heads * c.hd), gate, xn.dtype), k, v
