"""Nemotron-H hybrid decoder: Mamba-2, routed experts and attention in one stack.

Each layer is ``x = x + mixer(RMSNorm(x))`` with ONE mixer and no separate
feed-forward part; the mixer's kind comes from the published
``hybrid_override_pattern``: ``M`` Mamba-2 (a recurrent state and a short
causal convolution per sequence), ``E`` routed experts plus a shared expert
(keeps nothing), ``*`` grouped-query attention with no position embedding
(keys and values per position). Position is carried by the Mamba layers.

This file is a DESCRIPTION (ROADMAP C1): ``layer_kinds``, ``mixers`` = the
two forms of each kind, ``cache_spec()`` = what a layer of each kind keeps
per sequence, with parameters stacked by layer kind. The loops that walk it
(``scan_layers`` for a sequence, ``run_layers`` for a decode step, the
sequence forward) are ``models/hybrid.py``'s, shared with every other
description; the expert layer is ``models/experts.py``'s, configured here as
a sigmoid router with a correction bias, relu² experts of two matrices and
a plain shared expert.

The residual stream is in the weights' dtype, as published
(``residual_in_fp32`` false), or float32 where that key is true; norms
compute in float32 and hand back the stream's dtype. The router is
float32 on whatever the norm hands it.

Every mixer comes in two forms: over a padded sequence with its true
length (prefill and training; padded positions advance no state) and for
one token against cached state (decode). The expert layer drops no token
and is told which experts this chip holds (``expert_start``,
``num_local_experts``).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu.models import experts
from ray_tpu.models.experts import ExpertLayer, experts_dense, experts_grouped, route  # noqa: F401 - the layer's parts, by the names they had here
from ray_tpu.models.hybrid import ROUTING, HybridDescription, Mixer, attend_slot, forward, init_stacked, loss_fn  # noqa: F401 - the shared forward and loss, as the harness's family asks for them
from ray_tpu.ops.flash_attention import flash_attention_on_mesh
from ray_tpu.ops.layers import rms_norm
from ray_tpu.util.profiling import scope

# pattern character -> (parameter group, named scope in a profile)
KINDS = {"M": ("mamba", "mamba2"), "E": ("moe", "moe"), "*": ("attn", "attn")}


@dataclass(frozen=True)
class NemotronHConfig(HybridDescription):
    vocab_size: int = 131072  # rows of the embedding and head held here
    hidden_size: int = 2688
    layer_pattern: str = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    # M: Mamba-2
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # E: routed experts. The router is n_routed_experts wide whatever is held here.
    n_routed_experts: int = 128
    expert_start: int = 0
    num_local_experts: int | None = None  # None: all of them
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    # *: attention
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    rms_eps: float = 1e-5
    # init only (published rescale_prenorm_residual): every mixer's output projection is drawn
    # 1/sqrt(this) smaller, the published depth whatever depth is held here; 1 turns it off
    residual_rescale_layers: int = 52
    # init only: 0 draws the routers N(0, fan_in^-1/2) like every other matrix; > 0 anchors every
    # token id to its own top-k experts in every expert layer by this margin in the router's
    # logits (``_anchor_routing``), so that a trained router's decisiveness is there
    router_anchor: float = 0.0
    # published key: the residual stream (and with it what every norm hands on) is float32 when
    # true, the weights' dtype when false (the published value)
    residual_in_fp32: bool = False
    max_seq_len: int = 4096
    dtype: str = "bfloat16"
    remat: bool = False
    attention_impl: str = "auto"  # auto | pallas | xla

    def __post_init__(self):
        bad = set(self.layer_pattern) - set(KINDS)
        if bad or not self.layer_pattern:
            raise ValueError(f"layer_pattern holds {sorted(bad)}; the kinds are {sorted(KINDS)}")
        if self.d_inner % self.n_groups or self.mamba_num_heads % self.n_groups:
            raise ValueError("n_groups must divide the Mamba heads and their inner width")
        _ = self.expert_layer  # raises where the experts held do not lie inside the router's width

    # ---- the description the layer loop, the engine and the cache manager read
    def init_params(self, key):
        return init_params(self, key)

    @property
    def layer_kinds(self) -> tuple:
        return tuple(KINDS[c][0] for c in self.layer_pattern)

    @property
    def mixers(self) -> dict:
        """kind -> its scope in a profile and its two forms (``models/hybrid.Mixer``)."""
        dt = jnp.dtype(self.dtype)

        def mamba_seq(w, xn, ctx):
            y, ssm, conv = mamba2_seq(w, xn.astype(dt), ctx.lengths, self)
            return y, {"ssm": ssm, "conv": conv}

        def mamba_step(w, xn, cache, ctx):
            with scope("mamba2.state"):  # the state's read here, its decay and write in ``mamba2_step``, its way back below
                ssm = cache.read("ssm")
            y, ssm, conv = mamba2_step(w, xn.astype(dt), ssm, cache.read("conv"), self)
            with scope("mamba2.state"):
                cache.write("ssm", ssm)
            cache.write("conv", conv)
            return y, None

        def experts_seq(w, xn, ctx):
            y, counters = experts.moe_seq(w, xn, ctx.lengths, self, stacked=ctx.stacked)
            return y, {ROUTING: counters}

        def attention_seq(w, xn, ctx):
            y, k, v = attn_seq(w, xn.astype(dt), self, ctx.mesh, ctx.skippable)
            return y, {"k": k, "v": v}

        def attention_step(w, xn, cache, ctx):
            q, k, v = qkv(w, xn.astype(dt), self)
            cache.write("k", k)
            cache.write("v", v)
            return attn_step(w, q, cache, ctx, self), None

        forms = {"mamba": (mamba_seq, mamba_step, False), "attn": (attention_seq, attention_step, False),
                 "moe": (experts_seq, lambda w, xn, cache, ctx: experts.moe_step(w, xn, ctx.active, self, ctx.stacked), True)}
        return {kind: Mixer(scope, *forms[kind]) for kind, scope in KINDS.values()}

    def norm(self, x, w):
        return rms_norm(x, w, self.rms_eps)

    @property
    def expert_layer(self) -> ExpertLayer:
        return ExpertLayer(num_experts=self.n_routed_experts, top_k=self.num_experts_per_tok, expert_start=self.expert_start,
                           local_experts=self.num_local_experts, score="sigmoid", bias=True, norm_topk=self.norm_topk_prob,
                           scale=self.routed_scaling_factor, act="relu2", shared_gated=False)

    @property
    def hd(self) -> int:
        return self.head_dim

    def flash_calls(self, length: int) -> dict:
        return {self.hd: self.count("attn")}

    @property
    def stream_dtype(self):
        return jnp.dtype("float32" if self.residual_in_fp32 else self.dtype)

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def local_experts(self) -> int:
        return self.expert_layer.held

    def cache_spec(self) -> dict:
        """kind -> {name: (shape, dtype, "position" | "sequence")}: what ONE layer of that kind
        keeps in the cache, per position of a sequence or once per sequence."""
        return {
            "attn": {"k": ((self.num_kv_heads, self.hd), self.dtype, "position"),
                     "v": ((self.num_kv_heads, self.hd), self.dtype, "position")},
            "mamba": {"ssm": ((self.mamba_num_heads, self.mamba_head_dim, self.ssm_state_size), "float32", "sequence"),
                      "conv": ((self.conv_kernel - 1, self.conv_dim), self.dtype, "sequence")},
            "moe": {},
        }

    def num_params(self) -> int:
        """Parameters held here (the chip's share of experts and vocabulary)."""
        n = 2 * self.vocab_size * self.hidden_size + self.hidden_size
        for g, group in _shapes(self).items():
            n += self.count(g) * sum(math.prod(shape) for shape, _ in group.values())
        return n + self.count("mamba") * 3 * self.mamba_num_heads + self.count("moe") * self.n_routed_experts

    @staticmethod
    def tiny(**kw):
        base = dict(
            vocab_size=256, hidden_size=64, layer_pattern="ME*ME*ME", mamba_num_heads=4, mamba_head_dim=8,
            n_groups=2, ssm_state_size=16, chunk_size=8, n_routed_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=32, moe_shared_expert_intermediate_size=64, num_heads=4, num_kv_heads=2,
            head_dim=16, max_seq_len=128, dtype="float32",
        )
        return NemotronHConfig(**{**base, **kw})


# ------------------------------------------------------------------ parameters
def _shapes(c: NemotronHConfig) -> dict:
    """group -> {name: (shape of one layer, fan_in or fill)}: matrices are N(0, fan_in^-1/2), the
    projections back onto the residual stream 1/sqrt(N) smaller (``rescale_prenorm_residual``:
    "scale the weights of residual layers at initialization by 1/sqrt(N)", N the published depth;
    without it the stream is the sum of 16 unit-variance terms and no mixer is small beside it,
    as each is in a trained model).
    Both of an expert's matrices are stored [F, H], the residual width last: 2688 fills the
    chip's 128-wide tiles and 1856 does not, and the chip's compiler keeps the exact width minor
    whatever the program says, copying 4 GB of experts wherever a matmul wants the other order."""
    H, di, C, nh = c.hidden_size, c.d_inner, c.conv_dim, c.mamba_num_heads
    F, Fs, E, El = c.moe_intermediate_size, c.moe_shared_expert_intermediate_size, c.n_routed_experts, c.local_experts
    q, kv, N = c.num_heads * c.hd, c.num_kv_heads * c.hd, c.residual_rescale_layers  # N: outputs onto the stream
    return {
        "mamba": {"norm": ((H,), 1.0), "in_proj": ((H, di + C + nh), H), "conv_w": ((c.conv_kernel, C), c.conv_kernel),
                  "conv_b": ((C,), 0.0), "gate_norm": ((di,), 1.0), "out_proj": ((di, H), di * N)},
        "moe": {"norm": ((H,), 1.0), "router": ((H, E), H), "w_up": ((El, F, H), H), "w_down": ((El, F, H), F * N),
                "shared_up": ((H, Fs), H), "shared_down": ((Fs, H), Fs * N)},
        "attn": {"norm": ((H,), 1.0), "wq": ((H, q), H), "wk": ((H, kv), H), "wv": ((H, kv), H), "wo": ((q, H), q * N)},
    }


def init_params(config: NemotronHConfig, key):
    """Weights from a seed, stacked by layer kind. The published scheme where stability hangs on
    it: A_log = log U(1, 16), dt_bias the inverse softplus of a log-uniform step in
    [time_step_min, time_step_max] floored at time_step_floor, D = 1, the router's correction
    bias 0, norms 1. The per-head scalars and the router's bias stay float32."""
    c, dt = config, jnp.dtype(config.dtype)
    keys = iter(jax.random.split(key, 64))

    params = init_stacked(_shapes(c), c.count, keys, dt)
    if c.count("mamba"):
        n, nh = c.count("mamba"), c.mamba_num_heads
        step = jnp.exp(jax.random.uniform(next(keys), (n, nh)) * (math.log(c.time_step_max) - math.log(c.time_step_min))
                       + math.log(c.time_step_min))
        step = jnp.maximum(step, c.time_step_floor)
        params["mamba"]["dt_bias"] = step + jnp.log(-jnp.expm1(-step))  # softplus^-1
        params["mamba"]["A_log"] = jnp.log(jax.random.uniform(next(keys), (n, nh), minval=1.0, maxval=16.0))
        params["mamba"]["D"] = jnp.ones((n, nh), jnp.float32)
    if c.count("moe"):
        params["moe"]["router_bias"] = jnp.zeros((c.count("moe"), c.n_routed_experts), jnp.float32)
    embed = jax.random.normal(next(keys), (c.vocab_size, c.hidden_size), jnp.float32)
    if c.count("moe") and c.router_anchor:
        params["moe"]["router"], embed = _anchor_routing(c, next(keys), embed, dt)
    params["embed"] = embed.astype(dt)
    params["unembed"] = (jax.random.normal(next(keys), (c.hidden_size, c.vocab_size), jnp.float32)
                         * c.hidden_size ** -0.5).astype(dt)
    params["final_norm"] = jnp.ones((c.hidden_size,), dt)
    return params


def _anchor_routing(c: NemotronHConfig, key, embed, dt):
    """Routers and an embedding table under which every token id has ITS OWN top-k experts in
    every expert layer, uniformly drawn, ``router_anchor`` ahead of the rest in the router's
    logits. With every matrix N(0, fan_in^-1/2) the k-th and (k+1)-th of 128 scores lie 0.08 of
    their spread apart, so the bfloat16 path's rounding (0.1-0.3% of a logit) picks another set
    than a float32 reference on 2-3% of (token, expert layer) pairs, each moving that token's
    stream by a sixth of the routed output: a comparison with the reference then measures the
    router's coin flips. A trained router is decisive and, as measured on open MoE models, mostly
    a function of the token id. So: the routers' columns, over all expert layers together, are
    columns of ONE random orthogonal matrix (unit norm, as N(0, 1/fan_in) gives on average, and
    no cross-talk between them), and a token's embedding row is its N(0, 1) draw plus
    ``router_anchor`` times the sum of its chosen experts' columns. Context still moves the
    logits; it rarely moves them by the margin. -> (routers [L, H, E] in ``dt``, embed f32)."""
    L, E, H, k = c.count("moe"), c.n_routed_experts, c.hidden_size, c.num_experts_per_tok
    if L * E > H:
        raise ValueError(f"router_anchor needs {L} x {E} orthogonal router columns in {H} dimensions")
    k_q, k_pref = jax.random.split(key)
    q, _ = jnp.linalg.qr(jax.random.normal(k_q, (H, L * E), jnp.float32))
    routers = q.T.reshape(L, E, H).astype(dt)  # the code is built from the columns as they are stored

    def one_layer(code, lk):
        cols, kk = lk
        _, pref = jax.lax.top_k(jax.random.uniform(kk, (c.vocab_size, E)), k)
        chosen = jnp.sum(jax.nn.one_hot(pref, E, dtype=jnp.float32), axis=1)  # [V, E], k ones a row
        return code + jnp.dot(chosen, cols.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST), None

    code, _ = jax.lax.scan(one_layer, jnp.zeros_like(embed), (routers, jax.random.split(k_pref, L)))
    return routers.transpose(0, 2, 1), embed + c.router_anchor * code


def param_logical_axes(config: NemotronHConfig):
    """Logical axes for ``parallel/mesh.ShardingRules`` (vocabulary, experts and heads are the
    axes a mesh could split; the serving engine refuses a mesh for this model today)."""
    lead = {"mamba": {"norm": (None,), "in_proj": ("embed", None), "conv_w": (None, None), "conv_b": (None,),
                      "gate_norm": (None,), "out_proj": (None, "embed"), "dt_bias": (None,), "A_log": (None,), "D": (None,)},
            "moe": {"norm": (None,), "router": ("embed", None), "router_bias": (None,), "w_up": ("expert", "mlp", "embed"),
                    "w_down": ("expert", "mlp", "embed"), "shared_up": ("embed", "mlp"), "shared_down": ("mlp", "embed")},
            "attn": {"norm": (None,), "wq": ("embed", "heads"), "wk": ("embed", "kv_heads"), "wv": ("embed", "kv_heads"),
                     "wo": ("heads", "embed")}}
    axes = {g: {n: (None,) + a for n, a in group.items()} for g, group in lead.items() if config.count(g)}
    axes.update(embed=("vocab", "embed"), unembed=("embed", "vocab"), final_norm=(None,))
    return axes


# ------------------------------------------------------------------- M: Mamba-2
def _mamba_split(w, xn, c: NemotronHConfig):
    """in_proj -> gate z, the convolution's input (x, B, C) and the step dt, in that order."""
    di, C = c.d_inner, c.conv_dim
    zxbcdt = jnp.dot(xn, w["in_proj"])
    return zxbcdt[..., :di], zxbcdt[..., di:di + C], zxbcdt[..., di + C:]


def _mamba_ssm_inputs(w, xbc, dt, c: NemotronHConfig):
    """After the convolution: heads' inputs x [.., nh, P], B and C per group [.., G, N] (a group's
    B and C serve its nh/G heads), dt = softplus(dt + dt_bias) and A = -exp(A_log), all float32."""
    di, G, N, nh = c.d_inner, c.n_groups, c.ssm_state_size, c.mamba_num_heads
    xbc = jax.nn.silu(xbc)
    lead = xbc.shape[:-1]
    x = xbc[..., :di].reshape(*lead, nh, c.mamba_head_dim)
    Bm = xbc[..., di:di + G * N].reshape(*lead, G, N)
    Cm = xbc[..., di + G * N:].reshape(*lead, G, N)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + w["dt_bias"])
    return x, Bm, Cm, dt, -jnp.exp(w["A_log"])


def _mamba_out(w, y, z, c: NemotronHConfig, dtype):
    """y * SiLU(z), RMSNorm in n_groups groups under one weight, out_proj."""
    y = y.reshape(*y.shape[:-2], c.d_inner) * jax.nn.silu(z.astype(jnp.float32))
    g = y.reshape(*y.shape[:-1], c.n_groups, c.d_inner // c.n_groups)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + c.rms_eps)
    y = g.reshape(y.shape) * w["gate_norm"].astype(jnp.float32)
    return jnp.dot(y.astype(dtype), w["out_proj"])


def ssd_chunked(x, dt, A, Bm, Cm, chunk: int, operand_dtype=None):
    """The selective state-space recurrence over a sequence from a zero state, blocked in chunks:
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T, y_t = S_t C_t. x [B,T,nh,P], dt [B,T,nh],
    A [nh], Bm/Cm [B,T,G,N] (head h reads group h // (nh/G)), float32 -> (y [B,T,nh,P], the
    state after position T-1 [B,nh,P,N]). Inside a chunk the sum over earlier positions is one
    masked matmul; between chunks the state passes through a short scan. A position with
    dt = 0 leaves the state as it was, which is how padding is kept out. The matmuls take their
    operands in ``operand_dtype`` and accumulate in float32, as the published kernels do with
    bfloat16; without it they are float32 throughout."""
    if operand_dtype is None or jnp.dtype(operand_dtype) == jnp.float32:
        es = partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)
    else:
        def es(spec, a, b):
            return jnp.einsum(spec, a.astype(operand_dtype), b.astype(operand_dtype), preferred_element_type=jnp.float32)
    B, T, nh, P = x.shape
    G, N = Bm.shape[-2:]
    R, Q = nh // G, min(chunk, T)
    pad = -T % Q
    if pad:
        x, dt, Bm, Cm = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)) for a in (x, dt, Bm, Cm))
    nc = (T + pad) // Q
    x = x.reshape(B, nc, Q, G, R, P)
    dt = dt.reshape(B, nc, Q, G, R)
    Bm, Cm = Bm.reshape(B, nc, Q, G, N), Cm.reshape(B, nc, Q, G, N)
    acum = jnp.cumsum(dt * A.reshape(G, R), axis=2)  # log of the decay since the chunk's start, <= 0
    xdt = x * dt[..., None]
    # inside a chunk: y_q = sum_{s<=q} exp(acum_q - acum_s) (C_q . B_s) dt_s x_s
    tri = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None, None]
    seg = acum[:, :, :, None] - acum[:, :, None, :]  # [B,nc,q,s,G,R]
    decay = jnp.where(tri, jnp.exp(jnp.where(tri, seg, 0.0)), 0.0)
    cb = es("bcqgn,bcsgn->bcqsg", Cm, Bm)
    y = es("bcqsgr,bcsgrp->bcqgrp", cb[..., None] * decay, xdt)
    # what each chunk alone adds to the state at its end, and the chunk's whole decay
    local = es("bcsgn,bcsgrp->bcgrpn", Bm, xdt * jnp.exp(acum[:, :, -1:] - acum)[..., None])
    whole = jnp.exp(acum[:, :, -1])  # [B,nc,G,R]

    def pass_on(S, cl):
        w_c, l_c = cl
        return S * w_c[..., None, None] + l_c, S  # emits the state at the chunk's START

    S_end, S_start = jax.lax.scan(pass_on, jnp.zeros((B, G, R, P, N), jnp.float32),
                                  (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(local, 1, 0)))
    y = y + es("bcqgn,cbgrpn->bcqgrp", Cm, S_start) * jnp.exp(acum)[..., None]
    return y.reshape(B, nc * Q, nh, P)[:, :T], S_end.reshape(B, nh, P, N)


def mamba2_seq(w, xn, lengths, c: NemotronHConfig):
    """xn [B,T,H], lengths [B] -> (out [B,T,H], ssm [B,nh,P,N] f32, conv [B,K-1,C]): the state
    and the convolution's window AT each sequence's true length."""
    T, K = xn.shape[1], c.conv_kernel
    z, xbc, dt = _mamba_split(w, xn, c)
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))  # index j holds position j - (K-1)
    taps = w["conv_w"].astype(jnp.float32)
    conv = sum(padded[:, k:k + T].astype(jnp.float32) * taps[k] for k in range(K)) + w["conv_b"].astype(jnp.float32)
    window = jax.vmap(lambda p, n: jax.lax.dynamic_slice_in_dim(p, n, K - 1, 0))(padded, lengths)
    x, Bm, Cm, dt, A = _mamba_ssm_inputs(w, conv, dt, c)
    dt = jnp.where(jnp.arange(T)[None, :, None] < lengths[:, None, None], dt, 0.0)
    y, ssm = ssd_chunked(x, dt, A, Bm, Cm, c.chunk_size, None if c.residual_in_fp32 else xn.dtype)
    y = y + x * w["D"][:, None]
    return _mamba_out(w, y, z, c, xn.dtype), ssm, window


def mamba2_step(w, xn, ssm, conv, c: NemotronHConfig):
    """One token: xn [B,H], ssm [B,nh,P,N] f32, conv [B,K-1,C] -> (out [B,H], ssm, conv)."""
    z, xbc, dt = _mamba_split(w, xn, c)
    window = jnp.concatenate([conv, xbc[:, None].astype(conv.dtype)], axis=1)  # [B,K,C]
    out = jnp.sum(window.astype(jnp.float32) * w["conv_w"].astype(jnp.float32), axis=1) + w["conv_b"].astype(jnp.float32)
    x, Bm, Cm, dt, A = _mamba_ssm_inputs(w, out, dt, c)
    with scope("mamba2.state"):
        Bm, Cm = (jnp.repeat(a, c.mamba_num_heads // c.n_groups, axis=1)[:, :, None, :] for a in (Bm, Cm))  # [B,nh,1,N]
        ssm = ssm * jnp.exp(dt * A)[..., None, None] + (x * dt[..., None])[..., None] * Bm
        y = jnp.sum(ssm * Cm, axis=-1) + x * w["D"][:, None]
    return _mamba_out(w, y, z, c, xn.dtype), ssm, window[:, 1:]


# ------------------------------------------------------------ E: routed experts
# ``models/experts.py`` under this description's ``expert_layer``; ``route``, ``experts_dense`` and
# ``experts_grouped`` are its functions, imported above
_RELU2_SHARED = ExpertLayer(num_experts=1, top_k=1, act="relu2")


def _shared_expert(w, x):
    return experts.shared_expert(w, x, _RELU2_SHARED)


# ----------------------------------------------------------------- *: attention
def qkv(w, xn, c: NemotronHConfig):
    lead = xn.shape[:-1]
    q = jnp.dot(xn, w["wq"]).reshape(*lead, c.num_heads, c.hd)
    k = jnp.dot(xn, w["wk"]).reshape(*lead, c.num_kv_heads, c.hd)
    v = jnp.dot(xn, w["wv"]).reshape(*lead, c.num_kv_heads, c.hd)
    return q, k, v


def attn_seq(w, xn, c: NemotronHConfig, mesh=None, lengths=None):
    """Causal grouped-query attention with NO position embedding. -> (out, k, v [B,T,kv,hd]).
    ``lengths`` [B]: the true lengths, where the kernel may skip what lies past them (``SeqCtx.skippable``)."""
    B, T, _ = xn.shape
    q, k, v = qkv(w, xn, c)
    o = flash_attention_on_mesh(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                                mesh, c.attention_impl, lengths=lengths)
    return jnp.dot(o.transpose(0, 2, 1, 3).reshape(B, T, c.num_heads * c.hd), w["wo"]), k, v


def attn_step(w, q, cache, ctx, c: NemotronHConfig):
    """One token a lane (its query q [B,nh,hd] from ``qkv``) against the positions its lane holds
    in this layer's keys and values (``cache``: the layer's ``LayerCache``, the new token's key
    and value already written through it at index ctx.lengths[b]). -> out [B,H]."""
    o = attend_slot(q, cache, ctx, c.num_kv_heads)
    return jnp.dot(o.astype(q.dtype), w["wo"])
