"""Nemotron-H hybrid decoder: Mamba-2, routed experts and attention in one stack.

Each layer is ``x = x + mixer(RMSNorm(x))`` with ONE mixer and no separate
feed-forward part; the mixer's kind comes from the published
``hybrid_override_pattern``: ``M`` Mamba-2 (a recurrent state and a short
causal convolution per sequence), ``E`` routed experts plus a shared expert
(keeps nothing), ``*`` grouped-query attention with no position embedding
(keys and values per position). Position is carried by the Mamba layers.

This file is the first step of ROADMAP C1: the model is a DESCRIPTION
(``layer_kinds``, ``cache_spec()`` = what a layer of each kind keeps per
sequence, ``layer_plan`` = how the pattern repeats) walked by a loop, and
parameters are stacked by layer kind so that the loop can index them:
``scan_layers`` for a sequence (one scan, the body switches on the kind:
a program's size follows the kinds) and ``run_layers`` for a decode step
(a scan over the repeated period of the pattern, so that the caches in
its carry are updated in place). A uniform model is the special case of
one kind and a period of one: Llama can move onto the same loops.

The residual stream is in the weights' dtype, as published
(``residual_in_fp32`` false), or float32 where that key is true; norms
compute in float32 and hand back the stream's dtype. The router is
float32 on whatever the norm hands it.

Every mixer comes in two forms: over a padded sequence with its true
length (prefill and training; padded positions advance no state) and for
one token against cached state (decode). The expert layer drops no token
and is told which experts this chip holds (``expert_start``,
``num_local_experts``): the router scores all published experts, this
chip computes what its own give for the tokens routed to them and adds
the shared expert; a token whose choice lives on another chip gets
nothing from that choice here (expert parallelism without its exchange).
"""

from __future__ import annotations

import math
import sys
from collections import Counter
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu.ops.flash_attention import flash_attention_on_mesh
from ray_tpu.ops.layers import cross_entropy_loss, rms_norm

# pattern character -> (parameter group, named scope in a profile)
KINDS = {"M": ("mamba", "mamba2"), "E": ("moe", "moe"), "*": ("attn", "attn")}
SCOPES = dict(KINDS.values())


@dataclass(frozen=True)
class NemotronHConfig:
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
        if not 0 <= self.expert_start <= self.expert_start + self.local_experts <= self.n_routed_experts:
            raise ValueError("the experts held must lie inside the router's width")

    # ---- the description the layer loop, the engine and the cache manager read
    @property
    def model(self):
        """The module that holds this description's mixers and loops: the step programs of
        ``llm/hybrid_runner.py`` take them from here, so neither they nor the engine name a model."""
        return sys.modules[__name__]

    def init_params(self, key):
        return init_params(self, key)

    @property
    def layer_kinds(self) -> tuple:
        return tuple(KINDS[c][0] for c in self.layer_pattern)

    @property
    def num_layers(self) -> int:
        return len(self.layer_pattern)

    def count(self, kind: str) -> int:
        return self.layer_kinds.count(kind)

    @property
    def num_kv_layers(self) -> int:
        return self.count("attn")

    @property
    def hd(self) -> int:
        return self.head_dim

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
        return self.n_routed_experts if self.num_local_experts is None else self.num_local_experts

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

    @property
    def layer_plan(self) -> tuple:
        """(period, repeats, tail): the longest prefix of the pattern that is a block repeated
        at least twice, and the kinds that follow it. The loop scans over the repeats."""
        kinds, best = self.layer_kinds, ((), 0)
        for p in range(1, len(kinds) // 2 + 1):
            r = 1
            while kinds[r * p:(r + 1) * p] == kinds[:p]:
                r += 1
            if r >= 2 and r * p > best[1] * len(best[0]):
                best = (kinds[:p], r)
        period, r = best
        return period, r, kinds[r * len(period):]

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

    def fill(shape, how, n):
        if isinstance(how, float):
            return jnp.full((n,) + shape, how, dt)
        # a layer at a time: the float32 draw of all the experts at once would not fit the chip
        return jax.lax.map(lambda k: (jax.random.normal(k, shape, jnp.float32) * how ** -0.5).astype(dt),
                           jax.random.split(next(keys), n))

    params = {g: {name: fill(shape, how, c.count(g)) for name, (shape, how) in group.items()}
              for g, group in _shapes(c).items() if c.count(g)}
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


# --------------------------------------------------------------- the layer loop
def _layer_weights(params, kind, i):
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), params[kind])


def run_layers(config: NemotronHConfig, params, x, carry, layer_fn):
    """Walk the layer pattern with LARGE state in the carry (a decode step's caches, updated in
    place): ``layer_fn(kind, w, i, x, carry) -> (x, carry)`` with ``w`` one layer's weights and
    ``i`` its index among the layers of its kind (traced inside the scan over the repeated
    period, a plain int in the tail). The program holds one body per layer of the period and of
    the tail. Why not one body per kind (``scan_layers``): a conditional's branch hands back
    every carried array, and the chip's compiler copies the ones a branch did not touch, 8 GB a
    step for 0.75 GB of caches (compiled for a described v5e, PR 29)."""
    period, repeats, tail = config.layer_plan
    per = Counter(period)

    def apply(kind, i, x, carry):
        with jax.named_scope(SCOPES[kind]):
            return layer_fn(kind, _layer_weights(params, kind, i), i, x, carry)

    def block(xc, r):
        x, carry = xc
        seen = Counter()
        for kind in period:
            x, carry = apply(kind, r * per[kind] + seen[kind], x, carry)
            seen[kind] += 1
        return (x, carry), None

    if repeats:
        (x, carry), _ = jax.lax.scan(block, (x, carry), jnp.arange(repeats, dtype=jnp.int32))
    seen = Counter({k: repeats * n for k, n in per.items()})
    for kind in tail:
        x, carry = apply(kind, seen[kind], x, carry)
        seen[kind] += 1
    return x, carry


def scan_layers(config: NemotronHConfig, params, x, layer_fn, empty):
    """Walk the layer pattern over a SEQUENCE in one scan whose body switches on the layer's
    kind: ``layer_fn(kind, w, i, x) -> (x, kept)`` with ``kept`` what that layer keeps for the
    cache, a dict with some of ``empty``'s entries (``empty``: name -> zeros of one layer's
    entry). -> (x, {name: [layers, ...]} with a row for EVERY layer, zeros where a layer keeps
    no such entry). One body per KIND, so a prefill program's size and compile time follow the
    kinds and not the depth: 7 s a program against 17 s for ``run_layers``' nine bodies at 16
    layers (compiled for a described v5e, PR 29), and a serving replica warms some twenty."""
    kinds = sorted(set(config.layer_kinds))
    which = jnp.asarray([kinds.index(k) for k in config.layer_kinds], jnp.int32)
    among = jnp.asarray([config.layer_kinds[:n].count(k) for n, k in enumerate(config.layer_kinds)], jnp.int32)

    def branch(kind):
        def run(i, x):
            with jax.named_scope(SCOPES[kind]):
                x, kept = layer_fn(kind, _layer_weights(params, kind, i), i, x)
            return x, {n: kept[n].astype(z.dtype) if n in kept else z for n, z in empty.items()}
        return run

    branches = [branch(k) for k in kinds]

    def body(x, ki):
        return jax.lax.switch(ki[0], branches, ki[1], x)

    return jax.lax.scan(jax.checkpoint(body) if config.remat else body, x, (which, among))


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
    Bm, Cm = (jnp.repeat(a, c.mamba_num_heads // c.n_groups, axis=1)[:, :, None, :] for a in (Bm, Cm))  # [B,nh,1,N]
    ssm = ssm * jnp.exp(dt * A)[..., None, None] + (x * dt[..., None])[..., None] * Bm
    y = jnp.sum(ssm * Cm, axis=-1) + x * w["D"][:, None]
    return _mamba_out(w, y, z, c, xn.dtype), ssm, window[:, 1:]


# ------------------------------------------------------------ E: routed experts
def route(w, x, c: NemotronHConfig):
    """The published router, in float32 whatever the stream's dtype: sigmoid scores over ALL
    experts, the top k of score + correction bias, their own scores as weights, normalised and
    scaled. x [N,H] -> (expert ids [N,k] int32, weights [N,k] f32)."""
    s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32), w["router"].astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + w["router_bias"], c.num_experts_per_tok)
    wt = jnp.take_along_axis(s, idx, axis=-1)
    if c.norm_topk_prob:
        wt = wt / (jnp.sum(wt, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), wt * c.routed_scaling_factor


def _relu2(h):
    return jnp.square(jax.nn.relu(h))


def _shared_expert(w, x):
    return jnp.dot(_relu2(jnp.dot(x, w["shared_up"])), w["shared_down"])


def experts_dense(w, x, idx, wt, c: NemotronHConfig):
    """Every held expert over every row: right for a decode step, whose cost is reading the
    experts' weights either way. A choice held elsewhere has no column here and adds nothing."""
    comb = jnp.einsum("nke,nk->en", jax.nn.one_hot(idx - c.expert_start, c.local_experts, dtype=jnp.float32), wt)
    a = _relu2(jnp.einsum("nh,efh->enf", x, w["w_up"]))
    return jnp.einsum("enf,efh->nh", (a * comb[..., None]).astype(x.dtype), w["w_down"])


def experts_grouped(stacked, layer, x, idx, wt, valid, c: NemotronHConfig):
    """A grouped matmul in plain XLA: the (row, expert) pairs routed here, laid out by expert, each
    expert's run padded to whole blocks of rows, and one loop over the blocks IN USE: a block's
    rows against its expert's two matrices, read straight from the stacked weights. The work
    follows the pairs (plus at most a block an expert), not experts x rows; no pair is dropped,
    whatever the load on one expert. ``valid`` [N] keeps padding out of every group. The loop's
    length is data, so this path has no backward pass (training uses ``experts_dense``).
    ``stacked["w_up"]``/``["w_down"]`` are the arrays STACKED over the expert layers, and the
    loop reads expert e of layer ``layer`` from them: a layer's 0.6 GB of experts, sliced out
    first, would be copied once a layer to become the loop's operand."""
    N, k = idx.shape
    M, El, H = N * k, c.local_experts, x.shape[-1]
    block = 256 if M >= 32768 else 128
    n_rows = (-(-M // block) + El) * block  # the most that padding to whole blocks can need
    local = (idx - c.expert_start).reshape(-1)
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

    def one_block(b, ys):
        e = jnp.sum(last_block <= b).astype(jnp.int32)
        pair = jax.lax.dynamic_slice_in_dim(pair_at, b * block, block)
        ok = pair < M  # the padding at the end of an expert's run holds no pair
        pair = jnp.minimum(pair, M - 1)
        xb = jnp.where(ok[:, None], jnp.take(x, pair // k, axis=0), 0)
        up, down = (jax.lax.dynamic_slice(a, (layer, e, 0, 0), (1, 1) + a.shape[2:])[0, 0]
                    for a in (stacked["w_up"], stacked["w_down"]))
        yb = jnp.dot(_relu2(jnp.einsum("bh,fh->bf", xb, up)), down)
        yb = (yb * jnp.where(ok, scale[pair], 0.0)[:, None]).astype(ys.dtype)
        return jax.lax.dynamic_update_slice(ys, yb, (b * block, jnp.zeros((), jnp.int32)))

    ys = jax.lax.fori_loop(0, last_block[-1], one_block, jnp.zeros((n_rows + 1, H), x.dtype))
    return jnp.sum(jnp.take(ys, place, axis=0).reshape(N, k, H), axis=1, dtype=jnp.float32).astype(x.dtype)


def moe_seq(w, xn, lengths, c: NemotronHConfig, stacked=None):
    """xn [B,T,H] -> [B,T,H]: routed experts held here plus the shared expert. ``stacked`` =
    (the expert layers' stacked weights, this layer's index): the serving path's grouped matmul;
    without it every held expert over every token, which has a backward pass."""
    B, T, H = xn.shape
    idx, wt = route(w, xn.reshape(B * T, H), c)  # on the norm as it comes
    x = xn.reshape(B * T, H).astype(w["w_up"].dtype)
    valid = (jnp.arange(T)[None, :] < lengths[:, None]).reshape(-1)
    if stacked is not None:
        routed = experts_grouped(*stacked, x, idx, wt, valid, c)
    else:
        routed = experts_dense(w, x, idx, jnp.where(valid[:, None], wt, 0.0), c)
    return (routed + _shared_expert(w, x)).reshape(B, T, H)


def moe_step(w, xn, active, c: NemotronHConfig):
    """One token a lane: xn [B,H], active [B] bool -> (out [B,H], [held experts that got a
    token, pairs served here, most tokens at one expert] over the active lanes, float32)."""
    idx, wt = route(w, xn, c)  # on the norm as it comes
    xn = xn.astype(w["w_up"].dtype)
    hot = jax.nn.one_hot(idx - c.expert_start, c.local_experts, dtype=jnp.float32) * active[:, None, None]
    load = jnp.sum(hot, axis=(0, 1))
    stats = jnp.stack([jnp.sum(load > 0).astype(jnp.float32), jnp.sum(load), jnp.max(load)])
    return experts_dense(w, xn, idx, wt, c) + _shared_expert(w, xn), stats


# ----------------------------------------------------------------- *: attention
def qkv(w, xn, c: NemotronHConfig):
    lead = xn.shape[:-1]
    q = jnp.dot(xn, w["wq"]).reshape(*lead, c.num_heads, c.hd)
    k = jnp.dot(xn, w["wk"]).reshape(*lead, c.num_kv_heads, c.hd)
    v = jnp.dot(xn, w["wv"]).reshape(*lead, c.num_kv_heads, c.hd)
    return q, k, v


def attn_seq(w, xn, c: NemotronHConfig, mesh=None):
    """Causal grouped-query attention with NO position embedding. -> (out, k, v [B,T,kv,hd])."""
    B, T, _ = xn.shape
    q, k, v = qkv(w, xn, c)
    o = flash_attention_on_mesh(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                                mesh, c.attention_impl)
    return jnp.dot(o.transpose(0, 2, 1, 3).reshape(B, T, c.num_heads * c.hd), w["wo"]), k, v


def attn_step(w, q, k_cache, v_cache, lengths, c: NemotronHConfig):
    """One token a lane (its query q [B,nh,hd] from ``qkv``) against a layer's rows
    k/v_cache [B,S,kv,hd], in which the new token's key and value already sit at index
    lengths[b]. -> out [B,H]."""
    B, S = k_cache.shape[:2]
    qg = q.reshape(B, c.num_kv_heads, c.num_heads // c.num_kv_heads, c.hd)
    scores = jnp.einsum("bgrh,bsgh->bgrs", qg, k_cache, preferred_element_type=jnp.float32) / math.sqrt(c.hd)
    ok = (jnp.arange(S, dtype=jnp.int32)[None, :] <= lengths[:, None])[:, None, None]
    probs = jax.nn.softmax(jnp.where(ok, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bgrs,bsgh->bgrh", probs, v_cache.astype(jnp.float32))
    return jnp.dot(o.reshape(B, c.num_heads * c.hd).astype(q.dtype), w["wo"])


# ------------------------------------------------------------- sequence forward
def forward_hidden(params, tokens, lengths, config: NemotronHConfig, mesh=None, collect: bool = False):
    """tokens [B,T] right-padded, lengths [B] -> the final-norm'd stream [B,T,H] and, with
    ``collect`` (the serving prefill; its expert layers run the grouped matmul, which has no
    backward pass), what each caching layer keeps: {"k","v" [La,B,T,kv,hd], "ssm"
    [Lm,B,nh,P,N], "conv" [Lm,B,K-1,C]} with the recurrent state at each sequence's true length."""
    c = config
    B, T = tokens.shape
    dt, sd = params["embed"].dtype, c.stream_dtype
    x = jnp.take(params["embed"], tokens, axis=0).astype(sd)
    empty, rows = {}, {}
    if collect:
        for kind, spec in c.cache_spec().items():
            for name, (shape, dtype, per) in spec.items():
                empty[name] = jnp.zeros(((B, T) if per == "position" else (B,)) + shape, jnp.dtype(dtype))
                rows[name] = jnp.asarray([n for n, k in enumerate(c.layer_kinds) if k == kind], jnp.int32)

    def layer(kind, w, i, x):
        xn = rms_norm(x, w["norm"], c.rms_eps)
        if kind == "mamba":
            y, ssm, conv = mamba2_seq(w, xn.astype(dt), lengths, c)
            kept = {"ssm": ssm, "conv": conv}
        elif kind == "moe":
            y, kept = moe_seq(w, xn, lengths, c, stacked=(params["moe"], i) if collect else None), {}
        else:
            y, k, v = attn_seq(w, xn.astype(dt), c, mesh)
            kept = {"k": k, "v": v}
        return x + y.astype(sd), kept if collect else {}

    x, every = scan_layers(c, params, x, layer, empty)
    out = {name: jnp.take(every[name], rows[name], axis=0) for name in empty}  # the layers that keep it
    return rms_norm(x, params["final_norm"], c.rms_eps).astype(dt), out


def forward(params, tokens, config: NemotronHConfig, mesh=None):
    """tokens [B,T] -> logits [B,T,vocab] f32, every position real."""
    lengths = jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32)
    x, _ = forward_hidden(params, tokens, lengths, config, mesh)
    return jnp.dot(x, params["unembed"], preferred_element_type=jnp.float32)


def loss_fn(params, batch, config: NemotronHConfig, mesh=None):
    """batch: {tokens [B,T], targets [B,T] (-100 = ignore)} -> scalar loss."""
    return cross_entropy_loss(forward(params, batch["tokens"], config, mesh=mesh), batch["targets"])
