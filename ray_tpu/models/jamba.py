"""Jamba decoder (``model_type`` ``jamba``), as its dense members publish it (AI21-Jamba2-3B):
Mamba-1 selective state-space layers with an attention layer every ``attn_layer_period``, a dense
SwiGLU after every mixer, a head tied to the embedding.

A ninth DESCRIPTION over the one layer loop (``models/hybrid.py``). Every published decoder layer
is two residual sub-blocks over ``N(x) = w * x / sqrt(mean(x²) + eps)`` in float32:
``x' = x + mixer(N_in(x))``, ``x'' = x' + ffn(N_ff(x'))``; after the last layer one more ``N``, then
the head, which is the embedding table itself (the weights hold no ``unembed``: ``hybrid.head``). So
the loop walks ``2 x num_hidden_layers`` sub-blocks of three kinds, ``mamba1 | attn`` then ``ffn``:

- ``mamba1`` (every layer but those below; scope ``mamba1``): ``[u, z] = h W_in`` (H -> 2 x ``d_inner``,
  ``d_inner = mamba_expand x H``); ``c_t = silu(b + sum_k w_k u_{t-3+k})``, a causal depthwise
  convolution of ``mamba_d_conv`` taps with a bias (scope ``mamba1.conv``); ``[r, B, C] = c W_x``
  (``mamba_dt_rank + 2 x mamba_d_state``), each through an RMSNorm of its own; the step
  ``dt = softplus(r~ W_dt + b_dt)`` a channel; ``A = -exp(A_log)`` ``[d_inner, d_state]``; then the
  selective scan (scope ``mamba1.scan``; ``ops/selective_scan.py`` states the recurrence and why it
  is a kernel): EVERY (channel, state) pair decays by its own ``exp(dt[d] A[d, n])``. That is what
  tells it from ``models/nemotron_h.py``'s Mamba-2, where one scalar decays a head of 64 channels
  and a chunk is therefore a masked matmul; here there are no heads and no matmul form.
  ``mixer = (y * silu(z)) W_out``. What a sequence keeps of such a layer: the state ``ssm``
  ``[d_state, d_inner]`` float32 AT its true length (channels last, as the kernel holds it: on the
  chip the last axis is padded to the 128 lanes, and ``[d_inner, 16]`` would take eight times its
  bytes in the cache and in every step) and the convolution's window ``conv``, its last
  ``mamba_d_conv - 1`` inputs ``u``. The step reads and writes both under ``mamba1.state``.
- ``attn`` (layer ``l`` where ``l % attn_layer_period == attn_layer_offset``; scope ``attn``):
  grouped-query attention with NO position embedding of any kind, which is
  ``models/nemotron_h.attn_seq`` / ``attn_step`` to the letter (position is carried by the
  state-space layers in both models); 20 query heads over ONE key-value head as published.
- ``ffn`` (after every mixer; scope ``ffn``): SwiGLU at ``intermediate_size``,
  ``glm4_moe_lite.ffn`` over ``ops/layers.live_slabs``. The larger members of the family route
  this sub-block to experts every ``expert_layer_period`` layers; this description does not, and a
  config with ``num_experts`` > 1 raises.

Precision: weights, stream, window, cache and matmul operands in the weights' dtype (bfloat16 as
published), accumulation float32; norms, the convolution's sum, the softplus, the recurrence and
the state float32; ``c``, the step before its bias, ``B~``, ``C~`` and ``y`` in the weights' dtype,
which is how the published kernel takes and gives them.

Initialisation (weights are random from a seed): matrices N(0, fan_in^-1/2), every projection back
onto the stream 1/sqrt(``residual_rescale_layers``) smaller, norms 1; Mamba's published scheme
where stability hangs on it: ``A_log = log(1 .. d_state)`` in every channel, ``D = 1``, ``b_dt`` the
inverse softplus of a log-uniform step in [``time_step_min``, ``time_step_max``]; the convolution's
bias uniform in +-taps^-1/2 (a framework's default for it: zero would let a program that left it
out pass); and the final norm's weight ``+-head_scale / sqrt(mean |embedding row|²)`` with random
signs, for the reason ``models/lfm2.py`` gives: under a TIED head a weight of 1 hands every token
its own id back at log-probability 0 and no comparison has teeth.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ray_tpu.models.glm4_moe_lite import ffn
from ray_tpu.models.hybrid import HybridDescription, Mixer, forward, init_stacked, loss_fn  # noqa: F401 - the shared forward and loss, as the harness's family asks for them
from ray_tpu.models.nemotron_h import attn_seq, attn_step, qkv  # attention without positions: the same lines, the same reason
from ray_tpu.models.qwen3_next import a_few_at_a_time
from ray_tpu.ops import selective_scan
from ray_tpu.ops.layers import rms_norm
from ray_tpu.util.profiling import scope


@dataclass(frozen=True)
class JambaConfig(HybridDescription):
    vocab_size: int = 65536
    hidden_size: int = 2560
    num_hidden_layers: int = 28  # decoder layers: each a mixer sub-block and an ffn sub-block
    attn_layer_period: int = 14  # layer l is attention where l % period == offset, Mamba elsewhere
    attn_layer_offset: int = 7
    intermediate_size: int = 8192
    # mamba1
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False  # a bias on the mixer's input and output projections
    time_step_min: float = 0.001  # init only
    time_step_max: float = 0.1
    # attn
    num_heads: int = 20
    num_kv_heads: int = 1
    head_dim: int = 128
    num_experts: int = 1  # this description routes nothing: > 1 raises
    rms_eps: float = 1e-6
    # init only: every sub-block's projection back onto the stream is drawn 1/sqrt(this) smaller; 1 turns it off
    residual_rescale_layers: int = 56
    head_scale: float = 1.4  # init only: the spread of the tied head's logits, through the final norm's weight
    max_seq_len: int = 12288
    dtype: str = "bfloat16"
    remat: bool = False
    attention_impl: str = "auto"  # auto | pallas | xla

    def __post_init__(self):
        if self.num_experts > 1:
            raise ValueError(f"num_experts {self.num_experts}: Jamba's larger members route their feed-forward sub-block to experts every "
                             "expert_layer_period layers; this description holds the dense SwiGLU alone (no router, no expert layer), "
                             "which is all that a member with num_experts 1 has")
        if not 0 <= self.attn_layer_offset < self.attn_layer_period:
            raise ValueError("attn_layer_offset lies inside attn_layer_period")
        if self.num_heads % self.num_kv_heads:
            raise ValueError("key-value heads divide the query heads")

    # ---- the description the layer loop, the engine and the cache manager read
    def init_params(self, key):
        return init_params(self, key)

    @property
    def layer_kinds(self) -> tuple:
        return tuple(kind for l in range(self.num_hidden_layers)
                     for kind in ("attn" if l % self.attn_layer_period == self.attn_layer_offset else "mamba1", "ffn"))

    @property
    def mixers(self) -> dict:
        """kind -> its scope in a profile and its two forms (``models/hybrid.Mixer``)."""
        dt = jnp.dtype(self.dtype)

        def mamba_seq(w, xn, ctx):
            y, ssm, window = a_few_at_a_time(lambda xn, lengths: mamba1_seq(w, xn, lengths, self, ctx.mesh), xn.astype(dt), ctx.lengths)
            return y, {"ssm": ssm, "conv": window}

        def mamba_step(w, xn, cache, ctx):
            with scope("mamba1.state"):  # the state's and the window's read here, the decay in ``mamba1_step``, their way back below
                ssm, window = cache.read("ssm"), cache.read("conv")
            y, ssm, window = mamba1_step(w, xn.astype(dt), ssm, window, self)
            with scope("mamba1.state"):
                cache.write("ssm", ssm)
                cache.write("conv", window)
            return y, None

        def attention_seq(w, xn, ctx):
            y, k, v = attn_seq(w, xn.astype(dt), self, ctx.mesh, ctx.skippable)
            return y, {"k": k, "v": v}

        def attention_step(w, xn, cache, ctx):
            q, k, v = qkv(w, xn.astype(dt), self)
            cache.write("k", k)
            cache.write("v", v)
            return attn_step(w, q, cache, ctx, self), None

        return {"mamba1": Mixer("mamba1", mamba_seq, mamba_step), "attn": Mixer("attn", attention_seq, attention_step),
                "ffn": Mixer("ffn", lambda w, xn, ctx: (ffn(w, xn.astype(dt), ctx.skippable, ctx.stacked), {}), lambda w, xn, cache, ctx: (ffn(w, xn.astype(dt)), None))}

    def norm(self, x, w):
        return rms_norm(x, w, self.rms_eps)

    @property
    def hd(self) -> int:
        return self.head_dim

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def stream_dtype(self):
        return jnp.dtype(self.dtype)

    def cache_spec(self) -> dict:
        """kind -> {name: (shape, dtype, "position" | "sequence")}: keys and values of every position in an
        attention layer; the state (states x channels, float32) and the convolution's window of a sequence
        in a Mamba layer."""
        kv = ((self.num_kv_heads, self.hd), self.dtype, "position")
        return {"attn": {"k": kv, "v": kv},
                "mamba1": {"ssm": ((self.mamba_d_state, self.d_inner), "float32", "sequence"),
                           "conv": ((self.mamba_d_conv - 1, self.d_inner), self.dtype, "sequence")},
                "ffn": {}}

    def flash_calls(self, length: int) -> dict:
        return {self.hd: self.count("attn")}

    def prefill_counters(self, batch: int, length: int, lengths=()) -> dict:
        """Positions (as padded) x Mamba layers that ONE prefill program scans, and those of them the kernel runs."""
        return selective_scan.counters(self.count("mamba1"), batch, length, self.dtype, self.d_inner, self.mamba_d_state)

    def num_params(self) -> int:
        """Parameters held: the embedding table counts once, the head is the table."""
        n = self.vocab_size * self.hidden_size + self.hidden_size
        for g, group in _shapes(self).items():
            n += self.count(g) * sum(math.prod(shape) for shape, _ in group.values())
        return n + self.count("mamba1") * sum(math.prod(shape) for shape in _mamba_vectors(self).values())

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=8, attn_layer_period=4, attn_layer_offset=1, intermediate_size=96,
                    mamba_dt_rank=8, num_heads=4, num_kv_heads=1, head_dim=16, residual_rescale_layers=16, max_seq_len=128, dtype="float32")
        return JambaConfig(**{**base, **kw})


# ------------------------------------------------------------------ parameters
def _shapes(c: JambaConfig) -> dict:
    """group -> {name: (shape of one layer, fan_in or fill)}: matrices are N(0, fan_in^-1/2), the
    projections back onto the residual stream 1/sqrt(N) smaller, norms 1. A convolution's taps
    [taps, d_inner], the oldest input's first."""
    H, N, di, R, S, K = c.hidden_size, c.residual_rescale_layers, c.d_inner, c.mamba_dt_rank, c.mamba_d_state, c.mamba_d_conv
    q, kv, F = c.num_heads * c.hd, c.num_kv_heads * c.hd, c.intermediate_size
    mamba = {"norm": ((H,), 1.0), "in_proj": ((H, 2 * di), H), "conv_w": ((K, di), K), "x_proj": ((di, R + 2 * S), di),
             "dt_norm": ((R,), 1.0), "b_norm": ((S,), 1.0), "c_norm": ((S,), 1.0), "dt_proj": ((R, di), R), "out_proj": ((di, H), di * N)}
    if c.mamba_proj_bias:
        mamba.update(in_bias=((2 * di,), 0.0), out_bias=((H,), 0.0))
    return {"mamba1": mamba,
            "attn": {"norm": ((H,), 1.0), "wq": ((H, q), H), "wk": ((H, kv), H), "wv": ((H, kv), H), "wo": ((q, H), q * N)},
            "ffn": {"norm": ((H,), 1.0), "w_gate": ((H, F), H), "w_up": ((H, F), H), "w_down": ((F, H), F * N)}}


def _mamba_vectors(c: JambaConfig) -> dict:
    """What a Mamba layer holds beside ``_shapes``' entries, float32 but the convolution's bias: name -> shape of one layer."""
    di = c.d_inner
    return {"dt_bias": (di,), "A_log": (di, c.mamba_d_state), "D": (di,), **({"conv_b": (di,)} if c.mamba_conv_bias else {})}


def init_params(config: JambaConfig, key):
    """Weights from a seed, stacked by layer kind: no ``unembed`` (the head is tied); the module docstring says what is not N(0, fan_in^-1/2)."""
    c, dt = config, jnp.dtype(config.dtype)
    keys = iter(jax.random.split(key, 32))
    params = init_stacked(_shapes(c), c.count, keys, dt)
    n, S, K = c.count("mamba1"), c.mamba_d_state, c.mamba_d_conv
    if n:
        stacked = {name: (n,) + shape for name, shape in _mamba_vectors(c).items()}
        step = jnp.exp(jax.random.uniform(next(keys), stacked["dt_bias"]) * (math.log(c.time_step_max) - math.log(c.time_step_min)) + math.log(c.time_step_min))
        params["mamba1"]["dt_bias"] = step + jnp.log(-jnp.expm1(-step))  # softplus^-1
        params["mamba1"]["A_log"] = jnp.broadcast_to(jnp.log(jnp.arange(1, S + 1, dtype=jnp.float32)), stacked["A_log"])
        params["mamba1"]["D"] = jnp.ones(stacked["D"], jnp.float32)
        if c.mamba_conv_bias:
            params["mamba1"]["conv_b"] = jax.random.uniform(next(keys), stacked["conv_b"], minval=-K ** -0.5, maxval=K ** -0.5).astype(dt)
    embed = jax.random.normal(next(keys), (c.vocab_size, c.hidden_size), jnp.float32)
    params["embed"] = embed.astype(dt)
    signs = jnp.where(jax.random.bernoulli(next(keys), 0.5, (c.hidden_size,)), 1.0, -1.0)
    row = jnp.sqrt(jnp.mean(jnp.sum(jnp.square(embed), axis=-1)))  # the root mean square length of an embedding row
    params["final_norm"] = (signs * c.head_scale / row).astype(dt)
    return params


def param_logical_axes(config: JambaConfig):
    """Logical axes for ``parallel/mesh.ShardingRules`` (vocabulary, heads and the feed-forward width are the
    axes a mesh could split; the serving engine refuses a mesh for this model today)."""
    mamba = {"norm": (None,), "in_proj": ("embed", None), "conv_w": (None, None), "x_proj": (None, None), "dt_norm": (None,), "b_norm": (None,),
             "c_norm": (None,), "dt_proj": (None, None), "out_proj": (None, "embed"), "dt_bias": (None,), "A_log": (None, None), "D": (None,)}
    if config.mamba_conv_bias:
        mamba["conv_b"] = (None,)
    if config.mamba_proj_bias:
        mamba.update(in_bias=(None,), out_bias=(None,))
    lead = {"mamba1": mamba,
            "attn": {"norm": (None,), "wq": ("embed", "heads"), "wk": ("embed", "kv_heads"), "wv": ("embed", "kv_heads"), "wo": ("heads", "embed")},
            "ffn": {"norm": (None,), "w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"), "w_down": ("mlp", "embed")}}
    axes = {g: {n: (None,) + a for n, a in group.items()} for g, group in lead.items() if config.count(g)}
    axes.update(embed=("vocab", "embed"), final_norm=(None,))
    return axes


# ------------------------------------------------------- mamba1: the selective state-space layer
def _in(w, xn, c: JambaConfig):
    """``[u, z] = h W_in``: the convolution's input and the output's gate, each [.., d_inner] in the weights' dtype."""
    uz = jnp.dot(xn, w["in_proj"])
    return jnp.split(uz + w["in_bias"] if c.mamba_proj_bias else uz, 2, axis=-1)


def _activated(conv, w, c: JambaConfig, dtype):
    """The convolution's float32 sum -> ``c = silu(sum + bias)`` in the weights' dtype."""
    return jax.nn.silu(conv + w["conv_b"].astype(jnp.float32) if c.mamba_conv_bias else conv).astype(dtype)


def scan_inputs(w, cx, c: JambaConfig):
    """``[r, B, C] = c W_x`` through their norms, and the step before its bias and softplus: cx [.., d_inner] ->
    (s [.., d_inner], B~ [.., d_state], C~ [.., d_state]) in cx's dtype, and A [d_inner, d_state] float32."""
    R, S = c.mamba_dt_rank, c.mamba_d_state
    rbc = jnp.dot(cx, w["x_proj"])
    r, Bm, Cm = (c.norm(a, w[n]) for a, n in zip((rbc[..., :R], rbc[..., R:R + S], rbc[..., R + S:]), ("dt_norm", "b_norm", "c_norm")))
    return jnp.dot(r, w["dt_proj"]), Bm, Cm, -jnp.exp(w["A_log"].astype(jnp.float32))


def _out(w, y, z, c: JambaConfig, dtype):
    """``(y * silu(z)) W_out``."""
    out = jnp.dot((y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))).astype(dtype), w["out_proj"])
    return out + w["out_bias"] if c.mamba_proj_bias else out


def mamba1_seq(w, xn, lengths, c: JambaConfig, mesh=None):
    """xn [B,T,H], lengths [B] -> (out [B,T,H], ssm [B,d_state,d_inner] f32, conv [B,taps-1,d_inner]): the state and
    the convolution's window AT each sequence's true length."""
    T, K = xn.shape[1], c.mamba_d_conv
    u, z = _in(w, xn, c)
    with scope("mamba1.conv"):
        padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))  # index j holds position j - (K-1): zeros before the sequence
        taps = w["conv_w"].astype(jnp.float32)
        cx = _activated(sum(padded[:, k:k + T].astype(jnp.float32) * taps[k] for k in range(K)), w, c, xn.dtype)
        window = jax.vmap(lambda p, n: jax.lax.dynamic_slice_in_dim(p, n, K - 1, 0))(padded, lengths)
    s, Bm, Cm, A = scan_inputs(w, cx, c)
    with scope("mamba1.scan"):
        y, ssm = selective_scan.selective_scan(cx, s, A, Bm, Cm, w["D"], w["dt_bias"], lengths, mesh=mesh)
    return _out(w, y, z, c, xn.dtype), ssm, window


def mamba1_step(w, xn, ssm, window, c: JambaConfig):
    """One token: xn [B,H], ssm [B,d_state,d_inner] f32, window [B,taps-1,d_inner] -> (out [B,H], ssm, window)."""
    u, z = _in(w, xn, c)
    with scope("mamba1.conv"):
        window = jnp.concatenate([window, u[:, None].astype(window.dtype)], axis=1)  # [B,taps,d_inner]
        cx = _activated(jnp.sum(window.astype(jnp.float32) * w["conv_w"].astype(jnp.float32), axis=1), w, c, xn.dtype)
    s, Bm, Cm, A = scan_inputs(w, cx, c)
    with scope("mamba1.state"):
        y, ssm = selective_scan.step(ssm, cx, s, A, Bm, Cm, w["D"], w["dt_bias"])
    return _out(w, y.astype(xn.dtype), z, c, xn.dtype), ssm, window[:, 1:]
