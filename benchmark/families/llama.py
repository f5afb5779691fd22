"""The dense Llama block: pre-norm decoder, RMSNorm, rotary position embedding on q and k in the
rotate-half convention, grouped-query causal attention, SwiGLU feed forward, no biases, no window,
untied output head. The program's side is ``ray_tpu.models.llama``; the plain reference below is
written from the published description of the block, not from that file. Sizes come from a
configuration file's published keys (``hidden_size`` ...), never from the program's config object.
The weights are the pytree the program serves or trains (``embed``, ``unembed``, ``final_norm``,
``layers`` stacked on a leading axis), read, never copied whole.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.flops import attention_flops_fwd
from ray_tpu.models.llama import LlamaConfig, init_params, loss_fn, param_logical_axes  # noqa: F401 - the family's names

# sizes of the CPU rehearsal (--rehearse): wiring only, never a measurement
REHEARSAL_SIZES = {"hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 2,
                   "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32, "vocab_size": 512}


# ------------------------------------------------------------------------------ the program's side
def program_config(c: dict, max_seq_len: int, **extra) -> LlamaConfig:
    """The program's ``LlamaConfig`` for a configuration file's published keys."""
    return LlamaConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"], intermediate_size=c["intermediate_size"],
        num_layers=c["num_hidden_layers"], num_heads=c["num_attention_heads"], num_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim"), max_seq_len=max_seq_len, rope_theta=float(c["rope_theta"]),
        rms_eps=float(c["rms_norm_eps"]), tie_embeddings=bool(c["tie_word_embeddings"]),
        dtype={"bfloat16": "bfloat16", "float32": "float32"}[c.get("torch_dtype", "bfloat16")], **extra)


def rehearsal(c: dict) -> dict:
    return {**c, **REHEARSAL_SIZES, "torch_dtype": "float32"}


def kernels_expected(c: dict) -> dict:
    """The flash attention kernel: a Pallas kernel lowers to a ``tpu_custom_call``."""
    return {"flash kernel": "tpu_custom_call"}


# ----------------------------------------------------------------------------- operations, from shapes
def matmul_params(c: dict) -> int:
    """Parameters that take part in a matrix multiplication for every token: the blocks and the
    output head. The embedding table is a lookup, not a matmul, and is left out."""
    h, i, L = c["hidden_size"], c["intermediate_size"], c["num_hidden_layers"]
    hd = c.get("head_dim") or h // c["num_attention_heads"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    return L * (h * q + 2 * h * kv + q * h + 3 * h * i) + h * c["vocab_size"]


def train_flops_per_token(c: dict, seq: int) -> float:
    """FLOPs the forward and backward passes require per trained token: 6 per matmul parameter
    plus three times the causal attention forward. Recomputation (remat) is not counted."""
    attn = 3.0 * c["num_hidden_layers"] * attention_flops_fwd(c, 1, seq) / seq
    return 6.0 * matmul_params(c) + attn


# --------------------------------------------------------------------------------- the plain reference
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [T, heads, hd]. Rotate-half convention: pairs (i, i + hd/2) turn by pos * theta^(-2i/hd)."""
    T, _, hd = x.shape
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("nh", "nkv", "hd", "theta", "eps"))
def _layer(x, layers, i, *, nh, nkv, hd, theta, eps):
    """One block on x [T, H] in float32; ``layers`` is the stacked pytree, ``i`` the layer."""
    w = jax.tree.map(lambda p: jax.lax.dynamic_index_in_dim(p, i, 0, keepdims=False).astype(jnp.float32), layers)
    T = x.shape[0]
    xn = _rms(x, w["attn_norm"], eps)
    q = _rope((xn @ w["wq"]).reshape(T, nh, hd), theta)
    k = _rope((xn @ w["wk"]).reshape(T, nkv, hd), theta)
    v = (xn @ w["wv"]).reshape(T, nkv, hd)
    k, v = jnp.repeat(k, nh // nkv, axis=1), jnp.repeat(v, nh // nkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v).reshape(T, nh * hd)
    x = x + o @ w["wo"]
    xn = _rms(x, w["mlp_norm"], eps)
    return x + (jax.nn.silu(xn @ w["w_gate"]) * (xn @ w["w_up"])) @ w["w_down"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, unembed, *, eps):
    return jax.nn.log_softmax(_rms(x, final_norm.astype(jnp.float32), eps) @ unembed.astype(jnp.float32), axis=-1)


def hidden_states(params: dict, tokens, c: dict):
    """tokens [T] int32 -> the last block's output [T, H], float32."""
    hd = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(tokens, jnp.int32), axis=0).astype(jnp.float32)
        for i in range(c["num_hidden_layers"]):
            x = _layer(x, params["layers"], i, nh=c["num_attention_heads"], nkv=c["num_key_value_heads"],
                       hd=hd, theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]))
    return x


def reference_logprobs(params: dict, tokens, c: dict, start: int, stop: int):
    """Log-probabilities [stop - start, vocab] that the model gives, after reading
    tokens[: p + 1], to the token at position p + 1, for p in [start, stop)."""
    x = hidden_states(params, tokens, c)[start:stop]
    with jax.default_matmul_precision("highest"):
        return _head(x, params["final_norm"], params["unembed"], eps=float(c["rms_norm_eps"]))
