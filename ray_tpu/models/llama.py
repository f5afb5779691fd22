"""Llama-family transformer, TPU-native.

Functional pytree implementation (no framework classes on the hot path):
- layers stacked into single arrays and iterated with `lax.scan` (one XLA
  compilation of one layer; constant compile time in depth)
- `jax.checkpoint` per layer (rematerialization trades FLOPs for HBM)
- GQA + RoPE + SwiGLU + RMSNorm (Llama-2/3 architecture)
- every parameter carries a logical-axes annotation consumed by
  ray_tpu.parallel.mesh.ShardingRules, lowering DP/FSDP/TP/SP configs to
  GSPMD NamedShardings (the TPU-native equivalent of the reference's
  DDP/FSDP wrapping in train/torch/train_loop_utils.py:153,374 and vLLM
  tensor_parallel_size pass-through in vllm_models.py:215)

KV-cache decode path for serving lives in ray_tpu.llm.engine.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu.ops.flash_attention import flash_attention_on_mesh
from ray_tpu.ops.layers import apply_rope, cross_entropy_loss, rms_norm, rotary_embedding
from ray_tpu.util.profiling import scope, scoped


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: int | None = None
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: str = "bfloat16"
    remat: bool = True
    # jax.checkpoint_policies name: "nothing_saveable" recomputes the whole
    # layer in backward (min HBM); "dots_with_no_batch_dims_saveable" keeps
    # matmul outputs (fewer recompute FLOPs when HBM allows)
    remat_policy: str = "nothing_saveable"
    scan_layers: bool = True
    attention_impl: str = "auto"  # auto | pallas | xla
    tie_embeddings: bool = False

    @property
    def hd(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    def flash_calls(self, length: int) -> dict:
        """{head width: calls} of ``ops/flash_attention`` in one prefill program (``HybridDescription.flash_calls``)."""
        return {self.hd: self.num_layers}

    @staticmethod
    def llama2_7b(**kw):
        return LlamaConfig(**{**dict(vocab_size=32000, hidden_size=4096, intermediate_size=11008, num_layers=32, num_heads=32, num_kv_heads=32), **kw})

    @staticmethod
    def llama3_8b(**kw):
        return LlamaConfig(**{**dict(vocab_size=128256, hidden_size=4096, intermediate_size=14336, num_layers=32, num_heads=32, num_kv_heads=8, rope_theta=500000.0), **kw})

    @staticmethod
    def tiny(**kw):
        return LlamaConfig(**{**dict(vocab_size=512, hidden_size=128, intermediate_size=256, num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=256), **kw})

    def num_params(self) -> int:
        h, i, v, L = self.hidden_size, self.intermediate_size, self.vocab_size, self.num_layers
        hd = self.hd
        attn = h * (self.num_heads * hd) + 2 * h * (self.num_kv_heads * hd) + (self.num_heads * hd) * h
        mlp = 3 * h * i
        return L * (attn + mlp + 2 * h) + v * h * (1 if self.tie_embeddings else 2) + h


# logical axes per parameter (leaf name -> tuple of logical dims);
# layer-stacked params get a leading "layers" (unsharded) axis
PARAM_AXES = {
    "embed": ("vocab", "embed"),
    "unembed": ("embed", "vocab"),
    "final_norm": (None,),
    "layers": {
        "wq": (None, "embed", "heads"),
        "wk": (None, "embed", "kv_heads"),
        "wv": (None, "embed", "kv_heads"),
        "wo": (None, "heads", "embed"),
        "w_gate": (None, "embed", "mlp"),
        "w_up": (None, "embed", "mlp"),
        "w_down": (None, "mlp", "embed"),
        "attn_norm": (None, None),
        "mlp_norm": (None, None),
    },
}


def param_logical_axes(config: LlamaConfig):
    axes = {
        "embed": PARAM_AXES["embed"],
        "final_norm": PARAM_AXES["final_norm"],
        "layers": dict(PARAM_AXES["layers"]),
    }
    if not config.tie_embeddings:
        axes["unembed"] = PARAM_AXES["unembed"]
    return axes


def init_params(config: LlamaConfig, key) -> dict:
    h = config.hidden_size
    hd = config.hd
    dt = jnp.dtype(config.dtype)
    L = config.num_layers
    keys = jax.random.split(key, 10)

    def norm_init(*shape):
        return jnp.ones(shape, dtype=dt)

    def dense_init(k, *shape, fan_in):
        return (jax.random.normal(k, shape, dtype=jnp.float32) * (fan_in**-0.5)).astype(dt)

    params = {
        "embed": dense_init(keys[0], config.vocab_size, h, fan_in=h),
        "final_norm": norm_init(h),
        "layers": {
            "wq": dense_init(keys[1], L, h, config.num_heads * hd, fan_in=h),
            "wk": dense_init(keys[2], L, h, config.num_kv_heads * hd, fan_in=h),
            "wv": dense_init(keys[3], L, h, config.num_kv_heads * hd, fan_in=h),
            "wo": dense_init(keys[4], L, config.num_heads * hd, h, fan_in=config.num_heads * hd),
            "w_gate": dense_init(keys[5], L, h, config.intermediate_size, fan_in=h),
            "w_up": dense_init(keys[6], L, h, config.intermediate_size, fan_in=h),
            "w_down": dense_init(keys[7], L, config.intermediate_size, h, fan_in=config.intermediate_size),
            "attn_norm": norm_init(L, h),
            "mlp_norm": norm_init(L, h),
        },
    }
    if not config.tie_embeddings:
        params["unembed"] = dense_init(keys[8], h, config.vocab_size, fan_in=h)
    return params


def _attention(x, layer, config: LlamaConfig, cos, sin, positions, mesh=None):
    B, T, H = x.shape
    nh, nkv, hd = config.num_heads, config.num_kv_heads, config.hd
    xn = rms_norm(x, layer["attn_norm"], config.rms_eps)
    q = jnp.dot(xn, layer["wq"]).reshape(B, T, nh, hd).transpose(0, 2, 1, 3)
    k = jnp.dot(xn, layer["wk"]).reshape(B, T, nkv, hd).transpose(0, 2, 1, 3)
    v = jnp.dot(xn, layer["wv"]).reshape(B, T, nkv, hd).transpose(0, 2, 1, 3)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    if mesh is not None and "sp" in mesh.axis_names:
        # sequence parallelism: ring attention over the sp axis (shard_map
        # + ppermute on ICI; ray_tpu/parallel/ring_attention.py)
        from ray_tpu.parallel.ring_attention import sp_attention

        rep = nh // nkv
        if rep > 1:
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
        o = sp_attention(q, k, v, mesh, impl="ring", causal=True)
    else:
        o = flash_attention_on_mesh(q, k, v, mesh, config.attention_impl)
    o = o.transpose(0, 2, 1, 3).reshape(B, T, nh * hd)
    return x + jnp.dot(o, layer["wo"])


def _mlp(x, layer, config: LlamaConfig):
    xn = rms_norm(x, layer["mlp_norm"], config.rms_eps)
    g = jnp.dot(xn, layer["w_gate"])
    u = jnp.dot(xn, layer["w_up"])
    return x + jnp.dot(jax.nn.silu(g) * u, layer["w_down"])


# the block's two halves under the scopes the serving step programs give them (``llm/model_runner.py``)
_attention_block = scoped("attn", _attention)
_mlp_block = scoped("mlp", _mlp)


def _layer_fn(x, layer, config: LlamaConfig, cos, sin, positions, mesh=None):
    x = _attention_block(x, layer, config, cos, sin, positions, mesh=mesh)
    x = _mlp_block(x, layer, config)
    return x


def forward(params: dict, tokens, config: LlamaConfig, positions=None, mesh=None):
    """tokens: [B, T] int32 -> logits [B, T, vocab]."""
    B, T = tokens.shape
    if positions is None:
        positions = jnp.arange(T, dtype=jnp.int32)
    cos, sin = rotary_embedding(positions, config.hd, config.rope_theta, dtype=jnp.float32)
    with scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0)

    layer_fn = partial(_layer_fn, config=config, cos=cos, sin=sin, positions=positions, mesh=mesh)
    if config.remat:
        policy = getattr(jax.checkpoint_policies, config.remat_policy)
        layer_fn = jax.checkpoint(layer_fn, policy=policy)

    if config.scan_layers:
        def scan_body(carry, layer):
            return layer_fn(carry, layer), None

        x, _ = jax.lax.scan(scan_body, x, params["layers"])
    else:
        L = config.num_layers
        for i in range(L):
            layer = jax.tree.map(lambda p: p[i], params["layers"])
            x = layer_fn(x, layer)

    with scope("head"):
        x = rms_norm(x, params["final_norm"], config.rms_eps)
        unembed = params["embed"].T if config.tie_embeddings else params["unembed"]
        return jnp.dot(x, unembed, preferred_element_type=jnp.float32)


def loss_fn(params, batch, config: LlamaConfig, mesh=None):
    """batch: {tokens [B,T], targets [B,T] (-100 = ignore)} -> scalar loss."""
    logits = forward(params, batch["tokens"], config, mesh=mesh)
    with scope("head"):
        return cross_entropy_loss(logits, batch["targets"])


def flops_per_token(config: LlamaConfig, seq_len: int | None = None) -> float:
    """Training FLOPs/token ≈ 6N + attention quadratic term."""
    n = config.num_params()
    f = 6.0 * n
    if seq_len:
        # 12 * L * H * T * hd per token (fwd+bwd attention scores+values)
        f += 12.0 * config.num_layers * config.num_heads * seq_len * config.hd
    return f
