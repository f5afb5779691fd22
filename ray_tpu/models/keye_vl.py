"""Keye-VL-2.0's language model (``model_type`` ``KeyeVL2``): grouped-query attention under a
LEARNED index (a DeepSeek-Sparse-Attention indexer: a separate scoring network with a key of its
own a position) in every layer, and softmax-routed SwiGLU experts, none shared, in every layer.

An eighth DESCRIPTION over the one layer loop (``models/hybrid.py``) and the one expert layer
(``models/experts.py``). Every published decoder layer is two residual sub-blocks over
``N(x) = w * x / sqrt(mean(x²) + eps)`` in float32, no bias: ``x' = x + indexed(N(x))``,
``x'' = x' + moe(N(x'))``; then a final ``N`` and an untied head. The loop walks
``2 x num_hidden_layers`` sub-blocks of two kinds:

- ``indexed`` (scope ``indexed``): ``num_heads`` query heads over ``num_kv_heads`` key-value heads
  of ``head_dim``; ``N`` over a head's channels on q and on k (one weight vector each), then the
  rotation ``R``: rotate-half over all of a head's channels, theta ``rope_theta``, as M-RoPE
  (``mrope_section``: frequency i takes its angle from position stream 0, 1 or 2; the functions
  here take positions [3, T], and for text, which is all the engine serves, the three streams are
  the token's index and ``R`` is the ordinary rotation). Beside them the INDEXER (scope
  ``indexed.score``): ``qI = W_qI h`` (``index_heads`` heads of ``index_dim``), ``kI = R(LN(W_kI h))``
  (ONE key a position; a LayerNorm with weight and bias), ``qI`` rotated by the same ``R`` over its
  own channels, ``w = W_w h`` in float32. A query at position t attends, in all its heads, to the
  ``index_topk`` positions ``s <= t`` of largest ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``
  (``indexed.select``; all of them while t + 1 <= ``index_topk``; ties to the earlier position)
  with the ordinary softmax scaled by head_dim^-1/2 (``indexed.attend``), then ``W_o``. The choice
  is a query's own, so one sequence holds queries that attend to everything and queries that
  choose, and a lane crosses over while it decodes. ``ops/indexed_attention.py`` has the two forms;
  a prefill bucket of at most ``index_topk`` positions holds no query that chooses and goes through
  the flash kernel. Kept per position: ``k``, ``v`` by head and the indexer's key ``k_idx``, THREE
  entries of the slot cache.
- ``moe`` (scope ``moe``): ``p = softmax(W_r h)`` in float32 over all experts, the top k
  renormalised (``norm_topk_prob``), SwiGLU experts, no shared one: ``experts.route`` as Qwen3-Next uses it.

Precision: weights, stream, caches and matmul operands in the weights' dtype (bfloat16 as
published), accumulation float32; norms, the router, the index scores (exact products of the
bfloat16 ``qI`` and ``kI``, summed and weighed in float32) and the softmaxes float32.

Initialisation (weights are random from a seed): matrices N(0, fan_in^-1/2), every projection back
onto the stream 1/sqrt(``residual_rescale_layers``) smaller, norms 1 but the query and key norms,
which start at ``qk_norm_init``: at 1 a random model's softmax over thousands of keys is nearly
flat and no choice of positions moves a logit (PERF.md section 6, PR 45).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ray_tpu.models import experts
from ray_tpu.models.experts import ExpertLayer
from ray_tpu.models.hybrid import ROUTING, HybridDescription, Mixer, forward, init_stacked, loss_fn  # noqa: F401 - the shared forward and loss, as the harness's family asks for them
from ray_tpu.models.qwen3_next import a_few_at_a_time
from ray_tpu.ops import indexed_attention
from ray_tpu.ops.flash_attention import flash_attention_on_mesh
from ray_tpu.ops.layers import apply_rope, rms_norm
from ray_tpu.util.profiling import scope


@dataclass(frozen=True)
class KeyeVLConfig(HybridDescription):
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48  # decoder layers HELD: each an indexed-attention sub-block and an expert sub-block
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e7
    mrope_section: tuple = (16, 24, 24)  # frequencies of a head's half that take position stream 0, 1, 2
    # the indexer (``sa_config``)
    index_heads: int = 16
    index_dim: int = 64
    index_topk: int = 2048
    # moe: every expert is held, none is shared
    n_routed_experts: int = 128
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    norm_topk_prob: bool = True
    rms_eps: float = 1e-6
    # init only: every sub-block's projection back onto the stream is drawn 1/sqrt(this) smaller; 1 turns it off
    residual_rescale_layers: int = 96
    qk_norm_init: float = 1.0  # init only: what the query and key norms start at (their product scales every attention score)
    max_seq_len: int = 24576
    dtype: str = "bfloat16"
    remat: bool = False
    attention_impl: str = "auto"  # auto | pallas | xla

    def __post_init__(self):
        if self.num_heads % self.num_kv_heads or self.head_dim % 2 or self.index_dim % 2:
            raise ValueError("key-value heads divide the query heads, and a head is rotated in pairs")
        if sum(self.mrope_section) != self.head_dim // 2 or self.head_dim % self.index_dim:
            raise ValueError("mrope_section counts the frequencies of a head's half, and the indexer's head divides the attention head")

    # ---- the description the layer loop, the engine and the cache manager read
    def init_params(self, key):
        return init_params(self, key)

    @property
    def layer_kinds(self) -> tuple:
        return ("indexed", "moe") * self.num_hidden_layers

    @property
    def mixers(self) -> dict:
        """kind -> its scope in a profile and its two forms (``models/hybrid.Mixer``)."""
        dt = jnp.dtype(self.dtype)

        def attention_seq(w, xn, ctx):
            y, k, v, k_idx = indexed_seq(w, xn.astype(dt), ctx.lengths, self, ctx.mesh, ctx.skippable)
            return y, {"k": k, "v": v, "k_idx": k_idx}

        def experts_seq(w, xn, ctx):
            y, counters = experts.moe_seq(w, xn, ctx.lengths, self, stacked=ctx.stacked)
            return y, {ROUTING: counters}

        return {"indexed": Mixer("indexed", attention_seq, lambda w, xn, cache, ctx: (indexed_step(w, xn.astype(dt), cache, ctx, self), None)),
                "moe": Mixer("moe", experts_seq, lambda w, xn, cache, ctx: experts.moe_step(w, xn, ctx.active, self, ctx.stacked), True)}

    def norm(self, x, w):
        return rms_norm(x, w, self.rms_eps)

    @property
    def expert_layer(self) -> ExpertLayer:
        return ExpertLayer(num_experts=self.n_routed_experts, top_k=self.num_experts_per_tok, score="softmax", norm_topk=self.norm_topk_prob,
                           act="swiglu", shared=False)

    @property
    def hd(self) -> int:
        return self.head_dim

    @property
    def stream_dtype(self):
        return jnp.dtype(self.dtype)

    def cache_spec(self) -> dict:
        """kind -> {name: (shape, dtype, "position" | "sequence")}: an indexed layer keeps, for every
        position, a key and a value by head and the indexer's ONE key; an expert layer nothing."""
        kv = (self.num_kv_heads, self.hd)
        return {"indexed": {"k": (kv, self.dtype, "position"), "v": (kv, self.dtype, "position"),
                            "k_idx": ((self.index_dim,), self.dtype, "position")}, "moe": {}}

    @property
    def slot_attention_tile(self) -> dict:
        """What ``ops/slot_attention.refusal`` is asked about the decode step's attention: the one tile
        whose every live row the step reads, the indexer's key (ONE head of ``index_dim``), which the
        live-block kernel does not take. The step goes through ``ops/indexed_attention`` and not
        through that kernel, so the flight log carries no ``attn_blocks_read`` of a kernel that does
        not run; its rows carry ``rows_scored`` and ``rows_chosen``."""
        return dict(num_heads=self.index_heads, num_kv_heads=1, head_dim=self.index_dim)

    def flash_calls(self, length: int) -> dict:
        """A bucket over ``index_topk`` goes through the index, not the flash kernel (``indexed_seq``)."""
        return {self.hd: self.count("indexed")} if length <= self.index_topk else {}

    def prefill_counters(self, batch: int, length: int, lengths=()) -> dict:
        """(query, position) pairs of the indexed layers for prompts of the TRUE ``lengths`` in a bucket
        of ``length``: the causal pairs the indexer scores (none in a bucket of at most ``index_topk``,
        where no query chooses) and the pairs attention reads, min(t + 1, ``index_topk``) a query. And,
        where the two kernels run, ``choice_bytes``: the bytes of choice tables that ONE prefill program
        of ``batch`` x ``length`` writes, a bit a pair a layer, from its SHAPE alone (a tile of padding
        still has its zero words). Where the flash kernel runs (no query chooses) or the XLA form does
        (``ops/indexed_attention.refusal``, asked as ``ops/delta_rule.counters`` asks its own) no table
        is written and the row carries none, which its readers take for 0."""
        L, k = self.count("indexed"), self.index_topk
        causal = sum(int(n) * (int(n) + 1) // 2 for n in lengths)
        over = sum((int(n) - k) * (int(n) - k + 1) // 2 for n in lengths if n > k)  # what the queries past ``index_topk`` leave unread
        counted = {"pairs_scored": L * causal if length > k else 0, "pairs_chosen": L * (causal - over)}
        if length > k and indexed_attention.refusal(self.dtype, self.hd, self.index_dim, length) is None:
            counted["choice_bytes"] = L * batch * length * indexed_attention.choice_words(length) * 4
        return counted

    def decode_counters(self, positions) -> dict:
        """Rows of ``k_idx`` a decode step's indexed layers score for lanes holding ``positions`` (the
        new token's among them), and rows of ``k`` and ``v`` they then attend to."""
        L = self.count("indexed")
        return {"rows_scored": L * sum(int(n) for n in positions), "rows_chosen": L * sum(min(int(n), self.index_topk) for n in positions)}

    def num_params(self) -> int:
        n = 2 * self.vocab_size * self.hidden_size + self.hidden_size
        for g, group in _shapes(self).items():
            n += self.count(g) * sum(math.prod(shape) for shape, _ in group.values())
        return n

    @staticmethod
    def tiny(**kw):
        base = dict(
            vocab_size=256, hidden_size=64, num_hidden_layers=3, num_heads=4, num_kv_heads=2, head_dim=16, mrope_section=(2, 3, 3),
            index_heads=2, index_dim=8, index_topk=16, n_routed_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
            residual_rescale_layers=6, max_seq_len=128, dtype="float32",
        )
        return KeyeVLConfig(**{**base, **kw})


# ------------------------------------------------------------------ parameters
def _shapes(c: KeyeVLConfig) -> dict:
    """group -> {name: (shape of one layer, fan_in or fill)}: matrices are N(0, fan_in^-1/2), the
    projections back onto the residual stream 1/sqrt(N) smaller, norms 1 (the indexer's LayerNorm:
    weight 1, bias 0). An expert's three matrices are stored [F, H]."""
    H, N, q, kv = c.hidden_size, c.residual_rescale_layers, c.num_heads * c.hd, c.num_kv_heads * c.hd
    F, E, J, d = c.moe_intermediate_size, c.n_routed_experts, c.index_heads, c.index_dim
    return {
        "indexed": {"norm": ((H,), 1.0), "wq": ((H, q), H), "wk": ((H, kv), H), "wv": ((H, kv), H),
                    "q_norm": ((c.hd,), float(c.qk_norm_init)), "k_norm": ((c.hd,), float(c.qk_norm_init)), "wo": ((q, H), q * N),
                    "wq_idx": ((H, J * d), H), "wk_idx": ((H, d), H), "w_idx": ((H, J), H),
                    "k_idx_norm": ((d,), 1.0), "k_idx_norm_bias": ((d,), 0.0)},
        "moe": {"norm": ((H,), 1.0), "router": ((H, E), H), "w_gate": ((E, F, H), H), "w_up": ((E, F, H), H), "w_down": ((E, F, H), F * N)},
    }


def init_params(config: KeyeVLConfig, key):
    """Weights from a seed, stacked by layer kind, with an untied head."""
    c, dt = config, jnp.dtype(config.dtype)
    keys = iter(jax.random.split(key, 32))
    params = init_stacked(_shapes(c), c.count, keys, dt)
    params["embed"] = jax.random.normal(next(keys), (c.vocab_size, c.hidden_size), jnp.float32).astype(dt)
    params["unembed"] = (jax.random.normal(next(keys), (c.hidden_size, c.vocab_size), jnp.float32) * c.hidden_size ** -0.5).astype(dt)
    params["final_norm"] = jnp.ones((c.hidden_size,), dt)
    return params


def param_logical_axes(config: KeyeVLConfig):
    """Logical axes for ``parallel/mesh.ShardingRules`` (vocabulary, experts and heads are the
    axes a mesh could split; the serving engine refuses a mesh for this model today)."""
    lead = {"indexed": {"norm": (None,), "wq": ("embed", "heads"), "wk": ("embed", "kv_heads"), "wv": ("embed", "kv_heads"),
                        "q_norm": (None,), "k_norm": (None,), "wo": ("heads", "embed"), "wq_idx": ("embed", None), "wk_idx": ("embed", None),
                        "w_idx": ("embed", None), "k_idx_norm": (None,), "k_idx_norm_bias": (None,)},
            "moe": {"norm": (None,), "router": ("embed", None), "w_gate": ("expert", "mlp", "embed"), "w_up": ("expert", "mlp", "embed"),
                    "w_down": ("expert", "mlp", "embed")}}
    axes = {g: {n: (None,) + a for n, a in group.items()} for g, group in lead.items() if config.count(g)}
    axes.update(embed=("vocab", "embed"), unembed=("embed", "vocab"), final_norm=(None,))
    return axes


# ------------------------------------------------------------------ indexed: GQA under a learned index
def mrope_tables(positions, width: int, c: KeyeVLConfig):
    """cos, sin [.., T, width / 2] float32 of ``R`` over a head of ``width`` channels for positions
    [3, .., T] (three streams): frequency i of ``width / 2`` turns by ``theta^(-2 i / width)`` a
    position, of the stream that ``mrope_section`` gives the attention head's frequency at the same
    place (``i * head_dim / width``: the indexer's 32 frequencies go 8, 12, 12 where the head's 64
    go 16, 24, 24)."""
    half = width // 2
    freqs = c.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    bounds = jnp.cumsum(jnp.asarray(c.mrope_section))
    stream = jnp.sum(jnp.arange(half)[:, None] * (c.hd // width) >= bounds[None, :], axis=-1)  # [half] in 0..2
    angles = positions[..., None].astype(jnp.float32) * freqs  # [3, .., T, half]
    angles = jnp.sum(jnp.where(jnp.arange(3).reshape((3,) + (1,) * (angles.ndim - 1)) == stream, angles, 0.0), axis=0)
    return jnp.cos(angles), jnp.sin(angles)


def _rotate(x, positions, c: KeyeVLConfig):
    """``R`` on x [B, heads, T, width] for positions [3, T] or [3, B, T]."""
    cos, sin = mrope_tables(positions, x.shape[-1], c)
    return apply_rope(x.astype(jnp.float32), cos, sin).astype(x.dtype)


def qkv(w, xn, positions, c: KeyeVLConfig):
    """xn [B,T,H], positions [3,T] or [3,B,T] -> q [B,nh,T,hd], k, v [B,kv,T,hd]: q and k normed over
    a head (one weight vector for all query heads, one for all key heads), then rotated."""
    B, T, _ = xn.shape
    q = c.norm(jnp.dot(xn, w["wq"]).reshape(B, T, c.num_heads, c.hd), w["q_norm"]).transpose(0, 2, 1, 3)
    k = c.norm(jnp.dot(xn, w["wk"]).reshape(B, T, c.num_kv_heads, c.hd), w["k_norm"]).transpose(0, 2, 1, 3)
    v = jnp.dot(xn, w["wv"]).reshape(B, T, c.num_kv_heads, c.hd).transpose(0, 2, 1, 3)
    return _rotate(q, positions, c), _rotate(k, positions, c), v


def indexer(w, xn, positions, c: KeyeVLConfig):
    """The scoring network's side of a layer: xn [B,T,H] -> (qI [B,J,T,d] rotated, w [B,T,J] float32,
    kI [B,T,d]: ONE key a position, LayerNorm'd in float32 then rotated, as the cache keeps it)."""
    B, T, _ = xn.shape
    with scope("indexed.score"):
        qi = jnp.dot(xn, w["wq_idx"]).reshape(B, T, c.index_heads, c.index_dim).transpose(0, 2, 1, 3)
        ki = jnp.dot(xn, w["wk_idx"], preferred_element_type=jnp.float32)
        ki = ki - jnp.mean(ki, axis=-1, keepdims=True)
        ki = ki * jax.lax.rsqrt(jnp.mean(ki * ki, axis=-1, keepdims=True) + c.rms_eps) * w["k_idx_norm"].astype(jnp.float32) + w["k_idx_norm_bias"].astype(jnp.float32)
        weights = jnp.dot(xn, w["w_idx"], preferred_element_type=jnp.float32)
        return _rotate(qi, positions, c), weights, _rotate(ki.astype(xn.dtype)[:, None], positions, c)[:, 0]


def indexed_seq(w, xn, lengths, c: KeyeVLConfig, mesh=None, skippable=None, positions=None):
    """xn [B,T,H], lengths [B] -> (out [B,T,H], k, v [B,T,kv,hd] and k_idx [B,T,d] as the cache keeps
    them). ``positions`` [3,T]: the three position streams (the token's index thrice without them:
    text). A bucket of at most ``index_topk`` positions holds no query that chooses: the flash
    kernel (``skippable`` [B]: the true lengths again, where it may skip what lies past them); a
    longer one goes through the index, a few sequences at a time (``qwen3_next.a_few_at_a_time``)."""
    B, T, _ = xn.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (3, T))

    def some(xn, lengths):
        q, k, v = qkv(w, xn, positions, c)
        qi, weights, ki = indexer(w, xn, positions, c)
        if T <= c.index_topk:
            o = flash_attention_on_mesh(q, k, v, mesh, c.attention_impl, scale=c.hd ** -0.5, lengths=skippable)
        else:
            o = indexed_attention.indexed_attention_seq(q, k, v, qi, weights, ki, lengths, c.index_topk, mesh=mesh)
        y = jnp.dot(o.transpose(0, 2, 1, 3).reshape(xn.shape[0], T, c.num_heads * c.hd), w["wo"])
        return y, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), ki

    return a_few_at_a_time(some, xn, lengths)


def indexed_step(w, xn, cache, ctx, c: KeyeVLConfig):
    """One token a lane: xn [B,H] against what its lane holds in this layer (``cache``: keys, values
    and the indexer's keys), its own rows written first: ``ops/indexed_attention.indexed_attention_step``."""
    positions = jnp.broadcast_to(ctx.lengths[None, :, None], (3, xn.shape[0], 1))
    q, k, v = qkv(w, xn[:, None], positions, c)
    qi, weights, ki = indexer(w, xn[:, None], positions, c)
    cache.write("k", k[:, :, 0])
    cache.write("v", v[:, :, 0])
    cache.write("k_idx", ki[:, 0])
    (k_stack, i), (v_stack, _), (ki_stack, _) = (cache.stacked(n) for n in ("k", "v", "k_idx"))
    pos = jnp.minimum(ctx.lengths, k_stack.shape[2] - 1)
    o = indexed_attention.indexed_attention_step(q[:, :, 0], qi[:, :, 0], weights[:, 0], k_stack, v_stack, ki_stack, i, pos, c.index_topk)
    return jnp.dot(o.astype(xn.dtype), w["wo"])
