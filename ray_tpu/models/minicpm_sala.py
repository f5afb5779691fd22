"""MiniCPM-SALA decoder (``model_type`` ``minicpm_sala``): InfLLM-v2 block-sparse attention one
layer in four, Lightning linear attention the other three, a dense SwiGLU in every layer, and the
muP scalings on the embedding, every residual branch and the logits.

A fifth DESCRIPTION over the one layer loop (``models/hybrid.py``). Every published decoder layer
is two residual sub-blocks, ``x = x + a * mixer(N(x))`` then ``x = x + a * mlp(N(x))``,
``N(x) = w * x / sqrt(mean(x²) + eps)`` in float32, no bias anywhere,
``a = scale_depth / sqrt(published layers)`` whatever depth is held; the stream starts as
``scale_emb * E[token]`` and the head reads ``N(x) / (hidden_size / dim_model_base)``: the three
``stream_scales`` of the description, which the loops apply (1.0 everywhere else). Layer ``l``
(0-indexed, as the published ``mixer_types`` counts) mixes by sparse attention where
``mixer_types[l] == "minicpm4"`` and by Lightning attention where it is ``"lightning-attn"``; the
pattern has no period. A description may hold a run of the published layers (``first_layer``,
``num_hidden_layers``): one pipeline stage. The loop walks ``2 x num_hidden_layers`` sub-blocks of
three kinds:

- ``sparse`` (scope ``sparse``): ``num_heads`` query heads over ``num_kv_heads`` key-value heads (a
  GROUP of 16 a key-value head); q and k normalised per head with ``N`` (``qk_norm``); NO rotation
  (``attn_use_rope`` false: position reaches these layers through the Lightning layers alone);
  ``o * sigmoid(gate)`` and the output projection: the gated, normed projections of
  ``models/qwen3_next.py`` (``gated_attn_qkv``, ``_gated_out``) with this family's norm and nothing
  to rotate. A sequence of at most ``dense_len`` positions attends causally to everything; a longer
  one, for each query and group, to at most ``topk`` blocks of ``block`` positions that the query
  chooses itself by scores against COMPRESSED keys (``ops/sparse_attention.py`` says how). Kept per
  position: ``k`` and ``v``; kept per sequence: ``kc``, one compressed key for every ``stride``
  positions up to ``max_seq_len`` (the indexer's cache: a third granularity, stated as a
  per-sequence entry so that admission replaces it whole with the prefill's, which is the reset of
  a recycled slot; a decode step adds a row when a window of ``kernel`` positions completes).
- ``lightning`` (scope ``lightning``): q, k, v of ``lightning_nh`` heads of ``lightning_head_dim``,
  no activation; the same per-head norm on q and k, then rotate-half RoPE over all of a head's
  channels; per head a state ``S`` [key x value] in float32, ``S_t = lambda_h S_{t-1} + k_t v_t^T``,
  ``o_t = S_t^T q_t / sqrt(hd)``, no softmax and no normaliser; ``lambda_h = exp(-slope_h)``, a
  fixed slope a head and layer (``lightning_slopes``; held beside the weights as ``slope``, float32,
  so that one scan body serves every layer). Then ``W_o (N(o) * sigmoid(W_z x))`` with the norm
  over the concatenated heads. Kept per sequence: ``S``. Prefill runs the recurrence in chunks
  (``lightning_chunked``: the delta rule's chunked form without its triangular solve, since this
  rule only ADDS ``k v^T``; its own, simpler function), decode one position at a time.
- ``ffn`` (scope ``ffn``): SwiGLU at ``intermediate_size``; keeps nothing.

Precision: weights, stream, caches and matmul operands in the weights' dtype (bfloat16 as
published), accumulation float32; norms, gates, the selection's scores, the decay and the state
``S`` float32. Columns of the two query projections are laid out ``[q | gate]`` head by head (the
published ``q_proj`` and ``o_gate`` / ``z_proj`` side by side): a relabelling that random weights
cannot tell apart.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ray_tpu.models.glm4_moe_lite import ffn
from ray_tpu.models.hybrid import HybridDescription, Mixer, attend_slot, forward, init_stacked, loss_fn  # noqa: F401 - the shared forward and loss, as the harness's family asks for them
from ray_tpu.models.qwen3_next import _gated_out, a_few_at_a_time, gated_attn_qkv
from ray_tpu.ops import slot_attention, sparse_attention
from ray_tpu.ops.flash_attention import flash_attention_on_mesh
from ray_tpu.ops.layers import rms_norm
from ray_tpu.ops.sparse_attention import SparseConfig
from ray_tpu.util.profiling import scope

PUBLISHED_MIXERS = tuple("minicpm4" if l in (0, 9, 16, 17, 22, 29, 30, 31) else "lightning-attn" for l in range(32))
KINDS = {"minicpm4": "sparse", "lightning-attn": "lightning"}


class _Heads(NamedTuple):
    """What ``qwen3_next.gated_attn_qkv`` reads of a config, for one of this model's two mixers."""
    num_heads: int
    num_kv_heads: int
    hd: int
    rot_dim: int
    rope_theta: float
    norm: Any


@dataclass(frozen=True)
class MiniCPMSALAConfig(HybridDescription):
    vocab_size: int = 73448
    hidden_size: int = 4096
    num_hidden_layers: int = 32  # decoder layers HELD: each a mixer sub-block and an MLP sub-block
    published_layers: int = 32  # what ``a`` and the slopes are reckoned from, whatever is held
    first_layer: int = 0  # the published index of the first layer held
    mixer_types: tuple = PUBLISHED_MIXERS  # every published layer's, as published
    intermediate_size: int = 16384
    # sparse: InfLLM v2
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    sparse_kernel: int = 32
    sparse_stride: int = 16
    sparse_block: int = 64
    sparse_topk: int = 64
    sparse_window: int = 2048
    sparse_init_blocks: int = 1
    dense_len: int = 8192
    # lightning
    lightning_nh: int = 32
    lightning_head_dim: int = 128
    rope_theta: float = 10000.0
    chunk_size: int = 128  # how the recurrence is blocked over a sequence: not mathematics
    # muP
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    rms_eps: float = 1e-6
    # init only: what the sparse layers' query and key norms start at (their product scales every
    # attention score); 1 leaves a random model's softmax over thousands of keys nearly flat
    qk_norm_init: float = 1.0
    max_seq_len: int = 12288  # also sizes the compressed-key cache: one row for every ``sparse_stride`` positions
    dtype: str = "bfloat16"
    remat: bool = False
    attention_impl: str = "auto"  # auto | pallas | xla

    def __post_init__(self):
        if not 0 <= self.first_layer <= self.first_layer + self.num_hidden_layers <= len(self.mixer_types):
            raise ValueError("the layers held are a run of the published mixer_types")
        if set(self.mixer_types) - set(KINDS):
            raise ValueError(f"mixer_types are of {sorted(KINDS)}")
        if self.num_heads % self.num_kv_heads:
            raise ValueError("key-value heads must divide the query heads")
        self.sparse.check(self.max_seq_len)

    # ---- the description the layer loop, the engine and the cache manager read
    def init_params(self, key):
        return init_params(self, key)

    @property
    def held(self) -> tuple:
        """The published indices of the layers held."""
        return tuple(range(self.first_layer, self.first_layer + self.num_hidden_layers))

    @property
    def layer_kinds(self) -> tuple:
        return tuple(kind for l in self.held for kind in (KINDS[self.mixer_types[l]], "ffn"))

    @property
    def stream_scales(self) -> tuple:
        """(on the embedding, on every residual branch, on the stream before the head), as published."""
        return float(self.scale_emb), self.scale_depth / math.sqrt(self.published_layers), self.dim_model_base / self.hidden_size

    @property
    def sparse(self) -> SparseConfig:
        return SparseConfig(self.sparse_kernel, self.sparse_stride, self.sparse_block, self.sparse_topk, self.sparse_window,
                            self.sparse_init_blocks, self.dense_len)

    @property
    def sparse_heads(self) -> _Heads:
        return _Heads(self.num_heads, self.num_kv_heads, self.head_dim, 0, self.rope_theta, self.norm)

    @property
    def lightning_heads(self) -> _Heads:
        return _Heads(self.lightning_nh, self.lightning_nh, self.lightning_head_dim, self.lightning_head_dim, self.rope_theta, self.norm)

    @property
    def mixers(self) -> dict:
        """kind -> its scope in a profile and its two forms (``models/hybrid.Mixer``)."""
        dt = jnp.dtype(self.dtype)

        def sparse_seq_(w, xn, ctx):
            y, k, v, kc = sparse_seq(w, xn.astype(dt), ctx.lengths, self, ctx.mesh, ctx.skippable)
            return y, {"k": k, "v": v, "kc": kc}

        def lightning_seq_(w, xn, ctx):
            y, S = lightning_seq(w, xn.astype(dt), ctx.lengths, self)
            return y, {"S": S}

        def lightning_step_(w, xn, cache, ctx):
            with scope("lightning.state"):  # the state's read here, its decay and write in ``lightning_step``, its way back below
                S = cache.read("S")
            y, S = lightning_step(w, xn.astype(dt), S, ctx.lengths, self)
            with scope("lightning.state"):
                cache.write("S", S)
            return y, None

        return {"sparse": Mixer("sparse", sparse_seq_, lambda w, xn, cache, ctx: (sparse_step(w, xn.astype(dt), cache, ctx, self), None)),
                "lightning": Mixer("lightning", lightning_seq_, lightning_step_),
                "ffn": Mixer("ffn", lambda w, xn, ctx: (ffn(w, xn.astype(dt), ctx.skippable, ctx.stacked), {}),
                             lambda w, xn, cache, ctx: (ffn(w, xn.astype(dt)), None))}

    def norm(self, x, w):
        return rms_norm(x, w, self.rms_eps)

    @property
    def hd(self) -> int:
        return self.head_dim

    def flash_calls(self, length: int) -> dict:
        """A bucket over ``dense_len`` goes through the selection, not the flash kernel (``sparse_seq``)."""
        return {self.hd: self.count("sparse")} if length <= self.dense_len else {}

    @property
    def lightning_dim(self) -> int:
        return self.lightning_nh * self.lightning_head_dim

    @property
    def stream_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def compressed_rows(self) -> int:
        return self.max_seq_len // self.sparse_stride

    def cache_spec(self) -> dict:
        """kind -> {name: (shape, dtype, "position" | "sequence")}: a sparse layer keeps a key and a
        value by head per position and, per sequence, a compressed key for every ``sparse_stride``
        positions up to ``max_seq_len``; a Lightning layer a float32 state a head per sequence."""
        kv, d = (self.num_kv_heads, self.hd), self.lightning_head_dim
        return {"sparse": {"k": (kv, self.dtype, "position"), "v": (kv, self.dtype, "position"),
                           "kc": ((self.compressed_rows,) + kv, self.dtype, "sequence")},
                "lightning": {"S": ((self.lightning_nh, d, d), "float32", "sequence")}, "ffn": {}}

    def lightning_slopes(self):
        """float32 [lightning layers held, heads]: ``2^(-8 (h + 1) / heads) * (1 - l / (L - 1) + 1e-5)``
        for published layer ``l`` of ``L``: the Lightning Attention family's fixed decay."""
        heads = 2.0 ** (-8.0 * jnp.arange(1, self.lightning_nh + 1, dtype=jnp.float32) / self.lightning_nh)
        layers = jnp.asarray([1.0 - l / (self.published_layers - 1) + 1e-5 for l in self.held if self.mixer_types[l] == "lightning-attn"], jnp.float32)
        return layers[:, None] * heads[None, :]

    def _blocks_read(self, positions: int, sparse: bool) -> int:
        """Blocks a query at the last of ``positions`` reads in one group of one sparse layer."""
        live = -(-positions // self.sparse_block)
        return min(live, self.sparse_topk) if sparse else live

    def prefill_counters(self, batch: int, length: int, lengths=()) -> dict:
        """(query, block) pairs the sparse layers' prefill reads for prompts of the TRUE ``lengths``
        (host arithmetic: a query at position t of a prompt over ``dense_len`` reads
        min(t // block + 1, topk) blocks in each group, of a shorter one all t // block + 1)."""
        pairs = 0
        for n in lengths:
            full, rest = divmod(int(n), self.sparse_block)
            cap = self.sparse_topk if n > self.dense_len else full + 1
            pairs += sum(self.sparse_block * min(b + 1, cap) for b in range(full)) + rest * min(full + 1, cap)
        return {"prefill_sparse_pairs": self.count("sparse") * self.num_kv_heads * pairs}

    def decode_counters(self, positions) -> dict:
        """Blocks of ``sparse_block`` positions, a key-value head's share each, that a decode step's
        sparse layers read for lanes holding ``positions`` (the new token's among them), and would
        read attending densely."""
        each = self.count("sparse") * self.num_kv_heads
        return {"sparse_blocks_read": each * sum(self._blocks_read(n, n > self.dense_len) for n in positions),
                "sparse_blocks_live": each * sum(self._blocks_read(n, False) for n in positions)}

    def num_params(self) -> int:
        """Parameters held here (the fixed slopes are no parameters)."""
        n = 2 * self.vocab_size * self.hidden_size + self.hidden_size
        for g, group in _shapes(self).items():
            n += self.count(g) * sum(math.prod(shape) for shape, _ in group.values())
        return n

    @staticmethod
    def tiny(**kw):
        base = dict(
            vocab_size=256, hidden_size=64, num_hidden_layers=8, published_layers=12, first_layer=2,
            mixer_types=tuple("minicpm4" if l in (0, 2, 9, 10, 11) else "lightning-attn" for l in range(12)),
            intermediate_size=96, num_heads=4, num_kv_heads=2, head_dim=16, sparse_kernel=4, sparse_stride=2, sparse_block=8,
            sparse_topk=4, sparse_window=16, sparse_init_blocks=1, dense_len=32, lightning_nh=4, lightning_head_dim=8, chunk_size=8,
            dim_model_base=16, max_seq_len=128, dtype="float32",
        )
        return MiniCPMSALAConfig(**{**base, **kw})


# ------------------------------------------------------------------ parameters
def _shapes(c: MiniCPMSALAConfig) -> dict:
    """group -> {name: (shape of one layer, fan_in or fill)}: matrices are N(0, fan_in^-1/2), norms 1.
    No projection is drawn smaller by the depth: ``a`` on every residual branch IS this family's
    depth scaling."""
    H, F, q, kv, D = c.hidden_size, c.intermediate_size, c.num_heads * c.hd, c.num_kv_heads * c.hd, c.lightning_dim
    return {
        "sparse": {"norm": ((H,), 1.0), "wq": ((H, 2 * q), H), "wk": ((H, kv), H), "wv": ((H, kv), H),
                   "q_norm": ((c.hd,), float(c.qk_norm_init)), "k_norm": ((c.hd,), float(c.qk_norm_init)), "wo": ((q, H), q)},
        "lightning": {"norm": ((H,), 1.0), "wq": ((H, 2 * D), H), "wk": ((H, D), H), "wv": ((H, D), H),
                      "q_norm": ((c.lightning_head_dim,), 1.0), "k_norm": ((c.lightning_head_dim,), 1.0),
                      "o_norm": ((D,), 1.0), "wo": ((D, H), D)},
        "ffn": {"norm": ((H,), 1.0), "w_gate": ((H, F), H), "w_up": ((H, F), H), "w_down": ((F, H), F)},
    }


def init_params(config: MiniCPMSALAConfig, key):
    """Weights from a seed, stacked by layer kind. The embedding is drawn N(0, scale_emb^-2), so that
    the stream starts at unit scale once ``scale_emb`` is applied; the Lightning layers' ``slope`` is
    no weight (``lightning_slopes``)."""
    c, dt = config, jnp.dtype(config.dtype)
    keys = iter(jax.random.split(key, 32))
    params = init_stacked(_shapes(c), c.count, keys, dt)
    if c.count("lightning"):
        params["lightning"]["slope"] = c.lightning_slopes()
    params["embed"] = (jax.random.normal(next(keys), (c.vocab_size, c.hidden_size), jnp.float32) / c.scale_emb).astype(dt)
    params["unembed"] = (jax.random.normal(next(keys), (c.hidden_size, c.vocab_size), jnp.float32)
                         * c.hidden_size ** -0.5).astype(dt)
    params["final_norm"] = jnp.ones((c.hidden_size,), dt)
    return params


def param_logical_axes(config: MiniCPMSALAConfig):
    """Logical axes for ``parallel/mesh.ShardingRules`` (vocabulary, heads and the MLP's width are
    the axes a mesh could split; the serving engine refuses a mesh for this model today)."""
    heads = {"norm": (None,), "wq": ("embed", "heads"), "wk": ("embed", "kv_heads"), "wv": ("embed", "kv_heads"),
             "q_norm": (None,), "k_norm": (None,), "wo": ("heads", "embed")}
    lead = {"sparse": heads, "lightning": {**heads, "o_norm": (None,), "slope": (None,)},
            "ffn": {"norm": (None,), "w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"), "w_down": ("mlp", "embed")}}
    axes = {g: {n: (None,) + a for n, a in group.items()} for g, group in lead.items() if config.count(g)}
    axes.update(embed=("vocab", "embed"), unembed=("embed", "vocab"), final_norm=(None,))
    return axes


# ------------------------------------------------------------- sparse: InfLLM v2
def sparse_seq(w, xn, lengths, c: MiniCPMSALAConfig, mesh=None, skippable=None):
    """xn [B,T,H], lengths [B] -> (out [B,T,H], k, v [B,T,kv,hd] as the cache keeps them, kc
    [B, max_seq_len // stride, kv, hd]: the compressed keys of the whole windows inside each true
    length, zeros after). A bucket of at most ``dense_len`` positions holds only sequences that
    attend densely: the flash kernel; a longer one goes through the selection, a few sequences at
    a time (``qwen3_next.a_few_at_a_time``). ``skippable`` [B]: the true lengths again, where the
    flash kernel may skip what lies past them (``SeqCtx.skippable``: no backward pass follows)."""
    B, T, _ = xn.shape
    h, sp = c.sparse_heads, c.sparse
    width = c.num_heads * c.hd

    def some(xn, lengths):
        q, gate, k, v = gated_attn_qkv(w, xn, jnp.arange(T, dtype=jnp.int32), h)
        if T <= sp.dense_len:
            o = flash_attention_on_mesh(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                                        mesh, c.attention_impl, lengths=skippable).transpose(0, 2, 1, 3)
            with scope("sparse.select"):
                kc = sparse_attention.compress_keys(k, lengths, sp)
        else:
            o, kc = sparse_attention.sparse_attention_seq(q, k, v, lengths, sp, mesh=mesh)
        kc = jnp.pad(kc, ((0, 0), (0, c.compressed_rows - kc.shape[1]), (0, 0), (0, 0)))
        return _gated_out(w, o.reshape(xn.shape[0], T, width), gate, xn.dtype), k, v, kc

    return a_few_at_a_time(some, xn, lengths)


def sparse_step(w, xn, cache, ctx, c: MiniCPMSALAConfig):
    """One token a lane: xn [B,H] against what its lane holds in this layer (``cache``: keys, values
    and compressed keys). A lane that holds at most ``dense_len`` positions, the new one among them,
    attends to all of them (``hybrid.attend_slot``: the live-block form); a longer one to the blocks
    it chooses (``ops/slot_attention.attend_blocks``). Both forms run, each for its lanes (the
    kernel reads nothing for a lane it is told is not live)."""
    sp, G = c.sparse, c.num_kv_heads
    B = xn.shape[0]
    q, gate, k, v = gated_attn_qkv(w, xn[:, None], ctx.lengths[:, None], c.sparse_heads)
    cache.write("k", k[:, 0])
    cache.write("v", v[:, 0])
    (k_stack, i), (v_stack, _) = cache.stacked("k"), cache.stacked("v")
    horizon = k_stack.shape[2]
    pos = jnp.minimum(ctx.lengths, horizon - 1)
    lanes = jnp.arange(B)
    with scope("sparse.select"):
        # a window of ``kernel`` positions completes with this token: its compressed key joins the lane's
        held = pos + 1
        row = jnp.maximum(held - sp.kernel, 0) // sp.stride
        completes = (held >= sp.kernel) & ((held - sp.kernel) % sp.stride == 0)
        last = jnp.clip(held[:, None] - sp.kernel + jnp.arange(sp.kernel), 0, horizon - 1)  # [B,kernel]
        mean = jnp.mean(k_stack[i, lanes[:, None], last].astype(jnp.float32), axis=1)  # [B,kv,hd]
        kc = cache.read("kc")
        kc = kc.at[lanes, row].set(jnp.where(completes[:, None, None], mean.astype(kc.dtype), kc[lanes, row]))
        cache.write("kc", kc)
        qg = q.reshape(B, 1, G, c.num_heads // G, c.hd)
        blocks, ok = sparse_attention.choose_blocks(sparse_attention.block_scores(qg, kc, pos[:, None], sp), pos[:, None], sp)
    chooses = held > sp.dense_len
    with scope("sparse.attend"):
        o_blocks = slot_attention.attend_blocks(q[:, 0], k_stack, v_stack, i, pos, blocks[:, 0], ok[:, 0], sp.block, live=ctx.active & chooses)
    o_all = attend_slot(q[:, 0], cache, ctx._replace(active=ctx.active & ~chooses), G)
    return _gated_out(w, jnp.where(chooses[:, None], o_blocks, o_all), gate[:, 0], xn.dtype)


# --------------------------------------------------------- lightning: linear attention
def _lightning_out(w, o, gate, c: MiniCPMSALAConfig, dtype):
    """``W_o (N(o) * sigmoid(gate))``: the norm over the concatenated heads, in float32."""
    o = o.reshape(*o.shape[:-2], c.lightning_dim)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + c.rms_eps) * w["o_norm"].astype(jnp.float32)
    return jnp.dot((o * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(dtype), w["wo"])


def lightning_chunked(q, k, v, slope, lengths, chunk: int, operand_dtype=None):
    """``S_t = exp(-slope_h) S_{t-1} + k_t v_t^T``, ``o_t = S_t^T q_t`` over right-padded sequences
    from a zero state, blocked in chunks. q, k, v [B,T,N,D], slope [N] float32, lengths [B]
    -> (o [B,T,N,D] float32, the state AT each true length [B,N,D,D] float32).

    With C positions a chunk and S_0 the state as it starts: ``o_t = lambda^(t+1) q_t S_0 +
    sum_{s<=t} lambda^(t-s) (q_t . k_s) v_s`` and ``S_C = lambda^C S_0 + sum_s lambda^(C-1-s) k_s
    v_s^T``. Nothing here is sequential: every chunk's own sum, the pass of the state from chunk to
    chunk (a sum over the earlier chunks, decayed) and every product with a state are batched
    matmuls over all chunks at once. The decay enters as ``exp`` of a non-positive exponent everywhere (a
    [C, C] table of ``lambda^(t-s)``, never ``lambda^-s``), so no head's slope overflows whatever
    the chunk. A sequence's state at its true length n is the state as chunk n // C starts, moved
    on over that chunk's first n % C positions: padding neither writes nor decays. The matmuls take
    their operands in ``operand_dtype`` and accumulate in float32; without it they are float32 at
    ``highest`` precision."""
    hi = jax.lax.Precision.HIGHEST
    if operand_dtype is None or jnp.dtype(operand_dtype) == jnp.float32:
        def es(spec, a, b):
            return jnp.einsum(spec, a, b, precision=hi)
    else:
        def es(spec, a, b):
            return jnp.einsum(spec, a.astype(operand_dtype), b.astype(operand_dtype), preferred_element_type=jnp.float32)
    B, T, N, D = q.shape
    C = min(chunk, T)
    pad = -T % C
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0))) for a in (q, k, v))
    nc = (T + pad) // C
    q, k, v = (a.astype(jnp.float32).reshape(B, nc, C, N, D) for a in (q, k, v))
    at = jnp.arange(C, dtype=jnp.float32)
    since = at[:, None] - at[None, :]  # t - s
    table = jnp.where(since >= 0, jnp.exp(-slope[:, None, None] * jnp.maximum(since, 0.0)), 0.0)  # [N,t,s]
    to_end = jnp.exp(-slope[:, None] * (C - 1 - at))  # [N,s]: lambda^(C-1-s)
    from_start = jnp.exp(-slope[:, None] * (at + 1.0))  # [N,t]: lambda^(t+1)
    inside = es("bctnd,bcsnd->bcnts", q, k) * table
    o = es("bcnts,bcsnd->bctnd", inside, v)
    own = es("bcsnk,bcsnv->bcnkv", k * to_end.T[:, :, None], v)  # each chunk's own sum, as its end sees it
    # the state as chunk c STARTS is the sum of the earlier chunks' own sums, each decayed over the whole chunks between:
    # one float32 matmul along the chunk axis (a [nc, nc] table a head) where a scan would pass a state nc times
    between = jnp.arange(nc, dtype=jnp.float32)[:, None] - 1.0 - jnp.arange(nc, dtype=jnp.float32)[None, :]  # c - 1 - c'
    carry = jnp.where(between >= 0, jnp.exp(-slope[:, None, None] * C * jnp.maximum(between, 0.0)), 0.0)  # [N,c,c']
    starts = jnp.einsum("ncd,bdnkv->bcnkv", carry, own, precision=hi)  # [B,nc,N,K,V]
    o = o + es("bctnk,bcnkv->bctnv", q * from_start.T[:, :, None], starts)
    # the state at each true length: chunk n // C's start, then that chunk's first n % C positions
    n = jnp.clip(lengths, 0, T)
    c_at = jnp.minimum(n // C, nc - 1)
    r = n - c_at * C  # C where n is the padded end
    pick = lambda a: jnp.take_along_axis(a, c_at.reshape((B,) + (1,) * (a.ndim - 1)), axis=1)[:, 0]  # noqa: E731
    left = r[:, None, None].astype(jnp.float32) - 1.0 - at[None, None, :]  # [B,1,s]: r - 1 - s
    weight = jnp.where(left >= 0, jnp.exp(-slope[None, :, None] * jnp.maximum(left, 0.0)), 0.0)  # [B,N,s]
    S = pick(starts) * jnp.exp(-slope[None, :] * r[:, None].astype(jnp.float32))[..., None, None]
    S = S + jnp.einsum("bsnk,bsnv->bnkv", pick(k) * weight.transpose(0, 2, 1)[..., None], pick(v), precision=hi)
    return o.reshape(B, nc * C, N, D)[:, :T], S


def lightning_seq(w, xn, lengths, c: MiniCPMSALAConfig):
    """xn [B,T,H], lengths [B] -> (out [B,T,H], S [B,nh,hd,hd] f32 AT each sequence's true length)."""
    T = xn.shape[1]
    operand = None if xn.dtype == jnp.float32 else xn.dtype

    def some(xn, lengths):
        q, gate, k, v = gated_attn_qkv(w, xn, jnp.arange(T, dtype=jnp.int32), c.lightning_heads)
        with scope("lightning.chunk"):
            o, S = lightning_chunked(q, k, v, w["slope"], lengths, c.chunk_size, operand)
            o = o * c.lightning_head_dim ** -0.5
        return _lightning_out(w, o, gate, c, xn.dtype), S

    return a_few_at_a_time(some, xn, lengths)


def lightning_step(w, xn, S, positions, c: MiniCPMSALAConfig):
    """One token: xn [B,H], S [B,nh,hd,hd] f32, positions [B] -> (out [B,H], S)."""
    q, gate, k, v = gated_attn_qkv(w, xn[:, None], positions[:, None], c.lightning_heads)
    q, k, v = (a[:, 0].astype(jnp.float32) for a in (q, k, v))
    with scope("lightning.state"):
        S = S * jnp.exp(-w["slope"])[:, None, None] + k[..., None] * v[..., None, :]
        o = jnp.sum(S * q[..., None], axis=-2) * c.lightning_head_dim ** -0.5
    return _lightning_out(w, o, gate[:, 0], c, xn.dtype), S
