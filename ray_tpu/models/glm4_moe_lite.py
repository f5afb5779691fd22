"""GLM-4.7-Flash decoder (``model_type`` ``glm4_moe_lite``): multi-head latent attention in
every layer, one dense SwiGLU layer, then 64 sigmoid-routed experts behind a shared one.

A third DESCRIPTION over the one layer loop (``models/hybrid.py``) and the one expert layer
(``models/experts.py``). Every published decoder layer is two residual sub-blocks,
``x = x + attn(N(x))`` then ``x = x + mlp(N(x))``, ``N(x) = w * x / sqrt(mean(x²) + eps)`` in
float32, no bias anywhere. So the loop walks ``2 x num_hidden_layers`` sub-blocks of three kinds,
``mla ffn`` for each of the first ``first_k_dense_replace`` layers and ``mla moe`` for the rest:

- ``mla`` (scope ``mla``), latent attention. ``c_q = N(x W_qa)``; ``q = c_q W_qb``, each head
  ``[q_nope | q_rope]``. ``[c_kv | k_r] = x W_kva``; ``c_kv = N(c_kv)``; ``k_r = RoPE(k_r)``: ONE
  rotated key for all heads. ``k_nope_h = c_kv W_kb[h]``, ``v_h = c_kv W_vb[h]`` (the published
  ``kv_b_proj``'s columns, a head's ``[k_nope | v]``, kept as two matrices: a relabelling).
  ``score_h(t, s) = (q_nope_h(t) . k_nope_h(s) + RoPE(q_rope_h(t)) . k_r(s)) / sqrt(nope + rope)``,
  causal softmax, ``o_h = sum p v_h``, then ``W_o``. Kept per position: ``c_kv`` after its norm and
  ``k_r`` after its rotation, nothing per head. Two forms of the one attention:
  * ``seq`` (prefill, training) EXPANDS: keys and values of every head from ``c_kv``, the rotated
    key broadcast to the heads, through ``flash_attention`` (nope + rope wide keys, ``v_head_dim``
    wide values: equal in the published model, which the kernel asks for and the config checks);
  * ``step`` (decode) ABSORBS: ``q_lat_h = q_nope_h W_kb[h]^T``,
    ``score = q_lat_h . c_kv(s) + q_rope_h . k_r(s)``, ``o_lat_h = sum p c_kv(s)``,
    ``o_h = o_lat_h W_vb[h]``: every head reads the SAME row a position, as key and as value, from
    where it lies in the stacked cache (``ops/slot_attention.attend_latent``).
  The cache holds two entries, ``c_kv`` [kv_lora_rank] and ``k_r`` [``rope_row``: the rotated key
  in whole 128-lane tiles, zeros after its qk_rope_head_dim columns], and not one row of 512 + 64:
  576 is four and a half of the chip's lane tiles. Compiled for a described v5e (PR 36), a stacked
  entry 64 wide is handed to the kernel only through a copy of the whole entry into the tiled
  layout Mosaic reads (537 MB a step at 8 x 16 x 16,384: that IS the entry at 128 lanes), so the
  chip stores the 64 columns as 128 either way; stating it keeps the bytes honest (1,280 B a
  position and layer, not 1,152) and the copy away. Two entries and not one row of 640: the
  values are then the ``c_kv`` block whole, with no slice of a block.
- ``ffn`` (scope ``ffn``): SwiGLU at ``intermediate_size``, in slabs of rows where the batch is
  large; keeps nothing.
- ``moe`` (scope ``moe``): ``models/experts.py`` with a sigmoid router over all published
  experts, the top k of score + correction bias (``noaux_tc``; ``n_group`` = ``topk_group`` = 1,
  so no group limit), their own scores normalised and times ``routed_scaling_factor``, SwiGLU
  experts and one plain shared expert. Nemotron's scoring on Qwen3-Next's expert form.

Precision: weights, residual stream, cache and matmul operands are the weights' dtype (bfloat16
as published), accumulation float32; norms and the router compute in float32. Rotate-half RoPE
over all ``qk_rope_head_dim`` dimensions (``assumed`` in the benchmark's configuration says why
random weights cannot tell it from the interleaved pairing). Not here: the checkpoint's
multi-token-prediction module (``num_nextn_predict_layers``), which changes no logit of the model.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ray_tpu.models import experts
from ray_tpu.models.experts import ExpertLayer
from ray_tpu.models.hybrid import ROUTING, HybridDescription, Mixer, forward, init_stacked, loss_fn  # noqa: F401 - the shared forward and loss, as the harness's family asks for them
from ray_tpu.models.nemotron_h import _anchor_routing  # one orthogonal matrix for all expert layers' routers: the same scoring, the same reason
from ray_tpu.ops import slot_attention
from ray_tpu.ops.flash_attention import flash_attention_on_mesh
from ray_tpu.ops.layers import apply_rope, live_slabs, rms_norm, rotary_embedding
from ray_tpu.util.profiling import scope

# rows (batch x padded length) the dense layer takes at once: its two hidden activations are
# 40 KB a row at the published width, 1.3 GB for a 2 x 16,384 prefill
FFN_ROWS = 8192


class LatentAttention:
    """What ``mla_down``, ``mla_seq`` and ``mla_step`` read off a description beside its widths
    (``num_heads``, ``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
    ``rope_theta``, ``rms_eps``, ``attention_impl``): ``q_lora_rank`` (None: the queries come
    straight from the stream through ``w_q``, with no latent and no norm of their own) and
    ``mla_rotates`` (False: NoPE, neither the shared key nor any query column is rotated)."""

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def flash_width(self) -> int:
        """Columns of ``mla_seq``'s queries, keys and values: the widest of them, padded up to a width the flash kernel takes."""
        need = max(self.qk_head_dim, self.v_head_dim)
        return next((wd for wd in (64, 128, 256) if wd >= need), need)

    def flash_calls(self, length: int) -> dict:
        return {self.flash_width: self.count("mla")}

    @property
    def rope_row(self) -> int:
        """Columns of the cached shared key: qk_rope_head_dim rounded up to whole 128-lane tiles."""
        return -(-self.qk_rope_head_dim // 128) * 128

    @property
    def latent_tile(self) -> dict:
        """What ``ops/slot_attention.refusal`` is asked about a latent layer's decode attention."""
        return dict(num_heads=self.num_heads, num_kv_heads=1, head_dim=self.kv_lora_rank + self.rope_row, value_dim=self.kv_lora_rank)

    def latent_entries(self) -> dict:
        """What one latent layer keeps of every position: the normed latent and the one shared key."""
        return {"c_kv": ((self.kv_lora_rank,), self.dtype, "position"), "k_r": ((self.rope_row,), self.dtype, "position")}

    def latent_shapes(self, N: int) -> dict:
        """name -> (shape of one layer, fan_in or fill) of a latent layer's weights (``init_stacked``)."""
        H, nh, r, rq = self.hidden_size, self.num_heads, self.kv_lora_rank, self.q_lora_rank
        queries = {"w_q": ((H, nh * self.qk_head_dim), H)} if rq is None else {
            "w_qa": ((H, rq), H), "q_norm": ((rq,), 1.0), "w_qb": ((rq, nh * self.qk_head_dim), rq)}
        return {"norm": ((H,), 1.0), **queries, "w_kva": ((H, r + self.qk_rope_head_dim), H), "kv_norm": ((r,), 1.0),
                "w_kb": ((r, nh * self.qk_nope_head_dim), r), "w_vb": ((r, nh * self.v_head_dim), r),
                "wo": ((nh * self.v_head_dim, H), nh * self.v_head_dim * N)}

    def latent_axes(self) -> dict:
        queries = {"w_q": ("embed", "heads")} if self.q_lora_rank is None else {"w_qa": ("embed", None), "q_norm": (None,), "w_qb": (None, "heads")}
        return {"norm": (None,), **queries, "w_kva": ("embed", None), "kv_norm": (None,), "w_kb": (None, "heads"),
                "w_vb": (None, "heads"), "wo": ("heads", "embed")}


@dataclass(frozen=True)
class Glm4MoeLiteConfig(LatentAttention, HybridDescription):
    vocab_size: int = 154880
    hidden_size: int = 2048
    num_hidden_layers: int = 47  # published decoder layers: each an attention sub-block and an MLP sub-block
    first_k_dense_replace: int = 1  # the first layers' MLP is dense
    intermediate_size: int = 10240
    # mla
    num_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 1e6
    # moe: every expert is held
    n_routed_experts: int = 64
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1536
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.8
    rms_eps: float = 1e-5
    # init only: every sub-block's projection back onto the stream is drawn 1/sqrt(this) smaller; 1 turns it off
    residual_rescale_layers: int = 94
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
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim is rotated in pairs")
        if self.n_shared_experts != 1:
            raise ValueError("the expert layer has one shared expert")

    # ---- the description the layer loop, the engine and the cache manager read
    def init_params(self, key):
        return init_params(self, key)

    @property
    def layer_kinds(self) -> tuple:
        return tuple(kind for i in range(self.num_hidden_layers)
                     for kind in ("mla", "ffn" if i < self.first_k_dense_replace else "moe"))

    @property
    def mixers(self) -> dict:
        """kind -> its scope in a profile and its two forms (``models/hybrid.Mixer``)."""
        dt = jnp.dtype(self.dtype)

        def attention_seq(w, xn, ctx):
            y, c_kv, k_r = mla_seq(w, xn.astype(dt), self, ctx.mesh, ctx.skippable)
            return y, {"c_kv": c_kv, "k_r": k_r}

        def attention_step(w, xn, cache, ctx):
            return mla_step(w, xn.astype(dt), cache, ctx, self), None

        def experts_seq(w, xn, ctx):
            y, counters = experts.moe_seq(w, xn, ctx.lengths, self, stacked=ctx.stacked)
            return y, {ROUTING: counters}

        return {"mla": Mixer("mla", attention_seq, attention_step),
                "ffn": Mixer("ffn", lambda w, xn, ctx: (ffn(w, xn.astype(dt), ctx.skippable, ctx.stacked), {}),
                             lambda w, xn, cache, ctx: (ffn(w, xn.astype(dt)), None)),
                "moe": Mixer("moe", experts_seq, lambda w, xn, cache, ctx: experts.moe_step(w, xn, ctx.active, self, ctx.stacked), True)}

    def norm(self, x, w):
        return rms_norm(x, w, self.rms_eps)

    @property
    def expert_layer(self) -> ExpertLayer:
        return ExpertLayer(num_experts=self.n_routed_experts, top_k=self.num_experts_per_tok, score="sigmoid", bias=True,
                           norm_topk=self.norm_topk_prob, scale=self.routed_scaling_factor, act="swiglu", shared_gated=False)

    mla_rotates = True  # the shared key and each head's last qk_rope_head_dim query columns are rotated (``LatentAttention``)

    @property
    def stream_dtype(self):
        return jnp.dtype(self.dtype)

    def cache_spec(self) -> dict:
        """kind -> {name: (shape, dtype, "position" | "sequence")}: a latent layer keeps the normed
        latent and the one rotated key of every position; nothing is kept per sequence."""
        return {"mla": self.latent_entries(), "ffn": {}, "moe": {}}

    @property
    def slot_attention_tile(self) -> dict:
        """What ``ops/slot_attention.refusal`` is asked about this description's decode attention."""
        return self.latent_tile

    def num_params(self) -> int:
        """Parameters held here."""
        n = 2 * self.vocab_size * self.hidden_size + self.hidden_size
        for g, group in _shapes(self).items():
            n += self.count(g) * sum(math.prod(shape) for shape, _ in group.values())
        return n + self.count("moe") * self.n_routed_experts  # the routers' correction bias

    @staticmethod
    def tiny(**kw):
        base = dict(
            vocab_size=256, hidden_size=64, num_hidden_layers=4, first_k_dense_replace=1, intermediate_size=96, num_heads=4,
            q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16, n_routed_experts=8,
            num_experts_per_tok=2, moe_intermediate_size=32, residual_rescale_layers=8, max_seq_len=128, dtype="float32",
        )
        return Glm4MoeLiteConfig(**{**base, **kw})


# ------------------------------------------------------------------ parameters
def _shapes(c: Glm4MoeLiteConfig) -> dict:
    """group -> {name: (shape of one layer, fan_in or fill)}: matrices are N(0, fan_in^-1/2), the
    projections back onto the residual stream 1/sqrt(N) smaller, norms 1. An expert's three
    matrices are stored [F, H]."""
    H, N = c.hidden_size, c.residual_rescale_layers
    F, Fm, E = c.intermediate_size, c.moe_intermediate_size, c.n_routed_experts
    return {
        "mla": c.latent_shapes(N),
        "ffn": {"norm": ((H,), 1.0), "w_gate": ((H, F), H), "w_up": ((H, F), H), "w_down": ((F, H), F * N)},
        "moe": {"norm": ((H,), 1.0), "router": ((H, E), H), "w_gate": ((E, Fm, H), H), "w_up": ((E, Fm, H), H),
                "w_down": ((E, Fm, H), Fm * N), "shared_gate": ((H, Fm), H), "shared_up": ((H, Fm), H),
                "shared_down": ((Fm, H), Fm * N)},
    }


def init_params(config: Glm4MoeLiteConfig, key):
    """Weights from a seed, stacked by layer kind; the router's correction bias 0, in float32."""
    c, dt = config, jnp.dtype(config.dtype)
    keys = iter(jax.random.split(key, 64))
    params = init_stacked(_shapes(c), c.count, keys, dt)
    embed = jax.random.normal(next(keys), (c.vocab_size, c.hidden_size), jnp.float32)
    if c.count("moe"):
        params["moe"]["router_bias"] = jnp.zeros((c.count("moe"), c.n_routed_experts), jnp.float32)
        if c.router_anchor:
            params["moe"]["router"], embed = _anchor_routing(c, next(keys), embed, dt)
    params["embed"] = embed.astype(dt)
    params["unembed"] = (jax.random.normal(next(keys), (c.hidden_size, c.vocab_size), jnp.float32)
                         * c.hidden_size ** -0.5).astype(dt)
    params["final_norm"] = jnp.ones((c.hidden_size,), dt)
    return params


def param_logical_axes(config: Glm4MoeLiteConfig):
    """Logical axes for ``parallel/mesh.ShardingRules`` (vocabulary, experts and heads are the
    axes a mesh could split; the serving engine refuses a mesh for this model today)."""
    lead = {"mla": config.latent_axes(),
            "ffn": {"norm": (None,), "w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"), "w_down": ("mlp", "embed")},
            "moe": {"norm": (None,), "router": ("embed", None), "router_bias": (None,), "w_gate": ("expert", "mlp", "embed"),
                    "w_up": ("expert", "mlp", "embed"), "w_down": ("expert", "mlp", "embed"), "shared_gate": ("embed", "mlp"),
                    "shared_up": ("embed", "mlp"), "shared_down": ("mlp", "embed")}}
    axes = {g: {n: (None,) + a for n, a in group.items()} for g, group in lead.items() if config.count(g)}
    axes.update(embed=("vocab", "embed"), unembed=("embed", "vocab"), final_norm=(None,))
    return axes


# -------------------------------------------------------------- ffn: dense SwiGLU
def ffn(w, x, lengths=None, stacked=None):
    """``W_down (SiLU(W_gate x) * W_up x)`` on x [.., H], ``FFN_ROWS`` rows at a time; with
    ``lengths`` [B] (a serving prefill's x [B,T,H]: ``SeqCtx.skippable``) on the slabs of positions
    under them and no others, where the bucket has more than one, the matrices read where they
    lie in ``stacked`` = (the kind's stacked weights, this layer's index) (``ops/layers.live_slabs``)."""
    def some(x, w):
        return jnp.dot(jax.nn.silu(jnp.dot(x, w["w_gate"])) * jnp.dot(x, w["w_up"]), w["w_down"])

    def rows_at_a_time(x, w):
        rows = x.reshape(-1, x.shape[-1])
        if rows.shape[0] <= FFN_ROWS or rows.shape[0] % FFN_ROWS:
            return some(x, w)
        return jax.lax.map(lambda x: some(x, w), rows.reshape(-1, FFN_ROWS, x.shape[-1])).reshape(x.shape)

    # without lengths, or where the bucket has nothing to skip, ``rows_at_a_time`` of x whole; of a slab it is ``some``
    stack = None if lengths is None else ({n: stacked[0][n] for n in ("w_gate", "w_up", "w_down")}, stacked[1])
    return live_slabs(rows_at_a_time, x, lengths, w, stack)


# ------------------------------------------------------- mla: latent attention
def mla_down(w, xn, positions, c: LatentAttention):
    """The down projections of xn [B,T,H] at ``positions`` [B,T] or [T] -> what the queries come
    from (their latent c_q [B,T,q_lora_rank] after its norm; xn itself for a description without
    one), and what a position keeps: c_kv [B,T,kv_lora_rank] after its norm and the one shared key
    k_r [B,T,rope_row] (zeros after its rope columns), rotated where the description rotates, with
    the rotation's (cos, sin) or None."""
    with scope("mla.down"):
        c_q = xn if c.q_lora_rank is None else rms_norm(jnp.dot(xn, w["w_qa"]), w["q_norm"], c.rms_eps)
        kva = jnp.dot(xn, w["w_kva"])
        c_kv = rms_norm(kva[..., :c.kv_lora_rank], w["kv_norm"], c.rms_eps)
        k_r, rope = kva[..., c.kv_lora_rank:], None
        if c.mla_rotates:
            rope = rotary_embedding(positions, c.qk_rope_head_dim, c.rope_theta)
            k_r = apply_rope(k_r[..., None, :, :].astype(jnp.float32), *rope)[..., 0, :, :].astype(xn.dtype)
        k_r = jnp.pad(k_r, ((0, 0), (0, 0), (0, c.rope_row - c.qk_rope_head_dim)))
    return c_q, c_kv, k_r, rope


def _queries(w, c_q, rope, c: LatentAttention):
    """c_q [B,T,r] -> q_nope [B,nh,T,nope], q_rope [B,nh,T,rope] (rotated where ``rope`` is given)."""
    wq = w["w_q" if c.q_lora_rank is None else "w_qb"]
    q = jnp.einsum("btr,rnd->bntd", c_q, wq.reshape(wq.shape[0], c.num_heads, c.qk_head_dim))
    q_rope = q[..., c.qk_nope_head_dim:]
    if rope is not None:
        q_rope = apply_rope(q_rope.astype(jnp.float32), *rope).astype(q.dtype)
    return q[..., :c.qk_nope_head_dim], q_rope


def mla_seq(w, xn, c: LatentAttention, mesh=None, lengths=None):
    """The EXPANDED form over a padded sequence, positions 0..T-1: xn [B,T,H] -> (out [B,T,H],
    c_kv [B,T,kv_lora_rank], k_r [B,T,rope_row]) with the latter two as the cache keeps them.
    The flash kernel takes queries, keys and values of ONE width, of 64, 128 or 256 columns: a
    description whose values are narrower than its keys, or whose keys are of another width (Kimi
    Linear: 128 + 64 against 128), has both padded with zeros up to the next of those, which
    changes no score and adds zero columns to the output, cut off again. ``lengths`` [B]: the true
    lengths, where the kernel may skip what lies past them (``SeqCtx.skippable``)."""
    B, T, _ = xn.shape
    nh = c.num_heads
    c_q, c_kv, k_r, rope = mla_down(w, xn, jnp.arange(T, dtype=jnp.int32), c)
    with scope("mla.expand"):
        q_nope, q_rope = _queries(w, c_q, rope, c)
        k_nope = jnp.einsum("btr,rnd->bntd", c_kv, w["w_kb"].reshape(c.kv_lora_rank, nh, c.qk_nope_head_dim))
        v = jnp.einsum("btr,rnd->bntd", c_kv, w["w_vb"].reshape(c.kv_lora_rank, nh, c.v_head_dim))
        width = c.flash_width
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_r[:, None, :, :c.qk_rope_head_dim], (B, nh, T, c.qk_rope_head_dim))], axis=-1)
        q, k, v = (jnp.pad(a, ((0, 0), (0, 0), (0, 0), (0, width - a.shape[-1]))) for a in (q, k, v))
    with scope("mla.attn"):
        o = flash_attention_on_mesh(q, k, v, mesh, c.attention_impl, scale=None if width == c.qk_head_dim else c.qk_head_dim ** -0.5, lengths=lengths)
    o = o[..., :c.v_head_dim].transpose(0, 2, 1, 3).reshape(B, T, nh * c.v_head_dim)
    return jnp.dot(o.astype(xn.dtype), w["wo"]), c_kv, k_r


def mla_step(w, xn, cache, ctx, c: LatentAttention):
    """The ABSORBED form for one token a lane: xn [B,H] -> out [B,H]. Writes the new position's
    latent and key through ``cache``, then every head attends over the lane's live positions
    where they lie in the stack, on the latent itself."""
    B, nh, r = xn.shape[0], c.num_heads, c.kv_lora_rank
    c_q, c_kv, k_r, rope = mla_down(w, xn[:, None], ctx.lengths[:, None], c)
    cache.write("c_kv", c_kv[:, 0])
    cache.write("k_r", k_r[:, 0])
    with scope("mla.expand"):
        q_nope, q_rope = _queries(w, c_q, rope, c)  # [B,nh,1,.]
    with scope("mla.absorb"):
        q_lat = jnp.einsum("bnd,rnd->bnr", q_nope[:, :, 0], w["w_kb"].reshape(r, nh, c.qk_nope_head_dim))
    with scope("mla.attn"):
        (c_stack, i), (r_stack, _) = cache.stacked("c_kv"), cache.stacked("k_r")
        o_lat = slot_attention.attend_latent(q_lat, q_rope[:, :, 0], c_stack, r_stack, i, ctx.lengths,
                                             scale=c.qk_head_dim ** -0.5, live=ctx.active)  # [B, nh*r] f32
    with scope("mla.expand"):
        o = jnp.einsum("bnr,rnd->bnd", o_lat.reshape(B, nh, r).astype(xn.dtype), w["w_vb"].reshape(r, nh, c.v_head_dim))
    return jnp.dot(o.reshape(B, nh * c.v_head_dim), w["wo"])
