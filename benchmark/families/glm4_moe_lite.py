"""The GLM-4.7-Flash block (``model_type`` ``glm4_moe_lite``): every published decoder layer is two
residual sub-blocks, ``x = x + attn(N(x))`` then ``x = x + mlp(N(x))``, with
``N(x) = w * x / sqrt(mean(x²) + eps)``; attention is multi-head LATENT attention in every layer;
the MLP of the first ``first_k_dense_replace`` layers is a dense SwiGLU, of the others
``n_routed_experts`` sigmoid-routed SwiGLU experts behind one shared expert; then a final ``N``
and an untied head. The program's side is ``ray_tpu.models.glm4_moe_lite``; the plain reference
below is written from the catalog row's ``config`` and the equations of ISSUE 36 (PERF.md section
4 repeats them), not from that file: one sequence, float32 at ``highest`` precision, the EXPANDED
form of the attention only (keys and values of every head from the latent, the one rotated key
broadcast to the heads, full causal softmax), no cache, no kernel, every held expert over every
token one expert at a time, one layer's (one expert's) weights cast at a time. At the cell's
16,384 positions it goes in blocks (queries, rows of the dense layer, columns of the head) so
that it fits beside the engine: blocking, not mathematics.

Multi-head latent attention, 20 heads: ``c_q = N(x W_qa)``; ``q = c_q W_qb``, each head
``[q_nope | q_rope]``; ``[c_kv | k_r] = x W_kva``; ``c_kv = N(c_kv)``; ``k_r = RoPE(k_r)`` over
all its dimensions, ONE rotated key for the 20 heads; ``k_nope_h = c_kv W_kb[h]``,
``v_h = c_kv W_vb[h]``; ``score_h(t, s) = (q_nope_h(t) . k_nope_h(s) + RoPE(q_rope_h(t)) . k_r(s))
/ sqrt(nope + rope)``, causal softmax, ``o_h = sum p v_h``, ``W_o``. ``rope_scaling`` is null, so
no further scale. The router: ``s = sigmoid(x W_r)`` in float32, the top k of
``s + e_score_correction_bias`` (``noaux_tc``; one group, so no group limit), their own ``s``
normalised to sum 1, times ``routed_scaling_factor``.

Sizes come from the configuration file's keys, never from the program's config object. The
weights are the pytree the program serves (``embed``, ``unembed``, ``final_norm``, and ``mla`` /
``ffn`` / ``moe`` stacked by layer kind; the published ``kv_b_proj`` is held as its two column
sets ``w_kb`` and ``w_vb``, an expert's matrices [F, H]).

Departures from the published model, each of which program and reference share:
- the checkpoint's multi-token-prediction module (``num_nextn_predict_layers`` 1) is left out:
  it changes no logit of the 47 layers (``assumed`` in the configuration file);
- weights are random from a seed, the routers and the embedding table anchored
  (``init_router_anchor``) as PR 29 found necessary;
- rotate-half pairing of the rotated dimensions (``assumed``: random weights cannot tell it from
  the interleaved pairing, so long as program and reference agree).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.models.glm4_moe_lite import Glm4MoeLiteConfig, init_params, loss_fn, param_logical_axes  # noqa: F401 - the family's names

# sizes of the CPU rehearsal (--rehearse): wiring only, never a measurement; every kind of layer
REHEARSAL_SIZES = {
    "hidden_size": 64, "num_hidden_layers": 3, "first_k_dense_replace": 1, "vocab_size": 512, "intermediate_size": 96,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 12,
    "qk_rope_head_dim": 4, "v_head_dim": 16, "n_routed_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "init_router_anchor": 0.0, "reduced_from": {"num_hidden_layers": 3},
}

# the reference pads a sequence to a multiple of this: few distinct shapes to compile (four layer
# functions each), and what follows a position moves nothing before it
PAD_TO = 2048
# queries the reference's attention takes at once (20 heads x 256 x 16,384 float32 scores are 335 MB),
# rows its dense layer takes at once, and the least vocabulary its head takes in column blocks
QUERY_BLOCK, ROW_BLOCK, HEAD_BLOCKS_FROM = 256, 2048, 65536


def kinds(c: dict) -> list[str]:
    """The MLP of every published layer held here: ``F`` dense, ``E`` experts (each behind an ``A``)."""
    return ["F" if i < c["first_k_dense_replace"] else "E" for i in range(c["num_hidden_layers"])]


# ------------------------------------------------------------------------------ the program's side
def program_config(c: dict, max_seq_len: int, **extra) -> Glm4MoeLiteConfig:
    """The program's ``Glm4MoeLiteConfig`` for a configuration file's published keys."""
    published_depth = (c.get("reduced_from") or {}).get("num_hidden_layers", c["num_hidden_layers"])
    return Glm4MoeLiteConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"], num_hidden_layers=c["num_hidden_layers"],
        first_k_dense_replace=c["first_k_dense_replace"], intermediate_size=c["intermediate_size"],
        num_heads=c["num_attention_heads"], q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"], qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        rope_theta=float(c["rope_theta"]), n_routed_experts=c["n_routed_experts"], num_experts_per_tok=c["num_experts_per_tok"],
        moe_intermediate_size=c["moe_intermediate_size"], n_shared_experts=c["n_shared_experts"],
        norm_topk_prob=bool(c["norm_topk_prob"]), routed_scaling_factor=float(c["routed_scaling_factor"]),
        rms_eps=float(c["rms_norm_eps"]), max_seq_len=max_seq_len,
        # the initialisation's 1/sqrt(N) on the projections back onto the stream: N counts the PUBLISHED sub-blocks
        residual_rescale_layers=2 * published_depth, router_anchor=float(c.get("init_router_anchor", 0.0)),
        dtype={"bfloat16": "bfloat16", "float32": "float32"}[c.get("torch_dtype", "bfloat16")], **extra)


def rehearsal(c: dict) -> dict:
    return {**c, **REHEARSAL_SIZES, "torch_dtype": "float32"}


def kernels_expected(c: dict) -> dict:
    """Prefill expands and runs the flash kernel; a Pallas kernel lowers to a ``tpu_custom_call``."""
    return {"flash kernel": "tpu_custom_call"}


# ----------------------------------------------------------------------------- operations, from shapes
def layer_params(c: dict) -> dict:
    """Parameters by part: the attention of one layer (``A``: its five matrices and two inner norms),
    what a layer holds outside its attention and its routed experts (``F_rest``: the dense MLP and
    the two stream norms; ``E_rest``: router, correction bias, shared expert, the two norms), one
    routed ``expert``, and embedding plus head."""
    H, nh, r = c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    attn = (H * c["q_lora_rank"] + c["q_lora_rank"] + c["q_lora_rank"] * nh * qk + H * (r + c["qk_rope_head_dim"]) + r
            + r * nh * (c["qk_nope_head_dim"] + c["v_head_dim"]) + nh * c["v_head_dim"] * H)
    expert = 3 * H * c["moe_intermediate_size"]
    return {"A": attn, "F_rest": 3 * H * c["intermediate_size"] + 2 * H,
            "E_rest": H * c["n_routed_experts"] + c["n_routed_experts"] + c["n_shared_experts"] * expert + 2 * H,
            "expert": expert, "embed_and_head": 2 * c["vocab_size"] * H, "final_norm": H}


def parameters_held(c: dict) -> int:
    p, ks = layer_params(c), kinds(c)
    per = {"F": p["A"] + p["F_rest"], "E": p["A"] + p["E_rest"] + c["n_routed_experts"] * p["expert"]}
    return sum(per[k] for k in ks) + p["embed_and_head"] + p["final_norm"]


def row_width(c: dict) -> int:
    """What a latent layer keeps of one position: the latent and the one rotated key."""
    return c["kv_lora_rank"] + c["qk_rope_head_dim"]


def kv_bytes_per_token(c: dict, itemsize: int = 2) -> int:
    return c["num_hidden_layers"] * row_width(c) * itemsize


def latent_attention_least(c: dict, rows: float, itemsize: int = 2) -> dict:
    """What the decode step's latent attention must move and compute for ``rows`` live rows (one a
    layer, lane and position held, the new token's among them): each row read once, and for each of
    the heads a score over the row's whole width and a weighted sum over its latent part."""
    return {"bytes": float(rows * row_width(c) * itemsize),
            "flops": float(rows * 2 * c["num_attention_heads"] * (row_width(c) + c["kv_lora_rank"]))}


def _per_token_matmul(c: dict, experts_a_token: float) -> float:
    """Multiply-adds per token in the whole stack, without the head: every matrix (norm weights
    and the correction bias multiply nothing), with ``experts_a_token`` routed experts in each
    expert layer. The attention's projections are counted in the EXPANDED form (``W_kb`` and
    ``W_vb`` once a token), which is also what the absorbed form costs a decoded token."""
    p, ks = layer_params(c), kinds(c)
    H = c["hidden_size"]
    attn = p["A"] - c["q_lora_rank"] - c["kv_lora_rank"]
    dense = p["F_rest"] - 2 * H
    block = p["E_rest"] - 2 * H - c["n_routed_experts"] + experts_a_token * p["expert"]
    return len(ks) * attn + ks.count("F") * dense + ks.count("E") * block


def _fixed(c: dict) -> int:
    """Every weight outside the routed experts, the head and the final norm (not the embedding table)."""
    p, ks = layer_params(c), kinds(c)
    return len(ks) * p["A"] + ks.count("F") * p["F_rest"] + ks.count("E") * p["E_rest"] + c["hidden_size"] * c["vocab_size"] + c["hidden_size"]


def decode_step_least(c: dict, lanes: float, experts_hit: float, kv_tokens: float, itemsize: int = 2) -> dict:
    """What ONE decode step must move and compute, whatever the program: every weight outside
    the routed experts once (the embedding is ``lanes`` rows), ``experts_hit`` routed experts in
    each expert layer, and the latent rows of the ``kv_tokens`` positions the lanes hold, in every
    layer. FLOPs: two per weight and lane with the experts a token is routed to, plus the latent
    attention over the positions held. -> {"bytes", "flops"}."""
    p, ks = layer_params(c), kinds(c)
    attn = latent_attention_least(c, kv_tokens * len(ks), itemsize)
    nbytes = (_fixed(c) + ks.count("E") * experts_hit * p["expert"] + lanes * c["hidden_size"]) * itemsize + attn["bytes"]
    per_token = _per_token_matmul(c, c["num_experts_per_tok"]) + c["hidden_size"] * c["vocab_size"]
    return {"bytes": float(nbytes), "flops": float(2.0 * lanes * per_token + attn["flops"])}


def prefill_least(c: dict, lengths: list, pairs_local: float, experts_hit: float, itemsize: int = 2) -> dict:
    """What ONE prefill call over prompts of the TRUE ``lengths`` must move and compute, whatever
    the program: every weight outside the routed experts once, ``experts_hit`` routed experts in
    each expert layer once (a mean over the layers), the prompts' embedding rows, and the latent
    rows it hands the cache. FLOPs at the true lengths: two per weight outside the routed experts
    and token, two per expert weight and (token, expert) pair (``pairs_local``: a mean over the
    expert layers), and causal attention in the expanded form (each query against the positions
    up to its own: 2 x heads x (key width + value width) each). Padding to the bucket and to a
    power of two of prompts is the program's choice and is not in here. -> {"bytes", "flops"}."""
    p, ks = layer_params(c), kinds(c)
    H, V = c["hidden_size"], c["vocab_size"]
    tokens = float(sum(lengths))
    nbytes = (_fixed(c) + ks.count("E") * experts_hit * p["expert"] + tokens * H) * itemsize + tokens * kv_bytes_per_token(c, itemsize)
    causal = sum(n * (n + 1) / 2.0 for n in lengths)
    widths = c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"]
    flops = (2.0 * tokens * _per_token_matmul(c, 0.0) + 2.0 * len(lengths) * H * V  # the head reads each prompt's last position only
             + 2.0 * ks.count("E") * pairs_local * p["expert"] + 2.0 * causal * len(ks) * c["num_attention_heads"] * widths)
    return {"bytes": float(nbytes), "flops": float(flops)}


def train_flops_per_token(c: dict, seq: int) -> float:
    """FLOPs the forward and backward passes require per trained token: 6 per weight that
    multiplies the token (the experts it is routed to, not all that are held) plus three times the
    causal attention forward. No recompute. (No cell trains this family: the no-drop expert layer
    has no backward pass.)"""
    widths = c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"]
    one = 2.0 * (_per_token_matmul(c, c["num_experts_per_tok"]) + c["hidden_size"] * c["vocab_size"])
    return 3.0 * (one + seq * c["num_hidden_layers"] * c["num_attention_heads"] * widths)


# --------------------------------------------------------------------------------- the plain reference
def _norm(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _layer_weights(group, i):
    return jax.tree.map(lambda p: jax.lax.dynamic_index_in_dim(p, i, 0, keepdims=False), group)


@functools.partial(jax.jit, static_argnames=("nh", "nope", "rope", "vd", "theta", "eps"))
def _latent_attention(x, group, i, *, nh, nope, rope, vd, theta, eps):
    """One attention sub-block on x [T, H], in the expanded form: queries through their latent,
    keys and values of every head from the normed latent c_kv, the one rotated key broadcast to the
    heads, causal softmax((q_nope . k_nope + q_rope . k_r) / sqrt(nope + rope)) v, W_o. Queries in
    blocks of ``QUERY_BLOCK`` where there are more."""
    w = jax.tree.map(lambda p: p.astype(jnp.float32), _layer_weights(group, i))
    T, r = x.shape[0], w["kv_norm"].shape[0]
    xn = _norm(x, w["norm"], eps)
    q = (_norm(xn @ w["w_qa"], w["q_norm"], eps) @ w["w_qb"]).reshape(T, nh, nope + rope)
    kva = xn @ w["w_kva"]
    c_kv, k_r = _norm(kva[:, :r], w["kv_norm"], eps), kva[:, r:]
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * theta ** (-jnp.arange(0, rope, 2, dtype=jnp.float32) / rope)  # [T, rope/2]
    cos, sin = jnp.cos(angle), jnp.sin(angle)

    def rotate(a, cos, sin):  # rotate-half over all of a's last dimension
        a1, a2 = a[..., :rope // 2], a[..., rope // 2:]
        return jnp.concatenate([a1 * cos - a2 * sin, a2 * cos + a1 * sin], axis=-1)

    k_nope = (c_kv @ w["w_kb"]).reshape(T, nh, nope)
    v = (c_kv @ w["w_vb"]).reshape(T, nh, vd)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(rotate(k_r, cos, sin)[:, None], (T, nh, rope))], axis=-1)
    q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:], cos[:, None], sin[:, None])], axis=-1)

    def some_queries(qb):
        q_b, first = qb  # [Q, nh, nope + rope], the position of the block's first query
        s = jnp.einsum("qhd,khd->hqk", q_b, k) * (nope + rope) ** -0.5
        before = jnp.arange(T)[None, :] <= (first + jnp.arange(q_b.shape[0]))[:, None]
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(jnp.where(before[None], s, -jnp.inf), axis=-1), v)

    Q = QUERY_BLOCK if T > QUERY_BLOCK and T % QUERY_BLOCK == 0 else T
    o = jax.lax.map(some_queries, (q.reshape(T // Q, Q, nh, nope + rope), jnp.arange(0, T, Q)))
    return x + o.reshape(T, nh * vd) @ w["wo"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense(x, group, i, *, eps):
    """The dense MLP sub-block on x [T, H]: W_down (SiLU(W_gate x) * W_up x), rows in blocks."""
    w = jax.tree.map(lambda p: p.astype(jnp.float32), _layer_weights(group, i))
    T = x.shape[0]

    def some_rows(x_b):
        xn = _norm(x_b, w["norm"], eps)
        return x_b + (jax.nn.silu(xn @ w["w_gate"]) * (xn @ w["w_up"])) @ w["w_down"]

    R = ROW_BLOCK if T > ROW_BLOCK and T % ROW_BLOCK == 0 else T
    return jax.lax.map(some_rows, x.reshape(T // R, R, -1)).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("top_k", "norm", "scale", "eps"))
def _experts(x, group, i, *, top_k, norm, scale, eps):
    """One expert sub-block on x [T, H]: sigmoid scores over every expert, the top_k of score +
    correction bias, their own scores normalised to sum to 1 and scaled; every expert
    (W_down (SiLU(W_gate x) * W_up x)) over every token, one expert at a time, weighted by what the
    router gave it (nothing where it was not chosen); plus the shared expert, ungated."""
    w = _layer_weights(group, i)
    small = {k: w[k].astype(jnp.float32) for k in ("norm", "router", "router_bias", "shared_gate", "shared_up", "shared_down")}
    xn = _norm(x, small["norm"], eps)
    score = jax.nn.sigmoid(xn @ small["router"])
    _, idx = jax.lax.top_k(score + small["router_bias"], top_k)
    wt = jnp.take_along_axis(score, idx, axis=-1)
    wt = (wt / jnp.sum(wt, axis=-1, keepdims=True) if norm else wt) * scale
    given = jnp.zeros_like(score).at[jnp.arange(x.shape[0])[:, None], idx].set(wt)  # [T, experts]

    def one_expert(e, acc):
        gate, up, down = (w[n][e].astype(jnp.float32) for n in ("w_gate", "w_up", "w_down"))  # each stored [F, H]
        return acc + given[:, e, None] * ((jax.nn.silu(xn @ gate.T) * (xn @ up.T)) @ down)

    y = jax.lax.fori_loop(0, w["w_up"].shape[0], one_expert, jnp.zeros_like(x))
    shared = (jax.nn.silu(xn @ small["shared_gate"]) * (xn @ small["shared_up"])) @ small["shared_down"]
    return x + y + shared, idx


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, unembed, *, eps):
    """log softmax(N(x) W_head); the head's columns in blocks where the vocabulary is large (the
    published head in float32 is 1.27 GB)."""
    xn = _norm(x, final_norm.astype(jnp.float32), eps)
    H, V = unembed.shape
    blocks = 10 if V >= HEAD_BLOCKS_FROM and V % 10 == 0 else 1
    cols = jnp.moveaxis(unembed.reshape(H, blocks, V // blocks), 1, 0)
    logits = jax.lax.map(lambda u: xn @ u.astype(jnp.float32), cols)  # [blocks, n, V / blocks]
    return jax.nn.log_softmax(jnp.moveaxis(logits, 0, 1).reshape(x.shape[0], V), axis=-1)


def hidden_states(params: dict, tokens, c: dict, choices: list | None = None):
    """tokens [T] int32 -> the last layer's output [T, H], float32. ``choices``, if a list, gets
    each expert layer's chosen experts [T, top_k] appended (for the router-agreement count)."""
    eps, seen = float(c["rms_norm_eps"]), {"F": 0, "E": 0}
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(tokens, jnp.int32), axis=0).astype(jnp.float32)
        for layer, kind in enumerate(kinds(c)):
            i, seen[kind] = seen[kind], seen[kind] + 1
            x = _latent_attention(x, params["mla"], layer, nh=c["num_attention_heads"], nope=c["qk_nope_head_dim"],
                                  rope=c["qk_rope_head_dim"], vd=c["v_head_dim"], theta=float(c["rope_theta"]), eps=eps)
            if kind == "F":
                x = _dense(x, params["ffn"], i, eps=eps)
            else:
                x, idx = _experts(x, params["moe"], i, top_k=c["num_experts_per_tok"], norm=bool(c["norm_topk_prob"]),
                                  scale=float(c["routed_scaling_factor"]), eps=eps)
                if choices is not None:
                    choices.append(idx)
    return x


def reference_logprobs(params: dict, tokens, c: dict, start: int, stop: int):
    """Log-probabilities [stop - start, vocab] that the model gives, after reading
    tokens[: p + 1], to the token at position p + 1, for p in [start, stop)."""
    tokens = list(tokens) + [0] * (-len(tokens) % PAD_TO)  # few distinct shapes to compile; every layer is causal
    x = hidden_states(params, tokens, c)[start:stop]
    with jax.default_matmul_precision("highest"):
        return _head(x, params["final_norm"], params["unembed"], eps=float(c["rms_norm_eps"]))
