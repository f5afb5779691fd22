"""The Kimi Linear block (``model_type`` ``kimi_linear``): every published decoder layer is two
residual sub-blocks, ``x = x + mixer(N(x))`` then ``x = x + mlp(N(x))``, with
``N(x) = w * x / sqrt(mean(x²) + eps)``; layer ``i`` (1-indexed) mixes by multi-head latent
attention WITHOUT position where ``linear_attn_config.full_attn_layers`` lists it and by Kimi Delta
Attention where ``kda_layers`` does; the MLP of the first ``first_k_dense_replace`` layers is a
dense SwiGLU, of the others ``num_experts`` sigmoid-routed SwiGLU experts behind one shared expert;
then a final ``N`` and an untied head. The program's side is ``ray_tpu.models.kimi_linear``; the
plain reference below is written from the catalog row's ``config`` and the equations of ISSUE 42
(PERF.md section 4 repeats them), not from that file: one sequence, float32 at ``highest``
precision, the delta rule ONE POSITION AT A TIME (no chunks), the attention in its EXPANDED form
only (every head's key and value built from the latent, no absorption, no cache), every held
expert over every token one expert at a time, one layer's (one expert's) weights cast at a time.

Kimi Delta Attention, ``nh`` heads of ``dk``: ``q, k, v = SiLU(conv4(x W_q)), SiLU(conv4(x W_k)),
SiLU(conv4(x W_v))``, the convolutions causal and depthwise; q and k L2-normalised over a head's
channels, q times ``dk^-1/2``; ``g_t = -exp(A_log[h]) * softplus(W_fb (W_fa x) + dt_bias)``, one
value for EACH of a head's key channels; ``beta_t = sigmoid(W_b x)``, one a head. Per head a state
S [dk key x dk value]: ``S <- Diag(exp(g_t)) S``; ``S <- S + beta_t k_t (v_t - S^T k_t)^T``;
``o_t = S^T q_t``. Then ``w * o / sqrt(mean(o²) + eps) * sigmoid(W_gb (W_ga x))`` per head and
``W_o``. Latent attention, NoPE: ``q = x W_q`` (no query latent), a head ``[q_nope | q_s]``;
``[c_kv | k_s] = x W_kva``; ``c_kv = N(c_kv)``; a head's key ``[c_kv W_kb[h] | k_s]`` (``k_s``
shared by all heads), its value ``c_kv W_vb[h]``; scale ``(nope + rope)^-1/2``; causal softmax;
``W_o``. NOTHING is rotated. The router: ``s = sigmoid(x W_r)`` in float32, the top k of
``s + e_score_correction_bias`` (one group: no group limit), their own ``s`` renormalised to sum
1, times ``routed_scaling_factor``; the shared expert ungated.

A configuration of this family may be ONE CHIP'S SHARE of a deployment that splits each layer
over several chips by expert parallelism: ``num_experts`` and ``vocab_size`` are then what is held
here, and ``deployment`` says what was published and which part this is. The router keeps its
published width and its experts per token; a token's choice that lives on another chip adds
nothing here, in the program and in the reference alike. Sizes come from the configuration file's
keys, never from the program's config object. The weights are the pytree the program serves
(``embed``, ``unembed``, ``final_norm``, and ``kda`` / ``mla`` / ``ffn`` / ``moe`` stacked by layer
kind; the KDA's projections hold their columns flat, ``[q | k | v]`` and ``[f_a | g_a | b]``, the
published ``kv_b_proj`` is held as its two column sets ``w_kb`` and ``w_vb``, an expert's matrices
[F, H]).

Departures from the published model, each of which program and reference share: weights are random
from a seed (``assumed`` in the configuration file says how), the routers and the embedding table
anchored (``init_router_anchor``) as PR 29 found necessary; the flat column order of the KDA's
projections is a relabelling that random weights cannot tell apart.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.models.kimi_linear import KimiLinearConfig, init_params, loss_fn, param_logical_axes  # noqa: F401 - the family's names

# sizes of the CPU rehearsal (--rehearse): wiring only, never a measurement; every kind of layer,
# a dense layer before a period (M E K E) that repeats
REHEARSAL_SIZES = {
    "hidden_size": 64, "num_hidden_layers": 5, "first_k_dense_replace": 1, "vocab_size": 512, "intermediate_size": 96,
    "linear_attn_config": {"full_attn_layers": [2, 4], "kda_layers": [1, 3, 5], "head_dim": 8, "num_heads": 4, "short_conv_kernel_size": 4},
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16, "kv_lora_rank": 32, "qk_nope_head_dim": 12, "qk_rope_head_dim": 4,
    "v_head_dim": 8, "num_experts": 4, "num_experts_per_token": 2, "moe_intermediate_size": 32, "init_router_anchor": 0.0,
    "assumed": {"chunk_size": 8}, "reduced_from": {"num_hidden_layers": 5},
    "deployment": {"chips_per_layer": 2, "experts_published": 8, "experts_held": [0, 4], "vocab_rows_held": [0, 512]},
}

# the reference pads a sequence to the first of these lengths that holds it (a multiple of the last
# beyond that): what follows a position moves nothing before it, and every distinct length compiles
# five layer functions anew. Every prompt of the cell with its answer then has ONE length, the
# cell's horizon: at multiples of 1,024 a first run's check of five served samples took 82 s, 75 of
# them compiles (my chip run, PR 42)
PAD_TO = (1024, 4096)
# queries the reference's attention takes at once: 32 heads x 256 x 4,096 float32 scores are 134 MB
QUERY_BLOCK = 256
# peak FLOPs a state element and position that the rule itself asks for: the decay (1), the read
# S^T k (2), the rank-one write (2) and the read-out S^T q (2); no way of blocking it needs fewer
RULE_FLOPS = 7.0


def padded_length(n: int) -> int:
    return next((p for p in PAD_TO if p >= n), -(-n // PAD_TO[-1]) * PAD_TO[-1])


def held(c: dict) -> tuple[int, int, int]:
    """(router width, first expert held, experts held). Without a ``deployment`` the chip holds all."""
    dep = c.get("deployment") or {}
    first = int((dep.get("experts_held") or [0])[0])
    return int(dep.get("experts_published", c["num_experts"])), first, int(c["num_experts"])


def kinds(c: dict) -> list[tuple[str, str]]:
    """(mixer, MLP) of every published layer held here: ``K`` Kimi Delta Attention or ``M`` latent
    attention, then ``F`` the dense MLP or ``E`` the experts."""
    lin = c["linear_attn_config"]
    depth = c["num_hidden_layers"]
    full, kda = ({i for i in lin[k] if i <= depth} for k in ("full_attn_layers", "kda_layers"))
    if full & kda or full | kda != set(range(1, depth + 1)):
        raise ValueError("linear_attn_config: every layer held is in exactly one of full_attn_layers and kda_layers")
    return [("M" if i in full else "K", "F" if i <= c["first_k_dense_replace"] else "E") for i in range(1, depth + 1)]


# ------------------------------------------------------------------------------ the program's side
def program_config(c: dict, max_seq_len: int, **extra) -> KimiLinearConfig:
    """The program's ``KimiLinearConfig`` for a configuration file's published keys."""
    if c.get("moe_router_activation_func", "sigmoid") != "sigmoid" or c.get("num_expert_group", 1) != 1 or c.get("topk_group", 1) != 1:
        raise ValueError("this family routes by sigmoid scores over one group of experts")
    width, first, n_held = held(c)
    lin = c["linear_attn_config"]
    published_depth = (c.get("reduced_from") or {}).get("num_hidden_layers", c["num_hidden_layers"])
    return KimiLinearConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"], num_hidden_layers=c["num_hidden_layers"],
        first_k_dense_replace=c["first_k_dense_replace"], intermediate_size=c["intermediate_size"],
        full_attn_layers=tuple(lin["full_attn_layers"]), kda_num_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        conv_kernel=lin["short_conv_kernel_size"], chunk_size=int((c.get("assumed") or {}).get("chunk_size", 64)),
        num_heads=c["num_attention_heads"], head_dim=c["head_dim"], q_lora_rank=c.get("q_lora_rank"), kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"], qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        mla_use_nope=bool(c["mla_use_nope"]), rope_theta=float(c["rope_theta"]),
        num_experts=width, expert_start=first, num_local_experts=n_held, num_experts_per_tok=c["num_experts_per_token"],
        moe_intermediate_size=c["moe_intermediate_size"], num_shared_experts=c["num_shared_experts"],
        moe_renormalize=bool(c["moe_renormalize"]), routed_scaling_factor=float(c["routed_scaling_factor"]),
        rms_eps=float(c["rms_norm_eps"]), max_seq_len=max_seq_len,
        # the initialisation's 1/sqrt(N) on the projections back onto the stream: N counts the PUBLISHED sub-blocks
        residual_rescale_layers=2 * published_depth, router_anchor=float(c.get("init_router_anchor", 0.0)),
        dtype={"bfloat16": "bfloat16", "float32": "float32"}[c.get("torch_dtype", "bfloat16")], **extra)


def rehearsal(c: dict) -> dict:
    return {**c, **REHEARSAL_SIZES, "torch_dtype": "float32"}


def kernels_expected(c: dict) -> dict:
    """The ``M`` layers' prefill expands and runs the flash kernel: a Pallas kernel lowers to a ``tpu_custom_call``."""
    return {"flash kernel": "tpu_custom_call"}


# ----------------------------------------------------------------------------- operations, from shapes
def _kda_dim(c: dict) -> int:
    return c["linear_attn_config"]["num_heads"] * c["linear_attn_config"]["head_dim"]


def layer_params(c: dict) -> dict:
    """Parameters by part: one ``K`` mixer (q, k, v, the output projection, the two low-rank
    gates, b, three convolutions, dt_bias a channel, A_log a head, the head norm) and one ``M``
    mixer (q, the down projection, its norm, ``kv_b`` as two matrices, the output projection);
    what a layer holds outside its mixer and its routed experts (``F_rest``: the dense MLP and the
    two stream norms; ``E_rest``: router, correction bias, shared expert, the two norms); one routed
    ``expert``; embedding plus head."""
    H, D, lin = c["hidden_size"], _kda_dim(c), c["linear_attn_config"]
    nh_k, dk, K = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    nh, r, nope, rope, vd = c["num_attention_heads"], c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    width, _, _ = held(c)
    kda = 3 * H * D + D * H + 2 * (H * dk + dk * D) + H * nh_k + 3 * K * D + D + nh_k + dk
    mla = H * nh * (nope + rope) + H * (r + rope) + r + r * nh * (nope + vd) + nh * vd * H
    expert = 3 * H * c["moe_intermediate_size"]
    return {"K": kda, "M": mla, "F_rest": 3 * H * c["intermediate_size"] + 2 * H,
            "E_rest": H * width + width + c["num_shared_experts"] * expert + 2 * H,
            "expert": expert, "embed_and_head": 2 * c["vocab_size"] * H, "final_norm": H}


def parameters_held(c: dict) -> int:
    p, (_, _, n_held) = layer_params(c), held(c)
    per = {"F": p["F_rest"], "E": p["E_rest"] + n_held * p["expert"]}
    return sum(p[mixer] + per[mlp] for mixer, mlp in kinds(c)) + p["embed_and_head"] + p["final_norm"]


def _count(c: dict) -> dict:
    ks = kinds(c)
    return {k: sum(1 for mixer, mlp in ks if k in (mixer, mlp)) for k in "KMFE"}


def state_bytes_per_slot(c: dict, itemsize: int = 2) -> int:
    """What the ``K`` layers keep for one sequence: a float32 state a head and the three convolutions' window."""
    lin = c["linear_attn_config"]
    return _count(c)["K"] * (lin["num_heads"] * lin["head_dim"] ** 2 * 4 + (lin["short_conv_kernel_size"] - 1) * 3 * _kda_dim(c) * itemsize)


def row_width(c: dict) -> int:
    """What a latent layer keeps of one position: the latent and the one shared key."""
    return c["kv_lora_rank"] + c["qk_rope_head_dim"]


def kv_bytes_per_token(c: dict, itemsize: int = 2) -> int:
    """As published; the chip stores the shared key in whole 128-lane tiles (``models/glm4_moe_lite.py``)."""
    return _count(c)["M"] * row_width(c) * itemsize


def latent_attention_least(c: dict, rows: float, itemsize: int = 2) -> dict:
    """What the decode step's latent attention must move and compute for ``rows`` live rows (one a
    latent layer, lane and position held, the new token's among them): each row read once, and for
    each of the heads a score over the row's whole width and a weighted sum over its latent part."""
    return {"bytes": float(rows * row_width(c) * itemsize),
            "flops": float(rows * 2 * c["num_attention_heads"] * (row_width(c) + c["kv_lora_rank"]))}


def kda_chunk_least(c: dict, tokens: float, sequences: float = 0.0, itemsize: int = 2) -> dict:
    """What the delta rule of ONE ``K`` layer must move and compute for ``tokens`` positions in
    ``sequences`` sequences, whatever runs it: q, k and v read and the output written once (the
    configuration's dtype), the gate a key channel (float32, as the configuration states the
    gates) and beta a head read once, and each sequence's state written once (float32; it starts at
    zero). FLOPs: the recurrence's own, ``RULE_FLOPS`` a state element and position, with NO term
    that depends on a chunk size: the count is of the rule, not of one way to run it."""
    lin = c["linear_attn_config"]
    nh, dk = lin["num_heads"], lin["head_dim"]
    per_token = 4 * nh * dk * itemsize + nh * dk * 4 + nh * 4
    return {"bytes": float(tokens * per_token + sequences * nh * dk * dk * 4), "flops": float(RULE_FLOPS * tokens * nh * dk * dk)}


def _per_token_matmul(c: dict, routed_here: float) -> float:
    """Multiply-adds per token in the whole stack, without the head: every matrix (the
    convolutions' K taps a channel among them; norm weights, dt_bias, A_log and the correction
    bias multiply nothing), with ``routed_here`` of a token's chosen experts held on this chip (a
    mean) in each expert layer. The latent attention's projections are counted in the EXPANDED
    form (``W_kb`` and ``W_vb`` once a token), which is also what the absorbed form costs a decoded token."""
    p, n, lin = layer_params(c), _count(c), c["linear_attn_config"]
    H, width = c["hidden_size"], held(c)[0]
    kda = p["K"] - _kda_dim(c) - lin["num_heads"] - lin["head_dim"]
    mla = p["M"] - c["kv_lora_rank"]
    block = p["E_rest"] - 2 * H - width + routed_here * p["expert"]
    return n["K"] * kda + n["M"] * mla + n["F"] * (p["F_rest"] - 2 * H) + n["E"] * block


def _fixed(c: dict) -> int:
    """Every weight outside the routed experts, the head and the final norm (not the embedding table)."""
    p, n = layer_params(c), _count(c)
    return n["K"] * p["K"] + n["M"] * p["M"] + n["F"] * p["F_rest"] + n["E"] * p["E_rest"] + c["hidden_size"] * c["vocab_size"] + c["hidden_size"]


def _state_elements(c: dict) -> int:
    lin = c["linear_attn_config"]
    return lin["num_heads"] * lin["head_dim"] ** 2


def decode_step_least(c: dict, lanes: float, experts_hit: float, kv_tokens: float, itemsize: int = 2) -> dict:
    """What ONE decode step must move and compute, whatever the program: every weight outside
    the routed experts once (the embedding is ``lanes`` rows), ``experts_hit`` routed experts in
    each expert layer, the recurrent state of the ``lanes`` sequences read and written, and the
    latent rows of the ``kv_tokens`` positions the lanes hold, in every latent layer. FLOPs: two per
    weight and lane, with the experts a token is routed to HERE (a mean: top-k x held / published),
    the rule on the state, plus the latent attention over the positions held. -> {"bytes", "flops"}."""
    p, n = layer_params(c), _count(c)
    width, _, n_held = held(c)
    attn = latent_attention_least(c, kv_tokens * n["M"], itemsize)
    nbytes = (_fixed(c) + n["E"] * experts_hit * p["expert"] + lanes * c["hidden_size"]) * itemsize
    nbytes += 2 * lanes * state_bytes_per_slot(c, itemsize) + attn["bytes"]
    per_token = _per_token_matmul(c, c["num_experts_per_token"] * n_held / width) + c["hidden_size"] * c["vocab_size"]
    flops = 2.0 * lanes * per_token + RULE_FLOPS * lanes * n["K"] * _state_elements(c) + attn["flops"]
    return {"bytes": float(nbytes), "flops": float(flops)}


def prefill_least(c: dict, lengths: list, pairs_local: float, experts_hit: float, itemsize: int = 2) -> dict:
    """What ONE prefill call over prompts of the TRUE ``lengths`` must move and compute, whatever
    the program: every weight outside the routed experts once, ``experts_hit`` routed experts in
    each expert layer once (the held experts that got a pair; a mean over the layers), the prompts'
    embedding rows, and what it hands the caches (latent rows, state). FLOPs at the true lengths:
    two per weight outside the routed experts and token, two per expert weight and (token, expert)
    pair routed HERE (``pairs_local``: a mean over the expert layers), causal attention in the
    expanded form (each query against the positions up to its own: 2 x heads x (key width + value
    width) each), and the rule one position at a time (``RULE_FLOPS`` a state element and token: no
    blocking of the rule can need fewer, and the chunked form needs more). Padding to the bucket and
    to a power of two of prompts is the program's choice and is not in here. -> {"bytes", "flops"}."""
    p, n = layer_params(c), _count(c)
    H, V = c["hidden_size"], c["vocab_size"]
    tokens = float(sum(lengths))
    nbytes = (_fixed(c) + n["E"] * experts_hit * p["expert"] + tokens * H) * itemsize
    nbytes += len(lengths) * state_bytes_per_slot(c, itemsize) + tokens * kv_bytes_per_token(c, itemsize)
    causal = sum(m * (m + 1) / 2.0 for m in lengths)
    widths = c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"]
    flops = (2.0 * tokens * _per_token_matmul(c, 0.0) + 2.0 * len(lengths) * H * V  # the head reads each prompt's last position only
             + 2.0 * n["E"] * pairs_local * p["expert"] + RULE_FLOPS * tokens * n["K"] * _state_elements(c)
             + 2.0 * causal * n["M"] * c["num_attention_heads"] * widths)
    return {"bytes": float(nbytes), "flops": float(flops)}


def train_flops_per_token(c: dict, seq: int) -> float:
    """FLOPs the forward and backward passes require per trained token: 6 per weight that
    multiplies the token (the experts it is routed to here, not all that are held) plus three
    times the causal attention and the rule forward. No recompute. (No cell trains this family:
    neither the no-drop expert layer nor the chunked rule's triangular inverse has a backward pass
    worth timing; PERF.md section 7.)"""
    one = decode_step_least(c, 1, 0, 0)["flops"]
    widths = c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"]
    return 3.0 * (one + seq * _count(c)["M"] * c["num_attention_heads"] * widths)


# --------------------------------------------------------------------------------- the plain reference
def _norm(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _layer_weights(group, i):
    return jax.tree.map(lambda p: jax.lax.dynamic_index_in_dim(p, i, 0, keepdims=False), group)


@functools.partial(jax.jit, static_argnames=("nh", "dk", "eps"))
def _delta_attention(x, group, i, *, nh, dk, eps):
    """One Kimi Delta Attention sub-block on x [T, H], the recurrence one position at a time."""
    w = jax.tree.map(lambda p: p.astype(jnp.float32), _layer_weights(group, i))
    T, D, K, r = x.shape[0], nh * dk, w["conv_w"].shape[0], w["f_b"].shape[0]
    xn = _norm(x, w["norm"], eps)
    mixed, low = xn @ w["in_qkv"], xn @ w["in_low"]  # [q | k | v], [f_a | g_a | b]
    past = jnp.concatenate([jnp.zeros((K - 1, 3 * D), jnp.float32), mixed])
    mixed = jax.nn.silu(sum(past[j:j + T] * w["conv_w"][j] for j in range(K)))
    unit = lambda a: a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    q = unit(mixed[:, :D].reshape(T, nh, dk)) * dk ** -0.5
    k = unit(mixed[:, D:2 * D].reshape(T, nh, dk))
    v = mixed[:, 2 * D:].reshape(T, nh, dk)
    alpha = jnp.exp(-jnp.exp(w["A_log"])[:, None] * jax.nn.softplus((low[:, :r] @ w["f_b"] + w["dt_bias"]).reshape(T, nh, dk)))
    beta = jax.nn.sigmoid(low[:, 2 * r:])

    def one_position(S, inp):
        q_t, k_t, v_t, beta_t, alpha_t = inp  # [nh, dk] x 3, [nh], [nh, dk]: a decay for each key channel
        S = alpha_t[:, :, None] * S
        read = jnp.einsum("hkv,hk->hv", S, k_t)
        S = S + jnp.einsum("hk,hv->hkv", k_t, beta_t[:, None] * (v_t - read))
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(one_position, jnp.zeros((nh, dk, dk), jnp.float32), (q, k, v, beta, alpha))
    gate = jax.nn.sigmoid((low[:, r:2 * r] @ w["g_b"]).reshape(T, nh, dk))
    o = w["gate_norm"] * o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * gate
    return x + o.reshape(T, D) @ w["out_proj"]


@functools.partial(jax.jit, static_argnames=("nh", "nope", "rope", "vd", "eps"))
def _latent_attention(x, group, i, *, nh, nope, rope, vd, eps):
    """One latent attention sub-block on x [T, H], in the expanded form and without position:
    queries straight from the stream, keys and values of every head from the normed latent c_kv, the
    one shared key k_s beside every head's own, causal softmax((q_nope . k_nope + q_s . k_s) /
    sqrt(nope + rope)) v, W_o. Queries in blocks of ``QUERY_BLOCK`` where there are more."""
    w = jax.tree.map(lambda p: p.astype(jnp.float32), _layer_weights(group, i))
    T, r = x.shape[0], w["kv_norm"].shape[0]
    xn = _norm(x, w["norm"], eps)
    q = (xn @ w["w_q"]).reshape(T, nh, nope + rope)
    kva = xn @ w["w_kva"]
    c_kv, k_s = _norm(kva[:, :r], w["kv_norm"], eps), kva[:, r:]
    k = jnp.concatenate([(c_kv @ w["w_kb"]).reshape(T, nh, nope), jnp.broadcast_to(k_s[:, None], (T, nh, rope))], axis=-1)
    v = (c_kv @ w["w_vb"]).reshape(T, nh, vd)

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
    """The dense MLP sub-block on x [T, H]: W_down (SiLU(W_gate x) * W_up x)."""
    w = jax.tree.map(lambda p: p.astype(jnp.float32), _layer_weights(group, i))
    xn = _norm(x, w["norm"], eps)
    return x + (jax.nn.silu(xn @ w["w_gate"]) * (xn @ w["w_up"])) @ w["w_down"]


@functools.partial(jax.jit, static_argnames=("first", "top_k", "norm", "scale", "eps"))
def _experts(x, group, i, *, first, top_k, norm, scale, eps):
    """One expert sub-block on x [T, H]: sigmoid scores over the router's whole width, the top_k
    of score + correction bias, their own scores renormalised to sum to 1 and scaled; every HELD
    expert (W_down (SiLU(W_gate x) * W_up x)) over every token, one expert at a time, weighted by
    what the router gave it (nothing where it was not chosen, and nothing for a choice held
    elsewhere); plus the shared expert, ungated."""
    w = _layer_weights(group, i)
    small = {k: w[k].astype(jnp.float32) for k in ("norm", "router", "router_bias", "shared_gate", "shared_up", "shared_down")}
    xn = _norm(x, small["norm"], eps)
    score = jax.nn.sigmoid(xn @ small["router"])
    _, idx = jax.lax.top_k(score + small["router_bias"], top_k)
    wt = jnp.take_along_axis(score, idx, axis=-1)
    wt = (wt / jnp.sum(wt, axis=-1, keepdims=True) if norm else wt) * scale
    given = jnp.zeros_like(score).at[jnp.arange(x.shape[0])[:, None], idx].set(wt)  # [T, router width]

    def one_expert(e, acc):
        gate, up, down = (w[n][e].astype(jnp.float32) for n in ("w_gate", "w_up", "w_down"))  # each stored [F, H]
        return acc + given[:, first + e, None] * ((jax.nn.silu(xn @ gate.T) * (xn @ up.T)) @ down)

    y = jax.lax.fori_loop(0, w["w_up"].shape[0], one_expert, jnp.zeros_like(x))
    shared = (jax.nn.silu(xn @ small["shared_gate"]) * (xn @ small["shared_up"])) @ small["shared_down"]
    return x + y + shared, idx


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, unembed, *, eps):
    return jax.nn.log_softmax(_norm(x, final_norm.astype(jnp.float32), eps) @ unembed.astype(jnp.float32), axis=-1)


def hidden_states(params: dict, tokens, c: dict, choices: list | None = None):
    """tokens [T] int32 -> the last layer's output [T, H], float32. ``choices``, if a list, gets
    each expert layer's chosen experts [T, top_k] appended (for the router-agreement count)."""
    eps, seen, lin = float(c["rms_norm_eps"]), dict.fromkeys("KMFE", 0), c["linear_attn_config"]
    _, first, _ = held(c)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(tokens, jnp.int32), axis=0).astype(jnp.float32)
        for mixer, mlp in kinds(c):
            i, j = seen[mixer], seen[mlp]
            seen[mixer], seen[mlp] = i + 1, j + 1
            if mixer == "K":
                x = _delta_attention(x, params["kda"], i, nh=lin["num_heads"], dk=lin["head_dim"], eps=eps)
            else:
                x = _latent_attention(x, params["mla"], i, nh=c["num_attention_heads"], nope=c["qk_nope_head_dim"],
                                      rope=c["qk_rope_head_dim"], vd=c["v_head_dim"], eps=eps)
            if mlp == "F":
                x = _dense(x, params["ffn"], j, eps=eps)
            else:
                x, idx = _experts(x, params["moe"], j, first=first, top_k=c["num_experts_per_token"], norm=bool(c["moe_renormalize"]),
                                  scale=float(c["routed_scaling_factor"]), eps=eps)
                if choices is not None:
                    choices.append(idx)
    return x


def reference_logprobs(params: dict, tokens, c: dict, start: int, stop: int):
    """Log-probabilities [stop - start, vocab] that the model gives, after reading
    tokens[: p + 1], to the token at position p + 1, for p in [start, stop)."""
    tokens = list(tokens) + [0] * (padded_length(len(tokens)) - len(tokens))  # few distinct shapes to compile; every layer is causal
    x = hidden_states(params, tokens, c)[start:stop]
    with jax.default_matmul_precision("highest"):
        return _head(x, params["final_norm"], params["unembed"], eps=float(c["rms_norm_eps"]))
