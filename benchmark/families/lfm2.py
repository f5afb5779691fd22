"""The LFM2 block with experts (``model_type`` ``lfm2_moe``): every published decoder layer is two
residual sub-blocks over ``N(x) = w * x / sqrt(mean(x²) + norm_eps)``, no bias anywhere:
``x' = x + mixer(N_op(x))``, ``x'' = x' + ffn(N_ffn(x'))``; after the last layer one more ``N`` (the
published ``embedding_norm``), then the head, which is the embedding table (tied). The program's
side is ``ray_tpu.models.lfm2``; the plain reference below is written from the catalog row's
``config`` and the equations of ISSUE 53 (PERF.md section 4 repeats them), not from that file: one
sequence, float32 at ``highest`` precision, no cache, no window kept, no kernel; the convolution as
``conv_L_cache`` shifted products; attention as a masked softmax, a block of queries at a time;
every expert over every token one expert at a time; one layer's (one expert's) weights cast at a
time.

The mixer of layer l, by ``layer_types[l]``:

- ``conv``: ``[B, C, u] = h W_in`` (H -> 3H, split in that order); ``z_t = sum_j w_j (B * u)_{t-j}``
  by channel (depthwise, causal, ``conv_L_cache`` taps, no activation); ``y = (C * z) W_out``.
- ``full_attention``: ``num_attention_heads`` query heads over ``num_key_value_heads`` key-value
  heads, head width ``hidden_size / num_attention_heads`` (64); ``q = h W_q``, ``k = h W_k``,
  ``v = h W_v``; ``N`` over the 64 of every query head and every key head (one weight vector for
  the query heads, one for the key heads); rotate q and k (rotate-half over all 64, theta
  ``rope_parameters.rope_theta``, default type); causal softmax scaled by 64^-1/2; ``W_o``.

The ffn of layer l: for ``l < num_dense_layers`` a SwiGLU ``intermediate_size`` wide,
``W_2 (silu(W_1 h) * W_3 h)``; else ``num_experts`` SwiGLU experts ``moe_intermediate_size`` wide and
no shared one: ``s = sigmoid(h W_r)`` in float32; the top ``num_experts_per_tok`` of ``s + b``
(``use_expert_bias``: ``b`` one float32 an expert, which chooses and does not weigh); weights
``s_i / (sum of the chosen + 1e-6)`` (``norm_topk_prob``) times ``routed_scaling_factor``.

Sizes come from the configuration file's keys, never from the program's config object. The weights
are the pytree the program serves (``embed``, ``final_norm``, NO ``unembed``, and ``shortconv`` /
``attn`` / ``ffn`` / ``moe`` stacked by layer kind; an expert's matrices [F, H]; a convolution's taps
``conv_w`` [taps, H] oldest input first, as ``torch.nn.Conv1d`` stores a causal kernel: ``w_j`` above
is ``conv_w[taps - 1 - j]``).

Departures from the published model, each of which program and reference share (``assumed`` in the
configuration file): weights random from a seed; the routers and the embedding table anchored
(``init_router_anchor``, to one expert MORE than a token takes) and the selection bias all distinct
(``init_router_bias_range``), so that the bias chooses among a token's own experts; the final norm's
weight ``+-c`` with random signs, so that a tied head does not give every token its own id back;
rotate-half pairing. What the program does otherwise and the reference does not: the products
``B * u`` rounded to the weights' dtype (the window keeps them so); the router's normalisation
raised by 1e-6 in both.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.models.lfm2 import Lfm2Config, init_params, loss_fn, param_logical_axes  # noqa: F401 - the family's names

# sizes of the CPU rehearsal (--rehearse): wiring only, never a measurement; c c A c c c A c c c behind two dense layers, the cell's own shape
REHEARSAL_SIZES = {
    "hidden_size": 64, "num_hidden_layers": 10, "vocab_size": 512, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
    "layer_types": ["conv", "conv", "full_attention", "conv"] * 2 + ["conv", "conv"], "num_dense_layers": 2, "intermediate_size": 96,
    "num_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "init_router_anchor": 0.0, "init_router_bias_range": 0.0, "reduced_from": {"num_hidden_layers": 10},
}

# the reference pads a sequence to the first of these lengths that holds it (a multiple of the last
# beyond that): every layer is causal, and every distinct length compiles the layer functions anew.
# Every prompt of the cell with its answer then has ONE length, the cell's horizon
PAD_TO = (256, 12288)
# queries the reference's attention takes at once (32 heads x 256 x 12,288 float32 scores are 403 MB),
# rows its dense layer takes at once, and the least vocabulary whose head goes in column blocks
QUERY_BLOCK, ROW_BLOCK, HEAD_BLOCKS_FROM = 256, 2048, 65536
MIXER = {"conv": "shortconv", "full_attention": "attn"}  # a published layer type -> where the program keeps its weights


def padded_length(n: int) -> int:
    return next((p for p in PAD_TO if p >= n), -(-n // PAD_TO[-1]) * PAD_TO[-1])


def published_depth(c: dict) -> int:
    return int((c.get("reduced_from") or {}).get("num_hidden_layers", c["num_hidden_layers"]))


def head_dim(c: dict) -> int:
    return int(c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"])


def kinds(c: dict) -> list[tuple]:
    """(mixer ``conv`` | ``full_attention``, ffn ``dense`` | ``experts``) for every layer held, in order."""
    if len(c["layer_types"]) != c["num_hidden_layers"] or set(c["layer_types"]) - set(MIXER):
        raise ValueError("layer_types names every layer held: conv or full_attention")
    return [(t, "dense" if l < c["num_dense_layers"] else "experts") for l, t in enumerate(c["layer_types"])]


def count(c: dict, what: str) -> int:
    return sum(what in pair for pair in kinds(c))


# ------------------------------------------------------------------------------ the program's side
def program_config(c: dict, max_seq_len: int, **extra) -> Lfm2Config:
    """The program's ``Lfm2Config`` for a configuration file's published keys."""
    if c["conv_bias"] or not c["norm_topk_prob"] or (c.get("rope_parameters") or {}).get("rope_type", "default") != "default":
        raise ValueError("this family's convolution has no bias, its router normalises the chosen scores and its rotation is unscaled")
    kinds(c)
    return Lfm2Config(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"], num_hidden_layers=c["num_hidden_layers"], layer_types=tuple(c["layer_types"]),
        num_dense_layers=c["num_dense_layers"], intermediate_size=c["intermediate_size"], conv_L_cache=c["conv_L_cache"],
        num_heads=c["num_attention_heads"], num_kv_heads=c["num_key_value_heads"], head_dim=head_dim(c),
        rope_theta=float(c["rope_parameters"]["rope_theta"]), n_routed_experts=c["num_experts"], num_experts_per_tok=c["num_experts_per_tok"],
        moe_intermediate_size=c["moe_intermediate_size"], use_expert_bias=bool(c["use_expert_bias"]), norm_topk_prob=bool(c["norm_topk_prob"]),
        routed_scaling_factor=float(c["routed_scaling_factor"]), rms_eps=float(c["norm_eps"]), max_seq_len=max_seq_len,
        # the initialisation's 1/sqrt(N) on the projections back onto the stream: N counts the PUBLISHED sub-blocks
        residual_rescale_layers=2 * published_depth(c), router_anchor=float(c.get("init_router_anchor", 0.0)),
        router_bias_range=float(c.get("init_router_bias_range", 0.0)),
        dtype={"bfloat16": "bfloat16", "float32": "float32"}[c.get("torch_dtype", "bfloat16")], **extra)


def rehearsal(c: dict) -> dict:
    return {**c, **REHEARSAL_SIZES, "torch_dtype": "float32"}


def kernels_expected(c: dict) -> dict:
    """Every attention layer runs the flash kernel over a sequence: a Pallas kernel lowers to a ``tpu_custom_call``."""
    return {"flash kernel": "tpu_custom_call"}


# ----------------------------------------------------------------------------- operations, from shapes
def layer_params(c: dict) -> dict:
    """Parameters by part: one ``conv`` mixer (in, taps, out), one ``attention`` mixer (q, k, v, o and
    the two head norms), one ``dense`` ffn, one ``expert``, what an expert layer holds beside its
    experts (``router``: the router and the selection bias), a sub-block's ``norm``, the embedding
    (the head is the same table) and the final norm."""
    H, hd = c["hidden_size"], head_dim(c)
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    return {"conv": 4 * H * H + c["conv_L_cache"] * H, "attention": 2 * H * q + 2 * H * kv + 2 * hd, "dense": 3 * H * c["intermediate_size"],
            "expert": 3 * H * c["moe_intermediate_size"], "router": H * c["num_experts"] + c["num_experts"] * bool(c["use_expert_bias"]),
            "norm": H, "embed": c["vocab_size"] * H, "final_norm": H}


def parameters_held(c: dict) -> int:
    return _fixed(c) + count(c, "experts") * c["num_experts"] * layer_params(c)["expert"]


def parameters_published(c: dict) -> int:
    """The same count at the published depth: the pattern ``c c A c`` repeated, ``num_dense_layers`` dense layers first."""
    L = published_depth(c)
    whole = {**c, "num_hidden_layers": L, "layer_types": [["conv", "conv", "full_attention", "conv"][l % 4] for l in range(L)]}
    return parameters_held(whole)


def kv_bytes_per_token(c: dict, itemsize: int = 2) -> int:
    """What one position takes in the cache: a key and a value by head in every attention layer, nothing in a convolution layer."""
    return count(c, "full_attention") * 2 * c["num_key_value_heads"] * head_dim(c) * itemsize


def state_bytes_per_slot(c: dict, itemsize: int = 2) -> int:
    """What one sequence keeps beside its positions: the window of every convolution layer, its last ``conv_L_cache - 1`` inputs."""
    return count(c, "conv") * (c["conv_L_cache"] - 1) * c["hidden_size"] * itemsize


def cache_bytes(c: dict, slots: int, max_seq_len: int, itemsize: int = 2) -> int:
    return slots * (max_seq_len * kv_bytes_per_token(c, itemsize) + state_bytes_per_slot(c, itemsize))


def _fixed(c: dict) -> int:
    """Every weight outside the routed experts that a step reads whole: mixers, dense layers, routers, norms, the head (= the table) and the final norm."""
    p = layer_params(c)
    return (count(c, "conv") * p["conv"] + count(c, "full_attention") * p["attention"] + count(c, "dense") * p["dense"]
            + count(c, "experts") * p["router"] + 2 * len(kinds(c)) * p["norm"] + p["embed"] + p["final_norm"])


def _per_token_matmul(c: dict, experts_a_token: float) -> float:
    """Multiply-adds per token in the whole stack, without the head: every matrix (norm weights,
    the taps and the bias multiply nothing worth counting), ``experts_a_token`` routed experts in each expert layer."""
    p, H = layer_params(c), c["hidden_size"]
    return (count(c, "conv") * 4 * H * H + count(c, "full_attention") * (p["attention"] - 2 * head_dim(c)) + count(c, "dense") * p["dense"]
            + count(c, "experts") * (H * c["num_experts"] + experts_a_token * p["expert"]))


def causal_pairs(n: float) -> float:
    return n * (n + 1) / 2.0


def flash64_least(c: dict, pairs: float, tokens: float, itemsize: int = 2) -> dict:
    """What the attention layers' attention over a sequence must move and compute at the heads'
    TRUE width, whatever runs it, for ``pairs`` causal (query, key) pairs summed over the attention
    layers and ``tokens`` positions in each of them: q read and the output written once, k and v
    read once; a score and a weighted sum in every query head a pair (2 x 2 x head_dim). A kernel
    that pads a head to the 128 lanes moves and multiplies twice that, and reads low here."""
    nh, kv, hd = c["num_attention_heads"], c["num_key_value_heads"], head_dim(c)
    return {"bytes": float(count(c, "full_attention") * tokens * (2 * nh + 2 * kv) * hd * itemsize), "flops": float(pairs * 4 * nh * hd)}


def narrow_decode_least(c: dict, rows: float, itemsize: int = 2) -> dict:
    """What a decode step's attention must move for ``rows`` positions held (a lane's position + 1
    in a layer, summed over lanes and attention layers): a key and a value by head each, once, at
    the heads' TRUE width. FLOPs: every query head's score and weighted sum over them."""
    kv, hd = c["num_key_value_heads"], head_dim(c)
    return {"bytes": float(rows * 2 * kv * hd * itemsize), "flops": float(rows * 4 * c["num_attention_heads"] * hd)}


def decode_step_least(c: dict, lanes: float, experts_hit: float, kv_tokens: float, itemsize: int = 2) -> dict:
    """What ONE decode step must move and compute, whatever the program: every weight outside the
    routed experts once (the head IS the embedding table, read whole), ``experts_hit`` routed
    experts in each expert layer, the convolution windows of the ``lanes`` in use read and written,
    and the keys and values of the ``kv_tokens`` positions the lanes hold in every attention
    layer. -> {"bytes", "flops"}."""
    p, A = layer_params(c), count(c, "full_attention")
    window = 2 * lanes * state_bytes_per_slot(c, itemsize)
    nbytes = (_fixed(c) + count(c, "experts") * experts_hit * p["expert"]) * itemsize + window + kv_tokens * kv_bytes_per_token(c, itemsize)
    per_token = _per_token_matmul(c, c["num_experts_per_tok"]) + p["embed"]
    return {"bytes": float(nbytes), "flops": float(2.0 * lanes * per_token + kv_tokens * A * 4 * c["num_attention_heads"] * head_dim(c))}


def prefill_least(c: dict, lengths: list, pairs_local: float, experts_hit: float, itemsize: int = 2) -> dict:
    """What ONE prefill call over prompts of the TRUE ``lengths`` must move and compute, whatever
    the program: every weight outside the routed experts once, ``experts_hit`` routed experts in
    each expert layer once (a mean over the layers), the prompts' embedding rows, and what it hands
    the caches (every position's keys and values in an attention layer, a window a prompt in a
    convolution layer). FLOPs at the true lengths and the heads' true width: two per weight outside
    the routed experts and token, two per expert weight and (token, expert) pair (``pairs_local``: a
    mean over the layers), and causal attention (2 x 2 x head_dim in every query head a pair).
    Padding to the bucket and to a power of two of prompts is the program's choice and is not in
    here. -> {"bytes", "flops"}."""
    p, H, E = layer_params(c), c["hidden_size"], count(c, "experts")
    tokens = float(sum(lengths))
    kept = tokens * kv_bytes_per_token(c, itemsize) + len(lengths) * state_bytes_per_slot(c, itemsize)
    nbytes = (_fixed(c) + E * experts_hit * p["expert"] + tokens * H) * itemsize + kept
    flops = (2.0 * tokens * _per_token_matmul(c, 0.0) + 2.0 * len(lengths) * p["embed"]  # the head reads each prompt's last position only
             + 2.0 * E * pairs_local * p["expert"]
             + 4.0 * c["num_attention_heads"] * head_dim(c) * count(c, "full_attention") * sum(causal_pairs(float(n)) for n in lengths))
    return {"bytes": float(nbytes), "flops": float(flops)}


def train_flops_per_token(c: dict, seq: int) -> float:
    """FLOPs the forward and backward passes require per trained token: 6 per weight that
    multiplies the token (the experts it is routed to, not all that are held) plus three times the
    causal attention forward. No recompute. (No cell trains this family: the no-drop expert layer
    has no backward pass.)"""
    one = 2.0 * (_per_token_matmul(c, c["num_experts_per_tok"]) + layer_params(c)["embed"])
    return 3.0 * (one + 4.0 * c["num_attention_heads"] * head_dim(c) * count(c, "full_attention") * causal_pairs(seq) / seq)


# --------------------------------------------------------------------------------- the plain reference
def _norm(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _layer_weights(group, i):
    return jax.tree.map(lambda p: jax.lax.dynamic_index_in_dim(p, i, 0, keepdims=False), group)


def _rotate(x, theta):
    """Rotate-half RoPE over all of a head's dimensions: x [T, heads, d], positions 0 .. T - 1."""
    T, _, d = x.shape
    angles = jnp.arange(T, dtype=jnp.float32)[:, None] * theta ** (-jnp.arange(d // 2, dtype=jnp.float32) / (d // 2))
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("eps",))
def _conv(x, group, i, *, eps):
    """One convolution sub-block on x [T, H]: gate, the causal depthwise convolution as shifted products, gate, project."""
    w = jax.tree.map(lambda p: p.astype(jnp.float32), _layer_weights(group, i))
    T, K = x.shape[0], w["conv_w"].shape[0]
    b, c, u = jnp.split(_norm(x, w["norm"], eps) @ w["in_proj"], 3, axis=-1)
    bu = b * u
    z = sum(w["conv_w"][K - 1 - j] * jnp.pad(bu, ((j, 0), (0, 0)))[:T] for j in range(K))  # the input j back, zeros before the sequence
    return x + (c * z) @ w["out_proj"]


@functools.partial(jax.jit, static_argnames=("nh", "kv", "hd", "eps", "theta"))
def _attention(x, group, i, *, nh, kv, hd, eps, theta):
    """One attention sub-block on x [T, H]: heads normed and rotated, every query against every earlier key."""
    w = jax.tree.map(lambda p: p.astype(jnp.float32), _layer_weights(group, i))
    T, rep = x.shape[0], nh // kv
    h = _norm(x, w["norm"], eps)
    q = _norm((h @ w["wq"]).reshape(T, nh, hd), w["q_norm"], eps)
    k = _norm((h @ w["wk"]).reshape(T, kv, hd), w["k_norm"], eps)
    v = (h @ w["wv"]).reshape(T, kv, hd)
    q, k = _rotate(q, theta), _rotate(k, theta)
    at = jnp.arange(T)

    def some_queries(qb):
        q_b, first = qb  # [Q, kv, rep, hd], the position of the block's first query
        allowed = at[None, :] <= (first + jnp.arange(q_b.shape[0]))[:, None]
        s = jnp.einsum("qgrh,sgh->qgrs", q_b, k) * hd ** -0.5
        return jnp.einsum("qgrs,sgh->qgrh", jax.nn.softmax(jnp.where(allowed[:, None, None], s, -jnp.inf), axis=-1), v)

    Q = QUERY_BLOCK if T > QUERY_BLOCK and T % QUERY_BLOCK == 0 else T
    o = jax.lax.map(some_queries, (q.reshape(T // Q, Q, kv, rep, hd), jnp.arange(0, T, Q)))
    return x + o.reshape(T, nh * hd) @ w["wo"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense(x, group, i, *, eps):
    """One dense sub-block on x [T, H]: ``W_2 (silu(W_1 h) * W_3 h)``, ``ROW_BLOCK`` rows at a time where there are many."""
    w = jax.tree.map(lambda p: p.astype(jnp.float32), _layer_weights(group, i))
    h = _norm(x, w["norm"], eps)

    def some(h):
        return (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]

    T = x.shape[0]
    if T <= ROW_BLOCK or T % ROW_BLOCK:
        return x + some(h)
    return x + jax.lax.map(some, h.reshape(T // ROW_BLOCK, ROW_BLOCK, -1)).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "biased", "scale"))
def _experts(x, group, i, *, eps, top_k, biased, scale):
    """One expert sub-block on x [T, H] -> (x'', the chosen experts [T, top_k], the gap in ``s + b``
    between the last chosen and the first not chosen [T]): sigmoid scores, the top k of score + bias,
    their own scores normalised; every expert over every token, one expert at a time, weighted by
    what the router gave it (nothing where it was not chosen). No shared expert."""
    w = _layer_weights(group, i)
    h = _norm(x, w["norm"].astype(jnp.float32), eps)
    s = jax.nn.sigmoid(h @ w["router"].astype(jnp.float32))
    ranked, idx = jax.lax.top_k(s + w["router_bias"] if biased else s, top_k + 1)
    idx = idx[:, :top_k]
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    p = scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-6)
    E = w["w_up"].shape[0]
    given = jnp.zeros((x.shape[0], E), jnp.float32).at[jnp.arange(x.shape[0])[:, None], idx].set(p)  # [T, experts]

    def one_expert(e, acc):
        gate, up, down = (w[n][e].astype(jnp.float32) for n in ("w_gate", "w_up", "w_down"))  # each stored [F, H]
        return acc + given[:, e, None] * ((jax.nn.silu(h @ gate.T) * (h @ up.T)) @ down)

    return x + jax.lax.fori_loop(0, E, one_expert, jnp.zeros_like(x)), idx, ranked[:, top_k - 1] - ranked[:, top_k]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, embed, *, eps):
    """log softmax(N(x) E^T): the head is the embedding table; its rows in blocks where the
    vocabulary is large (the published table in float32 is 0.54 GB)."""
    xn = _norm(x, final_norm.astype(jnp.float32), eps)
    V, H = embed.shape
    blocks = 8 if V >= HEAD_BLOCKS_FROM and V % 8 == 0 else 1
    logits = jax.lax.map(lambda rows: xn @ rows.astype(jnp.float32).T, embed.reshape(blocks, V // blocks, H))  # [blocks, n, V / blocks]
    return jax.nn.log_softmax(jnp.moveaxis(logits, 0, 1).reshape(x.shape[0], V), axis=-1)


def hidden_states(params: dict, tokens, c: dict, choices: list | None = None, gaps: list | None = None):
    """tokens [T] int32 -> the last layer's output [T, H], float32. ``choices`` / ``gaps``, if
    lists, get each expert layer's chosen experts [T, top_k] and its gap [T] in ``s + b`` between the
    last expert chosen and the first one not (for a router-agreement count and the anchor's margin)."""
    eps, seen = float(c["norm_eps"]), {name: 0 for name in ("shortconv", "attn", "ffn", "moe")}
    heads = dict(nh=c["num_attention_heads"], kv=c["num_key_value_heads"], hd=head_dim(c), eps=eps, theta=float(c["rope_parameters"]["rope_theta"]))
    routing = dict(eps=eps, top_k=c["num_experts_per_tok"], biased=bool(c["use_expert_bias"]), scale=float(c["routed_scaling_factor"]))

    def nth(kind):
        seen[kind] += 1
        return seen[kind] - 1

    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(tokens, jnp.int32), axis=0).astype(jnp.float32)
        for mixer, ffn in kinds(c):
            x = _conv(x, params["shortconv"], nth("shortconv"), eps=eps) if mixer == "conv" else _attention(x, params["attn"], nth("attn"), **heads)
            if ffn == "dense":
                x = _dense(x, params["ffn"], nth("ffn"), eps=eps)
                continue
            x, idx, gap = _experts(x, params["moe"], nth("moe"), **routing)
            if choices is not None:
                choices.append(idx)
            if gaps is not None:
                gaps.append(gap)
    return x


def reference_logprobs(params: dict, tokens, c: dict, start: int, stop: int):
    """Log-probabilities [stop - start, vocab] that the model gives, after reading
    tokens[: p + 1], to the token at position p + 1, for p in [start, stop)."""
    tokens = list(tokens) + [0] * (padded_length(len(tokens)) - len(tokens))  # few distinct shapes to compile; every layer is causal
    x = hidden_states(params, tokens, c)[start:stop]
    with jax.default_matmul_precision("highest"):
        return _head(x, params["final_norm"], params["embed"], eps=float(c["norm_eps"]))
