"""The AFMoE block (``model_type`` ``afmoe``; Arcee Trinity): every published decoder layer is two
residual sub-blocks over ``N(x) = w * x / sqrt(mean(x²) + eps)``, each with a norm before it AND a
norm on its output before that joins the stream (a sandwich): ``x' = x + N_2(attention(N_1(x)))``,
``x'' = x' + N_4(feed_forward(N_3(x')))``; the stream starts as ``sqrt(hidden_size) * E[token]``
(``mup_enabled``); then a final ``N`` and an untied head. The program's side is
``ray_tpu.models.afmoe``; the plain reference below is written from the catalog row's ``config``
and the equations of ISSUE 64 (PERF.md section 4 repeats them), not from that file: one sequence,
float32 at ``highest`` precision, no cache, no ring, no kernel; a [queries, T] mask for the window,
a block of queries at a time; every HELD expert over every token one expert at a time; one
layer's (one expert's) weights cast at a time.

Attention, ``num_attention_heads`` query heads over ``num_key_value_heads`` key-value heads of
``head_dim`` (48 over 8 of 128: six a group), no bias: ``q = h W_q``, ``k = h W_k``, ``v = h W_v``,
``g = h W_g`` (as wide as q) on ``h = N_1(x)``; ``q <- N_q(q)``, ``k <- N_k(k)`` over a head's
dimensions with ONE weight for all heads, BEFORE any rotation. Layer ``l`` is a WINDOW layer where
``layer_types[l]`` is ``sliding_attention``: it rotates q and k (rotate-half over all of a head,
``rope_theta``, no scaling) and query i reads the keys j with ``i - W < j <= i`` (W =
``sliding_window`` keys, its own among them); a FULL layer (``full_attention``) rotates NOTHING and
reads every ``j <= i``. Scores over ``sqrt(head_dim)``, softmax in float32,
``a = concat(heads) * sigmoid(g)``, ``x' = x + N_2(a W_o)``.

Feed-forward on ``u = N_3(x')``: layers ``< num_dense_layers`` a SwiGLU at ``intermediate_size``;
the others ``s = sigmoid(u W_r)`` over the router's whole width in float32, the top k of ``s + b``
(``b`` the expert bias; one group: no group limit), weights ``route_scale * s_m / (sum of the chosen
s + 1e-20)`` from ``s`` WITHOUT ``b``, ``f = shared(u) + sum_m p_m expert_m(u)``, every expert and the
shared one a SwiGLU ``moe_intermediate_size`` wide, the shared one ungated.

A configuration of this family may be ONE CHIP'S SHARE of a deployment that splits each layer over
several chips by expert parallelism: ``num_experts`` and ``vocab_size`` are then what is held here,
and ``deployment`` says what was published and which part this is. The router keeps its published
width and its experts per token; a token's choice that lives on another chip adds nothing here, in
the program and in the reference alike. Sizes come from the configuration file's keys, never from
the program's config object. The weights are the pytree the program serves (``embed``, ``unembed``,
``final_norm``, and ``swa`` / ``attn`` / ``mlp`` / ``moe`` stacked by layer kind: a window layer's
attention under ``swa``, a full layer's under ``attn``, an expert's matrices [F, H]).

Departures from the published model, each of which program and reference share (``assumed`` in the
configuration file): weights random from a seed, the post-norms' gains depth-scaled, the routers and
the embedding table anchored (``init_router_anchor``), the expert bias drawn small and not zero;
rotate-half pairing.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ray_tpu.models.afmoe import AfmoeConfig, init_params, loss_fn, param_logical_axes  # noqa: F401 - the family's names

# sizes of the CPU rehearsal (--rehearse): wiring only, never a measurement; a dense layer, then W W W G, the cell's own shape
REHEARSAL_SIZES = {
    "hidden_size": 64, "num_hidden_layers": 5, "num_dense_layers": 1, "vocab_size": 512, "num_attention_heads": 6, "num_key_value_heads": 2,
    "head_dim": 16, "sliding_window": 16, "layer_types": ["sliding_attention"] * 4 + ["full_attention"], "intermediate_size": 96,
    "num_experts": 4, "num_experts_per_tok": 2, "moe_intermediate_size": 32, "init_router_anchor": 0.0, "init_router_bias": 0.1,
    "reduced_from": {"num_hidden_layers": 5},
    "deployment": {"chips_per_layer": 2, "experts_published": 8, "experts_held": [0, 4], "vocab_rows_held": [0, 512]},
}

# the reference pads a sequence to the first of these lengths that holds it (a multiple of the last
# beyond that): every layer is causal, and every distinct length compiles the layer functions anew.
# Every prompt of the cell with its answer then has ONE length, the cell's horizon
PAD_TO = (256, 12288)
# queries the reference's attention takes at once: 48 heads x 128 x 12,288 float32 scores are 302 MB
QUERY_BLOCK = 128
# rows the reference's dense layer takes at once: 2,048 x 12,288 float32 hidden values are 101 MB, three times
DENSE_ROWS = 2048
KIND = {"sliding_attention": "W", "full_attention": "G"}
GROUP = {"W": "swa", "G": "attn"}  # where the program's pytree holds a layer's attention


def padded_length(n: int) -> int:
    return next((p for p in PAD_TO if p >= n), -(-n // PAD_TO[-1]) * PAD_TO[-1])


def published_depth(c: dict) -> int:
    return int((c.get("reduced_from") or {}).get("num_hidden_layers", c["num_hidden_layers"]))


def held(c: dict) -> tuple[int, int, int]:
    """(router width, first expert held, experts held). Without a ``deployment`` the chip holds all."""
    dep = c.get("deployment") or {}
    first = int((dep.get("experts_held") or [0])[0])
    return int(dep.get("experts_published", c["num_experts"])), first, int(c["num_experts"])


def kinds(c: dict) -> list[str]:
    """``W`` (window, rotated) or ``G`` (full, no positions) for every layer held, in order."""
    if len(c["layer_types"]) != c["num_hidden_layers"] or set(c["layer_types"]) - set(KIND):
        raise ValueError(f"layer_types names every layer held, each one of {sorted(KIND)}")
    return [KIND[t] for t in c["layer_types"]]


def _expert_layers(c: dict) -> int:
    return c["num_hidden_layers"] - c["num_dense_layers"]


# ------------------------------------------------------------------------------ the program's side
def program_config(c: dict, max_seq_len: int, **extra) -> AfmoeConfig:
    """The program's ``AfmoeConfig`` for a configuration file's published keys."""
    if c.get("score_func", "sigmoid") != "sigmoid" or any(c.get(k, 1) != 1 for k in ("n_group", "topk_group", "num_expert_groups", "num_limited_groups")):
        raise ValueError("this family routes by sigmoid scores over one group of experts")
    if c.get("tie_word_embeddings") or c.get("rope_scaling") or c.get("hidden_act", "silu") != "silu":
        raise ValueError("this family's head is untied, its rotation unscaled and its gate a SiLU")
    kinds(c)
    width, first, n_held = held(c)
    return AfmoeConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"], num_hidden_layers=c["num_hidden_layers"],
        num_dense_layers=c["num_dense_layers"], layer_types=tuple(c["layer_types"]), sliding_window=c["sliding_window"],
        num_heads=c["num_attention_heads"], num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"], rope_theta=float(c["rope_theta"]),
        intermediate_size=c["intermediate_size"], num_experts=width, expert_start=first, num_local_experts=n_held,
        num_experts_per_tok=c["num_experts_per_tok"], moe_intermediate_size=c["moe_intermediate_size"],
        num_shared_experts=c["num_shared_experts"], route_norm=bool(c["route_norm"]), route_scale=float(c["route_scale"]),
        mup_enabled=bool(c["mup_enabled"]), rms_eps=float(c["rms_norm_eps"]), max_seq_len=max_seq_len,
        # the post-norms' depth-scaled gains, 1/sqrt(N): N counts the PUBLISHED sub-blocks
        residual_rescale_layers=2 * published_depth(c), router_anchor=float(c.get("init_router_anchor", 0.0)),
        router_bias_init=float(c.get("init_router_bias", 0.01)),
        dtype={"bfloat16": "bfloat16", "float32": "float32"}[c.get("torch_dtype", "bfloat16")], **extra)


def rehearsal(c: dict) -> dict:
    return {**c, **REHEARSAL_SIZES, "torch_dtype": "float32"}


def kernels_expected(c: dict) -> dict:
    """Every attention layer runs the flash kernel over a sequence: a Pallas kernel lowers to a ``tpu_custom_call``."""
    return {"flash kernel": "tpu_custom_call"}


# ----------------------------------------------------------------------------- operations, from shapes
def layer_params(c: dict) -> dict:
    """Parameters by part: one layer's attention (``A``: q, k, v, the gate, o and the two head
    norms), its four stream norms, a dense layer's SwiGLU (``F``), an expert layer's router with its
    bias and its shared expert (``E_rest``), one routed ``expert``, the held rows of embedding plus head."""
    H, q, kv = c["hidden_size"], c["num_attention_heads"] * c["head_dim"], c["num_key_value_heads"] * c["head_dim"]
    width = held(c)[0]
    return {"A": 3 * H * q + 2 * H * kv + 2 * c["head_dim"], "norms": 4 * H, "F": 3 * H * c["intermediate_size"],
            "E_rest": H * width + width + 3 * H * c["moe_intermediate_size"], "expert": 3 * H * c["moe_intermediate_size"],
            "embed_and_head": 2 * c["vocab_size"] * H, "final_norm": H}


def parameters_held(c: dict) -> int:
    p, dense, routed = layer_params(c), c["num_dense_layers"], _expert_layers(c)
    return (c["num_hidden_layers"] * (p["A"] + p["norms"]) + dense * p["F"] + routed * (p["E_rest"] + c["num_experts"] * p["expert"])
            + p["embed_and_head"] + p["final_norm"])


def kv_bytes_per_token(c: dict, kind: str | None = None, itemsize: int = 2) -> int:
    """What one position takes in the cache while it is held: a key and a value by head in every
    layer of ``kind`` (``G``: held for the sequence's life; ``W``: for the next ``sliding_window``
    positions, in a ring of that many rows), or in all layers."""
    layers = len(kinds(c)) if kind is None else kinds(c).count(kind)
    return layers * 2 * c["num_key_value_heads"] * c["head_dim"] * itemsize


def cache_bytes(c: dict, slots: int, max_seq_len: int, itemsize: int = 2) -> int:
    """The slot cache whole: every position of the full layers, a ring of the window's rows in the window layers."""
    ring = min(c["sliding_window"], max_seq_len)
    return slots * (max_seq_len * kv_bytes_per_token(c, "G", itemsize) + ring * kv_bytes_per_token(c, "W", itemsize))


def window_pairs(c: dict, n: int) -> int:
    """(query, key) pairs inside the window over a sequence of ``n`` positions, one layer: sum over i of min(i + 1, W)."""
    W = min(c["sliding_window"], n)
    return W * (W + 1) // 2 + (n - W) * W


def window_flash_least(c: dict, pairs: float, tokens: float, itemsize: int = 2) -> dict:
    """What the window layers' attention over a sequence must move and compute, whatever runs it,
    for ``pairs`` (query, key) pairs inside the window summed over the window layers and ``tokens``
    positions in each of them: q read and the output written once, k and v read once; a score and a
    weighted sum in every query head a pair (2 x 2 x head_dim). The count is of the pairs the
    MATHEMATICS needs, whatever tiles a kernel visits."""
    nh, kv, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    return {"bytes": float(kinds(c).count("W") * tokens * (2 * nh + 2 * kv) * hd * itemsize), "flops": float(pairs * 4 * nh * hd)}


def window_decode_least(c: dict, rows: float, itemsize: int = 2) -> dict:
    """What a decode step's window attention must move for ``rows`` ring rows (a lane's
    min(position + 1, W) in a layer, summed over lanes and window layers): a key and a value by
    head each, once. FLOPs: every query head's score and weighted sum over them."""
    kv, hd = c["num_key_value_heads"], c["head_dim"]
    return {"bytes": float(rows * 2 * kv * hd * itemsize), "flops": float(rows * 4 * c["num_attention_heads"] * hd)}


def moe_blocks_least(c: dict, experts_hit: float, pairs_local: float, itemsize: int = 2) -> dict:
    """What the routed experts' blocks of ONE expert layer must move and compute for a prefill call,
    whatever runs them (a loop, a kernel): the three matrices of every held expert that got a pair
    (``experts_hit``) read ONCE, each held pair's row read and its output written at the stream's
    width (``pairs_local``), and 2 x 3 x F x H FLOPs a pair. Padding of an expert's run to whole
    blocks is the program's choice and is not in here. -> {"bytes", "flops"}."""
    p, H = layer_params(c), c["hidden_size"]
    return {"bytes": float((experts_hit * p["expert"] + pairs_local * 2 * H) * itemsize), "flops": float(2.0 * pairs_local * p["expert"])}


def _per_token_matmul(c: dict) -> float:
    """Multiply-adds per token in the whole stack outside the routed experts and the head: every
    matrix (norm weights and the bias multiply nothing)."""
    p, H, width = layer_params(c), c["hidden_size"], held(c)[0]
    return (c["num_hidden_layers"] * (p["A"] - 2 * c["head_dim"]) + c["num_dense_layers"] * p["F"]
            + _expert_layers(c) * (H * width + 3 * H * c["moe_intermediate_size"]))


def _fixed(c: dict) -> int:
    """Every weight outside the routed experts, the head and the final norm (not the embedding table)."""
    p = layer_params(c)
    return (c["num_hidden_layers"] * (p["A"] + p["norms"]) + c["num_dense_layers"] * p["F"] + _expert_layers(c) * p["E_rest"]
            + c["hidden_size"] * c["vocab_size"] + c["hidden_size"])


def attention_pairs(c: dict, n: int) -> float:
    """(query, key) pairs all layers read over a sequence of ``n`` positions: causal in a full layer, the window's in a window layer."""
    ks = kinds(c)
    return ks.count("G") * n * (n + 1) / 2.0 + ks.count("W") * float(window_pairs(c, n))


def prefill_least(c: dict, lengths: list, pairs_local: float, experts_hit: float, itemsize: int = 2) -> dict:
    """What ONE prefill call over prompts of the TRUE ``lengths`` must move and compute, whatever
    the program: every weight outside the routed experts once (the gate's projection, the dense
    layer and the shared expert among them), ``experts_hit`` routed experts in each expert layer
    once (a mean over those layers), the prompts' embedding rows, and what it hands the cache (every
    position's keys and values in a full layer, the last ``sliding_window`` in a window layer).
    FLOPs at the true lengths: two per weight outside the routed experts and token, two per expert
    weight and (token, expert) pair held (``pairs_local``: a mean over the expert layers), and
    attention over the pairs the mathematics needs (causal in a full layer, inside the window in a
    window layer: 2 x 2 x head_dim in every query head). Padding to the bucket and to a power of two
    of prompts is the program's choice and is not in here. -> {"bytes", "flops"}."""
    p = layer_params(c)
    H, V, W = c["hidden_size"], c["vocab_size"], c["sliding_window"]
    tokens = float(sum(lengths))
    kept = tokens * kv_bytes_per_token(c, "G", itemsize) + sum(min(n, W) for n in lengths) * kv_bytes_per_token(c, "W", itemsize)
    nbytes = (_fixed(c) + _expert_layers(c) * experts_hit * p["expert"] + tokens * H) * itemsize + kept
    flops = (2.0 * tokens * _per_token_matmul(c) + 2.0 * len(lengths) * H * V  # the head reads each prompt's last position only
             + 2.0 * _expert_layers(c) * pairs_local * p["expert"]
             + 4.0 * c["num_attention_heads"] * c["head_dim"] * sum(attention_pairs(c, int(n)) for n in lengths))
    return {"bytes": float(nbytes), "flops": float(flops)}


def train_flops_per_token(c: dict, seq: int) -> float:
    """FLOPs the forward and backward passes require per trained token: 6 per weight that
    multiplies the token (the experts it is routed to, not all that are held) plus three times the
    attention forward over the pairs the mathematics needs. No recompute. (No cell trains this
    family: the no-drop expert layer and the window kernel have no backward pass.)"""
    routed = _expert_layers(c) * c["num_experts_per_tok"] * layer_params(c)["expert"]
    one = 2.0 * (_per_token_matmul(c) + routed + c["hidden_size"] * c["vocab_size"])
    return 3.0 * (one + 4.0 * c["num_attention_heads"] * c["head_dim"] * attention_pairs(c, seq) / seq)


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


@functools.partial(jax.jit, static_argnames=("nh", "kv", "hd", "eps", "theta", "window"))
def _attention(x, group, i, *, nh, kv, hd, eps, theta, window):
    """One attention sub-block on x [T, H] -> x'. ``window``: 0 for a full layer (no rotation, every
    earlier key), else the window's size (rotation, the last ``window`` keys)."""
    w = jax.tree.map(lambda p: p.astype(jnp.float32), _layer_weights(group, i))
    T, rep = x.shape[0], nh // kv
    h = _norm(x, w["norm"], eps)
    q, k, v = (h @ w["wq"]).reshape(T, nh, hd), (h @ w["wk"]).reshape(T, kv, hd), (h @ w["wv"]).reshape(T, kv, hd)
    q, k = _norm(q, w["q_norm"], eps), _norm(k, w["k_norm"], eps)  # one weight [hd] for all heads, before any rotation
    if window:
        q, k = _rotate(q, theta), _rotate(k, theta)
    at = jnp.arange(T)

    def some_queries(qb):
        q_b, first = qb  # [Q, kv, rep, hd], the position of the block's first query
        t = first + jnp.arange(q_b.shape[0])
        allowed = at[None, :] <= t[:, None]
        if window:
            allowed = allowed & (at[None, :] > t[:, None] - window)
        s = jnp.einsum("qgrh,sgh->qgrs", q_b, k) * hd ** -0.5
        return jnp.einsum("qgrs,sgh->qgrh", jax.nn.softmax(jnp.where(allowed[:, None, None], s, -jnp.inf), axis=-1), v)

    Q = QUERY_BLOCK if T > QUERY_BLOCK and T % QUERY_BLOCK == 0 else T
    o = jax.lax.map(some_queries, (q.reshape(T // Q, Q, kv, rep, hd), jnp.arange(0, T, Q)))
    a = o.reshape(T, nh * hd) * jax.nn.sigmoid(h @ w["wg"])  # query head n reads key-value head n // rep: heads in order
    return x + _norm(a @ w["wo"], w["post_norm"], eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense(x, group, i, *, eps):
    """One dense sub-block on x [T, H]: a SwiGLU at ``intermediate_size``, a block of rows at a time."""
    w = jax.tree.map(lambda p: p.astype(jnp.float32), _layer_weights(group, i))
    u = _norm(x, w["norm"], eps)
    T = u.shape[0]
    R = DENSE_ROWS if T > DENSE_ROWS and T % DENSE_ROWS == 0 else T
    f = jax.lax.map(lambda r: (jax.nn.silu(r @ w["w_gate"]) * (r @ w["w_up"])) @ w["w_down"], u.reshape(T // R, R, -1)).reshape(x.shape)
    return x + _norm(f, w["post_norm"], eps)


@functools.partial(jax.jit, static_argnames=("first", "top_k", "norm", "scale", "eps"))
def _experts(x, group, i, *, first, top_k, norm, scale, eps):
    """One expert sub-block on x [T, H]: sigmoid scores over the router's whole width, the top_k of
    score + bias, their own scores (WITHOUT the bias) renormalised to sum to 1 and scaled; every
    HELD expert (W_down (SiLU(W_gate u) * W_up u)) over every token, one expert at a time, weighted
    by what the router gave it (nothing where it was not chosen, and nothing for a choice held
    elsewhere); plus the shared expert, ungated; the sum normed before it joins the stream.
    -> (x'', the chosen experts [T, top_k], the gap between the top_k-th and the next of score + bias [T])."""
    w = _layer_weights(group, i)
    small = {k: w[k].astype(jnp.float32) for k in ("norm", "post_norm", "router", "router_bias", "shared_gate", "shared_up", "shared_down")}
    u = _norm(x, small["norm"], eps)
    score = jax.nn.sigmoid(u @ small["router"])
    best, idx = jax.lax.top_k(score + small["router_bias"], top_k + 1)
    gap, idx = best[:, top_k - 1] - best[:, top_k], idx[:, :top_k]
    wt = jnp.take_along_axis(score, idx, axis=-1)
    wt = (wt / (jnp.sum(wt, axis=-1, keepdims=True) + 1e-20) if norm else wt) * scale
    given = jnp.zeros_like(score).at[jnp.arange(x.shape[0])[:, None], idx].set(wt)  # [T, router width]

    def one_expert(e, acc):
        gate, up, down = (w[n][e].astype(jnp.float32) for n in ("w_gate", "w_up", "w_down"))  # each stored [F, H]
        return acc + given[:, first + e, None] * ((jax.nn.silu(u @ gate.T) * (u @ up.T)) @ down)

    f = jax.lax.fori_loop(0, w["w_up"].shape[0], one_expert, jnp.zeros_like(x))
    f = f + (jax.nn.silu(u @ small["shared_gate"]) * (u @ small["shared_up"])) @ small["shared_down"]
    return x + _norm(f, small["post_norm"], eps), idx, gap


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, unembed, *, eps):
    return jax.nn.log_softmax(_norm(x, final_norm.astype(jnp.float32), eps) @ unembed.astype(jnp.float32), axis=-1)


def hidden_states(params: dict, tokens, c: dict, choices: list | None = None, gaps: list | None = None):
    """tokens [T] int32 -> the last layer's output [T, H], float32. ``choices``, if a list, gets
    each expert layer's chosen experts [T, top_k] appended (for a router-agreement count), ``gaps``
    the gap between its last chosen and its first unchosen ``s + b`` [T]."""
    eps, seen = float(c["rms_norm_eps"]), {"W": 0, "G": 0}
    heads = dict(nh=c["num_attention_heads"], kv=c["num_key_value_heads"], hd=c["head_dim"], eps=eps, theta=float(c["rope_theta"]))
    first = held(c)[1]
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(tokens, jnp.int32), axis=0).astype(jnp.float32)
        if c["mup_enabled"]:
            x = x * math.sqrt(c["hidden_size"])
        for layer, kind in enumerate(kinds(c)):
            i, seen[kind] = seen[kind], seen[kind] + 1
            x = _attention(x, params[GROUP[kind]], i, window=c["sliding_window"] if kind == "W" else 0, **heads)
            if layer < c["num_dense_layers"]:
                x = _dense(x, params["mlp"], layer, eps=eps)
                continue
            x, idx, gap = _experts(x, params["moe"], layer - c["num_dense_layers"], first=first, top_k=c["num_experts_per_tok"],
                                   norm=bool(c["route_norm"]), scale=float(c["route_scale"]), eps=eps)
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
        return _head(x, params["final_norm"], params["unembed"], eps=float(c["rms_norm_eps"]))
