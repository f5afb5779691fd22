"""The SmallThinker block (``model_name`` ``smallthinker_*``): every published decoder layer is
two residual sub-blocks over ``N(x) = w * x / sqrt(mean(x²) + eps)``: attention on ``h = N_1(x)``,
then routed ReGLU experts on ``u = N_2(x')`` with NO shared expert, whose ROUTING was decided on
``h``, before attention; then a final ``N`` and an untied head. Layer ``l`` is a WINDOW layer where
``sliding_window_layout[l]`` is 1 (and ``rope_layout[l]`` with it: the two agree in every published
layer) and a GLOBAL layer where it is 0. The program's side is ``ray_tpu.models.smallthinker``; the
plain reference below is written from the catalog row's ``config`` and the equations of ISSUE 49
(PERF.md section 4 repeats them), not from that file: one sequence, float32 at ``highest``
precision, no cache, no ring, no kernel; a [queries, T] mask for the window, a block of queries at
a time; every expert over every token one expert at a time; one layer's (one expert's) weights cast
at a time.

Attention, 28 query heads over 4 key-value heads (7 a key-value head), no bias, no query-key norm:
``q = h W_q``, ``k = h W_k``, ``v = h W_v``; a window layer rotates q and k (rotate-half over all
128 dimensions of a head, theta 1,500,000) and query i reads the keys j with ``i - W < j <= i``
(W = 4,096 keys, its own among them); a global layer rotates NOTHING and reads every ``j <= i``.
Scores over ``sqrt(128)``, softmax in float32, ``x' = x + W_o concat(heads)``.

The router, on ``h``: ``logits = h W_r`` in float32, the top k LOGITS, ``p = softmax`` over those
k (``moe_primary_router_apply_softmax``): the published order, and not the program's (which takes
a softmax over all experts and renormalises the chosen k: the same numbers, another way). Experts:
``y = sum_m p_m W_down[e_m] (relu(W_gate[e_m] u) * W_up[e_m] u)``; ``x'' = x' + y``.

Sizes come from the configuration file's keys, never from the program's config object. The
weights are the pytree the program serves (``embed``, ``unembed``, ``final_norm``, and ``attn`` /
``swa`` / ``moe`` stacked by layer kind: a global layer's attention and router under ``attn``, a
window layer's under ``swa``, every layer's experts under ``moe``, an expert's matrices [F, H]).

Departures from the published model, each of which program and reference share (``assumed`` in the
configuration file): weights random from a seed, the routers and the embedding table anchored
(``init_router_anchor``); rotate-half pairing; no second level of experts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.models.smallthinker import SmallThinkerConfig, init_params, loss_fn, param_logical_axes  # noqa: F401 - the family's names

# sizes of the CPU rehearsal (--rehearse): wiring only, never a measurement; G W W W twice, the cell's own shape
REHEARSAL_SIZES = {
    "hidden_size": 64, "num_hidden_layers": 8, "vocab_size": 512, "num_attention_heads": 6, "num_key_value_heads": 2, "head_dim": 16,
    "sliding_window_size": 16, "sliding_window_layout": [0, 1, 1, 1] * 2, "rope_layout": [0, 1, 1, 1] * 2,
    "moe_num_primary_experts": 8, "moe_num_active_primary_experts": 2, "moe_ffn_hidden_size": 32,
    "init_router_anchor": 0.0, "reduced_from": {"num_hidden_layers": 8},
}

# the reference pads a sequence to the first of these lengths that holds it (a multiple of the last
# beyond that): every layer is causal, and every distinct length compiles the layer functions anew.
# Every prompt of the cell with its answer then has ONE length, the cell's horizon
PAD_TO = (256, 12288)
# queries the reference's attention takes at once: 28 heads x 256 x 12,288 float32 scores are 352 MB
QUERY_BLOCK = 256
HEAD_BLOCKS_FROM = 65536  # the least vocabulary whose head goes in column blocks


def padded_length(n: int) -> int:
    return next((p for p in PAD_TO if p >= n), -(-n // PAD_TO[-1]) * PAD_TO[-1])


def published_depth(c: dict) -> int:
    return int((c.get("reduced_from") or {}).get("num_hidden_layers", c["num_hidden_layers"]))


def kinds(c: dict) -> list[str]:
    """``W`` (window, rotated) or ``G`` (global, no positions) for every layer held, in order."""
    if list(c["sliding_window_layout"]) != list(c["rope_layout"]) or len(c["rope_layout"]) != c["num_hidden_layers"]:
        raise ValueError("sliding_window_layout and rope_layout name every layer held and agree, as published")
    return ["W" if w else "G" for w in c["sliding_window_layout"]]


# ------------------------------------------------------------------------------ the program's side
def program_config(c: dict, max_seq_len: int, **extra) -> SmallThinkerConfig:
    """The program's ``SmallThinkerConfig`` for a configuration file's published keys."""
    if not c["moe_primary_router_apply_softmax"] or not c["norm_topk_prob"] or c["tie_word_embeddings"] or c.get("rope_scaling"):
        raise ValueError("this family's router takes a softmax over its top k logits, its head is untied and its rotation unscaled")
    kinds(c)
    return SmallThinkerConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"], num_hidden_layers=c["num_hidden_layers"],
        sliding_window_layout=tuple(c["sliding_window_layout"]), rope_layout=tuple(c["rope_layout"]),
        sliding_window_size=c["sliding_window_size"], num_heads=c["num_attention_heads"], num_kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], rope_theta=float(c["rope_theta"]), n_routed_experts=c["moe_num_primary_experts"],
        num_experts_per_tok=c["moe_num_active_primary_experts"], moe_intermediate_size=c["moe_ffn_hidden_size"],
        rms_eps=float(c["rms_norm_eps"]), max_seq_len=max_seq_len,
        # the initialisation's 1/sqrt(N) on the projections back onto the stream: N counts the PUBLISHED sub-blocks
        residual_rescale_layers=2 * published_depth(c), router_anchor=float(c.get("init_router_anchor", 0.0)),
        dtype={"bfloat16": "bfloat16", "float32": "float32"}[c.get("torch_dtype", "bfloat16")], **extra)


def rehearsal(c: dict) -> dict:
    return {**c, **REHEARSAL_SIZES, "torch_dtype": "float32"}


def kernels_expected(c: dict) -> dict:
    """Every attention layer runs the flash kernel over a sequence: a Pallas kernel lowers to a ``tpu_custom_call``."""
    return {"flash kernel": "tpu_custom_call"}


# ----------------------------------------------------------------------------- operations, from shapes
def layer_params(c: dict) -> dict:
    """Parameters by part: one layer's attention (``A``: q, k, v, o), what it holds outside its
    attention and its experts (``rest``: the router and the two norms), one ``expert``, embedding
    plus head."""
    H, q, kv = c["hidden_size"], c["num_attention_heads"] * c["head_dim"], c["num_key_value_heads"] * c["head_dim"]
    return {"A": 2 * H * q + 2 * H * kv, "rest": H * c["moe_num_primary_experts"] + 2 * H,
            "expert": 3 * H * c["moe_ffn_hidden_size"], "embed_and_head": 2 * c["vocab_size"] * H, "final_norm": H}


def parameters_held(c: dict) -> int:
    p = layer_params(c)
    return c["num_hidden_layers"] * (p["A"] + p["rest"] + c["moe_num_primary_experts"] * p["expert"]) + p["embed_and_head"] + p["final_norm"]


def kv_bytes_per_token(c: dict, kind: str | None = None, itemsize: int = 2) -> int:
    """What one position takes in the cache while it is held: a key and a value by head in every
    layer of ``kind`` (``G``: held for the sequence's life; ``W``: for the next
    ``sliding_window_size`` positions, in a ring of that many rows), or in all layers."""
    layers = len(kinds(c)) if kind is None else kinds(c).count(kind)
    return layers * 2 * c["num_key_value_heads"] * c["head_dim"] * itemsize


def cache_bytes(c: dict, slots: int, max_seq_len: int, itemsize: int = 2) -> int:
    """The slot cache whole: every position of the global layers, a ring of the window's rows in the window layers."""
    ring = min(c["sliding_window_size"], max_seq_len)
    return slots * (max_seq_len * kv_bytes_per_token(c, "G", itemsize) + ring * kv_bytes_per_token(c, "W", itemsize))


def window_pairs(c: dict, n: int) -> int:
    """(query, key) pairs inside the window over a sequence of ``n`` positions, one layer: sum over i of min(i + 1, W)."""
    W = min(c["sliding_window_size"], n)
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


def _per_token_matmul(c: dict, experts_a_token: float) -> float:
    """Multiply-adds per token in the whole stack, without the head: every matrix (norm weights
    multiply nothing), with ``experts_a_token`` routed experts in each layer."""
    p = layer_params(c)
    return c["num_hidden_layers"] * (p["A"] + p["rest"] - 2 * c["hidden_size"] + experts_a_token * p["expert"])


def _fixed(c: dict) -> int:
    """Every weight outside the routed experts, the head and the final norm (not the embedding table)."""
    p = layer_params(c)
    return c["num_hidden_layers"] * (p["A"] + p["rest"]) + c["hidden_size"] * c["vocab_size"] + c["hidden_size"]


def attention_pairs(c: dict, n: int) -> float:
    """(query, key) pairs all layers read over a sequence of ``n`` positions: causal in a global layer, the window's in a window layer."""
    ks = kinds(c)
    return ks.count("G") * n * (n + 1) / 2.0 + ks.count("W") * float(window_pairs(c, n))


def decode_step_least(c: dict, lanes: float, experts_hit: float, kv_tokens: float, itemsize: int = 2) -> dict:
    """What ONE decode step must move and compute, whatever the program: every weight outside the
    routed experts once (the embedding is ``lanes`` rows), ``experts_hit`` routed experts in each
    layer, and the keys and values of the ``kv_tokens`` positions the lanes hold: all of them in a
    global layer, at most the window's a lane in a window layer (taken as ALL here where a lane's
    share is not known: the caller that knows passes the rows it read). -> {"bytes", "flops"}."""
    p, ks = layer_params(c), kinds(c)
    per_row = 2 * c["num_key_value_heads"] * c["head_dim"]
    nbytes = (_fixed(c) + len(ks) * experts_hit * p["expert"] + lanes * c["hidden_size"] + kv_tokens * len(ks) * per_row) * itemsize
    per_token = _per_token_matmul(c, c["moe_num_active_primary_experts"]) + c["hidden_size"] * c["vocab_size"]
    return {"bytes": float(nbytes), "flops": float(2.0 * lanes * per_token + kv_tokens * len(ks) * 4 * c["num_attention_heads"] * c["head_dim"])}


def prefill_least(c: dict, lengths: list, pairs_local: float, experts_hit: float, itemsize: int = 2) -> dict:
    """What ONE prefill call over prompts of the TRUE ``lengths`` must move and compute, whatever
    the program: every weight outside the routed experts once, ``experts_hit`` routed experts in
    each layer once (a mean over the layers), the prompts' embedding rows, and what it hands the
    cache (every position's keys and values in a global layer, the last ``sliding_window_size`` in
    a window layer). FLOPs at the true lengths: two per weight outside the routed experts and
    token, two per expert weight and (token, expert) pair (``pairs_local``: a mean over the
    layers), and attention over the pairs the mathematics needs (causal in a global layer, inside
    the window in a window layer: 2 x 2 x head_dim in every query head). Padding to the bucket and
    to a power of two of prompts is the program's choice and is not in here. -> {"bytes", "flops"}."""
    p, ks = layer_params(c), kinds(c)
    H, V, W = c["hidden_size"], c["vocab_size"], c["sliding_window_size"]
    tokens = float(sum(lengths))
    kept = tokens * kv_bytes_per_token(c, "G", itemsize) + sum(min(n, W) for n in lengths) * kv_bytes_per_token(c, "W", itemsize)
    nbytes = (_fixed(c) + len(ks) * experts_hit * p["expert"] + tokens * H) * itemsize + kept
    flops = (2.0 * tokens * _per_token_matmul(c, 0.0) + 2.0 * len(lengths) * H * V  # the head reads each prompt's last position only
             + 2.0 * len(ks) * pairs_local * p["expert"]
             + 4.0 * c["num_attention_heads"] * c["head_dim"] * sum(attention_pairs(c, int(n)) for n in lengths))
    return {"bytes": float(nbytes), "flops": float(flops)}


def train_flops_per_token(c: dict, seq: int) -> float:
    """FLOPs the forward and backward passes require per trained token: 6 per weight that
    multiplies the token (the experts it is routed to, not all that are held) plus three times the
    attention forward over the pairs the mathematics needs. No recompute. (No cell trains this
    family: the no-drop expert layer and the window kernel have no backward pass.)"""
    one = 2.0 * (_per_token_matmul(c, c["moe_num_active_primary_experts"]) + c["hidden_size"] * c["vocab_size"])
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


@functools.partial(jax.jit, static_argnames=("nh", "kv", "hd", "eps", "theta", "window", "top_k"))
def _attention(x, group, i, *, nh, kv, hd, eps, theta, window, top_k):
    """One attention sub-block on x [T, H] -> (x', the layer's routing: expert ids [T, top_k] and
    weights [T, top_k], decided on this sub-block's normed input). ``window``: 0 for a global layer
    (no rotation, every earlier key), else the window's size (rotation, the last ``window`` keys)."""
    w = jax.tree.map(lambda p: p.astype(jnp.float32), _layer_weights(group, i))
    T, rep = x.shape[0], nh // kv
    h = _norm(x, w["norm"], eps)
    top, idx = jax.lax.top_k(h @ w["router"], top_k)  # the top k LOGITS ...
    p = jax.nn.softmax(top, axis=-1)  # ... and a softmax over those k alone
    q, k, v = (h @ w["wq"]).reshape(T, nh, hd), (h @ w["wk"]).reshape(T, kv, hd), (h @ w["wv"]).reshape(T, kv, hd)
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
    return x + o.reshape(T, nh * hd) @ w["wo"], idx, p


@functools.partial(jax.jit, static_argnames=("eps",))
def _experts(x, group, i, idx, p, *, eps):
    """One expert sub-block on x [T, H] with the routing (idx, p) made before attention: every
    expert (W_down (relu(W_gate u) * W_up u)) over every token, one expert at a time, weighted by
    what the router gave it (nothing where it was not chosen). No shared expert."""
    w = _layer_weights(group, i)
    u = _norm(x, w["norm"].astype(jnp.float32), eps)
    E = w["w_up"].shape[0]
    given = jnp.zeros((x.shape[0], E), jnp.float32).at[jnp.arange(x.shape[0])[:, None], idx].set(p)  # [T, experts]

    def one_expert(e, acc):
        gate, up, down = (w[n][e].astype(jnp.float32) for n in ("w_gate", "w_up", "w_down"))  # each stored [F, H]
        return acc + given[:, e, None] * ((jax.nn.relu(u @ gate.T) * (u @ up.T)) @ down)

    return x + jax.lax.fori_loop(0, E, one_expert, jnp.zeros_like(x))


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, unembed, *, eps):
    """log softmax(N(x) W_head); the head's columns in blocks where the vocabulary is large (the
    published head in float32 is 1.56 GB)."""
    xn = _norm(x, final_norm.astype(jnp.float32), eps)
    H, V = unembed.shape
    blocks = 8 if V >= HEAD_BLOCKS_FROM and V % 8 == 0 else 1
    cols = jnp.moveaxis(unembed.reshape(H, blocks, V // blocks), 1, 0)
    logits = jax.lax.map(lambda u: xn @ u.astype(jnp.float32), cols)  # [blocks, n, V / blocks]
    return jax.nn.log_softmax(jnp.moveaxis(logits, 0, 1).reshape(x.shape[0], V), axis=-1)


def hidden_states(params: dict, tokens, c: dict, choices: list | None = None):
    """tokens [T] int32 -> the last layer's output [T, H], float32. ``choices``, if a list, gets
    each layer's chosen experts [T, top_k] appended (for a router-agreement count)."""
    eps, seen = float(c["rms_norm_eps"]), {"G": 0, "W": 0}
    heads = dict(nh=c["num_attention_heads"], kv=c["num_key_value_heads"], hd=c["head_dim"], eps=eps, theta=float(c["rope_theta"]),
                 top_k=c["moe_num_active_primary_experts"])
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(tokens, jnp.int32), axis=0).astype(jnp.float32)
        for layer, kind in enumerate(kinds(c)):
            i, seen[kind] = seen[kind], seen[kind] + 1
            x, idx, p = _attention(x, params["swa" if kind == "W" else "attn"], i, window=c["sliding_window_size"] if kind == "W" else 0, **heads)
            x = _experts(x, params["moe"], layer, idx, p, eps=eps)
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
