"""The Jamba block (``model_type`` ``jamba``), as its dense members publish it (AI21-Jamba2-3B): every
decoder layer is two residual sub-blocks over ``N(x) = w * x / sqrt(mean(x²) + rms_norm_eps)`` in
float32: ``x' = x + mixer(N_in(x))``, ``x'' = x' + W_down (silu(W_gate h) * W_up h)`` with
``h = N_ff(x')``; after the last layer one more ``N``, then the head, which is the embedding table
(``tie_word_embeddings``). The program's side is ``ray_tpu.models.jamba``; the plain reference
below is written from the catalog row's ``config`` and the equations of ISSUE 60 (PERF.md section 4
repeats them), not from that file: one sequence, float32 at ``highest`` precision, the recurrence
one position at a time over a state ``[d_inner, d_state]``, the convolution as ``mamba_d_conv``
shifted products, attention as a masked softmax a block of queries at a time, no cache, no window
kept, no kernel, one layer's weights cast at a time.

The mixer of layer l is attention where ``l % attn_layer_period == attn_layer_offset`` and Mamba
elsewhere (``kinds``):

- Mamba-1: ``[u, z] = W_in h`` (``d_inner = mamba_expand x hidden_size`` each);
  ``c_t = silu(b + sum_{k < taps} w_k u_{t - (taps - 1) + k})``, causal and depthwise, zeros before the
  sequence, ``b`` where ``mamba_conv_bias``; ``[r, B, C] = W_x c`` (``mamba_dt_rank`` +
  ``mamba_d_state`` + ``mamba_d_state``), ``r~ = N_dt(r)``, ``B~ = N_B(B)``, ``C~ = N_C(C)``;
  ``dt = softplus(W_dt r~ + b_dt)``; ``A = -exp(A_log)`` ``[d_inner, d_state]``;
  ``h_t[d, n] = exp(dt_t[d] A[d, n]) h_{t-1}[d, n] + dt_t[d] c_t[d] B~_t[n]`` from zero;
  ``y_t[d] = sum_n C~_t[n] h_t[d, n] + D[d] c_t[d]``; ``mixer = W_out (y * silu(z))``. Every (channel,
  state) pair has a decay of its own: no heads, no chunked matmul form.
- attention: ``num_attention_heads`` query heads over ``num_key_value_heads`` key-value heads,
  ``hidden_size / num_attention_heads`` wide, no bias, NO position embedding; causal softmax scaled
  by head_dim^-1/2; ``W_o``.

Sizes come from the configuration file's keys, never from the program's config object. The weights
are the pytree the program serves (``embed``, ``final_norm``, NO ``unembed``, and ``mamba1`` / ``attn``
/ ``ffn`` stacked by layer kind; ``conv_w`` [taps, d_inner] oldest input first, ``A_log``
[d_inner, d_state]).

Departures from the published model, each of which program and reference share (``assumed`` in the
configuration file): weights random from a seed; the final norm's weight ``+-c`` with random signs,
so that a tied head does not give every token its own id back. What the program does otherwise and
the reference does not: ``c``, the step before its bias, ``B~``, ``C~`` and ``y`` rounded to the
weights' dtype around the scan (the published kernel's operands), the window kept in it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.models.jamba import JambaConfig, init_params, loss_fn, param_logical_axes  # noqa: F401 - the family's names

# sizes of the CPU rehearsal (--rehearse): wiring only, never a measurement; M A M M twice, both kinds of mixer
REHEARSAL_SIZES = {
    "hidden_size": 64, "num_hidden_layers": 8, "attn_layer_period": 4, "attn_layer_offset": 1, "vocab_size": 512, "intermediate_size": 96,
    "mamba_dt_rank": 8, "num_attention_heads": 4, "num_key_value_heads": 1, "head_dim": 16,
}

# the reference pads a sequence to the first of these lengths that holds it (a multiple of the last
# beyond that): every layer is causal, and every distinct length compiles the layer functions anew
PAD_TO = (256, 12288)
# queries the reference's attention takes at once (20 heads x 256 x 12,288 float32 scores are 252 MB),
# rows its dense layer takes at once, and the least vocabulary whose head goes in row blocks
QUERY_BLOCK, ROW_BLOCK, HEAD_BLOCKS_FROM = 256, 2048, 65536
SCAN_FLOPS = 7  # a (position, channel, state): dt * A, the decay times the state, dt * c * B (two), their sum, C * h and its sum


def padded_length(n: int) -> int:
    return next((p for p in PAD_TO if p >= n), -(-n // PAD_TO[-1]) * PAD_TO[-1])


def head_dim(c: dict) -> int:
    return int(c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"])


def d_inner(c: dict) -> int:
    return c["mamba_expand"] * c["hidden_size"]


def kinds(c: dict) -> list[str]:
    """``attention`` or ``mamba`` for every layer, in order: by the family's own rule from period and offset."""
    if c.get("num_experts", 1) > 1:
        raise ValueError("this family's feed-forward sub-block is the dense SwiGLU: a member with num_experts > 1 routes it, and no code here does")
    return ["attention" if l % c["attn_layer_period"] == c["attn_layer_offset"] else "mamba" for l in range(c["num_hidden_layers"])]


def count(c: dict, what: str) -> int:
    return kinds(c).count(what)


# ------------------------------------------------------------------------------ the program's side
def program_config(c: dict, max_seq_len: int, **extra) -> JambaConfig:
    """The program's ``JambaConfig`` for a configuration file's published keys."""
    if c.get("hidden_act", "silu") != "silu" or not c.get("tie_word_embeddings", True) or c.get("sliding_window") is not None:
        raise ValueError("this family's activations are silu, its head is tied and its attention has no window")
    kinds(c)
    return JambaConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"], num_hidden_layers=c["num_hidden_layers"],
        attn_layer_period=c["attn_layer_period"], attn_layer_offset=c["attn_layer_offset"], intermediate_size=c["intermediate_size"],
        mamba_d_state=c["mamba_d_state"], mamba_d_conv=c["mamba_d_conv"], mamba_expand=c["mamba_expand"], mamba_dt_rank=c["mamba_dt_rank"],
        mamba_conv_bias=bool(c["mamba_conv_bias"]), mamba_proj_bias=bool(c["mamba_proj_bias"]),
        num_heads=c["num_attention_heads"], num_kv_heads=c["num_key_value_heads"], head_dim=head_dim(c), num_experts=c.get("num_experts", 1),
        rms_eps=float(c["rms_norm_eps"]), max_seq_len=max_seq_len,
        # the initialisation's 1/sqrt(N) on the projections back onto the stream: N counts the PUBLISHED sub-blocks
        residual_rescale_layers=2 * int((c.get("reduced_from") or {}).get("num_hidden_layers", c["num_hidden_layers"])),
        dtype={"bfloat16": "bfloat16", "float32": "float32"}[c.get("torch_dtype", "bfloat16")], **extra)


def rehearsal(c: dict) -> dict:
    return {**c, **REHEARSAL_SIZES, "torch_dtype": "float32"}


def kernels_expected(c: dict) -> dict:
    """Every attention layer runs the flash kernel over a sequence: a Pallas kernel lowers to a ``tpu_custom_call``."""
    return {"flash kernel": "tpu_custom_call"}


# ----------------------------------------------------------------------------- operations, from shapes
def layer_params(c: dict) -> dict:
    """Parameters by part: one ``mamba`` mixer (its matrices under ``mamba_matmul``), one ``attention`` mixer, one
    ``dense`` ffn, a sub-block's ``norm``, the embedding (the head is the same table) and the final norm."""
    H, di, R, S, K, hd = c["hidden_size"], d_inner(c), c["mamba_dt_rank"], c["mamba_d_state"], c["mamba_d_conv"], head_dim(c)
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    matmul = H * 2 * di + di * (R + 2 * S) + R * di + di * H
    small = K * di + di * bool(c["mamba_conv_bias"]) + (2 * di + H) * bool(c["mamba_proj_bias"]) + R + 2 * S + di + di * S + di  # taps, biases, three norms, b_dt, A_log, D
    return {"mamba": matmul + small, "mamba_matmul": matmul, "attention": 2 * H * q + 2 * H * kv, "dense": 3 * H * c["intermediate_size"],
            "norm": H, "embed": c["vocab_size"] * H, "final_norm": H}


def parameters_held(c: dict) -> int:
    p, L = layer_params(c), c["num_hidden_layers"]
    return count(c, "mamba") * p["mamba"] + count(c, "attention") * p["attention"] + L * p["dense"] + 2 * L * p["norm"] + p["embed"] + p["final_norm"]


parameters_published = parameters_held  # nothing is cut: the count from the file's keys IS the published model's


def kv_bytes_per_token(c: dict, itemsize: int = 2) -> int:
    """What one position takes in the cache: a key and a value by head in every attention layer, nothing in a Mamba layer."""
    return count(c, "attention") * 2 * c["num_key_value_heads"] * head_dim(c) * itemsize


def state_bytes_per_slot(c: dict, itemsize: int = 2) -> int:
    """What one sequence keeps beside its positions: in every Mamba layer the float32 state and the convolution's last ``taps - 1`` inputs."""
    return count(c, "mamba") * (d_inner(c) * c["mamba_d_state"] * 4 + (c["mamba_d_conv"] - 1) * d_inner(c) * itemsize)


def cache_bytes(c: dict, slots: int, max_seq_len: int, itemsize: int = 2) -> int:
    return slots * (max_seq_len * kv_bytes_per_token(c, itemsize) + state_bytes_per_slot(c, itemsize))


def _per_token_matmul(c: dict) -> float:
    """Multiply-adds per token in the whole stack, without the head: every matrix (norms, taps and biases multiply nothing worth counting)."""
    p = layer_params(c)
    return count(c, "mamba") * p["mamba_matmul"] + count(c, "attention") * p["attention"] + c["num_hidden_layers"] * p["dense"]


def causal_pairs(n: float) -> float:
    return n * (n + 1) / 2.0


def selective_scan_least(c: dict, tokens: float, sequences: float, itemsize: int = 2) -> dict:
    """What the Mamba layers' recurrence must move and compute for ``tokens`` positions of ``sequences`` sequences, whatever
    runs it, over ALL the Mamba layers held: ``c`` read and ``y`` written once at the stream's width, the step's rank-``dt_rank``
    source, ``B`` and ``C`` read once, a sequence's state written once (float32); ``SCAN_FLOPS`` a (position, channel,
    state), the exponential not counted. At the published widths 20,864 B and 573,440 FLOPs a position and layer: on a
    v5e's published peaks the bytes are the larger (0.026 us against 0.003 at the bf16 matmul peak, which no elementwise
    recurrence can use: ``peaks.py`` holds no peak for the vector unit). -> {"bytes", "flops"}."""
    di, S, layers = d_inner(c), c["mamba_d_state"], count(c, "mamba")
    per_position = (2 * di + c["mamba_dt_rank"] + 2 * S) * itemsize
    return {"bytes": float(layers * (tokens * per_position + sequences * di * S * 4)), "flops": float(layers * tokens * SCAN_FLOPS * di * S)}


def decode_step_least(c: dict, lanes: float, experts_hit: float, kv_tokens: float, itemsize: int = 2) -> dict:
    """What ONE decode step must move and compute, whatever the program: every weight once (the head IS the embedding
    table, read whole; nothing is routed, ``experts_hit`` is there for the readers' one signature), the state and the
    window of the ``lanes`` in use read and written, and the keys and values of the ``kv_tokens`` positions the lanes hold
    in every attention layer. -> {"bytes", "flops"}."""
    nbytes = parameters_held(c) * itemsize + 2 * lanes * state_bytes_per_slot(c, itemsize) + kv_tokens * kv_bytes_per_token(c, itemsize)
    flops = (2.0 * lanes * (_per_token_matmul(c) + layer_params(c)["embed"]) + kv_tokens * count(c, "attention") * 4 * c["num_attention_heads"] * head_dim(c)
             + selective_scan_least(c, lanes, 0)["flops"])
    return {"bytes": float(nbytes), "flops": float(flops)}


def prefill_least(c: dict, lengths: list, pairs_local: float = 0.0, experts_hit: float = 0.0, itemsize: int = 2) -> dict:
    """What ONE prefill call over prompts of the TRUE ``lengths`` must move and compute, whatever the program: every
    weight once, the prompts' embedding rows, and what it hands the caches (every position's key and value in an
    attention layer; a state and a window a prompt in a Mamba layer). FLOPs at the true lengths: two per weight of a
    matrix and token, the head at each prompt's last position only, causal attention (2 x 2 x head_dim in every query
    head a pair) and the recurrence (``selective_scan_least``). Nothing is routed: ``pairs_local`` and ``experts_hit``
    are there for the readers' one signature. -> {"bytes", "flops"}."""
    tokens = float(sum(lengths))
    kept = tokens * kv_bytes_per_token(c, itemsize) + len(lengths) * state_bytes_per_slot(c, itemsize)
    nbytes = (parameters_held(c) + tokens * c["hidden_size"]) * itemsize + kept
    flops = (2.0 * tokens * _per_token_matmul(c) + 2.0 * len(lengths) * layer_params(c)["embed"]
             + 4.0 * c["num_attention_heads"] * head_dim(c) * count(c, "attention") * sum(causal_pairs(float(n)) for n in lengths)
             + selective_scan_least(c, tokens, len(lengths))["flops"])
    return {"bytes": float(nbytes), "flops": float(flops)}


def train_flops_per_token(c: dict, seq: int) -> float:
    """FLOPs the forward and backward passes require per trained token: 6 per weight of a matrix plus three times the
    causal attention and the recurrence forward. No recompute. (No cell trains this family.)"""
    one = 2.0 * (_per_token_matmul(c) + layer_params(c)["embed"]) + selective_scan_least(c, 1, 0)["flops"]
    return 3.0 * (one + 4.0 * c["num_attention_heads"] * head_dim(c) * count(c, "attention") * causal_pairs(seq) / seq)


# --------------------------------------------------------------------------------- the plain reference
def _norm(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _layer_weights(group, i):
    return jax.tree.map(lambda p: jax.lax.dynamic_index_in_dim(p, i, 0, keepdims=False), group)


@functools.partial(jax.jit, static_argnames=("R", "S", "eps"))
def _mamba(x, group, i, *, R, S, eps):
    """One Mamba-1 sub-block on x [T, H], the equations of the module docstring line by line; a bias that the
    weights do not hold (``conv_b``, ``in_bias``, ``out_bias``) is not added."""
    w = jax.tree.map(lambda p: p.astype(jnp.float32), _layer_weights(group, i))
    T, K = x.shape[0], w["conv_w"].shape[0]
    u, z = jnp.split(_norm(x, w["norm"], eps) @ w["in_proj"] + w.get("in_bias", 0.0), 2, axis=-1)
    c = jax.nn.silu(sum(w["conv_w"][k] * jnp.pad(u, ((K - 1 - k, 0), (0, 0)))[:T] for k in range(K)) + w.get("conv_b", 0.0))  # tap k reads the input K - 1 - k back
    rbc = c @ w["x_proj"]
    r, B, C = _norm(rbc[:, :R], w["dt_norm"], eps), _norm(rbc[:, R:R + S], w["b_norm"], eps), _norm(rbc[:, R + S:], w["c_norm"], eps)
    dt = jax.nn.softplus(r @ w["dt_proj"] + w["dt_bias"])
    A = -jnp.exp(w["A_log"])  # [d_inner, d_state]

    def one_position(h, inp):
        c_t, dt_t, B_t, C_t = inp
        h = jnp.exp(dt_t[:, None] * A) * h + (dt_t * c_t)[:, None] * B_t[None, :]
        return h, h @ C_t

    _, y = jax.lax.scan(one_position, jnp.zeros_like(A), (c, dt, B, C))
    return x + ((y + w["D"] * c) * jax.nn.silu(z)) @ w["out_proj"] + w.get("out_bias", 0.0)


@functools.partial(jax.jit, static_argnames=("nh", "kv", "hd", "eps"))
def _attention(x, group, i, *, nh, kv, hd, eps):
    """One attention sub-block on x [T, H]: every query against every earlier key, no position embedding."""
    w = jax.tree.map(lambda p: p.astype(jnp.float32), _layer_weights(group, i))
    T, rep = x.shape[0], nh // kv
    h = _norm(x, w["norm"], eps)
    q, k, v = (h @ w["wq"]).reshape(T, nh, hd), (h @ w["wk"]).reshape(T, kv, hd), (h @ w["wv"]).reshape(T, kv, hd)
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
    """One dense sub-block on x [T, H]: ``W_down (silu(W_gate h) * W_up h)``, ``ROW_BLOCK`` rows at a time where there are many."""
    w = jax.tree.map(lambda p: p.astype(jnp.float32), _layer_weights(group, i))
    h = _norm(x, w["norm"], eps)

    def some(h):
        return (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]

    T = x.shape[0]
    if T <= ROW_BLOCK or T % ROW_BLOCK:
        return x + some(h)
    return x + jax.lax.map(some, h.reshape(T // ROW_BLOCK, ROW_BLOCK, -1)).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, embed, *, eps):
    """log softmax(N(x) E^T): the head is the embedding table; its rows in blocks where the vocabulary is large."""
    xn = _norm(x, final_norm.astype(jnp.float32), eps)
    V, H = embed.shape
    blocks = 8 if V >= HEAD_BLOCKS_FROM and V % 8 == 0 else 1
    logits = jax.lax.map(lambda rows: xn @ rows.astype(jnp.float32).T, embed.reshape(blocks, V // blocks, H))  # [blocks, n, V / blocks]
    return jax.nn.log_softmax(jnp.moveaxis(logits, 0, 1).reshape(x.shape[0], V), axis=-1)


def hidden_states(params: dict, tokens, c: dict):
    """tokens [T] int32 -> the last layer's output [T, H], float32."""
    eps, seen = float(c["rms_norm_eps"]), {"mamba1": 0, "attn": 0}
    heads = dict(nh=c["num_attention_heads"], kv=c["num_key_value_heads"], hd=head_dim(c), eps=eps)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(tokens, jnp.int32), axis=0).astype(jnp.float32)
        for l, mixer in enumerate(kinds(c)):
            group = "mamba1" if mixer == "mamba" else "attn"
            i, seen[group] = seen[group], seen[group] + 1
            x = _mamba(x, params[group], i, R=c["mamba_dt_rank"], S=c["mamba_d_state"], eps=eps) if mixer == "mamba" else _attention(x, params[group], i, **heads)
            x = _dense(x, params["ffn"], l, eps=eps)
    return x


def reference_logprobs(params: dict, tokens, c: dict, start: int, stop: int):
    """Log-probabilities [stop - start, vocab] that the model gives, after reading
    tokens[: p + 1], to the token at position p + 1, for p in [start, stop)."""
    tokens = list(tokens) + [0] * (padded_length(len(tokens)) - len(tokens))  # few distinct shapes to compile; every layer is causal
    x = hidden_states(params, tokens, c)[start:stop]
    with jax.default_matmul_precision("highest"):
        return _head(x, params["final_norm"], params["embed"], eps=float(c["rms_norm_eps"]))
