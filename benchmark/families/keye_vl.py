"""The Keye-VL-2.0 language block (``model_type`` ``KeyeVL2``): every published decoder layer is two
residual sub-blocks over ``N(x) = w * x / sqrt(mean(x²) + rms_norm_eps)``, no bias:
``x' = x + attention(N(x))``, ``x'' = x' + experts(N(x'))``; after the last layer one more ``N`` and
an untied head. The program's side is ``ray_tpu.models.keye_vl``; the plain reference below is
written from the catalog row's ``config`` and the equations of ISSUE 58 (PERF.md section 4 repeats
them), not from that file: one sequence, float32 at ``highest`` precision, no cache, no kernel, no
threshold: the index scores of a block of queries against every position, a full stable sort a
query, the choice as a mask, a masked softmax; every expert over every token one expert at a time;
one layer's (one expert's) weights cast at a time.

Attention of a layer, h = N(x): ``q_{t,i} = R(N_hd(W_q h_t)_i)`` for ``num_attention_heads`` heads
of ``head_dim``; ``k_{t,g} = R(N_hd(W_k h_t)_g)``, ``v_{t,g} = (W_v h_t)_g`` for
``num_key_value_heads`` heads (``N_hd``: RMSNorm over a head, one weight vector for the query heads
and one for the key heads). ``R``: rotate-half rotary over all of a head's channels, theta
``rope_theta``, as M-RoPE with ``rope_scaling.mrope_section`` [16, 24, 24]: frequency i of 64 takes
its angle from position stream 0 (i < 16), 1 (16 <= i < 40) or 2 (i >= 40); the functions here
take positions [3, T], and ``reference_logprobs`` serves text: the three streams are the token's
index. The indexer (``sa_config``): ``qI_{t,j} = R((W_qI h_t)_j)`` for ``indexer_num_heads`` heads of
``indexer_head_dim``; ``kI_s = R(LayerNorm(W_kI h_s))``, ONE key a position
(``indexer_num_kv_heads`` 1); ``w_t = W_w h_t``; ``R`` over the indexer's own channels, frequency i
of 32 with the stream of the attention head's frequency 2 i (8, 12, 12).
``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``; ``S_t`` = every s <= t while t + 1 <= ``topk``,
else the ``topk`` positions s <= t of largest I (ties to the earlier position); ONE ``S_t`` for all
heads; ``o_{t,i} = sum_{s in S_t} softmax_s(q_{t,i} . k_{s,g(i)} / sqrt(head_dim)) v_{s,g(i)}``;
``x + W_o o``. No positive scale on I changes ``S_t``, so none is stated.

Experts of a layer, h' = N(x'): ``p = softmax(W_r h')`` in float32 over ``num_experts``; the top
``num_experts_per_tok``, renormalised to sum to one (``norm_topk_prob``); SwiGLU experts
``moe_intermediate_size`` wide, no shared one; every layer (``decoder_sparse_step`` 1,
``mlp_only_layers`` []).

Sizes come from the configuration file's keys, never from the program's config object. The weights
are the pytree the program serves (``embed``, ``unembed``, ``final_norm``, and ``indexed`` / ``moe``
stacked by layer kind; an expert's matrices [F, H]). ``fault=`` plants one of four wrong KINDS of
selection in the reference (for the builder's one-off at the timed sizes, where serving each fault
anew would cost a compile each; the tests plant theirs in the program): ``dense`` no selection,
``half_topk``, ``one_head`` the score from the indexer's first head, ``no_relu``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.models.keye_vl import KeyeVLConfig, init_params, loss_fn, param_logical_axes  # noqa: F401 - the family's names

# sizes of the CPU rehearsal (--rehearse): wiring only, never a measurement
REHEARSAL_SIZES = {
    "hidden_size": 64, "num_hidden_layers": 3, "vocab_size": 512, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_experts": 8, "num_local_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 32, "intermediate_size": 96,
    "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default", "type": "default"},
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2, "indexer_num_kv_heads": 1, "kv_chunk_size": 8, "q_chunk_size": 8, "topk": 16},
    "init_qk_norm": 1.0, "reduced_from": {"num_hidden_layers": 3},
}

# the reference pads a sequence to the first of these lengths that holds it (a multiple of the last
# beyond that): every layer is causal, and every distinct length compiles the layer functions anew.
# Every prompt of the cell with its answer then has ONE length, the cell's horizon
PAD_TO = (128, 1024, 24576)
# queries the reference's attention takes at once (32 heads x 128 x 24,576 float32 scores are 403 MB), and the
# least vocabulary whose head goes in column blocks
QUERY_BLOCK, HEAD_BLOCKS_FROM = 128, 65536
FAULTS = ("dense", "half_topk", "one_head", "no_relu")


def padded_length(n: int) -> int:
    return next((p for p in PAD_TO if p >= n), -(-n // PAD_TO[-1]) * PAD_TO[-1])


def published_depth(c: dict) -> int:
    return int((c.get("reduced_from") or {}).get("num_hidden_layers", c["num_hidden_layers"]))


# ------------------------------------------------------------------------------ the program's side
def program_config(c: dict, max_seq_len: int, **extra) -> KeyeVLConfig:
    """The program's ``KeyeVLConfig`` for a configuration file's published keys."""
    sa = c["sa_config"]
    if c["attention_bias"] or c["tie_word_embeddings"] or c["decoder_sparse_step"] != 1 or c["mlp_only_layers"] or c.get("use_sliding_window"):
        raise ValueError("this family has no attention bias, an untied head, experts in every layer and no sliding window")
    if sa["indexer_num_kv_heads"] != 1 or not c["norm_topk_prob"] or c["num_local_experts"] != c["num_experts"] or c["hidden_act"] != "silu":
        raise ValueError("this family's indexer keeps ONE key a position, its router renormalises the chosen, every expert is local, and they gate by SiLU")
    return KeyeVLConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"], num_hidden_layers=c["num_hidden_layers"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"], rope_theta=float(c["rope_theta"]),
        mrope_section=tuple(c["rope_scaling"]["mrope_section"]), index_heads=sa["indexer_num_heads"], index_dim=sa["indexer_head_dim"],
        index_topk=sa["topk"], n_routed_experts=c["num_experts"], num_experts_per_tok=c["num_experts_per_tok"],
        moe_intermediate_size=c["moe_intermediate_size"], norm_topk_prob=bool(c["norm_topk_prob"]), rms_eps=float(c["rms_norm_eps"]),
        # the initialisation's 1/sqrt(N) on the projections back onto the stream: N counts the PUBLISHED sub-blocks
        residual_rescale_layers=2 * published_depth(c), qk_norm_init=float(c.get("init_qk_norm", 1.0)), max_seq_len=max_seq_len,
        dtype={"bfloat16": "bfloat16", "float32": "float32"}[c.get("torch_dtype", "bfloat16")], **extra)


def rehearsal(c: dict) -> dict:
    return {**c, **REHEARSAL_SIZES, "torch_dtype": "float32"}


def kernels_expected(c: dict) -> dict:
    """A sequence of at most ``topk`` positions runs the flash kernel in every layer: a Pallas kernel lowers to a ``tpu_custom_call``."""
    return {"flash kernel": "tpu_custom_call"}


# ----------------------------------------------------------------------------- operations, from shapes
def layer_params(c: dict) -> dict:
    """Parameters by part: one layer's ``attention`` (q, k, v, o and the two head norms), its
    ``indexer`` (the query heads, the one key with its LayerNorm's weight and bias, the heads'
    weights), its ``router``, one ``expert``, a sub-block's ``norm``, embedding plus head, the final norm."""
    H, hd, sa = c["hidden_size"], c["head_dim"], c["sa_config"]
    q, kv, d, J = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd, sa["indexer_head_dim"], sa["indexer_num_heads"]
    return {"attention": 2 * H * q + 2 * H * kv + 2 * hd, "indexer": H * J * d + H * d + H * J + 2 * d, "router": H * c["num_experts"],
            "expert": 3 * H * c["moe_intermediate_size"], "norm": H, "embed_and_head": 2 * c["vocab_size"] * H, "final_norm": H}


def _fixed(c: dict) -> int:
    """Every weight outside the routed experts and the two tables: attention, indexer, routers, norms."""
    p = layer_params(c)
    return c["num_hidden_layers"] * (p["attention"] + p["indexer"] + p["router"] + 2 * p["norm"]) + p["final_norm"]


def parameters_held(c: dict) -> int:
    p = layer_params(c)
    return _fixed(c) + c["num_hidden_layers"] * c["num_experts"] * p["expert"] + p["embed_and_head"]


def parameters_published(c: dict) -> int:
    """The same count at the published depth: every layer is alike."""
    return parameters_held({**c, "num_hidden_layers": published_depth(c)})


def kv_bytes_per_token(c: dict, itemsize: int = 2) -> int:
    """What one position takes in the cache: a key and a value by head and the indexer's one key, in every layer."""
    return c["num_hidden_layers"] * (2 * c["num_key_value_heads"] * c["head_dim"] + c["sa_config"]["indexer_head_dim"]) * itemsize


def cache_bytes(c: dict, slots: int, max_seq_len: int, itemsize: int = 2) -> int:
    return slots * max_seq_len * kv_bytes_per_token(c, itemsize)


def causal_pairs(n: float) -> float:
    return n * (n + 1) / 2.0


def chosen_pairs(c: dict, n: float) -> float:
    """(query, position) pairs attention reads over a prompt of ``n`` positions: min(t + 1, topk) a query."""
    k = c["sa_config"]["topk"]
    return causal_pairs(n) - (causal_pairs(n - k) if n > k else 0.0)


def indexer_score_least(c: dict, pairs: float, tokens: float, itemsize: int = 2) -> dict:
    """What the index scores of ONE layer must move and compute for ``pairs`` causal (query,
    position) pairs over ``tokens`` positions, whatever runs them: the indexer's queries, weights and
    keys read once; a product, a ReLU's worth and a weighted sum in every indexer head a pair
    (2 x indexer_head_dim FLOPs a head: the ReLU, the weight and the sum are not counted).
    The choice itself (a threshold, a sort) is no part of this count."""
    sa = c["sa_config"]
    J, d = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return {"bytes": float(tokens * ((J * d + d) * itemsize + J * 4)), "flops": float(pairs * 2 * J * d)}


def indexed_prefill_least(c: dict, pairs: float, tokens: float, itemsize: int = 2) -> dict:
    """What attention under the choice must move and compute in ONE layer for ``pairs`` CHOSEN
    (query, position) pairs (min(t + 1, topk) a query) over ``tokens`` positions, whatever runs it:
    q read and the output written once, k and v read once; a score and a weighted sum in every
    query head a pair (2 x 2 x head_dim). A masked pass that attends to every causal pair does
    mean(t) / topk times this and reads that much lower."""
    nh, kv, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    return {"bytes": float(tokens * (2 * nh + 2 * kv) * hd * itemsize), "flops": float(pairs * 4 * nh * hd)}


def indexed_decode_least(c: dict, rows_scored: float, rows_chosen: float, itemsize: int = 2) -> dict:
    """What a decode step's indexed attention must move in ONE layer for lanes that hold
    ``rows_scored`` positions in all and attend to ``rows_chosen`` of them: the chosen rows of
    ``k`` and ``v`` once plus the lanes' ``k_idx`` rows once. FLOPs: every indexer head's product a
    scored row, every query head's score and weighted sum a chosen row."""
    sa, hd = c["sa_config"], c["head_dim"]
    return {"bytes": float((rows_chosen * 2 * c["num_key_value_heads"] * hd + rows_scored * sa["indexer_head_dim"]) * itemsize),
            "flops": float(rows_scored * 2 * sa["indexer_num_heads"] * sa["indexer_head_dim"] + rows_chosen * 4 * c["num_attention_heads"] * hd)}


def _per_token_matmul(c: dict, experts_a_token: float) -> float:
    """Multiply-adds per token in the whole stack, without the head: every matrix (norms and the
    LayerNorm multiply nothing worth counting), ``experts_a_token`` routed experts in each layer."""
    p, sa = layer_params(c), c["sa_config"]
    return c["num_hidden_layers"] * (p["attention"] - 2 * c["head_dim"] + p["indexer"] - 2 * sa["indexer_head_dim"] + p["router"]
                                     + experts_a_token * p["expert"])


def decode_step_least(c: dict, lanes: float, experts_hit: float, kv_tokens: float, itemsize: int = 2) -> dict:
    """What ONE decode step must move and compute, whatever the program: every weight outside the
    routed experts once (the head whole, the embedding's rows of the lanes), ``experts_hit`` routed
    experts in each layer, and of the ``kv_tokens`` positions the lanes hold the indexer's keys in
    every layer plus the keys and values of the CHOSEN ones (at most ``topk`` a lane)."""
    p, L, V, H = layer_params(c), c["num_hidden_layers"], c["vocab_size"], c["hidden_size"]
    chosen = min(kv_tokens, lanes * c["sa_config"]["topk"])
    one = indexed_decode_least(c, kv_tokens, chosen, itemsize)
    nbytes = (_fixed(c) + H * V + lanes * H + L * experts_hit * p["expert"]) * itemsize + L * one["bytes"]
    return {"bytes": float(nbytes), "flops": float(2.0 * lanes * (_per_token_matmul(c, c["num_experts_per_tok"]) + H * V) + L * one["flops"])}


def prefill_least(c: dict, lengths: list, pairs_local: float, experts_hit: float, itemsize: int = 2) -> dict:
    """What ONE prefill call over prompts of the TRUE ``lengths`` must move and compute, whatever
    the program: every weight outside the routed experts once, ``experts_hit`` routed experts in
    each layer once (a mean over the layers), the prompts' embedding rows, what it hands the cache.
    FLOPs at the true lengths: two per weight outside the routed experts and token, two per expert
    weight and (token, expert) pair (``pairs_local``: a mean over the layers), the head at each
    prompt's last position, the index scores over every causal pair and attention over the CHOSEN
    pairs. Padding to the bucket is the program's choice and is not in here. -> {"bytes", "flops"}."""
    p, L, H, V = layer_params(c), c["num_hidden_layers"], c["hidden_size"], c["vocab_size"]
    tokens = float(sum(lengths))
    nbytes = (_fixed(c) + H * V + L * experts_hit * p["expert"] + tokens * H) * itemsize + tokens * kv_bytes_per_token(c, itemsize)
    flops = (2.0 * tokens * _per_token_matmul(c, 0.0) + 2.0 * len(lengths) * H * V + 2.0 * L * pairs_local * p["expert"]
             + L * indexer_score_least(c, sum(causal_pairs(float(n)) for n in lengths), tokens)["flops"]
             + L * indexed_prefill_least(c, sum(chosen_pairs(c, float(n)) for n in lengths), tokens)["flops"])
    return {"bytes": float(nbytes), "flops": float(flops)}


def train_flops_per_token(c: dict, seq: int) -> float:
    """FLOPs the forward and backward passes require per trained token: 6 per weight that
    multiplies the token (the experts it is routed to) plus three times the index scores and the
    attention over the chosen pairs forward. No recompute. (No cell trains this family: a backward
    pass through the choice is not built, and 16 bytes a parameter fit no cut of it on one chip.)"""
    L = c["num_hidden_layers"]
    own = L * (indexer_score_least(c, causal_pairs(seq), seq)["flops"] + indexed_prefill_least(c, chosen_pairs(c, seq), seq)["flops"]) / seq
    return 6.0 * (_per_token_matmul(c, c["num_experts_per_tok"]) + c["hidden_size"] * c["vocab_size"]) + 3.0 * own


# --------------------------------------------------------------------------------- the plain reference
def _norm(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _layer_weights(group, i):
    return jax.tree.map(lambda p: jax.lax.dynamic_index_in_dim(p, i, 0, keepdims=False), group)


def _rotate(x, positions, theta, sections, step):
    """``R``: rotate-half over all channels of x [T, heads, d] for positions [3, T]; frequency i of
    d / 2 turns by theta^(-2 i / d) a position of the stream that ``sections`` gives frequency ``step * i``."""
    d = x.shape[-1]
    streams = [s for s, n in enumerate(sections) for _ in range(n)]  # the stream of each of the attention head's frequencies
    stream = jnp.asarray([streams[step * i] for i in range(d // 2)])
    pos = jnp.take(positions.astype(jnp.float32), stream, axis=0).T  # [T, d / 2]
    angles = pos * theta ** (-jnp.arange(d // 2, dtype=jnp.float32) / (d // 2))
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("nh", "kv", "hd", "J", "d", "topk", "eps", "theta", "sections", "fault"))
def _attention(x, group, i, positions, *, nh, kv, hd, J, d, topk, eps, theta, sections, fault=None):
    """One attention sub-block on x [T, H] under the learned index: scores, a full sort a query, the choice as a mask."""
    w = jax.tree.map(lambda p: p.astype(jnp.float32), _layer_weights(group, i))
    T, rep = x.shape[0], nh // kv
    h = _norm(x, w["norm"], eps)
    q = _rotate(_norm((h @ w["wq"]).reshape(T, nh, hd), w["q_norm"], eps), positions, theta, sections, 1).reshape(T, kv, rep, hd)
    k = _rotate(_norm((h @ w["wk"]).reshape(T, kv, hd), w["k_norm"], eps), positions, theta, sections, 1)
    v = (h @ w["wv"]).reshape(T, kv, hd)
    qi = _rotate((h @ w["wq_idx"]).reshape(T, J, d), positions, theta, sections, hd // d)
    ki = h @ w["wk_idx"]
    ki = ki - jnp.mean(ki, axis=-1, keepdims=True)
    ki = ki * jax.lax.rsqrt(jnp.mean(ki * ki, axis=-1, keepdims=True) + eps) * w["k_idx_norm"] + w["k_idx_norm_bias"]
    ki = _rotate(ki[:, None], positions, theta, sections, hd // d)[:, 0]
    weights = h @ w["w_idx"]  # [T, J]
    if fault == "one_head":
        qi, weights = qi[:, :1], weights[:, :1]
    take = topk // 2 if fault == "half_topk" else topk
    at = jnp.arange(T)

    def some_queries(qb):
        q_b, qi_b, w_b, first = qb  # [Q, kv, rep, hd], [Q, J, d], [Q, J], the position of the block's first query
        t = first + jnp.arange(q_b.shape[0])
        causal = at[None, :] <= t[:, None]  # [Q, T]
        products = jnp.einsum("qjd,sd->qjs", qi_b, ki)
        index = jnp.sum(w_b[..., None] * (products if fault == "no_relu" else jax.nn.relu(products)), axis=1)  # I[t, s]
        order = jnp.argsort(-jnp.where(causal, index, -jnp.inf), axis=-1, stable=True)  # ties to the earlier position
        rank = jnp.argsort(order, axis=-1)  # a position's place in its query's order
        allowed = causal if fault == "dense" else causal & (rank < take)  # t + 1 <= topk: every s <= t ranks under topk
        s = jnp.einsum("qgrh,sgh->qgrs", q_b, k) * hd ** -0.5
        return jnp.einsum("qgrs,sgh->qgrh", jax.nn.softmax(jnp.where(allowed[:, None, None], s, -jnp.inf), axis=-1), v)

    Q = QUERY_BLOCK if T > QUERY_BLOCK and T % QUERY_BLOCK == 0 else T
    o = jax.lax.map(some_queries, (q.reshape(T // Q, Q, kv, rep, hd), qi.reshape(T // Q, Q, -1, d), weights.reshape(T // Q, Q, -1), jnp.arange(0, T, Q)))
    return x + o.reshape(T, nh * hd) @ w["wo"]


@functools.partial(jax.jit, static_argnames=("eps", "top_k"))
def _experts(x, group, i, *, eps, top_k):
    """One expert sub-block on x [T, H]: softmax over all experts, the top k renormalised; every
    expert over every token, one expert at a time, weighted by what the router gave it."""
    w = _layer_weights(group, i)
    h = _norm(x, w["norm"].astype(jnp.float32), eps)
    p = jax.nn.softmax(h @ w["router"].astype(jnp.float32), axis=-1)
    chosen, idx = jax.lax.top_k(p, top_k)
    chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    E = w["w_up"].shape[0]
    given = jnp.zeros((x.shape[0], E), jnp.float32).at[jnp.arange(x.shape[0])[:, None], idx].set(chosen)  # [T, experts]

    def one_expert(e, acc):
        gate, up, down = (w[n][e].astype(jnp.float32) for n in ("w_gate", "w_up", "w_down"))  # each stored [F, H]
        return acc + given[:, e, None] * ((jax.nn.silu(h @ gate.T) * (h @ up.T)) @ down)

    return x + jax.lax.fori_loop(0, E, one_expert, jnp.zeros_like(x))


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, unembed, *, eps):
    """log softmax(N(x) W_head), the head's columns in blocks where the vocabulary is large (the published head in float32 is 1.24 GB)."""
    xn = _norm(x, final_norm.astype(jnp.float32), eps)
    H, V = unembed.shape
    blocks = 8 if V >= HEAD_BLOCKS_FROM and V % 8 == 0 else 1
    logits = jax.lax.map(lambda cols: xn @ cols.astype(jnp.float32), jnp.moveaxis(unembed.reshape(H, blocks, V // blocks), 1, 0))  # [blocks, n, V / blocks]
    return jax.nn.log_softmax(jnp.moveaxis(logits, 0, 1).reshape(x.shape[0], V), axis=-1)


def hidden_states(params: dict, tokens, c: dict, positions=None, fault: str | None = None):
    """tokens [T] int32 -> the last layer's output [T, H], float32. ``positions`` [3, T]: the three
    position streams (the token's index thrice without them: text)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"a fault is one of {FAULTS}")
    eps, sa, T = float(c["rms_norm_eps"]), c["sa_config"], len(tokens)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (3, T)) if positions is None else jnp.asarray(positions)
    heads = dict(nh=c["num_attention_heads"], kv=c["num_key_value_heads"], hd=c["head_dim"], J=sa["indexer_num_heads"], d=sa["indexer_head_dim"],
                 topk=sa["topk"], eps=eps, theta=float(c["rope_theta"]), sections=tuple(c["rope_scaling"]["mrope_section"]), fault=fault)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(tokens, jnp.int32), axis=0).astype(jnp.float32)
        for l in range(c["num_hidden_layers"]):
            x = _attention(x, params["indexed"], l, positions, **heads)
            x = _experts(x, params["moe"], l, eps=eps, top_k=c["num_experts_per_tok"])
    return x


def reference_logprobs(params: dict, tokens, c: dict, start: int, stop: int, fault: str | None = None):
    """Log-probabilities [stop - start, vocab] that the model gives, after reading
    tokens[: p + 1], to the token at position p + 1, for p in [start, stop). A query's choice is its
    own (t + 1 against ``topk``), so it does not matter which positions were read as a prompt."""
    tokens = list(tokens) + [0] * (padded_length(len(tokens)) - len(tokens))  # few distinct shapes to compile; every layer is causal
    x = hidden_states(params, tokens, c, fault=fault)[start:stop]
    with jax.default_matmul_precision("highest"):
        return _head(x, params["final_norm"], params["unembed"], eps=float(c["rms_norm_eps"]))
