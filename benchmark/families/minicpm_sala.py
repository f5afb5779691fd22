"""The MiniCPM-SALA block (``model_type`` ``minicpm_sala``): every published decoder layer is two
residual sub-blocks, ``x = x + a * mixer(N(x))`` then ``x = x + a * mlp(N(x))``, with
``N(x) = w * x / sqrt(mean(x²) + eps)`` and ``a = scale_depth / sqrt(32)`` (the PUBLISHED depth,
whatever is held); the stream starts as ``scale_emb * E[token]``; layer ``l`` (0-indexed) mixes by
InfLLM-v2 block-sparse attention where ``mixer_types[l]`` is ``minicpm4`` and by Lightning linear
attention where it is ``lightning-attn``; the MLP is a dense SwiGLU; then a final ``N``, a division
by ``hidden_size / dim_model_base`` and an untied head. The program's side is
``ray_tpu.models.minicpm_sala``; the plain reference below is written from the catalog row's
``config`` and the equations of ISSUE 45 (PERF.md section 4 repeats them), not from that file: one
sequence, float32 at ``highest`` precision, Lightning ONE POSITION AT A TIME (no chunks), the
sparse layer's selection and attention a block of queries at a time against every key with a mask
(no kernel, no cache, no compressed-key cache: the compressed keys are means taken from the keys
as they stand), one layer's weights cast at a time.

Lightning layer, 32 heads of 128: ``q, k, v = W_q xn, W_k xn, W_v xn``, no activation; a learned
RMSNorm over a head's channels on q and on k; rotate-half RoPE (theta 10,000) over all 128
channels of q and k; per head a state S [key x value]: ``S_t = exp(-s_h) S_{t-1} + k_t v_t^T``,
``o_t = S_t^T q_t / sqrt(128)``; ``s_h = 2^(-8 (h + 1) / 32) * (1 - l / 31 + 1e-5)`` for PUBLISHED
layer index l; ``y = W_o (RMSNorm(o, over all 4,096 channels) * sigmoid(W_z xn))``.

Sparse layer, 32 query heads over 2 key-value heads (16 query heads a GROUP): the same per-head
norm on q and k, NO rotation, scale ``128^-1/2``, ``y = W_o (o * sigmoid(W_g xn))``. A query
computed while its sequence holds at most ``dense_len`` positions attends causally to everything.
Otherwise, for query position t and group g: (1) compressed keys ``Kc_j = mean(k[16 j : 16 j +
32])`` for every j whose 32 positions all lie at or before t; (2) ``r[t, h, j] = softmax_j(q[t, h]
. Kc_j / sqrt(128))``, ``R[t, g, j]`` its sum over the group's heads; (3) a block's score
``B[t, g, b]`` = the largest ``R[t, g, j]`` over the compressed keys whose window overlaps block b
(positions [64 b, 64 b + 64)); (4) forced: block 0 and the 32 blocks that end with t's own;
chosen: the 64 highest of B among the blocks at or before t's, the forced ones counted among them
(ties to the earlier block); (5) causal softmax attention over the positions at or before t of the
chosen blocks. WHEN a query is computed decides: a prompt's positions are all computed when the
prompt is read (it holds ``start + 1`` positions, ``start`` being the first position whose
prediction is asked for: the harness asks from the prompt's last), each later position when it is
decoded (the sequence then holds t + 1).

``selection_agreement``: beside the reference's own selection, each sparse layer runs the
PROGRAM's selection (``ray_tpu.ops.sparse_attention``) on the reference's layer input cast to the
served dtype, and the two sets are compared for every (query, group) that chooses among more blocks
than it may read. Printed on a ``[reference]`` line and kept in ``LAST_AGREEMENT``; judged by nothing.

Sizes come from the configuration file's keys, never from the program's config object; the
``sparse_config`` numbers stand under ``assumed`` (the catalog row's ``config`` lacks them). The
weights are the pytree the program serves (``embed``, ``unembed``, ``final_norm``, and ``sparse`` /
``lightning`` / ``ffn`` stacked by layer kind; the two query projections hold their columns
``[q | gate]`` head by head: a relabelling that random weights cannot tell apart).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ray_tpu.models.minicpm_sala import MiniCPMSALAConfig, init_params, loss_fn, param_logical_axes  # noqa: F401 - the family's names

# sizes of the CPU rehearsal (--rehearse): wiring only, never a measurement; S L L L L L L S, the cell's own shape
REHEARSAL_SIZES = {
    "hidden_size": 64, "num_hidden_layers": 8, "vocab_size": 512, "intermediate_size": 96, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "lightning_nh": 4, "lightning_nkv": 4, "lightning_head_dim": 8, "dim_model_base": 16,
    "mixer_types": ["minicpm4" if l in (0, 2, 9, 10, 11) else "lightning-attn" for l in range(12)],
    "layers_held": [2, 10], "reduced_from": {"num_hidden_layers": 12},
    "assumed": {"chunk_size": 8, "sparse_config": {"kernel_size": 4, "kernel_stride": 2, "block_size": 8, "topk": 4, "window_size": 16,
                                                    "init_blocks": 1, "dense_len": 32}},
}

# the reference pads a sequence to the first of these lengths that holds it (a multiple of the last
# beyond that): every layer is causal, and every distinct length compiles the layer functions anew.
# Every prompt of the cell with its answer then has ONE length, the cell's horizon
PAD_TO = (128, 1024, 12288)
# queries the reference's sparse layer takes at once: 32 heads x 128 x 12,288 float32 scores are 201 MB
QUERY_BLOCK = 128
# FLOPs a state element and position that the Lightning rule itself asks for: the decay (1), the
# rank-one write (2) and the read-out S^T q (2); no way of blocking it needs fewer
RULE_FLOPS = 5.0
LAST_AGREEMENT: dict = {}


def padded_length(n: int) -> int:
    return next((p for p in PAD_TO if p >= n), -(-n // PAD_TO[-1]) * PAD_TO[-1])


def published_depth(c: dict) -> int:
    return int((c.get("reduced_from") or {}).get("num_hidden_layers", c["num_hidden_layers"]))


def held(c: dict) -> list[int]:
    """The published indices of the layers held: ``layers_held`` [first, one past the last], else all."""
    first, stop = c.get("layers_held") or [0, c["num_hidden_layers"]]
    if stop - first != c["num_hidden_layers"] or stop > len(c["mixer_types"]):
        raise ValueError("layers_held names num_hidden_layers of the published mixer_types")
    return list(range(first, stop))


def kinds(c: dict) -> list[str]:
    """``S`` (sparse attention) or ``L`` (Lightning attention) for every layer held, in order."""
    return [{"minicpm4": "S", "lightning-attn": "L"}[c["mixer_types"][l]] for l in held(c)]


def sparse_numbers(c: dict) -> dict:
    return dict(c["assumed"]["sparse_config"])


# ------------------------------------------------------------------------------ the program's side
def program_config(c: dict, max_seq_len: int, **extra) -> MiniCPMSALAConfig:
    """The program's ``MiniCPMSALAConfig`` for a configuration file's published keys."""
    if c["attn_use_rope"] or not c["lightning_use_rope"] or not c["qk_norm"] or c["lightning_nkv"] != c["lightning_nh"]:
        raise ValueError("this family rotates the Lightning layers' queries and keys and nothing else, norms both, and has a key head a query head there")
    if not (c["use_output_gate"] and c["use_output_norm"] and c["attn_use_output_gate"]) or c["lightning_scale"] != "1/sqrt(d)" or c["tie_word_embeddings"]:
        raise ValueError("this family gates both mixers' outputs, norms the Lightning one, scales it by 1/sqrt(d) and has an untied head")
    sp = sparse_numbers(c)
    return MiniCPMSALAConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"], num_hidden_layers=c["num_hidden_layers"],
        published_layers=published_depth(c), first_layer=held(c)[0], mixer_types=tuple(c["mixer_types"]),
        intermediate_size=c["intermediate_size"], num_heads=c["num_attention_heads"], num_kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], sparse_kernel=sp["kernel_size"], sparse_stride=sp["kernel_stride"], sparse_block=sp["block_size"],
        sparse_topk=sp["topk"], sparse_window=sp["window_size"], sparse_init_blocks=sp["init_blocks"], dense_len=sp["dense_len"],
        lightning_nh=c["lightning_nh"], lightning_head_dim=c["lightning_head_dim"], rope_theta=float(c["rope_theta"]),
        chunk_size=int(c["assumed"].get("chunk_size", 128)), scale_emb=float(c["scale_emb"]), scale_depth=float(c["scale_depth"]),
        dim_model_base=c["dim_model_base"], rms_eps=float(c["rms_norm_eps"]), qk_norm_init=float(c.get("init_qk_norm", 1.0)), max_seq_len=max_seq_len,
        dtype={"bfloat16": "bfloat16", "float32": "float32"}[c.get("torch_dtype", "bfloat16")], **extra)


def rehearsal(c: dict) -> dict:
    return {**c, **REHEARSAL_SIZES, "torch_dtype": "float32"}


def kernels_expected(c: dict) -> dict:
    """A bucket of at most ``dense_len`` positions runs the flash kernel in the sparse layers: a
    Pallas kernel lowers to a ``tpu_custom_call``."""
    return {"flash kernel": "tpu_custom_call"}


# ----------------------------------------------------------------------------- operations, from shapes
def layer_params(c: dict) -> dict:
    """Parameters by part: one ``L`` mixer (q, k, v, the output gate and the output projection, the
    two head norms and the output norm), one ``S`` mixer (q and its gate, k, v, the output
    projection, the two head norms), what every layer holds outside its mixer (``rest``: the SwiGLU
    and the two stream norms), embedding plus head."""
    H, F = c["hidden_size"], c["intermediate_size"]
    D, q, kv = c["lightning_nh"] * c["lightning_head_dim"], c["num_attention_heads"] * c["head_dim"], c["num_key_value_heads"] * c["head_dim"]
    return {"L": 5 * H * D + 2 * c["lightning_head_dim"] + D, "S": 3 * H * q + 2 * H * kv + 2 * c["head_dim"],
            "rest": 3 * H * F + 2 * H, "embed_and_head": 2 * c["vocab_size"] * H, "final_norm": H}


def parameters_held(c: dict) -> int:
    p = layer_params(c)
    return sum(p[k] + p["rest"] for k in kinds(c)) + p["embed_and_head"] + p["final_norm"]


def state_bytes_per_slot(c: dict, max_seq_len: int, itemsize: int = 2) -> int:
    """What one sequence keeps beside its keys and values: a float32 state a head in every ``L``
    layer, a compressed key for every ``kernel_stride`` positions of the horizon in every ``S`` layer."""
    ks = kinds(c)
    rows = max_seq_len // sparse_numbers(c)["kernel_stride"]
    return (ks.count("L") * c["lightning_nh"] * c["lightning_head_dim"] ** 2 * 4
            + ks.count("S") * rows * c["num_key_value_heads"] * c["head_dim"] * itemsize)


def kv_bytes_per_token(c: dict, itemsize: int = 2) -> int:
    return kinds(c).count("S") * 2 * c["num_key_value_heads"] * c["head_dim"] * itemsize


def blocks_chosen(c: dict, t: int, chooses: bool) -> int:
    """Blocks a query at position ``t`` reads in one group: those at or before its own, at most ``topk`` where it chooses."""
    sp = sparse_numbers(c)
    return min(t // sp["block_size"] + 1, sp["topk"]) if chooses else t // sp["block_size"] + 1


def _sparse_pairs(c: dict, n: int) -> tuple[float, float]:
    """((query, block) pairs, (query, compressed key) pairs) of one group over a prompt of ``n`` positions."""
    sp = sparse_numbers(c)
    chooses = n > sp["dense_len"]
    pairs = float(sum(blocks_chosen(c, t, chooses) for t in range(n)))
    keys = float(sum(max((t + 1 - sp["kernel_size"]) // sp["kernel_stride"] + 1, 0) for t in range(n))) if chooses else 0.0
    return pairs, keys


def sparse_attend_least(c: dict, lengths: list, itemsize: int = 2) -> dict:
    """What steps 1-5 of ONE ``S`` layer must move and compute for prompts of the TRUE ``lengths``,
    whatever runs them: q read and the output written once, k and v read once (a kernel that keeps
    a tile of queries in fast memory streams the chosen blocks from it; the floor is each key and
    value once), the compressed keys written once. FLOPs: a prompt over ``dense_len`` scores every
    query head against every usable compressed key (2 x head_dim each) and attends, in every head,
    to ``block_size`` positions of each CHOSEN block (2 x 2 x head_dim a position: score and
    weighted sum); a shorter one attends to the blocks at or before the query's. The selection's
    softmax, sums and top-k are not counted."""
    sp, nh, kv, hd = sparse_numbers(c), c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    tokens = float(sum(lengths))
    nbytes = tokens * (2 * nh * hd + 2 * kv * hd) * itemsize + tokens / sp["kernel_stride"] * kv * hd * itemsize
    flops = 0.0
    for n in lengths:
        pairs, keys = _sparse_pairs(c, int(n))
        flops += nh * (4.0 * hd * sp["block_size"] * pairs + 2.0 * hd * keys)
    return {"bytes": float(nbytes), "flops": float(flops)}


def lightning_chunk_least(c: dict, tokens: float, sequences: float = 0.0, itemsize: int = 2) -> dict:
    """What the Lightning rule of ONE ``L`` layer must move and compute for ``tokens`` positions in
    ``sequences`` sequences, whatever runs it: q, k and v read and the output written once (the
    configuration's dtype) and each sequence's state written once (float32; it starts at zero).
    FLOPs: the recurrence's own, ``RULE_FLOPS`` a state element and position, with NO term that
    depends on a chunk size: the count is of the rule, not of one way to run it."""
    nh, d = c["lightning_nh"], c["lightning_head_dim"]
    return {"bytes": float(tokens * 4 * nh * d * itemsize + sequences * nh * d * d * 4), "flops": float(RULE_FLOPS * tokens * nh * d * d)}


def sparse_decode_least(c: dict, blocks: float, itemsize: int = 2) -> dict:
    """What a decode step's sparse attention must move for ``blocks`` chosen blocks (a key-value
    head's share of ``block_size`` positions each, keys and values): each once. FLOPs: 16 heads'
    scores and weighted sums over them."""
    sp, hd = sparse_numbers(c), c["head_dim"]
    rep = c["num_attention_heads"] // c["num_key_value_heads"]
    return {"bytes": float(blocks * 2 * sp["block_size"] * hd * itemsize), "flops": float(blocks * sp["block_size"] * rep * 4 * hd)}


def _per_token_matmul(c: dict) -> float:
    """Multiply-adds per token in the whole stack, without the head: every matrix (norm weights multiply nothing)."""
    p, ks = layer_params(c), kinds(c)
    H = c["hidden_size"]
    mixers = ks.count("L") * (p["L"] - 2 * c["lightning_head_dim"] - c["lightning_nh"] * c["lightning_head_dim"]) \
        + ks.count("S") * (p["S"] - 2 * c["head_dim"])
    return mixers + len(ks) * (p["rest"] - 2 * H)


def prefill_least(c: dict, lengths: list, pairs_local: float = 0.0, experts_hit: float = 0.0, itemsize: int = 2) -> dict:
    """What ONE prefill call over prompts of the TRUE ``lengths`` must move and compute, whatever
    the program: every weight once (the embedding: the prompts' rows), and what it hands the caches
    (keys and values, compressed keys, state). FLOPs at the true lengths: two per weight and token,
    the head at each prompt's last position, the Lightning rule one position at a time and the
    sparse layers' selection and attention over the CHOSEN blocks (``sparse_attend_least``), not
    dense attention's. Nothing is routed: ``pairs_local`` and ``experts_hit`` are what the reader
    passes every family, and are 0 here. Padding to the bucket and to a power of two of prompts is
    the program's choice and is not in here. -> {"bytes", "flops"}."""
    p, ks = layer_params(c), kinds(c)
    H, V = c["hidden_size"], c["vocab_size"]
    tokens = float(sum(lengths))
    weights = sum(p[k] + p["rest"] for k in ks) + H * V + H
    nbytes = (weights + tokens * H) * itemsize + tokens * kv_bytes_per_token(c, itemsize)
    nbytes += len(lengths) * ks.count("L") * c["lightning_nh"] * c["lightning_head_dim"] ** 2 * 4
    flops = 2.0 * tokens * _per_token_matmul(c) + 2.0 * len(lengths) * H * V
    flops += ks.count("L") * lightning_chunk_least(c, tokens)["flops"] + ks.count("S") * sparse_attend_least(c, lengths, itemsize)["flops"]
    return {"bytes": float(nbytes), "flops": float(flops)}


def train_flops_per_token(c: dict, seq: int) -> float:
    """FLOPs the forward and backward passes require per trained token: 6 per weight that
    multiplies the token plus three times the two mixers' own work forward. No recompute. (No cell
    trains this family: 16 bytes a parameter fit no cut of it on one chip; PERF.md section 7.)"""
    per_token = _per_token_matmul(c) + c["hidden_size"] * c["vocab_size"]
    own = kinds(c).count("L") * lightning_chunk_least(c, 1.0)["flops"] + kinds(c).count("S") * sparse_attend_least(c, [seq])["flops"] / seq
    return 6.0 * per_token + 3.0 * own


# --------------------------------------------------------------------------------- the plain reference
def _norm(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _layer_weights(group, i):
    return jax.tree.map(lambda p: jax.lax.dynamic_index_in_dim(p, i, 0, keepdims=False), group)


def _rotate(x, theta):
    """Rotate-half RoPE over all of a head's channels: x [T, heads, d], positions 0 .. T - 1."""
    T, _, d = x.shape
    angles = jnp.arange(T, dtype=jnp.float32)[:, None] * theta ** (-jnp.arange(d // 2, dtype=jnp.float32) / (d // 2))
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("nh", "d", "eps", "theta", "a", "depth"))
def _lightning(x, group, i, layer, *, nh, d, eps, theta, a, depth):
    """One Lightning sub-block on x [T, H], the recurrence one position at a time; ``layer``: its PUBLISHED index."""
    w = jax.tree.map(lambda p: p.astype(jnp.float32), _layer_weights(group, i))
    T = x.shape[0]
    xn = _norm(x, w["norm"], eps)
    qz = (xn @ w["wq"]).reshape(T, nh, 2 * d)  # a head's columns: [q | gate]
    q, z = qz[..., :d], qz[..., d:].reshape(T, nh * d)
    k, v = (xn @ w["wk"]).reshape(T, nh, d), (xn @ w["wv"]).reshape(T, nh, d)
    q, k = _rotate(_norm(q, w["q_norm"], eps), theta), _rotate(_norm(k, w["k_norm"], eps), theta)
    slope = 2.0 ** (-8.0 * jnp.arange(1, nh + 1, dtype=jnp.float32) / nh) * (1.0 - layer / (depth - 1) + 1e-5)
    decay = jnp.exp(-slope)[:, None, None]

    def one_position(S, inp):
        q_t, k_t, v_t = inp  # [nh, d] each
        S = decay * S + jnp.einsum("hk,hv->hkv", k_t, v_t)
        return S, jnp.einsum("hkv,hk->hv", S, q_t) * d ** -0.5

    _, o = jax.lax.scan(one_position, jnp.zeros((nh, d, d), jnp.float32), (q, k, v))
    o = _norm(o.reshape(T, nh * d), w["o_norm"], eps) * jax.nn.sigmoid(z)
    return x + a * (o @ w["wo"])


def _program_selection(w_served, xn, sp, heads, eps):
    """The PROGRAM's selection (``ray_tpu.ops.sparse_attention``) on the reference's layer input xn
    [T, H], given that input in the served dtype -> a function of a block of queries' positions t
    [Q] to bool [Q, G, blocks]: which blocks the program's steps 1-4 choose for them."""
    from ray_tpu.models.minicpm_sala import _Heads
    from ray_tpu.models.qwen3_next import gated_attn_qkv
    from ray_tpu.ops import sparse_attention as sa
    from ray_tpu.ops.layers import rms_norm

    nh, kv, hd = heads
    view = _Heads(nh, kv, hd, 0, 0.0, lambda x, w: rms_norm(x, w, eps))
    T = xn.shape[0]
    q, _, k, _ = gated_attn_qkv(w_served, xn.astype(w_served["wq"].dtype)[None], jnp.arange(T, dtype=jnp.int32), view)
    cfg = sa.SparseConfig(sp["kernel_size"], sp["kernel_stride"], sp["block_size"], sp["topk"], sp["window_size"], sp["init_blocks"], sp["dense_len"])
    kc = sa.compress_keys(k, jnp.full((1,), T, jnp.int32), cfg)

    def choose(t):
        qt = jnp.take(q[0], t, axis=0).reshape(1, t.shape[0], kv, nh // kv, hd)
        blocks, ok = sa.choose_blocks(sa.block_scores(qt, kc, t[None], cfg), t[None], cfg)
        return sa.chosen_mask(blocks, ok, T // sp["block_size"])[0]

    return choose


@functools.partial(jax.jit, static_argnames=("nh", "kv", "hd", "eps", "a", "sp", "agree"))
def _sparse(x, group, i, prompt, *, nh, kv, hd, eps, a, sp, agree):
    """One sparse-attention sub-block on x [T, H]; ``prompt``: how many positions were read as one
    prompt (a later position is computed when the sequence holds it and what came before).
    -> (x, [pairs that choose, pairs whose set equals the program's, summed overlap of the others])."""
    sp = dict(sp)
    kernel, stride, block, topk = sp["kernel_size"], sp["kernel_stride"], sp["block_size"], sp["topk"]
    w_served = _layer_weights(group, i)
    w = jax.tree.map(lambda p: p.astype(jnp.float32), w_served)
    T, rep = x.shape[0], nh // kv
    nb, J = T // block, (T - kernel) // stride + 1
    xn = _norm(x, w["norm"], eps)
    qg = (xn @ w["wq"]).reshape(T, nh, 2 * hd)  # a head's columns: [q | gate]
    q, gate = _norm(qg[..., :hd], w["q_norm"], eps).reshape(T, kv, rep, hd), qg[..., hd:].reshape(T, nh * hd)
    k, v = _norm((xn @ w["wk"]).reshape(T, kv, hd), w["k_norm"], eps), (xn @ w["wv"]).reshape(T, kv, hd)
    # (1) compressed keys, as means of the keys as they stand
    starts = stride * jnp.arange(J)
    Kc = jnp.mean(k[starts[:, None] + jnp.arange(kernel)], axis=1)  # [J, kv, hd]
    overlaps = (starts[:, None] < block * (jnp.arange(nb) + 1)[None, :]) & (starts[:, None] + kernel > block * jnp.arange(nb)[None, :])  # [J, nb]
    at = jnp.arange(T)
    program_chooses = _program_selection(w_served, xn, sp, (nh, kv, hd), eps) if agree else None

    def some_queries(qb):
        q_b, first = qb  # [Q, kv, rep, hd], the position of the block's first query
        t = first + jnp.arange(q_b.shape[0])
        held_then = jnp.maximum(prompt, t + 1)  # positions the sequence held when the query was computed
        usable = (starts + kernel)[None, :] <= (t + 1)[:, None]  # [Q, J]
        s = jnp.einsum("qgrh,jgh->qgrj", q_b, Kc) * hd ** -0.5
        r = jax.nn.softmax(jnp.where(usable[:, None, None], s, -jnp.inf), axis=-1)
        R = jnp.sum(jnp.where(usable[:, None, None], r, 0.0), axis=2)  # (2): [Q, kv, J]; a query with no usable key has none to sum
        score = jnp.max(jnp.where((usable[:, :, None] & overlaps[None])[:, None], R[..., None], -1.0), axis=2)  # (3): [Q, kv, nb]
        own = t // block
        b = jnp.arange(nb)
        forced = (b[None] < sp["init_blocks"]) | (b[None] > own[:, None] - sp["window_size"] // block)
        before = b[None] <= own[:, None]
        ranked = jnp.where(before[:, None], jnp.where(forced[:, None], jnp.inf, score), -jnp.inf)
        first_k = jnp.argsort(-ranked, axis=-1, stable=True)[..., :topk]  # (4): ties to the earlier block
        chosen = jnp.any(first_k[..., None] == b, axis=-2) & before[:, None]  # [Q, kv, nb]
        chooses = held_then > sp["dense_len"]
        read = jnp.where(chooses[:, None, None], chosen, before[:, None])
        allowed = jnp.repeat(read, block, axis=-1) & (at[None] <= t[:, None])[:, None]  # (5): [Q, kv, T]
        scores = jnp.einsum("qgrh,sgh->qgrs", q_b, k) * hd ** -0.5
        o = jnp.einsum("qgrs,sgh->qgrh", jax.nn.softmax(jnp.where(allowed[:, :, None], scores, -jnp.inf), axis=-1), v)
        counts = jnp.zeros((3,), jnp.float32)
        if agree:
            theirs = program_chooses(t)
            judged = (chooses & (own + 1 > topk))[:, None] & jnp.ones((1, kv), bool)
            same = jnp.all(theirs == chosen, axis=-1)
            overlap = jnp.sum(theirs & chosen, axis=-1) / jnp.maximum(jnp.sum(chosen, axis=-1), 1)
            counts = jnp.stack([jnp.sum(judged), jnp.sum(judged & same), jnp.sum(jnp.where(judged & ~same, overlap, 0.0))]).astype(jnp.float32)
        return o, counts

    Q = QUERY_BLOCK if T > QUERY_BLOCK and T % QUERY_BLOCK == 0 else T
    o, counts = jax.lax.map(some_queries, (q.reshape(T // Q, Q, kv, rep, hd), jnp.arange(0, T, Q)))
    y = (o.reshape(T, nh * hd) * jax.nn.sigmoid(gate)) @ w["wo"]
    return x + a * y, jnp.sum(counts, axis=0)


@functools.partial(jax.jit, static_argnames=("eps", "a"))
def _dense(x, group, i, *, eps, a):
    """The SwiGLU sub-block on x [T, H]: W_down (SiLU(W_gate x) * W_up x)."""
    w = jax.tree.map(lambda p: p.astype(jnp.float32), _layer_weights(group, i))
    xn = _norm(x, w["norm"], eps)
    return x + a * ((jax.nn.silu(xn @ w["w_gate"]) * (xn @ w["w_up"])) @ w["w_down"])


@functools.partial(jax.jit, static_argnames=("eps", "over"))
def _head(x, final_norm, unembed, *, eps, over):
    return jax.nn.log_softmax((_norm(x, final_norm.astype(jnp.float32), eps) / over) @ unembed.astype(jnp.float32), axis=-1)


def hidden_states(params: dict, tokens, c: dict, prompt: int = 0, agreement: list | None = None):
    """tokens [T] int32 -> the last layer's output [T, H], float32. ``prompt``: the positions read
    as one prompt (0: every position is computed when the sequence holds it and what came before).
    ``agreement``, if a list, gets each sparse layer's three counts appended (``_sparse``)."""
    eps, sp = float(c["rms_norm_eps"]), sparse_numbers(c)
    depth = published_depth(c)
    a = float(c["scale_depth"]) / math.sqrt(depth)
    seen = {"S": 0, "L": 0}
    with jax.default_matmul_precision("highest"):
        x = float(c["scale_emb"]) * jnp.take(params["embed"], jnp.asarray(tokens, jnp.int32), axis=0).astype(jnp.float32)
        for n, (layer, kind) in enumerate(zip(held(c), kinds(c))):
            i = seen[kind]
            seen[kind] += 1
            if kind == "L":
                x = _lightning(x, params["lightning"], i, layer, nh=c["lightning_nh"], d=c["lightning_head_dim"], eps=eps,
                               theta=float(c["rope_theta"]), a=a, depth=depth)
            else:
                x, counts = _sparse(x, params["sparse"], i, prompt, nh=c["num_attention_heads"], kv=c["num_key_value_heads"], hd=c["head_dim"],
                                    eps=eps, a=a, sp=tuple(sorted(sp.items())), agree=agreement is not None)
                if agreement is not None:
                    agreement.append(counts)
            x = _dense(x, params["ffn"], n, eps=eps, a=a)
    return x


def reference_logprobs(params: dict, tokens, c: dict, start: int, stop: int):
    """Log-probabilities [stop - start, vocab] that the model gives, after reading
    tokens[: p + 1], to the token at position p + 1, for p in [start, stop). The positions up to
    ``start`` were read as ONE prompt, each later one was decoded on its own: that decides which
    queries of a sparse layer choose their blocks (the module's text)."""
    import numpy as np

    tokens = list(tokens) + [0] * (padded_length(len(tokens)) - len(tokens))  # few distinct shapes to compile; every layer is causal
    counts: list = []
    x = hidden_states(params, tokens, c, prompt=start + 1, agreement=counts)[start:stop]
    judged, same, overlap = (float(v) for v in np.sum(np.asarray(counts), axis=0)) if counts else (0.0, 0.0, 0.0)
    for key, value in (("pairs", judged), ("same", same), ("overlap", overlap), ("calls", 1.0)):
        LAST_AGREEMENT[key] = LAST_AGREEMENT.get(key, 0.0) + value
    t = LAST_AGREEMENT
    if t["pairs"]:
        print(f"[reference] selection_agreement: {t['same'] / t['pairs']:.4f} of {int(t['pairs'])} (query, group, sparse layer) pairs that "
              f"choose among more blocks than they read chose the reference's set (the program's selection on the reference's layer "
              f"input in the served dtype); mean overlap of the others {t['overlap'] / max(t['pairs'] - t['same'], 1.0):.4f}; "
              f"over {int(t['calls'])} sample(s) so far", flush=True)
    with jax.default_matmul_precision("highest"):
        return _head(x, params["final_norm"], params["unembed"], eps=float(c["rms_norm_eps"]), over=c["hidden_size"] / c["dim_model_base"])
