"""The Qwen3-Next hybrid block (``model_type`` ``qwen3_next``): every published decoder layer is
two residual sub-blocks, ``x = x + mixer(N(x))`` then ``x = x + experts(N(x))``, with
``N(x) = x / sqrt(mean(x²) + eps) * (1 + w)``; layer ``i`` mixes by gated full attention when
``(i + 1) % full_attention_interval == 0`` and by Gated DeltaNet otherwise; then a final ``N`` and
an untied head. The program's side is ``ray_tpu.models.qwen3_next``; the plain reference below is
written from the published description of the block and the catalog row's ``config`` (PERF.md
section 4 repeats the equations), not from that file: one sequence, float32 at ``highest``
precision, the delta rule ONE POSITION AT A TIME, full softmax attention, every held expert over
every token, one layer's (one expert's) weights cast at a time.

A configuration of this family may be ONE CHIP'S SHARE of a deployment that splits each layer
over several chips by expert parallelism: ``num_experts`` and ``vocab_size`` are then what is held
here, and ``deployment`` says what was published and which part this is. The router keeps its
published width and its experts per token; a token's choice that lives on another chip adds
nothing here, in the program and in the reference alike. Sizes come from the configuration file's
keys, never from the program's config object. The weights are the pytree the program serves
(``embed``, ``unembed``, ``final_norm``, and ``gdn`` / ``attn`` / ``moe`` stacked by layer kind;
the DeltaNet's two projections hold their columns flat, ``[q | k | v | z]`` and ``[b | a]``).

Departures from the published model, each of which program and reference share:
- the checkpoint's one multi-token-prediction head is left out (the published ``config`` has no
  key for it, and the published modelling code neither loads nor runs it);
- weights are random from a seed (``assumed`` in the configuration file says how), the routers and
  the embedding table anchored (``init_router_anchor``) as PR 29 found necessary;
- the flat column order of the DeltaNet's projections is a relabelling of the published
  per-key-head interleaving, which random weights cannot tell apart.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.models.qwen3_next import Qwen3NextConfig, init_params, loss_fn, param_logical_axes  # noqa: F401 - the family's names

# sizes of the CPU rehearsal (--rehearse): wiring only, never a measurement; every kind of layer
REHEARSAL_SIZES = {
    "hidden_size": 64, "num_hidden_layers": 5, "full_attention_interval": 2, "vocab_size": 512,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4, "linear_key_head_dim": 8, "linear_value_head_dim": 8,
    "num_experts": 4, "num_experts_per_tok": 2, "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "init_router_anchor": 0.0,
    "assumed": {"chunk_size": 8},
    "deployment": {"chips_per_layer": 2, "experts_published": 8, "experts_held": [0, 4], "vocab_rows_held": [0, 512]},
}


# the reference pads a sequence to a multiple of this: a first run of the cell compiles four layer
# functions for each distinct length (its check of five served samples took 72 s, most of it
# compiles, at the harness's multiples of 256; my chip run, PR 34), and what follows a position
# moves nothing before it
PAD_TO = 1024


def held(c: dict) -> tuple[int, int, int]:
    """(router width, first expert held, experts held). Without a ``deployment`` the chip holds all."""
    dep = c.get("deployment") or {}
    first = int((dep.get("experts_held") or [0])[0])
    return int(dep.get("experts_published", c["num_experts"])), first, int(c["num_experts"])


def kinds(c: dict) -> list[str]:
    """The mixer of every published layer held here: ``D`` Gated DeltaNet, ``G`` gated attention."""
    return ["G" if (i + 1) % c["full_attention_interval"] == 0 else "D" for i in range(c["num_hidden_layers"])]


# ------------------------------------------------------------------------------ the program's side
def program_config(c: dict, max_seq_len: int, **extra) -> Qwen3NextConfig:
    """The program's ``Qwen3NextConfig`` for a configuration file's published keys."""
    width, first, n_held = held(c)
    published_depth = (c.get("reduced_from") or {}).get("num_hidden_layers", c["num_hidden_layers"])
    return Qwen3NextConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"], num_hidden_layers=c["num_hidden_layers"],
        full_attention_interval=c["full_attention_interval"], linear_num_key_heads=c["linear_num_key_heads"],
        linear_num_value_heads=c["linear_num_value_heads"], linear_key_head_dim=c["linear_key_head_dim"],
        linear_value_head_dim=c["linear_value_head_dim"], conv_kernel=c["linear_conv_kernel_dim"],
        chunk_size=int((c.get("assumed") or {}).get("chunk_size", 64)),
        num_experts=width, expert_start=first, num_local_experts=n_held, num_experts_per_tok=c["num_experts_per_tok"],
        moe_intermediate_size=c["moe_intermediate_size"], shared_expert_intermediate_size=c["shared_expert_intermediate_size"],
        norm_topk_prob=bool(c["norm_topk_prob"]), num_heads=c["num_attention_heads"], num_kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], partial_rotary_factor=float(c["partial_rotary_factor"]), rope_theta=float(c["rope_theta"]),
        rms_eps=float(c["rms_norm_eps"]), max_seq_len=max_seq_len,
        # the initialisation's 1/sqrt(N) on the projections back onto the stream: N counts the PUBLISHED sub-blocks
        residual_rescale_layers=2 * published_depth, router_anchor=float(c.get("init_router_anchor", 0.0)),
        dtype={"bfloat16": "bfloat16", "float32": "float32"}[c.get("torch_dtype", "bfloat16")], **extra)


def rehearsal(c: dict) -> dict:
    return {**c, **REHEARSAL_SIZES, "torch_dtype": "float32"}


def kernels_expected(c: dict) -> dict:
    """The ``G`` layers run the flash attention kernel: a Pallas kernel lowers to a ``tpu_custom_call``."""
    return {"flash kernel": "tpu_custom_call"}


# ----------------------------------------------------------------------------- operations, from shapes
def layer_params(c: dict) -> dict:
    """Parameters of one published layer of each kind, as held here: ``D`` and ``G`` OUTSIDE their
    routed experts (mixer, router, shared expert and its gate, both norms), one routed ``expert``,
    and embedding plus head."""
    H, K = c["hidden_size"], c["linear_conv_kernel_dim"]
    nk, nv, dk, dv = c["linear_num_key_heads"], c["linear_num_value_heads"], c["linear_key_head_dim"], c["linear_value_head_dim"]
    width, _, _ = held(c)
    F, Fs = c["moe_intermediate_size"], c["shared_expert_intermediate_size"]
    q, kv, hd = c["num_attention_heads"] * c["head_dim"], c["num_key_value_heads"] * c["head_dim"], c["head_dim"]
    outside = H * width + 3 * H * Fs + H + 2 * H  # router, shared expert, its gate, the two norms
    gdn = H * (2 * nk * dk + 2 * nv * dv) + H * 2 * nv + K * (2 * nk * dk + nv * dv) + 2 * nv + dv + nv * dv * H
    attn = H * 2 * q + 2 * H * kv + q * H + 2 * hd
    return {"D": gdn + outside, "G": attn + outside, "D_mixer": gdn, "G_mixer": attn, "outside_mixer": outside,
            "expert": 3 * H * F, "embed_and_head": 2 * c["vocab_size"] * H, "final_norm": H}


def parameters_held(c: dict) -> int:
    p, (_, _, n_held) = layer_params(c), held(c)
    return sum(p[k] + n_held * p["expert"] for k in kinds(c)) + p["embed_and_head"] + p["final_norm"]


def state_bytes_per_slot(c: dict, itemsize: int = 2) -> int:
    """What the ``D`` layers keep for one sequence: a float32 state a value head and the convolution's window."""
    nk, nv, dk, dv = c["linear_num_key_heads"], c["linear_num_value_heads"], c["linear_key_head_dim"], c["linear_value_head_dim"]
    conv = 2 * nk * dk + nv * dv
    return kinds(c).count("D") * (nv * dk * dv * 4 + (c["linear_conv_kernel_dim"] - 1) * conv * itemsize)


def kv_bytes_per_token(c: dict, itemsize: int = 2) -> int:
    return kinds(c).count("G") * 2 * c["num_key_value_heads"] * c["head_dim"] * itemsize


def _per_token_matmul(c: dict, routed_here: float) -> float:
    """Multiply-adds per token in the whole stack and the head: every matrix (the convolution's
    K taps a channel among them; norm weights and per-head scalars multiply nothing), with
    ``routed_here`` of a token's chosen experts held on this chip (a mean) in each expert block."""
    p, ks = layer_params(c), kinds(c)
    H = c["hidden_size"]
    d = p["D_mixer"] - 2 * c["linear_num_value_heads"] - c["linear_value_head_dim"]
    g = p["G_mixer"] - 2 * c["head_dim"]
    block = p["outside_mixer"] - 2 * H + routed_here * p["expert"]  # router, shared expert, its gate
    return ks.count("D") * d + ks.count("G") * g + len(ks) * block + H * c["vocab_size"]


def decode_step_least(c: dict, lanes: float, experts_hit: float, kv_tokens: float, itemsize: int = 2) -> dict:
    """What ONE decode step must move and compute, whatever the program: every weight outside
    the routed experts once (mixers, routers, shared experts, norms, the head's slice, the final
    norm; the embedding is ``lanes`` rows), ``experts_hit`` routed experts in each expert block,
    the recurrent state of the ``lanes`` sequences read and written, and the keys and values of
    the ``kv_tokens`` positions those sequences hold. FLOPs: two per weight and lane, with the
    experts a token is routed to HERE (a mean: top-k x held / published), the rule's decay,
    read, write and read-out of the state (8 per state element), plus the attention over the
    positions held. -> {"bytes", "flops"}."""
    p, ks = layer_params(c), kinds(c)
    H, V = c["hidden_size"], c["vocab_size"]
    width, _, n_held = held(c)
    nD, nG = ks.count("D"), ks.count("G")
    fixed = nD * p["D"] + nG * p["G"] + H * V + H
    nbytes = (fixed + len(ks) * experts_hit * p["expert"] + lanes * H) * itemsize
    nbytes += 2 * lanes * state_bytes_per_slot(c, itemsize) + kv_tokens * kv_bytes_per_token(c, itemsize)
    state = c["linear_num_value_heads"] * c["linear_key_head_dim"] * c["linear_value_head_dim"]
    per_token = _per_token_matmul(c, c["num_experts_per_tok"] * n_held / width)
    flops = 2.0 * lanes * per_token + 8.0 * lanes * nD * state + 4.0 * kv_tokens * nG * c["num_attention_heads"] * c["head_dim"]
    return {"bytes": float(nbytes), "flops": float(flops)}


def prefill_least(c: dict, lengths: list, pairs_local: float, experts_hit: float, itemsize: int = 2) -> dict:
    """What ONE prefill call over prompts of the TRUE ``lengths`` must move and compute, whatever
    the program: every weight outside the routed experts once, ``experts_hit`` routed experts in
    each expert block once (the held experts that got a pair; a mean over the blocks), the
    prompts' embedding rows, and what it hands the caches (keys, values, state). FLOPs at the
    true lengths: two per weight outside the routed experts and token, two per expert weight and
    (token, expert) pair routed HERE (``pairs_local``: a mean over the blocks), causal attention
    (each query against the positions up to its own: 4 x heads x head width each), and the rule's
    state update and read-out one position at a time (8 per state element and token: no blocking
    of the rule can need fewer, and the chunked form needs more). Padding to the bucket and to a
    power of two of prompts is the program's choice and is not in here. -> {"bytes", "flops"}."""
    p, ks = layer_params(c), kinds(c)
    H, V = c["hidden_size"], c["vocab_size"]
    nD, nG = ks.count("D"), ks.count("G")
    tokens = float(sum(lengths))
    fixed = nD * p["D"] + nG * p["G"] + H * V + H
    nbytes = (fixed + len(ks) * experts_hit * p["expert"] + tokens * H) * itemsize
    nbytes += len(lengths) * state_bytes_per_slot(c, itemsize) + tokens * kv_bytes_per_token(c, itemsize)
    state = c["linear_num_value_heads"] * c["linear_key_head_dim"] * c["linear_value_head_dim"]
    outside = _per_token_matmul(c, 0.0) - H * V
    causal = sum(n * (n + 1) / 2.0 for n in lengths)
    flops = (2.0 * tokens * outside + 2.0 * len(lengths) * H * V  # the head reads each prompt's last position only
             + 2.0 * len(ks) * pairs_local * p["expert"] + 8.0 * tokens * nD * state
             + 4.0 * causal * nG * c["num_attention_heads"] * c["head_dim"])
    return {"bytes": float(nbytes), "flops": float(flops)}


def train_flops_per_token(c: dict, seq: int) -> float:
    """FLOPs the forward and backward passes require per trained token: 6 per weight that
    multiplies the token (the experts it is routed to here, not all that are held) plus three
    times the causal attention and the rule forward. No recompute. (No cell trains this family.)"""
    one = decode_step_least(c, 1, 0, 0)["flops"]
    attn = 2.0 * seq * kinds(c).count("G") * c["num_attention_heads"] * c["head_dim"]
    return 3.0 * (one + attn)


# --------------------------------------------------------------------------------- the plain reference
def _norm1p(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def _layer_weights(group, i):
    return jax.tree.map(lambda p: jax.lax.dynamic_index_in_dim(p, i, 0, keepdims=False), group)


@functools.partial(jax.jit, static_argnames=("nk", "nv", "dk", "dv", "K", "eps"))
def _deltanet(x, group, i, *, nk, nv, dk, dv, K, eps):
    """One Gated DeltaNet sub-block on x [T, H]: the projection to (q | k | v | z) and to (b | a);
    causal depthwise convolution of width K without bias over (q, k, v), SiLU; q and k
    L2-normalised per head, q times dk^-1/2, a key head serving nv/nk value heads;
    beta = sigmoid(b), alpha = exp(-exp(A_log) softplus(a + dt_bias)); per value head, one
    position at a time: S' = alpha S; S = S' + k (beta (v - S'^T k))^T; o = S^T q; then
    w * o / sqrt(mean(o²) + eps) * SiLU(z) per head, and the output projection."""
    w = jax.tree.map(lambda p: p.astype(jnp.float32), _layer_weights(group, i))
    T, kd, vd = x.shape[0], nk * dk, nv * dv
    xn = _norm1p(x, w["norm"], eps)
    u, ba = xn @ w["in_qkvz"], xn @ w["in_ba"]
    mixed, z = u[:, :2 * kd + vd], u[:, 2 * kd + vd:].reshape(T, nv, dv)
    past = jnp.concatenate([jnp.zeros((K - 1, mixed.shape[1]), jnp.float32), mixed])
    mixed = jax.nn.silu(sum(past[j:j + T] * w["conv_w"][j] for j in range(K)))
    unit = lambda a: a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    q = jnp.repeat(unit(mixed[:, :kd].reshape(T, nk, dk)) * dk ** -0.5, nv // nk, axis=1)
    k = jnp.repeat(unit(mixed[:, kd:2 * kd].reshape(T, nk, dk)), nv // nk, axis=1)
    v = mixed[:, 2 * kd:].reshape(T, nv, dv)
    beta = jax.nn.sigmoid(ba[:, :nv])
    alpha = jnp.exp(-jnp.exp(w["A_log"]) * jax.nn.softplus(ba[:, nv:] + w["dt_bias"]))

    def one_position(S, inp):
        q_t, k_t, v_t, beta_t, alpha_t = inp  # [nv, dk], [nv, dk], [nv, dv], [nv], [nv]
        S = alpha_t[:, None, None] * S
        read = jnp.einsum("hkv,hk->hv", S, k_t)
        S = S + jnp.einsum("hk,hv->hkv", k_t, beta_t[:, None] * (v_t - read))
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(one_position, jnp.zeros((nv, dk, dv), jnp.float32), (q, k, v, beta, alpha))
    o = w["gate_norm"] * o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * jax.nn.silu(z)
    return x + o.reshape(T, vd) @ w["out_proj"]


@functools.partial(jax.jit, static_argnames=("nh", "nkv", "hd", "rot", "theta", "eps"))
def _gated_attention(x, group, i, *, nh, nkv, hd, rot, theta, eps):
    """One gated attention sub-block on x [T, H]: per head a query and a gate; k, v on nkv heads;
    q and k normalised per head with N; rotate-half RoPE on each head's first ``rot`` dimensions,
    the rest passing; causal softmax(q k^T / sqrt hd) v, grouped; o * sigmoid(gate); o_proj."""
    w = jax.tree.map(lambda p: p.astype(jnp.float32), _layer_weights(group, i))
    T = x.shape[0]
    xn = _norm1p(x, w["norm"], eps)
    qg = (xn @ w["wq"]).reshape(T, nh, 2 * hd)
    q, gate = _norm1p(qg[..., :hd], w["q_norm"], eps), qg[..., hd:]
    k = _norm1p((xn @ w["wk"]).reshape(T, nkv, hd), w["k_norm"], eps)
    v = (xn @ w["wv"]).reshape(T, nkv, hd)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)  # [T, rot/2]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]

    def rope(a):
        a1, a2, rest = a[..., :rot // 2], a[..., rot // 2:rot], a[..., rot:]
        return jnp.concatenate([a1 * cos - a2 * sin, a2 * cos + a1 * sin, rest], axis=-1)

    q, k = rope(q), jnp.repeat(rope(k), nh // nkv, axis=1)
    v = jnp.repeat(v, nh // nkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v) * jax.nn.sigmoid(gate)
    return x + o.reshape(T, nh * hd) @ w["wo"]


@functools.partial(jax.jit, static_argnames=("first", "top_k", "norm", "eps"))
def _experts(x, group, i, *, first, top_k, norm, eps):
    """One expert sub-block on x [T, H]: softmax over the router's whole width, the top_k, their
    probabilities normalised to sum to 1; every HELD expert (W_down (SiLU(W_gate x) * W_up x))
    over every token, one expert at a time, weighted by what the router gave it (nothing where
    it was not chosen, and nothing for a choice held elsewhere); plus the shared expert of the
    same form times sigmoid(x . w_sg) for every token."""
    w = _layer_weights(group, i)
    small = {k: w[k].astype(jnp.float32) for k in ("norm", "router", "shared_gate", "shared_up", "shared_down", "shared_sg")}
    xn = _norm1p(x, small["norm"], eps)
    prob = jax.nn.softmax(xn @ small["router"], axis=-1)
    wt, idx = jax.lax.top_k(prob, top_k)
    wt = wt / jnp.sum(wt, axis=-1, keepdims=True) if norm else wt
    given = jnp.zeros_like(prob).at[jnp.arange(x.shape[0])[:, None], idx].set(wt)  # [T, router width]

    def one_expert(e, acc):
        gate, up, down = (w[n][e].astype(jnp.float32) for n in ("w_gate", "w_up", "w_down"))  # each stored [F, H]
        return acc + given[:, first + e, None] * ((jax.nn.silu(xn @ gate.T) * (xn @ up.T)) @ down)

    y = jax.lax.fori_loop(0, w["w_up"].shape[0], one_expert, jnp.zeros_like(x))
    shared = (jax.nn.silu(xn @ small["shared_gate"]) * (xn @ small["shared_up"])) @ small["shared_down"]
    return x + y + jax.nn.sigmoid(xn @ small["shared_sg"])[:, None] * shared, idx


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, unembed, *, eps):
    return jax.nn.log_softmax(_norm1p(x, final_norm.astype(jnp.float32), eps) @ unembed.astype(jnp.float32), axis=-1)


def hidden_states(params: dict, tokens, c: dict, choices: list | None = None):
    """tokens [T] int32 -> the last layer's output [T, H], float32. ``choices``, if a list, gets
    each expert block's chosen experts [T, top_k] appended (for the router-agreement count)."""
    eps, seen = float(c["rms_norm_eps"]), {"D": 0, "G": 0}
    _, first, _ = held(c)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(tokens, jnp.int32), axis=0).astype(jnp.float32)
        for block, kind in enumerate(kinds(c)):
            i, seen[kind] = seen[kind], seen[kind] + 1
            if kind == "D":
                x = _deltanet(x, params["gdn"], i, nk=c["linear_num_key_heads"], nv=c["linear_num_value_heads"],
                              dk=c["linear_key_head_dim"], dv=c["linear_value_head_dim"], K=c["linear_conv_kernel_dim"], eps=eps)
            else:
                x = _gated_attention(x, params["attn"], i, nh=c["num_attention_heads"], nkv=c["num_key_value_heads"],
                                     hd=c["head_dim"], rot=int(c["head_dim"] * c["partial_rotary_factor"]),
                                     theta=float(c["rope_theta"]), eps=eps)
            x, idx = _experts(x, params["moe"], block, first=first, top_k=c["num_experts_per_tok"],
                              norm=bool(c["norm_topk_prob"]), eps=eps)
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
