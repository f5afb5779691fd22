"""The Nemotron-H hybrid block (``model_type`` ``nemotron_h``): every layer is
``x = x + mixer(RMSNorm(x))`` with one mixer, its kind given by ``hybrid_override_pattern``:
``M`` Mamba-2, ``E`` routed experts with a shared expert, ``*`` grouped-query attention WITHOUT a
position embedding; then a final norm and an untied head. The program's side is
``ray_tpu.models.nemotron_h``; the plain reference below is written from the published
description of the block (PERF.md section 4 repeats the equations), not from that file: one
sequence, float32 at ``highest`` precision, the state-space recurrence one position at a time,
every held expert over every token, one layer's (one expert's) weights cast at a time.

A configuration of this family may be ONE CHIP'S SHARE of a deployment that splits each layer
over several chips by expert parallelism: ``n_routed_experts`` and ``vocab_size`` are then what
is held here, and ``deployment`` says what was published and which part this is. The router keeps
its published width; a token's choice that lives on another chip adds nothing here, in the
program and in the reference alike. Sizes come from the configuration file's keys, never from
the program's config object. The weights are the pytree the program serves (``embed``,
``unembed``, ``final_norm``, and ``mamba`` / ``moe`` / ``attn`` stacked by layer kind).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.models.nemotron_h import NemotronHConfig, init_params, loss_fn, param_logical_axes  # noqa: F401 - the family's names

KIND = {"M": "mamba", "E": "moe", "*": "attn"}

# sizes of the CPU rehearsal (--rehearse): wiring only, never a measurement; all three kinds of layer
REHEARSAL_SIZES = {
    "hidden_size": 64, "hybrid_override_pattern": "ME*ME*ME", "num_hidden_layers": 8, "vocab_size": 512,
    "mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16, "chunk_size": 16,
    "n_routed_experts": 4, "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "deployment": {"chips_per_layer": 2, "experts_published": 8, "experts_held": [0, 4], "vocab_rows_held": [0, 512]},
}


def held(c: dict) -> tuple[int, int, int]:
    """(router width, first expert held, experts held). Without a ``deployment`` the chip holds all."""
    dep = c.get("deployment") or {}
    first = int((dep.get("experts_held") or [0])[0])
    return int(dep.get("experts_published", c["n_routed_experts"])), first, int(c["n_routed_experts"])


# ------------------------------------------------------------------------------ the program's side
def program_config(c: dict, max_seq_len: int, **extra) -> NemotronHConfig:
    """The program's ``NemotronHConfig`` for a configuration file's published keys."""
    width, first, n_held = held(c)
    return NemotronHConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"], layer_pattern=c["hybrid_override_pattern"],
        mamba_num_heads=c["mamba_num_heads"], mamba_head_dim=c["mamba_head_dim"], n_groups=c["n_groups"],
        ssm_state_size=c["ssm_state_size"], conv_kernel=c["conv_kernel"], chunk_size=c["chunk_size"],
        time_step_min=c["time_step_min"], time_step_max=c["time_step_max"], time_step_floor=c["time_step_floor"],
        n_routed_experts=width, expert_start=first, num_local_experts=n_held,
        num_experts_per_tok=c["num_experts_per_tok"], moe_intermediate_size=c["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=c["moe_shared_expert_intermediate_size"],
        routed_scaling_factor=float(c["routed_scaling_factor"]), norm_topk_prob=bool(c["norm_topk_prob"]),
        num_heads=c["num_attention_heads"], num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        rms_eps=float(c["layer_norm_epsilon"]), max_seq_len=max_seq_len,
        # the initialisation's 1/sqrt(N) on the projections back onto the stream: N is the PUBLISHED depth
        residual_rescale_layers=(c.get("reduced_from") or {}).get("num_hidden_layers", c["num_hidden_layers"]) if c.get("rescale_prenorm_residual") else 1,
        router_anchor=float(c.get("init_router_anchor", 0.0)),
        residual_in_fp32=bool(c.get("residual_in_fp32", False)),
        dtype={"bfloat16": "bfloat16", "float32": "float32"}[c.get("torch_dtype", "bfloat16")], **extra)


def rehearsal(c: dict) -> dict:
    return {**c, **REHEARSAL_SIZES, "torch_dtype": "float32"}


def kernels_expected(c: dict) -> dict:
    """The ``*`` layers run the flash attention kernel: a Pallas kernel lowers to a ``tpu_custom_call``."""
    return {"flash kernel": "tpu_custom_call"}


# ----------------------------------------------------------------------------- operations, from shapes
def layer_params(c: dict) -> dict:
    """Parameters of one layer of each kind and of embedding plus head, as held here."""
    H, nh, G, N, K = c["hidden_size"], c["mamba_num_heads"], c["n_groups"], c["ssm_state_size"], c["conv_kernel"]
    di = nh * c["mamba_head_dim"]
    conv = di + 2 * G * N
    width, _, n_held = held(c)
    F, Fs = c["moe_intermediate_size"], c["moe_shared_expert_intermediate_size"]
    q, kv = c["num_attention_heads"] * c["head_dim"], c["num_key_value_heads"] * c["head_dim"]
    expert = 2 * H * F
    return {"M": H + H * (di + conv + nh) + conv * K + conv + 3 * nh + di + di * H,
            "E": H + H * width + width + 2 * H * Fs * c["n_shared_experts"] + n_held * expert,
            "E_outside_routed": H + H * width + width + 2 * H * Fs * c["n_shared_experts"], "expert": expert,
            "*": H + H * q + 2 * H * kv + q * H, "embed_and_head": 2 * c["vocab_size"] * H, "final_norm": H}


def parameters_held(c: dict) -> int:
    p, pat = layer_params(c), c["hybrid_override_pattern"]
    return sum(p[ch] for ch in pat) + p["embed_and_head"] + p["final_norm"]


def state_bytes_per_slot(c: dict, itemsize: int = 2) -> int:
    """What the ``M`` layers keep for one sequence: a float32 state and the convolution's window."""
    nh, P, N = c["mamba_num_heads"], c["mamba_head_dim"], c["ssm_state_size"]
    conv = nh * P + 2 * c["n_groups"] * N
    return c["hybrid_override_pattern"].count("M") * (nh * P * N * 4 + (c["conv_kernel"] - 1) * conv * itemsize)


def kv_bytes_per_token(c: dict, itemsize: int = 2) -> int:
    return c["hybrid_override_pattern"].count("*") * 2 * c["num_key_value_heads"] * c["head_dim"] * itemsize


def decode_step_least(c: dict, lanes: float, experts_hit: float, kv_tokens: float, itemsize: int = 2) -> dict:
    """What ONE decode step must move and compute, whatever the program: every weight outside
    the routed experts once (``M`` and ``*`` layers, routers, shared experts, the head's slice,
    the final norm; the embedding is ``lanes`` rows), ``experts_hit`` routed experts in each
    ``E`` layer, the recurrent state of the ``lanes`` sequences read and written, and the keys
    and values of the ``kv_tokens`` positions those sequences hold. FLOPs: two per weight and
    lane, with the experts a token is routed to HERE (not those hit by others), plus the
    attention over the positions held. -> {"bytes", "flops"}."""
    p, pat = layer_params(c), c["hybrid_override_pattern"]
    nM, nE, nA = pat.count("M"), pat.count("E"), pat.count("*")
    H, V = c["hidden_size"], c["vocab_size"]
    width, _, n_held = held(c)
    fixed = nM * p["M"] + nA * p["*"] + nE * p["E_outside_routed"] + H * V + H
    nbytes = (fixed + nE * experts_hit * p["expert"] + lanes * H) * itemsize
    nbytes += 2 * lanes * state_bytes_per_slot(c, itemsize) + kv_tokens * kv_bytes_per_token(c, itemsize)
    di, q, kv = c["mamba_num_heads"] * c["mamba_head_dim"], c["num_attention_heads"] * c["head_dim"], c["num_key_value_heads"] * c["head_dim"]
    routed_here = c["num_experts_per_tok"] * n_held / width  # experts of a token that live on this chip, on average
    per_token = (nM * (H * (2 * di + 2 * c["n_groups"] * c["ssm_state_size"] + c["mamba_num_heads"]) + di * H)
                 + nA * (2 * H * q + 2 * H * kv)
                 + nE * (H * width + 2 * H * c["moe_shared_expert_intermediate_size"] * c["n_shared_experts"] + routed_here * p["expert"])
                 + H * V)
    flops = 2.0 * lanes * per_token + 4.0 * kv_tokens * nA * q
    return {"bytes": float(nbytes), "flops": float(flops)}


def train_flops_per_token(c: dict, seq: int) -> float:
    """FLOPs the forward and backward passes require per trained token: 6 per weight that
    multiplies the token (the experts it is routed to here, not all that are held) plus three
    times the causal attention and the state-space recurrence forward. No recompute."""
    one = decode_step_least(c, 1, 0, 0)["flops"]
    nM, nA = c["hybrid_override_pattern"].count("M"), c["hybrid_override_pattern"].count("*")
    attn = 2.0 * seq * nA * c["num_attention_heads"] * c["head_dim"]  # QK^T and PV over seq/2 positions on average
    ssm = 6.0 * nM * c["mamba_num_heads"] * c["mamba_head_dim"] * c["ssm_state_size"]  # decay, update, read-out
    return 3.0 * (one + attn + ssm)


# --------------------------------------------------------------------------------- the plain reference
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _layer_weights(group, i):
    return jax.tree.map(lambda p: jax.lax.dynamic_index_in_dim(p, i, 0, keepdims=False), group)


@functools.partial(jax.jit, static_argnames=("nh", "P", "G", "N", "K", "eps"))
def _mamba(x, group, i, *, nh, P, G, N, K, eps):
    """One Mamba-2 layer on x [T, H]: in_proj -> (z | x B C | dt); causal depthwise convolution
    of width K with bias, SiLU; dt = softplus(dt + dt_bias), A = -exp(A_log); per head
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T, y_t = S_t C_t + D x_t, one position at a time;
    RMSNorm of y * SiLU(z) in G groups under one weight; out_proj."""
    w = jax.tree.map(lambda p: p.astype(jnp.float32), _layer_weights(group, i))
    T, di = x.shape[0], nh * P
    u = _rms(x, w["norm"], eps) @ w["in_proj"]
    z, xbc, dt = u[:, :di], u[:, di:di + di + 2 * G * N], u[:, di + di + 2 * G * N:]
    past = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), jnp.float32), xbc])
    xbc = jax.nn.silu(sum(past[k:k + T] * w["conv_w"][k] for k in range(K)) + w["conv_b"])
    xs = xbc[:, :di].reshape(T, nh, P)
    Bs = jnp.repeat(xbc[:, di:di + G * N].reshape(T, G, N), nh // G, axis=1)
    Cs = jnp.repeat(xbc[:, di + G * N:].reshape(T, G, N), nh // G, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    A = -jnp.exp(w["A_log"])

    def one_position(S, inp):
        x_t, B_t, C_t, dt_t = inp
        S = jnp.exp(dt_t * A)[:, None, None] * S + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        return S, jnp.sum(S * C_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(one_position, jnp.zeros((nh, P, N), jnp.float32), (xs, Bs, Cs, dt))
    y = ((y + w["D"][:, None] * xs).reshape(T, di) * jax.nn.silu(z)).reshape(T, G, di // G)
    y = (y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)).reshape(T, di) * w["gate_norm"]
    return x + y @ w["out_proj"]


@functools.partial(jax.jit, static_argnames=("first", "top_k", "scale", "norm", "eps"))
def _experts(x, group, i, *, first, top_k, scale, norm, eps):
    """One expert layer on x [T, H]: sigmoid scores over the router's whole width, the top_k of
    score + correction bias, their scores normalised and scaled as weights; every HELD expert
    (W_down relu(W_up x)^2) over every token, one expert at a time, weighted by what the router
    gave it (nothing where it was not chosen, and nothing for a choice held elsewhere); plus the
    shared expert of the same form for every token."""
    w = _layer_weights(group, i)
    small = {k: w[k].astype(jnp.float32) for k in ("norm", "router", "router_bias", "shared_up", "shared_down")}
    xn = _rms(x, small["norm"], eps)
    s = jax.nn.sigmoid(xn @ small["router"])
    _, idx = jax.lax.top_k(s + small["router_bias"], top_k)
    wt = jnp.take_along_axis(s, idx, axis=-1)
    wt = (wt / (jnp.sum(wt, axis=-1, keepdims=True) + 1e-20) if norm else wt) * scale
    given = jnp.zeros_like(s).at[jnp.arange(x.shape[0])[:, None], idx].set(wt)  # [T, router width]

    def one_expert(e, acc):
        up, down = w["w_up"][e].astype(jnp.float32), w["w_down"][e].astype(jnp.float32)
        return acc + given[:, first + e, None] * (jnp.square(jax.nn.relu(xn @ up.T)) @ down)  # W_up is stored [F, H]

    y = jax.lax.fori_loop(0, w["w_up"].shape[0], one_expert, jnp.zeros_like(x))
    return x + y + jnp.square(jax.nn.relu(xn @ small["shared_up"])) @ small["shared_down"], idx


@functools.partial(jax.jit, static_argnames=("nh", "nkv", "hd", "eps"))
def _attention(x, group, i, *, nh, nkv, hd, eps):
    """One attention layer on x [T, H]: causal softmax(Q K^T / sqrt hd) V over grouped heads,
    no rotary or other position embedding, o_proj."""
    w = jax.tree.map(lambda p: p.astype(jnp.float32), _layer_weights(group, i))
    T = x.shape[0]
    xn = _rms(x, w["norm"], eps)
    q = (xn @ w["wq"]).reshape(T, nh, hd)
    k = jnp.repeat((xn @ w["wk"]).reshape(T, nkv, hd), nh // nkv, axis=1)
    v = jnp.repeat((xn @ w["wv"]).reshape(T, nkv, hd), nh // nkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    return x + jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v).reshape(T, nh * hd) @ w["wo"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, unembed, *, eps):
    return jax.nn.log_softmax(_rms(x, final_norm.astype(jnp.float32), eps) @ unembed.astype(jnp.float32), axis=-1)


def hidden_states(params: dict, tokens, c: dict, choices: list | None = None):
    """tokens [T] int32 -> the last layer's output [T, H], float32. ``choices``, if a list, gets
    each expert layer's chosen experts [T, top_k] appended (for the router-agreement count)."""
    eps, seen = float(c["layer_norm_epsilon"]), {"mamba": 0, "moe": 0, "attn": 0}
    _, first, _ = held(c)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(tokens, jnp.int32), axis=0).astype(jnp.float32)
        for ch in c["hybrid_override_pattern"]:
            kind = KIND[ch]
            i, seen[kind] = seen[kind], seen[kind] + 1
            if kind == "mamba":
                x = _mamba(x, params["mamba"], i, nh=c["mamba_num_heads"], P=c["mamba_head_dim"], G=c["n_groups"],
                           N=c["ssm_state_size"], K=c["conv_kernel"], eps=eps)
            elif kind == "moe":
                x, idx = _experts(x, params["moe"], i, first=first, top_k=c["num_experts_per_tok"],
                                  scale=float(c["routed_scaling_factor"]), norm=bool(c["norm_topk_prob"]), eps=eps)
                if choices is not None:
                    choices.append(idx)
            else:
                x = _attention(x, params["attn"], i, nh=c["num_attention_heads"], nkv=c["num_key_value_heads"],
                               hd=c["head_dim"], eps=eps)
    return x


def reference_logprobs(params: dict, tokens, c: dict, start: int, stop: int):
    """Log-probabilities [stop - start, vocab] that the model gives, after reading
    tokens[: p + 1], to the token at position p + 1, for p in [start, stop)."""
    x = hidden_states(params, tokens, c)[start:stop]
    with jax.default_matmul_precision("highest"):
        return _head(x, params["final_norm"], params["unembed"], eps=float(c["layer_norm_epsilon"]))
