"""The plain reference: the block's forward pass in straightforward ``jax.numpy``, float32 at
``highest`` matmul precision, no cache, no kernel, no batching, one layer's weights cast at a
time so that it fits beside the engine or the train state on the same chip.

Written from the published description of the block (pre-norm decoder: RMSNorm, rotary position
embedding on q and k in the rotate-half convention, grouped-query causal attention, SwiGLU feed
forward, no biases, untied output head), not from ``ray_tpu.models.llama``. Sizes come from a
configuration file's published keys. The weights are the pytree the program serves or trains
(``embed``, ``unembed``, ``final_norm``, ``layers`` stacked on a leading axis), read, never
copied whole.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [T, heads, hd]. Rotate-half convention: pairs (i, i + hd/2) turn by pos * theta^(-2i/hd)."""
    T, _, hd = x.shape
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("nh", "nkv", "hd", "theta", "eps"))
def _layer(x, layers, i, *, nh, nkv, hd, theta, eps):
    """One block on x [T, H] in float32; ``layers`` is the stacked pytree, ``i`` the layer."""
    w = jax.tree.map(lambda p: jax.lax.dynamic_index_in_dim(p, i, 0, keepdims=False).astype(jnp.float32), layers)
    T = x.shape[0]
    xn = _rms(x, w["attn_norm"], eps)
    q = _rope((xn @ w["wq"]).reshape(T, nh, hd), theta)
    k = _rope((xn @ w["wk"]).reshape(T, nkv, hd), theta)
    v = (xn @ w["wv"]).reshape(T, nkv, hd)
    k, v = jnp.repeat(k, nh // nkv, axis=1), jnp.repeat(v, nh // nkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v).reshape(T, nh * hd)
    x = x + o @ w["wo"]
    xn = _rms(x, w["mlp_norm"], eps)
    return x + (jax.nn.silu(xn @ w["w_gate"]) * (xn @ w["w_up"])) @ w["w_down"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, unembed, *, eps):
    return jax.nn.log_softmax(_rms(x, final_norm.astype(jnp.float32), eps) @ unembed.astype(jnp.float32), axis=-1)


def hidden_states(params: dict, tokens, c: dict):
    """tokens [T] int32 -> the last block's output [T, H], float32."""
    hd = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(tokens, jnp.int32), axis=0).astype(jnp.float32)
        for i in range(c["num_hidden_layers"]):
            x = _layer(x, params["layers"], i, nh=c["num_attention_heads"], nkv=c["num_key_value_heads"],
                       hd=hd, theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]))
    return x


def logprobs(params: dict, tokens, c: dict, start: int, stop: int):
    """Log-probabilities [stop - start, vocab] that the model gives, after reading
    tokens[: p + 1], to the token at position p + 1, for p in [start, stop)."""
    x = hidden_states(params, tokens, c)[start:stop]
    with jax.default_matmul_precision("highest"):
        return _head(x, params["final_norm"], params["unembed"], eps=float(c["rms_norm_eps"]))


def loss(params: dict, batch: dict, c: dict) -> float:
    """Mean next-token cross entropy over the batch's valid targets (-100 = ignored), one
    sequence at a time."""
    import numpy as np

    total, count = 0.0, 0
    for toks, tgts in zip(np.asarray(batch["tokens"]), np.asarray(batch["targets"])):
        lp = np.asarray(logprobs(params, toks, c, 0, len(toks)))
        valid = tgts >= 0
        total += float(-lp[np.arange(len(toks))[valid], tgts[valid]].sum(dtype=np.float64))
        count += int(valid.sum())
    return total / max(count, 1)


def check_served(params: dict, c: dict, samples: list[dict], tol: float) -> dict:
    """Teacher-force served tokens through the plain forward: every emitted token's served
    log-probability must agree with the reference's within ``tol``, and every greedy token must
    be the reference's top-1 or within ``tol`` of it (a tie that rounding broke the other way).
    ``samples``: dicts with ``prompt``, ``tokens``, ``logprobs`` and ``greedy``. Each position is
    judged on the prefix that was really served."""
    import numpy as np

    worst_lp, worst_margin, top1, n_greedy, n_tok, fails = 0.0, 0.0, 0, 0, 0, []
    for j, s in enumerate(samples):
        n, toks = len(s["prompt"]), list(s["prompt"]) + list(s["tokens"])
        pad = -len(toks) % 256  # few distinct shapes; causal attention ignores what follows
        lp = np.asarray(logprobs(params, toks + [0] * pad, c, n - 1, n - 1 + len(s["tokens"])))
        for t, tok in enumerate(s["tokens"]):
            n_tok += 1
            d = abs(float(lp[t, tok]) - float(s["logprobs"][t]))
            worst_lp = max(worst_lp, d)
            if d > tol:
                fails.append(f"sample {j} token {t}: served logprob {s['logprobs'][t]:.4f}, reference {lp[t, tok]:.4f}")
                break
            if s["greedy"]:
                n_greedy += 1
                margin = float(lp[t].max() - lp[t, tok])
                worst_margin = max(worst_margin, margin)
                top1 += margin == 0.0
                if margin > tol:
                    fails.append(f"sample {j} token {t}: served {tok}, reference top-1 {int(lp[t].argmax())} leads by {margin:.4f}")
                    break
    return {"ok": not fails and n_tok > 0, "tokens": n_tok, "greedy_tokens": n_greedy, "greedy_top1": top1,
            "max_abs_dlogprob": worst_lp, "max_margin": worst_margin, "tolerance": tol, "failures": fails[:5]}
