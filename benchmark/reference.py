"""What is generic in the comparison with the plain reference: the training loss over a batch and
the check of served tokens. Both take the family's ``reference_logprobs``
(``benchmark/families/<family>.py``: the block's forward pass in straightforward ``jax.numpy``,
float32 at ``highest`` matmul precision, no cache, no kernel, no batching, one layer's weights
cast at a time so that it fits beside the engine or the train state on the same chip). No layer
equations here: they belong to the family.
"""

from __future__ import annotations


def loss(logprobs, params: dict, batch: dict, c: dict) -> float:
    """Mean next-token cross entropy over the batch's valid targets (-100 = ignored), one
    sequence at a time. ``logprobs`` is the family's ``reference_logprobs``."""
    import numpy as np

    total, count = 0.0, 0
    for toks, tgts in zip(np.asarray(batch["tokens"]), np.asarray(batch["targets"])):
        lp = np.asarray(logprobs(params, toks, c, 0, len(toks)))
        valid = tgts >= 0
        total += float(-lp[np.arange(len(toks))[valid], tgts[valid]].sum(dtype=np.float64))
        count += int(valid.sum())
    return total / max(count, 1)


def check_served(logprobs, params: dict, c: dict, samples: list[dict], tol: float) -> dict:
    """Teacher-force served tokens through the plain forward (``logprobs``: the family's
    ``reference_logprobs``): every emitted token's served log-probability must agree with the
    reference's within ``tol``, and every greedy token must be the reference's top-1 or within
    ``tol`` of it (a tie that rounding broke the other way).
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
