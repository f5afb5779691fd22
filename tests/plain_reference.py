"""The plain reference the engine's streams are held to, and the one step-indexed harness that
drives an engine over a schedule. pytest does not collect this module (as ``hybrid_battery.py``).

The reference shares nothing with what it checks: ``models/llama.forward`` over the WHOLE sequence
for every token, no cache, no step program, no slot; of the serving code only the sampler
(``llm/sampling.sample``), whose key chain it states. It imports nothing of ``llm/engine.py``,
``llm/model_runner.py``, ``llm/hybrid_runner.py`` or the caches. A description's reference is its
family's (``hybrid_battery.check``). ``indexed_attention_by_hand`` is attention under a learned
index (``ops/indexed_attention.py``) one query at a time in numpy float64, a full stable sort a
query: what the op's forms and kernels are held to."""

from functools import lru_cache

import jax
import jax.numpy as jnp

from ray_tpu.llm.sampling import sample
from ray_tpu.models.llama import forward


@lru_cache(maxsize=None)
def _padded_forward(cfg):
    return jax.jit(lambda params, toks: forward(params, toks, cfg))


@lru_cache(maxsize=None)
def _padded_sample(cfg):
    def at_last(params, toks, n, key, temperature, top_k, top_p):
        logits = jax.lax.dynamic_index_in_dim(forward(params, toks, cfg)[0], n - 1, keepdims=True)
        return sample(logits, key[None], temperature[None], top_k[None], top_p[None])

    return jax.jit(at_last)


def _padded(cfg, toks):
    return jnp.asarray([toks + [0] * (cfg.max_seq_len - len(toks))], jnp.int32)


def full_forward_greedy(cfg, params, prompt, n_tokens):
    """Recompute the whole sequence every token, argmax of the last logits. The sequence is padded
    to one length (a causal model's logits at a position do not see what follows it), so the
    forward compiles once and not once a length."""
    toks = [int(t) for t in prompt]
    for _ in range(n_tokens):
        logits = _padded_forward(cfg)(params, _padded(cfg, toks))
        toks.append(int(jnp.argmax(logits[0, len(toks) - 1])))
    return toks[len(prompt):]


def full_forward_sampled(cfg, params, prompt, sp):
    """A seeded request's stream: the key chain the engine promises a lane. It starts at
    ``PRNGKey(sp.seed)`` and advances once per token of the lane's OWN (``sample`` splits it: one
    half draws, the other is the next key), whatever the lane's neighbours do and whichever slot
    holds it; the draw is from the whole-sequence forward's last logits under the request's
    temperature, top-k and top-p. Padded and jitted once, like the greedy form."""
    assert sp.seed is not None, "a seedless lane's key is the slot's own: no stream to promise"
    toks = [int(t) for t in prompt]
    key = jax.random.key_data(jax.random.PRNGKey(sp.seed))
    for _ in range(sp.max_tokens):
        tok, _, keys = _padded_sample(cfg)(params, _padded(cfg, toks), len(toks), key, jnp.float32(sp.temperature),
                                           jnp.int32(sp.top_k), jnp.float32(sp.top_p))
        key = keys[0]
        toks.append(int(tok[0]))
    return toks[len(prompt):]


def reference_stream(cfg, params, prompt, sp):
    """(tokens, finish reason) of one request served alone and never cut: greedy or seeded."""
    if sp.temperature == 0.0:
        toks = full_forward_greedy(cfg, params, prompt, sp.max_tokens)
    else:
        toks = full_forward_sampled(cfg, params, prompt, sp)
    stops = [i for i, t in enumerate(toks) if t in sp.stop_token_ids]
    return (toks[: stops[0] + 1], "stop") if stops else (toks, "length")


def indexed_attention_by_hand(q, k, v, qi, w, ki, topk):
    """q [B,nh,T,hd], k, v [B,G,T,hd], qi [B,J,T,d], w [B,T,J], ki [B,T,d] -> o [B,nh,T,hd] float64: query t
    attends to every s <= t while t + 1 <= topk, else to the topk positions s <= t of largest
    ``sum_j w[t, j] relu(qi[t, j] . ki[s])``, ties to the earlier position; ONE choice for all heads."""
    import numpy as np

    q, k, v, qi, w, ki = (np.asarray(a, np.float64) for a in (q, k, v, qi, w, ki))
    B, nh, T, hd = q.shape
    G, J = k.shape[1], qi.shape[1]
    out = np.zeros((B, nh, T, hd))
    for b in range(B):
        index = sum(w[b, :, j:j + 1] * np.maximum(qi[b, j] @ ki[b].T, 0.0) for j in range(J))
        for t in range(T):
            chosen = np.arange(t + 1) if t + 1 <= topk else np.argsort(-index[t, :t + 1], kind="stable")[:topk]
            for h in range(nh):
                g = h // (nh // G)
                s = q[b, h, t] @ k[b, g, chosen].T / np.sqrt(hd)
                p = np.exp(s - s.max())
                out[b, h, t] = (p / p.sum()) @ v[b, g, chosen]
    return out


def drive(engine, schedule, aborts=None, max_steps=900):
    """Step ``engine`` over a step-indexed schedule until it is idle. ``schedule``: {step:
    [admission, ...]}, an admission either ``(prompt, SamplingParams)``, given to ``add_request``,
    or a callable that admits (a handoff, a restore) and returns the request's id. ``aborts``:
    {step: the ordinal, in order of admission, of the request to abort there}. Returns ({ordinal:
    token_ids}, {ordinal: finish_reason})."""
    finals, reasons, ids = {}, {}, []
    last_t, t = max(schedule), 0
    while t <= last_t or engine.has_unfinished():
        for admission in schedule.get(t, []):
            ids.append(admission() if callable(admission) else engine.add_request(*admission))
        if aborts and t in aborts:
            engine.abort_request(ids[aborts[t]])
        for o in engine.step():
            if o.finished and o.request_id in ids:
                i = ids.index(o.request_id)
                finals[i], reasons[i] = o.token_ids, o.finish_reason
        t += 1
        assert t < max_steps, "schedule never converged"
    return finals, reasons


def admitted(schedule):
    """The schedule's admissions in the order ``drive`` makes them: ordinal -> admission."""
    return [a for t in sorted(schedule) for a in schedule[t]]


def assert_streams_are_the_references(cfg, params, schedule, finals, reasons):
    """Every stream ``drive`` returned is the reference's, token for token, with its finish reason;
    an aborted stream is a prefix of the reference's. -> the set of finish reasons seen."""
    requests = admitted(schedule)
    assert set(finals) == set(range(len(requests)))
    for i, (prompt, sp) in enumerate(requests):
        want, why = reference_stream(cfg, params, prompt, sp)
        if reasons[i] == "aborted":
            assert finals[i] == want[: len(finals[i])] and len(finals[i]) < len(want), f"request {i}: {finals[i]} is no prefix of {want}"
        else:
            assert finals[i] == want, f"request {i}: served {finals[i]} != reference {want}"
            assert reasons[i] == why
    return set(reasons.values())
