"""Token sampling, jit-compatible with per-slot parameters.

TPU-native replacement for the sampling-params plumbing the reference
delegates to vLLM (ref: python/ray/llm/_internal/serve/engines/vllm/
vllm_models.py:215-228 passes SamplingParams through to the engine).
Everything here is batched and static-shaped: one `sample` call handles a
whole decode batch with per-slot temperature / top-k / top-p arrays, so
continuous batching never recompiles as requests come and go.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from ray_tpu.util.profiling import scope


@dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling configuration (user-facing)."""

    max_tokens: int = 64
    temperature: float = 0.0  # 0.0 => greedy
    top_k: int = 0  # 0 => disabled
    top_p: float = 1.0  # 1.0 => disabled
    stop_token_ids: tuple = field(default_factory=tuple)
    seed: int | None = None
    logprobs: bool = False
    # request class for admission control / load shedding
    # (serve/overload.py): 0 = lowest, shed first; higher classes only
    # shed at larger fractions of the ingress caps. Never reorders
    # admitted work — priority decides WHO sheds, not who runs first.
    priority: int = 0

    def __post_init__(self):
        if self.temperature < 0.0:
            raise ValueError("temperature must be >= 0")
        if self.priority < 0:
            raise ValueError("priority must be >= 0")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError("top_p must be in (0, 1]")
        if self.top_k < 0:
            raise ValueError("top_k must be >= 0")


def _ordered_keys(x):
    """float32 -> uint32 whose unsigned order is the floats' order (-inf lowest, -0.0 just under 0.0)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def _threshold(keys, mass_from, target):
    """Per row, the largest uint32 ``t`` with ``mass_from(keys >= t) >= target`` (0 where no ``t``
    reaches it: everything is kept). ``mass_from`` sums a row's mask over the vocabulary axis and
    must not grow as the mask shrinks. Found bit by bit from the top, so in 32 passes over the
    row whatever its length: no sort. ``t`` is always one of the row's own keys, or 0."""

    def bit(i, t):
        cand = t | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        return jnp.where(mass_from(keys >= cand[..., None]) >= target, cand, t)

    return jax.lax.fori_loop(0, 32, bit, jnp.zeros(keys.shape[:-1], jnp.uint32))


def _apply_top_k(logits, top_k):
    """Mask logits under the row's top_k-th largest value (top_k <= 0 disables). Tokens tied with
    the k-th are all kept, as _apply_top_p keeps those tied at its threshold."""
    keys = _ordered_keys(logits)
    k = jnp.where(top_k <= 0, logits.shape[-1], top_k)
    thresh = _threshold(keys, lambda m: jnp.sum(m, axis=-1, dtype=jnp.int32), k)
    return jnp.where(keys >= thresh[..., None], logits, -jnp.inf)


def _apply_top_p(logits, top_p):
    """Nucleus filtering: keep the smallest set of most probable tokens whose mass reaches top_p
    (top_p >= 1 keeps everything), and every token tied with the last one kept."""
    keys = _ordered_keys(logits)
    probs = jax.nn.softmax(logits, axis=-1)
    thresh = _threshold(keys, lambda m: jnp.sum(jnp.where(m, probs, 0.0), axis=-1), top_p)
    return jnp.where((keys >= thresh[..., None]) | (top_p >= 1.0)[..., None], logits, -jnp.inf)


def filter_logits(logits, temperature, top_k, top_p):
    """Temperature-scale then top-k/top-p filter logits.

    logits: [..., V]; temperature/top_p: [...] f32; top_k: [...] i32
    (0 disables). The distribution surgery shared by sample() and the
    speculative verify step (llm/spec/verify.py) — spec acceptance must
    judge proposals against exactly the distribution plain sampling
    draws from, or rejection sampling would drift off-policy.

    Both filters select by a threshold found in 32 counting passes over
    the row (_threshold), never by sorting the vocabulary, and a filter
    that no row of the batch asks for is not run (a lax.cond on the whole
    batch: under vmap it would become a select that runs both sides, so
    callers pass the batch, not a row). A row's result does not depend on
    which branch its neighbours chose. Ties: top-k keeps every token tied
    with the k-th largest, so more than k where the k-th value repeats.
    """
    with scope("sample"):
        lead = logits.shape[:-1]
        top_k, top_p = jnp.broadcast_to(top_k, lead), jnp.broadcast_to(top_p, lead)
        scaled = logits / jnp.maximum(temperature, 1e-6)[..., None]
        scaled = jax.lax.cond(jnp.any(top_k > 0), _apply_top_k, lambda x, _: x, scaled, top_k)
        return jax.lax.cond(jnp.any(top_p < 1.0), _apply_top_p, lambda x, _: x, scaled, top_p)


def sample(logits, key, temperature, top_k, top_p):
    """Sample one token per row.

    logits: [B, V] f32; temperature/top_p: [B] f32; top_k: [B] i32;
    key: [B, 2] u32 per-slot PRNG keys. Returns (tokens [B] i32,
    logprobs [B] f32, new_keys [B, 2]).

    One path whatever the lanes ask for: a step whose lanes are all
    greedy draws too. A conditional around the filter and the draw would
    save such a step 0.06 ms of a 15 ms decode step at 12 x 92,544 on a
    v5e, and 0.17 where a lane asks for top-p (PERF.md section 6, PR 32).
    Each row's key advances by its own split, whatever its neighbours do.
    """
    with scope("sample"):
        logits = logits.astype(jnp.float32)
        greedy_tok = jnp.argmax(logits, axis=-1)
        scaled = filter_logits(logits, temperature, top_k, top_p)

        def _one(lg, k):
            k1, k2 = jax.random.split(jax.random.wrap_key_data(k, impl="threefry2x32"))
            return jax.random.categorical(k1, lg), jax.random.key_data(k2)

        sampled_tok, new_keys = jax.vmap(_one)(scaled, key)
        tokens = jnp.where(temperature == 0.0, greedy_tok, sampled_tok).astype(jnp.int32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        chosen_logp = jnp.take_along_axis(logp, tokens[:, None], axis=-1)[:, 0]
        return tokens, chosen_logp, new_keys


def seed_keys(seeds):
    """[N] integer seeds -> [N, 2] u32: ``PRNGKey(seed)``'s data a row, where a seeded lane's chain starts."""
    return jax.vmap(lambda s: jax.random.key_data(jax.random.PRNGKey(s)))(seeds)


def sample_first(logits, lane_keys, slots, seeds, seeded, temperature, top_k, top_p):
    """A group's first tokens, a row a sequence, from keys that never leave the device.

    logits: [G, V]; lane_keys: [B, 2] u32, every lane's key as the last step left it; slots: [G]
    i32; seeds: [G] i32; seeded: [G] bool; temperature/top_k/top_p: [G], as sample() takes them.
    A seeded row starts its chain at ``PRNGKey(seed)``, a seedless one draws from its lane's own
    key. A padding row's slot is out of range: it reads some lane's key and samples garbage that
    nothing reads. Returns sample()'s triple, the keys advanced once."""
    keys = jnp.where(seeded[:, None], seed_keys(seeds), jnp.take(lane_keys, slots, axis=0, mode="clip"))
    return sample(logits, keys, temperature, top_k, top_p)
