"""Drafters: propose k continuation tokens per lane, device-resident.

Both built-in drafters are DETERMINISTIC (one-hot proposal
distributions), which keeps the verify step's rejection sampling exact
without shipping a [B, k, V] q-tensor: accepting proposal d with
probability p(d) and resampling rejections from p-with-d-masked is the
one-hot special case of speculative rejection sampling, so the output
distribution still matches plain sampling token for token.

- ``NGramDrafter``: prompt-lookup decoding (zero extra weights). The
  trailing n-gram of the lane's token history is matched against the
  history itself; the k tokens after the most recent earlier occurrence
  become the proposals. Entirely jittable over the engine's device
  history lanes, so drafting never syncs the host — and ideal for
  CPU-tier tests.
- ``ModelDrafter``: a smaller llama with its OWN slot KV cache and a
  fused draft step: k+1 chained greedy decode steps under one jit (the
  extra step writes the last proposal's KV, so the draft cache tracks
  the target cache length exactly and no catch-up pass is ever needed).
  Rollback after verification is free: the next round simply overwrites
  positions past the accepted prefix, and draft attention masks by
  position, never by stale stored length.

The engine drives drafters through three hooks: ``init_slots`` (shape
the per-slot state), ``admit`` (host-side (re)admission: prefill the
draft cache), ``propose`` (device call on the hot path).
"""

from __future__ import annotations

from functools import partial
from typing import Protocol, runtime_checkable

import numpy as np

import jax
import jax.numpy as jnp

from ray_tpu.lint import jaxcheck
from ray_tpu.llm import kv_cache as kvc
from ray_tpu.llm.model_runner import _sds, _sds_cache, _sds_params, decode_step, named_jit, prefill
from ray_tpu.models.llama import LlamaConfig


@runtime_checkable
class Drafter(Protocol):
    """What LLMEngine needs from a drafter implementation.

    ``supports_mesh``: whether the drafter composes with a tensor-parallel
    engine mesh. A drafter qualifies when its per-lane state is replicated
    (or absent) — the engine's hist lanes are replicated over tp and the
    verify step itself compiles SPMD, so a zero-weight drafter rides along
    unchanged. A drafter with its own sharded-model state must implement
    mesh-aware prefill/propose before flipping this on.
    """

    kind: str
    k: int
    supports_mesh: bool

    def init_slots(self, num_slots: int, max_seq_len: int, prefill_buckets: tuple) -> None: ...

    def admit(self, slot: int, tokens: list) -> None: ...

    def propose(self, hist, hist_len, lengths): ...


# ---------------------------------------------------------------------------
# prompt-lookup (n-gram) drafting
# ---------------------------------------------------------------------------
def _bucket_ngram(B=8, H=517):
    return (_sds((B, H), jnp.int32), _sds((B,), jnp.int32), 3, 4), {}


@jaxcheck.entry(
    name="llm.spec_ngram_propose",
    shapes={"b8_h517": _bucket_ngram},
    donate_bytes=0,  # read-only over the hist lanes: nothing to donate
)
def ngram_propose(hist, hist_len, n: int, k: int):
    """Prompt-lookup proposals: for each lane, find the LAST earlier
    occurrence of the trailing n-gram inside the known history and
    propose the k tokens that followed it.

    hist: [B, H] int32 token history (zero right-padding); hist_len: [B]
    valid counts. Returns proposals [B, k] int32. A lane with no match
    proposes its last token repeated — garbage proposals are harmless
    (the verify step rejects them), so no validity lane is needed.
    """
    B, H = hist.shape
    idx = jnp.arange(H, dtype=jnp.int32)

    def one(row, ln):
        pat = jax.lax.dynamic_slice(row, (jnp.maximum(ln - n, 0),), (n,))  # trailing n-gram
        # win[i] = row[i : i + n] (wrapping windows; wraps are masked below)
        win = jnp.stack([jnp.roll(row, -j) for j in range(n)], axis=1)  # [H, n]
        # a usable start needs its continuation token row[i + n] inside
        # known history AND must not be the trailing occurrence itself
        match = jnp.all(win == pat[None, :], axis=1) & (idx + n < ln)
        i_star = jnp.max(jnp.where(match, idx, -1))
        src = jnp.where(i_star >= 0, i_star + n, jnp.maximum(ln - 1, 0))
        props = jax.lax.dynamic_slice(row, (src,), (k,))  # clamped at H - k
        last = row[jnp.maximum(ln - 1, 0)]
        return jnp.where(i_star >= 0, props, jnp.full((k,), last, row.dtype))

    return jax.vmap(one)(hist, hist_len)


class NGramDrafter:
    """Prompt-lookup drafter: stateless beyond the engine's hist lanes.
    Mesh-safe: the hist/length lanes are replicated over tp and propose
    has no weights — the same jitted program runs on every shard."""

    kind = "ngram"
    supports_mesh = True

    def __init__(self, k: int = 4, n: int = 3):
        self.k = int(k)
        self.n = int(n)
        self._propose = named_jit("llm_draft_propose", partial(ngram_propose, n=self.n, k=self.k))

    def init_slots(self, num_slots: int, max_seq_len: int, prefill_buckets: tuple) -> None:
        pass

    def admit(self, slot: int, tokens: list) -> None:
        pass

    def propose(self, hist, hist_len, lengths):
        del lengths  # history is the only state prompt-lookup needs
        return self._propose(hist, hist_len)


# ---------------------------------------------------------------------------
# draft-model drafting
# ---------------------------------------------------------------------------
def _draft_trace_cfg() -> LlamaConfig:
    # production-realistic small drafter: tile-true dims ((8,128) KV
    # tiles, like the target's trace config), target vocab
    return LlamaConfig(
        vocab_size=32256, hidden_size=512, intermediate_size=1408,
        num_layers=2, num_heads=8, num_kv_heads=8, head_dim=128,
        max_seq_len=512, remat=False,
    )


def _bucket_draft(B=8, S=256, H=517):
    cfg = _draft_trace_cfg()
    return (
        _sds_params(cfg), _sds_cache(cfg, B, S), _sds((B, H), jnp.int32),
        _sds((B,), jnp.int32), _sds((B,), jnp.int32), cfg, 4,
    ), {}


@jaxcheck.entry(
    name="llm.spec_draft_steps",
    shapes={"b8_s256": _bucket_draft},
    donate=("cache",),
    donate_bytes=0,
)
def draft_steps(params, cache, hist, hist_len, lengths, cfg: LlamaConfig, k: int):
    """ONE fused program: k+1 chained greedy decode steps of the draft
    model, proposing k tokens per lane.

    The draft cache's stored length lane is OVERWRITTEN with the target's
    ``lengths`` before stepping — that is the whole rollback protocol:
    step i processes the token at position lengths+i and attends
    0..lengths+i, so stale drafted KV past the last accepted token is
    overwritten before it could ever be read. The (k+1)-th step's
    prediction is discarded but its KV write keeps the draft cache level
    with the target cache, whatever the verify step accepts.

    hist/hist_len: the engine's token-history lanes (the draft chain
    starts from hist[hist_len-1], the lane's current input token).
    Returns (proposals [B, k] int32, new draft cache).
    """
    t0 = jnp.take_along_axis(hist, jnp.maximum(hist_len - 1, 0)[:, None], axis=1)[:, 0]
    cache = {"k": cache["k"], "v": cache["v"], "length": lengths}

    def body(carry, _):
        c, tok = carry
        logits, c = decode_step(params, c, tok, cfg)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (c, nxt), nxt

    (cache, _), outs = jax.lax.scan(body, (cache, t0), None, length=k + 1)
    return outs[:k].T, cache


class ModelDrafter:
    """Greedy draft-model drafter with its own slot KV cache.

    ``config`` must share the target's vocab; params default to a random
    init (tests/benchmarks — a real deployment passes distilled weights).
    Greedy drafting keeps the proposal distribution one-hot (see module
    docstring), so temperature>0 verification stays exact.
    """

    kind = "model"
    # the draft model's params, slot KV cache and fused draft_steps chain
    # are single-device today; the engine raises NotImplementedError on a
    # mesh rather than silently replicating a second model per chip
    supports_mesh = False

    def __init__(self, config: LlamaConfig, params=None, k: int = 4, seed: int = 0):
        from ray_tpu.models.llama import init_params

        self.cfg = config
        self.k = int(k)
        self.params = params if params is not None else init_params(config, jax.random.PRNGKey(seed))
        self._prefill = named_jit("llm_draft_prefill", partial(prefill, cfg=config))
        self._insert = named_jit("llm_draft_kv_insert", kvc.insert_sequence, donate_argnums=(0,))
        self._draft = named_jit("llm_draft_steps", partial(draft_steps, cfg=config, k=self.k), donate_argnums=(1,))
        self.cache = None
        self._buckets: tuple = ()

    def init_slots(self, num_slots: int, max_seq_len: int, prefill_buckets: tuple) -> None:
        self._buckets = tuple(prefill_buckets)
        # +k+1 headroom: the draft chain writes up to k+1 positions past
        # the target length each round, clamp-free
        self.cache = kvc.alloc(kvc.CacheConfig(
            num_layers=self.cfg.num_layers,
            num_slots=num_slots,
            max_seq_len=max_seq_len + self.k + 1,
            num_kv_heads=self.cfg.num_kv_heads,
            head_dim=self.cfg.hd,
            dtype=self.cfg.dtype,
        ))

    def admit(self, slot: int, tokens: list) -> None:
        """Prefill the draft model over the admitted sequence's tokens
        (everything already cached by the target: prompt plus any
        recompute-folded generation; NOT the freshly sampled token — that
        is the first chain input)."""
        from ray_tpu.llm.engine import _bucket

        n = len(tokens)
        T = _bucket(n, self._buckets)
        toks = np.zeros((1, T), np.int32)
        toks[0, :n] = tokens
        _, ks, vs = self._prefill(self.params, jnp.asarray(toks), jnp.asarray([n], np.int32))
        self.cache = self._insert(self.cache, slot, ks[:, 0], vs[:, 0], n)

    def propose(self, hist, hist_len, lengths):
        props, self.cache = self._draft(self.params, self.cache, hist, hist_len, lengths)
        return props
