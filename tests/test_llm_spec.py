"""Speculative decoding: token-identical oracle + subsystem behavior.

The non-speculative engine (speculative=None, the path this subsystem
never touches) is the equivalence oracle: speculative GREEDY decode must
emit token-for-token identical output under mixed admission / eviction /
preemption / abort schedules, for both drafters and both KV layouts.
Speculation changes how many tokens surface per step, never which.

Tiny model, CPU — tier-1. Engines are shared across assertions inside
each test to keep compile count (the dominant cost here) down.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from plain_reference import drive  # noqa: E402

from ray_tpu.llm import LLMEngine, SamplingParams, SpecConfig  # noqa: E402
from ray_tpu.models.llama import LlamaConfig, init_params  # noqa: E402

pytestmark = pytest.mark.usefixtures("shared_step_programs")  # many engines of equal configurations: their step programs compile once (conftest.py)

CFG = LlamaConfig.tiny(dtype="float32", remat=False, max_seq_len=256)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


def _mixed_schedule(n=6, seed=0):
    rng = np.random.default_rng(seed)
    sched = {}
    for _ in range(n):
        prompt = list(map(int, rng.integers(1, CFG.vocab_size - 1, size=int(rng.integers(4, 60)))))
        sp = SamplingParams(max_tokens=int(rng.integers(3, 13)), temperature=0.0)
        sched.setdefault(int(rng.integers(0, 8)), []).append((prompt, sp))
    return sched


def test_spec_slots_matches_plain_both_drafters(params):
    """Staggered admissions through 3 recycling slots with one mid-flight
    abort: the ngram drafter AND a draft-model drafter (sharing the
    target's weights, so acceptance is ~total and finishes land
    mid-round) must both reproduce the plain path's greedy streams."""
    sched = _mixed_schedule()
    kw = dict(params=params, max_num_seqs=3, max_seq_len=128)
    aborts = {6: 0}
    plain, plain_r = drive(LLMEngine(CFG, **kw), sched, aborts)
    spec_ngram = SpecConfig(drafter="ngram", k=3)
    spec_model = SpecConfig(drafter="model", k=3, draft_config=CFG, draft_params=params)
    for spec in (spec_ngram, spec_model):
        eng = LLMEngine(CFG, speculative=spec, **kw)
        got, got_r = drive(eng, sched, aborts)
        assert set(got) == set(plain)
        for rid in plain:
            if plain_r[rid] == "aborted":
                # an abort is host-timed: speculation emits up to k+1
                # tokens per step, so the cut lands elsewhere in the SAME
                # greedy stream — the surviving prefixes must agree
                n = min(len(plain[rid]), len(got[rid]))
                assert got[rid][:n] == plain[rid][:n]
            else:
                assert got[rid] == plain[rid], f"{spec.drafter} {rid}: {got[rid]} != {plain[rid]}"
        assert got_r == plain_r
        s = eng.spec_stats()
        assert s["rounds"] > 0 and s["emitted"] > 0
        if spec.drafter == "model":
            # weight-sharing drafter: the target agrees with nearly every
            # proposal, so rounds emit multiple tokens
            assert s["acceptance_rate"] > 0.8, s
            assert s["mean_tokens_per_round"] > 1.5, s
    assert "aborted" in set(plain_r.values())


def test_spec_stop_tokens_and_prefix_cache_match_plain(params):
    """Two oracle checks on one engine pair (weight-sharing model
    drafter, so acceptance is ~total and rounds emit multiple tokens):

    - a stop id hit mid-round must cut the stream at the same token as
      the plain path (accepted tokens past the stop are discarded);
    - satellite: a prefix-cache-hit admission (insert + suffix extend)
      followed by speculative decode stays token-identical."""
    kw = dict(params=params, max_num_seqs=2, max_seq_len=128, prefix_block=16)
    plain = LLMEngine(CFG, **kw)
    eng = LLMEngine(
        CFG, **kw, speculative=SpecConfig(drafter="model", k=3, draft_config=CFG, draft_params=params)
    )
    base = plain.generate([4, 4], SamplingParams(max_tokens=8, temperature=0.0)).token_ids
    stop = base[4]
    sp = SamplingParams(max_tokens=8, temperature=0.0, stop_token_ids=(stop,))
    want = plain.generate([4, 4], sp).token_ids
    out = eng.generate([4, 4], sp)
    assert out.token_ids == want and out.finish_reason == "stop"

    base40 = [(i % 50) + 1 for i in range(40)]
    p1, p2 = base40 + [7, 8, 9], base40 + [30, 31]
    sp6 = SamplingParams(max_tokens=6, temperature=0.0)
    h0p, h0s = plain.prefix_cache_stats()["hits"], eng.prefix_cache_stats()["hits"]
    o1, o2 = plain.generate(p1, sp6), plain.generate(p2, sp6)
    s1, s2 = eng.generate(p1, sp6), eng.generate(p2, sp6)
    assert plain.prefix_cache_stats()["hits"] - h0p == 1
    assert eng.prefix_cache_stats()["hits"] - h0s == 1
    assert s1.token_ids == o1.token_ids
    assert s2.token_ids == o2.token_ids  # decoded on top of reused prefix KV


def test_spec_paged_preemption_matches_plain(params):
    """A pool too small for the load forces recompute-preemption in both
    modes (spec growth even books k+1-token lookahead pages); greedy
    output must stay bitwise identical and the pool must drain."""
    rng = np.random.default_rng(1)
    sched = {}
    for _ in range(5):
        prompt = list(map(int, rng.integers(1, CFG.vocab_size - 1, size=int(rng.integers(50, 60)))))
        sp = SamplingParams(max_tokens=int(rng.integers(50, 64)), temperature=0.0)
        sched.setdefault(int(rng.integers(0, 6)), []).append((prompt, sp))
    kw = dict(
        params=params,
        max_num_seqs=3,
        max_seq_len=256,
        kv_layout="paged",
        page_size=32,
        num_pages=8,  # 7 usable: 2 admits + contended growth
        enable_prefix_caching=False,
    )
    ep, es = LLMEngine(CFG, **kw), LLMEngine(CFG, speculative=SpecConfig(drafter="ngram", k=3), **kw)
    plain, plain_r = drive(ep, sched)
    got, got_r = drive(es, sched)
    assert set(got) == set(plain)
    for rid in plain:
        assert got[rid] == plain[rid], f"{rid}: {got[rid]} != {plain[rid]}"
    assert got_r == plain_r
    assert ep.preemption_count > 0 and es.preemption_count > 0
    assert es._page_alloc.free_pages == es._pcfg.num_pages - 1


def test_spec_paged_model_drafter_matches_plain(params):
    """The remaining drafter x layout cell: the ModelDrafter's fused
    draft scan seeds its cache length from the paged engine's device
    lengths lane — greedy output must still match plain paged decode."""
    kw = dict(
        params=params, max_num_seqs=2, max_seq_len=128, kv_layout="paged",
        page_size=32, enable_prefix_caching=False,
    )
    prompts = [[3, 17, 40, 7, 99], [5, 6, 7, 8]]
    sp = SamplingParams(max_tokens=10, temperature=0.0)
    base = [o.token_ids for o in LLMEngine(CFG, **kw).generate(prompts, sp)]
    eng = LLMEngine(
        CFG, **kw, speculative=SpecConfig(drafter="model", k=3, draft_config=CFG, draft_params=params)
    )
    got = [o.token_ids for o in eng.generate(prompts, sp)]
    assert got == base
    assert eng.spec_stats()["acceptance_rate"] > 0.8  # weight-sharing drafter


def test_spec_trailing_round_capped_and_seeded_sampling(params):
    """Satellite: the discarded delayed-emit trailing step costs up to k
    verifications under speculation, so wasted work is bounded — a solo
    request that the pending round is guaranteed to finish must not
    dispatch another drafter round (max_tokens=2 -> exactly ONE round),
    and no rounds run after everything finished. Seeded temperature>0
    generation on the same engine is reproducible (rejection sampling
    preserves the distribution; the plain path's sample stream is not
    replayed, so only self-consistency is asserted)."""
    eng = LLMEngine(
        CFG, params, max_num_seqs=2, max_seq_len=64, speculative=SpecConfig(drafter="ngram", k=3)
    )
    eng.generate([5, 6], SamplingParams(max_tokens=2, temperature=0.0))
    assert eng.spec_stats()["rounds"] == 1, eng.spec_stats()
    for _ in range(3):
        eng.step()  # idle engine: no speculative work
    assert eng.spec_stats()["rounds"] == 1
    # one wasted round per finish even when another lane stays live
    eng.add_request([1, 2, 3], SamplingParams(max_tokens=12, temperature=0.0))
    eng.add_request([9, 8], SamplingParams(max_tokens=2, temperature=0.0))
    while eng.has_unfinished():
        eng.step()
    sp = SamplingParams(max_tokens=10, temperature=1.0, seed=7)
    a = eng.generate([2, 3], sp).token_ids
    b = eng.generate([2, 3], sp).token_ids
    assert a == b and len(a) == 10


def test_spec_adaptive_k_decays_on_misses(params):
    """Random prompts give the ngram drafter ~zero acceptance: the EMA
    controller must walk the request's effective k down to k_min, and the
    per-request k surfaces in spec_stats while the request is live."""
    eng = LLMEngine(
        CFG, params, max_num_seqs=1, max_seq_len=128,
        speculative=SpecConfig(drafter="ngram", k=4, k_min=1, ema_alpha=0.6),
    )
    rid = eng.add_request(
        list(map(int, np.random.default_rng(3).integers(1, CFG.vocab_size - 1, size=24))),
        SamplingParams(max_tokens=24, temperature=0.0),
    )
    seen = set()
    while eng.has_unfinished():
        eng.step()
        ks = eng.spec_stats()["k_per_request"]
        if rid in ks:
            seen.add(ks[rid])
    assert 1 in seen and len(seen) > 1, seen  # walked down from 4 to k_min
    s = eng.spec_stats()
    assert s["proposed"] > 0 and s["accepted"] <= s["proposed"]


def test_spec_config_validation(params):
    with pytest.raises(ValueError, match="draft_config"):
        LLMEngine(CFG, params, max_num_seqs=1, max_seq_len=64,
                  speculative=SpecConfig(drafter="model"))
    with pytest.raises(ValueError, match="vocab"):
        LLMEngine(CFG, params, max_num_seqs=1, max_seq_len=64,
                  speculative=SpecConfig(drafter="model", draft_config=LlamaConfig.tiny(vocab_size=64)))
    with pytest.raises(ValueError):
        SpecConfig(drafter="nope")
    with pytest.raises(ValueError):
        SpecConfig(k=0)
    with pytest.raises(ValueError):
        SpecConfig(k=2, k_min=0)  # a 0-k lane could never recover


def test_serve_replica_surfaces_spec_stats(params):
    """Satellite: the serve deployment exposes spec_stats() next to
    prefix_cache_stats(); LLMConfig.speculative reaches the engine."""
    from ray_tpu.serve.llm import LLMConfig, LLMServer

    server = LLMServer(LLMConfig(
        model_config=CFG,
        params=params,
        engine_kwargs={"max_num_seqs": 2, "max_seq_len": 64},
        speculative=SpecConfig(drafter="ngram", k=3),
    ))
    try:
        out = server.generate([1, 2, 3], {"max_tokens": 6, "temperature": 0.0}, timeout_s=120.0)
        assert len(out["token_ids"]) == 6
        s = server.spec_stats()
        assert s["drafter"] == "ngram" and s["rounds"] > 0 and s["emitted"] >= 5
        assert server.prefix_cache_stats() is not None  # surfaces side by side
    finally:
        server._stopped = True
