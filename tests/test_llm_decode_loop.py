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

from plain_reference import (  # noqa: E402
    _padded,
    _padded_forward,
    admitted as plain_admitted,
    assert_streams_are_the_references,
    drive,
    full_forward_sampled,
    reference_stream,
)

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


# ------------------------------------------------------------------------------------------------
# A wave's first tokens stay on the device (PR 50): one sample and one lane write a group, the
# step dispatched behind the wave's prefills, one readback after it
# ------------------------------------------------------------------------------------------------
def _wave(n):
    """n prompts in two buckets (16 and 64), greedy, seeded and seedless lanes mixed."""
    rng = np.random.default_rng(50 + n)
    prompts = [[int(t) for t in rng.integers(1, CFG.vocab_size - 1, size=length)] for length in [5, 40, 9, 33, 12][:n]]
    sps = [SamplingParams(max_tokens=6, logprobs=True),
           SamplingParams(max_tokens=7, temperature=0.9, top_k=12, seed=31, logprobs=True),
           SamplingParams(max_tokens=5, temperature=0.8, logprobs=True),  # seedless: it draws from its lane's own key
           SamplingParams(max_tokens=6, temperature=0.7, top_p=0.9, logprobs=True),  # seedless
           SamplingParams(max_tokens=4, temperature=1.0, seed=7, logprobs=True)][:n]
    return prompts, sps


# what the parent (13c6792: one sample, three readbacks and one lane write a SEQUENCE) served for the same waves
PARENT_STREAMS = {
    3: [[307, 443, 273, 331, 387, 396], [364, 135, 482, 385, 59, 296, 148], [415, 433, 123, 488, 380]],
    5: [[37, 493, 501, 303, 501, 47], [451, 108, 334, 227, 124, 185, 463], [211, 363, 123, 474, 176], [27, 411, 181, 235, 148, 276],
        [436, 312, 245, 122]],
}
PARENT_FIRST_LOGPS = {3: [-3.8761, -3.8542, -5.3135], 5: [-3.9255, -4.5176, -5.4045, -5.2191, -5.8256]}


def _reference_logps(params, prompt, tokens):
    """log-probability of each token of a stream under the whole-sequence forward, no cache."""
    toks, out = list(prompt), []
    for t in tokens:
        logits = _padded_forward(CFG)(params, _padded(CFG, toks))[0, len(toks) - 1]
        out.append(float(jax.nn.log_softmax(logits.astype("float32"))[t]))
        toks.append(t)
    return out


@pytest.mark.parametrize("n", [3, 5])
def test_a_waves_streams_are_the_references_and_the_parents(params, n):
    """A wave of n prompts in two groups: every lane's stream, seedless ones too, is what the parent served
    (the seedless lanes draw from the same device keys), and the greedy and seeded ones are the plain
    reference's, tokens and log-probabilities."""
    prompts, sps = _wave(n)
    eng = LLMEngine(CFG, params=params, max_num_seqs=6, max_seq_len=128, prefill_buckets=(16, 64), enable_prefix_caching=False)
    outs = eng.generate(prompts, sps)
    assert [o.token_ids for o in outs] == PARENT_STREAMS[n]
    assert [o.logprobs[0] for o in outs] == pytest.approx(PARENT_FIRST_LOGPS[n], abs=2e-4)
    for o, prompt, sp in zip(outs, prompts, sps):
        if sp.temperature == 0.0 or sp.seed is not None:
            assert (o.token_ids, o.finish_reason) == reference_stream(CFG, params, prompt, sp)
        assert o.logprobs == pytest.approx(_reference_logps(params, prompt, o.token_ids), abs=2e-4)
    wave = [s for s in eng.telemetry()["steps"] if s.get("admitted")]
    assert [(s["admitted"], len(s["prefill_dispatch_t"]), s["lanes_bound_device"], s["first_token_syncs"]) for s in wave] == [(n, 2, n, 1)]


@pytest.mark.parametrize("ending", ["max_tokens", "stop"])
def test_a_lane_that_ends_on_its_first_token_gives_its_slot_to_the_next(params, ending):
    """Inside a group of two, one request's first token ends it (``max_tokens=1``; a stop token): the host
    learns of it behind the dispatch, so the lane has run one discarded step, as a lane that ends on any
    later token has. One token emitted, the slot recycled, and the stream admitted into it is the reference's."""
    first = [9, 4, 33, 2]
    t0 = reference_stream(CFG, params, first, SamplingParams(max_tokens=1))[0][0]
    short = SamplingParams(max_tokens=1) if ending == "max_tokens" else SamplingParams(max_tokens=5, stop_token_ids=(t0,))
    sched = {0: [(first, short), ([5, 6, 7, 8, 1], SamplingParams(max_tokens=7))],
             1: [([3, 1, 4, 1, 5, 9, 2, 6], SamplingParams(max_tokens=6, temperature=0.9, seed=5))]}
    eng = LLMEngine(CFG, params=params, max_num_seqs=2, max_seq_len=64, enable_prefix_caching=False)
    taken = []
    bind = eng._bind
    eng._bind = lambda st, slot: (taken.append(slot), bind(st, slot))[1]
    finals, reasons = drive(eng, sched)
    assert finals[0] == [t0] and reasons[0] == ("length" if ending == "max_tokens" else "stop")
    assert assert_streams_are_the_references(CFG, params, sched, finals, reasons) == {"length", "stop"} - ({"stop"} if ending == "max_tokens" else set())
    assert taken == [0, 1, 0], "the third request took the slot the first one left"
    assert eng._slots == [None, None] and not eng._first_tokens


def test_an_abort_between_launch_and_readback_emits_nothing(params):
    """A lane that loses its slot after its first token was sampled and bound on the device, and before the
    host has read it (an abort lands there; a preemption for pages does the same), emits nothing: the request
    ends with no token, its neighbours' streams are the references, and the slot serves the next request."""
    sched = {0: [([9, 4, 33, 2], SamplingParams(max_tokens=6)), ([5, 6, 7, 8, 1], SamplingParams(max_tokens=7))],
             1: [([3, 1, 4, 1, 5, 9, 2, 6], SamplingParams(max_tokens=6))]}
    eng = LLMEngine(CFG, params=params, max_num_seqs=2, max_seq_len=64, enable_prefix_caching=False)
    dispatch = eng._dispatch_fused

    def abort_then_dispatch(prev=None):
        if eng._first_tokens and len(eng._first_tokens[0][3]) == 2:  # the wave of two is launched, nothing is read
            eng._finish(eng._first_tokens[0][3][0][1], "aborted")  # what abort_request does, under the lock the step holds
        dispatch(prev)

    eng._dispatch_fused = abort_then_dispatch
    finals, reasons = drive(eng, sched)
    assert finals[0] == [] and reasons[0] == "aborted"
    for i in (1, 2):
        prompt, sp = plain_admitted(sched)[i]
        assert (finals[i], reasons[i]) == reference_stream(CFG, params, prompt, sp)
    assert eng._slots == [None, None]


# ------------------------------------------------------------------------------------------------
# The flash kernel knows a row's true length where its bucket has a tile to skip (PR 52): query tiles
# past it are zeros, not attention among padding, and nothing a client reads moves
# ------------------------------------------------------------------------------------------------
ONE_BUCKET_LENGTHS = [1, 17, 40, 64]  # one bucket of 64 in tiles of 16: one, two, three and all four query tiles live


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("tile", [16, None])
def test_prompts_that_leave_whole_query_tiles_empty_are_served_the_plain_references_streams(params, impl, tile, monkeypatch):
    """Four prompts of mixed lengths in ONE bucket, greedy and seeded lanes, the XLA form and the kernel
    interpreted: in tiles of 16 the group leaves six of its sixteen query tiles empty, at the default
    tile the bucket is one tile and no call learns a length; either way every stream is the plain
    reference's, token for token, and the admitting step's row counts the query tiles of its flash
    calls and those under a true length."""
    from jax.experimental.pallas import tpu as pltpu

    from ray_tpu.ops import flash_attention as fa

    if tile is not None:
        monkeypatch.setattr(fa, "_default_blocks", lambda head_dim: (tile, tile))
    cfg = LlamaConfig.tiny(dtype="float32", remat=False, max_seq_len=256, attention_impl=impl)
    rng = np.random.default_rng(52)
    prompts = [[int(t) for t in rng.integers(1, cfg.vocab_size - 1, size=n)] for n in ONE_BUCKET_LENGTHS]
    sps = [SamplingParams(max_tokens=5), SamplingParams(max_tokens=6, temperature=0.9, top_k=12, seed=31),
           SamplingParams(max_tokens=5), SamplingParams(max_tokens=4, temperature=1.0, seed=7)]
    eng = LLMEngine(cfg, params=params, max_num_seqs=4, max_seq_len=128, prefill_buckets=(64,), enable_prefix_caching=False)
    with pltpu.force_tpu_interpret_mode():
        outs = eng.generate(prompts, sps)
    for o, prompt, sp in zip(outs, prompts, sps):
        assert (o.token_ids, o.finish_reason) == reference_stream(CFG, params, prompt, sp)
    (row,) = [s for s in eng.telemetry()["steps"] if s.get("admitted")]
    assert (row["prefill_tokens"], row["prefill_tokens_padded"]) == (sum(ONE_BUCKET_LENGTHS), 4 * 64)
    calls = cfg.num_layers
    want = (calls * 4 * 4, calls * (1 + 2 + 3 + 4)) if tile else (calls * 4, calls * 4)
    assert (row["attn_q_tiles"], row["attn_q_tiles_live"]) == want


# ------------------------------------------------------------------------------------------------
# A prefill's MLP runs the slabs of positions under a row's true length (PR 54, ``ops/layers.live_slabs``):
# what lies past them is zeros, not a SwiGLU of padding, and nothing a client reads moves
# ------------------------------------------------------------------------------------------------
@pytest.mark.parametrize("slab", [16, None])
def test_prompts_that_leave_whole_slabs_empty_are_served_the_plain_references_streams(params, slab, monkeypatch):
    """Three prompts and a padding row in ONE bucket of 64: in slabs of 16 the group runs seven of its sixteen
    slabs (one of them the padding row's, whose length is 1), at the chip's 512 the bucket is one slab and
    the plain form runs; either way every stream is the plain reference's, token for token, and the
    admitting step's row says how many positions the MLP ran: ``prefill_rows_live``, the host's arithmetic
    for the group's lengths, and ``prefill_tokens_padded`` where the plain form ran."""
    from ray_tpu.ops import layers

    if slab is not None:
        monkeypatch.setattr(layers, "LIVE_SLAB", slab)
    rng = np.random.default_rng(54)
    lengths = ONE_BUCKET_LENGTHS[:3]  # three prompts: the program's fourth row is padding, of length 1
    prompts = [[int(t) for t in rng.integers(1, CFG.vocab_size - 1, size=n)] for n in lengths]
    sps = [SamplingParams(max_tokens=5), SamplingParams(max_tokens=6, temperature=0.9, top_k=12, seed=31), SamplingParams(max_tokens=5)]
    eng = LLMEngine(CFG, params=params, max_num_seqs=4, max_seq_len=128, prefill_buckets=(64,), enable_prefix_caching=False)
    traced = str(jax.make_jaxpr(eng._prefill)(eng.params, np.zeros((4, 64), np.int32), np.ones((4,), np.int32)))
    assert ("while[" in traced) == (slab is not None)
    outs = eng.generate(prompts, sps)
    for o, prompt, sp in zip(outs, prompts, sps):
        assert (o.token_ids, o.finish_reason) == reference_stream(CFG, params, prompt, sp)
    (row,) = [s for s in eng.telemetry()["steps"] if s.get("admitted")]
    assert (row["prefill_tokens"], row["prefill_tokens_padded"]) == (sum(lengths), 4 * 64)
    assert row["prefill_rows_live"] == layers.live_rows(64, lengths + [1]) == (16 + 32 + 48 + 16 if slab else 4 * 64)
