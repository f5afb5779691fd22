"""Disaggregated prefill/decode serving (llm/disagg/): token-identity
against the plain reference, handoff codec validation, and the
router's bounded failure policy.

The plain reference (tests/plain_reference.py: a whole-sequence forward,
no cache) is the oracle: a prefill engine extracting handoff blocks + a
decode engine scattering them in must emit exactly the tokens it
computes, for both KV layouts, under admission / eviction / preemption /
abort, greedy and seeded sampling, with speculative decoding composing
on the decode side (tests mirror tests/test_llm_decode_loop.py's
methodology). An int8 cache is held to one int8 engine that prefills
locally: the reference keeps no cache to quantize.

Lean by design (tier-1 budget): one module-scoped prefill engine feeds
every layout's decode test through the codec round-trip.
"""

import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from plain_reference import drive, reference_stream  # noqa: E402

import ray_tpu  # noqa: E402
from ray_tpu.llm import LLMEngine, SamplingParams  # noqa: E402
from ray_tpu.llm.disagg import (  # noqa: E402
    DisaggRequestError,
    DisaggRouter,
    HandoffError,
    HandoffLostError,
    decode_handoff,
    encode_handoff,
)
from ray_tpu.models.llama import LlamaConfig, init_params  # noqa: E402

pytestmark = pytest.mark.usefixtures("shared_step_programs")  # many engines of equal configurations: their step programs compile once (conftest.py)

CFG = LlamaConfig.tiny(dtype="float32", remat=False, max_seq_len=256)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def prefill_eng(params):
    """One slots-layout prefill engine shared by every decode test: the
    handoff block is layout-agnostic, so a slots producer feeds both
    slots and paged consumers (cross-layout shipping covered for free)."""
    return LLMEngine(CFG, params, max_num_seqs=2, max_seq_len=128, enable_prefix_caching=False)


def _ship(prefill_eng, prompt):
    """Producer -> codec round-trip -> consumer-format payload."""
    return decode_handoff(encode_handoff(prefill_eng.prefill_handoff(prompt)))


def _streams(eng, reqs, admit, aborts=None):
    """Drive ``eng`` over ``reqs`` [(prompt, sampling, step)], request i admitted by ``admit(i)``;
    ``aborts``: {step: index into reqs}. Returns ({i: tokens}, {i: reason})."""
    order = sorted(range(len(reqs)), key=lambda i: reqs[i][2])  # drive's ordinals: by step, then as listed
    sched = {}
    for i in order:
        sched.setdefault(reqs[i][2], []).append(lambda i=i: admit(i))
    finals, reasons = drive(eng, sched, aborts and {t: order.index(i) for t, i in aborts.items()})
    return {order[o]: toks for o, toks in finals.items()}, {order[o]: why for o, why in reasons.items()}


def _mk_schedule(rng, n_req, max_len=90, max_tok=12):
    """(prompts, sampling, step) tuples incl. one seeded stochastic lane."""
    reqs = []
    for i in range(n_req):
        prompt = list(rng.integers(1, CFG.vocab_size - 1, size=int(rng.integers(4, max_len))))
        sp = SamplingParams(max_tokens=int(rng.integers(3, max_tok)), temperature=0.0)
        reqs.append((prompt, sp, int(rng.integers(0, 6))))
    reqs.append(([7, 7, 7], SamplingParams(max_tokens=8, temperature=1.0, seed=123), 1))
    return reqs


def _reference_streams(params, reqs):
    """The plain reference over the same request set: ({i: tokens}, {i: reason})."""
    both = [reference_stream(CFG, params, prompt, sp) for prompt, sp, _ in reqs]
    return {i: toks for i, (toks, _) in enumerate(both)}, {i: why for i, (_, why) in enumerate(both)}


def _single_engine_streams(params, reqs, engine_kwargs):
    """One engine that prefills locally, over the same request set: what an int8 cache is held to
    (the plain reference keeps no cache, so it says nothing of a quantized one)."""
    eng = LLMEngine(CFG, params=params, **engine_kwargs)
    return _streams(eng, reqs, lambda i: eng.add_request(reqs[i][0], reqs[i][1]))


def _disagg_streams(params, prefill_eng, reqs, engine_kwargs, aborts=None, speculative=None):
    """Prefill engine -> codec -> decode engine."""
    dec = LLMEngine(CFG, params=params, speculative=speculative, **engine_kwargs)
    handoffs = {i: _ship(prefill_eng, prompt) for i, (prompt, _, _) in enumerate(reqs)}
    finals, reasons = _streams(dec, reqs, lambda i: dec.add_prefilled(handoffs[i], reqs[i][1]), aborts)
    return finals, reasons, dec


def test_disagg_slots_token_identity_with_abort(params, prefill_eng):
    """Slots decode engine fed by handoffs == the plain reference,
    greedy + seeded sampling, with one mid-flight abort riding along."""
    reqs = _mk_schedule(np.random.default_rng(0), 4)
    kw = dict(max_num_seqs=3, max_seq_len=128, enable_prefix_caching=False)
    aborts = {5: 0}  # abort the first request mid-decode
    ref, ref_r = _reference_streams(params, reqs)
    dis, dis_r, _ = _disagg_streams(params, prefill_eng, reqs, kw, aborts)
    assert set(ref) == set(dis)
    for key in ref:
        if dis_r[key] == "aborted":
            # an abort is host-timed: it cuts the stream, and what
            # survives is a prefix of the reference's
            assert dis[key] == ref[key][: len(dis[key])] and len(dis[key]) < len(ref[key])
        else:
            assert dis[key] == ref[key], f"req {key}: disagg {dis[key]} != reference {ref[key]}"
            assert dis_r[key] == ref_r[key]
    assert "aborted" in set(dis_r.values())


def test_disagg_paged_token_identity_under_preemption(params, prefill_eng):
    """Paged decode engine with a pool too small for the load: handoff
    admissions + growth preemption (recompute re-prefill ON the decode
    replica, vLLM semantics) still emit the plain reference's greedy tokens."""
    rng = np.random.default_rng(1)
    reqs = []
    for i in range(4):
        prompt = list(rng.integers(1, CFG.vocab_size - 1, size=int(rng.integers(50, 60))))
        reqs.append((prompt, SamplingParams(max_tokens=int(rng.integers(40, 56)), temperature=0.0), int(rng.integers(0, 4))))
    kw = dict(
        max_num_seqs=3, max_seq_len=256, kv_layout="paged", page_size=32,
        num_pages=8, enable_prefix_caching=False,
    )
    ref, ref_r = _reference_streams(params, reqs)
    dis, dis_r, dec = _disagg_streams(params, prefill_eng, reqs, kw)
    for key in ref:
        assert dis[key] == ref[key], f"req {key}: disagg {dis[key]} != reference {ref[key]}"
    assert dis_r == ref_r
    assert dec.preemption_count > 0, "schedule never exercised decode-side preemption"
    assert dec._page_alloc.free_pages == dec._pcfg.num_pages - 1  # pool drained clean


def test_disagg_spec_composes_on_decode_side(params, prefill_eng):
    """Speculative decoding on the DECODE side of the split: handoff
    admissions draft/verify like local ones, token-identical to the
    non-speculative decode engine over the same handoffs."""
    from ray_tpu.llm.spec import SpecConfig

    # period-8 repeating prompts: the ngram drafter has something to hit
    reqs = [
        ([10 + (i % 8) for i in range(32)], SamplingParams(max_tokens=10, temperature=0.0), 0),
        ([50 + (i % 8) for i in range(24)], SamplingParams(max_tokens=8, temperature=0.0), 1),
    ]
    kw = dict(max_num_seqs=2, max_seq_len=128, enable_prefix_caching=False)
    plain, plain_r, _ = _disagg_streams(params, prefill_eng, reqs, kw)
    spec, spec_r, dec = _disagg_streams(
        params, prefill_eng, reqs, kw, speculative=SpecConfig(drafter="ngram", k=3)
    )
    assert spec == plain and spec_r == plain_r
    assert dec.spec_stats()["rounds"] > 0, "spec path never engaged"


def test_handoff_codec_rejects_inconsistent_payloads(params, prefill_eng):
    kv = prefill_eng.prefill_handoff([5, 6, 7, 8])
    wire = encode_handoff(kv)
    assert decode_handoff(wire)["n"] == 4
    bad = dict(wire)
    bad["n"] = 0
    with pytest.raises(HandoffError):
        decode_handoff(bad)
    bad = dict(wire)
    bad["shape"] = (1, 2, 3, 4)
    with pytest.raises(HandoffError):
        decode_handoff(bad)
    with pytest.raises(HandoffError):
        decode_handoff({"kind": "other"})
    trunc = dict(wire)
    trunc["k"] = trunc["k"][:, :1]
    with pytest.raises(HandoffError):
        decode_handoff(trunc)


def test_disagg_one_trace_id_stitches_replicas(params, prefill_eng):
    """ISSUE 10 acceptance: one disagg request yields ONE trace id
    spanning admission -> prefill -> handoff -> scatter-in -> decode ->
    first-token across BOTH replicas — the trace context rides inside
    the handoff wire dict, and the decode-side root span parents back
    into the prefill-side request's root."""
    from ray_tpu.util import tracing

    tracing.configure(True)
    try:
        dec = LLMEngine(CFG, params=params, max_num_seqs=2, max_seq_len=128, enable_prefix_caching=False)
        kv = _ship(prefill_eng, [5, 6, 7, 8, 9])
        assert kv.get("trace", {}).get("trace_id"), "trace context missing from the handoff wire dict"
        assert kv.get("submitted_at"), "submit stamp missing from the handoff wire dict"
        rid = dec.add_prefilled(kv, SamplingParams(max_tokens=4))
        while dec.has_unfinished():
            dec.step()
        tracing.shutdown()  # flush-close before reading (satellite: final spans never lost)
        tid = kv["trace"]["trace_id"]
        spans = [s for s in tracing.load_spans() if s["trace_id"] == tid]
        names = {s["name"] for s in spans}
        assert {
            "llm.admission", "llm.prefill", "llm.handoff",
            "llm.handoff.scatter_in", "llm.first_token", "llm.decode", "llm.request",
        } <= names, f"missing lifecycle spans: {sorted(names)}"
        # both replicas contributed admissions to the one trace
        assert len([s for s in spans if s["name"] == "llm.admission"]) >= 2
        roots = [s for s in spans if s["name"] == "llm.request"]
        assert len(roots) == 2  # prefill-side + decode-side request roots
        pre_root = next(s for s in roots if s["attrs"]["reason"] == "handoff")
        dec_root = next(s for s in roots if s is not pre_root)
        assert dec_root["attrs"]["request_id"] == rid
        assert dec_root["parent_id"] == pre_root["span_id"], "decode root must parent into the prefill root"
        # the scatter-in span belongs to the decode-side request
        scat = next(s for s in spans if s["name"] == "llm.handoff.scatter_in")
        assert scat["attrs"]["request_id"] == rid
    finally:
        tracing.configure(False)


# ----------------------------------------------- int8 (quantized) handoffs


@pytest.fixture(scope="module")
def prefill_eng_q8(params):
    """Int8-cache prefill engine: its handoff blocks ship int8 values +
    per-head scales ([L, kv, T_pad] wire layout) — ~half the bytes."""
    return LLMEngine(
        CFG, params, max_num_seqs=2, max_seq_len=128,
        enable_prefix_caching=False, cache_dtype="int8",
    )


def test_disagg_int8_token_identity(params, prefill_eng_q8):
    """Int8 producer -> codec -> int8 consumer emits exactly what one
    int8 engine that prefills locally emits (greedy): the
    quantized bytes that leave the producer are the bytes a local
    prefill would have written, so the streams are bit-for-bit the same
    cache state."""
    reqs = [
        ([5, 6, 7, 8] * 4, SamplingParams(max_tokens=8, temperature=0.0), 0),
        ([9, 10, 11] * 5, SamplingParams(max_tokens=6, temperature=0.0), 1),
    ]
    kw = dict(max_num_seqs=2, max_seq_len=128, enable_prefix_caching=False, cache_dtype="int8")
    local, local_r = _single_engine_streams(params, reqs, kw)
    dis, dis_r, _ = _disagg_streams(params, prefill_eng_q8, reqs, kw)
    assert dis == local and dis_r == local_r


def test_handoff_codec_validates_quantized_scales(prefill_eng_q8):
    """Scale-tensor shape/dtype are validated on decode: a garbage scale
    must raise HandoffError, never rescale a live pool."""
    kv = prefill_eng_q8.prefill_handoff([3, 4, 5, 6, 7])
    assert kv["k"].dtype == np.int8 and kv["k_scale"].shape == (
        CFG.num_layers, CFG.num_kv_heads, kv["k"].shape[1],
    )
    wire = encode_handoff(kv)
    out = decode_handoff(wire)
    assert out["k_scale"].dtype == np.float32
    bad = dict(wire)
    bad["k_scale"] = wire["k_scale"][:, :1]  # truncated head axis
    with pytest.raises(HandoffError):
        decode_handoff(bad)
    bad = dict(wire)
    bad["k_scale"] = wire["k_scale"].astype(np.float64)
    with pytest.raises(HandoffError):
        decode_handoff(bad)
    bad = dict(wire)
    del bad["k_scale"], bad["v_scale"]  # int8 block without scales
    with pytest.raises(HandoffError):
        decode_handoff(bad)
    bad = dict(wire)
    bad["dtype"] = "float32"  # scales on a claimed-fp block (either lane)
    bad["k"] = bad["k"].astype(np.float32)
    bad["v"] = bad["v"].astype(np.float32)
    with pytest.raises(HandoffError):
        decode_handoff(bad)
    # and the encoder refuses inconsistent producer payloads outright
    bad_kv = dict(kv)
    bad_kv["k_scale"] = kv["k_scale"][:, :, :1]
    with pytest.raises(HandoffError):
        encode_handoff(bad_kv)
    bad_kv = dict(kv)
    del bad_kv["v_scale"]  # unpaired scale lane: HandoffError, not KeyError
    with pytest.raises(HandoffError):
        encode_handoff(bad_kv)


def test_disagg_cross_dtype_requants_transparently(params, prefill_eng, prefill_eng_q8):
    """Producer and consumer cache dtypes may differ — the contract is
    TRANSPARENT requant, locked both ways: an fp block admitted by an
    int8 consumer quantizes at scatter-in (identical to a local int8
    prefill, so oracle-identical), and an int8 block admitted by an fp
    consumer dequantizes and decodes (first token rides the payload's fp
    logits, so it matches the int8 oracle's first token exactly)."""
    prompt = [7, 8, 9, 10] * 4
    sp = SamplingParams(max_tokens=6, temperature=0.0)
    kw = dict(max_num_seqs=2, max_seq_len=128, enable_prefix_caching=False)
    reqs = [(prompt, sp, 0)]
    oracle_q8, _ = _single_engine_streams(params, reqs, {**kw, "cache_dtype": "int8"})

    # fp producer -> int8 consumer: quantize-on-scatter == local prefill
    dis, _, _ = _disagg_streams(params, prefill_eng, reqs, {**kw, "cache_dtype": "int8"})
    assert dis == oracle_q8

    # int8 producer -> fp consumer: dequantized block decodes cleanly
    dis_fp, reasons, _ = _disagg_streams(params, prefill_eng_q8, reqs, kw)
    assert len(dis_fp[0]) == sp.max_tokens and reasons[0] == "length"
    assert dis_fp[0][0] == oracle_q8[0][0]


# ------------------------------------------------- router failure policy
# (real object plane, synthetic KV: no jax compiles in these tests)


@pytest.fixture
def rt_runtime():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2)
    yield
    ray_tpu.shutdown()


def _synthetic_kv(prompt):
    n = len(prompt)
    return {
        "k": np.zeros((2, 64, 2, 4), np.float32),
        "v": np.zeros((2, 64, 2, 4), np.float32),
        "n": n,
        "logits": np.zeros((32,), np.float32),
        "prompt_token_ids": list(prompt),
    }


def test_router_reprefills_when_handoff_evicted(rt_runtime):
    """Handoff object freed before scatter-in: the decode side's bounded
    fetch raises HandoffLostError (no hang), the router re-prefills a
    fresh block, the request succeeds."""
    from ray_tpu.core import direct
    from ray_tpu.llm.disagg import fetch_handoff, publish_handoff

    calls = {"prefill": 0}

    def prefill(prompt):
        calls["prefill"] += 1
        meta, ref = publish_handoff(_synthetic_kv(prompt))
        if calls["prefill"] == 1:
            direct.state().owned.free(ref.id.binary())  # evicted before scatter-in
        return meta, ref

    def decode(meta, ref, prompt, sp):
        try:
            kv = fetch_handoff(ref, meta, timeout_s=1.0, retries=1, retry_wait_s=0.05)
        except HandoffLostError as e:
            # as under Serve: the replica's exception crosses the wire
            # wrapped in TaskError — the router must still unwrap it and
            # re-prefill instead of burning retries on the dead ref
            from ray_tpu.exceptions import TaskError

            raise TaskError.from_exception(e)
        return {"token_ids": [kv["n"]], "finish_reason": "length"}

    router = DisaggRouter(prefill, decode, max_attempts=3)
    t0 = time.time()
    out = router.generate([1, 2, 3], {})
    assert out["token_ids"] == [3]
    assert time.time() - t0 < 30, "lost-handoff retry must be bounded, not a hang"
    s = router.stats()
    assert s["prefills"] == 2 and s["handoffs_lost"] == 1 and s["inflight"] == 0


def test_router_reuses_handoff_across_decode_death(rt_runtime):
    """Decode lane dies AFTER the handoff: the block still lives in its
    owner, so the retry reuses the same ref — no wasted re-prefill."""
    from ray_tpu.llm.disagg import fetch_handoff, publish_handoff

    seen_refs = []

    def prefill(prompt):
        return publish_handoff(_synthetic_kv(prompt))

    def decode(meta, ref, prompt, sp):
        seen_refs.append(ref)
        if len(seen_refs) == 1:
            raise ConnectionError("decode replica died mid-request")
        kv = fetch_handoff(ref, meta, timeout_s=1.0, retries=0)
        return {"token_ids": list(kv["prompt_token_ids"]), "finish_reason": "length"}

    router = DisaggRouter(prefill, decode, max_attempts=3)
    out = router.generate([9, 8], {})
    assert out["token_ids"] == [9, 8]
    assert len(seen_refs) == 2 and seen_refs[0] is seen_refs[1], "same handoff must be reused"
    s = router.stats()
    assert s["prefills"] == 1 and s["decode_retries"] == 1


def test_router_surfaces_terminal_failure(rt_runtime):
    """Every lane dead: a client-visible DisaggRequestError after the
    attempt budget — bounded, never hanging, nothing left in flight."""
    from ray_tpu.llm.disagg import publish_handoff

    def prefill(prompt):
        return publish_handoff(_synthetic_kv(prompt))

    def decode(meta, ref, prompt, sp):
        raise ConnectionError("no decode lane alive")

    router = DisaggRouter(prefill, decode, max_attempts=2)
    with pytest.raises(DisaggRequestError):
        router.generate([1], {})
    s = router.stats()
    assert s["failed"] == 1 and s["inflight"] == 0
