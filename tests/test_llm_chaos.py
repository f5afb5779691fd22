"""Serving-plane fault injection (ray_tpu/chaos.py) + overload plane.

The system-level invariants under EVERY injected fault:

- every request either completes TOKEN-IDENTICAL to the fault-free
  oracle or fails with a TYPED error (OverloadedError /
  DisaggRequestError / KVRouteError / HandoffLostError / the stepper's
  RuntimeError) within a bounded deadline;
- nothing hangs — each scenario asserts its own wall-clock bound, well
  inside the conftest watchdog;
- no silent corruption — after the fault clears, a fresh request on
  every surviving engine still matches the oracle (an injected loss must
  never scatter garbage into a live KV pool).

Plus the overload half of the plane: admission control sheds the lowest
request class first with typed 429s, the estimated-queue-wait test reads
the flight recorder's live EMAs, replica drain finishes in-flight work
and unregisters its cluster-plane routes, and LLMServer.shutdown() exits
the stepper promptly.

Chaos rules are seeded/cleared around every test by the autouse conftest
fixture; scenario tests carry the ``chaos`` marker.
"""

import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import ray_tpu  # noqa: E402
from ray_tpu import chaos  # noqa: E402
from ray_tpu.chaos import ChaosError  # noqa: E402
from ray_tpu.exceptions import ObjectLostError  # noqa: E402
from ray_tpu.llm import LLMEngine, SamplingParams  # noqa: E402
from ray_tpu.llm.disagg import (  # noqa: E402
    DisaggRequestError,
    DisaggRouter,
    fetch_handoff,
    publish_handoff,
)
from ray_tpu.llm.kvplane import CacheAwareRouter, KVPlaneClient, PrefixIndex  # noqa: E402
from ray_tpu.models.llama import LlamaConfig, init_params  # noqa: E402
from ray_tpu.serve.llm import KVPlaneServer, LLMConfig, LLMServer, OpenAIServer  # noqa: E402
from ray_tpu.serve.overload import (  # noqa: E402
    AdmissionConfig,
    AdmissionController,
    OverloadedError,
    ReplicaDrainingError,
    RetryBudget,
    http_error_of,
    is_overloaded,
)

pytestmark = pytest.mark.usefixtures("shared_step_programs")  # many engines of equal configurations: their step programs compile once (conftest.py)

CFG = LlamaConfig.tiny(dtype="float32", remat=False, max_seq_len=128)
SP = SamplingParams(max_tokens=6, temperature=0.0)
RNG = np.random.default_rng(11)
PROMPT = [int(x) for x in RNG.integers(1, CFG.vocab_size - 1, size=24)]
SHARED = [int(x) for x in RNG.integers(1, CFG.vocab_size - 1, size=70)]  # >= one 64-block


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def rt():
    """Real object plane (direct.put_owned / get_owned_view), exactly as
    the disagg and kvplane suites use it."""
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2)
    yield
    ray_tpu.shutdown()


@pytest.fixture(scope="module")
def oracle(params):
    """Fault-free oracle: greedy completions per prompt from one plain
    engine (module pays its compiles once). Every chaos scenario's
    success path must be token-identical to these."""
    eng = LLMEngine(CFG, params, max_num_seqs=2, max_seq_len=128)

    def run(prompt, sp=SP):
        return list(eng.generate(list(prompt), sp).token_ids)

    toks = {"prompt": run(PROMPT), "shared": run(SHARED)}
    toks["run"] = run
    return toks


def _cfg(params, **engine_kwargs):
    engine_kwargs.setdefault("max_num_seqs", 2)
    engine_kwargs.setdefault("max_seq_len", 128)
    return LLMConfig(model_config=CFG, params=params, engine_kwargs=engine_kwargs, prewarm=False)


# ---------------------------------------------------------------- satellites


def test_llmserver_shutdown_exits_stepper_promptly(params):
    """shutdown() sets _stopped AND wakes the idle wait: the stepper must
    exit immediately instead of riding out the 1 s idle tick."""
    srv = LLMServer(_cfg(params))
    time.sleep(0.15)  # let the stepper settle into its idle wait
    t0 = time.perf_counter()
    srv.shutdown()
    dt = time.perf_counter() - t0
    assert not srv._stepper.is_alive()
    assert dt < 0.8, f"shutdown rode out the idle tick: {dt:.2f}s"
    # idempotent, and __del__'s path is the same call
    srv.shutdown()


def test_chaos_marker_registered_and_fixture_reseeds():
    """The autouse fixture hands every test a cleared, deterministically
    seeded plane (same seed => same drop schedule)."""
    assert not chaos.active()
    r = chaos.inject("serve.step", drop_prob=0.5, max_hits=0)
    assert chaos.active() and r.hits == 0
    chaos.seed(123)
    a = [chaos.apply("rpc.x") for _ in range(0)]  # rpc namespace allowed
    del a
    chaos.clear()
    assert not chaos.active()


# ---------------------------------------------------------- admission control


def test_admission_sheds_lowest_class_first(params):
    """Queue past the cap: class 0 sheds with a typed 429 while a higher
    class still admits (shed-lowest-first), and the counters/stats see
    both. The engine queue is built directly so the scenario is
    deterministic against the stepper."""
    srv = LLMServer(
        LLMConfig(
            model_config=CFG, params=params, prewarm=False,
            engine_kwargs={"max_num_seqs": 1, "max_seq_len": 128},
            admission=AdmissionConfig(max_queue_depth=4, class_fracs=(0.25, 1.0)),
        )
    )
    try:
        # three waiting requests without waking the stepper: depth 3
        for _ in range(3):
            srv.engine.add_request(list(PROMPT), SamplingParams(max_tokens=2))
        with pytest.raises(OverloadedError) as ei:
            srv.generate(PROMPT, {"max_tokens": 2, "priority": 0})
        assert ei.value.status_code == 429
        assert ei.value.retry_after_s > 0
        assert ei.value.shed_class == 0
        # priority 1 admits at the same depth (3 < 4 * 1.0) and completes
        out = srv.generate(PROMPT, {"max_tokens": 2, "priority": 1}, timeout_s=120.0)
        assert len(out["token_ids"]) == 2
        stats = srv.overload_stats()
        assert stats["shed_depth"] == 1 and stats["shed_by_class"] == {0: 1}
        assert stats["admitted"] >= 1
    finally:
        srv.shutdown()


def test_estimated_queue_wait_feeds_admission(params):
    """The estimated-queue-wait test: queue_depth x live service-time EMA
    / slots, fed by the flight recorder's lifecycle stamps. A fake EMA
    makes the arithmetic exact; a real completed request then moves the
    EMA off zero (the recorder really feeds it)."""
    eng = LLMEngine(CFG, params, max_num_seqs=1, max_seq_len=128)
    eng._tel.service_ema_s = 10.0
    for _ in range(2):
        eng.add_request(list(PROMPT), SamplingParams(max_tokens=2))
    ac = AdmissionController(eng, AdmissionConfig(max_queue_depth=100, max_queue_wait_s=5.0))
    assert ac.estimate_queue_wait_s() == pytest.approx(20.0)
    with pytest.raises(OverloadedError) as ei:
        ac.check(0)
    assert ac.stats()["shed_wait"] == 1
    assert 0 < ei.value.retry_after_s <= 30.0
    # the ITL path covers the cold window before anything finishes:
    # queued max_tokens (2 x 2) x live ITL EMA / slots
    eng._tel.service_ema_s = 0.0
    eng._tel.itl_ema_s = 0.1
    assert ac.estimate_queue_wait_s() == pytest.approx(0.4)
    eng._tel.itl_ema_s = 0.0
    while eng.has_unfinished():
        eng.step()
    assert eng._tel.service_ema_s > 0.0  # on_finish fed the EMA
    assert eng._tel.itl_ema_s > 0.0  # on_emit fed the EMA
    ac.check(0)  # queue empty again: admits


def test_one_stalled_step_does_not_shed_the_requests_that_come_after_it(params):
    """PR 44's soak: one prefill's first-token wait took 2.2 s where 0.12 is usual, every one of the
    16 lanes saw that one gap, and the ITL EMA, fed a sample a TOKEN, stood at 1.84 s a moment later:
    the wait estimate (queued max_tokens x EMA / slots) read 11.5 s with three requests waiting and
    would have shed the next caller (15 s for class 0) with four, though a slot was free within a
    second. The EMA takes one sample a STEP: a stall is one observation; a stall that LASTS is a
    steady state, and the estimate follows it within a few dozen steps and sheds."""
    from types import SimpleNamespace

    eng = LLMEngine(CFG, params, max_num_seqs=16, max_seq_len=128)
    tel, now = eng._tel, [100.0]
    lanes = [SimpleNamespace(t_first=1.0, t_last=now[0], itls=[]) for _ in range(16)]

    def step(gap: float):
        now[0] += gap
        for st in lanes:
            tel.on_emit(st, now[0])
        tel.on_step(time.perf_counter(), 0, len(lanes), None)

    for _ in range(50):
        step(0.008)
    assert tel.itl_ema_s == pytest.approx(0.008)
    step(2.4)
    assert tel.itl_ema_s == pytest.approx(0.9 * 0.008 + 0.1 * 2.4)
    for _ in range(4):
        eng.add_request(list(PROMPT), SamplingParams(max_tokens=40))
    ac = AdmissionController(eng)  # the defaults a replica runs with: 30 s, half of it for class 0
    assert ac.estimate_queue_wait_s() < 3.0
    ac.check(0)
    assert ac.stats()["shed_wait"] == 0 and ac.stats()["admitted"] == 1
    for _ in range(40):
        step(2.4)
    with pytest.raises(OverloadedError):
        ac.check(0)
    assert ac.stats()["shed_wait"] == 1


def test_admission_check_is_cheap(params):
    """The admission test is host-only dict work — cheap enough to sit
    on every ingress without touching the serving budget (the 1.05x
    zero-overhead gate measures engine.step, which admission never
    enters; this bounds the ingress side)."""
    eng = LLMEngine(CFG, params, max_num_seqs=2, max_seq_len=128)
    ac = AdmissionController(eng)
    ac.check(0)  # warm binds
    t0 = time.perf_counter()
    for _ in range(1000):
        ac.check(0)
    assert time.perf_counter() - t0 < 1.0


def test_stats_estimates_queue_wait_outside_admission_lock(params):
    """Regression for the CCR001 fix in AdmissionController.stats(): the
    queue-wait estimate falls through to engine.host_load(), which waits
    on the ENGINE lock (held for whole serving steps) — it must be
    computed BEFORE taking the admission lock, or every ingress
    check()/record_outcome() stalls behind a step boundary."""
    eng = LLMEngine(CFG, params, max_num_seqs=1, max_seq_len=128)
    eng._tel.service_ema_s = 10.0
    eng.add_request(list(PROMPT), SamplingParams(max_tokens=2))
    ac = AdmissionController(eng)
    real_host_load = eng.host_load
    held_at_host_load = []

    def guarded():
        held_at_host_load.append(ac._lock.locked())
        return real_host_load()

    eng.host_load = guarded
    stats = ac.stats()
    assert stats["queue_wait_est_s"] == pytest.approx(10.0)
    assert held_at_host_load, "stats() stopped reading the live load snapshot"
    assert not any(held_at_host_load), \
        "stats() called engine.host_load() while holding the admission lock"


def test_http_429_mapping_and_priority_plumbing():
    """OverloadedError carries 429 + retry-after through the proxy
    mapping, directly and through a wire-wrapped cause chain; the OpenAI
    body's "priority" reaches SamplingParams."""
    code, body = http_error_of(OverloadedError("busy", retry_after_s=2.0))
    assert code == 429 and body["retry_after_s"] == 2.0
    wrapped = RuntimeError("task failed")
    wrapped.cause = OverloadedError("busy", retry_after_s=3.0)
    assert is_overloaded(wrapped)
    code, body = http_error_of(wrapped)
    # the surviving cause's REAL hint wins over the wrapper's tb fallback
    assert code == 429 and body["retry_after_s"] == 3.0
    tb_only = RuntimeError("remote")
    tb_only.tb_str = "... ray_tpu.serve.overload.OverloadedError: busy ..."
    assert is_overloaded(tb_only) and http_error_of(tb_only)[0] == 429
    drain_tb = RuntimeError("remote")
    drain_tb.tb_str = "... ray_tpu.serve.overload.ReplicaDrainingError: draining ..."
    assert is_overloaded(drain_tb) and http_error_of(drain_tb)[0] == 429
    assert http_error_of(RuntimeError("plain")) is None
    assert not is_overloaded(RuntimeError("plain"))
    sp = OpenAIServer._sampling(None, {"max_tokens": 4, "priority": 2})
    assert sp["priority"] == 2
    assert SamplingParams(**sp).priority == 2
    with pytest.raises(ValueError):
        SamplingParams(priority=-1)
    assert issubclass(ReplicaDrainingError, OverloadedError)


# -------------------------------------------------------------- retry budget


class _Ref:
    class id:  # noqa: N801 — mimics ObjectRef.id
        @staticmethod
        def binary():
            return b"ref"

        @staticmethod
        def hex():
            return "ref"


def test_retry_budget_is_shared_across_attempt_kinds():
    """ONE budget covers prefill retries, handoff-lost re-prefills and
    decode failovers; the handoff is reused across decode deaths (no
    re-prefill) and exhaustion is a typed terminal error + counter."""
    calls = {"prefill": 0, "decode": 0}

    def prefill(prompt):
        calls["prefill"] += 1
        return {"nbytes": 0}, _Ref()

    def decode(meta, ref, prompt, sp):
        calls["decode"] += 1
        raise RuntimeError("decode lane dead")

    router = DisaggRouter(prefill, decode, max_attempts=3)
    with pytest.raises(DisaggRequestError):
        router.generate([1, 2, 3])
    assert calls == {"prefill": 1, "decode": 3}  # block reused, 3 attempts total
    st = router.stats()
    assert st["budget_exhausted"] == 1 and st["failed"] == 1 and st["decode_retries"] == 3
    b = RetryBudget(2)
    assert b.try_spend() and b.try_spend() and not b.try_spend()
    assert b.remaining == 0


def test_routers_surface_overload_as_429():
    """A fleet whose every lane sheds is saturated, not broken: both
    routers re-raise OverloadedError (429 + the replica's backoff hint)
    instead of their terminal error class."""

    def prefill(prompt):
        return {"nbytes": 0}, _Ref()

    def decode(meta, ref, prompt, sp):
        # a TaskError-shaped wrapper: the hint lives on the CAUSE, the
        # router must dig it out (not read the wrapper's default)
        w = RuntimeError("TaskError wrapper")
        w.cause = OverloadedError("replica busy", retry_after_s=3.0, shed_class=1)
        raise w

    router = DisaggRouter(prefill, decode, max_attempts=2)
    with pytest.raises(OverloadedError) as ei:
        router.generate([1, 2, 3], {"priority": 1})
    assert ei.value.retry_after_s == 3.0 and ei.value.shed_class == 1
    assert router.stats()["shed"] == 1

    def submit(rid, prompt, sp):
        raise OverloadedError("replica draining", retry_after_s=1.5)

    kvr = CacheAwareRouter(PrefixIndex(), submit, ["r0", "r1"], max_attempts=2)
    with pytest.raises(OverloadedError) as ei:
        kvr.generate([1, 2, 3])
    assert ei.value.retry_after_s == 1.5
    st = kvr.stats()
    assert st["shed"] == 1 and st["budget_exhausted"] == 1

    # a fleet SMALLER than the budget: the ranked list running out is a
    # failure, not a budget exhaustion (the counter must not over-report)
    kvr2 = CacheAwareRouter(PrefixIndex(), submit, ["r0"], max_attempts=3)
    with pytest.raises(OverloadedError):
        kvr2.generate([1, 2, 3])
    assert kvr2.stats()["budget_exhausted"] == 0


# ---------------------------------------------------------------- drain


def test_drain_finishes_inflight_unregisters_and_sheds(params, rt):
    """drain(): in-flight completes token-identical, the cluster index
    forgets the replica (route dies before the bytes), stashed handoffs
    drop, new requests shed with ReplicaDrainingError, stepper exits."""
    idx = PrefixIndex(ttl_s=30.0)
    plane = KVPlaneClient(idx, "drainA", publish_min_hits=1)
    srv = KVPlaneServer(
        LLMConfig(
            model_config=CFG, params=params, prewarm=False,
            engine_kwargs={"max_num_seqs": 2, "max_seq_len": 128, "kv_plane": plane},
        ),
        idx, "drainA",
    )
    oracle_eng = LLMEngine(CFG, params, max_num_seqs=2, max_seq_len=128)
    want = list(oracle_eng.generate(list(SHARED), SP).token_ids)

    results = {}

    def bg():
        results["out"] = srv.generate(list(SHARED), {"max_tokens": SP.max_tokens}, timeout_s=120.0)

    th = threading.Thread(target=bg)
    th.start()
    # wait until the request is actually in flight before draining
    deadline = time.time() + 30
    while not srv.engine.has_unfinished() and time.time() < deadline:
        time.sleep(0.005)
    t0 = time.perf_counter()
    res = srv.drain(timeout_s=60.0)
    th.join(timeout=60)
    assert not th.is_alive()
    assert time.perf_counter() - t0 < 60
    assert res["drained"] and res["inflight_finished"] and res["aborted"] == 0
    assert results["out"]["token_ids"] == want  # finished, token-identical
    assert res["kvplane_keys_unregistered"] >= 1  # SHARED minted a 64-block
    assert idx.stats()["keys"] == 0  # route died before the bytes
    with pytest.raises(ReplicaDrainingError):
        srv.generate(PROMPT, {"max_tokens": 2})
    assert not srv._stepper.is_alive()
    assert srv.overload_stats()["draining"] and srv.overload_stats()["shed_draining"] == 1


def test_shutdown_with_inflight_fails_waiters_fast(params):
    """A bare shutdown() (no drain) with work in flight must fail the
    blocked waiters immediately — nothing will ever step them — and
    subsequent requests fail fast with the typed failover signal."""
    srv = LLMServer(_cfg(params))
    chaos.inject("serve.step", delay_s=0.2)  # keep the request in flight
    results = {}

    def bg():
        try:
            srv.generate(list(PROMPT), {"max_tokens": 64}, timeout_s=120.0)
        except Exception as e:  # noqa: BLE001
            results["err"] = e

    th = threading.Thread(target=bg)
    th.start()
    deadline = time.time() + 30
    while not srv.engine.has_unfinished() and time.time() < deadline:
        time.sleep(0.005)
    t0 = time.perf_counter()
    srv.shutdown()
    th.join(timeout=10.0)
    chaos.clear()
    assert not th.is_alive(), "waiter did not fail fast on shutdown"
    assert time.perf_counter() - t0 < 10.0
    assert isinstance(results.get("err"), RuntimeError)
    with pytest.raises((ReplicaDrainingError, RuntimeError)):
        srv.generate(PROMPT, {"max_tokens": 2}, timeout_s=5.0)


def test_drain_deadline_aborts_and_wakes_waiters(params):
    """A drain whose deadline passes with work in flight must abort the
    leftovers AND deliver their finals — the blocked waiter wakes with
    finish_reason 'aborted' immediately, never riding out its own
    timeout (abort outputs only publish via a step; drain runs one)."""
    srv = LLMServer(_cfg(params))
    # stall the stepper so the request cannot finish inside the deadline
    chaos.inject("serve.step", delay_s=0.2)
    results = {}

    def bg():
        results["out"] = srv.generate(list(PROMPT), {"max_tokens": 64}, timeout_s=120.0)

    th = threading.Thread(target=bg)
    th.start()
    deadline = time.time() + 30
    while not srv.engine.has_unfinished() and time.time() < deadline:
        time.sleep(0.005)
    t0 = time.perf_counter()
    res = srv.drain(timeout_s=0.3)
    th.join(timeout=10.0)
    chaos.clear()
    assert not th.is_alive(), "waiter did not wake after the drain abort"
    assert time.perf_counter() - t0 < 10.0
    assert not res["inflight_finished"] and res["aborted"] == 1
    assert results["out"]["finish_reason"] == "aborted"
    assert not srv._stepper.is_alive()


# ------------------------------------------------------------ chaos scenarios


def _disagg_pair(params):
    """Prefill + decode engines over the real object plane (the disagg
    suite's wiring, condensed)."""
    pre = LLMEngine(CFG, params, max_num_seqs=2, max_seq_len=128, enable_prefix_caching=False)
    dec = LLMEngine(CFG, params, max_num_seqs=2, max_seq_len=128, enable_prefix_caching=False)

    def prefill(prompt):
        return publish_handoff(pre.prefill_handoff(prompt))

    def decode(meta, ref, prompt, sp):
        kv = fetch_handoff(ref, meta, timeout_s=2.0, retries=1, retry_wait_s=0.02)
        rid = dec.add_prefilled(kv, SamplingParams(**sp))
        while dec.has_unfinished():
            for o in dec.step():
                if o.request_id == rid and o.finished:
                    return {"request_id": rid, "token_ids": o.token_ids, "finish_reason": o.finish_reason}
        raise RuntimeError("decode drained without finishing")

    return pre, dec, prefill, decode


@pytest.mark.chaos
def test_chaos_lost_and_delayed_handoff_fetch(params, rt, oracle):
    """Dropped handoff fetch: the first decode's bounded retries exhaust
    into HandoffLostError, the router re-prefills, the request completes
    token-identical. A delay-only rule completes without any retry. The
    surviving decode pool stays clean."""
    pre, dec, prefill, decode = _disagg_pair(params)
    router = DisaggRouter(prefill, decode, max_attempts=3)

    # decode's fetch budget is retries=1 => 2 attempts; lose both
    chaos.inject("handoff.fetch", raises=ObjectLostError, max_hits=2)
    t0 = time.perf_counter()
    out = router.generate(list(PROMPT), {"max_tokens": SP.max_tokens, "temperature": 0.0})
    wall = time.perf_counter() - t0
    assert out["token_ids"] == oracle["prompt"]
    assert wall < 60.0
    assert router.stats()["handoffs_lost"] == 1
    chaos.clear()

    chaos.inject("handoff.fetch", delay_s=0.05)
    out = router.generate(list(PROMPT), {"max_tokens": SP.max_tokens, "temperature": 0.0})
    assert out["token_ids"] == oracle["prompt"]
    assert router.stats()["handoffs_lost"] == 1  # delay is not loss
    chaos.clear()

    # no silent corruption: a clean request on the surviving pair
    out = router.generate(list(PROMPT), {"max_tokens": SP.max_tokens, "temperature": 0.0})
    assert out["token_ids"] == oracle["prompt"]


@pytest.mark.chaos
def test_chaos_owned_object_loss_bounded_typed_failure(params, rt, oracle):
    """Permanent owned-object loss at the direct plane: every fetch
    fails, the shared budget exhausts, and the TYPED terminal error
    surfaces in bounded time — no hang, and the decode pool was never
    touched (fresh request matches the oracle after the fault clears).
    A bounded put_owned fault retries through the same budget."""
    pre, dec, prefill, decode = _disagg_pair(params)
    router = DisaggRouter(prefill, decode, max_attempts=2)

    chaos.inject("direct.get_owned_view", raises=ObjectLostError)
    t0 = time.perf_counter()
    with pytest.raises(DisaggRequestError):
        router.generate(list(PROMPT), {"max_tokens": 4, "temperature": 0.0})
    assert time.perf_counter() - t0 < 30.0
    st = router.stats()
    assert st["budget_exhausted"] == 1 and st["handoffs_lost"] == 2
    chaos.clear()

    # one-shot publish fault: attempt 1 loses the prefill, attempt 2 lands
    chaos.inject("direct.put_owned", raises=RuntimeError, max_hits=1)
    out = router.generate(list(PROMPT), {"max_tokens": SP.max_tokens, "temperature": 0.0})
    assert out["token_ids"] == oracle["prompt"]
    chaos.clear()

    # no silent corruption on either engine
    out = router.generate(list(PROMPT), {"max_tokens": SP.max_tokens, "temperature": 0.0})
    assert out["token_ids"] == oracle["prompt"]


@pytest.mark.chaos
def test_chaos_replica_kill_mid_decode_fails_over(params, rt, oracle):
    """A raises rule on serve.step kills replica r0's stepper mid-decode
    — exactly a replica crash: the waiter gets the stepper-death error,
    check_health trips, and the router fails over to r1, which completes
    token-identical. Bounded wall, no hang."""
    srv0 = LLMServer(_cfg(params))
    srv1 = LLMServer(_cfg(params))
    try:
        handles = {"r0": srv0, "r1": srv1}

        def submit(rid, prompt, sp):
            return handles[rid].generate(prompt, sp, timeout_s=120.0)

        router = CacheAwareRouter(PrefixIndex(), submit, ["r0", "r1"], max_attempts=2)
        # two clean decode ticks, then the killer lands mid-request. Only
        # r0 steps (r1 is idle and the idle wait never reaches the site).
        chaos.inject("serve.step", raises=ChaosError, after=2, max_hits=1)
        t0 = time.perf_counter()
        out = router.generate(list(PROMPT), {"max_tokens": SP.max_tokens, "temperature": 0.0})
        wall = time.perf_counter() - t0
        assert out["token_ids"] == oracle["prompt"]
        assert wall < 60.0
        assert router.stats()["retries"] == 1
        assert srv0._stepper_error is not None and "ChaosError" in srv0._stepper_error
        with pytest.raises(RuntimeError):
            srv0.check_health()
        srv1.check_health()
        chaos.clear()
        # survivor's pool is clean
        out = srv1.generate(list(PROMPT), {"max_tokens": SP.max_tokens}, timeout_s=120.0)
        assert out["token_ids"] == oracle["prompt"]
    finally:
        srv0.shutdown()
        srv1.shutdown()


@pytest.mark.chaos
def test_chaos_replica_stall_degrades_queue_wait_not_correctness(params, rt, oracle):
    """A delay rule on serve.step stalls the replica's ticks: requests
    still complete token-identical (slow, never wrong, never hung)."""
    srv = LLMServer(_cfg(params))
    try:
        chaos.inject("serve.step", delay_s=0.05, max_hits=20)
        t0 = time.perf_counter()
        out = srv.generate(list(PROMPT), {"max_tokens": SP.max_tokens}, timeout_s=120.0)
        assert out["token_ids"] == oracle["prompt"]
        assert time.perf_counter() - t0 < 60.0
    finally:
        srv.shutdown()


@pytest.mark.chaos
def test_chaos_index_death_breaker_and_recovery_over_serve_classes(params, rt, oracle):
    """The kvplane circuit breaker driven through INJECTED index faults
    over the real serve classes (KVIndexServer + KVPlaneServer), not
    hand-mocked transports:

    - injected index death -> every plane RPC fails -> after 2
      consecutive failures the breaker opens;
    - while open, admissions short-circuit (zero new index RPCs) and
      serving degrades to LOCAL prefill — outputs token-identical;
    - fault cleared + cooldown elapsed -> the heartbeat probe closes the
      breaker, and the replica re-registers so a peer replica gets a
      REMOTE-tier hit again (full recovery, token-identical)."""
    from ray_tpu.serve.llm import KVIndexServer

    isrv = KVIndexServer(ttl_s=60.0)
    plane = KVPlaneClient(
        isrv, "cb0", publish_min_hits=1,
        index_down_cooldown_s=0.3, heartbeat_every_s=1e6,  # probes only when told
    )
    srv = KVPlaneServer(
        LLMConfig(
            model_config=CFG, params=params, prewarm=False,
            engine_kwargs={"max_num_seqs": 2, "max_seq_len": 128, "kv_plane": plane},
        ),
        isrv, "cb0",
    )
    srv2 = None
    try:
        # healthy: publish SHARED through the real serve class
        out = srv.generate(list(SHARED), {"max_tokens": SP.max_tokens}, timeout_s=120.0)
        assert out["token_ids"] == oracle["shared"]
        assert isrv.stats()["keys"] >= 1
        # consume the one unthrottled heartbeat (fresh client's stamp is
        # 0) so the idle stepper can't probe mid-scenario
        plane.maybe_heartbeat()

        rule = chaos.inject("kvplane.index", raises=ConnectionError)
        fresh = [int(x) for x in RNG.integers(1, CFG.vocab_size - 1, size=70)]
        t0 = time.perf_counter()
        out = srv.generate(list(fresh), {"max_tokens": SP.max_tokens}, timeout_s=120.0)
        assert out["token_ids"] == oracle["run"](fresh)  # degraded to local prefill
        assert time.perf_counter() - t0 < 60.0
        # miss -> lookup fail (1), store -> publish register fail (2): open
        assert plane.index_down()
        hits_at_open = rule.hits
        fresh2 = [int(x) for x in RNG.integers(1, CFG.vocab_size - 1, size=70)]
        out = srv.generate(list(fresh2), {"max_tokens": SP.max_tokens}, timeout_s=120.0)
        assert out["token_ids"] == oracle["run"](fresh2)
        assert rule.hits == hits_at_open, "open breaker must short-circuit, not re-RPC"

        chaos.clear()
        time.sleep(0.35)  # cooldown lapses; breaker half-open
        plane._last_heartbeat = 0.0
        plane.maybe_heartbeat()  # probe succeeds -> closed + re-registration
        assert not plane.index_down()
        # re-offer self-heal: a local hit republishes what the open
        # breaker kept cluster-invisible
        out = srv.generate(list(SHARED), {"max_tokens": SP.max_tokens}, timeout_s=120.0)
        assert out["token_ids"] == oracle["shared"]
        assert isrv.stats()["keys"] >= 1

        # full recovery: a PEER replica now gets a remote-tier hit
        srv2 = KVPlaneServer(
            LLMConfig(
                model_config=CFG, params=params, prewarm=False,
                engine_kwargs={"max_num_seqs": 2, "max_seq_len": 128},
            ),
            isrv, "cb1", publish_min_hits=1,
        )
        out = srv2.generate(list(SHARED), {"max_tokens": SP.max_tokens}, timeout_s=120.0)
        assert out["token_ids"] == oracle["shared"]
        stats = srv2.kvplane_stats()
        assert stats["remote"]["hits"] == 1, f"expected a remote-tier hit, got {stats}"
    finally:
        srv.shutdown()
        if srv2 is not None:
            srv2.shutdown()


# -------------------------------------------------- preemption & migration


def _kv_router_pair(params, **sp_defaults):
    """Two LLMServer replicas behind a CacheAwareRouter with BOTH legs
    wired (submit + resume_submit) — the chaos preemption suite's
    standard fleet. r0 gets the traffic; r1 idles (an idle stepper never
    reaches the chaos sites, so the preemption notice lands on r0
    deterministically)."""
    from ray_tpu.llm.kvplane import CacheAwareRouter, PrefixIndex

    srv0, srv1 = LLMServer(_cfg(params, **sp_defaults)), LLMServer(_cfg(params, **sp_defaults))
    handles = {"r0": srv0, "r1": srv1}

    def submit(rid, prompt, sp):
        return handles[rid].generate(prompt, sp, timeout_s=120.0)

    def resume_submit(rid, meta, ref, sp):
        return handles[rid].resume_from_migration(meta, ref, sp, timeout_s=120.0)

    router = CacheAwareRouter(
        PrefixIndex(), submit, ["r0", "r1"], max_attempts=3, resume_submit=resume_submit,
    )
    return srv0, srv1, router


def _hold_in_flight():
    """Slow every stepper tick (the chaos plane's own delay rule, until the next ``chaos.clear()``)
    for a test that needs its request IN FLIGHT when a notice lands: sixteen tokens of the toy
    model are over in some 16 ms on an idle machine, before ``_wait_tokens`` has looked twice
    or the preemption has taken the engine's lock. Without it
    ``test_chaos_preempt_seeded_and_checkpoint_lost`` failed 8 of 10 runs ALONE (PR 40: "never
    reached 4 tokens in flight" six times, ``resumed == 0`` twice) and passed beside five busy
    workers. At 50 ms a tick the notice has 400 ms and more to land in."""
    chaos.inject("serve.step", delay_s=0.05)


def _wait_tokens(srv, n, deadline_s=30.0):
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        with srv.engine._lock:
            sts = [s for s in srv.engine._requests.values() if not s.finished]
        if sts and all(len(s.token_ids) >= n for s in sts):
            return
        time.sleep(0.003)
    raise AssertionError(f"replica never reached {n} tokens in flight")


@pytest.mark.chaos
@pytest.mark.migrate
def test_chaos_preempt_migrates_inflight_to_peer(params, rt, oracle):
    """The serve.preempt site end to end: a preemption notice lands on
    the replica actively decoding two requests; drain(mode='migrate')
    checkpoints BOTH mid-decode, each waiter gets the typed
    RequestMigratedError, the router splices both checkpoints on the
    peer, and the clients see byte-identical streams with zero
    duplicated/dropped tokens at the splice. Bounded wall, zero hangs,
    and the surviving pool passes the no-silent-corruption re-check."""
    from ray_tpu.llm.migrate import RequestMigratedError

    srv0, srv1, router = _kv_router_pair(params)
    try:
        sp = {"max_tokens": 16, "temperature": 0.0}
        want = oracle["run"](PROMPT, SamplingParams(max_tokens=16, temperature=0.0))
        want2 = oracle["run"](SHARED, SamplingParams(max_tokens=16, temperature=0.0))
        results = {}

        def client_router():
            # leg 1: the ROUTER handles the whole failover
            results["a"] = router.generate(list(PROMPT), dict(sp))

        def client_direct():
            # leg 2: a bare client sees the typed resume signal itself
            # (the load-balancing tie-break would route a second router
            # request to the idle peer, so this one pins srv0 directly)
            try:
                results["b"] = srv0.generate(list(SHARED), dict(sp), timeout_s=120.0)
            except Exception as e:  # noqa: BLE001
                results["b"] = e

        th1 = threading.Thread(target=client_router)
        th2 = threading.Thread(target=client_direct)
        _hold_in_flight()
        th1.start(), th2.start()
        _wait_tokens(srv0, 4)
        # the preemption notice: SIGTERM-with-deadline, delivered once
        chaos.inject("serve.preempt", drop_prob=1.0, max_hits=1)
        t0 = time.perf_counter()
        th1.join(timeout=120), th2.join(timeout=120)
        chaos.clear()
        assert not th1.is_alive() and not th2.is_alive(), "clients hung across preemption"
        assert time.perf_counter() - t0 < 120.0
        # router leg: spliced on the peer, byte-identical, zero dup/drop
        assert results["a"]["token_ids"] == want
        st = router.stats()
        assert st["migrations"] == 1 and st["resumed"] == 1, st
        # direct leg: the waiter got the typed signal with a live ref and
        # the peer splices it token-identically
        err = results["b"]
        assert isinstance(err, RequestMigratedError), err
        out2 = srv1.resume_from_migration(err.migration_meta, err.migration_ref, dict(sp))
        assert out2["token_ids"] == want2
        assert out2["token_ids"][: err.migration_meta["emitted"]] == want2[: err.migration_meta["emitted"]]
        assert not srv0._stepper.is_alive()  # the replica actually died
        # evacuation accounting on the source replica
        snap = srv0.engine.telemetry()
        assert sum(1 for r in snap["requests"] if r["reason"] == "migrated") == 2
        # no silent corruption: the surviving peer still matches the oracle
        out = srv1.generate(list(PROMPT), {"max_tokens": SP.max_tokens}, timeout_s=120.0)
        assert out["token_ids"] == oracle["prompt"]
        # and the dead replica sheds typed (a router retry fails over)
        with pytest.raises(ReplicaDrainingError):
            srv0.generate(list(PROMPT), {"max_tokens": 2})
    finally:
        srv0.shutdown()
        srv1.shutdown()


@pytest.mark.chaos
@pytest.mark.migrate
def test_chaos_preempt_seeded_and_checkpoint_lost(params, rt, oracle):
    """Seeded sampling migrates token-identically (the ADVANCED key
    rides the checkpoint), and a checkpoint lost before the fetch
    degrades to re-prefill — token-identical for a seeded request (the
    replay re-derives from the seed) — inside the same retry budget."""
    srv0, srv1, router = _kv_router_pair(params)
    try:
        seeded = SamplingParams(max_tokens=12, temperature=0.8, seed=5, top_k=16)
        want = oracle["run"](PROMPT, seeded)
        sp = {"max_tokens": 12, "temperature": 0.8, "seed": 5, "top_k": 16}
        results = {}

        def client():
            results["out"] = router.generate(list(PROMPT), dict(sp))

        th = threading.Thread(target=client)
        _hold_in_flight()
        th.start()
        _wait_tokens(srv0, 4)
        chaos.inject("serve.preempt", drop_prob=1.0, max_hits=1)
        th.join(timeout=120)
        chaos.clear()
        assert not th.is_alive()
        assert results["out"]["token_ids"] == want
        assert router.stats()["resumed"] == 1

        # second round on the survivor pair: this time the checkpoint is
        # LOST at the object plane before the peer can fetch it — the
        # router's resume leg degrades to a full re-prefill, which for a
        # seeded request replays to the identical stream
        srv2 = LLMServer(_cfg(params))
        handles2 = {"r0": srv1, "r1": srv2}

        def submit(rid, prompt, p):
            return handles2[rid].generate(prompt, p, timeout_s=120.0)

        def resume_submit(rid, meta, ref, p):
            return handles2[rid].resume_from_migration(meta, ref, p, timeout_s=120.0)

        router2 = CacheAwareRouter(
            PrefixIndex(), submit, ["r0", "r1"], max_attempts=3, resume_submit=resume_submit,
        )
        try:
            results2 = {}

            def client2():
                results2["out"] = router2.generate(list(PROMPT), dict(sp))

            th2 = threading.Thread(target=client2)
            _hold_in_flight()
            th2.start()
            _wait_tokens(srv1, 4)
            chaos.inject("direct.get_owned_view", raises=ObjectLostError, max_hits=8)
            chaos.inject("serve.preempt", drop_prob=1.0, max_hits=1)
            t0 = time.perf_counter()
            th2.join(timeout=120)
            chaos.clear()
            assert not th2.is_alive(), "client hung on a lost checkpoint"
            assert time.perf_counter() - t0 < 120.0
            assert results2["out"]["token_ids"] == want  # re-prefill replayed the seed
            assert router2.stats()["migrations"] == 1 and router2.stats()["resumed"] == 0
        finally:
            srv2.shutdown()
    finally:
        srv0.shutdown()
        srv1.shutdown()


@pytest.mark.chaos
@pytest.mark.migrate
def test_preempt_deadline_zero_aborts_typed(params, rt, oracle):
    """A preemption whose deadline already passed checkpoints NOTHING:
    every in-flight request aborts with a typed 429 (ReplicaDrainingError
    — the router's re-prefill signal), never a partial result and never
    a hang; the oracle-identical completion lands on the peer."""
    srv0, srv1, router = _kv_router_pair(params)
    try:
        results = {}

        def client():
            results["out"] = router.generate(list(PROMPT), {"max_tokens": 16, "temperature": 0.0})

        th = threading.Thread(target=client)
        _hold_in_flight()
        th.start()
        _wait_tokens(srv0, 2)
        t0 = time.perf_counter()
        res = srv0.preempt(deadline_s=0.0)  # SIGTERM with no grace left
        chaos.clear()
        th.join(timeout=120)
        assert not th.is_alive()
        assert time.perf_counter() - t0 < 60.0
        assert res["mode"] == "migrate" and res["aborted"] == 1 and res["migrated"] == []
        want = oracle["run"](PROMPT, SamplingParams(max_tokens=16, temperature=0.0))
        assert results["out"]["token_ids"] == want  # re-prefilled on the peer
        assert router.stats()["resumed"] == 0
    finally:
        srv0.shutdown()
        srv1.shutdown()


def test_drain_and_release_handoffs_idempotent(params, rt):
    """Calling drain() twice (a controller retrying its shutdown hook
    races the stepper) and release_handoffs() twice must be no-ops, not
    double-frees: the second drain returns the first record with
    ``repeated=True``, the index sees exactly ONE drop_replica, and the
    plane client never re-frees its owned blocks."""
    idx = PrefixIndex(ttl_s=30.0)
    calls = {"drop": 0}
    real_drop = idx.drop_replica

    def counting_drop(replica):
        calls["drop"] += 1
        return real_drop(replica)

    idx.drop_replica = counting_drop
    plane = KVPlaneClient(idx, "idem0", publish_min_hits=1)
    srv = KVPlaneServer(
        LLMConfig(
            model_config=CFG, params=params, prewarm=False,
            engine_kwargs={"max_num_seqs": 2, "max_seq_len": 128, "kv_plane": plane},
        ),
        idx, "idem0",
    )
    out = srv.generate(list(SHARED), {"max_tokens": 4}, timeout_s=120.0)
    assert out["finish_reason"] in ("length", "stop")
    # engine-side: release_handoffs twice is (count, then 0), never an error
    with srv.engine._lock:
        srv.engine._handoffs["stash"] = {"k": None}  # a stranded stash
    assert srv.engine.release_handoffs() == 1
    assert srv.engine.release_handoffs() == 0  # idempotent
    first = srv.drain(timeout_s=30.0)
    freed_once = plane.counts["unpublished_blocks"]
    second = srv.drain(timeout_s=30.0)
    assert second.get("repeated") is True and second["drained"]
    assert calls["drop"] == 1, "second drain re-dropped the replica at the index"
    assert plane.counts["unpublished_blocks"] == freed_once, "double-free of owned blocks"
    assert plane.shutdown() == 0  # the client's own second shutdown is a no-op
    assert first["kvplane_keys_unregistered"] >= 1


def test_retry_after_jitter_bounds(params):
    """OverloadedError.retry_after_s is jittered ±25% (seeded) so a shed
    herd's synchronized retries don't re-saturate the replica: every
    hint stays inside [0.75, 1.25] x the clamped estimate, and the
    spread is real (not a constant)."""
    eng = LLMEngine(CFG, params, max_num_seqs=1, max_seq_len=128)
    eng._tel.service_ema_s = 10.0
    for _ in range(2):
        eng.add_request(list(PROMPT), SamplingParams(max_tokens=2))
    ac = AdmissionController(eng, AdmissionConfig(max_queue_depth=100, max_queue_wait_s=5.0))
    base = ac.estimate_queue_wait_s()  # 2 * 10 / 1 = 20, clamped base
    base = min(max(base, 0.25), 30.0)
    hints = []
    for _ in range(40):
        with pytest.raises(OverloadedError) as ei:
            ac.check(0)
        hints.append(ei.value.retry_after_s)
    assert all(0.75 * base - 1e-9 <= h <= 1.25 * base + 1e-9 for h in hints), hints
    assert len(set(round(h, 6) for h in hints)) > 1, "jitter is not live"
    assert max(hints) - min(hints) > 0.01 * base


def test_admission_cold_start_seeded_from_prewarm(params):
    """Admission cold-start: prewarm's compile-heavy request must not
    poison the service-time EMA (a multi-second 'service time' would
    shed everything through the est-queue-wait cap), and after prewarm
    the EMAs are WARM-seeded, so the wait cap is live from the first
    real request instead of vacuous."""
    srv = LLMServer(
        LLMConfig(model_config=CFG, params=params, prewarm=True,
                  engine_kwargs={"max_num_seqs": 2, "max_seq_len": 128})
    )
    try:
        tel = srv.engine._tel
        assert tel.service_ema_s > 0.0, "EMA unseeded after prewarm (wait cap vacuous)"
        assert tel.itl_ema_s > 0.0
        assert tel.service_ema_s < 2.0, (
            f"EMA poisoned by compile time: {tel.service_ema_s:.2f}s"
        )
        # a compile-scale EMA injected later is RESET by the seeding path
        tel.service_ema_s = 100.0
        srv._seed_admission_emas()
        assert 0.0 < tel.service_ema_s < 2.0
        # the cap is live, not shedding: an idle replica admits
        srv._admission.check(0)
    finally:
        srv.shutdown()


@pytest.mark.chaos
def test_chaos_index_restart_repopulates_via_heartbeat(params, rt, oracle):
    """Kill and restart a BLANK KVIndexServer mid-traffic: the restarted
    index knows nobody, the publisher's heartbeat sees fewer keys than
    it holds (the key-count path) and re-registers every live block,
    and the peer replica gets REMOTE-tier hits again — full recovery
    without any republish traffic from scratch."""
    from ray_tpu.llm.kvplane import PrefixIndex as _PI
    from ray_tpu.serve.llm import KVIndexServer

    isrv = KVIndexServer(ttl_s=60.0)
    plane = KVPlaneClient(isrv, "ir0", publish_min_hits=1, heartbeat_every_s=1e6)
    srv = KVPlaneServer(
        LLMConfig(
            model_config=CFG, params=params, prewarm=False,
            engine_kwargs={"max_num_seqs": 2, "max_seq_len": 128, "kv_plane": plane},
        ),
        isrv, "ir0",
    )
    srv2 = None
    try:
        out = srv.generate(list(SHARED), {"max_tokens": SP.max_tokens}, timeout_s=120.0)
        assert out["token_ids"] == oracle["shared"]
        keys_before = isrv.stats()["keys"]
        assert keys_before >= 1
        # mid-traffic restart: the deployment handle survives, its state
        # blanks — exactly a controller replacing a dead index replica
        isrv.index = _PI(ttl_s=60.0)
        assert isrv.stats()["keys"] == 0
        # the heartbeat's key count (0 < published) triggers re-registration
        plane._last_heartbeat = 0.0
        plane.maybe_heartbeat()
        assert isrv.stats()["keys"] == keys_before, "re-registration never happened"
        # the peer now gets a remote-tier hit off the repopulated index
        srv2 = KVPlaneServer(
            LLMConfig(
                model_config=CFG, params=params, prewarm=False,
                engine_kwargs={"max_num_seqs": 2, "max_seq_len": 128},
            ),
            isrv, "ir1", publish_min_hits=1,
        )
        out = srv2.generate(list(SHARED), {"max_tokens": SP.max_tokens}, timeout_s=120.0)
        assert out["token_ids"] == oracle["shared"]
        assert srv2.kvplane_stats()["remote"]["hits"] == 1
    finally:
        srv.shutdown()
        if srv2 is not None:
            srv2.shutdown()


@pytest.mark.chaos
def test_chaos_index_delay_bounded_by_engine_paths(params, rt, oracle):
    """A slow (not dead) index: delay rules on the index RPCs must only
    slow admissions, never change output or hang the engine."""
    idx = PrefixIndex(ttl_s=60.0)
    plane = KVPlaneClient(idx, "slow0", publish_min_hits=1, heartbeat_every_s=1e6)
    eng = LLMEngine(CFG, params, max_num_seqs=2, max_seq_len=128, kv_plane=plane)
    chaos.inject("kvplane.index", delay_s=0.05, max_hits=10)
    t0 = time.perf_counter()
    out = eng.generate(list(SHARED), SP)
    assert list(out.token_ids) == oracle["shared"]
    assert time.perf_counter() - t0 < 60.0
    assert not plane.index_down()  # slow is not dead: breaker stays closed


# ------------------------------------------------- fault taxonomy (ERR catalog)


def test_fault_taxonomy_registry_agreement():
    """The three-way contract the lint gate's chaos-coverage check locks:
    every chaos site declares its fault modes (FAULT_MODES), every declared
    mode is registered in SERVING_ERRORS with a sane wire classification,
    and @serving_error stamped the class so instance probes resolve."""
    from ray_tpu import exceptions as exc

    assert set(chaos.FAULT_MODES) == set(chaos.SITES)
    for site, names in chaos.FAULT_MODES.items():
        assert names, f"site {site} declares no fault modes"
        for name in names:
            spec = exc.SERVING_ERRORS[name]
            assert 400 <= spec.status_code < 600, f"{name}: {spec.status_code}"
    spec = exc.serving_error_spec(ChaosError("x"))
    assert spec is exc.SERVING_ERRORS["ChaosError"]
    assert ChaosError.status_code == spec.status_code
    assert ChaosError.retryable == spec.retryable


@pytest.mark.chaos
def test_chaos_suspend_fault_is_migration_error_with_cause(params):
    """An injected fault at llm.suspend surfaces as the typed
    MigrationError with the injected ChaosError intact on __cause__ (the
    ERR catalog's cause-chain discipline, end to end), and the refusal
    leaves the conversation RUNNING — a later suspend still works."""
    from ray_tpu.exceptions import serving_error_spec
    from ray_tpu.llm.migrate import MigrationError

    eng = LLMEngine(CFG, params, max_num_seqs=2, max_seq_len=128)
    rid = eng.add_request(list(PROMPT), SP)
    for _ in range(3):
        eng.step()
    chaos.inject("llm.suspend", raises=ChaosError)
    with pytest.raises(MigrationError) as ei:
        eng.suspend_request(rid, publish=False)
    assert isinstance(ei.value.__cause__, ChaosError)
    spec = serving_error_spec(ei.value)
    assert spec is not None and spec.status_code == 500 and not spec.retryable
    chaos.clear()
    assert not eng._requests[rid].finished  # refusal mutated nothing
    assert eng.suspend_request(rid, publish=False)["nbytes"] > 0


@pytest.mark.chaos
def test_chaos_stepper_death_is_typed_stepper_died(params):
    """A raises rule on serve.step kills the stepper: the waiter and the
    health probe both see the typed StepperDiedError (503, retryable) —
    still a RuntimeError subclass, so pre-taxonomy callers keep matching."""
    from ray_tpu.exceptions import serving_error_spec
    from ray_tpu.serve.overload import StepperDiedError

    srv = LLMServer(_cfg(params))
    try:
        chaos.inject("serve.step", raises=ChaosError, max_hits=1)
        with pytest.raises(StepperDiedError) as ei:
            srv.generate(list(PROMPT), {"max_tokens": SP.max_tokens}, timeout_s=30.0)
        assert isinstance(ei.value, RuntimeError)
        assert "ChaosError" in str(ei.value)
        spec = serving_error_spec(ei.value)
        assert spec is not None and spec.status_code == 503 and spec.retryable
        with pytest.raises(StepperDiedError):
            srv.check_health()
    finally:
        srv.shutdown()


def test_stream_stall_and_handoff_failures_map_typed():
    """Regression for the ERR002 fixes in serve/llm.py: the stream-stall
    abort raises GetTimeoutError (504, retryable — still a TimeoutError
    for pre-taxonomy callers) chained on the queue.Empty that tripped it,
    and a failed prefill-only request raises HandoffError (500, not
    retryable — still a ValueError). http_error_of maps both off the
    SERVING_ERRORS table, walking the cause chain, with retry_after_s
    only on the retryable row."""
    import queue as _queue

    from ray_tpu.exceptions import GetTimeoutError, serving_error_spec
    from ray_tpu.llm.disagg.handoff import HandoffError
    from ray_tpu.serve.overload import http_error_of

    assert issubclass(GetTimeoutError, TimeoutError)
    assert issubclass(HandoffError, ValueError)

    try:
        try:
            raise _queue.Empty()
        except _queue.Empty as e:
            raise GetTimeoutError("stream r1 produced no token for 300s") from e
    except GetTimeoutError as stall:
        assert isinstance(stall.__cause__, _queue.Empty)
        spec = serving_error_spec(stall)
        assert spec is not None and spec.status_code == 504 and spec.retryable
        status, body = http_error_of(stall)
        assert status == 504 and "stream r1" in body["error"]

    handoff = HandoffError("prefill-only request r2 failed: error")
    spec = serving_error_spec(handoff)
    assert spec is not None and spec.status_code == 500 and not spec.retryable
    status, body = http_error_of(handoff)
    assert status == 500 and "retry_after_s" not in body
