"""Live request migration (llm/migrate.py): the migration oracle.

The standing invariant: a request checkpointed MID-DECODE on one engine
and restored on a second engine emits a byte-identical token stream to
the never-migrated oracle — with zero duplicated or dropped tokens at
the splice — across layouts (slots + paged), cache dtypes (fp + int8
wire with per-head scales over the transparent-requant path), greedy +
seeded sampling, and with spec-ngram on (sticky effective-k/EMA
migrating with the request). Plus: codec validation (MigrationError,
never garbage into a live pool), cold checkpoints of waiting requests,
the object-plane publish/fetch lifecycle (MigrationLostError bounded,
never a hang), and both routers' resume-on-peer failover leg.
"""

import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from plain_reference import reference_stream  # noqa: E402

import ray_tpu  # noqa: E402
from ray_tpu import chaos  # noqa: E402
from ray_tpu.exceptions import ObjectLostError  # noqa: E402
from ray_tpu.llm import LLMEngine, SamplingParams, migrate  # noqa: E402
from ray_tpu.llm.disagg import DisaggRouter  # noqa: E402
from ray_tpu.llm.kvplane import CacheAwareRouter, PrefixIndex  # noqa: E402
from ray_tpu.llm.migrate import (  # noqa: E402
    MigrationError,
    MigrationLostError,
    RequestMigratedError,
    migration_lost,
    migration_of,
)
from ray_tpu.llm.spec import SpecConfig  # noqa: E402
from ray_tpu.models.llama import LlamaConfig, init_params  # noqa: E402

pytestmark = [pytest.mark.migrate, pytest.mark.usefixtures("shared_step_programs")]  # an oracle, a source and a destination a case: one set of programs a configuration

CFG = LlamaConfig.tiny(dtype="float32", remat=False, max_seq_len=128)
RNG = np.random.default_rng(17)
PROMPT = [int(x) for x in RNG.integers(1, CFG.vocab_size - 1, size=24)]
GREEDY = SamplingParams(max_tokens=14, temperature=0.0)
SEEDED = SamplingParams(max_tokens=14, temperature=0.8, seed=7, top_k=20)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


def _mk(params, layout="slots", dtype=None, spec=False, **kw):
    kw.setdefault("max_num_seqs", 2)
    kw.setdefault("max_seq_len", 128)
    if spec:
        kw.setdefault("speculative", SpecConfig(k=3))
    return LLMEngine(CFG, params, kv_layout=layout, cache_dtype=dtype, **kw)


def _run_until(eng, rid, n_tokens, deadline_s=60.0):
    """Step until the request has emitted >= n_tokens (host view). By the clock, not by a count of
    steps: a request deferred behind its async prefix lookup makes ``step()`` return at once, and
    500 such steps were over before the fetch worker had run once (beside five busy xdist
    workers, PR 40's fifth whole run); a step that emits nothing yields to that worker."""
    end = time.time() + deadline_s
    while time.time() < end:
        with eng._lock:
            st = eng._requests.get(rid)
            done = st is None or st.finished or len(st.token_ids) >= n_tokens
        if done:
            return
        if not eng.step():
            time.sleep(0.001)
    raise AssertionError(f"request never reached {n_tokens} tokens")


def _finish(eng, rid):
    toks = None
    while eng.has_unfinished():
        for o in eng.step():
            if o.request_id == rid and o.finished:
                toks = o.token_ids
    assert toks is not None, "request drained without finishing"
    return toks


def _migrate_mid_decode(params, sp, layout, dtype, spec, cut=6, wire=True):
    """Checkpoint at `cut` emitted tokens, restore on a fresh engine,
    return (oracle tokens, pre-splice tokens, post-restore tokens)."""
    oracle = _mk(params, layout, dtype, spec)
    want = list(oracle.generate(list(PROMPT), sp).token_ids)
    src = _mk(params, layout, dtype, spec)
    rid = src.add_request(list(PROMPT), sp)
    _run_until(src, rid, cut)
    state = src.checkpoint_request(rid)
    pre = list(src._requests[rid].token_ids)
    assert state["emitted_token_ids"] == pre
    assert src.finish_migrated(rid)
    assert src._requests[rid].finish_reason == "migrated"
    if wire:
        state = migrate.decode(migrate.encode(state))
    dst = _mk(params, layout, dtype, spec)
    rid2 = dst.restore_request(state)
    toks = _finish(dst, rid2)
    return want, pre, toks


# ------------------------------------------------------------- the oracle


@pytest.mark.parametrize("layout", ["slots", "paged"])
@pytest.mark.parametrize("dtype", [None, "int8"])
def test_migration_oracle_greedy_and_seeded(params, layout, dtype):
    """Byte-identical to the never-migrated oracle, zero dup/drop at the
    splice, for both layouts x fp/int8 wire x greedy + seeded sampling
    (the seeded lane's ADVANCED key rides the checkpoint — restore never
    resets from the seed)."""
    for sp in (GREEDY, SEEDED):
        want, pre, toks = _migrate_mid_decode(params, sp, layout, dtype, spec=False)
        assert toks == want, f"{layout}/{dtype}/temp={sp.temperature}"
        assert toks[: len(pre)] == pre  # nothing re-emitted or dropped
        assert len(pre) < len(toks)  # the splice actually continued


@pytest.mark.parametrize("layout", ["slots", "paged"])
def test_migration_oracle_spec_ngram(params, layout):
    """Speculative decoding composes: the spec history lane rebuilds
    from prompt+emitted and the adaptive-k EMA migrates sticky. Greedy
    (spec's lossless regime; seeded spec output depends on round
    structure, which a splice legitimately changes — same caveat as the
    spec suite's own oracle)."""
    want, pre, toks = _migrate_mid_decode(params, GREEDY, layout, None, spec=True)
    assert toks == want
    assert toks[: len(pre)] == pre


def test_migration_oracle_int8_spec(params):
    """The full stack: paged + int8 wire/scales + spec-ngram."""
    want, pre, toks = _migrate_mid_decode(params, GREEDY, "paged", "int8", spec=True)
    assert toks == want
    assert toks[: len(pre)] == pre


def test_cross_layout_migration(params):
    """Blocks are layout-agnostic (same contract as the disagg handoff):
    a slots producer's checkpoint restores into a paged consumer."""
    oracle = _mk(params, "paged")
    want = list(oracle.generate(list(PROMPT), GREEDY).token_ids)
    src = _mk(params, "slots")
    rid = src.add_request(list(PROMPT), GREEDY)
    _run_until(src, rid, 6)
    state = migrate.decode(migrate.encode(src.checkpoint_request(rid)))
    dst = _mk(params, "paged")
    toks = _finish(dst, dst.restore_request(state))
    assert toks == want


def test_a_restored_stream_continues_the_plain_references(params):
    """Checkpointed mid-decode and restored on a second engine, a stream
    goes on as the plain reference's (tests/plain_reference.py: the
    whole-sequence forward with no cache, a seeded lane's key chain from
    its seed) — everywhere else in this file the splice is compared with
    another engine's stream."""
    for sp in (GREEDY, SEEDED):
        _, pre, toks = _migrate_mid_decode(params, sp, "slots", None, spec=False)
        want, _ = reference_stream(CFG, params, PROMPT, sp)
        assert toks == want, f"temp={sp.temperature}: restored {toks} != reference {want}"
        assert 0 < len(pre) < len(toks) and toks[: len(pre)] == pre


def test_spec_controller_state_migrates(params):
    """The adaptive-k EMA/effective-k pair rides the wire and seeds the
    restoring controller under the NEW request id."""
    src = _mk(params, spec=True)
    rid = src.add_request(list(PROMPT), GREEDY)
    _run_until(src, rid, 6)
    # force a recognizable controller state (the checkpoint's settle of
    # the in-flight round folds one more observation into the EMA, so
    # compare against the post-settle export, not the forced literal)
    src._controller._state[rid] = [0.625, 2]
    state = migrate.decode(migrate.encode(src.checkpoint_request(rid)))
    exp = src._controller.export(rid)
    assert state["spec"] == {"ema": exp[0], "k": exp[1]} and state["spec"]["k"] == 2
    dst = _mk(params, spec=True)
    rid2 = dst.restore_request(state)
    _run_until(dst, rid2, len(state["emitted_token_ids"]) + 1)
    exp = dst._controller.export(rid2)
    assert exp is not None and exp[1] <= 3  # restored, clamped into [k_min, k]


# -------------------------------------------------------- cold checkpoints


def test_cold_checkpoint_waiting_request(params):
    """A request still WAITING (blocked behind a full engine) has no
    bound lane: its checkpoint ships without a KV block and the peer
    re-admits it like a recompute preemption — token-identical."""
    oracle = _mk(params)
    want = list(oracle.generate(list(PROMPT), GREEDY).token_ids)
    src = _mk(params, max_num_seqs=1)
    src.add_request([int(x) for x in RNG.integers(1, CFG.vocab_size - 1, size=16)],
                    SamplingParams(max_tokens=32, temperature=0.0))
    src.step()  # blocker occupies the one slot
    rid = src.add_request(list(PROMPT), GREEDY)
    state = src.checkpoint_request(rid)
    assert state.get("k") is None and state["emitted_token_ids"] == []
    state = migrate.decode(migrate.encode(state))
    dst = _mk(params)
    toks = _finish(dst, dst.restore_request(state))
    assert toks == want


def test_cold_checkpoint_sampled_with_tokens_refuses(params):
    """A sampled request with generated tokens but NO bound lane cannot
    checkpoint (its live key is gone — a cold re-admission would
    resample the suffix off-oracle): typed MigrationError, the router's
    re-prefill leg is the fallback."""
    src = _mk(params, "paged", max_num_seqs=2, num_pages=11, page_size=16)
    # both admit, then growth collides: the younger sampled request gets
    # recompute-preempted back to waiting WITH generated tokens
    r0 = src.add_request(list(PROMPT), SamplingParams(max_tokens=100, temperature=0.7, seed=3))
    r1 = src.add_request(list(PROMPT[:16]), SamplingParams(max_tokens=100, temperature=0.7, seed=4))
    for _ in range(200):
        src.step()
        with src._lock:
            preempted = [
                rid for rid in (r0, r1)
                if (st := src._requests.get(rid)) is not None
                and not st.finished and st.slot < 0 and st.token_ids
            ]
        if preempted:
            break
    assert preempted, "pool pressure never preempted a sampled request"
    with pytest.raises(MigrationError):
        src.checkpoint_request(preempted[0])


# --------------------------------------------------------- codec validation


def test_checkpoint_refuses_untransferable_state(params):
    src = _mk(params)
    with pytest.raises(MigrationError):
        src.checkpoint_request("nope")
    rid = src.add_request(list(PROMPT), GREEDY)
    _run_until(src, rid, 2)
    out_rid = src.add_prefill_request(list(PROMPT[:8]))
    with pytest.raises(MigrationError):  # prefill-only stub
        src.checkpoint_request(out_rid)
    s_rid = src.add_request(list(PROMPT[:8]), SamplingParams(max_tokens=4), stream=True)
    with pytest.raises(MigrationError):  # streaming consumer
        src.checkpoint_request(s_rid)
    src.abort_request(rid)
    with pytest.raises(MigrationError):  # finished
        src.checkpoint_request(rid)


def test_wire_validation_never_garbage_into_a_pool(params):
    """Every corruption a wire dict can carry dies in decode with
    MigrationError — before any array touches a live engine."""
    src = _mk(params)
    rid = src.add_request(list(PROMPT), GREEDY)
    _run_until(src, rid, 5)
    state = src.checkpoint_request(rid)
    good = migrate.encode(state)
    migrate.decode(good)  # sanity

    import copy

    def corrupt(fn):
        w = copy.deepcopy(good)
        fn(w)
        with pytest.raises(MigrationError):
            migrate.decode(w)

    corrupt(lambda w: w.update(kind="kv_handoff"))
    corrupt(lambda w: w["live"].update(version=99))
    corrupt(lambda w: w.update(k=w["k"][:, :-1]))  # truncated block
    corrupt(lambda w: w.update(dtype="int8"))  # dtype mismatch
    corrupt(lambda w: w["live"].update(emitted_token_ids=w["live"]["emitted_token_ids"][:-2]))
    corrupt(lambda w: w["live"].pop("rng_key"))
    corrupt(lambda w: w["live"].update(rng_key=np.zeros(2, np.float32)))  # wrong dtype
    corrupt(lambda w: w["live"].update(sampling={}))
    corrupt(lambda w: w["live"].update(n_prompt=5))  # coverage mismatch
    # engine-side geometry guard: a block wider than the consumer's row
    tiny = LLMEngine(CFG, init_params(CFG, jax.random.PRNGKey(1)), max_num_seqs=2, max_seq_len=32)
    with pytest.raises(MigrationError):
        tiny.restore_request(migrate.decode(good))


def test_int8_wire_scale_validation(params):
    src = _mk(params, dtype="int8")
    rid = src.add_request(list(PROMPT), GREEDY)
    _run_until(src, rid, 5)
    wire = migrate.encode(src.checkpoint_request(rid))
    import copy

    w = copy.deepcopy(wire)
    del w["k_scale"]
    with pytest.raises(MigrationError):
        migrate.decode(w)
    w = copy.deepcopy(wire)
    w["k_scale"] = w["k_scale"].astype(np.float64)
    with pytest.raises(MigrationError):
        migrate.decode(w)


# ------------------------------------------------------- object plane + loss


@pytest.fixture(scope="module")
def rt():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2)
    yield
    ray_tpu.shutdown()


def test_publish_fetch_roundtrip_and_loss(params, rt):
    """The checkpoint rides the object plane owner-local (put_owned):
    fetch validates and restores token-identically; a lost object
    surfaces as MigrationLostError after bounded retries, never a hang."""
    import time as _time

    oracle = _mk(params)
    want = list(oracle.generate(list(PROMPT), GREEDY).token_ids)
    src = _mk(params)
    rid = src.add_request(list(PROMPT), GREEDY)
    _run_until(src, rid, 6)
    meta, ref = migrate.publish(src.checkpoint_request(rid))
    # the checkpoint's settle of the in-flight step may add one token
    assert meta["hot"] and meta["nbytes"] > 0 and meta["emitted"] >= 6
    dst = _mk(params)
    rid2 = dst.restore_request(ref)  # restore_request accepts the raw ref
    assert _finish(dst, rid2) == want

    chaos.inject("direct.get_owned_view", raises=ObjectLostError)
    t0 = _time.perf_counter()
    with pytest.raises(MigrationLostError):
        migrate.fetch(ref, meta, timeout_s=1.0, retries=1, retry_wait_s=0.02)
    assert _time.perf_counter() - t0 < 30.0
    chaos.clear()


# -------------------------------------------------------- router resume legs


class _Ref:
    class id:  # noqa: N801 — mimics ObjectRef.id
        @staticmethod
        def binary():
            return b"mref"

        @staticmethod
        def hex():
            return "mref"


def test_migration_signal_probes():
    err = RequestMigratedError("req-1", {"nbytes": 4, "emitted": 3}, _Ref())
    assert migration_of(err) == ("req-1", {"nbytes": 4, "emitted": 3}, _Ref) or migration_of(err)[2] is not None
    wrapped = RuntimeError("TaskError wrapper")
    wrapped.cause = err
    got = migration_of(wrapped)
    assert got is not None and got[0] == "req-1" and got[2] is err.migration_ref
    assert migration_of(RuntimeError("plain")) is None
    lost = RuntimeError("wire")
    lost.cause = MigrationLostError("gone")
    assert migration_lost(lost)
    tb_only = RuntimeError("remote")
    tb_only.tb_str = "... ray_tpu.llm.migrate.MigrationLostError: gone ..."
    assert migration_lost(tb_only)
    assert not migration_lost(RuntimeError("plain"))


def test_disagg_router_resume_leg_beats_reprefill():
    """Decode lane preempted mid-request: the router resumes the
    checkpoint on a peer (recompute = 0) instead of re-prefilling, and
    the whole ladder spends ONE shared budget."""
    calls = {"prefill": 0, "decode": 0, "resume": 0}
    mig_err = RequestMigratedError("d-1", {"nbytes": 8, "emitted": 5}, _Ref())

    def prefill(prompt):
        calls["prefill"] += 1
        return {"nbytes": 0}, _Ref()

    def decode(meta, ref, prompt, sp):
        calls["decode"] += 1
        w = RuntimeError("TaskError wrapper")  # wire-wrapped, attribute walk
        w.cause = mig_err
        raise w

    def resume(meta, ref, sp):
        calls["resume"] += 1
        assert meta["emitted"] == 5 and ref is mig_err.migration_ref
        return {"request_id": "d-1", "token_ids": list(range(9)), "finish_reason": "length"}

    router = DisaggRouter(prefill, decode, resume=resume, max_attempts=3)
    out = router.generate([1, 2, 3])
    assert out["token_ids"] == list(range(9))
    assert calls == {"prefill": 1, "decode": 1, "resume": 1}  # no re-prefill
    st = router.stats()
    assert st["migrations"] == 1 and st["resumed"] == 1 and st["failed"] == 0


def test_disagg_router_lost_checkpoint_falls_back_to_reprefill():
    """Degradation order: migrate -> re-prefill -> typed error. A lost
    checkpoint clears the resume leg and the next attempt re-prefills."""
    calls = {"prefill": 0, "decode": 0, "resume": 0}

    def prefill(prompt):
        calls["prefill"] += 1
        return {"nbytes": 0}, _Ref()

    def decode(meta, ref, prompt, sp):
        calls["decode"] += 1
        if calls["decode"] == 1:
            raise RequestMigratedError("d-2", {"nbytes": 8, "emitted": 5}, _Ref())
        return {"request_id": "d-2", "token_ids": [1, 2], "finish_reason": "length"}

    def resume(meta, ref, sp):
        calls["resume"] += 1
        raise MigrationLostError("owner exited")

    router = DisaggRouter(prefill, decode, resume=resume, max_attempts=3)
    out = router.generate([1, 2, 3])
    assert out["token_ids"] == [1, 2]
    # the prefill handoff survived (its owner isn't the dying replica):
    # the fallback re-DECODES from the surviving block, no second prefill
    assert calls == {"prefill": 1, "decode": 2, "resume": 1}
    assert router.stats()["migrations"] == 1 and router.stats()["resumed"] == 0


def test_kvplane_router_resume_leg():
    """CacheAwareRouter: a preempted replica's migration signal turns the
    next-ranked attempt into a resume; budget exhaustion stays typed."""
    seen = []

    def submit(rid, prompt, sp):
        seen.append(("submit", rid))
        raise RequestMigratedError("k-1", {"nbytes": 8, "emitted": 4}, _Ref())

    def resume_submit(rid, meta, ref, sp):
        seen.append(("resume", rid))
        assert meta["emitted"] == 4
        return {"request_id": "k-1", "token_ids": [5, 6, 7], "finish_reason": "stop"}

    router = CacheAwareRouter(
        PrefixIndex(), submit, ["r0", "r1"], max_attempts=3, resume_submit=resume_submit,
    )
    out = router.generate([1, 2, 3])
    assert out["token_ids"] == [5, 6, 7]
    assert seen == [("submit", "r0"), ("resume", "r1")]
    st = router.stats()
    assert st["migrations"] == 1 and st["resumed"] == 1


def test_migration_splice_telemetry(params):
    """The restored request's first post-splice token lands in the
    migration metrics: outcome counters on both engines, splice series
    on the peer, finish reason 'migrated' on the source."""
    src = _mk(params)
    rid = src.add_request(list(PROMPT), GREEDY)
    _run_until(src, rid, 5)
    state = src.checkpoint_request(rid)
    src.finish_migrated(rid)
    snap = src.telemetry()
    reasons = [r["reason"] for r in snap["requests"]]
    assert "migrated" in reasons
    dst = _mk(params)
    rid2 = dst.restore_request(state)
    _finish(dst, rid2)
    with dst._lock:
        pass  # engine settled; the splice histogram observed on first emit
    from ray_tpu.llm.telemetry import instruments

    assert "rt_llm_migrations_total" in instruments()
