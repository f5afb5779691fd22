"""PR 55: a step that stands still says why. The sentinel beside the stepper (``llm/telemetry.StallSentinel``)
and what it leaves in the flight log, the stepping thread's CPU time beside a step's wall time, and the wait
for the engine's lock on a request's record.

Host-side and CPU-only. The clocks asserted on are sleeps this file plants and the thread's own CPU clock,
which busy neighbours cannot move; what a loaded box can stretch (how late a wake-up is) is only bounded
from the side it cannot reach."""

import logging
import threading
import time

import pytest

jax = pytest.importorskip("jax")

from ray_tpu.llm import LLMEngine, SamplingParams, telemetry  # noqa: E402
from ray_tpu.models.llama import LlamaConfig  # noqa: E402
from ray_tpu.serve.llm import LLMConfig, LLMServer, OpenAIServer  # noqa: E402

CFG = LlamaConfig.tiny(dtype="float32", remat=False, max_seq_len=256)


def _engine(**kw):
    kw.setdefault("max_num_seqs", 2)
    kw.setdefault("max_seq_len", 128)
    kw.setdefault("enable_prefix_caching", False)
    return LLMEngine(CFG, **kw)


def _server(cls=LLMServer, **engine_kwargs):
    engine_kwargs.setdefault("max_num_seqs", 4)
    engine_kwargs.setdefault("max_seq_len", 128)
    return cls(LLMConfig(model_config=CFG, engine_kwargs=engine_kwargs))


@pytest.fixture
def session(tmp_path, monkeypatch):
    from ray_tpu.util import state

    monkeypatch.setattr(state, "session_dir", lambda pid=None: str(tmp_path))
    return tmp_path


def _sentinels() -> list:
    return [t for t in threading.enumerate() if t.name == "llm-sentinel"]


class _Unready:
    """What a blocking read waits for while the device has not produced it."""

    def is_ready(self):
        return False


def _stand_once(eng, seconds: float, waited=None):
    """The engine's next ``_drain_wait`` with something to read publishes its arrays (or ``waited`` in their
    place), as the real one does, and then stands ``seconds`` before it reads them."""
    real, stood = eng._drain_wait, []

    def stands(pending):
        if pending is not None and not stood:
            stood.append(eng._tel.recorder.step_count + 1)
            eng._tel.blocked_on = pending[:-1] if waited is None else waited
            time.sleep(seconds)
        return real(pending)

    eng._drain_wait = stands
    return stood


def test_a_step_that_stands_in_its_drain_leaves_captures_that_say_the_host_was_late(session, caplog):
    srv = _server()
    try:
        srv.generate([1, 2, 3], {"max_tokens": 3})  # compile
        stood = _stand_once(srv.engine, 0.6)
        with caplog.at_level(logging.WARNING, logger="ray_tpu.llm"):
            srv.generate([4, 5, 6], {"max_tokens": 4})
        snap = srv.telemetry()
    finally:
        srv.shutdown()
    caps = [c for c in snap["stalls"] if c["step"] == stood[0]]
    # at 250 and at 500 ms, each up to a tick (and whatever the box adds) late; none at a second: the step stood 0.6
    assert [c["stage"] for c in caps] == ["llm.step.drain_wait"] * 2
    assert 0.25 <= caps[0]["age_s"] < 0.5 <= caps[1]["age_s"] < 1.0
    for c in caps:
        assert c["ready"] and all(c["ready"])  # the result was there: the host slept
        by_name = {t["name"]: t for t in c["threads"]}
        assert {"llm-stepper", "llm-sentinel", "MainThread"} <= set(by_name)
        assert len(by_name["MainThread"]["frames"]) == 3 and by_name["llm-stepper"]["frames"][0].endswith(" stands")
        # the stepper's frames go deeper than three (on the chip three ended inside jax's dispatch): down to its loop
        assert "_stage_decode" in by_name["llm-stepper"]["frames"][1] and by_name["llm-stepper"]["frames"][3].endswith(" _step_loop")
        assert c["t"] > 0 and len(c["tick_late_ms"]) >= 1 and c["process"]["voluntary"] > 0 and len(c["loadavg"]) == 3
        assert c["stepper"]["run_ns"] > 0  # this platform has /proc: the stepping thread's scheduler account
        assert any(comm and ticks > 0 and state in "RSDTtXZIPKW" for _, comm, state, ticks in c["native"])
        assert "memory" not in c  # the CPU backend gives no memory_stats()
    row = next(s for s in snap["steps"] if s["step"] == stood[0])
    assert row["captures"] == 2 and row["drain_wait_ms"] >= 600.0
    assert 0.0 < row["cpu_ms"] < row["wall_ms"] - 600.0  # the thread's clock stood while it slept
    assert all("captures" not in s for s in snap["steps"] if s["step"] != stood[0])
    assert not [r for r in caplog.records if "has stood in" in r.getMessage()]  # the line is for 2 s and more
    # the log on the disk holds them as its ``stalls`` section, and says how many its bound dropped
    log = telemetry.load_flight()
    assert [c["age_s"] for c in log["stalls"] if c["step"] == stood[0]] == [c["age_s"] for c in caps]
    assert (log["headers"][-1]["stalls"], log["headers"][-1]["dropped_stalls"]) == (len(log["stalls"]), 0)


def test_a_read_of_an_unready_result_says_the_device_is_late_and_warns_once_at_two_seconds(caplog):
    eng = _engine()
    sentinel = telemetry.StallSentinel(eng._tel)  # not started: the test takes its looks itself, at the ages it plants
    tel = eng._tel
    t0 = time.perf_counter()
    tel.at, tel.blocked_on = ("llm.step.prefill.first_tokens", t0), [(_Unready(), _Unready(), None)]
    assert sentinel.look(t0 + 0.1) is None  # younger than a quarter of a second
    first = sentinel.look(t0 + 0.3)
    assert first["ready"] == [False, False] and first["stage"] == "llm.step.prefill.first_tokens" and first["age_s"] == 0.3
    assert sentinel.look(t0 + 0.45) is None  # the same stage, not yet twice as old
    with caplog.at_level(logging.WARNING, logger="ray_tpu.llm"):
        late = sentinel.look(t0 + 2.1)  # a sentinel that was late itself jumps thresholds, and still warns
        assert sentinel.look(t0 + 3.9) is None
        again = sentinel.look(t0 + 4.2)
    assert late["ready"] == again["ready"] == [False, False]
    lines = [r.getMessage() for r in caplog.records if "has stood in" in r.getMessage()]
    assert len(lines) == 1 and "llm.step.prefill.first_tokens for 2." in lines[0] and "not ready" in lines[0]
    assert sentinel.take_step()[0] == 3 and sentinel.take_step() == (None, None)
    assert [c["age_s"] for c in eng.telemetry()["stalls"]] == [0.3, 2.1, 4.2]  # in order of time, from both rings
    # a stage that is not a blocking read carries no ``ready``; the stepper asleep until work arrives is no stall at all
    tel.blocked_on = None
    tel.at = ("llm.step.emit", time.perf_counter() - 0.3)
    assert "ready" not in sentinel.look()
    tel.at = ("llm.stepper.wait", time.perf_counter() - 0.9)
    assert sentinel.look() is None
    tel.at = None
    assert sentinel.look() is None and not sentinel._next


def test_the_young_captures_of_ordinary_waits_cannot_push_a_stalls_out_of_the_ring():
    rec = telemetry.FlightRecorder()
    rec.record_stall({"t": 1.0, "age_s": 2.0, "step": 1})
    for i in range(rec.STALLS):
        rec.record_stall({"t": 2.0 + i, "age_s": 0.25, "step": 2 + i})
    kept = rec.snapshot()["stalls"]
    assert len(kept) == rec.STALLS // 2 + 1 and kept[0]["step"] == 1 and rec.stall_count == rec.STALLS + 1


def test_a_platform_without_proc_or_memory_stats_leaves_those_keys_out(monkeypatch):
    eng = _engine()
    sentinel = telemetry.StallSentinel(eng._tel, stepper=threading.current_thread())

    def no_proc(*a, **kw):
        raise FileNotFoundError("/proc")

    monkeypatch.setattr(telemetry, "_proc_text", no_proc)
    monkeypatch.setattr(telemetry.os, "getloadavg", no_proc)
    cap = sentinel.capture("llm.step.drain_wait", 0.3)
    assert {"t", "step", "stage", "age_s", "tick_late_ms", "threads", "process"} <= set(cap)
    assert not {"native", "stepper", "pressure", "loadavg", "memory", "ready"} & set(cap)

    class Chip:
        id = 0

        def memory_stats(self):
            return {"bytes_in_use": 15_900_000_000, "peak_bytes_in_use": 16_100_000_000, "bytes_limit": 16_900_000_000,
                    "largest_free_block_bytes": 1 << 20, "num_allocs": 7, "pool_bytes": 3}

    class Bare(Chip):
        def memory_stats(self):
            raise NotImplementedError

    monkeypatch.setattr(jax, "local_devices", lambda: [Chip(), Bare()])
    assert "memory" not in sentinel.capture("llm.step.drain_wait", 0.3)  # one device that raises: the source is left out whole
    monkeypatch.setattr(jax, "local_devices", lambda: [Chip()])
    (mem,) = sentinel.capture("llm.step.drain_wait", 0.3)["memory"]
    assert mem == {"device": 0, "bytes_in_use": 15_900_000_000, "peak_bytes_in_use": 16_100_000_000,
                   "largest_free_block_bytes": 1 << 20, "num_allocs": 7, "bytes_limit": 16_900_000_000}


def test_a_bare_engine_starts_no_thread_and_shutdown_leaves_no_sentinel_behind():
    before = len(_sentinels())
    eng = _engine()
    eng.generate([[1, 2, 3]], SamplingParams(max_tokens=3))
    assert len(_sentinels()) == before and eng._tel.sentinel is None
    row = eng.telemetry()["steps"][-1]
    assert row["cpu_ms"] > 0 and "captures" not in row and "tick_late_ms" not in row
    srv = _server()
    assert len(_sentinels()) == before + 1 and srv._sentinel.is_alive() and srv.engine._tel.sentinel is srv._sentinel
    srv.shutdown()
    assert len(_sentinels()) == before and not srv._sentinel.is_alive()
    srv.shutdown()  # twice is once


def test_the_sentinel_records_how_late_its_own_wakes_were():
    eng = _engine()
    sentinel = telemetry.StallSentinel(eng._tel).start()
    try:
        deadline = time.time() + 5.0
        while len(sentinel._lates) < 3 and time.time() < deadline:
            time.sleep(0.02)
        assert len(sentinel._lates) >= 3 and all(late > -1.0 for late in sentinel._lates)  # a sleep never ends early
        eng.generate([[1, 2, 3]], SamplingParams(max_tokens=3))
        assert all(s.get("tick_late_ms") is None or s["tick_late_ms"] >= 1.0 for s in eng.telemetry()["steps"])
    finally:
        sentinel.stop()
    assert not sentinel.is_alive()


@pytest.mark.parametrize("entry", ["add_request", "add_prefill_request", "add_prefilled"])
def test_a_request_carries_its_wait_for_the_engines_lock(entry):
    eng = _engine()
    kv = eng.prefill_remote([1, 2, 3, 4]) if entry == "add_prefilled" else None
    holding, times = threading.Event(), {}

    def hold():
        with eng._lock:
            holding.set()
            time.sleep(0.05)
            times["released"] = time.perf_counter()

    holder = threading.Thread(target=hold)
    holder.start()
    assert holding.wait(5.0)
    asked = time.perf_counter()
    if entry == "add_request":
        rid = eng.add_request([1, 2, 3, 4], SamplingParams(max_tokens=2))
    elif entry == "add_prefill_request":
        rid = eng.add_prefill_request([1, 2, 3, 4])
    else:
        rid = eng.add_prefilled(kv, SamplingParams(max_tokens=2))
    holder.join(5.0)
    assert not holder.is_alive()
    while eng.has_unfinished():
        eng.step()
    rec = next(r for r in eng.telemetry()["requests"] if r["request_id"] == rid)
    # 0.05 less what it took this thread to get from the holder's signal to its call: the holder's own clock says how much
    assert rec["lock_wait_s"] == pytest.approx(times["released"] - asked, abs=0.02) and 0.02 < rec["lock_wait_s"] < 0.3
    # one that met no holder waited microseconds
    rid = eng.add_request([5, 6, 7], SamplingParams(max_tokens=2))
    while eng.has_unfinished():
        eng.step()
    assert next(r for r in eng.telemetry()["requests"] if r["request_id"] == rid)["lock_wait_s"] < 0.01


def test_the_wait_of_the_admission_check_for_the_lock_is_on_the_record_too():
    """On the chip the wait was found HERE (my chip run, PR 55: request threads stood in ``host_load``, the admission
    check's read of the queue under the engine's lock, and ``add_request`` found the lock free a moment later): a request
    through the serving ingress carries both waits as one ``lock_wait_s``, and a call outside an ingress leaves nothing behind."""
    srv = _server(OpenAIServer)
    try:
        srv({"prompt": [1, 2, 3], "max_tokens": 2})  # compile
        eng, holding, times = srv.engine, threading.Event(), {}

        def hold():
            with eng._lock:
                holding.set()
                time.sleep(0.05)
                times["released"] = time.perf_counter()

        holder = threading.Thread(target=hold)
        holder.start()
        assert holding.wait(5.0)
        asked = time.perf_counter()
        out = srv({"prompt": [4, 5, 6], "max_tokens": 2})
        holder.join(5.0)
        assert not holder.is_alive() and telemetry.LOCK_WAIT.get() is None
        recs = {r["request_id"]: r for r in srv.telemetry()["requests"]}
        assert recs[out["id"]]["lock_wait_s"] == pytest.approx(times["released"] - asked, abs=0.02) and recs[out["id"]]["lock_wait_s"] > 0.02
        eng.host_load()  # no ingress on this thread: nothing is kept for whatever this thread admits next
        direct = srv.generate([8, 9], {"max_tokens": 2})
        assert {r["request_id"]: r for r in srv.telemetry()["requests"]}[direct["request_id"]]["lock_wait_s"] < 0.02
    finally:
        srv.shutdown()


def test_every_dispatch_has_a_stamp_taken_before_it():
    eng = _engine()
    eng.generate([[1, 2, 3], [4, 5]], SamplingParams(max_tokens=4))
    rows = eng.telemetry()["steps"]
    fused = [s for s in rows if s.get("dispatch_t")]
    assert fused and all(s["t0"] <= s["dispatch_t0"] <= s["dispatch_t"] for s in fused)
    assert all("dispatch_t0" not in s for s in rows if not s.get("dispatch_t"))
    waves = [s for s in rows if s.get("prefill_dispatch_t")]
    assert waves and all(len(s["prefill_dispatch_t0"]) == len(s["prefill_dispatch_t"]) for s in waves)
    assert all(s["t0"] <= b <= g[0] for s in waves for b, g in zip(s["prefill_dispatch_t0"], s["prefill_dispatch_t"]))
    before, after = telemetry.dispatch_stamps_before(rows), telemetry.dispatch_stamps(rows)
    assert {k: len(v) for k, v in before.items()} == {k: len(v) for k, v in after.items()}
    old = [{k: v for k, v in s.items() if k not in ("dispatch_t0", "prefill_dispatch_t0")} for s in rows]
    assert telemetry.dispatch_stamps_before(old) is None  # a log from before the stamps is read as it was
