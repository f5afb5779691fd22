"""CPU tests of the readers PR 24 adds (benchmark/flight.py and eight files under
benchmark/metrics/): on a recorded flight log and ``obs``, with no log, against a program that
has no ``load_flight``, and (slow) after a rehearsal of each serving cell."""

import json
import os
import statistics
import subprocess
import sys

import pytest

from benchmark import common, flight
from benchmark.stats import percentile
from ray_tpu.llm import telemetry

NEW = ["handle_ingress_ms", "replica_ingress_ms", "token_handoff_ms", "stream_egress_ms",
       "stream_itl_added_p95_ms", "step_host_ms", "prefill_stall_ms", "prefill_bubble_ms"]
TTFT_PARTS = NEW[:4]


def _request(rid, sent, ingress, submit, first_token, first_yield, itl):
    return {"request_id": rid, "ingress_t": ingress, "submit_t": submit, "admit_t": submit + 0.1, "first_token_t": first_token,
            "first_yield_t": first_yield, "last_yield_t": first_yield + sum(itl) + 0.01, "finish_t": first_token + sum(itl),
            "itl_s": itl, "tokens": len(itl) + 1}


def _step(n, t, phase, wall, admitted=0, **stages):
    base = {f: 0.0 for f in telemetry.STAGES.values()}
    return {"step": n, "t": t, "t0": t - wall * 1e-3, "phase": phase, "wall_ms": wall, "admitted": admitted, **base, **stages}


@pytest.fixture
def recorded(tmp_path, monkeypatch):
    """A flight log on disk as the replica writes it, and the ``obs`` of the run that goes with it:
    two requests due in the window [10, 20), one after it, and a stale log of an earlier run."""
    from ray_tpu.util import state

    monkeypatch.setattr(state, "session_dir", lambda pid=None: str(tmp_path))
    requests = [_request("req-1", 11.001, 11.021, 11.030, 11.290, 11.291, [0.060, 0.061, 0.150]),
                _request("req-2", 12.000, 12.040, 12.070, 12.470, 12.474, [0.062]),
                _request("req-9", 25.000, 25.010, 25.020, 25.400, 25.401, [])]
    steps = [_step(1, 11.0, "decode", 60.0, dispatch_t=10.9405, dispatch_ms=0.4, drain_wait_ms=58.0, emit_ms=0.6),
             _step(2, 11.1, "idle", 0.1),
             _step(3, 11.3, "mixed", 130.0, admitted=1, dispatch_t=11.3 - 0.130 + 0.0745, admission_ms=0.5, prefill_ms=70.0,
                   dispatch_ms=4.0, drain_wait_ms=55.0),
             _step(4, 11.5, "mixed", 90.0, admitted=2, dispatch_t=11.5 - 0.090 + 0.0420, admission_ms=1.0, prefill_ms=40.0,
                   dispatch_ms=1.0, drain_wait_ms=47.0),
             _step(5, 11.6, "decode", 62.0, dispatch_t=11.539, dispatch_ms=0.5, drain_wait_ms=59.0),
             _step(6, 25.0, "decode", 99.0, drain_wait_ms=1.0)]
    d = tmp_path / "llm_flight"
    d.mkdir()
    for name, ts, reqs, rows in (("flight-7-1.jsonl", 30.0, requests, steps),
                                 ("flight-7-0.jsonl", 5.0, [_request("req-1", 1.0, 1.1, 1.2, 1.3, 1.4, [9.0])], [_step(1, 1.0, "decode", 999.0)])):
        with open(d / name, "w") as f:
            f.write(json.dumps({"kind": "flight_header", "ts": ts, "pid": 7}) + "\n")
            f.writelines(json.dumps({"kind": "step", **s}) + "\n" for s in rows)
            f.writelines(json.dumps({"kind": "request", **r}) + "\n" for r in reqs)
    client = [{"rid": "req-1", "due": 11.0, "sent": 11.001, "stamps": [11.300, 11.364, 11.424, 11.580]},
              {"rid": "req-2", "due": 12.0, "sent": 12.000, "stamps": [12.500, 12.560]},
              {"rid": "req-9", "due": 25.0, "sent": 25.000, "stamps": [25.5]}]
    worker = {"requests": {r["request_id"]: {k: r[k] for k in ("submit_t", "admit_t", "first_token_t")} for r in requests}}
    return {"window": [10.0, 20.0], "client": {"records": client}, "worker": worker}


def test_the_log_is_kept_to_this_runs_window(recorded):
    log = flight.records(recorded)
    assert [s["step"] for s in log["steps"]] == [1, 2, 3, 4, 5]
    assert sorted(log["requests"]) == ["req-1", "req-2", "req-9"] and log["requests"]["req-1"]["submit_t"] == 11.030
    assert [c["rid"] for c, _ in flight.due_in_window(recorded)] == ["req-1", "req-2"]
    assert [s["step"] for s in flight.admitting_steps(recorded)] == [3, 4]


def test_new_readers_on_a_recorded_log(recorded):
    read = lambda name: common.load_reader(name)(recorded)  # noqa: E731
    assert read("handle_ingress_ms") == pytest.approx(statistics.median([20.0, 40.0]))
    assert read("replica_ingress_ms") == pytest.approx(statistics.median([9.0, 30.0]))
    assert read("token_handoff_ms") == pytest.approx(statistics.median([1.0, 4.0]))
    assert read("stream_egress_ms") == pytest.approx(statistics.median([9.0, 26.0]))
    # client gaps 64, 60, 156 and 60 ms against the engine's 60, 61, 150 and 62: +4, -1, +6, -2
    assert read("stream_itl_added_p95_ms") == pytest.approx(percentile([4, -1, 6, -2], 95.0))
    assert read("step_host_ms") == pytest.approx(statistics.median([2.0, 3.0]))  # the two decode steps of the window
    assert read("prefill_stall_ms") == pytest.approx(statistics.median([70.0, 40.0]))
    assert read("prefill_bubble_ms") == pytest.approx(statistics.median([74.5 - 70.5, 42.0 - 41.0]))


def test_the_four_ttft_parts_are_what_client_overhead_subtracts(recorded):
    """Per request: ingress - sent, submit - ingress, first yield - first token and first stamp - first
    yield add up to (first stamp - sent) - (first token - submit), ``client_overhead_ms``'s difference."""
    one = dict(recorded, client={"records": recorded["client"]["records"][:1]})
    parts = sum(common.load_reader(n)(one) for n in TTFT_PARTS)
    assert parts == pytest.approx(common.load_reader("client_overhead_ms")(one), abs=1e-6)
    assert parts == pytest.approx(((11.300 - 11.001) - (11.290 - 11.030)) * 1e3, abs=1e-6)


@pytest.mark.parametrize("reader", NEW)
def test_a_new_reader_returns_nothing_without_a_log(reader, tmp_path, monkeypatch):
    from ray_tpu.util import state

    monkeypatch.setattr(state, "session_dir", lambda pid=None: str(tmp_path))
    obs = {"window": [10.0, 20.0], "client": {"records": [{"rid": "req-1", "due": 11.0, "sent": 11.0, "stamps": [11.3]}]},
           "worker": {"requests": {}}}
    assert common.load_reader(reader)(obs) is None  # a worker, but no log in the session
    # a program from before the log: the parent commit, which the new readers are also run against
    monkeypatch.delattr(telemetry, "load_flight")
    assert common.load_reader(reader)(obs) is None


def test_a_log_of_the_old_format_gives_nothing(recorded, tmp_path):
    """The parent's postmortem dump has step lines without stages and request lines without the new stamps."""
    with open(tmp_path / "llm_flight" / "flight-7-1.jsonl", "w") as f:
        f.write(json.dumps({"kind": "flight_header", "ts": 30.0}) + "\n")
        f.write(json.dumps({"kind": "step", "step": 1, "t": 11.0, "phase": "decode", "wall_ms": 60.0, "admitted": 1}) + "\n")
        f.write(json.dumps({"kind": "request", "request_id": "req-1", "submit_t": 11.03, "first_token_t": 11.29, "itl_s": []}) + "\n")
    assert [common.load_reader(n)(recorded) for n in NEW] == [None] * 8


@pytest.mark.slow
@pytest.mark.parametrize("cell,readers", [("internlm2-1.8b.chat", ["stream_itl_added_p95_ms", "step_host_ms", "prefill_stall_ms"]),
                                          ("internlm2-1.8b.longdoc", TTFT_PARTS + ["prefill_bubble_ms"])])
def test_after_a_rehearsal_the_log_exists_and_every_new_reader_reads_it(cell, readers, tmp_path):
    out = subprocess.run([sys.executable, os.path.join(common.ROOT, "benchmark", "run.py"), "--workload", cell, "--seed", "3000000019",
                          "--seconds", "4", "--trace", "1", "--rehearse"], cwd=tmp_path, capture_output=True, text=True,
                         env={**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "--xla_force_host_platform_device_count=1"},
                         timeout=300)
    line = next(ln for ln in out.stdout.splitlines() if ln.startswith("[run] per layer: "))
    per_layer = json.loads(line[len("[run] per layer: "):])
    assert set(readers) <= set(per_layer), out.stdout[-2000:]
    assert all(isinstance(per_layer[r], float) for r in readers)
