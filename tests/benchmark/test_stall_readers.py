"""CPU tests of the three readers PR 55 adds under benchmark/metrics/ (``engine_lock_wait_ms``, ``step_cpu_ms``,
``stall_excess_ms``): each on a synthetic flight log, with no log, against a log the parent wrote, and as
``BENCHMARK.json`` lists them."""

import json
import os

import pytest

from benchmark import common
from ray_tpu.llm import telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# The cells each is listed in at least. ISSUE 55 names ``kimi-linear-ep4.longdoc`` for the first and all seven cells of
# ``prefill_bubble_ms`` for the last; the tests of the Kimi, SmallThinker and LFM2 cells hold the exact set of names that
# list their cell, and are the benchmark's own files, which only a ``benchmark`` PR edits: that PR appends those cells.
LISTED = {
    "engine_lock_wait_ms": ("ms", "program_span", "replica admission", "ttft_p50_ms", ["internlm2-1.8b.longdoc", "qwen3-next-ep4.longdoc"]),
    "step_cpu_ms": ("ms", "program_counter", "engine", "itl_p95_ms", ["internlm2-1.8b.chat", "nemotron-3-nano-ep2.chat"]),
    "stall_excess_ms": ("ms", "program_span", "engine", "serve_tokens_per_s",
                        ["internlm2-1.8b.longdoc", "qwen3-next-ep4.longdoc", "glm-4.7-flash-d8.longdoc-16k", "minicpm-sala-d8.longdoc-12k"]),
}


def _step(n, t, phase="decode", wall=10.0, **cols):
    base = {f: 0.0 for f in telemetry.STAGES.values()}
    return {"step": n, "t": t, "t0": t - wall * 1e-3, "phase": phase, "wall_ms": wall, **base, **cols}


def _obs(tmp_path, monkeypatch, steps, requests=()):
    """The ``obs`` of a run whose replica wrote ``steps`` and ``requests``; the window is [10, 1000)."""
    from ray_tpu.util import state

    monkeypatch.setattr(state, "session_dir", lambda pid=None: str(tmp_path))
    d = tmp_path / "llm_flight"
    d.mkdir(exist_ok=True)
    with open(d / "flight-7-1.jsonl", "w") as f:
        f.write(json.dumps({"kind": "flight_header", "ts": 1.0, "pid": 7}) + "\n")
        f.writelines(json.dumps({"kind": "step", **s}) + "\n" for s in steps)
        f.writelines(json.dumps({"kind": "request", **r}) + "\n" for r in requests)
    client = [{"rid": r["request_id"], "due": r["submit_t"] - 0.01, "sent": r["submit_t"] - 0.01, "stamps": []} for r in requests]
    return {"window": [10.0, 1000.0], "client": {"records": client}, "worker": {"requests": {}}}


def _read(name, obs):
    return common.load_reader(name)(obs)


def _waves(first_token_ms: list, padded: int, start: int = 1, t: float = 11.0) -> list:
    return [_step(start + i, t + i, "mixed", wall=ms + 20.0, admitted=1, prefill_tokens_padded=padded, first_token_wait_ms=ms, drain_wait_ms=3.0)
            for i, ms in enumerate(first_token_ms)]


@pytest.mark.parametrize("case,want", [
    ("one_stall_of_3000_among_waits_of_100", 2900.0),
    ("a_16k_groups_1400_among_its_like", 0.0),
    ("a_drain_that_stood_2500_among_drains_of_2", 2498.0),
    ("a_lone_long_group_is_judged_by_the_windows_ms_a_token", 0.0),
    ("a_lone_short_group_that_stood_is_too", 3000.0 - 4096 * (100.0 / 4096)),
    ("a_second_and_a_half_that_is_only_three_times_the_usual", 0.0),
    ("no_read_over_a_second", 0.0),
])
def test_stall_excess_ms_tells_a_stall_from_a_long_prompts_wait(case, want, tmp_path, monkeypatch):
    decodes = [_step(100 + i, 200.0 + i, drain_wait_ms=2.0) for i in range(20)]
    steps = {
        "one_stall_of_3000_among_waits_of_100": _waves([100.0] * 9 + [3000.0] + [100.0] * 5, 4096) + decodes,
        "a_16k_groups_1400_among_its_like": _waves([1400.0, 1350.0, 1420.0, 1390.0, 1400.0], 16384) + _waves([100.0] * 6, 4096, start=50, t=60.0) + decodes,
        "a_drain_that_stood_2500_among_drains_of_2": decodes + [_step(150, 300.0, drain_wait_ms=2500.0, wall=2510.0)],
        "a_lone_long_group_is_judged_by_the_windows_ms_a_token": _waves([100.0] * 6, 4096) + _waves([1500.0], 65536, start=50, t=60.0) + decodes,
        "a_lone_short_group_that_stood_is_too": _waves([200.0] * 6, 8192) + _waves([3000.0], 4096, start=50, t=60.0) + decodes,
        "a_second_and_a_half_that_is_only_three_times_the_usual": _waves([500.0] * 6 + [1500.0], 8192) + decodes,
        "no_read_over_a_second": _waves([100.0, 900.0, 100.0], 4096) + decodes,
    }[case]
    got = _read("stall_excess_ms", _obs(tmp_path, monkeypatch, steps))
    assert got == pytest.approx(want) and isinstance(got, float)  # 0.0 is a value: a window without a stall


def test_engine_lock_wait_ms_is_the_median_of_the_requests_due_in_the_window(tmp_path, monkeypatch):
    def request(i, submit, lock_wait=None):
        return {"request_id": f"req-{i}", "submit_t": submit, "ingress_t": submit - 0.09,
                **({} if lock_wait is None else {"lock_wait_s": lock_wait})}

    requests = [request(1, 11.0, 0.080), request(2, 12.0, 0.002), request(3, 13.0, 0.075), request(4, 2000.0, 9.0)]  # the last: due after the window
    obs = _obs(tmp_path, monkeypatch, [_step(1, 11.0)], requests)
    assert _read("engine_lock_wait_ms", obs) == pytest.approx(75.0)
    assert _read("replica_ingress_ms", obs) == pytest.approx(90.0)  # the lump it is a part of reads what it read
    # a log the parent wrote: its records carry no ``lock_wait_s``, and the reader finds nothing
    old = _obs(tmp_path, monkeypatch, [_step(1, 11.0)], [request(1, 11.0), request(2, 12.0)])
    assert _read("engine_lock_wait_ms", old) is None


def test_step_cpu_ms_is_the_mean_over_the_decode_steps_because_the_chips_thread_clock_ticks_in_10_ms(tmp_path, monkeypatch):
    # nine steps of one millisecond as a clock of 10 ms charges them: eight read 0, one reads 10; their median says 0.0
    steps = [_step(1 + i, 11.0 + i, cpu_ms=10.0 if i == 4 else 0.0, drain_wait_ms=5.0) for i in range(9)]
    steps += [_step(20, 40.0, "mixed", cpu_ms=30.0), _step(21, 41.0, "idle", cpu_ms=10.0)]
    assert _read("step_cpu_ms", _obs(tmp_path, monkeypatch, steps)) == pytest.approx(10.0 / 9)
    old = [{k: v for k, v in s.items() if k != "cpu_ms"} for s in steps]  # the parent's rows
    obs = _obs(tmp_path, monkeypatch, old)
    assert _read("step_cpu_ms", obs) is None and _read("step_host_ms", obs) == pytest.approx(5.0)


@pytest.mark.parametrize("name", sorted(LISTED))
def test_without_a_log_each_new_reader_finds_nothing(name, tmp_path, monkeypatch):
    from ray_tpu.util import state

    monkeypatch.setattr(state, "session_dir", lambda pid=None: str(tmp_path))
    assert _read(name, {"window": [10.0, 20.0], "client": {"records": []}, "worker": {"requests": {}}}) is None  # a replica that wrote no log
    assert _read(name, {"window": [10.0, 20.0]}) is None  # a run with no worker at all


@pytest.mark.parametrize("name", sorted(LISTED))
def test_the_new_metrics_are_listed_with_their_cells(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    unit, source, layer, moves, cells = LISTED[name]
    m = by_name[name]
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (unit, "lower", source, layer, moves)
    assert m["workloads"][:len(cells)] == cells and set(m["workloads"]) <= set(by_name["prefill_bubble_ms" if moves == "serve_tokens_per_s" else
                                                                                  "replica_ingress_ms" if moves == "ttft_p50_ms" else "step_host_ms"]["workloads"])
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "metrics", name + ".py"))
    # every cell that lists it reports the end-to-end metric it moves
    e2e = next(e for e in bench["end_to_end"] if e["name"] == moves)
    assert set(m["workloads"]) <= set(e2e.get("workloads") or [w["name"] for w in bench["workloads"]])
