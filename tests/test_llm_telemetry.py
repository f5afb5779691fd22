"""Serving telemetry plane (llm/telemetry.py): flight recorder, live SLO
metrics, error-dump postmortems, Prometheus exposition format, and the
CI telemetry gate.

The zero-device-sync rule is enforced structurally (telemetry reads host
shadow state only; jaxcheck JXC002 keeps host callbacks out of the fused
programs) and its cost is gated in tests/test_perf_smoke.py. Lifecycle
trace stitching across the disagg split lives in tests/test_llm_disagg.py.
"""

import importlib.util
import json
import os
import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from ray_tpu.llm import LLMEngine, SamplingParams  # noqa: E402
from ray_tpu.llm.telemetry import METRICS, FlightRecorder  # noqa: E402
from ray_tpu.models.llama import LlamaConfig  # noqa: E402

CFG = LlamaConfig.tiny(dtype="float32", remat=False, max_seq_len=256)


def _engine(**kw):
    kw.setdefault("max_num_seqs", 2)
    kw.setdefault("max_seq_len", 128)
    kw.setdefault("enable_prefix_caching", False)
    return LLMEngine(CFG, **kw)


# ------------------------------------------------------------ flight recorder
def test_flight_recorder_steps_and_request_lifecycle():
    eng = _engine(telemetry_tags={"model": "fr-test"})
    outs = eng.generate([[1, 2, 3, 4], [5, 6, 7]], SamplingParams(max_tokens=6))
    snap = eng.telemetry()
    assert snap["tags"]["model"] == "fr-test"

    steps = snap["steps"]
    assert steps and steps[-1]["step"] == snap["step_count"]
    phases = {r["phase"] for r in steps}
    assert "decode" in phases and ("prefill" in phases or "mixed" in phases)
    for r in steps:
        assert r["wall_ms"] >= 0 and r["capacity_tokens"] > 0
        assert 0 <= r["batch"] <= 2 and r["occupied_tokens"] >= 0

    reqs = {r["request_id"]: r for r in snap["requests"]}
    assert len(reqs) == 2
    for out in outs:
        rec = reqs[out.request_id]
        assert rec["tokens"] == len(out.token_ids) == 6
        assert rec["reason"] == "length"
        # one TTFT sample, tokens-1 ITL samples, monotone stamps
        assert rec["ttft_s"] is not None and rec["ttft_s"] >= 0
        assert len(rec["itl_s"]) == rec["tokens"] - 1
        assert rec["submit_t"] <= rec["admit_t"] <= rec["first_token_t"] <= rec["finish_t"]
        assert rec["queue_wait_s"] >= 0
    # steady-state serving recompiled nothing (the sentinel's green path)
    assert snap["recompiles"] == {}


def test_flight_recorder_ring_is_bounded():
    rec = FlightRecorder(max_steps=8, max_requests=4)
    pad = (None,) * (len(FlightRecorder.STEP_FIELDS) - 3)
    for i in range(50):
        rec.record_step((float(i), "decode") + pad)
        rec.record_request({"request_id": f"r{i}"})
    snap = rec.snapshot()
    assert snap["step_count"] == 50
    assert len(snap["steps"]) == 8 and snap["steps"][-1]["step"] == 50
    assert snap["steps"][-1]["phase"] == "decode"
    assert len(snap["requests"]) == 4 and snap["requests"][-1]["request_id"] == "r49"


def _admitting_row(read_inside_prefill: bool) -> dict:
    """One admitting step's row, built by hand from the host's true spans (seconds from t0 = 100): two groups
    launched, and their first tokens read behind the dispatch (one read a wave), or inside ``prefill`` a group
    (speculation, where the stage of that name stays empty)."""
    from ray_tpu.llm import telemetry

    spans, h, groups = [], 100.0, []

    def stage(label, dur):
        nonlocal h
        spans.append((label, h, h + dur))
        h += dur

    stage(telemetry.IN_STEP, 0.05e-3)
    stage("admission", 0.2e-3)
    p0 = h
    stage("prefill", 0.1e-3)
    for _ in range(2):
        stage("prefill.launch", 0.4e-3)
        d = h
        stage("prefill.launch", 0.6e-3)
        stage("state_insert", 0.2e-3)
        groups.append([d, h, 0.0])
        if read_inside_prefill:
            stage("prefill.first_tokens", 40e-3)
            groups[-1][2] = h
    stage("prefill", 0.05e-3)
    prefill_ms = (h - p0) * 1e3
    stage("dispatch", 0.5e-3)
    dispatch_t = h
    stage("drain_wait", 0.05e-3)
    stage("emit", 0.3e-3)
    if not read_inside_prefill:
        stage("prefill.first_tokens", 80e-3)
        for g in groups:
            g[2] = h
    stage("outputs", 0.2e-3)
    row = {"step": 7, "t0": 100.0, "t": h, "wall_ms": (h - 100.0) * 1e3, "admitted": 5, "dispatch_t": dispatch_t, "prefill_dispatch_t": groups,
           "admission_ms": 0.2, "prefill_ms": prefill_ms, "prefill_launch_ms": 2.4, "state_insert_ms": 0.4, "dispatch_ms": 0.5, "drain_wait_ms": 0.05,
           "emit_ms": 0.3, "first_token_wait_ms": 0.0 if read_inside_prefill else 80.0, "outputs_ms": 0.2, "stepper_deliver_ms": 0.0, "stepper_wait_ms": 0.0}
    return {"row": row, "spans": spans}


@pytest.mark.parametrize("read_inside_prefill", [False, True], ids=["read_behind_the_dispatch", "read_before_it"])
def test_timeline_follows_a_wave_through_the_new_stage_order(read_inside_prefill):
    """PR 50: ``llm.step.prefill`` ends when the wave is launched, and the wait for its first tokens stands after
    ``emit``, no longer inside ``prefill``: ``timeline`` gives back the host's true spans, which tile the row, and
    ``prefill_bubble_ms``' formula (``dispatch_t - t0 - admission_ms - prefill_ms``) is not negative. Where the
    dispatch needs the tokens on the host, a group's third stamp puts the read inside ``prefill``."""
    from ray_tpu.llm import telemetry

    built = _admitting_row(read_inside_prefill)
    row, want = built["row"], built["spans"]
    names = list(telemetry.STAGES)
    assert names.index("llm.step.dispatch") < names.index("llm.step.emit") < names.index("llm.step.prefill.first_tokens") < names.index("llm.step.outputs")
    assert telemetry.INSIDE == {"llm.step.prefill.launch": "llm.step.prefill", "llm.step.state_insert": "llm.step.prefill.launch"}
    got = telemetry.timeline([row])
    merged = []  # the true spans, a launch's two halves as one
    for label, a, b in want:
        if merged and merged[-1][0] == label and abs(merged[-1][2] - a) < 1e-12:
            merged[-1] = (label, merged[-1][1], b)
        else:
            merged.append((label, a, b))
    assert [sp[0] for sp in got] == [sp[0] for sp in merged]
    assert all(a == pytest.approx(c, abs=2e-6) and b == pytest.approx(d, abs=2e-6) for (_, a, b), (_, c, d) in zip(got, merged))
    assert got[0][1] == row["t0"] and got[-1][2] == row["t"] and all(x[2] == pytest.approx(y[1], abs=1e-9) for x, y in zip(got, got[1:]))
    bubble = (row["dispatch_t"] - row["t0"]) * 1e3 - row["admission_ms"] - row["prefill_ms"]
    assert 0.0 <= bubble == pytest.approx(0.05 + 0.5, abs=1e-3)  # the wait for the lock and the dispatch's own host time
    assert sum(row[telemetry.STAGES[name]] for name in telemetry.TILED) == pytest.approx(row["wall_ms"] - 0.05, abs=1e-3)


def test_drain_stamps_say_when_each_steps_tokens_were_on_the_host():
    """The end of the NEXT row's ``drain_wait``, a row's stages ending at ``t``: what bounds the device's clock from
    below where no launch finds the device idle (``util/profiling._align``)."""
    from ray_tpu.llm import telemetry

    first, second = _admitting_row(False)["row"], dict(_admitting_row(False)["row"], step=8)
    shift = first["t"] + 1e-3 - second["t0"]
    second.update(t0=second["t0"] + shift, t=second["t"] + shift, dispatch_t=second["dispatch_t"] + shift)
    idle = dict(second, step=9, t0=second["t"] + 1e-3, t=second["t"] + 2e-3, dispatch_t=None, first_token_wait_ms=0.0, emit_ms=0.1, outputs_ms=0.1)
    assert telemetry.drain_stamps([first, second, idle]) == [pytest.approx(second["t"] - (0.3 + 80.0 + 0.2) * 1e-3), pytest.approx(idle["t"] - 0.2e-3)]
    assert telemetry.drain_stamps([first]) == [None]


def test_recompile_sentinel_counts_cache_growth():
    """The sentinel's contract: first observed program per entry is the
    warm baseline; any growth after that is a recompile, counted per
    entry. (A real recompile on the serving path is a bug — a drifting
    static arg minting one program per step — so it gets a counter, not
    a silent 100x step.)"""

    class FakeJit:
        def __init__(self):
            self.n = 0

        def _cache_size(self):
            return self.n

    rec = FlightRecorder()
    fn = FakeJit()
    rec.register_entry("fused_step", fn)
    assert rec.check_recompiles() == []  # never called: no baseline yet
    fn.n = 1
    assert rec.check_recompiles() == []  # first program = warm
    assert rec.check_recompiles() == []  # stable cache: quiet
    fn.n = 3
    assert rec.check_recompiles() == ["fused_step"]
    assert rec.recompiles == {"fused_step": 2}
    fn.n = 4
    assert rec.check_recompiles() == ["fused_step"]
    assert rec.recompiles == {"fused_step": 3}


def test_engine_error_dumps_flight_jsonl():
    """A dying engine persists its step history as JSONL in the session
    dir before the error surfaces (the postmortem the serve stepper's
    unhealthy-replica report points at)."""
    from ray_tpu.util.state import session_dir

    eng = _engine(telemetry_tags={"model": "crash-test"})
    eng.generate([[1, 2, 3]], SamplingParams(max_tokens=2))  # warm + some history

    def boom(*a, **kw):
        raise RuntimeError("injected fused-step failure")

    eng._fused_step = boom
    eng.add_request([4, 5, 6], SamplingParams(max_tokens=4))
    with pytest.raises(RuntimeError, match="injected fused-step failure"):
        while eng.has_unfinished():
            eng.step()
    d = os.path.join(session_dir(), "llm_flight")
    # this process's: serve replicas of the session write their logs here too
    dumps = sorted(n for n in os.listdir(d) if n.startswith(f"flight-{os.getpid()}-"))
    assert dumps, "engine error produced no flight dump"
    lines = [json.loads(ln) for ln in open(os.path.join(d, dumps[-1])) if ln.strip()]
    header = lines[0]
    assert header["kind"] == "flight_header"
    assert "injected fused-step failure" in header["error"]
    assert header["tags"]["model"] == "crash-test"
    kinds = {ln["kind"] for ln in lines[1:]}
    assert "step" in kinds  # the ride-along step history made it to disk
    # a second error on the same engine does not redump (one postmortem
    # per engine life; the stepper rethrows the same exception to waiters)
    assert eng._tel.dump_on_error(RuntimeError("again")) is None


# ------------------------------------------------------------- live metrics
def test_slo_metrics_flow_into_exposition():
    from ray_tpu.util import metrics

    eng = _engine(telemetry_tags={"model": "slo-test", "replica": "r0"})
    eng.generate([[1, 2, 3, 4, 5]], SamplingParams(max_tokens=8))
    text = metrics.export_prometheus()
    want_tag = 'model="slo-test"'

    def series(name):
        return [ln for ln in text.splitlines() if ln.startswith(name) and want_tag in ln]

    count_ln = [ln for ln in series("rt_llm_ttft_s_count") if 'replica="r0"' in ln]
    assert count_ln and float(count_ln[0].split()[-1]) >= 1
    itl_ln = series("rt_llm_itl_s_count")
    assert itl_ln and float(itl_ln[0].split()[-1]) >= 7  # 8 tokens -> 7 ITLs
    assert series("rt_llm_tokens_total") and series("rt_llm_kv_occupancy")
    assert series("rt_llm_queue_wait_s_count")
    # the recompile sentinel series exists at 0 (materialized at engine
    # construction so dashboards can alert on ANY increase)
    rec_ln = series("rt_llm_recompiles_total")
    assert rec_ln and float(rec_ln[0].split()[-1]) == 0
    # finish-reason tag rides the requests counter
    fin = [ln for ln in series("rt_llm_requests_finished_total") if 'reason="length"' in ln]
    assert fin and float(fin[0].split()[-1]) >= 1


def test_live_metrics_scrape_during_traffic(rt_start):
    """ISSUE 10 acceptance: a live /metrics scrape DURING serving traffic
    exposes non-empty TTFT and ITL histograms plus KV-occupancy and
    recompile-sentinel series backed by real requests."""
    import urllib.request

    from ray_tpu.core import context
    from ray_tpu.dashboard.dashboard import Dashboard

    eng = _engine(telemetry_tags={"model": "scrape-test"})
    eng.generate([[1, 2, 3]], SamplingParams(max_tokens=2))  # compile outside the loop
    db = Dashboard(context.get_client(), port=0)
    db.start()
    stop = threading.Event()
    errors: list[str] = []

    def traffic():
        try:
            while not stop.is_set():
                eng.generate([[1, 2, 3, 4, 5]], SamplingParams(max_tokens=8))
        except Exception as e:  # noqa: BLE001
            errors.append(repr(e))

    th = threading.Thread(target=traffic, daemon=True)
    th.start()
    text = ""
    try:
        deadline = time.time() + 60
        ok = False
        while time.time() < deadline and not ok:
            with urllib.request.urlopen(f"http://127.0.0.1:{db.port}/metrics", timeout=30) as r:
                text = r.read().decode()
            lines = text.splitlines()

            def hist_count(name):
                sel = [ln for ln in lines if ln.startswith(name + "_count") and 'model="scrape-test"' in ln]
                return sum(float(ln.split()[-1]) for ln in sel)

            ok = (
                hist_count("rt_llm_ttft_s") >= 1
                and hist_count("rt_llm_itl_s") >= 1
                and any(ln.startswith("rt_llm_kv_occupancy") and 'model="scrape-test"' in ln for ln in lines)
                and any(ln.startswith("rt_llm_recompiles_total") and 'model="scrape-test"' in ln for ln in lines)
            )
            time.sleep(0.2)
        assert not errors, f"traffic thread died: {errors}"
        assert ok, f"serving series never appeared in a live scrape:\n{text[:3000]}"
    finally:
        stop.set()
        th.join(timeout=30)
        db.stop()


def test_telemetry_off_is_really_off():
    eng = _engine(telemetry=False)
    out = eng.generate([[1, 2, 3]], SamplingParams(max_tokens=4))[0]
    assert len(out.token_ids) == 4
    assert eng.telemetry() == {}


# ------------------------------------------- Prometheus exposition (golden)
def test_prometheus_exposition_golden_histogram():
    """Format-level golden test over export_prometheus() (satellite of
    ISSUE 10): cumulative ``le`` buckets, the +Inf bucket, _count/_sum,
    and label-value escaping, which a Prometheus scraper parses strictly."""
    from ray_tpu.util import metrics

    h = metrics.Histogram(
        "golden_hist_s", description="golden histogram", boundaries=[0.1, 1.0], tag_keys=("route",)
    )
    tag_val = 'a"b\\c'  # quote + backslash: must be escaped on the wire
    h.observe(0.05, tags={"route": tag_val})
    h.observe(0.5, tags={"route": tag_val})
    h.observe(5.0, tags={"route": tag_val})
    text = metrics.export_prometheus()
    esc = 'route="a\\"b\\\\c"'
    # cumulative bucket counts: 1 (<=0.1), 2 (<=1.0), 3 (+Inf)
    assert f'golden_hist_s_bucket{{{esc},le="0.1"}} 1' in text
    assert f'golden_hist_s_bucket{{{esc},le="1.0"}} 2' in text
    assert f'golden_hist_s_bucket{{{esc},le="+Inf"}} 3' in text
    assert f"golden_hist_s_count{{{esc}}} 3" in text
    assert f"golden_hist_s_sum{{{esc}}} 5.55" in text
    assert "# TYPE golden_hist_s histogram" in text

    # HELP text escapes newlines (a raw newline would truncate the metric)
    metrics.Counter("golden_desc_total", description="line1\nline2").inc(1)
    text = metrics.export_prometheus()
    assert "# HELP golden_desc_total line1\\nline2" in text
    assert "\nline2\n" not in text


# ----------------------------------------------------------- CI telemetry gate
def _load_lint_gate():
    path = os.path.join(os.path.dirname(__file__), "..", "scripts", "lint_gate.py")
    spec = importlib.util.spec_from_file_location("lint_gate", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_lint_gate_telemetry_catalog_clean():
    """The committed catalog + dashboard must pass the CI telemetry gate:
    valid Prometheus names, kind-unique exposition names, every Grafana
    panel expr backed by a registered metric."""
    lg = _load_lint_gate()
    assert lg.check_telemetry() == []


def test_lint_gate_telemetry_flags_bad_catalog(monkeypatch):
    from ray_tpu.llm import telemetry

    lg = _load_lint_gate()
    bad = dict(telemetry.METRICS)
    bad["1bad-name"] = {"kind": "gauge", "tags": (), "desc": "x"}
    # histogram-derived exposition collision: a gauge squatting on the
    # TTFT histogram's _count output name
    bad["rt_llm_ttft_s_count"] = {"kind": "gauge", "tags": (), "desc": "x"}
    monkeypatch.setattr(telemetry, "METRICS", bad)
    probs = lg.check_telemetry()
    assert any("1bad-name" in p for p in probs)
    assert any("rt_llm_ttft_s_count" in p for p in probs)


def test_grafana_serving_row_queries_catalog_metrics():
    """Every Serving panel queries a cataloged rt_llm_* metric, and the
    dashboard JSON stays parseable with well-formed targets."""
    from ray_tpu.dashboard.grafana import grafana_dashboard_json

    dash = json.loads(grafana_dashboard_json())
    serving = [p for p in dash["panels"] if p["title"].startswith("Serving:")]
    assert len(serving) >= 8
    for p in serving:
        assert p["type"] == "timeseries" and p["targets"]
        for t in p["targets"]:
            assert any(name in t["expr"] for name in METRICS), (p["title"], t["expr"])
    titles = [p["title"] for p in serving]
    assert any("first token" in t for t in titles) and any("inter-token" in t for t in titles)
