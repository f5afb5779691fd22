"""CPU tests of the seven readers PR 39 adds (``benchmark/scopes.py`` and seven files under
``benchmark/metrics/``): a number where the summary holds the scope, ``None`` where it does not,
where the program has no ``summarize`` (the parent), where there is no trace; never a raise."""

import json
import os

import pytest

from benchmark import common, scopes

NEW = ["prefill_mixer_ms_per_ktok", "prefill_ffn_ms_per_ktok", "moe_blocks_share", "decode_mixer_ms", "decode_ffn_ms",
       "decode_state_ms", "prefill_stage_idle_ms"]


def _scope(s, calls=10):
    return {"s": s, "calls": calls, "flops": 0, "bytes": 0}


def _summary():
    """What ``summarize`` gives for a hybrid's stretch: two prefill buckets under one name, a fused step, an insert."""
    programs = {
        "jit_llm_hybrid_prefill": {"calls": 4, "device_s": 4.1, "leaf_s": 4.0, "ops": {}, "scopes": {
            "gdn": _scope(0.3), "gdn.chunk": _scope(0.7), "gdn.scan": _scope(0.2), "gated_attn": _scope(0.3),
            "moe": _scope(0.1), "moe.route": _scope(0.2), "moe.place": _scope(0.6), "moe.blocks": _scope(0.8), "moe.shared": _scope(0.3),
            "embed": _scope(0.05), "head": _scope(0.05), "cache": _scope(0.1), "unscoped": _scope(0.3)}},
        "jit_llm_hybrid_fused_step": {"calls": 100, "device_s": 0.62, "leaf_s": 0.6, "ops": {}, "scopes": {
            "mamba2": _scope(0.10), "mamba2.state": _scope(0.14), "attn": _scope(0.05), "moe": _scope(0.02), "moe.blocks": _scope(0.13),
            "moe.shared": _scope(0.05), "sample": _scope(0.04), "head": _scope(0.03), "cache": _scope(0.01), "unscoped": _scope(0.03)}},
        "jit_llm_kv_insert": {"calls": 8, "device_s": 0.01, "leaf_s": 0.01, "ops": {}, "scopes": {"cache": _scope(0.01)}},
    }
    from ray_tpu.util.profiling import SCOPES

    roles = {}
    for name, row in programs.items():
        for sc, cell in row["scopes"].items():
            role = SCOPES.get(sc, "unscoped")
            roles.setdefault(name, {})[role] = roles.setdefault(name, {}).get(role, 0.0) + cell["s"]
    return {"chips": 1, "window_s": 5.0, "busy_s": 4.7, "t_lo_ns": 0, "t_hi_ns": 5_000_000_000, "programs": programs, "roles": roles,
            "clock": {"offset_ns": -1000, "anchors": 104, "clock_residual_ms": 0.08}, "admitting_steps": 4,
            "idle": {"prefill.first_tokens": {"s": 0.12, "gaps": 9}, "prefill.launch": {"s": 0.03, "gaps": 4}, "prefill": {"s": 0.002, "gaps": 4},
                     "state_insert": {"s": 0.008, "gaps": 4}, "stepper.wait": {"s": 0.1, "gaps": 30}, "dispatch": {"s": 0.04, "gaps": 100},
                     "unattributed": {"s": 0.0, "gaps": 0}}}


@pytest.fixture
def obs(tmp_path, monkeypatch):
    """The ``obs`` of a traced run whose summary lies beside its trace, as ``scopes.summary`` keeps it."""
    monkeypatch.setattr(common, "ROOT", str(tmp_path))
    monkeypatch.setattr(scopes, "_memo", {})
    trace_dir = tmp_path / ".bench_out" / "toy.longdoc" / "trace"
    trace_dir.mkdir(parents=True)
    trace_host = [100.0, 105.0]
    (trace_dir / "scopes.json").write_text(json.dumps({"trace_host": trace_host, "summary": _summary()}))
    requests = {"a": {"admit_t": 101.0, "prompt_tokens": 3000}, "b": {"admit_t": 104.0, "prompt_tokens": 5000},
                "c": {"admit_t": 99.0, "prompt_tokens": 7000}, "d": {"admit_t": None, "prompt_tokens": 9000}}
    return {"window": [60.0, 105.0], "cell": {"name": "toy.longdoc"}, "worker": {"trace": {"trace_host": trace_host}, "requests": requests}}


def read(name, obs):
    return common.load_reader(name)(obs)


def test_the_seven_readers_on_a_summary(obs):
    # 8,000 prompt tokens were admitted inside the stretch
    assert read("prefill_mixer_ms_per_ktok", obs) == pytest.approx((0.3 + 0.7 + 0.2 + 0.3) * 1e3 / 8.0)
    assert read("prefill_ffn_ms_per_ktok", obs) == pytest.approx((0.1 + 0.2 + 0.6 + 0.8 + 0.3) * 1e3 / 8.0)
    assert read("moe_blocks_share", obs) == pytest.approx(100.0 * 0.8 / 2.0)
    assert read("decode_mixer_ms", obs) == pytest.approx((0.10 + 0.05) * 1e3 / 100)  # the state has a role of its own
    assert read("decode_ffn_ms", obs) == pytest.approx((0.02 + 0.13 + 0.05) * 1e3 / 100)
    assert read("decode_state_ms", obs) == pytest.approx(0.14 * 1e3 / 100)
    assert read("prefill_stage_idle_ms", obs) == pytest.approx((0.12 + 0.03 + 0.002 + 0.008) * 1e3 / 4)
    # the parts stay under the whole that the accepted readers print: mixer + ffn <= the prefill programs' time
    assert (read("prefill_mixer_ms_per_ktok", obs) + read("prefill_ffn_ms_per_ktok", obs)) * 8.0 <= 4.1e3


def test_a_program_without_the_scope_leaves_the_metric_out(obs):
    s = scopes.summary(obs)
    fused = s["programs"]["jit_llm_hybrid_fused_step"]
    del fused["scopes"]["mamba2.state"], s["roles"]["jit_llm_hybrid_fused_step"]["state"]
    assert read("decode_state_ms", obs) is None and read("decode_mixer_ms", obs) is not None
    for name in ("moe", "moe.route", "moe.place", "moe.blocks", "moe.shared"):
        del s["programs"]["jit_llm_hybrid_prefill"]["scopes"][name]
    assert read("moe_blocks_share", obs) is None
    del s["clock"]["offset_ns"]  # the clocks could not be set against each other: no idle by stage
    assert read("prefill_stage_idle_ms", obs) is None
    s["programs"] = {n: r for n, r in s["programs"].items() if "prefill" not in n}
    assert read("prefill_mixer_ms_per_ktok", obs) is None and read("prefill_ffn_ms_per_ktok", obs) is None


@pytest.mark.parametrize("reader", NEW)
def test_nothing_to_read(reader, obs, monkeypatch):
    assert read(reader, {"window": [0.0, 1.0]}) is None  # no cell, no worker
    assert read(reader, {**obs, "worker": {"requests": {}}}) is None  # an untraced run
    monkeypatch.setattr(scopes, "_memo", {})
    assert read(reader, {**obs, "cell": {"name": "toy.chat"}}) is None  # a traced run whose trace directory holds no trace


@pytest.mark.parametrize("reader", NEW)
def test_against_a_program_without_summarize_every_reader_returns_none(reader, obs, monkeypatch):
    """The parent of PR 39: ``ray_tpu.util.profiling`` has no ``summarize`` to import."""
    from ray_tpu.util import profiling

    os.remove(os.path.join(common.ROOT, ".bench_out", "toy.longdoc", "trace", "scopes.json"))
    monkeypatch.delattr(profiling, "summarize")
    assert read(reader, obs) is None


def test_a_trace_the_reduction_cannot_read_is_said_and_raises_nothing(obs, capsys):
    trace_dir = os.path.join(common.ROOT, ".bench_out", "toy.longdoc", "trace")
    os.remove(os.path.join(trace_dir, "scopes.json"))
    run = os.path.join(trace_dir, "plugins", "profile", "2026_09_29")
    os.makedirs(run)
    with open(os.path.join(run, "host.xplane.pb"), "wb") as f:
        f.write(b"\x0f\xff\xff not a trace")
    assert [read(name, obs) for name in NEW] == [None] * 7
    assert capsys.readouterr().out.count("[scopes] the reduction failed") == 1  # once a run, not once a reader


def test_the_summary_is_reduced_once_and_kept_beside_the_trace(obs, monkeypatch, capsys):
    """From a trace on disk: the recorded one, under the run's directory; the session holds no flight log."""
    import shutil

    from ray_tpu.util import profiling, state

    monkeypatch.setattr(state, "session_dir", lambda pid=None: os.path.join(common.ROOT, "no_session"))
    trace_dir = os.path.join(common.ROOT, ".bench_out", "toy.longdoc", "trace")
    os.remove(os.path.join(trace_dir, "scopes.json"))
    run = os.path.join(trace_dir, "plugins", "profile", "2026_09_29")
    os.makedirs(run)
    shutil.copy(os.path.join(common.HERE, "testdata", "scoped_tpu.xplane.pb"), os.path.join(run, "host.xplane.pb"))
    calls = []
    real = profiling.summarize
    monkeypatch.setattr(profiling, "summarize", lambda *a, **k: calls.append(a) or real(*a, **k))
    obs["worker"]["requests"] = {"a": {"admit_t": 101.0, "prompt_tokens": 2000}}
    assert [read(name, obs) for name in NEW] == [None] * 7  # no program of this trace has prefill or fused in its name
    s = scopes.summary(obs)
    assert len(calls) == 1 and s["programs"]["jit_scoped_step"]["calls"] == 2 and s["reduce_s"] < 5.0
    out = capsys.readouterr().out
    assert "[scopes] jit_scoped_step: 2 calls" in out and "moe.blocks" in out and "the reduction took" in out
    with open(os.path.join(trace_dir, "scopes.json")) as f:
        assert json.load(f)["summary"]["programs"]["jit_scoped_step"]["calls"] == 2
    monkeypatch.setattr(scopes, "_memo", {})
    assert scopes.summary(obs)["programs"]["jit_scoped_step"]["calls"] == 2 and len(calls) == 1  # read back, not reduced again
