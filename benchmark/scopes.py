"""The traced stretch by the program's own names, for the per-layer readers that go by them (PR 39).

``ray_tpu/util/profiling.summarize`` turns a trace and the replica's flight log into device
seconds by step program and named scope, and device idle seconds by engine stage, both on the
device's clock. This file finds the run's trace (``.bench_out/<cell>/trace``, where ``run.py``
put it) and its flight log (the session's, as ``benchmark/flight.py`` reads it), calls
``summarize`` once a run (the result kept beside the trace and in this process), prints the
``[scopes]`` lines and hands the readers what they ask for.

Nothing to read, and ``None`` from every reader that asks here: a run without a trace, an ``obs``
without a cell or a worker, a program without ``summarize`` (the parent of PR 39), a trace the
reduction cannot read (said on a ``[scopes]`` line; a reader never raises)."""

from __future__ import annotations

import json
import os
import time
import traceback

from benchmark import common

PREFILL_STAGE = ("prefill", "prefill.launch", "prefill.first_tokens", "state_insert")  # the stages inside llm.step.prefill
KEEP_OPS = 40  # operations a program kept in the cached summary, by time
_memo: dict = {}


def say(msg: str) -> None:
    print(f"[scopes] {msg}", flush=True)


def _reduce(trace_dir: str, trace_host: list) -> dict | None:
    try:
        from ray_tpu.llm.telemetry import load_flight
        from ray_tpu.util.profiling import find_xplane, summarize, tables
    except ImportError:
        return None  # a program from before the instrument
    if find_xplane(trace_dir) is None:
        return None
    a, b = trace_host
    t0 = time.time()
    # the replica's rows around the stretch: the clocks are set against each other by dispatches both sides hold
    steps = [s for s in load_flight()["steps"] if a - 10.0 <= s["t"] <= b + 10.0]
    pids = [s.get("pid") for s in steps]
    steps = [s for s in steps if s.get("pid") == max(set(pids), key=pids.count)] if pids else []
    s = summarize(trace_dir, flight=steps, stretch_s=b - a)
    if not s or not s.get("programs"):
        return None
    off = (s.get("clock") or {}).get("offset_ns")
    if off is not None:
        lo, hi = (s["t_lo_ns"] - off) * 1e-9, (s["t_hi_ns"] - off) * 1e-9
        s["admitting_steps"] = sum(1 for r in steps if r.get("admitted") and r["t0"] < hi and r["t"] > lo)
    s["reduce_s"] = time.time() - t0
    for line in tables(s):
        say(line)
    for word in ("prefill", "fused"):
        leaf = sum(r["leaf_s"] for n, r in s["programs"].items() if word in n)
        if leaf:
            un = sum(r["scopes"].get("unscoped", {}).get("s", 0.0) for n, r in s["programs"].items() if word in n)
            say(f"{word} programs: {100.0 * un / leaf:.2f}% of their operations' {leaf:.4f} s stand under no scope")
            outside = sorted(((o["s"], op, o["path"]) for n, r in s["programs"].items() if word in n
                              for op, o in r["ops"].items() if o["scope"] == "unscoped"), reverse=True)[:6]
            say(f"  the most of it: {json.dumps([[op, round(secs, 5), path] for secs, op, path in outside])}")
    idle = s.get("idle") or {}
    total = s["window_s"] - s["busy_s"]
    say(f"idle by stage sums to {sum(p['s'] for p in idle.values()):.4f} s of the window's {total:.4f} s idle; unattributed "
        f"{idle.get('unattributed', {}).get('s', 0.0):.4f} s; clock_residual_ms {(s.get('clock') or {}).get('clock_residual_ms')}; "
        f"admitting steps in the stretch {s.get('admitting_steps')}; the reduction took {s['reduce_s']:.2f} s")
    for row in s["programs"].values():
        row["ops"] = dict(sorted(row["ops"].items(), key=lambda kv: -kv[1]["s"])[:KEEP_OPS])
    return s


def summary(obs: dict) -> dict | None:
    """This run's summary, or None where there is nothing to read."""
    cell = (obs.get("cell") or {}).get("name")
    trace_host = ((obs.get("worker") or {}).get("trace") or {}).get("trace_host")
    if not cell or not trace_host:
        return None
    trace_dir = os.path.join(common.ROOT, ".bench_out", cell, "trace")
    key = (trace_dir, tuple(trace_host))
    if key not in _memo:
        kept = os.path.join(trace_dir, "scopes.json")
        try:
            with open(kept) as f:
                found = json.load(f)
            _memo[key] = found["summary"] if found.get("trace_host") == list(trace_host) else None
        except (OSError, ValueError):
            _memo[key] = None
        if _memo[key] is None:
            try:
                _memo[key] = _reduce(trace_dir, list(trace_host))
            except Exception:  # noqa: BLE001 - a trace the reduction cannot read leaves its metrics out; the run's result stands
                say("the reduction failed:\n" + traceback.format_exc())
            if _memo[key] is not None:
                with open(kept, "w") as f:
                    json.dump({"trace_host": list(trace_host), "summary": _memo[key]}, f)
    return _memo[key]


def _programs(s: dict, word: str) -> dict:
    return {n: r for n, r in s["programs"].items() if word in n}


def role_seconds(s: dict, word: str, role: str) -> float | None:
    """Device seconds under ``role`` in the programs with ``word`` in their name; None where none of them holds a scope of that role."""
    found = [s["roles"][n][role] for n in _programs(s, word) if role in s["roles"].get(n, {})]
    return sum(found) if found else None


def scope_seconds(s: dict, word: str, kind: str) -> float:
    """Device seconds under scope ``kind`` and its sub-scopes in the programs with ``word`` in their name."""
    return sum(c["s"] for r in _programs(s, word).values() for sc, c in r["scopes"].items() if sc == kind or sc.startswith(kind + "."))


def prefill_role_ms_per_ktok(obs: dict, role: str) -> float | None:
    """Device ms under ``role`` in the prefill programs per 1,000 prompt tokens admitted in the
    stretch: ``prefill_ms_per_ktok``'s denominator (the flight recorder's admit stamps inside the
    stretch's host edges; padding is in the time, not in the tokens)."""
    s = summary(obs)
    secs = role_seconds(s, "prefill", role) if s else None
    if secs is None:
        return None
    a, b = obs["worker"]["trace"]["trace_host"]
    tokens = sum(r["prompt_tokens"] for r in (obs["worker"].get("requests") or {}).values() if a <= (r["admit_t"] or 0) < b)
    return secs * 1e3 / (tokens / 1000.0) if tokens else None


def fused_role_ms(obs: dict, role: str) -> float | None:
    """Device ms under ``role`` a call of the program with ``fused`` in its name."""
    s = summary(obs)
    secs = role_seconds(s, "fused", role) if s else None
    calls = sum(r["calls"] for r in _programs(s, "fused").values()) if s else 0
    return secs * 1e3 / calls if secs is not None and calls else None


def moe_blocks_share(obs: dict) -> float | None:
    """``moe.blocks`` over ``moe`` and all its sub-scopes, in the prefill programs, in percent."""
    s = summary(obs)
    whole = scope_seconds(s, "prefill", "moe") if s else 0.0
    return 100.0 * scope_seconds(s, "prefill", "moe.blocks") / whole if whole else None


def prefill_stage_idle_ms(obs: dict) -> float | None:
    """Device idle inside ``llm.step.prefill`` (its launch, its first-token wait and the rest of it) per admitting step of the stretch."""
    s = summary(obs)
    if not s or not s.get("admitting_steps") or "offset_ns" not in (s.get("clock") or {}):
        return None
    return sum(s["idle"].get(k, {}).get("s", 0.0) for k in PREFILL_STAGE) * 1e3 / s["admitting_steps"]
