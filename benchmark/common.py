"""What every part of the harness shares: where the files are, and how a cell's names resolve to
its files: its configuration, its model family, its metrics' readers."""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def resolve_cell(bench: dict, workload: str) -> dict:
    """-> {"cell", "config" (its file's content), "config_entry", "metrics": {"end_to_end", "per_layer"}}"""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    if "family" not in config:
        raise SystemExit(f"{entry['file']} names no \"family\": benchmark/families/<family>.py has the model's block and its plain reference")

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]

    return {"cell": cell, "config": config, "config_entry": entry,
            "metrics": {k: [m for m in bench[k] if mine(m)] for k in ("end_to_end", "per_layer")}}


def load_family(name: str):
    """The model family a configuration file names: the module ``benchmark/families/<name>.py``
    (``benchmark/families/__init__.py`` says what it defines). Imported by that name, so that a
    worker process finds the same module and what it defines pickles by reference."""
    from benchmark.families import NAMES

    if not os.path.exists(os.path.join(HERE, "families", name + ".py")):
        raise SystemExit(f"no family {name!r}: benchmark/families/{name}.py is not there")
    family = importlib.import_module("benchmark.families." + name)
    missing = [n for n in NAMES if not callable(getattr(family, n, None))]
    if missing:
        raise SystemExit(f"benchmark/families/{name}.py lacks {missing}")
    return family


def load_reader(metric: str):
    """The per-layer metric's reader: ``benchmark/metrics/<metric>.py`` with ``read(obs)``.
    None where the file is missing (the metric is then left out of the line)."""
    path = os.path.join(HERE, "metrics", metric + ".py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location("benchmark_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_per_layer(names: list[str], obs: dict) -> dict:
    """name -> value for every reader that found something to read."""
    out = {}
    for name in names:
        reader = load_reader(name)
        value = reader(obs) if reader is not None else None
        if value is not None:
            out[name] = float(value)
    return out


def record_lowerings() -> list[tuple]:
    """Start counting compiles in this process: returns a list that grows by (host time, function
    name) at every lowering of a jitted function or eager operation, whether the persistent cache
    then serves the executable or not. A window's count is what falls between its edges."""
    import time

    import jax

    sink: list[tuple] = []

    def on_event(name, secs, **kw):
        if name.endswith("jaxpr_to_mlir_module_duration"):
            sink.append((time.time(), str(kw.get("fun_name", "?"))))

    jax.monitoring.register_event_duration_secs_listener(on_event)
    return sink


def device_block(dev: dict, memory_peak_bytes: int, trace: dict | None = None) -> dict:
    out = {"platform": dev["platform"], "kind": dev["kind"], "count": dev["count"],
           "memory_peak_bytes": int(memory_peak_bytes)}
    if trace:
        out["busy_s"], out["window_s"] = trace["busy_s"], trace["window_s"]
    return out
