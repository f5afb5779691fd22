#!/usr/bin/env python3
"""One cell, one run, one fresh process:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Starts the runtime, brings the model up on the chip(s) with weights made on the device from the
seed, warms only that cell's shapes, measures for ``--seconds``, checks a sample of outputs
against the plain reference outside the window, and prints as the LAST line of standard output
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``. With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics. Everything else worth reading is on earlier lines.

A run ends when its processes have (``benchmark/reaper.py``): once the driver has returned, and
before the result line, every process that inherited this run's marker is waited for, killed if
it stays, and counted on a ``[run] processes`` line. A failed or interrupted run does the same.

This process never initialises a JAX backend: the replica or train worker the scheduler binds is
the only holder of the chip. No chip, fewer chips than the cell asks for, or a ``device_kind``
that ``benchmark/peaks.py`` does not list: the run fails and prints no result.

    --rehearse            wiring only: toy sizes on whatever device is there; never says correct,
                          never prints a device metric off the TPU, exits non-zero
    --sabotage reference  gives the plain reference weights from another seed: correct must be false
    --sweep 0.5,0.7,0.9   one replica of a listed cell, the open loop at each rate for --seconds: the table
                          behind the cell's fixed rate; prints no result line
"""

from __future__ import annotations

import time

T_PROC0 = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)  # workers inherit sys.path and import benchmark.* by name


def end_of_run(reaper, t_down: float) -> None:
    """The driver has returned, and its last act was ``ray_tpu.shutdown()``, which leaves the
    multiprocessing forkserver and resource tracker to an exit hook and joins each worker for a
    second. Stop the two helpers now, by the program's own hook, so that what is left is only what
    should not be; then wait for everything that carries the run's marker, and say what there was."""
    from benchmark.reaper import WAIT_S
    from ray_tpu.core.node import stop_forkserver

    found = reaper.close()
    stop_forkserver()
    r = reaper.reap(found, t_down)
    print(f"[run] processes: {r['found']} of the {r['ever']} that carried this run's marker were alive when the driver "
          f"returned; the last was gone {r['outlived_s']:.2f} s after that; SIGKILL after {WAIT_S:.0f} s to {len(r['killed'])} "
          f"{r['killed']}; they were {r['who']}", flush=True)
    if r["left"]:
        raise SystemExit(f"process(es) {r['left']} of this run would not end: no result")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--sabotage", default="", choices=("", "reference"))
    ap.add_argument("--sweep", help="comma-separated open-loop rates: one set-up, --seconds at each, a table, no result line")
    a = ap.parse_args()

    try:
        import ray_tpu  # noqa: F401 - a directory without the program fails here
    except ImportError as e:
        print(f"the program is not here: {e}", file=sys.stderr)
        return 2
    from benchmark import common, xplane
    from benchmark.reaper import Reaper

    # the program's own helper (util/compile_cache.py) places the persistent compile cache: where
    # JAX_COMPILATION_CACHE_DIR says, else at one fixed path in the checkout. Here only: cache every
    # program, however small, so that a cell's second run compiles nothing. Workers inherit this.
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    bench = common.load_benchmark()
    if a.seconds is None:
        a.seconds = float(bench["run_seconds"])
    cell = common.resolve_cell(bench, a.workload)
    a.out_dir = os.path.join(ROOT, ".bench_out", a.workload)
    a.trace_dir = os.path.join(a.out_dir, "trace")
    shutil.rmtree(a.trace_dir, ignore_errors=True)
    os.makedirs(a.out_dir, exist_ok=True)

    from benchmark import traffic

    kind = traffic.load_mix(cell["cell"]["traffic"])["kind"]
    if kind == "serve":
        from benchmark import serve_cell as driver
    elif kind == "train":
        from benchmark import train_cell as driver
    else:
        raise SystemExit(f"traffic kind {kind!r} has no driver")
    if a.sweep and kind != "serve":
        raise SystemExit("--sweep is for the open loop of a serving cell")
    reaper = Reaper()
    reaper.start()  # before the runtime: the forkserver and every worker inherit the marker
    try:
        res = driver.sweep(a, cell) if a.sweep else driver.run(a, cell, T_PROC0)
    finally:
        end_of_run(reaper, time.time())
    if a.sweep:
        # past the knee a sweep leaves client threads blocked on streams of a replica that is gone, and the
        # interpreter would wait for them at exit (PR 33: 20 minutes, until the call's limit): every process of
        # the run has ended and the table is printed, so leave without them
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(res)

    dev = res["device"]
    on_tpu = dev["platform"] == "tpu"
    per_layer_names = [m["name"] for m in cell["metrics"]["per_layer"]]
    units = {m["name"]: m["unit"] for k in ("end_to_end", "per_layer") for m in bench[k]}
    if on_tpu:
        from benchmark.peaks import peaks_of

        res["obs"]["peaks"] = peaks_of(dev["kind"])
    per_layer = common.read_per_layer(per_layer_names, res["obs"])  # off the TPU: wiring only, never printed as metrics
    e2e = {m["name"]: res["end_to_end"][m["name"]] for m in cell["metrics"]["end_to_end"] if m["name"] in res["end_to_end"]}
    compared = "[run] compared: " + "; ".join(f"{what}: {value} (limit {limit})" for what, value, limit in res["compared"])
    print(compared, flush=True)
    print(f"[run] end to end: {json.dumps(res['end_to_end'])}", flush=True)
    print(f"[run] per layer: {json.dumps(per_layer)}", flush=True)
    trace = res.get("trace") or {}
    with open(os.path.join(a.out_dir, f"observed_trace{a.trace}.json"), "w") as f:
        json.dump({"end_to_end": res["end_to_end"], "per_layer": per_layer, "trace": trace,
                   "requests": res.get("requests")}, f)
    if trace.get("window_s"):
        print(f"[run] trace: lines {trace.get('lines')}; busy {trace.get('busy_s')} of {trace.get('window_s')} s; "
              f"programs {json.dumps(xplane.top(trace.get('programs', {}), 12))}", flush=True)
        print(f"[run] trace: op kinds {json.dumps(xplane.top(trace.get('op_kinds', {}), 15))}", flush=True)
    if not on_tpu:
        # a rehearsal: the wiring ran; no number from here is a device metric
        print(json.dumps({"correct": False, "attempted": res["attempted"], "failed": res["failed"], "metrics": {},
                          "device": common.device_block(dev, 0), "rehearsal": True}), flush=True)
        return 1
    chosen = per_layer if a.trace else e2e
    line = {"correct": bool(res["correct"]) and not a.rehearse, "attempted": res["attempted"], "failed": res["failed"],
            "metrics": {k: {"value": v, "unit": units.get(k, "")} for k, v in chosen.items()},
            "device": common.device_block(dev, res["memory_peak_bytes"], trace if a.trace else None)}
    if a.trace and trace.get("window_s"):
        line["breakdown"] = {"device_ops": xplane.top(trace["ops"], 10), "idle_gaps": trace.get("idle_gaps", [])[:10]}
    print(json.dumps(line), flush=True)
    print(compared, file=sys.stderr, flush=True)  # each number compared beside its limit, as standard error's last line too
    return 0


if __name__ == "__main__":
    sys.exit(main())
