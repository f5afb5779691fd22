"""The routed-expert layer alone (``models/experts._grouped``), one layer at the cells' shapes: the blocks run by the
plain XLA loop beside the kernel of ``ops/grouped_experts.py`` at several block heights, in one process on one chip.

    python3 scripts/experts_layer_bench.py [--only lg] [--heights 128,256,512] [--trips 512] [--alt <another grouped_experts.py>] [--out chiprun_out/<name>.json]

A row a shape, a call of it (a prefill's slabs differ in how many of their rows are true) and a form: ``loop<h>`` the loop
and ``kernel<h>`` the kernel at blocks of h rows (``blocks_plan`` answered for), ``alt<h>`` the kernel of the file ``--alt``
names; the form that ``experts.blocks_plan`` and ``experts.out_plan`` pick for the call on a TPU comes first and is marked
``the_trees``. ``--trips`` adds the tree's form with the gather out at other rows a trip than ``out_plan``'s (``.trip<rows>``;
512 is what every call had until PR 65). Milliseconds a call on the host clock
(the median of 5 means of 10 calls), the first call's seconds (trace, lower, compile, one run), device milliseconds of
the whole call, of ``moe.blocks`` and of ``moe.place.count`` / ``.into`` / ``.out`` (and their sum) from one traced stretch read by scope, the blocks' TFLOP/s over
the pairs' own operations (rows of padding inside a block count for nothing), the rows of the blocks in use, and whether
the output is the first form's bit for bit. The shapes are ISSUE 63's and ISSUE 65's: rows a call, experts published and held, top k,
F, H, form, and ``valid`` cut to the cells' true lengths. ``--compile`` runs nothing: it compiles every form for a
described v5e, which is what the chip's compiler would refuse. It times a chip and says so where there is none;
``tests/hybrid_battery.py`` has the interpreted kernel."""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import statistics
import sys
import tempfile
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# letter and name, rows a call, experts (published, held), top k, F, H, form, the calls of one prefill as (how often, (period, true rows a period))
SHAPES = [
    ("l lfm2 12288 of which 10500", 12288, (64, 64), 4, 1536, 2048, "swiglu", [(1, (12288, 10500))]),
    ("g glm 16384 of which 10000, slabs of 8192", 8192, (64, 64), 4, 1536, 2048, "swiglu", [(1, (8192, 8192)), (1, (8192, 1808))]),
    ("s smallthinker 12288 of which 10500", 12288, (64, 64), 6, 768, 2560, "reglu", [(1, (12288, 10500))]),
    ("k keye 24576 of which 20500, slabs of 8192", 8192, (128, 128), 8, 768, 2048, "swiglu", [(2, (8192, 8192)), (1, (8192, 4116))]),
    ("n nemotron 4 x 2048 of which 1500 each", 8192, (128, 64), 6, 1856, 2688, "relu2", [(1, (2048, 1500))]),
    ("t trinity 12288 of which 10500, an eighth of the experts", 12288, (256, 32), 4, 3072, 3072, "swiglu", [(1, (12288, 10500))]),
    ("i kimi 4096 of which 2500, a quarter of the experts", 4096, (256, 64), 8, 1024, 2304, "swiglu", [(1, (4096, 2500))]),
    ("j kimi 2 x 4096 of which 2500 each", 8192, (256, 64), 8, 1024, 2304, "swiglu", [(1, (4096, 2500))]),
    ("q qwen3-next 4096 of which 2500, a quarter of the experts", 4096, (512, 128), 10, 512, 2048, "swiglu", [(1, (4096, 2500))]),
]
TINY = [("t tiny", 2048, (8, 8), 2, 64, 128, "swiglu", [(1, (2048, 1900))]), ("u tiny relu2", 512, (16, 8), 2, 64, 128, "relu2", [(2, (256, 200))])]


def load(path: str, name: str):
    """A file of the kernel as a module of its own, under another name than the package's."""
    with open(path) as f:
        text = f.read()
    module = importlib.util.module_from_spec(importlib.util.spec_from_loader(name, loader=None, origin=path))
    module.__file__ = path
    sys.modules[name] = module
    exec(compile(text, path, "exec"), module.__dict__)
    return module


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--only", default=None, help="shapes whose name starts with one of these letters, e.g. lg")
    ap.add_argument("--heights", default="", help="rows of a block under the kernel, besides the tree's own, e.g. 128,256,512")
    ap.add_argument("--trips", default="", help="rows of a trip of the gather out, besides the tree's own, e.g. 512")
    ap.add_argument("--loops", default="", help="rows of a block under the loop, besides the tree's own")
    ap.add_argument("--alt", default=None, help="another tree's ray_tpu/ops/grouped_experts.py: its kernel as alt<h>")
    ap.add_argument("--out", default=None, help="where the rows go as JSON beside the printed lines")
    ap.add_argument("--tiny", action="store_true", help="rehearse on the CPU: two tiny shapes, the kernel interpreted, no trace")
    ap.add_argument("--compile", action="store_true", help="compile every form for a described v5e and run nothing")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import experts
    from ray_tpu.ops import grouped_experts
    from ray_tpu.util import profiling

    device = jax.devices()[0]
    if args.compile:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        one = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
        jax.default_backend = lambda: "tpu"  # the program's ``refusal()``s answer as they do on the chip
    elif device.platform != "tpu" and not args.tiny:
        print(f"[experts_layer_bench] {device.platform}: no TPU, and a time from anything else is no device's", file=sys.stderr)
        return 2
    jax.config.update("jax_enable_compilation_cache", False)  # a first call is timed: it compiles, whatever an earlier run left
    plan_of_the_tree, trip_of_the_tree = experts.blocks_plan, experts.out_plan
    heights = [int(h) for h in args.heights.split(",") if h]
    forms = {f"loop{h}": (int(h), False, grouped_experts) for h in args.loops.split(",") if h}
    forms.update({f"kernel{h}": (h, True, grouped_experts) for h in heights})
    if args.alt:
        alt = load(args.alt, "grouped_experts_alt")
        forms.update({f"alt{h}": (h, True, alt) for h in heights})
    shapes = [s for s in (TINY if args.tiny else SHAPES) if not args.only or s[0][0] in args.only]
    repeats, calls_a_repeat = (2, 2) if args.tiny else (5, 10)

    rows = []
    for name, N, (E, El), k, F, H, act, calls in shapes:
        c = types.SimpleNamespace(expert_layer=experts.ExpertLayer(num_experts=E, top_k=k, local_experts=El, act=act))
        s = c.expert_layer
        rs = np.random.RandomState(63)
        idx = np.argsort(rs.rand(N, E), axis=1)[:, :k].astype(np.int32)
        wt = rs.rand(N, k).astype(np.float32)
        wt /= wt.sum(1, keepdims=True)
        dt = jnp.float32 if args.tiny else jnp.bfloat16
        keys = iter(jax.random.split(jax.random.PRNGKey(63), 4))
        make = (lambda shape, _: jax.ShapeDtypeStruct(shape, dt)) if args.compile else lambda shape, scale: (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(dt)
        stacked = {n: make((2, El, F, H), 0.03) for n in s.matrices}
        x = make((N, H), 0.5)
        # what the tree's own rule picks for the call on a TPU (off it the kernel's refusal names the backend): that form leads, and the others are held to it
        backend, jax.default_backend = jax.default_backend, lambda: "tpu"
        picked, jax.default_backend = plan_of_the_tree(s, N, [stacked[n] for n in s.matrices]), backend
        the_trees = ("kernel" if picked[1] else "loop") + str(picked[0])
        # name -> rows a block, whether the kernel runs them, its module, the rows of a trip (None: ``out_plan``'s own)
        these = {the_trees: (*picked, grouped_experts, None), **{f: (*p, None) for f, p in forms.items() if f != the_trees}}
        these.update({f"{the_trees}.trip{rows}": (*picked, grouped_experts, int(rows)) for rows in args.trips.split(",") if rows and int(rows) != trip_of_the_tree(s)})
        for often, (period, true) in calls:
            valid = (np.arange(N) % period) < true
            data = (stacked, x, jnp.asarray(idx), jnp.asarray(wt), jnp.asarray(valid))
            if args.compile:
                data = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), data)
            first, fns = None, {}
            for form, (block, kernel, module, trip) in these.items():
                row = {"shape": name, "calls_a_prefill": often, "true_rows": int(valid.sum()), "form": form, "the_trees": form == the_trees, "block": block, "kernel": kernel,
                       "trip": trip or trip_of_the_tree(s), "device": "described v5e" if args.compile else device.device_kind}
                rows.append(row)

                def run(stacked, x, idx, wt, valid):
                    return experts._grouped(stacked, 1, x, idx, wt, valid, c)

                run.__name__ = f"layer_{name[0]}{true}_{form}"
                fn = jax.jit(run)
                experts.blocks_plan, experts.grouped_experts = (lambda *_a, plan=(block, kernel): plan), module
                experts.out_plan = (lambda *_a, trip=trip: trip) if trip else trip_of_the_tree
                try:
                    t0 = time.perf_counter()
                    if args.compile:
                        fn.lower(*data).compile()
                    else:
                        out = jax.block_until_ready(fn(*data))
                    row["compile_s" if args.compile else "first_call_s"] = round(time.perf_counter() - t0, 2)
                except Exception as e:  # noqa: BLE001 - a height the compiler refuses is a row's finding, not the run's end
                    row["error"] = f"{type(e).__name__}: {str(e)[:400]}"
                finally:
                    experts.blocks_plan, experts.grouped_experts, experts.out_plan = plan_of_the_tree, grouped_experts, trip_of_the_tree
                if "first_call_s" not in row:
                    continue
                means = []
                for _ in range(repeats):
                    t0 = time.perf_counter()
                    for _ in range(calls_a_repeat):
                        out = fn(*data)
                    jax.block_until_ready(out)
                    means.append((time.perf_counter() - t0) / calls_a_repeat * 1e3)
                got = np.asarray(out[0].astype(jnp.float32))
                first = got if first is None else first
                row.update(ms=round(statistics.median(means), 4), pairs=int(out[1].sum()), rows_in_blocks=int(out[2]),
                           equal_to_the_first_form=bool(np.array_equal(got, first)), max_abs_diff=float(np.abs(got - first).max()), max_abs=float(np.abs(first).max()))
                fns["jit_" + run.__name__] = (fn, row)
            if fns and not args.tiny:  # one traced stretch a call: two calls of one shape are one compiled program, and a trace tells programs apart, not their inputs
                logdir = tempfile.mkdtemp()
                profiling.start_trace(logdir, host_tracer_level=0)
                for fn, _ in fns.values():
                    for _ in range(3):
                        out = fn(*data)
                    jax.block_until_ready(out)
                profiling.stop_trace()
                programs = profiling.summarize(logdir).get("programs", {})
                for prog, (_, row) in fns.items():
                    traced = programs.get(prog)
                    if traced:
                        n = max(traced["calls"], 1)
                        scopes = {sc: v["s"] / n * 1e3 for sc, v in traced["scopes"].items()}
                        row["device_ms"] = round(traced["device_s"] / n * 1e3, 4)
                        row["blocks_ms"] = round(scopes.get("moe.blocks", 0.0), 4)
                        row["place_ms"] = round(sum(v for sc, v in scopes.items() if sc.startswith("moe.place")), 4)
                        row.update({part + "_ms": round(scopes.get("moe.place." + part, 0.0), 4) for part in ("count", "into", "out")})
                        if row["blocks_ms"]:  # over the pairs' own operations, and over the rows of the blocks in use (padding counted as work)
                            row["blocks_tflops"] = round(row["pairs"] * len(s.matrices) * 2 * F * H / row["blocks_ms"] / 1e9, 2)
                            row["blocks_tflops_padded"] = round(row["rows_in_blocks"] * len(s.matrices) * 2 * F * H / row["blocks_ms"] / 1e9, 2)
            for row in rows[-len(these):]:
                print(json.dumps(row), flush=True)
        del stacked, x
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 1 if any("error" in r for r in rows) else 0


if __name__ == "__main__":
    sys.exit(main())
