"""The flash forward kernel alone, one layer at the cells' shapes: the tree's ``ops/flash_attention.py``
beside another tree's file loaded by path, in one process on one chip.

    python3 scripts/flash_layer_bench.py <parent's flash_attention.py> [--out chiprun_out/<name>.json] [--blocks QxK]

A row a shape and set of lengths: milliseconds a call of each file (means of 5 calls on the host clock,
after the first, which is timed by itself: trace, lower, Mosaic compile and one run), and whether ``o``
and ``lse`` of the two are ``array_equal``. The shapes are ISSUE 61's (a)-(f); the roadmap's A16 (2), (3)
and (5) start from here, (g)-(i) are the 256-wide buckets under GLM's longest. Each file runs at its OWN
default tiles unless ``--blocks 512x1024`` gives both the same tiles: two files whose tiles differ add
their keys in another order, and only at equal tiles can their outputs be asked to be equal.
It times a chip and says so where there is none: ``tests/test_flash_lengths.py`` has the interpreted kernel."""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TREE = os.path.join(ROOT, "ray_tpu", "ops", "flash_attention.py")

# name, (batch, heads, positions, head width), window, true lengths a row (None: the call without them)
SHAPES = [
    ("a glm 20x256x16384", (1, 20, 16384, 256), None, [None, [10000], [16384]]),
    ("b keye 32x128x24576", (1, 32, 24576, 128), None, [[20500]]),
    ("c smallthinker window 28x128x12288 w4096", (1, 28, 12288, 128), 4096, [None, [10500]]),
    ("c smallthinker global 28x128x12288", (1, 28, 12288, 128), None, [[10500]]),
    ("d internlm2 16x128x4096", (1, 16, 4096, 128), None, [None, [3000]]),
    ("d internlm2 16x128x2048 (two query tiles)", (4, 16, 2048, 128), None, [[2048, 1500, 1025, 700]]),
    ("d internlm2 16x128x1024 (one query tile: the column)", (4, 16, 1024, 128), None, [None]),
    ("e mistral sft 8x32x128x2048", (8, 32, 2048, 128), None, [None]),
    ("f lfm2 32x64->128x12288", (1, 32, 12288, 128), None, [[10500]]),
    # heads 256 wide under 16,384: where the forward kernel's tile follows T (``_fwd_blocks``); ``--blocks`` places the rule
    ("g glm 20x256x8192", (1, 20, 8192, 256), None, [[5000], [8192]]),
    ("h qwen3-next 16x256x4096", (1, 16, 4096, 256), None, [[2500], [4096]]),
    ("h qwen3-next 16x256x2048", (2, 16, 2048, 256), None, [[2048, 1300]]),
    ("i kimi 32x256x4096", (1, 32, 4096, 256), None, [[2500]]),
    ("i kimi 32x256x2048", (2, 32, 2048, 256), None, [[2048, 1300]]),
]
def load(path: str, name: str):
    """A file of the kernel as a module of its own, under another name than the package's."""
    with open(path) as f:
        text = f.read()
    spec = importlib.util.spec_from_loader(name, loader=None, origin=path)
    module = importlib.util.module_from_spec(spec)
    module.__file__ = path
    sys.modules[name] = module
    exec(compile(text, path, "exec"), module.__dict__)
    return module


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("parent", help="the other tree's ray_tpu/ops/flash_attention.py")
    ap.add_argument("--out", default=None, help="where the rows go as JSON beside the printed lines")
    ap.add_argument("--blocks", default=None, help="QxK: both files at these tiles instead of their own")
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--only", default=None, help="shapes whose name starts with one of these letters, e.g. ae")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    jax.config.update("jax_enable_compilation_cache", False)  # a first call is timed: it compiles, whatever an earlier run left
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"[flash_layer_bench] {device.platform}: no TPU, and a time from anything else is no device's", file=sys.stderr)
        return 2
    files = {"parent": load(args.parent, "flash_parent"), "tree": load(TREE, "flash_tree")}
    blocks = dict(zip(("block_q", "block_k"), map(int, args.blocks.split("x")))) if args.blocks else {}
    shapes = [s for s in SHAPES if not args.only or s[0][0] in args.only]

    def run(module, q, k, v, window, lens):
        n = None if lens is None else jnp.asarray(lens, jnp.int32)
        fn = jax.jit(lambda q, k, v, n: module._fwd_pallas(q, k, v, causal=True, window=window, lengths=n, **blocks))
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(q, k, v, n))
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(args.calls):
            out = fn(q, k, v, n)
        jax.block_until_ready(out)
        return out, (time.perf_counter() - t0) / args.calls * 1e3, first

    rows = []
    for name, shape, window, lengths in shapes:
        q, k, v = (jax.random.normal(jax.random.PRNGKey(i), shape, jnp.bfloat16) for i in (1, 2, 3))
        for lens in lengths:
            row = {"shape": name, "lengths": lens, "device": device.device_kind, "blocks": args.blocks}
            outs = {}
            for side, module in files.items():
                try:
                    outs[side], ms, first = run(module, q, k, v, window, lens)
                except Exception as e:  # noqa: BLE001 - tiles the compiler refuses (--blocks) are a row's finding, not the run's end
                    row[f"{side}_error"] = f"{type(e).__name__}: {str(e)[:300]}"
                    continue
                row[f"{side}_ms"], row[f"{side}_first_call_s"] = round(ms, 3), round(first, 2)
            if "parent" not in outs:
                print(json.dumps(row), flush=True)
                rows.append(row)
                continue
            po, plse = (np.asarray(a) for a in outs["parent"])
            for side in outs:
                if side != "parent":
                    o, lse = (np.asarray(a) for a in outs[side])
                    row[f"{side}_o_equal"], row[f"{side}_lse_equal"] = bool(np.array_equal(o, po)), bool(np.array_equal(lse, plse))
                    if not row[f"{side}_o_equal"]:
                        row[f"{side}_o_max_abs_diff"] = float(np.max(np.abs(o.astype(np.float32) - po.astype(np.float32))))
            row["finite"] = bool(np.isfinite(po.astype(np.float32)).all() and np.isfinite(plse).all())
            print(json.dumps(row), flush=True)
            rows.append(row)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0 if all(r.get("tree_o_equal") and r.get("tree_lse_equal") for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
