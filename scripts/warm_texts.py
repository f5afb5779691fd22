"""Which warm-up programs a change renews, counted without a chip (ROADMAP.md A7).

``python scripts/warm_texts.py <tree> <out.json>`` lowers every warm prefill shape of every cell the hybrid
runner serves, eight that route experts and two that do not, and of the llama family's two serving cells
(the server's own first request in the smallest bucket, then the cell's warm plan) for the described v5e, from
the tree it is given (a checkout, or ``git archive`` of the parent unpacked somewhere), and writes
cell -> shape -> sha1 of the lowered text with the Mosaic kernels' payloads cut out (they hold the checkout's
path and line numbers). Run it on the parent's tree and on the change's, then
``python scripts/warm_texts.py --compare parent.json change.json`` says which programs are new ones: each costs a
cell 0.3-0.5 s of warm ``setup_s`` on the driver's machine, whether or not a metric of the cell can read the change.
"""
import dataclasses
import hashlib
import json
import os
import re
import sys
import time

if sys.argv[1] == "--compare":
    with open(sys.argv[2]) as fa, open(sys.argv[3]) as fb:
        a, b = json.load(fa), json.load(fb)
    for cell in sorted(set(a) & set(b)):
        changed = [k for k in a[cell] if a[cell][k] != b[cell][k]]
        print(cell, f"{len(changed)} of {len(a[cell])} programs renewed:", ", ".join(changed) or "none")
    sys.exit(0)

tree, out = os.path.abspath(sys.argv[1]), sys.argv[2]
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [tree, os.path.join(tree, "tests")]
os.chdir(tree)

from functools import partial  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import test_chip_compile as t  # noqa: E402  (the tree's own: its cells at their sizes)
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmark import serve_cell  # noqa: E402
from ray_tpu.llm import hybrid_runner as hr  # noqa: E402
from ray_tpu.llm import model_runner  # noqa: E402

assert os.path.abspath(hr.__file__).startswith(tree), hr.__file__
one = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
jax.default_backend = lambda: "tpu"  # the program's ``refusal()``s answer as they do on the chip


def lowered(prefill, cfg, params, B, T):
    """The text a prefill program of B x T lowers to for the chip, the Mosaic kernels' payloads cut out."""
    shapes = (jax.ShapeDtypeStruct((B, T), jnp.int32, sharding=one), jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one))
    return re.sub(r'\\22body\\22: \\22[A-Za-z0-9+/=]+', "BODY", jax.jit(partial(prefill, cfg=cfg)).lower(params, *shapes).as_text())


def prefill_text(cfg, params, B, T):
    """The tree's own ``tests/test_chip_compile._prefill_text`` where it has one (a parent older than PR 57 has not)."""
    return t._prefill_text(cfg, params, one, B, T) if hasattr(t, "_prefill_text") else lowered(hr.prefill, cfg, params, B, T)


# ``tests/test_chip_compile.CELLS``' name of a cell of the hybrid runner -> its traffic mix; a tree that lacks one (an older parent) leaves it out
CELLS = {"qwen3_next": "longdoc", "kimi": "longdoc", "nemotron": "chat", "glm": "longdoc-16k", "smallthinker": "longdoc-12k", "lfm2": "longdoc-12k", "keye": "longdoc-24k",
         "trinity": "longdoc-12k", "sala": "longdoc-12k", "jamba": "longdoc-12k"}  # the last two route nothing: the hybrid runner's other cells, which a change to it must leave alone
# the llama family's two serving cells, which ``llm/model_runner.prefill`` serves (``mistral-7b-d6.sft-2k`` trains: it warms no prefill)
LLAMA = {"internlm2.chat": "chat", "internlm2.longdoc": "longdoc"}
texts = {}
for cell, mix in {**CELLS, **LLAMA}.items():
    if cell in LLAMA:
        config, text_of = "internlm2-1.8b.json", partial(lowered, model_runner.prefill)
        cfg = dataclasses.replace(t._internlm2_1_8b(), attention_impl="pallas")
        params = t._on(model_runner._sds_params(cfg), one)
    elif cell in t.CELLS:
        config, text_of = t.CELLS[cell][0], prefill_text
        cfg, params, _, _ = t._cell_at_its_size(one, cell)
    else:
        continue
    with open(os.path.join("benchmark", "configs", config)) as f:
        serving = json.load(f)["serving"]
    with open(os.path.join("benchmark", "traffic", mix + ".json")) as f:
        buckets = [b for b, _ in serve_cell.warm_plan(json.load(f), serve_cell.default_buckets(serving["max_seq_len"]))]
    texts[cell] = {}
    # the server's own first request (``serve/llm.py::_prewarm_compile``, three tokens in the smallest bucket), then the cell's plan
    for T, most in [(serve_cell.default_buckets(serving["max_seq_len"])[0], 1)] + [(b, serving["warm_batch_max"]) for b in buckets]:
        B = 1
        while B <= most:
            t0 = time.time()
            txt = text_of(cfg, params, B, T)
            texts[cell][f"{B}x{T}"] = hashlib.sha1(txt.encode()).hexdigest()
            print(cell, f"{B}x{T}", round(time.time() - t0, 1), "s; grouped_experts in it:", "grouped_experts" in txt, flush=True)
            B *= 2
with open(out, "w") as f:
    json.dump(texts, f, indent=1)
