#!/usr/bin/env python3
"""Quickest proof that ray_tpu still starts on the chip.

    python chip_smoke.py            one TPU chip: serve -> reference -> train -> paged kernel -> hand-over
    python chip_smoke.py --chips 4  one four-chip host: tp=4 serving vs tp=1, int8 collective,
                                    fsdp=4 training (and nothing else)
    python chip_smoke.py --tiny     CPU rehearsal: toy sizes, Pallas interpreted, every phase and
                                    hand-off run on whatever device is there; never reports ok

The last line of stdout is ONE JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``, printed only when
every phase passed AND the device that did the work is a TPU. Everything else worth reading
(per-phase seconds, compile seconds, tokens, losses, the replica's own device) is on earlier lines.

Process model: this parent never imports JAX. Each phase runs in a child started from this
file (``--phase``); the serve and train children are plain drivers of the runtime, so the one
process that opens the chip is the replica / train worker the scheduler bound to it. Phases run
one after another, a phase starts only when the previous phase's process group is empty, and any
failing phase fails the run — there is no ``except`` here that lets the run finish with 0.

Weights, prompts and batches come from ``--seed``; nothing is read from outside the checkout.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chiprun_out", "chip_smoke")

# Tolerance of every served-vs-reference logit/logprob comparison below. bf16 keeps 8 mantissa
# bits, so one rounding is 2^-9 relative. Two correct bf16 programs that order their sums
# differently (a fused decode step reading a KV cache vs one causal forward; an f32-exact kernel
# vs XLA's default-precision einsum; a tp=4 all-reduce vs one device) drift apart by a few
# roundings per layer: on logits of magnitude ~1-3 through 18 layers that is a few 1e-2, and
# the largest drift measured on the v5e is printed by each phase. 0.25 leaves several times that
# room and is still far below what a wrong cache slot, mask, position or shard does (O(1)).
# A greedy token may therefore differ from the reference's top-1 only where the reference's own
# margin between the two is inside this tolerance — a tie that rounding broke the other way.
TOL = 0.25


# ----------------------------------------------------------------------------------------------
# shapes
# ----------------------------------------------------------------------------------------------
def serve_shape(tiny: bool) -> dict:
    """A ~1B serving shape at full width and depth."""
    if tiny:
        return dict(vocab_size=512, hidden_size=128, intermediate_size=256, num_layers=2, num_heads=4,
                    num_kv_heads=4, max_seq_len=256, remat=False, dtype="float32")
    return dict(vocab_size=32000, hidden_size=2048, intermediate_size=5632, num_layers=18, num_heads=16,
                num_kv_heads=16, max_seq_len=2048, remat=False)


def train_shape(tiny: bool) -> tuple[dict, int, int, int]:
    """The 8 x 2048 SFT shape: (config, batch, seq, steps)."""
    if tiny:
        return dict(vocab_size=512, hidden_size=128, intermediate_size=256, num_layers=2, num_heads=4,
                    num_kv_heads=2, max_seq_len=128), 4, 128, 3
    return dict(vocab_size=32000, hidden_size=2048, intermediate_size=5632, num_layers=18, num_heads=16,
                num_kv_heads=8, max_seq_len=2048), 8, 2048, 4


def make_requests(tiny: bool, seed: int) -> list[dict]:
    """A few concurrent requests: greedy ones of different prompt lengths and one seeded-sampled
    request sent TWICE (the same seed must give the same tokens)."""
    import random

    rnd = random.Random(seed)
    vocab = serve_shape(tiny)["vocab_size"]
    lens, n_greedy, n_sampled = ((24, 31, 17), 8, 6) if tiny else ((512, 480, 497), 48, 32)
    reqs = []
    for n in lens:
        reqs.append({"prompt": [rnd.randrange(1, vocab - 1) for _ in range(n)],
                     "sampling": {"max_tokens": n_greedy, "temperature": 0.0, "logprobs": True}})
    sampled = {"prompt": [rnd.randrange(1, vocab - 1) for _ in range(lens[0] - 3)],
               "sampling": {"max_tokens": n_sampled, "temperature": 0.8, "top_k": 50, "seed": seed + 7,
                            "logprobs": True}}
    return reqs + [sampled, json.loads(json.dumps(sampled))]


# ----------------------------------------------------------------------------------------------
# helpers that run inside children (they may import jax / ray_tpu)
# ----------------------------------------------------------------------------------------------
def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def device_of() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def compare_streams(name: str, a: list[dict], b: list[dict]) -> tuple[bool, str, float]:
    """Token-for-token comparison of two greedy runs of the same prompts ({tokens, logprobs} per
    lane). Streams must be equal up to each lane's first divergence, and a divergence is accepted
    only as a broken tie: both engines saw the same prefix there, so the two emitted tokens'
    logprobs (each its engine's maximum) must agree within TOL. After a divergence the prefixes
    differ and the lane is no longer compared. Returns (ok, line to print, max |dlogprob|)."""
    equal, worst_lp = 0, 0.0
    for lane, (x, y) in enumerate(zip(a, b)):
        if len(x["tokens"]) != len(y["tokens"]):
            return False, f"{name}: lane {lane} lengths differ {len(x['tokens'])} vs {len(y['tokens'])}", 0.0
        for t, (tx, ty) in enumerate(zip(x["tokens"], y["tokens"])):
            d = abs(x["logprobs"][t] - y["logprobs"][t])
            if d > TOL:
                return False, f"{name}: lane {lane} token {t}: logprob {x['logprobs'][t]:.4f} vs {y['logprobs'][t]:.4f}", d
            worst_lp = max(worst_lp, d)
            if tx != ty:
                break  # a tie broken the other way (|dlogprob| <= TOL was just checked)
            equal += 1
    n_tok = sum(len(x["tokens"]) for x in a)
    return True, (f"{name}: {equal}/{n_tok} tokens equal before any tie-break divergence, "
                  f"max |dlogprob| {worst_lp:.4f} (tol {TOL})"), worst_lp


def engine_run(eng, prompts, max_tokens: int) -> list[dict]:
    from ray_tpu.llm import SamplingParams

    outs = eng.generate(prompts, SamplingParams(temperature=0.0, max_tokens=max_tokens, logprobs=True))
    return [{"tokens": [int(t) for t in o.token_ids], "logprobs": [float(x) for x in o.logprobs]} for o in outs]


# ----------------------------------------------------------------------------------------------
# phase 0: probe — is there a chip, is the program there
# ----------------------------------------------------------------------------------------------
def phase_probe(a, inp: dict) -> dict:
    import ray_tpu  # noqa: F401 — a directory without the program fails here
    from ray_tpu._native import native_available
    from ray_tpu.accelerators.tpu import TPUAcceleratorManager
    from ray_tpu.util.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    dev = device_of()
    say("probe", f"device {dev}; node chips detected {TPUAcceleratorManager.get_current_node_num_accelerators()}")
    say("probe", "compile cache: " + (
        f"{cache} (from JAX_COMPILATION_CACHE_DIR)" if os.environ.get("JAX_COMPILATION_CACHE_DIR")
        else f"{cache} (fixed in-checkout default)" if cache else "off (process pinned to the CPU backend)"))
    say("probe", "native hashing: " + ("built on the spot with g++" if native_available() else "python fallback taken"))
    return {"passed": True, "device": dev}


# ----------------------------------------------------------------------------------------------
# phase 1: serve — init -> serve.run(build_llm_deployment) -> concurrent generate
# ----------------------------------------------------------------------------------------------
def _serve_requests(a, tp: int, requests: list[dict], tag: str) -> dict:
    """Driver of the serving plane. This process never initialises a JAX backend: the replica
    worker the scheduler binds to the chip(s) is the only holder."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.llm import LLMConfig, build_llm_deployment

    ray_tpu.init(num_cpus=4)
    try:
        res = ray_tpu.cluster_resources()
        say(tag, f"cluster resources {res}")
        # tp=1: auto (one chip where the node has chips). tp=4: num_tpus=4, which makes the runtime
        # bind TPU_VISIBLE_CHIPS=0,1,2,3 and 2x2 bounds (accelerators/tpu.py); only a CPU
        # rehearsal, which has no TPU resource to hold, asks for none
        num_tpus = -1 if tp == 1 else float(tp) if res.get("TPU", 0) >= tp or not a.tiny else 0.0
        app = build_llm_deployment(LLMConfig(
            model_config=LlamaConfig(**serve_shape(a.tiny)),
            engine_kwargs={"seed": a.seed},  # default engine options otherwise
            tensor_parallel_size=tp,
            num_tpus_per_replica=num_tpus,
        ))
        t0 = time.time()
        h = serve.run(app, name=f"chip_smoke_{tag}", blocking_timeout_s=900.0)
        spinup = time.time() - t0  # replica construction: weights, engine, prewarm compiles
        stats = h.kv_cache_stats.remote().result(timeout_s=120)
        say(tag, f"replica up in {spinup:.1f}s on its own device {stats['device']}; kv {stats['layout']}/{stats['dtype']}")

        def wave(label):
            t = time.time()
            refs = [h.generate.remote(r["prompt"], r["sampling"]) for r in requests]
            outs = [ref.result(timeout_s=900) for ref in refs]
            dt = time.time() - t
            say(tag, f"{label}: {len(outs)} concurrent requests, {sum(len(o['token_ids']) for o in outs)} tokens in {dt:.1f}s")
            return outs, dt

        # warm-up wave compiles the prompt buckets; the measured wave must compile nothing
        _, warm_s = wave("warm-up wave")
        rec0 = h.telemetry.remote().result(timeout_s=120)["recompiles"]
        outs, wave_s = wave("measured wave")
        tel = h.telemetry.remote().result(timeout_s=120)
        stats = h.kv_cache_stats.remote().result(timeout_s=120)
        recompiles = sum(tel["recompiles"].values())
        say(tag, f"rt_llm_recompiles_total after warm-up: {recompiles} (before measured wave {sum(rec0.values())}); "
                 f"engine steps {tel['step_count']}")
        ok = recompiles == 0
        for r, o in zip(requests, outs):
            want = r["sampling"]["max_tokens"]
            if len(o["token_ids"]) != want or len(o["logprobs"] or []) != want:
                say(tag, f"FAIL request returned {len(o['token_ids'])} tokens, asked {want}")
                ok = False
        if outs[-1]["token_ids"] != outs[-2]["token_ids"]:
            say(tag, "FAIL the seeded-sampled request is not reproducible")
            ok = False
        return {
            "passed": ok, "device": stats["device"], "stats": stats, "spinup_s": spinup,
            "compile_s": spinup + max(0.0, warm_s - wave_s),  # set-up: spin-up plus the warm wave's excess
            "outputs": [{"tokens": o["token_ids"], "logprobs": o["logprobs"]} for o in outs],
        }
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def phase_serve(a, inp: dict) -> dict:
    return _serve_requests(a, 1, inp["requests"], "serve")


# ----------------------------------------------------------------------------------------------
# phase 2: reference — the plain models/llama.py forward, teacher-forced on the served tokens
# ----------------------------------------------------------------------------------------------
def reference_check(a, tag: str, requests: list[dict], outputs: list[dict]) -> bool:
    """Teacher-force the served tokens through the plain forward on the same seeded weights:
    every emitted token's logprob must agree with the reference within TOL, and every greedy
    token must be the reference's top-1 or inside TOL of it. Holds whatever the streams did
    after a tie-break, because each position is judged on the prefix that was really served."""
    from dataclasses import replace

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.llama import LlamaConfig, forward, init_params

    # independent of the serving code: the XLA attention, one causal forward, no cache, one device
    cfg = replace(LlamaConfig(**serve_shape(a.tiny)), attention_impl="xla")
    seed = a.seed + (1 if a.sabotage == "reference" else 0)  # --sabotage: the comparison must notice
    params = jax.jit(lambda k: init_params(cfg, k))(jax.random.PRNGKey(seed))
    fwd = jax.jit(lambda p, t: jax.nn.log_softmax(forward(p, t, cfg).astype(jnp.float32), axis=-1))
    ok, worst_lp, worst_margin, equal, n_greedy = True, 0.0, 0.0, 0, 0
    t0 = time.time()
    for i, (r, o) in enumerate(zip(requests, outputs)):
        toks, n = r["prompt"] + o["tokens"], len(r["prompt"])
        pad = -len(toks) % 64  # few distinct shapes; causal attention ignores the right padding
        logp = np.asarray(fwd(params, jnp.asarray([toks + [0] * pad], jnp.int32))[0])
        for t, tok in enumerate(o["tokens"]):
            row = logp[n + t - 1]
            d = abs(float(row[tok]) - o["logprobs"][t])
            worst_lp = max(worst_lp, d)
            if d > TOL:
                say(tag, f"FAIL request {i} token {t}: served logprob {o['logprobs'][t]:.4f}, reference {row[tok]:.4f}")
                ok = False
                break
            if r["sampling"]["temperature"] == 0.0:
                n_greedy += 1
                margin = float(row.max() - row[tok])  # 0 where the served token IS the reference's top-1
                worst_margin = max(worst_margin, margin)
                equal += margin == 0.0
                if margin > TOL:
                    say(tag, f"FAIL request {i} token {t}: served {tok} but reference top-1 "
                             f"{int(row.argmax())} leads by {margin:.4f} > {TOL}")
                    ok = False
                    break
    say(tag, f"plain forward in {time.time() - t0:.1f}s: {equal}/{n_greedy} greedy tokens are the reference's "
             f"top-1, the rest within margin {worst_margin:.4f}; max |served - reference| logprob over all "
             f"{sum(len(o['tokens']) for o in outputs)} tokens {worst_lp:.4f} (tol {TOL})")
    return ok


def phase_reference(a, inp: dict) -> dict:
    from ray_tpu.util.compile_cache import enable_compile_cache

    enable_compile_cache()
    dev = device_of()
    return {"passed": reference_check(a, "reference", inp["requests"], inp["outputs"]), "device": dev}


# ----------------------------------------------------------------------------------------------
# phase 3: train — JaxTrainer, one TPU worker, parallel/train_step.py at the 8 x 2048 SFT shape
# ----------------------------------------------------------------------------------------------
def _train_loop(config: dict):
    """Runs in the train worker (the process that holds the chips)."""
    from dataclasses import replace
    from functools import partial

    import jax
    import numpy as np
    import optax

    from ray_tpu import train
    from ray_tpu.models.llama import LlamaConfig, init_params, loss_fn, param_logical_axes
    from ray_tpu.ops.flash_attention import _use_pallas
    from ray_tpu.parallel.mesh import create_mesh
    from ray_tpu.parallel.train_step import make_train_step, shard_batch

    cfg = LlamaConfig(**config["shape"])
    batch, seq, steps, fsdp = config["batch"], config["seq"], config["steps"], config["fsdp"]
    devs = jax.devices()
    mesh = create_mesh(fsdp=fsdp) if fsdp > 1 else create_mesh(dp=len(devs))
    t0 = time.time()
    # the mesh reaches the model so that, on several devices, the flash kernel runs under shard_map
    init_fn, compile_step, _ = make_train_step(
        partial(loss_fn, config=cfg, mesh=mesh), optax.adamw(3e-4, weight_decay=0.01), mesh, param_logical_axes(cfg))
    state, shardings = init_fn(jax.random.PRNGKey(config["seed"]), partial(init_params, cfg))
    step = compile_step(shardings)
    rng = np.random.default_rng(config["seed"])
    data = {"tokens": rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32),
            "targets": rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)}
    sb = shard_batch(data, mesh)
    q = jax.ShapeDtypeStruct((batch, cfg.num_heads, seq, cfg.hd), cfg.dtype)
    attn = "pallas" if _use_pallas(q, cfg.attention_impl) else "xla"
    kernel_in_program = "tpu_custom_call" in step.lower(state, sb).as_text()
    # where the weights really sit: one shard per device of the mesh, not all on the first
    wq = state.params["layers"]["wq"]
    shard_devs = sorted({s.device.id for s in wq.addressable_shards})
    shard_frac = wq.addressable_shards[0].data.size / wq.size
    # loss parity: the sharded jitted step must report the loss an unsharded direct
    # loss_fn eval computes on the same initial params — here through the XLA attention, so the
    # flash kernel is checked against an independent path as well
    ref_loss = float(jax.jit(partial(loss_fn, config=replace(cfg, attention_impl="xla")))(state.params, data))
    losses = []
    t1 = time.time()
    state, m = step(state, sb)
    losses.append(float(m["loss"]))
    first_step_s = time.time() - t1
    t2 = time.time()
    for _ in range(steps - 1):
        state, m = step(state, sb)
        losses.append(float(m["loss"]))
    steady_s = (time.time() - t2) / max(1, steps - 1)
    mem = [d.memory_stats() or {} for d in devs]
    train.report({
        "device": {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)},
        "attn": attn, "kernel_in_program": kernel_in_program, "ref_loss": ref_loss, "losses": losses,
        "setup_s": t1 - t0, "first_step_s": first_step_s, "steady_step_s": steady_s,
        "shard_devices": shard_devs, "shard_fraction": shard_frac,
        "bytes_in_use": [int(x.get("bytes_in_use", 0)) for x in mem],
        "env": {k: os.environ.get(k) for k in ("TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_HOST_BOUNDS", "JAX_PLATFORMS")},
    })


def _train(a, fsdp: int, tag: str) -> dict:
    import ray_tpu
    from ray_tpu import train
    from ray_tpu.train import RunConfig, ScalingConfig

    shape, batch, seq, steps = train_shape(a.tiny)
    ray_tpu.init(num_cpus=4)
    try:
        has_tpu = ray_tpu.cluster_resources().get("TPU", 0) > 0
        scaling = ScalingConfig(num_workers=1, use_tpu=has_tpu,
                                resources_per_worker={"TPU": float(fsdp)} if has_tpu and fsdp > 1 else None)
        say(tag, f"JaxTrainer with one worker, resources {scaling._worker_resources}")
        result = train.JaxTrainer(
            _train_loop,
            train_loop_config={"shape": shape, "batch": batch, "seq": seq, "steps": steps, "fsdp": fsdp, "seed": a.seed},
            scaling_config=scaling,
            run_config=RunConfig(name=f"chip_smoke_{tag}", storage_path=os.path.join(OUT, "train")),
        ).fit()
    finally:
        ray_tpu.shutdown()
    m = result.metrics
    losses, dev = m["losses"], m["device"]
    say(tag, f"worker device {dev}, env {m['env']}; attention impl that ran: {m['attn']} "
             f"(tpu_custom_call in the step program: {m['kernel_in_program']})")
    say(tag, f"batch {batch} x seq {seq}, {steps} steps: build+init {m['setup_s']:.1f}s, first step (compile) "
             f"{m['first_step_s']:.1f}s, steady {m['steady_step_s']:.3f}s/step")
    say(tag, f"loss parity: sharded step {losses[0]:.4f} vs unsharded loss_fn {m['ref_loss']:.4f}; losses {['%.4f' % x for x in losses]}")
    say(tag, f"wq shards on devices {m['shard_devices']}, each {m['shard_fraction']:.3f} of the array; "
             f"bytes_in_use per device {m['bytes_in_use']}")
    ok = True
    if abs(losses[0] - m["ref_loss"]) >= 0.05:
        say(tag, "FAIL loss parity")
        ok = False
    if not all(x == x and abs(x) != float("inf") for x in losses) or not losses[-1] < losses[0]:
        say(tag, "FAIL loss is not finite and falling")
        ok = False
    if dev["platform"] == "tpu" and not (m["attn"] == "pallas" and m["kernel_in_program"]):
        say(tag, "FAIL flash attention was not selected on the TPU")
        ok = False
    if fsdp > 1 and (len(m["shard_devices"]) != fsdp or abs(m["shard_fraction"] - 1.0 / fsdp) > 1e-6
                     or min(m["bytes_in_use"]) <= 0 and dev["platform"] == "tpu"):
        say(tag, f"FAIL weights are not sharded over {fsdp} devices")
        ok = False
    return {"passed": ok, "device": dev, "compile_s": m["setup_s"] + m["first_step_s"]}


def phase_train(a, inp: dict) -> dict:
    return _train(a, 1, "train")


# ----------------------------------------------------------------------------------------------
# phase 4: paged KV + the Pallas kernel against the XLA paged path, in one process
# ----------------------------------------------------------------------------------------------
def phase_paged(a, inp: dict) -> dict:
    from ray_tpu.llm import LLMEngine
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.util.compile_cache import enable_compile_cache

    enable_compile_cache()
    dev = device_of()
    # full width, depth cut to 4: eight engine builds stay inside the smoke's time limit
    cfg = LlamaConfig(**{**serve_shape(a.tiny), "num_layers": 2 if a.tiny else 4})
    prompts = [r["prompt"][: (20 if a.tiny else 200) + 7 * i] for i, r in enumerate(inp["requests"][:3])]
    new = 6 if a.tiny else 16
    ok, t0, params = True, time.time(), None
    # fp pool at the default page; int8 pool at a page its [kv, page] scale plane tiles (128 lanes)
    max_len = 256 if a.tiny else 512
    for label, kw in (("fp pool, page 16", {"page_size": 16}),
                      ("int8 pool, page 128", {"page_size": 128, "cache_dtype": "int8", "prefix_block": 128,
                                               "prefill_buckets": (128, 256, 512)[: 2 if a.tiny else 3]})):
        runs = {}
        for kernel in ("xla", "pallas"):
            eng = LLMEngine(cfg, params, seed=a.seed, max_num_seqs=4, max_seq_len=max_len,
                            kv_layout="paged", attn_kernel=kernel, enable_prefix_caching=False, **kw)
            params = eng.params
            if eng.attn_kernel != kernel:
                raise RuntimeError(f"asked for attn_kernel={kernel!r}, engine resolved {eng.attn_kernel!r}")
            if kernel == "pallas":
                txt = eng._fused_attn.lower(eng.params, eng.pool, eng._dtables, eng._dlengths, eng._dtokens,
                                            eng._dkeys, eng._dtemps, eng._dtopk, eng._dtopp).as_text()
                compiled = "tpu_custom_call" in txt
                say("paged", f"{label}: tpu_custom_call in the engine's decode program: {compiled}"
                             + ("" if compiled else " (Pallas interpret mode: the kernel body runs as plain jax ops)"))
                if dev["platform"] == "tpu" and not compiled:
                    say("paged", "FAIL the kernel was not compiled for the TPU")
                    ok = False
            runs[kernel] = engine_run(eng, prompts, new)
            del eng
        good, msg, _ = compare_streams(f"{label}: pallas vs xla", runs["pallas"], runs["xla"])
        say("paged", ("" if good else "FAIL ") + msg)
        ok = ok and good
    say("paged", f"four engines built and decoded in {time.time() - t0:.1f}s")
    return {"passed": ok, "device": dev}


# ----------------------------------------------------------------------------------------------
# phase 5: hand-over — chip-bound workers are single-use, and the next one finds the chip free
# ----------------------------------------------------------------------------------------------
def _open_device() -> dict:
    """Runs in a worker: open whatever backend the runtime bound this process to and use it."""
    import jax
    import jax.numpy as jnp

    devs = jax.devices()
    return {"pid": os.getpid(), "platform": devs[0].platform, "count": len(devs),
            "sum": float(jnp.ones((256, 256)).sum()), "visible": os.environ.get("TPU_VISIBLE_CHIPS"),
            "jax_platforms": os.environ.get("JAX_PLATFORMS")}


def phase_handover(a, inp: dict) -> dict:
    """runtime.py retires a chip-bound worker after one use and holds its chips back until the
    process has exited. Two num_tpus=1 tasks in a row, then a chip-bound actor that is killed and
    restarted: each is a new process and each must open the chip (JAX_PLATFORMS=tpu makes a chip
    that is still held an error, not a CPU run). A CPU rehearsal has no chip to bind and checks
    the control flow only."""
    import ray_tpu
    from ray_tpu.exceptions import ActorDiedError, ActorUnavailableError

    ray_tpu.init(num_cpus=4)
    try:
        n = 1 if ray_tpu.cluster_resources().get("TPU", 0) > 0 else 0
        want = "tpu" if n else "cpu"
        task = ray_tpu.remote(num_cpus=1, num_tpus=n)(_open_device)
        seen = []
        for i in range(2):
            t0 = time.time()
            seen.append(ray_tpu.get(task.remote(), timeout=300))
            say("handover", f"task {i + 1} opened {seen[-1]} in {time.time() - t0:.1f}s")

        @ray_tpu.remote(num_cpus=1, num_tpus=n, max_restarts=1)
        class Holder:
            def where(self):
                return _open_device()

        h = Holder.remote()
        seen.append(ray_tpu.get(h.where.remote(), timeout=300))
        say("handover", f"actor opened {seen[-1]}")
        ray_tpu.kill(h, no_restart=False)
        t0, deadline = time.time(), time.time() + 300
        while True:  # the restarted incarnation must open the chip the killed one held
            try:
                seen.append(ray_tpu.get(h.where.remote(), timeout=120))
                break
            except (ActorDiedError, ActorUnavailableError):
                if time.time() > deadline:
                    raise
                time.sleep(0.5)
        say("handover", f"restarted actor opened {seen[-1]} {time.time() - t0:.1f}s after the kill")
    finally:
        ray_tpu.shutdown()
    ok = all(x["platform"] == want and x["sum"] == 65536.0 for x in seen)
    if n and len({x["pid"] for x in seen}) != len(seen):
        say("handover", "FAIL a chip-bound worker process was reused")
        ok = False
    if n and not all(x["visible"] == "0" and x["jax_platforms"] == "tpu" for x in seen):
        say("handover", "FAIL a chip-bound worker was not given TPU_VISIBLE_CHIPS / JAX_PLATFORMS=tpu")
        ok = False
    return {"passed": ok, "device": {"platform": seen[0]["platform"], "kind": inp["kind"], "count": seen[0]["count"]}}


# ----------------------------------------------------------------------------------------------
# --chips 4: tp=4 replica vs a tp=1 engine, the int8 collective, fsdp=4 training
# ----------------------------------------------------------------------------------------------
def phase_tp4_serve(a, inp: dict) -> dict:
    greedy = [r for r in inp["requests"] if r["sampling"]["temperature"] == 0.0]
    # the seeded-sampled pair rides along so _serve_requests' reproducibility check still runs
    res = _serve_requests(a, 4, greedy + inp["requests"][-2:], "tp4-serve")
    if not a.tiny and res["device"]["count"] != 4:
        say("tp4-serve", f"FAIL the replica's cache sits on {res['device']['count']} devices, not 4")
        res["passed"] = False
    res["requests"] = greedy + inp["requests"][-2:]  # what was served, in order, for the reference check
    return res


def phase_tp4_engines(a, inp: dict) -> dict:
    """One process holding all four chips: the tp=1 engine the replica is compared with, then
    tp=4 engines with fp and int8 collectives, judged as tests/test_llm_tp.py judges them."""
    import jax

    from ray_tpu.llm import LLMEngine
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.parallel.mesh import create_mesh
    from ray_tpu.util.compile_cache import enable_compile_cache

    enable_compile_cache()
    dev = device_of()
    devs = jax.devices()
    if len(devs) < 4:
        say("tp4-engines", f"FAIL {len(devs)} devices, need 4")
        return {"passed": False, "device": dev}
    cfg = LlamaConfig(**serve_shape(a.tiny))
    greedy = [r for r in inp["requests"] if r["sampling"]["temperature"] == 0.0]
    prompts, new = [r["prompt"] for r in greedy], greedy[0]["sampling"]["max_tokens"]
    ok = True

    # the replica's every token against the plain forward (greedy and seeded-sampled alike) ...
    ok = reference_check(a, "tp4-engines", inp["served_requests"], inp["served"]) and ok
    # ... and its greedy streams against a tp=1 engine, token for token
    base = engine_run(LLMEngine(cfg, seed=a.seed), prompts, new)
    good, msg, _ = compare_streams("tp=4 replica vs tp=1 engine", inp["served"][: len(greedy)], base)
    say("tp4-engines", ("" if good else "FAIL ") + msg)
    ok = ok and good

    mesh = create_mesh(tp=4, devices=devs[:4])
    runs = {}
    for coll in ("fp", "int8"):
        eng = LLMEngine(cfg, seed=a.seed, mesh=mesh, tp_collective=coll)
        runs[coll] = engine_run(eng, prompts, new)
        if coll == "fp":
            # weights and cache really sit on four devices, not all on the first
            wq, ck = eng.params["layers"]["wq"], eng.cache["k"]
            wdev = sorted({s.device.id for s in wq.addressable_shards})
            frac = (wq.addressable_shards[0].data.size / wq.size, ck.addressable_shards[0].data.size / ck.size)
            used = [int((d.memory_stats() or {}).get("bytes_in_use", 0)) for d in devs[:4]]
            say("tp4-engines", f"wq shards on devices {wdev} ({frac[0]:.3f} each), cache k {frac[1]:.3f} each; "
                               f"bytes_in_use per device {used}")
            if len(wdev) != 4 or abs(frac[0] - 0.25) > 1e-6 or abs(frac[1] - 0.25) > 1e-6:
                say("tp4-engines", "FAIL weights/cache are not sharded four ways")
                ok = False
        del eng
    good, msg, _ = compare_streams("tp=4 fp engine vs tp=1 engine", runs["fp"], base)
    say("tp4-engines", ("" if good else "FAIL ") + msg)
    ok = ok and good
    # judged as tests/test_llm_tp.py judges the int8 collective: the decode logprobs drift by a
    # bounded, NONZERO amount (zero would mean the quantized all-reduce never engaged)
    good, msg, drift = compare_streams("tp=4 int8-collective vs fp-collective", runs["int8"], runs["fp"])
    say("tp4-engines", ("" if good else "FAIL ") + msg)
    ok = ok and good
    if not 0.0 < drift < 0.2:
        say("tp4-engines", f"FAIL int8 collective logprob drift {drift:.5f} is not in (0, 0.2)")
        ok = False
    return {"passed": ok, "device": dev}


def phase_fsdp4_train(a, inp: dict) -> dict:
    return _train(a, 4, "fsdp4-train")


PHASES = {
    "probe": phase_probe, "serve": phase_serve, "reference": phase_reference, "train": phase_train,
    "paged": phase_paged, "handover": phase_handover, "tp4-serve": phase_tp4_serve, "tp4-engines": phase_tp4_engines,
    "fsdp4-train": phase_fsdp4_train,
}


# ----------------------------------------------------------------------------------------------
# parent: no JAX here
# ----------------------------------------------------------------------------------------------
def run_phase(a, name: str, payload: dict) -> dict:
    """Run one phase in a child that leads its own process group; return its result. The group is
    killed and waited empty afterwards, so the next phase's process finds the chip free."""
    os.makedirs(OUT, exist_ok=True)
    io_in, io_out = os.path.join(OUT, f"{name}.in.json"), os.path.join(OUT, f"{name}.out.json")
    with open(io_in, "w") as f:
        json.dump(payload, f)
    if os.path.exists(io_out):
        os.remove(io_out)
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", name, "--seed", str(a.seed)]
    cmd += ["--tiny"] if a.tiny else []
    cmd += ["--sabotage", a.sabotage] if a.sabotage else []
    t0 = time.time()
    child = subprocess.Popen(cmd, cwd=ROOT, start_new_session=True)
    try:
        rc = child.wait()
    finally:
        _reap(child.pid)
    secs = time.time() - t0
    if rc != 0 or not os.path.exists(io_out):
        print(f"[{name}] FAILED: child exit code {rc} after {secs:.1f}s", flush=True)
        return {"passed": False, "seconds": secs}
    with open(io_out) as f:
        res = json.load(f)
    res["seconds"] = secs
    compile_s = f", compile/set-up {res['compile_s']:.1f}s" if "compile_s" in res else ""
    print(f"[{name}] {'passed' if res['passed'] else 'FAILED'} in {secs:.1f}s{compile_s}", flush=True)
    return res


def _reap(pgid: int) -> None:
    """Stop every process the phase started (the child led its own group) and wait until none is
    left: a dying libtpu still holds the chip."""
    deadline = time.time() + 60
    while time.time() < deadline:
        try:
            os.killpg(pgid, signal.SIGKILL)
        except ProcessLookupError:
            return
        time.sleep(0.2)
    raise RuntimeError(f"process group {pgid} did not exit")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--tiny", action="store_true", help="CPU rehearsal at toy sizes; never reports ok")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sabotage", choices=("reference",), default=None,
                    help="give the named phase wrong data; the run must then fail, and ends after that phase (tested)")
    ap.add_argument("--phase", choices=sorted(PHASES), default=None, help=argparse.SUPPRESS)
    a = ap.parse_args()

    if a.phase:  # child
        with open(os.path.join(OUT, f"{a.phase}.in.json")) as f:
            inp = json.load(f)
        res = PHASES[a.phase](a, inp)
        with open(os.path.join(OUT, f"{a.phase}.out.json"), "w") as f:
            json.dump(res, f)
        return 0

    t0 = time.time()
    results: dict[str, dict] = {}
    requests = make_requests(a.tiny, a.seed)

    def phase(name: str, payload: dict | None = None) -> dict:
        results[name] = run_phase(a, name, payload or {})
        return results[name]

    probe = phase("probe")
    if not probe["passed"]:
        return 1
    if probe["device"]["platform"] != "tpu" and not a.tiny:
        print(f"[probe] FAILED: JAX finds no TPU (device {probe['device']}); nothing was run", flush=True)
        return 1
    if probe["device"]["count"] != a.chips and not a.tiny:
        print(f"[probe] FAILED: --chips {a.chips} but JAX sees {probe['device']['count']} devices", flush=True)
        return 1

    if a.chips == 1:
        serve = phase("serve", {"requests": requests})
        if serve["passed"]:  # the reference needs the served tokens
            phase("reference", {"requests": requests, "outputs": serve["outputs"]})
        if not a.sabotage:  # a sabotaged run has failed by now; its test asks no more of it, and the rehearsal runs the rest
            phase("train")
            phase("paged", {"requests": requests})
            phase("handover", {"kind": probe["device"]["kind"]})
        expected = ("probe", "serve", "reference", "train", "paged", "handover")
    else:
        serve = phase("tp4-serve", {"requests": requests})
        if serve["passed"]:
            phase("tp4-engines", {"requests": requests, "served": serve["outputs"], "served_requests": serve["requests"]})
        phase("fsdp4-train")
        expected = ("probe", "tp4-serve", "tp4-engines", "fsdp4-train")

    compile_total = sum(r.get("compile_s", 0.0) for r in results.values())
    print(f"[summary] {time.time() - t0:.1f}s total, compile/set-up {compile_total:.1f}s across phases; "
          + ", ".join(f"{n} {'passed' if results.get(n, {}).get('passed') else 'FAILED' if n in results else 'not run'}"
                      for n in expected), flush=True)
    if not all(results.get(n, {}).get("passed") for n in expected):
        return 1
    devices = [results[n]["device"] for n in expected]
    worker = devices[1]  # the serving replica's own device: the process that did the work
    if any(d["platform"] != "tpu" for d in devices):
        print(f"[summary] every phase passed, but not on a TPU ({worker}): not ok", flush=True)
        return 1
    if worker["count"] != a.chips:
        print(f"[summary] the replica saw {worker['count']} devices, --chips {a.chips}: not ok", flush=True)
        return 1
    final = {"ok": True, "device": worker}
    if a.tiny:
        final["tiny"] = True
    print(json.dumps(final), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
