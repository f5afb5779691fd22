"""A training cell: the job goes through ``JaxTrainer.fit`` and the loop below runs in the train
worker, the one process that holds the chip. The driver never initialises a JAX backend.
"""

from __future__ import annotations

import json
import os
import time

from benchmark import common, traffic
from benchmark.peaks import peaks_of


def say(msg: str) -> None:
    print(f"[train] {msg}", flush=True)


def train_loop(config: dict):
    """Runs in the train worker. Builds the sharded step (``parallel/train_step.py``), warms it,
    then takes optimizer steps on fresh seeded host batches until ``seconds`` have passed; every
    step ends when its loss is on the host. Reference losses are computed outside the window."""
    from functools import partial

    import jax
    import optax

    from benchmark import reference, stats, xplane
    from ray_tpu import train
    from ray_tpu.parallel.mesh import create_mesh
    from ray_tpu.parallel.train_step import make_train_step, shard_batch

    c, mix, seed, seconds = config["config"], config["mix"], config["seed"], config["seconds"]
    family = common.load_family(c["family"])
    compiles = common.record_lowerings()
    batch, seq = int(mix["global_batch"]), int(mix["seq_len"])
    cfg = family.program_config(c, seq, **config["model_extra"])
    devs = jax.devices()
    mesh = create_mesh(dp=len(devs))
    opt = mix["optimizer"]
    tx = {"adamw": optax.adamw}[opt["name"]](opt["learning_rate"], weight_decay=opt["weight_decay"])
    t_build = time.time()
    init_fn, compile_step, _ = make_train_step(partial(family.loss_fn, config=cfg, mesh=mesh), tx, mesh,
                                               family.param_logical_axes(cfg))
    state, shardings = init_fn(jax.random.PRNGKey(seed), partial(family.init_params, cfg))
    step = compile_step(shardings)

    def host_batch(i):
        return traffic.train_batch(seed, i, batch, seq, c["vocab_size"])

    def ref_params(params):
        if not config["sabotage"]:
            return params
        return {**params, "embed": jax.jit(lambda k: family.init_params(cfg, k)["embed"])(jax.random.PRNGKey(seed + 1))}

    losses, i = [], 0
    for _ in range(int(mix["warmup_steps"])):
        state, m = step(state, shard_batch(host_batch(i), mesh))
        losses.append(float(m["loss"]))
        i += 1
    build_s = time.time() - t_build
    first = host_batch(i)
    t_ref = time.time()
    ref_first = reference.loss(family.reference_logprobs, ref_params(state.params), first, c)
    ref_s = time.time() - t_ref

    trace_dir, traced, trace_host, spans = config.get("trace_dir"), None, [0.0, 0.0], []
    ends, window_losses = [], []
    t0 = time.time()
    while True:
        t_a = time.time()
        if trace_dir and traced is None and t_a - t0 >= seconds * 0.3:
            trace_host[0], traced = time.time(), 0
            jax.profiler.start_trace(trace_dir, profiler_options=xplane.device_only_options())
            t_a = time.time()
        sb = shard_batch(first if not ends else host_batch(i), mesh)
        t_b = time.time()
        state, m = step(state, sb)
        loss = float(m["loss"])  # the step has ended when its loss is here
        now = time.time()
        spans += [("host: make the batch and put it on the device", t_a, t_b), ("step dispatched, host waits for the loss", t_b, now)]
        ends.append(now)
        window_losses.append(loss)
        i += 1
        if traced is not None and trace_host[1] == 0.0:
            traced += 1
            if traced >= 5:
                jax.profiler.stop_trace()
                trace_host[1] = time.time()
                ends[-1] = trace_host[1]  # stopping the profiler is inside the window too
        if ends[-1] - t0 >= seconds:
            break
    if traced is not None and trace_host[1] == 0.0:
        jax.profiler.stop_trace()
        trace_host[1] = time.time()
    in_window = [x for x in compiles if t0 <= x[0] < ends[-1]]

    last = host_batch(i)
    ref_last = reference.loss(family.reference_logprobs, ref_params(state.params), last, c)
    state, m = step(state, shard_batch(last, mesh))
    loss_last = float(m["loss"])
    mem = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in jax.local_devices())
    lowered = step.lower(state, shard_batch(last, mesh)).as_text()
    out = {
        "device": {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)},
        "t0": t0, "step_ends": ends, "summary": stats.train_summary(ends, t0, batch * seq),
        "warmup_losses": losses, "loss_first": window_losses[0], "ref_first": ref_first,
        "loss_last": loss_last, "ref_last": ref_last, "losses_finite": all(x == x and abs(x) != float("inf") for x in window_losses),
        "build_s": build_s, "reference_s": ref_s, "memory_peak_bytes": mem,
        "compiles_in_window": len(in_window), "compiled_in_window": sorted({x[1] for x in in_window})[:20],
        "kernels_missing": [name for name, marker in family.kernels_expected(c).items() if marker not in lowered],
    }
    if trace_dir:
        out["trace"] = xplane.reduce_trace_dir(trace_dir, spans, trace_host[0])
        out["trace"].update(trace_host=trace_host, traced_steps=traced)
    train.report(out)


def run(a, cell: dict, t_proc0: float) -> dict:
    import ray_tpu
    from ray_tpu import train
    from ray_tpu.train import RunConfig, ScalingConfig

    name, chips = cell["cell"]["name"], int(cell["cell"]["chips"])
    family = common.load_family(cell["config"]["family"])
    config = family.rehearsal(cell["config"]) if a.rehearse else cell["config"]
    mix = traffic.load_mix(cell["cell"]["traffic"], name)
    extra = dict(cell["config"].get("training", {}))
    if a.rehearse:
        mix.update(global_batch=2, seq_len=64)
    ray_tpu.init(num_cpus=4)
    try:
        have = ray_tpu.cluster_resources().get("TPU", 0)
        if have < chips and not a.rehearse:
            raise SystemExit(f"cell {name} needs {chips} TPU chip(s); the runtime found {have}")
        scaling = ScalingConfig(num_workers=1, use_tpu=have >= chips,
                                resources_per_worker={"TPU": float(chips)} if have >= chips and chips > 1 else None)
        result = train.JaxTrainer(
            train_loop,
            train_loop_config={"config": config, "mix": mix, "seed": a.seed % (2**31), "seconds": float(a.seconds),
                               "model_extra": extra, "sabotage": a.sabotage == "reference",
                               "trace_dir": a.trace_dir if a.trace else None},
            scaling_config=scaling,
            run_config=RunConfig(name="bench_train", storage_path=os.path.join(a.out_dir, "train")),
        ).fit()
    finally:
        ray_tpu.shutdown()
    m = result.metrics
    dev, s = m["device"], m["summary"]
    if dev["platform"] != "tpu" and not a.rehearse:
        raise SystemExit(f"the train worker runs on {dev['platform']}, not a TPU")
    tol = float(cell["config"]["tolerance"]["loss_abs"])
    d_first, d_last = abs(m["loss_first"] - m["ref_first"]), abs(m["loss_last"] - m["ref_last"])
    say(f"family {config['family']}; worker device {dev}; build+init+warm-up {m['build_s']:.1f}s, reference loss {m['reference_s']:.1f}s; "
        f"window: {json.dumps(s)}")
    say(f"loss vs plain reference: first measured step {m['loss_first']:.4f} vs {m['ref_first']:.4f} (d {d_first:.4f}), "
        f"step after the window {m['loss_last']:.4f} vs {m['ref_last']:.4f} (d {d_last:.4f}), tolerance {tol}; "
        f"warm-up losses {m['warmup_losses']}; kernels expected in the step program {sorted(family.kernels_expected(config))}, "
        f"missing {m['kernels_missing']}")
    say(f"compiles in the window: {m['compiles_in_window']} {m['compiled_in_window']}; peak memory {m['memory_peak_bytes'] / 1e9:.2f} GB")
    if dev["platform"] == "tpu":
        # the rate again, in other units: FLOPs the passes require (remat's recompute not counted) over the published peak
        per_token = family.train_flops_per_token(config, int(mix["seq_len"]))
        say(f"mfu {100.0 * s['train_tokens_per_s'] * per_token / (dev['count'] * peaks_of(dev['kind'])['bf16_flops']):.2f}% "
            f"at {per_token / 1e9:.3f} GFLOP a token")
    correct = d_first <= tol and d_last <= tol and m["losses_finite"]
    if dev["platform"] == "tpu" and m["kernels_missing"]:
        say(f"FAIL not in the step program on the TPU: {m['kernels_missing']}")
        correct = False
    obs = {"cell": cell["cell"], "config": config, "mix": mix, "seconds": float(a.seconds), "train": m, "device": dev,
           "worker": {"compiles_in_window": m["compiles_in_window"], "trace": m.get("trace")}}
    e2e = {"setup_s": m["t0"] - t_proc0, "train_tokens_per_s": s["train_tokens_per_s"]}
    compared = [["loss, first measured step, |program - reference|", d_first, tol], ["loss, step after the window, |program - reference|", d_last, tol],
                ["kernels missing from the step program", len(m["kernels_missing"]) if dev["platform"] == "tpu" else 0, 0]]
    return {"correct": bool(correct), "attempted": s["steps"], "failed": 0, "end_to_end": e2e, "obs": obs, "compared": compared,
            "device": dev, "memory_peak_bytes": m["memory_peak_bytes"], "trace": m.get("trace")}
