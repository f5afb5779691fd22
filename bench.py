"""Headline benchmark: Llama SFT train-step MFU on the local TPU chip.

Prints exactly ONE JSON line on a TPU, and fails anywhere else:
  {"metric": "llama_sft_mfu", "value": <MFU>, "unit": "mfu", "vs_baseline": <MFU/0.35>}

Baseline: the reference's north-star target of 35% MFU for Llama SFT on
v5e (BASELINE.md; the reference publishes no absolute LLM throughput of
its own). The model is scaled to fill one chip's HBM; on a pod the same
program scales via the dp/fsdp mesh (see __graft_entry__.dryrun_multichip).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from functools import partial

# bf16 peak FLOP/s per chip, keyed by jax's device_kind (Google Cloud TPU
# documentation, per-generation system architecture pages). A device that
# is not in the table is an error, never a default.
PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def peak_flops(device) -> float:
    kind = str(device.device_kind)
    if kind not in PEAK_FLOPS:
        raise KeyError(f"no peak FLOP/s on record for device_kind {kind!r}; add it to bench.PEAK_FLOPS with its source")
    return PEAK_FLOPS[kind]


def main(config: str = "sft", tiny: bool = False) -> int:
    import jax
    import numpy as np
    import optax

    from ray_tpu.models.llama import LlamaConfig, flops_per_token, init_params, loss_fn, param_logical_axes
    from ray_tpu.parallel.mesh import create_mesh
    from ray_tpu.parallel.train_step import make_train_step, shard_batch  # make_train_step places the compile cache

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not tiny:
        # a measurement path that finds no chip fails; it does not fall back
        print(f"bench.py measures a TPU; JAX found {dev.platform}:{dev.device_kind} (use --tiny to rehearse the path)", file=sys.stderr)
        return 1

    metric = "llama_sft_mfu"
    # ~940M-param model: fills a 16GB v5e chip with bf16 adam state
    shape = dict(vocab_size=32000, hidden_size=2048, intermediate_size=5632, num_layers=18, num_heads=16, num_kv_heads=8)
    if config == "longctx":
        # the SAME model at 4x the sequence length, one sequence per step —
        # the long-context regime where attention FLOPs start to matter
        metric = "llama_sft_mfu_seq8192"
        cfg, batch, seq, steps = LlamaConfig(**shape, max_seq_len=8192), 2, 8192, 6
    else:
        cfg, batch, seq, steps = LlamaConfig(**shape, max_seq_len=2048), 8, 2048, 10
    if tiny:  # rehearsal chosen by the caller: same path, toy size, no metric
        cfg, batch, seq, steps = LlamaConfig.tiny(max_seq_len=512), 4, 128, 3

    mesh = create_mesh(dp=len(jax.devices()))
    init_fn, compile_step, _ = make_train_step(
        partial(loss_fn, config=cfg), optax.adamw(3e-4, weight_decay=0.01), mesh, param_logical_axes(cfg)
    )
    state, shardings = init_fn(jax.random.PRNGKey(0), partial(init_params, cfg))
    step = compile_step(shardings)

    rng = np.random.default_rng(0)
    data = {
        "tokens": rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32),
        "targets": rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32),
    }
    sb = shard_batch(data, mesh)

    # loss parity: the sharded+jitted train step must report the SAME loss
    # an unsharded direct loss_fn eval computes on the initial params —
    # catches masking/scaling/sharding wiring bugs that a plausibility
    # range check cannot (an MFU number on a subtly-wrong loss is void)
    ref_loss = float(jax.jit(partial(loss_fn, config=cfg))(state.params, data))
    state, metrics = step(state, sb)
    first_loss = float(metrics["loss"])
    assert abs(first_loss - ref_loss) < 0.05, (
        f"sharded step loss {first_loss} != unsharded reference {ref_loss}"
    )

    # warm-up step, then drain the dispatch queue before timing
    state, metrics = step(state, sb)
    jax.block_until_ready(state)

    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, sb)
    jax.block_until_ready((state, metrics))
    dt = time.perf_counter() - t0
    loss = float(metrics["loss"])

    if not on_tpu:
        print(f"rehearsal on {dev.platform}: {steps} steps ran, loss {loss:.4f}; no device metric is reported off the TPU", file=sys.stderr)
        return 1

    tokens_per_s = batch * seq * steps / dt
    achieved = flops_per_token(cfg, seq) * tokens_per_s
    mfu = achieved / (peak_flops(dev) * len(jax.devices()))
    assert 0.0 < mfu <= 1.0, f"MFU {mfu} is not physically possible; harness is lying"

    print(
        json.dumps(
            {
                "metric": metric,
                "value": round(mfu, 4),
                "unit": "mfu",
                "vs_baseline": round(mfu / 0.35, 4),
                "detail": {
                    "tokens_per_s": round(tokens_per_s, 1),
                    "params": cfg.num_params(),
                    "device": str(dev.device_kind),
                    "n_devices": len(jax.devices()),
                    "batch": batch,
                    "seq": seq,
                    "loss": round(loss, 4),
                    "tiny": tiny,
                },
            }
        )
    )
    return 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="sft", choices=("sft", "longctx"))
    ap.add_argument("--tiny", action="store_true", help="rehearse the path at a toy size (reports no metric off the TPU)")
    args = ap.parse_args()
    sys.exit(main(args.config, args.tiny))
