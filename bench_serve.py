"""Serving benchmark on the real TPU chip (VERDICT r4 #3a).

ENGINE benches, committed as BENCH_serve.json: prefill tokens/s and
steady-state decode tokens/s of the continuous-batching engine on the
same ~1B-param llama bench.py trains, for both KV layouts (slots /
paged), plus the A/B records. Every bench runs in THIS process, which
holds the chip. The full stack (serve.run -> router -> LLMServer replica
-> engine) is proven by chip_smoke.py, whose parent stays off JAX so the
replica worker can open the chip. A phase that raises is recorded and
makes the exit code non-zero.

Reference numbers being mirrored: the Serve-LLM benchmark page the
reference publishes (/root/reference/doc/source/serve/llm/benchmarks.md).

Run ON THE CHIP (no JAX_PLATFORMS override): python bench_serve.py
Quick CPU sanity: JAX_PLATFORMS=cpu python bench_serve.py --tiny
"""

from __future__ import annotations

import argparse
import contextlib
import itertools
import json
import sys
import threading
import time
import traceback

# HBM bandwidth (GB/s) by device kind prefix, for the decode roofline
# (decode is memory-bound: every step must stream the weights plus the
# occupied KV working set from HBM at least once).
_HBM_GBPS = {
    "TPU v4": 1228.0,
    "TPU v5 lite": 819.0,
    "TPU v5e": 819.0,
    "TPU v5p": 2765.0,
    "TPU v5": 2765.0,
    "TPU v6 lite": 1640.0,
    "TPU v6e": 1640.0,
}

# one-way ICI bandwidth per link (GB/s) for the tensor-parallel
# all-reduce roofline: at TP>=2 the per-layer all-reduce is the
# ICI-bound cost of every decode step, and a 1D tp ring ships
# 2(n-1)/n x payload per chip per all-reduce (the factor
# collective_wire_report already folds in).
_ICI_GBPS = {
    "TPU v4": 45.0,
    "TPU v5 lite": 45.0,
    "TPU v5e": 45.0,
    "TPU v5p": 90.0,
    "TPU v5": 90.0,  # v5p spelling on some hosts (matches _HBM_GBPS); AFTER the v5e/v5p keys — the lookup is first-startswith-wins
    "TPU v6 lite": 90.0,
    "TPU v6e": 90.0,
}


def _device_info() -> dict:
    """Prove which device the numbers came from (VERDICT r5: the artifact
    must show it ran on the TPU)."""
    import jax

    d = jax.devices()
    return {"device": d[0].platform, "device_kind": d[0].device_kind, "n_devices": len(d)}


def _tp_of(eng) -> int:
    """Tensor-parallel width of the engine's mesh (1 = single device)."""
    mesh = getattr(eng, "mesh", None)
    if mesh is None:
        return 1
    from ray_tpu.parallel.mesh import mesh_axes

    return int(mesh_axes(mesh).get("tp", 1))


def _roofline(eng, cfg, batch: int, mean_len: float, device_kind: str) -> dict:
    """HBM-roofline decode estimate: ms/step >= (param bytes + occupied
    KV bytes) / HBM bandwidth. Unknown device kinds (e.g. cpu) report
    the byte traffic with no time bound. The per-token KV bytes come from
    the engine's actual cache dtype — for int8 that is values PLUS the
    per-head scales (kv_quant.bytes_per_token), so the roofline stays
    honest under quantization instead of claiming the full 2x."""
    import jax

    from ray_tpu.llm.kv_quant import bytes_per_token

    param_bytes = int(sum(x.nbytes for x in jax.tree.leaves(eng.params)))
    kv_per_token = bytes_per_token(cfg.num_layers, cfg.num_kv_heads, cfg.hd, eng.kv_dtype)
    kv_bytes = int(batch * mean_len * kv_per_token)
    bw = next((v for k, v in _HBM_GBPS.items() if device_kind.startswith(k)), None)
    out = {
        "roofline_param_bytes": param_bytes,
        "roofline_kv_bytes": kv_bytes,
        "roofline_kv_bytes_per_token": int(kv_per_token),
    }
    if bw is not None:
        ms = (param_bytes + kv_bytes) / (bw * 1e9) * 1e3
        out["roofline_decode_step_ms"] = round(ms, 3)
        out["roofline_decode_tokens_per_s"] = round(batch / ms * 1e3, 1)
    return out


def _model(tiny: bool):
    from ray_tpu.models.llama import LlamaConfig

    if tiny:
        return LlamaConfig.tiny(dtype="float32", remat=False, max_seq_len=512), 64, 32
    # the bench.py flagship: ~1B params, bf16
    cfg = LlamaConfig(
        vocab_size=32000,
        hidden_size=2048,
        intermediate_size=5632,
        num_layers=18,
        num_heads=16,
        num_kv_heads=16,
        max_seq_len=2048,
        remat=False,
    )
    return cfg, 512, 128


def bench_engine(
    cfg,
    prompt_len: int,
    gen_len: int,
    kv_layout: str,
    max_num_seqs: int = 8,
    device_resident: bool | None = None,
    trace_dir: str | None = None,
    repeats: int = 1,
    cache_dtype: str | None = None,
    attn_kernel: str = "xla",
) -> dict:
    import numpy as np

    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.sampling import SamplingParams

    kw = {"kv_layout": kv_layout, "page_size": 64, "attn_kernel": attn_kernel} if kv_layout == "paged" else {}
    if device_resident is not None:
        kw["device_resident"] = device_resident
    eng = LLMEngine(
        cfg, max_num_seqs=max_num_seqs, max_seq_len=cfg.max_seq_len,
        enable_prefix_caching=False, cache_dtype=cache_dtype, **kw,
    )
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(1, cfg.vocab_size - 1, size=prompt_len)) for _ in range(max_num_seqs)]
    sp = SamplingParams(temperature=0.7, max_tokens=gen_len)

    # warm/compile with a FULL batch so the batched-prefill program and
    # the fused decode program both compile outside the timed region
    eng.generate(prompts, SamplingParams(temperature=0.7, max_tokens=4))

    # best-of-N repeats: on shared/loaded hosts a single sample is noise
    # (the min is the least-contended measurement of the same program)
    prefill_s = decode_s = float("inf")
    steps = prefill_waves = 1
    for r in range(max(repeats, 1)):
        # prefill phase: admit a full batch, time until all prefills done
        t0 = time.perf_counter()
        ids = [eng.add_request(p, sp) for p in prompts]
        waves = 0
        while eng.num_waiting:
            eng.step()
            waves += 1
        p_s = time.perf_counter() - t0
        if p_s < prefill_s:
            prefill_s, prefill_waves = p_s, waves

        # decode phase: step until done, count generated tokens
        trace = contextlib.nullcontext()
        if trace_dir and r == 0:
            from ray_tpu.util.profiling import profile_trace

            trace = profile_trace(trace_dir)
        t0 = time.perf_counter()
        n_steps = 0
        with trace:
            while eng.has_unfinished():
                eng.step()
                n_steps += 1
        d_s = time.perf_counter() - t0
        if d_s / max(n_steps, 1) < decode_s / max(steps, 1):
            decode_s, steps = d_s, n_steps
        del ids
    prefill_tok_s = max_num_seqs * prompt_len / prefill_s
    gen_tokens = max_num_seqs * gen_len

    info = _device_info()
    decode_step_ms = decode_s / max(steps, 1) * 1e3
    roof = _roofline(eng, cfg, max_num_seqs, prompt_len + gen_len / 2, info["device_kind"])
    roof_ms = roof.get("roofline_decode_step_ms")
    if roof_ms:
        print(
            f"  decode {decode_step_ms:.2f} ms/step vs HBM roofline ~{roof_ms:.2f} ms/step "
            f"({decode_step_ms / roof_ms:.1f}x off) on {info['device_kind']}",
            flush=True,
        )
    else:
        print(
            f"  decode {decode_step_ms:.2f} ms/step on {info['device_kind']} "
            f"(no HBM roofline for this device; step must move >= "
            f"{(roof['roofline_param_bytes'] + roof['roofline_kv_bytes']) / 1e9:.2f} GB)",
            flush=True,
        )
    return {
        "metric": f"engine_{kv_layout}",
        **info,
        "kv_dtype": eng.kv_dtype,
        "tp": _tp_of(eng),
        "tp_collective": eng.tp_collective,
        "attn_kernel": eng.attn_kernel,
        "device_resident": eng._device_resident,
        "prefill_tokens_per_s": round(prefill_tok_s, 1),
        "prefill_ms_per_step": round(prefill_s / max(prefill_waves, 1) * 1e3, 2),
        "prefill_ms_per_seq": round(prefill_s / max_num_seqs * 1e3, 2),
        "decode_tokens_per_s": round(gen_tokens / decode_s, 1),
        "decode_step_ms": round(decode_step_ms, 2),
        **roof,
        "batch": max_num_seqs,
        "prompt_len": prompt_len,
        "gen_len": gen_len,
    }


def _copy_model_params(cfg, period: int = 16, seed: int = 0):
    """Deterministic 'copy model' for the speculative A/B: identical
    architecture and per-step FLOPs to the random-weight bench model
    (zeroed weights still multiply at full cost), but greedy decode
    provably follows a fixed successor map with short cycles — attention
    and MLP blocks are zeroed so the residual stream carries the token
    embedding to an unembed matrix wired column-for-column to each
    token's successor. This reproduces, deterministically, the
    repetitive-suffix regime (grounded/summarization decoding) that
    prompt-lookup drafting exploits in production."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.llama import init_params

    params = init_params(cfg, jax.random.PRNGKey(seed))
    E = np.asarray(params["embed"], np.float32)
    ids = np.arange(cfg.vocab_size)
    succ = (ids // period) * period + (ids % period + 1) % period  # cycle inside period-blocks
    U = np.zeros((E.shape[1], cfg.vocab_size), np.float32)
    U[:, succ] = E.T  # argmax(rms(E[t]) @ U) = succ(t): |E[t]|^2 dominates cross terms
    zero_layers = jax.tree.map(jnp.zeros_like, params["layers"])
    return {**params, "layers": zero_layers, "unembed": jnp.asarray(U, dtype=params["unembed"].dtype)}


def bench_spec(cfg, prompt_len: int, gen_len: int, max_num_seqs: int = 8, k: int = 4, ngram: int = 3, repeats: int = 1) -> dict:
    """Speculative A/B (--speculative): spec-ngram vs plain decode on a
    repetitive-suffix workload, recording acceptance rate, mean
    tokens/step (per lane per verify round) and the wall-clock speedup.
    The outputs are also asserted token-identical — the bench doubles as
    the oracle check on whatever device it runs on."""
    import numpy as np

    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.sampling import SamplingParams
    from ray_tpu.llm.spec import SpecConfig

    period = 16
    params = _copy_model_params(cfg, period=period)
    rng = np.random.default_rng(0)
    blocks = rng.integers(1, (cfg.vocab_size - 1) // period, size=max_num_seqs)
    # each prompt is >= 2 full cycles of its block's successor chain, so
    # the trailing n-gram always has an earlier occurrence to look up
    prompts = [[int(b) * period + i % period for i in range(prompt_len)] for b in blocks]
    sp = SamplingParams(temperature=0.0, max_tokens=gen_len)

    def run(speculative):
        eng = LLMEngine(
            cfg, params, max_num_seqs=max_num_seqs, max_seq_len=cfg.max_seq_len,
            enable_prefix_caching=False, speculative=speculative,
        )
        eng.generate(prompts, SamplingParams(temperature=0.0, max_tokens=4))  # warm/compile
        best = float("inf")
        toks = deltas = None
        for _ in range(max(repeats, 1)):
            before = eng.spec_stats()
            finals = {}
            ids = [eng.add_request(p, sp) for p in prompts]
            while eng.num_waiting:
                eng.step()
            t0 = time.perf_counter()
            while eng.has_unfinished():
                for o in eng.step():
                    if o.finished:
                        finals[o.request_id] = o.token_ids
            dt = time.perf_counter() - t0
            if dt < best:
                best = dt
                toks = [finals[i] for i in ids]
                after = eng.spec_stats()
                deltas = {
                    key: after[key] - before[key]
                    for key in ("rounds", "lane_rounds", "proposed", "accepted", "emitted")
                } if after else {}
        return best, toks, deltas

    t_plain, toks_plain, _ = run(None)
    t_spec, toks_spec, d = run(SpecConfig(drafter="ngram", k=k, ngram=ngram))
    # the oracle check: a divergent run must fail the bench loudly, not
    # record a speedup measured off a broken stream
    assert toks_spec == toks_plain, "speculative outputs diverged from the plain path"
    decode_toks = max_num_seqs * (gen_len - 1)  # first tokens emit at prefill
    rec = {
        "metric": "engine_spec_ngram",
        **_device_info(),
        "kv_dtype": cfg.dtype,
        "tp": 1,
        "tp_collective": "fp",
        "drafter": "ngram",
        "k": k,
        "ngram": ngram,
        "acceptance_rate": round(d["accepted"] / max(d["proposed"], 1), 3),
        "mean_tokens_per_step": round(d["emitted"] / max(d["lane_rounds"], 1), 2),
        "plain_decode_tokens_per_s": round(decode_toks / t_plain, 1),
        "spec_decode_tokens_per_s": round(decode_toks / t_spec, 1),
        "speedup": round(t_plain / t_spec, 2),
        "outputs_match_plain": bool(toks_spec == toks_plain),
        "workload": f"repetitive-suffix (copy model, period {period})",
        "batch": max_num_seqs,
        "prompt_len": prompt_len,
        "gen_len": gen_len,
    }
    print(
        f"  spec-ngram {rec['mean_tokens_per_step']:.2f} tok/step at acceptance "
        f"{rec['acceptance_rate']:.2f} -> {rec['speedup']:.2f}x decode speedup "
        f"(match={rec['outputs_match_plain']})",
        flush=True,
    )
    return rec


def bench_kv_int8(cfg, prompt_len: int, gen_len: int, max_num_seqs: int = 8, repeats: int = 3) -> dict:
    """Int8-KV A/B against a bf16 cache, both layouts, two claims:

    1. SPEED at equal batch: int8 decode ms/step must stay within 1.1x
       of bf16 (dequant rides the existing f32 attention compute; the
       step moves roughly half the cache bytes).
    2. CAPACITY at equal HBM: the byte budget of the bf16 cache at
       ``max_num_seqs`` holds ``~2*hd/(hd+4)`` times as many int8
       sequences (scales included) — the equal-HBM engine is actually
       built and driven to steady-state decode to prove the extra
       concurrency serves, not just allocates.

    Both engines share prompts/params/greedy sampling; accuracy (exact
    top-1 vs the fp cache) is tier-1's job (tests/test_llm_kv_int8.py),
    this record is the perf gate."""
    import numpy as np

    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.sampling import SamplingParams

    sp = SamplingParams(temperature=0.0, max_tokens=gen_len)

    def run(layout: str, dtype: str, B: int):
        kw = {"kv_layout": "paged", "page_size": 64} if layout == "paged" else {}
        eng = LLMEngine(
            cfg, max_num_seqs=B, max_seq_len=cfg.max_seq_len,
            enable_prefix_caching=False, cache_dtype=dtype, **kw,
        )
        # fresh stream per leg: the bf16 and int8 legs of one A/B must
        # time IDENTICAL prompts (a shared mutated rng would hand each
        # leg a different set)
        rng = np.random.default_rng(0)
        prompts = [list(int(x) for x in rng.integers(1, cfg.vocab_size - 1, size=prompt_len)) for _ in range(B)]
        eng.generate(prompts, SamplingParams(temperature=0.0, max_tokens=4))  # warm/compile
        best = float("inf")
        for _ in range(max(repeats, 1)):
            for p in prompts:
                eng.add_request(p, sp)
            while eng.num_waiting:
                eng.step()
            t0 = time.perf_counter()
            steps = 0
            while eng.has_unfinished():
                eng.step()
                steps += 1
            best = min(best, (time.perf_counter() - t0) / max(steps, 1))
        return best * 1e3, eng.kv_cache_stats()

    layouts = {}
    for layout in ("slots", "paged"):
        bf_ms, bf_st = run(layout, "bfloat16", max_num_seqs)
        q8_ms, q8_st = run(layout, "int8", max_num_seqs)
        # equal-HBM concurrency: the bf16 allocation's bytes, refilled
        # with int8 sequences (per-seq bytes shrink by bytes_per_token's
        # ratio; engine sizing is proportional, so allocated bytes stay
        # <= the bf16 budget by construction — recorded to prove it)
        b_equal = int(max_num_seqs * bf_st["bytes_per_token"] / q8_st["bytes_per_token"])
        eq_ms, eq_st = run(layout, "int8", b_equal)
        assert eq_st["allocated_bytes"] <= bf_st["allocated_bytes"], (
            f"{layout}: equal-HBM int8 engine exceeds the bf16 byte budget "
            f"({eq_st['allocated_bytes']} > {bf_st['allocated_bytes']})"
        )
        layouts[layout] = {
            "bf16_decode_step_ms": round(bf_ms, 2),
            "int8_decode_step_ms": round(q8_ms, 2),
            "int8_step_ratio": round(q8_ms / bf_ms, 3),
            "bytes_per_token_bf16": bf_st["bytes_per_token"],
            "bytes_per_token_int8": q8_st["bytes_per_token"],
            "cache_bytes_bf16": bf_st["allocated_bytes"],
            "cache_bytes_int8_equal_hbm": eq_st["allocated_bytes"],
            "max_seqs_bf16": max_num_seqs,
            "max_seqs_int8_equal_hbm": b_equal,
            "capacity_ratio": round(b_equal / max_num_seqs, 3),
            "int8_equal_hbm_decode_step_ms": round(eq_ms, 2),
            "bf16_decode_tokens_per_s": round(max_num_seqs / bf_ms * 1e3, 1),
            "int8_equal_hbm_decode_tokens_per_s": round(b_equal / eq_ms * 1e3, 1),
        }
        print(
            f"  {layout}: bf16 {bf_ms:.2f} ms/step -> int8 {q8_ms:.2f} ms/step "
            f"({q8_ms / bf_ms:.2f}x) at batch {max_num_seqs}; equal-HBM capacity "
            f"{max_num_seqs} -> {b_equal} seqs ({b_equal / max_num_seqs:.2f}x) at "
            f"{eq_ms:.2f} ms/step",
            flush=True,
        )
    return {
        "metric": "engine_kv_int8_ab",
        **_device_info(),
        "kv_dtype": "int8",
        "tp": 1,
        "tp_collective": "fp",
        "baseline_dtype": "bfloat16",
        "layouts": layouts,
        "batch": max_num_seqs,
        "prompt_len": prompt_len,
        "gen_len": gen_len,
    }


def bench_attn_kernel(cfg, prompt_len: int, gen_len: int, max_num_seqs: int = 4, repeats: int = 3) -> dict:
    """Paged-attention kernel A/B (ROADMAP item 4): attn_kernel="xla"
    (page gather -> dequant -> attend, materializing every gathered page)
    vs "pallas" (llm/pallas/paged_attn.py: one HBM-streaming program),
    fp and int8 pools.

    On a TPU-less host the kernel runs in INTERPRET mode, so the timing
    legs prove presence (the kernel compiled and served every step), the
    greedy-identity flags prove correctness against the XLA oracle, and
    the PERF claim is the v5e roofline pair: bytes each impl must move
    per decode step, with the gather-materialization traffic the kernel
    deletes called out (full math in bench_artifacts/README.md)."""
    import numpy as np

    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.kv_quant import bytes_per_token
    from ray_tpu.llm.sampling import SamplingParams

    page = 64
    B = max_num_seqs
    gen = min(gen_len, 32)
    sp = SamplingParams(temperature=0.0, max_tokens=gen)
    dtypes = {}
    params = None
    interpreted = _device_info()["device"] != "tpu"
    for dtype in (cfg.dtype, "int8"):
        legs, outs, resolved = {}, {}, {}
        for ak in ("xla", "pallas"):
            eng = LLMEngine(
                cfg, params, max_num_seqs=B, max_seq_len=cfg.max_seq_len,
                kv_layout="paged", page_size=page, enable_prefix_caching=False,
                cache_dtype=dtype, attn_kernel=ak,
            )
            params = eng.params  # every leg decodes with the SAME weights
            # an unservable kernel request raises at construction
            # (AttnKernelUnavailableError), so this is always == ak
            resolved[ak] = eng.attn_kernel
            rng = np.random.default_rng(0)
            prompts = [
                list(int(x) for x in rng.integers(1, cfg.vocab_size - 1, size=prompt_len))
                for _ in range(B)
            ]
            outs[ak] = [r.token_ids for r in eng.generate(prompts, sp)]
            best = float("inf")
            for _ in range(max(repeats, 1)):
                for p in prompts:
                    eng.add_request(p, sp)
                while eng.num_waiting:
                    eng.step()
                t0 = time.perf_counter()
                steps = 0
                while eng.has_unfinished():
                    eng.step()
                    steps += 1
                best = min(best, (time.perf_counter() - t0) / max(steps, 1))
            legs[ak] = round(best * 1e3, 2)
        # v5e roofline: what each impl MUST stream per decode step at the
        # steady-state mean occupancy. Both read the occupied pool pages
        # (per-token bytes incl. int8 scales); the XLA path additionally
        # materializes every gathered page as an f32 copy at the
        # attention compute dtype — one write + one re-read of K and V
        # over all layers (the dequant pass int8 pays is the same copy).
        mean_len = prompt_len + gen / 2
        s_pad = -(-mean_len // page) * page
        L, kvh, hd = cfg.num_layers, cfg.num_kv_heads, cfg.hd
        pool_bytes = int(B * s_pad * bytes_per_token(L, kvh, hd, dtype))
        copy_bytes = int(2 * 2 * L * B * s_pad * kvh * hd * 4)  # (K+V) x (write+reread) x f32
        bw = _HBM_GBPS["TPU v5e"] * 1e9
        dtypes[str(dtype)] = {
            "outputs_match_xla": outs["pallas"] == outs["xla"],
            "pallas_resolved_kernel": resolved["pallas"],
            "xla_decode_step_ms": legs["xla"],
            "pallas_decode_step_ms": legs["pallas"],
            "pallas_interpret_mode": interpreted and resolved["pallas"] == "pallas",
            "v5e_attn_bytes_per_step_xla": pool_bytes + copy_bytes,
            "v5e_attn_bytes_per_step_pallas": pool_bytes,
            "v5e_materialization_bytes_eliminated": copy_bytes,
            "v5e_attn_ms_per_step_xla": round((pool_bytes + copy_bytes) / bw * 1e3, 4),
            "v5e_attn_ms_per_step_pallas": round(pool_bytes / bw * 1e3, 4),
        }
        d = dtypes[str(dtype)]
        print(
            f"  {dtype}: outputs_match={d['outputs_match_xla']} xla {legs['xla']} ms/step vs "
            f"pallas {legs['pallas']} ms/step ({'interpret' if interpreted else 'compiled'}); "
            f"v5e attn bytes/step {d['v5e_attn_bytes_per_step_xla'] / 1e6:.1f} -> "
            f"{d['v5e_attn_bytes_per_step_pallas'] / 1e6:.1f} MB "
            f"({d['v5e_materialization_bytes_eliminated'] / 1e6:.1f} MB materialization deleted)",
            flush=True,
        )
    return {
        "metric": "engine_attn_kernel_ab",
        **_device_info(),
        "kv_dtype": "both",
        "tp": 1,
        "tp_collective": "fp",
        "attn_kernel": "ab",  # provenance: this record IS the xla-vs-pallas A/B
        "dtypes": dtypes,
        "batch": B,
        "prompt_len": prompt_len,
        "gen_len": gen,
        "page_size": page,
    }


def bench_tp(cfg, prompt_len: int, gen_len: int, max_num_seqs: int = 8, repeats: int = 1) -> dict:
    """Tensor-parallel A/B (ROADMAP item 1's bench ask): tp=1 vs tp=2
    (explicit shard_map psum) vs tp=2 + int8 quantized all-reduce, slot
    layout, recording per-mode decode ms/step, greedy-output equivalence
    (tp=2 fp must match tp=1 EXACTLY; int8 must keep exact top-1 on the
    decisive-logits copy-model workload), and the bytes-on-the-wire
    evidence: a jaxpr-level accounting of every collective's operand
    dtype/bytes per fused step plus the v5e ICI roofline those bytes
    imply. On CPU the wall-clock columns measure virtual devices sharing
    one socket (tp=2 is SLOWER there — more programs, same silicon); the
    wire-byte columns are platform-independent and are the gate."""
    import jax
    import numpy as np

    from ray_tpu.collective.ici import collective_wire_report
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.model_runner import _sharded_fused_slots
    from ray_tpu.llm.sampling import SamplingParams
    from ray_tpu.parallel.mesh import create_mesh

    if len(jax.devices()) < 2:
        return {"metric": "engine_tp_ab", **_device_info(), "skipped": "needs >= 2 devices"}
    mesh = create_mesh(tp=2, devices=jax.devices()[:2])
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(1, cfg.vocab_size - 1, size=prompt_len)) for _ in range(max_num_seqs)]
    sp = SamplingParams(temperature=0.0, max_tokens=gen_len)

    def run(mesh_, coll):
        eng = LLMEngine(
            cfg, max_num_seqs=max_num_seqs, max_seq_len=cfg.max_seq_len,
            enable_prefix_caching=False, mesh=mesh_, tp_collective=coll, seed=0,
        )
        eng.generate(prompts, SamplingParams(temperature=0.0, max_tokens=4))  # warm/compile
        decode_s, steps, toks = float("inf"), 1, None
        for _ in range(max(repeats, 1)):
            ids = [eng.add_request(p, sp) for p in prompts]
            while eng.num_waiting:
                eng.step()
            t0 = time.perf_counter()
            n_steps, finals = 0, {}
            while eng.has_unfinished():
                for o in eng.step():
                    if o.finished:
                        finals[o.request_id] = o.token_ids
                n_steps += 1
            d_s = time.perf_counter() - t0
            if d_s / max(n_steps, 1) < decode_s / max(steps, 1):
                decode_s, steps = d_s, n_steps
            toks = [finals[i] for i in ids]
        return toks, decode_s / max(steps, 1) * 1e3, eng

    toks1, ms1, _ = run(None, "fp")
    toks2, ms2, eng2 = run(mesh, "fp")
    toksq, msq, engq = run(mesh, "int8")

    # exact top-1 for the int8 collective is gated on a DECISIVE-logits
    # workload (the copy model bench_spec uses): random-weight logits are
    # near-uniform, where any rounding flips a meaningless argmax
    cp = _copy_model_params(cfg)
    cprompt = [[1, 2, 3, 4, 5, 6, 7, 8]] * 2
    csp = SamplingParams(temperature=0.0, max_tokens=min(gen_len, 24))
    cp_base = [o.token_ids for o in LLMEngine(
        cfg, cp, max_num_seqs=2, max_seq_len=cfg.max_seq_len, enable_prefix_caching=False,
    ).generate(cprompt, csp)]
    cp_q = [o.token_ids for o in LLMEngine(
        cfg, cp, max_num_seqs=2, max_seq_len=cfg.max_seq_len, enable_prefix_caching=False,
        mesh=mesh, tp_collective="int8",
    ).generate(cprompt, csp)]

    # bytes-on-the-wire: trace the two fused programs and account every
    # collective operand (scan-aware, so per-layer psums count L times)
    sds = lambda t: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t)  # noqa: E731
    args = (sds(eng2.params), sds(eng2.cache), sds(eng2._dtokens), sds(eng2._dkeys),
            sds(eng2._dtemps), sds(eng2._dtopk), sds(eng2._dtopp))
    wire = {}
    for coll in ("fp", "int8"):
        rep = collective_wire_report(
            jax.make_jaxpr(_sharded_fused_slots(cfg, mesh, coll, eng2.kv_quant))(*args), axis_size=2
        )
        layer = [op for op in rep["ops"] if op["count"] > 1]
        wire[coll] = {
            "bytes_per_step_by_dtype": rep["bytes_by_dtype"],
            "bytes_per_step_total": rep["total_bytes"],
            "per_layer_allreduce_bytes": int(sum(op["wire_bytes"] for op in layer)),
            "per_layer_dtypes": sorted({op["dtype"] for op in layer}),
        }
    ratio_layer = wire["int8"]["per_layer_allreduce_bytes"] / max(wire["fp"]["per_layer_allreduce_bytes"], 1)
    # ICI roofline: what those bytes cost on a real chip (v5e default when
    # the bench ran TPU-less — the CPU cannot show the ICI wall-clock win)
    info = _device_info()
    ici = next((v for k, v in _ICI_GBPS.items() if info["device_kind"].startswith(k)), _ICI_GBPS["TPU v5e"])
    roof = {
        "ici_gbps_per_link_oneway": ici,
        "assumed_device": info["device_kind"] if info["device"] == "tpu" else "TPU v5e (TPU-less run)",
        "fp_allreduce_us_per_step": round(wire["fp"]["bytes_per_step_total"] / (ici * 1e9) * 1e6, 2),
        "int8_allreduce_us_per_step": round(wire["int8"]["bytes_per_step_total"] / (ici * 1e9) * 1e6, 2),
    }
    print(
        f"  tp=1 {ms1:.2f} ms/step | tp=2 fp {ms2:.2f} | tp=2 int8c {msq:.2f}; "
        f"per-layer all-reduce bytes int8/fp = {ratio_layer:.2f} "
        f"({wire['int8']['per_layer_allreduce_bytes']}/{wire['fp']['per_layer_allreduce_bytes']}); "
        f"v5e ICI roofline {roof['fp_allreduce_us_per_step']} -> {roof['int8_allreduce_us_per_step']} us/step",
        flush=True,
    )
    return {
        "metric": "engine_tp_ab",
        **info,
        "kv_dtype": eng2.kv_dtype,
        "tp": 2,
        "tp_collective": "int8",  # the mode under test; per-mode rows below
        "modes": {
            "tp1": {"decode_step_ms": round(ms1, 2), "tp": 1, "tp_collective": "fp"},
            "tp2_fp": {
                "decode_step_ms": round(ms2, 2), "tp": 2, "tp_collective": "fp",
                "outputs_match_tp1": toks2 == toks1,
            },
            "tp2_int8": {
                "decode_step_ms": round(msq, 2), "tp": 2, "tp_collective": "int8",
                "copy_model_top1_match": cp_q == cp_base,
            },
        },
        "wire": wire,
        "per_layer_allreduce_bytes_ratio": round(ratio_layer, 3),
        "ici_roofline": roof,
        "batch": max_num_seqs,
        "prompt_len": prompt_len,
        "gen_len": gen_len,
    }


def _pct(xs, q: float):
    if not xs:
        return None
    xs = sorted(xs)
    return round(xs[min(len(xs) - 1, int(round(q * (len(xs) - 1))))] * 1e3, 2)


def _dist(ttfts, itls) -> dict:
    return {
        "ttft_ms_p50": _pct(ttfts, 0.50),
        "ttft_ms_p99": _pct(ttfts, 0.99),
        "itl_ms_p50": _pct(itls, 0.50),
        "itl_ms_p99": _pct(itls, 0.99),
        "tokens": len(itls) + len(ttfts),
    }


def _tel_latencies(eng, rids, short_ids):
    """TTFT/ITL samples (seconds) from the engine's flight recorder
    (llm/telemetry.py) for the benchmarked requests: the SAME numbers a
    live /metrics scrape aggregates, so the committed bench and the
    production dashboards can never drift apart silently. ITL is taken
    over the decode-heavy streams only (mirrors the stopwatch path)."""
    recs = eng.telemetry().get("requests", [])
    ttfts = [r["ttft_s"] for r in recs if r["request_id"] in rids and r["ttft_s"] is not None]
    itls = [x for r in recs if r["request_id"] in short_ids for x in r["itl_s"]]
    return ttfts, itls


def bench_disagg(cfg, prompt_len: int, gen_len: int, max_num_seqs: int = 4, n_long: int = 6) -> dict:
    """Disaggregated prefill/decode A/B on a MIXED workload: latency-
    sensitive decode streams with long-prompt prefills arriving mid-
    flight. Records time-to-first-token and inter-token latency as
    SEPARATE distributions (p50/p99) for both modes:

    - single engine: one engine interleaves everything — a long prefill
      admission stalls every in-flight decode lane for a whole prefill
      forward (the committed bench's ~44 ms vs ~7 ms gap);
    - disagg split: a prefill engine on its own thread feeds a decode
      engine through the full handoff path (extract -> codec round-trip
      -> fused scatter-in), so decode admissions cost one scatter
      instead of a prefill forward.

    ITL is measured over the decode-heavy streams only (the lanes the
    split protects); TTFT over every request. The same arrival cadence
    (in decode steps) drives both modes."""
    import queue as _queue
    import threading as _threading

    import numpy as np

    from ray_tpu.llm.disagg import decode_handoff, encode_handoff
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.sampling import SamplingParams

    short_len = max(16, prompt_len // 8)
    # the stall source must actually be LONG: near the model's context
    # limit, several prefill buckets above the decode streams' prompts
    long_len = min(cfg.max_seq_len - 16, max(4 * prompt_len, 256))
    short_sp = SamplingParams(temperature=0.0, max_tokens=gen_len)
    long_sp = SamplingParams(temperature=0.0, max_tokens=4)
    rng = np.random.default_rng(0)
    shorts = [list(int(x) for x in rng.integers(1, cfg.vocab_size - 1, size=short_len)) for _ in range(max_num_seqs - 1)]
    longs = [list(int(x) for x in rng.integers(1, cfg.vocab_size - 1, size=long_len)) for _ in range(n_long)]
    inject_every = max(4, gen_len // (n_long + 1))  # decode steps between long arrivals

    def _engine():
        return LLMEngine(cfg, max_num_seqs=max_num_seqs, max_seq_len=cfg.max_seq_len, enable_prefix_caching=False)

    def _warm(eng):
        # compile BOTH buckets + the fused decode outside the timed region
        eng.generate(shorts[0], SamplingParams(temperature=0.0, max_tokens=3))
        eng.generate(longs[0], SamplingParams(temperature=0.0, max_tokens=3))

    def _record(outs, now, submit, last_tok, short_ids, ttfts, itls):
        for o in outs:
            rid = o.request_id
            if rid not in submit or not o.new_token_ids:
                continue
            if rid not in last_tok:
                ttfts.append(now - submit[rid])
            elif rid in short_ids:
                itls.append(now - last_tok[rid])
            last_tok[rid] = now

    def run_single():
        eng = _engine()
        _warm(eng)
        ttfts, itls, submit, last_tok, short_ids = [], [], {}, {}, set()
        for p in shorts:
            rid = eng.add_request(p, short_sp)
            submit[rid] = time.perf_counter()
            short_ids.add(rid)
        li = steps = 0
        while eng.has_unfinished() or li < len(longs):
            if li < len(longs) and steps >= (li + 1) * inject_every:
                rid = eng.add_request(longs[li], long_sp)
                submit[rid] = time.perf_counter()
                li += 1
            outs = eng.step()
            _record(outs, time.perf_counter(), submit, last_tok, short_ids, ttfts, itls)
            steps += 1
        return ttfts, itls, _tel_latencies(eng, set(submit), short_ids)

    def run_disagg():
        pre, dec = _engine(), _engine()
        _warm(pre)
        _warm(dec)
        # warm the handoff path itself (extract + codec + scatter-in
        # programs for both buckets)
        for p in (shorts[0], longs[0]):
            dec.add_prefilled(decode_handoff(encode_handoff(pre.prefill_handoff(p))), SamplingParams(temperature=0.0, max_tokens=2))
        while dec.has_unfinished():
            dec.step()
        in_q: _queue.Queue = _queue.Queue()
        ready: _queue.Queue = _queue.Queue()

        def prefill_loop():
            try:
                while True:
                    item = in_q.get()
                    if item is None:
                        return
                    kind, prompt, arrived = item
                    # the arrival wall stamp rides the handoff so the
                    # decode engine's telemetry TTFT spans queue + prefill
                    # + ship, matching what the bench stopwatch measures
                    kv = decode_handoff(encode_handoff(pre.prefill_handoff(prompt, submitted_at=arrived)))
                    ready.put((kind, kv))
            except BaseException as e:  # noqa: BLE001
                # surface through the ready queue: the decode loop must
                # fail loudly, never spin forever waiting for handoffs
                ready.put(("error", e))

        th = _threading.Thread(target=prefill_loop, daemon=True, name="bench-prefill")
        th.start()
        from collections import deque as _deque

        ttfts, itls, submit, last_tok, short_ids = [], [], {}, {}, set()
        # the prefill thread preserves arrival order per kind: FIFO submit
        # times pair back up at decode admission
        pending_t = {"short": _deque(), "long": _deque()}
        for p in shorts:
            pending_t["short"].append(time.perf_counter())
            in_q.put(("short", p, time.time()))
        li = steps = done = 0
        n_total = len(shorts) + len(longs)
        while done < n_total or li < len(longs):
            # cadence in decode steps; an idle decode engine (shorts done
            # early) flushes the remaining arrivals immediately
            if li < len(longs) and (steps >= (li + 1) * inject_every or not dec.has_unfinished()):
                pending_t["long"].append(time.perf_counter())
                in_q.put(("long", longs[li], time.time()))
                li += 1
            try:
                kind, kv = ready.get_nowait()
                if kind == "error":
                    raise RuntimeError("disagg bench prefill thread died") from kv
                rid = dec.add_prefilled(kv, short_sp if kind == "short" else long_sp)
                submit[rid] = pending_t[kind].popleft()
                if kind == "short":
                    short_ids.add(rid)
            except _queue.Empty:
                pass
            if not dec.has_unfinished():
                time.sleep(0.0005)  # idle: let the prefill thread run
                continue
            outs = dec.step()
            now = time.perf_counter()
            _record(outs, now, submit, last_tok, short_ids, ttfts, itls)
            done += sum(1 for o in outs if o.finished and o.request_id in submit)
            steps += 1
        in_q.put(None)
        th.join(timeout=10)
        return ttfts, itls, _tel_latencies(dec, set(submit), short_ids)

    s_ttft, s_itl, s_tel = run_single()
    d_ttft, d_itl, d_tel = run_disagg()
    # committed numbers come from the ENGINE'S FLIGHT RECORDER (the same
    # samples the live rt_llm_ttft_s/rt_llm_itl_s series aggregate); the
    # bench's own stopwatch survives only as a cross-check so the two
    # measurement paths can never drift apart silently
    single, split = _dist(*s_tel), _dist(*d_tel)
    single_sw, split_sw = _dist(s_ttft, s_itl), _dist(d_ttft, d_itl)
    # agreement gate over BOTH modes on the p50s (p99 is a ~single-sample
    # max statistic that the two clocks punctuate differently around long
    # stalls; p50 catches systematic drift — wrong units, a mis-stamped
    # handoff submitted_at, double-counted ITLs). Telemetry stamps a token
    # when the consumer can actually see it (out_queue.put at drain); the
    # stopwatch stamps at step return — expect telemetry <= stopwatch by
    # up to one step of skew, inside this tolerance.
    for mode, sw_d, tel_d in (("single_engine", single_sw, single), ("disagg_split", split_sw, split)):
        for key in ("ttft_ms_p50", "itl_ms_p50"):
            sw, tel = sw_d[key], tel_d[key]
            assert sw is not None and tel is not None and abs(sw - tel) <= max(0.5 * max(sw, tel), 25.0), (
                f"bench stopwatch and engine telemetry disagree on {mode} {key}: "
                f"stopwatch {sw} ms vs telemetry {tel} ms"
            )
    single["telemetry"] = split["telemetry"] = True  # provenance
    ratio = (single["itl_ms_p99"] / split["itl_ms_p99"]) if split["itl_ms_p99"] else None
    rec = {
        "metric": "engine_disagg_ab",
        **_device_info(),
        "kv_dtype": cfg.dtype,
        "tp": 1,
        "tp_collective": "fp",
        "disagg": True,  # provenance: this record came from the split-path A/B
        "workload": (
            f"{len(shorts)} decode streams (prompt {short_len}, gen {gen_len}) + "
            f"{n_long} long-prefill arrivals (prompt {long_len}) every {inject_every} decode steps"
        ),
        "single_engine": single,
        "disagg_split": split,
        "stopwatch_crosscheck": {"single_engine": single_sw, "disagg_split": split_sw},
        "decode_itl_p99_speedup": round(ratio, 2) if ratio else None,
        "batch": max_num_seqs,
    }
    print(
        f"  single ITL p50/p99 {single['itl_ms_p50']}/{single['itl_ms_p99']} ms, "
        f"disagg ITL p50/p99 {split['itl_ms_p50']}/{split['itl_ms_p99']} ms "
        f"({rec['decode_itl_p99_speedup']}x p99), TTFT p50 {single['ttft_ms_p50']} -> {split['ttft_ms_p50']} ms",
        flush=True,
    )
    return rec


def bench_kvplane(cfg, prompt_len: int, gen_len: int, n_replicas: int = 2,
                  n_prefixes: int = 4, reqs_per_prefix: int = 4) -> dict:
    """Cluster KV plane A/B (llm/kvplane/): shared-system-prompt traffic
    over a 2-replica deployment, cache-aware routing + cluster prefix
    reuse vs the replica-local baseline.

    Workload: ``n_prefixes`` distinct long system prompts, each hit by
    ``reqs_per_prefix`` CONCURRENT requests with short unique suffixes —
    the millions-of-users shape where every request repeats a long shared
    prefix. Baseline: the same engines, prefix caching ON but replica-
    LOCAL, round-robin routing (each replica pays its own prefill of
    every prefix). Plane: shared PrefixIndex + cache-aware router —
    shared-prefix traffic lands on the holder (local tier), load spills
    fetch the block over the object plane instead of re-prefilling
    (remote tier).

    TTFT comes from each ENGINE'S FLIGHT RECORDER (telemetry-sourced,
    the same samples the live rt_llm_ttft_s series aggregates); the
    record carries cluster hit-rate and per-tier hit counts."""
    import queue as _queue
    import threading as _threading

    import numpy as np

    import ray_tpu as rt
    from ray_tpu.llm.kvplane import CacheAwareRouter, PrefixIndex
    from ray_tpu.llm.sampling import SamplingParams
    from ray_tpu.serve.llm import KVPlaneServer, LLMConfig, LLMServer

    prefix_len = max(128, prompt_len)  # the stall source must be LONG
    suffix_len, gen = 8, min(gen_len, 8)
    max_seq = 1 << (prefix_len + suffix_len + gen + 16 - 1).bit_length()
    rng = np.random.default_rng(3)
    prefixes = [
        [int(x) for x in rng.integers(1, cfg.vocab_size - 1, size=prefix_len)]
        for _ in range(n_prefixes)
    ]
    prompts = [
        [p + [int(x) for x in rng.integers(1, cfg.vocab_size - 1, size=suffix_len)]
         for _ in range(reqs_per_prefix)]
        for p in prefixes
    ]
    sp = {"max_tokens": gen, "temperature": 0.0}

    def _servers(plane_index):
        """Replica surfaces, not bare engines: concurrent callers batch
        through each replica's stepping thread exactly as under Serve
        (KVPlaneServer joins the cluster plane; LLMServer = the
        replica-local baseline)."""
        llm_cfg = lambda: LLMConfig(  # noqa: E731
            model_config=cfg, prewarm=False,
            engine_kwargs={"max_num_seqs": reqs_per_prefix + 1, "max_seq_len": max_seq},
        )
        servers = {}
        for i in range(n_replicas):
            rid = f"r{i}"
            if plane_index is not None:
                # publish-on-store (min_hits=1): this A/B measures the
                # routing + reuse machinery on the SAME traffic shape as
                # the committed PR-10 record; the default min_hits=2
                # publication policy is exercised (and tested) separately
                servers[rid] = KVPlaneServer(llm_cfg(), plane_index, rid, publish_min_hits=1)
            else:
                servers[rid] = LLMServer(llm_cfg())
        # compile every measured program outside the timed region: both
        # prefill buckets AND the prefix-hit admission (insert + suffix
        # extend at the measured suffix bucket). Warm prompts are DISTINCT
        # per replica and one token longer than the measured ones, so
        # they can never register as cluster hits or pollute the
        # flight-recorder TTFT filter below.
        for srv in servers.values():
            warm = [int(x) for x in rng.integers(1, cfg.vocab_size - 1, size=prefix_len + suffix_len + 1)]
            srv.generate(warm, {"max_tokens": 2, "temperature": 0.0}, timeout_s=600.0)
            srv.generate(warm[:8], {"max_tokens": 2, "temperature": 0.0}, timeout_s=600.0)
            # the hit warm must reproduce the MEASURED hit shape: matched
            # boundary at prefix_len, so the suffix extend compiles at the
            # same small bucket the followers use
            hitter = warm[:prefix_len] + [int(x) for x in rng.integers(1, cfg.vocab_size - 1, size=suffix_len + 1)]
            srv.generate(hitter, {"max_tokens": 2, "temperature": 0.0}, timeout_s=600.0)
        # post-warm stat baseline: _drive reports DELTAS, so the warm
        # phase's own hits never inflate the measured hit-rate
        return servers, {rid: srv.engine.prefix_cache_stats() for rid, srv in servers.items()}

    def _drive(servers, s0, router_generate):
        """Per prefix: ONE sequential leader (somebody must prefill and
        publish the shared prompt), then the remaining requests
        CONCURRENTLY — the follower traffic cache-aware routing exists
        for, with enough simultaneous load to spill some of it off the
        holder (the remote tier)."""
        errs: _queue.Queue = _queue.Queue()

        def one(prompt):
            try:
                router_generate(prompt, sp)
            except BaseException as e:  # noqa: BLE001
                errs.put(repr(e))

        for group in prompts:
            one(group[0])  # leader: the cold prefill that seeds the prefix
            threads = [_threading.Thread(target=one, args=(p,)) for p in group[1:]]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        if not errs.empty():
            raise RuntimeError(f"bench request failed: {errs.get()}")
        ttfts = []
        for srv in servers.values():
            for rec in srv.engine.telemetry().get("requests", []):
                # measured requests only (warmups are one token longer)
                if rec["prompt_tokens"] == prefix_len + suffix_len and rec["ttft_s"] is not None:
                    ttfts.append(rec["ttft_s"])
        n_req = n_prefixes * reqs_per_prefix
        stats = [srv.engine.prefix_cache_stats() for srv in servers.values()]
        base = list(s0.values())
        local = sum(s["local"]["hits"] - b["local"]["hits"] for s, b in zip(stats, base))
        remote = sum(
            s.get("remote", {}).get("hits", 0) - b.get("remote", {}).get("hits", 0)
            for s, b in zip(stats, base)
        )
        fetched = sum(
            s.get("remote", {}).get("fetched_bytes", 0) - b.get("remote", {}).get("fetched_bytes", 0)
            for s, b in zip(stats, base)
        )
        return {
            "ttft_ms_p50": _pct(ttfts, 0.50),
            "ttft_ms_p99": _pct(ttfts, 0.99),
            "requests": n_req,
            "local_hits": local,
            "remote_hits": remote,
            "cluster_hit_rate": round((local + remote) / n_req, 3),
            "remote_fetched_mb": round(fetched / 2**20, 2),
            "telemetry": True,  # provenance: flight-recorder-sourced
        }

    rt.init(num_cpus=2)
    base_servers = plane_servers = {}
    try:
        # baseline: replica-local caches, round-robin routing
        base_servers, base_s0 = _servers(None)
        rr = itertools.count()

        def rr_generate(prompt, sp_):
            rid = f"r{next(rr) % n_replicas}"
            return base_servers[rid].generate(prompt, sp_, timeout_s=600.0)

        base = _drive(base_servers, base_s0, rr_generate)

        # cluster plane: shared index + cache-aware router
        index = PrefixIndex()
        plane_servers, plane_s0 = _servers(index)

        def submit(rid, prompt, sp_):
            return plane_servers[rid].generate(prompt, sp_, timeout_s=600.0)

        # block derived from the replicas' own prefix cache: a mismatched
        # hardcode would hash different boundaries than they publish and
        # silently report an all-cold A/B
        blk = next(iter(plane_servers.values())).engine._prefix_cache.block
        router = CacheAwareRouter(index, submit, list(plane_servers), block=blk, load_weight=0.5)
        plane = _drive(plane_servers, plane_s0, router.generate)
        plane["router"] = {
            k: router.stats()[k]
            for k in ("routed_to_holder", "routed_off_holder", "cold", "matched_tokens")
        }
    finally:
        # both pools share replica ids — stop them individually (a merged
        # dict would silently drop the baseline pool's steppers)
        for srv in list(base_servers.values()) + list(plane_servers.values()):
            srv._stopped = True
        rt.shutdown()
    speed = (base["ttft_ms_p50"] / plane["ttft_ms_p50"]) if plane["ttft_ms_p50"] else None
    rec = {
        "metric": "engine_kvplane_ab",
        **_device_info(),
        "kv_dtype": cfg.dtype,
        "tp": 1,
        "tp_collective": "fp",
        "kvplane": True,  # provenance: cluster-plane A/B
        "workload": (
            f"{n_prefixes} shared system prompts (len {prefix_len}) x {reqs_per_prefix} concurrent "
            f"requests (suffix {suffix_len}, gen {gen}) over {n_replicas} replicas"
        ),
        "replica_local_baseline": base,
        "kvplane_cache_aware": plane,
        "ttft_p50_speedup": round(speed, 2) if speed else None,
    }
    print(
        f"  baseline hit-rate {base['cluster_hit_rate']} TTFT p50/p99 "
        f"{base['ttft_ms_p50']}/{base['ttft_ms_p99']} ms -> kvplane hit-rate "
        f"{plane['cluster_hit_rate']} ({plane['local_hits']}L+{plane['remote_hits']}R) TTFT p50/p99 "
        f"{plane['ttft_ms_p50']}/{plane['ttft_ms_p99']} ms ({rec['ttft_p50_speedup']}x p50)",
        flush=True,
    )
    return rec


def bench_kvplane_async(cfg, prompt_len: int, gen_len: int, n_prefixes: int = 4,
                        fetch_delay_ms: float = 25.0) -> dict:
    """Async vs sync-under-lock cluster-tier fetch A/B (ROADMAP item 3a).

    A VICTIM request decodes a long stream on engine B while shared-
    prefix followers arrive whose blocks live on engine A. SYNC arm (the
    pre-async behavior, reconstructed by resolving the fetch inline at
    admission): every fetch rides the engine lock, so the victim's
    decode stalls behind each transfer — its ITL tail IS the fetch cost.
    ASYNC arm (the shipped path): admission launches the fetch on the
    engine's worker and keeps stepping; the victim never notices.

    A fixed delay is added to BOTH arms' client fetch, standing in for
    the multi-MB cross-host transfer a real fleet pays (tiny CPU blocks
    fetch in microseconds — the A/B measures WHERE the cost lands, not
    how big it is). The delay is ``fetch_delay_ms`` floored at 2.5x the
    measured decode step wall, so a fetch span always outlasts a step:
    the overlap evidence counts step records whose end timestamp falls
    INSIDE a fetch span, which only a step running CONCURRENTLY with
    the fetch can produce (sync is 0 by construction — the fetch blocks
    the only stepping thread, and the blocked step ends after the span
    closes). Victim ITL and follower TTFT come from the flight
    recorder."""
    import numpy as np

    import ray_tpu as rt
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.kvplane import KVPlaneClient, PrefixIndex
    from ray_tpu.llm.sampling import SamplingParams

    prefix_len = max(128, prompt_len)
    suffix_len, gen = 8, min(gen_len, 8)
    max_seq = 1 << (prefix_len + suffix_len + gen + 16 - 1).bit_length()
    rng = np.random.default_rng(11)
    # +1 warm prefix: each arm serves it once before the victim starts, so
    # the fetch+scatter+suffix-prefill programs compile OUTSIDE the
    # measured phase (a compile under the lock would swamp both arms' ITL)
    prefixes = [
        [int(x) for x in rng.integers(1, cfg.vocab_size - 1, size=prefix_len)]
        for _ in range(n_prefixes + 1)
    ]
    warm_prefix, prefixes = prefixes[0], prefixes[1:]
    victim_prompt = [int(x) for x in rng.integers(1, cfg.vocab_size - 1, size=suffix_len)]
    victim_sp = SamplingParams(max_tokens=48, temperature=0.0)
    sp = SamplingParams(max_tokens=gen, temperature=0.0)

    def _sfx():
        return [int(x) for x in rng.integers(1, cfg.vocab_size - 1, size=suffix_len)]

    rt.init(num_cpus=2)
    try:
        index = PrefixIndex()
        a = LLMEngine(cfg, kv_plane=KVPlaneClient(index, "A", publish_min_hits=1),
                      max_num_seqs=2, max_seq_len=max_seq)
        for p in [warm_prefix] + prefixes:
            a.generate(p + _sfx(), sp)  # A holds + registered every prefix
        # size the simulated transfer off the model's actual decode step
        # wall (A's flight recorder) — the span must outlast a step for
        # the end-timestamp overlap evidence to resolve at any scale
        walls = sorted(
            s["wall_ms"] for s in a._tel.recorder.snapshot()["steps"]
            if s.get("phase") == "decode"
        )
        step_wall_ms = walls[len(walls) // 2] if walls else 0.0
        delay_s = max(fetch_delay_ms, 2.5 * step_wall_ms) / 1e3

        def _arm(async_mode: bool) -> dict:
            cb = KVPlaneClient(index, f"B-{'async' if async_mode else 'sync'}",
                               publish_min_hits=1)
            orig_fetch = cb.fetch

            def slow_fetch(hit):
                time.sleep(delay_s)
                return orig_fetch(hit)

            cb.fetch = slow_fetch
            b = LLMEngine(cfg, kv_plane=cb, max_num_seqs=n_prefixes + 1,
                          max_seq_len=max_seq)
            if not async_mode:
                # sync-under-lock reconstruction: mint the same record
                # _launch_prefix_fetch would, but resolve it INLINE on
                # the admission thread (which holds the engine lock) —
                # the record is done before admission reads it, so it
                # splices in the same wave, exactly the pre-item-3a flow
                def launch_inline(request_id, prompt):
                    rec = {
                        "request_id": request_id, "done": False, "error": False,
                        "lost": False, "pref": None, "restore": None,
                        "nbytes": 0, "n_p": 0, "t0": time.time(), "t1": 0.0,
                        "deadline": time.time() + b.prefix_fetch_deadline_s,
                    }
                    b._fetch_state[request_id] = rec
                    b._run_prefix_fetch(rec, [int(t) for t in prompt])
                    return rec

                b._launch_prefix_fetch = launch_inline
            # compile outside the timed region: victim's prefill/decode
            # buckets, and the full remote-hit path (fetch via the arm's
            # launch + scatter-in + suffix prefill) through warm_prefix
            warm_v = [int(x) for x in rng.integers(1, cfg.vocab_size - 1, size=suffix_len)]
            b.generate(warm_v, SamplingParams(max_tokens=2, temperature=0.0))
            b.generate(warm_prefix + _sfx(), SamplingParams(max_tokens=2, temperature=0.0))
            vid = b.add_request(victim_prompt, victim_sp)
            while True:  # victim decoding before any follower arrives
                with b._lock:
                    if len(b._requests[vid].token_ids) >= 2:
                        break
                b.step()
            for p in prefixes:
                b.add_request(p + _sfx(), sp)
            while b.has_unfinished():
                b.step()
            snap = b._tel.recorder.snapshot()
            itls, ttfts = [], []
            for rec in snap["requests"]:
                if rec["prompt_tokens"] == len(victim_prompt) and rec["itl_s"]:
                    itls = list(rec["itl_s"])
                elif rec["prompt_tokens"] == prefix_len + suffix_len and rec["ttft_s"] is not None:
                    ttfts.append(rec["ttft_s"])
            # only the measured followers' spans: drop the warm request's
            spans = [f for f in snap["fetches"] if f["hit"]][-n_prefixes:]
            overlapped = sum(
                1 for f in spans
                if any(f["t0"] <= s["t"] <= f["t1"] for s in snap["steps"])
            )
            remote = b.prefix_cache_stats()["remote"]
            return {
                "victim_itl_ms_p50": _pct(itls, 0.50),
                "victim_itl_ms_p99": _pct(itls, 0.99),
                "follower_ttft_ms_p50": _pct(ttfts, 0.50),
                "remote_hits": remote["hits"],
                "fetch_spans": len(spans),
                "fetch_spans_overlapping_steps": overlapped,
                "telemetry": True,  # provenance: flight-recorder-sourced
            }

        sync = _arm(False)
        async_ = _arm(True)
    finally:
        rt.shutdown()
    speed = (sync["victim_itl_ms_p99"] / async_["victim_itl_ms_p99"]) if async_["victim_itl_ms_p99"] else None
    rec = {
        "metric": "engine_kvplane_async_ab",
        **_device_info(),
        "kv_dtype": cfg.dtype,
        "tp": 1,
        "tp_collective": "fp",
        "kvplane": True,
        "workload": (
            f"victim decode stream (48 tokens) on B while {n_prefixes} shared-prefix followers "
            f"(len {prefix_len}) fetch remote blocks from A at +{round(delay_s * 1e3, 1)} ms "
            f"simulated transfer each (2.5x median decode step wall); sync arm resolves the "
            f"fetch inline under the engine lock"
        ),
        "fetch_delay_ms": round(delay_s * 1e3, 1),
        "decode_step_wall_ms": round(step_wall_ms, 2),
        "sync_under_lock": sync,
        "async_fetch": async_,
        "victim_itl_p99_speedup": round(speed, 2) if speed else None,
    }
    print(
        f"  victim ITL p50/p99 sync {sync['victim_itl_ms_p50']}/{sync['victim_itl_ms_p99']} ms "
        f"-> async {async_['victim_itl_ms_p50']}/{async_['victim_itl_ms_p99']} ms "
        f"({rec['victim_itl_p99_speedup']}x p99); overlap evidence: "
        f"{async_['fetch_spans_overlapping_steps']}/{async_['fetch_spans']} async fetch spans "
        f"contain step records (sync: {sync['fetch_spans_overlapping_steps']})",
        flush=True,
    )
    return rec


def bench_kvplane_prefetch(cfg, prompt_len: int, gen_len: int, n_prefixes: int = 4) -> dict:
    """Predictive-prefetch hit-rate uplift A/B (ROADMAP item 3b): the
    fleet's hot system prompts land on replica B BEFORE its first
    request. Baseline arm: B serves one request per hot prefix cold —
    every hit is a REMOTE fetch at admission time. Prefetch arm: a
    heartbeat prefetch round (index top_hot over router-accrued demand)
    pulls the blocks into B's local cache first, so the same traffic is
    all LOCAL-tier hits, attributed as ``prefetch_hits``."""
    import numpy as np

    import ray_tpu as rt
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.kvplane import KVPlaneClient, PrefixIndex, boundary_keys
    from ray_tpu.llm.sampling import SamplingParams

    prefix_len = max(128, prompt_len)
    suffix_len, gen = 8, min(gen_len, 8)
    max_seq = 1 << (prefix_len + suffix_len + gen + 16 - 1).bit_length()
    rng = np.random.default_rng(13)
    prefixes = [
        [int(x) for x in rng.integers(1, cfg.vocab_size - 1, size=prefix_len)]
        for _ in range(n_prefixes)
    ]
    sp = SamplingParams(max_tokens=gen, temperature=0.0)

    def _sfx():
        return [int(x) for x in rng.integers(1, cfg.vocab_size - 1, size=suffix_len)]

    rt.init(num_cpus=2)
    try:
        index = PrefixIndex()
        a = LLMEngine(cfg, kv_plane=KVPlaneClient(index, "A", publish_min_hits=1),
                      max_num_seqs=2, max_seq_len=max_seq)
        for p in prefixes:
            a.generate(p + _sfx(), sp)
        # router-shaped demand: every match_replicas scores bump the keys
        blk = a._prefix_cache.block
        for p in prefixes:
            for _ in range(3):
                index.match_replicas(boundary_keys(p + [1] * suffix_len, blk))

        def _arm(prefetch: bool) -> dict:
            cb = KVPlaneClient(index, f"B-{'pf' if prefetch else 'cold'}",
                               publish_min_hits=1,
                               prefetch_k=n_prefixes if prefetch else 0,
                               heartbeat_every_s=0.0)
            b = LLMEngine(cfg, kv_plane=cb, max_num_seqs=2, max_seq_len=max_seq)
            warm = [int(x) for x in rng.integers(1, cfg.vocab_size - 1, size=prefix_len)]
            b.generate(warm + _sfx(), SamplingParams(max_tokens=2, temperature=0.0))
            b.generate(warm + _sfx() + [1], SamplingParams(max_tokens=2, temperature=0.0))
            if prefetch:
                cb.maybe_heartbeat()  # one prefetch round on the worker
                t = cb._prefetch_thread
                if t is not None:
                    t.join(120.0)
                cb.prefetch_k = 0  # freeze: the measured phase stays fixed
            s0 = b.prefix_cache_stats()
            for p in prefixes:
                b.generate(p + _sfx(), sp)
            s1 = b.prefix_cache_stats()
            remote = {k: s1["remote"][k] - s0["remote"][k] for k in s1["remote"]}
            local_hits = s1["local"]["hits"] - s0["local"]["hits"]
            # true TTFT from the flight recorder: the measured requests
            # are the LAST n_prefixes records at the hit prompt shape
            # (the warm request shares the length — slice it off)
            ttfts = [
                rec["ttft_s"]
                for rec in b._tel.recorder.snapshot()["requests"]
                if rec["prompt_tokens"] == prefix_len + suffix_len
                and rec["ttft_s"] is not None
            ][-n_prefixes:]
            return {
                "requests": n_prefixes,
                "local_hits": local_hits,
                "remote_hits": remote["hits"],
                "prefetch_hits": remote["prefetch_hits"],
                "prefetched_blocks": s1["remote"]["prefetched_blocks"],
                "local_hit_rate": round(local_hits / n_prefixes, 3),
                "ttft_ms_p50": _pct(ttfts, 0.50),
                "telemetry": True,  # provenance: flight-recorder-sourced
            }

        cold = _arm(False)
        pf = _arm(True)
    finally:
        rt.shutdown()
    rec = {
        "metric": "engine_kvplane_prefetch_ab",
        **_device_info(),
        "kv_dtype": cfg.dtype,
        "tp": 1,
        "tp_collective": "fp",
        "kvplane": True,
        "workload": (
            f"{n_prefixes} hot system prompts (len {prefix_len}) published on A with router "
            f"demand; B serves one request per prefix, cold vs after one heartbeat prefetch round"
        ),
        "cold_baseline": cold,
        "prefetch": pf,
        "local_hit_rate_uplift": round(pf["local_hit_rate"] - cold["local_hit_rate"], 3),
        "ttft_p50_speedup": (
            round(cold["ttft_ms_p50"] / pf["ttft_ms_p50"], 2) if pf["ttft_ms_p50"] else None
        ),
    }
    print(
        f"  cold: {cold['remote_hits']} remote hits (local rate {cold['local_hit_rate']}, "
        f"TTFT p50 {cold['ttft_ms_p50']} ms) -> prefetch: {pf['prefetch_hits']} "
        f"prefetch-converted local hits (local rate {pf['local_hit_rate']}, uplift "
        f"{rec['local_hit_rate_uplift']}, TTFT p50 {pf['ttft_ms_p50']} ms, "
        f"{rec['ttft_p50_speedup']}x)",
        flush=True,
    )
    return rec


def bench_conversation_resume(cfg, prompt_len: int, gen_lens=(16, 48, 128),
                              max_num_seqs: int = 4) -> dict:
    """Tiered conversation KV A/B (ROADMAP item 3c): time-to-next-token
    when an idle conversation returns, at several history lengths G.

    - RESUME arm: the conversation decoded G tokens, went idle, and was
      suspended (KV spilled out of HBM through the migration codec,
      slot/pages freed). resume_suspended scatters the block back in:
      TTNT = resume call -> token G+1; recomputed tokens = 0.
    - RE-PREFILL arm (the no-tiering baseline): the conversation was
      simply evicted; the returning user pays a full prompt prefill plus
      G recomputed decode steps to reach the same token.

    Resume cost is ~flat in G (one scatter + one step); re-prefill grows
    linearly — at fleet scale the gap is why effective KV capacity is
    DRAM, not HBM."""
    import numpy as np

    import ray_tpu as rt
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.sampling import SamplingParams

    rng = np.random.default_rng(5)
    prompt = [int(x) for x in rng.integers(1, cfg.vocab_size - 1, size=prompt_len)]
    gen_lens = [g for g in gen_lens if prompt_len + g + 9 <= cfg.max_seq_len] or [8]

    def _run_until(eng, rid, n_tokens):
        while True:
            with eng._lock:
                st = eng._requests.get(rid)
                if st is None or st.finished or len(st.token_ids) >= n_tokens:
                    return
            eng.step()

    rt.init(num_cpus=2)
    try:
        eng = LLMEngine(cfg, max_num_seqs=max_num_seqs, max_seq_len=cfg.max_seq_len,
                        enable_prefix_caching=False)
        warm_sp = SamplingParams(temperature=0.0, max_tokens=3)
        eng.generate(prompt, warm_sp)
        # warm the suspend/resume cycle at EVERY row's history length:
        # each G can land in a different checkpoint-block bucket, and the
        # restore scatter compiles per bucket width (bench_migrate's
        # warm-every-bucket discipline)
        for g in gen_lens:
            wid = eng.add_request(prompt, SamplingParams(temperature=0.0, max_tokens=g + 8))
            _run_until(eng, wid, g)
            eng.suspend_request(wid, publish=False)
            eng.resume_suspended(wid)
            _run_until(eng, wid, g + 2)
            eng.abort_request(wid)
            while eng.has_unfinished():
                eng.step()

        rows = []
        for g in gen_lens:
            sp = SamplingParams(temperature=0.0, max_tokens=g + 8)
            # --- suspend/resume arm ---
            rid = eng.add_request(prompt, sp)
            _run_until(eng, rid, g)
            t0 = time.perf_counter()
            info = eng.suspend_request(rid)  # DRAM + object plane
            suspend_ms = (time.perf_counter() - t0) * 1e3
            emitted = len(eng._suspended[rid]["state"]["emitted_token_ids"])
            t0 = time.perf_counter()
            eng.resume_suspended(rid)
            _run_until(eng, rid, emitted + 1)
            ttnt_resume = time.perf_counter() - t0
            eng.abort_request(rid)
            while eng.has_unfinished():
                eng.step()
            # --- re-prefill arm (evicted conversation) ---
            t0 = time.perf_counter()
            rid2 = eng.add_request(prompt, sp)
            _run_until(eng, rid2, g + 1)
            ttnt_reprefill = time.perf_counter() - t0
            eng.abort_request(rid2)
            while eng.has_unfinished():
                eng.step()
            rows.append({
                "gen_history": g,
                "resume_ttnt_ms": round(ttnt_resume * 1e3, 2),
                "reprefill_ttnt_ms": round(ttnt_reprefill * 1e3, 2),
                "speedup": round(ttnt_reprefill / ttnt_resume, 2) if ttnt_resume else None,
                "suspend_ms": round(suspend_ms, 2),
                "spilled_bytes": int(info["nbytes"]),
                "published": info["published"],
                "recomputed_tokens_resume": 0,
                "recomputed_tokens_reprefill": g,
            })
            print(
                f"  G={g}: resume TTNT {rows[-1]['resume_ttnt_ms']} ms "
                f"({rows[-1]['spilled_bytes'] >> 10} KiB spilled) vs re-prefill "
                f"{rows[-1]['reprefill_ttnt_ms']} ms ({rows[-1]['speedup']}x, "
                f"{g} tokens recomputed)",
                flush=True,
            )
        spill = eng.suspend_stats()
    finally:
        rt.shutdown()
    return {
        "metric": "engine_conversation_resume_ab",
        **_device_info(),
        "kv_dtype": str(eng.kv_dtype),
        "tp": 1,
        "tp_collective": "fp",
        "workload": (
            f"prompt {prompt_len}, conversation idles after G generated tokens; TTNT = return -> "
            f"token G+1 (resume: scatter-in from the DRAM/object-plane tier; re-prefill: full "
            f"prompt prefill + G recomputed decode steps)"
        ),
        "suspend_stats": spill,
        "rows": rows,
    }


def bench_overload(cfg, max_num_seqs: int = 4, stream_gen: int = 96, n_phases: int = 3,
                   arrivals_per_phase: int = 8) -> dict:
    """Overload A/B (serve/overload.py): an OPEN-LOOP ramp of
    prefill-heavy arrivals past a saturated replica's capacity, with
    admission control ON vs OFF.

    The replica runs ``max_num_seqs`` latency-sensitive decode streams
    (priority 1) that saturate every slot — the SLO traffic whose ITL
    the fleet must protect. Arrivals are long-prompt/short-gen requests
    (priority 0) submitted open-loop at 1x/2x/4x the replica's serial
    arrival-service rate; with zero free capacity EVERY arrival is
    over-capacity by construction.

    - **OFF** (AdmissionConfig(enabled=False)): every arrival joins the
      engine queue. Each slot a finishing stream frees is immediately
      backfilled from the backlog, so the surviving streams eat one
      prefill stall per served arrival for the rest of the run — decode
      ITL p99 blows up to the prefill stall, and queue wait grows with
      the backlog (unbounded in an open loop).
    - **ON**: class-0 arrivals shed with typed 429s while the streams
      hold the slots (max_slot_occupancy headroom reservation + queue
      caps), so overload degrades SHED RATE, never the streams' ITL —
      the committed gate is ITL p99 within 1.2x of the same replica's
      unloaded baseline, measured while the OFF arm shows the blow-up.

    Both ITL distributions and the queue waits come from the engine's
    FLIGHT RECORDER (the same samples the live rt_llm_itl_s /
    rt_llm_queue_wait_s series aggregate) — telemetry-sourced
    provenance, like the disagg A/B."""
    import numpy as np

    from ray_tpu.serve.llm import LLMConfig, LLMServer
    from ray_tpu.serve.overload import AdmissionConfig, OverloadedError

    rng = np.random.default_rng(3)
    stream_prompts = [
        [int(x) for x in rng.integers(1, cfg.vocab_size - 1, size=48)] for _ in range(max_num_seqs)
    ]
    # STAGGERED stream lengths: slots free progressively, so the OFF arm
    # backfills each freed slot from its backlog and the surviving
    # streams eat a prefill stall per served arrival — the blow-up the
    # ON arm's headroom reservation prevents
    stream_gens = [
        max(8, stream_gen * (max_num_seqs - i) // max_num_seqs) for i in range(max_num_seqs)
    ]
    arrival_len = min(cfg.max_seq_len - 16, 256)
    arrival_prompt = [int(x) for x in rng.integers(1, cfg.vocab_size - 1, size=arrival_len)]
    mults = [2 ** i for i in range(n_phases)]  # 1x, 2x, 4x serial service rate

    def run(admission_on: bool) -> dict:
        srv = LLMServer(LLMConfig(
            model_config=cfg,
            engine_kwargs={
                "max_num_seqs": max_num_seqs,
                "max_seq_len": cfg.max_seq_len,
                "enable_prefix_caching": False,
            },
            prewarm=True,
            admission=AdmissionConfig(
                enabled=admission_on,
                max_queue_depth=8,
                max_queue_wait_s=5.0,
                # reserve the slots for the priority-1 streams: class 0
                # sheds whenever >= 25% of slots are busy (i.e. always,
                # while any stream lives), the streams admit at the full cap
                max_slot_occupancy=1.0,
                class_fracs=(0.25, 1.0),
            ),
        ))
        try:
            def warm_round(gen, n_arr):
                ths = [
                    threading.Thread(target=lambda p=p: srv.generate(
                        p, {"max_tokens": gen, "temperature": 0.0, "priority": 1}, timeout_s=1200.0))
                    for p in stream_prompts
                ]
                for t in ths:
                    t.start()
                for t in ths:
                    t.join()
                # arrival-shaped warms AFTER the streams drain: the slots
                # are free, so the ON arm's headroom reservation admits
                # them and the 256-bucket prefill compiles here, not
                # inside the measured window (or the t_arrival probe)
                for _ in range(n_arr):
                    try:
                        srv.generate(arrival_prompt, {"max_tokens": 4, "temperature": 0.0, "priority": 1},
                                     timeout_s=1200.0)
                    except OverloadedError:
                        pass

            # two warm rounds: compile every prefill-batch variant and
            # the fused decode the measured pattern can mint
            warm_round(6, 2)
            warm_round(4, 1)
            # serial arrival service time -> the phase rates
            t0 = time.perf_counter()
            srv.generate(arrival_prompt, {"max_tokens": 4, "temperature": 0.0, "priority": 1}, timeout_s=1200.0)
            t_arrival = max(time.perf_counter() - t0, 1e-3)

            def stream_round(label):
                ids, ths = [], []
                lock = threading.Lock()

                def one(p, g):
                    out = srv.generate(
                        p, {"max_tokens": g, "temperature": 0.0, "priority": 1},
                        timeout_s=1200.0,
                    )
                    with lock:
                        ids.append(out["request_id"])

                for p, g in zip(stream_prompts, stream_gens):
                    ths.append(threading.Thread(target=one, args=(p, g), name=f"stream-{label}"))
                return ids, ths

            # ---- baseline: streams alone, no arrivals ----
            base_ids, ths = stream_round("base")
            for t in ths:
                t.start()
            for t in ths:
                t.join()

            # ---- loaded: streams + open-loop arrival ramp ----
            load_ids, ths = stream_round("load")
            phases = []
            arr_lock = threading.Lock()
            arr_threads = []
            for t in ths:
                t.start()
            for mult in mults:
                interval = t_arrival / mult
                ph = {"rate_mult": mult, "interval_s": round(interval, 4),
                      "submitted": 0, "shed": 0, "errors": 0, "completed": 0}
                phases.append(ph)

                def arrive(ph=ph):
                    try:
                        out = srv.generate(
                            arrival_prompt,
                            {"max_tokens": 4, "temperature": 0.0, "priority": 0},
                            timeout_s=1200.0,
                        )
                        with arr_lock:
                            ph["completed"] += 1
                            ph.setdefault("ids", []).append(out["request_id"])
                    except OverloadedError as e:
                        with arr_lock:
                            ph["shed"] += 1
                            ph.setdefault("retry_after_s", round(float(e.retry_after_s), 3))
                    except Exception:  # noqa: BLE001
                        with arr_lock:
                            ph["errors"] += 1

                for _ in range(arrivals_per_phase):
                    if not any(t.is_alive() for t in ths):
                        break  # streams done: the overload window closed
                    ph["submitted"] += 1
                    th = threading.Thread(target=arrive)
                    th.start()
                    arr_threads.append(th)
                    time.sleep(interval)
            for t in ths:
                t.join()
            t_streams_done = time.perf_counter()
            for t in arr_threads:
                t.join(timeout=600)
            drain_s = time.perf_counter() - t_streams_done

            # ---- telemetry-sourced distributions ----
            recs = srv.engine.telemetry()["requests"]

            def dist(ids):
                idset = set(ids)
                itls = [x for r in recs if r["request_id"] in idset for x in r["itl_s"]]
                return _dist([], itls), itls

            base, _ = dist(base_ids)
            load, load_itls = dist(load_ids)
            arrival_ids = {i for ph in phases for i in ph.get("ids", [])}
            qwaits = [r["queue_wait_s"] for r in recs
                      if r["request_id"] in arrival_ids and r.get("queue_wait_s") is not None]
            st = srv.overload_stats()
            submitted = sum(p["submitted"] for p in phases)
            shed = sum(p["shed"] for p in phases)
            for ph in phases:
                ph.pop("ids", None)
                ph["shed_rate"] = round(ph["shed"] / ph["submitted"], 3) if ph["submitted"] else None
            return {
                "admission": admission_on,
                "telemetry": True,  # ITL/queue-wait sourced from the flight recorder
                "baseline_itl_ms_p50": base["itl_ms_p50"],
                "baseline_itl_ms_p99": base["itl_ms_p99"],
                "loaded_itl_ms_p50": load["itl_ms_p50"],
                "loaded_itl_ms_p99": load["itl_ms_p99"],
                "itl_p99_vs_baseline": (
                    round(load["itl_ms_p99"] / base["itl_ms_p99"], 3) if base["itl_ms_p99"] else None
                ),
                "itl_samples": len(load_itls),
                "arrival_service_s": round(t_arrival, 3),
                "phases": phases,
                "arrivals_submitted": submitted,
                "arrivals_shed": shed,
                "shed_rate": round(shed / submitted, 3) if submitted else None,
                "queue_wait_ms_p50": _pct(qwaits, 0.50),
                "queue_wait_ms_p99": _pct(qwaits, 0.99),
                "backlog_drain_s": round(drain_s, 2),
                "shed_counters": {k: v for k, v in st.items() if k.startswith("shed")},
            }
        finally:
            srv.shutdown()

    on = run(True)
    off = run(False)
    rec = {
        "metric": "engine_overload_ab",
        **_device_info(),
        "kv_dtype": cfg.dtype,
        "tp": 1,
        "tp_collective": "fp",
        "workload": (
            f"{max_num_seqs} decode streams (priority 1, staggered gen {stream_gens}) saturating every "
            f"slot + open-loop priority-0 arrivals (prompt {arrival_len}, 4 tokens) ramped at "
            f"{'/'.join(str(m) + 'x' for m in mults)} the serial arrival-service rate, "
            f"{arrivals_per_phase} per phase"
        ),
        "admission_on": on,
        "admission_off": off,
        "batch": max_num_seqs,
    }
    print(
        f"  ON : ITL p99 {on['loaded_itl_ms_p99']} ms ({on['itl_p99_vs_baseline']}x baseline), "
        f"shed {on['arrivals_shed']}/{on['arrivals_submitted']}, queue-wait p99 {on['queue_wait_ms_p99']} ms\n"
        f"  OFF: ITL p99 {off['loaded_itl_ms_p99']} ms ({off['itl_p99_vs_baseline']}x baseline), "
        f"shed {off['arrivals_shed']}/{off['arrivals_submitted']}, queue-wait p99 {off['queue_wait_ms_p99']} ms",
        flush=True,
    )
    return rec


def bench_migrate(cfg, prompt_len: int, gen_lens=(16, 48, 128), max_num_seqs: int = 4) -> dict:
    """Live migration vs abort-and-re-prefill A/B (llm/migrate.py):
    time-to-NEXT-token after a replica death, at several generated-
    prefix lengths G.

    - MIGRATE arm: a request decodes G tokens on engine A; A is
      "preempted" — checkpoint_request extracts + publishes the live
      state over the real object plane (put_owned), engine B fetches,
      restores and decodes. TTNT = death -> token G+1 on B; recomputed
      tokens = 0 (the splice-dedup contract).
    - ABORT arm (the pre-migration failover): the router re-prefills the
      ORIGINAL prompt on B from scratch. TTNT = death -> token G+1,
      which costs a full prompt prefill plus G recomputed decode steps.

    Migrate's cost is ~constant in G (one extract + transfer + scatter +
    one step); abort's grows linearly — the crossover is where live
    migration starts paying for itself, and the per-G rows show it."""
    import numpy as np

    import ray_tpu as rt
    from ray_tpu.llm import migrate as mig
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.sampling import SamplingParams

    rng = np.random.default_rng(0)
    prompt = [int(x) for x in rng.integers(1, cfg.vocab_size - 1, size=prompt_len)]
    gen_lens = [g for g in gen_lens if prompt_len + g + 9 <= cfg.max_seq_len] or [8]

    def _engine():
        return LLMEngine(
            cfg, max_num_seqs=max_num_seqs, max_seq_len=cfg.max_seq_len,
            enable_prefix_caching=False,
        )

    def _run_until(eng, rid, n_tokens):
        while True:
            with eng._lock:
                st = eng._requests.get(rid)
                if st is None or st.finished or len(st.token_ids) >= n_tokens:
                    return
            eng.step()

    def _drain_request(eng, rid):
        while True:
            for o in eng.step():
                if o.request_id == rid and o.finished:
                    return o

    rt.init(num_cpus=2)
    try:
        src, dst = _engine(), _engine()
        warm_sp = SamplingParams(temperature=0.0, max_tokens=3)
        # compile every bucket + the restore scatter OUTSIDE the timed
        # region: the A/B measures the steady-state failover, not XLA
        src.generate(prompt, warm_sp)
        dst.generate(prompt, warm_sp)
        for g in gen_lens:
            wid = src.add_request(prompt, SamplingParams(temperature=0.0, max_tokens=g + 2))
            _run_until(src, wid, g)
            wmeta, wref = mig.publish(src.checkpoint_request(wid))
            src.abort_request(wid)
            rid = dst.restore_request(mig.fetch(wref, wmeta))
            # one token PAST the checkpoint: the restore must actually
            # step (scatter-in + splice step compile), not just admit —
            # the settle already put g+1 tokens in the checkpoint
            _run_until(dst, rid, g + 2)
            dst.abort_request(rid)
            while src.has_unfinished():
                src.step()
            while dst.has_unfinished():
                dst.step()

        rows = []
        for g in gen_lens:
            sp = SamplingParams(temperature=0.0, max_tokens=g + 8)
            # --- migrate arm ---
            rid = src.add_request(prompt, sp)
            _run_until(src, rid, g)
            t0 = time.perf_counter()
            state = src.checkpoint_request(rid)
            meta, ref = mig.publish(state)
            pub_ms = (time.perf_counter() - t0) * 1e3
            fetched = mig.fetch(ref, meta)
            rid2 = dst.restore_request(fetched)
            _run_until(dst, rid2, len(state["emitted_token_ids"]) + 1)
            ttnt_mig = time.perf_counter() - t0
            src.finish_migrated(rid)
            dst.abort_request(rid2)
            while dst.has_unfinished():
                dst.step()
            while src.has_unfinished():
                src.step()
            # --- abort-and-re-prefill arm ---
            t0 = time.perf_counter()
            rid3 = dst.add_request(prompt, sp)
            _run_until(dst, rid3, g + 1)  # re-reach the NEXT token from scratch
            ttnt_abort = time.perf_counter() - t0
            dst.abort_request(rid3)
            while dst.has_unfinished():
                dst.step()
            rows.append({
                "gen_prefix": g,
                "migrate_ttnt_ms": round(ttnt_mig * 1e3, 2),
                "abort_ttnt_ms": round(ttnt_abort * 1e3, 2),
                "speedup": round(ttnt_abort / ttnt_mig, 2) if ttnt_mig else None,
                "checkpoint_publish_ms": round(pub_ms, 2),
                "migrated_bytes": int(meta["nbytes"]),
                "recomputed_tokens_migrate": 0,
                "recomputed_tokens_abort": g,
            })
            print(
                f"  G={g}: migrate TTNT {rows[-1]['migrate_ttnt_ms']} ms "
                f"({rows[-1]['migrated_bytes'] >> 10} KiB) vs abort {rows[-1]['abort_ttnt_ms']} ms "
                f"({rows[-1]['speedup']}x, {g} tokens recomputed)",
                flush=True,
            )
    finally:
        rt.shutdown()
    return {
        "metric": "engine_migrate_ab",
        **_device_info(),
        "kv_dtype": str(src.kv_dtype),
        "tp": 1,
        "tp_collective": "fp",
        "workload": (
            f"prompt {prompt_len}, replica death after G generated tokens; TTNT = death -> "
            f"token G+1 on the peer (migrate: checkpoint+publish+fetch+restore+1 step over the "
            f"real object plane; abort: full re-prefill + G recomputed decode steps)"
        ),
        "rows": rows,
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true", help="CPU sanity mode")
    ap.add_argument("--small", action="store_true", help="~125M model (CPU-runnable engine bench)")
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--only", default="")
    ap.add_argument("--compare", action="store_true", help="also run the synchronous host-driven loop (before/after)")
    ap.add_argument("--speculative", action="store_true", help="spec-ngram vs plain A/B on a repetitive-suffix workload")
    ap.add_argument("--spec-k", type=int, default=4, help="verify width for --speculative")
    ap.add_argument(
        "--attn-kernel", default="xla", choices=["xla", "pallas"],
        help="paged-attention impl for the engine benches (the engine_attn_kernel_ab record "
        "always measures both; off-TPU the pallas leg runs in interpret mode)",
    )
    ap.add_argument("--trace", default="", help="capture a jax.profiler trace of each decode phase under DIR/<metric>")
    ap.add_argument("--write", action="store_true", help="write --out even in --tiny/--small/--only modes")
    ap.add_argument("--repeats", type=int, default=3, help="best-of-N engine phases (min = least-contended sample)")
    args = ap.parse_args(argv)

    # the tp A/B needs >= 2 devices: on a TPU-less host give the CPU
    # platform virtual devices BEFORE jax initializes (harmless on real
    # TPU hosts — the flag only affects the host platform)
    import os

    if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
        )

    cfg, prompt_len, gen_len = _model(args.tiny or args.small)
    if args.small:
        from ray_tpu.models.llama import LlamaConfig

        cfg = LlamaConfig(
            vocab_size=8192,
            hidden_size=768,
            intermediate_size=2048,
            num_layers=10,
            num_heads=12,
            num_kv_heads=12,
            max_seq_len=1024,
            dtype="float32",
            remat=False,
        )
        prompt_len, gen_len = 256, 64
    results = []
    benches = [
        ("engine_slots", lambda: bench_engine(cfg, prompt_len, gen_len, "slots", trace_dir=args.trace and f"{args.trace}/engine_slots", repeats=args.repeats)),
        ("engine_paged", lambda: bench_engine(cfg, prompt_len, gen_len, "paged", trace_dir=args.trace and f"{args.trace}/engine_paged", repeats=args.repeats, attn_kernel=args.attn_kernel)),
    ]
    if args.compare:
        benches += [
            ("engine_slots_sync", lambda: bench_engine(cfg, prompt_len, gen_len, "slots", device_resident=False, trace_dir=args.trace and f"{args.trace}/engine_slots_sync", repeats=args.repeats)),
            ("engine_paged_sync", lambda: bench_engine(cfg, prompt_len, gen_len, "paged", device_resident=False, trace_dir=args.trace and f"{args.trace}/engine_paged_sync", repeats=args.repeats)),
        ]
    if args.speculative:
        benches.append(("engine_spec_ngram", lambda: bench_spec(cfg, prompt_len, gen_len, k=args.spec_k, repeats=args.repeats)))
    benches.append(("engine_kv_int8_ab", lambda: bench_kv_int8(cfg, prompt_len, gen_len, repeats=args.repeats)))
    benches.append(("engine_attn_kernel_ab", lambda: bench_attn_kernel(cfg, prompt_len, gen_len, repeats=args.repeats)))
    benches.append(("engine_tp_ab", lambda: bench_tp(cfg, prompt_len, gen_len, repeats=args.repeats)))
    benches.append(("engine_disagg_ab", lambda: bench_disagg(cfg, prompt_len, gen_len)))
    benches.append(("engine_kvplane_ab", lambda: bench_kvplane(cfg, prompt_len, gen_len)))
    benches.append(("engine_kvplane_async_ab", lambda: bench_kvplane_async(cfg, prompt_len, gen_len)))
    benches.append(("engine_kvplane_prefetch_ab", lambda: bench_kvplane_prefetch(cfg, prompt_len, gen_len)))
    benches.append(("engine_conversation_resume_ab", lambda: bench_conversation_resume(cfg, prompt_len)))
    benches.append(("engine_overload_ab", lambda: bench_overload(cfg)))
    benches.append(("engine_migrate_ab", lambda: bench_migrate(cfg, prompt_len)))
    # the proxy -> router -> replica path is proven by chip_smoke.py, whose
    # parent never touches JAX: here every bench runs in THIS process, which
    # holds the chip, so a replica worker could not open it
    from ray_tpu.util.compile_cache import enable_compile_cache

    enable_compile_cache()
    failed = []
    for name, fn in benches:
        if args.only and args.only not in name:
            continue
        print(f"=== {name} ===", flush=True)
        try:
            rec = fn()
        except Exception as e:  # noqa: BLE001 — recorded, the other phases still run, and the exit code says so
            traceback.print_exc()
            rec = {"metric": name, "error": f"{type(e).__name__}: {e}"}
            failed.append(name)
        if "metric" in rec:
            rec["metric"] = name
        if "error" not in rec:
            # attn_kernel provenance on EVERY record: benches that build
            # their own engines stamp it from engine.attn_kernel; the
            # default-engine benches all serve the XLA paged path
            rec.setdefault("attn_kernel", "xla")
        results.append(rec)
        print(json.dumps(rec), flush=True)
    if args.write or (not args.only and not args.tiny and not args.small):
        blob = {
            "benchmarks": results,
            "model": "tiny" if args.tiny else ("small" if args.small else "1B"),
            "note": "each record carries device/device_kind; regenerate on-chip with: python bench_serve.py [--compare --trace bench_artifacts/serve_traces]",
            "ts": time.time(),
        }
        with open(args.out, "w") as f:
            json.dump(blob, f, indent=1)
        print(f"wrote {args.out}")
    if failed:
        print(f"FAILED phases: {failed}", flush=True)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
