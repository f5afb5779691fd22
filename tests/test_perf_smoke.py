"""Core-runtime performance regression floor.

Thresholds are ~5-10x below the numbers seen on the build machine's CPU
so VM jitter never trips them, but a structural regression (an O(n^2)
queue scan, a lost zero-copy path, a serialization copy) does. Reference parity: python/ray/_private/ray_perf.py is run in
release tests with recorded floors (release/microbenchmark/).
"""

import time

import numpy as np
import pytest

import ray_tpu


@pytest.fixture(scope="module")
def rt():
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()


def _rate(op, n):
    """Calls a second by the BEST of ``n`` timed calls: a structural regression slows every call, five busy xdist workers
    slow some (the mean of four read 216 tasks/s against a floor of 300 once in PR 40's whole runs; the best reads 16,800 alone)."""
    op()  # warm
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        op()
        best = min(best, time.perf_counter() - t0)
    return 1.0 / best


def test_task_throughput_floor(rt):
    @ray_tpu.remote
    def nop():
        return b"ok"

    ray_tpu.get([nop.remote() for _ in range(20)])  # spin up workers
    rate = _rate(lambda: ray_tpu.get([nop.remote() for _ in range(50)]), 4) * 50
    assert rate > 300, f"trivial task throughput collapsed: {rate:.0f}/s"


def test_put_get_bandwidth_floor(rt):
    arr = np.ones(32 << 20, dtype=np.uint8)

    def op():
        r = ray_tpu.put(arr)
        out = ray_tpu.get(r)
        assert out.nbytes == arr.nbytes
        ray_tpu.internal_free([r])

    rate = _rate(op, 5)
    gib_s = rate * arr.nbytes / (1 << 30)
    assert gib_s > 0.1, f"put/get bandwidth collapsed: {gib_s:.3f} GiB/s"


def test_get_is_zero_copy(rt):
    """Large-array get returns a view of the shm mapping, not a copy."""
    arr = np.arange(4 << 20, dtype=np.uint8)
    r = ray_tpu.put(arr)
    out = ray_tpu.get(r)
    assert not out.flags.writeable  # plasma semantics: immutable view
    assert not out.flags.owndata
    np.testing.assert_array_equal(out[:64], arr[:64])
    # a second get maps independently
    out2 = ray_tpu.get(r)
    np.testing.assert_array_equal(out2[:64], arr[:64])
    del out, out2
    ray_tpu.internal_free([r])


def test_zero_copy_survives_free(rt):
    """POSIX shm: unlink by the owner leaves live mappings valid."""
    arr = np.full(2 << 20, 7, dtype=np.uint8)
    r = ray_tpu.put(arr)
    out = ray_tpu.get(r)
    ray_tpu.internal_free([r])
    assert int(out[123]) == 7  # mapping still readable after unlink


def test_llm_engine_throughput_floor():
    """Serving-engine floors (device-resident decode loop): ~10x under
    the numbers measured on the build machine (tiny model, one loaded
    CPU core: prefill ~5.8k tok/s, decode ~450 tok/s at batch 8) so VM
    jitter never trips them, but a structural regression — reintroducing
    a per-step host round trip, losing batched prefill, a per-step
    recompile — does."""
    pytest.importorskip("jax")
    from ray_tpu.llm import LLMEngine, SamplingParams
    from ray_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig.tiny(dtype="float32", remat=False, max_seq_len=256)
    B, P, G = 4, 48, 24
    eng = LLMEngine(cfg, max_num_seqs=B, max_seq_len=128, enable_prefix_caching=False)
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(1, cfg.vocab_size - 1, size=P)) for _ in range(B)]
    eng.generate(prompts, SamplingParams(max_tokens=2))  # compile everything

    t0 = time.perf_counter()
    for p in prompts:
        eng.add_request(p, SamplingParams(max_tokens=G))
    while eng.num_waiting:
        eng.step()
    prefill_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    while eng.has_unfinished():
        eng.step()
    decode_s = time.perf_counter() - t0

    prefill_tok_s = B * P / prefill_s
    decode_tok_s = B * G / decode_s
    assert prefill_tok_s > 300, f"prefill throughput collapsed: {prefill_tok_s:.0f} tok/s"
    assert decode_tok_s > 25, f"decode throughput collapsed: {decode_tok_s:.0f} tok/s"


def test_llm_int8_decode_step_floor():
    """Int8-KV decode step floor: the quantized fused step may cost no more than 1.1x the bf16
    step. A structural regression (dequant materializing the full cache in f32 outside the fused
    step, a per-step requant of old positions, a lost scale-lane donation) is what the gate is for.

    Judged by the COMPILER'S account of the two programs the engines run, not by their wall
    clock: until PR 40 this compared best-of-three step times of two engines, a ratio that reads
    0.58-0.59 alone and that six xdist workers on one box swung past 1.1 (ROADMAP C11). Each
    regression above has a count: a requant of old positions is arithmetic over the whole cache
    every step (flops; the int8 step has 1.06x the bf16 step's here, for the dequant of the rows
    it reads), a full-cache dequant is a temporary of the cache's size in f32 (temp bytes; the
    two steps' differ by 32 bytes), a lost donation is a cache that is not aliased to its input."""
    pytest.importorskip("jax")
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm import model_runner as mr
    from ray_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig.tiny(dtype="float32", remat=False, max_seq_len=256)
    B, S = 4, 128
    bf16 = {n: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16 if n != "length" else a.dtype) for n, a in mr._sds_cache(cfg, B, S).items()}
    seen = {}
    for name, cache in (("bfloat16", bf16), ("int8", mr._sds_cache_q(cfg, B, S))):
        compiled = mr.make_fused_fns(cfg, kv_quant=name == "int8").lower(mr._sds_params(cfg), cache, *mr._sds_lanes(B)).compile()
        mem, cost = compiled.memory_analysis(), compiled.cost_analysis()
        held = sum(a.size * a.dtype.itemsize for a in cache.values())
        assert mem.alias_size_in_bytes >= held, f"{name}: the cache (values, scales, lengths) is not updated in place"
        seen[name] = (cost["flops"], cost["bytes accessed"], mem.temp_size_in_bytes)
    (flops, read, temp), (flops_q, read_q, temp_q) = seen["bfloat16"], seen["int8"]
    cache_f32 = 2 * cfg.num_layers * B * S * cfg.num_kv_heads * cfg.hd * 4
    assert flops_q <= 1.1 * flops, f"int8 decode step regressed past the 1.1x bf16 gate: {flops_q:.0f} flops vs {flops:.0f}"
    assert read_q <= 1.1 * read, f"the int8 step reads and writes {read_q:.0f} bytes, the bf16 step {read:.0f}"
    assert temp_q < temp + cache_f32 // 4, f"the int8 step holds {temp_q - temp} more bytes of temporaries: a dequantized cache is {cache_f32}"


def test_llm_pallas_interpret_step_within_sane_multiple():
    """ISSUE 13 floor: the attn_kernel='pallas' paged decode step (the
    kernel runs in INTERPRET mode on this CPU container) must stay
    within a sane multiple of the XLA step, with matching greedy output.
    The gate is correctness-PRESENCE, not speed — the interpreter is
    allowed to be slow (~1.4x on this box; 25x leaves room for any CI).
    What this catches structurally: the kernel
    silently falling off its per-page streaming shape (e.g. a whole-pool
    operand slipping into the grid), which multiplies the interpreted
    step by orders of magnitude, or the opt-in quietly breaking output
    parity."""
    pytest.importorskip("jax")
    from ray_tpu.llm import LLMEngine, SamplingParams
    from ray_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig.tiny(dtype="float32", remat=False, max_seq_len=256)
    B, P, G = 3, 32, 24
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(1, cfg.vocab_size - 1, size=P)) for _ in range(B)]
    best, outs = {}, {}
    engines = {}
    for ak in ("xla", "pallas"):
        eng = LLMEngine(cfg, max_num_seqs=B, max_seq_len=128, kv_layout="paged", page_size=32,
                        enable_prefix_caching=False, attn_kernel=ak)
        outs[ak] = [r.token_ids for r in eng.generate(prompts, SamplingParams(max_tokens=G))]
        engines[ak] = eng
        best[ak] = float("inf")
    assert engines["pallas"].attn_kernel == "pallas"
    assert outs["pallas"] == outs["xla"], "kernel output diverged from the XLA oracle"
    for _ in range(3):  # interleaved rounds: jitter degrades both alike
        for ak, eng in engines.items():
            for p in prompts:
                eng.add_request(p, SamplingParams(max_tokens=G))
            while eng.num_waiting:
                eng.step()
            t0 = time.perf_counter()
            steps = 0
            while eng.has_unfinished():
                eng.step()
                steps += 1
            best[ak] = min(best[ak], (time.perf_counter() - t0) / max(steps, 1))
    assert best["pallas"] <= 25 * best["xla"], (
        f"interpret-mode kernel step blew past the sane-multiple gate: "
        f"pallas {best['pallas'] * 1e3:.2f} ms vs xla {best['xla'] * 1e3:.2f} ms"
    )


def test_llm_telemetry_zero_overhead_gate(monkeypatch):
    """ISSUE 10 acceptance: being observed costs the device-resident decode step at most 5% of
    its own time, and nothing of it reads a device value.

    Judged by the telemetry's OWN host time a step: every entry point the engine calls
    (``begin_step``, ``on_step``, ``on_emit``, ``on_bind``, ``on_submit``, ``on_finish``, and each
    ``stage()``'s construction, entry and exit, less its body) is timed where it runs, summed
    over a round and divided by the round's steps. Until PR 39 the gate compared best-of-rounds
    wall clock of an instrumented engine with a plain one: a ratio of two times of tens of
    milliseconds, each of which a loaded box swings by more than the 5% asked of their ratio
    (six xdist workers: it failed on the tree that stood). The time inside the calls is a
    thousandth of the step's and swings with it, not against it. Budget: 0.5 ms a step in
    absolute terms (a tuple append, pre-bound metric handles, gauges every 16th step, nine
    stamped stages: some 0.1 ms on an idle box) and 5% of the round's own step time, on the
    best of three rounds (one preempted call does not fail the gate, a per-step sync or a
    per-token pull does: either costs every step of every round).

    A model whose step takes milliseconds (6 ms here, 0.10 ms of it the telemetry's, my run,
    PR 39; 9 and 0.14-0.18 with six busy processes beside it): on the micro tiny-model step the
    same fixed cost is several percent."""
    pytest.importorskip("jax")
    import jax

    from ray_tpu.llm import LLMEngine, SamplingParams
    from ray_tpu.llm import engine as engine_module
    from ray_tpu.llm import telemetry
    from ray_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig(
        vocab_size=2048, hidden_size=512, intermediate_size=1024, num_layers=4,
        num_heads=8, num_kv_heads=4, max_seq_len=256, dtype="float32", remat=False,
    )
    B, P, G = 4, 32, 24
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(1, cfg.vocab_size - 1, size=P)) for _ in range(B)]
    eng = LLMEngine(cfg, max_num_seqs=B, max_seq_len=128, enable_prefix_caching=False)
    eng.generate(prompts, SamplingParams(max_tokens=2))  # compile everything
    tel = eng._tel
    spent = [0.0]

    def timed(fn):
        def run(*args, **kwargs):
            t = time.perf_counter()
            try:
                return fn(*args, **kwargs)
            finally:
                spent[0] += time.perf_counter() - t
        return run

    for name in ("begin_step", "on_step", "on_emit", "on_bind", "on_submit", "on_finish"):
        monkeypatch.setattr(tel, name, timed(getattr(tel, name)))
    monkeypatch.setattr(engine_module, "stage", timed(telemetry.stage))  # a stage's construction
    monkeypatch.setattr(telemetry._Stage, "__enter__", timed(telemetry._Stage.__enter__))  # its two stamps, not its body
    monkeypatch.setattr(telemetry._Stage, "__exit__", timed(telemetry._Stage.__exit__))

    rounds = []
    for _ in range(3):
        for p in prompts:
            eng.add_request(p, SamplingParams(max_tokens=G))
        while eng.num_waiting:
            eng.step()
        spent[0], steps, t0 = 0.0, 0, time.perf_counter()
        while eng.has_unfinished():
            eng.step()
            steps += 1
        rounds.append((spent[0] / max(steps, 1), (time.perf_counter() - t0) / max(steps, 1)))
    own, step = min(rounds)
    assert own <= 0.5e-3 and own <= 0.05 * step, (
        f"telemetry took {own * 1e3:.3f} ms of a {step * 1e3:.3f} ms step on the best of "
        f"{[(round(a * 1e3, 3), round(b * 1e3, 2)) for a, b in rounds]} (ms own, ms a step)")
    # no stage reads a device value: every row of the flight ring is the host's own numbers
    snap = tel.recorder.snapshot()
    assert snap["steps"] and all(set(telemetry.STAGES.values()) <= set(s) for s in snap["steps"])
    assert not [v for row in tel.recorder.steps for v in row if isinstance(v, jax.Array)]
    assert all(isinstance(s[f], float) for s in snap["steps"] for f in telemetry.STAGES.values())
    decode = [s for s in snap["steps"] if s["phase"] == "decode" and s["batch"]]
    assert decode and all(s["dispatch_t"] >= s["t0"] for s in decode)


def test_actor_call_floor(rt):
    @ray_tpu.remote
    class A:
        def ping(self):
            return b"ok"

    a = A.remote()
    ray_tpu.get(a.ping.remote())
    rate = _rate(lambda: ray_tpu.get([a.ping.remote() for _ in range(50)]), 4) * 50
    ray_tpu.kill(a)
    assert rate > 300, f"actor call throughput collapsed: {rate:.0f}/s"
