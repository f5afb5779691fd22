"""Tensor-parallel serving equivalence: the shard_map'd fused decode
path over a TP mesh must emit token-for-token identical output to the
tp=1 engine (which test_llm_decode_loop.py already holds to the
plain reference, tests/plain_reference.py), for both KV layouts, composing with the
int8 KV cache and spec-ngram decoding — and the opt-in int8 QUANTIZED
all-reduce (tp_collective="int8") must keep exact top-1 on a
decisive-logits workload with bounded logit drift vs the fp collective,
while provably moving int8 (not fp) bytes for every per-layer
all-reduce on the wire.

Runs on a virtual CPU mesh: conftest.py exports
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before jax
imports, so ``create_mesh(tp=2, devices=jax.devices()[:2])`` works
TPU-less. To run standalone:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        pytest tests/test_llm_tp.py -q
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from copy_model import copy_model_params  # noqa: E402
from plain_reference import drive  # noqa: E402

from ray_tpu.llm import LLMEngine, SamplingParams  # noqa: E402
from ray_tpu.llm.spec import SpecConfig  # noqa: E402
from ray_tpu.models.llama import LlamaConfig, init_params  # noqa: E402
from ray_tpu.parallel.mesh import create_mesh  # noqa: E402

pytestmark = pytest.mark.usefixtures("shared_step_programs")  # many engines of equal configurations: their step programs compile once (conftest.py)

CFG = LlamaConfig.tiny(num_heads=4, num_kv_heads=4, dtype="float32", attention_impl="xla", max_seq_len=256)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


def _mesh(n=2):
    return create_mesh(tp=n, devices=jax.devices()[:n])


def _mixed_schedule(seed=0, n=6):
    """Staggered admissions, varying lengths/budgets, one seeded
    stochastic lane — slot recycling and a sampling lane both ride."""
    rng = np.random.default_rng(seed)
    sched = {}
    for _ in range(n):
        prompt = list(rng.integers(1, CFG.vocab_size - 1, size=int(rng.integers(4, 60))))
        sp = SamplingParams(max_tokens=int(rng.integers(3, 12)), temperature=0.0)
        sched.setdefault(int(rng.integers(0, 8)), []).append((prompt, sp))
    sched.setdefault(1, []).append(
        ([7, 7, 7], SamplingParams(max_tokens=8, temperature=1.0, seed=123))
    )
    return sched


@pytest.mark.parametrize("layout", ["slots", "paged"])
def test_tp2_fused_token_identical(params, layout):
    """TP=2 shard_map fused loop == tp=1 device-resident loop under a
    mixed admission/eviction schedule, greedy + seeded sampling, both KV
    layouts. The tp=1 engine is the token-identical oracle (itself held
    to the plain reference by test_llm_decode_loop.py)."""
    sched = _mixed_schedule()
    kw = dict(params=params, max_num_seqs=3, max_seq_len=128, kv_layout=layout)
    if layout == "paged":
        kw["page_size"] = 32
    base, base_r = drive(LLMEngine(CFG, **kw), sched)
    eng = LLMEngine(CFG, mesh=_mesh(2), **kw)
    got, got_r = drive(eng, sched)
    assert got == base
    assert got_r == base_r
    # the weights and cache are actually sharded over both chips
    arrs = eng.pool if layout == "paged" else eng.cache
    assert len(arrs["k"].sharding.device_set) == 2
    assert len(jax.tree.leaves(eng.params)[0].sharding.device_set) == 2


@pytest.mark.parametrize("layout", ["slots", "paged"])
def test_tp2_int8_kv_cache_composes(params, layout):
    """cache_dtype='int8' under tp=2: the scale lanes shard their
    kv-head axis alongside the values and output stays identical to the
    tp=1 int8 engine."""
    prompts = [[1, 2, 3, 4, 5, 6, 7], [9, 8, 7, 6], [4, 4, 4, 4, 4, 4]]
    sp = SamplingParams(temperature=0.0, max_tokens=10)
    kw = dict(params=params, max_num_seqs=4, max_seq_len=64, kv_layout=layout, cache_dtype="int8")
    if layout == "paged":
        kw["page_size"] = 16
    base = [o.token_ids for o in LLMEngine(CFG, **kw).generate(prompts, sp)]
    got = [o.token_ids for o in LLMEngine(CFG, mesh=_mesh(2), **kw).generate(prompts, sp)]
    assert got == base


@pytest.mark.parametrize("layout", ["slots", "paged"])
def test_tp2_spec_ngram_composes(params, layout):
    """Speculative decoding with the zero-weight NGramDrafter over a
    tp=2 mesh: the sharded verify step must stay token-identical to the
    PLAIN tp=1 engine, with real acceptances (repetitive workload)."""
    prompts = [[1, 2, 3, 1, 2, 3, 1, 2, 3, 1, 2], [5, 6, 5, 6, 5, 6, 5]]
    sp = SamplingParams(temperature=0.0, max_tokens=14)
    kw = dict(params=params, max_num_seqs=4, max_seq_len=64, kv_layout=layout)
    if layout == "paged":
        kw["page_size"] = 16
    base = [o.token_ids for o in LLMEngine(CFG, **kw).generate(prompts, sp)]
    eng = LLMEngine(CFG, mesh=_mesh(2), speculative=SpecConfig(k=3), **kw)
    got = [o.token_ids for o in eng.generate(prompts, sp)]
    assert got == base
    assert eng.spec_stats()["rounds"] > 0


def test_model_drafter_mesh_is_named_gap(params):
    """ModelDrafter x tp stays a clear NotImplementedError naming what
    is missing (sharded draft state), not a silent mis-compile."""
    dcfg = LlamaConfig.tiny(num_heads=4, num_kv_heads=4, dtype="float32")
    with pytest.raises(NotImplementedError, match="draft model"):
        LLMEngine(
            CFG, params, mesh=_mesh(2), max_num_seqs=2, max_seq_len=64,
            speculative=SpecConfig(k=3, drafter="model", draft_config=dcfg),
        )


def test_tp_divisibility_validation():
    """Every tp-sharded model dim is validated at construction with an
    actionable message (an indivisible q-head count used to die deep
    inside GSPMD partitioning)."""
    mesh4 = _mesh(4)
    with pytest.raises(ValueError, match="num_kv_heads"):
        LLMEngine(LlamaConfig.tiny(dtype="float32"), max_seq_len=64, mesh=mesh4)  # 2 kv heads
    with pytest.raises(ValueError, match="num_heads"):
        LLMEngine(
            LlamaConfig.tiny(num_heads=6, num_kv_heads=4, head_dim=16, dtype="float32"),
            max_seq_len=64, mesh=mesh4,
        )
    with pytest.raises(ValueError, match="intermediate_size"):
        LLMEngine(
            LlamaConfig.tiny(num_heads=4, num_kv_heads=4, intermediate_size=250, dtype="float32"),
            max_seq_len=64, mesh=mesh4,
        )
    with pytest.raises(ValueError, match="vocab_size"):
        LLMEngine(
            LlamaConfig.tiny(num_heads=4, num_kv_heads=4, vocab_size=514, dtype="float32"),
            max_seq_len=64, mesh=mesh4,
        )
    # int8 collective needs the shard_map path (pure tp>=2 mesh) ...
    with pytest.raises(ValueError, match="tp_collective"):
        LLMEngine(LlamaConfig.tiny(num_heads=4, num_kv_heads=4, dtype="float32"),
                  max_seq_len=64, tp_collective="int8")
    # ... and an even hidden-dim chunking
    with pytest.raises(ValueError, match="hidden_size"):
        LLMEngine(
            LlamaConfig.tiny(num_heads=4, num_kv_heads=4, hidden_size=126, head_dim=32,
                             vocab_size=512, dtype="float32"),
            max_seq_len=64, mesh=mesh4, tp_collective="int8",
        )
    with pytest.raises(ValueError, match="'fp' or 'int8'"):
        LLMEngine(LlamaConfig.tiny(dtype="float32"), max_seq_len=64, tp_collective="bf8")


# ---------------------------------------------------------------------------
# int8 quantized all-reduce: accuracy + bytes-on-the-wire gates
# ---------------------------------------------------------------------------
def test_tp_collective_int8_exact_top1_on_decisive_workload():
    """tp_collective='int8' vs 'fp' vs tp=1: exact top-1 (identical
    greedy streams) on the decisive-logits workload — the acceptance
    gate for shipping half the ICI bytes per layer."""
    cfg = LlamaConfig.tiny(num_heads=4, num_kv_heads=4, dtype="float32", attention_impl="xla")
    params = copy_model_params(cfg)
    prompts = [[0, 1, 2, 3], [8, 9, 10]]
    sp = SamplingParams(temperature=0.0, max_tokens=12)
    kw = dict(max_num_seqs=2, max_seq_len=64)
    base = [o.token_ids for o in LLMEngine(cfg, params, **kw).generate(prompts, sp)]
    fp = [o.token_ids for o in LLMEngine(cfg, params, mesh=_mesh(2), **kw).generate(prompts, sp)]
    q = [o.token_ids for o in LLMEngine(cfg, params, mesh=_mesh(2), tp_collective="int8", **kw).generate(prompts, sp)]
    assert fp == base
    assert q == base  # exact top-1 under the quantized collective
    # and the streams actually follow the successor map (workload sanity)
    assert base[0][:4] == [4, 5, 6, 7]


def test_tp_collective_int8_bounded_logit_drift(params):
    """Direct logit comparison of one sharded decode step: int8
    collectives drift the logits by a bounded, NONZERO amount vs the fp
    collective (zero would mean the quantization never engaged)."""
    from ray_tpu.llm.model_runner import _cache_pspecs, _param_pspecs, _sharded_fused_slots
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _mesh(2)
    B, S, L = 4, 64, CFG.num_layers
    rng = np.random.default_rng(0)
    k0 = rng.normal(size=(L, B, S, CFG.num_kv_heads, CFG.hd)).astype(np.float32)
    v0 = rng.normal(size=k0.shape).astype(np.float32)
    psh = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), params, _param_pspecs(CFG, mesh)
    )
    csp = _cache_pspecs("slots", False)
    rep = lambda a: jax.device_put(jnp.asarray(a), NamedSharding(mesh, P()))  # noqa: E731

    def run(collective):
        cache = {
            "k": jax.device_put(jnp.asarray(k0), NamedSharding(mesh, csp["k"])),
            "v": jax.device_put(jnp.asarray(v0), NamedSharding(mesh, csp["v"])),
            "length": rep(np.full((B,), 3, np.int32)),
        }
        lanes = (
            rep(np.asarray([5, 6, 7, 8], np.int32)),
            rep(np.asarray(jax.vmap(lambda s: jax.random.key_data(jax.random.PRNGKey(s)))(
                jnp.arange(B, dtype=jnp.uint32)))),
            rep(np.zeros((B,), np.float32)),
            rep(np.zeros((B,), np.int32)),
            rep(np.ones((B,), np.float32)),
        )
        out = _sharded_fused_slots(CFG, mesh, collective, False)(psh, cache, *lanes)
        return np.asarray(out[2])  # logps of the sampled token

    lp_fp, lp_q = run("fp"), run("int8")
    drift = float(np.abs(lp_fp - lp_q).max())
    assert 0.0 < drift < 0.2, drift


def test_tp_int8_collective_wire_bytes():
    """The bytes-on-the-wire gate (CPU cannot show the ICI wall-clock
    win, so the traced program IS the measurement): in int8 mode every
    PER-LAYER collective payload is int8 — no fp tensor all-reduces
    inside the layer scan, only the tiny f32 amax scales — and total
    wire bytes per step land well under the fp-collective program's."""
    from ray_tpu.collective.ici import collective_wire_report
    from ray_tpu.llm.model_runner import (
        _bucket_fused_tp,
        _sharded_fused_slots,
        _trace_cfg,
    )

    mesh = _mesh(2)
    cfg = _trace_cfg()
    args, _ = _bucket_fused_tp()

    def report(collective):
        jaxpr = jax.make_jaxpr(_sharded_fused_slots(cfg, mesh, collective, False))(*args)
        return collective_wire_report(jaxpr, axis_size=2)

    rep_fp, rep_q = report("fp"), report("int8")
    # fp mode: per-layer psums are f32/bf16 — no int8 anywhere
    assert "int8" not in rep_fp["bytes_by_dtype"]
    # int8 mode, inside the layer scan (count>1): the all-reduce payload
    # is int8; the only fp collectives there are the amax scales, which
    # must be a rounding error next to the payload
    in_scan = [op for op in rep_q["ops"] if op["count"] > 1]
    assert in_scan, "no per-layer collectives found in the scan body"
    assert all(op["prim"] in ("all_to_all", "all_gather") for op in in_scan), in_scan
    i8 = sum(op["wire_bytes"] for op in in_scan if op["dtype"] == "int8")
    fp_scales = sum(op["wire_bytes"] for op in in_scan if op["dtype"] != "int8")
    assert i8 > 0
    assert fp_scales < 0.02 * i8, (i8, fp_scales)
    # per-layer wire bytes shrink by ~4x at f32 operands (>= ~2x at bf16);
    # gate at < 0.55 so the claim holds for either serving dtype. The
    # per-layer term is THE scaling cost: it multiplies by num_layers
    # (4 in the trace config, 18-80 in serving models) while the fp
    # embed-psum and logits-gather stay once-per-step.
    fp_layer = sum(op["wire_bytes"] for op in rep_fp["ops"] if op["count"] > 1)
    assert i8 + fp_scales < 0.55 * fp_layer, (i8 + fp_scales, fp_layer)
    # whole-step bytes shrink too (by less here: the once-per-step logits
    # gather over the trace config's 32k vocab dominates its 4 layers)
    assert rep_q["total_bytes"] < rep_fp["total_bytes"]


def test_tp_mixed_mesh_falls_back_and_rejects_int8(params):
    """A mesh with non-tp axes keeps the GSPMD compilation (no shard_map
    manual programs over dims they assume replicated) — and therefore
    cannot honor tp_collective='int8'."""
    import numpy as _np
    from jax.sharding import Mesh

    mesh = Mesh(_np.asarray(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    with pytest.raises(ValueError, match="tp_collective"):
        LLMEngine(CFG, params, mesh=mesh, max_seq_len=64, tp_collective="int8")
