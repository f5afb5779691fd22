"""Int8 KV cache (llm/kv_quant.py): the fp cache is the accuracy oracle.

- exact top-1: greedy decode with an int8 cache is token-identical to
  the fp cache on the deterministic copy model (tests/copy_model.py:
  the repetitive-suffix regime), for BOTH layouts;
- bounded logit drift: one decode step over identical state, fp vs int8
  cache, asserted within a small max-|delta| bound AND argmax-equal on a
  random model (no copy-model margins to hide behind);
- speculative decoding composes: spec-int8 is token-identical to its own
  oracle, plain-int8 (the disagg-int8 oracle lives in
  tests/test_llm_disagg.py);
- cache_dtype is VALIDATED at engine construction (bf16/f32 aliases
  normalize, anything else raises — no silent passthrough), and
  kv_cache_stats() reports the honest scale-inclusive byte math.

Lean by design (tier-1 budget): one module-scoped copy-model parameter
set; engines are built once per (layout, dtype) and reused.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from copy_model import copy_model_params  # noqa: E402

from ray_tpu.llm import LLMEngine, SamplingParams  # noqa: E402
from ray_tpu.llm.kv_quant import bytes_per_token, normalize_cache_dtype  # noqa: E402
from ray_tpu.models.llama import LlamaConfig, init_params  # noqa: E402

pytestmark = pytest.mark.usefixtures("shared_step_programs")  # many engines of equal configurations: their step programs compile once (conftest.py)

CFG = LlamaConfig.tiny(dtype="float32", remat=False, max_seq_len=256)
PERIOD = 8
GREEDY = SamplingParams(temperature=0.0, max_tokens=12)


@pytest.fixture(scope="module")
def copy_params():
    """The deterministic copy model on the tiny config: greedy decode
    provably follows a fixed successor map."""
    return copy_model_params(CFG, period=PERIOD)


@pytest.fixture(scope="module")
def copy_prompts():
    rng = np.random.default_rng(0)
    blocks = rng.integers(1, (CFG.vocab_size - 1) // PERIOD, size=3)
    return [[int(b) * PERIOD + i % PERIOD for i in range(20)] for b in blocks]


def _engine(params, dtype, layout, **kw):
    lk = dict(kv_layout="paged", page_size=32) if layout == "paged" else {}
    return LLMEngine(
        CFG, params, max_num_seqs=3, max_seq_len=128,
        enable_prefix_caching=False, cache_dtype=dtype, **lk, **kw,
    )


@pytest.mark.parametrize("layout", ["slots", "paged"])
def test_int8_exact_top1_on_bench_workload(copy_params, copy_prompts, layout):
    """Greedy int8 output == greedy fp output, token for token."""
    fp = _engine(copy_params, None, layout)
    q8 = _engine(copy_params, "int8", layout)
    fp_out = [r.token_ids for r in fp.generate(copy_prompts, GREEDY)]
    q8_out = [r.token_ids for r in q8.generate(copy_prompts, GREEDY)]
    assert q8_out == fp_out, f"{layout}: int8 cache broke greedy top-1"
    # the copy model's successor map: every token advances its cycle
    succ = [(t // PERIOD) * PERIOD + (t % PERIOD + 1) % PERIOD for t in copy_prompts[0][-1:]]
    assert fp_out[0][0] == succ[0]  # the workload really is deterministic


def test_int8_logit_drift_bounded_and_top1_stable():
    """One decode step over IDENTICAL state, fp cache vs int8 cache, on a
    random model: max |logit delta| stays within a small bound (int8
    per-head quantization error is ~0.4% of amax per element) and the
    argmax never flips. Catches a broken scale layout or a dequant
    applied to the wrong axis, which token-level tests could mask."""
    from ray_tpu.llm import kv_cache as kvc
    from ray_tpu.llm.model_runner import decode_step, prefill

    params = init_params(CFG, jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    toks = np.zeros((2, 32), np.int32)
    toks[:, :] = rng.integers(1, CFG.vocab_size - 1, size=(2, 32))
    lens = np.full((2,), 32, np.int32)
    _, ks, vs = prefill(params, jax.numpy.asarray(toks), jax.numpy.asarray(lens), CFG)
    logits = {}
    for dt in ("float32", "int8"):
        cache = kvc.alloc(kvc.CacheConfig(CFG.num_layers, 2, 64, CFG.num_kv_heads, CFG.hd, dtype=dt))
        for b in range(2):
            cache = kvc.insert_sequence(cache, b, ks[:, b], vs[:, b], int(lens[b]))
        lg, _ = decode_step(params, cache, jax.numpy.asarray([7, 9]), CFG)
        logits[dt] = np.asarray(lg)
    drift = np.abs(logits["float32"] - logits["int8"]).max()
    assert 0 < drift < 0.5, f"int8 logit drift out of bounds: {drift}"
    assert (logits["float32"].argmax(-1) == logits["int8"].argmax(-1)).all()


def test_int8_spec_token_identical_to_plain_int8(copy_params, copy_prompts):
    """Speculative decoding on an int8 cache: token-identical to the
    plain int8 engine (its own oracle), with the spec path engaged."""
    from ray_tpu.llm.spec import SpecConfig

    plain = _engine(copy_params, "int8", "slots")
    spec = _engine(copy_params, "int8", "slots", speculative=SpecConfig(drafter="ngram", k=3))
    p_out = [r.token_ids for r in plain.generate(copy_prompts, GREEDY)]
    s_out = [r.token_ids for r in spec.generate(copy_prompts, GREEDY)]
    assert s_out == p_out
    st = spec.spec_stats()
    assert st["rounds"] > 0 and st["accepted"] > 0, "spec path never engaged"


def test_int8_prefix_cache_hit_identity(copy_params):
    """Prefix-cache hit on an int8 cache: the cached fp prefix quantizes
    at insert and the suffix re-attends through the quantized extend
    program — token-identical to the fp engine over the same pair of
    shared-prefix prompts."""
    base = [PERIOD + int(i) % PERIOD for i in range(64)]  # block-aligned shared prefix
    p1, p2 = base + [3, 4, 5], base + [9, 8, 7, 6]
    outs = {}
    for dt in (None, "int8"):
        eng = LLMEngine(
            CFG, copy_params, max_num_seqs=2, max_seq_len=256,
            enable_prefix_caching=True, prefix_block=64, cache_dtype=dt,
        )
        r1 = eng.generate(p1, GREEDY)
        r2 = eng.generate(p2, GREEDY)
        assert eng.prefix_cache_stats()["hits"] >= 1, "schedule never hit the prefix cache"
        outs[dt] = (r1.token_ids, r2.token_ids)
    assert outs["int8"] == outs[None]


def test_cache_dtype_validated_and_normalized():
    params = init_params(CFG, jax.random.PRNGKey(0))
    for bad in ("fp8", "float16", "int4", "INT8 "):
        with pytest.raises(ValueError, match="cache_dtype"):
            LLMEngine(CFG, params, max_num_seqs=2, max_seq_len=64, cache_dtype=bad)
    # aliases normalize; None inherits the model dtype
    assert normalize_cache_dtype("bf16") == "bfloat16"
    assert normalize_cache_dtype("F32") == "float32"
    eng = LLMEngine(CFG, params, max_num_seqs=2, max_seq_len=64, cache_dtype="bf16")
    assert eng.kv_dtype == "bfloat16" and not eng.kv_quant
    assert LLMEngine(CFG, params, max_num_seqs=2, max_seq_len=64).kv_dtype == "float32"


def test_kv_cache_stats_scale_inclusive(copy_params, copy_prompts):
    """bytes/token counts the f32 scales (2*L*kv*(hd+4)), allocated HBM
    matches the device arrays, and occupancy tracks admissions."""
    eng = _engine(copy_params, "int8", "paged")
    st = eng.kv_cache_stats()
    want = 2 * CFG.num_layers * CFG.num_kv_heads * (CFG.hd + 4)
    assert st["dtype"] == "int8" and st["quantized"] and st["bytes_per_token"] == want
    assert st["allocated_bytes"] == sum(int(a.nbytes) for a in eng.pool.values())
    assert st["occupied_tokens"] == 0 and st["pages_free"] == st["pages_total"]
    eng.add_request(copy_prompts[0], SamplingParams(max_tokens=4))
    eng.step()
    mid = eng.kv_cache_stats()
    assert mid["occupied_tokens"] >= len(copy_prompts[0])
    assert mid["occupied_bytes"] == mid["occupied_tokens"] * want
    assert mid["slots_in_use"] == 1 and mid["pages_free"] < mid["pages_total"]
    while eng.has_unfinished():
        eng.step()
    # int8 vs bf16 byte ratio is the capacity multiplier the bench gates
    bf = bytes_per_token(CFG.num_layers, CFG.num_kv_heads, CFG.hd, "bfloat16")
    assert bf / want == pytest.approx(2 * CFG.hd / (CFG.hd + 4), rel=1e-6)
