"""Pallas paged-attention kernel (llm/pallas/paged_attn.py): the XLA
paged path is the token-identical oracle.

Everything here runs the kernel in INTERPRET mode (this container has no
TPU): slow but exact — the same kernel body TPU compiles, executed as
plain jax ops. The module is marked ``pallas`` so TPU CI can select
exactly these tests (``-m pallas``) while tier-1 keeps them (they are
not ``slow``).

The guarantees under test:

- IDENTITY: an ``attn_kernel="pallas"`` engine emits token-identical
  streams to the ``"xla"`` engine — both cache dtypes, greedy and
  seeded sampling, under admission waves, slot recycling and pool
  preemption; spec verify's wide-block attention riding the kernel
  matches the plain engine; prefix-hit admission (the chunked-prefill
  extend path) matches too.
- RAGGED BOUNDS: kernel == XLA at the page-boundary lengths that break
  off-by-one masking (0, 1, page_size, page_size+1).
- ALIASING CONTRACT: the kernel never reads the position being written
  this step — poisoning every lane's write target in the pool cannot
  change the output (the k_self/v_self in-registers split,
  `_paged_attn_batch`'s documented contract, third consumer).
- FALLBACK: attn_kernel is engine-validated; unsupported configs degrade
  to XLA with a one-time warning, never an error.
"""

import warnings

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from ray_tpu.llm import LLMEngine, SamplingParams  # noqa: E402
from ray_tpu.llm.kv_quant import quantize_heads  # noqa: E402
from ray_tpu.llm.paged_kv import _paged_attn_batch, _paged_attn_seq_batch  # noqa: E402
from ray_tpu.models.llama import LlamaConfig, init_params  # noqa: E402

pytestmark = [pytest.mark.pallas, pytest.mark.usefixtures("shared_step_programs")]  # engines of equal configurations compile their step programs once (conftest.py)

CFG = LlamaConfig.tiny(dtype="float32", remat=False, max_seq_len=256)
PAGE = 32


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


def _prompts(k, lo=8, hi=40, seed=0):
    rng = np.random.default_rng(seed)
    return [list(rng.integers(1, 255, size=int(rng.integers(lo, hi)))) for _ in range(k)]


def _engine(params, attn_kernel, dtype=None, **kw):
    kw.setdefault("kv_layout", "paged")
    kw.setdefault("page_size", PAGE)
    kw.setdefault("enable_prefix_caching", False)
    return LLMEngine(
        CFG, params, max_num_seqs=3, max_seq_len=128,
        cache_dtype=dtype, attn_kernel=attn_kernel, **kw,
    )


def _streams(eng, prompts, sp):
    return [r.token_ids for r in eng.generate(prompts, sp)]


# ----------------------------------------------------------- engine identity
@pytest.mark.parametrize(
    "dtype,temp",
    [(None, 0.0), (None, 0.8), ("int8", 0.0), ("int8", 0.8)],
    ids=["fp-greedy", "fp-seeded", "int8-greedy", "int8-seeded"],
)
def test_kernel_token_identical_under_scheduler_churn(params, dtype, temp):
    """6 prompts through 3 slots over an 11-page pool: admission waves,
    slot recycling AND recompute-style preemption all happen, and the
    kernel engine's streams must equal the XLA engine's token for token
    (same seed -> same PRNG lanes, so seeded sampling is deterministic
    per engine and comparable across them)."""
    sp = SamplingParams(temperature=temp, max_tokens=10)
    prompts = _prompts(6, seed=3)
    kw = dict(num_pages=11, seed=5)
    a = _engine(params, "xla", dtype, **kw)
    b = _engine(params, "pallas", dtype, **kw)
    assert b.attn_kernel == "pallas"
    out_a = _streams(a, prompts, sp)
    out_b = _streams(b, prompts, sp)
    assert out_a == out_b, f"{dtype}/{temp}: kernel stream diverged from the XLA oracle"
    assert all(len(t) == 10 for t in out_b)
    assert a.preemption_count == b.preemption_count
    assert b.kv_cache_stats()["attn_kernel"] == "pallas"
    assert a.kv_cache_stats()["attn_kernel"] == "xla"


def test_spec_verify_rides_kernel_token_identical(params):
    """Spec verify's wide-block attention on the kernel: the speculative
    pallas engine must match the PLAIN xla engine (transitively locking
    kernel == xla on the k+1-wide `_paged_attn_seq_batch` path), with the
    spec path demonstrably engaged."""
    from ray_tpu.llm.spec import SpecConfig

    sp = SamplingParams(temperature=0.0, max_tokens=12)
    prompts = _prompts(4, seed=11)
    plain = _engine(params, "xla")
    spec = _engine(params, "pallas", speculative=SpecConfig(drafter="ngram", k=3))
    out_p = _streams(plain, prompts, sp)
    out_s = _streams(spec, prompts, sp)
    assert out_s == out_p, "spec-on-kernel diverged from the plain XLA oracle"
    st = spec.spec_stats()
    assert st["rounds"] > 0, "spec path never engaged"


def test_prefix_hit_extend_rides_kernel_token_identical(params):
    """Prefix-cache-hit admission re-attends the suffix through
    extend_attn_paged — the kernel's chunked-prefill consumer — and must
    stay token-identical to the XLA engine on the same hit."""
    sp = SamplingParams(temperature=0.0, max_tokens=8)
    base = list(np.random.default_rng(4).integers(1, 255, size=96))
    pair = [base, base[:64] + [9, 8, 7]]
    outs = {}
    for ak in ("xla", "pallas"):
        eng = _engine(params, ak, enable_prefix_caching=True, prefix_block=64)
        outs[ak] = [_streams(eng, [p], sp)[0] for p in pair]
        assert eng.prefix_cache_stats().get("hits", 0) >= 1, "fixture must actually hit"
    assert outs["pallas"] == outs["xla"]


# ----------------------------------------------------- kernel-level contracts
def _rand_pool(rng, P, nkv, hd, quant):
    k = jnp.asarray(rng.standard_normal((P, PAGE, nkv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((P, PAGE, nkv, hd)), jnp.float32)
    if not quant:
        return k, v, None, None
    kq, ks = quantize_heads(k)
    vq, vs = quantize_heads(v)
    return kq, vq, jnp.transpose(ks, (0, 2, 1)), jnp.transpose(vs, (0, 2, 1))


@pytest.mark.parametrize("quant", [False, True], ids=["fp", "int8"])
def test_ragged_lengths_at_page_boundaries(quant):
    """lengths 0, 1, page_size and page_size+1 — the off-by-one corners
    of the page mask — agree between the kernel and the XLA scan."""
    rng = np.random.default_rng(0)
    B, nkv, rep, hd, P = 4, 4, 2, 32, 9
    pool_k, pool_v, ksc, vsc = _rand_pool(rng, P, nkv, hd, quant)
    qg = jnp.asarray(rng.standard_normal((B, nkv, rep, hd)), jnp.float32)
    table = jnp.asarray(rng.integers(1, P, size=(B, 4)), jnp.int32)
    k_self = jnp.asarray(rng.standard_normal((B, nkv, hd)), jnp.float32)
    v_self = jnp.asarray(rng.standard_normal((B, nkv, hd)), jnp.float32)
    lengths = jnp.asarray([0, 1, PAGE, PAGE + 1], jnp.int32)
    scale = 1.0 / np.sqrt(hd)
    o_x = _paged_attn_batch(qg, pool_k, pool_v, table, lengths, scale, k_self, v_self, ksc, vsc)
    o_p = _paged_attn_batch(qg, pool_k, pool_v, table, lengths, scale, k_self, v_self, ksc, vsc,
                            impl="pallas")
    np.testing.assert_allclose(np.asarray(o_x), np.asarray(o_p), atol=1e-5, rtol=1e-5)
    # wide-block twin at the same boundary starts (spec verify / extend)
    T = 3
    qs = jnp.asarray(rng.standard_normal((B, nkv, rep, T, hd)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((B, T, nkv, hd)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((B, T, nkv, hd)), jnp.float32)
    s_x = _paged_attn_seq_batch(qs, pool_k, pool_v, table, lengths, kc, vc, scale, ksc, vsc)
    s_p = _paged_attn_seq_batch(qs, pool_k, pool_v, table, lengths, kc, vc, scale, ksc, vsc,
                                impl="pallas")
    np.testing.assert_allclose(np.asarray(s_x), np.asarray(s_p), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_write_target_poison_cannot_reach_attention(impl):
    """The aliasing contract, regression-locked for BOTH impls: the
    current token's pool position (index ``lengths[b]``, where the
    separate append program will scatter) is poisoned with garbage, and
    the attention output must be bit-identical to the clean pool —
    proving the current position reaches attention only through the
    k_self/v_self registers, never a pool read."""
    rng = np.random.default_rng(7)
    B, nkv, rep, hd, P = 3, 4, 2, 32, 13
    pool_k, pool_v, _, _ = _rand_pool(rng, P, nkv, hd, False)
    qg = jnp.asarray(rng.standard_normal((B, nkv, rep, hd)), jnp.float32)
    # DISTINCT pages per (lane, slot), as the allocator guarantees — a
    # shared page would let the poison leak through a legitimate read
    table = jnp.asarray(rng.permutation(np.arange(1, 13)).reshape(B, 4), jnp.int32)
    k_self = jnp.asarray(rng.standard_normal((B, nkv, hd)), jnp.float32)
    v_self = jnp.asarray(rng.standard_normal((B, nkv, hd)), jnp.float32)
    lengths = jnp.asarray([5, PAGE, 2 * PAGE + 1], jnp.int32)
    scale = 1.0 / np.sqrt(hd)
    clean = _paged_attn_batch(qg, pool_k, pool_v, table, lengths, scale, k_self, v_self, impl=impl)
    pk, pv = np.asarray(pool_k).copy(), np.asarray(pool_v).copy()
    for b in range(B):
        pos = int(lengths[b])
        page_id = int(table[b, pos // PAGE])
        pk[page_id, pos % PAGE] = 1e9  # the write target the append program owns
        pv[page_id, pos % PAGE] = -1e9
    dirty = _paged_attn_batch(
        qg, jnp.asarray(pk), jnp.asarray(pv), table, lengths, scale, k_self, v_self, impl=impl
    )
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))


# ------------------------------------------------------- validation / fallback
def test_attn_kernel_engine_validation(params):
    with pytest.raises(ValueError, match="attn_kernel"):
        _engine(params, "triton")
    with pytest.raises(ValueError, match="paged"):
        LLMEngine(CFG, params, max_num_seqs=2, max_seq_len=128,
                  kv_layout="slots", attn_kernel="pallas")
    # slot engines still resolve (and report) the xla kernel
    eng = LLMEngine(CFG, params, max_num_seqs=2, max_seq_len=128, enable_prefix_caching=False)
    assert eng.attn_kernel == "xla"


def test_unsupported_config_degrades_with_warning_not_error(params, monkeypatch):
    """kernel_supported says no -> the engine refuses at construction
    with the typed error (an explicit kernel request is never served by
    the XLA path under the kernel's name). The test keeps its old name:
    it is the rewritten fallback test, not a new one."""
    import ray_tpu.llm.pallas.paged_attn as pa
    from ray_tpu.exceptions import AttnKernelUnavailableError

    monkeypatch.setattr(pa, "kernel_supported", lambda *a, **k: (False, "simulated platform gap"))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        with pytest.raises(AttnKernelUnavailableError, match="simulated platform gap"):
            _engine(params, "pallas")
    assert not any("falling back" in str(x.message) for x in w)
    # the typed error is a ValueError, like every other constructor refusal
    assert issubclass(AttnKernelUnavailableError, ValueError)
