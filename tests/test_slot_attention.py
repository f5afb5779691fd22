"""``ops/slot_attention``: the decode step's attention over the stacked slot cache. The kernel
(run by the Pallas interpreter: the body the TPU compiles) against the XLA form it stands in for,
at toy sizes of the three tiles the cells have; what it must not read; the gate that chooses
between the two forms; and the two callers (``model_runner.decode_step``, a hybrid's attention
layers) with the kernel forced on, against themselves with it off."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import slot_attention as sa

S, BLK, L = 64, 16, 3
# (kv heads, head_dim, query heads): InternLM2-1.8B, Nemotron-3-Nano, Qwen3-Next
TILES = {"kv8_hd128": (8, 128, 16), "kv2_hd128": (2, 128, 32), "kv2_hd256": (2, 256, 16)}
# the index of each lane's new token (it attends 0..length): nothing before it, one position, a
# block less one, exactly a block, a block plus one, the last position, and lanes that differ
LENGTHS = {"zero": (0, 0), "one": (1, 1), "block_less_one": (BLK - 2, BLK - 2), "a_block": (BLK - 1, BLK - 1),
           "a_block_plus_one": (BLK, BLK), "last": (S - 1, S - 1), "mixed": (0, 3 * BLK, BLK - 1, S - 1, BLK, 7)}


def _inputs(tile, B, dtype=jnp.bfloat16, seed=0):
    kv, hd, nh = TILES[tile]
    kq, kk, kvv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (B, nh, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(kk, (L, B, S, kv, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(kvv, (L, B, S, kv, hd), jnp.float32).astype(dtype)
    return q, k, v, kv


@pytest.mark.parametrize("lengths", list(LENGTHS))
@pytest.mark.parametrize("tile", list(TILES))
def test_kernel_equals_the_xla_form(tile, lengths):
    lens = jnp.asarray(LENGTHS[lengths], jnp.int32)
    q, k, v, kv = _inputs(tile, len(lens))
    for layer in (0, L - 1):
        want = sa.attend_rows(q, k[layer], v[layer], lens, kv)
        got = sa.attend_kernel(q, k, v, jnp.int32(layer), lens + 1, block=BLK, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("tile", list(TILES))
def test_dead_blocks_are_neither_fetched_into_the_result_nor_multiplied_by_zero(tile):
    """Every block past a lane's last live one, and every block of an unbound lane, holds NaN: the
    bound lanes' outputs are bit for bit what they were, the unbound lanes' are zeros."""
    lens = jnp.asarray(LENGTHS["mixed"], jnp.int32)
    live = jnp.asarray([True, True, False, True, False, True])
    q, k, v, kv = _inputs(tile, len(lens), seed=1)
    bound = jnp.where(live, lens + 1, 0)
    first_dead = -(-bound // BLK) * BLK  # [B]: the first position of the lane's first dead block
    dead = (jnp.arange(S)[None, :] >= first_dead[:, None])[None, :, :, None, None]
    run = partial(sa.attend_kernel, block=BLK, interpret=True)
    clean = run(q, k, v, 1, bound)
    poisoned = run(q, jnp.where(dead, jnp.nan, k), jnp.where(dead, jnp.nan, v), 1, bound)
    np.testing.assert_array_equal(np.asarray(poisoned), np.asarray(clean))
    assert not np.asarray(clean)[~np.asarray(live)].any() and np.isfinite(np.asarray(clean)).all()
    want = sa.attend_rows(q, k[1], v[1], lens, kv)
    np.testing.assert_allclose(np.asarray(clean)[np.asarray(live)], np.asarray(want)[np.asarray(live)], atol=2e-6, rtol=2e-6)


def test_float32_rows_and_a_layer_index_that_is_traced():
    lens = jnp.asarray(LENGTHS["mixed"], jnp.int32)
    q, k, v, kv = _inputs("kv2_hd128", len(lens), dtype=jnp.float32, seed=2)
    got = jax.jit(lambda i: sa.attend_kernel(q, k, v, i, lens + 1, block=BLK, interpret=True))(jnp.int32(2))
    np.testing.assert_allclose(np.asarray(got), np.asarray(sa.attend_rows(q, k[2], v[2], lens, kv)), atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("case, want", [
    ((4096, 8, 128, 2), 512), ((4096, 2, 128, 2), 2048), ((4096, 2, 256, 2), 1024), ((2048, 16, 128, 2), 256),
    ((64, 2, 8, 4), 64), ((96, 8, 128, 2), 32), ((100, 8, 128, 2), 4),
])
def test_block_positions_follow_the_bytes_a_position_takes(case, want):
    assert sa.block_positions(*case) == want
    assert case[0] % want == 0


def _on_a_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("args, kw, word", [
    ((jnp.bfloat16, 16, 8, 128, 4096), {"sharded": True}, "shard_map"),
    ((jnp.int8, 16, 8, 128, 4096), {"quantized": True}, "int8"),
    ((jnp.float32, 16, 8, 128, 4096), {}, "float32"),
    ((jnp.bfloat16, 24, 8, 64, 4096), {}, "head_dim 64"),  # 16 or 32 over 8 go through since PR 53: two heads a 128-lane row
    ((jnp.bfloat16, 16, 8, 32, 4096), {}, "head_dim 32"),
    ((jnp.bfloat16, 16, 2, 256, 4096), {}, "copy of the whole cache"),
    ((jnp.bfloat16, 24, 3, 128, 4096), {}, "3 kv heads"),
    ((jnp.bfloat16, 8, 8, 128, 4096), {}, "8 query heads"),
    ((jnp.bfloat16, 16, 8, 128, 48), {}, "48 positions"),
])
def test_the_gate_refuses_with_a_reason(monkeypatch, args, kw, word):
    assert "backend 'cpu'" in sa.refusal(*args, **kw)  # off the TPU: always the XLA form
    _on_a_tpu(monkeypatch)
    assert word in sa.refusal(*args, **kw)


@pytest.mark.parametrize("tile", ["kv8_hd128", "kv2_hd128"])
def test_the_gate_lets_through_what_has_been_compiled(monkeypatch, tile):
    kv, hd, nh = TILES[tile]
    _on_a_tpu(monkeypatch)
    assert sa.refusal(jnp.bfloat16, nh, kv, hd, 4096) is None


# ------------------------------------------------------------------------- the two callers
def _force_kernel(monkeypatch):
    """What a TPU decides for a bfloat16 cache, here: the kernel, interpreted."""
    monkeypatch.setattr(sa, "refusal", lambda *a, **k: None)


def _llama():
    from ray_tpu.models.llama import LlamaConfig, init_params

    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=48, num_layers=3, num_heads=4, num_kv_heads=2,
                      head_dim=8, max_seq_len=S, dtype="float32", remat=False)
    return cfg, init_params(cfg, jax.random.PRNGKey(3))


def _slot_cache(cfg, lengths, seed=0):
    shape = (cfg.num_layers, len(lengths), S, cfg.num_kv_heads, cfg.hd)
    k, v = jax.random.split(jax.random.PRNGKey(seed))
    return {"k": jax.random.normal(k, shape, jnp.float32), "v": jax.random.normal(v, shape, jnp.float32),
            "length": jnp.asarray(lengths, jnp.int32)}


def test_decode_step_with_the_kernel_equals_decode_step_without(monkeypatch):
    from ray_tpu.llm import model_runner as mr

    cfg, params = _llama()
    cache = _slot_cache(cfg, (0, 5, S - 1, S, 31, 32))
    tokens = jnp.asarray([7, 0, 63, 21, 40, 2], jnp.int32)
    want_logits, want_cache = jax.jit(partial(mr.decode_step, cfg=cfg))(params, cache, tokens)
    _force_kernel(monkeypatch)
    logits, new_cache = jax.jit(partial(mr.decode_step, cfg=cfg))(params, cache, tokens)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want_logits), atol=2e-5, rtol=0)
    for name in want_cache:
        np.testing.assert_allclose(np.asarray(new_cache[name]), np.asarray(want_cache[name]), atol=2e-5, rtol=0, err_msg=name)
    # an unbound lane reads nothing and changes no other lane's logits
    live = jnp.asarray([True, False, True, True, False, True])
    masked, _ = jax.jit(partial(mr.decode_step, cfg=cfg))(params, cache, tokens, live=live)
    np.testing.assert_array_equal(np.asarray(masked)[np.asarray(live)], np.asarray(logits)[np.asarray(live)])


def test_chained_decode_steps_with_the_kernel_equal_single_steps(monkeypatch):
    """The drafter's use (``spec/drafter.py::draft_steps``): k + 1 steps chained inside one program
    with the length lane overridden. The kernel takes the lengths as an argument, so masking stays
    a pure function of the carried cache: the same proposals as single steps, one program each."""
    from ray_tpu.llm import model_runner as mr
    from ray_tpu.llm.spec.drafter import draft_steps

    _force_kernel(monkeypatch)
    cfg, params = _llama()
    k, lengths = 3, jnp.asarray([4, 0, S - 5, 15, 16], jnp.int32)
    cache = _slot_cache(cfg, (9, 9, 9, 9, 9), seed=1)
    hist = jax.random.randint(jax.random.PRNGKey(5), (5, 12), 0, cfg.vocab_size, jnp.int32)
    hist_len = jnp.asarray([4, 1, 11, 8, 2], jnp.int32)
    proposals, new_cache = jax.jit(partial(draft_steps, cfg=cfg, k=k))(params, cache, hist, hist_len, lengths)
    step = jax.jit(partial(mr.decode_step, cfg=cfg))
    tok, want, c = hist[jnp.arange(5), hist_len - 1], [], {**cache, "length": lengths}
    for _ in range(k + 1):
        logits, c = step(params, c, tok)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        want.append(tok)
    np.testing.assert_array_equal(np.asarray(proposals), np.stack(want[:k], axis=1))
    for name in c:
        np.testing.assert_allclose(np.asarray(new_cache[name]), np.asarray(c[name]), atol=1e-6, rtol=0, err_msg=name)


def _greedy(engine, prompts, n):
    from ray_tpu.llm import SamplingParams

    outs = engine.generate(prompts, [SamplingParams(max_tokens=n, temperature=0.0) for _ in prompts])
    return [o.token_ids for o in outs]


def test_greedy_tokens_of_an_engine_run_equal_the_oracles(monkeypatch):
    """More requests than slots through the device-resident loop, so that lanes empty, are masked
    out of the kernel's reads by the engine, and are bound again."""
    from ray_tpu.llm import LLMEngine

    cfg, params = _llama()
    rs = np.random.RandomState(0)
    prompts = [[int(t) for t in rs.randint(1, 63, size=n)] for n in (3, 17, 9, 30, 5, 12)]
    kw = dict(max_num_seqs=3, max_seq_len=S, prefill_buckets=(8, 16, 32), enable_prefix_caching=False)
    want = _greedy(LLMEngine(cfg, params, **kw), prompts, 12)
    _force_kernel(monkeypatch)
    eng = LLMEngine(cfg, params, **kw)
    assert eng._attn_block == S, "the engine saw the kernel chosen: one block of 64 positions a lane at this toy size"
    assert _greedy(eng, prompts, 12) == want


def test_a_hybrids_attention_layers_take_the_same_op(monkeypatch):
    from benchmark.families import nemotron_h as family
    from ray_tpu.llm import LLMEngine
    from ray_tpu.models import nemotron_h as nh

    c = family.rehearsal({"conv_kernel": 4, "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
                          "routed_scaling_factor": 2.5, "norm_topk_prob": True, "layer_norm_epsilon": 1e-5,
                          "n_shared_experts": 1})
    cfg = family.program_config(c, 128, remat=False)
    params = jax.jit(lambda k: nh.init_params(cfg, k))(jax.random.PRNGKey(7))
    rs = np.random.RandomState(1)
    prompts = [[int(t) for t in rs.randint(1, c["vocab_size"] - 1, size=n)] for n in (5, 21, 9, 14, 3)]
    kw = dict(max_num_seqs=2, max_seq_len=128, prefill_buckets=(16, 32))
    want = _greedy(LLMEngine(cfg, params, **kw), prompts, 8)
    calls = []
    real = sa.attend_kernel
    monkeypatch.setattr(sa, "attend_kernel", lambda *a, **k: (calls.append(a[1].shape), real(*a, **k))[1])
    _force_kernel(monkeypatch)
    assert _greedy(LLMEngine(cfg, params, **kw), prompts, 8) == want
    assert calls and all(shape[0] == cfg.num_kv_layers for shape in calls), "the stacked leaf, not a layer's rows"
