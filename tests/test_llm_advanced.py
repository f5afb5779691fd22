"""LLM frontier features: prefix caching, prefill/decode disaggregation,
Data batch inference.

Reference test strategy: python/ray/llm/tests/serve/deployments/
prefill_decode_disagg/ (disagg serve graph), vllm_models.py:215-228
(enable_prefix_caching), llm/_internal/batch/processor tests (dataset ->
engine pool -> dataset). Parity here is exact greedy-token equality with
the full-recompute oracle.
"""

import pytest

jax = pytest.importorskip("jax")

import ray_tpu  # noqa: E402
from ray_tpu.llm import LLMEngine, SamplingParams  # noqa: E402
from ray_tpu.models.llama import LlamaConfig, init_params  # noqa: E402
from test_llm import full_forward_greedy as oracle  # noqa: E402 - greedy by the padded full forward, of the same toy configuration

pytestmark = pytest.mark.usefixtures("shared_step_programs")  # many engines of equal configurations: their step programs compile once (conftest.py)

CFG = LlamaConfig.tiny(dtype="float32", remat=False, max_seq_len=128)
GREEDY = SamplingParams(max_tokens=6, temperature=0.0)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


# ---------------------------------------------------------------- prefix cache


def test_prefix_reuse_parity_and_stats(params):
    eng = LLMEngine(CFG, params, max_num_seqs=2, max_seq_len=128, prefix_block=16)
    base = [(i % 50) + 1 for i in range(40)]
    p1, p2 = base + [7, 8, 9], base + [30, 31]
    o1 = eng.generate(p1, GREEDY)
    assert eng.prefix_cache_stats()["entries"] == 1
    o2 = eng.generate(p2, GREEDY)
    s = eng.prefix_cache_stats()
    assert s["hits"] == 1 and s["tokens_saved"] == 32, s
    assert o1.token_ids == oracle(params, p1, 6)
    assert o2.token_ids == oracle(params, p2, 6)  # through insert+extend


def test_prefix_full_prompt_still_leaves_suffix(params):
    """A prompt exactly equal to a cached prefix must re-attend >=1 token
    (logits come from the suffix extend, never from a bare insert)."""
    eng = LLMEngine(CFG, params, max_num_seqs=2, max_seq_len=128, prefix_block=8)
    p = [(i % 30) + 1 for i in range(16)]  # exactly 2 blocks
    o1 = eng.generate(p, GREEDY)
    o2 = eng.generate(p, GREEDY)
    s = eng.prefix_cache_stats()
    assert s["hits"] == 1 and s["tokens_saved"] == 8, s  # capped at len-1 -> 8, not 16
    assert o1.token_ids == o2.token_ids == oracle(params, p, 6)


def test_prefix_eviction_under_budget(params):
    # entries pad to the 64-token prefill bucket: budget fits exactly one
    tiny = 2 * CFG.num_layers * 64 * CFG.num_kv_heads * CFG.hd * 4 + 1
    eng = LLMEngine(CFG, params, max_num_seqs=2, max_seq_len=128, prefix_block=16, prefix_cache_bytes=tiny)
    eng.generate([(i % 20) + 1 for i in range(20)], GREEDY)
    eng.generate([(i % 20) + 40 for i in range(20)], GREEDY)
    s = eng.prefix_cache_stats()
    assert s["evictions"] >= 1 and s["bytes"] <= tiny, s


def test_prefix_disabled(params):
    eng = LLMEngine(CFG, params, max_num_seqs=2, max_seq_len=128, enable_prefix_caching=False)
    p = [(i % 30) + 1 for i in range(40)]
    assert eng.generate(p, GREEDY).token_ids == oracle(params, p, 6)
    assert eng.prefix_cache_stats() == {}


# ------------------------------------------------------- disaggregation (engine)


def test_disagg_engine_parity(params):
    pre = LLMEngine(CFG, params, max_num_seqs=1, max_seq_len=128, enable_prefix_caching=False)
    dec = LLMEngine(CFG, params, max_num_seqs=2, max_seq_len=128, enable_prefix_caching=False)
    prompts = [[3, 17, 40, 7, 99], [5, 6, 7]]
    kvs = [pre.prefill_remote(p) for p in prompts]
    rids = [dec.add_prefilled(kv, GREEDY) for kv in kvs]
    finals = {}
    while dec.has_unfinished():
        for o in dec.step():
            if o.finished:
                finals[o.request_id] = o
    for rid, p in zip(rids, prompts):
        assert finals[rid].token_ids == oracle(params, p, 6), p


# -------------------------------------------------------- disaggregation (serve)


def test_disagg_serve_graph(params):
    from ray_tpu import serve
    from ray_tpu.serve.llm import LLMConfig, build_pd_disagg_deployment

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8)
    try:
        serve.start()
        app = build_pd_disagg_deployment(
            LLMConfig(
                model_config=CFG,
                params=params,
                engine_kwargs={"max_num_seqs": 2, "max_seq_len": 64},
            ),
            num_prefill_replicas=1,
            num_decode_replicas=2,
        )
        h = serve.run(app, name="pd", blocking_timeout_s=240)
        prompt = [3, 17, 40, 7, 99]
        outs = [
            h.generate.remote(prompt, {"max_tokens": 6, "temperature": 0.0}).result(timeout_s=240)
            for _ in range(4)
        ]
        want = oracle(params, prompt, 6)
        for out in outs:
            assert out["token_ids"] == want
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


# ----------------------------------------------------------- batch inference


def test_data_batch_inference(params):
    from ray_tpu import data as rtd
    from ray_tpu.llm.batch import build_llm_processor

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=6)
    try:
        def engine_factory():
            import jax as _jax

            from ray_tpu.llm import LLMEngine as _E
            from ray_tpu.models.llama import LlamaConfig as _C, init_params as _ip

            cfg = _C.tiny(dtype="float32", remat=False, max_seq_len=64)
            return _E(cfg, _ip(cfg, _jax.random.PRNGKey(0)), max_num_seqs=4, max_seq_len=64)

        ds = rtd.from_items([{"prompt": [i % 11 + 1, i % 7 + 1, 5]} for i in range(24)])
        proc = build_llm_processor(
            engine_factory,
            sampling=SamplingParams(max_tokens=4, temperature=0.0),
            batch_size=8,
            concurrency=2,
        )
        rows = proc(ds).take_all()
        assert len(rows) == 24
        assert all(len(r["generated"]) == 4 for r in rows)
        assert all(r["generated_finish_reason"] == "length" for r in rows)
        # spot-check parity on one row
        local = init_params(LlamaConfig.tiny(dtype="float32", remat=False, max_seq_len=64), jax.random.PRNGKey(0))
        row = rows[0]
        assert list(row["generated"]) == oracle(local, [int(t) for t in row["prompt"]], 4)
    finally:
        ray_tpu.shutdown()
