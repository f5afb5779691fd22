"""The ``keye_vl`` family file and the cell ``keye-vl-2.0-d6.longdoc-24k``: the configuration keeps
every published key (depth alone is cut), the family's counts are ISSUE 58's arithmetic and the
program's, the least a prefill, the index scores, attention under the choice and a decode step's
read must do is counted by hand at a small size, the reference refuses nothing at toy size and each
of its four planted faults reads otherwise, and the five new readers read a made-up summary, trace
and flight log, and nothing where there is nothing."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark import common, scopes, traffic
from benchmark.peaks import peaks_of

BENCH = common.load_benchmark()
CONFIG, CELL = "keye-vl-2.0-30b-a3b-d6", "keye-vl-2.0-d6.longdoc-24k"
# the catalog row's ``config`` (guide model-configs, architectures.jsonl, Keye-VL-2.0-30B-A3B), key for key
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48, "mlp_only_layers": [], "model_type": "KeyeVL2", "moe_intermediate_size": 768,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06, "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default", "type": "default"},
    "rope_theta": 10000000, "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                                          "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936,
}
SERVE_READERS = {"prefill_ms_per_ktok.longdoc", "decode_device_ms.longdoc", "prefill_bubble_ms", "prefill_stage_idle_ms", "prefill_mixer_ms_per_ktok",
                 "prefill_ffn_ms_per_ktok", "prefill_step_roofline", "moe_block_fill", "moe_blocks_share", "prefill_indexed_ms_per_ktok",
                 "prefill_indexer_ms_per_ktok", "indexer_score_roofline", "indexed_attend_roofline", "indexed_decode_roofline"}
NEW = (("prefill_indexed_ms_per_ktok", "ms", "lower", "step programs"), ("prefill_indexer_ms_per_ktok", "ms", "lower", "step programs"),
       ("indexer_score_roofline", "%", "higher", "kernels"), ("indexed_attend_roofline", "%", "higher", "kernels"),
       ("indexed_decode_roofline", "%", "higher", "kernels"))


@pytest.fixture(scope="module")
def c():
    with open(os.path.join(common.HERE, "configs", CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def family():
    return common.load_family("keye_vl")


def test_the_configuration_keeps_every_published_key_and_cuts_depth_alone(c, family):
    assert c["family"] == "keye_vl" and c["reduced"] == ["num_hidden_layers"] and c["reduced_from"] == {"num_hidden_layers": 48}
    assert set(c["why_reduced"]) == set(c["reduced"]) and c["layers_held"] == [24, 30]
    for k, v in PUBLISHED.items():
        assert c[k] == (6 if k == "num_hidden_layers" else v), k
    assert family.published_depth(c) == 48
    d = c["deployment"]
    assert (d["pipeline_stages"], d["layers_per_stage"], d["pipeline_stages"] * d["layers_per_stage"]) == (8, 6, 48) and "left out" in d["vision_tower"]
    assert {"qk_norm", "indexer", "selection", "mrope", "q_chunk_size, kv_chunk_size", "router", "init_qk_norm", "initialisation", "vision tower"} <= set(c["assumed"])
    tol = c["tolerance"]
    assert 0 < tol["logprob_abs"] <= 0.25 and tol["why"] and max(tol["served"]) < tol["logprob_abs"] < min(tol["float8"] + [min(v) for v in tol["faults"].values()])
    assert set(tol["faults"]) == set(family.FAULTS)
    cfg = family.program_config(c, 24576)
    assert (str(cfg.stream_dtype), cfg.num_hidden_layers, cfg.rope_theta, cfg.residual_rescale_layers, cfg.qk_norm_init) == ("bfloat16", 6, 1e7, 96, c["init_qk_norm"])
    assert (cfg.hd, cfg.mrope_section, cfg.index_heads, cfg.index_dim, cfg.index_topk) == (128, (16, 24, 24), 16, 64, 2048)
    entry = {e["name"]: e for e in BENCH["configs"]}[CONFIG]
    assert entry["source"] == c["source"] and entry["reduced"] == c["reduced"] and entry["file"].endswith(CONFIG + ".json") and len(entry["why"]) <= 200
    sv = c["serving"]
    assert (sv["max_num_seqs"], sv["max_seq_len"], sv["max_ongoing_requests"]) == (12, 24576, 64) and "engine_kwargs" not in sv
    for key, value, says in (("attention_bias", True, "no attention bias"), ("tie_word_embeddings", True, "untied head"), ("mlp_only_layers", [3], "every layer"),
                             ("norm_topk_prob", False, "renormalises")):
        with pytest.raises(ValueError, match=says):
            family.program_config({**c, key: value}, 24576)
    with pytest.raises(ValueError, match="ONE key a position"):
        family.program_config({**c, "sa_config": {**c["sa_config"], "indexer_num_kv_heads": 2}}, 24576)


def test_the_cell_is_listed_and_what_stood_before_it_still_stands_in_its_order(c):
    """Listed, and never "last": the next PR appends after it."""
    names = [w["name"] for w in BENCH["workloads"]]
    cell = BENCH["workloads"][names.index(CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "longdoc-24k", 1) and len(cell["why"]) <= 200
    before = ["internlm2-1.8b.chat", "mistral-7b-d6.sft-2k", "internlm2-1.8b.longdoc", "nemotron-3-nano-ep2.chat", "qwen3-next-ep4.longdoc",
              "glm-4.7-flash-d8.longdoc-16k", "kimi-linear-ep4.longdoc", "minicpm-sala-d8.longdoc-12k", "smallthinker-21b-d8.longdoc-12k",
              "lfm2-24b-d10.longdoc-12k"]
    assert names[:10] == before and names.index(CELL) == 10 and [e["name"] for e in BENCH["configs"]].index(CONFIG) == 9
    listed = {m["name"] for k in ("end_to_end", "per_layer") for m in BENCH[k] if CELL in m.get("workloads", ())}
    assert listed == SERVE_READERS | {"serve_tokens_per_s"}, "tokens per second and what moves it; no time to a first token in a 24k cell"
    for k in ("end_to_end", "per_layer"):
        for m in BENCH[k]:
            if CELL in m.get("workloads", ()) and len(m["workloads"]) > 1:
                assert m["workloads"].index(CELL) == m["workloads"].index(before[-1]) + 1, m["name"]
    per = {m["name"]: m for m in BENCH["per_layer"]}
    for name, unit, better, layer in NEW:
        assert per[name] == {"name": name, "unit": unit, "better": better, "source": "device_trace", "layer": layer,
                             "moves": "serve_tokens_per_s", "workloads": [CELL]}
    assert all(common.load_reader(name) is not None for name in listed - {"serve_tokens_per_s"})
    mix = traffic.load_mix("longdoc-24k", CELL)
    assert (mix["kind"], mix["loop"], mix["clients"]) == ("serve", "closed", 16) and mix["clients"] == round(4 / 3 * c["serving"]["max_num_seqs"])
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 20500, "sigma": 0.10, "min": 16896, "max": 24320} and mix["output_len"] == {"dist": "uniform", "min": 64, "max": 192}
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] <= c["serving"]["max_seq_len"] and (mix["sampled_share"], mix["sampled"]) == (0.1, {"temperature": 0.8, "top_p": 0.95})
    from benchmark.serve_cell import default_buckets, warm_plan

    assert default_buckets(24576)[-2:] == [16384, 24576] and [b for b, _ in warm_plan(mix, default_buckets(24576))] == [24576], "one bucket"
    lengths = traffic.quantile_lengths(mix["prompt_len"], 400)
    assert min(lengths) > 8 * c["sa_config"]["topk"] and max(lengths) < 12 * c["sa_config"]["topk"], "every prompt is read at 8-12 x topk"


def test_the_counts_are_the_issues_arithmetic(c, family):
    p = family.layer_params(c)
    assert p["attention"] == 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 128 == 18_874_368 + 256
    assert p["indexer"] == 2048 * 1024 + 2048 * 64 + 2048 * 16 + 2 * 64 == 2_260_992 + 128 and p["router"] == 262_144
    assert p["expert"] == 3 * 2048 * 768 == 4_718_592 and 128 * p["expert"] == 603_979_776 and p["embed_and_head"] == 2 * 151936 * 2048 == 622_329_856
    layer = p["attention"] + p["indexer"] + p["router"] + 128 * p["expert"] + 2 * p["norm"]
    assert layer == 625_381_760 and round(2 * layer / 1e9, 2) == 1.25
    held = family.parameters_held(c)
    assert held == c["parameters"] == 6 * layer + 622_329_856 + 2048 == 4_374_622_464 and round(2 * held / 1e9, 2) == 8.75
    assert family.parameters_published(c) == c["parameters_published"] == 48 * layer + 622_329_856 + 2048 == 30_640_656_384
    assert family.program_config(c, 24576).num_params() == held
    # a token's matrix products in a layer: the experts' 75.5 MFLOP (LFM2's, to the digit: 8 x 768 against 4 x 1,536), attention's projections 37.7, the indexer 4.5
    assert 2 * 8 * p["expert"] == 75_497_472 and round(2 * (p["attention"] - 256) / 1e6, 1) == 37.7 and round(2 * (p["indexer"] - 128) / 1e6, 1) == 4.5
    # a position in the cache: a key and a value by head and the indexer's key, in six layers
    assert family.kv_bytes_per_token(c) == 6 * (2 * 4 * 128 + 64) * 2 == 13_056 and family.cache_bytes(c, 12, 24576) == 3_850_371_072
    from ray_tpu.llm.kv_cache import alloc_entries, entry_bytes_per_token

    cfg = family.program_config(c, 24576)
    assert entry_bytes_per_token(cfg.position_entries()) == 13_056
    cache = jax.eval_shape(lambda: alloc_entries(cfg.position_entries(), 12, 24576, cfg.ring_entries()))
    assert {n: a.shape for n, a in cache.items() if n != "length"} == {"k": (6, 12, 24576, 4, 128), "v": (6, 12, 24576, 4, 128), "k_idx": (6, 12, 24576, 64)}
    assert sum(a.size * a.dtype.itemsize for n, a in cache.items() if n != "length") == 3_850_371_072
    assert 0.25 * 16 * 2**30 < 2 * held + 3_850_371_072 < 0.75 * 16 * 2**30, "12.6 GB of the chip's 16 GiB"
    # the counters the program writes into its flight log are the family's counts from the same lengths
    lengths = [16896, 20500, 24320, 100]
    assert cfg.prefill_counters(4, 24576, lengths=lengths) == {"pairs_scored": 6 * sum(family.causal_pairs(n) for n in lengths),
                                                                 "pairs_chosen": 6 * sum(family.chosen_pairs(c, n) for n in lengths)}
    assert family.chosen_pairs(c, 5000) == sum(min(t + 1, 2048) for t in range(5000)) and family.chosen_pairs(c, 100) == 5050


def test_the_least_a_prefill_the_index_and_a_steps_read_must_do_by_hand_at_one_small_size(family):
    """Two layers, hidden 8, 4 heads of 4 over 2, an indexer of 2 x 2 that keeps 3, 4 experts of 6 top 2: every term written out."""
    c = {"hidden_size": 8, "num_hidden_layers": 2, "vocab_size": 16, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 4,
         "num_experts": 4, "num_experts_per_tok": 2, "moe_intermediate_size": 6,
         "sa_config": {"indexer_head_dim": 2, "indexer_num_heads": 2, "indexer_num_kv_heads": 1, "topk": 3}}
    q, kv = 4 * 4, 2 * 4
    attention, indexer, router, expert = 2 * 8 * q + 2 * 8 * kv + 2 * 4, 8 * 2 * 2 + 8 * 2 + 8 * 2 + 2 * 2, 8 * 4, 3 * 8 * 6
    p = family.layer_params(c)
    assert (p["attention"], p["indexer"], p["router"], p["expert"]) == (attention, indexer, router, expert)
    fixed = 2 * (attention + indexer + router + 2 * 8) + 8
    assert family.parameters_held(c) == fixed + 2 * 4 * expert + 2 * 16 * 8
    # prompts of 5 and 2: 15 + 3 causal pairs; a query reads min(t + 1, 3): 1 + 2 + 3 + 3 + 3 and 1 + 2
    assert family.causal_pairs(5) + family.causal_pairs(2) == 18 and family.chosen_pairs(c, 5) + family.chosen_pairs(c, 2) == 15
    assert family.indexer_score_least(c, pairs=18, tokens=7) == {"bytes": 7.0 * ((2 * 2 + 2) * 2 + 2 * 4), "flops": 18.0 * 2 * 2 * 2}
    assert family.indexed_prefill_least(c, pairs=15, tokens=7) == {"bytes": 7.0 * (2 * 4 + 2 * 2) * 4 * 2, "flops": 15.0 * 4 * 4 * 4}
    assert family.indexed_decode_least(c, rows_scored=20, rows_chosen=6) == {"bytes": (6.0 * 2 * kv + 20 * 2) * 2, "flops": 20.0 * 2 * 2 * 2 + 6 * 4 * 4 * 4}
    need = family.prefill_least(c, lengths=[5, 2], pairs_local=14.0, experts_hit=3.0)
    assert need["bytes"] == 2 * (fixed + 8 * 16 + 2 * 3.0 * expert + 7 * 8) + 7 * 2 * (2 * kv + 2) * 2
    matmul = 2 * (attention - 2 * 4 + indexer - 2 * 2 + router)
    assert need["flops"] == 2 * 7 * matmul + 2 * 2 * 8 * 16 + 2 * 2 * 14.0 * expert + 2 * 18 * 8 + 2 * 15 * 64
    step = family.decode_step_least(c, lanes=2.0, experts_hit=3.0, kv_tokens=20.0)
    assert step["bytes"] == 2 * (fixed + 8 * 16 + 2 * 8 + 2 * 3.0 * expert) + 2 * (6 * 2 * kv + 20 * 2) * 2  # 2 lanes x topk 3 rows chosen of the 20 held
    assert step["flops"] == 2 * 2.0 * (matmul + 2 * 2 * expert + 8 * 16) + 2 * (20 * 8 + 6 * 64)
    assert family.train_flops_per_token(c, 10) > 6 * (matmul + 2 * 2 * expert + 8 * 16)


def test_at_the_cells_size_the_chosen_work_is_a_fifth_of_a_masked_pass_and_a_steps_read_a_sixth_of_every_row(c, family):
    peaks = peaks_of("TPU v5 lite")
    n = 20500
    whole = family.prefill_least(c, lengths=[n], pairs_local=8.0 * n, experts_hit=128.0)
    assert whole["flops"] / peaks["bf16_flops"] > 5 * whole["bytes"] / peaks["hbm_bytes_per_s"], "bound by FLOPs"
    chosen, causal = family.chosen_pairs(c, n), family.causal_pairs(n)
    assert 0.18 < chosen / causal < 0.20, "2,048 of a mean 10,250 earlier positions"
    attend, score = family.indexed_prefill_least(c, chosen, n), family.indexer_score_least(c, causal, n)
    assert round(1e3 * attend["flops"] / peaks["bf16_flops"], 2) == 3.32 and round(1e3 * score["flops"] / peaks["bf16_flops"], 2) == 2.18  # ms a layer
    rows = family.indexed_decode_least(c, rows_scored=12 * 20000.0, rows_chosen=12 * 2048.0)
    assert rows["bytes"] == 12 * (2048 * 2048 + 20000 * 128) and rows["bytes"] / peaks["hbm_bytes_per_s"] > rows["flops"] / peaks["bf16_flops"], "bound by bytes: 0.1 ms a layer"
    assert round(12 * 20000 * 2048 / rows["bytes"], 1) == 6.1, "every row of k and v against the chosen rows and k_idx: 6.1 x"


def test_the_reference_refuses_nothing_at_toy_size_blocks_change_nothing_and_each_fault_reads_otherwise(family, monkeypatch):
    c = family.rehearsal({k: v for k, v in PUBLISHED.items() if k not in family.REHEARSAL_SIZES} | {"family": "keye_vl"})
    cfg = family.program_config(c, 128, remat=False)
    params = jax.jit(lambda k: family.init_params(cfg, k))(jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(params)) == family.parameters_held(c) == cfg.num_params()
    toks = [int(t) for t in np.random.RandomState(0).randint(1, c["vocab_size"] - 1, size=70)]
    assert [family.padded_length(n) for n in (1, 128, 129, 9000, 24576, 24577)] == [128, 128, 1024, 24576, 24576, 49152]
    lp = np.asarray(family.reference_logprobs(params, toks, c, 39, 70))
    assert lp.shape == (31, c["vocab_size"]) and np.isfinite(lp).all() and np.allclose(np.exp(lp).sum(-1), 1.0, atol=1e-5)
    # the blocks it goes in at the cell's size are not mathematics; nor is what follows a position
    monkeypatch.setattr(family, "QUERY_BLOCK", 16)
    family._attention.clear_cache()
    np.testing.assert_allclose(np.asarray(family.reference_logprobs(params, toks, c, 39, 70)), lp, atol=2e-5, rtol=0)
    monkeypatch.setattr(family, "PAD_TO", (96, 256))
    np.testing.assert_allclose(np.asarray(family.reference_logprobs(params, toks + [5] * 20, c, 39, 70)), lp, atol=2e-5, rtol=0)
    # the four wrong KINDS of selection, planted in the reference: every one reads otherwise past top-k 16, and none before it
    for fault in family.FAULTS:
        wrong = np.asarray(family.reference_logprobs(params, toks, c, 0, 70, fault=fault))
        right = np.asarray(family.reference_logprobs(params, toks, c, 0, 70))
        assert np.abs(wrong[39:] - right[39:]).max() > 1e-3, fault
        assert np.abs(wrong[:8] - right[:8]).max() < 1e-5 or fault == "half_topk", fault  # a query with at most 8 positions chooses nothing under any of them
    with pytest.raises(ValueError, match="a fault is one of"):
        family.reference_logprobs(params, toks, c, 0, 70, fault="none")


# ------------------------------------------------------------------------------------ the five readers
def _scope(s, calls=10):
    return {"s": s, "calls": calls, "flops": 0, "bytes": 0}


def _summary(indexed=True):
    mixers = ({"indexed": _scope(0.50), "indexed.score": _scope(0.10), "indexed.select": _scope(0.90), "indexed.attend": _scope(3.0)} if indexed
              else {"sparse": _scope(0.7), "sparse.select": _scope(0.4)})
    step = {"indexed": _scope(0.1), "indexed.score": _scope(0.15), "indexed.select": _scope(0.25), "indexed.attend": _scope(0.2)} if indexed else {"sparse": _scope(0.2)}
    programs = {"jit_llm_hybrid_prefill": {"calls": 4, "device_s": 7.1, "leaf_s": 7.0, "ops": {}, "scopes": {**mixers, "moe.route": _scope(0.05), "moe.blocks": _scope(2.0), "unscoped": _scope(0.1)}},
                "jit_llm_hybrid_fused_step": {"calls": 100, "device_s": 1.2, "leaf_s": 1.2, "ops": {}, "scopes": {**step, "moe.blocks": _scope(0.3)}}}
    return {"chips": 1, "window_s": 9.0, "busy_s": 8.5, "programs": programs, "roles": {}}


@pytest.fixture
def obs(c, tmp_path, monkeypatch):
    """The ``obs`` of a traced run whose summary lies beside its trace, as ``scopes.summary`` keeps it."""
    def make(summary):
        monkeypatch.setattr(common, "ROOT", str(tmp_path))
        monkeypatch.setattr(scopes, "_memo", {})
        trace_dir = tmp_path / ".bench_out" / "toy.longdoc" / "trace"
        trace_dir.mkdir(parents=True, exist_ok=True)
        trace_host = [100.0, 105.0]
        (trace_dir / "scopes.json").write_text(json.dumps({"trace_host": trace_host, "summary": summary}))
        requests = {"a": {"admit_t": 101.0, "prompt_tokens": 18000}, "b": {"admit_t": 104.0, "prompt_tokens": 24000},
                    "c": {"admit_t": 99.0, "prompt_tokens": 20000}, "d": {"admit_t": None, "prompt_tokens": 21000}}
        return {"window": [60.0, 105.0], "cell": {"name": "toy.longdoc"}, "config": c, "peaks": peaks_of("TPU v5 lite"),
                "worker": {"trace": {"trace_host": trace_host}, "requests": requests}}
    return make


def test_the_two_prefill_times_on_a_made_up_summary_and_on_nothing(obs):
    whole, indexer = common.load_reader("prefill_indexed_ms_per_ktok"), common.load_reader("prefill_indexer_ms_per_ktok")
    # two prompts admitted in the stretch, 42,000 tokens: the scope whole (its sub-scopes among it) and the scoring and the choice alone, a 1,000 of them
    assert whole(obs(_summary())) == pytest.approx(4.5 * 1e3 / 42.0) and indexer(obs(_summary())) == pytest.approx(1.0 * 1e3 / 42.0)
    assert whole(obs(_summary(indexed=False))) is None and indexer(obs(_summary(indexed=False))) is None
    o = obs(_summary())
    o["worker"]["requests"] = {"c": {"admit_t": 99.0, "prompt_tokens": 20000}}
    assert whole(o) is None and indexer(o) is None and whole({"cell": {"name": "toy.longdoc"}}) is None and indexer({}) is None


def test_the_three_rooflines_on_a_made_up_summary_and_flight_log(c, family, obs, monkeypatch):
    """Two prompts of 18,000 and 24,000 admitted in the stretch, six layers: their causal pairs at
    2,048 FLOPs a pair and their chosen pairs at 16,384 over 197 TFLOP/s against the seconds under
    the scopes; twelve lanes at 20,000 positions read 12 x (2,048 x 2 KB + 20,000 x 128 B) a layer."""
    from benchmark import flight

    score, attend, decode = (common.load_reader(n) for n in ("indexer_score_roofline", "indexed_attend_roofline", "indexed_decode_roofline"))
    lengths = (18000, 24000)
    causal, chosen = sum(family.causal_pairs(n) for n in lengths), sum(family.chosen_pairs(c, n) for n in lengths)
    steps = ([{"t": 101.0, "admitted": 1, "prefill_tokens": 18000, "pairs_scored": 6 * family.causal_pairs(18000), "pairs_chosen": 6 * family.chosen_pairs(c, 18000)},
              {"t": 104.0, "admitted": 1, "prefill_tokens": 24000, "pairs_scored": 6 * family.causal_pairs(24000), "pairs_chosen": 6 * family.chosen_pairs(c, 24000)},
              {"t": 99.0, "admitted": 1, "prefill_tokens": 20000, "pairs_scored": 6 * family.causal_pairs(20000), "pairs_chosen": 6 * family.chosen_pairs(c, 20000)}]  # before the stretch
             + [{"t": 101.5 + 0.1 * n, "rows_scored": 6 * 12 * 20000, "rows_chosen": 6 * 12 * 2048} for n in range(10)] + [{"t": 103.0, "phase": "mixed"}])
    monkeypatch.setattr(flight, "records", lambda obs: {"steps": steps, "requests": {}})
    peaks = peaks_of("TPU v5 lite")
    o = obs(_summary())
    assert score(o) == pytest.approx(100.0 * 6 * (causal * 2048 / peaks["bf16_flops"]) / 1.0) and 2.5 < score(o) < 3.5
    assert attend(o) == pytest.approx(100.0 * 6 * (chosen * 16384 / peaks["bf16_flops"]) / 3.0) and 1.2 < attend(o) < 1.8
    one_layer = 12 * (2048 * 2048 + 20000 * 128)
    assert decode(o) == pytest.approx(100.0 * 6 * (one_layer / peaks["hbm_bytes_per_s"]) * 100 / 0.6) and 9.5 < decode(o) < 10.5
    # nothing to read: off the chip (no peaks), a program without the scopes, a stretch without the rows, no flight log, no trace
    for read in (score, attend, decode):
        assert read({k: v for k, v in o.items() if k != "peaks"}) is None and read(obs(_summary(indexed=False))) is None
    o = obs(_summary())
    monkeypatch.setattr(flight, "records", lambda obs: {"steps": [{"t": 103.0, "phase": "mixed"}], "requests": {}})
    assert score(o) is None and attend(o) is None and decode(o) is None
    monkeypatch.setattr(flight, "records", lambda obs: None)
    assert score(o) is None and attend(o) is None and decode(o) is None and score({}) is None and attend({}) is None and decode({}) is None
