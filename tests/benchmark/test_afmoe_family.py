"""The ``afmoe`` family file and the cell ``trinity-large-ep8-d5.longdoc-12k``: the configuration keeps
every key of the catalog row and cuts only what ``reduced`` lists, the family's counts are ISSUE 64's
arithmetic and the program's, the least a prefill, the window kernels and the expert blocks must do is
counted by hand at a small size, the reference refuses nothing at toy size, and the new readers read a
made-up summary and flight log, and nothing where there is nothing."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark import common, flight, scopes, traffic
from benchmark.peaks import peaks_of

BENCH = common.load_benchmark()
CONFIG, CELL = "trinity-large-preview-ep8-d5", "trinity-large-ep8-d5.longdoc-12k"
# the catalog row's ``config`` (guide model-configs, architectures.jsonl, Trinity-Large-Preview), key for key, but its 60 layer_types
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu", "hidden_size": 3072, "intermediate_size": 12288, "load_balance_coeff": 5e-05,
    "max_position_embeddings": 262144, "model_type": "afmoe", "moe_intermediate_size": 3072, "mup_enabled": True, "n_group": 1, "num_attention_heads": 48,
    "num_dense_layers": 6, "num_expert_groups": 1, "num_experts": 256, "num_experts_per_tok": 4, "num_hidden_layers": 60, "num_key_value_heads": 8,
    "num_limited_groups": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000, "route_norm": True, "route_scale": 2.448,
    "score_func": "sigmoid", "sliding_window": 4096, "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True, "vocab_size": 200192,
}
REDUCED = {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 32, "vocab_size": 25024}
SERVE_READERS = {"prefill_ms_per_ktok.longdoc", "decode_device_ms.longdoc", "prefill_bubble_ms", "prefill_stage_idle_ms", "prefill_mixer_ms_per_ktok",
                 "prefill_ffn_ms_per_ktok", "prefill_step_roofline", "moe_block_fill", "moe_blocks_share", "moe_blocks_roofline", "moe_fetches_per_expert",
                 "prefill_gate_ms_per_ktok", "prefill_swa_ms_per_ktok.afmoe", "window_flash_roofline.afmoe", "window_decode_roofline.afmoe"}
NEW = (("moe_blocks_roofline", "%", "higher", "device_trace", "kernels"), ("moe_fetches_per_expert", "count", "lower", "program_counter", "step programs"),
       ("prefill_gate_ms_per_ktok", "ms", "lower", "device_trace", "step programs"), ("prefill_swa_ms_per_ktok.afmoe", "ms", "lower", "device_trace", "step programs"),
       ("window_flash_roofline.afmoe", "%", "higher", "device_trace", "kernels"), ("window_decode_roofline.afmoe", "%", "higher", "device_trace", "kernels"))


@pytest.fixture(scope="module")
def c():
    with open(os.path.join(common.HERE, "configs", CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def family():
    return common.load_family("afmoe")


def test_the_configuration_keeps_every_published_key_and_cuts_what_it_lists(c, family):
    assert c["family"] == "afmoe" and c["reduced"] == ["num_hidden_layers", "num_dense_layers", "layer_types", "num_experts", "vocab_size"]
    for k, v in PUBLISHED.items():
        assert c[k] == REDUCED.get(k, v), k
    assert c["layer_types"] == ["sliding_attention"] * 4 + ["full_attention"] and family.kinds(c) == ["W", "W", "W", "W", "G"]
    assert c["reduced_from"] == {"num_hidden_layers": 60, "num_dense_layers": 6, "layer_types": "[sliding_attention x 3, full_attention] x 15 (60 entries)",
                                 "num_experts": 256, "vocab_size": 200192} and set(c["why_reduced"]) == set(c["reduced"])
    dep = c["deployment"]
    assert (dep["chips_per_layer"], dep["experts_published"], dep["experts_held"], dep["vocab_rows_held"]) == (8, 256, [0, 32], [0, 25024]) and family.held(c) == (256, 0, 32)
    assert {"rope in window layers only", "gate", "query-key norm", "router", "sandwich norms", "window edge", "rope pairing", "mup_enabled", "initialisation",
            "anchored routing", "torch_dtype"} <= set(c["assumed"])
    tol = c["tolerance"]
    assert 0 < tol["logprob_abs"] <= 0.25 and tol["why"] and max(tol["served"]) < tol["logprob_abs"] < min(tol["float8"])
    cfg = family.program_config(c, 12288)
    assert (str(cfg.stream_dtype), cfg.num_hidden_layers, cfg.num_dense_layers, cfg.sliding_window, cfg.rope_theta, cfg.residual_rescale_layers, cfg.rms_eps) == (
        "bfloat16", 5, 1, 4096, 10000.0, 120, 1e-5)
    assert cfg.hd == c["head_dim"] and (cfg.num_heads, cfg.num_kv_heads, cfg.hidden_size, cfg.intermediate_size, cfg.moe_intermediate_size) == (48, 8, 3072, 12288, 3072)
    s = cfg.expert_layer
    assert (s.num_experts, s.expert_start, s.held, s.top_k, s.scale, s.score, s.bias) == (256, 0, 32, 4, 2.448, "sigmoid", True)
    assert cfg.layer_plan == (("swa", "moe"), 3, ("attn", "moe"), ("swa", "mlp")) and cfg.ring_entries() == {"k_w": 4096, "v_w": 4096}
    assert (cfg.router_anchor, cfg.router_bias_init, cfg.mup_enabled) == (8.0, 0.01, True)
    entry = {e["name"]: e for e in BENCH["configs"]}[CONFIG]
    assert entry["source"] == c["source"] and entry["reduced"] == c["reduced"] and entry["file"].endswith(CONFIG + ".json") and len(entry["why"]) <= 200
    sv = c["serving"]
    assert (sv["max_num_seqs"], sv["max_seq_len"], sv["max_ongoing_requests"], sv["warm_batch_max"]) == (16, 12288, 64, 4) and "engine_kwargs" not in sv
    with pytest.raises(ValueError, match="layer_types names every layer held"):
        family.kinds({**c, "layer_types": ["sliding_attention"] * 4})
    with pytest.raises(ValueError, match="sigmoid scores over one group"):
        family.program_config({**c, "n_group": 8}, 12288)
    with pytest.raises(ValueError, match="head is untied"):
        family.program_config({**c, "tie_word_embeddings": True}, 12288)


def test_the_cell_is_listed_and_what_stood_before_it_still_stands_in_its_order(c):
    """Listed, and never "last": the next PR appends after it."""
    names = [w["name"] for w in BENCH["workloads"]]
    cell = BENCH["workloads"][names.index(CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "longdoc-12k", 1) and len(cell["why"]) <= 200
    before = ["internlm2-1.8b.chat", "mistral-7b-d6.sft-2k", "internlm2-1.8b.longdoc", "nemotron-3-nano-ep2.chat", "qwen3-next-ep4.longdoc", "glm-4.7-flash-d8.longdoc-16k",
              "kimi-linear-ep4.longdoc", "minicpm-sala-d8.longdoc-12k", "smallthinker-21b-d8.longdoc-12k", "lfm2-24b-d10.longdoc-12k", "keye-vl-2.0-d6.longdoc-24k",
              "jamba2-3b.longdoc-12k"]
    assert names[:12] == before and names.index(CELL) == 12 and [e["name"] for e in BENCH["configs"]].index(CONFIG) == 11
    assert not any(w["chips"] == 4 for w in BENCH["workloads"][:13])
    listed = {m["name"] for k in ("end_to_end", "per_layer") for m in BENCH[k] if CELL in m.get("workloads", ())}
    assert listed == SERVE_READERS | {"serve_tokens_per_s"}, "tokens per second and what moves it; no time to a first token in a 12k cell"
    for k in ("end_to_end", "per_layer"):
        for m in BENCH[k]:
            if CELL in m.get("workloads", ()) and len(m["workloads"]) > 1:
                assert m["workloads"].index(CELL) == len(m["workloads"]) - 1 or m["workloads"].index(CELL) == m["workloads"].index(before[-1]) + 1, m["name"]
    per = {m["name"]: m for m in BENCH["per_layer"]}
    for name, unit, better, source, layer in NEW:
        assert per[name] == {"name": name, "unit": unit, "better": better, "source": source, "layer": layer, "moves": "serve_tokens_per_s", "workloads": [CELL]}
    assert all(common.load_reader(name) is not None for name in listed - {"serve_tokens_per_s"})
    mix = traffic.load_mix("longdoc-12k", CELL)
    assert (mix["kind"], mix["loop"], mix["clients"]) == ("serve", "closed", 21) and mix["clients"] == round(4 / 3 * c["serving"]["max_num_seqs"])
    assert mix["prompt_len"]["min"] > 2 * c["sliding_window"], "every prompt is over two windows long: every ring wraps in prefill"
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] <= c["serving"]["max_seq_len"]
    from benchmark.serve_cell import default_buckets, warm_plan

    assert [b for b, _ in warm_plan(mix, default_buckets(12288))] == [12288], "one bucket"


def test_the_counts_are_the_issues_arithmetic(c, family):
    p = family.layer_params(c)
    assert p["A"] == 62_914_816 == 2 * 3072 * 6144 + 2 * 3072 * 1024 + 6144 * 3072 + 256 and p["norms"] == 12_288
    assert p["F"] == 3 * 3072 * 12288 == 113_246_208 and p["expert"] == 3 * 3072 * 3072 == 28_311_552 and p["E_rest"] == 786_432 + 256 + 28_311_552
    assert p["A"] + p["norms"] + p["F"] == 176_173_312 and p["A"] + p["norms"] + p["E_rest"] + 32 * p["expert"] == 997_995_008
    assert p["embed_and_head"] == 2 * 25024 * 3072 == 153_747_456 and round(2 * p["expert"] / 2**20, 1) == 54.0
    held = family.parameters_held(c)
    assert held == c["parameters"] == 176_173_312 + 4 * 997_995_008 + 153_747_456 + 3072 == 4_321_903_872 and round(2 * held / 1e9, 2) == 8.64
    assert family.program_config(c, 12288).num_params() == held
    whole = {**c, "num_hidden_layers": 60, "num_dense_layers": 6, "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 15, "num_experts": 256,
             "vocab_size": 200192, "deployment": None}
    assert family.parameters_held(whole) == c["parameters_published"] == 398_635_286_016
    # a position in the cache while a window layer still holds it and afterwards, and the cache whole
    assert family.kv_bytes_per_token(c) == 5 * 2 * 8 * 128 * 2 == 20_480 and family.kv_bytes_per_token(c, "G") == 4096
    assert family.cache_bytes(c, 16, 12288) == 16 * (12288 * 4096 + 4096 * 4 * 4096) == 1_879_048_192
    from ray_tpu.llm.kv_cache import alloc_entries

    cfg = family.program_config(c, 12288)
    cache = jax.eval_shape(lambda: alloc_entries(cfg.position_entries(), 16, 12288, cfg.ring_entries()))
    assert cache["k"].shape == (1, 16, 12288, 8, 128) and cache["k_w"].shape == (4, 16, 4096, 8, 128)
    assert sum(a.size * a.dtype.itemsize for n, a in cache.items() if n != "length") == 1_879_048_192
    # with the weights: 10.52 GB, 61% of the chip's 16 GiB
    assert round((2 * held + 1_879_048_192) / 1e9, 2) == 10.52 and round((2 * held + 1_879_048_192) / 2**34, 2) == 0.61
    # what an expert expects of a call: 49,152 pairs over 256 experts, under two blocks of 128
    assert 12288 * 4 // 256 == 192 < 2 * 128 and 10500 * 4 // 256 == 164
    assert cfg.prefill_counters(1, 12288, lengths=[10500])["swa_pairs"] == 4 * family.window_pairs(c, 10500) == 4 * 34_621_440


def test_the_least_a_prefill_the_window_kernels_and_the_blocks_must_do_by_hand_at_one_small_size(family):
    """Three layers (a dense W, then W G routed), hidden 8, 4 heads of 2 over 2, a window of 4, a dense layer of 12, 3 of 6 experts of 5 held: every term written out."""
    c = {"hidden_size": 8, "num_hidden_layers": 3, "num_dense_layers": 1, "layer_types": ["sliding_attention", "sliding_attention", "full_attention"], "vocab_size": 16,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 2, "sliding_window": 4, "intermediate_size": 12, "num_experts": 3, "num_experts_per_tok": 2,
         "moe_intermediate_size": 5, "deployment": {"experts_published": 6, "experts_held": [0, 3]}}
    A, F, shared, expert, router = 2 * 8 * 8 + 2 * 8 * 4 + 8 * 8, 3 * 8 * 12, 3 * 8 * 5, 3 * 8 * 5, 8 * 6
    p = family.layer_params(c)
    assert (p["A"], p["F"], p["expert"], p["E_rest"], p["norms"]) == (A + 4, F, expert, router + 6 + shared, 32)
    held = 3 * (A + 4 + 32) + F + 2 * (router + 6 + shared + 3 * expert) + 2 * 16 * 8 + 8
    assert family.parameters_held(c) == held
    pairs = {n: sum(min(i + 1, 4) for i in range(n)) for n in (10, 3)}
    assert family.window_pairs(c, 10) == pairs[10] == 34 and family.window_pairs(c, 3) == pairs[3] == 6
    assert family.window_flash_least(c, pairs=2 * 40, tokens=13) == {"bytes": 2.0 * 13 * (2 * 4 + 2 * 2) * 2 * 2, "flops": 80.0 * 4 * 4 * 2}
    assert family.window_decode_least(c, rows=7) == {"bytes": 7.0 * 2 * 2 * 2 * 2, "flops": 7.0 * 4 * 4 * 2}
    assert family.moe_blocks_least(c, experts_hit=2.5, pairs_local=9.0) == {"bytes": (2.5 * expert + 9.0 * 2 * 8) * 2, "flops": 2.0 * 9.0 * expert}
    need = family.prefill_least(c, lengths=[10, 3], pairs_local=9.0, experts_hit=2.5)
    fixed = 3 * (A + 4 + 32) + F + 2 * (router + 6 + shared) + 8 * 16 + 8  # every weight outside the routed experts, the head and the final norm
    kept = 13 * 1 * 2 * 2 * 2 * 2 + (4 + 3) * 2 * 2 * 2 * 2 * 2  # every position of the full layer, the last four of each of the two window layers
    assert need["bytes"] == (fixed + 2 * 2.5 * expert + 13 * 8) * 2 + kept
    per_token = 3 * A + F + 2 * (router + shared)
    attention = 4 * 4 * 2 * (2 * (pairs[10] + pairs[3]) + 55 + 6)
    assert need["flops"] == 2 * 13 * per_token + 2 * 2 * 8 * 16 + 2 * 2 * 9.0 * expert + attention
    assert family.train_flops_per_token(c, 10) > 6 * (per_token + 2 * 2 * expert + 8 * 16)


def test_at_the_cells_size_the_blocks_are_bound_by_bytes_and_a_prefill_by_flops(c, family):
    """ISSUE 64's reckoning: 32 held experts' matrices once are 1.81 GB, 2.2 ms at 819 GB/s, where the 6,144 held pairs
    of a 12,288-row call are 348 GFLOP, 1.8 ms; the loop at a fetch a block, 63 blocks, reads about twice those bytes."""
    peaks = peaks_of("TPU v5 lite")
    blocks = family.moe_blocks_least(c, experts_hit=32.0, pairs_local=6144.0)
    assert round(blocks["bytes"] / 1e9, 2) == 1.89 and round(blocks["flops"] / 1e9) == 348
    bytes_s, flops_s = blocks["bytes"] / peaks["hbm_bytes_per_s"], blocks["flops"] / peaks["bf16_flops"]
    assert bytes_s > flops_s and round(1e3 * bytes_s, 1) == 2.3 and round(1e3 * flops_s, 1) == 1.8
    whole = family.prefill_least(c, lengths=[10500], pairs_local=5250.0, experts_hit=32.0)
    assert whole["flops"] / peaks["bf16_flops"] > 3 * whole["bytes"] / peaks["hbm_bytes_per_s"]
    assert round(whole["flops"] / 1e12, 1) == 17.4 and round(1e3 * whole["flops"] / peaks["bf16_flops"]) == 88  # 11.4 of the matrices outside the experts, 1.2 of the experts, 4.8 of attention
    window = family.window_flash_least(c, pairs=4 * family.window_pairs(c, 10500), tokens=10500)
    assert window["flops"] / peaks["bf16_flops"] > 10 * window["bytes"] / peaks["hbm_bytes_per_s"] and window["flops"] == 4 * 34_621_440 * 4 * 48 * 128
    ring = family.window_decode_least(c, rows=16 * 4096.0)
    assert ring["bytes"] == 268_435_456 and ring["bytes"] / peaks["hbm_bytes_per_s"] > 10 * ring["flops"] / peaks["bf16_flops"]


def test_the_reference_refuses_nothing_at_toy_size_and_blocks_change_nothing(family, monkeypatch):
    c = family.rehearsal({k: v for k, v in PUBLISHED.items() if k not in family.REHEARSAL_SIZES} | {"family": "afmoe"})
    cfg = family.program_config(c, 128, remat=False)
    params = jax.jit(lambda k: family.init_params(cfg, k))(jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(params)) == family.parameters_held(c) == cfg.num_params()
    assert float(abs(params["moe"]["router_bias"]).min()) > 0 and float(params["swa"]["post_norm"][0, 0]) == pytest.approx(10 ** -0.5)
    toks = [int(t) for t in np.random.RandomState(0).randint(1, c["vocab_size"] - 1, size=70)]
    assert [family.padded_length(n) for n in (1, 256, 257, 9000, 12288, 12289)] == [256, 256, 12288, 12288, 12288, 24576]
    lp = np.asarray(family.reference_logprobs(params, toks, c, 39, 70))
    assert lp.shape == (31, c["vocab_size"]) and np.isfinite(lp).all() and np.allclose(np.exp(lp).sum(-1), 1.0, atol=1e-5)
    # the blocks it goes in at the cell's size are not mathematics; nor is what follows a position
    monkeypatch.setattr(family, "QUERY_BLOCK", 16)
    monkeypatch.setattr(family, "DENSE_ROWS", 32)
    family._attention.clear_cache()
    family._dense.clear_cache()
    np.testing.assert_allclose(np.asarray(family.reference_logprobs(params, toks, c, 39, 70)), lp, atol=2e-5, rtol=0)
    monkeypatch.setattr(family, "PAD_TO", (128, 256))
    np.testing.assert_allclose(np.asarray(family.reference_logprobs(params, toks + [5] * 20, c, 39, 70)), lp, atol=2e-5, rtol=0)
    # the choices and the gaps it can report: one entry an expert layer, the gap between the last chosen and the first unchosen of s + b
    choices, gaps = [], []
    family.hidden_states(params, toks, c, choices, gaps)
    assert len(choices) == len(gaps) == 4 and choices[0].shape == (70, 2) and gaps[0].shape == (70,) and float(min(g.min() for g in gaps)) >= 0.0
    # the window acts at this size: the same weights under a window of 8 read otherwise
    assert np.abs(np.asarray(family.reference_logprobs(params, toks, {**c, "sliding_window": 8}, 39, 70)) - lp).max() > 1e-4


# ------------------------------------------------------------------------------------ the new readers
def _scope(s, calls=10):
    return {"s": s, "calls": calls, "flops": 0, "bytes": 0}


def _summary(gated=True):
    mixers = ({"swa": _scope(0.60), "swa.gate": _scope(0.12), "attn": _scope(0.30), "attn.gate": _scope(0.03)} if gated else {"swa": _scope(0.6), "attn": _scope(0.3)})
    experts = {"moe": _scope(0.2), "moe.route": _scope(0.05), "moe.blocks": _scope(0.80), "moe.place": _scope(0.3), "moe.shared": _scope(0.25)} if gated else {}
    programs = {"jit_llm_hybrid_prefill": {"calls": 4, "device_s": 3.1, "leaf_s": 3.0, "ops": {}, "scopes": {**mixers, **experts, "mlp": _scope(0.2), "unscoped": _scope(0.05)}},
                # the step's layers are not the prefill's: their seconds are not read
                "jit_llm_hybrid_fused_step": {"calls": 100, "device_s": 0.9, "leaf_s": 0.9, "ops": {}, "scopes": {"swa": _scope(0.2), "swa.gate": _scope(0.1), "moe.blocks": _scope(0.3)}}}
    return {"chips": 1, "window_s": 5.0, "busy_s": 4.7, "programs": programs, "roles": {}}


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
        requests = {"a": {"admit_t": 101.0, "prompt_tokens": 9000}, "b": {"admit_t": 104.0, "prompt_tokens": 12000},
                    "c": {"admit_t": 99.0, "prompt_tokens": 10000}, "d": {"admit_t": None, "prompt_tokens": 11000}}
        return {"window": [60.0, 105.0], "cell": {"name": "toy.longdoc"}, "config": c, "peaks": peaks_of("TPU v5 lite"),
                "worker": {"trace": {"trace_host": trace_host}, "requests": requests}}
    return make


STEPS = [  # two admitting steps in the stretch (one of them of two programs), one before it, a decode step
    {"t": 101.0, "admitted": 1, "prefill_ms": 1.0, "prefill_tokens": 9000, "prefill_experts_hit": 32.0, "prefill_moe_pairs_local": 4500.0, "moe_rows_computed": 7936.0,
     "moe_rows_kernel": 0.0, "moe_expert_fetches": 62.0, "prefill_dispatch_t": [[1, 2, 3]]},
    {"t": 104.0, "admitted": 2, "prefill_ms": 1.0, "prefill_tokens": 21000, "prefill_experts_hit": 31.5, "prefill_moe_pairs_local": 10500.0, "moe_rows_computed": 16000.0,
     "moe_rows_kernel": 0.0, "moe_expert_fetches": 125.0, "prefill_dispatch_t": [[1, 2, 3], [4, 5, 6]]},
    {"t": 99.0, "admitted": 1, "prefill_ms": 1.0, "prefill_tokens": 10000, "prefill_experts_hit": 32.0, "prefill_moe_pairs_local": 5000.0, "moe_rows_computed": 8064.0,
     "moe_rows_kernel": 0.0, "moe_expert_fetches": 63.0, "prefill_dispatch_t": [[1, 2, 3]]},
    {"t": 103.0, "phase": "decode"}]


def test_the_three_new_readers_on_a_made_up_summary_and_flight_log_and_on_nothing(obs, family, c, monkeypatch):
    """Two admitting steps in the stretch, 21,000 tokens admitted by the requests' stamps: the gate's seconds of both kinds of
    layer a 1,000 of them; the blocks' least for (32 + 2 x 31.5) experts hit and 15,000 pairs a layer in four layers over
    their 0.80 s; the window's fetches over its experts hit, a program at a time."""
    blocks, fetches, gate = (common.load_reader(n) for n in ("moe_blocks_roofline", "moe_fetches_per_expert", "prefill_gate_ms_per_ktok"))
    monkeypatch.setattr(flight, "records", lambda obs: {"steps": STEPS, "requests": {}})
    o = obs(_summary())
    assert gate(o) == pytest.approx(0.15 * 1e3 / 21.0)
    peaks = peaks_of("TPU v5 lite")
    least_s = 4 * sum(family.moe_blocks_least(c, experts_hit=h, pairs_local=p)["bytes"] for h, p in ((32.0, 4500.0), (63.0, 10500.0))) / peaks["hbm_bytes_per_s"]
    assert blocks(o) == pytest.approx(100.0 * least_s / 0.80) and 2.5 < blocks(o) < 3.5
    assert fetches(o) == pytest.approx((62.0 + 125.0 + 63.0) / (32.0 + 63.0 + 32.0)) and 1.9 < fetches(o) < 2.0
    # the three that are SmallThinker's readers under a name of their own are those readers
    for name in ("prefill_swa_ms_per_ktok", "window_flash_roofline", "window_decode_roofline"):
        assert common.load_reader(name + ".afmoe").__code__ == common.load_reader(name).__code__
    assert common.load_reader("prefill_swa_ms_per_ktok.afmoe")(o) == pytest.approx(0.72 * 1e3 / 21.0)  # the gate's sub-scope is the window layers' too
    # nothing to read: a program without the scopes or the counter, a stretch that admitted nothing, off the chip (no peaks), no log, no trace
    other = obs(_summary(gated=False))
    assert gate(other) is None and blocks(other) is None
    o = obs(_summary())
    monkeypatch.setattr(flight, "records", lambda obs: {"steps": [{k: v for k, v in s.items() if k != "moe_expert_fetches"} for s in STEPS], "requests": {}})
    assert fetches(o) is None and blocks(o) is not None
    monkeypatch.setattr(flight, "records", lambda obs: {"steps": STEPS[3:], "requests": {}})
    assert blocks(o) is None and fetches(o) is None
    monkeypatch.setattr(flight, "records", lambda obs: {"steps": STEPS, "requests": {}})
    o = obs(_summary())
    o["worker"]["requests"] = {"c": {"admit_t": 99.0, "prompt_tokens": 10000}}
    assert gate(o) is None
    o = obs(_summary())
    assert blocks({k: v for k, v in o.items() if k != "peaks"}) is None
    assert blocks({**o, "config": {**c, "family": "lfm2"}}) is None, "a family without such a count"
    monkeypatch.setattr(flight, "records", lambda obs: None)
    assert blocks(o) is None and fetches(o) is None
    for read in (blocks, fetches, gate):
        assert read({"cell": {"name": "toy.longdoc"}}) is None and read({}) is None
