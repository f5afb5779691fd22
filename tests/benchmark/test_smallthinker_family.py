"""The ``smallthinker`` family file and the cell ``smallthinker-21b-d8.longdoc-12k``: the
configuration keeps every published key (depth is cut, and the two per-layer layouts with it, each
with its ``reduced_from``), the family's counts are ISSUE 49's arithmetic and the program's, the
least a prefill, the windowed attention and a ring's decode read must do is counted by hand at a
small size, the reference refuses nothing at toy size, and the three new readers read a made-up
summary, trace and flight log, and nothing where there is nothing."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark import common, scopes, traffic
from benchmark.peaks import peaks_of

BENCH = common.load_benchmark()
CONFIG, CELL = "smallthinker-21b-a3b-d8", "smallthinker-21b-d8.longdoc-12k"
# the catalog row's ``config`` (guide model-configs, architectures.jsonl, SmallThinker-21BA3B-Instruct), key for key
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384, "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64, "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52, "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_layout": [0, 1, 1, 1] * 13,
    "rope_scaling": None, "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13, "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936,
}
CUT = {"num_hidden_layers": 8, "rope_layout": [0, 1, 1, 1] * 2, "sliding_window_layout": [0, 1, 1, 1] * 2}
SERVE_READERS = {"prefill_ms_per_ktok.longdoc", "decode_device_ms.longdoc", "prefill_bubble_ms", "prefill_stage_idle_ms", "prefill_mixer_ms_per_ktok",
                 "prefill_ffn_ms_per_ktok", "prefill_step_roofline", "moe_block_fill", "moe_blocks_share", "prefill_swa_ms_per_ktok",
                 "window_flash_roofline", "window_decode_roofline"}
NEW = (("prefill_swa_ms_per_ktok", "ms", "lower", "step programs"), ("window_flash_roofline", "%", "higher", "kernels"),
       ("window_decode_roofline", "%", "higher", "kernels"))


@pytest.fixture(scope="module")
def c():
    with open(os.path.join(common.HERE, "configs", CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def family():
    return common.load_family("smallthinker")


def test_the_configuration_keeps_every_published_key_and_cuts_depth_with_its_two_layouts(c, family):
    assert c["family"] == "smallthinker" and c["reduced"] == ["num_hidden_layers", "rope_layout", "sliding_window_layout"]
    assert set(c["reduced_from"]) == set(c["reduced"]) == set(c["why_reduced"]) and c["reduced_from"]["num_hidden_layers"] == 52
    for k, v in PUBLISHED.items():
        assert c[k] == CUT.get(k, v), k
    assert all(PUBLISHED[k][:8] == CUT[k] for k in ("rope_layout", "sliding_window_layout")), "the published lists' first eight entries"
    assert family.kinds(c) == ["G", "W", "W", "W", "G", "W", "W", "W"] and family.published_depth(c) == 52
    d = c["deployment"]
    assert (d["pipeline_stages"], d["layers_per_stage"], sum(d["layers_per_stage"])) == (7, [8, 8, 8, 8, 8, 8, 4], 52)
    assert {"router tap", "top-k then softmax", "window edge", "rope pairing", "NoPE global layers", "secondary experts", "initialisation",
            "anchored routing", "torch_dtype"} <= set(c["assumed"]) and c["init_router_anchor"] == 8.0
    assert 0 < c["tolerance"]["logprob_abs"] <= 0.25 and c["tolerance"]["why"]
    cfg = family.program_config(c, 12288)
    assert (str(cfg.stream_dtype), cfg.num_hidden_layers, cfg.sliding_window_size, cfg.rope_theta, cfg.residual_rescale_layers) == ("bfloat16", 8, 4096, 1.5e6, 104)
    assert cfg.hd == c["head_dim"] and cfg.layer_plan == (("attn", "moe", "swa", "moe", "swa", "moe", "swa", "moe"), 2, (), ())
    assert cfg.ring_entries() == {"k_w": 4096, "v_w": 4096} and cfg.router_anchor == 8.0
    entry = {e["name"]: e for e in BENCH["configs"]}[CONFIG]
    assert entry["source"] == c["source"] and entry["reduced"] == c["reduced"] and entry["file"].endswith(CONFIG + ".json") and len(entry["why"]) <= 200
    sv = c["serving"]
    assert (sv["max_num_seqs"], sv["max_seq_len"], sv["max_ongoing_requests"], sv["warm_batch_max"]) == (16, 12288, 64, 4) and "engine_kwargs" not in sv
    with pytest.raises(ValueError, match="agree"):
        family.kinds({**c, "rope_layout": [1] * 8})
    with pytest.raises(ValueError, match="softmax over its top k"):
        family.program_config({**c, "moe_primary_router_apply_softmax": False}, 12288)


def test_the_cell_is_listed_and_what_stood_before_it_still_stands_in_its_order(c):
    """Listed, and never "last": the next PR appends after it."""
    names = [w["name"] for w in BENCH["workloads"]]
    cell = BENCH["workloads"][names.index(CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "longdoc-12k", 1) and len(cell["why"]) <= 200
    before = ["internlm2-1.8b.chat", "mistral-7b-d6.sft-2k", "internlm2-1.8b.longdoc", "nemotron-3-nano-ep2.chat", "qwen3-next-ep4.longdoc",
              "glm-4.7-flash-d8.longdoc-16k", "kimi-linear-ep4.longdoc", "minicpm-sala-d8.longdoc-12k"]
    assert names[:8] == before and names.index(CELL) == 8 and [e["name"] for e in BENCH["configs"]].index(CONFIG) == 7
    listed = {m["name"] for k in ("end_to_end", "per_layer") for m in BENCH[k] if CELL in m.get("workloads", ())}
    assert listed == SERVE_READERS | {"serve_tokens_per_s"}, "tokens per second and what moves it; no time to a first token in a 12k cell"
    for k in ("end_to_end", "per_layer"):
        for m in BENCH[k]:
            if CELL in m.get("workloads", ()) and len(m["workloads"]) > 1:
                assert m["workloads"].index(CELL) == m["workloads"].index(before[-1] if before[-1] in m["workloads"] else m["workloads"][m["workloads"].index(CELL) - 1]) + 1, m["name"]
    per = {m["name"]: m for m in BENCH["per_layer"]}
    for name, unit, better, layer in NEW:
        assert per[name] == {"name": name, "unit": unit, "better": better, "source": "device_trace", "layer": layer,
                             "moves": "serve_tokens_per_s", "workloads": [CELL]}
    assert all(common.load_reader(name) is not None for name in listed - {"serve_tokens_per_s"})
    mix = traffic.load_mix("longdoc-12k", CELL)
    assert (mix["kind"], mix["loop"], mix["clients"]) == ("serve", "closed", 21) and mix["clients"] == round(4 / 3 * c["serving"]["max_num_seqs"])
    assert mix["prompt_len"]["min"] > 2 * c["sliding_window_size"], "every prompt is over two windows long: every ring wraps in prefill"
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] <= c["serving"]["max_seq_len"]
    from benchmark.serve_cell import default_buckets, warm_plan

    assert [b for b, _ in warm_plan(mix, default_buckets(12288))] == [12288], "one bucket"


def test_the_counts_are_the_issues_arithmetic(c, family):
    p = family.layer_params(c)
    assert p["A"] == 2 * 2560 * 3584 + 2 * 2560 * 512 == 20_971_520 and p["rest"] == 2560 * 64 + 2 * 2560 == 168_960
    assert p["expert"] == 3 * 2560 * 768 == 5_898_240 and 64 * p["expert"] == 377_487_360 and p["embed_and_head"] == 2 * 151936 * 2560 == 777_912_320
    assert p["A"] + p["rest"] + 64 * p["expert"] == 398_627_840
    held = family.parameters_held(c)
    assert held == c["parameters"] == 8 * 398_627_840 + 777_912_320 + 2560 == 3_966_937_600 and round(2 * held / 1e9, 2) == 7.93
    whole = {**c, "num_hidden_layers": 52, "rope_layout": PUBLISHED["rope_layout"], "sliding_window_layout": PUBLISHED["sliding_window_layout"]}
    assert family.parameters_held(whole) == c["parameters_published"] == 52 * 398_627_840 + 777_912_320 + 2560 == 21_506_562_560
    assert family.program_config(c, 12288).num_params() == held
    # active parameters a token and layer: attention and six experts
    assert p["A"] + 6 * p["expert"] == 56_360_960
    # a position in the cache, by layer kind, and the cache whole: rows for every position beside rings of the window's
    assert (family.kv_bytes_per_token(c, "G"), family.kv_bytes_per_token(c, "W"), family.kv_bytes_per_token(c)) == (2 * 2048, 6 * 2048, 8 * 2048)
    assert family.cache_bytes(c, 16, 12288) == 16 * (12288 * 4096 + 4096 * 12288) == 1_610_612_736
    assert family.cache_bytes({**c, "sliding_window_size": 12288}, 16, 12288) == 3_221_225_472, "every position kept in every layer"
    from ray_tpu.llm.kv_cache import alloc_entries, entry_bytes_per_token

    cfg = family.program_config(c, 12288)
    assert entry_bytes_per_token(cfg.position_entries()) == 16_384
    cache = jax.eval_shape(lambda: alloc_entries(cfg.position_entries(), 16, 12288, cfg.ring_entries()))
    assert sum(a.size * a.dtype.itemsize for n, a in cache.items() if n != "length") == 1_610_612_736
    # the counters the program writes into its flight log are the family's counts from the same lengths
    lengths = [8704, 10500, 12160, 4096, 100]
    assert cfg.prefill_counters(8, 12288, lengths=lengths) == {"swa_pairs": 6 * sum(family.window_pairs(c, n) for n in lengths)}
    assert family.window_pairs(c, 10500) == 34_621_440 and 0.62 < family.window_pairs(c, 10500) / (10500 * 10501 / 2) < 0.64
    assert cfg.decode_counters([12000, 4097, 4096, 17]) == {"swa_rows_read": 6 * (4096 + 4096 + 4096 + 17)}


def test_the_least_a_prefill_the_window_and_a_rings_read_must_do_by_hand_at_one_small_size(family):
    """Three layers (G W W), hidden 8, a window of 4, 4 experts of 6 top 2: every term written out."""
    c = {"hidden_size": 8, "num_hidden_layers": 3, "vocab_size": 16, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 3,
         "sliding_window_size": 4, "sliding_window_layout": [0, 1, 1], "rope_layout": [0, 1, 1], "moe_num_primary_experts": 4,
         "moe_num_active_primary_experts": 2, "moe_ffn_hidden_size": 6}
    q, kv = 4 * 3, 2 * 3
    A, router, expert = 2 * 8 * q + 2 * 8 * kv, 8 * 4, 3 * 8 * 6
    p = family.layer_params(c)
    assert (p["A"], p["rest"], p["expert"]) == (A, router + 16, expert) and family.kinds(c) == ["G", "W", "W"]
    assert family.parameters_held(c) == 3 * (A + router + 16 + 4 * expert) + 2 * 16 * 8 + 8
    # pairs inside the window: a prompt of 10 reads 1 + 2 + 3 + 4 and then 4 a query; one of 3 is under the window
    assert family.window_pairs(c, 10) == 10 + 6 * 4 == 34 and family.window_pairs(c, 3) == 6 and family.window_pairs(c, 4) == 10
    assert family.attention_pairs(c, 10) == 55 + 2 * 34 and family.attention_pairs(c, 3) == 3 * 6
    flash = family.window_flash_least(c, pairs=2 * (34 + 6), tokens=13)
    assert flash == {"bytes": 2.0 * 13 * (2 * q + 2 * kv) * 2, "flops": 2.0 * (34 + 6) * 4 * 4 * 3}
    assert family.window_decode_least(c, rows=7.0) == {"bytes": 7.0 * 2 * kv * 2, "flops": 7.0 * 4 * 4 * 3}
    need = family.prefill_least(c, lengths=[10, 3], pairs_local=26.0, experts_hit=3.0)
    fixed = 3 * (A + router + 16) + 8 * 16 + 8
    kept = 13 * 1 * 2 * kv * 2 + (4 + 3) * 2 * 2 * kv * 2  # every position in the global layer, the last 4 (or all 3) in the two window layers
    assert need["bytes"] == 2 * (fixed + 3 * 3.0 * expert + 13 * 8) + kept
    assert need["flops"] == 2 * 13 * 3 * (A + router) + 2 * 2 * 8 * 16 + 2 * 3 * 26.0 * expert + 4 * 4 * 3 * ((55 + 2 * 34) + 3 * 6)
    # a lower bound by construction: the same prompts with every layer global cost more
    full = {**c, "sliding_window_layout": [0, 0, 0], "rope_layout": [0, 0, 0]}
    assert family.prefill_least(full, lengths=[10, 3], pairs_local=26.0, experts_hit=3.0)["flops"] > need["flops"]
    step = family.decode_step_least(c, lanes=2.0, experts_hit=3.0, kv_tokens=20.0)
    assert step["bytes"] == 2 * (fixed + 3 * 3.0 * expert + 2 * 8 + 20 * 3 * 2 * kv)
    assert family.train_flops_per_token(c, 10) > 6 * (3 * (A + router + 2 * expert) + 8 * 16)


def test_at_the_cells_size_the_experts_are_most_of_a_prefill_and_a_window_layer_costs_less_than_two_thirds_of_a_global_one(c, family):
    peaks = peaks_of("TPU v5 lite")
    whole = family.prefill_least(c, lengths=[10500], pairs_local=6.0 * 10500, experts_hit=64.0)
    assert whole["flops"] / peaks["bf16_flops"] > whole["bytes"] / peaks["hbm_bytes_per_s"], "bound by FLOPs"
    experts = 8 * 2.0 * 6 * 10500 * 5_898_240
    attention = 4.0 * 28 * 128 * family.attention_pairs(c, 10500)
    assert 0.40 < experts / whole["flops"] < 0.50 and 0.30 < attention / whole["flops"] < 0.40
    assert 3 * family.window_pairs(c, 10500) / (10500 * 10501 / 2) < 2.0, "the six window layers read less than four global ones would"
    flash = family.window_flash_least(c, pairs=6.0 * family.window_pairs(c, 10500), tokens=10500)
    assert flash["flops"] / peaks["bf16_flops"] > 10 * flash["bytes"] / peaks["hbm_bytes_per_s"], "the windowed attention is bound by FLOPs: 15 ms a prompt and six layers"
    ring = family.window_decode_least(c, rows=16 * 4096.0)
    assert ring["bytes"] == 16 * 4096 * 2048 and ring["bytes"] / peaks["hbm_bytes_per_s"] > ring["flops"] / peaks["bf16_flops"], "a ring's read is bound by bytes: 0.16 ms a layer"


def test_the_reference_refuses_nothing_at_toy_size_and_blocks_change_nothing(family, monkeypatch):
    c = family.rehearsal({k: v for k, v in PUBLISHED.items() if k not in family.REHEARSAL_SIZES} | {"family": "smallthinker"})
    cfg = family.program_config(c, 128, remat=False)
    params = jax.jit(lambda k: family.init_params(cfg, k))(jax.random.PRNGKey(0))
    toks = [int(t) for t in np.random.RandomState(0).randint(1, c["vocab_size"] - 1, size=70)]
    assert [family.padded_length(n) for n in (1, 256, 257, 9000, 12288, 12289)] == [256, 256, 12288, 12288, 12288, 24576]
    lp = np.asarray(family.reference_logprobs(params, toks, c, 39, 70))
    assert lp.shape == (31, c["vocab_size"]) and np.isfinite(lp).all() and np.allclose(np.exp(lp).sum(-1), 1.0, atol=1e-5)
    # the blocks of queries it goes in at the cell's size are not mathematics; nor is what follows a position
    monkeypatch.setattr(family, "QUERY_BLOCK", 16)
    family._attention.clear_cache()
    np.testing.assert_allclose(np.asarray(family.reference_logprobs(params, toks, c, 39, 70)), lp, atol=2e-5, rtol=0)
    monkeypatch.setattr(family, "PAD_TO", (128, 256))
    np.testing.assert_allclose(np.asarray(family.reference_logprobs(params, toks + [5] * 20, c, 39, 70)), lp, atol=2e-5, rtol=0)
    # the window acts at this size (16 of 70 positions): the same tokens under a window of 70 read otherwise
    wide = np.asarray(family.reference_logprobs(params, toks, {**c, "sliding_window_size": 70}, 39, 70))
    assert np.abs(wide - lp).max() > 1e-3


# ------------------------------------------------------------------------------------ the three readers
def _scope(s, calls=10):
    return {"s": s, "calls": calls, "flops": 0, "bytes": 0}


def _summary(windowed=True):
    mixers = {"swa": _scope(0.60), "attn": _scope(0.50)} if windowed else {"mla": _scope(0.7), "mla.attn": _scope(0.4)}
    programs = {"jit_llm_hybrid_prefill": {"calls": 4, "device_s": 5.1, "leaf_s": 5.0, "ops": {}, "scopes": {**mixers, "moe.route": _scope(0.05), "moe.blocks": _scope(3.0), "unscoped": _scope(0.1)}},
                # the step's window layers are not the prefill's: their seconds are not read
                "jit_llm_hybrid_fused_step": {"calls": 100, "device_s": 0.9, "leaf_s": 0.9, "ops": {}, "scopes": {"swa": _scope(0.2), "attn": _scope(0.1)}}}
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


def test_the_window_layers_prefill_time_on_a_made_up_summary_and_on_nothing(obs):
    read = common.load_reader("prefill_swa_ms_per_ktok")
    # two prompts admitted in the stretch, 21,000 tokens: the window layers' whole seconds a 1,000 of them; the global layers' are not theirs
    assert read(obs(_summary())) == pytest.approx(0.60 * 1e3 / 21.0)
    assert read(obs(_summary(windowed=False))) is None
    o = obs(_summary())
    o["worker"]["requests"] = {"c": {"admit_t": 99.0, "prompt_tokens": 10000}}
    assert read(o) is None and read({"cell": {"name": "toy.longdoc"}}) is None and read({}) is None


def test_the_two_kernels_readers_on_a_made_up_trace_and_flight_log(c, family, monkeypatch):
    """Two prompts of 9,000 and 12,000 admitted in the stretch: 6 layers x (28.5 M + 40.8 M) pairs at
    14,336 FLOPs a pair over 197 TFLOP/s is 30 ms; sixteen lanes with their rings full read 6 x 16 x
    4,096 rows a step, 134 MB a call at 819 GB/s."""
    from benchmark import flight

    flash, ring = common.load_reader("window_flash_roofline"), common.load_reader("window_decode_roofline")
    pairs = 6 * (family.window_pairs(c, 9000) + family.window_pairs(c, 12000))
    steps = ([{"t": 101.0, "admitted": 1, "prefill_tokens": 9000, "swa_pairs": 6 * family.window_pairs(c, 9000)},
              {"t": 104.0, "admitted": 1, "prefill_tokens": 12000, "swa_pairs": 6 * family.window_pairs(c, 12000)},
              {"t": 99.0, "admitted": 1, "prefill_tokens": 10000, "swa_pairs": 6 * family.window_pairs(c, 10000)}]  # before the stretch
             + [{"t": 101.5 + 0.1 * n, "swa_rows_read": 6 * 16 * 4096} for n in range(10)] + [{"t": 103.0, "phase": "mixed"}])
    monkeypatch.setattr(flight, "records", lambda obs: {"steps": steps, "requests": {}})
    peaks = peaks_of("TPU v5 lite")
    obs = {"config": c, "peaks": peaks, "window": [60.0, 105.0],
           "worker": {"trace": {"trace_host": [100.0, 105.0], "ops": {"window_flash_attention": [12, 0.080], "window_flash_attention.1": [12, 0.020],
                                                                       "window_decode_attention.2": [30, 0.010], "window_decode_attention.3": [30, 0.010],
                                                                       "slot_decode_attention": [20, 0.015], "flash_attention": [4, 0.050]}}}}
    assert pairs == 6 * (28_477_440 + 40_765_440)
    assert flash(obs) == pytest.approx(100.0 * (pairs * 4 * 28 * 128 / peaks["bf16_flops"]) / 0.100) and 29.5 < flash(obs) < 31.0
    assert family.window_decode_least(c, rows=16 * 4096.0)["bytes"] == 134_217_728
    assert ring(obs) == pytest.approx(100.0 * (134_217_728 / peaks["hbm_bytes_per_s"]) * 60 / 0.020) and 49.0 < ring(obs) < 50.0
    # nothing to read: off the chip (no peaks), a program whose prefill or step runs no such kernel, a stretch without the rows, no trace
    assert flash({k: v for k, v in obs.items() if k != "peaks"}) is None and ring({k: v for k, v in obs.items() if k != "peaks"}) is None
    obs["worker"]["trace"]["ops"] = {"slot_decode_attention": [40, 0.001], "flash_attention": [4, 0.050]}
    assert flash(obs) is None and ring(obs) is None
    obs["worker"]["trace"]["ops"] = {"window_flash_attention": [12, 0.080], "window_decode_attention.2": [30, 0.010]}
    monkeypatch.setattr(flight, "records", lambda obs: {"steps": [{"t": 103.0, "phase": "mixed"}], "requests": {}})
    assert flash(obs) is None and ring(obs) is None
    monkeypatch.setattr(flight, "records", lambda obs: None)
    assert flash(obs) is None and ring(obs) is None and flash({}) is None and ring({}) is None
