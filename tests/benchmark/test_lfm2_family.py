"""The ``lfm2`` family file and the cell ``lfm2-24b-d10.longdoc-12k``: the configuration keeps every
published key (depth is cut, and the list of layer types with it, each with its ``reduced_from``),
the family's counts are ISSUE 53's arithmetic and the program's, the least a prefill, the 64-wide
attention and a decode step's read must do is counted by hand at a small size, the reference
refuses nothing at toy size, and the three new readers read a made-up summary, trace and flight
log, and nothing where there is nothing."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark import common, scopes, traffic
from benchmark.peaks import peaks_of

BENCH = common.load_benchmark()
CONFIG, CELL = "lfm2-24b-a2b-d10", "lfm2-24b-d10.longdoc-12k"
# the catalog row's ``config`` (guide model-configs, architectures.jsonl, LFM2-24B-A2B), key for key
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 11776, "layer_types": ["conv", "conv", "full_attention", "conv"] * 10,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe", "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4, "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}, "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
}
CUT = {"num_hidden_layers": 10, "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv"]}
SERVE_READERS = {"prefill_ms_per_ktok.longdoc", "decode_device_ms.longdoc", "prefill_bubble_ms", "prefill_stage_idle_ms", "prefill_mixer_ms_per_ktok",
                 "prefill_ffn_ms_per_ktok", "prefill_step_roofline", "moe_block_fill", "moe_blocks_share", "prefill_shortconv_ms_per_ktok",
                 "flash64_roofline", "narrow_decode_roofline"}
NEW = (("prefill_shortconv_ms_per_ktok", "ms", "lower", "step programs"), ("flash64_roofline", "%", "higher", "kernels"),
       ("narrow_decode_roofline", "%", "higher", "kernels"))


@pytest.fixture(scope="module")
def c():
    with open(os.path.join(common.HERE, "configs", CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def family():
    return common.load_family("lfm2")


def test_the_configuration_keeps_every_published_key_and_cuts_depth_with_its_list_of_layer_types(c, family):
    assert c["family"] == "lfm2" and c["reduced"] == ["num_hidden_layers", "layer_types"]
    assert set(c["reduced_from"]) == set(c["reduced"]) == set(c["why_reduced"]) and c["reduced_from"]["num_hidden_layers"] == 40
    for k, v in PUBLISHED.items():
        assert c[k] == CUT.get(k, v), k
    assert PUBLISHED["layer_types"][:10] == CUT["layer_types"], "the published list's first ten entries"
    assert family.kinds(c) == [("conv", "dense")] * 2 + [("full_attention", "experts"), ("conv", "experts"), ("conv", "experts"), ("conv", "experts")] * 2
    assert family.published_depth(c) == 40 and family.head_dim(c) == 64 == c["head_dim"]
    assert (family.count(c, "conv"), family.count(c, "full_attention"), family.count(c, "dense"), family.count(c, "experts")) == (8, 2, 2, 8)
    d = c["deployment"]
    assert (d["pipeline_stages"], d["layers_per_stage"], sum(d["layers_per_stage"])) == (4, [10, 10, 10, 10], 40)
    assert {"head_dim", "tied head", "split order", "taps", "norms", "router", "rope pairing", "initialisation", "anchored routing", "selection bias",
            "final norm", "torch_dtype"} <= set(c["assumed"]) and (c["init_router_anchor"], c["init_router_bias_range"]) == (32.0, 0.25)
    tol = c["tolerance"]
    assert 0 < tol["logprob_abs"] <= 0.25 and tol["why"] and max(tol["served"]) < tol["logprob_abs"] < min(tol["float8"])
    cfg = family.program_config(c, 12288)
    assert (str(cfg.stream_dtype), cfg.num_hidden_layers, cfg.conv_L_cache, cfg.rope_theta, cfg.residual_rescale_layers) == ("bfloat16", 10, 3, 1e6, 80)
    assert cfg.hd == c["head_dim"] and cfg.layer_plan == (("attn", "moe", "shortconv", "moe", "shortconv", "moe", "shortconv", "moe"), 2, (), ("shortconv", "ffn", "shortconv", "ffn"))
    assert (cfg.router_anchor, cfg.router_bias_range, cfg.use_expert_bias, cfg.rms_eps) == (32.0, 0.25, True, 1e-5)
    entry = {e["name"]: e for e in BENCH["configs"]}[CONFIG]
    assert entry["source"] == c["source"] and entry["reduced"] == c["reduced"] and entry["file"].endswith(CONFIG + ".json") and len(entry["why"]) <= 200
    sv = c["serving"]
    assert (sv["max_num_seqs"], sv["max_seq_len"], sv["max_ongoing_requests"], sv["warm_batch_max"]) == (16, 12288, 64, 4) and "engine_kwargs" not in sv
    with pytest.raises(ValueError, match="names every layer held"):
        family.kinds({**c, "layer_types": ["conv"] * 9})
    with pytest.raises(ValueError, match="no bias"):
        family.program_config({**c, "conv_bias": True}, 12288)


def test_the_cell_is_listed_and_what_stood_before_it_still_stands_in_its_order(c):
    """Listed, and never "last": the next PR appends after it."""
    names = [w["name"] for w in BENCH["workloads"]]
    cell = BENCH["workloads"][names.index(CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "longdoc-12k", 1) and len(cell["why"]) <= 200
    before = ["internlm2-1.8b.chat", "mistral-7b-d6.sft-2k", "internlm2-1.8b.longdoc", "nemotron-3-nano-ep2.chat", "qwen3-next-ep4.longdoc",
              "glm-4.7-flash-d8.longdoc-16k", "kimi-linear-ep4.longdoc", "minicpm-sala-d8.longdoc-12k", "smallthinker-21b-d8.longdoc-12k"]
    assert names[:9] == before and names.index(CELL) == 9 and [e["name"] for e in BENCH["configs"]].index(CONFIG) == 8
    listed = {m["name"] for k in ("end_to_end", "per_layer") for m in BENCH[k] if CELL in m.get("workloads", ())}
    assert listed == SERVE_READERS | {"serve_tokens_per_s"}, "tokens per second and what moves it; no time to a first token in a 12k cell"
    for k in ("end_to_end", "per_layer"):
        for m in BENCH[k]:
            if CELL in m.get("workloads", ()) and len(m["workloads"]) > 1:
                assert m["workloads"].index(CELL) == m["workloads"].index(before[-1]) + 1, m["name"]
    per = {m["name"]: m for m in BENCH["per_layer"]}
    for name, unit, better, layer in NEW:
        assert per[name] == {"name": name, "unit": unit, "better": better, "source": "device_trace", "layer": layer,
                             "moves": "serve_tokens_per_s", "workloads": [CELL]}
    assert all(common.load_reader(name) is not None for name in listed - {"serve_tokens_per_s"})
    mix = traffic.load_mix("longdoc-12k", CELL)
    assert (mix["kind"], mix["loop"], mix["clients"]) == ("serve", "closed", 21) and mix["clients"] == round(4 / 3 * c["serving"]["max_num_seqs"])
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] <= c["serving"]["max_seq_len"]
    from benchmark.serve_cell import default_buckets, warm_plan

    assert [b for b, _ in warm_plan(mix, default_buckets(12288))] == [12288], "one bucket"


def test_the_counts_are_the_issues_arithmetic(c, family):
    p = family.layer_params(c)
    assert p["conv"] == 4 * 2048 * 2048 + 3 * 2048 == 16_783_360 and p["attention"] == 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64 == 10_485_888
    assert p["dense"] == 3 * 2048 * 11776 == 72_351_744 and p["expert"] == 3 * 2048 * 1536 == 9_437_184 and p["router"] == 2048 * 64 + 64
    assert 64 * p["expert"] + p["router"] == 604_110_912 and p["embed"] == 65536 * 2048 == 134_217_728
    held = family.parameters_held(c)
    assert held == c["parameters"] == 8 * 16_783_360 + 2 * 10_485_888 + 2 * 72_351_744 + 8 * 604_110_912 + 20 * 2048 + 134_217_728 + 2048 == 5_267_090_176
    assert round(2 * held / 1e9, 2) == 10.53 and round(2 * held / 2**30, 2) == 9.81
    assert family.parameters_published(c) == c["parameters_published"] == 23_843_661_440
    assert family.program_config(c, 12288).num_params() == held
    # the cut's matrix products a token: 1.21 GFLOP, of which the experts half, the dense layers a quarter, the convolution mixers a fifth
    per_token = 2 * family._per_token_matmul(c, 4)
    assert round(per_token / 1e9, 2) == 1.21
    shares = {"experts": 2 * 8 * 4 * p["expert"] / per_token, "dense": 2 * 2 * p["dense"] / per_token, "conv": 2 * 8 * 4 * 2048 * 2048 / per_token,
              "attention": 2 * 2 * (p["attention"] - 128) / per_token}
    assert {k: round(v, 2) for k, v in shares.items()} == {"experts": 0.50, "dense": 0.24, "conv": 0.22, "attention": 0.03}
    # a position in the cache, a sequence's windows, and the caches whole
    assert family.kv_bytes_per_token(c) == 2 * 2 * 8 * 64 * 2 == 4096 and family.state_bytes_per_slot(c) == 8 * 2 * 2048 * 2 == 65_536
    assert family.cache_bytes(c, 16, 12288) == 16 * (12288 * 4096 + 65_536) == 806_354_944
    from ray_tpu.llm import state_cache
    from ray_tpu.llm.kv_cache import alloc_entries, entry_bytes_per_token

    cfg = family.program_config(c, 12288)
    assert entry_bytes_per_token(cfg.position_entries()) == 4096 and state_cache.bytes_per_slot(cfg) == 65_536
    cache = jax.eval_shape(lambda: alloc_entries(cfg.position_entries(), 16, 12288, cfg.ring_entries()))
    assert cache["k"].shape == (2, 16, 12288, 4, 128) and sum(a.size * a.dtype.itemsize for n, a in cache.items() if n != "length") == 805_306_368
    # the counters the program writes into its flight log are the family's counts from the same lengths
    lengths = [8704, 10500, 12160, 100]
    assert cfg.prefill_counters(4, 12288, lengths=lengths) == {"narrow_pairs": 2 * sum(family.causal_pairs(n) for n in lengths)}
    assert cfg.decode_counters([12000, 4097, 17]) == {"narrow_rows_read": 2 * (12000 + 4097 + 17)}


def test_the_least_a_prefill_the_narrow_attention_and_a_steps_read_must_do_by_hand_at_one_small_size(family):
    """Four layers (c A c c, the first dense), hidden 8, 4 heads of 2 over 2, 4 experts of 6 top 2: every term written out."""
    c = {"hidden_size": 8, "num_hidden_layers": 4, "vocab_size": 16, "num_attention_heads": 4, "num_key_value_heads": 2, "conv_L_cache": 3,
         "layer_types": ["conv", "full_attention", "conv", "conv"], "num_dense_layers": 1, "intermediate_size": 10, "num_experts": 4,
         "num_experts_per_tok": 2, "moe_intermediate_size": 6, "use_expert_bias": True}
    q, kv, hd = 4 * 2, 2 * 2, 2
    conv, attention, dense, expert, router = 4 * 8 * 8 + 3 * 8, 2 * 8 * q + 2 * 8 * kv + 2 * hd, 3 * 8 * 10, 3 * 8 * 6, 8 * 4 + 4
    p = family.layer_params(c)
    assert (p["conv"], p["attention"], p["dense"], p["expert"], p["router"]) == (conv, attention, dense, expert, router) and family.head_dim(c) == 2
    assert family.parameters_held(c) == 3 * conv + attention + dense + 3 * (4 * expert + router) + 8 * 8 + 16 * 8 + 8
    flash = family.flash64_least(c, pairs=55 + 6, tokens=13)
    assert flash == {"bytes": 1.0 * 13 * (2 * q + 2 * kv) * 2, "flops": (55 + 6) * 4.0 * 4 * 2}
    assert family.narrow_decode_least(c, rows=7.0) == {"bytes": 7.0 * 2 * kv * 2, "flops": 7.0 * 4 * 4 * 2}
    fixed = 3 * conv + attention + dense + 3 * router + 8 * 8 + 16 * 8 + 8
    need = family.prefill_least(c, lengths=[10, 3], pairs_local=26.0, experts_hit=3.0)
    kept = 13 * 2 * kv * 2 + 2 * 3 * 2 * 8 * 2  # every position's keys and values in the one attention layer, a window of two rows a prompt in the three convolution layers
    assert need["bytes"] == 2 * (fixed + 3 * 3.0 * expert + 13 * 8) + kept
    matmul = 3 * 4 * 8 * 8 + (attention - 2 * hd) + dense + 3 * 8 * 4
    assert need["flops"] == 2 * 13 * matmul + 2 * 2 * 16 * 8 + 2 * 3 * 26.0 * expert + 4 * 4 * 2 * (55 + 6)
    step = family.decode_step_least(c, lanes=2.0, experts_hit=3.0, kv_tokens=20.0)
    assert step["bytes"] == 2 * (fixed + 3 * 3.0 * expert) + 2 * 2.0 * (3 * 2 * 8 * 2) + 20 * 2 * kv * 2
    assert step["flops"] == 2 * 2.0 * (matmul + 3 * 2 * expert + 16 * 8) + 20 * 4 * 4 * 2
    assert family.train_flops_per_token(c, 10) > 6 * (matmul + 3 * 2 * expert + 16 * 8)


def test_at_the_cells_size_a_prefill_is_bound_by_flops_and_the_narrow_attention_is_a_fourteenth_of_them(c, family):
    peaks = peaks_of("TPU v5 lite")
    whole = family.prefill_least(c, lengths=[10500], pairs_local=4.0 * 10500, experts_hit=64.0)
    assert whole["flops"] / peaks["bf16_flops"] > 5 * whole["bytes"] / peaks["hbm_bytes_per_s"], "bound by FLOPs: 69 ms against 13"
    assert round(whole["flops"] / 1e12, 2) == 13.57 and round(1e3 * whole["flops"] / peaks["bf16_flops"]) == 69
    attention = 4.0 * 32 * 64 * 2 * family.causal_pairs(10500)
    assert 0.06 < attention / whole["flops"] < 0.08 and round(attention / 1e12, 2) == 0.90
    flash = family.flash64_least(c, pairs=2 * family.causal_pairs(10500), tokens=10500)
    assert flash["flops"] == attention and flash["flops"] / peaks["bf16_flops"] > 10 * flash["bytes"] / peaks["hbm_bytes_per_s"]
    step = family.decode_step_least(c, lanes=16.0, experts_hit=41.0, kv_tokens=16 * 10500.0)
    assert step["bytes"] / peaks["hbm_bytes_per_s"] > 10 * step["flops"] / peaks["bf16_flops"] and 9.0 < 1e3 * step["bytes"] / peaks["hbm_bytes_per_s"] < 10.0
    rows = family.narrow_decode_least(c, rows=16 * 10500.0)
    assert rows["bytes"] == 16 * 10500 * 2048 and rows["bytes"] / peaks["hbm_bytes_per_s"] > rows["flops"] / peaks["bf16_flops"], "a layer's read is bound by bytes: 0.42 ms"


def test_the_reference_refuses_nothing_at_toy_size_and_blocks_change_nothing(family, monkeypatch):
    c = family.rehearsal({k: v for k, v in PUBLISHED.items() if k not in family.REHEARSAL_SIZES} | {"family": "lfm2"})
    c["init_router_bias_range"] = 0.5
    cfg = family.program_config(c, 128, remat=False)
    params = jax.jit(lambda k: family.init_params(cfg, k))(jax.random.PRNGKey(0))
    assert "unembed" not in params and sum(a.size for a in jax.tree.leaves(params)) == family.parameters_held(c) == cfg.num_params()
    toks = [int(t) for t in np.random.RandomState(0).randint(1, c["vocab_size"] - 1, size=70)]
    assert [family.padded_length(n) for n in (1, 256, 257, 9000, 12288, 12289)] == [256, 256, 12288, 12288, 12288, 24576]
    choices, gaps = [], []
    family.hidden_states(params, toks, c, choices, gaps)
    assert len(choices) == len(gaps) == 8 and choices[0].shape == (70, 2) and all(float(g.min()) >= 0 for g in gaps)
    lp = np.asarray(family.reference_logprobs(params, toks, c, 39, 70))
    assert lp.shape == (31, c["vocab_size"]) and np.isfinite(lp).all() and np.allclose(np.exp(lp).sum(-1), 1.0, atol=1e-5)
    # the blocks it goes in at the cell's size are not mathematics; nor is what follows a position
    monkeypatch.setattr(family, "QUERY_BLOCK", 16)
    monkeypatch.setattr(family, "ROW_BLOCK", 32)
    family._attention.clear_cache()
    family._dense.clear_cache()
    np.testing.assert_allclose(np.asarray(family.reference_logprobs(params, toks, c, 39, 70)), lp, atol=2e-5, rtol=0)
    monkeypatch.setattr(family, "PAD_TO", (128, 256))
    np.testing.assert_allclose(np.asarray(family.reference_logprobs(params, toks + [5] * 20, c, 39, 70)), lp, atol=2e-5, rtol=0)
    # the bias acts at this size: the same tokens with the bias left out of the choice read otherwise
    unbiased = np.asarray(family.reference_logprobs(params, toks, {**c, "use_expert_bias": False}, 39, 70))
    assert np.abs(unbiased - lp).max() > 1e-3


def test_the_anchor_gives_a_token_one_expert_more_than_it_takes_and_the_bias_chooses_among_them(family):
    """``router_anchor``: k + 1 = 3 own experts a token and layer, ahead of the rest; ``b`` is all
    distinct, so the two of them with the larger ``b`` are taken, and the choice by ``s`` alone is
    another for about two tokens in three. (64 dimensions do not saturate three anchored scores as
    2,048 do: the published width's margins are read on the chip, PERF.md section 6.)"""
    c = family.rehearsal({k: v for k, v in PUBLISHED.items() if k not in family.REHEARSAL_SIZES} | {"family": "lfm2"})
    c.update(init_router_anchor=24.0, init_router_bias_range=0.05, num_hidden_layers=3, layer_types=["conv", "conv", "full_attention"])
    cfg = family.program_config(c, 128, remat=False)
    p = jax.jit(lambda k: family.init_params(cfg, k))(jax.random.PRNGKey(5))
    w = jax.tree.map(lambda a: a[0], p["moe"])
    assert len(set(np.asarray(w["router_bias"]).round(7))) == cfg.n_routed_experts and float(jax.numpy.ptp(w["router_bias"])) == pytest.approx(0.05)
    xn = cfg.norm(p["embed"], jax.numpy.ones((cfg.hidden_size,)))
    s = np.asarray(jax.nn.sigmoid(jax.numpy.dot(xn, w["router"], precision=jax.lax.Precision.HIGHEST)))
    top = np.sort(s, -1)[:, ::-1]
    assert (top[:, 2] - top[:, 3]).min() > 0.1 > (top[:, 0] - top[:, 2]).max(), "three own experts, close to each other and far ahead of the fourth"
    from ray_tpu.models import experts

    idx, _ = experts.route(w, xn, cfg)
    own, by_s = np.argsort(s, -1)[:, -3:], np.argsort(s, -1)[:, -2:]
    assert all(set(i) <= set(o) for i, o in zip(np.asarray(idx), own)), "the bias chooses among a token's own experts"
    assert 0.4 < np.mean([set(i) != set(o) for i, o in zip(np.asarray(idx), by_s)]) < 0.9


# ------------------------------------------------------------------------------------ the three readers
def _scope(s, calls=10):
    return {"s": s, "calls": calls, "flops": 0, "bytes": 0}


def _summary(convolved=True):
    mixers = {"shortconv": _scope(0.50), "shortconv.conv": _scope(0.10), "attn": _scope(0.40)} if convolved else {"mla": _scope(0.7), "mla.attn": _scope(0.4)}
    programs = {"jit_llm_hybrid_prefill": {"calls": 4, "device_s": 5.1, "leaf_s": 5.0, "ops": {}, "scopes": {**mixers, "moe.route": _scope(0.05), "moe.blocks": _scope(3.0), "unscoped": _scope(0.1)}},
                # the step's convolution layers are not the prefill's: their seconds are not read
                "jit_llm_hybrid_fused_step": {"calls": 100, "device_s": 0.9, "leaf_s": 0.9, "ops": {}, "scopes": {"shortconv": _scope(0.2), "shortconv.state": _scope(0.1)}}}
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


def test_the_convolution_layers_prefill_time_on_a_made_up_summary_and_on_nothing(obs):
    read = common.load_reader("prefill_shortconv_ms_per_ktok")
    # two prompts admitted in the stretch, 21,000 tokens: the convolution layers' whole seconds (their sub-scope's among them) a 1,000 of them
    assert read(obs(_summary())) == pytest.approx(0.60 * 1e3 / 21.0)
    assert read(obs(_summary(convolved=False))) is None
    o = obs(_summary())
    o["worker"]["requests"] = {"c": {"admit_t": 99.0, "prompt_tokens": 10000}}
    assert read(o) is None and read({"cell": {"name": "toy.longdoc"}}) is None and read({}) is None


def test_the_two_kernels_readers_on_a_made_up_trace_and_flight_log(c, family, monkeypatch):
    """Two prompts of 9,000 and 12,000 admitted in the stretch: 2 layers x (40.5 M + 72.0 M) pairs at
    8,192 FLOPs a pair over 197 TFLOP/s is 9.4 ms; sixteen lanes at 10,500 positions read 2 x 168,000
    positions a step, 344 MB a call at 819 GB/s."""
    from benchmark import flight

    flash, rows = common.load_reader("flash64_roofline"), common.load_reader("narrow_decode_roofline")
    pairs = 2 * (family.causal_pairs(9000) + family.causal_pairs(12000))
    steps = ([{"t": 101.0, "admitted": 1, "prefill_tokens": 9000, "narrow_pairs": 2 * family.causal_pairs(9000)},
              {"t": 104.0, "admitted": 1, "prefill_tokens": 12000, "narrow_pairs": 2 * family.causal_pairs(12000)},
              {"t": 99.0, "admitted": 1, "prefill_tokens": 10000, "narrow_pairs": 2 * family.causal_pairs(10000)}]  # before the stretch
             + [{"t": 101.5 + 0.1 * n, "narrow_rows_read": 2 * 16 * 10500} for n in range(10)] + [{"t": 103.0, "phase": "mixed"}])
    monkeypatch.setattr(flight, "records", lambda obs: {"steps": steps, "requests": {}})
    peaks = peaks_of("TPU v5 lite")
    obs = {"config": c, "peaks": peaks, "window": [60.0, 105.0],
           "worker": {"trace": {"trace_host": [100.0, 105.0], "ops": {"_fwd_pallas": [4, 0.060], "_fwd_pallas.1": [4, 0.020],
                                                                       "slot_decode_attention_narrow.2": [30, 0.015], "slot_decode_attention_narrow.3": [30, 0.015],
                                                                       "slot_decode_attention": [20, 0.015], "step_experts": [80, 0.5]}}}}
    assert pairs == 2 * (40_504_500 + 72_006_000)
    assert flash(obs) == pytest.approx(100.0 * (pairs * 4 * 32 * 64 / peaks["bf16_flops"]) / 0.080) and 11.0 < flash(obs) < 12.5
    assert family.narrow_decode_least(c, rows=16 * 10500.0)["bytes"] == 344_064_000
    assert rows(obs) == pytest.approx(100.0 * (344_064_000 / peaks["hbm_bytes_per_s"]) * 60 / 0.030) and 83.0 < rows(obs) < 85.0
    # nothing to read: off the chip (no peaks), a program whose prefill or step runs no such kernel, a stretch without the rows, no trace
    assert flash({k: v for k, v in obs.items() if k != "peaks"}) is None and rows({k: v for k, v in obs.items() if k != "peaks"}) is None
    obs["worker"]["trace"]["ops"] = {"slot_decode_attention": [40, 0.001], "window_flash_attention": [4, 0.050]}
    assert flash(obs) is None and rows(obs) is None
    obs["worker"]["trace"]["ops"] = {"_fwd_pallas": [12, 0.080], "slot_decode_attention_narrow.2": [30, 0.010]}
    monkeypatch.setattr(flight, "records", lambda obs: {"steps": [{"t": 103.0, "phase": "mixed"}], "requests": {}})
    assert flash(obs) is None and rows(obs) is None
    monkeypatch.setattr(flight, "records", lambda obs: None)
    assert flash(obs) is None and rows(obs) is None and flash({}) is None and rows({}) is None
