"""The ``jamba`` family file and the cell ``jamba2-3b.longdoc-12k``: the configuration keeps every
published key and cuts nothing, the family's counts are ISSUE 60's arithmetic and the program's,
the least a selective scan, a prefill and a decode step must do is counted by hand at a small size,
the reference refuses nothing at toy size, and the three new readers read a made-up summary, and
nothing where there is nothing."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark import common, scopes, traffic
from benchmark.peaks import peaks_of

BENCH = common.load_benchmark()
CONFIG, CELL = "jamba2-3b", "jamba2-3b.longdoc-12k"
# the catalog row's ``config`` (guide model-configs, architectures.jsonl, AI21-Jamba2-3B), key for key
PUBLISHED = {
    "attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1, "expert_layer_period": 2, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 8192, "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160, "mamba_expand": 2,
    "mamba_proj_bias": False, "max_position_embeddings": 262144, "model_type": "jamba", "num_attention_heads": 20, "num_experts": 1,
    "num_experts_per_tok": 1, "num_hidden_layers": 28, "num_key_value_heads": 1, "num_logits_to_keep": 1, "rms_norm_eps": 1e-06, "sliding_window": None,
    "tie_word_embeddings": True, "use_mamba_kernels": True, "vocab_size": 65536,
}
SERVE_READERS = {"prefill_ms_per_ktok.longdoc", "decode_device_ms.longdoc", "prefill_bubble_ms", "prefill_stage_idle_ms", "prefill_mixer_ms_per_ktok",
                 "prefill_ffn_ms_per_ktok", "prefill_step_roofline", "prefill_mamba1_ms_per_ktok", "prefill_selscan_ms_per_ktok", "selective_scan_roofline"}
NEW = (("prefill_mamba1_ms_per_ktok", "ms", "lower", "step programs"), ("prefill_selscan_ms_per_ktok", "ms", "lower", "step programs"),
       ("selective_scan_roofline", "%", "higher", "kernels"))


@pytest.fixture(scope="module")
def c():
    with open(os.path.join(common.HERE, "configs", CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def family():
    return common.load_family("jamba")


def test_the_configuration_keeps_every_published_key_and_cuts_nothing(c, family):
    assert c["family"] == "jamba" and c["reduced"] == [] and c["reduced_from"] == {}
    for k, v in PUBLISHED.items():
        assert c[k] == v, k
    kinds = family.kinds(c)
    assert len(kinds) == 28 and [l for l, k in enumerate(kinds) if k == "attention"] == [7, 21] and kinds.count("mamba") == 26
    assert family.head_dim(c) == 128 == c["head_dim"] and family.d_inner(c) == 5120
    assert {"head_dim", "layer order", "feed-forward", "inner norms", "dt_proj bias", "no positions", "state layout", "tied head", "torch_dtype", "initialisation", "final norm"} <= set(c["assumed"])
    tol = c["tolerance"]
    assert 0 < tol["logprob_abs"] <= 0.25 and tol["why"] and max(tol["served"]) < tol["logprob_abs"] < min(tol["float8"])
    cfg = family.program_config(c, 12288)
    assert (str(cfg.stream_dtype), cfg.num_hidden_layers, cfg.mamba_d_conv, cfg.mamba_dt_rank, cfg.residual_rescale_layers, cfg.rms_eps) == ("bfloat16", 28, 4, 160, 56, 1e-6)
    assert cfg.hd == c["head_dim"] and (cfg.mamba_conv_bias, cfg.mamba_proj_bias, cfg.num_experts) == (True, False, 1)
    assert cfg.layer_plan.repeats == 2 and len(cfg.layer_plan.period) == 28 and not cfg.layer_plan.head and not cfg.layer_plan.tail
    entry = {e["name"]: e for e in BENCH["configs"]}[CONFIG]
    assert entry["source"] == c["source"] and entry["reduced"] == c["reduced"] == [] and entry["file"].endswith(CONFIG + ".json") and len(entry["why"]) <= 200
    sv = c["serving"]
    assert (sv["max_num_seqs"], sv["max_seq_len"], sv["max_ongoing_requests"], sv["warm_batch_max"]) == (16, 12288, 64, 4) and "engine_kwargs" not in sv
    with pytest.raises(ValueError, match="num_experts > 1 routes it"):
        family.kinds({**c, "num_experts": 16})
    with pytest.raises(ValueError, match="its head is tied"):
        family.program_config({**c, "tie_word_embeddings": False}, 12288)


def test_the_cell_is_listed_and_what_stood_before_it_still_stands_in_its_order(c):
    """Listed, and never "last": the next PR appends after it."""
    names = [w["name"] for w in BENCH["workloads"]]
    cell = BENCH["workloads"][names.index(CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "longdoc-12k", 1) and len(cell["why"]) <= 200
    before = ["internlm2-1.8b.chat", "mistral-7b-d6.sft-2k", "internlm2-1.8b.longdoc", "nemotron-3-nano-ep2.chat", "qwen3-next-ep4.longdoc", "glm-4.7-flash-d8.longdoc-16k",
              "kimi-linear-ep4.longdoc", "minicpm-sala-d8.longdoc-12k", "smallthinker-21b-d8.longdoc-12k", "lfm2-24b-d10.longdoc-12k", "keye-vl-2.0-d6.longdoc-24k"]
    assert names[:11] == before and names.index(CELL) == 11 and [e["name"] for e in BENCH["configs"]].index(CONFIG) == 10
    assert not any(w["chips"] == 4 for w in BENCH["workloads"][:12])
    listed = {m["name"] for k in ("end_to_end", "per_layer") for m in BENCH[k] if CELL in m.get("workloads", ())}
    assert listed == SERVE_READERS | {"serve_tokens_per_s"}, "tokens per second and what moves it; nothing is routed, so no reader of the expert layer"
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
    plain = traffic.load_mix("longdoc-12k", "lfm2-24b-d10.longdoc-12k")
    assert {k: v for k, v in mix.items() if not k.startswith("why_") and k not in ("ramp_s", "drain_s")} == {k: v for k, v in plain.items() if not k.startswith("why_") and k not in ("ramp_s", "drain_s")}, "the mix as it stands, but the ramp and the drain"
    from benchmark.serve_cell import default_buckets, warm_plan

    assert [b for b, _ in warm_plan(mix, default_buckets(12288))] == [12288], "one bucket"


def test_the_counts_are_the_issues_arithmetic(c, family):
    p = family.layer_params(c)
    assert p["mamba"] == 41_241_792 == 26_214_400 + 25_600 + 983_040 + 192 + 824_320 + 81_920 + 5_120 + 13_107_200
    assert p["mamba_matmul"] == 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560 and p["attention"] == 2 * 2560 * 2560 + 2 * 2560 * 128 == 13_762_560
    assert p["dense"] == 3 * 2560 * 8192 == 62_914_560 and p["embed"] == 65536 * 2560 == 167_772_160
    assert p["mamba"] + p["dense"] + 2 * p["norm"] == 104_161_472 and p["attention"] + p["dense"] + 2 * p["norm"] == 76_682_240
    held = family.parameters_held(c)
    assert held == c["parameters"] == c["parameters_published"] == 26 * 104_161_472 + 2 * 76_682_240 + 167_772_160 + 2560 == 3_029_337_472
    assert round(2 * held / 1e9, 2) == 6.06 and family.parameters_published(c) == held and family.program_config(c, 12288).num_params() == held
    # a token's matrix products: 5.72 GFLOP, of which the SwiGLU 62%, the Mamba mixers 37%, attention's projections 1%
    per_token = 2 * family._per_token_matmul(c)
    shares = {"dense": 2 * 28 * p["dense"] / per_token, "mamba": 2 * 26 * p["mamba_matmul"] / per_token, "attention": 2 * 2 * p["attention"] / per_token}
    assert round(per_token / 1e9, 2) == 5.72 and {k: round(v, 2) for k, v in shares.items()} == {"dense": 0.62, "mamba": 0.37, "attention": 0.01}
    # a position in the cache, a sequence's state, and the caches whole
    assert family.kv_bytes_per_token(c) == 2 * 2 * 128 * 2 == 1024 and family.state_bytes_per_slot(c) == 26 * (5120 * 16 * 4 + 3 * 5120 * 2) == 9_318_400
    assert family.cache_bytes(c, 16, 12288) == 16 * (12288 * 1024 + 9_318_400) == 350_420_992
    from ray_tpu.llm import state_cache
    from ray_tpu.llm.kv_cache import alloc_entries, entry_bytes_per_token

    cfg = family.program_config(c, 12288)
    assert entry_bytes_per_token(cfg.position_entries()) == 1024 and state_cache.bytes_per_slot(cfg) == 9_318_400
    cache = jax.eval_shape(lambda: alloc_entries(cfg.position_entries(), 16, 12288, cfg.ring_entries()))
    state = jax.eval_shape(lambda: state_cache.alloc(cfg, 16))
    assert cache["k"].shape == (2, 16, 12288, 1, 128) and sum(a.size * a.dtype.itemsize for n, a in cache.items() if n != "length") == 201_326_592
    assert {n: (a.shape, str(a.dtype)) for n, a in state.items()} == {"ssm": ((26, 16, 16, 5120), "float32"), "conv": ((26, 16, 3, 5120), "bfloat16")}
    # with the weights: 6.41 GB, 37% of the chip's 16 GiB
    assert round((2 * held + 350_420_992) / 1e9, 2) == 6.41 and round((2 * held + 350_420_992) / 2**34, 2) == 0.37
    # the counter the program writes into its flight log: positions as padded, in every Mamba layer
    assert cfg.prefill_counters(4, 12288, lengths=[8704, 10500, 12160, 100])["selscan_positions"] == 26 * 4 * 12288


def test_the_least_a_scan_a_prefill_and_a_step_must_do_by_hand_at_one_small_size(family):
    """Four layers (M A M M), hidden 8, d_inner 16, 4 states, step rank 2, 4 heads of 2 over 1, a SwiGLU of 10: every term written out."""
    c = {"hidden_size": 8, "num_hidden_layers": 4, "vocab_size": 16, "num_attention_heads": 4, "num_key_value_heads": 1, "attn_layer_period": 4,
         "attn_layer_offset": 1, "intermediate_size": 10, "mamba_expand": 2, "mamba_d_state": 4, "mamba_d_conv": 3, "mamba_dt_rank": 2,
         "mamba_conv_bias": True, "mamba_proj_bias": False, "num_experts": 1}
    di, R, S, K, hd = 16, 2, 4, 3, 2
    matmul = 8 * 2 * di + di * (R + 2 * S) + R * di + di * 8
    mamba = matmul + K * di + di + R + 2 * S + di + di * S + di
    attention, dense = 2 * 8 * 8 + 2 * 8 * 2, 3 * 8 * 10
    p = family.layer_params(c)
    assert (p["mamba"], p["mamba_matmul"], p["attention"], p["dense"]) == (mamba, matmul, attention, dense) and family.kinds(c) == ["mamba", "attention", "mamba", "mamba"]
    assert family.layer_params({**c, "mamba_conv_bias": False, "mamba_proj_bias": True})["mamba"] == mamba - di + 2 * di + 8
    held = 3 * mamba + attention + 4 * dense + 8 * 8 + 16 * 8 + 8
    assert family.parameters_held(c) == held
    scan = family.selective_scan_least(c, tokens=13, sequences=2)
    assert scan == {"bytes": 3.0 * (13 * (2 * di + R + 2 * S) * 2 + 2 * di * S * 4), "flops": 3.0 * 13 * 7 * di * S}
    state = 3 * (di * S * 4 + (K - 1) * di * 2)
    assert family.state_bytes_per_slot(c) == state and family.kv_bytes_per_token(c) == 2 * 1 * hd * 2
    need = family.prefill_least(c, lengths=[10, 3], pairs_local=0.0, experts_hit=0.0)
    assert need["bytes"] == 2 * (held + 13 * 8) + 13 * 2 * hd * 2 + 2 * state
    per_token = 3 * matmul + attention + 4 * dense
    assert need["flops"] == 2 * 13 * per_token + 2 * 2 * 16 * 8 + 4 * 4 * hd * (55 + 6) + scan["flops"]
    assert family.prefill_least(c, lengths=[10, 3]) == need, "nothing is routed: the readers' two routing arguments change nothing"
    step = family.decode_step_least(c, lanes=2.0, experts_hit=0.0, kv_tokens=20.0)
    assert step["bytes"] == 2 * held + 2 * 2.0 * state + 20 * 2 * hd * 2
    assert step["flops"] == 2 * 2.0 * (per_token + 16 * 8) + 20 * 4 * 4 * hd + 3.0 * 2 * 7 * di * S
    assert family.train_flops_per_token(c, 10) > 6 * (per_token + 16 * 8)


def test_at_the_cells_size_the_scan_is_bound_by_bytes_on_the_published_peaks_and_a_prefill_by_flops(c, family):
    peaks = peaks_of("TPU v5 lite")
    scan = family.selective_scan_least(c, tokens=10500, sequences=1)
    assert scan["bytes"] == 26 * (10500 * 20_864 + 327_680) and scan["flops"] == 26 * 10500 * 7 * 81_920
    bytes_s, flops_s = scan["bytes"] / peaks["hbm_bytes_per_s"], scan["flops"] / peaks["bf16_flops"]
    assert bytes_s > 8 * flops_s, "no peak of the vector unit is published: the share is of the HBM bound"
    assert round(1e6 * bytes_s / (26 * 10500), 3) == 0.026 and round(1e3 * bytes_s, 1) == 7.0
    whole = family.prefill_least(c, lengths=[10500])
    assert whole["flops"] / peaks["bf16_flops"] > 10 * whole["bytes"] / peaks["hbm_bytes_per_s"]
    assert round(whole["flops"] / 1e12, 1) == 61.3 and round(1e3 * whole["flops"] / peaks["bf16_flops"]) == 311
    attention = 4.0 * 20 * 128 * 2 * family.causal_pairs(10500)
    assert 0.015 < attention / whole["flops"] < 0.025 and round(scan["flops"] / whole["flops"], 4) == 0.0026
    step = family.decode_step_least(c, lanes=16.0, experts_hit=0.0, kv_tokens=16 * 10500.0)
    assert step["bytes"] / peaks["hbm_bytes_per_s"] > 10 * step["flops"] / peaks["bf16_flops"] and 7.5 < 1e3 * step["bytes"] / peaks["hbm_bytes_per_s"] < 8.5


def test_the_reference_refuses_nothing_at_toy_size_and_blocks_change_nothing(family, monkeypatch):
    c = family.rehearsal({k: v for k, v in PUBLISHED.items() if k not in family.REHEARSAL_SIZES} | {"family": "jamba"})
    cfg = family.program_config(c, 128, remat=False)
    params = jax.jit(lambda k: family.init_params(cfg, k))(jax.random.PRNGKey(0))
    assert "unembed" not in params and sum(a.size for a in jax.tree.leaves(params)) == family.parameters_held(c) == cfg.num_params()
    toks = [int(t) for t in np.random.RandomState(0).randint(1, c["vocab_size"] - 1, size=70)]
    assert [family.padded_length(n) for n in (1, 256, 257, 9000, 12288, 12289)] == [256, 256, 12288, 12288, 12288, 24576]
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
    # the decay by state acts at this size: one decay a channel (the mean over its states) reads otherwise
    A = jax.numpy.exp(params["mamba1"]["A_log"])
    flat = {**params, "mamba1": {**params["mamba1"], "A_log": jax.numpy.log(jax.numpy.broadcast_to(A.mean(-1, keepdims=True), A.shape))}}
    assert np.abs(np.asarray(family.reference_logprobs(flat, toks, c, 39, 70)) - lp).max() > 1e-3


# ------------------------------------------------------------------------------------ the three readers
def _scope(s, calls=10):
    return {"s": s, "calls": calls, "flops": 0, "bytes": 0}


def _summary(scanned=True):
    mixers = ({"mamba1": _scope(0.50), "mamba1.conv": _scope(0.10), "mamba1.scan": _scope(0.30), "attn": _scope(0.05)} if scanned
              else {"mamba2": _scope(0.7), "attn": _scope(0.1)})
    programs = {"jit_llm_hybrid_prefill": {"calls": 4, "device_s": 2.1, "leaf_s": 2.0, "ops": {}, "scopes": {**mixers, "ffn": _scope(1.0), "unscoped": _scope(0.05)}},
                # the step's Mamba layers are not the prefill's: their seconds are not read
                "jit_llm_hybrid_fused_step": {"calls": 100, "device_s": 0.9, "leaf_s": 0.9, "ops": {}, "scopes": {"mamba1": _scope(0.2), "mamba1.state": _scope(0.1)}}}
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


def test_the_three_readers_on_a_made_up_summary_and_on_nothing(obs, family, c):
    """Two prompts admitted in the stretch, 21,000 tokens: the Mamba layers' whole seconds (their sub-scopes' among
    them) and the scan's alone a 1,000 of them; the scan's least, 26 layers x (21,000 x 20,864 B + 2 states) at
    819 GB/s = 13.9 ms, over its 0.30 s."""
    whole, scan, share = (common.load_reader(n) for n in ("prefill_mamba1_ms_per_ktok", "prefill_selscan_ms_per_ktok", "selective_scan_roofline"))
    o = obs(_summary())
    assert whole(o) == pytest.approx(0.90 * 1e3 / 21.0) and scan(o) == pytest.approx(0.30 * 1e3 / 21.0)
    least_s = 26 * (21000 * 20_864 + 2 * 327_680) / peaks_of("TPU v5 lite")["hbm_bytes_per_s"]
    assert share(o) == pytest.approx(100.0 * least_s / 0.30) and 4.5 < share(o) < 4.8
    # nothing to read: a program without the scopes, a stretch that admitted nothing, off the chip (no peaks), no trace
    other = obs(_summary(scanned=False))
    assert whole(other) is None and scan(other) is None and share(other) is None
    o = obs(_summary())
    o["worker"]["requests"] = {"c": {"admit_t": 99.0, "prompt_tokens": 10000}}
    assert whole(o) is None and scan(o) is None and share(o) is None
    o = obs(_summary())
    assert share({k: v for k, v in o.items() if k != "peaks"}) is None
    assert share({**o, "config": {**c, "family": "lfm2"}}) is None, "a family without such a count"
    for read in (whole, scan, share):
        assert read({"cell": {"name": "toy.longdoc"}}) is None and read({}) is None
