"""The ``kimi_linear`` family file and the cell ``kimi-linear-ep4.longdoc``: the configuration keeps
every published key (depth, experts held and vocabulary are cut, each with its ``reduced_from``), the
family's counts are ISSUE 42's arithmetic and the program's, the least a prefill, a decode step, the
latent attention and the delta rule must do is counted by hand at a small size, the reference refuses
nothing at toy size, and the two new readers (``kda_chunk_roofline``, ``prefill_kda_ms_per_ktok``) read
a made-up summary and nothing where there is nothing."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark import common, scopes, traffic
from benchmark.peaks import peaks_of

BENCH = common.load_benchmark()
CONFIG, CELL = "kimi-linear-48b-a3b-ep4", "kimi-linear-ep4.longdoc"
# the catalog row's ``config`` (guide model-configs, architectures.jsonl, Kimi-Linear-48B-A3B-Instruct), key for key
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu", "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {"full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
                           "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26],
                           "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576, "model_type": "kimi_linear", "moe_intermediate_size": 1024, "moe_layer_freq": 1,
    "moe_renormalize": True, "moe_router_activation_func": "sigmoid", "num_attention_heads": 32, "num_expert_group": 1, "num_experts": 256,
    "num_experts_per_token": 8, "num_hidden_layers": 27, "num_key_value_heads": 32, "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False, "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840,
}
CUT = {"num_hidden_layers": 9, "num_experts": 64, "vocab_size": 40960}
# the readers that list qwen3-next-ep4.longdoc's prefill and decode, which this cell shares; the six that move
# ``ttft_p50_ms`` are listed with that metric or not at all (PERF.md section 2)
SERVE_READERS = {"prefill_ms_per_ktok.longdoc", "decode_device_ms.longdoc", "prefill_bubble_ms", "prefill_step_roofline", "moe_block_fill",
                 "prefill_mixer_ms_per_ktok", "prefill_ffn_ms_per_ktok", "moe_blocks_share", "prefill_stage_idle_ms", "latent_decode_roofline",
                 "kda_chunk_roofline", "prefill_kda_ms_per_ktok"}
TTFT_READERS = {"client_overhead_ms", "queue_wait_p50_ms", "handle_ingress_ms", "replica_ingress_ms", "token_handoff_ms", "stream_egress_ms"}


@pytest.fixture(scope="module")
def c():
    with open(os.path.join(common.HERE, "configs", CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def family():
    return common.load_family("kimi_linear")


def test_the_configuration_keeps_every_published_key_and_cuts_depth_experts_and_vocabulary(c, family):
    assert c["family"] == "kimi_linear" and c["reduced"] == list(CUT) and c["reduced_from"] == {k: PUBLISHED[k] for k in CUT}
    for k, v in PUBLISHED.items():
        assert c[k] == CUT.get(k, v), k
    assert set(c["why_reduced"]) == set(CUT)
    assert family.kinds(c) == [("K", "F"), ("K", "E"), ("K", "E"), ("M", "E"), ("K", "E"), ("K", "E"), ("K", "E"), ("M", "E"), ("K", "E")]
    dep = c["deployment"]
    assert (dep["chips_per_layer"], dep["pipeline_stages"], dep["layers_per_stage"]) == (4, 3, 9) and 3 * 9 == PUBLISHED["num_hidden_layers"]
    assert (dep["experts_published"], dep["experts_held"], dep["vocab_rows_held"]) == (256, [0, 64], [0, 40960]) and family.held(c) == (256, 0, 64)
    assert 4 * c["num_experts"] == 256 and 4 * c["vocab_size"] == 163840
    assert {"chunk_size", "KDA equations", "NoPE latent attention", "router", "initialisation", "anchored routing", "torch_dtype", "norms"} <= set(c["assumed"])
    assert c["init_router_anchor"] == 8.0 and c["tolerance"]["logprob_abs"] <= 0.25 and c["tolerance"]["why"]
    cfg = family.program_config(c, 4096)
    assert (str(cfg.stream_dtype), cfg.router_anchor, cfg.residual_rescale_layers, cfg.expert_layer.held, cfg.chunk_size) == ("bfloat16", 8.0, 54, 64, 64)
    assert cfg.count("moe") * cfg.num_experts == 2048 <= cfg.hidden_size, "one orthogonal matrix serves all the routers (PR 29's scheme)"
    assert cfg.layer_plan == (("kda", "moe", "kda", "moe", "mla", "moe", "kda", "moe"), 2, (), ("kda", "ffn"))
    assert (cfg.qk_head_dim, cfg.rope_row, cfg.q_lora_rank, cfg.mla_rotates, cfg.hd) == (192, 128, None, False, 72)
    entry = {e["name"]: e for e in BENCH["configs"]}[CONFIG]
    assert entry["source"] == c["source"] and entry["reduced"] == c["reduced"] and entry["file"].endswith(CONFIG + ".json") and len(entry["why"]) <= 200
    sv = c["serving"]
    assert (sv["max_num_seqs"], sv["max_seq_len"], sv["warm_batch_max"]) == (16, 4096, 8) and "engine_kwargs" not in sv
    with pytest.raises(ValueError, match="exactly one of"):
        family.kinds({**c, "linear_attn_config": {**c["linear_attn_config"], "kda_layers": [1, 2, 3, 4]}})
    with pytest.raises(ValueError, match="sigmoid"):
        family.program_config({**c, "moe_router_activation_func": "softmax"}, 4096)


def test_the_cell_is_listed_and_whatever_follows_it_was_appended(c):
    cell = {w["name"]: w for w in BENCH["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "longdoc", 1) and len(cell["why"]) <= 200
    listed = {m["name"] for k in ("end_to_end", "per_layer") for m in BENCH[k] if CELL in m.get("workloads", ())}
    assert "serve_tokens_per_s" in listed and SERVE_READERS <= listed
    # time to the first token and the readers that move it go together: listed with it, or none of them
    assert listed - SERVE_READERS - {"serve_tokens_per_s"} in (set(), TTFT_READERS | {"ttft_p50_ms"})
    names = [w["name"] for w in BENCH["workloads"]]
    assert names.index(CELL) > names.index("glm-4.7-flash-d8.longdoc-16k"), "after the cells that were there"
    for k in ("end_to_end", "per_layer"):
        for m in BENCH[k]:
            if CELL in m.get("workloads", ()) and "qwen3-next-ep4.longdoc" in m["workloads"]:
                assert m["workloads"].index(CELL) > m["workloads"].index("qwen3-next-ep4.longdoc"), m["name"]
    per = {m["name"]: m for m in BENCH["per_layer"]}
    assert per["kda_chunk_roofline"] == {"name": "kda_chunk_roofline", "unit": "%", "better": "higher", "source": "device_trace",
                                         "layer": "kernels", "moves": "serve_tokens_per_s", "workloads": [CELL]}
    assert per["prefill_kda_ms_per_ktok"] == {"name": "prefill_kda_ms_per_ktok", "unit": "ms", "better": "lower", "source": "device_trace",
                                              "layer": "step programs", "moves": "serve_tokens_per_s", "workloads": [CELL]}
    assert all(common.load_reader(name) is not None for name in listed - {"serve_tokens_per_s", "ttft_p50_ms"})
    mix = traffic.load_mix("longdoc", CELL)
    assert (mix["kind"], mix["loop"], mix["clients"]) == ("serve", "closed", 21) and mix["clients"] == round(4 / 3 * c["serving"]["max_num_seqs"])
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 2500, "sigma": 0.4, "min": 1024, "max": 3584}
    assert mix["output_len"] == {"dist": "uniform", "min": 16, "max": 48} and mix["sampled_share"] == 0.1
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] <= c["serving"]["max_seq_len"]
    from benchmark.serve_cell import default_buckets, warm_plan

    assert [b for b, _ in warm_plan(mix, default_buckets(4096))] == [1024, 2048, 4096]


def test_the_counts_are_the_issues_arithmetic(c, family):
    p = family.layer_params(c)
    assert p["K"] == 3 * 2304 * 4096 + 4096 * 2304 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32 + 3 * 4 * 4096 + 4096 + 32 + 128 == 39_514_272
    assert p["M"] == 2304 * 32 * 192 + 2304 * 576 + 512 + 512 * 32 * 256 + 32 * 128 * 2304 == 29_114_880
    assert p["expert"] == 3 * 2304 * 1024 == 7_077_888 and p["E_rest"] == 2304 * 256 + 256 + 7_077_888 + 2 * 2304 == 7_672_576
    assert p["F_rest"] == 3 * 2304 * 9216 + 2 * 2304 == 63_705_600 and p["embed_and_head"] == 2 * 40960 * 2304 == 188_743_680
    kda_layer, mla_layer = p["K"] + p["E_rest"] + 64 * p["expert"], p["M"] + p["E_rest"] + 64 * p["expert"]
    assert (kda_layer, mla_layer, p["K"] + p["F_rest"]) == (500_171_680, 489_772_288, 103_219_872)
    held = family.parameters_held(c)
    assert held == c["parameters"] == 103_219_872 + 6 * kda_layer + 2 * mla_layer + 188_743_680 + 2_304 == 4_272_540_512
    assert round(2 * held / 1e9, 2) == 8.55 and round(2 * held / 2**30, 2) == 7.96
    whole = {**c, **c["reduced_from"], "deployment": None}
    assert family.parameters_held(whole) == c["parameters_published"] == 49_122_681_728
    assert family.layer_params(whole)["E_rest"] + 256 * p["expert"] + p["K"] == 1_859_126_176, "one expert layer whole: 3.7 GB"
    assert family.program_config(c, 4096).num_params() == held
    # what a sequence keeps: a state a KDA layer, and a row a position and latent layer as published and as the chip stores it
    assert family.state_bytes_per_slot(c) == 7 * (32 * 128 * 128 * 4 + 3 * 3 * 4096 * 2) == 15_196_160
    assert family.row_width(c) == 576 and family.kv_bytes_per_token(c) == 2 * 1_152
    from ray_tpu.llm import state_cache
    from ray_tpu.llm.kv_cache import entry_bytes_per_token

    cfg = family.program_config(c, 4096)
    assert entry_bytes_per_token(cfg.position_entries()) == 2 * 1_280 and state_cache.bytes_per_slot(cfg) == 15_196_160


def test_the_least_a_prefill_a_step_the_latent_attention_and_the_rule_must_do_by_hand_at_one_small_size(family):
    """Three published layers (KDA with the dense MLP, MLA with experts, KDA with experts), hidden 8, so that every term can be written out."""
    c = {"hidden_size": 8, "num_hidden_layers": 3, "first_k_dense_replace": 1, "vocab_size": 16, "intermediate_size": 12,
         "linear_attn_config": {"full_attn_layers": [2, 4], "kda_layers": [1, 3], "head_dim": 3, "num_heads": 2, "short_conv_kernel_size": 4},
         "num_attention_heads": 2, "kv_lora_rank": 4, "qk_nope_head_dim": 3, "qk_rope_head_dim": 2, "v_head_dim": 5,
         "num_experts": 2, "num_shared_experts": 1, "num_experts_per_token": 2, "moe_intermediate_size": 4,
         "deployment": {"experts_published": 4, "experts_held": [2, 4]}}
    D = 2 * 3
    kda_mats = 8 * 3 * D + 8 * (2 * 3 + 2) + 4 * 3 * D + 2 * 3 * D + D * 8  # q k v, [f_a | g_a | b], the convolutions' taps, f_b and g_b, o
    kda = kda_mats + D + 2 + 3  # dt_bias, A_log, the head norm
    mla_mats = 8 * 2 * 5 + 8 * 6 + 4 * 2 * (3 + 5) + 2 * 5 * 8  # W_q, W_kva, W_kb + W_vb, W_o
    mla = mla_mats + 4  # the latent's norm
    dense_rest, expert = 3 * 8 * 12 + 16, 3 * 8 * 4
    experts_rest = 8 * 4 + 4 + expert + 16  # router (its published width), correction bias, shared expert, two norms
    p = family.layer_params(c)
    assert (p["K"], p["M"], p["F_rest"], p["E_rest"], p["expert"]) == (kda, mla, dense_rest, experts_rest, expert)
    assert family.held(c) == (4, 2, 2) and family.kinds(c) == [("K", "F"), ("M", "E"), ("K", "E")]
    assert family.parameters_held(c) == 2 * kda + mla + dense_rest + 2 * (experts_rest + 2 * expert) + 2 * 16 * 8 + 8
    fixed = 2 * kda + mla + dense_rest + 2 * experts_rest + 8 * 16 + 8  # every weight outside the routed experts, the head, the final norm
    state = 2 * (2 * 3 * 3 * 4 + 3 * 3 * D * 2)  # two KDA layers: a float32 state a head, three inputs of three convolutions
    assert (family.state_bytes_per_slot(c), family.row_width(c), family.kv_bytes_per_token(c)) == (state, 6, 1 * 6 * 2)
    macs = 2 * kda_mats + mla_mats + (dense_rest - 16) + 2 * (experts_rest - 16 - 4)  # what multiplies a token outside routed experts and head
    lengths = [5, 3]
    need = family.prefill_least(c, lengths=lengths, pairs_local=6.0, experts_hit=1.5)
    assert need["bytes"] == 2 * (fixed + 2 * 1.5 * expert + 8 * 8) + 2 * state + 8 * 12
    assert need["flops"] == (2 * 8 * macs + 2 * 2 * 8 * 16 + 2 * 2 * 6.0 * expert + 7 * 8 * 2 * (2 * 3 * 3)
                             + 2 * (5 * 6 / 2 + 3 * 4 / 2) * 1 * 2 * (3 + 2 + 5))
    # lower bounds by construction: one prompt of the same tokens has more attention and one state fewer; more pairs, more work
    assert family.prefill_least(c, [8], 6.0, 1.5)["bytes"] == need["bytes"] - state and family.prefill_least(c, [8], 6.0, 1.5)["flops"] > need["flops"]
    assert family.prefill_least(c, lengths, 7.0, 1.5)["flops"] > need["flops"] and family.prefill_least(c, lengths, 6.0, 2.0)["bytes"] > need["bytes"]
    assert family.latent_attention_least(c, rows=40.0) == {"bytes": 40.0 * 6 * 2, "flops": 40.0 * 2 * 2 * (6 + 4)}
    # the rule of ONE layer: q, k, v and the output in the configuration's dtype, the gate a channel and beta a head in float32,
    # a sequence's state once; 7 FLOPs a state element and position, and nothing that knows of a chunk
    rule = family.kda_chunk_least(c, tokens=10.0, sequences=2.0)
    assert rule == {"bytes": 10.0 * (4 * D * 2 + D * 4 + 2 * 4) + 2.0 * 2 * 3 * 3 * 4, "flops": 7.0 * 10.0 * 2 * 3 * 3}
    assert family.kda_chunk_least(c, tokens=10.0)["bytes"] == 10.0 * (4 * D * 2 + D * 4 + 2 * 4)
    step = family.decode_step_least(c, lanes=3, experts_hit=1.0, kv_tokens=20)
    assert step["bytes"] == 2 * (fixed + 2 * 1.0 * expert + 3 * 8) + 2 * 3 * state + 20 * 1 * 6 * 2
    assert step["flops"] == 2 * 3 * (macs + 2 * (2 * 2 / 4) * expert + 8 * 16) + 7 * 3 * 2 * (2 * 3 * 3) + 20 * 1 * 2 * 2 * (6 + 4)
    assert family.train_flops_per_token(c, 10) == 3 * (2 * (macs + 2 * 1.0 * expert + 8 * 16) + 7 * 2 * 18 + 10 * 1 * 2 * 10)


def test_at_the_cells_size_the_rule_is_bound_by_bytes_and_half_a_prefills_flops_are_the_kda_layers(c, family):
    peaks = peaks_of("TPU v5 lite")
    rule = family.kda_chunk_least(c, tokens=1000.0)
    assert rule == {"bytes": 1000.0 * 49_280, "flops": 1000.0 * 7 * 32 * 128 * 128} and rule["flops"] / 1000 == 3_670_016
    assert rule["bytes"] / peaks["hbm_bytes_per_s"] > 3 * rule["flops"] / peaks["bf16_flops"]
    assert rule["bytes"] / peaks["hbm_bytes_per_s"] * 1e3 == pytest.approx(0.060, abs=0.001), "ms a 1,000 positions and layer"
    one = family.prefill_least(c, lengths=[2500], pairs_local=2.0 * 2500, experts_hit=64.0)
    assert one["flops"] / 2500 / 1e9 == pytest.approx(1.23, abs=0.02) and one["flops"] / peaks["bf16_flops"] > one["bytes"] / peaks["hbm_bytes_per_s"]
    p = family.layer_params(c)
    kda = 7 * (2.0 * (p["K"] - 4096 - 32 - 128) + 7 * 32 * 128 * 128)
    assert kda / (one["flops"] / 2500) == pytest.approx(0.47, abs=0.02)
    step = family.decode_step_least(c, lanes=16, experts_hit=25, kv_tokens=16 * 2500)
    assert step["bytes"] / peaks["hbm_bytes_per_s"] > step["flops"] / peaks["bf16_flops"]
    assert 0.0045 < step["bytes"] / peaks["hbm_bytes_per_s"] < 0.0065  # 1.1 GB outside the experts, 2.8 GB of experts hit, 0.5 GB of state moved


def test_the_reference_refuses_nothing_at_toy_size_and_blocks_change_nothing(family, monkeypatch):
    c = family.rehearsal({"rms_norm_eps": 1e-5, "mla_use_nope": True, "rope_theta": 10000, "num_shared_experts": 1, "moe_renormalize": True,
                          "routed_scaling_factor": 2.446, "q_lora_rank": None, "family": "kimi_linear"})
    cfg = family.program_config(c, 128, remat=False)
    params = jax.jit(lambda k: family.init_params(cfg, k))(jax.random.PRNGKey(0))
    toks = [int(t) for t in np.random.RandomState(0).randint(1, c["vocab_size"] - 1, size=70)]
    assert [family.padded_length(n) for n in (1, 1024, 1025, 3632, 4096, 4097)] == [1024, 1024, 4096, 4096, 4096, 8192]
    monkeypatch.setattr(family, "PAD_TO", (96,))
    lp = np.asarray(family.reference_logprobs(params, toks, c, 10, 70))
    assert lp.shape == (60, c["vocab_size"]) and np.isfinite(lp).all() and np.allclose(np.exp(lp).sum(-1), 1.0, atol=1e-5)
    # the blocks of queries it goes in at the cell's size are not mathematics; nor is what follows a position
    monkeypatch.setattr(family, "QUERY_BLOCK", 8)
    family._latent_attention.clear_cache()
    np.testing.assert_allclose(np.asarray(family.reference_logprobs(params, toks, c, 10, 70)), lp, atol=2e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(family.reference_logprobs(params, toks + [5] * 20, c, 10, 70)), lp, atol=2e-5, rtol=0)
    choices = []
    family.hidden_states(params, toks, c, choices)
    assert len(choices) == 4 and all(np.asarray(idx).shape == (70, 2) and np.asarray(idx).max() < 8 for idx in choices), "the router's whole width"


# ------------------------------------------------------------------------------------ the two readers
def _scope(s, calls=10):
    return {"s": s, "calls": calls, "flops": 0, "bytes": 0}


def _summary(kda=True):
    rule = {"kda": _scope(0.30), "kda.chunk": _scope(0.50), "kda.scan": _scope(0.20)} if kda else {"gdn": _scope(0.3), "gdn.chunk": _scope(0.7)}
    programs = {"jit_llm_hybrid_prefill": {"calls": 4, "device_s": 3.1, "leaf_s": 3.0, "ops": {}, "scopes": {
                    **rule, "mla": _scope(0.1), "mla.attn": _scope(0.2), "moe": _scope(0.1), "moe.blocks": _scope(0.8), "unscoped": _scope(0.1)}},
                # the step's rule is not the prefill's: its seconds are not read
                "jit_llm_hybrid_fused_step": {"calls": 100, "device_s": 0.6, "leaf_s": 0.6, "ops": {}, "scopes": {"kda": _scope(0.2), "kda.state": _scope(0.1)}}}
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
        requests = {"a": {"admit_t": 101.0, "prompt_tokens": 3000}, "b": {"admit_t": 104.0, "prompt_tokens": 5000},
                    "c": {"admit_t": 99.0, "prompt_tokens": 7000}, "d": {"admit_t": None, "prompt_tokens": 9000}}
        return {"window": [60.0, 105.0], "cell": {"name": "toy.longdoc"}, "config": c, "peaks": peaks_of("TPU v5 lite"),
                "worker": {"trace": {"trace_host": trace_host}, "requests": requests}}
    return make


def test_the_two_new_readers_on_a_made_up_summary_and_on_nothing(c, family, obs):
    roofline, per_ktok = common.load_reader("kda_chunk_roofline"), common.load_reader("prefill_kda_ms_per_ktok")
    o = obs(_summary())
    # two prompts admitted in the stretch, 8,000 tokens: the whole layer's seconds a 1,000 of them
    assert per_ktok(o) == pytest.approx((0.30 + 0.50 + 0.20) * 1e3 / 8.0)
    # seven layers x the least of one (bytes: 49,280 a token and 2 MB of state a sequence, at 819 GB/s) over chunk + scan
    least = 7 * (8000 * 49_280 + 2 * 32 * 128 * 128 * 4) / 819e9
    assert family.kda_chunk_least(c, tokens=8000, sequences=2)["bytes"] == 8000 * 49_280 + 2 * 2_097_152
    assert roofline(o) == pytest.approx(100.0 * least / 0.70) and 0.4 < roofline(o) < 0.6
    # the same work whatever runs the rule: a kernel that takes a tenth of the time under the same scopes reads ten times the share
    fast = _summary()
    fast["programs"]["jit_llm_hybrid_prefill"]["scopes"].update({"kda.chunk": _scope(0.05), "kda.scan": _scope(0.02)})
    assert common.load_reader("kda_chunk_roofline")(obs(fast)) == pytest.approx(100.0 * least / 0.07)
    # nothing to read: another description's scopes, no peaks (off the chip), no admission in the stretch, no trace, no worker
    other = obs(_summary(kda=False))
    assert roofline(other) is None and per_ktok(other) is None
    o = obs(_summary())
    assert roofline({k: v for k, v in o.items() if k != "peaks"}) is None
    none_admitted = {**o, "worker": {**o["worker"], "requests": {"c": {"admit_t": 99.0, "prompt_tokens": 7000}}}}
    assert roofline(none_admitted) is None and per_ktok(none_admitted) is None
    assert roofline({**o, "worker": {}}) is None and per_ktok({**o, "worker": {}}) is None
    assert roofline({"window": [0.0, 1.0]}) is None and per_ktok({"window": [0.0, 1.0]}) is None
