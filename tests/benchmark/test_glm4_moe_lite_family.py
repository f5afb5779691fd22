"""The ``glm4_moe_lite`` family file and the cell ``glm-4.7-flash-d8.longdoc-16k``: the configuration
keeps every published key (depth alone is cut), the family's counts are ISSUE 36's arithmetic and
the program's, the least a prefill, a decode step and the latent attention must do is counted by
hand at a small size, the reference refuses nothing at toy size, and the one new reader
(``latent_decode_roofline``) reads a made-up observation and nothing where there is nothing."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark import common, traffic
from benchmark.peaks import peaks_of

BENCH = common.load_benchmark()
CONFIG, CELL = "glm-4.7-flash-d8", "glm-4.7-flash-d8.longdoc-16k"
# the catalog row's ``config`` (guide model-configs, architectures.jsonl, GLM-4.7-Flash), key for key
PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536, "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1, "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4, "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1, "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 192,
    "qk_rope_head_dim": 64, "v_head_dim": 256, "vocab_size": 154880,
}
# the readers of the long-document cells that move ``serve_tokens_per_s``; the six that move ``ttft_p50_ms`` are not
# listed, because the cell does not report that metric (its median lies in a gap of a quantised distribution: PERF.md section 2)
LONGDOC_READERS = {"prefill_ms_per_ktok.longdoc", "decode_device_ms.longdoc", "prefill_bubble_ms"}


@pytest.fixture(scope="module")
def c():
    with open(os.path.join(common.HERE, "configs", CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def family():
    return common.load_family("glm4_moe_lite")


def test_the_configuration_keeps_every_published_key_and_cuts_depth_alone(c, family):
    assert c["family"] == "glm4_moe_lite" and c["reduced"] == ["num_hidden_layers"] and c["reduced_from"] == {"num_hidden_layers": 47}
    for k, v in PUBLISHED.items():
        assert c[k] == (8 if k == "num_hidden_layers" else v), k
    assert set(c["why_reduced"]) == {"num_hidden_layers"} and family.kinds(c) == ["F"] + ["E"] * 7
    dep = c["deployment"]
    assert (dep["chips_per_layer"], dep["pipeline_stages"], dep["layers_per_stage"]) == (1, 6, [8, 8, 8, 8, 8, 7]) and sum(dep["layers_per_stage"]) == 47
    assert "stage 0" in dep["this_chip"] and "head" in dep["this_chip"]
    assert {"rope pairing", "initialisation", "anchored routing", "multi-token prediction", "torch_dtype", "norms"} <= set(c["assumed"])
    assert "NOT run" in c["assumed"]["multi-token prediction"] and c["num_nextn_predict_layers"] == 1, "left out, and written down as left out"
    assert c["init_router_anchor"] == 8.0 and c["tolerance"]["logprob_abs"] <= 0.25 and c["tolerance"]["why"]
    cfg = family.program_config(c, 16384)
    assert (str(cfg.stream_dtype), cfg.router_anchor, cfg.residual_rescale_layers, cfg.expert_layer.held) == ("bfloat16", 8.0, 94, 64)
    assert cfg.count("moe") * cfg.n_routed_experts <= cfg.hidden_size, "one orthogonal matrix serves all the routers (PR 29's scheme)"
    assert cfg.layer_plan == (("mla", "moe"), 7, (), ("mla", "ffn")) and (cfg.qk_head_dim, cfg.rope_row) == (256, 128)
    entry = {e["name"]: e for e in BENCH["configs"]}[CONFIG]
    assert entry["source"] == c["source"] and entry["reduced"] == c["reduced"] and entry["file"].endswith(CONFIG + ".json")
    sv = c["serving"]
    assert (sv["max_num_seqs"], sv["max_seq_len"], sv["warm_batch_max"]) == (16, 16384, 4) and "engine_kwargs" not in sv


def test_the_cell_is_listed_where_issue_36_says(c):
    cell = {w["name"]: w for w in BENCH["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "longdoc-16k", 1) and len(cell["why"]) <= 200
    listed = {m["name"] for k in ("end_to_end", "per_layer") for m in BENCH[k] if CELL in m.get("workloads", ())}
    assert listed == {"serve_tokens_per_s", "prefill_step_roofline", "moe_block_fill", "latent_decode_roofline"} | LONGDOC_READERS
    assert all(m["workloads"][-1] == CELL for k in ("end_to_end", "per_layer") for m in BENCH[k] if CELL in m.get("workloads", ())), "appended"
    per = {m["name"]: m for m in BENCH["per_layer"]}
    assert per["latent_decode_roofline"] == {"name": "latent_decode_roofline", "unit": "%", "better": "higher", "source": "device_trace",
                                             "layer": "kernels", "moves": "serve_tokens_per_s", "workloads": [CELL]}
    assert BENCH["per_layer"][-1]["name"] == "latent_decode_roofline"
    mix = traffic.load_mix("longdoc-16k", CELL)
    assert (mix["kind"], mix["loop"], mix["clients"]) == ("serve", "closed", 21) and mix["clients"] == round(4 / 3 * c["serving"]["max_num_seqs"])
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 10000, "sigma": 0.4, "min": 4608, "max": 15872}
    assert mix["output_len"] == {"dist": "uniform", "min": 16, "max": 48} and mix["sampled_share"] == 0.1
    assert mix["sampled"] == traffic.load_mix("longdoc")["sampled"] == {"temperature": 0.8, "top_p": 0.95}
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] <= c["serving"]["max_seq_len"]
    lengths = traffic.quantile_lengths(mix["prompt_len"], 1000)
    assert 0.25 < sum(n <= 8192 for n in lengths) / 1000 < 0.35, "about 3 prompts in 10 fall in the 8,192 bucket"
    from benchmark.serve_cell import default_buckets, warm_plan

    assert [b for b, _ in warm_plan(mix, default_buckets(16384))] == [8192, 16384]


def test_the_counts_are_the_issues_arithmetic(c, family):
    p = family.layer_params(c)
    assert p["A"] == 1_572_864 + 768 + 3_932_160 + 1_179_648 + 512 + 4_587_520 + 10_485_760 == 21_759_232
    assert p["expert"] == 9_437_184 and p["A"] + p["E_rest"] == 31_331_648 and p["A"] + p["E_rest"] + 64 * p["expert"] == 635_311_424
    assert p["A"] + p["F_rest"] == 84_677_888 and p["embed_and_head"] == 634_388_480
    held = family.parameters_held(c)
    assert held == c["parameters"] == 84_677_888 + 7 * 635_311_424 + 634_388_480 + 2_048 == 5_166_248_384
    assert round(2 * held / 1e9, 2) == 10.33 and round(2 * held / 2**30, 2) == 9.62
    assert family.parameters_held({**c, **c["reduced_from"]}) == c["parameters_published"] == 29_943_393_920
    assert family.program_config(c, 16384).num_params() == held
    # what a position keeps as published (the latent and the one rotated key), and as the chip stores it (the key in whole lane tiles)
    assert family.row_width(c) == 576 and family.kv_bytes_per_token(c) == 8 * 1_152 == 9_216
    from ray_tpu.llm.kv_cache import entry_bytes_per_token

    assert entry_bytes_per_token(family.program_config(c, 16384).position_entries()) == 8 * 1_280 == 10_240
    assert round(family._per_token_matmul(c, 4) / 1e6) == 568 and round(8 * (p["A"] - 768 - 512) / 1e6) == 174


def test_the_least_a_prefill_a_step_and_the_latent_attention_must_do_by_hand_at_one_small_size(family):
    """Two published layers (one dense, one of experts), hidden 8, 2 heads, so that every term can be written out."""
    c = {"hidden_size": 8, "num_hidden_layers": 2, "first_k_dense_replace": 1, "vocab_size": 16, "intermediate_size": 12,
         "num_attention_heads": 2, "q_lora_rank": 6, "kv_lora_rank": 4, "qk_nope_head_dim": 3, "qk_rope_head_dim": 2, "v_head_dim": 5,
         "n_routed_experts": 4, "n_shared_experts": 1, "num_experts_per_tok": 2, "moe_intermediate_size": 4}
    attn = 8 * 6 + 6 + 6 * 2 * 5 + 8 * 6 + 4 + 4 * 2 * (3 + 5) + 2 * 5 * 8  # W_qa, its norm, W_qb, W_kva, its norm, W_kb + W_vb, W_o
    dense_rest, expert = 3 * 8 * 12 + 16, 3 * 8 * 4
    experts_rest = 8 * 4 + 4 + expert + 16  # router, correction bias, shared expert, two norms
    p = family.layer_params(c)
    assert (p["A"], p["F_rest"], p["E_rest"], p["expert"]) == (attn, dense_rest, experts_rest, expert)
    assert family.parameters_held(c) == 2 * attn + dense_rest + experts_rest + 4 * expert + 2 * 16 * 8 + 8
    fixed = 2 * attn + dense_rest + experts_rest + 8 * 16 + 8  # every weight outside the routed experts, the head, the final norm
    assert (family.row_width(c), family.kv_bytes_per_token(c)) == (6, 2 * 6 * 2)
    macs = 2 * (attn - 6 - 4) + (dense_rest - 16) + (experts_rest - 16 - 4)  # what multiplies a token outside routed experts and head
    lengths = [5, 3]
    need = family.prefill_least(c, lengths=lengths, pairs_local=6.0, experts_hit=1.5)
    assert need["bytes"] == 2 * (fixed + 1 * 1.5 * expert + 8 * 8) + 8 * 24
    assert need["flops"] == 2 * 8 * macs + 2 * 2 * 8 * 16 + 2 * 1 * 6.0 * expert + 2 * (5 * 6 / 2 + 3 * 4 / 2) * 2 * 2 * (3 + 2 + 5)
    # lower bounds by construction: one prompt of the same tokens has more attention; more pairs, more work; more experts hit, more bytes
    assert family.prefill_least(c, [8], 6.0, 1.5)["bytes"] == need["bytes"] and family.prefill_least(c, [8], 6.0, 1.5)["flops"] > need["flops"]
    assert family.prefill_least(c, lengths, 7.0, 1.5)["flops"] > need["flops"] and family.prefill_least(c, lengths, 6.0, 2.0)["bytes"] > need["bytes"]
    latent = family.latent_attention_least(c, rows=40.0)
    assert latent == {"bytes": 40.0 * 6 * 2, "flops": 40.0 * 2 * 2 * (6 + 4)}
    step = family.decode_step_least(c, lanes=3, experts_hit=1.0, kv_tokens=20)
    assert step["bytes"] == 2 * (fixed + 1 * 1.0 * expert + 3 * 8) + 20 * 2 * 6 * 2
    assert step["flops"] == 2 * 3 * (macs + 2 * expert + 8 * 16) + 20 * 2 * 2 * 2 * (6 + 4)
    assert family.train_flops_per_token(c, 10) == 3 * (2 * (macs + 2 * expert + 8 * 16) + 10 * 2 * 2 * 10)


def test_a_prefill_of_the_cell_is_bound_by_flops_a_decode_step_and_its_attention_by_bytes(c, family):
    peaks = peaks_of("TPU v5 lite")
    for T, tflop, mla_share, attn_share in ((8192, 14.8, 0.57, 0.37), (16384, 40.6, 0.68, 0.54)):
        one = family.prefill_least(c, lengths=[T], pairs_local=4.0 * T, experts_hit=64.0)
        assert one["flops"] / peaks["bf16_flops"] > one["bytes"] / peaks["hbm_bytes_per_s"]
        assert one["flops"] / 1e12 == pytest.approx(tflop, abs=0.1)
        attention = 81_920.0 * T * (T + 1)  # 2 FLOPs x T (T + 1) / 2 pairs x 8 layers x 20 heads x (256 + 256): ISSUE 36's 81,920 T²
        projections = 2.0 * T * 8 * (family.layer_params(c)["A"] - 768 - 512)
        assert attention / one["flops"] == pytest.approx(attn_share, abs=0.01) and (attention + projections) / one["flops"] == pytest.approx(mla_share, abs=0.01)
    step = family.decode_step_least(c, lanes=16, experts_hit=64, kv_tokens=16 * 10_000)
    assert step["bytes"] / peaks["hbm_bytes_per_s"] > step["flops"] / peaks["bf16_flops"]
    assert 0.0134 < step["bytes"] / peaks["hbm_bytes_per_s"] < 0.0139  # 9.70 GB of layers and head once, 1.47 GB of latent rows at 819 GB/s
    attn = family.latent_attention_least(c, rows=16 * 10_000)
    assert attn["bytes"] / peaks["hbm_bytes_per_s"] > 6 * attn["flops"] / peaks["bf16_flops"], "20 heads on one row: 38 FLOPs a byte, the chip has 240"


def test_the_reference_refuses_nothing_at_toy_size_and_blocks_change_nothing(family, monkeypatch):
    c = family.rehearsal({"rope_theta": 1000000, "n_shared_experts": 1, "norm_topk_prob": True, "routed_scaling_factor": 1.8,
                          "rms_norm_eps": 1e-5, "family": "glm4_moe_lite"})
    cfg = family.program_config(c, 128, remat=False)
    params = jax.jit(lambda k: family.init_params(cfg, k))(jax.random.PRNGKey(0))
    toks = [int(t) for t in np.random.RandomState(0).randint(1, c["vocab_size"] - 1, size=70)]
    lp = np.asarray(family.reference_logprobs(params, toks, c, 10, 70))
    assert lp.shape == (60, c["vocab_size"]) and np.isfinite(lp).all() and np.allclose(np.exp(lp).sum(-1), 1.0, atol=1e-5)
    # the blocks it goes in at the cell's size (queries, rows of the dense layer, columns of the head) are not mathematics
    monkeypatch.setattr(family, "PAD_TO", 96)
    monkeypatch.setattr(family, "QUERY_BLOCK", 96)
    monkeypatch.setattr(family, "ROW_BLOCK", 96)
    monkeypatch.setattr(family, "HEAD_BLOCKS_FROM", 10**9)
    whole = np.asarray(family.reference_logprobs(params, toks, c, 10, 70))
    monkeypatch.setattr(family, "QUERY_BLOCK", 8)
    monkeypatch.setattr(family, "ROW_BLOCK", 16)
    monkeypatch.setattr(family, "HEAD_BLOCKS_FROM", 10)
    for f in (family._latent_attention, family._dense, family._head):
        f.clear_cache() if hasattr(f, "clear_cache") else None
    np.testing.assert_allclose(np.asarray(family.reference_logprobs(params, toks, c, 10, 70)), whole, atol=2e-5, rtol=0)
    np.testing.assert_allclose(lp, whole, atol=2e-5, rtol=0)


def _obs(c, steps, ops, kv=None):
    kv = kv or {"allocated_bytes": 16 * 16384 * 10240, "bytes_per_token": 10240}
    return {"config": c, "window": [0.0, 100.0], "peaks": peaks_of("TPU v5 lite"),
            "worker": {"kv": kv, "trace": {"trace_host": [50.0, 55.0], "ops": ops}},
            "_log": None if steps is None else {"steps": steps, "requests": {}}}


def test_the_new_reader_on_a_made_up_log_and_on_nothing(c, family, monkeypatch):
    from benchmark import flight

    monkeypatch.setattr(flight, "records", lambda obs: obs.get("_log"))
    read = common.load_reader("latent_decode_roofline")
    total = 16 * 16 * 8  # 16 slots x 16 blocks of 1,024 positions x 8 layers
    steps = [{"t": 51.0, "phase": "decode", "attn_blocks_read": 8 * 100, "attn_blocks_total": total},
             {"t": 52.0, "phase": "decode", "attn_blocks_read": 8 * 140, "attn_blocks_total": total},
             {"t": 53.0, "phase": "mixed"},  # a step that dispatched nothing
             {"t": 10.0, "phase": "decode", "attn_blocks_read": 8, "attn_blocks_total": total}]  # before the traced stretch
    ops = {"latent_decode_attention": [10, 0.002], "latent_decode_attention.1": [70, 0.014], "fusion.3": [80, 1.0]}
    # a call reads the mean step's live blocks of ONE layer: 120 blocks x 1,024 positions x 1,152 B at 819 GB/s
    one_call_s = 120 * 1024 * 1152 / 819e9
    assert family.latent_attention_least(c, rows=120 * 1024)["bytes"] == 120 * 1024 * 1152
    want = 100.0 * one_call_s * 80 / 0.016
    assert read(_obs(c, steps, ops)) == pytest.approx(want) and 80 < want < 90
    assert read(_obs(c, steps, {**ops, "latent_decode_attention.1": [70, 0.030]})) == pytest.approx(100.0 * one_call_s * 80 / 0.032)
    # nothing to read: the XLA form (no such op), a log without the fields (the parent), no log, no trace, no decode step in the stretch
    assert read(_obs(c, steps, {"fusion.3": [80, 1.0]})) is None
    assert read(_obs(c, [{"t": 51.0, "phase": "decode"}], ops)) is None
    assert read(_obs(c, None, ops)) is None and read(_obs(c, steps[3:], ops)) is None
    assert read({**_obs(c, steps, ops), "worker": {}}) is None and read({"window": [0.0, 1.0]}) is None
