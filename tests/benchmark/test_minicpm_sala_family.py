"""The ``minicpm_sala`` family file and the cell ``minicpm-sala-d8.longdoc-12k``: the configuration
keeps every published key (depth alone is cut, with its ``reduced_from``, and the ``sparse_config``
numbers stand under ``assumed``), the family's counts are ISSUE 45's arithmetic and the program's,
the least a prefill, the sparse attention and the Lightning rule must do is counted by hand at a
small size, the reference refuses nothing at toy size, and the five new readers read a made-up
summary (the decode kernel's a made-up trace and flight log) and nothing where there is nothing."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark import common, scopes, traffic
from benchmark.peaks import peaks_of

BENCH = common.load_benchmark()
CONFIG, CELL = "minicpm-sala-9b-d8", "minicpm-sala-d8.longdoc-12k"
S, L = "minicpm4", "lightning-attn"
# the catalog row's ``config`` (guide model-configs, architectures.jsonl, MiniCPM-SALA), key for key
PUBLISHED = {
    "attention_bias": False, "attn_use_rope": False, "head_dim": 128, "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 16384,
    "lightning_head_dim": 128, "lightning_nh": 32, "lightning_nkv": 32, "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
    "max_position_embeddings": 524288, "model_type": "minicpm_sala",
    "mixer_types": [S] + [L] * 8 + [S] + [L] * 6 + [S, S] + [L] * 4 + [S] + [L] * 6 + [S, S, S],
    "num_attention_heads": 32, "num_hidden_layers": 32, "num_key_value_heads": 2, "qk_norm": True, "rand_init": False, "rms_norm_eps": 1e-06,
    "vocab_size": 73448, "rope_theta": 10000, "scale_emb": 12, "scale_depth": 1.4, "mup_denominator": 32, "dim_model_base": 256,
    "tie_word_embeddings": False, "use_output_gate": True, "use_output_norm": True, "attn_use_output_gate": True,
}
SERVE_READERS = {"prefill_ms_per_ktok.longdoc", "decode_device_ms.longdoc", "prefill_bubble_ms", "prefill_step_roofline", "prefill_mixer_ms_per_ktok",
                 "prefill_ffn_ms_per_ktok", "prefill_stage_idle_ms", "prefill_sparse_ms_per_ktok", "prefill_lightning_ms_per_ktok",
                 "sparse_attend_roofline", "lightning_chunk_roofline", "sparse_decode_roofline"}
TTFT_READERS = {"client_overhead_ms", "queue_wait_p50_ms", "handle_ingress_ms", "replica_ingress_ms", "token_handoff_ms", "stream_egress_ms"}


@pytest.fixture(scope="module")
def c():
    with open(os.path.join(common.HERE, "configs", CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def family():
    return common.load_family("minicpm_sala")


def test_the_configuration_keeps_every_published_key_and_cuts_depth_alone(c, family):
    assert c["family"] == "minicpm_sala" and c["reduced"] == ["num_hidden_layers"] and c["reduced_from"] == {"num_hidden_layers": 32}
    for k, v in PUBLISHED.items():
        assert c[k] == (8 if k == "num_hidden_layers" else v), k
    assert len(c["mixer_types"]) == 32 and c["layers_held"] == [9, 17] and family.held(c) == list(range(9, 17))
    assert family.kinds(c) == ["S", "L", "L", "L", "L", "L", "L", "S"] and set(c["why_reduced"]) == {"num_hidden_layers"}
    assert (c["deployment"]["pipeline_stages"], c["deployment"]["layers_per_stage"]) == (4, 8)
    assert c["assumed"]["sparse_config"] == {"kernel_size": 32, "kernel_stride": 16, "block_size": 64, "topk": 64, "window_size": 2048,
                                             "init_blocks": 1, "dense_len": 8192}
    assert {"sparse_config_why", "sparse equations", "lightning equations", "muP", "initialisation", "norms", "torch_dtype", "chunk_size"} <= set(c["assumed"])
    assert 0 < c["tolerance"]["logprob_abs"] <= 0.25 and c["tolerance"]["why"]
    cfg = family.program_config(c, 12288)
    assert (str(cfg.stream_dtype), cfg.first_layer, cfg.published_layers, cfg.num_hidden_layers, cfg.chunk_size) == ("bfloat16", 9, 32, 8, 128)
    assert cfg.sparse == (32, 16, 64, 64, 2048, 1, 8192) and cfg.stream_scales == (12.0, 1.4 / 32 ** 0.5, 1 / 16)
    assert cfg.layer_plan == (("ffn", "lightning"), 6, ("ffn", "sparse", "ffn"), ("sparse",))
    entry = {e["name"]: e for e in BENCH["configs"]}[CONFIG]
    assert entry["source"] == c["source"] and entry["reduced"] == c["reduced"] and entry["file"].endswith(CONFIG + ".json") and len(entry["why"]) <= 200
    sv = c["serving"]
    assert (sv["max_num_seqs"], sv["max_seq_len"], sv["max_ongoing_requests"]) == (16, 12288, 64) and "engine_kwargs" not in sv
    with pytest.raises(ValueError, match="layers_held"):
        family.held({**c, "layers_held": [9, 16]})
    with pytest.raises(ValueError, match="rotates the Lightning"):
        family.program_config({**c, "attn_use_rope": True}, 12288)


def test_the_cell_is_listed_and_whatever_follows_it_was_appended(c):
    cell = {w["name"]: w for w in BENCH["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "longdoc-12k", 1) and len(cell["why"]) <= 200
    listed = {m["name"] for k in ("end_to_end", "per_layer") for m in BENCH[k] if CELL in m.get("workloads", ())}
    assert "serve_tokens_per_s" in listed and SERVE_READERS <= listed
    # time to the first token and the readers that move it go together: listed with it, or none of them
    assert listed - SERVE_READERS - {"serve_tokens_per_s"} in (set(), TTFT_READERS | {"ttft_p50_ms"})
    assert [w["name"] for w in BENCH["workloads"]][-1] == CELL and BENCH["configs"][-1]["name"] == CONFIG, "after what was there"
    for k in ("end_to_end", "per_layer"):
        for m in BENCH[k]:
            if CELL in m.get("workloads", ()):
                assert m["workloads"][-1] == CELL, m["name"]
    per = {m["name"]: m for m in BENCH["per_layer"]}
    for name, unit, better, layer in (("prefill_sparse_ms_per_ktok", "ms", "lower", "step programs"), ("prefill_lightning_ms_per_ktok", "ms", "lower", "step programs"),
                                      ("sparse_attend_roofline", "%", "higher", "kernels"), ("lightning_chunk_roofline", "%", "higher", "kernels"),
                                      ("sparse_decode_roofline", "%", "higher", "kernels")):
        assert per[name] == {"name": name, "unit": unit, "better": better, "source": "device_trace", "layer": layer,
                             "moves": "serve_tokens_per_s", "workloads": [CELL]}
    assert all(common.load_reader(name) is not None for name in listed - {"serve_tokens_per_s", "ttft_p50_ms"})
    mix = traffic.load_mix("longdoc-12k", CELL)
    assert (mix["kind"], mix["loop"], mix["clients"]) == ("serve", "closed", 21) and mix["clients"] == round(4 / 3 * c["serving"]["max_num_seqs"])
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 10500, "sigma": 0.12, "min": 8704, "max": 12160}
    assert mix["output_len"] == {"dist": "uniform", "min": 16, "max": 48} and mix["sampled_share"] == 0.1
    assert mix["prompt_len"]["min"] > c["assumed"]["sparse_config"]["dense_len"], "every prompt chooses its blocks"
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] <= c["serving"]["max_seq_len"]
    from benchmark.serve_cell import default_buckets, warm_plan

    assert default_buckets(12288)[-2:] == [8192, 12288] and [b for b, _ in warm_plan(mix, default_buckets(12288))] == [12288], "one bucket"
    # the multiset of lengths is the same for every seed: quantiles, shuffled
    a, b = (sorted(len(r["prompt"]) for r in traffic.make_requests(mix, 40, 100, seed)) for seed in (1, 2**31 + 5))
    assert a == b and a[0] >= 8704 and a[-1] <= 12160


def test_the_counts_are_the_issues_arithmetic(c, family):
    p = family.layer_params(c)
    assert p["L"] == 5 * 4096 * 4096 + 2 * 128 + 4096 == 83_890_432 and p["S"] == 3 * 4096 * 4096 + 2 * 4096 * 256 + 2 * 128 == 52_429_056
    assert p["rest"] == 3 * 4096 * 16384 + 2 * 4096 == 201_334_784 and p["embed_and_head"] == 2 * 73448 * 4096 == 601_686_016
    assert (p["L"] + p["rest"], p["S"] + p["rest"]) == (285_225_216, 253_763_840)
    held = family.parameters_held(c)
    assert held == c["parameters"] == 6 * 285_225_216 + 2 * 253_763_840 + 601_686_016 + 4096 == 2_820_569_088
    assert round(2 * held / 1e9, 2) == 5.64 and round(2 * held / 2**30, 2) == 5.25
    whole = {**c, **c["reduced_from"], "layers_held": None}
    assert family.parameters_held(whole) == c["parameters_published"] == 24 * 285_225_216 + 8 * 253_763_840 + 601_686_016 + 4096 == 9_477_206_016
    assert family.program_config(c, 12288).num_params() == held
    # what a sequence keeps: a state a Lightning layer, 768 compressed keys a sparse layer; a key and a value a position and sparse layer
    assert family.state_bytes_per_slot(c, 12288) == 6 * 32 * 128 * 128 * 4 + 2 * 768 * 2 * 128 * 2 == 13_369_344
    assert family.kv_bytes_per_token(c) == 2 * 2 * 2 * 128 * 2 == 2_048
    from ray_tpu.llm import state_cache
    from ray_tpu.llm.kv_cache import entry_bytes_per_token

    cfg = family.program_config(c, 12288)
    assert entry_bytes_per_token(cfg.position_entries()) == 2_048 and state_cache.bytes_per_slot(cfg) == 13_369_344


def test_the_least_a_prefill_the_sparse_attention_and_the_rule_must_do_by_hand_at_one_small_size(family):
    """Three layers (S L S), hidden 8, blocks of 4 with a top-k of 2, so that every term can be written out."""
    c = {"hidden_size": 8, "num_hidden_layers": 3, "vocab_size": 16, "intermediate_size": 12, "num_attention_heads": 4, "num_key_value_heads": 2,
         "head_dim": 3, "lightning_nh": 2, "lightning_head_dim": 5, "mixer_types": [S, L, S, L], "layers_held": [0, 3],
         "assumed": {"sparse_config": {"kernel_size": 4, "kernel_stride": 2, "block_size": 4, "topk": 2, "window_size": 4, "init_blocks": 1, "dense_len": 8}}}
    q, kv, D = 4 * 3, 2 * 3, 2 * 5
    sparse_mats, lightning_mats, mlp = 3 * 8 * q + 2 * 8 * kv, 5 * 8 * D, 3 * 8 * 12
    p = family.layer_params(c)
    assert (p["S"], p["L"], p["rest"]) == (sparse_mats + 2 * 3, lightning_mats + 2 * 5 + D, mlp + 16) and family.kinds(c) == ["S", "L", "S"]
    assert family.parameters_held(c) == 2 * p["S"] + p["L"] + 3 * p["rest"] + 2 * 16 * 8 + 8
    # a prompt of 10 (over dense_len 8) chooses: query t reads min(t // 4 + 1, 2) blocks; one of 6 reads all t // 4 + 1
    pairs_10 = 4 * 1 + 4 * 2 + 2 * 2
    keys_10 = sum(max((t + 1 - 4) // 2 + 1, 0) for t in range(10))  # compressed keys whose 4 positions lie at or before t
    assert (pairs_10, keys_10) == (16, 0 + 0 + 0 + 1 + 1 + 2 + 2 + 3 + 3 + 4)
    pairs_6 = 4 * 1 + 2 * 2
    sparse = family.sparse_attend_least(c, lengths=[10, 6])
    assert sparse["flops"] == 4 * (4.0 * 3 * 4 * (pairs_10 + pairs_6) + 2.0 * 3 * keys_10)
    assert sparse["bytes"] == 16 * (2 * q + 2 * kv) * 2 + 16 / 2 * kv * 2
    assert family.blocks_chosen(c, 9, True) == 2 and family.blocks_chosen(c, 9, False) == 3
    rule = family.lightning_chunk_least(c, tokens=16.0, sequences=2.0)
    assert rule == {"bytes": 16.0 * 4 * D * 2 + 2.0 * 2 * 5 * 5 * 4, "flops": 5.0 * 16.0 * 2 * 5 * 5}
    macs = 2 * sparse_mats + lightning_mats + 3 * mlp
    need = family.prefill_least(c, lengths=[10, 6], pairs_local=0.0, experts_hit=0.0)
    weights = 2 * p["S"] + p["L"] + 3 * p["rest"] + 8 * 16 + 8
    assert need["bytes"] == 2 * (weights + 16 * 8) + 16 * 2 * 2 * kv * 2 + 2 * 1 * 2 * 5 * 5 * 4
    assert need["flops"] == 2 * 16 * macs + 2 * 2 * 8 * 16 + 1 * 5.0 * 16 * 2 * 25 + 2 * sparse["flops"]
    # a lower bound by construction: the same tokens attending to everything cost more than the chosen blocks
    dense = {**c, "assumed": {"sparse_config": {**c["assumed"]["sparse_config"], "dense_len": 64}}}
    assert family.sparse_attend_least(dense, [10, 6])["flops"] > sparse["flops"] - 4 * 2.0 * 3 * keys_10
    assert family.sparse_decode_least(c, blocks=10.0) == {"bytes": 10.0 * 2 * 4 * 3 * 2, "flops": 10.0 * 4 * 2 * 4 * 3}
    assert family.train_flops_per_token(c, 10) > 6 * (macs + 8 * 16)


def test_at_the_cells_size_the_chosen_blocks_are_a_third_of_dense_attention_and_the_swiglu_most_of_a_prefill(c, family):
    peaks = peaks_of("TPU v5 lite")
    one = family.sparse_attend_least(c, lengths=[10500])
    dense = family.sparse_attend_least({**c, "assumed": {"sparse_config": {**c["assumed"]["sparse_config"], "dense_len": 12288}}}, lengths=[10500])
    assert 0.55 < one["flops"] / dense["flops"] < 0.75, "64 of up to 165 blocks, and the scores against 655 compressed keys a query"
    assert one["flops"] / peaks["bf16_flops"] > one["bytes"] / peaks["hbm_bytes_per_s"], "bound by FLOPs: about 5 ms a prompt and layer"
    rule = family.lightning_chunk_least(c, tokens=1000.0)
    assert rule == {"bytes": 1000.0 * 4 * 4096 * 2, "flops": 1000.0 * 5 * 32 * 128 * 128} and rule["bytes"] / peaks["hbm_bytes_per_s"] > rule["flops"] / peaks["bf16_flops"]
    whole = family.prefill_least(c, lengths=[10500])
    p = family.layer_params(c)
    swiglu = 8 * 2.0 * 10500 * 3 * 4096 * 16384
    assert 0.65 < swiglu / whole["flops"] < 0.75 and whole["flops"] / peaks["bf16_flops"] > whole["bytes"] / peaks["hbm_bytes_per_s"]
    assert p["rest"] / (p["L"] + p["rest"]) > 0.70 and p["rest"] / (p["S"] + p["rest"]) > 0.79


def test_the_reference_refuses_nothing_at_toy_size_and_blocks_change_nothing(family, monkeypatch):
    c = family.rehearsal({k: v for k, v in PUBLISHED.items() if k not in family.REHEARSAL_SIZES} | {"family": "minicpm_sala"})
    cfg = family.program_config(c, 128, remat=False)
    params = jax.jit(lambda k: family.init_params(cfg, k))(jax.random.PRNGKey(0))
    toks = [int(t) for t in np.random.RandomState(0).randint(1, c["vocab_size"] - 1, size=70)]
    assert [family.padded_length(n) for n in (1, 128, 129, 1024, 9000, 12288, 12289)] == [128, 128, 1024, 1024, 12288, 12288, 24576]
    family.LAST_AGREEMENT.clear()
    lp = np.asarray(family.reference_logprobs(params, toks, c, 39, 70))
    assert lp.shape == (31, c["vocab_size"]) and np.isfinite(lp).all() and np.allclose(np.exp(lp).sum(-1), 1.0, atol=1e-5)
    # the program's selection on the reference's own float32 stream is the reference's, pair for pair
    seen = dict(family.LAST_AGREEMENT)
    assert seen["pairs"] > 0 and seen["same"] == seen["pairs"] and seen["calls"] == 1
    # the blocks of queries it goes in at the cell's size are not mathematics; nor is what follows a position
    monkeypatch.setattr(family, "QUERY_BLOCK", 16)
    family._sparse.clear_cache()
    np.testing.assert_allclose(np.asarray(family.reference_logprobs(params, toks, c, 39, 70)), lp, atol=2e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(family.reference_logprobs(params, toks + [5] * 20, c, 39, 70)), lp, atol=2e-5, rtol=0)


# ------------------------------------------------------------------------------------ the four readers
def _scope(s, calls=10):
    return {"s": s, "calls": calls, "flops": 0, "bytes": 0}


def _summary(sala=True):
    mixers = {"sparse": _scope(0.10), "sparse.select": _scope(0.30), "sparse.attend": _scope(0.60), "lightning": _scope(0.20),
              "lightning.chunk": _scope(0.40)} if sala else {"kda": _scope(0.3), "kda.chunk": _scope(0.7)}
    programs = {"jit_llm_hybrid_prefill": {"calls": 4, "device_s": 5.1, "leaf_s": 5.0, "ops": {}, "scopes": {**mixers, "ffn": _scope(3.0), "unscoped": _scope(0.1)}},
                # the step's mixers are not the prefill's: their seconds are not read
                "jit_llm_hybrid_fused_step": {"calls": 100, "device_s": 0.9, "leaf_s": 0.9, "ops": {}, "scopes": {"sparse": _scope(0.2), "sparse.attend": _scope(0.1), "lightning.state": _scope(0.1)}}}
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


def test_the_four_new_readers_on_a_made_up_summary_and_on_nothing(c, family, obs):
    readers = {n: common.load_reader(n) for n in ("prefill_sparse_ms_per_ktok", "prefill_lightning_ms_per_ktok", "sparse_attend_roofline", "lightning_chunk_roofline")}
    o = obs(_summary())
    # two prompts admitted in the stretch, 21,000 tokens: each mixer's whole seconds a 1,000 of them
    assert readers["prefill_sparse_ms_per_ktok"](o) == pytest.approx((0.10 + 0.30 + 0.60) * 1e3 / 21.0)
    assert readers["prefill_lightning_ms_per_ktok"](o) == pytest.approx((0.20 + 0.40) * 1e3 / 21.0)
    need = family.sparse_attend_least(c, lengths=[9000, 12000])
    peaks = o["peaks"]
    least = 2 * max(need["bytes"] / peaks["hbm_bytes_per_s"], need["flops"] / peaks["bf16_flops"])
    assert readers["sparse_attend_roofline"](o) == pytest.approx(100.0 * least / 0.90) and 1.0 < readers["sparse_attend_roofline"](o) < 5.0
    rule = family.lightning_chunk_least(c, tokens=21000, sequences=2)
    assert readers["lightning_chunk_roofline"](o) == pytest.approx(100.0 * 6 * rule["bytes"] / peaks["hbm_bytes_per_s"] / 0.40)
    # the same work whatever computes it: a kernel that takes a tenth of the time under the same scopes reads ten times the share
    fast = _summary()
    fast["programs"]["jit_llm_hybrid_prefill"]["scopes"].update({"sparse.select": _scope(0.03), "sparse.attend": _scope(0.06)})
    assert common.load_reader("sparse_attend_roofline")(obs(fast)) == pytest.approx(100.0 * least / 0.09)
    # nothing to read: another description's scopes, no peaks (off the chip), no admission in the stretch, no trace, no worker
    other = obs(_summary(sala=False))
    assert all(read(other) is None for read in readers.values())
    o = obs(_summary())
    assert readers["sparse_attend_roofline"]({k: v for k, v in o.items() if k != "peaks"}) is None
    assert readers["lightning_chunk_roofline"]({k: v for k, v in o.items() if k != "peaks"}) is None
    o["worker"]["requests"] = {"c": {"admit_t": 99.0, "prompt_tokens": 10000}}
    assert all(read(o) is None for read in readers.values())
    assert all(read({"cell": {"name": "toy.longdoc"}}) is None and read({}) is None for read in readers.values())


def test_the_decode_kernels_reader_on_a_made_up_trace_and_flight_log(c, family, monkeypatch):
    """Two sparse layers x 16 lanes x 2 heads x 64 chosen blocks = 4,096 blocks a step: 2,048 a call,
    each 64 positions of a key and a value by one head (32 KB): 67 MB a call at 819 GB/s."""
    from benchmark import flight

    read = common.load_reader("sparse_decode_roofline")
    steps = [{"t": 101.0 + 0.1 * n, "sparse_blocks_read": 4096, "sparse_blocks_live": 11000} for n in range(10)] + [{"t": 103.0, "phase": "mixed"}]
    monkeypatch.setattr(flight, "records", lambda obs: {"steps": steps, "requests": {}})
    obs = {"config": c, "peaks": peaks_of("TPU v5 lite"), "window": [60.0, 105.0],
           "worker": {"trace": {"trace_host": [100.0, 105.0], "ops": {"sparse_decode_attention.2": [20, 0.004], "sparse_decode_attention.3": [20, 0.004],
                                                                       "slot_decode_attention": [40, 0.001]}}}}
    assert family.sparse_decode_least(c, blocks=2048.0)["bytes"] == 2048 * 2 * 64 * 128 * 2 == 67_108_864
    assert read(obs) == pytest.approx(100.0 * (67_108_864 / 819e9) * 40 / 0.008) and 40.0 < read(obs) < 42.0
    assert read({k: v for k, v in obs.items() if k != "peaks"}) is None
    obs["worker"]["trace"]["ops"] = {"slot_decode_attention": [40, 0.001]}  # the XLA form of the table: no such kernel in the step
    assert read(obs) is None and read({}) is None
