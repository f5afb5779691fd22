"""CPU tests of what PR 34 adds to the benchmark: the family ``qwen3_next`` defines every name the
harness asks for, the configuration's keys are the published ones but for the three that the cut
changes, the counts behind ``prefill_step_roofline`` are ISSUE 34's arithmetic and lower bounds by
construction, and the two new readers read a made-up log and answer nothing where there is
nothing. The cell's rehearsal is slow."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import common
from benchmark.families import NAMES
from benchmark.peaks import peaks_of

CONFIG, CELL = "qwen3-next-80b-a3b-ep4", "qwen3-next-ep4.longdoc"
BENCH = common.load_benchmark()

# config.json of Qwen/Qwen3-Next-80B-A3B-Instruct as the catalog beside the model-configs guide
# holds it (the catalog is not in the repo, so the table is here)
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5120, "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128, "linear_num_key_heads": 16,
    "linear_num_value_heads": 32, "linear_value_head_dim": 128, "max_position_embeddings": 262144, "mlp_only_layers": [],
    "model_type": "qwen3_next", "moe_intermediate_size": 512, "norm_topk_prob": True, "num_attention_heads": 16,
    "num_experts": 512, "num_experts_per_tok": 10, "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936}
CUT = {"num_hidden_layers": 12, "num_experts": 128, "vocab_size": 37984}
WIDTHS = ("hidden_size", "intermediate", "latent", "state_size", "proj", "_dim", "_rank", "head", "expand", "per_tok")
LONGDOC_READERS = {"client_overhead_ms", "queue_wait_p50_ms", "prefill_ms_per_ktok.longdoc", "decode_device_ms.longdoc",
                   "prefill_bubble_ms", "handle_ingress_ms", "replica_ingress_ms", "token_handoff_ms", "stream_egress_ms"}


@pytest.fixture(scope="module")
def c():
    with open(os.path.join(common.HERE, "configs", CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def family():
    return common.load_family("qwen3_next")


def test_the_family_defines_every_name_and_its_reference_is_its_own(family):
    assert all(callable(getattr(family, n)) for n in NAMES)
    assert callable(family.prefill_least) and callable(family.decode_step_least)
    assert family.kernels_expected({}) == {"flash kernel": "tpu_custom_call"}
    with open(family.__file__) as f:
        text = f.read()
    # the reference is written from the equations: the one line that names the program's model imports what the harness asks for
    assert [ln for ln in text.splitlines() if "ray_tpu" in ln and "import" in ln] == [
        "from ray_tpu.models.qwen3_next import Qwen3NextConfig, init_params, loss_fn, param_logical_axes  # noqa: F401 - the family's names"]


def test_the_configuration_is_the_published_one_but_for_the_cut(c, family):
    assert c["family"] == "qwen3_next" and sorted(c["reduced"]) == sorted(CUT)
    for k, v in PUBLISHED.items():
        assert c[k] == (CUT[k] if k in CUT else v), k
    assert c["reduced_from"] == {k: PUBLISHED[k] for k in CUT} and set(c["why_reduced"]) == set(CUT)
    assert not [k for k in c["reduced"] if any(w in k for w in WIDTHS)], "a cut may never name a width"
    assert family.kinds(c) == list("DDDG" * 3), "three whole periods"
    dep = c["deployment"]
    assert (dep["chips_per_layer"], dep["experts_published"], dep["experts_held"], dep["vocab_rows_held"]) == (4, 512, [0, 128], [0, 37984])
    assert (dep["pipeline_stages"], dep["layers_per_stage"]) == (4, 12) and c["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    assert {"chunk_size", "norms", "initialisation", "anchored routing", "multi-token prediction"} <= set(c["assumed"]) and c["tolerance"]["why"]
    assert c["tolerance"]["logprob_abs"] <= 0.25 and c["init_router_anchor"] == 8.0 and c["assumed"]["chunk_size"] == 64
    cfg = family.program_config(c, 4096)
    assert (str(cfg.stream_dtype), cfg.router_anchor, cfg.chunk_size, cfg.num_experts, cfg.expert_start, cfg.local_experts) == ("bfloat16", 8.0, 64, 512, 0, 128)
    assert cfg.num_experts <= cfg.hidden_size, "the anchored routers need orthogonal columns within a block"
    assert (cfg.rot_dim, cfg.residual_rescale_layers, cfg.layer_plan[1], cfg.layer_plan[2]) == (64, 96, 3, ())
    entry = {e["name"]: e for e in BENCH["configs"]}[CONFIG]
    assert entry["source"] == c["source"] and entry["reduced"] == c["reduced"] and entry["file"].endswith(CONFIG + ".json")
    sv = c["serving"]
    assert (sv["max_num_seqs"], sv["max_seq_len"]) == (16, 4096) and "engine_kwargs" not in sv, "the state cache's size follows from the config"


def test_the_cell_is_listed_where_issue_34_says(c):
    from benchmark import traffic

    cell = {w["name"]: w for w in BENCH["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "longdoc", 1) and len(cell["why"]) <= 200
    assert BENCH["workloads"][-1] is cell and BENCH["configs"][-1]["name"] == CONFIG, "new entries go at the end of their lists"
    listed = {m["name"] for k in ("end_to_end", "per_layer") for m in BENCH[k] if CELL in m.get("workloads", ())}
    assert listed == {"ttft_p50_ms", "serve_tokens_per_s", "prefill_step_roofline", "moe_block_fill"} | LONGDOC_READERS
    per = {m["name"]: m for m in BENCH["per_layer"]}
    assert per["prefill_step_roofline"] == {"name": "prefill_step_roofline", "unit": "%", "better": "higher", "source": "device_trace",
                                            "layer": "step programs", "moves": "serve_tokens_per_s", "workloads": [CELL]}
    assert per["moe_block_fill"] == {"name": "moe_block_fill", "unit": "%", "better": "higher", "source": "program_counter",
                                     "layer": "step programs", "moves": "serve_tokens_per_s", "workloads": [CELL]}
    # the mix as it stands, but for the callers: 4/3 of this configuration's slots
    mix, base = traffic.load_mix("longdoc", CELL), traffic.load_mix("longdoc")
    assert mix["clients"] == 21 == round(4 / 3 * c["serving"]["max_num_seqs"])
    assert {k: v for k, v in mix.items() if "clients" not in k} == {k: v for k, v in base.items() if "clients" not in k}


def test_the_counts_are_the_issues_arithmetic(c, family):
    p = family.layer_params(c)
    assert (p["D"], p["G"], p["expert"], p["embed_and_head"]) == (37_918_912, 31_463_936, 3_145_728, 2 * 37_984 * 2_048)
    assert p["D"] == 25_165_824 + 131_072 + 32_768 + 64 + 128 + 8_388_608 + 1_048_576 + 3_145_728 + 2_048 + 4_096
    assert p["G"] == 16_777_216 + 2 * 1_048_576 + 8_388_608 + 512 + 4_196_352 + 4_096
    held = family.parameters_held(c)
    assert held == c["parameters"] == 9 * p["D"] + 3 * p["G"] + 12 * 402_653_184 + 2 * 37_984 * 2_048 + 2_048 == 5_423_084_736
    assert round(2 * held / 1e9, 2) == 10.85 and round(2 * held / 2**30, 2) == 10.10
    whole = {**c, **c["reduced_from"], "deployment": None}
    assert family.parameters_held(whole) == c["parameters_published"] == 79_674_391_296
    assert family.program_config(c, 4096).num_params() == held
    assert family.state_bytes_per_slot(c) == 9 * (2_097_152 + 49_152) == 19_316_736
    assert family.kv_bytes_per_token(c) == 3 * 2_048 == 6_144


def test_prefill_least_and_decode_step_least_by_hand_at_one_small_size(family):
    """Two published layers (one DeltaNet, one attention), hidden 8, 2 of 4 experts held, so that
    every term can be written out."""
    c = {"hidden_size": 8, "num_hidden_layers": 2, "full_attention_interval": 2, "vocab_size": 16, "linear_conv_kernel_dim": 4,
         "linear_num_key_heads": 1, "linear_num_value_heads": 2, "linear_key_head_dim": 4, "linear_value_head_dim": 4,
         "num_experts": 2, "num_experts_per_tok": 2, "moe_intermediate_size": 4, "shared_expert_intermediate_size": 4,
         "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 4,
         "deployment": {"experts_published": 4, "experts_held": [0, 2]}}
    gdn = 8 * (2 * 4 + 2 * 8) + 8 * 4 + 4 * (2 * 4 + 8) + 4 + 4 + 8 * 8  # in_qkvz, in_ba, conv, A_log + dt_bias, head norm, out_proj
    attn = 8 * 16 + 2 * 8 * 4 + 8 * 8 + 8  # wq with its gates, wk, wv, wo, the two head norms
    outside = 8 * 4 + 3 * 8 * 4 + 8 + 16  # router (published width), shared expert, its gate, two norms
    expert = 3 * 8 * 4
    p = family.layer_params(c)
    assert (p["D_mixer"], p["G_mixer"], p["outside_mixer"], p["expert"]) == (gdn, attn, outside, expert)
    assert family.parameters_held(c) == gdn + attn + 2 * outside + 2 * 2 * expert + 2 * 16 * 8 + 8
    fixed = gdn + attn + 2 * outside + 8 * 16 + 8  # every weight outside the routed experts, the head, the final norm
    state_bytes, kv_bytes = 2 * 4 * 4 * 4 + 3 * 16 * 2, 2 * 1 * 4 * 2
    assert (family.state_bytes_per_slot(c), family.kv_bytes_per_token(c)) == (state_bytes, kv_bytes)
    macs = (gdn - 8) + (attn - 8) + 2 * (outside - 16)  # what multiplies a token outside routed experts and head
    lengths = [5, 3]
    need = family.prefill_least(c, lengths=lengths, pairs_local=6.0, experts_hit=1.5)
    assert need["bytes"] == 2 * (fixed + 2 * 1.5 * expert + 8 * 8) + 2 * state_bytes + 8 * kv_bytes
    assert need["flops"] == (2 * 8 * macs + 2 * 2 * 8 * 16 + 2 * 2 * 6.0 * expert + 8 * 8 * 1 * (2 * 4 * 4)
                             + 4 * (5 * 6 / 2 + 3 * 4 / 2) * 1 * 2 * 4)
    # lower bounds by construction: one prompt of the same tokens has less to hold but more attention; more pairs, more work
    assert family.prefill_least(c, [8], 6.0, 1.5)["bytes"] < need["bytes"] and family.prefill_least(c, [8], 6.0, 1.5)["flops"] > need["flops"]
    assert family.prefill_least(c, lengths, 7.0, 1.5)["flops"] > need["flops"] and family.prefill_least(c, lengths, 6.0, 2.0)["bytes"] > need["bytes"]
    step = family.decode_step_least(c, lanes=3, experts_hit=1.0, kv_tokens=20)
    assert step["bytes"] == 2 * (fixed + 2 * 1.0 * expert + 3 * 8) + 2 * 3 * state_bytes + 20 * kv_bytes
    assert step["flops"] == 2 * 3 * (macs + 2 * (2 * 2 / 4) * expert + 8 * 16) + 8 * 3 * 1 * 32 + 4 * 20 * 1 * 2 * 4


def test_a_prefill_of_the_cell_is_bound_by_flops_and_a_decode_step_by_bytes(c, family):
    peaks = peaks_of("TPU v5 lite")
    one = family.prefill_least(c, lengths=[2500], pairs_local=6250.0, experts_hit=128.0)
    assert one["flops"] / peaks["bf16_flops"] > one["bytes"] / peaks["hbm_bytes_per_s"]
    assert 0.013 < one["flops"] / peaks["bf16_flops"] < 0.017  # 2 x 2500 x 0.44e9 outside the experts, 0.47e12 in them, the rule, the attention: 2.9 TFLOP
    step = family.decode_step_least(c, lanes=16, experts_hit=128, kv_tokens=16 * 2600)
    assert step["bytes"] / peaks["hbm_bytes_per_s"] > step["flops"] / peaks["bf16_flops"]
    assert 0.0135 < step["bytes"] / peaks["hbm_bytes_per_s"] < 0.0150  # the weights whole, 10.7 GB at 819 GB/s, the 16 lanes' state twice and their rows


def _obs(c, steps, requests, prefill_s=0.1):
    return {"config": c, "window": [0.0, 100.0], "peaks": peaks_of("TPU v5 lite"),
            "worker": {"trace": {"trace_host": [50.0, 55.0], "programs": {"jit_llm_hybrid_fused_step": [100, 1.7],
                                                                          "jit_llm_hybrid_prefill": [2, prefill_s]}}},
            "_log": None if steps is None else {"steps": steps, "requests": requests}}


def test_the_two_readers_on_a_made_up_log_and_on_nothing(c, family, monkeypatch):
    from benchmark import flight

    monkeypatch.setattr(flight, "records", lambda obs: obs.get("_log"))
    roofline, fill = common.load_reader("prefill_step_roofline"), common.load_reader("moe_block_fill")
    prefill = {"phase": "mixed", "admitted": 2, "prefill_ms": 300.0, "prefill_tokens": 5000, "prefill_tokens_padded": 8192,
               "prefill_moe_pairs_local": 12500.0, "prefill_experts_hit": 128.0, "moe_rows_computed": 40960.0}
    steps = [{**prefill, "t": 51.0, "t0": 50.6}, {"t": 52.0, "t0": 51.98, "phase": "decode", "experts_hit": 30.0, "moe_pairs_total": 160.0},
             {**prefill, "t": 10.0, "t0": 9.6, "prefill_moe_pairs_local": 1.0}]  # the last before the traced stretch
    requests = {"a": {"admit_t": 50.7, "prompt_tokens": 3000}, "b": {"admit_t": 50.9, "prompt_tokens": 2000},
                "z": {"admit_t": 9.7, "prompt_tokens": 5000}}
    need = family.prefill_least(c, lengths=[3000, 2000], pairs_local=12500.0, experts_hit=128.0)
    want = 100.0 * need["flops"] / 197e12 / 0.1
    assert roofline(_obs(c, steps, requests)) == pytest.approx(want) and 20 < want < 40
    assert roofline(_obs(c, steps, requests, prefill_s=0.2)) == pytest.approx(want / 2)
    # stamps that do not tell the prompts apart: as many EQUAL prompts as the step admitted, the split with the least attention
    even = family.prefill_least(c, lengths=[2500.0, 2500.0], pairs_local=12500.0, experts_hit=128.0)
    assert roofline(_obs(c, steps, {})) == pytest.approx(100.0 * even["flops"] / 197e12 / 0.1) and even["flops"] < need["flops"]
    assert even["bytes"] == need["bytes"] < even["flops"] / 197e12 * 819e9
    # the fill is over the WINDOW's admitting steps, traced stretch or not
    assert fill(_obs(c, steps, requests)) == pytest.approx(100.0 * (12500.0 + 1.0) / (2 * 40960.0))
    assert fill(_obs(c, steps[:1], requests)) == pytest.approx(100.0 * 12500.0 / 40960.0)
    # nothing to read: a program whose log lacks the fields (the parent), no log, no trace, no admitting step in the stretch
    old = [{"t": 51.0, "t0": 50.6, "phase": "mixed", "admitted": 2, "prefill_ms": 300.0}]
    assert roofline(_obs(c, old, requests)) is None and fill(_obs(c, old, requests)) is None
    assert roofline(_obs(c, None, None)) is None and fill(_obs(c, None, None)) is None
    assert roofline({**_obs(c, steps, requests), "worker": {}}) is None
    assert roofline(_obs(c, steps[1:], requests)) is None
    no_program = _obs(c, steps, requests)
    no_program["worker"]["trace"]["programs"] = {"jit_llm_hybrid_fused_step": [100, 1.7]}
    assert roofline(no_program) is None


@pytest.mark.slow
def test_the_cells_rehearsal_runs_the_wiring_and_never_says_correct(tmp_path):
    out = subprocess.run([sys.executable, os.path.join(common.ROOT, "benchmark", "run.py"), "--workload", CELL, "--seed", "3000000019",
                          "--seconds", "3", "--trace", "1", "--rehearse"], cwd=tmp_path, capture_output=True, text=True,
                         env={**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "--xla_force_host_platform_device_count=1"},
                         timeout=600)
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert out.returncode == 1 and last["correct"] is False and last["metrics"] == {} and last["device"]["platform"] == "cpu"
    assert "family qwen3_next" in out.stdout and '"ok": true' in out.stdout
