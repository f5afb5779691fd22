"""A seventh description over the one layer loop (``models/lfm2.py``: gated short convolutions three
layers in four, grouped-query attention with query-key norms at heads 64 wide the fourth, a dense
SwiGLU in the first two layers and sigmoid experts chosen with a bias in the rest, a tied head)
through the engine, against the plain reference of ``benchmark/families/lfm2.py`` (float32, the
convolution as shifted products, a [T, T] mask, no cache, no window kept, written from the
published equations): logits, not tokens. What is this file's own: a layer whose only state is a
convolution's window (taken AT each prompt's true length, moved on by every decoded token), a
cache whose rows hold two 64-wide heads each, the selection bias, the query-key norms, the tied
head, the 64-wide decode kernel and flash call interpreted, the counters. Toy widths (hidden 64,
4 heads of 64 over 2, 8 experts top 2, prompts of 5-61), float32."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hybrid_battery as battery
from benchmark.families import lfm2 as family
from hybrid_battery import *  # noqa: F401,F403 - the tests every description is held to, collected here against DESC
from ray_tpu.llm import SamplingParams
from ray_tpu.models import experts, hybrid
from ray_tpu.models import lfm2
from ray_tpu.ops import flash_attention as fa
from ray_tpu.ops import slot_attention as sa

PUBLISHED = {"conv_L_cache": 3, "conv_bias": False, "norm_eps": 1e-5, "norm_topk_prob": True, "routed_scaling_factor": 1,
             "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}, "use_expert_bias": True, "family": "lfm2"}
# the configuration file's side of the toy model: c c A c c c A c c c behind two dense layers, the cell's own shape;
# a bias as wide as the random scores' own spread, so that it decides the choice for most tokens
C = {**family.rehearsal(PUBLISHED), "init_router_bias_range": 0.5}
CFG = family.program_config(C, 128, remat=False)


def _gates_swapped(params):
    """``[C, B, u]`` for ``[B, C, u]``: the output gate and the convolution's first factor change places."""
    b, c, u = jnp.split(params["shortconv"]["in_proj"], 3, axis=-1)
    return battery.in_kind(params, "shortconv", in_proj=jnp.concatenate([c, b, u], axis=-1))


# float32 program against float32 reference: the same mathematics summed in another order (tiles of
# queries, the grouped matmul). They agree to 1e-5 in a log-probability; what a wrong window, gate,
# bias or head norm does is over 1e-3
DESC = battery.Description(
    family=family, c=C, cfg=CFG, tol=2e-4, agrees_to=1e-5,
    state_bytes_per_slot=8 * 2 * 64 * 4,  # eight convolution layers, a window of two inputs 64 wide
    kv_bytes_per_token=2 * 2 * (2 * 64) * 4,  # two attention layers, a key and a value of 2 heads x 64: one row of 128
    poison={"k": jnp.nan, "v": 1e4},
    faults={"window_at_the_padded_length": battery.Fault(battery.padded_length),
            "slot_not_reset": battery.Fault(battery.slot_not_reset),
            "bias_left_out": battery.Fault(battery.with_params(lambda p: battery.in_kind(p, "moe", router_bias=jnp.zeros_like(p["moe"]["router_bias"])))),
            "taps_reversed": battery.Fault(battery.with_params(lambda p: battery.in_kind(p, "shortconv", conv_w=p["shortconv"]["conv_w"][:, ::-1]))),
            "gates_swapped": battery.Fault(battery.with_params(_gates_swapped)),
            "head_norms_weights_left_off": battery.Fault(battery.with_params(lambda p: battery.in_kind(p, "attn", q_norm=jnp.ones_like(p["attn"]["q_norm"]), k_norm=jnp.ones_like(p["attn"]["k_norm"]))))},
    refusal_says=("its recurrent layers keep a state per sequence (conv)",),
    refusal_says_not=("c_kv", "ring"))


@pytest.fixture(scope="module")
def params():
    return battery.jiggled(jax.jit(lambda k: lfm2.init_params(CFG, k))(jax.random.PRNGKey(7)))


# ------------------------------------------------------------------------------ the description
def test_the_description_is_two_dense_layers_then_a_period_of_eight_sub_blocks_and_keeps_a_window_and_packed_rows():
    period = ("attn", "moe", "shortconv", "moe", "shortconv", "moe", "shortconv", "moe")
    assert CFG.layer_kinds == ("shortconv", "ffn", "shortconv", "ffn") + period * 2
    assert CFG.layer_plan == hybrid.LayerPlan(period=period, repeats=2, tail=(), head=("shortconv", "ffn", "shortconv", "ffn"))
    published = lfm2.Lfm2Config()
    assert published.layer_plan == hybrid.LayerPlan(period, 9, ("attn", "moe", "shortconv", "moe"), ("shortconv", "ffn", "shortconv", "ffn"))
    assert published.num_params() == 23_843_661_440 and (published.count("shortconv"), published.count("attn"), published.count("ffn"), published.count("moe")) == (30, 10, 2, 38)
    cut = dataclasses.replace(published, num_hidden_layers=10, layer_types=published.layer_types[:10], max_seq_len=12288)
    assert cut.layer_plan == CFG.layer_plan and cut.kinds_held == "8 x shortconv, 2 x ffn, 2 x attn, 8 x moe" and cut.num_params() == 5_267_090_176
    assert (cut.num_kv_layers, cut.routing_layers, cut.num_layers) == (2, 8, 20)
    assert {k: (m.scope, m.routes, m.hands) for k, m in cut.mixers.items()} == {
        "shortconv": ("shortconv", False, False), "attn": ("attn", False, False), "ffn": ("ffn", False, False), "moe": ("moe", True, False)}
    s = cut.expert_layer
    assert (s.num_experts, s.held, s.top_k, s.score, s.bias, s.norm_topk, s.scale, s.act, s.shared, s.norm_eps) == (64, 64, 4, "sigmoid", True, True, 1.0, "swiglu", False, 1e-6)
    # a position's 8 heads of 64 as 4 rows of 128 lanes: 4,096 B over the two attention layers, and the window alone in a convolution layer
    kv = ((4, 128), "bfloat16", "position")
    assert cut.cache_spec() == {"attn": {"k": kv, "v": kv}, "shortconv": {"conv": ((2, 2048), "bfloat16", "sequence")}, "ffn": {}, "moe": {}}
    assert cut.position_entries() == {"k": (2, (4, 128), "bfloat16"), "v": (2, (4, 128), "bfloat16")} and cut.ring_entries() == {}
    assert cut.slot_attention_tile == dict(num_heads=32, num_kv_heads=8, head_dim=64) and cut.flash_calls(12288) == {128: 2} and cut.flash_width == 128
    assert family.kv_bytes_per_token(_cell()) == 4096 and family.state_bytes_per_slot(_cell()) == 65536 and family.cache_bytes(_cell(), 16, 12288) == 805_306_368 + 16 * 65536
    # the counters, from lengths alone: i + 1 keys a position and attention layer, position + 1 rows a lane
    assert cut.prefill_counters(2, 12288, lengths=[10500, 100]) == {"narrow_pairs": 2 * (10500 * 10501 // 2 + 5050)}
    assert cut.decode_counters([12000, 100]) == {"narrow_rows_read": 2 * 12100}
    wide = dataclasses.replace(cut, head_dim=128)  # heads that fill a row count nothing of the kind
    assert wide.kv_tile == (8, 128) and wide.prefill_counters(1, 64, lengths=[9]) == {} and wide.decode_counters([9]) == {}
    with pytest.raises(ValueError, match="layer_types names every held layer"):
        dataclasses.replace(cut, layer_types=("conv",) * 9)


def _cell():
    import json
    import os

    from benchmark import common

    with open(os.path.join(common.ROOT, "benchmark", "configs", "lfm2-24b-a2b-d10.json")) as f:
        return json.load(f)


def test_the_head_is_the_embedding_table_and_the_final_norm_keeps_a_token_from_its_own_id(params):
    """No ``unembed`` among the weights: ``hybrid.head`` multiplies by the table. The final norm's
    weight is +-c: with a weight of 1 the stream, which holds its token's embedding row, would give
    that token's own id the largest logit by far, whatever the layers did."""
    assert "unembed" not in params and set(params) == {"embed", "final_norm", "shortconv", "attn", "ffn", "moe"}
    x = jax.random.normal(jax.random.PRNGKey(1), (3, CFG.hidden_size))
    np.testing.assert_allclose(hybrid.head(x, params), x @ params["embed"].T, atol=1e-5)
    np.testing.assert_allclose(hybrid.head(x, {"unembed": params["embed"].T * 2.0, "embed": params["embed"]}), 2.0 * (x @ params["embed"].T), atol=1e-5)
    fresh = jax.jit(lambda k: lfm2.init_params(CFG, k))(jax.random.PRNGKey(7))
    w = np.asarray(fresh["final_norm"])
    assert len(set(np.abs(w).round(6))) == 1 and 0.2 < (w > 0).mean() < 0.8
    np.testing.assert_allclose(np.abs(w)[0], CFG.head_scale / np.sqrt(np.mean(np.sum(np.square(np.asarray(fresh["embed"])), -1))), rtol=1e-5)
    toks = np.asarray(battery.prompts(DESC, 3, (40,)), np.int32)
    forward = jax.jit(lambda p, t: hybrid.forward(p, t, CFG)[0])
    logits = np.asarray(forward(fresh, jnp.asarray(toks)))
    assert (logits.argmax(-1) == toks[0]).mean() < 0.2 and 1.0 < logits.std() < 2.0
    ones = np.asarray(forward({**fresh, "final_norm": jnp.ones_like(fresh["final_norm"])}, jnp.asarray(toks)))
    assert (ones.argmax(-1) == toks[0]).mean() > 0.9, "with a weight of 1 the tied head hands every token its own id back"


# ------------------------------------------------------------------------------ the router's bias
def test_the_bias_chooses_and_does_not_weigh(params):
    """``s + b`` and ``s`` choose differently for most of these tokens; the weights are the chosen
    experts' own normalised scores, with the published 1e-6, whatever ``b`` is: against the
    equations, and the reference's choices against the program's through a whole forward."""
    w = jax.tree.map(lambda a: a[0], params["moe"])
    assert float(jnp.ptp(w["router_bias"])) == pytest.approx(0.5) and len(set(np.asarray(w["router_bias"]).round(6))) == 8
    x = jax.random.normal(jax.random.PRNGKey(3), (200, CFG.hidden_size))
    idx, wt = experts.route(w, x, CFG)
    s = jax.nn.sigmoid(jnp.dot(x, w["router"], precision=jax.lax.Precision.HIGHEST))
    by_s, by_sb = jax.lax.top_k(s, 2)[1], jax.lax.top_k(s + w["router_bias"], 2)[1]
    assert (np.sort(np.asarray(idx), -1) == np.sort(np.asarray(by_sb), -1)).all()
    differ = (np.sort(np.asarray(by_s), -1) != np.sort(np.asarray(by_sb), -1)).any(-1).mean()
    assert differ > 0.3, f"the bias decides {differ:.0%} of these choices: the test shows nothing"
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    np.testing.assert_allclose(wt, chosen / (chosen.sum(-1, keepdims=True) + 1e-6), atol=1e-7)
    toks = np.asarray(battery.prompts(DESC, 9, (29,)), np.int32)
    choices = []
    family.hidden_states(params, toks[0], C, choices)
    assert len(choices) == 8 and all(c.shape == (29, 2) for c in choices)


# ------------------------------------------------------------------------------ the mixers, one at a time
def test_the_head_norms_act_on_every_head_before_the_rotation(params):
    w = jax.tree.map(lambda a: a[0], params["attn"])
    xn = jax.random.normal(jax.random.PRNGKey(2), (2, 9, CFG.hidden_size))
    q, k, v = jax.jit(lambda w, xn: lfm2.qkv(w, xn, jnp.arange(9), CFG))(w, xn)
    assert q.shape == (2, 4, 9, 64) and k.shape == v.shape == (2, 2, 9, 64)

    def want(x, norm, heads):
        x = x.reshape(2, 9, heads, 64)
        x = norm * x / np.sqrt(np.mean(np.square(x), -1, keepdims=True) + 1e-5)
        return np.asarray(family._rotate(jnp.asarray(x.reshape(18, heads, 64)).reshape(2, 9, heads, 64)[0], 1e6))

    np.testing.assert_allclose(q[0].transpose(1, 0, 2), want(np.asarray(xn @ w["wq"]), np.asarray(w["q_norm"]), 4), atol=2e-5)
    np.testing.assert_allclose(k[0].transpose(1, 0, 2), want(np.asarray(xn @ w["wk"]), np.asarray(w["k_norm"]), 2), atol=2e-5)
    np.testing.assert_allclose(v, (xn @ w["wv"]).reshape(2, 9, 2, 64).transpose(0, 2, 1, 3), atol=1e-6)


def test_the_window_is_taken_at_each_true_length_and_a_step_moves_it_on(params):
    """Three prompts of 5, 16 and 11 in one padded group: the window a sequence keeps is its last two
    products ``B * u`` AT its true length, and one more token through the step form gives what the
    sequence form gives over the longer sequence, output and window."""
    w = jax.tree.map(lambda a: a[0], params["shortconv"])
    xn = jax.random.normal(jax.random.PRNGKey(4), (3, 16, CFG.hidden_size))
    lengths = jnp.asarray([5, 16, 11])
    y, window = lfm2.shortconv_seq(w, xn, lengths)
    bu = np.asarray(lfm2._gates(w, xn)[0])
    for b, n in enumerate((5, 16, 11)):
        np.testing.assert_allclose(window[b], bu[b, n - 2:n], atol=1e-6)
    short_y, short_window = lfm2.shortconv_seq(w, xn[:, :15], jnp.asarray([4, 15, 10]))
    step_y, step_window = lfm2.shortconv_step(w, jnp.stack([xn[0, 4], xn[1, 15], xn[2, 10]]), short_window)
    np.testing.assert_allclose(step_y, jnp.stack([y[0, 4], y[1, 15], y[2, 10]]), atol=1e-5)
    np.testing.assert_allclose(step_window, window, atol=1e-6)


def test_prompts_of_different_true_lengths_in_one_group_then_a_decode_over_many_positions(params, eng):
    """One admission wave whose groups pad 17, 30 and 25 to the 32 bucket (and 9 to 16): every
    window is its own prompt's, and 14 decoded tokens move it on through more than four times its
    width, against the reference's full forward; the flight log's counters are the family's."""
    ps = battery.prompts(DESC, 13, (17, 30, 25, 9))
    sp = [SamplingParams(max_tokens=14, temperature=0.0, logprobs=True)] * len(ps)
    mark = eng.telemetry()["step_count"]
    res = battery.check(DESC, params, battery.served(eng.generate(ps, sp), ps, sp))
    assert res["ok"] and res["tokens"] == 56 and res["max_abs_dlogprob"] < DESC.agrees_to, res
    rows = battery.steps_after(eng, mark)
    admitting = [r for r in rows if r.get("admitted")]
    assert sum(r["narrow_pairs"] for r in admitting) == 2 * sum(family.causal_pairs(len(p)) for p in ps)
    reads = [r["narrow_rows_read"] for r in rows if "narrow_rows_read" in r]
    assert reads and min(reads) >= 2 * 10 and max(reads) == 2 * sum(len(p) + 14 for p in ps)
    assert not any("narrow_pairs" in r for r in rows if not r.get("admitted"))


# ------------------------------------------------------------------------------ the kernels at heads 64 wide
def test_the_narrow_decode_kernel_interpreted_equals_the_xla_form(monkeypatch):
    """16 query heads over 4 key-value heads of 64 (two heads a row: the kernel sees 2 heads of
    128), 128 positions in blocks of 32, lanes at a block's edge, inside one, at the last position
    and bound to no sequence: against ``attend_rows`` over the rows seen as heads again; through
    the op with the gate swapped open, under the kernel's own name."""
    L, B, S, nh, kv, hd = 2, 4, 128, 16, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    k_heads, v_heads = (jax.random.normal(k, (L, B, S, kv, hd), jnp.float32).astype(jnp.bfloat16) for k in ks[:2])
    q = jax.random.normal(ks[2], (B, nh, hd), jnp.float32).astype(jnp.bfloat16)
    assert sa.position_tile(kv, hd) == (2, 128)
    k_stack, v_stack = (a.reshape(L, B, S, 2, 128) for a in (k_heads, v_heads))
    lengths = jnp.asarray([31, 50, 127, 7], jnp.int32)
    live = jnp.asarray([True, True, True, False])
    want = sa.attend_rows(q, k_heads[1], v_heads[1], lengths, kv)
    # through the op: off the TPU the XLA form, on the rows seen as heads again; then the gate swapped open, the kernel by its own name
    np.testing.assert_allclose(sa.attend(q, k_stack, v_stack, 1, lengths, kv, live=live)[:3], want[:3], atol=1e-5)
    names, launch = [], sa._launch
    monkeypatch.setattr(sa, "refusal", lambda *a, **kw: None)
    monkeypatch.setattr(sa, "block_positions", lambda *a, **kw: 32)
    monkeypatch.setattr(sa, "_launch", lambda kernel, name, *a: names.append(name) or launch(kernel, name, *a))
    got = sa.attend(q, k_stack, v_stack, 1, lengths, kv, live=live)
    np.testing.assert_allclose(got[:3], want[:3], atol=2e-3)
    assert not np.asarray(got[3]).any() and names == [sa.KERNEL_NARROW] == ["slot_decode_attention_narrow"]


def test_the_flash_kernel_at_heads_64_wide_equals_the_xla_form():
    """The forward kernel, interpreted, at 2 heads of 64 over 1 and 128 positions in tiles of 32,
    with true lengths that skip query tiles: against ``attention_xla``."""
    from jax.experimental.pallas import tpu as pltpu

    q = jax.random.normal(jax.random.PRNGKey(1), (2, 2, 128, 64), jnp.float32)
    k, v = (jnp.repeat(jax.random.normal(jax.random.PRNGKey(n), (2, 1, 128, 64), jnp.float32), 2, axis=1) for n in (2, 3))
    ref = fa.attention_xla(q, k, v, causal=True)
    with pltpu.force_tpu_interpret_mode():
        out, _ = fa._fwd_pallas(q, k, v, causal=True, block_q=32, block_k=32, lengths=jnp.asarray([128, 50]))
    np.testing.assert_allclose(out[0], ref[0], atol=2e-3)
    np.testing.assert_allclose(out[1, :, :50], ref[1, :, :50], atol=2e-3)
    assert not np.asarray(out[1, :, 64:]).any(), "the query tiles past the true length come out as zeros"
    assert fa._use_pallas(q, "xla") is False and fa._default_blocks(64) == (1024, 1024)


def test_the_gate_lets_32_heads_over_8_of_64_through_and_says_why_not_by_name(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert sa.refusal(jnp.bfloat16, 32, 8, 64, 12288) is None and sa.refusal(jnp.bfloat16, 16, 4, 64, 4096) is None
    assert sa.refusal(jnp.bfloat16, 32, 8, 128, 4096) is None and sa.refusal(jnp.bfloat16, 28, 4, 128, 12288) is None  # as they were
    assert "a float32 cache" in sa.refusal(jnp.float32, 32, 8, 64, 12288) and "int8" in sa.refusal(jnp.bfloat16, 32, 8, 64, 12288, quantized=True)
    assert "head_dim 32: compiled at 64 (two heads a row), 128 and 256" in sa.refusal(jnp.bfloat16, 32, 8, 32, 4096)
    assert "32 query heads over 1 kv heads x head_dim 64" in sa.refusal(jnp.bfloat16, 32, 1, 64, 4096)
    assert "positions a slot" in sa.refusal(jnp.bfloat16, 32, 8, 64, 96)
