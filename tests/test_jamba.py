"""A ninth description over the one layer loop (``models/jamba.py``: Mamba-1 selective state-space
layers whose every (channel, state) pair decays by its own factor, attention without positions over
ONE key-value head every fourth layer here, a dense SwiGLU after every mixer, a tied head) through
the engine, against the plain reference of ``benchmark/families/jamba.py`` (float32, the recurrence
one position at a time over a state [d_inner, d_state], the convolution as four shifted products, a
masked softmax, no cache, no kernel, written from the published equations): logits, not tokens. What
is this file's own: the selective scan (``ops/selective_scan.py``: the kernel interpreted = the XLA
form = the reference's recurrence, over lengths that end inside, on and one past a position block;
one decode step = the sequence form's next position), a state and a window taken AT each prompt's
true length, the inner norms, the two bias keys, the tied head, the counters. Toy widths (hidden 64,
d_inner 128, 16 states, step rank 8, 4 heads of 16 over 1, prompts of 5-61), float32."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hybrid_battery as battery
from benchmark.families import jamba as family
from hybrid_battery import *  # noqa: F401,F403 - the tests every description is held to, collected here against DESC
from ray_tpu.llm import SamplingParams
from ray_tpu.models import hybrid, jamba
from ray_tpu.ops import selective_scan as ss

PUBLISHED = {"attn_layer_offset": 7, "attn_layer_period": 14, "hidden_act": "silu", "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
             "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False, "num_experts": 1, "rms_norm_eps": 1e-6, "sliding_window": None,
             "tie_word_embeddings": True, "family": "jamba"}
C = family.rehearsal(PUBLISHED)  # the configuration file's side of the toy model: M A M M twice
CFG = family.program_config(C, 128, remat=False)


def _zero(name):
    return battery.with_params(lambda p: battery.in_kind(p, "mamba1", **{name: jnp.zeros_like(p["mamba1"][name])}))


def _one_decay_a_channel(params):
    """``A`` averaged over its 16 states: every state of a channel decays alike, Mamba-2's scalar form."""
    A = jnp.exp(params["mamba1"]["A_log"])
    return battery.in_kind(params, "mamba1", A_log=jnp.log(jnp.broadcast_to(A.mean(-1, keepdims=True), A.shape)))


class _NoStepNorm:
    """The description with the step's inner norm left out (B's and C's stay)."""

    def __init__(self, c):
        self._c = c

    def __getattr__(self, name):
        return getattr(self._c, name)

    def norm(self, x, w):
        return x if x.shape[-1] == self._c.mamba_dt_rank else self._c.norm(x, w)


def _rotated(real):
    """Rotary positions on the attention layers' prefill: what a reader of the Llama family would put there."""
    from ray_tpu.ops.flash_attention import flash_attention_on_mesh
    from ray_tpu.ops.layers import apply_rope, rotary_embedding

    def attn_seq(w, xn, c, mesh=None, lengths=None):
        B, T, _ = xn.shape
        q, k, v = (a.transpose(0, 2, 1, 3) for a in jamba.qkv(w, xn, c))
        cos, sin = rotary_embedding(jnp.arange(T), c.hd)
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        o = flash_attention_on_mesh(q, k, v, mesh, c.attention_impl, lengths=lengths)
        return jnp.dot(o.transpose(0, 2, 1, 3).reshape(B, T, -1), w["wo"]), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    return attn_seq


# float32 program against float32 reference: the same mathematics summed in another order (the state
# transposed, tiles of queries). They agree to 1e-5 in a log-probability; what a wrong state, decay,
# norm or bias does is over 1e-3
DESC = battery.Description(
    family=family, c=C, cfg=CFG, tol=2e-4, agrees_to=1e-5,
    state_bytes_per_slot=6 * (16 * 128 + 3 * 128) * 4,  # six Mamba layers: a state of 16 x 128 and a window of three inputs 128 wide
    kv_bytes_per_token=2 * 2 * 16 * 4,  # two attention layers, a key and a value of ONE head x 16
    poison={"k": jnp.nan, "v": 1e4},
    faults={"state_and_window_at_the_padded_length": battery.Fault(battery.padded_length),
            "slot_not_reset": battery.Fault(battery.slot_not_reset),
            "one_decay_a_channel": battery.Fault(battery.with_params(_one_decay_a_channel)),
            "step_bias_left_out": battery.Fault(_zero("dt_bias")),
            "skip_left_out": battery.Fault(_zero("D")),
            "convolution_bias_left_out": battery.Fault(_zero("conv_b")),
            "inner_norm_left_out": battery.Fault(battery.patched(jamba, "scan_inputs", lambda real: lambda w, cx, c: real(w, cx, _NoStepNorm(c)))),
            "rotary_positions_on_attention": battery.Fault(battery.patched(jamba, "attn_seq", _rotated))},
    refusal_says=("its recurrent layers keep a state per sequence (conv, ssm)",),
    refusal_says_not=("c_kv", "ring"))


@pytest.fixture(scope="module")
def params():
    return battery.jiggled(jax.jit(lambda k: jamba.init_params(CFG, k))(jax.random.PRNGKey(7)))


# ------------------------------------------------------------------------------ the description
def test_the_description_is_a_period_of_28_sub_blocks_twice_and_keeps_a_state_a_window_and_one_heads_rows():
    assert CFG.layer_kinds == ("mamba1", "ffn", "attn", "ffn", "mamba1", "ffn", "mamba1", "ffn") * 2
    assert CFG.layer_plan == hybrid.LayerPlan(period=CFG.layer_kinds[:8], repeats=2, tail=(), head=())
    published = family.program_config(_cell(), 12288, remat=False)
    period = tuple(kind for l in range(14) for kind in ("attn" if l == 7 else "mamba1", "ffn"))
    assert published.layer_plan == hybrid.LayerPlan(period, 2, (), ()) and len(period) == 28, "the longest period any description has had"
    assert published.num_params() == 3_029_337_472 == _cell()["parameters"] == family.parameters_held(_cell())
    assert published.kinds_held == "26 x mamba1, 28 x ffn, 2 x attn" and (published.num_kv_layers, published.routing_layers, published.num_layers) == (2, 0, 56)
    assert {k: (m.scope, m.routes, m.hands) for k, m in published.mixers.items()} == {
        "mamba1": ("mamba1", False, False), "attn": ("attn", False, False), "ffn": ("ffn", False, False)}
    kv = ((1, 128), "bfloat16", "position")
    assert published.cache_spec() == {"attn": {"k": kv, "v": kv}, "ffn": {},
                                      "mamba1": {"ssm": ((16, 5120), "float32", "sequence"), "conv": ((3, 5120), "bfloat16", "sequence")}}
    assert published.position_entries() == {"k": (2, (1, 128), "bfloat16"), "v": (2, (1, 128), "bfloat16")} and published.ring_entries() == {}
    assert published.slot_attention_tile == dict(num_heads=20, num_kv_heads=1, head_dim=128) and published.flash_calls(12288) == {128: 2}
    assert family.kv_bytes_per_token(_cell()) == 1024 and family.state_bytes_per_slot(_cell()) == 26 * (327_680 + 30_720) == 9_318_400
    assert family.cache_bytes(_cell(), 16, 12288) == 201_326_592 + 16 * 9_318_400
    # the counters, from the programs' shapes alone: off the TPU the XLA form runs every position
    assert published.prefill_counters(2, 12288, lengths=[10500, 100]) == {"selscan_positions": 26 * 2 * 12288, "selscan_kernel_positions": 0}
    assert published.decode_counters([12000, 100]) == {}


def _cell():
    import json
    import os

    from benchmark import common

    with open(os.path.join(common.ROOT, "benchmark", "configs", "jamba2-3b.json")) as f:
        return json.load(f)


def test_a_siblings_config_fails_loudly_and_the_two_bias_keys_are_honoured():
    """``num_experts`` > 1 (Jamba's larger members route every second layer) raises by the missing piece's
    name; ``mamba_conv_bias`` false and ``mamba_proj_bias`` true change the weights held, the count, and
    what both program and reference compute: they still agree."""
    with pytest.raises(ValueError, match="route their feed-forward sub-block to experts"):
        jamba.JambaConfig.tiny(num_experts=16)
    with pytest.raises(ValueError, match="num_experts > 1 routes it"):
        family.program_config({**_cell(), "num_experts": 16}, 128)
    with pytest.raises(ValueError, match="attn_layer_offset lies inside"):
        jamba.JambaConfig.tiny(attn_layer_offset=4)
    c = {**C, "mamba_conv_bias": False, "mamba_proj_bias": True}
    cfg = family.program_config(c, 128, remat=False)
    params = jax.jit(lambda k: jamba.init_params(cfg, k))(jax.random.PRNGKey(3))
    assert "conv_b" not in params["mamba1"] and params["mamba1"]["in_bias"].shape == (6, 256) and params["mamba1"]["out_bias"].shape == (6, 64)
    assert cfg.num_params() == CFG.num_params() + 6 * (256 + 64 - 128) == sum(a.size for a in jax.tree.leaves(params)) == family.parameters_held(c)
    params = battery.in_kind(params, "mamba1", in_bias=0.3 * jax.random.normal(jax.random.PRNGKey(4), (6, 256)), out_bias=0.3 * jax.random.normal(jax.random.PRNGKey(5), (6, 64)))
    toks = np.asarray(battery.prompts(DESC, 2, (23,)), np.int32)
    got = jax.nn.log_softmax(hybrid.forward(params, jnp.asarray(toks), cfg)[0], -1)
    np.testing.assert_allclose(got, family.reference_logprobs(params, toks[0], c, 0, 23), atol=1e-5)
    without = jax.nn.log_softmax(hybrid.forward(battery.in_kind(params, "mamba1", in_bias=jnp.zeros((6, 256))), jnp.asarray(toks), cfg)[0], -1)
    assert float(jnp.abs(got - without).max()) > 1e-2, "the bias is there to be honoured"


def test_the_head_is_the_embedding_table_and_the_initialisation_is_mambas(params):
    assert "unembed" not in params and set(params) == {"embed", "final_norm", "mamba1", "attn", "ffn"}
    x = jax.random.normal(jax.random.PRNGKey(1), (3, CFG.hidden_size))
    np.testing.assert_allclose(hybrid.head(x, params), x @ params["embed"].T, atol=1e-5)
    fresh = jax.jit(lambda k: jamba.init_params(CFG, k))(jax.random.PRNGKey(7))
    w = np.asarray(fresh["final_norm"])
    assert len(set(np.abs(w).round(6))) == 1 and 0.2 < (w > 0).mean() < 0.8
    m = fresh["mamba1"]
    np.testing.assert_allclose(np.exp(np.asarray(m["A_log"])), np.broadcast_to(np.arange(1, 17, dtype=np.float32), (6, 128, 16)), rtol=1e-6)
    step = np.asarray(jax.nn.softplus(m["dt_bias"]))
    assert (np.asarray(m["D"]) == 1).all() and 0.001 <= step.min() < 0.003 and 0.03 < step.max() <= 0.1 + 1e-6
    assert np.abs(np.asarray(m["conv_b"])).max() <= 0.5 and np.asarray(m["conv_b"]).std() > 0.2
    assert all(m[n].dtype == jnp.float32 for n in ("dt_bias", "A_log", "D"))
    toks = np.asarray(battery.prompts(DESC, 3, (40,)), np.int32)
    logits = np.asarray(jax.jit(lambda p, t: hybrid.forward(p, t, CFG)[0])(fresh, jnp.asarray(toks)))
    assert (logits.argmax(-1) == toks[0]).mean() < 0.2 and 1.0 < logits.std() < 2.0


# ------------------------------------------------------------------------------ the selective scan
def _scan_inputs(B, T, W, N=16, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x, s = jax.random.normal(ks[0], (B, T, W)).astype(dtype), (jax.random.normal(ks[1], (B, T, W)) - 2.0).astype(dtype)
    A = -jnp.exp(jax.random.uniform(ks[2], (W, N), minval=0.0, maxval=2.5))  # a decay of its own for every (channel, state) pair
    Bm, Cm = (jax.random.normal(k, (B, T, N)).astype(dtype) for k in ks[3:5])
    return x, s, A, Bm, Cm, 1.0 + 0.1 * jax.random.normal(ks[5], (W,)), 0.5 * jax.random.normal(ks[5], (W,))


def _recurrence_by_the_reference(x, s, A, Bm, Cm, D, bias, n):
    """The family's equations on ONE sequence's first ``n`` positions, in numpy float64: (y [n, W], the state [W, N])."""
    x, s, A, Bm, Cm, D, bias = (np.asarray(a, np.float64) for a in (x, s, A, Bm, Cm, D, bias))
    dt, h, ys = np.log1p(np.exp(s + bias)), np.zeros(A.shape), []
    for t in range(n):
        h = np.exp(dt[t][:, None] * A) * h + (dt[t] * x[t])[:, None] * Bm[t][None, :]
        ys.append(h @ Cm[t] + D * x[t])
    return np.stack(ys), h


@pytest.mark.parametrize("T, lengths", [(40, (40, 17)), (128, (128, 127)), (129, (129, 1)), (300, (300, 256)), (272, (257, 130))])
def test_the_scan_kernel_interpreted_equals_the_xla_form_equals_the_recurrence(T, lengths):
    """Blocks of 128 positions: lengths that end inside a block, on its edge and one past it, a state
    carried across two and three blocks, a block that lies wholly past a sequence (its ``y`` is zeros, the
    state stays), in runs of 128 channels: the state AT the true length, ``y`` up to it."""
    a, L = _scan_inputs(2, T, 256, seed=T), jnp.asarray(lengths, jnp.int32)
    y_x, h_x = ss.scan_xla(*a, L)
    y_k, h_k = ss.selective_scan(*a, L, interpret=True)
    assert y_k.shape == y_x.shape == (2, T, 256) and h_k.shape == h_x.shape == (2, 16, 256)
    for b, n in enumerate(lengths):
        want_y, want_h = _recurrence_by_the_reference(*(v[b] if v.ndim == 3 else v for v in a), n)
        for y, h in ((y_x, h_x), (y_k, h_k)):
            np.testing.assert_allclose(y[b, :n], want_y, atol=2e-5)
            np.testing.assert_allclose(h[b].T, want_h, atol=2e-5)
    past = -(-lengths[1] // ss.BLOCK) * ss.BLOCK
    assert not np.asarray(y_k[1, past:]).any(), "a block past the sequence is skipped and its output is zeros"


def test_one_decode_step_is_the_sequence_forms_next_position_and_bfloat16_operands_stay_close():
    a = _scan_inputs(3, 33, 128, seed=5)
    x, s, A, Bm, Cm, D, bias = a
    y_all, h_all = ss.scan_xla(*a, jnp.asarray([33, 33, 20]))
    _, h_before = ss.scan_xla(x[:, :32], s[:, :32], A, Bm[:, :32], Cm[:, :32], D, bias, jnp.asarray([32, 32, 19]))
    at = jnp.asarray([32, 32, 19])
    pick = lambda v: v[jnp.arange(3), at]  # noqa: E731
    y, h = ss.step(h_before, pick(x), pick(s), A, pick(Bm), pick(Cm), D, bias)
    np.testing.assert_allclose(y, pick(y_all), atol=1e-5)
    np.testing.assert_allclose(h, h_all, atol=1e-5)
    low = tuple(v.astype(jnp.bfloat16) if v.ndim == 3 else v for v in a)
    y_low, h_low = ss.selective_scan(*low, jnp.asarray([33, 33, 20]), interpret=True)
    assert y_low.dtype == jnp.bfloat16 and h_low.dtype == jnp.float32
    np.testing.assert_allclose(y_low[:2].astype(jnp.float32), y_all[:2], atol=0.15, rtol=0.05)


def test_the_gate_says_why_not_by_name_and_the_counters_follow_it(monkeypatch):
    assert "backend 'cpu'" in ss.refusal(jnp.bfloat16, 5120, 16)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ss.refusal(jnp.bfloat16, 5120, 16) is None
    assert "float32 operands" in ss.refusal(jnp.float32, 5120, 16) and "8 states a channel" in ss.refusal(jnp.bfloat16, 5120, 8)
    assert "5000 channels: compiled for whole runs of 512" in ss.refusal(jnp.bfloat16, 5000, 16)

    class Mesh:
        size = 4

    assert "a program over a mesh" in ss.refusal(jnp.bfloat16, 5120, 16, mesh=Mesh())
    assert ss.counters(26, 4, 12288, "bfloat16", 5120, 16) == {"selscan_positions": 26 * 4 * 12288, "selscan_kernel_positions": 26 * 4 * 12288}
    assert ss.counters(26, 1, 12288, "float32", 5120, 16)["selscan_kernel_positions"] == 0


# ------------------------------------------------------------------------------ the mixer, one layer at a time
def test_the_state_and_the_window_are_taken_at_each_true_length_and_a_step_moves_them_on(params):
    """Three prompts of 5, 16 and 11 in one padded group: a sequence keeps its last three inputs ``u`` and
    its state AT its true length, and one more token through the step form gives what the sequence form
    gives over the longer sequence: output, state and window."""
    w = jax.tree.map(lambda a: a[0], params["mamba1"])
    xn = jax.random.normal(jax.random.PRNGKey(4), (3, 16, CFG.hidden_size))
    y, ssm, window = jamba.mamba1_seq(w, xn, jnp.asarray([5, 16, 11]), CFG)
    u = np.asarray(jamba._in(w, xn, CFG)[0])
    for b, n in enumerate((5, 16, 11)):
        np.testing.assert_allclose(window[b], u[b, n - 3:n], atol=1e-6)
        alone = jamba.mamba1_seq(w, xn[b:b + 1, :n], jnp.asarray([n]), CFG)
        np.testing.assert_allclose(ssm[b], alone[1][0], atol=1e-6)
        np.testing.assert_allclose(y[b, :n], alone[0][0], atol=1e-5)
    _, short_ssm, short_window = jamba.mamba1_seq(w, xn[:, :15], jnp.asarray([4, 15, 10]), CFG)
    step_y, step_ssm, step_window = jamba.mamba1_step(w, jnp.stack([xn[0, 4], xn[1, 15], xn[2, 10]]), short_ssm, short_window, CFG)
    np.testing.assert_allclose(step_y, jnp.stack([y[0, 4], y[1, 15], y[2, 10]]), atol=1e-5)
    np.testing.assert_allclose(step_ssm, ssm, atol=1e-6)
    np.testing.assert_allclose(step_window, window, atol=1e-6)
    assert ssm.shape == (3, 16, 128) and ssm.dtype == jnp.float32


def test_prefill_through_the_kernel_interpreted_then_a_decode_over_many_positions(params, monkeypatch):
    """The gate answered for before a fresh engine traces its programs: the prefill's scans run the kernel's
    body interpreted (one admission wave whose groups pad 17, 30 and 25 to the 32 bucket and 9 to 16), every
    state is its own prompt's at its true length, 14 decoded tokens move it on, all against the reference's
    full forward; the admitting rows count the positions scanned, as padded, and those the kernel ran."""
    monkeypatch.setattr(ss, "refusal", lambda *a, **kw: None)
    eng = battery.engine(CFG, params)
    ps = battery.prompts(DESC, 13, (17, 30, 25, 9))
    sp = [SamplingParams(max_tokens=14, temperature=0.0, logprobs=True)] * len(ps)
    res = battery.check(DESC, params, battery.served(eng.generate(ps, sp), ps, sp))
    assert res["ok"] and res["tokens"] == 56 and res["max_abs_dlogprob"] < DESC.agrees_to, res
    rows = eng.telemetry()["steps"]
    admitting = [r for r in rows if r.get("admitted")]
    assert sum(r["selscan_positions"] for r in admitting) == sum(r["selscan_kernel_positions"] for r in admitting) == 6 * sum(r["prefill_tokens_padded"] for r in admitting)
    assert not any("selscan_positions" in r for r in rows if not r.get("admitted"))


def test_the_xla_form_counts_no_kernel_positions(eng):
    mark = eng.telemetry()["step_count"]
    eng.generate(battery.prompts(DESC, 14, (12, 20)), [SamplingParams(max_tokens=2, temperature=0.0)] * 2)
    admitting = [r for r in battery.steps_after(eng, mark) if r.get("admitted")]
    assert admitting and all(r["selscan_positions"] == 6 * r["prefill_tokens_padded"] and r["selscan_kernel_positions"] == 0 for r in admitting)
