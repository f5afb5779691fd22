"""A tenth description over the one layer loop (``models/afmoe.py``: Arcee Trinity's AFMoE block:
gated, query-key-normed attention with a window and RoPE in the window layers and full attention
without positions in the others, every sub-block's output normed before it joins the stream, a
leading dense layer, sigmoid-routed experts chosen by score + bias behind a shared one, the
embedding scaled at entry) through the engine, against the plain reference of
``benchmark/families/afmoe.py`` (float32, a [T, T] mask for the window, no cache, no ring, written
from the published equations): logits, not tokens. What is this file's own: the sandwich inside the
mixers, the gate and the head norms, the choice by ``s + b`` against the weights by ``s``, a chip's
share of the experts under a post-norm, the live-block kernel at three tiles of query rows, the
counter of expert fetches. Toy widths (window 16, prompts of 5-61, 4 of 8 experts held, top 2), float32."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hybrid_battery as battery
from benchmark.families import afmoe as family
from hybrid_battery import *  # noqa: F401,F403 - the tests every description is held to, collected here against DESC
from hybrid_battery import test_the_grouped_matmul_places_the_pairs_held_here_whatever_their_number  # noqa: F401 - it routes experts
from ray_tpu.llm import SamplingParams
from ray_tpu.llm import kv_cache as kvc
from ray_tpu.models import afmoe, experts, hybrid
from ray_tpu.ops import slot_attention as sa

PUBLISHED = {"rope_theta": 10000, "rms_norm_eps": 1e-5, "score_func": "sigmoid", "route_norm": True, "route_scale": 2.448, "mup_enabled": True,
             "num_shared_experts": 1, "tie_word_embeddings": False, "rope_scaling": None, "hidden_act": "silu", "n_group": 1, "topk_group": 1,
             "family": "afmoe"}
# the configuration file's side of the toy model: a dense layer, then W W W G, the cell's own shape; experts 0-3 of 8 held
C = family.rehearsal(PUBLISHED)
CFG = family.program_config(C, 128, remat=False)
W = CFG.sliding_window


def _another_window(by):
    """The window one key wider or narrower than the reference's, in prefill, in the ring and in the decode step alike."""
    return lambda desc, params, eng, monkeypatch: battery.least_engine(dataclasses.replace(desc.cfg, sliding_window=W + by), params)


def _rotation(kind):
    """Every attention layer rotated like a window layer (``swa``), or none rotated (``attn``): what ``qkvg`` reads of the kind."""
    return battery.patched(afmoe, "qkvg", lambda real: lambda w, xn, positions, c, _: real(w, xn, positions, c, kind))


def _not_normed(which):
    """q (0) or k (1) as it leaves its projection: its head norm left out."""
    def wrap(real):
        def head_norms(w, q, k, c):
            normed = real(w, q, k, c)
            return (q, normed[1]) if which == 0 else (normed[0], k)
        return head_norms
    return battery.patched(afmoe, "head_norms", wrap)


def _norm_after_rotation(real):
    """The head norms applied to q and k AFTER their rotation (a norm over a head commutes with no rotation that its weight does not)."""
    def qkvg(w, xn, positions, c, kind):
        keep, afmoe.head_norms = afmoe.head_norms, lambda w, q, k, c: (q, k)
        try:
            q, k, v, gate = real(w, xn, positions, c, kind)
        finally:
            afmoe.head_norms = keep
        return c.norm(q, w["q_norm"]), c.norm(k, w["k_norm"]), v, gate
    return qkvg


def _gate_from_the_stream(desc, params, eng, monkeypatch):
    """The gate read from the stream x and not from ``N_1(x)``: the loop norms x just before the mixer
    runs, so the last stream-wide input of a norm, as ``qkvg`` is traced, is this sub-block's x."""
    seen, norm, qkvg = {}, afmoe.rms_norm, afmoe.qkvg

    def watching(x, w, eps):
        if x.shape[-1] == desc.cfg.hidden_size:
            seen["x"] = x
        return norm(x, w, eps)

    def from_x(w, xn, positions, c, kind):
        q, k, v, _ = qkvg(w, xn, positions, c, kind)
        return q, k, v, jnp.dot(seen["x"].reshape(xn.shape).astype(xn.dtype), w["wg"])

    monkeypatch.setattr(afmoe, "rms_norm", watching)
    monkeypatch.setattr(afmoe, "qkvg", from_x)
    return battery.least_engine(desc.cfg, params)


def _weights_from_s_plus_b(real):
    """The chosen experts weighted by ``s + b``, the numbers they were chosen by, and not by ``s``."""
    def route(w, x, c):
        idx, _ = real(w, x, c)
        s = c.expert_layer
        scores = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32), w["router"].astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)) + w["router_bias"]
        wt = jnp.take_along_axis(scores, idx, axis=-1)
        return idx, wt / (jnp.sum(wt, axis=-1, keepdims=True) + s.norm_eps) * s.scale
    return route


def _dense_layer_routed(desc, params, eng, monkeypatch):
    """The leading dense layer run as an expert layer (with the first expert layer's weights): ``num_dense_layers`` 0."""
    moe = jax.tree.map(lambda a: jnp.concatenate([a[:1], a]), params["moe"])
    return battery.least_engine(dataclasses.replace(desc.cfg, num_dense_layers=0), {**params, "moe": moe})


def _in_both(params, **new):
    """``params`` with the entries ``new`` replaced in the window layers' and the full layers' attention alike."""
    return battery.in_kind(battery.in_kind(params, "swa", **{n: f(params["swa"][n]) for n, f in new.items()}), "attn", **{n: f(params["attn"][n]) for n, f in new.items()})


# float32 program against float32 reference: the same mathematics summed in another order (tiles of
# queries, the grouped matmul, a ring's rows in another order than the positions'). They agree to
# 1e-5 in a log-probability; what a planted fault does is over 2e-4
DESC = battery.Description(
    family=family, c=C, cfg=CFG, tol=2e-4, agrees_to=1e-5,
    state_bytes_per_slot=0, kv_bytes_per_token=5 * 2 * (2 * 16) * 4,  # five layers, a key and a value of 2 heads x 16, while a position is held
    poison={"k": jnp.nan, "v": 1e4, "k_w": jnp.nan, "v_w": 1e4},
    faults={"window_one_wider": battery.Fault(_another_window(+1)),
            "window_one_narrower": battery.Fault(_another_window(-1)),
            "full_layer_rotated": battery.Fault(_rotation("swa")),
            "window_layer_not_rotated": battery.Fault(_rotation("attn")),
            "q_not_normed": battery.Fault(_not_normed(0)),
            "k_not_normed": battery.Fault(_not_normed(1)),
            "norm_after_rotation": battery.Fault(battery.patched(afmoe, "qkvg", _norm_after_rotation)),
            # behind the post-norm a gate of one half everywhere IS no gate: the fault is in the weights, on the module's engine
            "gate_left_out": battery.Fault(battery.with_params(lambda p: _in_both(p, wg=jnp.zeros_like))),
            "gate_from_the_unnormed_stream": battery.Fault(_gate_from_the_stream),
            "post_norm_left_out": battery.Fault(battery.patched(afmoe, "post_norm", lambda real: lambda c, w, y: y)),
            "choice_by_s_alone": battery.Fault(battery.with_params(lambda p: battery.in_kind(p, "moe", router_bias=jnp.zeros_like(p["moe"]["router_bias"])))),
            "weights_from_s_plus_b": battery.Fault(battery.patched(experts, "route", _weights_from_s_plus_b)),
            "no_route_scale": battery.Fault(lambda desc, params, eng, monkeypatch: battery.least_engine(dataclasses.replace(desc.cfg, route_scale=1.0), params)),
            "embedding_unscaled": battery.Fault(battery.with_params(lambda p: {**p, "embed": p["embed"] / math.sqrt(CFG.hidden_size)})),
            "dense_layer_routed": battery.Fault(_dense_layer_routed)},
    refusal_says=("its window layers keep k_w and v_w in a ring of the last 16 positions",),
    refusal_says_not=("recurrent", "c_kv"))


@pytest.fixture(scope="module")
def params():
    return battery.jiggled(jax.jit(lambda k: afmoe.init_params(CFG, k))(jax.random.PRNGKey(7)))


# ------------------------------------------------------------------------------ the description
def test_the_description_is_a_dense_layer_and_a_period_of_four_kinds_with_a_ring_beside_rows():
    assert CFG.layer_kinds == ("swa", "mlp", "swa", "moe", "swa", "moe", "swa", "moe", "attn", "moe")
    assert CFG.layer_plan == hybrid.LayerPlan(period=("swa", "moe"), repeats=3, tail=("attn", "moe"), head=("swa", "mlp"))
    published = afmoe.AfmoeConfig()
    assert (published.count("swa"), published.count("attn"), published.count("mlp"), published.count("moe")) == (45, 15, 6, 54)
    assert published.num_params() == 398_635_286_016 and published.layer_kinds[:14] == ("swa", "mlp") * 3 + ("attn", "mlp") + ("swa", "mlp") * 2 + ("swa", "moe")
    cut = dataclasses.replace(published, num_hidden_layers=5, num_dense_layers=1, layer_types=("sliding_attention",) * 4 + ("full_attention",),
                              num_local_experts=32, vocab_size=25024, max_seq_len=12288)
    assert cut.layer_plan == CFG.layer_plan and cut.kinds_held == "4 x swa, 1 x mlp, 4 x moe, 1 x attn" and cut.num_params() == 4_321_903_872
    assert (cut.num_kv_layers, cut.routing_layers, cut.num_layers, cut.stream_scales) == (4, 4, 10, (math.sqrt(3072), 1.0, 1.0))
    assert {k: (m.scope, m.routes, m.hands) for k, m in cut.mixers.items()} == {
        "swa": ("swa", False, False), "attn": ("attn", False, False), "mlp": ("mlp", False, False), "moe": ("moe", True, False)}
    s = cut.expert_layer
    assert (s.num_experts, s.held, s.top_k, s.score, s.bias, s.norm_topk, s.scale, s.act, s.shared, s.shared_gated, s.norm_eps) == (
        256, 32, 4, "sigmoid", True, True, 2.448, "swiglu", True, False, 1e-20)
    kv = ((8, 128), "bfloat16", "position")
    assert cut.cache_spec() == {"attn": {"k": kv, "v": kv}, "swa": {"k_w": kv, "v_w": kv}, "mlp": {}, "moe": {}} and cut.handed == {}
    assert cut.ring_entries() == {"k_w": 4096, "v_w": 4096} and cut.flash_calls(12288) == {128: 5}
    cache = jax.eval_shape(lambda: kvc.alloc_entries(cut.position_entries(), 16, 12288, cut.ring_entries()))
    assert cache["k"].shape == (1, 16, 12288, 8, 128) and cache["k_w"].shape == (4, 16, 4096, 8, 128)
    assert sum(a.size * a.dtype.itemsize for n, a in cache.items() if n != "length") == 1_879_048_192  # 0.81 GB of rows and 1.07 GB of rings
    # the counters, from lengths alone: sum over positions of min(i + 1, W), and min(pos + 1, W) a lane
    assert cut.prefill_counters(2, 12288, lengths=[10500, 100]) == {"swa_pairs": 4 * ((4096 * 4097) // 2 + (10500 - 4096) * 4096 + 5050)}
    assert cut.decode_counters([12000, 4096, 100]) == {"swa_rows_read": 4 * (4096 + 4096 + 100)}
    assert cut.prefill_rows_live(12288, [10500, 1]) == 10752 + 512  # the dense layer goes over live slabs of 512
    with pytest.raises(ValueError, match="layer_types names every held layer"):
        dataclasses.replace(cut, layer_types=("sliding_attention",) * 4)
    with pytest.raises(ValueError, match="inside the router's width"):
        dataclasses.replace(cut, expert_start=240)


def test_a_programs_expert_fetches_follow_from_its_routing_counters_and_its_shape(monkeypatch):
    """``moe_expert_fetches``: where the loop runs the blocks, a fetch a block in use (rows over the block's height, which
    ``blocks_plan`` gives the call); where the kernel does, a fetch an expert hit. Trinity's 12,288-row call: blocks of 128, the loop."""
    cut = dataclasses.replace(afmoe.AfmoeConfig(), num_hidden_layers=5, num_dense_layers=1, layer_types=("sliding_attention",) * 4 + ("full_attention",), num_local_experts=32)
    routing = np.asarray([32.0, 6144.0, 8064.0, 0.0], np.float32)  # 32 experts hit, 6,144 pairs in 63 blocks of 128
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the gate of ``ops/grouped_experts`` is asked as on the chip
    assert cut.routed_counters(12288, routing) == {"moe_expert_fetches": 63.0} and cut.routed_counters(2 * 12288, routing) == {"moe_expert_fetches": 63.0}
    monkeypatch.setattr(experts, "blocks_plan", lambda s, N, mats: (256, True))
    assert cut.routed_counters(12288, np.asarray([32.0, 6144.0, 8192.0, 8192.0])) == {"moe_expert_fetches": 32.0}
    assert dataclasses.replace(cut, num_dense_layers=5).routed_counters(12288, routing) == {} and hybrid.trace_description().routed_counters(64, routing) == {}


def test_the_router_chooses_by_score_plus_bias_and_weighs_by_the_score_alone(params):
    """``experts.route`` under this description's ``ExpertLayer``: the top k of ``s + b``, their own ``s`` renormalised
    and times ``route_scale``; the bias moves the choice on some rows (or the test shows nothing) and no weight."""
    w = jax.tree.map(lambda a: a[0], params["moe"])
    x = jax.random.normal(jax.random.PRNGKey(3), (400, CFG.hidden_size))
    idx, wt = experts.route(w, x, CFG)
    s = jax.nn.sigmoid(jnp.dot(x, w["router"], precision=jax.lax.Precision.HIGHEST))
    _, want = jax.lax.top_k(s + w["router_bias"], CFG.num_experts_per_tok)
    assert (np.asarray(idx) == np.asarray(want)).all()
    assert (np.sort(np.asarray(want), -1) != np.sort(np.asarray(jax.lax.top_k(s, CFG.num_experts_per_tok)[1]), -1)).any(), "the bias never moves the choice"
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    np.testing.assert_allclose(wt, 2.448 * chosen / jnp.sum(chosen, axis=-1, keepdims=True), atol=1e-6)
    np.testing.assert_allclose(jnp.sum(wt, axis=-1), 2.448, atol=1e-5)


def test_the_eight_chips_shares_add_up_to_the_uncut_expert_layer_under_its_post_norm():
    """Each of the deployment's eight chips holds an equal run of the experts (here 2 of 16); the routed parts of all of
    them, with what every chip computes alike (the shared expert) counted once, normed as the layer norms its output,
    are the uncut reference layer."""
    whole = family.program_config({**C, "num_experts": 16, "deployment": None}, 128)
    s = whole.expert_layer
    assert (s.num_experts, s.held) == (16, 16)
    params = battery.jiggled(jax.jit(whole.init_params)(jax.random.PRNGKey(11)))
    group = jax.tree.map(lambda a: a[:1], params["moe"])
    x = jax.random.normal(jax.random.PRNGKey(12), (40, whole.hidden_size))
    ref, _, _ = family._experts(x, group, 0, first=0, top_k=s.top_k, norm=True, scale=2.448, eps=1e-5)
    layer = jax.tree.map(lambda a: a[0], group)
    xn = whole.norm(x, layer["norm"])
    idx, wt = experts.route(layer, xn, whole)
    total = experts.shared_expert(layer, xn, s)
    for chip in range(8):
        share = dataclasses.replace(whole, expert_start=2 * chip, num_local_experts=2)
        w = {**layer, **{n: layer[n][2 * chip:2 * chip + 2] for n in s.matrices}}
        routed = jax.jit(lambda w, *a, share=share: experts.experts_grouped(w, 0, *a, share))(jax.tree.map(lambda a: a[None], w), xn, idx, wt, jnp.ones((40,), bool))
        assert np.abs(np.asarray(routed)).max() > 0
        total = total + routed
    np.testing.assert_allclose(x + afmoe.post_norm(whole, layer, total), ref, atol=1e-5)


# ------------------------------------------------------------------------------ the ring and the counters
def test_one_slot_serves_a_long_sequence_then_shorter_ones_across_the_rings_wrap(params):
    """ONE slot, so every sequence after the first lives in rows the last one left: a prompt of 61 (its ring wrapped three
    times over) and 30 decoded tokens, then prompts under, at and over the window, each decoding across a wrap (or up to
    it), against the reference's full forward; and the flight log's counters against their definitions."""
    eng = battery.engine(CFG, params, max_num_seqs=1)
    ps = battery.prompts(DESC, 12, (61, 9, 16, 15, 17, 33))
    sp = [SamplingParams(max_tokens=30 if i == 0 else 12, temperature=0.0, logprobs=True) for i in range(len(ps))]
    mark = eng.telemetry()["step_count"]
    res = battery.check(DESC, params, battery.served(eng.generate(ps, sp), ps, sp))
    assert res["ok"] and res["tokens"] == 30 + 5 * 12 and res["max_abs_dlogprob"] < DESC.agrees_to, res
    rows = battery.steps_after(eng, mark)
    admitting = [r for r in rows if r.get("admitted")]
    assert sum(r["swa_pairs"] for r in admitting) == 4 * sum(family.window_pairs(C, len(p)) for p in ps)
    reads = [r["swa_rows_read"] for r in rows if "swa_rows_read" in r]
    assert reads and all(0 < n <= 4 * W for n in reads) and max(reads) == 4 * W and min(reads) == 4 * 10  # the prompt of 9 and its first token
    # off the TPU the loop runs the blocks: a fetch a block in use, so between one and two an expert hit at these fills
    assert all(r["moe_expert_fetches"] == r["moe_rows_computed"] / experts.BLOCK >= r["prefill_experts_hit"] > 0 for r in admitting)
    assert not any("swa_pairs" in r or "moe_expert_fetches" in r for r in rows if not r.get("admitted"))


# ------------------------------------------------------------------------------ the kernels
def test_the_live_block_kernel_at_three_tiles_of_query_rows_equals_the_xla_form():
    """48 query heads over 8 key-value heads are three bfloat16 tiles of 16 query rows a lane, one more than any cell
    had: the kernel's body as it stands, interpreted, over the rows of every position and over a ring's, lanes at
    different lengths and one bound to nothing, against ``attend_rows``."""
    B, S, nh, kv, hd = 3, 256, 48, 8, 128
    assert sa.padded_heads(nh, kv) == 48
    q = jax.random.normal(jax.random.PRNGKey(1), (B, nh, hd), jnp.float32).astype(jnp.bfloat16)
    k, v = (jax.random.normal(jax.random.PRNGKey(n), (2, B, S, kv, hd), jnp.float32).astype(jnp.bfloat16) for n in (2, 3))
    lengths = jnp.asarray([200, 37, 5], jnp.int32)
    want = sa.attend_rows(q, k[1], v[1], lengths, kv)
    bound = jnp.asarray([201, 38, 0], jnp.int32)
    got = sa.attend_kernel(q, k, v, jnp.int32(1), bound, block=64, interpret=True, name="window_decode_attention")
    np.testing.assert_allclose(np.asarray(got).reshape(B, -1)[:2], np.asarray(want)[:2], atol=2e-2, rtol=2e-2)
    assert not np.asarray(got)[2].any()  # the lane bound to nothing read nothing and gets zeros
