"""A sixth description over the one layer loop (``models/smallthinker.py``: sliding-window
attention with RoPE three layers in four, full attention without positions the fourth, ReGLU
experts with no shared one, routed from the stream BEFORE attention) through the engine, against
the plain reference of ``benchmark/families/smallthinker.py`` (float32, a [T, T] mask for the
window, no cache, no ring, written from the published equations): logits, not tokens. What is this
file's own: window and global entries side by side in one slot cache (a ring of W rows beside
rows for every position), the ring's insertion and its wrap, the routing handed from one
sub-block to the next, the windowed flash kernel and the decode kernels interpreted, the router's
identity, the counters. Toy widths (window 16, prompts of 5-61, 8 experts top 2), float32."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hybrid_battery as battery
from benchmark.families import smallthinker as family
from hybrid_battery import *  # noqa: F401,F403 - the tests every description is held to, collected here against DESC
from hybrid_battery import test_the_grouped_matmul_places_the_pairs_held_here_whatever_their_number  # noqa: F401 - it routes experts
from ray_tpu.llm import SamplingParams
from ray_tpu.llm import kv_cache as kvc
from ray_tpu.models import experts, hybrid
from ray_tpu.models import smallthinker as st
from ray_tpu.ops import flash_attention as fa
from ray_tpu.ops import slot_attention as sa

PUBLISHED = {"rope_theta": 1500000, "rms_norm_eps": 1e-6, "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
             "tie_word_embeddings": False, "rope_scaling": None, "family": "smallthinker"}
# the configuration file's side of the toy model: G W W W twice, the cell's own shape
C = family.rehearsal(PUBLISHED)
CFG = family.program_config(C, 128, remat=False)
W = CFG.sliding_window_size


def _another_window(by):
    """The window one key wider or narrower than the reference's, in prefill, in the ring and in the decode step alike."""
    return lambda desc, params, eng, monkeypatch: battery.least_engine(dataclasses.replace(desc.cfg, sliding_window_size=W + by), params)


def _rotation(always):
    """A global layer rotated like a window layer (``always``), or a window layer left unrotated."""
    return battery.patched(st, "qkv", lambda real: lambda w, xn, positions, c, rotates: real(w, xn, positions, c, always))


def _routed_after_attention(desc, params, eng, monkeypatch):
    """The router fed ``N_2(x')``, the expert sub-block's own input, as every other expert layer
    here routes: the expert layers get the routers (in the layers' order) and ignore what they are handed."""
    order = {"attn": [l for l, w in enumerate(desc.cfg.sliding_window_layout) if not w], "swa": [l for l, w in enumerate(desc.cfg.sliding_window_layout) if w]}
    routers = jnp.zeros((desc.cfg.num_hidden_layers,) + params["attn"]["router"].shape[1:], params["attn"]["router"].dtype)
    for kind, layers in order.items():
        routers = routers.at[jnp.asarray(layers)].set(params[kind]["router"])
    seq, step = experts.moe_seq, experts.moe_step
    monkeypatch.setattr(experts, "moe_seq", lambda w, xn, lengths, c, stacked=None, routing=None: seq(w, xn, lengths, c, stacked))
    monkeypatch.setattr(experts, "moe_step", lambda w, xn, active, c, stacked, routing=None: step(w, xn, active, c, stacked))
    return battery.least_engine(desc.cfg, battery.in_kind(params, "moe", router=routers))


def _silu_gate(desc, params, eng, monkeypatch):
    real = st.SmallThinkerConfig.expert_layer
    monkeypatch.setattr(st.SmallThinkerConfig, "expert_layer", property(lambda self: dataclasses.replace(real.fget(self), act="swiglu")))
    return battery.least_engine(desc.cfg, params)


def _row_at_pos(real):
    """A decode step writes a ring entry's row at ``pos``, not ``pos mod W``: past the window the write lands nowhere."""
    return lambda arrays, per_position, i, lanes, pos, rings=frozenset(): real(arrays, per_position, i, lanes, pos)


def _first_rows(desc, params, eng, monkeypatch):
    """A prompt longer than the window inserted from its FIRST W positions, not its last: the engine's insertion is a program
    of its own between the prefill and the step (``eng._insert``), so the fault stands there, on the module's engine."""
    monkeypatch.setattr(eng, "_insert", jax.jit(lambda cache, slot, new, length: kvc.insert_entries(
        cache, slot, {n: a[:, :cache[n].shape[2]] for n, a in new.items()}, length)))
    return eng


def _ring_unmasked(real):
    """A young slot's ring read whole: the rows its sequence has not written yet are attended too."""
    def attend(q, k, v, layer, lengths, num_kv_heads, **kw):
        return real(q, k, v, layer, jnp.maximum(lengths, k.shape[2] - 1) if kw.get("name") == st.DECODE_KERNEL["swa"] else lengths, num_kv_heads, **kw)
    return attend


# float32 program against float32 reference: the same mathematics summed in another order (tiles of
# queries, the grouped matmul, a ring's rows in another order than the positions'). They agree to
# 1e-5 in a log-probability; what a wrong window, rotation, routing or ring row does is over 1e-3
DESC = battery.Description(
    family=family, c=C, cfg=CFG, tol=2e-4, agrees_to=1e-5,
    state_bytes_per_slot=0, kv_bytes_per_token=8 * 2 * (2 * 16) * 4,  # eight layers, a key and a value of 2 heads x 16, while a position is held
    poison={"k": jnp.nan, "v": 1e4, "k_w": jnp.nan, "v_w": 1e4},
    faults={"window_one_wider": battery.Fault(_another_window(+1)),
            "window_one_narrower": battery.Fault(_another_window(-1)),
            "global_layer_rotated": battery.Fault(_rotation(True)),
            "window_layer_not_rotated": battery.Fault(_rotation(False)),
            "routed_after_attention": battery.Fault(_routed_after_attention),
            "silu_for_relu": battery.Fault(_silu_gate),
            "ring_row_at_pos": battery.Fault(battery.patched(hybrid, "LayerCache", _row_at_pos)),
            "ring_from_the_first_rows": battery.Fault(_first_rows),
            "young_ring_not_masked": battery.Fault(battery.patched(sa, "attend", _ring_unmasked))},
    refusal_says=("its window layers keep k_w and v_w in a ring of the last 16 positions",),
    refusal_says_not=("recurrent", "c_kv"))


@pytest.fixture(scope="module")
def params():
    return battery.jiggled(jax.jit(lambda k: st.init_params(CFG, k))(jax.random.PRNGKey(7)))


# ------------------------------------------------------------------------------ the description
def test_the_description_is_a_period_of_eight_sub_blocks_and_keeps_a_ring_beside_rows():
    assert CFG.layer_kinds == ("attn", "moe", "swa", "moe", "swa", "moe", "swa", "moe") * 2
    assert CFG.layer_plan == hybrid.LayerPlan(period=("attn", "moe", "swa", "moe", "swa", "moe", "swa", "moe"), repeats=2, tail=(), head=())
    published = st.SmallThinkerConfig()
    assert published.layer_plan == hybrid.LayerPlan(CFG.layer_plan.period, 13, (), ()) and published.num_params() == 21_506_562_560
    assert (published.count("attn"), published.count("swa"), published.count("moe")) == (13, 39, 52)
    cut = dataclasses.replace(published, num_hidden_layers=8, sliding_window_layout=(0, 1, 1, 1) * 2, rope_layout=(0, 1, 1, 1) * 2, max_seq_len=12288)
    assert cut.layer_plan == CFG.layer_plan and cut.kinds_held == "2 x attn, 8 x moe, 6 x swa" and cut.num_params() == 3_966_937_600
    assert (cut.num_kv_layers, cut.routing_layers, cut.num_layers) == (6, 8, 16)
    assert {k: (m.scope, m.routes, m.hands) for k, m in cut.mixers.items()} == {
        "attn": ("attn", False, True), "swa": ("swa", False, True), "moe": ("moe", True, False)}
    s = cut.expert_layer
    assert (s.num_experts, s.held, s.top_k, s.score, s.bias, s.norm_topk, s.scale, s.act, s.shared) == (64, 64, 6, "softmax", False, True, 1.0, "reglu", False)
    assert s.matrices == ("w_gate", "w_up", "w_down") and cut.handed == {"experts": ((6,), "int32"), "weights": ((6,), "float32")}
    assert hybrid.trace_description().handed == {} and hybrid.trace_description().ring_entries() == {}
    kv = ((4, 128), "bfloat16", "position")
    assert cut.cache_spec() == {"attn": {"k": kv, "v": kv}, "swa": {"k_w": kv, "v_w": kv}, "moe": {}}
    assert cut.ring_entries() == {"k_w": 4096, "v_w": 4096}
    assert cut.position_entries() == {"k": (2, (4, 128), "bfloat16"), "v": (2, (4, 128), "bfloat16"), "k_w": (6, (4, 128), "bfloat16"), "v_w": (6, (4, 128), "bfloat16")}
    cache = jax.eval_shape(lambda: kvc.alloc_entries(cut.position_entries(), 16, 12288, cut.ring_entries()))
    assert cache["k"].shape == (2, 16, 12288, 4, 128) and cache["k_w"].shape == (6, 16, 4096, 4, 128)
    nbytes = sum(a.size * a.dtype.itemsize for n, a in cache.items() if n != "length")
    assert nbytes == 1_610_612_736 == family.cache_bytes(family_config(), 16, 12288)  # 0.81 GB of rows and 0.81 GB of rings; 3.22 GB with every position kept
    # a horizon under the window: the ring is the horizon, and never wraps
    assert jax.eval_shape(lambda: kvc.alloc_entries(cut.position_entries(), 2, 2048, cut.ring_entries()))["k_w"].shape == (6, 2, 2048, 4, 128)
    # the counters, from lengths alone: sum over positions of min(i + 1, W), and min(pos + 1, W) a lane
    assert cut.prefill_counters(2, 12288, lengths=[10500, 100]) == {"swa_pairs": 6 * ((4096 * 4097) // 2 + (10500 - 4096) * 4096 + 5050)}
    assert cut.prefill_counters(1, 12288, lengths=[10500])["swa_pairs"] // 6 == 34_621_440  # 63% of the 55.1 M a full layer reads
    assert cut.decode_counters([12000, 4096, 100]) == {"swa_rows_read": 6 * (4096 + 4096 + 100)}
    with pytest.raises(ValueError, match="rotates where it has a window"):
        dataclasses.replace(cut, rope_layout=(1,) * 8)


def family_config():
    import json
    import os

    from benchmark import common

    with open(os.path.join(common.ROOT, "benchmark", "configs", "smallthinker-21b-a3b-d8.json")) as f:
        return json.load(f)


def test_the_published_router_is_route_with_softmax_and_norm_topk():
    """softmax over the top k LOGITS (the published order, the reference's) equals a softmax over
    all experts renormalised over the chosen k (``experts.route``, the program's): the same sets,
    the same weights."""
    x = jax.random.normal(jax.random.PRNGKey(3), (200, CFG.hidden_size))
    router = jax.random.normal(jax.random.PRNGKey(4), (CFG.hidden_size, CFG.n_routed_experts))
    idx, wt = experts.route({"router": router}, x, CFG)
    top, want = jax.lax.top_k(jnp.dot(x, router, precision=jax.lax.Precision.HIGHEST), CFG.num_experts_per_tok)
    assert (np.asarray(idx) == np.asarray(want)).all()
    np.testing.assert_allclose(wt, jax.nn.softmax(top, axis=-1), atol=1e-6)
    np.testing.assert_allclose(jnp.sum(wt, axis=-1), 1.0, atol=1e-6)


def test_the_routing_is_made_on_the_attention_sub_blocks_input_and_handed_on(params):
    """An attention sub-block hands on the routing of ITS normed input, ``N_1(x)``: the one the
    reference makes before attention, and not the one the expert sub-block's own input ``N_2(x')``
    would give under the same router. (That the loops carry it to the expert sub-block is what
    the planted fault ``routed_after_attention`` and every comparison with the reference hold.)"""
    toks = np.asarray(battery.prompts(DESC, 9, (29,)), np.int32)
    w = jax.tree.map(lambda a: a[0], params["attn"])
    x = hybrid.embed_tokens(params, jnp.asarray(toks), CFG)
    y, kept, handed = CFG.mixers["attn"].seq(w, CFG.norm(x, w["norm"]), hybrid.SeqCtx(jnp.asarray([29]), None, None))
    assert set(kept) == {"k", "v"} and handed["experts"].shape == (1, 29, 2) and handed["weights"].dtype == jnp.float32
    choices = []
    family.hidden_states(params, toks[0], C, choices)
    assert (np.sort(np.asarray(handed["experts"][0]), -1) == np.sort(np.asarray(choices[0]), -1)).all()
    u = CFG.norm(hybrid.add_branch(x, y, CFG), params["moe"]["norm"][0])
    own = experts.route(w, u.reshape(29, -1), CFG)[0]
    assert (np.sort(np.asarray(own), -1) != np.sort(np.asarray(choices[0]), -1)).any(), "the two routings never differ: the test shows nothing"


# ------------------------------------------------------------------------------ the ring
@pytest.mark.parametrize("length", [5, 16, 17, 40, 64])
def test_a_prompt_goes_into_the_ring_by_its_last_positions_each_at_its_row(length):
    """``insert_entries`` with a ring of 16 rows under a bucket of 64: position p of the last
    min(length, 16) lies at row p mod 16; an entry that spans the horizon is written from row 0."""
    cache = kvc.alloc_entries({"k": (1, (1,), "float32"), "k_w": (2, (1,), "float32")}, 3, 128, {"k_w": 16})
    new = {"k": jnp.arange(64, dtype=jnp.float32).reshape(1, 64, 1), "k_w": jnp.stack([jnp.arange(64.0), 100 + jnp.arange(64.0)]).reshape(2, 64, 1)}
    out = jax.jit(kvc.insert_entries, static_argnames="rings")(cache, 1, new, length, rings=frozenset({"k_w"}))
    assert int(out["length"][1]) == length and (np.asarray(out["k"][0, 1, :64, 0]) == np.arange(64)).all()
    ring = np.asarray(out["k_w"][:, 1, :, 0])
    for p in range(max(length - 16, 0), length):
        assert ring[0, p % 16] == p and ring[1, p % 16] == 100 + p
    assert not np.asarray(out["k_w"][:, 0]).any() and not np.asarray(out["k_w"][:, 2]).any()
    # a bucket no longer than the ring: written as it stands
    short = jax.jit(kvc.insert_entries, static_argnames="rings")(cache, 2, {"k_w": new["k_w"][:, :16]}, min(length, 16), rings=frozenset({"k_w"}))
    assert (np.asarray(short["k_w"][0, 2, :, 0]) == np.arange(16)).all()


def test_one_slot_serves_a_long_sequence_then_shorter_ones_across_the_rings_wrap(params):
    """ONE slot, so every sequence after the first lives in rows the last one left: a prompt of
    61 (its ring wrapped three times over) and 30 decoded tokens, then prompts under, at and over
    the window, each decoding across a wrap (or up to it), against the reference's full forward."""
    eng = battery.engine(CFG, params, max_num_seqs=1)
    ps = battery.prompts(DESC, 12, (61, 9, 16, 15, 17, 33))
    sp = [SamplingParams(max_tokens=30 if i == 0 else 12, temperature=0.0, logprobs=True) for i in range(len(ps))]
    mark = eng.telemetry()["step_count"]
    res = battery.check(DESC, params, battery.served(eng.generate(ps, sp), ps, sp))
    assert res["ok"] and res["tokens"] == 30 + 5 * 12 and res["max_abs_dlogprob"] < DESC.agrees_to, res
    # the flight log's counters equal the family's count from the same lengths
    rows = battery.steps_after(eng, mark)
    admitting = [r for r in rows if r.get("admitted")]
    assert sum(r["swa_pairs"] for r in admitting) == 6 * sum(family.window_pairs(C, len(p)) for p in ps)
    reads = [r["swa_rows_read"] for r in rows if "swa_rows_read" in r]
    assert reads and all(0 < n <= 6 * W for n in reads) and max(reads) == 6 * W and min(reads) == 6 * 10  # the prompt of 9 and its first token
    assert not any("swa_pairs" in r for r in rows if not r.get("admitted"))


# ------------------------------------------------------------------------------ the kernels
@pytest.mark.parametrize("T, window, blocks", [(128, 32, 32), (128, 48, 32), (100, 32, 100), (96, 100, 32), (128, 1, 64)])
def test_the_flash_kernel_with_a_window_equals_the_xla_form_with_the_mask(T, window, blocks):
    """The forward kernel, interpreted, at a length of whole tiles and at a ragged one (100, which
    goes as one tile: the kernel's last partial tile reads padding, with or without a window, so
    its callers pad to whole tiles as the engine's buckets do), windows on and off the tiles, a
    window wider than the sequence and a window of the query's own key: against ``attention_xla``
    with the mask, and that against the definition."""
    from jax.experimental.pallas import tpu as pltpu

    q, k, v = (jax.random.normal(jax.random.PRNGKey(n), (1, 1, T, 128), jnp.float32) for n in (1, 2, 3))
    ref = fa.attention_xla(q, k, v, causal=True, window=window)
    i, j = np.arange(T)[:, None], np.arange(T)[None, :]
    s = np.where((j <= i) & (j > i - window), np.einsum("bhqd,bhkd->bhqk", q, k) * 128 ** -0.5, -np.inf)
    np.testing.assert_allclose(ref, np.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v), atol=2e-5)
    with pltpu.force_tpu_interpret_mode():
        out, lse = fa._fwd_pallas(q, k, v, causal=True, window=window, block_q=blocks, block_k=blocks)
    np.testing.assert_allclose(out, ref, atol=2e-3)
    np.testing.assert_allclose(lse, jax.nn.logsumexp(s, axis=-1), atol=2e-3)
    # through the op: the XLA pass off the TPU, with its backward pass; no window is the kernel as it was
    got = fa.flash_attention(q, k, v, True, None, "xla", window)
    np.testing.assert_allclose(got, ref, atol=2e-5)
    g = jax.grad(lambda q: fa.flash_attention(q, k, v, True, None, "xla", window).sum())(q)
    np.testing.assert_allclose(g, jax.grad(lambda q: fa.attention_xla(q, k, v, causal=True, window=window).sum())(q), atol=2e-4)
    np.testing.assert_allclose(fa.flash_attention(q, k, v, True, None, "xla", None), fa.attention_xla(q, k, v, causal=True), atol=2e-5)


def test_the_decode_kernels_interpreted_serve_what_the_xla_forms_serve(params, monkeypatch):
    """Off the TPU the gates refuse; swapped open, the live-block kernel reads the rows of every
    position AND a ring's (6 query heads over 2 go as 16 rows, a group of 3 as 8), and the step
    kernel reads the ReGLU experts hit: through the engine, against the reference."""
    from ray_tpu.ops import step_experts

    assert sa.padded_heads(6, 2) == 16 and sa.padded_heads(28, 4) == 32 and sa.padded_heads(32, 2) == 32 and sa.padded_heads(16, 8) == 16
    monkeypatch.setattr(sa, "refusal", lambda *a, **kw: None)
    monkeypatch.setattr(step_experts, "refusal", lambda *a: None)
    names = []
    launch = sa._launch
    monkeypatch.setattr(sa, "_launch", lambda kernel, name, *a: names.append(name) or launch(kernel, name, *a))
    ps = battery.prompts(DESC, 24, (50, 13, 9))
    sp = [SamplingParams(max_tokens=8, temperature=0.0, logprobs=True)] * 3
    eng = battery.engine(CFG, params, prefill_buckets=(64,))  # the kernels are the step's: one prefill program serves the three prompts
    res = battery.check(DESC, params, battery.served(eng.generate(ps, sp), ps, sp))
    assert res["ok"] and res["tokens"] == 24 and res["max_abs_dlogprob"] < DESC.agrees_to, res
    assert set(names) == {"slot_decode_attention", st.DECODE_KERNEL["swa"]}


def test_the_gate_lets_28_heads_over_4_through_and_says_why_not_by_name(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert sa.refusal(jnp.bfloat16, 28, 4, 128, 12288) is None and sa.refusal(jnp.bfloat16, 28, 4, 128, 4096) is None
    assert sa.refusal(jnp.bfloat16, 16, 8, 128, 4096) is None and sa.refusal(jnp.bfloat16, 32, 2, 128, 12288) is None
    assert sa.refusal(jnp.bfloat16, 20, 4, 128, 4096) is None  # groups of 5 go as 8: the 32 rows over 4 that 28 take
    assert sa.refusal(jnp.bfloat16, 48, 8, 128, 12288) is None and sa.refusal(jnp.bfloat16, 40, 8, 128, 4096) is None  # three tiles (PR 64): groups of 6, and of 5 as 6
    for heads, kv in ((8, 8), (12, 4), (56, 8)):  # under one tile of 16 rows (padding is for a group's rows, not for a small head count); over three
        assert f"{heads} query heads over {kv} kv heads" in sa.refusal(jnp.bfloat16, heads, kv, 128, 4096)
