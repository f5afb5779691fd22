"""A third description over the one layer loop (``models/glm4_moe_lite.py``: latent attention in
every layer, one dense layer, sigmoid-routed experts) through the engine, against the plain
reference of ``benchmark/families/glm4_moe_lite.py`` (the EXPANDED form only, float32, written from
the published equations): logits, not tokens. The two forms of the one attention, prefill's
expanded and decode's absorbed, against each other position by position; the latent kernel, run by
the Pallas interpreter, against the XLA oracle; the layer plan with a head before its period; the
slot cache allocated from ``cache_spec()`` for all three descriptions. Toy widths, float32."""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hybrid_battery as battery
from benchmark.families import glm4_moe_lite as family
from hybrid_battery import *  # noqa: F401,F403 - the tests every description is held to, collected here against DESC
from hybrid_battery import engine
from hybrid_battery import test_the_grouped_matmul_places_the_pairs_held_here_whatever_their_number  # noqa: F401 - it routes experts
from ray_tpu.llm import SamplingParams
from ray_tpu.llm import hybrid_runner as hr
from ray_tpu.llm import kv_cache as kvc
from ray_tpu.models import glm4_moe_lite as glm
from ray_tpu.models import hybrid
from ray_tpu.ops import slot_attention as sa

# the configuration file's side of the toy model: the published keys the family reads, at toy sizes
C = family.rehearsal({"rope_theta": 1000000, "n_shared_experts": 1, "norm_topk_prob": True, "routed_scaling_factor": 1.8,
                      "rms_norm_eps": 1e-5, "family": "glm4_moe_lite"})
CFG = family.program_config(C, 128, remat=False)


def _a_wrong_cache(fault):
    """The mistakes a latent cache invites: the cached key kept before its rotation, the latent kept
    before its norm, and the latent rounded to bfloat16 (the nearest precision below this test's
    float32) on its way into the cache."""
    def wrap(real):
        def down(w, xn, positions, c):
            c_q, c_kv, k_r, rope = real(w, xn, positions, c)
            if fault == "key_not_rotated":
                k_r = jnp.pad(jnp.dot(xn, w["w_kva"])[..., c.kv_lora_rank:], ((0, 0), (0, 0), (0, c.rope_row - c.qk_rope_head_dim)))
            elif fault == "latent_not_normed":
                c_kv = jnp.dot(xn, w["w_kva"])[..., :c.kv_lora_rank]
            else:
                c_kv = c_kv.astype(jnp.bfloat16).astype(c_kv.dtype)
            return c_q, c_kv, k_r, rope
        return down
    return battery.patched(glm, "mla_down", wrap)


# float32 program against float32 reference: the same mathematics summed in another order (the
# absorbed products, the grouped matmul, blocks of queries). They agree to 2e-6 in a
# log-probability; what a wrong rotation, a missed position or a cache row of another slot does
# is over 1e-2 (the faults below)
DESC = battery.Description(
    family=family, c=C, cfg=CFG, tol=1e-4, agrees_to=2e-5,
    state_bytes_per_slot=0, kv_bytes_per_token=3 * (32 + 128) * 4,  # the rotated key in whole lane tiles: 128, not the published 4
    # the latent is a value too, and a masked position still multiplies its value by zero in the XLA form: large, not NaN
    poison={"c_kv": 1e4, "k_r": jnp.nan},
    faults={"key_not_rotated": battery.Fault(_a_wrong_cache("key_not_rotated"), over=100, margin=True),
            "latent_not_normed": battery.Fault(_a_wrong_cache("latent_not_normed"), over=100, margin=True),
            "bfloat16_latent": battery.Fault(_a_wrong_cache("bfloat16_latent"), over=3, margin=True)},
    refusal_says=("keep c_kv and k_r per position",), refusal_says_not=("recurrent",))


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: glm.init_params(CFG, k))(jax.random.PRNGKey(11))


# ------------------------------------------------------------------------------ the description
def test_the_description_is_a_dense_layer_before_the_period_and_the_plan_has_a_head():
    assert CFG.layer_kinds == ("mla", "ffn", "mla", "moe", "mla", "moe")
    assert CFG.layer_plan == hybrid.LayerPlan(period=("mla", "moe"), repeats=2, tail=(), head=("mla", "ffn"))
    published = glm.Glm4MoeLiteConfig()
    assert published.layer_plan == (("mla", "moe"), 46, (), ("mla", "ffn")) and published.num_params() == 29_943_393_920
    cut = dataclasses.replace(published, num_hidden_layers=8)
    assert cut.layer_plan == (("mla", "moe"), 7, (), ("mla", "ffn")) and cut.kinds_held == "8 x mla, 1 x ffn, 7 x moe"
    assert (cut.num_kv_layers, cut.routing_layers, cut.num_layers, cut.num_params()) == (8, 7, 16, 5_166_248_384)
    assert {k: m.scope for k, m in cut.mixers.items()} == {"mla": "mla", "ffn": "ffn", "moe": "moe"}
    two_dense = dataclasses.replace(published, num_hidden_layers=6, first_k_dense_replace=2)
    assert two_dense.layer_plan == (("mla", "moe"), 4, (), ("mla", "ffn", "mla", "ffn"))
    s = cut.expert_layer
    assert (s.num_experts, s.held, s.top_k, s.score, s.bias, s.norm_topk, s.scale, s.act, s.shared_gated) == (
        64, 64, 4, "sigmoid", True, True, 1.8, "swiglu", False)


@pytest.mark.parametrize("pattern, plan", [
    # the two descriptions that stand: a period from the first layer on, no head
    ("MEMEM*EMEMEM*EME", ("MEMEM*E", 2, "ME", "")),
    ("ME*ME*ME", ("ME*", 2, "ME", "")),
    ("M*E", ("", 0, "M*E", "")),
    # a head AND a tail around the period; the stretch that covers most layers wins
    ("*MEMEMEM", ("ME", 3, "M", "*")),
    ("**MEMEME*", ("ME", 3, "*", "**")),
    ("MMEEEEEE", ("E", 6, "", "MM")),
    ("MEMEM", ("ME", 2, "M", "")),
])
def test_layer_plan_finds_the_stretch_that_repeats_with_what_stands_before_and_after_it(pattern, plan):
    from ray_tpu.models import nemotron_h as nh

    short = {"mamba": "M", "moe": "E", "attn": "*"}
    got = nh.NemotronHConfig.tiny(layer_pattern=pattern).layer_plan
    assert ("".join(short[k] for k in got.period), got.repeats, "".join(short[k] for k in got.tail),
            "".join(short[k] for k in got.head)) == plan
    assert got.head + got.period * got.repeats + got.tail == nh.NemotronHConfig.tiny(layer_pattern=pattern).layer_kinds


def test_run_layers_walks_head_period_and_tail_in_order_with_each_kinds_own_index():
    """The decode loop over ``* ME ME ME M``: every layer once, in order, the layers of a kind
    numbered through head, period and tail."""
    from ray_tpu.models import nemotron_h as nh

    cfg = nh.NemotronHConfig.tiny(layer_pattern="*MEMEMEM")
    params = {k: {"id": jnp.arange(cfg.count(k), dtype=jnp.int32)} for k in ("mamba", "moe", "attn")}
    code = {"attn": 0, "mamba": 1, "moe": 2}

    def walk(kind, w, i, x, carry):
        return x + 1, carry.at[x[0]].set(10 * code[kind] + w["id"])

    _, seen = jax.jit(lambda: hybrid.run_layers(cfg, params, jnp.zeros((1,), jnp.int32), jnp.full((8,), -1, jnp.int32), walk))()
    assert list(np.asarray(seen)) == [0, 10, 20, 11, 21, 12, 22, 13]


def test_the_slot_cache_is_allocated_from_cache_spec_for_all_three_descriptions():
    from ray_tpu.models import nemotron_h as nh
    from ray_tpu.models import qwen3_next as qn

    for cfg, want in [
        (nh.NemotronHConfig.tiny(), {"k": (2, (2, 16)), "v": (2, (2, 16))}),
        (qn.Qwen3NextConfig.tiny(), {"k": (2, (2, 16)), "v": (2, (2, 16))}),
        (CFG, {"c_kv": (3, (32,)), "k_r": (3, (128,))}),
    ]:
        entries = cfg.position_entries()
        assert {n: (layers, shape) for n, (layers, shape, _) in entries.items()} == want, type(cfg).__name__
        cache = kvc.alloc_entries(entries, 4, 64)
        assert set(cache) == set(want) | {"length"} and cache["length"].shape == (4,)
        assert all(cache[n].shape == (layers, 4, 64) + shape and cache[n].dtype == jnp.float32 for n, (layers, shape) in want.items())
        assert kvc.entry_bytes_per_token(entries) == sum(layers * int(np.prod(shape)) * 4 for layers, shape in want.values())
        assert cfg.num_kv_layers == max(layers for layers, _ in want.values())
        # one position's rows of one sequence land in their slot, and nowhere else
        new = {n: jnp.ones((layers, 16) + shape, jnp.float32) for n, (layers, shape) in want.items()}
        cache = jax.jit(kvc.insert_entries)(cache, 2, new, 9)
        assert list(np.asarray(cache["length"])) == [0, 0, 9, 0]
        assert all(float(cache[n][:, 2, :16].min()) == 1.0 and float(jnp.abs(cache[n]).sum()) == new[n].size for n in want)
    # ``k`` and ``v`` by head are the case of two entries: the old call is the new one
    old = kvc.alloc(kvc.CacheConfig(num_layers=2, num_slots=4, max_seq_len=64, num_kv_heads=2, head_dim=16, dtype="float32"))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), old) == jax.tree.map(
        lambda a: (a.shape, a.dtype), kvc.alloc_entries(nh.NemotronHConfig.tiny().position_entries(), 4, 64))


# ------------------------------------------------------------------ the program against the reference
def test_the_counts_are_the_programs(params):
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    assert n == CFG.num_params() == family.parameters_held(C)


def test_the_absorbed_step_equals_the_expanded_sequence_form_position_by_position(params):
    """One token at a time through ``decode_step`` from an empty latent cache (the absorbed form:
    queries folded into the latent, every head on the same cached row) against the sequence forward
    (the expanded form: keys and values of every head), at every position of two sequences of
    different lengths, one lane left unbound."""
    T = 24
    toks = np.asarray(battery.prompts(DESC, 4, (T, T)), np.int32)
    want = np.asarray(hybrid.forward(params, jnp.asarray(toks), CFG))  # [2, T, V]
    cache = kvc.alloc_entries(CFG.position_entries(), 3, 32)
    step = jax.jit(partial(hr.decode_step, cfg=CFG))
    active = jnp.asarray([True, False, True])
    for t in range(T):
        logits, cache, _, moe = step(params, cache, {}, jnp.asarray([toks[0, t], 0, toks[1, t]], jnp.int32), active)
        np.testing.assert_allclose(np.asarray(logits)[[0, 2]], want[:, t], atol=3e-5, rtol=0, err_msg=f"position {t}")
        assert float(moe[2]) == 2 * CFG.num_experts_per_tok  # two lanes' choices, the unbound lane kept out
    assert list(np.asarray(cache["length"])) == [T, T, T]
    # what the step wrote is what the prefill hands the cache: the normed latent and the rotated key, zeros after it
    _, rows, _ = jax.jit(partial(hr.prefill, cfg=CFG))(params, jnp.asarray(toks), jnp.asarray([T, T], jnp.int32))
    for name in ("c_kv", "k_r"):
        np.testing.assert_allclose(np.asarray(cache[name])[:, [0, 2], :T], np.asarray(rows[name]), atol=2e-6, rtol=0, err_msg=name)
    assert not np.asarray(rows["k_r"])[..., CFG.qk_rope_head_dim:].any() and np.asarray(rows["k_r"])[..., :CFG.qk_rope_head_dim].any()


def test_the_latent_cache_is_counted_as_the_chip_stores_it(eng):
    """What the family counts is the latent and the key as published; the chip stores the key in whole lane tiles."""
    stats = eng.kv_cache_stats()
    assert stats["bytes_per_token"] == 3 * (32 + 128) * 4 and stats["allocated_bytes"] == 4 * 128 * stats["bytes_per_token"]
    assert stats["entries"] == {"c_kv": [3, [32], "float32"], "k_r": [3, [128], "float32"]}
    assert (stats["state_bytes_per_slot"], stats["state_allocated_bytes"]) == (0, 0) and eng.state == {} and set(eng.cache) == {"c_kv", "k_r", "length"}
    assert family.kv_bytes_per_token(C, itemsize=4) == 3 * (32 + 4) * 4 < stats["bytes_per_token"]


def test_a_sigmoid_router_with_its_bias_chooses_as_the_reference_does(params):
    """The expert layer is configured, not copied: the top k of score + correction bias, weighted
    by the scores themselves. With a bias that matters (random, as large as the scores' spread) the
    program still chooses what the reference chooses and agrees with it."""
    biased = {**params, "moe": {**params["moe"], "router_bias": 0.5 * jax.random.normal(jax.random.PRNGKey(2), params["moe"]["router_bias"].shape)}}
    toks = battery.prompts(DESC, 9, (40,))[0]
    choices = []
    family.hidden_states(biased, toks + [0] * (-len(toks) % family.PAD_TO), C, choices)
    plain = []
    family.hidden_states(params, toks + [0] * (-len(toks) % family.PAD_TO), C, plain)
    assert any((np.asarray(a)[:40] != np.asarray(b)[:40]).any() for a, b in zip(choices, plain)), "the bias moves the choice"
    logits = hybrid.forward(biased, jnp.asarray([toks], jnp.int32), CFG)
    want = family.reference_logprobs(biased, toks, C, 0, len(toks))
    np.testing.assert_allclose(np.asarray(jax.nn.log_softmax(logits[0], axis=-1)), np.asarray(want), atol=2e-5, rtol=0)


# ------------------------------------------------------------------------------ the latent kernel
S, BLK, L, R, ROPE, NH = 64, 16, 3, 128, 128, 20
LENGTHS = {"zero": (0, 0), "one": (1, 1), "block_less_one": (BLK - 2, BLK - 2), "a_block": (BLK - 1, BLK - 1),
           "a_block_plus_one": (BLK, BLK), "the_horizon": (S - 1, S - 1), "mixed": (0, 3 * BLK, BLK - 1, S - 1, BLK, 7)}


def _latent_inputs(B, dtype=jnp.bfloat16, seed=0):
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed), 4)
    q_lat = jax.random.normal(k1, (B, NH, R), jnp.float32).astype(dtype)
    q_rope = jax.random.normal(k2, (B, NH, ROPE), jnp.float32).astype(dtype)
    c = jax.random.normal(k3, (L, B, S, R), jnp.float32).astype(dtype)
    r = jax.random.normal(k4, (L, B, S, ROPE), jnp.float32).astype(dtype)
    return q_lat, q_rope, c, r


def _oracle(q_lat, q_rope, c, r, layer, lens, scale):
    q = jnp.concatenate([q_lat, q_rope], axis=-1)
    return sa.attend_rows(q, jnp.concatenate([c[layer], r[layer]], axis=-1)[:, :, None], c[layer][:, :, None], lens, 1, scale)


@pytest.mark.parametrize("lengths", list(LENGTHS))
def test_the_latent_kernel_equals_the_xla_oracle(lengths):
    """Twenty query heads (padded to 32 rows inside) on one row a position that is key and, in its
    latent part, value: the kernel, interpreted, against ``attend_rows`` with keys wider than
    values, at every placing of a lane's bound against the blocks and with a lane at the horizon."""
    lens = jnp.asarray(LENGTHS[lengths], jnp.int32)
    q_lat, q_rope, c, r = _latent_inputs(len(lens))
    for layer in (0, L - 1):
        want = _oracle(q_lat, q_rope, c, r, layer, lens, 0.07)
        got = sa.attend_latent_kernel(q_lat, q_rope, c, r, jnp.int32(layer), lens + 1, 0.07, block=BLK, interpret=True)
        assert got.shape == (len(lens), NH * R)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-6, rtol=3e-6)


def test_the_latent_kernel_reads_no_dead_block_and_nothing_for_an_unbound_lane():
    lens = jnp.asarray(LENGTHS["mixed"], jnp.int32)
    live = jnp.asarray([True, True, False, True, False, True])
    q_lat, q_rope, c, r = _latent_inputs(len(lens), seed=1)
    bound = jnp.where(live, lens + 1, 0)
    first_dead = -(-bound // BLK) * BLK
    dead = (jnp.arange(S)[None, :] >= first_dead[:, None])[None, :, :, None]
    run = partial(sa.attend_latent_kernel, block=BLK, interpret=True)
    clean = run(q_lat, q_rope, c, r, 1, bound, 0.07)
    poisoned = run(q_lat, q_rope, jnp.where(dead, jnp.nan, c), jnp.where(dead, jnp.nan, r), 1, bound, 0.07)
    np.testing.assert_array_equal(np.asarray(poisoned), np.asarray(clean))
    assert not np.asarray(clean)[~np.asarray(live)].any() and np.isfinite(np.asarray(clean)).all()
    want = _oracle(q_lat, q_rope, c, r, 1, lens, 0.07)
    np.testing.assert_allclose(np.asarray(clean)[np.asarray(live)], np.asarray(want)[np.asarray(live)], atol=3e-6, rtol=3e-6)


def test_the_latent_kernel_on_float32_rows_with_a_traced_layer_and_the_op_that_chooses(monkeypatch):
    lens = jnp.asarray(LENGTHS["mixed"], jnp.int32)
    q_lat, q_rope, c, r = _latent_inputs(len(lens), dtype=jnp.float32, seed=2)
    got = jax.jit(lambda i: sa.attend_latent_kernel(q_lat, q_rope, c, r, i, lens + 1, 0.07, block=BLK, interpret=True))(jnp.int32(2))
    np.testing.assert_allclose(np.asarray(got), np.asarray(_oracle(q_lat, q_rope, c, r, 2, lens, 0.07)), atol=3e-6, rtol=3e-6)
    # the op: off the TPU the oracle (a narrower rotated query is padded to the cached key's width) ...
    narrow = q_rope[..., :64]
    want = _oracle(q_lat, jnp.pad(narrow, ((0, 0), (0, 0), (0, 64))), c, r, 2, lens, 0.07)
    np.testing.assert_array_equal(np.asarray(sa.attend_latent(q_lat, narrow, c, r, 2, lens, scale=0.07)), np.asarray(want))
    # ... and where the gate lets the tile through, the kernel, with the unbound lanes reading nothing
    monkeypatch.setattr(sa, "refusal", lambda *a, **k: None)
    live = jnp.asarray([True, False, True, True, False, True])
    got = sa.attend_latent(q_lat, narrow, c, r, 2, lens, scale=0.07, live=live)
    np.testing.assert_allclose(np.asarray(got)[np.asarray(live)], np.asarray(want)[np.asarray(live)], atol=3e-6, rtol=3e-6)
    assert not np.asarray(got)[~np.asarray(live)].any()


@pytest.mark.parametrize("args, kw, word", [
    ((jnp.bfloat16, 20, 1, 640, 16384), {"value_dim": 512, "sharded": True}, "shard_map"),
    ((jnp.int8, 20, 1, 640, 16384), {"value_dim": 512, "quantized": True}, "int8"),
    ((jnp.float32, 20, 1, 640, 16384), {"value_dim": 512}, "float32"),
    ((jnp.bfloat16, 20, 1, 576, 16384), {"value_dim": 512}, "512 + 64"),
    ((jnp.bfloat16, 20, 2, 640, 16384), {"value_dim": 512}, "2 kv head"),
    ((jnp.bfloat16, 20, 1, 896, 16384), {"value_dim": 768}, "768 + 128"),
    ((jnp.bfloat16, 48, 1, 640, 16384), {"value_dim": 512}, "48 query heads"),
    ((jnp.bfloat16, 20, 1, 640, 1000), {"value_dim": 512}, "1000 positions"),
])
def test_the_gate_refuses_a_latent_tile_with_a_reason_and_lets_the_compiled_one_through(monkeypatch, args, kw, word):
    assert "backend 'cpu'" in sa.refusal(*args, **kw)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert word in sa.refusal(*args, **kw)
    assert sa.refusal(jnp.bfloat16, 20, 1, 640, 16384, value_dim=512) is None
    assert sa.refusal(jnp.bfloat16, 16, 8, 128, 4096) is None and sa.refusal(jnp.bfloat16, 32, 2, 128, 4096) is None, "PR 35's tiles"


def test_the_engine_runs_the_latent_kernel_where_the_gate_allows_and_counts_its_blocks(params, monkeypatch):
    """More requests than slots with the kernel forced on (interpreted): the same greedy tokens as
    the XLA form, the stacked entries handed over whole, and the flight log's step rows carry the
    blocks the step reads and the blocks the cache holds, over the layers that keep a latent."""
    ps = battery.prompts(DESC, 7, (5, 21, 9, 14, 3))
    sp = [SamplingParams(max_tokens=8, temperature=0.0) for _ in ps]
    kw = dict(max_num_seqs=2, max_seq_len=128, prefill_buckets=(16, 32))
    plain = engine(CFG, params, **kw)
    assert plain._attn_block is None
    want = [o.token_ids for o in plain.generate(ps, sp)]
    calls, real = [], sa.attend_latent_kernel
    monkeypatch.setattr(sa, "attend_latent_kernel", lambda *a, **k: (calls.append((a[2].shape, a[3].shape)), real(*a, **k))[1])
    monkeypatch.setattr(sa, "refusal", lambda *a, **k: None)
    eng = engine(CFG, params, **kw)
    assert eng._attn_block == 128, "one block of 128 positions a lane at this toy size"
    assert [o.token_ids for o in eng.generate(ps, sp)] == want
    assert calls and all(shapes == ((3, 2, 128, 32), (3, 2, 128, 128)) for shapes in calls), "the stacked entries, not a layer's rows"
    rows = [s for s in eng.telemetry()["steps"] if s.get("attn_blocks_total")]
    assert rows and all(r["attn_blocks_total"] == 2 * 1 * 3 and 3 <= r["attn_blocks_read"] <= 6 for r in rows)


def test_a_wave_that_does_not_fit_the_devices_free_memory_goes_through_in_several_programs(params):
    """The expanded form holds every head's keys and values for every position of a prefill, so
    the admission wave that fits the free slots need not fit the memory beside weights and cache.
    The ENGINE bounds one prefill program by what the device has free and by the compiler's own
    account of the shapes that have run (no constant of this description): a same-bucket wave
    beyond it goes through in runs of a power of two of prompts, and serves what it would have."""
    ps = battery.prompts(DESC, 12, (20, 30, 25, 31, 19, 60, 40))  # five in the 32 bucket, two in the 64 bucket
    sp = [SamplingParams(max_tokens=6, temperature=0.0, logprobs=True)] * len(ps)
    eng = engine(CFG, params, max_num_seqs=8)
    assert eng._prefill_room is None and "prefill_room_bytes" not in eng.kv_cache_stats(), "the CPU keeps no account: no bound"
    want = [o.token_ids for o in eng.generate(ps, sp)]
    assert eng._prefill_need == {}
    eng._prefill_room = 1 << 60  # a device that keeps an account, first with room for anything: the warm-up
    eng.generate(ps[:2], sp[:2])
    assert set(eng._prefill_need) == {(2, 32)} and eng._prefill_need[2, 32] > 2 * 32 * 4 * CFG.hidden_size
    eng._prefill_room = eng._prefill_need[2, 32]  # then with room for that program and no more
    runs, real = [], eng._admit_prefill_batch
    eng._admit_prefill_batch = lambda group: (runs.append(len(group)), real(group))[1]
    outs = eng.generate(ps, sp)
    assert [o.token_ids for o in outs] == want and battery.check(DESC, params, battery.served(outs, ps, sp))["ok"]
    assert sorted(runs) == [1, 1, 1, 2, 2] and set(eng._prefill_need) == {(1, 32), (2, 32), (1, 64)}, "two of 32, one of 64"
    stats = eng.kv_cache_stats()
    assert stats["prefill_room_bytes"] == eng._prefill_room and set(stats["prefill_program_bytes"]) == {"1x32", "2x32", "1x64"}
