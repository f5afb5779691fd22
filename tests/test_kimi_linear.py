"""A fourth description over the one layer loop (``models/kimi_linear.py``: Kimi Delta Attention
three layers in four, latent attention without position the fourth, one dense layer, sigmoid-routed
experts of which this chip holds a share) through the engine, against the plain reference of
``benchmark/families/kimi_linear.py`` (the delta rule one position at a time, the attention in its
expanded form, float32, written from the published equations): logits, not tokens. What is its own:
the chunked rule with a gate by key channel against the recurrence at the strongest gates, the
scalar gate's path left as it was, a state cache BESIDE a latent slot cache from ``cache_spec()``,
the step through both against the sequence form, the latent kernel at 32 heads. Toy widths, float32."""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hybrid_battery as battery
from benchmark.families import kimi_linear as family
from hybrid_battery import *  # noqa: F401,F403 - the tests every description is held to, collected here against DESC
from hybrid_battery import test_the_chips_shares_add_up_to_the_uncut_expert_layer  # noqa: F401 - chip 0 of four
from hybrid_battery import test_the_grouped_matmul_places_the_pairs_held_here_whatever_their_number  # noqa: F401 - it routes experts
from ray_tpu.llm import SamplingParams, state_cache
from ray_tpu.llm import hybrid_runner as hr
from ray_tpu.llm import kv_cache as kvc
from ray_tpu.models import glm4_moe_lite as glm
from ray_tpu.models import hybrid
from ray_tpu.models import kimi_linear as kl
from ray_tpu.models import qwen3_next as qn
from ray_tpu.ops import delta_rule
from ray_tpu.ops import slot_attention as sa
from ray_tpu.ops.layers import apply_rope, rotary_embedding

# the configuration file's side of the toy model: chip 0 of two, experts 0-3 of 8; a dense layer, then M E K E twice
C = family.rehearsal({"rms_norm_eps": 1e-5, "mla_use_nope": True, "rope_theta": 10000, "num_shared_experts": 1, "moe_renormalize": True,
                      "routed_scaling_factor": 2.446, "q_lora_rank": None, "moe_router_activation_func": "sigmoid", "family": "kimi_linear"})
CFG = family.program_config(C, 128, remat=False)


def _a_gate_a_head(real):
    """The forget gate applied as the mean over a head's key channels: Gated DeltaNet's gate, not KDA's."""
    def inputs(w, conv, low, c):
        q, k, v, beta, g = real(w, conv, low, c)
        return q, k, v, beta, jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    return inputs


def _a_rotated_shared_key(real):
    """The shared key rotated by its position, as every other latent attention does: these layers carry none."""
    def down(w, xn, positions, c):
        c_q, c_kv, k_r, rope = real(w, xn, positions, c)
        cos, sin = rotary_embedding(positions, c.qk_rope_head_dim, c.rope_theta)
        turned = apply_rope(k_r[..., None, :, :c.qk_rope_head_dim], cos, sin)[..., 0, :, :]
        return c_q, c_kv, jnp.pad(turned, ((0, 0), (0, 0), (0, c.rope_row - c.qk_rope_head_dim))), rope
    return down


def _rows_past_the_length(real):
    """Every lane attends three rows past its new token: what the slot held before, or nothing yet."""
    return lambda q_lat, q_rope, c_stack, r_stack, layer, lengths, **kw: real(q_lat, q_rope, c_stack, r_stack, layer, lengths + 3, **kw)


# float32 program against float32 reference: the same mathematics summed in another order (chunks of
# the rule, the absorbed products, the grouped matmul). They agree to 1e-5 in a log-probability;
# what breaks a state, a gate or a cache row is far over 1e-3 (the faults below)
DESC = battery.Description(
    family=family, c=C, cfg=CFG, tol=1e-3, agrees_to=1e-4,
    state_bytes_per_slot=family.state_bytes_per_slot(C, itemsize=4),
    kv_bytes_per_token=2 * (32 + 128) * 4,  # two latent layers; the shared key in whole lane tiles: 128, not the published 4
    poison={"c_kv": 1e4, "k_r": jnp.nan},
    faults={"bf16_state": battery.Fault(battery.bf16_state("kda", "S")),
            "gate_a_head": battery.Fault(battery.patched(kl, "_kda_inputs", _a_gate_a_head)),
            "rotated_shared_key": battery.Fault(battery.patched(glm, "mla_down", _a_rotated_shared_key)),
            "slot_not_reset": battery.Fault(battery.slot_not_reset),
            "padded_length": battery.Fault(battery.padded_length),
            "rows_past_the_length": battery.Fault(battery.patched(sa, "attend_latent", _rows_past_the_length))},
    refusal_says=("its recurrent layers keep a state per sequence (S, conv)", "its attention layers keep c_kv and k_r per position"),
    refusal_says_not=("gdn", "mamba"),
    shares=("num_experts", 4, {"norm": True, "scale": 2.446, "eps": 1e-5}))


@pytest.fixture(scope="module")
def params():
    return battery.jiggled(jax.jit(lambda k: kl.init_params(CFG, k))(jax.random.PRNGKey(7)))


# ------------------------------------------------------------------------------ the description
def test_the_description_is_a_dense_layer_then_the_period_twice_and_keeps_two_kinds_of_cache():
    assert CFG.layer_kinds == ("kda", "ffn", "mla", "moe", "kda", "moe", "mla", "moe", "kda", "moe")
    assert CFG.layer_plan == hybrid.LayerPlan(period=("mla", "moe", "kda", "moe"), repeats=2, tail=(), head=("kda", "ffn"))
    published = kl.KimiLinearConfig()
    assert published.layer_plan == (("kda", "moe", "kda", "moe", "mla", "moe", "kda", "moe"), 6, ("kda", "moe", "mla", "moe"), ("kda", "ffn"))
    assert (published.count("kda"), published.count("mla"), published.count("ffn"), published.count("moe")) == (20, 7, 1, 26)
    assert published.num_params() == 49_122_681_728
    cut = dataclasses.replace(published, num_hidden_layers=9, num_local_experts=64, vocab_size=40960)
    assert cut.layer_plan == hybrid.LayerPlan(period=("kda", "moe", "kda", "moe", "mla", "moe", "kda", "moe"), repeats=2, tail=(), head=("kda", "ffn"))
    assert cut.kinds_held == "7 x kda, 1 x ffn, 8 x moe, 2 x mla" and cut.num_params() == 4_272_540_512
    assert (cut.num_kv_layers, cut.routing_layers, cut.num_layers) == (2, 8, 18)
    assert {k: m.scope for k, m in cut.mixers.items()} == {"kda": "kda", "mla": "mla", "ffn": "ffn", "moe": "moe"}
    spec = cut.cache_spec()
    assert spec["kda"] == {"S": ((32, 128, 128), "float32", "sequence"), "conv": ((3, 3 * 4096), "bfloat16", "sequence")}
    assert spec["mla"] == {"c_kv": ((512,), "bfloat16", "position"), "k_r": ((128,), "bfloat16", "position")} and spec["ffn"] == spec["moe"] == {}
    assert cut.position_entries() == {"c_kv": (2, (512,), "bfloat16"), "k_r": (2, (128,), "bfloat16")}
    assert state_cache.sequence_entries(cut) == {"S": (7, (32, 128, 128), "float32"), "conv": (7, (3, 12288), "bfloat16")}
    assert state_cache.bytes_per_slot(cut) == 15_196_160 and kvc.entry_bytes_per_token(cut.position_entries()) == 2_560
    assert cut.slot_attention_tile == dict(num_heads=32, num_kv_heads=1, head_dim=640, value_dim=512)
    assert not cut.mla_rotates and cut.q_lora_rank is None and glm.Glm4MoeLiteConfig().mla_rotates
    s = cut.expert_layer
    assert (s.num_experts, s.held, s.top_k, s.score, s.bias, s.norm_topk, s.scale, s.act, s.shared_gated) == (
        256, 64, 8, "sigmoid", True, True, 2.446, "swiglu", False)
    # what a prefill program runs of the rule, from its shape: 7 layers x 8 sequences x 4,096 / 64 chunks
    assert cut.prefill_counters(8, 4096) == {"kda_chunks": 7 * 8 * 64, "kda_kernel_chunks": 0} and glm.Glm4MoeLiteConfig().prefill_counters(8, 4096) == {}


def test_the_counts_are_the_programs(params):
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    assert n == CFG.num_params() == family.parameters_held(C)


# ------------------------------------------------------------------------------ the delta rule
@jax.jit
def _recurrence(q, k, v, g, beta):
    """``delta_rule_step`` position by position from a zero state: q, k [B,T,G,K], v [B,T,G,1,V], g [B,T,G,1(,K)], beta [B,T,G,1]."""
    def one(S, at):
        q_t, k_t, v_t, g_t, beta_t = at
        o, S = qn.delta_rule_step(S, q_t, k_t, v_t[:, :, 0], g_t[:, :, 0], beta_t[:, :, 0])
        return S, o

    S, o = jax.lax.scan(one, jnp.zeros(q.shape[:1] + q.shape[2:] + v.shape[-1:]), tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)[:, :, :, None], S[:, :, None]


def _rule_inputs(T, gate, seed=0, B=2, G=3, K=16, V=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda a: a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    q, k = unit(jax.random.normal(ks[0], (B, T, G, K))) * K ** -0.5, unit(jax.random.normal(ks[1], (B, T, G, K)))
    v, beta = jax.random.normal(ks[2], (B, T, G, 1, V)), jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, G, 1)))
    return q, k, v, gate(ks[4], (B, T, G, 1, K)), beta


GATES = {
    # exp(A_log) 16 on a step of 0.1, every position of every channel: 64 x 1.6 = 102 > 88, where exp(-gc) leaves float32 inside one chunk
    "the_initialisations_strongest": lambda key, shape: jnp.full(shape, -1.6),
    # the data's part can make a channel forget within a position, and its neighbour not at all
    "forgets_in_one_position": lambda key, shape: -40.0 * jax.random.uniform(key, shape) ** 4,
    "mild": lambda key, shape: -0.1 * jax.random.uniform(key, shape),
    "none": lambda key, shape: jnp.zeros(shape),
}


@pytest.mark.parametrize("gate", list(GATES))
@pytest.mark.parametrize("T, chunk", [(150, 64), (37, 8), (37, 5), (23, 64)])
def test_the_chunked_rule_with_a_gate_by_channel_equals_the_recurrence_and_stays_finite(gate, T, chunk):
    """Two chunks and more, a last chunk that is not whole, a chunk the sub-blocks do not divide, one
    chunk longer than the sequence; at every gate the initialisation and the data can draw."""
    q, k, v, g, beta = _rule_inputs(T, GATES[gate])
    o, S = qn.delta_rule_chunked(q, k, v, g, beta, chunk, name="kda")
    want_o, want_S = _recurrence(q, k, v, g, beta)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(S)).all()
    np.testing.assert_allclose(o, want_o, atol=1e-5, rtol=0)
    np.testing.assert_allclose(S, want_S, atol=1e-5, rtol=0)


# what the scalar gate's path gave before this file existed, on inputs of _rule_inputs(150, mild, seed=1, G=2)
# with two value heads a key head (the parent commit's ``delta_rule_chunked``, this machine's CPU)
GAVE = {"o": [0.015243963338434696, -0.025912880897521973, -0.008725151419639587, -0.04772038385272026, 0.11292494088411331,
              -0.1066182479262352, -0.1330193728208542, 0.11088989675045013, -0.03877686336636543, -0.049987249076366425,
              0.02712375298142433, 0.05053428187966347],
        "S": [0.18794430792331696, 0.39339613914489746, 0.13572004437446594]}


def test_a_gate_a_head_takes_the_path_it_took_and_is_the_broadcast_of_a_gate_by_channel():
    q, k, _, g, _ = _rule_inputs(150, GATES["mild"], seed=1, G=2)
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    v, beta = jax.random.normal(ks[0], (2, 150, 2, 2, 8)), jax.nn.sigmoid(jax.random.normal(ks[1], (2, 150, 2, 2)))
    g_head = jnp.concatenate([g[..., 0], 2.0 * g[..., 1]], axis=-1)  # [B,T,G,R]
    o, S = qn.delta_rule_chunked(q, k, v, g_head, beta, 64)
    o_c, S_c = qn.delta_rule_chunked(q, k, v, jnp.broadcast_to(g_head[..., None], g_head.shape + (16,)), beta, 64)
    np.testing.assert_allclose(o, o_c, atol=1e-6, rtol=0)
    np.testing.assert_allclose(S, S_c, atol=1e-6, rtol=0)
    got = {"o": np.asarray(o)[1, [0, 63, 64, 149], 1, 1, :3].ravel(), "S": np.asarray(S)[0, 1, 0, [0, 7, 15], 2]}
    for name, want in GAVE.items():
        np.testing.assert_allclose(got[name], np.asarray(want, np.float32), atol=1e-6, rtol=0, err_msg=name)


@pytest.mark.parametrize("chunk", [8, 5, 64])
def test_kda_sequence_form_equals_its_step_form_with_the_state_at_each_true_length(params, chunk):
    """Padded batches through ``kda_seq`` (the chunked rule) against ``kda_step`` one token at a time:
    the state and the convolutions' window AT each sequence's true length, not after the padding."""
    cfg = dataclasses.replace(CFG, chunk_size=chunk)
    w = jax.tree.map(lambda a: a[1], params["kda"])
    xn = jax.random.normal(jax.random.PRNGKey(3), (2, 21, cfg.hidden_size))
    lengths = jnp.asarray([21, 13])
    y, S, conv = kl.kda_seq(w, xn, lengths, cfg)
    for b, n in enumerate((21, 13)):
        s, cv = jnp.zeros((1,) + S.shape[1:]), jnp.zeros((1,) + conv.shape[1:])
        for t in range(n):
            y_t, s, cv = kl.kda_step(w, xn[b:b + 1, t], s, cv, cfg)
            np.testing.assert_allclose(y_t[0], y[b, t], atol=2e-5)
        np.testing.assert_allclose(s[0], S[b], atol=2e-5)
        np.testing.assert_allclose(cv[0], conv[b], atol=1e-6)
    assert float(jnp.abs(S).max()) > 1e-3
    # the gate IS by channel: two channels of one head decay differently
    low = jnp.dot(xn, w["in_low"])
    *_, g = kl._kda_inputs(w, jnp.dot(xn, w["in_qkv"]), low, cfg)
    assert g.shape == (2, 21, cfg.kda_num_heads, cfg.kda_head_dim) and float(jnp.std(g, axis=-1).min()) > 0 and float(g.max()) < 0


# ------------------------------------------------------------------ both caches, side by side
def test_the_state_cache_and_the_latent_slot_cache_are_allocated_side_by_side_from_cache_spec(eng):
    stats = eng.kv_cache_stats()
    assert stats["entries"] == {"c_kv": [2, [32], "float32"], "k_r": [2, [128], "float32"]}
    assert stats["bytes_per_token"] == 2 * (32 + 128) * 4 and stats["allocated_bytes"] == 4 * 128 * stats["bytes_per_token"]
    assert set(eng.cache) == {"c_kv", "k_r", "length"} and set(eng.state) == {"S", "conv"}
    assert eng.state["S"].shape == (3, 4, 4, 8, 8) and eng.state["conv"].shape == (3, 4, 3, 3 * 32) and eng.cache["c_kv"].shape == (2, 4, 128, 32)
    assert stats["state_bytes_per_slot"] == 3 * (4 * 8 * 8 * 4 + 3 * 96 * 4) == family.state_bytes_per_slot(C, itemsize=4)
    assert family.kv_bytes_per_token(C, itemsize=4) == 2 * (32 + 4) * 4 < stats["bytes_per_token"]


def test_one_token_at_a_time_through_both_caches_equals_the_sequence_form(params):
    """``decode_step`` from empty caches (the rule one position at a time on the state cache, the
    attention absorbed on the latent rows) against the sequence forward (the chunked rule, the
    expanded attention) at every position of two sequences, one lane left unbound; then what the
    steps left in both caches against what a prefill hands them."""
    T = 24
    toks = np.asarray(battery.prompts(DESC, 4, (T, T)), np.int32)
    want = np.asarray(hybrid.forward(params, jnp.asarray(toks), CFG))  # [2, T, V]
    cache, state = kvc.alloc_entries(CFG.position_entries(), 3, 32), state_cache.alloc(CFG, 3)
    step = jax.jit(partial(hr.decode_step, cfg=CFG))
    active = jnp.asarray([True, False, True])
    for t in range(T):
        logits, cache, state, moe = step(params, cache, state, jnp.asarray([toks[0, t], 0, toks[1, t]], jnp.int32), active)
        np.testing.assert_allclose(np.asarray(logits)[[0, 2]], want[:, t], atol=5e-5, rtol=0, err_msg=f"position {t}")
        assert float(moe[2]) == 2 * CFG.num_experts_per_tok  # two lanes' choices, the unbound lane kept out
    _, rows, kept = jax.jit(partial(hr.prefill, cfg=CFG))(params, jnp.asarray(toks), jnp.asarray([T, T], jnp.int32))
    for name in ("c_kv", "k_r"):
        np.testing.assert_allclose(np.asarray(cache[name])[:, [0, 2], :T], np.asarray(rows[name]), atol=2e-6, rtol=0, err_msg=name)
    for name in ("S", "conv"):
        np.testing.assert_allclose(np.asarray(state[name])[:, [0, 2]], np.asarray(kept[name]), atol=2e-5, rtol=0, err_msg=name)
    assert not np.asarray(rows["k_r"])[..., CFG.qk_rope_head_dim:].any() and np.asarray(rows["k_r"])[..., :CFG.qk_rope_head_dim].any()
    # no position in the latent layers: the shared key of a token does not depend on where it stands
    w, xn = jax.tree.map(lambda a: a[0], params["mla"]), jax.random.normal(jax.random.PRNGKey(1), (1, 6, CFG.hidden_size))
    here, there = (glm.mla_down(w, xn, jnp.full((6,), p, jnp.int32), CFG) for p in (0, 9))
    assert here[3] is None and all(np.array_equal(a, b) for a, b in zip(here[:3], there[:3]))


@pytest.mark.parametrize("form", ["the_xla_lines", "the_kernel"])
def test_an_admitting_row_of_the_flight_log_counts_the_chunks_its_prefills_ran(eng, params, monkeypatch, form):
    """``kda_kernel_chunks`` beside ``kda_chunks`` says which form ran them: none on the CPU, where
    ``ops/delta_rule.refusal`` speaks; all of them once it does not (a second engine, so that its
    prefill programs are traced with the kernel in them, interpreted), and the tokens are the same."""
    lengths = (20, 9, 41)  # buckets 32, 16 and 64: three programs of one sequence, chunks of 8
    ps = battery.prompts(DESC, 6, lengths)
    if form == "the_kernel":
        want = [o.token_ids for o in eng.generate(ps[:1], SamplingParams(max_tokens=3, temperature=0.0))]
        monkeypatch.setattr(delta_rule, "refusal", lambda *a, **kw: None)
        eng, ps, lengths = battery.engine(CFG, params), ps[:1], lengths[:1]
    mark = eng.telemetry()["step_count"]
    outs = eng.generate(ps, SamplingParams(max_tokens=3, temperature=0.0))
    rows = battery.steps_after(eng, mark)
    admitting = [r for r in rows if r.get("admitted")]
    assert sum(r["kda_chunks"] for r in admitting) == CFG.count("kda") * sum(1 << (n - 1).bit_length() for n in lengths) // 8
    assert all(r["kda_chunks"] * 8 == CFG.count("kda") * r["prefill_tokens_padded"] for r in admitting)
    assert all(r["kda_kernel_chunks"] == (r["kda_chunks"] if form == "the_kernel" else 0) for r in admitting)
    assert not any("kda_chunks" in r or "kda_kernel_chunks" in r for r in rows if not r.get("admitted"))
    assert form == "the_xla_lines" or [o.token_ids for o in outs] == want


# ------------------------------------------------------------------------------ the latent kernel
def test_the_latent_kernel_at_32_heads_equals_the_xla_oracle_and_the_gate_lets_its_tile_through(monkeypatch):
    """Kimi's tile: 32 query heads (two whole bfloat16 tiles, no padded row) on rows of 512 + 128."""
    S, BLK, L, R, ROPE, NH = 64, 16, 2, 128, 128, 32
    lens = jnp.asarray((0, 3 * BLK, BLK - 1, S - 1, BLK, 7), jnp.int32)
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(0), 4)
    q_lat, q_rope = (jax.random.normal(k, (len(lens), NH, R), jnp.float32).astype(jnp.bfloat16) for k in (k1, k2))
    c, r = (jax.random.normal(k, (L, len(lens), S, R), jnp.float32).astype(jnp.bfloat16) for k in (k3, k4))
    r = r.at[..., 64:].set(0)  # the shared key's own 64 columns, zeros after them
    want = sa.attend_rows(jnp.concatenate([q_lat, q_rope], axis=-1), jnp.concatenate([c[1], r[1]], axis=-1)[:, :, None], c[1][:, :, None], lens, 1, 192 ** -0.5)
    got = sa.attend_latent_kernel(q_lat, q_rope, c, r, jnp.int32(1), lens + 1, 192 ** -0.5, block=BLK, interpret=True)
    assert got.shape == (len(lens), NH * R)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-6, rtol=3e-6)
    tile = kl.KimiLinearConfig().slot_attention_tile
    assert "backend 'cpu'" in sa.refusal(jnp.bfloat16, **tile, S=4096)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert sa.refusal(jnp.bfloat16, **tile, S=4096) is None and sa.block_positions(4096, 1, 512, 2) == 1024
    assert "33 query heads: compiled at 20 and 32" in sa.refusal(jnp.bfloat16, **{**tile, "num_heads": 33}, S=4096)
