"""A second hybrid (Gated DeltaNet + gated attention + a softmax-routed expert block,
``models/qwen3_next.py``) over the shared layer loop and the shared expert layer: the program
against the plain reference of ``benchmark/families/qwen3_next.py`` (written from the published
equations, float32, the delta rule one position at a time), each mixer's sequence form against its
step form, the engine through both caches, and the shares of an expert layer against the uncut
layer. Toy widths, every kind of layer, a pattern with a repeated period and a tail, prompt
lengths that are no multiple of the chunk."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hybrid_battery as battery
from benchmark.families import qwen3_next as family
from hybrid_battery import *  # noqa: F401,F403 - the tests every description is held to, collected here against DESC
from hybrid_battery import test_the_chips_shares_add_up_to_the_uncut_expert_layer  # noqa: F401 - chip 0 of four
from hybrid_battery import test_the_grouped_matmul_places_the_pairs_held_here_whatever_their_number  # noqa: F401 - it routes experts
from ray_tpu.llm.sampling import SamplingParams
from ray_tpu.models import experts, hybrid
from ray_tpu.models import qwen3_next as qn
from ray_tpu.ops import delta_rule

# the configuration file's side of the toy model: chip 0 of two, experts 0-3 of 8
C = family.rehearsal({"linear_conv_kernel_dim": 4, "norm_topk_prob": True, "rms_norm_eps": 1e-6,
                      "partial_rotary_factor": 0.25, "rope_theta": 1e7})
CFG = family.program_config(C, 128, remat=False)


def _a_bfloat16_router(real):
    """The router's logits from operands rounded to bfloat16."""
    return lambda w, x, c: real({**w, "router": w["router"].astype(jnp.bfloat16)}, x.astype(jnp.bfloat16), c)


DESC = battery.Description(
    family=family, c=C, cfg=CFG,
    tol=1e-3, agrees_to=1e-4,  # float32 program against float32 reference: they agree to 1e-5; what breaks the state is far over
    state_bytes_per_slot=family.state_bytes_per_slot(C, itemsize=4), kv_bytes_per_token=family.kv_bytes_per_token(C, itemsize=4),
    poison={"k": jnp.nan, "v": 1e4},
    faults={"bf16_state": battery.Fault(battery.bf16_state("gdn", "S")),
            "bf16_router": battery.Fault(battery.patched(experts, "route", _a_bfloat16_router)),
            "slot_not_reset": battery.Fault(battery.slot_not_reset),
            "padded_length": battery.Fault(battery.padded_length)},
    refusal_says=("its recurrent layers keep a state per sequence (S, conv)",), refusal_says_not=("per position",),
    shares=("num_experts", 4, {"norm": True, "eps": 1e-6}))


@pytest.fixture(scope="module")
def params():
    return battery.jiggled(jax.jit(lambda k: qn.init_params(CFG, k))(jax.random.PRNGKey(7)))


def test_the_description_is_two_sub_blocks_a_layer_and_the_loop_finds_its_period(monkeypatch):
    assert CFG.layer_kinds == ("gdn", "moe", "attn", "moe", "gdn", "moe", "attn", "moe", "gdn", "moe")
    assert CFG.layer_plan == (("gdn", "moe", "attn", "moe"), 2, ("gdn", "moe"), ())
    published = qn.Qwen3NextConfig()
    period, repeats, tail, head = published.layer_plan
    assert period == ("gdn", "moe") * 3 + ("attn", "moe") and repeats == 12 and tail == () and head == ()
    assert (published.count("gdn"), published.count("attn"), published.count("moe")) == (36, 12, 48)
    cut = dataclasses.replace(published, num_hidden_layers=12)
    assert cut.layer_plan[1] == 3 and (cut.num_kv_layers, cut.routing_layers, cut.num_layers) == (3, 12, 24)
    assert cut.kinds_held == "9 x gdn, 12 x moe, 3 x attn"
    assert {k: m.scope for k, m in cut.mixers.items()} == {"gdn": "gdn", "attn": "gated_attn", "moe": "moe"}
    spec = cut.cache_spec()
    assert spec["gdn"]["S"] == ((32, 128, 128), "float32", "sequence") and spec["attn"]["k"][0] == (2, 256) and spec["moe"] == {}
    # what a prefill program runs of the rule, from its shape: 9 layers x 8 sequences x 4,096 / 64 chunks; off the TPU none in the kernel
    assert cut.prefill_counters(8, 4096) == {"gdn_chunks": 9 * 8 * 64, "gdn_kernel_chunks": 0}
    monkeypatch.setattr(delta_rule, "refusal", lambda *a, **kw: None)
    assert cut.prefill_counters(8, 4096) == {"gdn_chunks": 9 * 8 * 64, "gdn_kernel_chunks": 9 * 8 * 64}


@pytest.mark.parametrize("chunk", [8, 5, 64])
def test_chunked_delta_rule_equals_the_one_position_recurrence(params, chunk):
    """Padded batches, the state AT each sequence's true length, chunks that divide the length, that
    do not, and one chunk longer than the sequence."""
    cfg = dataclasses.replace(CFG, chunk_size=chunk)
    w = jax.tree.map(lambda a: a[1], params["gdn"])
    xn = jax.random.normal(jax.random.PRNGKey(3), (2, 21, cfg.hidden_size))
    lengths = jnp.asarray([21, 13])
    y, S, conv = qn.gdn_seq(w, xn, lengths, cfg)
    for b, n in enumerate((21, 13)):
        s, cv = jnp.zeros((1,) + S.shape[1:]), jnp.zeros((1,) + conv.shape[1:])
        for t in range(n):
            y_t, s, cv = qn.gdn_step(w, xn[b:b + 1, t], s, cv, cfg)
            np.testing.assert_allclose(y_t[0], y[b, t], atol=2e-5)
        np.testing.assert_allclose(s[0], S[b], atol=2e-5)  # not the state after the padding
        np.testing.assert_allclose(cv[0], conv[b], atol=1e-6)
    assert float(jnp.abs(S).max()) > 1e-3


@pytest.mark.parametrize("form", ["the_xla_lines", "the_kernel"])
def test_an_admitting_row_of_the_flight_log_counts_the_chunks_its_prefills_ran(eng, params, monkeypatch, form):
    """``gdn_kernel_chunks`` beside ``gdn_chunks`` says which form ran them: none on the CPU, where
    ``ops/delta_rule.refusal`` speaks; all of them once it does not (a second engine, so that its
    prefill programs are traced with the kernel in them, interpreted: two value heads a key head, one
    gate a head), and what that engine serves agrees with the plain reference as closely as the XLA
    lines' does (``agrees_to``: float32 against float32)."""
    lengths = (20, 9, 41)  # buckets 32, 16 and 64: three programs of one sequence, chunks of 8
    ps = battery.prompts(DESC, 6, lengths)
    sampling = [SamplingParams(max_tokens=3, temperature=0.0, logprobs=True)] * len(ps)
    traced, real = [], delta_rule.delta_rule
    monkeypatch.setattr(delta_rule, "delta_rule", lambda *a, **kw: traced.append(a[3].ndim) or real(*a, **kw))
    if form == "the_kernel":
        monkeypatch.setattr(delta_rule, "refusal", lambda *a, **kw: None)
        eng = battery.engine(CFG, params)
    mark = eng.telemetry()["step_count"]
    outs = eng.generate(ps, sampling)
    rows = battery.steps_after(eng, mark)
    admitting = [r for r in rows if r.get("admitted")]
    assert sum(r["gdn_chunks"] for r in admitting) == CFG.count("gdn") * sum(1 << (n - 1).bit_length() for n in lengths) // 8
    assert all(r["gdn_chunks"] * 8 == CFG.count("gdn") * r["prefill_tokens_padded"] for r in admitting)
    assert all(r["gdn_kernel_chunks"] == (r["gdn_chunks"] if form == "the_kernel" else 0) for r in admitting)
    assert not any("gdn_chunks" in r or "gdn_kernel_chunks" in r for r in rows if not r.get("admitted"))
    assert set(traced) == ({4} if form == "the_kernel" else set()), "the kernel was traced into the prefill programs, its gate a head: [B,T,G,R]"
    res = battery.check(DESC, params, battery.served(outs, ps, sampling))
    assert res["ok"] and res["tokens"] == 9 and res["max_abs_dlogprob"] < DESC.agrees_to, res


def test_a_large_prefill_goes_through_the_rule_a_few_sequences_at_a_time(params, monkeypatch):
    w = jax.tree.map(lambda a: a[0], params["gdn"])
    xn = jax.random.normal(jax.random.PRNGKey(4), (4, 16, CFG.hidden_size))
    lengths = jnp.asarray([16, 9, 12, 3])
    whole = qn.gdn_seq(w, xn, lengths, CFG)
    monkeypatch.setattr(qn, "RULE_POSITIONS", 32)  # two sequences of 16 at a time
    for a, b in zip(whole, qn.gdn_seq(w, xn, lengths, CFG)):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_gated_attention_step_against_its_sequence_form(params):
    """Positions come from the cache's lengths: token t's step, against rows 0..t, is row t of the
    sequence form; the partial rotation turns only the first quarter of a head."""
    w = jax.tree.map(lambda a: a[0], params["attn"])
    T = 11
    xn = jax.random.normal(jax.random.PRNGKey(5), (2, T, CFG.hidden_size))
    y, k, v = qn.gated_attn_seq(w, xn, CFG)
    step = CFG.mixers["attn"].step
    arrays = {"k": jnp.zeros((1, 2, 16, CFG.num_kv_heads, CFG.hd)), "v": jnp.zeros((1, 2, 16, CFG.num_kv_heads, CFG.hd))}
    for t in range(T):
        lengths = jnp.full((2,), t, jnp.int32)
        view = hybrid.LayerCache(arrays, frozenset({"k", "v"}), 0, jnp.arange(2), lengths)
        y_t, _ = step(w, xn[:, t], view, hybrid.StepCtx(lengths, jnp.ones((2,), bool), None))
        arrays = view.arrays
        np.testing.assert_allclose(y_t, y[:, t], atol=1e-5)
    np.testing.assert_allclose(arrays["k"][0, :, :T], k, atol=1e-6)
    q0, _, k0, _ = qn.gated_attn_qkv(w, xn, jnp.zeros((T,), jnp.int32), CFG)
    q5, _, k5, _ = qn.gated_attn_qkv(w, xn, jnp.full((T,), 5, jnp.int32), CFG)
    assert CFG.rot_dim == 4
    np.testing.assert_array_equal(q0[..., 4:], q5[..., 4:])
    assert float(jnp.abs(k0[..., :4] - k5[..., :4]).max()) > 1e-3


def test_the_expert_block_grouped_dense_and_one_token_a_lane_agree(params):
    w = jax.tree.map(lambda a: a[0], params["moe"])
    x = jax.random.normal(jax.random.PRNGKey(5), (200, CFG.hidden_size))
    idx, wt = experts.route(w, x, CFG)
    np.testing.assert_allclose(wt.sum(-1), 1.0, atol=1e-6)  # softmax, top k, normalised
    assert (np.asarray(idx) >= CFG.local_experts).any() and (np.asarray(idx) < CFG.local_experts).any()
    want = battery.one_by_one(w, x, idx, wt, CFG)
    assert np.abs(want).max() > 0
    np.testing.assert_allclose(experts.experts_dense(w, x, idx, wt, CFG), want, atol=1e-4)
    np.testing.assert_allclose(experts.experts_grouped(params["moe"], 0, x, idx, wt, jnp.ones((200,), bool), CFG), want, atol=1e-4)
    # the block over a padded sequence, grouped (serving) and dense (training), and one token a lane
    lengths = jnp.asarray([100, 37])
    xs = x.reshape(2, 100, -1)
    served_form, counters = experts.moe_seq(w, xs, lengths, CFG, stacked=(params["moe"], 0))
    dense_form, _ = experts.moe_seq(w, xs, lengths, CFG)
    real = np.arange(100)[None, :] < np.asarray(lengths)[:, None]
    np.testing.assert_allclose(np.asarray(served_form)[real], np.asarray(dense_form)[real], atol=1e-4)
    lane, stats = experts.moe_step(w, xs[:, 3], jnp.ones((2,), bool), CFG, (params["moe"], 0))
    np.testing.assert_allclose(lane, served_form[:, 3], atol=1e-4)
    # the counters: pairs served here over the real rows, held experts hit, rows of whole blocks
    local = (np.asarray(idx).reshape(2, 100, -1)[real] < CFG.local_experts).sum()
    assert counters[1] == local and 0 < counters[0] <= CFG.local_experts
    assert counters[2] % experts.BLOCK == 0 and counters[2] >= counters[1] and stats[1] <= 2 * CFG.num_experts_per_tok
    # the shared expert is gated per token
    shared = experts.shared_expert(w, x, CFG.expert_layer)
    plain = (jax.nn.silu(x @ w["shared_gate"]) * (x @ w["shared_up"])) @ w["shared_down"]
    np.testing.assert_allclose(shared, plain * jax.nn.sigmoid(x @ w["shared_sg"])[:, None], atol=1e-5)


def test_a_batch_beyond_a_slab_goes_through_the_grouped_matmul_in_slabs(params, monkeypatch):
    w = jax.tree.map(lambda a: a[0], params["moe"])
    xs = jax.random.normal(jax.random.PRNGKey(8), (4, 16, CFG.hidden_size))
    lengths = jnp.asarray([16, 5, 11, 16])
    whole, counted = experts.moe_seq(w, xs, lengths, CFG, stacked=(params["moe"], 0))
    monkeypatch.setattr(experts, "SLAB_ROWS", 16)
    slabbed, counted_slabs = experts.moe_seq(w, xs, lengths, CFG, stacked=(params["moe"], 0))
    np.testing.assert_allclose(whole, slabbed, atol=1e-6)
    assert counted[1] == counted_slabs[1] and counted[0] == counted_slabs[0] and counted_slabs[2] >= counted[2]


def test_an_anchored_router_keeps_its_top_k_under_bfloat16_rounding_of_the_stream():
    """``router_anchor`` for a model with more experts over its layers than its stream has
    dimensions: one orthonormal set of columns, permuted by layer. Every token id's k-th logit
    leads its (k+1)-th by about the anchor in EVERY expert block, the choice survives rounding the
    router's input to bfloat16 (with plain routers it does not, somewhere), the experts a token
    meets differ from block to block, and program and reference still agree."""
    cfg = dataclasses.replace(CFG, num_experts=32, num_local_experts=8, router_anchor=8.0)
    assert cfg.count("moe") * cfg.num_experts > cfg.hidden_size  # PR 29's construction could not be had
    plain, anchored = (jax.jit(dataclasses.replace(cfg, router_anchor=a).init_params)(jax.random.PRNGKey(1)) for a in (0.0, 8.0))

    def top(p, dtype):
        x = cfg.norm(p["embed"], jnp.zeros((cfg.hidden_size,))).astype(dtype).astype(jnp.float32)
        logits = jnp.einsum("vh,lhe->lve", x, p["moe"]["router"], precision=jax.lax.Precision.HIGHEST)
        vals, idx = jax.lax.top_k(logits, cfg.num_experts_per_tok + 1)
        return np.asarray(vals[..., -2] - vals[..., -1]), np.sort(np.asarray(idx[..., :-1]), axis=-1)

    gap_plain, idx_plain = top(plain, jnp.float32)
    gap, idx = top(anchored, jnp.float32)
    assert np.median(gap_plain) < 0.5 and np.median(gap) > 3.0 and (gap > 1.0).mean() > 0.99, (np.median(gap_plain), np.median(gap))
    assert (top(anchored, jnp.bfloat16)[1] == idx).all() and not (top(plain, jnp.bfloat16)[1] == idx_plain).all()
    assert (idx[0] != idx[1]).any(axis=-1).mean() > 0.9  # another set of experts in the next block
    cols = np.asarray(anchored["moe"]["router"][0]).T
    np.testing.assert_allclose(cols @ cols.T, np.eye(len(cols)), atol=1e-5)
    c = {**C, "num_experts": 8, "deployment": {**C["deployment"], "experts_published": 32, "experts_held": [0, 8]}, "init_router_anchor": 8.0}
    assert family.program_config(c, 128) == dataclasses.replace(cfg, remat=False)
    toks = np.asarray(battery.prompts(DESC, 2, (29,)), np.int32)
    ref = family.reference_logprobs(anchored, toks[0], c, 0, 29)
    np.testing.assert_allclose(jax.nn.log_softmax(qn.forward(anchored, jnp.asarray(toks), cfg)[0], -1), ref, atol=1e-4)
    with pytest.raises(ValueError, match="orthogonal router columns"):
        jax.eval_shape(dataclasses.replace(cfg, num_experts=128).init_params, jax.random.PRNGKey(0))
