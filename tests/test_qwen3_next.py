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

from benchmark import reference
from benchmark.families import qwen3_next as family
from ray_tpu.llm import LLMEngine, SamplingParams
from ray_tpu.models import experts, hybrid
from ray_tpu.models import qwen3_next as qn

# the configuration file's side of the toy model: chip 0 of two, experts 0-3 of 8
C = family.rehearsal({"linear_conv_kernel_dim": 4, "norm_topk_prob": True, "rms_norm_eps": 1e-6,
                      "partial_rotary_factor": 0.25, "rope_theta": 1e7})
CFG = family.program_config(C, 128, remat=False)
TOL = 1e-3  # float32 program against float32 reference: they agree to 1e-5; what breaks the state is far over


def _jiggled(params):
    """Norm weights off their initial 0 and 1, so that ``1 + w`` against a plain ``w`` shows."""
    def jig(path, a):
        if "norm" in str(path[-1]):
            return a + 0.1 * jax.random.normal(jax.random.PRNGKey(len(str(path))), a.shape, a.dtype)
        return a
    return jax.tree_util.tree_map_with_path(jig, params)


@pytest.fixture(scope="module")
def params():
    return _jiggled(jax.jit(lambda k: qn.init_params(CFG, k))(jax.random.PRNGKey(7)))


def prompts(seed, lengths):
    rs = np.random.RandomState(seed)
    return [[int(t) for t in rs.randint(1, C["vocab_size"] - 1, size=n)] for n in lengths]


def engine(params, cfg=CFG, **kw):
    return LLMEngine(cfg, params, **{"max_num_seqs": 4, "max_seq_len": 128, "prefill_buckets": (16, 32, 64), **kw})


def served(outs, ps, sampling):
    return [{"prompt": p, "tokens": o.token_ids, "logprobs": o.logprobs, "greedy": sp.temperature == 0.0}
            for o, p, sp in zip(outs, ps, sampling)]


def check(params, samples, tol=TOL):
    return reference.check_served(family.reference_logprobs, params, C, samples, tol)


def test_the_description_is_two_sub_blocks_a_layer_and_the_loop_finds_its_period():
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


def test_sequence_forward_matches_the_reference(params):
    toks = np.asarray(prompts(0, (37, 37)), np.int32)  # 37: four whole chunks of 8 and a rest
    logits = qn.forward(params, jnp.asarray(toks), CFG)
    for b in range(2):
        ref = family.reference_logprobs(params, toks[b], C, 0, 37)
        np.testing.assert_allclose(jax.nn.log_softmax(logits[b], -1), ref, atol=1e-4)


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


def one_by_one(w, x, idx, wt, cfg):
    """Each (token, chosen expert) pair computed alone: what no dispatch may lose."""
    out = np.zeros(x.shape, np.float32)
    for n in range(x.shape[0]):
        for e, g in zip(np.asarray(idx[n]), np.asarray(wt[n])):
            e = int(e) - cfg.expert_start
            if 0 <= e < cfg.local_experts:
                h = jax.nn.silu(x[n] @ w["w_gate"][e].T) * (x[n] @ w["w_up"][e].T)
                out[n] += g * np.asarray(h @ w["w_down"][e])
    return out


def test_the_expert_block_grouped_dense_and_one_token_a_lane_agree(params):
    w = jax.tree.map(lambda a: a[0], params["moe"])
    x = jax.random.normal(jax.random.PRNGKey(5), (200, CFG.hidden_size))
    idx, wt = experts.route(w, x, CFG)
    np.testing.assert_allclose(wt.sum(-1), 1.0, atol=1e-6)  # softmax, top k, normalised
    assert (np.asarray(idx) >= CFG.local_experts).any() and (np.asarray(idx) < CFG.local_experts).any()
    want = one_by_one(w, x, idx, wt, CFG)
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


def test_the_four_chips_shares_add_up_to_the_uncut_expert_layer():
    """Chip j of four holds experts 2j and 2j+1 of 8; the routed parts of all four, with what
    every chip computes alike (the gated shared expert) counted once, are the uncut reference layer."""
    whole_c = {**C, "num_experts": 8, "deployment": None}
    whole = family.program_config(whole_c, 128)
    group = jax.tree.map(lambda a: a[:1], _jiggled(jax.jit(lambda k: qn.init_params(whole, k))(jax.random.PRNGKey(11)))["moe"])
    x = jax.random.normal(jax.random.PRNGKey(12), (40, whole.hidden_size))
    ref, _ = family._experts(x, group, 0, first=0, top_k=2, norm=True, eps=1e-6)
    layer = jax.tree.map(lambda a: a[0], group)
    xn = whole.norm(x, layer["norm"])
    idx, wt = experts.route(layer, xn, whole)
    total = experts.shared_expert(layer, xn, whole.expert_layer)
    for chip in range(4):
        share = dataclasses.replace(whole, expert_start=2 * chip, num_local_experts=2)
        w = {**layer, **{n: layer[n][2 * chip:2 * chip + 2] for n in ("w_gate", "w_up", "w_down")}}
        routed = experts.experts_grouped(jax.tree.map(lambda a: a[None], w), 0, xn, idx, wt, jnp.ones((40,), bool), share)
        assert np.abs(np.asarray(routed)).max() > 0
        total = total + routed
    np.testing.assert_allclose(x + total, ref, atol=1e-4)


def test_prefill_then_decode_through_the_engine_matches_the_reference(params):
    """Admission waves of batched same-bucket prefills at lengths off the bucket and off the chunk,
    more requests than slots (so slots are recycled), greedy and seeded, and before the second
    round every slot's old state and rows poisoned: all of it against the full forward."""
    eng = engine(params)
    lengths = (5, 19, 23, 40, 7, 33, 18, 61, 9)
    ps = prompts(1, lengths)
    sampling = [SamplingParams(max_tokens=10, temperature=0.0 if i % 3 else 0.8, top_p=0.95, seed=i, logprobs=True)
                for i in range(len(ps))]
    outs = eng.generate(ps, sampling)
    res = check(params, served(outs, ps, sampling))
    assert res["ok"] and res["tokens"] == 90, res
    stats = eng.kv_cache_stats()
    assert stats["state_bytes_per_slot"] == family.state_bytes_per_slot(C, itemsize=4)
    assert stats["bytes_per_token"] == family.kv_bytes_per_token(C, itemsize=4)
    assert stats["state_allocated_bytes"] == 4 * stats["state_bytes_per_slot"] and eng.prefix_cache_stats() == {}
    assert set(eng.state) == {"S", "conv"} and eng.state["S"].dtype == jnp.float32
    # every slot has held a sequence by now: poison what they left, then serve again
    eng.state = jax.tree.map(lambda a: jnp.full_like(a, jnp.nan), eng.state)
    eng.cache = {**eng.cache, "k": jnp.full_like(eng.cache["k"], jnp.nan), "v": jnp.full_like(eng.cache["v"], 1e4)}
    ps2 = prompts(2, (31, 12, 50, 6, 17))
    sp2 = [SamplingParams(max_tokens=8, temperature=0.0, logprobs=True)] * len(ps2)
    res = check(params, served(eng.generate(ps2, sp2), ps2, sp2))
    assert res["ok"] and res["tokens"] == 40, res
    # the flight log: decode rows carry PR 29's four counters, admitting rows the prefills' five
    steps = eng.telemetry()["steps"]
    rows = [s for s in steps if "experts_hit" in s]
    assert rows and all(0 < r["experts_hit"] <= 4 and r["moe_pairs_local"] <= r["moe_pairs_total"] for r in rows)
    assert all(r["experts_read"] == r["experts_hit"] for r in rows), "a decode step reads the experts its lanes hit, and no others (PR 37)"
    admitting = [s for s in steps if s.get("admitted")]
    assert admitting and all("prefill_tokens" in s for s in admitting)
    for s in admitting:
        assert 0 < s["prefill_tokens"] <= s["prefill_tokens_padded"] and s["prefill_tokens_padded"] % 16 == 0
        assert 0 < s["prefill_moe_pairs_local"] <= s["moe_rows_computed"] and s["prefill_moe_pairs_local"] <= 2 * s["prefill_tokens"]
        assert 0 < s["prefill_experts_hit"] <= 4
    assert sum(s["prefill_tokens"] for s in admitting) == sum(lengths) + sum(len(p) for p in ps2)
    assert not any("prefill_tokens" in s for s in steps if not s.get("admitted"))


def test_the_synchronous_loop_is_the_fused_steps_oracle(params):
    ps = prompts(3, (9, 30, 14, 47, 22))
    sp = SamplingParams(max_tokens=7, temperature=0.0, logprobs=True)
    a = engine(params).generate(ps, sp)
    b = engine(params, device_resident=False).generate(ps, sp)
    assert [o.token_ids for o in a] == [o.token_ids for o in b]
    np.testing.assert_allclose([o.logprobs for o in a], [o.logprobs for o in b], atol=1e-5)


@dataclasses.dataclass(frozen=True)
class Bf16State(qn.Qwen3NextConfig):
    """The same model with its recurrent state kept in bfloat16: the precision below the stated one."""

    def cache_spec(self):
        spec = super().cache_spec()
        shape, _, per = spec["gdn"]["S"]
        return {**spec, "gdn": {**spec["gdn"], "S": (shape, "bfloat16", per)}}


@pytest.mark.parametrize("fault", ["bf16_state", "bf16_router", "slot_not_reset", "padded_length"])
def test_the_comparison_fails_lower_precision_and_a_wrong_state(params, fault, monkeypatch):
    ps = prompts(4, (21, 38, 11, 27))  # none on a bucket, none on a chunk
    sp = [SamplingParams(max_tokens=24, temperature=0.0, logprobs=True)] * len(ps)
    eng = engine(params, cfg=Bf16State(**dataclasses.asdict(CFG))) if fault == "bf16_state" else engine(params)
    if fault == "bf16_router":  # the router's logits from operands rounded to bfloat16
        real = experts.route
        monkeypatch.setattr(experts, "route", lambda w, x, c: real(
            {**w, "router": w["router"].astype(jnp.bfloat16)}, x.astype(jnp.bfloat16), c))
    elif fault == "slot_not_reset":  # a recycled slot keeps the last sequence's state: no insert at admission
        assert check(params, served(eng.generate(ps, sp), ps, sp))["ok"]
        eng._state_insert = lambda state, slot, row, new: state
    elif fault == "padded_length":  # the rule run over the padding too
        real_prefill = eng._prefill

        def at_padded_length(params, toks, lens):
            logits, rows, _ = real_prefill(params, toks, lens)
            return logits, rows, real_prefill(params, toks, jnp.full_like(lens, toks.shape[1]))[2]

        eng._prefill = at_padded_length
    res = check(params, served(eng.generate(ps, sp), ps, sp))
    assert not res["ok"] and res["max_abs_dlogprob"] > TOL, res


def test_an_anchored_router_keeps_its_top_k_under_bfloat16_rounding_of_the_stream():
    """``router_anchor`` for a model with more experts over its layers than its stream has
    dimensions: one orthonormal set of columns, permuted by layer. Every token id's k-th logit
    leads its (k+1)-th by about the anchor in EVERY expert block, the choice survives rounding the
    router's input to bfloat16 (with plain routers it does not, somewhere), the experts a token
    meets differ from block to block, and program and reference still agree."""
    cfg = dataclasses.replace(CFG, num_experts=32, num_local_experts=8, router_anchor=8.0)
    assert cfg.count("moe") * cfg.num_experts > cfg.hidden_size  # PR 29's construction could not be had
    plain, anchored = (qn.init_params(dataclasses.replace(cfg, router_anchor=a), jax.random.PRNGKey(1)) for a in (0.0, 8.0))

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
    toks = np.asarray(prompts(2, (29,)), np.int32)
    ref = family.reference_logprobs(anchored, toks[0], c, 0, 29)
    np.testing.assert_allclose(jax.nn.log_softmax(qn.forward(anchored, jnp.asarray(toks), cfg)[0], -1), ref, atol=1e-4)
    with pytest.raises(ValueError, match="orthogonal router columns"):
        qn.init_params(dataclasses.replace(cfg, num_experts=128), jax.random.PRNGKey(0))


def test_serves_through_the_openai_server_streaming(params):
    """LLMConfig(model_config=<the description>) through OpenAIServer: the normal serving path."""
    from ray_tpu.serve.llm import LLMConfig, OpenAIServer

    srv = OpenAIServer(LLMConfig(model_config=CFG, params=params, model_id="toy-qwen3-next",
                                 engine_kwargs={"max_num_seqs": 4, "max_seq_len": 128, "prefill_buckets": (16, 32, 64)}))
    try:
        assert srv.engine._hybrid and srv.engine._device_resident
        p = prompts(5, (26,))[0]
        chunks = list(srv({"prompt": p, "max_tokens": 6, "stream": True}))
        assert chunks[-1].startswith("data: [DONE]") and len(chunks) >= 7
        out = srv.generate(p, {"max_tokens": 6, "logprobs": True})
        assert check(params, [{"prompt": p, "tokens": out["token_ids"], "logprobs": out["logprobs"], "greedy": True}])["ok"]
    finally:
        srv.shutdown()
