"""A hybrid model (Mamba-2 + routed experts + attention, ``models/nemotron_h.py``) through the
engine: the program against the plain reference of ``benchmark/families/nemotron_h.py`` (written
from the published equations, float32), the state cache beside the KV rows, the expert layer that
drops nothing and is told its experts, and every refusal by its name. Toy widths, all three kinds
of layer, a pattern with a repeated period (the loop's scan) and a tail (its unrolled part)."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference
from benchmark.families import nemotron_h as family
from ray_tpu.exceptions import HybridModelUnsupportedError
from ray_tpu.llm import LLMEngine, SamplingParams
from ray_tpu.models import experts
from ray_tpu.models import nemotron_h as nh

# the configuration file's side of the toy model: chip 0 of two, experts 0-3 of 8
C = family.rehearsal({"conv_kernel": 4, "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
                      "routed_scaling_factor": 2.5, "norm_topk_prob": True, "layer_norm_epsilon": 1e-5,
                      "n_shared_experts": 1})
CFG = family.program_config(C, 128, remat=False)
TOL = 1e-3  # float32 program against float32 reference: they agree to 1e-5; what breaks the state is far over


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: nh.init_params(CFG, k))(jax.random.PRNGKey(7))


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


def test_layer_plan_scans_the_repeated_period_and_unrolls_the_tail():
    assert CFG.layer_plan == (("mamba", "moe", "attn"), 2, ("mamba", "moe"), ())
    published = nh.NemotronHConfig()
    period, repeats, tail, head = published.layer_plan
    assert head == ()
    assert "".join(k[0] for k in period) == "mmmmmam" and repeats == 5 and len(tail) == 52 - 35
    cut = dataclasses.replace(published, layer_pattern="MEMEM*EMEMEM*EME")
    assert cut.layer_plan[1] == 2 and len(cut.layer_plan[0]) == 7 and cut.layer_plan[2] == ("mamba", "moe")
    assert (cut.count("mamba"), cut.count("moe"), cut.count("attn")) == (7, 7, 2)
    assert nh.NemotronHConfig(layer_pattern="M*E").layer_plan == ((), 0, ("mamba", "attn", "moe"), ())


def test_sequence_forward_matches_the_reference(params):
    toks = np.asarray(prompts(0, (37, 37)), np.int32)
    logits = nh.forward(params, jnp.asarray(toks), CFG)
    for b in range(2):
        ref = family.reference_logprobs(params, toks[b], C, 0, 37)
        np.testing.assert_allclose(jax.nn.log_softmax(logits[b], -1), ref, atol=1e-4)


def test_chunked_scan_equals_the_token_by_token_recurrence(params):
    w = jax.tree.map(lambda a: a[1], params["mamba"])
    xn = jax.random.normal(jax.random.PRNGKey(3), (2, 21, CFG.hidden_size))  # 21: two whole chunks of 8 and a rest
    lengths = jnp.asarray([21, 13])
    y, ssm, conv = nh.mamba2_seq(w, xn, lengths, CFG)
    for b, n in enumerate((21, 13)):
        s = jnp.zeros((1,) + ssm.shape[1:])
        cv = jnp.zeros((1,) + conv.shape[1:])
        for t in range(n):
            y_t, s, cv = nh.mamba2_step(w, xn[b:b + 1, t], s, cv, CFG)
            np.testing.assert_allclose(y_t[0], y[b, t], atol=1e-5)
        # the state handed to decode is the state at the TRUE length, not at the padded one
        np.testing.assert_allclose(s[0], ssm[b], atol=1e-5)
        np.testing.assert_allclose(cv[0], conv[b], atol=1e-6)


def one_by_one(w, x, idx, wt, cfg):
    """Each (token, chosen expert) pair computed alone: what no dispatch may lose."""
    out = np.zeros(x.shape, np.float32)
    for n in range(x.shape[0]):
        for e, g in zip(np.asarray(idx[n]), np.asarray(wt[n])):
            e = int(e) - cfg.expert_start
            if 0 <= e < cfg.local_experts:
                out[n] += g * np.asarray(jnp.square(jax.nn.relu(x[n] @ w["w_up"][e].T)) @ w["w_down"][e])
    return out


def test_every_token_routed_to_one_expert_loses_nothing(params):
    w = jax.tree.map(lambda a: a[0], params["moe"])
    x = jax.random.normal(jax.random.PRNGKey(5), (200, CFG.hidden_size))
    idx = jnp.tile(jnp.asarray([[2, 1]], jnp.int32), (200, 1))  # all 200 tokens at experts 2 and 1, two blocks each: no capacity
    wt = jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (200, 2))) + 0.1
    want = one_by_one(w, x, idx, wt, CFG)
    assert np.abs(want).min(axis=1).max() > 0
    valid = jnp.ones((200,), bool)
    np.testing.assert_allclose(nh.experts_grouped(params["moe"], 0, x, idx, wt, valid, CFG), want, atol=1e-4)
    np.testing.assert_allclose(nh.experts_dense(w, x, idx, wt, CFG), want, atol=1e-4)
    # the router's own choices, some of them held on the other chip; padding rows are in no group
    idx, wt = nh.route(w, x, CFG)
    assert (np.asarray(idx) >= CFG.local_experts).any() and (np.asarray(idx) < CFG.local_experts).any()
    want = one_by_one(w, x, idx, wt, CFG)
    np.testing.assert_allclose(nh.experts_dense(w, x, idx, wt, CFG), want, atol=1e-4)
    half = jnp.arange(200) < 24
    got = nh.experts_grouped(params["moe"], 0, x, idx, wt, half, CFG)
    np.testing.assert_allclose(got[:24], want[:24], atol=1e-4)
    assert not np.asarray(got[24:]).any()


def test_the_two_chips_shares_add_up_to_the_uncut_expert_layer():
    """Chip 0 holds experts 0-3, chip 1 holds 4-7; the routed parts of both, with what every chip
    computes alike (the shared expert) counted once, are the uncut 8-expert reference layer."""
    whole_c = {**C, "n_routed_experts": 8, "deployment": None}
    whole = family.program_config(whole_c, 128)
    group = jax.tree.map(lambda a: a[:1], jax.jit(lambda k: nh.init_params(whole, k))(jax.random.PRNGKey(11))["moe"])
    x = jax.random.normal(jax.random.PRNGKey(12), (40, whole.hidden_size))
    ref, _ = family._experts(x, group, 0, first=0, top_k=2, scale=2.5, norm=True, eps=1e-5)
    layer = jax.tree.map(lambda a: a[0], group)
    xn = nh.rms_norm(x, layer["norm"], 1e-5)
    idx, wt = nh.route(layer, xn, whole)
    total = nh._shared_expert(layer, xn)
    for chip in (0, 1):
        share = dataclasses.replace(whole, expert_start=4 * chip, num_local_experts=4)
        w = {**layer, "w_up": layer["w_up"][4 * chip:4 * chip + 4], "w_down": layer["w_down"][4 * chip:4 * chip + 4]}
        routed = nh.experts_grouped(jax.tree.map(lambda a: a[None], w), 0, xn, idx, wt, jnp.ones((40,), bool), share)
        assert np.abs(np.asarray(routed)).max() > 0
        total = total + routed
    np.testing.assert_allclose(x + total, ref, atol=1e-4)


def test_prefill_then_decode_through_the_engine_matches_the_reference(params):
    """Admission waves of batched same-bucket prefills at lengths off the bucket, more requests
    than slots (so slots are recycled), greedy and seeded, an abort in the middle, and before the
    second round every slot's old state and rows poisoned: all of it against the full forward."""
    eng = engine(params)
    lengths = (5, 19, 23, 40, 7, 33, 18, 61, 9)
    ps = prompts(1, lengths)
    sampling = [SamplingParams(max_tokens=10, temperature=0.0 if i % 3 else 0.8, top_p=0.95, seed=i, logprobs=True)
                for i in range(len(ps))]
    ids = [eng.add_request(p, sp) for p, sp in zip(ps, sampling)]
    finals, steps = {}, 0
    while eng.has_unfinished():
        steps += 1
        if steps == 4:
            assert eng.abort_request(ids[1])
        finals.update({o.request_id: o for o in eng.step() if o.finished})
    assert finals[ids[1]].finish_reason == "aborted" and len(finals[ids[1]].token_ids) < 10
    keep = [i for i in range(len(ps)) if i != 1]
    res = check(params, served([finals[ids[i]] for i in keep], [ps[i] for i in keep], [sampling[i] for i in keep]))
    assert res["ok"] and res["tokens"] == 80, res
    stats = eng.kv_cache_stats()
    assert stats["state_bytes_per_slot"] == family.state_bytes_per_slot(C, itemsize=4)
    assert stats["bytes_per_token"] == family.kv_bytes_per_token(C, itemsize=4)
    assert stats["state_allocated_bytes"] == 4 * stats["state_bytes_per_slot"] and eng.prefix_cache_stats() == {}
    # every slot has held a sequence by now: poison what they left, then serve again
    eng.state = jax.tree.map(lambda a: jnp.full_like(a, jnp.nan), eng.state)
    eng.cache = {**eng.cache, "k": jnp.full_like(eng.cache["k"], jnp.nan), "v": jnp.full_like(eng.cache["v"], 1e4)}
    ps2 = prompts(2, (31, 12, 50, 6, 17))
    sp2 = [SamplingParams(max_tokens=8, temperature=0.0, logprobs=True)] * len(ps2)
    res = check(params, served(eng.generate(ps2, sp2), ps2, sp2))
    assert res["ok"] and res["tokens"] == 40, res
    # the flight log's decode rows carry the routing counters of the drained step
    rows = [s for s in eng.telemetry()["steps"] if "experts_hit" in s]
    assert rows and all(0 < r["experts_hit"] <= 4 and r["moe_pairs_local"] <= r["moe_pairs_total"] for r in rows)
    assert all(r["moe_pairs_total"] % 2 == 0 and r["moe_max_load"] >= 1 for r in rows)
    assert any(s.get("state_insert_ms", 0) > 0 for s in eng.telemetry()["steps"])


def test_the_synchronous_loop_is_the_fused_steps_oracle(params):
    ps = prompts(3, (9, 30, 14, 47, 22))
    sp = SamplingParams(max_tokens=7, temperature=0.0, logprobs=True)
    a = engine(params).generate(ps, sp)
    b = engine(params, device_resident=False).generate(ps, sp)
    assert [o.token_ids for o in a] == [o.token_ids for o in b]
    np.testing.assert_allclose([o.logprobs for o in a], [o.logprobs for o in b], atol=1e-5)


@dataclasses.dataclass(frozen=True)
class Bf16State(nh.NemotronHConfig):
    """The same model with its recurrent state kept in bfloat16: the precision below the stated one."""

    def cache_spec(self):
        spec = super().cache_spec()
        shape, _, per = spec["mamba"]["ssm"]
        return {**spec, "mamba": {**spec["mamba"], "ssm": (shape, "bfloat16", per)}}


def test_the_comparison_fails_a_bfloat16_state_and_a_slot_that_is_not_reset(params):
    ps = prompts(4, (21, 38, 11, 27))
    sp = [SamplingParams(max_tokens=24, temperature=0.0, logprobs=True)] * len(ps)
    # (a) the state in bfloat16
    low = engine(params, cfg=Bf16State(**dataclasses.asdict(CFG)))
    assert low.state["ssm"].dtype == jnp.bfloat16
    res = check(params, served(low.generate(ps, sp), ps, sp))
    assert not res["ok"] and res["max_abs_dlogprob"] > TOL, res
    # (b) a recycled slot that keeps the last sequence's state: no insert at admission
    eng = engine(params)
    assert check(params, served(eng.generate(ps, sp), ps, sp))["ok"]
    eng._state_insert = lambda state, slot, row, new: state
    res = check(params, served(eng.generate(ps, sp), ps, sp))
    assert not res["ok"] and res["max_abs_dlogprob"] > 100 * TOL, res


@pytest.mark.parametrize("fault", ["padded_length", "dropped_token"])
def test_the_comparison_fails_a_state_at_the_padded_length_and_a_dropped_token(params, fault, monkeypatch):
    ps = prompts(6, (21, 38, 11, 27))  # none on a bucket: 32, 64, 16, 32
    sp = [SamplingParams(max_tokens=24, temperature=0.0, logprobs=True)] * len(ps)
    eng = engine(params)
    if fault == "padded_length":  # the recurrence run over the padding too
        real = eng._prefill

        def at_padded_length(params, toks, lens):
            logits, rows, _ = real(params, toks, lens)
            return logits, rows, real(params, toks, jnp.full_like(lens, toks.shape[1]))[2]

        eng._prefill = at_padded_length
    else:  # a capacity fault: one lane's token gets nothing from its experts, every decode step
        real = experts.experts_step
        monkeypatch.setattr(experts, "experts_step", lambda stacked, layer, x, idx, wt, active, c: real(stacked, layer, x, idx, wt.at[0].set(0.0), active, c))
    res = check(params, served(eng.generate(ps, sp), ps, sp))
    assert not res["ok"] and res["max_abs_dlogprob"] > 100 * TOL, res


def test_an_anchored_router_is_decisive_and_the_key_chooses_the_streams_dtype():
    """``router_anchor``: every token id's k-th score leads its (k+1)-th by about the anchor in
    every expert layer (with plain N(0, fan_in^-1/2) routers the two lie close), the routers'
    columns are orthonormal over all expert layers together, and program and reference still
    agree. The published ``residual_in_fp32`` chooses the stream's dtype."""
    def gaps(p):
        logits = jnp.einsum("vh,lhe->lve", p["embed"], p["moe"]["router"])
        top = jax.lax.top_k(logits, CFG.num_experts_per_tok + 1)[0]
        return np.asarray(top[..., -2] - top[..., -1])

    plain = gaps(nh.init_params(CFG, jax.random.PRNGKey(1)))
    anchored_cfg = family.program_config({**C, "init_router_anchor": 8.0}, 128, remat=False)
    assert anchored_cfg.router_anchor == 8.0
    p = nh.init_params(anchored_cfg, jax.random.PRNGKey(1))
    g = gaps(p)
    assert np.median(plain) < 1.0 and np.median(g) > 5.0 and (g > 1.0).mean() > 0.99, (np.median(plain), np.median(g))
    cols = np.asarray(p["moe"]["router"]).transpose(0, 2, 1).reshape(-1, CFG.hidden_size)
    np.testing.assert_allclose(cols @ cols.T, np.eye(len(cols)), atol=1e-5)
    toks = np.asarray(prompts(2, (29,)), np.int32)
    ref = family.reference_logprobs(p, toks[0], C, 0, 29)
    np.testing.assert_allclose(jax.nn.log_softmax(nh.forward(p, jnp.asarray(toks), anchored_cfg)[0], -1), ref, atol=1e-4)
    with pytest.raises(ValueError, match="orthogonal router columns"):
        nh.init_params(dataclasses.replace(anchored_cfg, hidden_size=16, n_groups=1, mamba_num_heads=2), jax.random.PRNGKey(0))
    # the stream's dtype: false (as published) is the weights' dtype, true is float32
    low = {**C, "torch_dtype": "bfloat16"}
    assert family.program_config(low, 128).stream_dtype == jnp.bfloat16
    wide = family.program_config({**low, "residual_in_fp32": True}, 128)
    assert wide.stream_dtype == jnp.float32 and wide.residual_in_fp32
    seen = []
    real = nh.rms_norm
    try:
        nh.rms_norm = lambda x, w, eps: (seen.append(x.dtype), real(x, w, eps))[1]
        for cfg_ in (family.program_config(low, 128), wide):
            jax.eval_shape(lambda pp, cfg_=cfg_: nh.forward(pp, jnp.zeros((1, 8), jnp.int32), cfg_),
                           jax.eval_shape(lambda: nh.init_params(cfg_, jax.random.PRNGKey(0))))
    finally:
        nh.rms_norm = real
    assert set(seen[:len(seen) // 2]) == {jnp.dtype("bfloat16")} and set(seen[len(seen) // 2:]) == {jnp.dtype("float32")}, seen


class _Anything:
    vocab_size = C["vocab_size"]


@pytest.mark.parametrize("kwargs, named", [
    ({"kv_layout": "paged"}, "kv_layout='paged'"),
    ({"cache_dtype": "int8"}, "cache_dtype='int8'"),
    ({"speculative": _Anything()}, "speculative decoding"),
    ({"kv_plane": _Anything(), "enable_prefix_caching": True}, "KV plane"),
    ({"mesh": "tp2"}, "tensor_parallel_size > 1"),
])
def test_what_the_hybrid_cannot_do_is_refused_at_construction_by_name(params, kwargs, named):
    if kwargs.get("mesh") == "tp2":
        from ray_tpu.parallel.mesh import create_mesh

        kwargs = {"mesh": create_mesh(tp=2, devices=jax.devices()[:2])}
    with pytest.raises(HybridModelUnsupportedError, match=named.replace("(", r"\(").replace(")", r"\)")):
        engine(params, **kwargs)


@pytest.mark.parametrize("call, named", [
    (lambda e: e.add_prefill_request([1, 2, 3]), "disaggregated prefill"),
    (lambda e: e.prefill_handoff([1, 2, 3]), "disaggregated prefill"),
    (lambda e: e.prefill_remote([1, 2, 3]), "disaggregated prefill"),
    (lambda e: e.add_prefilled([1, 2, 3], {}), "transferred KV block"),
    (lambda e: e.checkpoint_request("r"), "migration"),
    (lambda e: e.restore_request({}), "migration"),
    (lambda e: e.suspend_request("r"), "suspend"),
    (lambda e: e.resume_suspended("r"), "suspend"),
    (lambda e: e.adopt_prefetched([1, 2, 3], None, None), "KV plane"),
])
def test_moving_a_sequence_is_refused_at_the_call_by_name(params, call, named):
    eng = engine(params, enable_prefix_caching=True)  # the default: off for a hybrid, and said once
    assert eng._prefix_cache is None and eng.prefix_cache_stats() == {}
    with pytest.raises(HybridModelUnsupportedError, match=named):
        call(eng)


def test_serves_through_the_openai_server_streaming(params):
    """LLMConfig(model_config=<the hybrid config>) through OpenAIServer: the normal serving path."""
    from ray_tpu.serve.llm import LLMConfig, OpenAIServer

    srv = OpenAIServer(LLMConfig(model_config=CFG, params=params, model_id="toy-hybrid",
                                 engine_kwargs={"max_num_seqs": 4, "max_seq_len": 128, "prefill_buckets": (16, 32, 64)}))
    try:
        assert type(srv.engine._fused_step).__name__ and srv.engine._hybrid and srv.engine._device_resident
        p = prompts(5, (26,))[0]
        chunks = list(srv({"prompt": p, "max_tokens": 6, "stream": True}))
        assert chunks[-1].startswith("data: [DONE]") and len(chunks) >= 7
        out = srv.generate(p, {"max_tokens": 6, "logprobs": True})
        sp = [SamplingParams(max_tokens=6)]
        assert check(params, [{"prompt": p, "tokens": out["token_ids"], "logprobs": out["logprobs"], "greedy": True}])["ok"]
        assert sp
    finally:
        srv.shutdown()


def _second_description():
    """The other description over the same step programs (``models/qwen3_next.py``), at toy widths."""
    from ray_tpu.models import qwen3_next as qn

    cfg = qn.Qwen3NextConfig.tiny(vocab_size=C["vocab_size"])
    return cfg, jax.jit(lambda k: qn.init_params(cfg, k))(jax.random.PRNGKey(3))


@pytest.mark.parametrize("kwargs, named", [
    ({"kv_layout": "paged"}, "kv_layout='paged'"),
    ({"cache_dtype": "int8"}, "cache_dtype='int8'"),
    ({"speculative": _Anything()}, "speculative decoding"),
    ({"kv_plane": _Anything(), "enable_prefix_caching": True}, "KV plane"),
])
def test_every_refusal_at_construction_holds_for_the_second_description_and_says_what_it_holds(kwargs, named):
    cfg, p = _second_description()
    with pytest.raises(HybridModelUnsupportedError, match=named.replace("(", r"\(").replace(")", r"\)")) as e:
        engine(p, cfg=cfg, **kwargs)
    assert "Qwen3NextConfig: 3 x gdn, 5 x moe, 2 x attn" in str(e.value)  # the description says what kinds it holds
    with pytest.raises(HybridModelUnsupportedError, match=r"NemotronHConfig: 3 x mamba, 3 x moe, 2 x attn"):
        engine(None, **kwargs)


@pytest.mark.parametrize("call, named", [
    (lambda e: e.add_prefill_request([1, 2, 3]), "disaggregated prefill"),
    (lambda e: e.add_prefilled([1, 2, 3], {}), "transferred KV block"),
    (lambda e: e.checkpoint_request("r"), "migration"),
    (lambda e: e.suspend_request("r"), "suspend"),
    (lambda e: e.adopt_prefetched([1, 2, 3], None, None), "KV plane"),
])
def test_moving_a_sequence_is_refused_for_the_second_description_too(call, named):
    cfg, p = _second_description()
    eng = engine(p, cfg=cfg, enable_prefix_caching=True)
    assert eng._prefix_cache is None and eng.prefix_cache_stats() == {} and set(eng.state) == {"S", "conv"}
    with pytest.raises(HybridModelUnsupportedError, match=named):
        call(eng)


def _third_description():
    """A description with no recurrent layer at all (``models/glm4_moe_lite.py``: latent attention,
    a dense layer, experts), at toy widths."""
    from ray_tpu.models import glm4_moe_lite as glm

    cfg = glm.Glm4MoeLiteConfig.tiny(vocab_size=C["vocab_size"])
    return cfg, jax.jit(lambda k: glm.init_params(cfg, k))(jax.random.PRNGKey(5))


@pytest.mark.parametrize("kwargs, named", [
    ({"kv_layout": "paged"}, "kv_layout='paged'"),
    ({"cache_dtype": "int8"}, "cache_dtype='int8'"),
    ({"speculative": _Anything()}, "speculative decoding"),
    ({"kv_plane": _Anything(), "enable_prefix_caching": True}, "KV plane"),
    ({"mesh": "tp2"}, "tensor_parallel_size > 1"),
])
def test_every_refusal_at_construction_holds_for_the_latent_description_and_is_worded_truly(params, kwargs, named):
    """The third description keeps NO state per sequence: its refusal says what it does keep (a
    latent and a rotated key per position, not keys and values by head) and speaks of no
    recurrent layer; the descriptions that have recurrent layers are still told so, by entry."""
    if kwargs.get("mesh") == "tp2":
        from ray_tpu.parallel.mesh import create_mesh

        kwargs = {"mesh": create_mesh(tp=2, devices=jax.devices()[:2])}
    cfg, p = _third_description()
    with pytest.raises(HybridModelUnsupportedError, match=named.replace("(", r"\(").replace(")", r"\)")) as e:
        engine(p, cfg=cfg, **kwargs)
    said = str(e.value)
    assert "Glm4MoeLiteConfig: 4 x mla, 1 x ffn, 3 x moe" in said and "keep c_kv and k_r per position" in said and "recurrent" not in said
    with pytest.raises(HybridModelUnsupportedError, match=r"its recurrent layers keep a state per sequence \(conv, ssm\)") as e:
        engine(params, **kwargs)
    assert "per position" not in str(e.value)


@pytest.mark.parametrize("call, named", [
    (lambda e: e.add_prefill_request([1, 2, 3]), "disaggregated prefill"),
    (lambda e: e.prefill_remote([1, 2, 3]), "disaggregated prefill"),
    (lambda e: e.add_prefilled([1, 2, 3], {}), "transferred KV block"),
    (lambda e: e.checkpoint_request("r"), "migration"),
    (lambda e: e.restore_request({}), "migration"),
    (lambda e: e.suspend_request("r"), "suspend"),
    (lambda e: e.resume_suspended("r"), "suspend"),
    (lambda e: e.adopt_prefetched([1, 2, 3], None, None), "KV plane"),
])
def test_moving_a_sequence_is_refused_for_the_latent_description_and_is_worded_truly(call, named):
    cfg, p = _third_description()
    eng = engine(p, cfg=cfg, enable_prefix_caching=True)  # off for a description, and said once
    assert eng._prefix_cache is None and eng.prefix_cache_stats() == {} and eng.state == {} and set(eng.cache) == {"c_kv", "k_r", "length"}
    with pytest.raises(HybridModelUnsupportedError, match=named) as e:
        call(eng)
    assert "keep c_kv and k_r per position" in str(e.value) and "recurrent" not in str(e.value)


def test_neither_the_runner_nor_the_engine_names_a_model_or_a_kind_of_layer():
    """ROADMAP C1 after PR 36: three descriptions over one loop. What a kind of layer is called,
    computes and keeps comes from the description; the step programs and the engine ask it."""
    import re

    from ray_tpu.llm import engine as engine_module
    from ray_tpu.llm import hybrid_runner

    for module in (hybrid_runner, engine_module):
        with open(module.__file__) as f:
            text = f.read()
        found = re.findall(r"nemotron|qwen|glm|mamba|gdn|deltanet|\"attn\"|\"moe\"|\"mla\"|'moe'|'attn'|c_kv|k_r\b", text, flags=re.IGNORECASE)
        assert not found, (module.__name__, found)


# ---------------------------------------------------------------------------
# PR 37: a decode step's routed experts are a loop over the held experts that a BOUND lane chose
# (``experts.experts_step``), held to ``experts_dense`` for each description's expert layer
# ---------------------------------------------------------------------------
@functools.cache
def _expert_layer_of(name):
    """(a config object for ``experts``, its expert layers' stacked weights [layers, ...]) at toy
    widths: each description's own ``ExpertLayer`` as chip 0 of two (experts 0-3 of 8); GLM's
    description holds every expert (as its cell does), so its layer is cut to the same share."""
    import importlib
    from types import SimpleNamespace

    config = {"nemotron_h": "NemotronHConfig", "qwen3_next": "Qwen3NextConfig", "glm4_moe_lite": "Glm4MoeLiteConfig"}[name]
    tiny = getattr(importlib.import_module(f"ray_tpu.models.{name}"), config).tiny
    cfg = tiny() if name == "glm4_moe_lite" else tiny(num_local_experts=4)
    stacked = jax.jit(cfg.init_params)(jax.random.PRNGKey(3))["moe"]
    s = cfg.expert_layer
    if s.held == s.num_experts:
        s = dataclasses.replace(s, local_experts=s.num_experts // 2)
        stacked = {n: a[:, :s.held] if n in s.matrices else a for n, a in stacked.items()}
    assert (s.held, s.num_experts) == (4, 8) and stacked["w_up"].shape[:2] == (cfg.count("moe"), 4) and cfg.count("moe") >= 2
    return SimpleNamespace(expert_layer=s, hidden_size=cfg.hidden_size), stacked


@pytest.mark.parametrize("case", ["no_lane_active", "one_lane", "every_held_expert_hit", "all_choices_on_another_chip", "unbound_lanes_hold_garbage"])
@pytest.mark.parametrize("form", ["loop", "kernel"])
@pytest.mark.parametrize("name", ["nemotron_h", "qwen3_next", "glm4_moe_lite"])
def test_the_step_form_reads_the_experts_its_bound_lanes_hit_and_equals_the_dense_form(name, form, case, monkeypatch):
    """Both forms of ``experts_step``: the loop that runs off the TPU, and the TPU's kernel run by
    the Pallas interpreter (asked for by swapping its gate, as ``tests/test_slot_attention.py`` does)."""
    from ray_tpu.ops import step_experts

    assert "backend 'cpu'" in step_experts.refusal(jnp.bfloat16, 2688, 1856, 2)
    c, stacked = _expert_layer_of(name)
    if form == "kernel":  # with a buffer so small that an expert's 32 rows go through in two tiles of 16
        monkeypatch.setattr(step_experts, "refusal", lambda *a: None)
        monkeypatch.setattr(step_experts, "_TILE_BYTES", 3 * c.hidden_size * 4 * 16)
        assert step_experts.tile_rows(stacked["w_up"].shape[2], c.hidden_size, len(c.expert_layer.matrices), 4) == 16 < stacked["w_up"].shape[2]
    s, layer, B = c.expert_layer, 1, 8
    w = jax.tree.map(lambda a: a[layer], stacked)
    x = jax.random.normal(jax.random.PRNGKey(21), (B, c.hidden_size))
    idx, wt = experts.route(w, x, c)
    active = jnp.ones((B,), bool)
    if case == "no_lane_active":
        active = jnp.zeros((B,), bool)
    elif case == "one_lane":  # the last lane that chose an expert held here
        lane = int(np.flatnonzero(((np.asarray(idx) >= s.expert_start) & (np.asarray(idx) < s.expert_start + s.held)).any(axis=1))[-1])
        active = jnp.arange(B) == lane
    elif case == "every_held_expert_hit":  # lane b chooses held experts b*k .. b*k+k-1 (mod held): 8 lanes cover all of them
        idx = s.expert_start + (jnp.arange(B * s.top_k, dtype=jnp.int32) % s.held).reshape(B, s.top_k)
    elif case == "all_choices_on_another_chip":
        idx = (s.expert_start + s.held + idx % (s.num_experts - s.held)).astype(jnp.int32)
    else:
        active = jnp.arange(B) % 2 == 0
    step = jax.jit(lambda x, idx, wt, active: experts.experts_step(stacked, layer, x, idx, wt, active, c))
    got, read = step(x, idx, wt, active)
    want = experts.experts_dense(w, x, idx, wt * active[:, None], c)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    chosen = (np.asarray(idx) - s.expert_start)[np.asarray(active)]
    assert int(read) == len(np.unique(chosen[(chosen >= 0) & (chosen < s.held)])), "the walk's length is the held experts that a bound lane chose"
    if case in ("no_lane_active", "all_choices_on_another_chip"):
        assert int(read) == 0 and not np.asarray(got).any()
    elif case == "every_held_expert_hit":
        assert int(read) == s.held and np.abs(np.asarray(got)).min(axis=1).max() > 0
    elif case == "one_lane":
        assert 1 <= int(read) <= s.top_k and not np.asarray(got)[np.arange(B) != lane].any() and np.abs(np.asarray(got)[lane]).max() > 0
    else:  # what an unbound lane holds, and so where its garbage routes, changes nothing for the bound ones
        garbage = jnp.where(active[:, None], x, 1e4 * jax.random.normal(jax.random.PRNGKey(22), x.shape))
        idx2, wt2 = experts.route(w, garbage, c)
        assert (np.asarray(idx2) != np.asarray(idx)).any()
        got2, read2 = step(garbage, idx2, wt2, active)
        np.testing.assert_array_equal(np.asarray(got2)[::2], np.asarray(got)[::2])
        assert int(read2) == int(read) > 0 and not np.asarray(got2)[1::2].any()


def test_every_decode_row_of_the_flight_log_read_the_experts_it_hit(params):
    """``experts_read`` beside ``experts_hit`` in a step row (``hybrid_runner.MOE_STATS``): means
    over the expert layers of the held experts whose weights the step read and that got a token.
    The step loops over the experts hit, so the two are equal in every decode row."""
    eng = engine(params)
    ps = prompts(8, (12, 30, 7, 21, 44, 9))
    eng.generate(ps, [SamplingParams(max_tokens=6 + 3 * i, temperature=0.0) for i in range(len(ps))])
    rows = [s for s in eng.telemetry()["steps"] if "experts_hit" in s]
    assert len(rows) >= 10 and all(r["experts_read"] == r["experts_hit"] for r in rows)
    assert len({r["experts_read"] for r in rows}) > 1 and all(0 < r["experts_read"] <= CFG.expert_layer.held for r in rows)
