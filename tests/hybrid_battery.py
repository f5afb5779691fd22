"""What every description over the ONE hybrid loop (``models/hybrid.py``, ``llm/hybrid_runner.py``)
is held to, written once: the sequence form against the family's plain reference, prefill then
decode through the engine against it, a seeded lane's stream the same alone and beside others, the
comparison failing each planted fault, the OpenAI server streaming, every refusal by its name.

pytest does not collect this module. A description's file states a ``Description`` as ``DESC``,
keeps its module-scoped ``params`` fixture and does ``from hybrid_battery import *``: the tests
below are then collected there, against that description, beside what is truly the file's own.
A fourth description costs a ``DESC``, not a file of copies. ``eng`` is ONE engine a module at the
default arguments: for a test that leaves it as it found it, or plants its fault on the engine
object through ``monkeypatch``; a test that patches a module before the step programs are traced,
or needs other arguments, builds its own with ``engine(...)``.

An engine is what a test pays for (three prefill buckets and a fused step traced and compiled: 7-20 s
for a model of a few layers), so a test builds the LEAST one that shows its point, and a file's cost
is the count of its engines. The rule for a planted fault (``Fault``), cheapest first:
(a) a fault that can be said in the WEIGHTS the engine serves, or on the engine object, is planted on
the module's ``eng`` (``with_params``, ``slot_not_reset``, ``padded_length``): nothing is traced, the
case costs its four prompts and the reference's run, about a second;
(b) a fault in TRACED code or in the description (``patched``, ``bf16_state``, a window one wider) needs
programs of its own: it builds ``least_engine``, ONE bucket that holds all four prompts, so one
prefill program and one fused step. Write a new fault under (a) wherever its meaning allows.
The clean round is served and checked once a module (``clean_round``), not once a fault."""

import dataclasses
import re
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference
from ray_tpu.exceptions import HybridModelUnsupportedError
from ray_tpu.llm import LLMEngine, SamplingParams
from ray_tpu.models import experts, hybrid
from ray_tpu.ops import grouped_experts

ENGINE_KW = {"max_num_seqs": 4, "max_seq_len": 128, "prefill_buckets": (16, 32, 64)}


class Fault(NamedTuple):
    """A mistake the comparison must catch. ``plant(desc, params, eng, monkeypatch)`` returns the
    engine to serve with; the served log-probabilities are then off the reference's by more than
    ``over`` tolerances (``margin``: or the reference's top-1 leads the served token by as much).
    Two ways to plant one, cheapest first (the module's docstring): (a) on the module's ``eng``,
    which ``plant`` then returns, for a fault in the weights served (``with_params``) or in what the
    engine object does between its programs (``monkeypatch.setattr(eng, ...)``); (b) on a
    ``least_engine`` of its own, only where the fault is in traced code or in the description."""
    plant: Callable
    over: float = 1.0
    margin: bool = False


@dataclasses.dataclass(frozen=True)
class Description:
    family: Any  # benchmark/families/<name>.py: the plain reference and the configuration file's side
    c: dict  # the family's rehearsal configuration
    cfg: Any  # the program's description of the same model
    tol: float  # where the comparison fails
    agrees_to: float  # what program and reference agree to, in a log-probability
    state_bytes_per_slot: int
    kv_bytes_per_token: int  # as the chip stores a position
    poison: dict  # cache entry -> what a finished sequence's rows are overwritten with before the second round
    faults: dict  # name -> Fault
    refusal_says: tuple  # what a refusal says of this description ...
    refusal_says_not: tuple  # ... and what it must not
    shares: tuple = ()  # (the family's key for the expert count, chips, the reference layer's keywords)


def prompts(desc, seed, lengths):
    rs = np.random.RandomState(seed)
    return [[int(t) for t in rs.randint(1, desc.c["vocab_size"] - 1, size=n)] for n in lengths]


def engine(cfg, params, **kw):
    return LLMEngine(cfg, params, **{**ENGINE_KW, **kw})


def least_engine(cfg, params):
    """An engine for a fault that needs programs of its own: ONE bucket, which all four prompts of the
    fault test pad to in one group, so one prefill program and one fused step are compiled, not three and one."""
    return engine(cfg, params, prefill_buckets=(64,))


def served(outs, ps, sampling):
    return [{"prompt": p, "tokens": o.token_ids, "logprobs": o.logprobs, "greedy": sp.temperature == 0.0}
            for o, p, sp in zip(outs, ps, sampling)]


def check(desc, params, samples):
    return reference.check_served(desc.family.reference_logprobs, params, desc.c, samples, desc.tol)


def steps_after(eng, mark):
    """The flight log's step rows since ``eng.telemetry()["step_count"]`` was ``mark``."""
    return [s for s in eng.telemetry()["steps"] if s["step"] > mark]


@pytest.fixture(scope="module")
def desc(request):
    return request.module.DESC


@pytest.fixture(scope="module")
def eng(desc, params):
    return engine(desc.cfg, params)


def pytest_generate_tests(metafunc):
    if "fault" in metafunc.fixturenames:
        metafunc.parametrize("fault", list(metafunc.module.DESC.faults), indirect=True)


@pytest.fixture
def fault(request, desc):
    return desc.faults[request.param]


# ------------------------------------------------------------------ the program against the reference
def test_sequence_forward_matches_the_reference(desc, params):
    toks = np.asarray(prompts(desc, 0, (37, 37)), np.int32)  # 37: whole chunks of 8 and a rest
    logits = hybrid.forward(params, jnp.asarray(toks), desc.cfg)
    for b in range(2):
        ref = desc.family.reference_logprobs(params, toks[b], desc.c, 0, 37)
        np.testing.assert_allclose(jax.nn.log_softmax(logits[b], -1), ref, atol=desc.agrees_to)


def test_prefill_then_decode_through_the_engine_matches_the_reference(desc, params, eng):
    """Admission waves of batched same-bucket prefills at lengths off the bucket and off the chunk,
    more requests than slots (so slots are recycled), greedy and seeded, an abort in the middle, and
    before the second round every slot's old state and rows poisoned: all of it against the
    reference's full forward."""
    s, mark = desc.cfg.expert_layer if desc.cfg.routing_layers else None, eng.telemetry()["step_count"]
    lengths = (5, 19, 23, 40, 7, 33, 18, 61, 9)
    ps = prompts(desc, 1, lengths)
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
    res = check(desc, params, served([finals[ids[i]] for i in keep], [ps[i] for i in keep], [sampling[i] for i in keep]))
    assert res["ok"] and res["tokens"] == 80 and res["max_abs_dlogprob"] < desc.agrees_to, res
    stats = eng.kv_cache_stats()
    assert stats["state_bytes_per_slot"] == desc.state_bytes_per_slot and stats["bytes_per_token"] == desc.kv_bytes_per_token
    assert stats["state_allocated_bytes"] == 4 * desc.state_bytes_per_slot and eng.prefix_cache_stats() == {}
    assert all(a.dtype == jnp.float32 for a in eng.state.values())
    # every slot has held a sequence by now: poison what they left, then serve again
    eng.state = jax.tree.map(lambda a: jnp.full_like(a, jnp.nan), eng.state)
    eng.cache = {**eng.cache, **{name: jnp.full_like(eng.cache[name], value) for name, value in desc.poison.items()}}
    ps2 = prompts(desc, 2, (31, 12, 50, 6, 17))
    sp2 = [SamplingParams(max_tokens=8, temperature=0.0, logprobs=True)] * len(ps2)
    res = check(desc, params, served(eng.generate(ps2, sp2), ps2, sp2))
    assert res["ok"] and res["tokens"] == 40, res
    # the flight log: decode rows carry the routing counters of the drained step, admitting rows the prefills' five
    new = steps_after(eng, mark)
    admitting = [r for r in new if r.get("admitted")]
    assert admitting and all("prefill_tokens" in r for r in admitting)
    assert all(0 < r["prefill_tokens"] <= r["prefill_tokens_padded"] and r["prefill_tokens_padded"] % 16 == 0 for r in admitting)
    assert sum(r["prefill_tokens"] for r in admitting) == sum(lengths) + sum(len(p) for p in ps2)
    assert not any("prefill_tokens" in r for r in new if not r.get("admitted"))
    assert not eng.state or any(r.get("state_insert_ms", 0) > 0 for r in new)
    if s is None:  # a description that routes nothing: no decode row carries a routing counter, no prefill served a pair
        assert not any("experts_hit" in r for r in new) and all(r["prefill_moe_pairs_local"] == 0 for r in admitting)
        return
    rows = [r for r in new if "experts_hit" in r]
    assert rows and all(0 < r["experts_hit"] <= s.held and r["moe_pairs_local"] <= r["moe_pairs_total"] for r in rows)
    assert all(r["moe_pairs_total"] % s.top_k == 0 and r["moe_max_load"] >= 1 for r in rows)
    assert s.held < s.num_experts or all(r["moe_pairs_local"] == r["moe_pairs_total"] for r in rows), "every expert is held here"
    for r in admitting:
        assert 0 < r["prefill_moe_pairs_local"] <= r["moe_rows_computed"] and r["prefill_moe_pairs_local"] <= s.top_k * r["prefill_tokens"]
        assert 0 < r["prefill_experts_hit"] <= s.held and r["moe_rows_kernel"] == 0  # off the TPU the loop runs the blocks


def test_a_seeded_lane_draws_one_stream_alone_and_beside_others_in_a_recycled_slot(desc, params, eng):
    """Lanes are independent under sampling: a request with ``seed=`` and a temperature draws the
    same stream served alone and served again behind more requests than there are slots, so that it
    is admitted into a slot another sequence left while its neighbours, greedy and stochastic, are
    in the middle of theirs. Its key starts at the seed and advances with its own tokens alone."""
    p, others = prompts(desc, 3, (22,))[0], prompts(desc, 6, (9, 30, 14, 47, 25, 12))
    sp = SamplingParams(max_tokens=9, temperature=0.9, top_p=0.95, seed=41, logprobs=True)
    alone = eng.generate(p, sp)
    sps = [SamplingParams(max_tokens=4 + 3 * i, temperature=0.7 * (i % 2), seed=i) for i in range(len(others))]
    ids = [eng.add_request(q, s) for q, s in zip(others[:5] + [p] + others[5:], sps[:5] + [sp] + sps[5:])]
    beside, neighbours = None, 0
    while eng.has_unfinished():
        for o in eng.step():
            if o.request_id == ids[5] and o.finished:
                beside = o
        st = eng._requests.get(ids[5])
        if st is not None and st.slot >= 0:
            neighbours = max(neighbours, eng.num_running - 1)
    assert neighbours >= 2, "the lane never ran beside others"  # and its slot was another's: the first four filled all four
    assert beside.token_ids == alone.token_ids and len(set(alone.token_ids)) > 1
    np.testing.assert_allclose(beside.logprobs, alone.logprobs, atol=desc.tol / 100)
    assert check(desc, params, served([beside], [p], [sp]))["ok"]


def test_prompts_that_leave_whole_slabs_empty_are_served_what_the_reference_gives(desc, params, monkeypatch):
    """PR 54: a serving prefill's dense FFN runs the slabs of positions under a row's true length and writes
    zeros past them (``ops/layers.live_slabs``). In slabs of 16 three prompts and a padding row in ONE bucket
    of 64 leave nine of their sixteen slabs empty; the zeros go on through every later layer's mixer, and what
    is served is still the reference's. The admitting row counts the positions such a sub-block runs: every
    padded position for a description that has no dense layer, whose programs compute them all."""
    from ray_tpu.ops import layers

    monkeypatch.setattr(layers, "LIVE_SLAB", 16)
    lengths = [17, 40, 33]
    ps = prompts(desc, 54, lengths)
    sampling = [SamplingParams(max_tokens=4, logprobs=True), SamplingParams(max_tokens=5, temperature=0.9, top_k=12, seed=31, logprobs=True),
                SamplingParams(max_tokens=4, logprobs=True)]
    eng = engine(desc.cfg, params, prefill_buckets=(64,))
    res = check(desc, params, served(eng.generate(ps, sampling), ps, sampling))
    assert res["ok"] and res["tokens"] == 13 and res["max_abs_dlogprob"] < desc.agrees_to, res
    (row,) = [r for r in eng.telemetry()["steps"] if r.get("admitted")]
    slabbed = bool({"ffn", "mlp"} & set(desc.cfg.layer_kinds))  # the dense SwiGLU over ``live_slabs``, under the kind's name in the description
    assert (row["prefill_tokens"], row["prefill_tokens_padded"], row["prefill_rows_live"]) == (sum(lengths), 4 * 64, 32 + 48 + 48 + 16 if slabbed else 4 * 64)


def test_a_prefill_whose_blocks_the_kernel_runs_is_served_what_the_reference_gives_and_counts_their_rows(desc, params, monkeypatch):
    """PR 57: where a small expert expects less than two blocks' rows of a prefill, a TPU runs the grouped matmul's
    blocks as one kernel (``ops/grouped_experts.py``). The test answers for its ``refusal`` before a fresh engine
    traces its programs, the same body runs interpreted, and what is served is the reference's; every admitting
    row then carries ``moe_rows_kernel`` equal to ``moe_rows_computed``. The least engine that shows it: one bucket
    and a slot a prompt, so the five prompts are ONE prefill program's rows and one fused step's lanes."""
    if not desc.cfg.routing_layers:  # nothing is routed: no program of this description holds the layer
        return
    monkeypatch.setattr(grouped_experts, "refusal", lambda *a: None)
    ps = prompts(desc, 56, (21, 38, 11, 27, 50))
    sp = [SamplingParams(max_tokens=6, temperature=0.0, logprobs=True)] * len(ps)
    eng = engine(desc.cfg, params, prefill_buckets=(64,), max_num_seqs=8)
    res = check(desc, params, served(eng.generate(ps, sp), ps, sp))
    assert res["ok"] and res["tokens"] == 30 and res["max_abs_dlogprob"] < desc.agrees_to, res
    admitting = [r for r in eng.telemetry()["steps"] if r.get("admitted")]
    assert admitting and all(r["moe_rows_kernel"] == r["moe_rows_computed"] > 0 for r in admitting)


def test_every_decode_row_of_the_flight_log_read_the_experts_it_hit(desc, eng):
    """``experts_read`` beside ``experts_hit`` in a step row (``hybrid_runner.MOE_STATS``): means
    over the expert layers of the held experts whose weights the step read and that got a token.
    The step loops over the experts hit, so the two are equal in every decode row."""
    mark = eng.telemetry()["step_count"]
    ps = prompts(desc, 8, (12, 30, 7, 21, 44, 9))
    eng.generate(ps, [SamplingParams(max_tokens=6 + 3 * i, temperature=0.0) for i in range(len(ps))])
    rows = [r for r in steps_after(eng, mark) if "experts_hit" in r]
    if not desc.cfg.routing_layers:  # nothing is routed: the rows say nothing of experts
        assert not rows and len(steps_after(eng, mark)) >= 10
        return
    assert len(rows) >= 10 and all(r["experts_read"] == r["experts_hit"] for r in rows)
    assert len({r["experts_read"] for r in rows}) > 1 and all(0 < r["experts_read"] <= desc.cfg.expert_layer.held for r in rows)


# ------------------------------------------------------------------------------ the planted faults
def with_params(change):
    """(a) A fault in the WEIGHTS the engine serves (the reference keeps the true ones): planted on the module's engine, no program is traced anew."""
    def plant(desc, params, eng, monkeypatch):
        monkeypatch.setattr(eng, "params", change(params))
        return eng
    return plant


def in_kind(params, kind, **new):
    """``params`` with the entries ``new`` of one kind of layer replaced."""
    return {**params, kind: {**params[kind], **new}}


def slot_not_reset(desc, params, eng, monkeypatch):
    """(a) A recycled slot keeps the last sequence's state (``clean_round`` left one in every slot): no insert at admission."""
    monkeypatch.setattr(eng, "_state_insert", lambda state, slot, row, new: state)
    return eng


def padded_length(desc, params, eng, monkeypatch):
    """(a) The recurrence run over the padding too: the state at the bucket's length, not the prompt's."""
    real = eng._prefill

    def at_padded_length(params, toks, lens):
        logits, rows, _ = real(params, toks, lens)
        return logits, rows, real(params, toks, jnp.full_like(lens, toks.shape[1]))[2]

    monkeypatch.setattr(eng, "_prefill", at_padded_length)
    return eng


def bf16_state(kind, entry):
    """(b) The recurrent state kept in bfloat16: the precision below the stated one."""
    def plant(desc, params, eng, monkeypatch):
        @dataclasses.dataclass(frozen=True)
        class Bf16State(type(desc.cfg)):
            def cache_spec(self):
                spec = super().cache_spec()
                shape, _, per = spec[kind][entry]
                return {**spec, kind: {**spec[kind], entry: (shape, "bfloat16", per)}}

        low = least_engine(Bf16State(**dataclasses.asdict(desc.cfg)), params)
        assert low.state[entry].dtype == jnp.bfloat16
        return low
    return plant


def patched(module, name, wrap):
    """(b) A fault in ``module.name``, planted before a fresh engine traces its step programs: ``wrap(real)`` is what stands there."""
    def plant(desc, params, eng, monkeypatch):
        monkeypatch.setattr(module, name, wrap(getattr(module, name)))
        return least_engine(desc.cfg, params)
    return plant


@pytest.fixture(scope="module")
def clean_round(desc, params, eng):
    """The fault test's four prompts (none on a bucket: 32, 64, 16, 32, and all 64 in ``least_engine``; none on a
    chunk), their sampling, and the round served CLEAN on the module's engine and checked against the
    reference, once a module: what every planted fault is a departure from. It also leaves a finished
    sequence's state in every one of ``eng``'s four slots, which is what ``slot_not_reset`` leans on
    (a slot that was never held has zeros to keep, and keeping zeros is no fault): every fault case
    asks for this fixture, so the round has run before any fault is planted, whatever ran before."""
    ps = prompts(desc, 4, (21, 38, 11, 27))
    sp = [SamplingParams(max_tokens=24, temperature=0.0, logprobs=True)] * len(ps)
    return ps, sp, check(desc, params, served(eng.generate(ps, sp), ps, sp))


def test_the_comparison_fails_each_planted_fault(desc, params, eng, clean_round, fault, monkeypatch):
    ps, sp, clean = clean_round
    assert clean["ok"] and clean["tokens"] == 4 * 24, clean
    res = check(desc, params, served(fault.plant(desc, params, eng, monkeypatch).generate(ps, sp), ps, sp))
    off = max(res["max_abs_dlogprob"], res["max_margin"]) if fault.margin else res["max_abs_dlogprob"]
    assert not res["ok"] and off > fault.over * desc.tol, res


# -------------------------------------------------------------------------------------- the server
def test_serves_through_the_openai_server_streaming(desc, params):
    """LLMConfig(model_config=<the description>) through OpenAIServer: the normal serving path."""
    from ray_tpu.serve.llm import LLMConfig, OpenAIServer

    srv = OpenAIServer(LLMConfig(model_config=desc.cfg, params=params, model_id="toy-description", engine_kwargs=dict(ENGINE_KW)))
    try:
        assert srv.engine._hybrid
        p = prompts(desc, 5, (26,))[0]
        chunks = list(srv({"prompt": p, "max_tokens": 6, "stream": True}))
        assert chunks[-1].startswith("data: [DONE]") and len(chunks) >= 7
        out = srv.generate(p, {"max_tokens": 6, "logprobs": True})
        assert check(desc, params, [{"prompt": p, "tokens": out["token_ids"], "logprobs": out["logprobs"], "greedy": True}])["ok"]
    finally:
        srv.shutdown()


# ------------------------------------------------------------------------------------ the refusals
class _Anything:
    vocab_size = 512  # the rehearsal configurations' own


def _says(desc, error):
    said = str(error.value)
    assert all(words in said for words in desc.refusal_says) and not any(words in said for words in desc.refusal_says_not), said
    return said


@pytest.mark.parametrize("kwargs, named", [
    ({"kv_layout": "paged"}, "kv_layout='paged'"),
    ({"cache_dtype": "int8"}, "cache_dtype='int8'"),
    ({"speculative": _Anything()}, "speculative decoding"),
    ({"kv_plane": _Anything(), "enable_prefix_caching": True}, "KV plane"),
    ({"mesh": "tp2"}, "tensor_parallel_size > 1"),
])
def test_what_the_hybrid_cannot_do_is_refused_at_construction_by_name(desc, params, kwargs, named):
    """The refusal names what was asked, says what kinds of layer the description holds and what a
    sequence of it keeps (a state per sequence, a latent per position), and nothing it does not."""
    if kwargs.get("mesh") == "tp2":
        from ray_tpu.parallel.mesh import create_mesh

        kwargs = {"mesh": create_mesh(tp=2, devices=jax.devices()[:2])}
    with pytest.raises(HybridModelUnsupportedError, match=re.escape(named)) as e:
        engine(desc.cfg, params, **kwargs)
    assert f"{type(desc.cfg).__name__}: {desc.cfg.kinds_held}" in _says(desc, e)


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
def test_moving_a_sequence_is_refused_at_the_call_by_name(desc, eng, call, named):
    assert eng._prefix_cache is None and eng.prefix_cache_stats() == {}  # asked for by default, off for a description, and said once
    with pytest.raises(HybridModelUnsupportedError, match=named) as e:
        call(eng)
    _says(desc, e)


# ---------------------------------------------------------------- for the descriptions that are a share
def test_the_chips_shares_add_up_to_the_uncut_expert_layer(desc):
    """Each chip of the deployment holds an equal run of the experts; the routed parts of all of
    them, with what every chip computes alike (the shared expert) counted once, are the uncut
    reference layer. (Not in ``__all__``: for the descriptions that have expert-share fields.)"""
    key, chips, ref_kw = desc.shares
    whole = desc.family.program_config({**desc.c, key: 8, "deployment": None}, 128)
    s, each = whole.expert_layer, 8 // chips
    params = jax.jit(whole.init_params)(jax.random.PRNGKey(11))
    group = jax.tree.map(lambda a: a[:1], jiggled(params)["moe"])
    x = jax.random.normal(jax.random.PRNGKey(12), (40, whole.hidden_size))
    ref, _ = desc.family._experts(x, group, 0, first=0, top_k=s.top_k, **ref_kw)
    layer = jax.tree.map(lambda a: a[0], group)
    xn = whole.norm(x, layer["norm"])
    idx, wt = experts.route(layer, xn, whole)
    total = experts.shared_expert(layer, xn, s)
    for chip in range(chips):
        share = dataclasses.replace(whole, expert_start=each * chip, num_local_experts=each)
        w = {**layer, **{n: layer[n][each * chip:each * chip + each] for n in s.matrices}}
        # traced whole, a program a share: run op by op the layout's loops compile one at a time, each anew
        routed = jax.jit(lambda w, *a, share=share: experts.experts_grouped(w, 0, *a, share))(jax.tree.map(lambda a: a[None], w), xn, idx, wt, jnp.ones((40,), bool))
        assert np.abs(np.asarray(routed)).max() > 0
        total = total + routed
    np.testing.assert_allclose(x + total, ref, atol=1e-4)


PLACEMENTS = ("one_expert", "none_here", "a_block_and_one_more", "valid_ends_inside_a_block", "tall_blocks", "few_rows_an_expert", "in_slabs", "many_small_trips",
              "a_second_trip", "an_empty_tile", "full_blocks")
TRACE_READS = ("BLOCK", "TALL_FROM", "SLAB", "TILE", "SLAB_ROWS", "ALIGN")  # the constants of ``models/experts.py`` that a trace of ``_grouped`` reads, beside what ``out_plan`` answers


@pytest.fixture(scope="module")
def routed(desc, params):
    """What the placement's twenty-two cases share, made once a module: 300 tokens and the router's choice
    for them, the first expert layer's weights (the one-by-one oracle's), and ``grouped``:
    ``experts._grouped`` traced ONCE for each set of values its trace reads (who runs the blocks,
    ``TRACE_READS`` and the rows of a trip as the case has patched them), so the ten cases that patch
    nothing share two programs and only a case that changes the traced program traces one."""
    cfg, w = desc.cfg, jax.tree.map(lambda a: a[0], params["moe"])
    x = jax.random.normal(jax.random.PRNGKey(21), (300, cfg.hidden_size))
    # a layer whose routing is made elsewhere holds no router: the first one that any kind holds serves
    router = w if "router" in w else {"router": next(g["router"][0] for g in params.values() if isinstance(g, dict) and "router" in g)}
    idx, wt = experts.route(router, x, cfg)
    traced = {}

    def grouped(runs, *operands):
        reads = (runs, experts.out_plan(cfg.expert_layer)) + tuple(getattr(experts, name) for name in TRACE_READS)
        if reads not in traced:  # a jit of its own: one keyed by ``_grouped`` would hand back the program of other constants
            traced[reads] = jax.jit(lambda *a: experts._grouped(params["moe"], 0, *a, cfg))
        return traced[reads](*operands)

    return w, x, idx, wt, grouped


@pytest.mark.parametrize("runs", ["loop", "kernel"])
@pytest.mark.parametrize("case", PLACEMENTS)
def test_the_grouped_matmul_places_the_pairs_held_here_whatever_their_number(desc, params, routed, case, runs, monkeypatch):
    """The layout of ``experts._grouped`` follows the pairs held here, so its loops' lengths are
    data: every pair at ONE held expert (a run of many blocks); no pair held here (no trip, zeros
    out); an expert with exactly ``BLOCK`` pairs beside one with ``BLOCK + 1``; ``valid`` that ends
    inside a block; the tall blocks, which a call takes where it has ``TALL_FROM`` pairs AND an expert
    is expected to get two blocks' rows of them, and not where it expects fewer; a batch in slabs;
    slabs, tiles and trips a few rows long, so that every loop turns many times; tiles that hold
    more pairs than ``out_plan`` sized a trip for, as under expert parallelism a tile whose tokens all
    chose experts held here does (every pair held here: a second trip and more for every tile); a tile
    with no pair at all between two that have them (no trip); every expert a run of two blocks and
    more (the regime in which the kernel serves an expert of any size). Each against the
    pairs computed one by one, and the counters against their definition; with the blocks run by the
    loop, and by the kernel (``ops/grouped_experts.py``: the test answers for its ``refusal`` and the
    same body runs interpreted). (Not in ``__all__``: for the descriptions that route experts.)"""
    cfg, s = desc.cfg, desc.cfg.expert_layer
    w, x, idx, wt, grouped = routed
    N, k, El, B = 300, s.top_k, s.held, experts.BLOCK
    if runs == "kernel":
        monkeypatch.setattr(grouped_experts, "refusal", lambda *a: None)
    valid = np.ones((N,), bool)
    elsewhere = [e for e in range(s.num_experts) if not s.expert_start <= e < s.expert_start + El]
    if case == "one_expert":
        idx = jnp.full((N, k), s.expert_start + 1, jnp.int32)
    elif case == "none_here" and elsewhere:
        idx = jnp.full((N, k), elsewhere[0], jnp.int32)
    elif case == "none_here":
        valid[:] = False
    elif case == "a_block_and_one_more":
        rest = elsewhere[0] if elsewhere else s.expert_start + 2
        first, second = np.where(np.arange(N) < B, s.expert_start, rest), np.where(np.arange(N) < B + 1, s.expert_start + 1, rest)
        idx = jnp.asarray(np.stack([first, second] + [np.full((N,), rest)] * (k - 2), axis=1), jnp.int32)
    elif case == "valid_ends_inside_a_block":
        idx = jnp.asarray(np.asarray(idx) % 2 + s.expert_start)  # two experts, runs of more than a block
        valid[173:] = False
    elif case == "tall_blocks":  # 600 pairs over 8 published experts: 75 rows an expert, more than two blocks of 32
        monkeypatch.setattr(experts, "TALL_FROM", 64)
        monkeypatch.setattr(experts, "BLOCK", 32)
        B = 2 * 32
        assert N * k // s.num_experts >= B
    elif case == "few_rows_an_expert":  # the pairs for tall blocks, and an expert expects less than one: the blocks stay short
        monkeypatch.setattr(experts, "TALL_FROM", 64)
        assert N * k // s.num_experts < 2 * B
    elif case == "in_slabs":
        monkeypatch.setattr(experts, "SLAB_ROWS", 100)
    elif case in ("many_small_trips", "a_second_trip"):
        monkeypatch.setattr(experts, "SLAB", 16)
        monkeypatch.setattr(experts, "TILE", 8)
        monkeypatch.setattr(experts, "out_plan", lambda *_: 4)
        if case == "a_second_trip":  # the same program: every tile holds all its 8 k pairs, 2 k trips of it
            idx = jnp.asarray(np.asarray(idx) % El + s.expert_start)
    elif case == "an_empty_tile":
        valid[experts.TILE:2 * experts.TILE] = False
    elif case == "full_blocks":  # 300 x k pairs over the published experts are two blocks of 16 an expert and more
        monkeypatch.setattr(experts, "BLOCK", 16)
        B = 16
        assert N * k // s.num_experts >= 2 * B
    want = one_by_one(w, x, idx, np.where(valid[:, None], wt, 0.0), cfg)
    assert experts.blocks_plan(s, N, [params["moe"][n] for n in s.matrices]) == (B, runs == "kernel")
    if case == "in_slabs":  # the layer over three sequences of 100, the shared expert taken off again
        lengths = jnp.asarray([100, 100, 100])
        monkeypatch.setattr(experts, "route", lambda *_: (idx, wt))
        got, counters = experts.moe_seq(w, x.reshape(3, 100, -1), lengths, cfg, stacked=(params["moe"], 0))
        got = got.reshape(N, -1) - (experts.shared_expert(w, x, s) if s.shared else 0.0)
        sizes = None
        # the fourth counter is there in the programs whose blocks the kernel runs, and is the rows of the blocks it ran: all in use
        assert len(counters) == experts.seq_counters(cfg, params["moe"], N) == (4 if runs == "kernel" else 3) and counters[-1] == counters[2]
    else:
        got, sizes, rows = grouped(runs, x, idx, wt, jnp.asarray(valid))
        sizes = np.asarray(sizes)
        counters = [(sizes > 0).sum(), sizes.sum(), int(rows)]
    here = (np.asarray(idx) - s.expert_start)[valid]
    pairs = np.bincount(here[(here >= 0) & (here < El)], minlength=El)
    assert (np.abs(want).max() > 0) == (pairs.sum() > 0)
    np.testing.assert_allclose(got, want, atol=2e-4)
    assert counters[0] == (pairs > 0).sum() and counters[1] == pairs.sum()
    if sizes is not None:
        assert (sizes == pairs).all() and counters[2] == (-(-pairs // B)).sum() * B
    if case == "none_here":
        assert counters[2] == 0 and not np.asarray(got).any()
    if case == "a_block_and_one_more":
        assert pairs[0] == experts.BLOCK and pairs[1] == experts.BLOCK + 1


def one_by_one(w, x, idx, wt, cfg):
    """Each (token, chosen expert) pair computed alone, on the host: what no dispatch may lose."""
    s = cfg.expert_layer
    w, x, idx, wt = jax.tree.map(np.asarray, w), np.asarray(x), np.asarray(idx), np.asarray(wt)
    out = np.zeros(x.shape, np.float32)
    for n in range(x.shape[0]):
        for e, g in zip(idx[n], wt[n]):
            e = int(e) - s.expert_start
            if 0 <= e < s.held:
                up = x[n] @ w["w_up"][e].T
                if s.act == "relu2":
                    h = np.square(np.maximum(up, 0.0))
                else:
                    gate = x[n] @ w["w_gate"][e].T
                    h = (np.maximum(gate, 0.0) if s.act == "reglu" else gate / (1.0 + np.exp(-gate))) * up
                out[n] += g * (h @ w["w_down"][e])
    return out


def jiggled(params):
    """Norm weights off their initial 0 and 1, so that ``1 + w`` against a plain ``w`` shows."""
    def jig(path, a):
        if "norm" in str(path[-1]):
            return a + 0.1 * jax.random.normal(jax.random.PRNGKey(len(str(path))), a.shape, a.dtype)
        return a
    return jax.tree_util.tree_map_with_path(jig, params)


# what ``import *`` hands a description's file: the fixtures, the hook that parametrizes ``fault``, and every test but the share's
__all__ = ["desc", "eng", "clean_round", "routed", "fault", "pytest_generate_tests"] + [n for n in dir() if n.startswith("test_") and n not in ("test_the_chips_shares_add_up_to_the_uncut_expert_layer", "test_the_grouped_matmul_places_the_pairs_held_here_whatever_their_number")]
