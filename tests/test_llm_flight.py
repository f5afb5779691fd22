"""PR 24's serving instrumentation: step programs that say their names, the
stages inside a step, the flight log that holds a run and is written when
the replica stops, the boundary stamps along a request's way, and the
graceful ``serve.shutdown()`` that lets a replica write them.

Host-side and CPU-only: nothing here times the device, and no assert is
a speed (the 1.05x cost gate lives in tests/test_perf_smoke.py).
"""

import json
import os
import queue
import threading
import time

import pytest

jax = pytest.importorskip("jax")

from ray_tpu.llm import LLMEngine, SamplingParams, telemetry  # noqa: E402
from ray_tpu.llm.model_runner import (  # noqa: E402
    STEP_PROGRAM_NAMES,
    make_fused_fns,
    make_fused_paged_fns,
    named_jit,
)
from ray_tpu.models.llama import LlamaConfig  # noqa: E402
from ray_tpu.serve.llm import LLMConfig, LLMServer, OpenAIServer  # noqa: E402

CFG = LlamaConfig.tiny(dtype="float32", remat=False, max_seq_len=256)
STEP_STAGES = [telemetry.STAGES[name] for name in telemetry.TILED]


def _engine(**kw):
    kw.setdefault("max_num_seqs", 2)
    kw.setdefault("max_seq_len", 128)
    kw.setdefault("enable_prefix_caching", False)
    return LLMEngine(CFG, **kw)


def _server(cls=LLMServer, **engine_kwargs):
    engine_kwargs.setdefault("max_num_seqs", 4)
    engine_kwargs.setdefault("max_seq_len", 128)
    return cls(LLMConfig(model_config=CFG, engine_kwargs=engine_kwargs))


@pytest.fixture
def session(tmp_path, monkeypatch):
    """A session dir of this test's own: the flight log and ``load_flight`` both ask
    ``util.state.session_dir`` at call time."""
    from ray_tpu.util import state

    monkeypatch.setattr(state, "session_dir", lambda pid=None: str(tmp_path))
    return tmp_path


# ------------------------------------------------------------- program names
def _jitted(eng) -> dict:
    """attribute -> jitted handle, of every step program the engine holds."""
    names = ("_prefill", "_insert", "_decode", "_extend", "_fused_step", "_fused_attn", "_fused_append",
             "_verify_step", "_verify_attn", "_verify_append")
    return {n: getattr(eng, n) for n in names if hasattr(getattr(eng, n, None), "lower")}


@pytest.mark.parametrize("layout,per_token", [("slots", {"llm_fused_step"}), ("paged", {"llm_fused_paged_step", "llm_kv_append"})])
def test_step_programs_say_their_names(layout, per_token):
    eng = _engine(kv_layout=layout)
    fns = _jitted(eng)
    names = {n: f.__name__ for n, f in fns.items()}
    assert set(names.values()) <= STEP_PROGRAM_NAMES and not [v for v in names.values() if "unknown" in v]
    # the two words the trace readers go by: one `fused` program a decode step, `prefill` on prefill alone
    assert [v for v in names.values() if "fused" in v] == [next(iter(per_token - {"llm_kv_append"}))]
    assert [n for n, v in names.items() if "prefill" in v] == ["_prefill"]
    decode = {names[n] for n in ("_fused_step", "_fused_attn", "_fused_append") if n in names}
    assert decode == per_token
    # the lowered module, which is what the profiler's ``XLA Modules`` line and the compile cache show
    toks = jax.numpy.zeros((1, 64), "int32")
    assert "jit_llm_prefill" in eng._prefill.lower(eng.params, toks, jax.numpy.ones((1,), "int32")).as_text()[:400]
    # the recompile sentinel still finds every fused entry it watched before
    eng.generate([[1, 2, 3]], SamplingParams(max_tokens=3))
    watched = {name for name, (fn, warm) in eng._tel.recorder._entries.items() if warm}
    assert {n.lstrip("_") for n in names if n.startswith("_fused")} <= watched


def test_tp_factories_name_their_programs_like_the_single_chip_ones():
    from jax.sharding import Mesh

    import numpy as np

    mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    assert make_fused_fns(CFG, mesh=mesh).__name__ == "llm_fused_step"
    attn, append = make_fused_paged_fns(CFG, mesh=mesh)
    assert (attn.__name__, append.__name__) == ("llm_fused_paged_step", "llm_kv_append")
    with pytest.raises(ValueError, match="not a documented step program name"):
        named_jit("llm_mystery", lambda x: x)


def test_speculative_programs_are_named_too():
    from ray_tpu.llm.spec import SpecConfig

    eng = _engine(speculative=SpecConfig(k=2))
    names = {f.__name__ for f in _jitted(eng).values()} | {eng._drafter._propose.__name__}
    assert {"llm_verify_step", "llm_draft_propose"} <= names <= STEP_PROGRAM_NAMES


# ------------------------------------------------------------------- stages
def test_stages_sum_to_the_step_and_drain_wait_is_the_device(monkeypatch):
    eng = _engine()
    eng.generate([[1, 2, 3]], SamplingParams(max_tokens=2))  # compile
    real = eng._drain_wait

    def slow_device(pending):
        if pending is not None:
            time.sleep(0.02)  # the device takes 20 ms a step: the readback blocks that long
        return real(pending)

    monkeypatch.setattr(eng, "_drain_wait", slow_device)
    eng.generate([[4, 5, 6, 7], [8, 9]], SamplingParams(max_tokens=8))
    steps = [s for s in eng.telemetry()["steps"] if s["wall_ms"] >= 20.0]
    assert len(steps) >= 6
    for s in steps:
        # What the instrument guarantees: the tiled stages lie one behind the other inside [t0 (perf_counter, step()'s
        # first act), on_step's first stamp], so their sum never exceeds ``wall_ms``, and the row's two time.time()
        # stamps lie inside it as well. What is left over is ``IN_STEP`` (begin_step, the lock, the lines between two
        # stages): microseconds of work, but a thread that loses the processor there loses it for as long as the box
        # likes. Held to 5% of the step (1 ms of these) it failed beside five busy workers (the driver's whole run on
        # 077e41f); a quarter of a second says "not a stage left out", which is what the sum is for.
        tiled = sum(s[f] for f in STEP_STAGES)
        assert tiled <= s["wall_ms"] + 0.01 and s["wall_ms"] - tiled < 250.0
        assert s["t0"] <= s["t"] and (s["t"] - s["t0"]) * 1e3 <= s["wall_ms"] + 1.0 and s["wall_ms"] - (s["t"] - s["t0"]) * 1e3 < 250.0
        # the stepping thread's own clock does not run while it sleeps in the "device": busy neighbours cannot move this
        assert 0.0 < s["cpu_ms"] <= s["wall_ms"] - 19.0
    decode = [s for s in steps if s["phase"] == "decode"]
    # the device's 20 ms are in ``drain_wait_ms`` and in no other stage; as a share of the step (0.8 of it, until PR 40)
    # it failed beside five busy workers, whose host stages take more than 5 ms
    assert decode and all(s["drain_wait_ms"] >= 19.0 for s in decode)
    # the fused program was enqueued inside the step, before the host went to wait for the last one
    assert all(s["t0"] <= s["dispatch_t"] <= s["t"] - 0.015 for s in decode if s["batch"])
    admitting = [s for s in eng.telemetry()["steps"] if s.get("admitted")]
    assert admitting and all(s["prefill_ms"] > 0 for s in admitting)


def _count_reads(monkeypatch, events: list):
    """Every way the engine's code can bring a device array to the host, counted: ``np.asarray`` /
    ``np.array`` of one in ``llm/engine.py`` (on the CPU they read the buffer in place and pass every
    guard jax has), ``jax.device_get``, and ``int()`` / ``float()`` / ``bool()`` of one. ``events`` takes
    a line a read, between the lines the test's own hooks write."""
    import numpy as np
    from jax._src import array as jax_array

    from ray_tpu.llm import engine as engine_mod

    def counted(fn):
        def call(a, *args, **kw):
            if isinstance(a, jax.Array):
                events.append("read:np")
            return fn(a, *args, **kw)
        return call

    class Numpy:
        asarray, array = staticmethod(counted(np.asarray)), staticmethod(counted(np.array))

        def __getattr__(self, name):
            return getattr(np, name)

    monkeypatch.setattr(engine_mod, "np", Numpy())
    get, inside = jax.device_get, []

    def device_get(tree):  # one blocking transfer, whatever it holds: its own reads of the leaves are not counted again
        events.append("read:device_get")
        inside.append(1)
        try:
            return get(tree)
        finally:
            inside.pop()

    monkeypatch.setattr(jax, "device_get", device_get)
    value = jax_array.ArrayImpl._value
    monkeypatch.setattr(jax_array.ArrayImpl, "_value", property(lambda a: (inside or events.append("read:value"), value.fget(a))[1]))


def test_a_wave_is_bound_on_the_device_and_read_once_behind_the_dispatch(monkeypatch):
    """PR 50: between the launch of a wave's first group and the dispatch of the step that follows the
    host reads nothing from the device: each group's first tokens are sampled and its lanes written by
    programs that take and leave device arrays, and the wave's ONE readback stands behind the dispatch.
    The step's row says so: ``lanes_bound_device == admitted``, ``first_token_syncs <= groups``."""
    eng = _engine(max_num_seqs=6, prefill_buckets=(16, 64))
    warm = [SamplingParams(max_tokens=2), SamplingParams(max_tokens=2, temperature=0.7, seed=3), SamplingParams(max_tokens=2, temperature=0.9)]
    eng.generate([[1, 2, 3], list(range(1, 30)), [4, 5]], warm)  # compile: tracing reads constants
    events: list = []
    _count_reads(monkeypatch, events)
    for name in ("_admit_prefill_batch", "_dispatch_fused"):
        monkeypatch.setattr(eng, name, lambda *a, _f=getattr(eng, name), _n=name, **kw: (events.append(_n), _f(*a, **kw))[1])
    before = eng.telemetry()["step_count"]
    for prompt, sp in zip([[7, 8, 9], list(range(2, 40)), [3, 1], list(range(5, 25)), [6]], warm + warm[:2]):
        eng.add_request(prompt, sp)  # greedy, seeded and seedless lanes, in two buckets
    eng.step()
    assert events == ["_admit_prefill_batch", "_admit_prefill_batch", "_dispatch_fused", "read:device_get"]
    row = eng.telemetry()["steps"][before]
    assert (row["admitted"], len(row["prefill_dispatch_t"]), row["lanes_bound_device"], row["first_token_syncs"]) == (5, 2, 5, 1)
    assert all(launched <= row["dispatch_t"] <= read <= row["t"] for _, launched, read in row["prefill_dispatch_t"])
    # the row's stages tile it in the new order: the wave launched inside ``prefill``, read after ``emit``
    assert list(telemetry.STAGES).index("llm.step.prefill.first_tokens") > list(telemetry.STAGES).index("llm.step.emit")
    assert "llm.step.prefill.first_tokens" not in telemetry.INSIDE and row["first_token_wait_ms"] > 0
    assert sum(row[f] for f in STEP_STAGES) <= row["wall_ms"] and row["prefill_launch_ms"] <= row["prefill_ms"]
    assert (row["dispatch_t"] - row["t0"]) * 1e3 - row["admission_ms"] - row["prefill_ms"] >= 0.0  # ``prefill_bubble_ms``' formula
    labels = [sp[0] for sp in telemetry.timeline([row]) if sp[0] in ("prefill.launch", "dispatch", "emit", "prefill.first_tokens", "outputs")]
    assert labels == ["prefill.launch", "prefill.launch", "dispatch", "emit", "prefill.first_tokens", "outputs"]
    events.clear()
    while eng.has_unfinished():
        eng.step()
    assert "_admit_prefill_batch" not in events and "read:device_get" not in events  # decode steps: the drain's reads alone,
    assert "read:np" in events  # which the count does see
    assert all("lanes_bound_device" not in s and "first_token_syncs" not in s for s in eng.telemetry()["steps"][before + 1:])


@pytest.mark.parametrize("path", ["speculative", "prefill_only", "resume"])
def test_the_paths_that_need_the_token_on_the_host_read_it_before_the_dispatch(path):
    """Speculation builds the drafter's history from the first token, a prefill replica ships the logits, a
    restored request samples nothing: their rows read ``lanes_bound_device == 0``, and under speculation one
    readback a group, inside ``llm.step.prefill`` (the group's third stamp lies before ``dispatch_t``)."""
    from ray_tpu.llm.spec import SpecConfig

    eng = _engine(max_num_seqs=4, prefill_buckets=(16, 64), **({"speculative": SpecConfig(k=2)} if path == "speculative" else {}))
    if path == "speculative":
        outs = eng.generate([[1, 2, 3], list(range(1, 30)), [4, 5]], SamplingParams(max_tokens=5))
        assert all(len(o.token_ids) == 5 for o in outs)
        (row,) = [s for s in eng.telemetry()["steps"] if s.get("admitted")]
        assert (row["admitted"], row["lanes_bound_device"], row["first_token_syncs"]) == (3, 0, 2)
        assert all(launched <= read <= row["dispatch_t"] for _, launched, read in row["prefill_dispatch_t"])
        assert row["first_token_wait_ms"] == 0.0 and "prefill.first_tokens" in [sp[0] for sp in telemetry.timeline([row])]
    elif path == "prefill_only":
        payload = eng.prefill_handoff([1, 2, 3, 4, 5])
        assert payload["n"] == 5
        (row,) = [s for s in eng.telemetry()["steps"] if s.get("admitted")]
        assert (row["admitted"], row["lanes_bound_device"], row["first_token_syncs"]) == (1, 0, 0)
    else:
        rid = eng.add_request([1, 2, 3, 4, 5], SamplingParams(max_tokens=8))
        for _ in range(3):
            eng.step()
        state = eng.checkpoint_request(rid)
        eng.abort_request(rid)
        peer = _engine(max_num_seqs=4)
        peer.restore_request(state)
        while peer.has_unfinished():
            peer.step()
        (row,) = [s for s in peer.telemetry()["steps"] if s.get("admitted")]
        assert (row["admitted"], row["lanes_bound_device"], row["first_token_syncs"]) == (1, 0, 0)


def test_a_hybrid_models_rows_carry_routing_counters_and_the_state_insert_stage():
    """PR 29: the step record grew four counters of a hybrid model's expert layers (read back with
    the tokens, one step late) and the stage that writes a prefilled sequence's recurrent state
    into its slot, inside ``llm.step.prefill``. A model without such layers leaves the counters out."""
    from ray_tpu.llm.hybrid_runner import MOE_STATS
    from ray_tpu.models.nemotron_h import NemotronHConfig

    fields = telemetry.FlightRecorder.STEP_FIELDS
    assert set(MOE_STATS) <= set(fields) and "state_insert_ms" in fields and telemetry.STAGES["llm.step.state_insert"] == "state_insert_ms"
    plain = _engine()
    plain.generate([[1, 2, 3]], SamplingParams(max_tokens=3))
    assert all(not set(MOE_STATS) & set(s) and s["state_insert_ms"] == 0.0 for s in plain.telemetry()["steps"])
    cfg = NemotronHConfig.tiny(num_local_experts=4)
    eng = LLMEngine(cfg, max_num_seqs=2, max_seq_len=64)
    eng.generate([[1, 2, 3, 4, 5], [6, 7]], SamplingParams(max_tokens=5))
    steps = eng.telemetry()["steps"]
    admitting = [s for s in steps if s.get("admitted")]
    assert admitting and all(0 < s["state_insert_ms"] <= s["prefill_ms"] for s in admitting)
    # its buckets are one query tile each: every flash call's tile is live whatever the length (PR 52)
    assert all(s["attn_q_tiles"] == s["attn_q_tiles_live"] >= cfg.count("attn") > 0 for s in admitting)
    drained = [s for s in steps if "experts_hit" in s]
    assert len(drained) >= 4 and all(set(MOE_STATS) <= set(s) for s in drained)
    for s in drained:  # 2 lanes x 2 experts a token asked for; chip 0 of two holds 4 of the 8
        assert s["moe_pairs_total"] in (2.0, 4.0) and 0 <= s["moe_pairs_local"] <= s["moe_pairs_total"]
        assert s["experts_hit"] <= min(4, s["moe_pairs_local"]) and s["moe_max_load"] <= s["moe_pairs_total"] / 2
    assert eng._tel._state_bytes == eng.kv_cache_stats()["state_allocated_bytes"] > 0 and plain._tel._state_bytes == 0


@pytest.mark.parametrize("tile", [None, 16])
def test_a_hybrid_models_admitting_row_counts_its_flash_calls_query_tiles(tile, monkeypatch):
    """PR 52: an admitting step's row carries ``attn_q_tiles`` (calls x rows x tiles of the bucket) and
    ``attn_q_tiles_live`` (those that start under a row's true length: what the kernel computes and
    fetches). At the default tile the bucket is ONE tile, no call learns a length and the two are equal;
    in tiles of 16 a group of lengths 1, 17, 40 and 64 leaves six of its sixteen tiles empty, its rows
    go on through the delta rule and the experts as zeros, and what is served is the family's plain
    reference's and, token for token, what an engine whose kernel never learns a length serves."""
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference
    from benchmark.families import qwen3_next as family
    from ray_tpu.models import qwen3_next as qn
    from ray_tpu.ops import flash_attention as fa

    assert {"attn_q_tiles", "attn_q_tiles_live"} <= set(telemetry.PREFILL_COUNTERS) <= set(telemetry.FlightRecorder.STEP_FIELDS)
    if tile is not None:
        monkeypatch.setattr(fa, "_default_blocks", lambda head_dim: (tile, tile))
    c = family.rehearsal({"linear_conv_kernel_dim": 4, "norm_topk_prob": True, "rms_norm_eps": 1e-6,
                          "partial_rotary_factor": 0.25, "rope_theta": 1e7})
    cfg = family.program_config(c, 128, remat=False)
    weights = jax.jit(lambda k: qn.init_params(cfg, k))(jax.random.PRNGKey(7))
    rs = np.random.RandomState(52)
    lengths = [1, 17, 40, 64]
    prompts = [[int(t) for t in rs.randint(1, cfg.vocab_size - 1, size=n)] for n in lengths]
    sps = [SamplingParams(max_tokens=5, logprobs=True), SamplingParams(max_tokens=6, temperature=0.9, top_k=12, seed=31, logprobs=True),
           SamplingParams(max_tokens=5, logprobs=True), SamplingParams(max_tokens=4, temperature=1.0, seed=7, logprobs=True)]

    def serve():
        eng = LLMEngine(cfg, params=weights, max_num_seqs=4, max_seq_len=128, prefill_buckets=(64,), enable_prefix_caching=False)
        return eng, eng.generate(prompts, sps)

    eng, outs = serve()
    (row,) = [s for s in eng.telemetry()["steps"] if s.get("admitted")]
    calls = cfg.count("attn")
    assert (row["prefill_tokens"], row["prefill_tokens_padded"]) == (sum(lengths), 4 * 64)
    assert (row["attn_q_tiles"], row["attn_q_tiles_live"]) == ((calls * 16, calls * 10) if tile else (calls * 4, calls * 4))
    samples = [{"prompt": p, "tokens": o.token_ids, "logprobs": o.logprobs, "greedy": sp.temperature == 0.0}
               for o, p, sp in zip(outs, prompts, sps)]
    res = reference.check_served(family.reference_logprobs, weights, c, samples, 1e-3)
    assert res["ok"] and res["tokens"] == 20 and res["max_abs_dlogprob"] < 1e-4, res
    with monkeypatch.context() as m:
        m.setattr(fa, "_skippable", lambda lengths, *shape: None)  # the parent: every query tile of the bucket computed
        _, parents = serve()
    assert [o.token_ids for o in outs] == [o.token_ids for o in parents]
    assert jnp.allclose(jnp.asarray([o.logprobs for o in outs][0]), jnp.asarray([o.logprobs for o in parents][0]), atol=1e-5)


def test_a_step_row_carries_the_time_the_process_spent_collecting_garbage():
    """A collection holds every thread of the process, the stepper blocked on the device among them
    (a prefill's first-token wait of 2.2 s beside its usual 0.12, PR 44): the row of the step that
    sat through it says so (``gc_ms``), and a row with none leaves the column out."""
    import gc

    eng = _engine()
    eng.generate([[1, 2, 3]], SamplingParams(max_tokens=2))
    before = len(eng.telemetry()["steps"])
    junk = [[i] for i in range(200_000)]  # something for the collector to walk
    t0 = time.perf_counter()
    gc.collect()
    held_ms = (time.perf_counter() - t0) * 1e3
    eng.generate([[4, 5, 6]], SamplingParams(max_tokens=3))
    rows = eng.telemetry()["steps"][before:]
    assert rows and "gc_ms" in telemetry.FlightRecorder.STEP_FIELDS
    assert rows[0]["gc_ms"] >= 0.8 * held_ms > 0, "the first step after the collection saw it"
    assert all(r.get("gc_ms", 0.0) < held_ms for r in rows[1:])
    del junk


def test_every_step_row_counts_the_lanes_that_sample():
    """PR 32: ``sampling_lanes`` is the count of bound lanes with temperature > 0, from the host's
    lane table: the lanes whose top-k or top-p make a step run the sampler's counting passes."""
    eng = _engine(max_num_seqs=4)
    eng.generate([[1, 2, 3], [4, 5]], SamplingParams(max_tokens=4))
    greedy_rows = eng.telemetry()["step_count"]
    params = [SamplingParams(max_tokens=4, temperature=0.8, seed=1), SamplingParams(max_tokens=12),
              SamplingParams(max_tokens=8, temperature=0.7, top_p=0.9, seed=2)]
    ids = [eng.add_request([7, 8, 9 + i], sp) for i, sp in enumerate(params)]
    want = []
    while eng.has_unfinished():
        eng.step()
        bound = {s.request_id for s in eng._slots if s is not None}
        want.append(sum(1 for i, sp in zip(ids, params) if i in bound and sp.temperature > 0.0))
    steps = eng.telemetry()["steps"]
    assert all(isinstance(s["sampling_lanes"], int) and 0 <= s["sampling_lanes"] <= s["batch"] for s in steps)
    assert all(s["sampling_lanes"] == 0 for s in steps[:greedy_rows])
    assert [s["sampling_lanes"] for s in steps[greedy_rows:]] == want
    seen = [n for n, prev in zip(want, [None] + want) if n != prev]
    assert seen == [2, 1, 0], "both sampled requests, then the longer one alone, then the greedy one alone"


def test_step_rows_count_the_blocks_the_attention_kernel_reads():
    """PR 35: ``attn_blocks_read`` / ``attn_blocks_total`` of the decode program a step dispatched,
    reckoned on the host (prompt + tokens emitted + the step in flight) where the attention runs as
    the kernel that reads live blocks only. Here the engine is told by hand that it does, in blocks
    of 16 positions: the rows agree with the lengths the device holds after each step, and with a
    request worked out by hand. Where the XLA form runs (every engine off the TPU) they are absent."""
    import numpy as np

    plain = _engine()
    plain.generate([[1, 2, 3]], SamplingParams(max_tokens=3))
    assert all("attn_blocks_read" not in s and "attn_blocks_total" not in s for s in plain.telemetry()["steps"])

    eng = _engine(max_num_seqs=3)
    eng._attn_block, layers, per_lane = 16, CFG.num_layers, 128 // 16
    # by hand: a prompt of 15 tokens. Its first decode step writes position 15 and reads 16
    # positions, one block; the next reads 17, two blocks. 8 tokens come from decode steps, and the
    # loop, which learns of the end one step late, dispatches a ninth; then nothing is bound
    eng.generate([list(range(1, 16))], SamplingParams(max_tokens=9))
    rows = [s for s in eng.telemetry()["steps"] if "attn_blocks_read" in s]
    assert [s["attn_blocks_read"] for s in rows] == [1 * layers] + [2 * layers] * 8
    assert {s["attn_blocks_total"] for s in rows} == {3 * per_lane * layers}
    # against the device: lanes admitted and finished at different steps, lengths across block edges
    seen = len(eng.telemetry()["steps"])
    for n, m in ((30, 6), (1, 20), (47, 3), (16, 12)):
        eng.add_request(list(range(1, n + 1)), SamplingParams(max_tokens=m))
    want = []
    while eng.has_unfinished():
        eng.step()
        if eng._pending is None:
            want.append(None)
            continue
        held = np.asarray(eng.cache["length"])  # after the step: each dispatched lane's new token counted
        want.append(sum(-(-int(held[slot]) // 16) for _, slot in eng._pending[-1]) * layers)
    got = [s.get("attn_blocks_read") for s in eng.telemetry()["steps"][seen:]]
    assert got == want and len({w for w in want if w}) > 3
    assert all(s["attn_blocks_read"] <= s["attn_blocks_total"] for s in eng.telemetry()["steps"] if "attn_blocks_read" in s)


def test_an_uninstrumented_engine_steps_through_the_same_code():
    eng = _engine(telemetry=False)
    out = eng.generate([[1, 2, 3]], SamplingParams(max_tokens=4))
    assert len(out[0].token_ids) == 4 and eng.telemetry() == {}


def test_stepper_stages_land_on_the_row_of_the_step_that_follows():
    srv = _server()
    try:
        time.sleep(0.15)  # the stepper idles, blocked on _work
        srv.generate([1, 2, 3], {"max_tokens": 4})
        steps = srv.telemetry()["steps"][-5:]
        first = next(s for s in steps if s.get("admitted"))
        assert first["stepper_wait_ms"] >= 100.0
        assert all(s["stepper_deliver_ms"] >= 0.0 and s["stepper_wait_ms"] == 0.0 for s in steps if s["step"] > first["step"])
    finally:
        srv.shutdown()


# --------------------------------------------------------------- the flight log
def test_the_log_holds_a_run_whole_while_the_rings_stay_short(session):
    srv = _server()
    srv.generate([1, 2, 3], {"max_tokens": 6})
    n0 = srv.telemetry()["step_count"]
    for _ in range(3000):  # the stepper idles (nothing unfinished); each of these is a recorded step
        srv.engine.step()
    for i in range(3):
        srv.generate([4 + i, 5, 6], {"max_tokens": 3})
    assert not os.listdir(session), "nothing is written while requests are served"
    srv.shutdown()
    snap = srv.telemetry()
    assert len(snap["steps"]) == 512 and snap["step_count"] >= n0 + 3000
    log = telemetry.load_flight()
    (header,) = log["headers"]
    assert header["pid"] == os.getpid() and header["dropped_steps"] == 0 and header["dropped_requests"] == 0
    assert [s["step"] for s in log["steps"]] == list(range(1, snap["step_count"] + 1))
    assert header["steps"] == len(log["steps"]) and "error" not in header
    # the prewarm's two requests, then the four of this test
    assert [r["request_id"] for r in log["requests"]][-4:] == [r["request_id"] for r in snap["requests"]][-4:]
    assert all(set(telemetry.STAGES.values()) <= set(s) and s["pid"] == os.getpid() for s in log["steps"])
    srv.shutdown()  # written once
    assert len(os.listdir(session / "llm_flight")) == 1


def test_the_logs_bound_drops_the_oldest_and_says_how_many(session, monkeypatch):
    monkeypatch.setattr(telemetry.FlightRecorder, "LOG_STEPS", 100)
    monkeypatch.setattr(telemetry.FlightRecorder, "LOG_REQUESTS", 2)
    eng = _engine()
    for i in range(4):
        eng.generate([[1 + i, 2, 3]], SamplingParams(max_tokens=2))
    for _ in range(150):
        eng.step()
    assert eng._tel.write_flight_log() is not None
    (header,) = telemetry.load_flight()["headers"]
    total = eng.telemetry()["step_count"]
    assert (header["steps"], header["dropped_steps"]) == (100, total - 100)
    assert (header["requests"], header["dropped_requests"]) == (2, 2)


def test_the_logs_memory_stays_under_its_stated_bound():
    """12,000 step rows and 2,000 requests of 150 tokens: the stated bound (21 MB since PR 39 added three fields to a step row; 21.5 since PR 46 added two, 16 bytes a row; 21.7 since PR 50 added two; 21.9 since PR 52 added two; 22.1 since PR 54 added one; 23.3 since PR 55 added five, two of them floats on every row; 23.7 since PR 58 added four counts, 8 bytes a row each; 23.9 since PR 60 added two; 24.0 since PR 64 added one)."""
    import tracemalloc

    rec = telemetry.FlightRecorder()
    floats = {"t", "wall_ms", "t0", "dispatch_t", "dispatch_t0", "cpu_ms", *telemetry.STAGES.values()}  # the rest are counts, a phase, or None
    tracemalloc.start()
    for i in range(rec.LOG_STEPS):
        rec.record_step(tuple(1.5 + i + k if f in floats else "decode" if f == "phase" else 12
                              for k, f in enumerate(rec.STEP_FIELDS[1:])))
    for i in range(rec.LOG_REQUESTS):
        rec.record_request({"request_id": f"req-{i}", "submit_t": 1.5 + i, "itl_s": [0.06 + i * 1e-9 + k * 1e-9 for k in range(150)],
                            **{k: 2.5 + i for k in ("ingress_t", "admit_t", "first_token_t", "finish_t", "first_yield_t", "last_yield_t")}})
    held, _ = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert len(rec.log_steps) == 12_000 and len(rec.log_requests) == 2_000
    assert held < 24.0e6, f"the flight log holds {held / 1e6:.1f} MB"


def test_load_flight_merges_two_processes_and_skips_a_torn_last_line(session):
    pad = (None,) * (len(telemetry.FlightRecorder.STEP_FIELDS) - 3)
    for pid, n in ((111, 3), (222, 2)):
        rec = telemetry.FlightRecorder()
        for i in range(n):
            rec.record_step((100.0 * pid + i, "decode") + pad)
            rec.record_request({"request_id": f"req-{i}", "submit_t": 100.0 * pid + i})
        rec.dump_jsonl(str(session / "llm_flight" / f"flight-{pid}-1.jsonl"), header={"pid": pid})
    with open(session / "llm_flight" / "flight-222-1.jsonl", "a") as f:
        f.write('{"kind": "step", "step": 3, "t": 2')  # the process died here
    (session / "llm_flight" / "flight-333-1.jsonl").write_text("")
    log = telemetry.load_flight()
    assert [h["pid"] for h in log["headers"]] == [111, 222]
    assert [(s["pid"], s["step"]) for s in log["steps"]] == [(111, 1), (111, 2), (111, 3), (222, 1), (222, 2)]
    assert [(r["pid"], r["request_id"]) for r in log["requests"]] == [(111, "req-0"), (111, "req-1"), (111, "req-2"), (222, "req-0"), (222, "req-1")]


def test_an_engine_error_writes_the_same_log_once(session):
    eng = _engine()
    eng.generate([[1, 2, 3]], SamplingParams(max_tokens=2))

    def boom(*a, **kw):
        raise RuntimeError("injected")

    eng._fused_step = boom
    eng.add_request([4, 5, 6], SamplingParams(max_tokens=4))
    with pytest.raises(RuntimeError, match="injected"):
        while eng.has_unfinished():
            eng.step()
    log = telemetry.load_flight()
    assert "injected" in log["headers"][0]["error"] and log["steps"] and log["requests"]
    assert eng._tel.write_flight_log() is None


# ----------------------------------------------------------- the request path
def test_boundary_stamps_of_a_streamed_request_are_in_order(session):
    srv = _server(OpenAIServer)
    try:
        chunks = list(srv({"prompt": [1, 2, 3, 4], "max_tokens": 6, "stream": True}))
        assert len(chunks) == 7 and chunks[-1].startswith("data: [DONE]")
        unary = srv({"prompt": [5, 6, 7], "max_tokens": 3})
        recs = {r["request_id"]: r for r in srv.telemetry()["requests"]}
        rid = json.loads(chunks[0][6:])["id"]
        r = recs[rid]
        order = [r[k] for k in ("ingress_t", "submit_t", "admit_t", "first_token_t", "first_yield_t", "last_yield_t")]
        assert all(order) and order == sorted(order)
        assert r["finish_t"] <= r["last_yield_t"]  # the stream outlives the engine's last token
        # the engine's per-token emit times are in the record already: first_token_t plus the running sum of itl_s
        assert r["first_token_t"] + sum(r["itl_s"]) <= r["last_yield_t"] and len(r["itl_s"]) == 5
        u = recs[unary["id"]]
        assert u["ingress_t"] <= u["submit_t"] and u["first_yield_t"] is None
        # the ingress stamp belongs to its request: a later call that is no request of this ingress has none
        direct = srv.generate([8, 9], {"max_tokens": 2})
        assert {x["request_id"]: x for x in srv.telemetry()["requests"]}[direct["request_id"]]["ingress_t"] is None
    finally:
        srv.shutdown()
    assert rid in {x["request_id"] for x in telemetry.load_flight()["requests"]}


def test_a_starved_stream_goes_on_past_its_poll(monkeypatch):
    """``_stream_tokens`` named ``_queue.Empty`` with ``_queue`` imported in another function: a stream
    that waited a whole poll (5 s) for a token died with NameError (34 of 34 requests at 2 req/s, PR 23)."""
    from ray_tpu.serve import llm as serve_llm

    monkeypatch.setattr(serve_llm, "_STREAM_POLL_S", 0.05)
    srv = _server(OpenAIServer)
    try:
        q = queue.SimpleQueue()

        def late():
            time.sleep(0.4)  # eight polls with nothing to take
            q.put(17)
            q.put(None)

        t = threading.Thread(target=late)
        t.start()
        chunks = list(srv._stream_tokens("req-starved", q, chat=False))
        t.join(timeout=5)
        assert not t.is_alive() and len(chunks) == 2 and json.loads(chunks[0][6:])["choices"][0]["text"] == [17]
    finally:
        srv.shutdown()


@pytest.mark.parametrize("ending", ["to_its_end", "closed_by_the_consumer", "aborted_by_the_engine", "the_stepper_died"])
def test_the_replica_counts_the_streams_that_ended_badly_by_cause(ending):
    """``stream_stats``: a stream that gave every token and its ``[DONE]`` counts as served and no more;
    one whose consumer closed it between two tokens (the worker's stream loop does, when the client
    cancelled), one the engine ended before its last token, and one whose stepper died each count
    under their cause, so that a request the client lost shows on the replica's side too."""
    from ray_tpu.serve.overload import StepperDiedError

    srv = _server(OpenAIServer)
    try:
        gen = srv({"prompt": [1, 2, 3], "max_tokens": 40 if ending != "to_its_end" else 4, "stream": True})
        first = next(gen)
        rid = json.loads(first[6:])["id"]
        if ending == "to_its_end":
            rest = list(gen)
            assert len(rest) == 4 and rest[-1].startswith("data: [DONE]")
        elif ending == "closed_by_the_consumer":
            gen.close()
        elif ending == "aborted_by_the_engine":
            assert srv.engine.abort_request(rid)
            rest = list(gen)
            assert rest[-1].startswith("data: [DONE]") and len(rest) < 40, "the stream ends by its sentinel all the same, short"
        else:
            srv._fail_all_waiters("the test killed it")
            with pytest.raises(StepperDiedError):
                list(gen)
        want = {"to_its_end": {}, "closed_by_the_consumer": {"closed by the consumer": 1},
                "aborted_by_the_engine": {"the engine ended it: aborted": 1}, "the_stepper_died": {"stepper died": 1}}[ending]
        assert srv.stream_stats() == {"served": 1, "ended_badly": want}
    finally:
        srv.shutdown()


def test_request_spans_gain_ingress_and_stream_under_tracing(session):
    from ray_tpu.util import tracing

    tracing.configure(True)
    srv = _server(OpenAIServer)
    try:
        chunks = list(srv({"prompt": [1, 2, 3], "max_tokens": 4, "stream": True}))
        rid = json.loads(chunks[0][6:])["id"]
    finally:
        srv.shutdown()
        tracing.shutdown()
        tracing.configure(False)
    spans = [s for s in tracing.load_spans() if s["attrs"].get("request_id") == rid]
    by = {s["name"]: s for s in spans}
    assert {"llm.request", "llm.ingress", "llm.admission", "llm.prefill", "llm.decode", "llm.stream"} <= set(by)
    root = by["llm.request"]
    assert all(s["parent_id"] == root["span_id"] and s["trace_id"] == root["trace_id"] for n, s in by.items() if n != "llm.request")
    rec = next(r for r in telemetry.load_flight()["requests"] if r["request_id"] == rid)
    # no second source of time: the spans are the record's stamps
    assert by["llm.ingress"]["start_ns"] == int(rec["ingress_t"] * 1e9) and by["llm.ingress"]["end_ns"] == int(rec["submit_t"] * 1e9)
    assert by["llm.stream"]["start_ns"] == int(rec["first_yield_t"] * 1e9) and by["llm.stream"]["end_ns"] == int(rec["last_yield_t"] * 1e9)


# ------------------------------------------------------------- the profiler hook
def test_profile_hook_records_the_stage_annotations(tmp_path):
    from jax.profiler import ProfileData

    from benchmark import xplane

    srv = _server()
    try:
        t0 = time.time()
        assert srv.profile("start", str(tmp_path)) >= t0
        srv.generate([1, 2, 3], {"max_tokens": 4})
        srv.profile("stop")
        with pytest.raises(ValueError):
            srv.profile("pause")
    finally:
        srv.shutdown()
    names = {ev.name for plane in ProfileData.from_file(xplane.find_xplane(str(tmp_path))).planes
             for line in plane.lines for ev in line.events if ev.name.startswith("llm.")}
    assert {"llm.step", "llm.step.prefill", "llm.step.dispatch", "llm.step.drain_wait", "llm.stepper.deliver"} <= names
    assert not hasattr(__import__("ray_tpu.util.profiling", fromlist=["x"]), "WallProfiler")


# ------------------------------------------------------------ serve.shutdown
def test_serve_shutdown_stops_replicas_before_it_kills_them(tmp_path):
    """``graceful_shutdown`` shot every replica (``ray_tpu.kill`` is SIGTERM, no handler), so neither a
    deployment's shutdown hook nor an LLM replica's last spans and flight log survived ``serve.shutdown()``."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import build_openai_app
    from ray_tpu.util import tracing

    marker = str(tmp_path / "stopped")

    @serve.deployment
    class WithHook:
        def __call__(self, x):
            return x

        def shutdown(self):
            with open(marker, "w") as f:
                f.write("clean")

    ray_tpu.shutdown()
    os.environ["RT_TRACING"] = "1"
    tracing.configure(True)
    try:
        ray_tpu.init(num_cpus=4)
        hook = serve.run(WithHook.bind(), name="hook_app", route_prefix="/hook")
        assert hook.remote(1).result(timeout_s=60) == 1
        app = build_openai_app(LLMConfig(model_config=CFG, engine_kwargs={"max_num_seqs": 4, "max_seq_len": 128}))
        h = serve.run(app, name="oai", route_prefix="/v1", blocking_timeout_s=240.0)
        rids = []
        for i in range(3):
            chunks = list(h.options(stream=True).remote({"prompt": [1 + i, 2, 3], "max_tokens": 4, "stream": True}))
            assert chunks[-1].startswith("data: [DONE]") and len(chunks) == 5
            rids.append(json.loads(chunks[0][6:])["id"])
        t0 = time.time()
        serve.shutdown()
        # What is held is the ORDER of events: ``ray_tpu.kill`` is SIGTERM with no handler, so whatever a replica wrote,
        # it wrote before it was killed: the hook's marker, the flight log and the last spans below say that stop came
        # before kill. The clock says only that shutdown rode out none of its waits twice: 10 s, which stood here, is the
        # call's OWN budget for the controller's ``graceful_shutdown`` (in it 8 s a drain and a second of slack).
        assert time.time() - t0 < 30.0
        assert open(marker).read() == "clean"
        log = telemetry.load_flight()
        # The session's directory holds the logs of every engine this process has had, each with a ``req-0`` of its own,
        # and files merge in the order of their names, pids compared as text: which log comes last says nothing (a
        # record of an earlier test's, without a stream's stamps, stood in for the replica's whenever this process's pid
        # sorted after the replica's: the driver's whole runs of PRs 42-47). The requests served here finished last.
        served = {r["request_id"]: r for r in sorted(log["requests"], key=lambda r: r["finish_t"])}
        assert set(rids) <= set(served) and all(served[r]["pid"] != os.getpid() for r in rids)
        assert all(served[r]["ingress_t"] and served[r]["last_yield_t"] for r in rids)
        # the worker's span file: the last request's spans, the stream's among them, reached the disk
        names = {s["name"] for s in tracing.load_spans() if s["attrs"].get("request_id") == rids[-1]}
        assert {"llm.request", "llm.ingress", "llm.stream"} <= names
    finally:
        os.environ.pop("RT_TRACING", None)
        tracing.configure(False)
        serve.shutdown()
        ray_tpu.shutdown()
