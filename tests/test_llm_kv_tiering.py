"""Latency-hiding KV plane v2 (ROADMAP item 3): async fetch, predictive
prefetch, tiered conversation KV.

The guarantees under test:

- ASYNC FETCH (3a): a cluster-tier prefix fetch runs on the engine's
  dedicated worker thread, NEVER under the engine lock — the flight
  recorder's fetch span overlaps live step records — and splices in
  token-identically at a later admission wave. A dropped index, a lost
  block, or a fetch outliving its deadline degrades to plain local
  prefill: correct output, bounded time, zero hangs.
- PREDICTIVE PREFETCH (3b): the index's decayed-demand ``top_hot`` feed
  pulls the fleet's hottest blocks into a replica's local cache ahead of
  demand (heartbeat-piggybacked, daemon worker), converting would-be
  remote hits into LOCAL-tier hits counted as ``prefetch_hits``. Chaos
  at ``kvplane.prefetch`` (drop/fault) leaves serving token-identical.
- TIERED CONVERSATION KV (3c): ``suspend_request`` spills an idle
  conversation out of HBM through the migration codec (host DRAM +
  object plane); ``resume_suspended`` scatters it back in under the
  ORIGINAL request id with zero recomputed tokens — byte-identical to
  the never-suspended oracle across layouts x cache dtypes x greedy/
  seeded, including a resume racing a concurrent admission wave. Every
  failure is typed: chaos at ``llm.suspend`` refuses with MigrationError
  and the conversation keeps RUNNING; both tiers gone is
  MigrationLostError, never a hang.

Engines are tiny CPU configs; the object plane is the real direct plane
(rt fixture), mirroring tests/test_llm_kvplane.py and test_llm_migrate.py.
"""

import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import ray_tpu  # noqa: E402
from ray_tpu import chaos  # noqa: E402
from ray_tpu.llm import SamplingParams  # noqa: E402
from ray_tpu.llm.kvplane import KVPlaneClient, PrefixIndex, boundary_keys  # noqa: E402
from ray_tpu.llm.migrate import MigrationError, MigrationLostError  # noqa: E402
from ray_tpu.models.llama import LlamaConfig, init_params  # noqa: E402
from test_llm_migrate import _mk, _run_until  # noqa: E402 - an engine of the same toy configuration; stepping by the clock

pytestmark = pytest.mark.usefixtures("shared_step_programs")  # many engines of equal configurations: their step programs compile once (conftest.py)

CFG = LlamaConfig.tiny(dtype="float32", remat=False, max_seq_len=128)
SP = SamplingParams(max_tokens=6, temperature=0.0)
GREEDY = SamplingParams(max_tokens=14, temperature=0.0)
SEEDED = SamplingParams(max_tokens=14, temperature=0.8, seed=7, top_k=20)
RNG = np.random.default_rng(23)
SHARED = [int(x) for x in RNG.integers(1, CFG.vocab_size - 1, size=70)]  # >= one 64-block
PROMPT = [int(x) for x in RNG.integers(1, CFG.vocab_size - 1, size=24)]
PROMPT_B = [int(x) for x in RNG.integers(1, CFG.vocab_size - 1, size=24)]


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def rt():
    """The real object plane: publish/fetch/spill ride direct.put_owned /
    get_owned_view exactly as in a fleet."""
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2)
    yield
    ray_tpu.shutdown()


@pytest.fixture(scope="module")
def oracle_fp(params):
    """One shared slots-fp oracle engine (no plane) for every identity
    assertion — the module pays its compiles once."""
    return _engine(params)


def _engine(params, plane=None, **kw):
    return _mk(params, kv_plane=plane, **kw)


def _client(idx, rid, **kw):
    kw.setdefault("publish_min_hits", 1)
    return KVPlaneClient(idx, rid, **kw)


def _drain(eng, rid):
    """Drain the engine and return the request's FINAL token stream —
    tolerating the transient finished=suspended report a suspend emits
    when a step runs before the resume."""
    out = None
    while eng.has_unfinished():
        for o in eng.step():
            if o.request_id == rid and o.finished and o.finish_reason != "suspended":
                out = o
    assert out is not None, "request drained without finishing"
    return list(out.token_ids)


# ------------------------------------------------------------- plane stats


def test_plane_stats_full_shape_seeded_at_construction(params):
    """The remote tier's counter set — failure and async/prefetch legs
    included — exists (all zeros) from construction: dashboards and
    diff-based tests never see the dict change shape on first error."""
    eng = _engine(params, _client(PrefixIndex(), "solo"))
    remote = eng.prefix_cache_stats()["remote"]
    assert set(remote) == {
        "hits", "tokens_saved", "fetched_bytes", "lost",
        "published_blocks", "published_bytes", "errors", "abandoned",
        "prefetched_blocks", "prefetched_bytes", "prefetch_hits",
        "inflight_fetches",
    }
    assert all(v == 0 for v in remote.values())
    assert "held" in eng.suspend_stats() and eng.suspend_stats()["suspended"] == 0


# ------------------------------------------------------- async fetch (3a)


def test_async_fetch_off_lock_token_identical(params, rt, oracle_fp, monkeypatch):
    """The cluster-tier fetch runs on the dedicated "llm-prefix-fetch"
    worker — never the caller's thread, never under the engine lock —
    and the spliced completion is token-identical to local prefill."""
    want = list(oracle_fp.generate(SHARED + [7, 8], SP).token_ids)
    idx = PrefixIndex()
    a = _engine(params, _client(idx, "A"))
    a.generate(SHARED + [5, 6], SP)  # publishes the 64-boundary block

    cb = _client(idx, "B")
    b = _engine(params, cb)
    fetch_threads = []
    orig_fetch = cb.fetch

    def spy(hit):
        fetch_threads.append(threading.current_thread().name)
        assert not b._lock.locked() or threading.current_thread().name == "llm-prefix-fetch"
        return orig_fetch(hit)

    monkeypatch.setattr(cb, "fetch", spy)
    out = b.generate(SHARED + [7, 8], SP)
    assert list(out.token_ids) == want
    assert fetch_threads == ["llm-prefix-fetch"]
    remote = b.prefix_cache_stats()["remote"]
    assert remote["hits"] == 1 and remote["tokens_saved"] == 64
    assert remote["inflight_fetches"] == 0  # record consumed at the splice


def test_fetch_span_overlaps_step_records(params, rt, oracle_fp, monkeypatch):
    """The latency actually hides: while the fetch is in flight the
    engine keeps stepping (a follower decodes), so the flight recorder
    shows step records INSIDE the fetch span [t0, t1] — the item-3a
    overlap evidence the bench reads from the same ring."""
    want = list(oracle_fp.generate(SHARED + [7, 8], SP).token_ids)
    idx = PrefixIndex()
    a = _engine(params, _client(idx, "A"))
    a.generate(SHARED + [5, 6], SP)

    cb = _client(idx, "B")
    b = _engine(params, cb)
    orig_fetch = cb.fetch

    def slow_fetch(hit):
        time.sleep(0.2)  # well inside the 2s deadline; many steps long
        return orig_fetch(hit)

    monkeypatch.setattr(cb, "fetch", slow_fetch)
    r1 = b.add_request(PROMPT, SamplingParams(max_tokens=24, temperature=0.0))
    _run_until(b, r1, 2)  # a live decode keeps the step loop busy
    r2 = b.add_request(SHARED + [7, 8], SP)
    outs = {}
    while b.has_unfinished():
        for o in b.step():
            if o.finished:
                outs[o.request_id] = o
    assert list(outs[r2].token_ids) == want
    snap = b._tel.recorder.snapshot()
    fetches = [f for f in snap["fetches"] if f["hit"]]
    assert fetches, "no fetch span recorded"
    f = fetches[-1]
    assert f["tokens"] == 64 and f["t1"] >= f["t0"]
    overlapped = [s for s in snap["steps"] if f["t0"] <= s["t"] <= f["t1"]]
    assert overlapped, "no step ran during the fetch span — the transfer was not overlapped"


def test_index_chaos_mid_prefill_degrades_token_identical(params, rt, oracle_fp):
    """A dropped index RPC while the wave is mid-prefill degrades to
    plain local prefill: token-identical, bounded time, no hang; a
    merely DELAYED index still lands the remote hit."""
    want = list(oracle_fp.generate(SHARED + [7, 8], SP).token_ids)
    idx = PrefixIndex()
    a = _engine(params, _client(idx, "A"))
    a.generate(SHARED + [5, 6], SP)

    # dropped: every lookup dies on the worker -> local prefill
    b = _engine(params, _client(idx, "B"))
    chaos.inject("kvplane.index", drop_prob=1.0, methods=("lookup",))
    t0 = time.time()
    out = b.generate(SHARED + [7, 8], SP)
    chaos.clear()
    assert list(out.token_ids) == want
    assert time.time() - t0 < 60.0
    remote = b.prefix_cache_stats()["remote"]
    assert remote["hits"] == 0 and remote["inflight_fetches"] == 0

    # delayed: the async fetch just takes longer, the hit still splices
    c = _engine(params, _client(idx, "C"))
    chaos.inject("kvplane.index", delay_s=0.05, methods=("lookup",))
    out = c.generate(SHARED + [7, 8], SP)
    chaos.clear()
    assert list(out.token_ids) == want
    assert c.prefix_cache_stats()["remote"]["hits"] == 1


def test_lost_block_mid_fetch_degrades_token_identical(params, rt, oracle_fp):
    """``handoff.fetch`` dropped mid-prefill (block evicted under the
    fetch): the worker reports the loss, admission falls back to local
    prefill, output stays token-identical."""
    want = list(oracle_fp.generate(SHARED + [7, 8], SP).token_ids)
    idx = PrefixIndex()
    a = _engine(params, _client(idx, "A"))
    a.generate(SHARED + [5, 6], SP)
    b = _engine(params, _client(idx, "B"))
    chaos.inject("handoff.fetch", drop_prob=1.0)
    out = b.generate(SHARED + [7, 8], SP)
    chaos.clear()
    assert list(out.token_ids) == want
    remote = b.prefix_cache_stats()["remote"]
    assert remote["lost"] == 1 and remote["hits"] == 0


def test_fetch_deadline_abandons_to_local_prefill(params, rt, oracle_fp, monkeypatch):
    """A wedged plane (fetch outliving prefix_fetch_deadline_s) abandons
    the record and admits with plain prefill — bounded by the deadline,
    never a hang, counted in ``abandoned``."""
    want = list(oracle_fp.generate(SHARED + [7, 8], SP).token_ids)
    idx = PrefixIndex()
    a = _engine(params, _client(idx, "A"))
    a.generate(SHARED + [5, 6], SP)
    cb = _client(idx, "B")
    b = _engine(params, cb, prefix_fetch_deadline_s=0.1)
    orig_fetch = cb.fetch

    def wedged(hit):
        time.sleep(1.0)  # far past the 0.1s deadline
        return orig_fetch(hit)

    monkeypatch.setattr(cb, "fetch", wedged)
    t0 = time.time()
    out = b.generate(SHARED + [7, 8], SP)
    assert list(out.token_ids) == want
    assert time.time() - t0 < 30.0
    remote = b.prefix_cache_stats()["remote"]
    assert remote["abandoned"] == 1 and remote["hits"] == 0


# ------------------------------------------------- predictive prefetch (3b)


def test_top_hot_demand_decay_and_alias_dedup():
    """The prefetch feed: decayed demand ranks live blocks, the asker's
    own holdings are excluded, boundary aliases of one published ref
    dedup to the longest, and demand halves away to nothing."""
    t = [0.0]
    idx = PrefixIndex(ttl_s=1e6, time_fn=lambda: t[0], demand_halflife_s=10.0)
    ids = list(range(200))
    (k64, k128) = [key for _, key in boundary_keys(ids[:130], 64)]
    ref = object()  # top_hot only identity-compares refs
    idx.register("A", [(k64, 64, {"nbytes": 1}, ref), (k128, 128, {"nbytes": 1}, ref)])
    for _ in range(3):
        idx.lookup([(64, k64), (128, k128)], None, "router")
    hot = idx.top_hot(4)
    assert len(hot) == 1, "boundary aliases of one ref must dedup"
    assert hot[0]["n"] == 128 and hot[0]["replica"] == "A"
    assert set(hot[0]) == {"key", "n", "replica", "meta", "ref", "demand"}
    assert hot[0]["demand"] == pytest.approx(3.0)
    assert idx.top_hot(4, exclude="A") == []  # the holder never prefetches itself
    t[0] = 200.0  # 20 halvings: 3 / 2**20 is dust, dropped
    idx.match_replicas([])  # any demand touch runs the lazy decay
    assert idx.top_hot(4) == []


def test_predictive_prefetch_converts_remote_to_local_hit(params, rt, oracle_fp):
    """End to end: demand accrues on the index, a heartbeat tick pulls
    the hot block into replica B's local cache on the prefetch worker,
    and the next shared-prefix request is a LOCAL hit attributed to the
    prefetcher (``prefetch_hits``) — token-identical throughout."""
    want = list(oracle_fp.generate(SHARED + [9, 10], SP).token_ids)
    idx = PrefixIndex()
    a = _engine(params, _client(idx, "A"))
    a.generate(SHARED + [5, 6], SP)  # A holds + registered the block
    # router-shaped demand: every match_replicas scores bump the key
    for _ in range(3):
        idx.match_replicas(boundary_keys(SHARED + [9, 10], 64))

    cb = _client(idx, "B", prefetch_k=2, heartbeat_every_s=0.0)
    b = _engine(params, cb)
    cb.maybe_heartbeat()  # piggybacks one prefetch round on a worker
    t = cb._prefetch_thread
    assert t is not None and t.name == "kvplane-prefetch"
    t.join(30.0)
    assert not t.is_alive()
    cb.prefetch_k = 0  # freeze: the assertion window stays deterministic
    assert cb.counts["prefetch_rounds"] == 1 and cb.counts["prefetch_blocks"] == 1
    remote = b.prefix_cache_stats()["remote"]
    assert remote["prefetched_blocks"] == 1 and remote["prefetched_bytes"] > 0

    out = b.generate(SHARED + [9, 10], SP)
    assert list(out.token_ids) == want
    remote = b.prefix_cache_stats()["remote"]
    assert remote["prefetch_hits"] == 1, "the local hit was not attributed to the prefetcher"
    assert remote["hits"] == 0, "prefetch must convert the REMOTE hit into a LOCAL one"


def test_prefetch_chaos_drop_and_fault_leave_serving_identical(params, rt, oracle_fp):
    """Prefetch is background opportunism: a dropped or faulting round
    is counted and swallowed, and serving stays token-identical (the
    demand path simply pays the remote fetch it would have paid anyway)."""
    want = list(oracle_fp.generate(SHARED + [9, 10], SP).token_ids)
    idx = PrefixIndex()
    a = _engine(params, _client(idx, "A"))
    a.generate(SHARED + [5, 6], SP)
    for _ in range(3):
        idx.match_replicas(boundary_keys(SHARED + [9, 10], 64))

    cb = _client(idx, "B", prefetch_k=2, heartbeat_every_s=0.0)
    b = _engine(params, cb)
    chaos.inject("kvplane.prefetch", drop_prob=1.0)
    cb.maybe_heartbeat()
    cb._prefetch_thread.join(30.0)
    assert cb.counts["prefetch_skipped"] == 1 and cb.counts["prefetch_blocks"] == 0

    chaos.inject("kvplane.prefetch", raises=RuntimeError)
    cb._last_heartbeat = 0.0
    cb.maybe_heartbeat()
    cb._prefetch_thread.join(30.0)
    chaos.clear()
    assert cb.counts["prefetch_errors"] == 1 and cb.counts["prefetch_blocks"] == 0

    cb.prefetch_k = 0
    out = b.generate(SHARED + [9, 10], SP)  # demand path: remote tier
    assert list(out.token_ids) == want
    remote = b.prefix_cache_stats()["remote"]
    assert remote["hits"] == 1 and remote["prefetch_hits"] == 0


# --------------------------------------------- tiered conversation KV (3c)


@pytest.mark.parametrize("layout", ["slots", "paged"])
@pytest.mark.parametrize("dtype", [None, "int8"])
def test_suspend_resume_oracle_matrix(params, layout, dtype):
    """suspend -> resume is byte-identical to the never-suspended oracle
    with ZERO recomputed/re-emitted tokens, under the ORIGINAL request
    id, across layouts x cache dtypes x greedy/seeded."""
    oracle = _mk(params, layout, dtype)
    eng = _mk(params, layout, dtype)
    for sp in (GREEDY, SEEDED):
        want = list(oracle.generate(list(PROMPT), sp).token_ids)
        rid = eng.add_request(list(PROMPT), sp)
        _run_until(eng, rid, 6)
        pre = list(eng._requests[rid].token_ids)
        info = eng.suspend_request(rid, publish=False)
        assert info["nbytes"] > 0 and info["published"] is False
        assert eng._requests[rid].finish_reason == "suspended"
        assert eng.suspended_requests() == [rid]
        assert not eng.has_unfinished()  # slot and queue fully retired
        assert eng.resume_suspended(rid) == rid
        toks = _drain(eng, rid)
        assert toks == want, f"{layout}/{dtype}/temp={sp.temperature}"
        assert toks[: len(pre)] == pre  # nothing re-emitted or dropped
        assert len(pre) < len(toks)  # the resume actually continued
    stats = eng.suspend_stats()
    assert stats["suspended"] == 2 and stats["resumed"] == 2
    assert stats["held"] == 0 and stats["spilled_bytes"] > 0


def test_resume_races_concurrent_admission(params):
    """Resume while a fresh request is being admitted into the freed
    slot: restore just appends to the waiting queue under the lock, both
    requests finish, and the resumed stream stays oracle-identical."""
    oracle = _mk(params)
    want1 = list(oracle.generate(list(PROMPT), GREEDY).token_ids)
    want2 = list(oracle.generate(list(PROMPT_B), GREEDY).token_ids)
    eng = _mk(params)
    rid1 = eng.add_request(list(PROMPT), GREEDY)
    _run_until(eng, rid1, 5)
    pre = list(eng._requests[rid1].token_ids)
    eng.suspend_request(rid1, publish=False)
    rid2 = eng.add_request(list(PROMPT_B), GREEDY)
    eng.step()  # admission wave claims the freed slot while rid1 is spilled
    assert eng.resume_suspended(rid1) == rid1
    outs = {}
    while eng.has_unfinished():
        for o in eng.step():
            if o.finished and o.finish_reason != "suspended":
                outs[o.request_id] = o
    assert list(outs[rid1].token_ids) == want1
    assert outs[rid1].token_ids[: len(pre)] == pre
    assert list(outs[rid2].token_ids) == want2


def test_suspend_resume_via_object_plane_and_loss_is_typed(params, rt):
    """The plane tier: with the DRAM copy evicted the resume fetches the
    published checkpoint (still oracle-identical); with BOTH tiers gone
    the resume is a bounded, typed MigrationLostError — never a hang —
    and the spent record is no longer claimable."""
    oracle = _mk(params)
    want = list(oracle.generate(list(PROMPT), GREEDY).token_ids)
    eng = _mk(params)
    rid = eng.add_request(list(PROMPT), GREEDY)
    _run_until(eng, rid, 6)
    pre = list(eng._requests[rid].token_ids)
    info = eng.suspend_request(rid)  # publish=True
    assert info["published"] is True
    rec = eng._suspended[rid]
    rec["state"] = None  # DRAM tier evicted: only the plane copy remains
    assert eng.resume_suspended(rid) == rid
    toks = _drain(eng, rid)
    assert toks == want and toks[: len(pre)] == pre

    rid_b = eng.add_request(list(PROMPT_B), GREEDY)
    _run_until(eng, rid_b, 6)
    assert eng.suspend_request(rid_b)["published"] is True
    rec_b = eng._suspended[rid_b]
    rec_b["state"] = None
    from ray_tpu.exceptions import ObjectLostError

    chaos.inject("direct.get_owned_view", raises=ObjectLostError)  # plane copy dies too
    t0 = time.time()
    with pytest.raises(MigrationLostError):
        eng.resume_suspended(rid_b)
    chaos.clear()
    assert time.time() - t0 < 30.0
    assert eng.suspend_stats()["dropped"] == 1
    with pytest.raises(MigrationError):  # the record was consumed
        eng.resume_suspended(rid_b)


def test_suspend_chaos_typed_and_conversation_untouched(params):
    """Chaos at ``llm.suspend`` (drop AND injected fault) refuses with a
    typed MigrationError before any state mutates: the conversation is
    still RUNNING and finishes oracle-identical."""
    oracle = _mk(params)
    want = list(oracle.generate(list(PROMPT), GREEDY).token_ids)
    eng = _mk(params)
    rid = eng.add_request(list(PROMPT), GREEDY)
    _run_until(eng, rid, 4)
    chaos.inject("llm.suspend", drop_prob=1.0)
    with pytest.raises(MigrationError):
        eng.suspend_request(rid)
    chaos.inject("llm.suspend", raises=RuntimeError)
    with pytest.raises(MigrationError):
        eng.suspend_request(rid)
    chaos.clear()
    assert not eng._requests[rid].finished
    assert eng.suspended_requests() == []
    assert eng.suspend_stats()["suspended"] == 0
    assert _drain(eng, rid) == want


def test_suspend_refusals_and_drop(params):
    """Unknown/finished requests refuse typed; drop_suspended frees the
    record exactly once."""
    eng = _mk(params)
    with pytest.raises(MigrationError):
        eng.suspend_request("nope")
    with pytest.raises(MigrationError):
        eng.resume_suspended("nope")
    out = eng.generate(list(PROMPT), GREEDY)
    with pytest.raises(MigrationError):
        eng.suspend_request(out.request_id)
    rid = eng.add_request(list(PROMPT_B), GREEDY)
    _run_until(eng, rid, 3)
    eng.suspend_request(rid, publish=False)
    assert eng.drop_suspended(rid) is True
    assert eng.drop_suspended(rid) is False
    assert eng.suspend_stats()["dropped"] == 1 and eng.suspended_requests() == []
