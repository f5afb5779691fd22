"""Cluster KV plane (llm/kvplane/): cross-replica prefix reuse.

The guarantees under test:

- IDENTITY: a prefix prefilled on replica A serves a TOKEN-IDENTICAL
  completion on replica B (both KV layouts, fp and int8 wire), with the
  hit reported in prefix_cache_stats()'s REMOTE tier and the next
  same-prefix request on B hitting the LOCAL tier (re-publish).
- KEY STABILITY: prefix keys are content-stable blake2b digests —
  identical across processes regardless of PYTHONHASHSEED (the bug that
  made Python's salted hash() un-shareable) — and the local PrefixCache
  and the cluster index share the one key space.
- BOUNDED FAILURE: an evicted/lost remote block degrades to local
  prefill (correct output, bounded time, never a hang) and the dead
  route is dropped from the index; local eviction unregisters-then-frees
  the published copy.
- STALENESS: a dead replica's entries stop matching after its lease
  (router never routes to them).
- ROUTING: cache-aware scoring lands shared-prefix traffic on the
  holder, sheds under load, balances cold traffic.

Engines are tiny CPU configs; the object plane is the real direct plane
(rt fixture), exactly like tests/test_llm_disagg.py's router tests.
"""

import hashlib
import subprocess
import sys
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import ray_tpu  # noqa: E402
from ray_tpu.llm import LLMEngine, SamplingParams  # noqa: E402
from ray_tpu.llm.kvplane import (  # noqa: E402
    CacheAwareRouter,
    KVPlaneClient,
    KVRouteError,
    PrefixIndex,
    boundary_keys,
    rank_replicas,
    stable_hash,
    token_bytes,
)
from ray_tpu.llm.kvplane.index import prefix_key  # noqa: E402
from ray_tpu.models.llama import LlamaConfig, init_params  # noqa: E402

pytestmark = pytest.mark.usefixtures("shared_step_programs")  # many engines of equal configurations: their step programs compile once (conftest.py)

CFG = LlamaConfig.tiny(dtype="float32", remat=False, max_seq_len=128)
SP = SamplingParams(max_tokens=6, temperature=0.0)
RNG = np.random.default_rng(7)
SHARED = [int(x) for x in RNG.integers(1, CFG.vocab_size - 1, size=70)]  # >= one 64-block


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def rt():
    """The real object plane: publish/fetch ride direct.put_owned /
    get_owned_view exactly as in a fleet (owner-local shm + borrows)."""
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2)
    yield
    ray_tpu.shutdown()


def _engine(params, plane=None, **kw):
    kw.setdefault("max_num_seqs", 2)
    kw.setdefault("max_seq_len", 128)
    return LLMEngine(CFG, params, kv_plane=plane, **kw)


def _client(idx, rid, **kw):
    """Plane client with publish-on-first-store (publish_min_hits=1):
    these tests exercise the publish/fetch/evict MACHINERY, where the
    capacity policy's default skip-the-first-sighting would just add a
    warm-up request to every scenario. The policy itself is locked by
    test_publish_min_hits_policy."""
    kw.setdefault("publish_min_hits", 1)
    return KVPlaneClient(idx, rid, **kw)


@pytest.fixture(scope="module")
def oracle_fp(params):
    """One shared slots-fp oracle engine (no plane): every default-config
    identity assertion compares against it, so the module pays its
    compiles once. Its own prefix cache is fine — prefix-hit ≡ full
    prefill identity is already locked by test_llm_advanced."""
    return _engine(params)


# --------------------------------------------------------------- key space


def test_stable_hash_is_content_derived_and_hashseed_independent():
    """The key is blake2b over int32 token bytes — locked against the
    exact derivation here, and against PYTHONHASHSEED in subprocesses
    (builtin hash() of the same tuple differs across seeds; these keys
    must not)."""
    ids = [3, 1, 4, 1, 5, 9, 2, 6]
    expect = hashlib.blake2b(
        b"rt-kvplane-v1:" + np.asarray(ids, np.int32).tobytes(), digest_size=16
    ).digest()
    assert stable_hash(ids) == expect
    assert stable_hash(token_bytes(ids)) == expect
    prog = (
        "import importlib.util, sys;"
        "spec = importlib.util.spec_from_file_location('idx', sys.argv[1]);"
        "m = importlib.util.module_from_spec(spec); spec.loader.exec_module(m);"
        "print(m.stable_hash([3, 1, 4, 1, 5, 9, 2, 6]).hex())"
    )
    import os

    path = os.path.join(os.path.dirname(ray_tpu.__file__), "llm", "kvplane", "index.py")
    digests = set()
    for seed in ("0", "1"):
        r = subprocess.run(
            [sys.executable, "-c", prog, path],
            env={**os.environ, "PYTHONHASHSEED": seed},
            capture_output=True, text=True, timeout=60,
        )
        assert r.returncode == 0, r.stderr
        digests.add(r.stdout.strip())
    assert digests == {expect.hex()}, "prefix keys must not depend on the process hash seed"


def test_boundary_keys_strict_and_publish_modes():
    ids = list(range(200))
    strict = boundary_keys(ids, 64)
    assert [n for n, _ in strict] == [64, 128, 192]  # strictly shorter than 200
    assert [n for n, _ in boundary_keys(ids[:192], 64)] == [64, 128]  # 192 excluded at len 192
    full = boundary_keys(ids[:128], 64, strict=False)
    assert [n for n, _ in full] == [64, 128]  # publish side: own tail included
    buf = token_bytes(ids)
    assert strict[0][1] == prefix_key(buf, 64) == stable_hash(ids[:64])


def test_prefix_cache_keys_are_stable_hashes(params):
    """The LOCAL cache and the CLUSTER index share one key space: after a
    store, the cache's internal map is keyed by the same digests
    boundary_keys derives."""
    eng = _engine(params)
    eng.generate(SHARED + [5, 6], SP)
    cache = eng._prefix_cache
    (n, key), = boundary_keys(SHARED + [5, 6], cache.block)
    assert n == 64 and key in cache._keys
    assert cache._keys[key][1] == 64


# ------------------------------------------------------------------ index


def test_index_longest_live_match_staleness_and_lost_routes():
    clock = {"t": 1000.0}
    idx = PrefixIndex(ttl_s=5.0, time_fn=lambda: clock["t"])
    keys = boundary_keys(list(range(140)), 64)  # n = 64, 128
    idx.register("A", [(key, n, {"nbytes": 1}, f"ref-{n}") for n, key in keys])
    hit = idx.lookup(keys)
    assert hit["n"] == 128 and hit["replica"] == "A" and hit["ref"] == "ref-128"
    assert idx.lookup(keys, exclude="A") is None  # own entries never "remote"
    assert idx.match_replicas(keys) == {"A": 128}
    # a second, shorter holder: longest still wins; match is per-replica
    idx.register("B", [(keys[0][1], 64, {}, "b-ref")])
    assert idx.lookup(keys)["n"] == 128
    assert idx.match_replicas(keys) == {"A": 128, "B": 64}
    # lease expiry: A goes silent -> its entries stop matching (the
    # "router never routes to a dead replica" contract), B stays
    clock["t"] += 4.0
    idx.heartbeat("B")
    clock["t"] += 2.0  # A last seen 6s ago > ttl 5; B 2s ago
    assert idx.match_replicas(keys) == {"B": 64}
    assert idx.lookup(keys)["replica"] == "B"
    # pruning actually removes the dead replica's entries
    assert idx.expire() == 2
    assert idx.stats()["replicas_known"] == 1
    # a heartbeat revives liveness for anything still registered
    idx.heartbeat("B")
    assert idx.match_replicas(keys) == {"B": 64}
    # lost-route report drops the one dead entry
    idx.report_lost("B", keys[0][1])
    assert idx.lookup(keys) is None and idx.match_replicas(keys) == {}


def test_router_scoring_prefers_holder_then_sheds_on_load():
    replicas = ["r0", "r1", "r2"]
    # holder wins over idle peers
    assert rank_replicas(replicas, {"r1": 128}, {}, 140)[0] == "r1"
    # a swamped holder sheds to an idle peer (load_weight dominates once
    # inflight backlog outweighs the match fraction)
    ranked = rank_replicas(replicas, {"r1": 128}, {"r1": 20}, 140, load_weight=0.1)
    assert ranked[0] != "r1"
    # cold traffic balances by load, ties break on declaration order
    assert rank_replicas(replicas, {}, {"r0": 2, "r1": 0, "r2": 0}, 100)[0] == "r1"
    assert rank_replicas(replicas, {}, {}, 100) == replicas


def test_router_retries_next_ranked_then_bounded_failure():
    idx = PrefixIndex()
    calls = []

    def submit(rid, prompt, sp):
        calls.append(rid)
        if len(calls) == 1:
            raise ConnectionError("replica died")
        return {"token_ids": [1], "finish_reason": "length", "replica": rid}

    router = CacheAwareRouter(idx, submit, ["r0", "r1"], max_attempts=2)
    out = router.generate(list(range(70)), {})
    assert out["replica"] == "r1" and calls == ["r0", "r1"]
    assert router.stats()["retries"] == 1

    def always_dead(rid, prompt, sp):
        raise ConnectionError("no replica alive")

    router2 = CacheAwareRouter(idx, always_dead, ["r0", "r1"], max_attempts=2)
    with pytest.raises(KVRouteError):
        router2.generate(list(range(70)), {})
    assert router2.stats()["failed"] == 1 and all(v == 0 for v in router2.stats()["inflight"].values())


def test_index_breaker_opens_and_heartbeat_reregisters_after_prune():
    """Two plane-degradation guards: (1) repeated index failures open the
    client's circuit breaker so a dead index costs one timeout, not one
    per admission under the engine lock; (2) a replica the index PRUNED
    (partition outliving the lease + expire()) re-registers its live
    published blocks on the next heartbeat — pruned entries can never
    stay unroutable forever."""

    class _DeadIndex:
        def __getattr__(self, name):
            def boom(*a, **k):
                raise ConnectionError("index down")

            return boom

    c = KVPlaneClient(_DeadIndex(), "r", heartbeat_every_s=0.0, index_down_cooldown_s=60.0)
    assert c.lookup([(64, b"k")]) is None  # failure 1
    c.maybe_heartbeat()  # failure 2 -> breaker opens
    assert c.index_down() and c.stats()["index_down"]
    assert c.lookup([(64, b"k")]) is None  # short-circuits, no new RPC
    assert c.stats()["index_errors"] == 2

    class _Ref:
        class id:  # noqa: N801 — mimics ObjectRef.id.binary()
            @staticmethod
            def binary():
                return b"ref-1"

    clock = {"t": 0.0}
    idx = PrefixIndex(ttl_s=5.0, time_fn=lambda: clock["t"])
    c2 = KVPlaneClient(idx, "A", heartbeat_every_s=0.0)
    key = stable_hash([1, 2, 3])
    c2._published[key] = (64, {"nbytes": 1}, _Ref())
    c2._ref_keys[b"ref-1"] = {key}
    idx.register("A", [(key, 64, {"nbytes": 1}, _Ref())])
    clock["t"] += 10.0  # lease lapses
    assert idx.expire() == 1 and idx.stats()["keys"] == 0  # pruned
    c2.maybe_heartbeat()  # reply says 0 known keys < 1 published -> re-register
    assert idx.stats()["keys"] == 1
    assert idx.match_replicas([(64, key)]) == {"A": 64}


# ------------------------------------------- cross-replica identity (tentpole)


@pytest.mark.parametrize(
    "layout,dtype",
    [("slots", None), ("slots", "int8"), ("paged", None), ("paged", "int8")],
    ids=["slots-fp", "slots-int8", "paged-fp", "paged-int8"],
)
def test_cross_replica_prefix_reuse_token_identical(params, rt, layout, dtype):
    """ISSUE 12 acceptance: a prefix prefilled on replica A serves a
    token-identical completion on replica B, with the hit in the REMOTE
    tier — both layouts, fp and int8 wire. A second same-prefix request
    on B hits the LOCAL tier (the fetched block re-stored + republished)."""
    kw = dict(kv_layout=layout, cache_dtype=dtype)
    if layout == "paged":
        kw["page_size"] = 32
    idx = PrefixIndex()
    a = _engine(params, _client(idx, "A"), **kw)
    a.generate(SHARED + [5, 6, 7], SP)
    assert a.prefix_cache_stats()["remote"]["published_blocks"] == 1
    assert idx.stats()["keys"] == 1

    prompt_b = SHARED + [9, 10, 11, 12]
    b = _engine(params, _client(idx, "B"), **kw)
    out_b = b.generate(prompt_b, SP)
    oracle_eng = _engine(params, **kw)  # same layout/dtype, no plane
    oracle = oracle_eng.generate(prompt_b, SP)
    assert out_b.token_ids == oracle.token_ids, f"{layout}/{dtype}: remote-hit stream diverged"
    s = b.prefix_cache_stats()
    assert s["remote"]["hits"] == 1 and s["remote"]["tokens_saved"] == 64
    assert s["remote"]["fetched_bytes"] > 0 and s["local"]["hits"] == 0
    if dtype == "int8":
        # int8 wire: the published block ships quantized values + scales
        # at roughly half the fp bytes
        assert s["remote"]["fetched_bytes"] < 0.75 * 64 * CFG.num_layers * CFG.num_kv_heads * CFG.hd * 2 * 4

    # the fetched prefix re-published locally: next hit is LOCAL tier and
    # still token-identical
    prompt_b2 = SHARED + [42, 43]
    out_b2 = b.generate(prompt_b2, SP)
    assert out_b2.token_ids == oracle_eng.generate(prompt_b2, SP).token_ids
    s2 = b.prefix_cache_stats()
    assert s2["local"]["hits"] == 1 and s2["remote"]["hits"] == 1
    assert idx.stats()["keys"] == 1 and idx.match_replicas(
        boundary_keys(prompt_b2, 64)
    ).keys() == {"A", "B"}


def test_publish_min_hits_policy(params, rt):
    """Capacity-aware publication policy (ROADMAP item 1 follow-on): with
    the default publish_min_hits=2, a ONCE-seen prefix (one store, no
    reuse evidence) is NOT published — no wire quantize, no owned object,
    no index entry — and the skip is counted in the plane tier; the
    SECOND sighting (the first local hit's re-offer) publishes it."""
    idx = PrefixIndex()
    a = _engine(params, KVPlaneClient(idx, "A"))  # default policy: min_hits=2
    a.generate(SHARED + [5, 6], SP)  # store mints the 64-boundary: seen=1
    s = a.prefix_cache_stats()
    assert idx.stats()["keys"] == 0, "a once-seen prefix must not publish"
    assert s["plane"]["published_skipped"] == 1
    assert s["plane"]["published_blocks"] == 0 and s["remote"]["published_blocks"] == 0

    a.generate(SHARED + [7, 8], SP)  # local hit -> re-offer: seen=2 -> publish
    s = a.prefix_cache_stats()
    assert s["local"]["hits"] == 1
    assert idx.stats()["keys"] == 1, "the second sighting must publish"
    assert s["plane"]["published_blocks"] == 1 and s["remote"]["published_blocks"] == 1
    assert s["plane"]["published_skipped"] == 1  # no new skips

    # a REMOTE FETCH is itself reuse evidence: replica B's republish of
    # the block it just fetched bypasses the policy (proven_reuse), so B
    # registers as a second holder immediately — not after min_hits of
    # its own local traffic
    b = _engine(params, KVPlaneClient(idx, "B"))  # default policy too
    b.generate(SHARED + [9, 10], SP)
    sb = b.prefix_cache_stats()
    assert sb["remote"]["hits"] == 1
    assert sb["plane"]["published_blocks"] == 1 and sb["plane"]["published_skipped"] == 0
    assert idx.match_replicas(boundary_keys(SHARED + [0], 64)).keys() == {"A", "B"}


def test_publish_runs_with_engine_lock_released(params, rt):
    """Regression for the CCR001 fix in LLMEngine._plane_publish: the
    actual publish — serialization, put_owned, a 10s-timeout index
    register RPC — must run at the step tail with the engine lock
    RELEASED (a slow plane/index must never stall admissions or any
    lock-holding caller), while the block is still published by the time
    step() returns (the contract every kvplane test above leans on)."""
    idx = PrefixIndex()
    client = _client(idx, "A")
    eng = _engine(params, client)
    real_publish = client.publish
    held_at_publish = []

    def guarded(*a, **kw):
        held_at_publish.append(eng._lock.locked())
        return real_publish(*a, **kw)

    client.publish = guarded
    eng.generate(SHARED + [5, 6], SP)
    assert held_at_publish, "the minted prefix block was never offered to the plane"
    assert not any(held_at_publish), \
        "kv_plane.publish() ran while the engine lock was held"
    assert eng.prefix_cache_stats()["remote"]["published_blocks"] == 1
    assert idx.stats()["keys"] == 1  # registered by the time generate() returned


def test_publish_free_failure_is_counted_not_raised(params, rt, monkeypatch):
    """Regression for the ERR001 fix in KVPlaneClient.publish: when the
    index register RPC fails (the compensating path frees the freshly
    put owned block) AND that free ALSO fails, publish still degrades to
    0 — it never raises into the prefill stage — but the stranded
    owner-side bytes stay visible as a free_errors count instead of
    vanishing in a silent swallow."""
    from ray_tpu.core import direct

    client = _client(PrefixIndex(), "A")
    monkeypatch.setattr(client, "_safe_call", lambda *a, **kw: None)

    def boom(refs):
        raise RuntimeError("owner store unreachable")

    monkeypatch.setattr(direct, "free_owned", boom)
    ids = list(range(1, 65))  # one full 64-token block boundary
    blk = np.zeros((2, 64, 1, 4), np.float32)
    assert client.publish(ids, blk, blk) == 0
    assert client.counts["free_errors"] == 1
    assert client.counts["published_blocks"] == 0


def test_blocked_follower_still_hits_leaders_same_wave_store(params):
    """A leader and a shared-prefix follower arriving together, pool too
    small for both: the follower's first resolution MISSES (the leader's
    store hasn't run yet) and gets cached — but the store-generation
    check re-resolves it once the leader mints the prefix, so the
    follower admits through the cached-insert + suffix-extend path (a
    local hit), never a redundant full prefill. Accounting stays
    once-per-request: 2 requests -> exactly 1 hit."""
    eng = LLMEngine(
        CFG, params, max_num_seqs=2, max_seq_len=128, kv_layout="paged",
        page_size=32, num_pages=7,  # leader's bucket+headroom starves the follower
    )
    leader = SHARED + [8, 9]
    follower = SHARED + [3, 4, 5]
    eng.add_request(leader, SamplingParams(max_tokens=24, temperature=0.0))
    eng.add_request(follower, SP)
    outs = {}
    while eng.has_unfinished():
        for o in eng.step():
            if o.finished:
                outs[len(o.prompt_token_ids)] = o.token_ids
    s = eng.prefix_cache_stats()
    assert s["hits"] == 1 and s["tokens_saved"] == 64, s
    fresh = _engine(params).generate(follower, SP)
    assert outs[len(follower)] == fresh.token_ids


def test_evicted_remote_block_bounded_retry_local_prefill(params, rt, oracle_fp):
    """The block is routed but its bytes are GONE (owner freed it under
    the index's feet): B's fetch exhausts its bounded retries, falls back
    to a full local prefill — correct output, bounded wall time, no hang
    — and the dead route is dropped so the next request never retries it."""
    from ray_tpu.core import direct

    idx = PrefixIndex()
    a = _engine(params, _client(idx, "A"))
    a.generate(SHARED + [5, 6, 7], SP)
    # simulate the eviction RACE: free the owned bytes WITHOUT
    # unregistering (a clean eviction unregisters first; the race is what
    # the bounded-retry fallback exists for)
    key = boundary_keys(SHARED + [1], 64)[0][1]
    ref = idx._entries[key]["A"]["ref"]
    direct.free_owned([ref.id])

    prompt = SHARED + [9, 10, 11]
    b = _engine(params, _client(idx, "B", fetch_timeout_s=1.0, fetch_retries=1, retry_wait_s=0.05))
    t0 = time.time()
    out_b = b.generate(prompt, SP)
    assert time.time() - t0 < 30, "lost-block fallback must be bounded, not a hang"
    assert out_b.token_ids == oracle_fp.generate(prompt, SP).token_ids, "fallback prefill diverged"
    # the fetch resolves on the engine's async worker: with the client's
    # retry budget above the fetch deadline the request abandons to local
    # prefill FIRST and the terminal lost-accounting lands when the
    # worker finishes (zombie reap) — poll briefly for it
    deadline = time.time() + 10
    while time.time() < deadline:
        s = b.prefix_cache_stats()
        if s["remote"]["lost"]:
            break
        time.sleep(0.05)
    assert s["remote"]["hits"] == 0 and s["remote"]["lost"] == 1
    assert s["plane"]["fetch_lost"] == 1
    # report_lost dropped the dead route; B's own publish (from its local
    # prefill) is now the only holder
    assert idx.match_replicas(boundary_keys(prompt, 64)) == {"B": 64}


def test_local_eviction_unregisters_then_frees(params, rt):
    """Clean eviction lifecycle: the LRU evicting a published group first
    unregisters its keys (route dies) and then frees the owned object
    (bytes die) — nothing left for a peer to route to, nothing leaked."""
    from ray_tpu.llm.disagg.handoff import HandoffLostError, fetch as fetch_handoff

    idx = PrefixIndex()
    client = _client(idx, "A")
    a = _engine(params, client)
    a.generate(SHARED + [5, 6], SP)
    key = boundary_keys(SHARED + [1], 64)[0][1]
    ref = idx._entries[key]["A"]["ref"]
    with a._lock:
        a._prefix_cache._evict_one()
    # the unregister-then-free pair runs on the client's eviction worker
    # (off the engine lock); await it with a bounded poll
    deadline = time.time() + 10.0
    while time.time() < deadline and (idx.stats()["keys"] or client.stats()["unpublished_blocks"] < 1):
        time.sleep(0.02)
    assert idx.stats()["keys"] == 0, "eviction must unregister the route"
    assert client.stats()["unpublished_blocks"] == 1
    with pytest.raises(HandoffLostError):
        fetch_handoff(ref, kind="kv_prefix", timeout_s=0.5, retries=0)


def test_cache_aware_router_over_live_engines(params, rt, oracle_fp):
    """Routing policy over two real engines sharing one index: the first
    shared-prefix request is cold and lands by load order; every later
    one routes to the HOLDER (local-tier hit, no fetch), token-identical
    to the oracle."""
    idx = PrefixIndex()
    engines = {
        "r0": _engine(params, _client(idx, "r0")),
        "r1": _engine(params, _client(idx, "r1")),
    }

    def submit(rid, prompt, sp):
        out = engines[rid].generate(prompt, SamplingParams(**sp))
        return {"token_ids": out.token_ids, "finish_reason": out.finish_reason, "replica": rid}

    router = CacheAwareRouter(idx, submit, list(engines), block=64)
    sp = {"max_tokens": 6, "temperature": 0.0}
    first = router.generate(SHARED + [5, 6, 7], sp)
    assert first["replica"] == "r0" and router.stats()["cold"] == 1
    outs = [router.generate(SHARED + [40 + i], sp) for i in range(3)]
    assert all(o["replica"] == "r0" for o in outs), "shared-prefix traffic must land on the holder"
    assert router.stats()["routed_to_holder"] == 3
    assert engines["r0"].prefix_cache_stats()["local"]["hits"] == 3
    assert engines["r1"].prefix_cache_stats()["remote"]["hits"] == 0  # never fetched: affinity held
    oracle = oracle_fp.generate(SHARED + [40], SamplingParams(**sp))
    assert outs[0]["token_ids"] == oracle.token_ids


# ------------------------------------------------------------ codec + serve


def test_prefix_codec_validation(params):
    """kind=kv_prefix rides the handoff codec's validation: no logits on
    the wire, kind confusion rejected, scale garbage rejected."""
    from ray_tpu.llm.disagg import handoff

    k = np.zeros((2, 64, 2, 4), np.float32)
    kv = {"k": k, "v": k.copy(), "n": 64, "prompt_token_ids": list(range(64))}
    wire = handoff.encode(kv, kind=handoff.PREFIX_KIND)
    assert "logits" not in wire
    out = handoff.decode(wire, kind=handoff.PREFIX_KIND)
    assert out["n"] == 64 and "logits" not in out
    with pytest.raises(handoff.HandoffError):
        handoff.decode(wire)  # a prefix block is NOT a kv_handoff
    with pytest.raises(handoff.HandoffError):
        handoff.decode({"kind": "kv_handoff"}, kind=handoff.PREFIX_KIND)
    bad = dict(wire)
    bad["n"] = 70  # n must equal len(prompt)
    with pytest.raises(handoff.HandoffError):
        handoff.decode(bad, kind=handoff.PREFIX_KIND)
    q = dict(kv, k=k.astype(np.int8), v=k.astype(np.int8))
    with pytest.raises(handoff.HandoffError):
        handoff.encode(q, kind=handoff.PREFIX_KIND)  # int8 without scales
    # meta accounting works without logits
    assert handoff.meta_of(wire)["nbytes"] == 2 * k.nbytes


def test_serve_kvplane_deployment_graph_and_replica_stats(params):
    """The Serve pieces: build_kvplane_deployment flattens into index +
    N addressable single-replica deployments + router ingress (each
    replica arg a handle marker), and a KVPlaneServer surfaces the
    tiered stats next to the other *_stats endpoints."""
    from ray_tpu.serve.deployment import _HandleMarker, build_app_spec
    from ray_tpu.serve.llm import KVPlaneServer, LLMConfig, build_kvplane_deployment

    app = build_app_spec(
        build_kvplane_deployment(LLMConfig(model_config=CFG), num_replicas=2, name="kvp"),
        "app",
    )
    specs, ingress = app
    names = {s["name"] for s in specs}
    assert names == {"kvp-kvindex", "kvp-r0", "kvp-r1", "kvp-router"}
    assert ingress == "kvp-router"
    router_spec = next(s for s in specs if s["name"] == "kvp-router")
    # index + the two replica handles resolve inside the router replica
    markers = [a for a in router_spec["init_args"] if isinstance(a, _HandleMarker)]
    assert {m.deployment for m in markers} == {"kvp-kvindex", "kvp-r0", "kvp-r1"}
    assert router_spec["init_args"][2] == ("kvp-r0", "kvp-r1")
    replica_spec = next(s for s in specs if s["name"] == "kvp-r0")
    assert replica_spec["config"].num_replicas == 1  # addressable: the scoring target

    # replica surface (in-process index, no cluster): stats tiers exposed
    idx = PrefixIndex()
    server = KVPlaneServer(
        LLMConfig(model_config=CFG, params=params,
                  engine_kwargs={"max_num_seqs": 2, "max_seq_len": 128}, prewarm=False),
        idx, "kvp-r0",
    )
    try:
        out = server.generate(SHARED + [3], {"max_tokens": 4, "temperature": 0.0}, timeout_s=120.0)
        assert len(out["token_ids"]) == 4
        s = server.kvplane_stats()
        assert "local" in s and "remote" in s and s["plane"]["replica_id"] == "kvp-r0"
    finally:
        server._stopped = True
