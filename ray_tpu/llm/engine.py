"""Continuous-batching LLM engine (the module models/llama.py promises).

Architecture (TPU-native replacement for the reference's vLLM wrapping in
python/ray/llm/_internal/serve/engines/vllm/vllm_engine.py):

- a static slot-based KV cache (kv_cache.py) or paged pool (paged_kv.py)
  compiled once;
- prompt prefill bucketed to powers of two (one compiled program per
  bucket, not per prompt length), and BATCHED: same-bucket admissions
  run as one forward with the batch dim padded to a power of two;
- a DEVICE-RESIDENT decode loop (Podracer-style): tokens, PRNG keys,
  sampling params, block tables and lengths live on device; one fused
  jitted step advances *all* slots one token (decode -> sample ->
  append-KV -> advance lengths) with the big buffers donated, scheduler
  changes land as O(1) scatter deltas, and token readback overlaps the
  next step's dispatch (emission trails the device by one step);
- a host-side scheduler does admission (waiting queue -> free slot),
  completion (eos / max_tokens / stop ids), and slot recycling between
  device steps against numpy shadow state. The device never sees dynamic
  shapes, and nothing syncs the host per decode step;
- every step() is three explicit STAGES — admission (plan: queue ->
  slot/page reservation), prefill (execute: batched forwards +
  transferred-KV / prefix-hit scatter-ins), decode (dispatch + drain).
  The stage split is what disaggregated serving (llm/disagg/) rides: a
  prefill replica runs only the first two stages (prefill-only requests
  finish with their KV extracted into a handoff block), a decode replica
  admits handoff blocks through a fused scatter-in and runs the third;
- optional speculative decoding (speculative=SpecConfig(...), llm/spec/):
  a drafter proposes up to k tokens per lane and one fused verify step
  accepts/extends them — multiple tokens per tick, greedy output
  token-identical to the plain path (which stays untouched as the
  subsystem's equivalence oracle).

The one loop's reference is plain code that shares nothing with it:
tests/plain_reference.py and, for a description, its family's reference
(tests/hybrid_battery.py). Engine steps are cheap to drive from an actor
or a Serve replica; `generate()` is the batteries-included loop.
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from collections import deque
from dataclasses import dataclass, field

import numpy as np

from ray_tpu.llm.kvplane.index import prefix_key, token_bytes
from ray_tpu.llm.sampling import SamplingParams
from ray_tpu.llm.telemetry import LOCK_WAIT, NO_STAGE, stage

logger = logging.getLogger("ray_tpu.llm")

# public methods that move a sequence between engines as keys and values alone, and what each is
# called in the refusal a hybrid model's engine gives instead (what its description keeps, a state
# per sequence or a latent per position, is in no handoff, migration or KV-plane format)
KV_ONLY_METHODS = {
    "add_prefill_request": "disaggregated prefill (add_prefill_request)",
    "prefill_remote": "disaggregated prefill (prefill_remote)",
    "add_prefilled": "a transferred KV block (add_prefilled)",
    "checkpoint_request": "migration (checkpoint_request)",
    "restore_request": "migration (restore_request)",
    "suspend_request": "suspend (suspend_request)",
    "resume_suspended": "suspend (resume_suspended)",
    "adopt_prefetched": "the KV plane (adopt_prefetched)",
}


@dataclass
class RequestState:
    request_id: str
    prompt_token_ids: list
    params: SamplingParams
    token_ids: list = field(default_factory=list)
    logprobs: list = field(default_factory=list)
    slot: int = -1
    finished: bool = False
    finish_reason: str | None = None
    # streaming consumers read from here
    out_queue: "queue.SimpleQueue | None" = None
    # KV computed by a remote prefill engine (disaggregation)
    prefilled: dict | None = None
    # prefill-only: run admission+prefill stages, extract the KV block
    # into a handoff (pop_handoff) and finish — never enters decode
    prefill_only: bool = False
    # paged layout: admission order (preemption picks the youngest) and
    # preemption count (observability)
    admit_seq: int = -1
    preemptions: int = 0
    # telemetry lifecycle stamps (llm/telemetry.py; host wall clocks only)
    t_ingress: float | None = None  # serving entry (telemetry.INGRESS_T), before parse/encode/admission
    t_submit: float = 0.0
    lock_wait: float | None = None  # seconds the admitting thread waited for the engine's lock
    t_admit: float = 0.0
    t_first: float = 0.0
    t_last: float = 0.0
    itls: list = field(default_factory=list)
    # (trace_id, root_span_id, parent_span_id) when RT_TRACING=1; the
    # disagg handoff carries (trace_id, root_span_id) across replicas
    trace: tuple | None = None
    # prefix resolution cached across steps while the request is
    # head-of-line blocked (paged pool full): the lookup/fetch and its
    # hit accounting (cache counters, telemetry tiers, any object-plane
    # transfer) happen ONCE per request, never once per blocked step
    cached_pref: tuple | None = None
    # live migration (llm/migrate.py): a restored request's splice state
    # (exact PRNG key, spec controller state) consumed by _bind_resume —
    # set together with `prefilled` so the checkpointed KV block rides
    # the existing transferred-KV admission path, but the bind continues
    # generation instead of sampling a first token from shipped logits
    resume: dict | None = None
    # restore ingress wall clock (0.0 = never migrated): the splice
    # latency observed at the first post-splice token
    t_restore: float = 0.0


@dataclass
class RequestOutput:
    request_id: str
    prompt_token_ids: list
    token_ids: list
    new_token_ids: list
    finished: bool
    finish_reason: str | None = None
    logprobs: list | None = None
    streamed: bool = False  # consumer reads an out_queue, not this output


def _bucket(n: int, buckets) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"prompt length {n} exceeds the largest prefill bucket {buckets[-1]}")  # tpulint: disable=ERR002 — suspend_request wraps it `raise MigrationError(...) from e`; ingress callers treat it as 400-class input validation


# RequestState.cached_pref miss marker: prefix resolution ran and MISSED
# (distinct from None = not yet resolved). Cached as (_PREF_MISS, gen,
# expires_at) where gen is the local PrefixCache's store generation at
# resolution time: a blocked request must not re-pay the lookup/fetch
# every step, but a SAME-WAVE leader's store (admitted just before the
# block hit pool pressure) mints the prefix after the miss resolved — the
# generation mismatch re-resolves exactly then, so the follower still
# gets its hit when pages free. expires_at additionally time-bounds the
# miss on cluster-plane engines (another REPLICA's publish can't bump the
# local generation); local-only engines never expire it (nothing external
# can mint their keys).
_PREF_MISS = object()


class PrefixCache:
    """Hash-prefix KV reuse across requests (reference capability:
    enable_prefix_caching, python/ray/llm/_internal/serve/engines/vllm/
    vllm_models.py:215-228 — vLLM hashes fixed-size blocks; here prefixes
    are cached at block-aligned lengths as whole device arrays, matching
    the slot cache's contiguous layout, and admission re-attends the
    remaining suffix with model_runner.extend).

    Entries: stable_hash(tokens[:n]) -> (k [L, n, kv, hd], v, n) on
    device. Keys are CONTENT-STABLE blake2b digests over the token bytes
    (kvplane/index.py) — never Python's process-salted ``hash()``, whose
    PYTHONHASHSEED made the same prefix key out differently on every
    replica — so the local cache and the cluster KV plane index
    (ray_tpu/llm/kvplane/) speak one key space. LRU-evicted under a byte
    budget; ``evict_hook`` (set by the plane client) hears each evicted
    group's keys so published copies deregister-then-free before the
    bytes die. Stats drive tests and metrics.
    """

    def __init__(self, block: int = 64, max_bytes: int = 256 << 20):
        self.block = block
        self.max_bytes = max_bytes
        # called with the evicted group's key list (cluster KV plane:
        # unregister + free the published block); None = local-only cache
        self.evict_hook = None
        # store generation: bumped whenever new boundary keys mint, so a
        # cached resolution MISS (engine _PREF_MISS) knows when the cache
        # gained entries that could turn it into a hit
        self.gen = 0
        # one GROUP per stored prompt: shared (k, v) device arrays; every
        # block boundary of the prompt aliases into the group with its own
        # valid length (insert masks the padded tail, so no slicing)
        self._groups: dict = {}  # gid -> (k, v, nbytes, [keys])
        self._keys: dict = {}  # hash(prefix) -> (gid, n)
        self._order: deque = deque()  # LRU over gids: left = coldest
        self._next_gid = 0
        self._bytes = 0
        self.hits = 0
        self.misses = 0
        self.tokens_saved = 0
        self.evictions = 0

    def lookup(self, prompt_token_ids, admissible=None):
        """Longest block-aligned cached prefix STRICTLY shorter than the
        prompt (at least one token must remain to produce logits). Hits
        are verified token-for-token — a hash collision must never serve
        a foreign prompt's KV (the reference block cache exact-matches
        too). ``admissible(n) -> bool`` filters boundaries BEFORE they
        can match (the engine's suffix-overrun guard): a rejected longer
        boundary falls through to the next shorter one instead of
        discarding the whole lookup — and never inflates the hit
        counters on its way out."""
        ids = tuple(int(t) for t in prompt_token_ids)  # tuple ONCE, slice per boundary
        buf = token_bytes(ids)  # packed ONCE; each boundary hashes a slice
        n = ((len(ids) - 1) // self.block) * self.block
        while n >= self.block:
            if admissible is not None and not admissible(n):
                n -= self.block
                continue
            hit = self._keys.get(prefix_key(buf, n))
            if hit is not None:
                gid, n_valid = hit
                k, v, _, _, group_ids = self._groups[gid]
                # token-for-token verification against the group's ONE
                # stored tuple: a hash collision must never serve a
                # foreign prompt's KV (the reference block cache
                # exact-matches too)
                if group_ids[:n_valid] == ids[:n_valid]:
                    self._order.remove(gid)
                    self._order.append(gid)
                    self.hits += 1
                    self.tokens_saved += n_valid
                    return k, v, n_valid
            n -= self.block
        self.misses += 1
        return None

    def store(self, prompt_token_ids, ks, vs, buckets):
        """Cache a freshly prefilled prompt's K/V once, keyed at EVERY
        block boundary. ks/vs: [L, T_pad, kv, hd] device arrays, stored
        padded to the prefix's PREFILL BUCKET so re-insert reuses the
        already-compiled insert program (a raw per-length shape would mint
        one XLA program per distinct n). Returns ``(new_keys, pad)`` —
        the freshly minted (key, n) boundary pairs and the stored block
        width — so a cluster KV plane client can publish exactly what was
        stored (None when nothing new was cached)."""
        n_max = (len(prompt_token_ids) // self.block) * self.block
        if n_max < self.block:
            return None
        # ONE token tuple per group; boundary keys alias into it with
        # their valid length (no O(n^2/block) host tuples — lookup
        # verifies against slices of this single tuple)
        ids = tuple(int(t) for t in prompt_token_ids[:n_max])
        buf = token_bytes(ids)
        new_keys = []
        for n in range(self.block, n_max + 1, self.block):
            key = prefix_key(buf, n)
            if key not in self._keys:
                new_keys.append((key, n))
        if not new_keys:
            return None
        pad = _bucket(n_max, buckets)
        k = ks[:, :pad]
        v = vs[:, :pad]
        nbytes = int(k.nbytes) + int(v.nbytes)
        if nbytes > self.max_bytes:
            return None
        while self._bytes + nbytes > self.max_bytes and self._order:
            self._evict_one()
        gid = self._next_gid
        self._next_gid += 1
        self._groups[gid] = (k, v, nbytes, [key for key, _ in new_keys], ids)
        for key, n in new_keys:
            self._keys[key] = (gid, n)
        self._order.append(gid)
        self._bytes += nbytes
        self.gen += 1
        return new_keys, pad

    def _evict_one(self):
        gid = self._order.popleft()
        _, _, nbytes, keys, _ = self._groups.pop(gid)
        for key in keys:
            self._keys.pop(key, None)
        self._bytes -= nbytes
        self.evictions += 1
        if self.evict_hook is not None:
            # the route must die before the bytes: the hook unregisters
            # the published copy's keys and frees the owned block
            try:
                self.evict_hook(keys)
            except Exception:  # noqa: BLE001 — plane trouble never breaks eviction
                pass

    def stats(self) -> dict:
        return {
            "hits": self.hits,
            "misses": self.misses,
            "tokens_saved": self.tokens_saved,
            "evictions": self.evictions,
            "entries": len(self._groups),
            "bytes": self._bytes,
        }


class LLMEngine:
    """Continuous-batching engine over a slot KV cache.

    config: ray_tpu.models.llama.LlamaConfig, or a hybrid model's
    description (a config that mixes in models.hybrid.HybridDescription:
    layers of several kinds, a recurrent state per sequence kept in a
    state cache beside the KV rows; its step programs are
    llm/hybrid_runner.py's);
    params: matching pytree (if None, randomly initialized — useful for
    tests/benchmarks).
    """

    def __init__(
        self,
        config,
        params=None,
        *,
        max_num_seqs: int = 8,
        max_seq_len: int | None = None,
        prefill_buckets: tuple | None = None,
        seed: int = 0,
        cache_dtype: str | None = None,
        mesh=None,
        tp_collective: str = "fp",
        enable_prefix_caching: bool = True,
        prefix_cache_bytes: int = 256 << 20,
        prefix_block: int = 64,
        kv_plane=None,
        prefix_fetch_deadline_s: float = 2.0,
        kv_layout: str = "slots",
        num_pages: int | None = None,
        page_size: int = 64,
        attn_kernel: str = "xla",
        speculative=None,
        telemetry: bool = True,
        telemetry_tags: dict | None = None,
    ):
        """kv_layout: "slots" (static per-sequence rows; llm/kv_cache.py)
        or "paged" (block-table page pool; llm/paged_kv.py — concurrency
        bounded by total pages, vLLM-class memory management). For paged,
        ``num_pages`` sizes the pool (default: the slot-equivalent HBM,
        max_num_seqs * max_seq_len / page_size) and ``page_size`` must
        divide every prefill bucket and the prefix block.

        attn_kernel: paged-attention implementation for the decode /
        spec-verify / chunked-prefill hot path (kv_layout="paged" only).
        "xla" (default) is the gather-then-attend page scan — the
        token-identical oracle; "pallas" opts into the fused
        HBM-streaming kernel (llm/pallas/paged_attn.py: page-table
        gather, int8 dequant and flash-style attend in ONE program,
        interpret mode off-TPU). Validated here: an unknown value or
        "pallas" on the slot layout raises, and so does a config/platform
        the kernel cannot serve (kernel_supported says why):
        AttnKernelUnavailableError, never a quiet XLA run. The choice is
        ``engine.attn_kernel`` (bench provenance reads it).

        cache_dtype: KV-cache storage dtype, validated against
        {bfloat16/bf16, float32/f32, int8} (None = the model dtype).
        "int8" stores quantized K/V with per-layer/head amax scales
        (llm/kv_quant.py): quantize-on-append inside the fused step,
        dequantize-in-attention — ~2x the servable concurrency at fixed
        cache HBM, with the fp cache as the accuracy oracle
        (tests/test_llm_kv_int8.py).

        speculative (llm.spec.SpecConfig | None): speculative decoding on
        the device-resident loop — a drafter proposes up to k tokens per
        lane and one fused verify step accepts/extends them (llm/spec/).
        Greedy output stays token-identical to speculative=None, which is
        the subsystem's equivalence oracle (tests/test_llm_spec.py).

        tp_collective: dtype of the per-layer tensor-parallel all-reduce
        on the device-resident fused/spec hot path (only meaningful with
        a tp>=2 mesh). "fp" (default) reduces exactly at the operand
        dtype; "int8" quantizes the all-reduce payload to int8 with f32
        amax scales (EQuARX, arxiv 2506.17615) — ~1/2 the ICI bytes per
        layer at bf16 operands, with the fp-collective engine as the
        accuracy oracle (tests/test_llm_tp.py).

        kv_plane (llm.kvplane.KVPlaneClient | None): joins this engine to
        the CLUSTER prefix tier (ray_tpu/llm/kvplane/). Freshly cached
        prefixes publish as owned objects on the direct plane; a local
        prefix-cache miss LAUNCHES the cluster lookup+fetch on the
        engine's fetch worker — never under the engine lock — and the
        result splices in at a later admission wave, overlapping the
        transfer with the current wave's prefill/decode work. A landed
        block (bounded retry — an evicted/lost block degrades to local
        prefill, never a hang) scatter-ins through the existing fused
        insert/transparent-requant path and re-stores + republishes
        locally so the next hit is local-tier.
        ``prefix_fetch_deadline_s`` bounds how long an admission defers
        a request on its in-flight fetch: past it the request degrades
        to a plain local prefill and the late result is discarded.
        Requires enable_prefix_caching=True (the plane IS the cache's
        cluster tier). prefix_cache_stats() grows local/remote hit
        tiers."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.llm import kv_cache as kvc
        from ray_tpu.llm.model_runner import device_free_bytes, make_paged_runner_fns, make_runner_fns
        from ray_tpu.llm.sampling import sample_first, seed_keys
        from ray_tpu.models.llama import init_params
        from ray_tpu.util.compile_cache import enable_compile_cache

        enable_compile_cache()
        self.config = config
        self.mesh = mesh
        # a hybrid model (layers of several kinds, a recurrent state per sequence beside keys
        # and values) is known by its description and takes its step programs from
        # llm/hybrid_runner.py; what it cannot do yet is refused there, by name
        self._hybrid = hasattr(config, "layer_kinds")
        if self._hybrid:
            from ray_tpu.llm.hybrid_runner import refuse, refuser

            init_params = type(config).init_params  # noqa: F811 - the description carries its own weights
            refuse(config, kv_layout=kv_layout, cache_dtype=cache_dtype, mesh=mesh,
                   speculative=speculative, kv_plane=kv_plane)
            for name, what in KV_ONLY_METHODS.items():
                setattr(self, name, refuser(what, config))
            if enable_prefix_caching:
                # the default would engage: a hit needs a snapshot of the recurrent state at the
                # block boundary, which nothing keeps yet. Off, and said once an engine.
                logger.info("prefix caching is off for a hybrid model: a hit would need what its description "
                            "keeps (a recurrent state at the block boundary, a latent layer's rows), which the "
                            "prefix store does not hold; prefix_cache_stats() answers {}")
                enable_prefix_caching = False
        self._kv_layers = getattr(config, "num_kv_layers", config.num_layers)  # layers that keep keys and values (or a latent) per position
        # a description names what it keeps per position (``k`` and ``v`` by head, or a latent layer's
        # rows): the slot cache is allocated, inserted into and counted from these
        self._kv_entries = config.position_entries() if self._hybrid else None
        if tp_collective not in ("fp", "int8"):
            raise ValueError(f"tp_collective must be 'fp' or 'int8', got {tp_collective!r}")
        self.tp_collective = tp_collective
        self.max_num_seqs = int(max_num_seqs)
        self.max_seq_len = int(max_seq_len or config.max_seq_len)
        if kv_layout not in ("slots", "paged"):
            raise ValueError(f"kv_layout must be 'slots' or 'paged', got {kv_layout!r}")
        self.kv_layout = kv_layout
        if attn_kernel not in ("xla", "pallas"):
            raise ValueError(f"attn_kernel must be 'xla' or 'pallas', got {attn_kernel!r}")
        if attn_kernel == "pallas" and kv_layout != "paged":
            raise ValueError(
                "attn_kernel='pallas' is the paged-attention kernel and needs "
                "kv_layout='paged' (the slot layout has no page gather to fuse)"
            )
        from ray_tpu.llm.kv_quant import is_int8, normalize_cache_dtype

        # validate EARLY: an unsupported string must raise here, never
        # fall through to jnp.dtype() (or worse, silently serve bf16)
        self.kv_dtype = normalize_cache_dtype(cache_dtype) if cache_dtype is not None else config.dtype
        self.kv_quant = is_int8(self.kv_dtype)
        if prefill_buckets is None:
            b, buckets = 64, []
            while b < self.max_seq_len:
                buckets.append(b)
                b *= 2
            buckets.append(self.max_seq_len)
            prefill_buckets = tuple(buckets)
        self.prefill_buckets = tuple(sorted(prefill_buckets))
        self._sample_first = jax.jit(sample_first)

        if kv_layout == "paged":
            from ray_tpu.llm import paged_kv as pkv

            if any(b % page_size for b in self.prefill_buckets):
                raise ValueError(f"page_size {page_size} must divide every prefill bucket {self.prefill_buckets}")
            if prefix_block % page_size:
                raise ValueError(f"page_size {page_size} must divide prefix_block {prefix_block}")
            max_pg = -(-self.max_seq_len // page_size)
            if num_pages is None:
                # slot-equivalent HBM: same bytes, but shared across
                # sequences instead of stranded per slot (+1 for trash)
                num_pages = self.max_num_seqs * max_pg + 1
            self._pcfg = pkv.PagedCacheConfig(
                num_layers=config.num_layers,
                num_pages=int(num_pages),
                page_size=int(page_size),
                max_pages_per_seq=max_pg,
                num_slots=self.max_num_seqs,
                num_kv_heads=config.num_kv_heads,
                head_dim=config.hd,
                dtype=self.kv_dtype,
            )
            if attn_kernel == "pallas":
                # an explicit request that cannot be served is an error at
                # construction, never a quiet XLA run under the kernel's name
                from ray_tpu.exceptions import AttnKernelUnavailableError
                from ray_tpu.llm.pallas.paged_attn import kernel_supported
                from ray_tpu.parallel.mesh import axis_size as _tp_axis

                ok, why = kernel_supported(
                    self._pcfg.page_size, config.num_kv_heads, config.hd, quantized=self.kv_quant
                )
                if ok and mesh is not None and _tp_axis(mesh, "tp") > 1:
                    ok, why = False, "the shard_map tensor-parallel path does not ride the kernel yet"
                if not ok:
                    raise AttnKernelUnavailableError(f"attn_kernel='pallas' cannot be served: {why}")
            self.attn_kernel = attn_kernel
            self._prefill, self._insert, self._extend = make_paged_runner_fns(
                config, attn_impl=attn_kernel, mesh=mesh
            )
            self._page_alloc = pkv.PageAllocator(self._pcfg.num_pages)
            self._tables = np.zeros((self.max_num_seqs, max_pg), np.int32)
            self._lengths = np.zeros((self.max_num_seqs,), np.int32)
            self._slot_pages: list[list[int]] = [[] for _ in range(self.max_num_seqs)]
            self._admit_counter = 0
        else:
            self.attn_kernel = "xla"  # slot layout: no page gather to fuse
            if not self._hybrid:  # the hybrid's programs are hybrid_runner's, built below
                self._prefill, self._insert, self._extend = make_runner_fns(config, mesh=mesh)

        cache_cfg = (
            None
            if kv_layout == "paged" or self._hybrid
            else kvc.CacheConfig(
                num_layers=self._kv_layers,
                num_slots=self.max_num_seqs,
                max_seq_len=self.max_seq_len,
                num_kv_heads=config.num_kv_heads,
                head_dim=config.hd,
                dtype=self.kv_dtype,
            )
        )
        # disaggregation plumbing: fused extract (prefill side) and
        # scatter-in (decode side) programs for both layouts, plus the
        # completed-handoff stash pop_handoff() serves (llm/disagg/)
        from ray_tpu.llm.disagg.scatter import make_handoff_fns

        (self._extract_slots, self._extract_paged,
         self._scatter_slots, self._scatter_paged) = make_handoff_fns()
        self._handoffs: dict[str, dict] = {}

        if mesh is None:
            self.params = params if params is not None else init_params(config, jax.random.PRNGKey(seed))
            if kv_layout == "paged":
                from ray_tpu.llm import paged_kv as pkv

                self.pool = pkv.alloc(self._pcfg)
            elif self._hybrid:
                self.cache = kvc.alloc_entries(self._kv_entries, self.max_num_seqs, self.max_seq_len, config.ring_entries())
            else:
                self.cache = kvc.alloc(cache_cfg)
            if self._hybrid:
                from ray_tpu.llm import state_cache

                # the recurrent layers' state, one entry a slot, beside the KV rows
                self.state = state_cache.alloc(config, self.max_num_seqs)
        else:
            param_sh, cache_sh = self._mesh_shardings(mesh)
            if params is not None:
                # host/device arrays go straight to their shards
                self.params = jax.device_put(params, param_sh)
            else:
                # init SHARDED: no single device ever holds the full tree
                # (the whole point of tp for models beyond one chip's HBM)
                self.params = jax.jit(lambda k: init_params(config, k), out_shardings=param_sh)(
                    jax.random.PRNGKey(seed)
                )
            if kv_layout == "paged":
                from ray_tpu.llm import paged_kv as pkv

                self.pool = jax.jit(lambda: pkv.alloc(self._pcfg), out_shardings=cache_sh)()
            else:
                self.cache = jax.jit(lambda: kvc.alloc(cache_cfg), out_shardings=cache_sh)()
        # positions in one block of the decode step's attention where it runs as the kernel that
        # reads a lane's live blocks only (ops/slot_attention.py decides: backend, cache dtype,
        # mesh, tile), else None; the step's blocks read and in all, for the flight log
        self._attn_block = None
        self._step_attn_blocks = None
        # what a description counts of a decode step from the positions its lanes hold, for the
        # flight log; asked only of a description that counts something
        self._step_counted = None
        self._counts_decode = self._hybrid and bool(config.decode_counters([1]))
        if kv_layout == "slots":
            from ray_tpu.ops import slot_attention

            tile = getattr(config, "slot_attention_tile", None) or dict(
                num_heads=config.num_heads, num_kv_heads=config.num_kv_heads, head_dim=config.hd)
            kv_dt = next(a for name, a in self.cache.items() if name != "length").dtype
            if slot_attention.refusal(kv_dt, **tile, S=self.max_seq_len, quantized=self.kv_quant,
                                      sharded=mesh is not None) is None:
                # a block holds so many positions of the rows that are values too (a latent layer's latent rows)
                self._attn_block = slot_attention.block_positions(
                    self.max_seq_len, tile["num_kv_heads"], tile.get("value_dim", tile["head_dim"]), kv_dt.itemsize)
        B = self.max_num_seqs
        # per-slot device-side sampling state
        self._temps = np.zeros((B,), np.float32)
        self._top_k = np.zeros((B,), np.int32)
        self._top_p = np.ones((B,), np.float32)

        self._slots: list[RequestState | None] = [None] * B
        self._waiting: deque[RequestState] = deque()
        self._requests: dict[str, RequestState] = {}  # guarded-by: _lock
        self._lock = threading.Lock()
        self._auto_id = 0
        self._prefix_cache = (
            PrefixCache(block=prefix_block, max_bytes=prefix_cache_bytes) if enable_prefix_caching else None
        )
        # cluster KV plane (llm/kvplane/): publish stored prefixes, fetch
        # remote hits, deregister on eviction. Remote-tier counters live
        # here (the PrefixCache keeps its local-tier ones).
        self._kv_plane = kv_plane
        # the FULL counter set is seeded here — including the failure and
        # async/prefetch legs — so prefix_cache_stats() tiers never change
        # shape before/after the first error (no lazy .get() minting)
        self._plane_stats = {
            "hits": 0, "tokens_saved": 0, "fetched_bytes": 0,
            "lost": 0, "published_blocks": 0, "published_bytes": 0,
            "errors": 0, "abandoned": 0,
            "prefetched_blocks": 0, "prefetched_bytes": 0, "prefetch_hits": 0,
        }
        # ASYNC cluster-tier fetch (ROADMAP item 3a): admission LAUNCHES
        # lookup+fetch+validate on the fetch worker and keeps planning;
        # the result splices in at a later wave. _fetch_state maps
        # request_id -> in-flight record, guarded-by: _lock; the record
        # dict itself is FILLED by the worker thread (plain assignments,
        # "done" flipped last — atomic under the GIL) and only read at
        # admission once "done" is observed.
        self.prefix_fetch_deadline_s = float(prefix_fetch_deadline_s)
        self._fetch_state: dict[str, dict] = {}
        self._fetch_q = None  # lazy: SimpleQueue + daemon worker on first launch
        self._fetch_thread = None
        # deadline-abandoned fetch records awaiting their worker's
        # terminal resolution: reaped (stats credit only — the request
        # already prefilled locally) at admission and on a stats read.
        # Without the reap, a client fetch budget above the engine
        # deadline means lost/errors are never counted under async.
        self._fetch_zombies: list[dict] = []  # guarded-by: _lock
        # boundary keys minted by the predictive prefetcher
        # (adopt_prefetched): local hits on them count as prefetch hits
        self._prefetched_keys: set[bytes] = set()  # guarded-by: _lock
        # tiered conversation KV (ROADMAP item 3c): suspended
        # conversations spilled out of HBM — request_id -> {"state" (host
        # DRAM tier), "meta", "ref" (object-plane tier), "nbytes", "t"}
        self._suspended: dict[str, dict] = {}  # guarded-by: _lock
        self._suspend_stats = {"suspended": 0, "resumed": 0, "spilled_bytes": 0, "dropped": 0}
        # publishes minted under the engine lock (admission self-heal,
        # remote-fetch republish, prefill store) are deferred here and
        # flushed at the step tail AFTER the lock is released: a publish
        # is serialization + put_owned + a 10s-timeout index RPC, and
        # paying that under self._lock would stall every add_request/
        # abort/stats caller behind the plane (tpulint CCR001)
        self._plane_offers: list[tuple] = []
        if kv_plane is not None:
            if self._prefix_cache is None:
                raise ValueError(
                    "kv_plane is the prefix cache's cluster tier and needs "
                    "enable_prefix_caching=True (remote hits re-store locally)"
                )
            kv_plane.attach(self)
            self._prefix_cache.evict_hook = kv_plane.on_evict
        self.preemption_count = 0

        # in-flight fused step awaiting host readback:
        # (tokens [B] dev, logps [B] dev, [(RequestState, slot), ...])
        self._pending = None
        # first tokens sampled on the device and not read yet, a group an entry: (tokens [G] dev,
        # logps [G] dev, a hybrid prefill's routing counters or None, [(row, RequestState, slot), ...],
        # the group's stamps or None, (tokens, padded tokens, shape counters) of a hybrid prefill or
        # None); _read_first_tokens empties it, behind the step's dispatch. And the step's two counters
        self._first_tokens: list = []
        self._lanes_bound_device = self._first_token_syncs = 0
        # the shard_map hot path engages on a PURE tp mesh (other axes
        # would shard dims the per-shard programs assume replicated; a
        # mixed mesh falls back to the GSPMD compilation, fp collectives)
        from ray_tpu.parallel.mesh import axis_size, is_tp_only

        self._tp_fused = (
            mesh is not None and is_tp_only(mesh) and axis_size(mesh, "tp") > 1
        )
        if tp_collective == "int8" and not self._tp_fused:
            raise ValueError(
                "tp_collective='int8' quantizes the explicit shard_map all-reduce, which only "
                "exists on the fused path over a pure tp>=2 mesh "
                "(got mesh=%s)" % (getattr(mesh, "axis_names", None),)
            )
        if self._tp_fused and tp_collective == "int8" and config.hidden_size % axis_size(mesh, "tp"):
            raise ValueError(
                f"hidden_size ({config.hidden_size}) must divide by tp ({axis_size(mesh, 'tp')}) "
                "to chunk the int8 quantized all-reduce payload; use tp_collective='fp'"
            )
        if self._hybrid:
            from ray_tpu.llm.hybrid_runner import make_hybrid_fns

            self._prefill, self._insert, self._state_insert, self._fused_step = make_hybrid_fns(config)
        self._moe_stats = None  # the drained step's expert-routing counters (hybrid models)
        # this step's hybrid prefills: (tokens, tokens as padded, programs, summed routing counters,
        # what the description counts of the programs' shapes)
        self._prefill_stats = None
        from ray_tpu.llm.model_runner import make_delta_fns, make_fused_fns, make_fused_paged_fns

        tp_mesh = mesh if self._tp_fused else None
        if kv_layout == "paged":
            self._fused_attn, self._fused_append = make_fused_paged_fns(
                config, mesh=tp_mesh, tp_collective=tp_collective, kv_quant=self.kv_quant,
                attn_impl=self.attn_kernel,
            )
        elif not self._hybrid:
            self._fused_step = make_fused_fns(
                config, mesh=tp_mesh, tp_collective=tp_collective, kv_quant=self.kv_quant,
                partitioned=mesh is not None,
            )
        self._set_lanes, self._set_table, self._set_table_cell = make_delta_fns()
        if mesh is None:
            _put = jnp.asarray
        else:
            from jax.sharding import NamedSharding, PartitionSpec as P

            _repl = NamedSharding(mesh, P())
            _put = lambda a: jax.device_put(a, _repl)  # noqa: E731
        # device-resident decode state; host arrays above stay as the
        # scheduler's shadow copies (never re-uploaded wholesale)
        self._dtokens = _put(np.zeros((B,), np.int32))  # each lane's input token for the next step
        # each lane's key lives here alone: a seedless lane draws from it, a seeded admission overwrites it
        self._dkeys = _put(np.asarray(seed_keys(jnp.arange(B, dtype=jnp.uint32))))
        self._dtemps = _put(self._temps)
        self._dtopk = _put(self._top_k)
        self._dtopp = _put(self._top_p)
        if kv_layout == "paged":
            self._dtables = _put(self._tables)
            self._dlengths = _put(self._lengths)
        self._spec_cfg = None
        if speculative is not None:
            if mesh is not None and not self._tp_fused:
                raise ValueError(
                    "speculative decoding over a mesh needs the shard_map fused path "
                    f"(a pure tp>=2 mesh); got axes {getattr(mesh, 'axis_names', None)}"
                )
            self._init_spec(speculative, _put)
        # serving telemetry plane (llm/telemetry.py): flight recorder +
        # live SLO metrics + request-lifecycle tracing. Host-side only —
        # never forces a device readback (the zero-sync rule, gated at
        # <= 1.05x the uninstrumented step in tests/test_perf_smoke.py).
        # telemetry=False opts the whole plane out (A/B baselines).
        self._last_spec_drain = None
        self._tel = None
        if telemetry:
            from ray_tpu.llm.telemetry import EngineTelemetry

            self._tel = EngineTelemetry(self, telemetry_tags)
            self._tel.register_fused_entries()
        # the memory ONE prefill program may take: what the device has free now that weights, cache
        # and state are resident (None where the backend keeps no account, as the CPU's), and what
        # each shape that has run takes by the compiler's account (``_prefill_batch``)
        self._prefill_room = device_free_bytes(next(a for name, a in (self.pool if kv_layout == "paged" else self.cache).items()
                                                    if name != "length"))
        self._prefill_need: dict[tuple[int, int], int] = {}

    def _init_spec(self, spec_cfg, _put):
        """Speculative decoding state: drafter, adaptive-k controller,
        per-lane device history/effective-k lanes, and the fused verify
        program for this KV layout (llm/spec/)."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.llm.spec import verify as specv
        from ray_tpu.llm.spec.controller import AdaptiveKController, SpecConfig
        from ray_tpu.llm.spec.drafter import ModelDrafter, NGramDrafter

        if not isinstance(spec_cfg, SpecConfig):
            raise TypeError(f"speculative must be a llm.spec.SpecConfig, got {type(spec_cfg).__name__}")
        self._spec_cfg = spec_cfg
        B, k = self.max_num_seqs, spec_cfg.k
        if spec_cfg.drafter == "model":
            dcfg = spec_cfg.draft_config
            if dcfg is None:
                raise ValueError("drafter='model' needs SpecConfig.draft_config (a smaller LlamaConfig)")
            if dcfg.vocab_size != self.config.vocab_size:
                raise ValueError(
                    f"draft vocab ({dcfg.vocab_size}) must match the target's ({self.config.vocab_size})"
                )
            self._drafter = ModelDrafter(dcfg, params=spec_cfg.draft_params, k=k, seed=spec_cfg.draft_seed)
        else:
            self._drafter = NGramDrafter(k=k, n=spec_cfg.ngram)
        if self.mesh is not None and not self._drafter.supports_mesh:
            # the verify step shards like the fused step, but a draft
            # MODEL brings its own weights + slot KV cache + fused
            # k+1-step chain, none of which is mesh-sharded yet
            raise NotImplementedError(
                f"drafter '{self._drafter.kind}' does not support tensor-parallel meshes: the "
                "draft model's params/KV cache and its fused draft_steps chain are not sharded "
                "over tp; use the zero-weight drafter='ngram' (its proposal lanes are replicated)"
            )
        self._drafter.init_slots(B, self.max_seq_len, self.prefill_buckets)
        self._controller = AdaptiveKController(spec_cfg)
        # token-history lanes: prompt + everything emitted on device, one
        # round AHEAD of host emission (the drafter's matching corpus);
        # +k+1 headroom so trailing-round writes never wrap
        self._spec_hist_width = self.max_seq_len + k + 1
        self._dhist = _put(jnp.zeros((B, self._spec_hist_width), jnp.int32))
        self._dhist_len = _put(jnp.zeros((B,), jnp.int32))
        self._dspec_k = _put(jnp.full((B,), k, jnp.int32))
        self._lane_k = np.full((B,), k, np.int32)  # host mirror, updated with the device lane
        tp_mesh = self.mesh if self._tp_fused else None
        if self.kv_layout == "paged":
            self._verify_attn, self._verify_append = specv.make_spec_verify_paged(
                self.config, k, mesh=tp_mesh, tp_collective=self.tp_collective, kv_quant=self.kv_quant,
                attn_impl=self.attn_kernel,
            )
        else:
            self._verify_step = specv.make_spec_verify_slots(
                self.config, k, mesh=tp_mesh, tp_collective=self.tp_collective, kv_quant=self.kv_quant
            )
        self._set_hist = jax.jit(specv.set_hist_row)
        self._set_slot_scalar = jax.jit(specv.set_slot_scalar)
        self._spec_rounds = self._spec_lane_rounds = 0
        self._spec_proposed = self._spec_accepted = self._spec_emitted = 0

    def spec_stats(self) -> dict:
        """Speculation counters (empty when speculative decoding is off):
        verify rounds, proposed/accepted totals, acceptance-rate and
        tokens-per-round means, and each live request's effective k."""
        with self._lock:
            if self._spec_cfg is None:
                return {}
            return {
                "drafter": self._drafter.kind,
                "k": self._spec_cfg.k,
                "rounds": self._spec_rounds,
                "lane_rounds": self._spec_lane_rounds,
                "proposed": self._spec_proposed,
                "accepted": self._spec_accepted,
                "emitted": self._spec_emitted,
                "acceptance_rate": self._spec_accepted / max(self._spec_proposed, 1),
                # per LANE per round: the per-sequence tokens/step multiplier
                "mean_tokens_per_round": self._spec_emitted / max(self._spec_lane_rounds, 1),
                "k_per_request": {
                    rid: kk for rid, kk in self._controller.current().items() if rid in self._requests
                },
            }

    def telemetry(self) -> dict:
        """Flight-recorder snapshot (llm/telemetry.py): per-step ring
        (phase, wall ms, occupancy, queue depth, spec accounting,
        recompile sentinel), finished-request lifecycle records (TTFT /
        queue-wait / per-token ITL samples), recompile counts, tags.
        Empty dict when the engine was built with telemetry=False."""
        if self._tel is None:
            return {}
        return self._tel.snapshot()

    def kv_cache_stats(self) -> dict:
        """KV-cache accounting (the HBM side of serving capacity): cache
        dtype and layout, honest bytes/token (per-head scales included
        for int8), allocated vs occupied HBM, and slot/page occupancy.
        Sits next to spec_stats()/prefix_cache_stats() on the engine and
        the serve replica."""
        cfg = self.config
        per_tok = self.kv_bytes_per_token()
        with self._lock:
            arrs = self.pool if self.kv_layout == "paged" else self.cache
            allocated = int(sum(int(a.nbytes) for name, a in arrs.items() if name != "length"))
            devs = sorted(next(a for name, a in arrs.items() if name != "length").devices(), key=lambda d: d.id)
            out = {
                # the devices that HOLD the cache, as this process sees them
                "device": {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)},
                "layout": self.kv_layout,
                "dtype": self.kv_dtype,
                "quantized": self.kv_quant,
                "attn_kernel": self.attn_kernel,
                "bytes_per_token": int(per_tok),
                "allocated_bytes": allocated,
                "slots_total": self.max_num_seqs,
                "slots_in_use": sum(1 for s in self._slots if s is not None),
            }
            if self.kv_layout == "paged":
                # host shadow lengths: exact for every bound lane, no sync
                occupied = int(self._lengths.sum())
                out["page_size"] = self._pcfg.page_size
                out["pages_total"] = self._pcfg.num_pages - 1  # page 0 = trash
                out["pages_free"] = self._page_alloc.free_pages
            else:
                occupied = sum(
                    len(s.prompt_token_ids) + len(s.token_ids) for s in self._slots if s is not None
                )
            out["occupied_tokens"] = occupied
            out["occupied_bytes"] = occupied * int(per_tok)
            if self._prefill_room is not None:
                # what one prefill program may take of the device, and what each shape that has run does take
                out["prefill_room_bytes"] = self._prefill_room
                out["prefill_program_bytes"] = {f"{b}x{t}": need for (b, t), need in sorted(self._prefill_need.items())}
            if self._hybrid:
                # the state cache beside the KV rows: fixed bytes a slot, whatever the lengths
                from ray_tpu.llm import state_cache

                out["entries"] = self.kv_entries()
                out["state_bytes_per_slot"] = state_cache.bytes_per_slot(cfg)
                out["state_allocated_bytes"] = int(sum(int(a.nbytes) for a in self.state.values()))
            return out

    def kv_entries(self) -> dict:
        """What a position of the cache is made of: entry -> [layers that keep it, its shape, its dtype]."""
        cfg = self.config
        entries = self._kv_entries or {n: (self._kv_layers, (cfg.num_kv_heads, cfg.hd), self.kv_dtype) for n in ("k", "v")}
        return {name: [layers, list(shape), str(dtype)] for name, (layers, shape, dtype) in entries.items()}

    def kv_bytes_per_token(self) -> int:
        """Honest bytes one position of one sequence takes, over all layers (an int8 cache's scales included)."""
        if self._kv_entries is not None:
            from ray_tpu.llm.kv_cache import entry_bytes_per_token

            return entry_bytes_per_token(self._kv_entries)
        from ray_tpu.llm.kv_quant import bytes_per_token

        return int(bytes_per_token(self._kv_layers, self.config.num_kv_heads, self.config.hd, self.kv_dtype))

    def _mesh_shardings(self, mesh):
        """Tensor-parallel serving (reference capability: the vLLM engine's
        tensor_parallel_size, llm/_internal/serve/engines/vllm/
        vllm_models.py:215-228 — here expressed as GSPMD shardings, no
        NCCL): weights shard by the model's logical axes (heads/kv_heads/
        mlp/vocab -> tp), the KV cache shards its kv_heads dim, and the
        SAME jitted prefill/decode programs compile SPMD over the mesh —
        XLA inserts the tp collectives on ICI."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ray_tpu.models.llama import param_logical_axes
        from ray_tpu.parallel.mesh import ShardingRules, axis_or_none, mesh_axes

        tp = axis_or_none(mesh, "tp")
        tp_size = max(mesh_axes(mesh).get("tp", 1), 1)
        # validate EVERY tp-sharded model dim up front with an actionable
        # message — an indivisible q-head count or MLP width used to fail
        # deep inside GSPMD partitioning with an inscrutable HLO error
        if self.config.num_kv_heads % tp_size != 0:
            raise ValueError(
                f"num_kv_heads ({self.config.num_kv_heads}) must divide by tp ({tp_size}) to shard "
                "the KV cache; pick tp from the divisors of num_kv_heads (or replicate KV by "
                "raising num_kv_heads to match)"
            )
        if self.config.num_heads % tp_size != 0:
            raise ValueError(
                f"num_heads ({self.config.num_heads}) must divide by tp ({tp_size}) to shard the "
                "attention projections (wq/wo split by head); pick tp from the divisors of num_heads"
            )
        if self.config.intermediate_size % tp_size != 0:
            raise ValueError(
                f"intermediate_size ({self.config.intermediate_size}) must divide by tp ({tp_size}) "
                "to shard the MLP (w_gate/w_up/w_down split on the hidden dim); pad "
                "intermediate_size to a multiple of tp"
            )
        if self.config.vocab_size % tp_size != 0:
            raise ValueError(
                f"vocab_size ({self.config.vocab_size}) must divide by tp ({tp_size}) to shard the "
                "embed/unembed tables (and the shard_map decode path's logits gather); pad the "
                "vocab to a multiple of tp"
            )
        rules = ShardingRules()
        param_sh = jax.tree.map(
            lambda axes: NamedSharding(mesh, rules.spec(axes, mesh)),
            param_logical_axes(self.config),
            is_leaf=lambda x: isinstance(x, tuple),
        )
        # both layouts put kv_heads at axis 3: slot rows [L,B,S,kv,hd],
        # paged pool [L,P,page,kv,hd]
        # no trailing None: shard_map hands the cache back with the normalised
        # spec, and a textually different (if equivalent) input sharding on the
        # second step would compile the fused step a second time
        kv_s = NamedSharding(mesh, P(None, None, None, tp))
        if getattr(self, "kv_layout", "slots") == "paged":
            cache_sh = {"k": kv_s, "v": kv_s}
        else:
            cache_sh = {"k": kv_s, "v": kv_s, "length": NamedSharding(mesh, P())}
        if getattr(self, "kv_quant", False):
            # scale tensors put kv_heads at axis 2 ([L,B,kv,S] / [L,P,kv,page])
            sc_s = NamedSharding(mesh, P(None, None, tp))
            cache_sh["k_scale"] = cache_sh["v_scale"] = sc_s
        return param_sh, cache_sh

    # ------------------------------------------------------------- admission

    def add_request(
        self,
        prompt_token_ids,
        params: SamplingParams | None = None,
        request_id: str | None = None,
        stream: bool = False,
        out_queue=None,
        submitted_at: float | None = None,
    ) -> str:
        """``out_queue`` lets a streaming caller supply its own queue and
        hold a reference BEFORE admission — the request may finish (and be
        dropped from the registry) before add_request even returns to a
        caller racing the stepping thread. ``submitted_at`` (time.time())
        backdates the telemetry clock to the true ingress arrival when a
        front-end queued the request before admitting it here."""
        params = params or SamplingParams()
        asked = time.perf_counter()
        with self._lock:
            held = time.perf_counter()
            if request_id is None:
                request_id = f"req-{self._auto_id}"
                self._auto_id += 1
            if len(prompt_token_ids) + params.max_tokens > self.max_seq_len:
                raise ValueError(  # tpulint: disable=ERR002 — request-shape validation at admission: 400-class caller error, not a fleet fault
                    f"prompt ({len(prompt_token_ids)}) + max_tokens ({params.max_tokens}) "
                    f"exceeds max_seq_len ({self.max_seq_len})"
                )
            if self.kv_layout == "paged":
                T = _bucket(len(prompt_token_ids), self.prefill_buckets)
                need = min(T // self._pcfg.page_size + 1, self._pcfg.max_pages_per_seq)
                if need > self._pcfg.num_pages - 1:
                    raise ValueError(  # tpulint: disable=ERR002 — pool-sizing validation at admission: config error the operator must fix, not a serving fault
                        f"prompt needs {need} pages but the pool has "
                        f"{self._pcfg.num_pages - 1}; raise num_pages"
                    )
            st = RequestState(request_id, list(prompt_token_ids), params)
            if stream or out_queue is not None:
                st.out_queue = out_queue if out_queue is not None else queue.SimpleQueue()
            return self._enqueue(st, held - asked, submitted_at)

    def _enqueue(self, st: RequestState, lock_wait_s: float | None, submitted_at: float | None, parent_trace: tuple | None = None) -> str:  # holds-lock: _lock
        """The tail of every admission: the request's submit stamp, with how long its thread waited
        for the lock that the caller holds, then the registry and the queue. -> its id."""
        if self._tel is not None:
            self._tel.on_submit(st, submitted_at, parent_trace=parent_trace, lock_wait_s=lock_wait_s)
        self._requests[st.request_id] = st
        self._waiting.append(st)
        return st.request_id

    def prefix_cache_stats(self) -> dict:
        """Prefix-reuse accounting. Flat keys are the LOCAL cache's
        legacy counters (hits/misses/tokens_saved/evictions/entries/
        bytes); with a cluster KV plane attached the dict grows hit
        TIERS — ``local`` (this replica's cache) and ``remote`` (blocks
        fetched over the object plane: hits, tokens_saved, fetched_bytes,
        lost, published_*) — plus the plane client's own counters under
        ``plane``. Empty dict when prefix caching is off."""
        with self._lock:
            if self._prefix_cache is None:
                return {}
            self._reap_fetch_zombies_locked()
            out = self._prefix_cache.stats()
            out["local"] = {"hits": out["hits"], "tokens_saved": out["tokens_saved"]}
            if self._kv_plane is not None:
                out["remote"] = dict(self._plane_stats, inflight_fetches=len(self._fetch_state))
                out["plane"] = self._kv_plane.stats()
            return out

    # ------------------------------------------- prefill/decode disaggregation

    def add_prefill_request(
        self, prompt_token_ids, request_id: str | None = None, submitted_at: float | None = None
    ) -> str:
        """PREFILL-ONLY admission (disaggregated serving, llm/disagg/).

        The request rides the normal admission + prefill stages — batching
        into the same bucketed forwards as everything else admitted that
        step, prefix-cache reuse included — then finishes with reason
        "handoff": its KV block is extracted into a contiguous buffer
        (fused extract program) and stashed for ``pop_handoff``, and the
        slot/pages recycle immediately. It never enters the decode stage."""
        asked = time.perf_counter()
        with self._lock:
            held = time.perf_counter()
            if request_id is None:
                request_id = f"req-{self._auto_id}"
                self._auto_id += 1
            n = len(prompt_token_ids)
            if not 0 < n <= self.prefill_buckets[-1]:
                raise ValueError(f"prompt length {n} outside prefill buckets (max {self.prefill_buckets[-1]})")
            if self.kv_layout == "paged":
                T = _bucket(n, self.prefill_buckets)
                need = min(T // self._pcfg.page_size + 1, self._pcfg.max_pages_per_seq)
                if need > self._pcfg.num_pages - 1:
                    raise ValueError(
                        f"prompt needs {need} pages but the pool has "
                        f"{self._pcfg.num_pages - 1}; raise num_pages"
                    )
            st = RequestState(request_id, list(prompt_token_ids), SamplingParams(max_tokens=1), prefill_only=True)
            return self._enqueue(st, held - asked, submitted_at)

    def pop_handoff(self, request_id: str) -> dict | None:
        """Claim a finished prefill-only request's handoff payload
        (None until the prefill stage has run it). Payload format is
        ``add_prefilled``'s input: k/v [L, T_pad, kv, hd] host arrays,
        n, first-token logits, prompt_token_ids."""
        with self._lock:
            return self._handoffs.pop(request_id, None)

    def prefill_handoff(self, prompt_token_ids, submitted_at: float | None = None) -> dict:
        """Blocking convenience (single-threaded drivers: tests, bench):
        admit a prefill-only request and step until its handoff is ready.
        ``submitted_at`` backdates the telemetry clock to the true ingress
        arrival (it rides the handoff, so the decode side's TTFT spans
        the whole pipeline)."""
        rid = self.add_prefill_request(prompt_token_ids, submitted_at=submitted_at)
        while True:
            outs = self.step()
            kv = self.pop_handoff(rid)
            if kv is not None:
                return kv
            for o in outs:
                if o.request_id == rid and o.finished:
                    raise RuntimeError(f"prefill-only request failed: {o.finish_reason}")

    def prefill_remote(self, prompt_token_ids) -> dict:
        """Prefill-only: compute the prompt's KV and first-token logits and
        return them as HOST arrays for a decode engine to admit
        (reference: python/ray/llm/tests/serve/.../prefill_decode_disagg/ —
        vLLM KV-connector handoff; here the payload rides the object store
        between a prefill replica and its decode replicas)."""
        import jax.numpy as jnp

        n = len(prompt_token_ids)
        T = _bucket(n, self.prefill_buckets)
        toks = np.zeros((1, T), np.int32)
        toks[0, :n] = prompt_token_ids
        logits, ks, vs = self._prefill(self.params, jnp.asarray(toks), jnp.asarray([n], np.int32))
        return {
            "k": np.asarray(ks[:, 0]),
            "v": np.asarray(vs[:, 0]),
            "n": n,
            "logits": np.asarray(logits[0]),
            "prompt_token_ids": list(prompt_token_ids),
        }

    def add_prefilled(
        self,
        kv: dict,
        params: SamplingParams | None = None,
        request_id: str | None = None,
        stream: bool = False,
        out_queue=None,
    ) -> str:
        """Admit a sequence whose prefill ran on another engine; decoding
        starts from the transferred KV without touching the prompt again."""
        params = params or SamplingParams()
        asked = time.perf_counter()
        with self._lock:
            held = time.perf_counter()
            if request_id is None:
                request_id = f"req-{self._auto_id}"
                self._auto_id += 1
            prompt = list(kv["prompt_token_ids"])
            if len(prompt) + params.max_tokens > self.max_seq_len:
                raise ValueError(
                    f"prompt ({len(prompt)}) + max_tokens ({params.max_tokens}) "
                    f"exceeds max_seq_len ({self.max_seq_len})"
                )
            st = RequestState(request_id, prompt, params, prefilled=kv)
            if stream or out_queue is not None:
                st.out_queue = out_queue if out_queue is not None else queue.SimpleQueue()
            # a handoff payload carries the ORIGINAL submit stamp and
            # trace context, so TTFT spans the whole pipeline and one
            # trace id stitches prefill and decode replicas
            tr = kv.get("trace")
            return self._enqueue(st, held - asked, kv.get("submitted_at"),
                                 (tr["trace_id"], tr.get("parent_id")) if isinstance(tr, dict) else None)

    def abort_request(self, request_id: str) -> bool:
        with self._lock:
            st = self._requests.get(request_id)
            if st is None or st.finished:
                return False
            self._finish(st, "aborted")
            return True

    def has_unfinished(self) -> bool:
        with self._lock:
            return bool(self._waiting) or any(s is not None for s in self._slots) or self._pending is not None

    @property
    def num_waiting(self) -> int:
        return len(self._waiting)

    @property
    def num_running(self) -> int:
        return sum(1 for s in self._slots if s is not None)

    def host_load(self) -> dict:
        """Load snapshot for admission control (serve/overload.py): queue
        depth, slot occupancy, occupied/queued/capacity tokens — all host
        scheduler shadow state, never a device array (the telemetry
        plane's zero-sync rule applies to the actuator too). Queued
        demand counts each waiting request's prompt + max_tokens: the
        admission caps bound BACKLOG, not just live occupancy. A request
        on its way in waits for the engine's lock HERE first (a step holds
        it from end to end): the wait goes to its record's ``lock_wait_s``
        through ``telemetry.LOCK_WAIT``, where the ingress set one."""
        waited = LOCK_WAIT.get()
        asked = time.perf_counter()
        with self._lock:
            if waited is not None:
                waited[0] += time.perf_counter() - asked
            waiting = len(self._waiting)
            # max_tokens bounds TOTAL generated tokens, so a preempted
            # requeued request's footprint stays prompt + max_tokens
            # (its already-generated tokens are part of that budget, not
            # additional demand)
            queued_tokens = 0
            queued_gen_tokens = 0
            for st in self._waiting:
                queued_tokens += len(st.prompt_token_ids) + st.params.max_tokens
                queued_gen_tokens += st.params.max_tokens
            slots_in_use = sum(1 for s in self._slots if s is not None)
            if self.kv_layout == "paged":
                occupied = int(self._lengths.sum())
                capacity = (self._pcfg.num_pages - 1) * self._pcfg.page_size
            else:
                occupied = sum(
                    len(s.prompt_token_ids) + len(s.token_ids) for s in self._slots if s is not None
                )
                capacity = self.max_num_seqs * self.max_seq_len
        return {
            "queue_depth": waiting,
            "queued_tokens": queued_tokens,
            "queued_gen_tokens": queued_gen_tokens,
            "slots_in_use": slots_in_use,
            "slots_total": self.max_num_seqs,
            "occupied_tokens": occupied,
            "capacity_tokens": capacity,
        }

    def release_handoffs(self) -> int:
        """Drop every stashed (never-popped) handoff payload. Replica
        drain calls this after admission stops: nothing will ever pop
        them, and the host arrays would otherwise pin their bytes for
        the replica's remaining life. Returns how many were dropped."""
        with self._lock:
            n = len(self._handoffs)
            self._handoffs.clear()
            return n

    # ------------------------------------------------------- live migration

    def checkpoint_request(self, request_id: str) -> dict:
        """Extract one in-flight request's COMPLETE resumable state
        (llm/migrate.py): the KV block covering every attended position
        via the fused extract programs (int8 caches ship int8 values +
        per-head wire scales), the emitted token/logprob stream, the
        lane's live PRNG key, the sampling params, and the speculative
        controller's sticky EMA/effective-k. A peer engine's
        ``restore_request`` continues generation token-identically.

        The one-step-delayed emission is settled FIRST: the in-flight
        fused step (or speculative round) drains here, so the checkpoint
        holds every token the device has minted — the splice-dedup half
        of the migration contract (restore emits nothing at admission;
        the next token comes from the peer's first decode step).

        Pure snapshot: the request keeps running locally until the
        caller finishes it (``finish_migrated``). Raises MigrationError
        for state that cannot move — a finished/unknown request, a
        prefill-only stub (its handoff already IS the transferable
        state), a streaming consumer, or a WAITING sampled request with
        generated tokens (its live key existed only on a bound lane; a
        cold re-admission would resample the suffix — the router's
        re-prefill leg is the token-identical fallback there)."""
        with self._lock:
            return self._checkpoint_locked(request_id)

    def _checkpoint_locked(self, request_id: str) -> dict:
        # holds-lock: _lock — shared by checkpoint_request (migration)
        # and suspend_request (tiered conversation KV), which must
        # checkpoint AND finish under ONE lock acquisition so no decode
        # step can advance the state between snapshot and retirement
        from ray_tpu.llm.migrate import LIVE_KIND, MigrationError

        st = self._requests.get(request_id)
        if st is None or st.finished:
            raise MigrationError(f"request {request_id!r} is not in flight")
        if st.prefill_only:
            raise MigrationError("prefill-only requests hand off, they do not migrate")
        if st.out_queue is not None:
            raise MigrationError(
                "streaming requests cannot migrate (the consumer holds a live token queue)"
            )
        if self._pending is not None:
            prev, self._pending = self._pending, None
            if self._spec_cfg is not None:
                self._drain_spec(prev)
            else:
                self._drain(prev)
            if st.finished:
                raise MigrationError(
                    f"request {request_id!r} finished while settling the in-flight step"
                )
        p = st.params
        state: dict = {
            "kind": LIVE_KIND,
            "prompt_token_ids": list(st.prompt_token_ids),
            "emitted_token_ids": list(st.token_ids),
            "emitted_logprobs": [float(x) for x in st.logprobs],
            "sampling": {
                "max_tokens": int(p.max_tokens),
                "temperature": float(p.temperature),
                "top_k": int(p.top_k),
                "top_p": float(p.top_p),
                "stop_token_ids": [int(t) for t in p.stop_token_ids],
                "seed": None if p.seed is None else int(p.seed),
                "logprobs": bool(p.logprobs),
                "priority": int(p.priority),
            },
            "spec": None,
        }
        if st.t_submit:
            state["submitted_at"] = float(st.t_submit)
        if st.trace is not None:
            state["trace"] = {"trace_id": st.trace[0], "parent_id": st.trace[1]}
        if self._spec_cfg is not None:
            exp = self._controller.export(request_id)
            if exp is not None:
                state["spec"] = {"ema": exp[0], "k": int(exp[1])}
        if st.slot < 0:
            # COLD checkpoint: the request is waiting (queued or
            # recompute-preempted) — no bound lane, no live KV/key.
            # The peer re-admits prompt+generated exactly like a
            # local recompute preemption: token-identical for greedy
            # (and for fresh requests with nothing generated yet).
            if st.token_ids and p.temperature > 0.0:
                raise MigrationError(
                    "cannot cold-checkpoint a sampled request with generated tokens "
                    "(its live PRNG key exists only on a bound lane); the router's "
                    "re-prefill leg is the token-identical fallback"
                )
            if self._tel is not None:
                self._tel.on_migration("checkpointed", 0)
            return state
        slot = st.slot
        l = len(st.prompt_token_ids) + len(st.token_ids) - 1
        # the authoritative cache length must agree with the host
        # view before the block can claim to cover l positions
        if self.kv_layout == "paged":
            l_auth = int(self._lengths[slot])
        else:
            l_auth = int(np.asarray(self.cache["length"][slot]))
        if l_auth != l:
            raise MigrationError(
                f"inconsistent decode state for {request_id!r}: cache length "
                f"{l_auth} != prompt + emitted - 1 = {l}"
            )
        T = _bucket(l, self.prefill_buckets)
        if self.kv_layout == "paged":
            page = self._pcfg.page_size
            # table cells past the allocated pages are 0 (trash):
            # the gather's tail is garbage the peer masks by length
            row = np.asarray(self._tables[slot][: T // page], np.int32)
            out = self._extract_paged(self.pool, row)
        else:
            out = self._extract_slots(self.cache, np.int32(slot), T)
        state.update(k=np.asarray(out[0]), v=np.asarray(out[1]), n=l)
        if len(out) == 4:
            state.update(k_scale=np.asarray(out[2]), v_scale=np.asarray(out[3]))
        # the LIVE key: it advanced on device (seeded lanes included —
        # restore must continue the sequence, never reset from the seed)
        state["rng_key"] = np.asarray(self._dkeys[slot]).astype(np.uint32)
        if self._tel is not None:
            nbytes = int(state["k"].nbytes + state["v"].nbytes)
            if state.get("k_scale") is not None:
                nbytes += int(state["k_scale"].nbytes + state["v_scale"].nbytes)
            self._tel.on_migration("checkpointed", nbytes)
        return state

    # ------------------------------------------------ tiered conversation KV

    def suspend_request(self, request_id: str, *, publish: bool = True) -> dict:
        """Spill an IDLE conversation's KV out of HBM (ROADMAP item 3c):
        checkpoint the request through the migration codec (fused
        extract, int8 wire, live PRNG key) and retire its slot/pages,
        keeping the state in host DRAM — and, with ``publish=True``, on
        the object plane too (``migrate.publish``), so any replica can
        resume it. ``resume_suspended`` scatters the block back in
        instead of re-prefilling: resume cost is one transfer, flat in
        history length.

        Checkpoint + retire happen under ONE lock acquisition (no decode
        step can advance the state in between); the plane publish runs
        OUTSIDE the lock, and a publish failure degrades to the DRAM
        tier (ref=None), never an error. Raises MigrationError when the
        request cannot suspend (unknown/finished, streaming, prefill-
        only, cold-sampled-with-tokens) or when a chaos rule at
        ``llm.suspend`` drops the spill decision — in every refusal the
        conversation is untouched and still RUNNING."""
        from ray_tpu import chaos
        from ray_tpu.llm import migrate as _mig

        # the chaos gate sits OUTSIDE the lock and BEFORE the snapshot:
        # an injected drop/fault models "the spill path is down" and must
        # degrade to the typed refusal with zero request state mutated
        try:
            ok = chaos.apply("llm.suspend")
        except _mig.MigrationError:
            raise
        except Exception as e:  # noqa: BLE001 — injected fault, typed on the way out
            raise _mig.MigrationError(f"suspend of {request_id!r} faulted: {e}") from e
        if not ok:
            raise _mig.MigrationError(f"suspend of {request_id!r} dropped (chaos)")
        with self._lock:
            state = self._checkpoint_locked(request_id)
            st = self._requests[request_id]
            self._finish(st, "suspended")
            nbytes = _mig.state_nbytes(state)
            self._suspend_stats["suspended"] += 1
            self._suspend_stats["spilled_bytes"] += nbytes
            rec = {"state": state, "meta": None, "ref": None, "nbytes": nbytes, "t": time.time()}
            self._suspended[request_id] = rec
        if self._tel is not None:
            self._tel.on_kv_spill(nbytes)
        if publish:
            try:
                meta, ref = _mig.publish(state)
                with self._lock:
                    rec["ref"], rec["meta"] = ref, meta
            except Exception:  # tpulint: disable=ERR001 — noqa: BLE001 — plane publish is opportunism: the DRAM tier copy stays valid, resume still works
                pass
        return {"request_id": request_id, "nbytes": nbytes, "published": rec["ref"] is not None}

    def resume_suspended(
        self, request_id: str, stream: bool = False, out_queue=None
    ) -> str:
        """Re-admit a suspended conversation under its ORIGINAL request
        id: the spilled block scatters back in through the transferred-KV
        admission path (restore_request — exact PRNG key, no re-prefill,
        no token re-emission), racing concurrent admission safely
        because restore just appends to the waiting queue under the
        lock. Prefers the DRAM copy; falls back to fetching the plane
        ref. Raises MigrationError for an unknown suspension or when
        both tiers are gone (MigrationLostError from the fetch)."""
        from ray_tpu.llm import migrate as _mig

        with self._lock:
            rec = self._suspended.pop(request_id, None)
        if rec is None:
            raise _mig.MigrationError(f"no suspended conversation {request_id!r}")
        state = rec["state"]
        if state is None:
            try:
                state = _mig.fetch(rec["ref"], rec["meta"])
            except Exception:
                with self._lock:
                    self._suspend_stats["dropped"] += 1
                raise
        try:
            rid = self.restore_request(
                state, request_id=request_id, stream=stream, out_queue=out_queue
            )
        except Exception:
            with self._lock:  # refused restore: keep the record claimable
                self._suspended.setdefault(request_id, rec)
            raise
        with self._lock:
            self._suspend_stats["resumed"] += 1
        return rid

    def suspended_requests(self) -> list:
        """Request ids currently spilled to the conversation-KV tier."""
        with self._lock:
            return sorted(self._suspended)

    def drop_suspended(self, request_id: str) -> bool:
        """Discard a suspended conversation (client gone, TTL expired):
        frees the DRAM copy; the plane ref ages out with its owner."""
        with self._lock:
            rec = self._suspended.pop(request_id, None)
            if rec is not None:
                self._suspend_stats["dropped"] += 1
            return rec is not None

    def suspend_stats(self) -> dict:
        with self._lock:
            return dict(self._suspend_stats, held=len(self._suspended))

    def finish_migrated(self, request_id: str) -> bool:
        """Finish a checkpointed request locally with reason "migrated"
        (its continuation now lives on a peer): slot/pages recycle, spec
        state drops, stream consumers get their sentinel. The abort
        twin for the migration path — telemetry counts the reason
        separately so evacuations never read as error-rate."""
        with self._lock:
            st = self._requests.get(request_id)
            if st is None or st.finished:
                return False
            self._finish(st, "migrated")
            return True

    def restore_request(
        self,
        state,
        request_id: str | None = None,
        stream: bool = False,
        out_queue=None,
    ) -> str:
        """Splice a checkpointed request into THIS engine and continue
        generation token-identically (llm/migrate.py). ``state`` is the
        validated live_state dict — or an ObjectRef straight off the
        object plane (fetched + decoded here, bounded retry).

        A HOT checkpoint scatters its KV block through the existing
        transferred-KV admission path (fused scatter-in, transparent
        requant across producer/consumer cache dtypes), then
        ``_bind_resume`` rebinds the lane from the checkpoint: exact
        PRNG key, last emitted token as the next decode input, sticky
        spec k — and emits NOTHING (no dup, no drop at the splice). A
        COLD checkpoint re-admits prompt+generated like a recompute
        preemption. Raises MigrationError when the state cannot fit this
        engine's geometry."""
        from ray_tpu.llm import migrate as _mig

        if not isinstance(state, dict):
            state = _mig.fetch(state)
        _mig.check_state(state)
        params = _mig.params_of(state)
        prompt = [int(t) for t in state["prompt_token_ids"]]
        emitted = [int(t) for t in state["emitted_token_ids"]]
        hot = state.get("k") is not None
        with self._lock:
            if request_id is None:
                request_id = f"req-{self._auto_id}"
                self._auto_id += 1
            if len(prompt) + params.max_tokens > self.max_seq_len:
                raise _mig.MigrationError(
                    f"prompt ({len(prompt)}) + max_tokens ({params.max_tokens}) "
                    f"exceeds this engine's max_seq_len ({self.max_seq_len})"
                )
            st = RequestState(request_id, prompt, params)
            st.token_ids = list(emitted)
            st.logprobs = [float(x) for x in state.get("emitted_logprobs", [])]
            st.t_restore = time.time()
            if stream or out_queue is not None:
                st.out_queue = out_queue if out_queue is not None else queue.SimpleQueue()
            nbytes = 0
            if hot:
                T_pad = int(state["k"].shape[1])
                if T_pad > self.max_seq_len:
                    raise _mig.MigrationError(
                        f"checkpoint block width {T_pad} exceeds this engine's cache row "
                        f"({self.max_seq_len}); the producer's bucket ladder is wider"
                    )
                if self.kv_layout == "paged":
                    page = self._pcfg.page_size
                    need = min(-(-T_pad // page) + 1, self._pcfg.max_pages_per_seq)
                    if need > self._pcfg.num_pages - 1:
                        raise _mig.MigrationError(
                            f"checkpoint needs {need} pages but the pool has "
                            f"{self._pcfg.num_pages - 1}"
                        )
                pref = {"k": state["k"], "v": state["v"], "n": int(state["n"]),
                        "prompt_token_ids": prompt}
                if state.get("k_scale") is not None:
                    pref["k_scale"] = state["k_scale"]
                    pref["v_scale"] = state["v_scale"]
                st.prefilled = pref
                st.resume = {
                    "rng_key": np.asarray(state["rng_key"], np.uint32),
                    "spec": state.get("spec"),
                }
                nbytes = int(state["k"].nbytes + state["v"].nbytes)
                if state.get("k_scale") is not None:
                    nbytes += int(state["k_scale"].nbytes + state["v_scale"].nbytes)
            elif self._spec_cfg is not None and state.get("spec"):
                # cold restore: the sticky spec state still survives (the
                # eventual bind's _spec_admit reads it back from the
                # controller under the NEW request id)
                sp = state["spec"]
                self._controller.restore(request_id, sp.get("ema"), sp.get("k"))
            if self._tel is not None:
                tr = state.get("trace")
                self._tel.on_submit(
                    st,
                    state.get("submitted_at"),
                    parent_trace=(tr["trace_id"], tr.get("parent_id")) if isinstance(tr, dict) else None,
                )
                self._tel.on_migration("restored", nbytes)
            self._requests[request_id] = st
            self._waiting.append(st)
            return request_id

    # --------------------------------------------------------------- engine

    def _finish(self, st: RequestState, reason: str):
        st.finished = True
        st.finish_reason = reason
        # a prefix fetch still in flight for this request is orphaned:
        # drop the record (the worker's writes into it become no-ops)
        self._fetch_state.pop(st.request_id, None)
        if self._tel is not None:
            self._tel.on_finish(st, reason)
        if st.prefill_only and reason != "handoff":
            # aborted/errored prefill-only request: drop any stashed block
            # (nobody will ever pop it)
            self._handoffs.pop(st.request_id, None)
        if self._spec_cfg is not None:
            self._controller.forget(st.request_id)
        if st.slot >= 0:
            if self.kv_layout == "paged":
                self._release_slot_pages(st.slot)
            self._slots[st.slot] = None
            st.slot = -1
        if st.out_queue is not None:
            st.out_queue.put(None)  # sentinel

    # ------------------------------------------------------ paged plumbing
    def _push_table(self, slot: int):
        """Scatter one slot's block-table row + length into the device
        decode state (the delta that replaces whole-array re-uploads)."""
        import jax.numpy as jnp

        self._dtables, self._dlengths = self._set_table(
            self._dtables,
            self._dlengths,
            np.int32(slot),
            jnp.asarray(self._tables[slot]),
            np.int32(self._lengths[slot]),
        )

    def _release_slot_pages(self, slot: int):
        self._page_alloc.free(self._slot_pages[slot])
        self._slot_pages[slot] = []
        self._tables[slot, :] = 0
        self._lengths[slot] = 0
        # point the lane at the trash page so in-flight/idle steps
        # scatter harmlessly instead of into recycled pages
        self._push_table(slot)

    def _preempt_for(self, need: int, exclude: RequestState | None = None) -> bool:
        """Recompute-preemption (vLLM's default policy): the YOUNGEST
        running sequence frees its pages and re-queues with its generated
        tokens folded into the prompt. Returns True once >= need pages
        are free."""
        while self._page_alloc.free_pages < need:
            victims = [s for s in self._slots if s is not None and s is not exclude]
            if not victims:
                return False
            victim = max(victims, key=lambda s: s.admit_seq)
            victim.preemptions += 1
            self.preemption_count += 1
            slot = victim.slot
            self._release_slot_pages(slot)
            self._slots[slot] = None
            victim.slot = -1
            self._waiting.appendleft(victim)
        return True

    def _paged_grow(self):
        """Before a decode step: any sequence whose upcoming appends
        cross into unallocated pages gets them (preempting the youngest
        OTHER sequence when the pool is dry; a sequence that cannot grow
        at all preempts itself back to waiting). Plain decode looks ahead
        one token; a speculative lane needs up to k+1 appends for the
        still-pending round plus k+1 for the round about to dispatch,
        capped at the request's own prompt+max_tokens budget (KV past it
        is never attended, so those writes may land in the trash page)."""
        page = self._pcfg.page_size
        spec = self._spec_cfg is not None
        pending_k: dict = {}
        if self._pending is not None:
            for entry in self._pending[-1]:  # lanes: (st, slot[, k_eff])
                pending_k[id(entry[0])] = entry[2] if len(entry) > 2 else 0
        for key in self._first_unread():  # bound this step: the first token is sampled and not read yet
            pending_k[key] = 0
        for st in [s for s in self._slots if s is not None]:
            if st.slot < 0 or self._slots[st.slot] is not st:
                continue  # preempted by an earlier iteration's _preempt_for
            if id(st) in pending_k and len(st.token_ids) + 1 >= st.params.max_tokens:
                # the not-yet-drained round finishes this sequence at
                # max_tokens: this call's step is its discarded trailing
                # step — never grow (let alone PREEMPT a live sequence)
                # for it; the unallocated-page write lands in the trash
                # page.
                continue
            slot = st.slot
            l = int(self._lengths[slot])
            if spec:
                look = int(self._lane_k[slot]) + 1
                if id(st) in pending_k:
                    look += pending_k[id(st)] + 1
                budget = len(st.prompt_token_ids) + st.params.max_tokens
                horizon = min(l + look, max(budget, l))
            else:
                horizon = l + 1
            if horizon <= l:
                continue
            target_pg = (horizon - 1) // page + 1
            if not spec and target_pg > self._pcfg.max_pages_per_seq:
                self._finish(st, "length")  # cache row exhausted
                continue
            target_pg = min(target_pg, self._pcfg.max_pages_per_seq)
            while len(self._slot_pages[slot]) < target_pg:
                got = self._page_alloc.alloc(1)
                if got is None and self._preempt_for(1, exclude=st):
                    got = self._page_alloc.alloc(1)
                if got is None:
                    # nothing left to preempt: this sequence itself re-queues
                    st.preemptions += 1
                    self.preemption_count += 1
                    self._release_slot_pages(slot)
                    self._slots[slot] = None
                    st.slot = -1
                    self._waiting.appendleft(st)
                    break
                pg_ix = len(self._slot_pages[slot])
                self._slot_pages[slot].extend(got)
                self._tables[slot, pg_ix] = got[0]
                self._dtables = self._set_table_cell(
                    self._dtables, np.int32(slot), np.int32(pg_ix), np.int32(got[0])
                )

    def _pages_needed(self, st: RequestState, pref, prompt) -> int | None:
        """Pages a request needs to admit (prompt bucket + one decode
        headroom page). None = can never fit; the request is finished with
        an error instead of spinning in the admission loop forever."""
        page = self._pcfg.page_size
        n = len(prompt)
        if st.prefilled is not None:
            # the transferred KV is bucket-padded; pages cover the padding
            # too (garbage tail is masked by length, overwritten by appends)
            T_pad = -(-int(st.prefilled["k"].shape[1]) // page) * page
            need = T_pad // page + 1
        elif pref is not None:
            n_p = pref[2]
            Tm = _bucket(n - n_p, self.prefill_buckets)
            need = (n_p + Tm) // page + 1
        else:
            T = _bucket(n, self.prefill_buckets)
            need = T // page + 1
        # the +1 decode-headroom page must not overflow the table row
        # (a prompt bucket that already fills it grows via _paged_grow,
        # which finishes the sequence at the row edge)
        need = min(need, self._pcfg.max_pages_per_seq)
        if need > self._pcfg.num_pages - 1:
            self._finish(st, f"error: needs {need} pages, pool holds {self._pcfg.num_pages - 1}")
            return None
        return need

    def _stage_admission(self) -> list:  # holds-lock: _lock (step pipeline)
        """ADMISSION stage (planning only, no forwards): admit every
        waiting request that fits right now (FIFO; a head-of-line request
        that cannot get pages blocks the wave — vLLM semantics: waiting
        requests wait for free blocks, ADMISSION never preempts running
        sequences). Reserves slots/pages and resolves prefix-cache hits;
        returns the wave of (st, slot, pref, pages, prompt) plans the
        prefill stage executes."""
        wave: list[tuple] = []  # (st, slot, pref, pages, prompt)
        if self._fetch_zombies:
            self._reap_fetch_zombies_locked()
        # requests skipped THIS wave on an in-flight async prefix fetch:
        # re-queued at the front (original order) after the loop so they
        # keep FIFO priority without blocking followers behind a transfer
        deferred: list[RequestState] = []
        while self._waiting and None in self._slots:
            st = self._waiting[0]
            if st.finished:  # aborted while waiting
                self._waiting.popleft()
                self._fetch_state.pop(st.request_id, None)
                continue
            slot = self._slots.index(None)
            # preempted sequences resume with generated tokens as prompt tail
            prompt = st.prompt_token_ids + st.token_ids
            # pref: (k, v, n_valid, k_scale, v_scale) — scales None except
            # for an int8-wire block fetched over the cluster KV plane
            # (the fused insert requants transparently either way). The
            # resolution caches on the request so a head-of-line wait
            # (paged pool full -> break below) never re-looks-up, never
            # refetches, and counts its hit exactly once per request
            pref = None
            if st.prefilled is None and self._prefix_cache is not None and not st.token_ids:
                cached = st.cached_pref
                if cached is not None and cached[0] is _PREF_MISS and (
                    cached[1] != self._prefix_cache.gen or time.time() >= cached[2]
                ):
                    cached = None  # keys minted / miss lease lapsed: re-resolve
                if cached is not None:
                    pref = None if cached[0] is _PREF_MISS else cached
                else:
                    # suffix-overrun guard, applied INSIDE the lookup so a
                    # rejected longest boundary falls through to the next
                    # shorter LOCAL one (never off to a remote fetch of
                    # bytes this replica already holds)
                    local = self._prefix_cache.lookup(
                        prompt, admissible=lambda n_p: self._prefix_fits(n_p, len(prompt))
                    )
                    if local is not None:
                        pref = local + (None, None)
                        if self._tel is not None:
                            self._tel.on_prefix_hit("local", local[2])
                        if self._prefetched_keys:
                            # attribution: a hit served by a block the
                            # predictive prefetcher pulled in ahead of
                            # demand (cheap: only computed while any
                            # prefetched key is live in the cache)
                            kb = prefix_key(token_bytes(tuple(int(t) for t in prompt)), local[2])
                            if kb in self._prefetched_keys:
                                self._plane_stats["prefetch_hits"] += 1
                                if self._tel is not None:
                                    self._tel.on_prefetch_hit()
                        if self._kv_plane is not None:
                            # publish self-heal: a boundary whose original
                            # publish failed transiently would otherwise
                            # stay cluster-invisible forever (store never
                            # re-mints cached keys) — the client filters
                            # already-published bounds, so this is a cheap
                            # no-op in steady state
                            self._plane_publish(prompt[: local[2]], local[0], local[1])
                    elif self._kv_plane is not None:
                        # cluster tier, ASYNC (ROADMAP item 3a): the
                        # lookup+fetch runs on the engine's fetch worker,
                        # NEVER under this lock. First sight launches it
                        # and DEFERS the request (followers keep
                        # admitting, their prefills overlap the
                        # transfer); a landed result splices in here; a
                        # fetch outliving its deadline abandons to a
                        # plain local prefill — zero hangs by
                        # construction. Any failure inside degrades to
                        # pref = None.
                        rec = self._fetch_state.get(st.request_id)
                        if rec is None and not self._kv_plane.index_down():
                            rec = self._launch_prefix_fetch(st.request_id, prompt)
                        if rec is not None:
                            if rec["done"]:
                                pref = self._splice_prefix_fetch(st, rec, prompt)
                            elif time.time() < rec["deadline"]:
                                self._waiting.popleft()
                                deferred.append(st)
                                continue
                            else:
                                # wedged plane: abandon the fetch. The
                                # record moves to the zombie list so the
                                # worker's TERMINAL resolution still
                                # lands in the stats (with the default
                                # client fetch budget above the engine
                                # deadline, lost/errors would otherwise
                                # NEVER be credited under async)
                                self._fetch_state.pop(st.request_id, None)
                                self._plane_stats["abandoned"] += 1
                                self._fetch_zombies.append(rec)
                    if pref is None:
                        # plane engines re-check after a short lease: a
                        # PEER's publish can't bump the local generation
                        exp = (time.time() + 1.0) if self._kv_plane is not None else float("inf")
                        st.cached_pref = (_PREF_MISS, self._prefix_cache.gen, exp)
                    else:
                        st.cached_pref = pref
            pages = None
            if self.kv_layout == "paged":
                need = self._pages_needed(st, pref, prompt)
                if need is None:
                    self._waiting.popleft()  # finished with an error
                    continue
                if self._page_alloc.free_pages < need:
                    break  # pool full: head-of-line waits
                pages = self._page_alloc.alloc(need)
                if pages is None:
                    break
            self._waiting.popleft()
            st.cached_pref = None  # admission consumes the cached resolution
            self._slots[slot] = st  # reserve; _bind_group fills the rest
            wave.append((st, slot, pref, pages, prompt))
        for st in reversed(deferred):
            self._waiting.appendleft(st)  # original FIFO order restored
        return wave

    def _prefix_fits(self, n_p: int, prompt_len: int) -> bool:
        """Suffix-overrun admissibility for a prefix boundary: the
        bucket-padded remaining suffix must fit the cache row (the slot
        layout's extend wrote its chunk with a dynamic_update_slice, which
        CLAMPS the start and silently corrupts the prefix; it scatters by
        position and drops the overrun now, so for that layout this guard
        only costs hits: ROADMAP A5). The ONE predicate both the local lookup and
        the remote candidate filter apply — the two tiers can never
        disagree on admissibility."""
        return n_p + _bucket(prompt_len - n_p, self.prefill_buckets) <= self.max_seq_len

    # ------------------------------------------------ async cluster fetch

    def _ensure_fetch_worker(self):  # holds-lock: _lock (via admission)
        if self._fetch_thread is not None and self._fetch_thread.is_alive():
            return
        self._fetch_q = queue.SimpleQueue()
        t = threading.Thread(target=self._fetch_worker, daemon=True, name="llm-prefix-fetch")
        self._fetch_thread = t
        t.start()

    def _fetch_worker(self):
        """Drains prefix-fetch jobs OFF the engine lock: the index RPC,
        the multi-MB object-plane transfer, the token verification and
        the dequant all run here while step() keeps prefilling/decoding —
        the transfer overlaps compute instead of serializing admission
        (ROADMAP item 3a; "The Big Send-off" schedules transfers against
        compute the same way)."""
        while True:
            job = self._fetch_q.get()
            if job is None:
                return
            rec, prompt = job
            try:
                self._run_prefix_fetch(rec, prompt)
            except BaseException:  # noqa: BLE001 — a dying worker would wedge every deferral
                rec["error"] = True
                rec["done"] = True

    def _launch_prefix_fetch(self, request_id: str, prompt) -> dict:
        """Mint the in-flight record and hand the job to the fetch
        worker (called at admission, under the engine lock — the launch
        is a queue put, nothing blocking). The record is the ONLY shared
        state: the worker fills it lock-free and flips ``done`` last;
        admission reads it once ``done`` is observed, or abandons it at
        ``deadline`` (a wedged plane degrades to local prefill)."""
        rec = {
            "request_id": request_id, "done": False, "error": False, "lost": False,
            "pref": None, "restore": None, "nbytes": 0, "n_p": 0,
            "t0": time.time(), "t1": 0.0,
            "deadline": time.time() + self.prefix_fetch_deadline_s,
        }
        self._fetch_state[request_id] = rec
        self._ensure_fetch_worker()
        self._fetch_q.put((rec, [int(t) for t in prompt]))
        return rec

    def _run_prefix_fetch(self, rec: dict, prompt: list) -> None:
        """One cluster-tier resolution, STRICTLY lock-free (runs on the
        fetch worker; a bench's synchronous shim may call it inline):
        candidates, index lookup, object-plane fetch, token verify and
        dequant fill ``rec`` — every engine-state mutation (counters,
        cache re-store, republish) waits for ``_splice_prefix_fetch``
        under the lock. EVERY failure mode (index down, block evicted,
        owner dead, token mismatch, dequant error) degrades to a plain
        local prefill, never an engine error or a hang."""
        try:
            self._resolve_remote_prefix(rec, prompt)
        except Exception:  # noqa: BLE001 — the plane is an accelerator, never a dependency
            rec["error"] = True
        rec["t1"] = time.time()
        if self._tel is not None:
            # the fetch span lands in the flight recorder: overlap with
            # concurrent step records is the item-3a evidence
            self._tel.on_prefix_fetch(rec["t0"], rec["t1"], rec["n_p"], rec["pref"] is not None)
        rec["done"] = True

    def _resolve_remote_prefix(self, rec: dict, prompt: list) -> None:
        from ray_tpu.llm.kvplane.index import boundary_keys

        block = self._prefix_cache.block
        # candidate boundaries whose bucket-padded suffix still fits the
        # cache row (the SAME _prefix_fits guard as the local-hit path)
        cands = [
            (n, key) for n, key in boundary_keys(prompt, block)
            if self._prefix_fits(n, len(prompt))
        ]
        if not cands:
            return
        hit = self._kv_plane.lookup(cands)
        if hit is None:
            return
        # producer-bucket width gate BEFORE the transfer: the routed
        # meta already carries the block shape, so a producer whose
        # bucket ladder is narrower than our pad for this boundary
        # (heterogeneous fleet config) costs nothing, not a multi-MB
        # fetch discarded post-hoc
        shape = tuple(hit.get("meta", {}).get("shape") or ())
        if len(shape) > 1 and shape[1] < _bucket(int(hit["n"]), self.prefill_buckets):
            return
        payload = self._kv_plane.fetch(hit)
        if payload is None:
            # evicted/lost remote block after the bounded retries: the
            # client already reported the dead route to the index
            rec["lost"] = True
            return
        n_p = int(hit["n"])
        # token-for-token verification — the same collision guarantee the
        # local cache keeps: a hash collision (or a stale publish) must
        # never serve a foreign prompt's KV. The prompt snapshot is the
        # launch-time one, which cannot drift: only token-less requests
        # (st.prefilled is None, no generated tokens) ever launch.
        if payload["n"] < n_p or payload["prompt_token_ids"][:n_p] != [int(t) for t in prompt[:n_p]]:
            return
        pad = _bucket(n_p, self.prefill_buckets)
        if payload["k"].shape[1] < pad:
            return  # producer's bucket ladder narrower than ours
        k_w, v_w = payload["k"][:, :pad], payload["v"][:, :pad]
        k_sc, v_sc = payload.get("k_scale"), payload.get("v_scale")
        if k_sc is not None:
            k_sc, v_sc = k_sc[:, :, :pad], v_sc[:, :, :pad]
        wire_int8 = str(k_w.dtype) == "int8"
        rec["n_p"] = n_p
        rec["nbytes"] = int(hit.get("meta", {}).get("nbytes") or (k_w.nbytes + v_w.nbytes))
        # dequant for the local re-store is PURE compute — do it here on
        # the worker; only when a later local hit reproduces the same
        # cache bytes: fp wire re-inserts exactly; int8 wire dequantized
        # re-quantizes byte-identically into an int8 cache (kv_quant
        # idempotence) — an fp cache re-storing a dequantized int8 block
        # would drift from its own prefill oracle
        if wire_int8 == self.kv_quant:
            import jax.numpy as jnp

            if wire_int8:
                rec["restore"] = self._kv_plane.dequantize_wire(k_w, v_w, k_sc, v_sc)
            else:
                rec["restore"] = (jnp.asarray(k_w), jnp.asarray(v_w))
        rec["pref"] = (k_w, v_w, n_p, k_sc, v_sc)

    def _reap_fetch_zombies_locked(self) -> None:  # holds-lock: _lock
        # credit the terminal resolution of
        # deadline-abandoned fetches once the worker finishes. A landed
        # hit counts NOTHING here (the request already prefilled locally
        # and the bytes are discarded — "abandoned" is its record);
        # lost/error keep their meaning: the plane lost a routed block /
        # the resolution faulted, whether or not anyone waited for it.
        if not self._fetch_zombies:
            return
        live = []
        for rec in self._fetch_zombies:
            if not rec["done"]:
                live.append(rec)
            elif rec["error"]:
                self._plane_stats["errors"] += 1
            elif rec["lost"]:
                self._plane_stats["lost"] += 1
        self._fetch_zombies = live

    def _splice_prefix_fetch(self, st: RequestState, rec: dict, prompt):
        """Apply a landed fetch at admission (under the engine lock):
        counters and telemetry, the local PrefixCache re-store, and the
        republish offer — everything the lock-free worker deferred.
        Returns the pref tuple ``(k, v, n_valid, k_scale, v_scale)`` for
        the fused insert/transparent-requant path, or None (miss/lost/
        error: the request degrades to a plain local prefill)."""
        self._fetch_state.pop(st.request_id, None)
        if rec["error"]:
            self._plane_stats["errors"] += 1
            return None
        if rec["lost"]:
            self._plane_stats["lost"] += 1
            return None
        pref = rec["pref"]
        if pref is None:
            return None
        n_p = int(pref[2])
        self._plane_stats["hits"] += 1
        self._plane_stats["tokens_saved"] += n_p
        self._plane_stats["fetched_bytes"] += rec["nbytes"]
        if self._tel is not None:
            self._tel.on_prefix_hit("remote", n_p, rec["nbytes"])
        if rec["restore"] is not None:
            k_fp, v_fp = rec["restore"]
            stored = self._prefix_cache.store(prompt[:n_p], k_fp, v_fp, self.prefill_buckets)
            if stored is not None:
                # proven_reuse: THIS replica just fetched the block over
                # the plane — the fetch itself is reuse evidence, so the
                # republish bypasses publish_min_hits (holding it back
                # would hide a live second holder from the index until
                # this replica's own local hits re-prove what the
                # cluster already demonstrated)
                self._plane_publish(prompt[:n_p], k_fp, v_fp, *stored, proven_reuse=True)
        return pref

    def adopt_prefetched(self, prompt_token_ids, k_fp, v_fp) -> int:
        """Install a PREDICTIVELY fetched hot block into the local prefix
        cache (KVPlaneClient's prefetch worker, ROADMAP item 3b): the
        fleet's top-k demanded prefixes become LOCAL-tier hits before any
        request here asks for them. ``k_fp``/``v_fp`` are float arrays
        (the worker already dequantized an int8 wire); the cache store
        re-quantizes under kv_quant exactly like a remote-fetch re-store,
        so later local hits reproduce the prefill oracle byte-for-byte.
        Returns the adopted bytes (0 when the cache refused — duplicate,
        too-wide block, prefix caching off). The boundary keys minted
        here are remembered so the FIRST local hit they serve counts as a
        prefetch hit (the uplift evidence), and the block republishes
        under this replica (proven_reuse — the fleet demanded it)."""
        ids = [int(t) for t in prompt_token_ids]
        with self._lock:
            if self._prefix_cache is None or not ids:
                return 0
            stored = self._prefix_cache.store(ids, k_fp, v_fp, self.prefill_buckets)
            if stored is None:
                return 0
            nbytes = int(k_fp.nbytes + v_fp.nbytes)
            self._plane_stats["prefetched_blocks"] += 1
            self._plane_stats["prefetched_bytes"] += nbytes
            self._prefetched_keys.add(prefix_key(token_bytes(ids), len(ids)))
            self._plane_publish(ids, k_fp, v_fp, *stored, proven_reuse=True)
        # the publish itself (owned object + index RPC) runs lock-free,
        # same as the step tail — the prefetch worker is not a stepper,
        # so nobody else would flush this offer promptly
        self._flush_plane_offers()
        return nbytes

    def _plane_publish(self, prompt, ks, vs, new_keys=None, pad=None, proven_reuse=False):
        """Queue a prefix-block publish for the cluster plane. Every
        caller runs under the engine lock (admission self-heal, the
        remote-fetch republish, the prefill store path), so the actual
        publish — serialization, ``put_owned``, a timeout-bounded index
        RPC — is deferred to ``_flush_plane_offers()`` at the step tail,
        outside the lock. The offer holds references to the same arrays
        the prefix cache just stored, so nothing is copied and the block
        is still published by the time ``step()`` returns."""
        block = self._prefix_cache.block
        n_max = (len(prompt) // block) * block
        if n_max < block:
            return
        self._plane_offers.append((list(prompt), ks, vs, new_keys, pad, proven_reuse))

    def _flush_plane_offers(self):
        """Publish queued prefix blocks (owned object + index
        registration) — called from the step tail with the engine lock
        RELEASED. ``new_keys`` scopes registration to the boundaries the
        local cache just minted (the store path); None lets the client
        cover every still-unpublished boundary (the local-hit self-heal
        after a transient publish failure). ``proven_reuse`` bypasses the
        client's publish_min_hits policy (the remote-fetch republish
        path). Failures degrade silently — the client counts them;
        serving never depends on the plane."""
        if not self._plane_offers:
            return
        with self._lock:
            offers, self._plane_offers = self._plane_offers, []
        block = self._prefix_cache.block
        for prompt, ks, vs, new_keys, pad, proven_reuse in offers:
            n_max = (len(prompt) // block) * block
            pad = int(ks.shape[1]) if pad is None else pad
            nbytes = self._kv_plane.publish(
                [int(t) for t in prompt[:n_max]], ks[:, :pad], vs[:, :pad],
                bounds=None if new_keys is None else [(n, key) for key, n in new_keys],
                proven_reuse=proven_reuse,
            )
            if nbytes:
                with self._lock:
                    self._plane_stats["published_blocks"] += 1
                    self._plane_stats["published_bytes"] += nbytes

    def _stage_prefill(self, wave: list) -> list:
        """PREFILL stage (execution): run the admission wave's forwards.
        Plain prefills sharing a bucket run as ONE batched forward instead
        of B=1 dispatches; transferred-KV and prefix-hit requests scatter
        in without re-attending cached tokens; prefill-only requests
        complete into handoff blocks inside _bind_group. Group after
        group is enqueued (prefill, inserts, first-token sample, lane
        write) and nothing is read: the first tokens stay on the device
        until _stage_decode has dispatched the step that consumes them.
        Returns the admitted RequestStates."""
        admitted: list[RequestState] = []
        if not wave:
            return admitted
        self._t_prefill_start = time.time()  # telemetry: wave prefill span start
        plains: list[tuple] = []
        for st, slot, pref, pages, prompt in wave:
            if self.kv_layout == "paged":
                self._slot_pages[slot] = pages
                self._tables[slot, :] = 0
                self._tables[slot, : len(pages)] = pages
            if st.prefilled is not None or pref is not None:
                if self.kv_layout == "paged":
                    self._admit_special_paged(st, slot, pref, prompt)
                else:
                    self._admit_special_slots(st, slot, pref, prompt)
            else:
                plains.append((st, slot, prompt))
            admitted.append(st)
        if plains:
            for group in self._bucket_groups(plains):
                self._admit_prefill_batch(group)
        return admitted

    def _bucket_groups(self, plains):
        """Group (st, slot, prompt) triples by prefill bucket, a bucket's group in runs of as many
        prompts as ONE prefill program may take (``_prefill_batch``)."""
        groups: dict[int, list] = {}
        for item in plains:
            T = _bucket(len(item[2]), self.prefill_buckets)
            groups.setdefault(T, []).append(item)
        runs = []
        for T, group in groups.items():
            n = self._prefill_batch(T, len(group))
            runs += [group[i:i + n] for i in range(0, len(group), n)]
        return runs

    def _prefill_batch(self, T: int, waiting: int) -> int:
        """The most prompts of bucket T that one prefill program takes: the next power of two over
        those waiting, halved while its temporaries and results would not fit what the device has
        free beside weights and cache. An admission wave is as many prompts as there are free
        slots, and a prefill holds something for every position (a latent layer's expanded keys
        and values: 88 KB), so the wave that fits the slots need not fit the memory. One prompt is
        never split: where even that does not fit, the device says so."""
        n = 1 << (waiting - 1).bit_length()
        while n > 1 and self._prefill_room is not None and self._prefill_bytes(n, T) > self._prefill_room:
            n //= 2
        return n

    def _prefill_bytes(self, n: int, T: int) -> int:
        """Bytes the prefill of n prompts of bucket T takes beside its arguments: the compiler's
        account where the shape has run (``_admit_prefill_batch`` keeps it), else reckoned by
        positions from the largest shape of the bucket that has, else of any; 0 before any has."""
        known = self._prefill_need
        if (n, T) in known:
            return known[n, T]
        like = [(b * t, need) for (b, t), need in known.items() if t == T] or [(b * t, need) for (b, t), need in known.items()]
        if not like:
            return 0
        positions, need = max(like)
        return need * n * T // positions

    def _admit_prefill_batch(self, group):
        """One batched forward prefills every prompt in the group (all in
        the same length bucket). The batch dimension is padded to a power
        of two so compile count stays (buckets x log2(max_num_seqs));
        padding rows carry length 1 and produce garbage that is never
        inserted. This is how forward-only prefill reaches training-step
        MXU utilization instead of B=1 dispatch overhead.

        One stamped stage inside ``llm.step.prefill``, once a group:
        ``llm.step.prefill.launch`` (the host until the prefill program,
        every sequence's inserts, the group's first-token sample and its
        lane write are enqueued), and the group's three stamps on the
        step's row (``prefill_dispatch_t``: prefill enqueued, all of it
        enqueued, first tokens read: after the step's dispatch, or here
        where the next dispatch needs them on the host), against which a
        trace's prefill executions are set (util/profiling.summarize)."""
        import jax.numpy as jnp

        tel = self._tel
        with stage(tel, "llm.step.prefill.launch"):
            T = _bucket(max(len(p) for _, _, p in group), self.prefill_buckets)
            B = len(group)
            Bp = 1 << (B - 1).bit_length()
            toks = np.zeros((Bp, T), np.int32)
            lens = np.ones((Bp,), np.int32)
            for i, (_, _, prompt) in enumerate(group):
                toks[i, : len(prompt)] = prompt
                lens[i] = len(prompt)
            # a hybrid's prefill also hands back each recurrent layer's state at the prompt's true
            # length, and its routing layers' counters (hybrid_runner.PREFILL_STATS)
            ks = vs = rows = kept = None
            rows_lens = lens  # on the host: every row's length as the program gets it, a padding row's 1 among them
            toks, lens = jnp.asarray(toks), jnp.asarray(lens)
            t_before = time.time() if tel is not None else 0.0
            if self._hybrid:
                # what each layer keeps per position and per sequence, by entry name (hybrid_runner.prefill)
                logits, rows, kept = self._prefill(self.params, toks, lens)
            else:
                logits, ks, vs = self._prefill(self.params, toks, lens)
            t_dispatch = time.time() if tel is not None else 0.0
            if self._prefill_room is not None and (Bp, T) not in self._prefill_need:
                # the shape's first run (a warm-up's, where there is one): the executable is the one just run, nothing compiles
                from ray_tpu.llm.model_runner import program_bytes

                self._prefill_need[Bp, T] = program_bytes(self._prefill, self.params, toks, lens)
            # every sequence's rows into its slot, all enqueued behind the prefill before the host
            # waits for anything
            for i, (st, slot, prompt) in enumerate(group):
                n = len(prompt)
                if self._prefix_cache is not None and not st.token_ids:
                    stored = self._prefix_cache.store(prompt, ks[:, i], vs[:, i], self.prefill_buckets)
                    if stored is not None and self._kv_plane is not None:
                        # the block every other replica would re-prefill —
                        # publish it to the cluster tier (llm/kvplane/)
                        self._plane_publish(prompt, ks[:, i], vs[:, i], *stored)
                if self.kv_layout == "paged":
                    page = self._pcfg.page_size
                    table_row = jnp.asarray(self._tables[slot])
                    self.pool = self._insert(self.pool, table_row[: T // page], ks[:, i], vs[:, i])
                    self._lengths[slot] = n
                    self._push_table(slot)
                elif not self._hybrid:
                    self.cache = self._insert(self.cache, slot, ks[:, i], vs[:, i], n)
                else:
                    self.cache = self._insert(self.cache, slot, {name: a[:, i] for name, a in rows.items()}, n)
                    if self.state:
                        # replaces whatever the slot's last sequence left: the reset of a recycled slot
                        with stage(tel, "llm.step.state_insert"):
                            self.state = self._state_insert(self.state, np.int32(slot), np.int32(i), kept)
            stamps = None
            if tel is not None:
                stamps = [t_dispatch, 0.0, 0.0]
                if tel.prefill_dispatch_t is None:
                    tel.prefill_dispatch_t, tel.prefill_dispatch_t0 = [], []
                tel.prefill_dispatch_t.append(stamps)
                tel.prefill_dispatch_t0.append(t_before)
            stats = None
            if tel is not None:
                # the step's row in the flight log: tokens prefilled, true and as padded, what the description
                # counts of the shape, the flash calls' query tiles, the positions a position-wise sub-block runs; the
                # routing counters ride the first tokens' readback
                from ray_tpu.ops.flash_attention import query_tiles
                from ray_tpu.ops.layers import live_rows

                true = [len(p) for _, _, p in group]
                counted = self.config.prefill_counters(Bp, T, lengths=true) if self._hybrid else {}
                if self._hybrid:  # the description knows which of its sub-blocks go through ``live_slabs``
                    rows_live = self.config.prefill_rows_live(T, rows_lens)
                else:  # ``model_runner.prefill``'s MLP does; under a mesh the plain form runs
                    rows_live = live_rows(T, rows_lens) if self.mesh is None else Bp * T
                stats = (sum(true), Bp * T, {**counted, **query_tiles(self.config.flash_calls(T), T, rows_lens), "prefill_rows_live": rows_live})
            self._bind_group([(st, slot) for st, slot, _ in group], logits, stamps=stamps, stats=stats,
                             routing=kept.get("routing") if self._hybrid and stats is not None else None)
            if stamps is not None:
                stamps[1] = time.time()
        if self._spec_cfg is not None:  # the drafter's history is built from the token: read it before the dispatch
            self._read_first_tokens()

    def _admit_special_paged(self, st: RequestState, slot: int, pref, prompt):
        """Paged admission for transferred-KV / prefix-cache-hit requests
        (pages already allocated and mirrored into the host table)."""
        import jax.numpy as jnp

        page = self._pcfg.page_size
        n = len(prompt)
        table_row = jnp.asarray(self._tables[slot])
        if st.prefilled is not None:
            kv = st.prefilled
            st.prefilled = None
            t_scatter = time.time()
            kn, vn, n_real = kv["k"], kv["v"], int(kv["n"])
            T_pad = -(-int(kn.shape[1]) // page) * page
            k_pad = np.zeros((kn.shape[0], T_pad) + tuple(kn.shape[2:]), kn.dtype)
            v_pad = np.zeros_like(k_pad)
            k_pad[:, : kn.shape[1]] = kn
            v_pad[:, : vn.shape[1]] = vn
            scales = ()
            if kv.get("k_scale") is not None:  # int8 payload: pad the wire
                # scales ([L, kv, T]) to the same page multiple
                ks_w, vs_w = kv["k_scale"], kv["v_scale"]
                ks_pad = np.zeros(ks_w.shape[:2] + (T_pad,), np.float32)
                vs_pad = np.zeros_like(ks_pad)
                ks_pad[..., : ks_w.shape[2]] = ks_w
                vs_pad[..., : vs_w.shape[2]] = vs_w
                scales = (jnp.asarray(ks_pad), jnp.asarray(vs_pad))
            # ONE fused scatter-in (llm/disagg/scatter.py): pool pages
            # + device table row + device length lane in a single
            # program — the handoff admission hot path
            self.pool, self._dtables, self._dlengths = self._scatter_paged(
                self.pool, self._dtables, self._dlengths, np.int32(slot),
                table_row, jnp.asarray(k_pad), jnp.asarray(v_pad), np.int32(n_real), *scales,
            )
            self._lengths[slot] = n_real
            if self._tel is not None:
                self._tel.on_scatter_in(st, t_scatter)
            # a live-state restore ships no logits: the bind below
            # splices instead of sampling a first token
            logits = None if st.resume is not None else jnp.asarray(kv["logits"])[None]
        else:
            k_p, v_p, n_p, k_sc, v_sc = pref
            m = n - n_p
            Tm = _bucket(m, self.prefill_buckets)
            # the cache stores K/V at the ORIGINAL prompt's bucket width;
            # the hit may be any block-aligned prefix of it — slice to the
            # matched length (page-aligned: page_size divides prefix_block).
            # A cluster-plane remote hit arrives with wire-layout scales
            # when the producer cache was int8; insert_pages requants
            # transparently exactly like the disagg scatter-in.
            scales = () if k_sc is None else (jnp.asarray(k_sc[:, :, :n_p]), jnp.asarray(v_sc[:, :, :n_p]))
            self.pool = self._insert(
                self.pool, table_row[: n_p // page], jnp.asarray(k_p)[:, :n_p], jnp.asarray(v_p)[:, :n_p],
                *scales,
            )
            toks = np.zeros((Tm,), np.int32)
            toks[:m] = prompt[n_p:]
            logits, self.pool = self._extend(
                self.params, self.pool, table_row, jnp.asarray(n_p, np.int32), jnp.asarray(toks), jnp.asarray(m, np.int32)
            )
            logits = logits[None]
            self._lengths[slot] = n
            self._push_table(slot)
        if st.resume is not None:
            self._bind_resume(st, slot)
        else:
            self._bind_group([(st, slot)], logits)
            if self._spec_cfg is not None:  # the drafter's history is built from the token
                self._read_first_tokens()

    def _admit_special_slots(self, st: RequestState, slot: int, pref, prompt):
        """Slot-layout admission for transferred-KV / prefix-cache-hit
        requests."""
        import jax.numpy as jnp

        n = len(prompt)
        if st.prefilled is not None:
            # disaggregated admission: KV arrived from a prefill engine
            # and scatters in through the audited disagg program. An int8
            # payload carries its wire-layout scales; producer/consumer
            # dtype mismatches requant transparently inside the program.
            kv = st.prefilled
            st.prefilled = None
            t_scatter = time.time()
            k_sc, v_sc = kv.get("k_scale"), kv.get("v_scale")
            scales = (jnp.asarray(k_sc), jnp.asarray(v_sc)) if k_sc is not None else ()
            self.cache = self._scatter_slots(
                self.cache, np.int32(slot), jnp.asarray(kv["k"]), jnp.asarray(kv["v"]),
                np.int32(int(kv["n"])), *scales,
            )
            if self._tel is not None:
                self._tel.on_scatter_in(st, t_scatter)
            # a live-state restore ships no logits: the bind below
            # splices instead of sampling a first token
            logits = None if st.resume is not None else jnp.asarray(kv["logits"])[None]
        else:
            # reuse the cached prefix KV; re-attend only the suffix. A
            # cluster-plane remote hit carries wire-layout scales when the
            # producer cache was int8 — insert_sequence requants
            # transparently, same contract as the disagg scatter-in.
            k_p, v_p, n_p, k_sc, v_sc = pref
            m = n - n_p
            Tm = _bucket(m, self.prefill_buckets)
            scales = () if k_sc is None else (jnp.asarray(k_sc), jnp.asarray(v_sc))
            self.cache = self._insert(self.cache, slot, jnp.asarray(k_p), jnp.asarray(v_p), n_p, *scales)
            toks = np.zeros((Tm,), np.int32)
            toks[:m] = prompt[n_p:]
            logits, self.cache = self._extend(
                self.params, self.cache, slot, jnp.asarray(toks), jnp.asarray(m, np.int32)
            )
            logits = logits[None]
        # sample the first generated token from the prefill logits (a
        # live-state restore splices instead: no sample, no emit)
        if st.resume is not None:
            self._bind_resume(st, slot)
        else:
            self._bind_group([(st, slot)], logits)
            if self._spec_cfg is not None:  # the drafter's history is built from the token
                self._read_first_tokens()

    def _bind(self, st: RequestState, slot: int):
        """The host's side of a binding, which needs no device value: the slot, the admission order,
        the request's stamps, the sampling parameters' shadows."""
        st.slot = slot
        st.admit_seq = self._admit_counter = getattr(self, "_admit_counter", 0) + 1
        self._slots[slot] = st
        if self._tel is not None:
            self._tel.on_bind(st, getattr(self, "_t_prefill_start", st.t_submit))
        p = st.params
        self._temps[slot] = p.temperature
        self._top_k[slot] = p.top_k
        self._top_p[slot] = p.top_p

    def _bind_group(self, lanes: list, logits, stamps=None, stats=None, routing=None):
        """Bind a group's lanes (``lanes``: [(st, slot)], the first rows of ``logits`` [G, V]) and
        enqueue its first tokens: ONE sample over the group's rows, the seedless rows' keys gathered
        from the lanes on the device, and ONE lane write (first input token, advanced key, sampling
        parameters) from the sample's device results. The host reads nothing here: the tokens wait in
        ``_first_tokens`` for ``_read_first_tokens``. A prefill-only request's row is not sampled: its
        block and logits leave at once (``_complete_handoff``), and decode never sees it."""
        G, B = int(logits.shape[0]), self.max_num_seqs
        slots = np.full((G,), B, np.int32)  # out of range: a row the lane write drops
        seeds, seeded = np.zeros((G,), np.int32), np.zeros((G,), np.bool_)
        temps, top_k, top_p = np.zeros((G,), np.float32), np.zeros((G,), np.int32), np.ones((G,), np.float32)
        live = []
        for i, (st, slot) in enumerate(lanes):
            self._bind(st, slot)
            if st.prefill_only:
                self._complete_handoff(st, slot, logits[i : i + 1])
                continue
            p = st.params
            slots[i], temps[i], top_k[i], top_p[i] = slot, p.temperature, p.top_k, p.top_p
            if p.seed is not None:
                # PRNGKey(seed) is made inside the program, from the seed as jax takes a Python int in
                seeds[i], seeded[i] = np.int64(p.seed).astype(np.int32), True
            live.append((i, st, slot))
        if not live and routing is None:  # nothing to read back: the group's counts go onto the step's row at once
            self._count_prefill(stats, None)
            return
        tok = logp = None
        if live:
            if self._spec_cfg is None:
                self._lanes_bound_device += len(live)  # no host round trip before the dispatch
            tok, logp, keys = self._sample_first(logits, self._dkeys, slots, seeds, seeded, temps, top_k, top_p)
            self._write_lanes(slots, tok, keys, temps, top_k, top_p)
        self._first_tokens.append((tok, logp, routing, live, stamps, stats))

    def _write_lanes(self, slots, tokens, keys, temps, top_k, top_p):
        """Lane delta: the rows' next input tokens, keys and sampling params into the device-resident
        decode state at ``slots``, from host or device values alike."""
        self._dtokens, self._dkeys, self._dtemps, self._dtopk, self._dtopp = self._set_lanes(
            self._dtokens, self._dkeys, self._dtemps, self._dtopk, self._dtopp, slots, tokens, keys, temps, top_k, top_p)

    def _first_unread(self) -> set:
        """ids of the requests bound this step whose first token the host has not read yet: they
        hold one position more than ``token_ids`` says, as the lanes of a step in flight do."""
        return {id(st) for entry in self._first_tokens for _, st, _ in entry[3]}

    def _read_first_tokens(self):
        """ONE blocking transfer for every group enqueued since the last call: first tokens and
        log-probabilities (and a hybrid prefill's routing counters, which ride it), then the emits.
        Called behind the step's dispatch, so the device runs that step while the host waits here;
        a lane finished by its first token has then run one discarded trailing step, as a lane
        finished by any later token has. A lane that lost its slot since (an abort, a preemption)
        emits nothing."""
        import jax

        groups, self._first_tokens = self._first_tokens, []
        if not groups:
            return
        waited = [entry[:3] for entry in groups]
        tel = self._tel
        if tel is not None:
            tel.blocked_on = waited
        host = jax.device_get(waited)  # tpulint: disable=CCR002 — the wave's one first-token readback, behind the dispatch of the step that runs meanwhile
        if tel is not None:
            tel.blocked_on = None
        self._first_token_syncs += 1
        now = time.time()
        for (tok, logp, routing), (_, _, _, live, stamps, stats) in zip(host, groups):
            if stamps is not None:
                stamps[2] = now
            for i, st, slot in live:
                if st.finished or st.slot != slot:
                    continue
                self._emit(st, int(tok[i]), float(logp[i]))  # tpulint: disable=CCR002 — reads the host arrays the one transfer above brought
                if self._spec_cfg is not None:
                    self._spec_admit(st, slot, st.prompt_token_ids + st.token_ids)
            self._count_prefill(stats, routing)

    def _count_prefill(self, stats, routing):
        """One prefill program's counts (``_admit_prefill_batch``'s ``stats``; None without telemetry)
        and its routing counters, on the host, onto the sums that the step's row takes."""
        if stats is None:
            return
        # hybrid_runner.PREFILL_STATS: the fourth comes only from a program whose blocks the kernel runs
        routing = np.zeros((4,), np.float32) if routing is None else np.pad(routing, (0, 4 - len(routing)))
        # what the description reads off the program's routing counters and its shape (``stats[1]``: its rows as padded), host arithmetic alone
        counted = {**stats[2], **self.config.routed_counters(stats[1], routing)} if self._hybrid else stats[2]
        seen = self._prefill_stats or (0, 0, 0, np.zeros_like(routing), {})
        self._prefill_stats = (seen[0] + stats[0], seen[1] + stats[1], seen[2] + 1, seen[3] + routing,
                               {**seen[4], **{k: seen[4].get(k, 0) + v for k, v in counted.items()}})  # a program may count what another of the step does not

    def _bind_resume(self, st: RequestState, slot: int):
        """Splice a restored live-state request into the decode loop
        (llm/migrate.py): bind the slot and every lane from the
        CHECKPOINTED state — the exact (already-advanced) PRNG key, the
        last emitted token as the next decode input, the sticky spec
        effective-k — and emit NOTHING. The checkpoint settled the
        source's in-flight step, so the next client-visible token is
        minted by the first decode step here: the stream can neither
        repeat nor drop a token across the splice."""
        self._bind(st, slot)
        rs = st.resume
        st.resume = None
        p = st.params
        # the checkpointed key, NEVER re-derived from the seed: a seeded
        # lane's key advanced once per sample at the source, and the
        # oracle's post-splice draws continue that sequence
        key = np.asarray(rs["rng_key"], np.uint32)  # tpulint: disable=CCR002 — checkpoint splice: rs is host state from llm/migrate.py, not a device array
        # a group of one, from host values: the last emitted token is the next decode input
        self._write_lanes(np.int32([slot]), np.int32(st.token_ids[-1:]), key[None],
                          np.float32([p.temperature]), np.int32([p.top_k]), np.float32([p.top_p]))
        if self._spec_cfg is not None:
            spec = rs.get("spec") or {}
            self._controller.restore(st.request_id, spec.get("ema"), spec.get("k"))
            # history = prompt + everything emitted; the drafter caches
            # hist[:-1] — exactly the positions the restored block covers
            self._spec_admit(st, slot, st.prompt_token_ids + st.token_ids)

    def _complete_handoff(self, st: RequestState, slot: int, logits):
        """Finish a prefill-only request: extract its KV block into a
        contiguous buffer with the fused extract program for this layout
        (llm/disagg/scatter.py — slots: dynamic row slice; paged: page
        gather), stash the handoff payload, free the slot/pages. The
        block ships at the prompt's prefill-bucket width; the tail past
        the real length is garbage the decode side masks by length (the
        same contract as prefill's own padding). An int8 producer ships
        int8 values + per-head scales ([L, kv, T] wire layout) — ~half
        the object-plane bytes of a bf16 block."""
        import jax.numpy as jnp

        t_extract = time.time()
        prompt = st.prompt_token_ids
        n = len(prompt)
        T = _bucket(n, self.prefill_buckets)
        if self.kv_layout == "paged":
            page = self._pcfg.page_size
            row = np.asarray(self._tables[slot][: T // page], np.int32)
            out = self._extract_paged(self.pool, jnp.asarray(row))
        else:
            out = self._extract_slots(self.cache, np.int32(slot), T)
        payload = {
            "k": np.asarray(out[0]),
            "v": np.asarray(out[1]),
            "n": n,
            "logits": np.asarray(logits[0], np.float32),
            "prompt_token_ids": list(prompt),
        }
        if len(out) == 4:
            payload["k_scale"] = np.asarray(out[2])
            payload["v_scale"] = np.asarray(out[3])
        if self._tel is not None:
            # stamps trace context + original submit time into the payload
            # (handoff.py carries them on the wire) and accounts the bytes
            self._tel.on_handoff_extract(st, payload, t_extract)
        self._handoffs[st.request_id] = payload
        self._finish(st, "handoff")

    def _spec_admit(self, st: RequestState, slot: int, hist_tokens: list):
        """Spec lane state for a freshly admitted sequence: the token
        history row (prompt + recompute-folded generation + the first
        sampled token), the controller's sticky effective k, and the
        drafter's own prefill. A request that finished at admission
        (stop/max_tokens on the first token) never drafts."""
        import jax.numpy as jnp

        if st.finished or st.slot != slot:
            return
        n = len(hist_tokens)
        row = np.zeros((self._spec_hist_width,), np.int32)
        row[:n] = hist_tokens
        k0 = self._controller.admit(st.request_id)
        self._lane_k[slot] = k0
        self._dhist, self._dhist_len, self._dspec_k = self._set_hist(
            self._dhist, self._dhist_len, self._dspec_k,
            np.int32(slot), jnp.asarray(row), np.int32(n), np.int32(k0),
        )
        # the drafter caches everything the target has cached: the full
        # admitted prompt, NOT the fresh token (the first chain input)
        self._drafter.admit(slot, hist_tokens[:-1])

    def _emit(self, st: RequestState, token: int, logp: float):
        st.token_ids.append(token)
        st.logprobs.append(logp)
        if self._tel is not None:
            self._tel.on_emit(st)
        if st.out_queue is not None:
            st.out_queue.put(token)
        if token in st.params.stop_token_ids:
            self._finish(st, "stop")
        elif len(st.token_ids) >= st.params.max_tokens:
            self._finish(st, "length")

    def step(self) -> list[RequestOutput]:
        """Admit what fits, advance decode one step, return per-request
        deltas.

        The fused jitted step is DISPATCHED before the previous step's
        tokens are read back, so step N's host transfer overlaps step
        N+1's device compute —
        emission (streaming tokens, finish detection, slot recycling)
        trails the device by exactly one step, and each sequence runs up
        to one discarded trailing step. Under speculation that trailing
        step would cost a whole drafter round (up to k verifications), so
        wasted work is capped: a round whose every lane is guaranteed to
        finish from the still-pending round is skipped outright, and a
        finished lane never enters another round — at most ONE drafter
        round ever runs past a request's finish detection.
        """
        tel = self._tel
        t0 = time.perf_counter() if tel is not None else 0.0
        try:
            with tel.begin_step() if tel is not None else NO_STAGE, self._lock:
                self._last_spec_drain = None
                self._moe_stats = None
                self._step_emitted = 0
                self._first_tokens = []
                self._lanes_bound_device = self._first_token_syncs = 0
                with stage(tel, "llm.step.admission"):
                    wave = self._stage_admission()
                with stage(tel, "llm.step.prefill"):
                    admitted = self._stage_prefill(wave)
                reported = self._stage_decode(admitted, tel)
                with stage(tel, "llm.step.outputs"):
                    outs = self._build_outputs(reported)
                if tel is not None:
                    tel.on_step(t0, len(admitted), self._step_emitted, self._last_spec_drain)
            if self._kv_plane is not None:
                # publish the step's minted prefix blocks and refresh the
                # cluster-index lease (throttled) — both outside the
                # engine lock, so a slow plane/index can never stall
                # admissions or any lock-holding caller
                self._flush_plane_offers()
                self._kv_plane.maybe_heartbeat()
            return outs
        except BaseException as exc:
            # postmortem: persist the flight ring as JSONL in the session
            # dir before the error surfaces (serve marks the replica
            # unhealthy; the ring is the step history that led here)
            if tel is not None:
                tel.dump_on_error(exc)
            raise

    def _stage_decode(self, admitted: list, tel) -> list:
        """DECODE stage: advance every occupied slot one tick: dispatch
        the fused (or speculative) step and drain the PREVIOUS one.
        Prefill-only requests never reach here — they finished (and freed
        their slot) inside the prefill stage. Four telemetry stages:
        dispatch (host time to enqueue), drain_wait (the host blocked on
        the device's readback), emit (finish detection, queue puts), and
        in an admitting step prefill.first_tokens: the wave's first
        tokens read and emitted, while the device runs the step just
        dispatched behind the wave's prefills. They come after the last
        step's tokens (which the device had ready before the prefills
        began) and before the new lanes' second tokens, which that step
        is making."""
        spec = self._spec_cfg is not None
        with stage(tel, "llm.step.dispatch"):
            if self.kv_layout == "paged":
                self._paged_grow()
            prev = self._pending
            self._pending = None
            t_before = time.time() if tel is not None else 0.0
            if spec:
                self._dispatch_spec(prev)
            else:
                self._dispatch_fused(prev)
            if tel is not None and self._pending is not None:
                tel.dispatch_t0, tel.dispatch_t = t_before, time.time()
        with stage(tel, "llm.step.drain_wait"):
            host = self._drain_wait(prev)
        with stage(tel, "llm.step.emit"):
            emitted = self._drain_spec(prev, host) if spec else self._drain(prev, host)
        if self._first_tokens:
            with stage(tel, "llm.step.prefill.first_tokens"):
                self._read_first_tokens()
        self._step_emitted = len(emitted)
        return admitted + emitted

    def _lane_mask(self, active: list) -> np.ndarray:
        """[slots] bool, true where a lane is bound to a live sequence: a hybrid's step keeps
        the other lanes out of its routing counters."""
        mask = np.zeros((self.max_num_seqs,), np.bool_)
        mask[[st.slot for st in active]] = True
        return mask

    def _positions_held(self, active: list, prev) -> list:
        """Positions each active lane holds as the step about to be dispatched attends, its new
        token's among them. From host state alone: a lane holds its prompt and the tokens emitted
        so far, and one more where the step still in flight (``prev``) ran it or its first token is
        sampled and not read yet."""
        in_flight = self._first_unread() | ({id(st) for st, _ in prev[-1]} if prev is not None else set())
        return [min(len(st.prompt_token_ids) + len(st.token_ids) + (id(st) in in_flight), self.max_seq_len) for st in active]

    def _count_step_attn_blocks(self, active: list, prev) -> tuple:
        """(blocks of positions the step about to be dispatched reads, blocks the cache holds),
        over the layers that keep keys and values: a lane reads the blocks up to its new token's
        position, an unbound lane none. From host state alone: a lane holds its prompt and the
        tokens emitted so far, and one more where the step still in flight (``prev``) ran it."""
        blk = self._attn_block
        read = sum(-(-n // blk) for n in self._positions_held(active, prev))
        return read * self._kv_layers, self.max_num_seqs * (self.max_seq_len // blk) * self._kv_layers

    def _dispatch_fused(self, prev=None):
        """Launch the fused device step for the current occupancy; never
        blocks on results (stored in self._pending for the next call).
        ``prev``: the step still in flight, which the caller has taken."""
        active = [s for s in self._slots if s is not None]
        self._step_attn_blocks = self._step_counted = None
        if not active:
            return
        if self._attn_block is not None:
            self._step_attn_blocks = self._count_step_attn_blocks(active, prev)
        if self._counts_decode:
            self._step_counted = self.config.decode_counters(self._positions_held(active, prev))
        # the fused programs donate the sampling lanes and hand them back
        # as passthrough outputs (zero-copy aliases); rebind the handles
        if self.kv_layout == "paged":
            (toks, logps, self._dkeys, k_new, v_new, wp, wo, self._dlengths,
             self._dtemps, self._dtopk, self._dtopp) = self._fused_attn(
                self.params,
                self.pool,
                self._dtables,
                self._dlengths,
                self._dtokens,
                self._dkeys,
                self._dtemps,
                self._dtopk,
                self._dtopp,
            )
            self.pool = self._fused_append(self.pool, wp, wo, k_new, v_new)
            for st in active:
                self._lengths[st.slot] += 1  # host shadow, no upload
        elif self._hybrid:
            (self.cache, self.state, toks, logps, moe, self._dkeys,
             self._dtemps, self._dtopk, self._dtopp) = self._fused_step(
                self.params, self.cache, self.state, self._dtokens, self._dkeys,
                self._dtemps, self._dtopk, self._dtopp, self._lane_mask(active),
            )
            self._dtokens = toks
            # the routing counters ride the tokens' delayed readback: no sync of their own
            self._pending = (toks, logps, moe, [(st, st.slot) for st in active])
            return
        else:
            # which lanes are bound: the attention kernel reads nothing for the others (a
            # shard_map body takes its arguments by position and runs no kernel)
            live = {} if self._tp_fused else {"live": self._lane_mask(active)}
            (self.cache, toks, logps, self._dkeys,
             self._dtemps, self._dtopk, self._dtopp) = self._fused_step(
                self.params,
                self.cache,
                self._dtokens,
                self._dkeys,
                self._dtemps,
                self._dtopk,
                self._dtopp,
                **live,
            )
        self._dtokens = toks
        self._pending = (toks, logps, [(st, st.slot) for st in active])

    def _drain_wait(self, pending) -> tuple:
        """Read back the PREVIOUS step's (or speculative round's) device
        outputs: the one place the device-resident loop blocks, and only
        on work that overlapped the current step's dispatch. -> the host
        arrays, in the pending tuple's order, for _drain/_drain_spec."""
        if pending is None:
            return ()
        tel = self._tel
        if tel is not None:
            tel.blocked_on = pending[:-1]
        host = tuple(np.asarray(a) for a in pending[:-1])  # tpulint: disable=CCR002 — sanctioned one-step-delayed drain readback (overlaps next step's compute)
        if tel is not None:
            tel.blocked_on = None
        return host

    def _drain(self, pending, host: tuple | None = None) -> list:
        """Emit the PREVIOUS step's tokens from their read-back arrays
        (``host``: _drain_wait's, read back here when not passed)."""
        if pending is None:
            return []
        lanes = pending[-1]
        toks, logps, *moe = host if host is not None else self._drain_wait(pending)
        if moe:
            self._moe_stats = moe[0]
        emitted = []
        for st, slot in lanes:
            if st.finished:
                continue  # aborted (or finished) between dispatch and drain
            self._emit(st, int(toks[slot]), float(logps[slot]))  # tpulint: disable=CCR002 — reads the already-drained host array
            emitted.append(st)
        return emitted

    def _dispatch_spec(self, prev):
        """Launch one speculative round (draft -> fused verify) for the
        current occupancy; never blocks on results. The drafter reads the
        device history/length lanes the PREVIOUS verify step wrote, so
        draft chains on verify without any host round trip."""
        active = [s for s in self._slots if s is not None]
        if not active:
            return
        if prev is not None:
            # wasted-work cap: the pending round emits >= 1 token per
            # lane, so a lane within one token of max_tokens is finished
            # no matter what drains — if EVERY active lane is, this round
            # could only produce discarded tokens; skip it entirely
            pend = {id(entry[0]) for entry in prev[3]}
            if all(
                id(s) in pend and len(s.token_ids) + 1 >= s.params.max_tokens for s in active
            ):
                return
        lengths_lane = self._dlengths if self.kv_layout == "paged" else self.cache["length"]
        props = self._drafter.propose(self._dhist, self._dhist_len, lengths_lane)
        if self.kv_layout == "paged":
            (emit, logps, acc, toks, self._dkeys, k_blk, v_blk, wp, wo, self._dlengths,
             self._dtemps, self._dtopk, self._dtopp, self._dspec_k,
             self._dhist, self._dhist_len) = self._verify_attn(
                self.params,
                self.pool,
                self._dtables,
                self._dlengths,
                props,
                self._dtokens,
                self._dkeys,
                self._dtemps,
                self._dtopk,
                self._dtopp,
                self._dspec_k,
                self._dhist,
                self._dhist_len,
            )
            self.pool = self._verify_append(self.pool, wp, wo, k_blk, v_blk)
        else:
            (self.cache, emit, logps, acc, toks, self._dkeys,
             self._dtemps, self._dtopk, self._dtopp, self._dspec_k,
             self._dhist, self._dhist_len) = self._verify_step(
                self.params,
                self.cache,
                props,
                self._dtokens,
                self._dkeys,
                self._dtemps,
                self._dtopk,
                self._dtopp,
                self._dspec_k,
                self._dhist,
                self._dhist_len,
            )
        self._dtokens = toks
        self._spec_rounds += 1
        lanes = [(st, st.slot, int(self._lane_k[st.slot])) for st in active]
        self._pending = (emit, logps, acc, lanes)

    def _drain_spec(self, pending, host: tuple | None = None) -> list:
        """Emit the PREVIOUS speculative round from its read-back arrays
        (``host``, as for _drain): up to accepted+1 tokens per lane,
        stopping at finish (stop ids / max_tokens mid-round) and, for the
        paged layout, at the cache row's capacity — the same point the
        plain path's page growth finishes a row-exhausted sequence with
        reason 'length'."""
        if pending is None:
            return []
        lanes = pending[-1]
        emit, logps, acc = host if host is not None else self._drain_wait(pending)
        row_cap = (
            self._pcfg.max_pages_per_seq * self._pcfg.page_size if self.kv_layout == "paged" else None
        )
        emitted = []
        for st, slot, k_eff in lanes:
            if st.finished:
                continue  # aborted (or finished) between dispatch and drain
            a = int(acc[slot])
            n_new = a + 1
            cap = n_new
            if row_cap is not None:
                owns = self._slots[slot] is st
                if owns:
                    # a recompute-preempted lane's shadow was already
                    # reset; only a live occupant mirrors the device's
                    # length advance
                    cap = max(row_cap - int(self._lengths[slot]), 0)
                    self._lengths[slot] += n_new
            self._spec_proposed += k_eff
            self._spec_accepted += a
            self._spec_lane_rounds += 1
            for i in range(min(n_new, cap)):
                self._emit(st, int(emit[slot, i]), float(logps[slot, i]))  # tpulint: disable=CCR002 — reads the already-drained host array
                self._spec_emitted += 1
                if st.finished:
                    break
            if not st.finished and cap < n_new:
                # accepted tokens past the row edge had their KV dropped
                # to the trash page; the plain path would have finished
                # this row at the same token
                self._finish(st, "length")
            if not st.finished:
                new_k = self._controller.observe(st.request_id, k_eff, a)
                if st.slot == slot and new_k != self._lane_k[slot]:
                    self._lane_k[slot] = new_k
                    self._dspec_k = self._set_slot_scalar(self._dspec_k, np.int32(slot), np.int32(new_k))
            emitted.append(st)
        if emitted and self._tel is not None:
            # per-round accounting for the flight record (host ints only:
            # acc was already read back as part of this drain)
            self._last_spec_drain = (
                int(sum(entry[2] for entry in lanes)),
                int(sum(int(acc[entry[1]]) for entry in lanes)),
            )
        return emitted

    def _build_outputs(self, reported: list) -> list[RequestOutput]:  # holds-lock: _lock
        """Per-request deltas for everything that changed this step."""
        outputs: list[RequestOutput] = []
        seen: set = set()
        for st in reported:
            if st.request_id in seen:
                continue
            seen.add(st.request_id)
            outputs.append(
                RequestOutput(
                    request_id=st.request_id,
                    prompt_token_ids=st.prompt_token_ids,
                    token_ids=list(st.token_ids),
                    new_token_ids=st.token_ids[-1:],
                    finished=st.finished,
                    finish_reason=st.finish_reason,
                    logprobs=list(st.logprobs) if st.params.logprobs else None,
                    streamed=st.out_queue is not None,
                )
            )
        # also report requests finished outside the decode path (aborts,
        # admission errors)
        for st in list(self._requests.values()):
            if st.finished and st.request_id not in seen and st.request_id in self._requests:
                outputs.append(
                    RequestOutput(
                        request_id=st.request_id,
                        prompt_token_ids=st.prompt_token_ids,
                        token_ids=list(st.token_ids),
                        new_token_ids=[],
                        finished=True,
                        finish_reason=st.finish_reason,
                        logprobs=list(st.logprobs) if st.params.logprobs else None,
                        streamed=st.out_queue is not None,
                    )
                )
                del self._requests[st.request_id]
        for o in outputs:
            if o.finished and o.request_id in self._requests:
                del self._requests[o.request_id]
        return outputs

    def generate(self, prompts, params: SamplingParams | list | None = None) -> list[RequestOutput]:
        """Blocking batch generation with continuous batching underneath."""
        import numbers

        if len(prompts) == 0:
            return []
        # a single prompt is a sequence of token ids — including numpy
        # integer ids from tokenizers/arrays, hence Integral not int
        single = isinstance(prompts[0], numbers.Integral)
        if single:
            prompts = [prompts]
        if params is None or isinstance(params, SamplingParams):
            params = [params or SamplingParams()] * len(prompts)
        ids = [self.add_request(p, sp) for p, sp in zip(prompts, params)]
        finals: dict[str, RequestOutput] = {}
        while self.has_unfinished():
            for out in self.step():
                if out.finished:
                    finals[out.request_id] = out
        results = [finals[i] for i in ids]
        return results[0] if single else results
