"""Serving telemetry plane: flight recorder, live SLO metrics, tracing.

The serving stack (engine.py, llm/disagg/, serve/llm.py) reports through
this module into the runtime's existing observability substrate —
util/metrics (worker→GCS flush, /metrics exposition), util/tracing
(JSONL spans under the session dir), dashboard/grafana.py (a "Serving"
panel row) — instead of ad-hoc ``*_stats()`` dicts only a caller who
knows to poll can see.

Hard rule: ZERO device synchronization. Every sample here is host-side
scheduler state (shadow lengths, queue depths, wall clocks at the
one-step-delayed drain); instrumentation never reads a device array and
never injects a host callback into a fused program (jaxcheck JXC002
keeps that honest). The cost of being observed is a few dict updates per
step, gated in tests/test_perf_smoke.py at ≤1.05x the uninstrumented
step.

Three pieces:

- **Flight recorder** — a fixed-size ring of per-step records (phase,
  host wall ms, occupancy, queue depth, spec round accounting, handoff
  events, recompile sentinel) plus a ring of finished-request lifecycle
  records (submit/admit/first-token/finish stamps, per-token ITL
  samples). ``LLMEngine.telemetry()`` returns the snapshot of the rings;
  beside them the recorder keeps the FLIGHT LOG, every step and request
  since the engine started (bounded: ``LOG_STEPS``/``LOG_REQUESTS``),
  written once as JSONL into the session dir when the replica stops
  (``LLMServer.shutdown``) or the engine dies, and read back by
  ``load_flight()``. Nothing is written while requests are served. Each
  step record carries its STAGES (``stage()``: where the host's time in
  a step went, and the same spans as ``TraceAnnotation``s on the
  profiler's clock) and the stepping thread's CPU time beside its wall
  time. Two more rings ride the snapshot: ``fetches``, the span of every
  async remote prefix fetch (the operator's proof that a transfer over
  the KV plane overlapped live steps: tests/test_llm_kv_tiering.py and
  README "KV tiering" read it), and ``stalls``, the captures of the
  STALL SENTINEL (``StallSentinel``: a thread beside a replica's stepper
  that says, of a step that stands still, whether the device or the host
  is late, and what every thread of the process was doing), which the
  flight log keeps as a section of its own. The recompile sentinel watches each registered
  fixed-shape fused entry's jit cache: the serving hot path compiles
  ONCE per entry, so any growth after the first program is a bug
  (a varying static arg, a dtype drifting per step) and gets its own
  counter instead of a silent 100x step.
- **Live SLO metrics** — the catalog in ``METRICS`` (TTFT/ITL/queue-wait
  histograms, token/preemption/recompile counters, KV-occupancy /
  HBM-bytes / spec-acceptance / collective-wire-bytes gauges), tagged by
  model/replica/stage so a fleet's series stay separable in one scrape.
- **Request-lifecycle tracing** — spans for admission → prefill →
  handoff(put/fetch/scatter-in) → decode → first-token → finish when
  RT_TRACING=1. The trace context rides INSIDE the disagg handoff wire
  dict, so one trace id stitches a request across the prefill and
  decode replicas.
"""

from __future__ import annotations

import contextlib
import contextvars
import gc
import json
import logging
import os
import sys
import threading
import time
import uuid
from collections import deque

import jax
from jax.profiler import TraceAnnotation

from ray_tpu.util import tracing
from ray_tpu.util.profiling import SCOPES

logger = logging.getLogger("ray_tpu.llm")

# Stages of one serving step, in the order they run. ``llm.step.*`` are
# timed inside LLMEngine.step (children of the ``llm.step`` annotation);
# ``llm.stepper.*`` by the serve stepper between two steps, and land on
# the row of the step that follows. drain_wait is the host blocked on the
# device (the delayed readback); everything else is the host's own work.
# what a hybrid description may count of a prefill program from its shape alone
# (``HybridDescription.prefill_counters``), by the name its sum over an admitting step's programs
# takes on that step's row; then what the engine counts of any model's from its ``flash_calls``
# (``ops/flash_attention.query_tiles``) and from the rows' lengths (``ops/layers.live_rows``, a description's ``prefill_rows_live``)
PREFILL_COUNTERS = ("kda_chunks", "kda_kernel_chunks", "prefill_sparse_pairs", "gdn_chunks", "gdn_kernel_chunks", "swa_pairs",
                    "narrow_pairs", "pairs_scored", "pairs_chosen", "choice_bytes", "selscan_positions", "selscan_kernel_positions", "attn_q_tiles", "attn_q_tiles_live", "prefill_rows_live",
                    # and what a description reads off a program's routing counters on the host (``HybridDescription.routed_counters``)
                    "moe_expert_fetches")
# and of a decode step from the positions its lanes hold (``HybridDescription.decode_counters``), on that step's row
DECODE_COUNTERS = ("sparse_blocks_read", "sparse_blocks_live", "swa_rows_read", "narrow_rows_read", "rows_scored", "rows_chosen")

STAGES = {  # annotation name -> the step record's column (milliseconds)
    "llm.step.admission": "admission_ms",
    # the wave LAUNCHED, nothing read: it ends when the last group is enqueued
    "llm.step.prefill": "prefill_ms",
    # inside llm.step.prefill, once a group of the wave: the host from the group's start until its
    # prefill program, its inserts, its first-token sample and its lane write are dispatched (sums
    # over the wave's groups)
    "llm.step.prefill.launch": "prefill_launch_ms",
    # inside llm.step.prefill.launch: a hybrid model's recurrent state written into its slot
    "llm.step.state_insert": "state_insert_ms",
    "llm.step.dispatch": "dispatch_ms",
    "llm.step.drain_wait": "drain_wait_ms",
    "llm.step.emit": "emit_ms",
    # an admitting step's one readback of the wave's first tokens, and their emits: BEHIND the
    # dispatch, while the device runs the step that consumes them. (Where the next dispatch needs
    # the tokens on the host, under speculation, the read stands inside llm.step.prefill and has
    # no stage of its own: a group's third stamp says when it returned.)
    "llm.step.prefill.first_tokens": "first_token_wait_ms",
    "llm.step.outputs": "outputs_ms",
    "llm.stepper.deliver": "stepper_deliver_ms",
    "llm.stepper.wait": "stepper_wait_ms",
}
_STAGE_IX = {name: i for i, name in enumerate(STAGES)}
# stages timed INSIDE another: their time is in the outer stage's too
INSIDE = {"llm.step.prefill.launch": "llm.step.prefill", "llm.step.state_insert": "llm.step.prefill.launch"}
# the stages that tile a step's row, in the order they run: each ends where the next starts, the last at the row's ``t``
TILED = [name for name in STAGES if name.startswith("llm.step.") and name not in INSIDE]

# time.time() at which the serving ingress took the request now being
# admitted on this thread/task (OpenAIServer.__call__ sets it, on_submit
# reads it): the request's first stamp, before parse/encode/admission
INGRESS_T: contextvars.ContextVar = contextvars.ContextVar("rt_llm_ingress_t", default=None)

# seconds the request now on its way in on this thread/task has waited for the ENGINE's lock before its
# admission call: a one-item list that the serving ingress sets beside INGRESS_T (None elsewhere) and
# ``LLMEngine.host_load`` (the admission check's read of the queue, under that lock) adds to; on_submit
# puts it on the request's record together with the admission call's own wait (``lock_wait_s``)
LOCK_WAIT: contextvars.ContextVar = contextvars.ContextVar("rt_llm_lock_wait", default=None)

NO_STAGE = contextlib.nullcontext()

# seconds the cyclic garbage collector has held this process (a collection stops every thread: the
# stepper blocked on the device wakes only when it ends), and the start of the one under way. One
# callback a process, registered by the first EngineTelemetry; a step's row takes what its time saw.
_GC_HELD = [0.0, 0.0]


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        _GC_HELD[1] = time.perf_counter()
    else:
        _GC_HELD[0] += time.perf_counter() - _GC_HELD[1]


class _Stage:
    """One stage, two clocks, one pair of stamps: a TraceAnnotation for
    the profiler (~0.4 us while no profile is active; inside one, a host
    span on the profiler's own clock, next to the device lines) and the
    duration added to the step's flight record. While it runs, its name
    and start stand in ``EngineTelemetry.at`` for the sentinel to read; a
    stage inside another hands the slot back to the outer one."""

    __slots__ = ("_tel", "_name", "_ix", "_ann", "_t0", "_outer")

    def __init__(self, tel: "EngineTelemetry", name: str):
        self._tel = tel
        self._name = name
        self._ix = _STAGE_IX[name]
        self._ann = TraceAnnotation(name)

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        tel = self._tel
        self._outer, tel.at = tel.at, (self._name, self._t0)
        return self

    def __exit__(self, *exc):
        tel = self._tel
        tel.at = self._outer
        tel._stage_ms[self._ix] += (time.perf_counter() - self._t0) * 1e3
        self._ann.__exit__(*exc)
        return False


def stage(tel: "EngineTelemetry | None", name: str):
    """``with stage(tel, "llm.step.prefill"): ...`` — the ONE way a stage
    is stamped (name from STAGES). A no-op context for an engine built
    with telemetry=False. Reads no device value, adds no host callback."""
    return _Stage(tel, name) if tel is not None else NO_STAGE

# SLO histogram boundaries (seconds): decode steps are single-digit ms on
# chip, prefill stalls are tens-to-hundreds of ms, a cold compile is
# seconds — the buckets must resolve all three regimes.
_LATENCY_BOUNDARIES = [0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0]

_SERVE_TAGS = ("model", "replica", "stage")

# The serving metric catalog: name -> {kind, desc, tags[, boundaries]}.
# scripts/lint_gate.py's telemetry gate validates every name is legal
# Prometheus, unique across kinds (including histogram-derived
# _bucket/_count/_sum names), and that the Grafana "Serving" panels
# reference only names registered here.
METRICS: dict[str, dict] = {
    "rt_llm_ttft_s": {
        "kind": "histogram", "tags": _SERVE_TAGS, "boundaries": _LATENCY_BOUNDARIES,
        "desc": "time to first token: request submit -> first emitted token",
    },
    "rt_llm_itl_s": {
        "kind": "histogram", "tags": _SERVE_TAGS, "boundaries": _LATENCY_BOUNDARIES,
        "desc": "inter-token latency between consecutive emitted tokens",
    },
    "rt_llm_queue_wait_s": {
        "kind": "histogram", "tags": _SERVE_TAGS, "boundaries": _LATENCY_BOUNDARIES,
        "desc": "admission queue wait: request submit -> prefill-wave start",
    },
    "rt_llm_tokens_total": {
        "kind": "counter", "tags": _SERVE_TAGS,
        "desc": "generated tokens emitted to consumers",
    },
    "rt_llm_prefill_tokens_total": {
        "kind": "counter", "tags": _SERVE_TAGS,
        "desc": "prompt tokens prefilled (transferred-KV admissions count 0)",
    },
    "rt_llm_requests_finished_total": {
        "kind": "counter", "tags": _SERVE_TAGS + ("reason",),
        "desc": "finished requests by finish reason",
    },
    "rt_llm_preemptions_total": {
        "kind": "counter", "tags": _SERVE_TAGS,
        "desc": "recompute preemptions (paged pool pressure)",
    },
    "rt_llm_recompiles_total": {
        "kind": "counter", "tags": _SERVE_TAGS,
        "desc": "fused-entry recompiles after warmup (serving-path bug sentinel)",
    },
    "rt_llm_kv_occupancy": {
        "kind": "gauge", "tags": _SERVE_TAGS,
        "desc": "occupied fraction of KV-cache token capacity",
    },
    "rt_llm_kv_hbm_bytes": {
        "kind": "gauge", "tags": _SERVE_TAGS,
        "desc": "occupied KV bytes (scale-inclusive for int8 caches)",
    },
    "rt_llm_state_hbm_bytes": {
        "kind": "gauge", "tags": _SERVE_TAGS,
        "desc": "allocated bytes of the per-sequence state cache beside the KV cache (hybrid models; 0 otherwise)",
    },
    "rt_llm_queue_depth": {
        "kind": "gauge", "tags": _SERVE_TAGS,
        "desc": "requests waiting for a slot",
    },
    "rt_llm_slots_in_use": {
        "kind": "gauge", "tags": _SERVE_TAGS,
        "desc": "KV slots bound to live sequences",
    },
    "rt_llm_spec_acceptance": {
        "kind": "gauge", "tags": _SERVE_TAGS,
        "desc": "speculative acceptance rate over drained rounds (lifetime mean)",
    },
    "rt_llm_collective_wire_bytes_total": {
        "kind": "counter", "tags": _SERVE_TAGS,
        "desc": "estimated ICI bytes shipped by the fused step's collectives (jaxpr-accounted per step)",
    },
    "rt_llm_handoff_bytes_total": {
        "kind": "counter", "tags": _SERVE_TAGS,
        "desc": "disagg KV handoff bytes leaving prefill replicas",
    },
    "rt_llm_handoffs_total": {
        "kind": "counter", "tags": _SERVE_TAGS + ("event",),
        "desc": "disagg handoff events (published/scattered/lost/reused)",
    },
    # cluster KV plane (llm/kvplane/): prefix reuse by tier. "local" =
    # this replica's own PrefixCache; "remote" = a block fetched from
    # another replica over the object plane. Cluster hit-rate =
    # sum(rate(hits)) / rate(requests); the Grafana "cluster prefix
    # reuse" panel plots both tiers.
    "rt_llm_prefix_hits_total": {
        "kind": "counter", "tags": _SERVE_TAGS + ("tier",),
        "desc": "prefix-cache hits by tier (local replica cache vs remote cluster KV plane)",
    },
    "rt_llm_prefix_tokens_saved_total": {
        "kind": "counter", "tags": _SERVE_TAGS + ("tier",),
        "desc": "prompt tokens served from cached prefixes instead of prefill compute, by tier",
    },
    "rt_llm_prefix_fetch_bytes_total": {
        "kind": "counter", "tags": _SERVE_TAGS,
        "desc": "bytes fetched from remote replicas' published prefix blocks (cluster KV plane)",
    },
    # overload plane (serve/overload.py): admission control sheds by
    # request class BEFORE queue wait grows, queue wait grows before
    # decode ITL ever does — these series are how a dashboard sees that
    # degradation order actually holding.
    "rt_llm_requests_shed_total": {
        "kind": "counter", "tags": _SERVE_TAGS + ("class",),
        "desc": (
            "admission sheds (OverloadedError) by request class; each replica ingress counts "
            "its own shed and a router counts once per client request, so separate by stage "
            "when summing request-level shed rates"
        ),
    },
    "rt_llm_admission_queue_wait_est_ms": {
        "kind": "gauge", "tags": _SERVE_TAGS,
        "desc": "admission controller's live queue-wait estimate (queue depth x service-time EMA / slots)",
    },
    "rt_llm_retry_budget_exhausted_total": {
        "kind": "counter", "tags": _SERVE_TAGS,
        "desc": "requests whose router failover budget ran out (terminal typed error surfaced)",
    },
    "rt_llm_drain_state": {
        "kind": "gauge", "tags": _SERVE_TAGS,
        "desc": "replica drain lifecycle: 0 serving, 1 draining (shedding new work), 2 drained",
    },
    # live request migration (llm/migrate.py): preemption-tolerant
    # serving's evacuation path. Outcomes: "checkpointed" (source
    # extracted + published), "restored" (peer spliced), "aborted"
    # (could not checkpoint before the deadline — the abort fallback),
    # "resumed"/"lost" (router-stage resume leg succeeded / checkpoint
    # gone before fetch). Source and destination replicas count their
    # own halves, routers count once per client request — separate by
    # stage when summing.
    "rt_llm_migrations_total": {
        "kind": "counter", "tags": _SERVE_TAGS + ("outcome",),
        "desc": "live request migrations by outcome (checkpointed/restored/aborted/resumed/lost)",
    },
    "rt_llm_migration_bytes_total": {
        "kind": "counter", "tags": _SERVE_TAGS,
        "desc": "live_state checkpoint bytes (KV block + scales) moved over the object plane",
    },
    "rt_llm_migration_splice_s": {
        "kind": "histogram", "tags": _SERVE_TAGS, "boundaries": _LATENCY_BOUNDARIES,
        "desc": "splice latency: restore ingress -> first post-splice token on the peer",
    },
    # latency-hiding KV plane v2 (ROADMAP item 3): the async fetch span
    # (runs on the engine's fetch worker, overlapping prefill/decode
    # steps — the histogram is what the A/B bench reads), predictive
    # prefetch attribution (a local-tier hit served by a block pulled in
    # ahead of demand), and the tiered-conversation-KV spill volume.
    "rt_llm_prefix_fetch_overlap_s": {
        "kind": "histogram", "tags": _SERVE_TAGS, "boundaries": _LATENCY_BOUNDARIES,
        "desc": "async remote prefix fetch span (launch -> result landed), overlapped with serving steps",
    },
    "rt_llm_prefix_prefetch_hits_total": {
        "kind": "counter", "tags": _SERVE_TAGS,
        "desc": "local prefix hits served by predictively prefetched blocks (remote->local conversion)",
    },
    "rt_llm_kv_spilled_bytes_total": {
        "kind": "counter", "tags": _SERVE_TAGS,
        "desc": "conversation KV bytes spilled out of HBM by suspend_request (tiered conversation KV)",
    },
}

_instruments: dict = {}
_instr_lock = threading.Lock()


def instruments() -> dict:
    """Instantiate (once per process) and return the catalog's util.metrics
    instruments, name -> Counter/Gauge/Histogram. Registration is shared
    across engines in the process; per-engine separation rides the tags."""
    from ray_tpu.util import metrics as m

    with _instr_lock:
        if _instruments:
            return _instruments
        ctor = {"counter": m.Counter, "gauge": m.Gauge, "histogram": m.Histogram}
        for name, spec in METRICS.items():
            kw = {"description": spec["desc"], "tag_keys": tuple(spec["tags"])}
            if spec["kind"] == "histogram":
                kw["boundaries"] = list(spec["boundaries"])
            _instruments[name] = ctor[spec["kind"]](name, **kw)
        return _instruments


def default_tags(stage: str, model: str | None = None, replica: str | None = None) -> dict:
    """The model/replica/stage tag triple every serving series carries.
    Replica defaults to the worker id (the same key the metrics flusher
    uses) so a fleet's series stay separable after the GCS merge."""
    return {
        "model": model or "default",
        "replica": replica or os.environ.get("RT_WORKER_ID", str(os.getpid())),
        "stage": stage,
    }


# ----------------------------------------------------------------------
# flight recorder
# ----------------------------------------------------------------------
class FlightRecorder:
    """Fixed-size ring of per-step records + finished-request lifecycle
    records, all host-side. Thread-safe against concurrent readers
    (``snapshot`` under the engine lock vs. a stats scrape).

    The step ring stores flat TUPLES (schema ``STEP_FIELDS``) and only
    expands them to dicts in ``snapshot()``: record_step runs on every
    serving step, so it allocates one small tuple instead of a 12-slot
    dict, keeping the hot path inside the zero-overhead gate; snapshot
    and the JSONL dump are cold paths."""

    STEP_FIELDS = (
        "step", "t", "phase", "wall_ms", "admitted", "emitted", "batch",
        # bound lanes with temperature > 0: the lanes whose top-k or top-p the sampler's counting
        # passes run for (llm/sampling.py); a step with none takes its tokens from the argmax
        "sampling_lanes", "waiting",
        "occupied_tokens", "capacity_tokens",
        # the decode program this step dispatched, where its attention is the kernel that reads a
        # lane's live blocks only (ops/slot_attention.py): blocks of positions it reads and blocks
        # the slot cache holds, over the layers that keep keys and values. Their ratio is the share
        # of the cache the step moves. Absent where the XLA form runs (it reads all of it) and in a
        # step that dispatched nothing
        "attn_blocks_read", "attn_blocks_total",
        # what the description counts of the decode program this step dispatched from the positions its
        # lanes hold (``HybridDescription.decode_counters``): blocks of 64 positions, a key-value head's
        # share each, that the sparse layers read for the blocks their queries chose, and would read
        # attending to everything; rows of a window layer's ring that the step's bound lanes read, over the
        # window layers (``swa_rows_read``: min(position + 1, window) a lane and layer); positions whose keys and
        # values the attention layers with heads narrower than the 128 lanes read, over those layers
        # (``narrow_rows_read``: position + 1 a lane and layer); rows of the indexer's keys that the layers under a learned index
        # score (``rows_scored``: position + 1 a lane and layer) and rows of keys and values they then attend to (``rows_chosen``:
        # min(position + 1, top-k)); absent for a description that counts none
        *DECODE_COUNTERS,
        "pages_free", "pages_total",
        "recompiled", "spec_k", "spec_accepted",
        # the step's start and the instant its fused program was enqueued
        # (both time.time(); dispatch_t absent where none was); of an ADMITTING
        # step, for each group of its wave [its prefill program enqueued, its
        # inserts, first-token sample and lane write enqueued too (launch's
        # end), its first tokens read back (after dispatch_t, but where the
        # dispatch needed them on the host)]; and of an admitting step the
        # lanes whose first token was sampled and bound by device programs
        # alone, no host round trip before the dispatch (all that were
        # admitted, but lanes resumed from a checkpoint, handed off after
        # their prefill, or bound under speculation), and the blocking
        # readbacks of first tokens (at most one a wave; one a group under
        # speculation)
        "t0", "dispatch_t", "prefill_dispatch_t", "lanes_bound_device", "first_token_syncs",
        # a hybrid model's drained decode step (llm/hybrid_runner.MOE_STATS): held experts that
        # got a token (mean over expert layers), (token, expert) pairs served here and asked
        # for in all, most tokens at one expert, held experts whose weights the step read (mean over
        # expert layers: the experts hit, since the step loops over those); absent for a model
        # without routed experts
        "experts_hit", "moe_pairs_local", "moe_pairs_total", "moe_max_load", "experts_read",
        # an ADMITTING step, of that step's prefills: tokens taken in, true and as padded to bucket
        # and batch, then llm/hybrid_runner.PREFILL_STATS (zeros for a model without routed experts),
        # each a mean over the routing layers: held experts that got a pair (mean over the step's prefill programs),
        # (token, expert) pairs served here, rows of the grouped matmul's blocks in use and rows of the
        # blocks that the kernel ran (``ops/grouped_experts.py``; 0 where the loop ran them) (sums
        # over them). Under names of their own: the four above stay the drained DECODE step's
        "prefill_tokens", "prefill_tokens_padded", "prefill_experts_hit", "prefill_moe_pairs_local", "moe_rows_computed", "moe_rows_kernel",
        # and what the description counts of those prefill programs from their shapes alone
        # (``HybridDescription.prefill_counters``): chunks of the delta rule that the programs ran, padding's
        # among them, over the layers of Kimi Delta Attention (``kda_``) or of Gated DeltaNet (``gdn_``), and how
        # many of them the kernel ran; (query, block) pairs that the sparse layers read at the prompts' true
        # lengths; (query, key) pairs inside the window that the sliding-window layers' mathematics needs at
        # the prompts' true lengths (``swa_pairs``: min(i + 1, window) a position and layer); causal (query, key)
        # pairs of the attention layers with heads narrower than the 128 lanes at the prompts' true lengths
        # (``narrow_pairs``: i + 1 a position and layer); (query, position) pairs that the layers under a learned index score
        # (``pairs_scored``: every causal pair of a bucket longer than the top-k) and attend to (``pairs_chosen``: min(i + 1, top-k) a
        # position and layer), and the bytes of choice tables, a bit a pair, that the programs' thresholds' kernels wrote by their shape
        # (``choice_bytes``: 0 where the XLA form or the flash kernel ran); positions, as padded, that the programs' Mamba-1 layers scan
        # (``selscan_positions``: batch rows x bucket x layers) and how many of them the kernel ran (``selscan_kernel_positions``,
        # ``ops/selective_scan.py``: all, or 0 where the XLA form ran); absent for a description that counts none. Last, of any model: the query tiles that the programs' flash calls
        # have by their shape (``attn_q_tiles``: calls x batch rows x tiles of the bucket) and those that start
        # under a row's true length (``attn_q_tiles_live``): the kernel computes and fetches these alone;
        # and the positions that a position-wise sub-block of the programs runs (``prefill_rows_live``: a dense
        # FFN or a Llama MLP, through ``ops/layers.live_slabs``), whole slabs under each row's true length beside
        # ``prefill_tokens_padded``, and equal to it where the plain form runs or the description has no such
        # sub-block (``HybridDescription.prefill_rows_live``); and how many times a held expert's matrices were brought in by the
        # step's prefills (``moe_expert_fetches``, a mean over the expert layers, summed over the programs: a block in the grouped
        # matmul's loop, a run of an expert's blocks in its kernel; ``HybridDescription.routed_counters`` reckons it on the host from
        # the counters above and the program's shape; absent for a description that does not)
        *PREFILL_COUNTERS,
        # then the stage durations, and the milliseconds of the step that the process spent inside
        # the garbage collector (every thread held; absent where there were none)
    ) + tuple(STAGES.values()) + (
        "gc_ms",
        # the stamps (time.time()) taken BEFORE the calls whose return ``dispatch_t`` and a group's first
        # stamp of ``prefill_dispatch_t`` mark: an execution cannot start before the host began to enqueue
        # it, whatever the thread lost afterwards (``util/profiling._align``'s bound from above)
        "dispatch_t0", "prefill_dispatch_t0",
        # the stepping thread's CPU time over the step (``time.thread_time()`` at its two ends): the host's own
        # stages (``wall_ms`` less the two blocking reads) against it is how long the stepper was runnable and
        # not running. With a sentinel beside the stepper (``StallSentinel``): the captures it took during the
        # step (absent where none) and its worst lateness at a wake-up (ms; absent under one)
        "cpu_ms", "captures", "tick_late_ms",
    )

    # The flight log's bound: it holds a run whole — 10 minutes at 20
    # steps/s, 2,000 requests (about 8.5 MB of step rows and 11 MB of
    # request records at 150 tokens each, tests/test_llm_flight.py).
    # Past it the oldest go, and the log's header says how many.
    LOG_STEPS = 12_000
    LOG_REQUESTS = 2_000
    # the sentinel's captures (``StallSentinel``), newest kept: those of a stage that had stood a second or
    # more in one ring, the younger ones (a long prompt's ordinary wait takes them by the hundred) in another
    # that cannot push them out
    STALLS = 256

    def __init__(self, max_steps: int = 512, max_requests: int = 256):
        self.steps: deque = deque(maxlen=max_steps)
        self.requests: deque = deque(maxlen=max_requests)
        # the flight log shares the rings' records (one tuple / one dict
        # each, appended twice)
        self.log_steps: deque = deque(maxlen=self.LOG_STEPS)
        self.log_requests: deque = deque(maxlen=self.LOG_REQUESTS)
        self.request_count = 0
        # async prefix-fetch spans (engine fetch worker): cross-checking
        # a fetch record's [t0, t1] against step records' timestamps is
        # the item-3a overlap evidence the bench and tests read
        self.fetches: deque = deque(maxlen=max_requests)
        self.stalls = (deque(maxlen=self.STALLS // 2), deque(maxlen=self.STALLS // 2))  # (stood under a second, a second or more)
        self.stall_count = 0
        self._lock = threading.Lock()
        self._entries: dict[str, tuple] = {}  # name -> (fn, warm_size or None)
        self.recompiles: dict[str, int] = {}
        self.step_count = 0

    # -- recompile sentinel --
    def register_entry(self, name: str, fn) -> None:
        """Register a FIXED-SHAPE fused entry (the decode hot path's jit
        handles: fused step, delta scatters, spec verify). These compile
        exactly once per engine config; cache growth after the first
        observed program is counted as a recompile — the bug class where
        a drifting static arg or dtype silently mints a program per step."""
        if fn is not None and hasattr(fn, "_cache_size"):
            self._entries[name] = (fn, None)

    def check_recompiles(self) -> list[str]:
        """Poll every registered entry's jit cache size (a host attribute
        read — no device work). Returns the entries that recompiled since
        the last check."""
        hits: list[str] = []
        for name, (fn, warm) in list(self._entries.items()):
            try:
                size = fn._cache_size()
            except Exception:
                continue
            if warm is None:
                if size > 0:  # first program = warm baseline
                    self._entries[name] = (fn, size)
                continue
            if size > warm:
                self.recompiles[name] = self.recompiles.get(name, 0) + (size - warm)
                self._entries[name] = (fn, size)
                hits.append(name)
        return hits

    def record_step(self, row: tuple) -> None:
        """``row`` = STEP_FIELDS[1:] values (the step counter is
        prepended here)."""
        with self._lock:
            self.step_count += 1
            row = (self.step_count,) + row
            self.steps.append(row)
            self.log_steps.append(row)

    def record_request(self, rec: dict) -> None:
        with self._lock:
            self.request_count += 1
            self.requests.append(rec)
            self.log_requests.append(rec)

    def stamp_request(self, request_id: str, **fields) -> dict | None:
        """Add late stamps (the stream's yields, which end after the
        engine finished the request) to a recorded request; -> the record,
        or None if it is not (or no longer) in the log. The request
        finished moments ago, so the scan from the newest end is short."""
        with self._lock:
            for rec in reversed(self.log_requests):
                if rec["request_id"] == request_id:
                    rec.update(fields)
                    return rec
        return None

    def record_fetch(self, rec: dict) -> None:
        with self._lock:
            self.fetches.append(rec)

    def record_stall(self, rec: dict) -> None:
        """One capture of the sentinel's (its thread's call; ``age_s``: how long the stage had stood)."""
        with self._lock:
            self.stall_count += 1
            self.stalls[1 if rec["age_s"] >= 1.0 else 0].append(rec)

    def _stalls(self) -> list:  # holds-lock: _lock
        return sorted((dict(r) for ring in self.stalls for r in ring), key=lambda r: r["t"])

    def snapshot(self) -> dict:
        with self._lock:
            rows = list(self.steps)
            reqs = [dict(r) for r in self.requests]
            fetches = [dict(r) for r in self.fetches]
            stalls = self._stalls()
            count = self.step_count
            recs = dict(self.recompiles)
        return {"step_count": count, "steps": [self._step_dict(row) for row in rows], "requests": reqs,
                "fetches": fetches, "stalls": stalls, "recompiles": recs}

    def _step_dict(self, row: tuple) -> dict:
        # drop layout-/mode-inapplicable fields (None) for readability
        return {k: v for k, v in zip(self.STEP_FIELDS, row) if v is not None}

    def dump_jsonl(self, path: str, header: dict | None = None) -> str:
        """Write the flight log as JSONL: one header line, then one line
        per step record, then one per request record, then one per capture
        of the sentinel's (the ``stalls`` section). The header counts
        what the log's bounds dropped (``dropped_steps``/``_requests``/``_stalls``)."""
        with self._lock:
            rows = list(self.log_steps)
            reqs = [dict(r) for r in self.log_requests]
            stalls = self._stalls()
            head = {"kind": "flight_header", "ts": time.time(), "pid": os.getpid(),
                    "recompiles": dict(self.recompiles),
                    "steps": len(rows), "dropped_steps": self.step_count - len(rows),
                    "requests": len(reqs), "dropped_requests": self.request_count - len(reqs),
                    "stalls": len(stalls), "dropped_stalls": self.stall_count - len(stalls)}
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(json.dumps({**head, **(header or {})}) + "\n")
            for row in rows:
                f.write(json.dumps({"kind": "step", **self._step_dict(row)}) + "\n")
            for rec in reqs:
                f.write(json.dumps({"kind": "request", **rec}) + "\n")
            for rec in stalls:
                f.write(json.dumps({"kind": "stall", **rec}) + "\n")
        return path


# ----------------------------------------------------------------------
# engine-facing facade
# ----------------------------------------------------------------------
_NO_MOE = (None,) * 5  # a step row's routing counters for a model without routed experts
_NO_PREFILL = (None,) * (6 + len(PREFILL_COUNTERS))  # and its prefill counters where the step admitted nothing through a hybrid's prefill


class EngineTelemetry:
    """Everything LLMEngine calls, one object. All entry points are
    host-only and cheap; the engine holds its own lock while calling in,
    so internal state needs no second lock beyond the recorder's."""

    def __init__(self, engine, tags: dict | None = None):
        self.engine = engine
        base = default_tags("engine")
        base.update(tags or {})
        self.tags = {k: str(v) for k, v in base.items() if k in _SERVE_TAGS}
        self.m = instruments()
        self.recorder = FlightRecorder(
            max_steps=int(os.environ.get("RT_LLM_FLIGHT_STEPS", "512")),
            max_requests=int(os.environ.get("RT_LLM_FLIGHT_REQUESTS", "256")),
        )
        # hot-path handles: tags resolved ONCE (util.metrics bind); the
        # per-step/per-token calls below must stay in single-digit
        # microseconds each to hold the 1.05x zero-overhead gate
        self._b_ttft = self.m["rt_llm_ttft_s"].bind(self.tags)
        self._b_itl = self.m["rt_llm_itl_s"].bind(self.tags)
        self._b_qwait = self.m["rt_llm_queue_wait_s"].bind(self.tags)
        self._b_tokens = self.m["rt_llm_tokens_total"].bind(self.tags)
        self._b_pf_tokens = self.m["rt_llm_prefill_tokens_total"].bind(self.tags)
        self._b_preempt = self.m["rt_llm_preemptions_total"].bind(self.tags)
        self._b_recompiles = self.m["rt_llm_recompiles_total"].bind(self.tags)
        self._b_wire = self.m["rt_llm_collective_wire_bytes_total"].bind(self.tags)
        self._b_qdepth = self.m["rt_llm_queue_depth"].bind(self.tags)
        self._b_slots = self.m["rt_llm_slots_in_use"].bind(self.tags)
        self._b_occ = self.m["rt_llm_kv_occupancy"].bind(self.tags)
        self._b_hbm = self.m["rt_llm_kv_hbm_bytes"].bind(self.tags)
        # the state cache is allocated once and never grows: its gauge is set here, not per step
        self._state_bytes = float(sum(int(a.nbytes) for a in getattr(engine, "state", {}).values()))
        self.m["rt_llm_state_hbm_bytes"].bind(self.tags).set(self._state_bytes)
        self._b_spec = self.m["rt_llm_spec_acceptance"].bind(self.tags)
        # prefix-reuse tiers (cluster KV plane): per-ADMISSION events, so
        # pre-bound handles keep them off the per-step budget entirely
        self._b_pfx_hits = {
            tier: self.m["rt_llm_prefix_hits_total"].bind({**self.tags, "tier": tier})
            for tier in ("local", "remote")
        }
        self._b_pfx_tokens = {
            tier: self.m["rt_llm_prefix_tokens_saved_total"].bind({**self.tags, "tier": tier})
            for tier in ("local", "remote")
        }
        self._b_pfx_bytes = self.m["rt_llm_prefix_fetch_bytes_total"].bind(self.tags)
        self._b_pfx_prefetch = self.m["rt_llm_prefix_prefetch_hits_total"].bind(self.tags)
        self._b_fetch_overlap = self.m["rt_llm_prefix_fetch_overlap_s"].bind(self.tags)
        self._b_spill = self.m["rt_llm_kv_spilled_bytes_total"].bind(self.tags)
        # materialize the sentinel series at 0 so a dashboard can alert
        # on ANY increase (a series that only appears on the first
        # recompile is invisible to a rate()/increase() alert rule)
        self._b_recompiles.inc(0.0)
        self._b_preempt.inc(0.0)
        # per-step constants, computed once (the on_step path must stay
        # in the tens-of-microseconds)
        self._bytes_per_token = engine.kv_bytes_per_token()
        if engine.kv_layout == "paged":
            self._capacity_tokens = (engine._pcfg.num_pages - 1) * engine._pcfg.page_size
        else:
            self._capacity_tokens = engine.max_num_seqs * engine.max_seq_len
        # gauges + the recompile poll refresh every SAMPLE_EVERY steps:
        # scrapes run at >= 1s cadence, so per-step gauge precision buys
        # nothing and the saved metric ops keep on_step inside the
        # zero-overhead gate (the flight RECORD still lands every step)
        self.SAMPLE_EVERY = 16
        self._nstep = 0
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)
        self._gc_seen = _GC_HELD[0]  # the collector's clock as the last step's row took it
        self._wire_accum = 0.0
        self._tok_accum = 0.0
        # cumulative spec accounting mirrors (deltas per step go into the
        # flight record; the gauge shows the lifetime mean)
        self._last_preemptions = 0
        self._dumped = False
        # the step under way: stage durations (ms, by _STAGE_IX; stage()
        # adds, on_step records and zeroes), its start and the instant
        # its fused program was enqueued (time.time(); the engine sets
        # dispatch_t, None where a step dispatched nothing)
        self._stage_ms = [0.0] * len(STAGES)
        self._step_t0 = 0.0
        self._step_cpu0 = 0.0  # the stepping thread's CPU clock at the step's start
        self.dispatch_t: float | None = None
        self.dispatch_t0: float | None = None  # taken before the call whose return dispatch_t marks
        self.prefill_dispatch_t: list | None = None  # the engine appends a group's three stamps
        self.prefill_dispatch_t0: list | None = None  # and the stamp before the group's prefill call
        # where the stepper is, for the sentinel (another thread) to read: (name, perf_counter start) of the
        # stage or step under way, None between two steps; and what a blocking read of the device is
        # about to wait for (any tree of arrays), None once it has returned. One store each; no lock.
        self.at: tuple | None = None
        self.blocked_on = None
        self.sentinel: "StallSentinel | None" = None  # set by its start(): a bare engine has none
        # per-step ICI wire bytes of the fused step's collectives: a
        # one-shot jaxpr accounting turned into a LIVE series (counter
        # advanced every dispatched step). 0 on tp=1 engines; computed
        # lazily so engine construction never pays an extra trace.
        self._wire_bytes_per_step: float | None = None
        # live EMAs the admission controller reads (serve/overload.py):
        # inter-token latency and per-request service time (admit ->
        # finish wall). One multiply-add on paths already stamping these
        # clocks — inside the zero-overhead gate's budget. The ITL EMA
        # takes one sample a STEP, the mean of the gaps the step's lanes
        # saw (_gap_sum / _gap_n, folded in on_step): a sample a token
        # made its memory shorter than one step at 16 lanes, so that one
        # stalled step of 2.4 s read as a steady state and the wait
        # estimate built on it (queued tokens x EMA / slots) shed
        # requests a moment later (PR 44).
        self.itl_ema_s = 0.0
        self._gap_sum, self._gap_n = 0.0, 0
        self.service_ema_s = 0.0
        # optional per-sample-tick callback (the admission controller's
        # queue-wait-gauge refresh): called with the current queue depth
        # so the gauge tracks DRAINING pressure too — a gauge only set at
        # admission time would freeze at its peak once arrivals stop
        self.sample_hook = None

    # -- registration -----------------------------------------------------
    def register_fused_entries(self) -> None:
        """Pick up the engine's fixed-shape jit handles for the recompile
        sentinel (called after the engine finished building them)."""
        eng = self.engine
        for name in ("_fused_step", "_fused_attn", "_fused_append",
                     "_set_table", "_set_table_cell",
                     "_verify_step", "_verify_attn", "_verify_append"):
            self.recorder.register_entry(name.lstrip("_"), getattr(eng, name, None))
        if getattr(eng, "_tp_fused", False):
            # pay the one-shot wire-bytes jaxpr trace HERE, at engine
            # construction (which already compiles these programs), never
            # inside a live serving step under the engine lock
            self._wire_bytes()

    # -- wire-bytes accounting -------------------------------------------
    def _wire_bytes(self) -> float:
        """Per-step collective wire bytes, computed once from the fused
        program's jaxpr (collective/ici.collective_wire_report) for tp>=2
        shard_map engines; 0 elsewhere. Abstract tracing only — no
        compile, no device work — and any failure degrades to 0 rather
        than touching the hot path."""
        if self._wire_bytes_per_step is not None:
            return self._wire_bytes_per_step
        eng = self.engine
        bytes_per_step = 0.0
        if getattr(eng, "_tp_fused", False):
            try:
                import jax

                from ray_tpu.collective.ici import collective_wire_report
                from ray_tpu.parallel.mesh import axis_size

                sds = lambda t: jax.tree.map(  # noqa: E731
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t
                )
                tp = axis_size(eng.mesh, "tp")
                if eng.kv_layout == "paged":
                    from ray_tpu.llm.model_runner import _sharded_fused_paged

                    fn = _sharded_fused_paged(eng.config, eng.mesh, eng.tp_collective, eng.kv_quant)
                    args = (sds(eng.params), sds(eng.pool), sds(eng._dtables), sds(eng._dlengths),
                            sds(eng._dtokens), sds(eng._dkeys), sds(eng._dtemps), sds(eng._dtopk),
                            sds(eng._dtopp))
                else:
                    from ray_tpu.llm.model_runner import _sharded_fused_slots

                    fn = _sharded_fused_slots(eng.config, eng.mesh, eng.tp_collective, eng.kv_quant)
                    args = (sds(eng.params), sds(eng.cache), sds(eng._dtokens), sds(eng._dkeys),
                            sds(eng._dtemps), sds(eng._dtopk), sds(eng._dtopp))
                rep = collective_wire_report(jax.make_jaxpr(fn)(*args), axis_size=tp)
                bytes_per_step = float(rep["total_bytes"])
            except Exception:
                bytes_per_step = 0.0
        self._wire_bytes_per_step = bytes_per_step
        return bytes_per_step

    # -- request lifecycle ------------------------------------------------
    def on_submit(self, st, submitted_at: float | None = None, parent_trace: tuple | None = None,
                  lock_wait_s: float | None = None) -> None:
        """Stamp admission-queue entry. ``parent_trace`` (trace_id,
        span_id) joins an existing trace — the disagg decode side passes
        the context the handoff carried so ONE trace id spans replicas.
        ``lock_wait_s``: how long the admitting thread waited for the
        engine's lock (which a step holds from end to end) in this call;
        what it waited for it on its way here (``LOCK_WAIT``) is added."""
        st.t_submit = float(submitted_at) if submitted_at is not None else time.time()
        st.t_ingress = INGRESS_T.get()
        earlier = LOCK_WAIT.get()
        st.lock_wait = lock_wait_s if earlier is None or lock_wait_s is None else lock_wait_s + earlier[0]
        # latched HERE: the prefill stage consumes st.prefilled (sets it
        # None) before the slot binds, so on_bind can't tell a transferred
        # block from a local prefill anymore
        st.kv_transferred = st.prefilled is not None
        if tracing.enabled():
            if parent_trace is not None:
                trace_id, parent_id = parent_trace[0], parent_trace[1]
            else:
                trace_id, parent_id = tracing.child_context()
            st.trace = (trace_id, uuid.uuid4().hex[:16], parent_id)  # (trace, root span, parent)

    def on_bind(self, st, t_prefill_start: float) -> None:
        """Slot bound + prefill LAUNCHED (the engine binds a group's lanes
        as it enqueues the group; the device's work on the prompt falls
        under ``llm.first_token``, which ends at the first emit): close
        the admission and prefill spans, observe queue wait. FIRST bind
        only — a recompute-preempted request re-binds through here, but
        its queue wait was already observed (re-measuring from t_submit
        would report the request's whole lifetime) and a second
        admission/prefill span pair would show the one request admitted
        twice; preemptions have their own counter and flight-record
        field."""
        now = time.time()
        if st.t_admit != 0.0:
            return
        st.t_admit = now
        # one queue-wait definition everywhere: submit -> prefill-wave
        # start (the moment the request stops WAITING and starts being
        # worked on); the finish record reuses this exact value so a
        # postmortem dump can never disagree with the live histogram
        st.queue_wait = max(t_prefill_start - st.t_submit, 0.0)
        self._b_qwait.observe(st.queue_wait)
        if not getattr(st, "kv_transferred", False) and not st.token_ids:
            self._b_pf_tokens.inc(float(len(st.prompt_token_ids)))
        if st.trace is not None:
            self._span(st, "llm.admission", st.t_submit, t_prefill_start)
            self._span(st, "llm.prefill", t_prefill_start, now)

    def on_emit(self, st, now: float | None = None) -> None:
        """One token reached the host (the first token's sample at
        admission, or the one-step-delayed drain — either way this is
        when a consumer could see it). First token observes TTFT; later ones observe ITL."""
        now = time.time() if now is None else now
        if st.t_first == 0.0:
            st.t_first = now
            self._b_ttft.observe(max(now - st.t_submit, 0.0))
            if st.t_restore:
                # a restored request's first token IS the splice landing:
                # restore ingress -> first post-splice token on this peer
                self.m["rt_llm_migration_splice_s"].observe(
                    max(now - st.t_restore, 0.0), tags=self.tags
                )
            if st.trace is not None:
                self._span(st, "llm.first_token", st.t_admit or st.t_submit, now)
        else:
            gap = now - st.t_last
            st.itls.append(gap)
            self._b_itl.observe(max(gap, 0.0))
            # the live EMA takes ONE sample a step, in on_step: the lanes of a step all wait out
            # the same step, and sixteen gaps of one stall are one observation, not sixteen
            self._gap_sum += max(gap, 0.0)
            self._gap_n += 1
        st.t_last = now
        self._tok_accum += 1.0  # flushed into the counter on sample ticks

    def on_finish(self, st, reason: str) -> None:
        now = time.time()
        if st.t_admit:
            dur = max(now - st.t_admit, 0.0)
            self.service_ema_s = dur if self.service_ema_s == 0.0 else 0.9 * self.service_ema_s + 0.1 * dur
        self.m["rt_llm_requests_finished_total"].inc(1.0, tags={**self.tags, "reason": reason.split(":")[0]})
        self.recorder.record_request({
            "request_id": st.request_id,
            "reason": reason,
            # the request path's boundary stamps, all time.time():
            # ingress (serving entry, before parse/encode/admission) ->
            # submit (engine queue) -> admit (slot bound, prefill
            # launched) -> first token (engine emit) -> first/last yield
            # (the stream's generator; stamped by on_stream once the
            # stream ends)
            "ingress_t": st.t_ingress,
            "first_yield_t": None,
            "last_yield_t": None,
            "submit_t": st.t_submit,
            "admit_t": st.t_admit,
            "first_token_t": st.t_first,
            "finish_t": now,
            "ttft_s": (st.t_first - st.t_submit) if st.t_first else None,
            "queue_wait_s": getattr(st, "queue_wait", None),
            # inside ingress -> submit: the wait for the engine's lock alone
            "lock_wait_s": st.lock_wait,
            "itl_s": list(st.itls),
            "tokens": len(st.token_ids),
            "prompt_tokens": len(st.prompt_token_ids),
            "preemptions": st.preemptions,
            "trace_id": st.trace[0] if st.trace else None,
            "span_id": st.trace[1] if st.trace else None,
        })
        if st.trace is not None:
            if st.t_ingress:
                self._span(st, "llm.ingress", st.t_ingress, st.t_submit)
            if st.t_first:
                self._span(st, "llm.decode", st.t_first, now)
            # the root span: the whole request, recorded last so child
            # spans exist when a viewer walks the tree
            trace_id, span_id, parent_id = st.trace
            tracing.record_span(
                "llm.request", "server", trace_id, span_id, parent_id,
                int(st.t_submit * 1e9), int(now * 1e9),
                {"request_id": st.request_id, "reason": reason,
                 "tokens": len(st.token_ids), "stage": self.tags["stage"]},
            )

    def on_stream(self, request_id: str, first_yield_t: float, last_yield_t: float) -> dict | None:
        """The serving stream of a finished request ended: when its
        generator yielded its first and last token chunk. Called from the
        replica's request thread AFTER on_finish (the stream outlives the
        engine's last token), so the stamps are added to the recorded
        request, and the ``llm.stream`` span is built from that record.
        -> the record, or None where the request is not (or no longer) in the log."""
        rec = self.recorder.stamp_request(request_id, first_yield_t=first_yield_t, last_yield_t=last_yield_t)
        if rec is not None and rec["trace_id"] is not None and first_yield_t:
            tracing.record_span(
                "llm.stream", "internal", rec["trace_id"], uuid.uuid4().hex[:16], rec["span_id"],
                int(first_yield_t * 1e9), int(last_yield_t * 1e9),
                {"request_id": request_id, "stage": self.tags["stage"]},
            )
        return rec

    def on_prefix_hit(self, tier: str, tokens: int, nbytes: int = 0) -> None:
        """A prompt admission reused a cached prefix. ``tier``: "local"
        (this replica's PrefixCache) or "remote" (fetched over the
        cluster KV plane — ``nbytes`` then counts the object-plane
        transfer). Admission-path only: never on the per-step budget."""
        self._b_pfx_hits[tier].inc(1.0)
        self._b_pfx_tokens[tier].inc(float(tokens))
        if nbytes:
            self._b_pfx_bytes.inc(float(nbytes))

    def on_prefetch_hit(self) -> None:
        """A local-tier admission hit was served by a block the
        predictive prefetcher pulled in ahead of demand — the
        remote->local conversion the prefetch A/B bench measures.
        Rides alongside the tier="local" on_prefix_hit for the same
        admission."""
        self._b_pfx_prefetch.inc(1.0)

    def on_kv_spill(self, nbytes: int) -> None:
        """suspend_request spilled a conversation's KV out of HBM
        (tiered conversation KV). Once per suspension, never per step."""
        self._b_spill.inc(float(nbytes))

    def on_prefix_fetch(self, t0: float, t1: float, tokens: int, hit: bool) -> None:
        """An async remote prefix fetch span closed. Called from the
        engine's FETCH WORKER thread — the one entry point not under the
        engine lock; the instruments and the recorder ring carry their
        own thread-safety. The recorded [t0, t1] span is the overlap
        evidence: tests/bench cross-check it against concurrent step
        records."""
        self._b_fetch_overlap.observe(max(t1 - t0, 0.0))
        self.recorder.record_fetch(
            {"t0": float(t0), "t1": float(t1), "tokens": int(tokens), "hit": bool(hit)}
        )

    def on_handoff_extract(self, st, payload: dict, t_start: float) -> None:
        """Prefill side: the KV block left the cache into a handoff stash.
        Plants the trace context + original submit stamp in the payload so
        the decode replica's telemetry continues the same request."""
        # same accounting as handoff.meta_of (k + v + logits + scales):
        # the prefill-stage and router-stage series must agree byte for
        # byte so extracted-vs-published comparisons can detect drops
        nbytes = int(payload["k"].nbytes + payload["v"].nbytes + payload["logits"].nbytes)
        if payload.get("k_scale") is not None:
            nbytes += int(payload["k_scale"].nbytes + payload["v_scale"].nbytes)
        self.m["rt_llm_handoff_bytes_total"].inc(float(nbytes), tags=self.tags)
        self.m["rt_llm_handoffs_total"].inc(1.0, tags={**self.tags, "event": "extracted"})
        payload["submitted_at"] = st.t_submit
        if st.trace is not None:
            payload["trace"] = {"trace_id": st.trace[0], "parent_id": st.trace[1]}
            self._span(st, "llm.handoff", t_start, time.time(), nbytes=nbytes)

    def on_scatter_in(self, st, t_start: float) -> None:
        """Decode side: a transferred KV block scattered into the live
        cache/pool."""
        self.m["rt_llm_handoffs_total"].inc(1.0, tags={**self.tags, "event": "scattered"})
        if st.trace is not None:
            self._span(st, "llm.handoff.scatter_in", t_start, time.time())

    def on_migration(self, outcome: str, nbytes: int = 0) -> None:
        """Live-migration event (llm/migrate.py): checkpoint extracted
        here, checkpoint restored here, or the abort fallback. Cold
        path — once per evacuated request, never per step."""
        self.m["rt_llm_migrations_total"].inc(1.0, tags={**self.tags, "outcome": str(outcome)})
        if nbytes:
            self.m["rt_llm_migration_bytes_total"].inc(float(nbytes), tags=self.tags)

    def _span(self, st, name: str, t0: float, t1: float, **attrs) -> None:
        trace_id, root_id, _ = st.trace
        tracing.record_span(
            name, "internal", trace_id, uuid.uuid4().hex[:16], root_id,
            int(t0 * 1e9), int(t1 * 1e9),
            {"request_id": st.request_id, "stage": self.tags["stage"], **attrs},
        )

    # -- per-step ----------------------------------------------------------
    def begin_step(self):
        """Head of engine.step(): stamp the step's start, -> the
        ``llm.step`` annotation (parent of the stage spans, carrying the
        step's number) for the step to run under."""
        self._step_t0 = time.time()
        self._step_cpu0 = time.thread_time()
        self.at = ("llm.step", time.perf_counter())
        return TraceAnnotation("llm.step", step=self.recorder.step_count + 1)

    def on_step(self, t0: float, n_admitted: int, n_emitted: int, spec_drained: tuple | None) -> None:
        """Called at the tail of engine.step() under the engine lock.
        Everything read here is host shadow state."""
        eng = self.engine
        now = time.time()
        wall_ms = (time.perf_counter() - t0) * 1e3
        cpu_ms = (time.thread_time() - self._step_cpu0) * 1e3
        self.at = None
        stages, dispatch_t, prefill_t = self._stage_ms, self.dispatch_t, self.prefill_dispatch_t
        dispatch_t0, prefill_t0 = self.dispatch_t0, self.prefill_dispatch_t0
        self._stage_ms, self.dispatch_t, self.prefill_dispatch_t = [0.0] * len(STAGES), None, None
        self.dispatch_t0 = self.prefill_dispatch_t0 = None
        captures, tick_late = self.sentinel.take_step() if self.sentinel is not None else (None, None)
        slots_in_use = sum(1 for s in eng._slots if s is not None)
        sampling_lanes = sum(1 for s in eng._slots if s is not None and s.params.temperature > 0.0)
        waiting = len(eng._waiting)
        phase = (
            "idle" if not n_admitted and not slots_in_use and not n_emitted
            else "mixed" if n_admitted and (slots_in_use or n_emitted)
            else "prefill" if n_admitted
            else "decode"
        )
        if eng.kv_layout == "paged":
            occupied = int(eng._lengths.sum())
        else:
            occupied = sum(
                len(s.prompt_token_ids) + len(s.token_ids) for s in eng._slots if s is not None
            )
        capacity = self._capacity_tokens
        per_tok = self._bytes_per_token
        self._nstep += 1
        # first step always samples; a drained engine (no bound slots)
        # samples too, so the token/wire accumulators flush when traffic
        # stops instead of waiting for a tick that never comes
        sample = self._nstep % self.SAMPLE_EVERY == 1 or slots_in_use == 0
        recompiled = self.recorder.check_recompiles() if sample else []
        if recompiled:
            self._b_recompiles.inc(float(len(recompiled)))
        preempt_delta = eng.preemption_count - self._last_preemptions
        if preempt_delta > 0:
            self._b_preempt.inc(float(preempt_delta))
        self._last_preemptions = eng.preemption_count

        paged = eng.kv_layout == "paged"
        sd = spec_drained or (None, None)
        moe = eng._moe_stats if getattr(eng.config, "routing_layers", 0) else None  # host array of the drained step (a hybrid that routes), else None
        moe = _NO_MOE if moe is None else tuple(round(float(v), 3) for v in moe)
        pf, eng._prefill_stats = eng._prefill_stats, None
        if pf is None:
            moe += _NO_PREFILL
        else:
            tokens, padded, programs, (hit, pairs, rows, kernel), counted = pf
            moe += (tokens, padded, round(float(hit) / programs, 3), round(float(pairs), 3), round(float(rows), 3), round(float(kernel), 3),
                    *(counted.get(name) for name in PREFILL_COUNTERS))
        self.recorder.record_step((
            now, phase, round(wall_ms, 4), n_admitted, n_emitted, slots_in_use, sampling_lanes, waiting,
            occupied, capacity, *(eng._step_attn_blocks or (None, None)),
            *((eng._step_counted or {}).get(name) for name in DECODE_COUNTERS),
            eng._page_alloc.free_pages if paged else None,
            eng._pcfg.num_pages - 1 if paged else None,
            recompiled or None, sd[0], sd[1],
            self._step_t0, dispatch_t, prefill_t,
            *((eng._lanes_bound_device, eng._first_token_syncs) if n_admitted else (None, None)),
            *moe, *[round(ms, 4) for ms in stages],
            round((_GC_HELD[0] - self._gc_seen) * 1e3, 3) or None,
            dispatch_t0, prefill_t0, round(cpu_ms, 4), captures, tick_late,
        ))
        self._gc_seen = _GC_HELD[0]
        if self._gap_n:
            g = self._gap_sum / self._gap_n
            self.itl_ema_s = g if self.itl_ema_s == 0.0 else 0.9 * self.itl_ema_s + 0.1 * g
            self._gap_sum, self._gap_n = 0.0, 0

        if slots_in_use and self._wire_bytes_per_step:
            # accumulate locally (one float add), flush on sample ticks
            self._wire_accum += self._wire_bytes_per_step
        if not sample:
            return
        if self._tok_accum:
            self._b_tokens.inc(self._tok_accum)
            self._tok_accum = 0.0
        self._b_qdepth.set(float(waiting))
        self._b_slots.set(float(slots_in_use))
        self._b_occ.set(occupied / max(capacity, 1))
        self._b_hbm.set(float(occupied * per_tok))
        if eng._spec_cfg is not None:
            prop = eng._spec_proposed
            if prop:
                self._b_spec.set(eng._spec_accepted / prop)
        if self._wire_accum:
            self._b_wire.inc(self._wire_accum)
            self._wire_accum = 0.0
        if self.sample_hook is not None:
            try:
                self.sample_hook(waiting)
            except Exception:  # noqa: BLE001 — observers never break the step
                pass

    # -- the flight log ----------------------------------------------------
    def write_flight_log(self, error: BaseException | None = None) -> str | None:
        """Persist the flight log as JSONL under the session dir
        (``llm_flight/``), ONCE per engine life: when the replica stops
        (LLMServer.shutdown, off the stepper thread) or, with ``error``,
        when the engine died mid-step (the serve stepper surfaces the
        SAME exception to every waiter). Returns the path, or None if
        already written or if writing itself failed (a dying engine must
        still raise its real error)."""
        if self._dumped:
            return None
        self._dumped = True
        try:
            from ray_tpu.util.state import session_dir

            d = os.path.join(session_dir(), "llm_flight")
            path = os.path.join(d, f"flight-{os.getpid()}-{time.time_ns()}.jsonl")
            eng = self.engine
            header = {
                "tags": self.tags,
                "kv_layout": eng.kv_layout,
                "kv_dtype": str(eng.kv_dtype),
                "max_num_seqs": eng.max_num_seqs,
                # what the cache holds for a position, and of which entries it is made
                "kv_bytes_per_token": eng.kv_bytes_per_token(),
                "kv_entries": eng.kv_entries(),
                # what the names in a trace of this replica mean: named scope -> role
                "scopes": dict(SCOPES),
            }
            if error is not None:
                header["error"] = f"{type(error).__name__}: {error}"
            return self.recorder.dump_jsonl(path, header=header)
        except Exception:
            logger.warning("flight log not written", exc_info=True)
            return None

    def dump_on_error(self, exc: BaseException) -> str | None:
        """Engine died mid-step: the postmortem is the flight log."""
        return self.write_flight_log(error=exc)

    def snapshot(self) -> dict:
        snap = self.recorder.snapshot()
        snap["tags"] = dict(self.tags)
        snap["wire_bytes_per_step"] = self._wire_bytes_per_step or 0.0
        return snap


# ----------------------------------------------------------------------
# the stall sentinel
# ----------------------------------------------------------------------
def _proc_text(path: str) -> str:
    with open(path) as f:
        return f.read()


def _task_stat(tid: str) -> list:
    """[tid, comm, state, user + system CPU ticks] of one native thread (``/proc/self/task/<tid>/stat``;
    the comm stands in brackets and may hold any character, so the fields are counted from its end)."""
    text = _proc_text(f"/proc/self/task/{tid}/stat")
    comm = text[text.index("(") + 1:text.rindex(")")]
    rest = text[text.rindex(")") + 2:].split()  # rest[0] is the state, field 3; utime and stime are fields 14 and 15
    return [int(tid), comm, rest[0], int(rest[11]) + int(rest[12])]


def _python_threads(deep: int | None = None) -> list:
    """Every Python thread: its name, native id, and innermost three frames (innermost first); twelve of the
    thread whose ident is ``deep``: the stepper's, where three end inside jax and do not reach the engine's line."""
    names = {t.ident: (t.name, t.native_id) for t in threading.enumerate()}
    out = []
    for ident, frame in sys._current_frames().items():
        frames, depth = [], 12 if ident == deep else 3
        while frame is not None and len(frames) < depth:
            frames.append(f"{os.path.basename(frame.f_code.co_filename)}:{frame.f_lineno} {frame.f_code.co_name}")
            frame = frame.f_back
        name, tid = names.get(ident, (str(ident), None))
        out.append({"name": name, "tid": tid, "frames": frames})
    return out


def _native_threads() -> list:
    """Every native thread of the process that has ever run: the runtime's transfer and compile threads show
    here and nowhere in Python. Two captures of one stall apart say which of them burned CPU between."""
    out = []
    for tid in os.listdir("/proc/self/task"):
        try:
            row = _task_stat(tid)
        except (OSError, ValueError, IndexError):  # a thread that ended between the listing and the read
            continue
        if row[3]:
            out.append(row)
    return out


def _process_counters() -> dict:
    """The process's context switches (given up, taken away) and major faults."""
    import resource

    ru = resource.getrusage(resource.RUSAGE_SELF)
    return {"voluntary": ru.ru_nvcsw, "involuntary": ru.ru_nivcsw, "major_faults": ru.ru_majflt}


def _thread_sched(tid: int) -> dict:
    """One native thread's own switches, and the kernel's account of it: ns on a processor and ns runnable
    and waiting for one (``schedstat``)."""
    out = {}
    for line in _proc_text(f"/proc/self/task/{tid}/status").splitlines():
        key, _, value = line.partition(":")
        if key in ("voluntary_ctxt_switches", "nonvoluntary_ctxt_switches"):
            out["voluntary" if key[0] == "v" else "involuntary"] = int(value)
    run_ns, wait_ns, _ = _proc_text(f"/proc/self/task/{tid}/schedstat").split()
    return {**out, "run_ns": int(run_ns), "runnable_wait_ns": int(wait_ns)}


def _pressure() -> dict:
    """{"cpu" / "memory" / "io": {"some" / "full": [avg10, total us]}} where the kernel keeps them."""
    out = {}
    for kind in ("cpu", "memory", "io"):
        try:
            lines = _proc_text(f"/proc/pressure/{kind}").splitlines()
        except OSError:
            continue
        out[kind] = {}
        for line in lines:
            share, *pairs = line.split()
            fields = dict(p.split("=") for p in pairs)
            out[kind][share] = [float(fields["avg10"]), int(fields["total"])]
    return out


_MEMORY_KEYS = ("bytes_in_use", "peak_bytes_in_use", "largest_free_block_bytes", "num_allocs", "bytes_limit")


def _device_memory() -> list:
    """Each local device's allocator figures, where the backend gives any (the CPU's gives none)."""
    out = []
    for dev in jax.local_devices():
        stats = dev.memory_stats()
        if stats:
            out.append({"device": dev.id, **{k: int(stats[k]) for k in _MEMORY_KEYS if k in stats}})
    return out


class StallSentinel:
    """A thread beside the stepper (``llm-sentinel``) that says why a step stands still. It sleeps ``TICK_S``
    at a time and notes how late each wake-up was against the sleep it asked for (a process that was frozen,
    or an interpreter lock that nobody gave up, makes it late as well). Where the stage the stepper published
    (``EngineTelemetry.at``) is older than ``FIRST_S`` it takes a CAPTURE into the flight recorder's ``stalls``
    ring, and again each time that age doubles (0.25, 0.5, 1, 2, 4, 8 s ...):

    - ``t`` (time.time()), ``step`` (the step's number), ``stage`` and ``age_s``;
    - ``ready``: ``is_ready()`` of every array the stepper said it was about to block on (``blocked_on``; no
      transfer, no block). False: the device or the runtime has not produced the result. True: the host thread
      is late picking it up. Absent where the stage is not a blocking read;
    - ``tick_late_ms``: the sentinel's own lateness at its last wakes;
    - ``threads``: every Python thread's name and innermost three frames (the stepper's twelve); ``native``: every native thread's
      [tid, comm, state, CPU ticks]; ``stepper``: the stepping thread's own switches and scheduler account;
    - ``process`` (switches, major faults), ``loadavg``, ``pressure``, ``memory`` (each device's allocator).

    A source that a platform lacks is left out of the capture, never an error. At the capture at ``WARN_S`` of a
    blocking read it logs ONE warning line: what an operator of a replica would have. A long prompt's ordinary
    wait is captured like a stall (some milliseconds on a thread that is not the stepper); the reader tells them
    apart (``benchmark/metrics/stall_excess_ms.py``), the sentinel does not try to. Started by ``LLMServer``
    beside its stepper; a bare ``LLMEngine`` has none."""

    TICK_S = 0.05
    FIRST_S = 0.25
    WARN_S = 2.0
    IDLE = "llm.stepper.wait"  # the stepper asleep until a request arrives: not a stall

    def __init__(self, tel: EngineTelemetry, stepper: threading.Thread | None = None):
        self.tel = tel
        self.stepper = stepper
        self.captures = 0  # taken since the last step's row (take_step)
        self.late_ms = 0.0  # worst lateness since then
        self._lates: deque = deque(maxlen=8)
        self._next: dict[tuple, float] = {}  # published stage -> the age at which its next capture is due
        self._step = -1  # the recorder's count as the thresholds were last cleared
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True, name="llm-sentinel")

    def start(self) -> "StallSentinel":
        self.tel.sentinel = self
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread.is_alive() and self._thread is not threading.current_thread():
            self._thread.join(timeout=5.0)

    def is_alive(self) -> bool:
        return self._thread.is_alive()

    def take_step(self) -> tuple:
        """on_step's: (captures during the step or None, worst lateness in ms or None under one), then zeroed.
        Two threads and no lock: a count that lands between the read and the store goes to no row."""
        captures, late = self.captures, self.late_ms
        self.captures, self.late_ms = 0, 0.0
        return captures or None, round(late, 3) if late >= 1.0 else None

    def _run(self) -> None:
        while True:
            asked = time.perf_counter()
            if self._stop.wait(self.TICK_S):
                return
            late = (time.perf_counter() - asked - self.TICK_S) * 1e3
            self._lates.append(round(late, 3))
            self.late_ms = max(self.late_ms, late)
            try:
                self.look()
            except Exception:  # noqa: BLE001 — an observer never takes its replica down
                logger.warning("stall sentinel: capture failed", exc_info=True)

    def look(self, now: float | None = None) -> dict | None:
        """One look at the published stage (at ``now``, perf_counter's): -> the capture taken, or None where none was due."""
        at, step = self.tel.at, self.tel.recorder.step_count
        if at is None or step != self._step:  # the thresholds are those of the step under way: the last one's stages are gone
            self._next.clear()
            self._step = step
        if at is None:
            return None
        name, since = at
        age = (time.perf_counter() if now is None else now) - since
        due = self._next.get(at, self.FIRST_S)
        if name == self.IDLE or age < due:
            return None
        warn = due <= self.WARN_S <= age  # the first capture of this stage at WARN_S or later
        while due <= age:
            due *= 2.0
        self._next[at] = due
        rec = self.capture(name, age)
        self.captures += 1
        self.tel.recorder.record_stall(rec)
        if warn and "ready" in rec:
            memory = "".join(f"; device {m['device']} holds {m.get('bytes_in_use')} of {m.get('bytes_limit')} bytes" for m in rec.get("memory", ()))
            logger.warning(
                "step %d has stood in %s for %.1f s: result %s; the sentinel's last wakes were late by %.0f ms at most%s; "
                "the captures are in the flight log's stalls section", rec["step"], name, age,
                "ready (the host is late picking it up)" if all(rec["ready"]) else "not ready (the device or the runtime is late)",
                max(self._lates, default=0.0), memory)
        return rec

    def capture(self, stage: str, age_s: float) -> dict:
        rec = {"t": time.time(), "step": self.tel.recorder.step_count + 1, "stage": stage, "age_s": round(age_s, 4),
               "tick_late_ms": list(self._lates)}
        waited = self.tel.blocked_on
        if waited is not None:
            rec["ready"] = [bool(a.is_ready()) for a in jax.tree_util.tree_leaves(waited) if hasattr(a, "is_ready")]
        sources = {"threads": lambda: _python_threads(self.stepper and self.stepper.ident), "native": _native_threads, "process": _process_counters,
                   "loadavg": lambda: list(os.getloadavg()), "pressure": _pressure, "memory": _device_memory}
        if self.stepper is not None and self.stepper.native_id is not None:
            sources["stepper"] = lambda: _thread_sched(self.stepper.native_id)
        for key, read in sources.items():
            try:
                value = read()
            except Exception:  # noqa: BLE001 — a platform without /proc, a backend without memory_stats: left out, never an error
                logger.debug("stall sentinel: no %s here", key, exc_info=True)
                continue
            if value:
                rec[key] = value
        return rec


def load_flight(pid: int | None = None) -> dict:
    """Merge every flight log of the session (each replica process
    writes its own under the shared session dir, like the span files
    ``tracing.load_spans`` merges): -> {"headers", "steps", "requests", "stalls"},
    every step, request and capture carrying the ``pid`` that wrote it. A torn
    last line (a process killed while writing) is skipped."""
    from ray_tpu.util.state import session_dir

    d = os.path.join(session_dir(pid), "llm_flight")
    out: dict = {"headers": [], "steps": [], "requests": [], "stalls": []}
    try:
        names = sorted(os.listdir(d))
    except OSError:
        return out
    for n in names:
        writer = None
        try:
            with open(os.path.join(d, n)) as f:
                for line in f:
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    kind = rec.pop("kind", None)
                    if kind == "flight_header":
                        writer = rec.get("pid")
                        out["headers"].append(rec)
                    elif kind in ("step", "request", "stall"):
                        rec["pid"] = writer
                        out[kind + "s"].append(rec)
        except OSError:
            continue
    return out


def dispatch_stamps(steps: list) -> dict:
    """{"fused": [...], "prefill": [...]}: the host stamps (time.time()) of every fused step and
    of every prefill program the rows say were dispatched, in order: what a trace's executions of
    the programs with that word in their name are set against (``util/profiling.summarize``)."""
    steps = sorted(steps, key=lambda s: s["t0"])
    return {"fused": [s["dispatch_t"] for s in steps if s.get("dispatch_t")],
            "prefill": [g[0] for s in steps for g in s.get("prefill_dispatch_t") or ()]}


def dispatch_stamps_before(steps: list) -> dict | None:
    """The same dispatches by the stamps taken BEFORE each call (``dispatch_t0``, ``prefill_dispatch_t0``), one
    for one with ``dispatch_stamps``; None for a log that has a dispatch without one (written before PR 55)."""
    steps = sorted(steps, key=lambda s: s["t0"])
    if any((s.get("dispatch_t") and not s.get("dispatch_t0"))
           or len(s.get("prefill_dispatch_t") or ()) != len(s.get("prefill_dispatch_t0") or ()) for s in steps):
        return None
    return {"fused": [s["dispatch_t0"] for s in steps if s.get("dispatch_t")],
            "prefill": [t for s in steps for t in s.get("prefill_dispatch_t0") or ()]}


def drain_stamps(steps: list) -> list:
    """For every fused step ``dispatch_stamps`` lists, in its order: the host's time (time.time()) at
    which that step's tokens were on the host: the end of the NEXT row's ``drain_wait`` (a row's
    stages end at ``t``); None for the last row's. An execution has ended by then, which bounds the
    device's clock from the side its start after the dispatch does not (``util/profiling._align``)."""
    steps = sorted(steps, key=lambda s: s["t0"])
    after = [STAGES[name] for name in TILED[TILED.index("llm.step.drain_wait") + 1:]]
    return [nxt and nxt["t"] - sum(float(nxt.get(col) or 0.0) for col in after) * 1e-3
            for s, nxt in zip(steps, steps[1:] + [None]) if s.get("dispatch_t")]


IN_STEP = "in step, no stage"  # the engine's lock, on_step's own time


def timeline(steps: list) -> list[tuple]:
    """Where the host was, from one replica's step rows: (label, start, end) on time.time()'s
    clock, in order and without overlap, from the first row's start to the last one's end. A row
    holds its start (``t0``), the stamp at its end (``t``: on_step's first act) and its stages'
    durations; the stages run in STAGES' order and end at ``t``, so each one's edges follow by
    walking back from there, and what is left before the first is the wait for the engine's
    lock. Inside ``prefill``, a group's launch lies where its stamps (``prefill_dispatch_t``) say,
    the state inserts (whose sum alone is known) at the launches' ends, and a group's first-token
    read where its third stamp falls inside the stage (under speculation; otherwise the wave's one
    read is the stage of that name, after ``emit``); what is neither is ``prefill``. Between two
    steps the stepper's wait ends at the next step's start and its delivery stands before that. Labels: STAGES' names less their prefix."""
    def label(name):
        return name.split(".", 2)[2] if name.startswith("llm.step.") else name[len("llm."):]

    out: list[tuple] = []

    def put(what, a, b):
        if b > a:
            out.append((what, a, b))

    last_end = None
    for s in sorted(steps, key=lambda s: s["t0"]):
        t0, t = s["t0"], max(s["t"], s["t0"])
        ms = lambda name: float(s.get(STAGES[name]) or 0.0) * 1e-3  # noqa: E731
        if last_end is not None and t0 > last_end:
            wait = min(ms("llm.stepper.wait"), t0 - last_end)
            deliver = min(ms("llm.stepper.deliver"), t0 - wait - last_end)
            put(IN_STEP, last_end, t0 - wait - deliver)
            put("stepper.deliver", t0 - wait - deliver, t0 - wait)
            put("stepper.wait", t0 - wait, t0)
        t0 = t0 if last_end is None else max(t0, last_end)
        edges, at = {}, t
        for name in reversed(TILED):
            edges[name] = (max(at - ms(name), t0), at)
            at = edges[name][0]
        put(IN_STEP, t0, at)
        for name, (a, b) in sorted(edges.items(), key=lambda kv: kv[1][0]):
            groups = s.get("prefill_dispatch_t") if name == "llm.step.prefill" else None
            if not groups:
                put(label(name), a, b)
                continue
            # launch g = [start, launched], and the next group's launch starts there, or where g's first
            # tokens were read if that was inside this stage
            ends = [read if launched <= read <= b else launched for _, launched, read in groups]
            later = sum(g[1] - end for end, g in zip(ends, groups[1:]))
            at = min(max(groups[0][1] - max(ms("llm.step.prefill.launch") - later, 0.0), a), b)
            put("prefill", a, at)
            insert = ms("llm.step.state_insert") / len(groups)
            for (_, launched, _), end in zip(groups, ends):
                launched, end = min(max(launched, at), b), min(max(end, at), b)
                cut = max(launched - insert, at)
                put("prefill.launch", at, cut)
                put("state_insert", cut, launched)
                put("prefill.first_tokens", launched, end)
                at = end
            put("prefill", at, b)
        last_end = t
    return out


# ----------------------------------------------------------------------
# router-facing metrics (control plane: no engine, no recorder)
# ----------------------------------------------------------------------
class RouterTelemetry:
    """Counters for the disagg router's control-plane events, sharing the
    serving catalog so one scrape covers the whole split."""

    def __init__(self, tags: dict | None = None):
        base = default_tags("router")
        base.update(tags or {})
        self.tags = {k: str(v) for k, v in base.items() if k in _SERVE_TAGS}
        self.m = instruments()

    def on_published(self, nbytes: int) -> None:
        self.m["rt_llm_handoff_bytes_total"].inc(float(nbytes), tags=self.tags)
        self.m["rt_llm_handoffs_total"].inc(1.0, tags={**self.tags, "event": "published"})

    def on_lost(self) -> None:
        self.m["rt_llm_handoffs_total"].inc(1.0, tags={**self.tags, "event": "lost"})

    def on_reused(self) -> None:
        self.m["rt_llm_handoffs_total"].inc(1.0, tags={**self.tags, "event": "reused"})

    def on_failed(self) -> None:
        self.m["rt_llm_requests_finished_total"].inc(1.0, tags={**self.tags, "reason": "error"})

    def on_budget_exhausted(self) -> None:
        """A request's shared failover budget (serve/overload.RetryBudget)
        ran dry — the typed terminal error is about to surface."""
        self.m["rt_llm_retry_budget_exhausted_total"].inc(1.0, tags=self.tags)

    def on_migration(self, outcome: str) -> None:
        """Router-stage migration event: "resumed" (a dying replica's
        checkpoint spliced on a peer, zero recomputed tokens) or "lost"
        (checkpoint gone before the fetch — degraded to re-prefill)."""
        self.m["rt_llm_migrations_total"].inc(1.0, tags={**self.tags, "outcome": str(outcome)})

    def on_shed(self, shed_class: int) -> None:
        """The router itself shed a request (every ranked replica was
        overloaded/draining). Same series as the replica-level sheds but
        under this router's ``stage`` tag: one CLIENT request that shed
        at several replicas during failover counts once per replica plus
        once here — separate by stage when summing request-level rates
        (the Grafana panel does). Label clamped like the replicas'."""
        self.m["rt_llm_requests_shed_total"].inc(
            1.0, tags={**self.tags, "class": str(max(0, min(int(shed_class), 9)))}
        )
