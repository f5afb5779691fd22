"""Runtime config flag registry.

TPU-native equivalent of the reference's ``RayConfig`` flag system
(reference: src/ray/common/ray_config_def.h — 232 RAY_CONFIG entries, each
overridable via a ``RAY_<name>`` env var, parsed in common/ray_config.h:60).

Every flag declared here is overridable via the ``RT_<NAME>`` environment
variable at import time, and via ``ray_tpu.init(_system_config={...})`` at
runtime.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field, fields
from typing import Any

_ENV_PREFIX = "RT_"


def _env_override(name: str, default: Any) -> Any:
    raw = os.environ.get(_ENV_PREFIX + name.upper())
    if raw is None:
        return default
    if isinstance(default, bool):
        return raw.lower() in ("1", "true", "yes", "on")
    if isinstance(default, int):
        return int(raw)
    if isinstance(default, float):
        return float(raw)
    return raw


@dataclass
class Config:
    """Global runtime configuration (one instance per process)."""

    # --- object store ---
    # Objects smaller than this are stored inline in the owner's in-process
    # memory store and piggybacked on RPC replies (reference:
    # max_direct_call_object_size, common/ray_config_def.h:198).
    max_direct_call_object_size: int = 100 * 1024
    # Per-node shared-memory object store capacity.
    object_store_memory: int = 2 * 1024 * 1024 * 1024
    # Fraction of the store above which LRU-evictable objects are released.
    object_store_eviction_threshold: float = 0.8
    # Spill cold sealed objects to disk under memory pressure instead of
    # evicting them (reference: local_object_manager.h:43); restore on read.
    object_spilling_enabled: bool = True
    # Directory for spill files; empty = <session_dir>/spill.
    object_spill_dir: str = ""
    # Disk budget for spilled bytes; past it, cold objects are evicted
    # (lineage reconstruction) instead of spilled.
    object_spill_max_bytes: int = 50 * 1024 * 1024 * 1024

    # --- transport / cross-node object plane ---
    # Bind host for the head's agent listener (TCP) and transfer servers.
    # 127.0.0.1 for single-host; 0.0.0.0 to accept cross-host `rt agent`
    # joins (reference: gRPC server bind, rpc/grpc_server.h).
    node_manager_host: str = "127.0.0.1"
    # Give every added node its own shm namespace so all object movement
    # crosses the transfer service, as it would between real hosts.
    shm_isolation: bool = False
    # Fixed agent-listener port (0 = ephemeral). A fixed port lets agents
    # reconnect to a RESTARTED head (GCS fault tolerance; reference:
    # gcs_server_port + raylet reconnect backoff).
    node_manager_port: int = 0
    # Seconds an agent keeps redialing the head after connection loss
    # (0 = die with the head; set alongside node_manager_port for head FT).
    agent_reconnect_s: float = 0.0

    # --- GCS persistence (reference: redis_store_client.h:126) ---
    # Path of the append-only GCS table log; empty = in-memory only.
    # With a path set, KV / job table / named+detached actors survive a
    # head kill -9 and are re-hydrated by the next head.
    gcs_persist_path: str = ""

    # --- scheduler ---
    # Pack onto busiest feasible node until its utilization crosses this
    # threshold, then spread (reference: scheduler_spread_threshold=0.5,
    # common/ray_config_def.h:178).
    scheduler_spread_threshold: float = 0.5
    # Max task retries on worker crash when not overridden per task.
    default_max_retries: int = 3

    # --- worker pool ---
    worker_start_method: str = "forkserver"
    prestart_workers: bool = True

    # --- health / failure detection ---
    # Reference: gcs_health_check_manager.h — period + failure threshold.
    health_check_period_s: float = 1.0
    health_check_failure_threshold: int = 5
    # Session state.json dump period for the out-of-process CLI
    # (scripts/cli.py); 0 disables.
    state_dump_interval_s: float = 2.0
    # Stream worker log files back to the driver tty (log_monitor.py).
    log_to_driver: bool = True
    # --- reference counting (reference: reference_counter.h) ---
    # Free store entries once no process holds a ref and no live task spec
    # pins them as an argument. RT_OBJECT_REF_COUNTING=0 disables.
    object_ref_counting: bool = True
    ref_counting_interval_s: float = 0.2
    # --- memory protection (reference: memory_monitor.h,
    # worker_killing_policy.h) ---
    memory_monitor_refresh_ms: int = 250  # 0 disables
    memory_usage_threshold: float = 0.95

    # --- data ---
    # Blocks observed above this size are split into ~this-sized chunks
    # between pipeline stages (reference: DataContext.target_max_block_size
    # + _internal/execution dynamic block splitting). 0 disables.
    target_max_block_size: int = 128 * 1024 * 1024

    # --- direct call plane (ownership model; core/direct.py) ---
    # Caller->worker direct actor calls, worker leases for stateless tasks
    # and owner-local small objects (reference: reference_counter.h
    # per-owner metadata + cluster_lease_manager.h lease scheduling).
    # RT_DIRECT_CALLS=0 routes everything through the head (round-3 mode).
    direct_calls: bool = True
    # Seconds an owned object lingers after its last reference drops
    # (absorbs the async borrow-registration race).
    owned_object_grace_s: float = 1.0
    # Entries whose ref was SERIALIZED OUT of this process but never saw a
    # registered borrow use this much longer window instead: the owner
    # waits for the explicit borrow-release, and the timer is only the
    # leak backstop for borrowers that died before registering (round-5
    # advisory: time-based grace premature-frees a live borrowed ref when
    # the ref pump stalls past the grace window).
    owned_object_leak_backstop_s: float = 30.0

    # --- collective / mesh ---
    collective_timeout_s: float = 120.0

    # --- lineage ---
    # Bounded lineage window: terminal task specs beyond this count are
    # pruned (their outputs become non-reconstructable, like the
    # reference's lineage eviction under max_lineage_bytes).
    max_lineage_tasks: int = 20_000

    # --- misc ---
    session_dir: str = "/tmp/ray_tpu"

    extra: dict = field(default_factory=dict)

    def __post_init__(self):
        for f in fields(self):
            if f.name == "extra":
                continue
            setattr(self, f.name, _env_override(f.name, getattr(self, f.name)))

    def update(self, overrides: dict | None):
        if not overrides:
            return
        known = {f.name for f in fields(self)}
        for k, v in overrides.items():
            if k in known:
                setattr(self, k, v)
            else:
                self.extra[k] = v


_config: Config | None = None


def get_config() -> Config:
    global _config
    if _config is None:
        _config = Config()
    return _config


def reset_config():
    global _config
    _config = None
