"""State API: programmatic cluster introspection + session state dumps.

Reference parity: python/ray/util/state (list_tasks/list_actors/
list_nodes/list_objects/list_placement_groups, summarize_*) backed by the
head's live registries instead of a state-API server. For out-of-process
inspection (the CLI), the head periodically dumps a JSON snapshot under
the session dir (/tmp/ray_tpu/session_<pid>/state.json) — scripts/cli.py
reads the freshest session.
"""

from __future__ import annotations

import collections
import json
import os
import time


def _client():
    from ray_tpu.core import context

    return context.get_client()


def list_nodes() -> list[dict]:
    return _client().cluster_info("nodes")


def list_actors() -> list[dict]:
    return _client().cluster_info("actors")


def list_tasks() -> list[dict]:
    return _client().cluster_info("tasks")


def list_objects() -> dict:
    return _client().cluster_info("objects")


def list_placement_groups() -> list[dict]:
    return _client().cluster_info("placement_groups")


def summarize_tasks() -> dict:
    """Counts by (name, state) — reference: `ray summary tasks`."""
    by_state: dict = collections.defaultdict(lambda: collections.defaultdict(int))
    for t in list_tasks():
        by_state[t.get("name", "?")][t.get("state", "?")] += 1
    return {name: dict(states) for name, states in by_state.items()}


def summarize_actors() -> dict:
    by_state: dict = collections.defaultdict(int)
    for a in list_actors():
        by_state[a.get("state", "?")] += 1
    return dict(by_state)


def cluster_status(client=None) -> dict:
    """`ray status`-shaped summary."""
    c = client or _client()
    actors = collections.defaultdict(int)
    for a in c.cluster_info("actors"):
        actors[a.get("state", "?")] += 1
    return {
        "nodes": c.cluster_info("nodes"),
        "cluster_resources": c.cluster_info("cluster_resources"),
        "available_resources": c.cluster_info("available_resources"),
        "pending_demand": c.scheduler.pending_demand() if hasattr(c, "scheduler") else [],
        "actors": dict(actors),
        # lifetime totals (never pruned) — throughput must derive from
        # these, not from the windowed task-record list
        "task_counts": c.task_manager.lifetime_counts() if hasattr(c, "task_manager") else {},
    }


# ----------------------------------------------------------------------
# session state dump (for the out-of-process CLI)
# ----------------------------------------------------------------------
def session_dir(pid: int | None = None) -> str:
    pid = pid or int(os.environ.get("RT_SESSION_PID", os.getpid()))
    return os.path.join("/tmp", "ray_tpu", f"session_{pid}")


def dump_state(client=None) -> str:
    """Write the current snapshot; returns the path."""
    c = client or _client()
    d = session_dir()
    os.makedirs(d, exist_ok=True)
    path = os.path.join(d, "state.json")
    tasks: dict = collections.defaultdict(lambda: collections.defaultdict(int))
    for t in c.cluster_info("tasks"):
        tasks[t.get("name", "?")][t.get("state", "?")] += 1
    snap = {
        "ts": time.time(),
        "pid": os.getpid(),
        "status": cluster_status(c),
        "tasks": {k: dict(v) for k, v in tasks.items()},
        "actors_list": c.cluster_info("actors"),
        "placement_groups": c.cluster_info("placement_groups"),
        "objects": c.cluster_info("objects"),
    }
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(snap, f, default=str)
    os.replace(tmp, path)
    return path


def dump_cluster_info(client) -> str:
    """Write the join credentials (agent listener address + authkeys) for
    out-of-process `rt agent` joins. 0600: the authkeys gate cluster entry
    (reference: redis password in `ray start --address`)."""
    d = session_dir()
    os.makedirs(d, exist_ok=True)
    path = os.path.join(d, "cluster_info.json")
    info = {
        "ts": time.time(),
        "pid": os.getpid(),
        "agent_address": list(client._agent_listener.address),
        "authkey": client._agent_listener.authkey.hex(),
        "transfer_authkey": client._transfer_authkey.hex(),
    }
    tmp = path + ".tmp"
    # 0600 from birth: the file holds cluster-entry authkeys
    fd = os.open(tmp, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o600)
    with os.fdopen(fd, "w") as f:
        json.dump(info, f)
    os.replace(tmp, path)
    return path


def _newest(name: str, a_head_finds_itself: bool) -> str | None:
    """The path of ``name`` in the session this process belongs to, else the newest across the
    machine's sessions. A head exports ``RT_SESSION_PID`` to itself and to everything it starts,
    so a process that carries it is told which session is its own; "the newest" is then a guess
    it does not need, and on a machine with several heads (six pytest workers) often another
    head's, or a file a head left behind. ``a_head_finds_itself``: whether a process that IS the
    head of its session (it carries its own pid) means that session too: yes for its own state
    dump, no for an address to attach to."""
    root = os.path.join("/tmp", "ray_tpu")
    try:
        sessions = os.listdir(root)
    except FileNotFoundError:
        return None
    own = os.environ.get("RT_SESSION_PID", "")
    if own and (a_head_finds_itself or own != str(os.getpid())) and os.path.exists(os.path.join(root, f"session_{own}", name)):
        sessions = [f"session_{own}"]
    best, best_ts = None, -1.0
    for s in sessions:
        p = os.path.join(root, s, name)
        try:
            ts = os.path.getmtime(p)
        except OSError:
            continue
        if ts > best_ts:
            best, best_ts = p, ts
    return best


def load_latest_cluster_info() -> dict | None:
    """The join credentials of this process's session, else of the newest live one (for `rt agent`
    and ``init(address="auto")``)."""
    best = _newest("cluster_info.json", a_head_finds_itself=False)
    if best is None:
        return None
    with open(best) as f:
        info = json.load(f)
    try:
        os.kill(info["pid"], 0)
    except (ProcessLookupError, PermissionError):
        return None  # head is gone
    return info


def load_latest_state() -> dict | None:
    """The state dump of this process's session, else the newest across sessions (CLI entry)."""
    best = _newest("state.json", a_head_finds_itself=True)
    if best is None:
        return None
    with open(best) as f:
        return json.load(f)
