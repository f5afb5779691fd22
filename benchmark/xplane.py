"""From a profiler trace (``.xplane.pb``) to device busy time, per-program and per-operation
device time, and idle gaps. The reduction every PR's per-layer metrics go through.

``jax.profiler.ProfileData`` reads the file with nothing but JAX. A TPU trace has one plane per
chip (``/device:TPU:<n>``); its ``XLA Modules`` line has one event per execution of a compiled
program (``jit_<function>(<fingerprint>)``) and its ``XLA Ops`` line one event per operation
inside it (fusions, collectives, custom calls such as Pallas kernels). Busy time is the union
of the operation intervals; a gap is a stretch inside the window with no operation running.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULES_LINE, OPS_LINE = "XLA Modules", "XLA Ops"


def device_only_options():
    """Profiler options for the traced stretch: the device planes are all the reduction reads,
    and with the host and Python tracers on, stopping the profiler stalled the replica for tens
    of seconds (my chip runs, PR 23)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level, opts.python_tracer_level = 0, 0
    return opts


def find_xplane(trace_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def read_planes(path: str, plane_filter=None) -> list[dict]:
    """-> [{"name", "lines": [{"name", "events": [(name, start_ns, duration_ns), ...]}]}]; the events of
    the two lines the reduction reads, the other lines by name only (a TPU trace has millions of events)."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        if plane_filter is not None and not plane_filter(plane.name):
            continue
        lines = [{"name": line.name,
                  "events": [(ev.name, int(ev.start_ns), int(ev.duration_ns)) for ev in line.events]
                  if line.name in (MODULES_LINE, OPS_LINE) else []}
                 for line in plane.lines]
        planes.append({"name": plane.name, "lines": lines})
    return planes


def device_planes(planes: list[dict]) -> list[dict]:
    return sorted((p for p in planes if DEVICE_PLANE.match(p["name"])),
                  key=lambda p: int(DEVICE_PLANE.match(p["name"]).group(1)))


def _line(plane: dict, name: str) -> list[tuple]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def union_intervals(events: list[tuple]) -> list[tuple[int, int]]:
    """Merged, sorted [start, end) intervals of (name, start, duration) events."""
    out: list[list[int]] = []
    for _, s, d in sorted(events, key=lambda e: e[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], s + d)
        else:
            out.append([s, s + d])
    return [(a, b) for a, b in out]


def program_name(event_name: str) -> str:
    """``jit_fused_step(1234567)`` -> ``jit_fused_step``. A program jitted from a
    ``functools.partial`` has no name of its own (``jit__unknown``): those keep their
    fingerprint, which is all that tells one such program from another."""
    name = re.sub(r"\(\d+\)$", "", event_name)
    return event_name if "unknown" in name else name


def programs_holding(modules: list[tuple], ops: list[tuple], marker: str) -> set[str]:
    """Names of the programs inside whose executions an operation whose name contains
    ``marker`` ran (by time: an operation belongs to the execution that spans it)."""
    import bisect

    runs = sorted((s, s + d, program_name(n)) for n, s, d in modules)
    starts = [r[0] for r in runs]
    found = set()
    for n, s, _ in ops:
        if marker in n.split(" ", 1)[0]:
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < runs[i][1]:
                found.add(runs[i][2])
    return found


CONTAINERS = ("while", "conditional", "call")  # their events span the operations inside them


def op_short(event_name: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO line (``%fusion.12 = bf16[...] fusion(...)``):
    keep the instruction's name, ``fusion.12``."""
    return event_name.split(" ", 1)[0].lstrip("%")


def op_name(event_name: str) -> str:
    """``%fusion.123 = ...`` -> ``fusion``; ``%all-reduce.5 = ...`` -> ``all-reduce``."""
    head = op_short(event_name)
    return re.sub(r"[.\d]+$", "", head) or head


def totals(events: list[tuple], key) -> dict[str, list]:
    """name -> [calls, seconds], by ``key(event name)``."""
    out: dict[str, list] = {}
    for name, _, dur in events:
        row = out.setdefault(key(name), [0, 0.0])
        row[0] += 1
        row[1] += dur * 1e-9
    return out


def clip_planes(planes: list[dict], stretch_ns: int) -> list[dict]:
    """The device planes cut to the first ``stretch_ns`` after their first event: events that
    start later are dropped, one that straddles the cut is shortened."""
    devs = device_planes(planes)
    starts = [ev[1] for p in devs for ln in p["lines"] for ev in ln["events"]]
    if not starts:
        return devs
    cut = min(starts) + stretch_ns
    return [{"name": p["name"], "lines": [
        {"name": ln["name"], "events": [(n, s, min(d, cut - s)) for n, s, d in ln["events"] if s < cut]}
        for ln in p["lines"]]} for p in devs]


def reduce_planes(planes: list[dict]) -> dict:
    """The numbers the per-layer readers and the result's ``device`` block use.

    ``window_s`` runs from the first to the last device event over all chips; ``busy_s`` is the
    union of operation intervals inside it, averaged over the chips. ``programs`` and ``ops`` are
    per chip 0 (every chip of a tensor-parallel mesh runs the same programs), operations by their
    instruction name and by kind, loops and calls left out because their events span the
    operations inside them; ``gaps`` are chip 0's idle stretches as (start_ns, end_ns), longest
    first."""
    devs = device_planes(planes)
    if not devs:
        return {}
    per_dev, t_lo, t_hi = [], None, None
    for p in devs:
        ops = _line(p, OPS_LINE) or _line(p, MODULES_LINE)
        iv = union_intervals(ops)
        per_dev.append(iv)
        if iv:
            t_lo = iv[0][0] if t_lo is None else min(t_lo, iv[0][0])
            t_hi = iv[-1][1] if t_hi is None else max(t_hi, iv[-1][1])
    if t_lo is None:
        return {"chips": len(devs), "busy_s": 0.0, "window_s": 0.0, "programs": {}, "ops": {}, "gaps": [],
                "lines": [ln["name"] for ln in devs[0]["lines"]]}
    busy = [sum(b - a for a, b in iv) * 1e-9 for iv in per_dev]
    first = per_dev[0]
    edges = [t_lo] + [x for a, b in first for x in (a, b)] + [t_hi]
    gaps = sorted(((edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]),
                  key=lambda g: g[0] - g[1])
    leaves = [e for e in _line(devs[0], OPS_LINE) if op_name(e[0]) not in CONTAINERS]
    return {
        "chips": len(devs),
        "busy_s": sum(busy) / len(busy),
        "window_s": (t_hi - t_lo) * 1e-9,
        "t_lo_ns": t_lo, "t_hi_ns": t_hi,
        "programs": totals(_line(devs[0], MODULES_LINE), program_name),
        "flash_programs": sorted(programs_holding(_line(devs[0], MODULES_LINE), _line(devs[0], OPS_LINE), "_fwd_pallas")),
        "ops": totals(leaves, op_short),
        "op_kinds": totals(leaves, op_name),
        "gaps": gaps,
        "lines": [ln["name"] for ln in devs[0]["lines"]],
    }


def reduce_trace_dir(trace_dir: str, spans: list[tuple], host_start: float, stretch_s: float | None = None,
                     keep_ops: int = 200) -> dict:
    """What a worker sends back of its traced stretch: the device planes of the newest trace under
    ``trace_dir`` (cut to their first ``stretch_s`` seconds where the profiler ran on after the
    stretch) reduced, the gaps attributed to ``spans`` (the trace's clock is set against the host's
    by ``host_start``, when ``start_trace`` was called), and the ``keep_ops`` operations with most
    time. {} where there is no trace or no device plane in it."""
    path = find_xplane(trace_dir)
    planes = read_planes(path, lambda n: n.startswith("/device:")) if path else []
    red = reduce_planes(clip_planes(planes, int(stretch_s * 1e9)) if stretch_s else planes)
    if red.get("window_s"):
        red["idle_gaps"] = attribute_gaps(red.pop("gaps"), spans, int(host_start * 1e9) - red["t_lo_ns"])
        red["ops"] = dict(sorted(red["ops"].items(), key=lambda kv: -kv[1][1])[:keep_ops])
    return red


def top(table: dict[str, list], n: int = 10) -> list[list]:
    """[[name, seconds], ...] of the ``n`` rows with most time."""
    return [[k, v[1]] for k, v in sorted(table.items(), key=lambda kv: -kv[1][1])[:n]]


def attribute_gaps(gaps: list[tuple], spans: list[tuple], offset_ns: int = 0, n: int = 10) -> list[list]:
    """Idle seconds by what the host was doing. ``spans``: (label, start_s, end_s) on the host's
    clock, ``offset_ns`` what must be added to a trace time to reach that clock. A gap goes, by
    its midpoint, to the first span that holds it, else to ``unattributed``."""
    spans = sorted(spans, key=lambda s: s[1])
    starts = [s[1] for s in spans]
    import bisect

    out: dict[str, float] = {}
    for a, b in gaps:
        mid = ((a + b) / 2 + offset_ns) * 1e-9
        i = bisect.bisect_right(starts, mid) - 1
        label = "unattributed"
        while i >= 0 and mid - spans[i][1] < 60.0:
            if spans[i][1] <= mid < spans[i][2]:
                label = spans[i][0]
                break
            i -= 1
        out[label] = out.get(label, 0.0) + (b - a) * 1e-9
    return [[k, v] for k, v in sorted(out.items(), key=lambda kv: -kv[1])[:n]]
