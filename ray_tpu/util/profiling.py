"""Profiling: the program's named scopes, a jax.profiler trace, and the reduction that reads one by the other.

Three pieces, one vocabulary:

- ``SCOPES``: every ``jax.named_scope`` the step programs set, with its role. ``scope(name)`` is
  the one way a scope is set (a name outside the table raises where the program is traced, as
  ``llm/model_runner.named_jit`` refuses an undocumented program name). A named scope changes
  an operation's metadata and nothing else.
- ``start_trace`` / ``stop_trace``: device planes, the ``llm.step.*`` annotations of
  ``llm/telemetry.py`` at ``ANNOTATION_LEVEL``, the Python tracer off. Only the process that
  holds a chip can trace it: a serving replica is traced through ``LLMServer.profile``
  (serve/llm.py), which calls these inside the replica.
- ``summarize(logdir, flight=None)``: the traced stretch as (a) device seconds by step program
  and named scope and (b), with the flight log's step rows, device idle seconds by engine stage,
  both on the device's clock. ``python -m ray_tpu.util.profiling <logdir> [--session <pid>]``
  prints the two tables.

The reduction reads the raw ``.xplane.pb``: the EVENT METADATA of every ``XLA Ops`` event
carries ``tf_op`` (the operation's ``op_name``, where ``jax.named_scope`` puts its path),
``program_id``, ``flops`` and ``bytes_accessed``, and ``jax.profiler.ProfileData`` hands back an
event's own stats only. The file is walked in its wire format (the handful of messages of
``xplane.proto``): nothing to import but this module.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import statistics
import struct

import jax

# The host tracer level that records ``jax.profiler.TraceAnnotation``s
# (the serving step's ``llm.step.*`` stages, llm/telemetry.py) and no
# finer host events. The Python tracer stays off: with both the host
# tracer at its default and the Python tracer on, stopping a trace of a
# loaded replica stalled it for tens of seconds (PERF.md, PR 23).
ANNOTATION_LEVEL = 1

# ---------------------------------------------------------------------------
# The scope vocabulary: name -> role. A sub-scope is named ``<kind>.<part>`` and set INSIDE its
# kind's scope (a path ``.../moe/moe.place/...``); an operation belongs to the DEEPEST table name
# on its path. Roles: ``mixer`` whatever mixes along the sequence, ``ffn`` whatever acts on a
# position alone, ``state`` a recurrent state's read-decay-write (or a convolution's window's read and write) in a decode step, ``cache`` a
# write into the KV / latent / state caches, ``embed`` / ``head`` / ``sample`` the ends of a step.
# ---------------------------------------------------------------------------
SCOPES = {
    "embed": "embed", "head": "head", "sample": "sample", "cache": "cache",
    "attn": "mixer", "gated_attn": "mixer", "mamba2": "mixer", "swa": "mixer",
    "attn.gate": "mixer", "swa.gate": "mixer",
    "gdn": "mixer", "gdn.chunk": "mixer", "gdn.scan": "mixer",
    "kda": "mixer", "kda.chunk": "mixer", "kda.scan": "mixer",
    "mla": "mixer", "mla.down": "mixer", "mla.expand": "mixer", "mla.absorb": "mixer", "mla.attn": "mixer",
    "sparse": "mixer", "sparse.select": "mixer", "sparse.attend": "mixer",
    "lightning": "mixer", "lightning.chunk": "mixer",
    "shortconv": "mixer", "shortconv.conv": "mixer",
    "indexed": "mixer", "indexed.score": "mixer", "indexed.select": "mixer", "indexed.attend": "mixer",
    "mamba1": "mixer", "mamba1.conv": "mixer", "mamba1.scan": "mixer",
    "gdn.state": "state", "kda.state": "state", "mamba2.state": "state", "lightning.state": "state", "shortconv.state": "state", "mamba1.state": "state",
    "mlp": "ffn", "ffn": "ffn",
    "moe": "ffn", "moe.route": "ffn", "moe.place": "ffn", "moe.place.count": "ffn", "moe.place.into": "ffn", "moe.place.out": "ffn",
    "moe.blocks": "ffn", "moe.shared": "ffn",
}
UNSCOPED = "unscoped"


def scope(name: str):
    """``with scope("attn"): ...``: ``jax.named_scope(name)`` for a name of ``SCOPES``."""
    if name not in SCOPES:
        raise ValueError(f"{name!r} is not a documented scope (ray_tpu.util.profiling.SCOPES)")  # tpulint: disable=ERR002 — programmer error where a program is traced, never client-visible
    return jax.named_scope(name)


def scoped(name: str, fn):
    """``fn`` with everything it traces under ``scope(name)``; arguments pass through."""
    def run(*args, **kwargs):
        with scope(name):
            return fn(*args, **kwargs)

    run.__name__ = run.__qualname__ = getattr(fn, "__name__", name)
    return run


_WRAPPED = re.compile(r"^(?:transpose|jvp|vmap|remat|checkpoint|custom_jvp|custom_vjp)\((.*)\)$")


def scope_of(op_name: str) -> str:
    """The deepest name of ``SCOPES`` on an operation's path (``jit(f)/jit(main)/while/body/moe/
    moe.blocks/dot_general`` -> ``moe.blocks``), ``transpose(jvp(attn))`` read as ``attn`` (a
    training step's backward pass); ``UNSCOPED`` where the path holds none."""
    for part in reversed(op_name.split("/")):
        while (m := _WRAPPED.match(part)) is not None:
            part = m.group(1)
        if part in SCOPES:
            return part
    return UNSCOPED


def under(name: str, kind: str) -> bool:
    """Whether scope ``name`` is ``kind`` or one of its sub-scopes."""
    return name == kind or name.startswith(kind + ".")


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------
def start_trace(logdir: str, host_tracer_level: int = ANNOTATION_LEVEL) -> None:
    """Start a jax.profiler trace into ``logdir``: device planes, host
    annotations at ``host_tracer_level`` (0 = device planes only), the
    Python tracer off."""
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = int(host_tracer_level)
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)


def stop_trace() -> None:
    jax.profiler.stop_trace()


# ---------------------------------------------------------------------------
# the trace file, in its wire format. xplane.proto, the fields read here:
#   XSpace          1 planes
#   XPlane          2 name, 3 lines, 4 event_metadata (map), 5 stat_metadata (map)
#   XLine           2 name, 3 timestamp_ns, 4 events
#   XEvent          1 metadata_id, 2 offset_ps, 3 duration_ps, 4 stats
#   XEventMetadata  1 id, 2 name, 5 stats
#   XStatMetadata   1 id, 2 name
#   XStat           1 metadata_id, 2 double, 3 uint64, 4 int64, 5 str, 6 bytes, 7 ref (a stat_metadata id)
# ---------------------------------------------------------------------------
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULES_LINE, OPS_LINE = "XLA Modules", "XLA Ops"
CONTAINERS = ("while", "conditional", "call")  # their events span the operations inside them
HLO_STAT = "Hlo Proto"


def _varint(buf, pos: int):
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def _skip(buf, pos: int, kind: int) -> int:
    """Past the value of a field of wire type ``kind`` that starts at ``pos``."""
    if kind == 0:
        return _varint(buf, pos)[1]
    if kind == 2:
        n, pos = _varint(buf, pos)
        return pos + n
    return pos + (8 if kind == 1 else 4)


def _fields(buf, pos: int, end: int):
    """(field number, wire type, value) of one message: an int for a varint or a fixed-width
    field (its raw bits), (start, end) into ``buf`` for a length-delimited one."""
    while pos < end:
        tag, pos = _varint(buf, pos)
        kind = tag & 7
        if kind == 0:
            val, pos = _varint(buf, pos)
        elif kind == 2:
            n, pos = _varint(buf, pos)
            val, pos = (pos, pos + n), pos + n
        elif kind == 1:
            val, pos = int.from_bytes(buf[pos:pos + 8], "little"), pos + 8
        elif kind == 5:
            val, pos = int.from_bytes(buf[pos:pos + 4], "little"), pos + 4
        else:
            raise ValueError(f"wire type {kind} at byte {pos}: not an xplane file")
        yield tag >> 3, kind, val


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _stat(buf, span, stat_names: dict):
    """One XStat -> (its name, its value); a length-delimited value stays a span into ``buf``
    unless it is a string."""
    name = value = None
    for f, kind, val in _fields(buf, *span):
        if f == 1:
            name = stat_names.get(val)
        elif f == 2:
            value = struct.unpack("<d", val.to_bytes(8, "little"))[0]
        elif f in (3, 4):
            value = val
        elif f == 5:
            value = _text(buf, val)
        elif f == 6:
            value = val
        elif f == 7:
            value = stat_names.get(val, "")
    return name, value


def _events(buf, span, with_stats: bool) -> list:
    """An XLine's events as (metadata id, offset_ps, duration_ps[, stat spans]). The loop every
    event of a trace goes through: a few hundred thousand in a traced stretch."""
    out = []
    pos, end = span
    while pos < end:
        tag, pos = _varint(buf, pos)
        if tag != 0x22:  # not field 4, length-delimited: the line's own fields
            pos = _skip(buf, pos, tag & 7)
            continue
        n, pos = _varint(buf, pos)
        stop = pos + n
        mid = off = dur = 0
        stats = []
        while pos < stop:
            t = buf[pos]
            pos += 1
            if t == 0x22:
                n, pos = _varint(buf, pos)
                if with_stats:
                    stats.append((pos, pos + n))
                pos += n
            elif t == 0x08:
                mid, pos = _varint(buf, pos)
            elif t == 0x10:
                off, pos = _varint(buf, pos)
            elif t == 0x18:
                dur, pos = _varint(buf, pos)
            else:  # num_occurrences, or a field from after this was written
                tag, pos = _varint(buf, pos - 1)
                pos = _skip(buf, pos, tag & 7)
        out.append((mid, off, dur, stats) if with_stats else (mid, off, dur))
    return out


def _map_entry(buf, span):
    key, value = 0, None
    for f, _, val in _fields(buf, *span):
        if f == 1:
            key = val
        elif f == 2:
            value = val
    return key, value


def read_xspace(path: str, want=lambda name: True) -> list[dict]:
    """The planes of ``path`` whose name ``want`` takes: {"name", "buf", "stat_names" {id: name},
    "metadata" {id: {"name", "stats" {name: value}}}, "lines" [{"name", "timestamp_ns", "span"}]}.
    A line's events stay undecoded (``line_events``): a reader asks for the lines it wants."""
    with open(path, "rb") as f:
        buf = f.read()
    planes = []
    for f, _, span in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name, lines, metas, stat_names = "", [], [], {}
        for g, _, val in _fields(buf, *span):
            if g == 2:
                name = _text(buf, val)
            elif g == 3:
                lines.append(val)
            elif g == 4:
                metas.append(val)
            elif g == 5:
                key, value = _map_entry(buf, val)
                if value is not None:
                    stat_names[key] = next((_text(buf, v) for h, _, v in _fields(buf, *value) if h == 2), "")
        if not want(name):
            continue
        metadata = {}
        for entry in metas:
            key, value = _map_entry(buf, entry)
            if value is None:
                continue
            meta = {"name": "", "stats": {}}
            for h, _, v in _fields(buf, *value):
                if h == 2:
                    meta["name"] = _text(buf, v)
                elif h == 5:
                    k, x = _stat(buf, v, stat_names)
                    meta["stats"][k] = x
            metadata[key] = meta
        out_lines = []
        for ln in lines:
            line = {"name": "", "timestamp_ns": 0, "span": ln}
            pos, end = ln
            while pos < end:  # the line's own fields, stepping over its events
                tag, pos = _varint(buf, pos)
                if tag == 0x12:  # name
                    n, at = _varint(buf, pos)
                    line["name"] = _text(buf, (at, at + n))
                elif tag == 0x18:  # timestamp_ns
                    line["timestamp_ns"] = _varint(buf, pos)[0]
                pos = _skip(buf, pos, tag & 7)
            out_lines.append(line)
        planes.append({"name": name, "buf": buf, "stat_names": stat_names, "metadata": metadata, "lines": out_lines})
    return planes


def line_events(plane: dict, line_name: str, with_stats: bool = False) -> list:
    """(metadata id, start_ns, duration_ns[, stats {name: value}]) of the plane's line of that name."""
    out = []
    for line in plane["lines"]:
        if line["name"] != line_name:
            continue
        base = line["timestamp_ns"]
        for ev in _events(plane["buf"], line["span"], with_stats):
            row = (ev[0], base + ev[1] // 1000, ev[2] // 1000)
            if with_stats:
                row += (dict(_stat(plane["buf"], s, plane["stat_names"]) for s in ev[3]),)
            out.append(row)
    return out


def find_xplane(logdir_or_xplane: str) -> str | None:
    """The newest trace under a profiler's log directory, or the file itself."""
    if os.path.isfile(logdir_or_xplane):
        return logdir_or_xplane
    files = sorted(glob.glob(os.path.join(logdir_or_xplane, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def program_name(event_name: str) -> str:
    """``jit_fused_step(1234567)`` -> ``jit_fused_step``; a program without a name of its own
    (``jit__unknown``) keeps its fingerprint, which is all that tells it from another."""
    name = re.sub(r"\(-?\d+\)$", "", event_name)
    return event_name if "unknown" in name else name


def _program_id(event_name: str) -> int | None:
    m = re.search(r"\((-?\d+)\)$", event_name)
    return int(m.group(1)) & (2**64 - 1) if m else None


def op_short(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" ", 1)[0].lstrip("%")


def op_kind(event_name: str) -> str:
    """``%fusion.123 = ...`` -> ``fusion``."""
    head = op_short(event_name)
    return re.sub(r"[.\d]+$", "", head) or head


# --- the compiled module's own text of an operation, where its event's metadata has no ``tf_op``:
# HloProto 1 hlo_module; HloModuleProto 3 computations; HloComputationProto 2 instructions,
# 5 id, 6 root_id; HloInstructionProto 1 name, 2 opcode, 7 metadata (OpMetadata 2 op_name), 35 id,
# 38 called_computation_ids
def hlo_op_names(buf, span) -> dict[str, str]:
    """instruction name -> ``op_name`` over a serialized ``HloProto``; a fusion without one of its
    own takes its fused computation's root's, else the one most of its instructions carry."""
    module = next((v for f, _, v in _fields(buf, *span) if f == 1), None)
    if module is None:
        return {}
    comps, out, fusions = {}, {}, []
    for f, _, comp in _fields(buf, *module):
        if f != 3:
            continue
        cid = root = None
        instrs = []
        for g, kind, val in _fields(buf, *comp):
            if g == 2:
                name = opcode = op = ""
                iid, called = None, []
                for h, k, v in _fields(buf, *val):
                    if h == 1:
                        name = _text(buf, v)
                    elif h == 2:
                        opcode = _text(buf, v)
                    elif h == 7:
                        op = next((_text(buf, w) for j, _, w in _fields(buf, *v) if j == 2), "")
                    elif h == 35:
                        iid = v
                    elif h == 38:
                        if k == 2:  # packed
                            p, e = v
                            while p < e:
                                c, p = _varint(buf, p)
                                called.append(c)
                        else:
                            called.append(v)
                instrs.append((iid, op))
                out[name] = op
                if not op and opcode == "fusion" and called:
                    fusions.append((name, called[0]))
            elif g == 5:
                cid = val
            elif g == 6:
                root = val
        comps[cid] = (root, instrs)
    for name, cid in fusions:
        root, instrs = comps.get(cid, (None, []))
        ops = [op for _, op in instrs if op]
        out[name] = next((op for iid, op in instrs if iid == root and op), "") or (max(set(ops), key=ops.count) if ops else "")
    return out


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------
def _union(events: list) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for _, s, d in sorted(events, key=lambda e: e[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], s + d)
        else:
            out.append([s, s + d])
    return [(a, b) for a, b in out]


def _best_shift(starts: list, stamps: list) -> int | None:
    """Which stamp the first execution goes with: executions and stamps are both in order and one
    to one, the trace holds a stretch of a longer run, so execution i goes with stamp s + i for
    one s: the s under which ``start - stamp`` varies least (under another, every irregular
    interval of the run shows in it whole)."""
    import numpy as np

    n, m = len(starts), len(stamps)
    if not n or m < n:
        return None
    a, b = np.asarray(starts, np.float64), np.asarray(stamps, np.float64)
    windows = np.lib.stride_tricks.sliding_window_view(b, n)  # [m - n + 1, n]
    diff = a[None, :] - windows
    return int(np.argmin(diff.max(axis=1) - diff.min(axis=1)))


def _shifts(runs: list, anchors: dict) -> dict:
    """{word: which of its stamps the word's first execution goes with}. The device runs its programs
    in the order the host dispatched them, so the executions' order by family (fused, prefill, prefill,
    fused, ...) is a stretch of the stamps' order by family, and where it occurs once that is the
    alignment, whatever the times say: a step dispatched behind a wave's prefills starts long after
    its own stamp and, the loop being bound by the device, a steady host round BEFORE the next one, so
    times alone take the next. Where it occurs more than once (a run as regular as a clock), the
    occurrence under which ``start - stamp`` varies least; where it does not occur (rows dropped from
    the log), each family by that rule alone (``_best_shift``)."""
    words = [w for w in anchors if any(w in name for name, _, _ in runs)]
    execs = sorted((start, i) for name, start, _ in runs for i, w in enumerate(words) if w in name)
    stamps = sorted((t * 1e9, i) for i, w in enumerate(words) for t in anchors[w])
    order, stamped = ("".join(chr(65 + i) for _, i in seq) for seq in (execs, stamps))
    found, k = [], stamped.find(order) if order else -1
    while k >= 0:
        found.append(k)
        k = stamped.find(order, k + 1)
    if not found:
        return {w: _best_shift([s for name, s, _ in runs if w in name], [t * 1e9 for t in anchors[w]]) for w in words}

    def spread(k):
        d = [start - t for (start, _), (t, _) in zip(execs, stamps[k:])]
        return max(d) - min(d)

    k = min(found, key=spread)
    return {w: stamped[:k].count(chr(65 + i)) for i, w in enumerate(words)}


def _align(runs: list, anchors: dict, idle_before: dict, drained: list | None = None, before: dict | None = None) -> dict:
    """The offset between the device's clock and the host's, from events both sides record.
    ``runs``: chip 0's executions (program name, start_ns, duration_ns) in order; ``anchors``:
    {"fused": [...], "prefill": [...]} host stamps (seconds) of every dispatch of the run, in
    order, each taken as its call returned; ``before``: the same dispatches' stamps taken BEFORE the
    call (``llm/telemetry.dispatch_stamps_before``; None for a log without them); ``idle_before``:
    execution start -> the ns no program had run before it; ``drained``
    (``llm/telemetry.drain_stamps``): for every fused stamp the host's time at which that step's
    tokens had been read.

    The BRACKET (``clock_bracket_ns``, its width ``clock_bounds_ms``), where ``before`` is given: an
    execution cannot start before the host began to enqueue it, so the least ``start - stamp before``
    bounds the offset from above whatever a thread lost after its stamp; an execution had ENDED by the
    time its tokens were on the host, so the largest ``end - drained`` bounds it from below (the host,
    blocked on that read, returns from it a transfer after the end). Neither can be moved by a late stamp.

    The offset inside it: an execution that found the device idle started as soon as the host had
    dispatched it, so its start less its stamp IS the offset, to within a launch; the offset is the
    MEDIAN over those launches, not their minimum: a stamp taken after the dispatching call returns is
    late by milliseconds where the thread loses the interpreter in between (one such pair in 750 moved a
    minimum by 2.5 to 7.5 ms, PERF.md PR 39). Where fewer than three launches found the device idle,
    the loop is bound by the device and every start lies a queue's length above its stamp: the offset
    is then the bound from below (without ``drained``, the one from above). A log without ``before``
    is read as it was before PR 55: the least ``start - stamp`` stands in for the bound from above, and
    the bound from below is taken (and ``clock_bounds_ms`` given) in the second regime alone.
    ``clock_residual_ms``: the spread (interquartile range) of the launches onto an idle device about the
    offset; ``clock_residual_max_ms`` the farthest of them; ``late_stamps`` how many pairs of all lie more
    than a millisecond BELOW the offset (a start before its stamp: the stamp was late) and
    ``latest_stamp_ms`` by how much at most."""
    pairs, early, shifts = [], [], _shifts(runs, before or anchors)  # a late stamp can stand behind the next family's: the order is the earlier stamps'
    for word, shift in shifts.items():
        starts = [s for name, s, _ in runs if word in name]
        if shift is not None:
            pairs += [(s - anchors[word][shift + i] * 1e9, s) for i, s in enumerate(starts)]
            if before:
                early += [s - before[word][shift + i] * 1e9 for i, s in enumerate(starts)]
    if not pairs:
        return {}
    waited = sorted(d for d, s in pairs if idle_before.get(s, 0) >= 100_000)
    lowest = min(d for d, _ in pairs)
    above, below = min(early) if early else lowest, None
    if (early or len(waited) < 3) and drained and shifts.get("fused") is not None:
        fused = [(s, d) for name, s, d in runs if "fused" in name]
        ends = [s + d - drained[k] * 1e9 for k, (s, d) in enumerate(fused, shifts["fused"]) if k < len(drained) and drained[k]]
        below = max(ends) if ends else None
    if len(waited) >= 3:
        offset = statistics.median(waited)
        if early:
            offset = min(offset if below is None else max(offset, below), above)
    else:
        offset = above if below is None else min(below, above)
    out = {"offset_ns": offset, "clock_bounds_ms": (above - below) * 1e-6 if below is not None else None,
           "anchors": len(pairs), "anchors_on_an_idle_device": len(waited),
           "clock_residual_ms": None, "clock_residual_max_ms": None,
           "late_stamps": sum(1 for d, _ in pairs if d < offset - 1e6), "latest_stamp_ms": max(offset - lowest, 0.0) * 1e-6}
    if early:
        out["clock_bracket_ns"] = [below, above]
    if waited:
        q = statistics.quantiles(waited, n=4) if len(waited) >= 4 else (waited[0], offset, waited[-1])
        out["clock_residual_ms"] = (q[2] - q[0]) * 1e-6
        out["clock_residual_max_ms"] = max(abs(d - offset) for d in waited) * 1e-6
    return out


def _annotation_offset(planes: list, steps: list) -> float | None:
    """The same offset from the ``llm.step`` annotations of a trace taken with the host tracer
    on (``LLMServer.profile``): each carries its step's number, and the step's row its ``t0``."""
    t0 = {s["step"]: s["t0"] for s in steps if "step" in s and "t0" in s}
    diffs = []
    for plane in planes:
        if not plane["name"].startswith("/host:"):
            continue
        ids = {k for k, m in plane["metadata"].items() if m["name"] == "llm.step"}
        if not ids:
            continue
        for line in plane["lines"]:
            for mid, start, _, stats in line_events(plane, line["name"], with_stats=True):
                if mid in ids and "step" in stats and int(stats["step"]) in t0:
                    diffs.append(start - t0[int(stats["step"])] * 1e9)
    return statistics.median(diffs) if diffs else None


def _program_row() -> dict:
    return {"calls": 0, "device_s": 0.0, "leaf_s": 0.0, "scopes": {}, "ops": {}}


def _idle_by_stage(gaps: list, spans: list, offset_ns: float) -> dict:
    """The device's idle gaps (ns, its clock) given, whole or cut at the boundaries, to the host
    spans (label, start_s, end_s: in order, without overlap) that hold them; what no span holds
    is ``unattributed``. -> {label: {"s", "gaps"}}."""
    idle = {"unattributed": {"s": 0.0, "gaps": 0}}

    def give(label, secs):
        piece = idle.setdefault(label, {"s": 0.0, "gaps": 0})
        piece["s"] += secs
        piece["gaps"] += 1

    span_starts = [s[1] for s in spans]
    for a, b in gaps:
        a, b = (a - offset_ns) * 1e-9, (b - offset_ns) * 1e-9
        i = max(bisect.bisect_right(span_starts, a) - 1, 0)
        at = a
        while at < b and i < len(spans):
            label, s, e = spans[i]
            lo, hi = max(at, s), min(b, e)
            if lo > at:  # before this span, after the last: nobody's
                give("unattributed", min(lo, b) - at)
                at = min(lo, b)
            if hi > lo:
                give(label, hi - lo)
                at = hi
            i += 1
        if at < b:
            give("unattributed", b - at)
    return idle


def _captures_on_the_device(stalls: list, runs: list, offset_ns: float, t_lo: int, t_hi: int) -> list:
    """The sentinel's captures that fall inside the traced stretch, each with what the device was doing at
    its instant: the program it was running (chip 0's), or ``idle``."""
    runs = sorted(runs, key=lambda r: r[1])
    starts = [r[1] for r in runs]
    out = []
    for cap in sorted(stalls, key=lambda c: c["t"]):
        at = cap["t"] * 1e9 + offset_ns
        if not t_lo <= at <= t_hi:
            continue
        i = bisect.bisect_right(starts, at) - 1
        running = i >= 0 and at < runs[i][1] + runs[i][2]
        out.append({**{k: cap.get(k) for k in ("t", "step", "stage", "age_s", "ready")}, "device": runs[i][0] if running else "idle"})
    return out


def summarize(logdir_or_xplane: str, flight: list | None = None, stretch_s: float | None = None, stalls: list | None = None) -> dict:
    """A traced stretch by the program's own names.

    -> {"window_s", "busy_s", "chips",
        "programs": {name: {"calls", "device_s", "leaf_s", "scopes": {scope: {"s", "calls", "flops", "bytes"}},
                            "ops": {instruction: {"s", "calls", "path", "scope", "flops", "bytes" (the last two a call)}}}},
        "roles": {name: {role: seconds}},
        "clock": {...}, "idle": {stage: {"s", "gaps"}},  (the last two with ``flight``)
        "captures": [{"t", "step", "stage", "age_s", "ready", "device"}]}  (with ``flight`` and ``stalls``)

    ``programs`` is chip 0's (every chip of a mesh runs the same programs), by ``program_id`` and
    named as ``program_name`` names it, prefill buckets together: executions, their device
    seconds (the ``XLA Modules`` line), the seconds of the operations inside them (``leaf_s``:
    containers left out, their events span the operations inside them), and those seconds by
    scope (``scope_of`` the operation's ``tf_op``; ``UNSCOPED`` for the rest): the scopes'
    seconds add up to ``leaf_s``. ``stretch_s`` cuts the trace to its first seconds, as the
    benchmark's harness cuts it.

    ``flight``: the step rows of the flight log (``llm/telemetry.load_flight()["steps"]``, one
    replica's). The two clocks are set against each other by what both record (``_align``), and
    every idle gap of the device goes, whole or cut at the boundaries, to the stage of the step
    that holds it (``llm/telemetry.timeline``). ``stalls``: the log's captures
    (``load_flight()["stalls"]``): each one inside the stretch is set on the device's clock by the same
    offset and told what the device was running at that instant, or that it was idle."""
    path = find_xplane(logdir_or_xplane)
    if path is None:
        return {}
    planes = read_xspace(path, lambda n: n.startswith(("/device:TPU:", "/host:")))
    devs = sorted((p for p in planes if DEVICE_PLANE.match(p["name"])), key=lambda p: int(DEVICE_PLANE.match(p["name"]).group(1)))
    if not devs:
        return {}
    per_dev = [(line_events(p, MODULES_LINE), line_events(p, OPS_LINE)) for p in devs]
    starts = [e[1] for mods, ops in per_dev for e in mods + ops]
    if not starts:
        return {"chips": len(devs), "window_s": 0.0, "busy_s": 0.0, "programs": {}, "roles": {}}
    if stretch_s:
        cut = min(starts) + int(stretch_s * 1e9)
        per_dev = [tuple([(m, s, min(d, cut - s)) for m, s, d in evs if s < cut] for evs in pair) for pair in per_dev]
    unions = [_union(ops or mods) for mods, ops in per_dev]
    t_lo = min(u[0][0] for u in unions if u)
    t_hi = max(u[-1][1] for u in unions if u)
    busy = [sum(b - a for a, b in u) * 1e-9 for u in unions]
    edges = [t_lo] + [x for a, b in unions[0] for x in (a, b)] + [t_hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]

    # chip 0: executions by program, operations by program and scope
    meta, (mods, ops) = devs[0]["metadata"], per_dev[0]
    name_of, programs, runs = {}, {}, []
    for mid, s, d in mods:
        full = meta[mid]["name"] if mid in meta else str(mid)
        name = name_of.setdefault(_program_id(full), program_name(full))
        row = programs.setdefault(name, _program_row())
        row["calls"] += 1
        row["device_s"] += d * 1e-9
        runs.append((name, s, d))
    hlo = next((p for p in planes if p["name"] == "/host:metadata"), None)
    hlo_names: dict[int, dict] = {}
    per_meta: dict[int, tuple | None] = {}
    for mid, _, d in ops:
        if mid not in per_meta:
            m = meta.get(mid) or {"name": str(mid), "stats": {}}
            if (m["stats"].get("hlo_category") or op_kind(m["name"])) in CONTAINERS:  # ``%cond.2.clone.2`` is a conditional by its category alone
                per_meta[mid] = None
            else:
                st = m["stats"]
                pid = st.get("program_id")
                pid = pid & (2**64 - 1) if isinstance(pid, int) else None
                op = st.get("tf_op") or ""
                if not op and hlo is not None and pid in hlo["metadata"] and isinstance(hlo["metadata"][pid]["stats"].get(HLO_STAT), tuple):
                    if pid not in hlo_names:
                        hlo_names[pid] = hlo_op_names(hlo["buf"], hlo["metadata"][pid]["stats"][HLO_STAT])
                    op = hlo_names[pid].get(op_short(m["name"]), "")
                path = op.rstrip(":")
                per_meta[mid] = (name_of.get(pid, f"program {pid}"), scope_of(path), int(st.get("flops") or 0),
                                 int(st.get("bytes_accessed") or 0), op_short(m["name"]), path)
        found = per_meta[mid]
        if found is None:
            continue
        prog, sc, flops, nbytes, short, path = found
        row = programs.setdefault(prog, _program_row())
        cell = row["scopes"].setdefault(sc, {"s": 0.0, "calls": 0, "flops": 0, "bytes": 0})
        op_row = row["ops"].setdefault(short, {"s": 0.0, "calls": 0, "path": path, "scope": sc, "flops": flops, "bytes": nbytes})
        row["leaf_s"] += d * 1e-9
        for r in (cell, op_row):
            r["s"] += d * 1e-9
            r["calls"] += 1
        cell["flops"] += flops
        cell["bytes"] += nbytes
    roles = {}
    for name, row in programs.items():
        by = roles.setdefault(name, {})
        for sc, cell in row["scopes"].items():
            role = SCOPES.get(sc, UNSCOPED)
            by[role] = by.get(role, 0.0) + cell["s"]
    out = {"chips": len(devs), "window_s": (t_hi - t_lo) * 1e-9, "busy_s": sum(busy) / len(busy),
           "t_lo_ns": t_lo, "t_hi_ns": t_hi, "programs": programs, "roles": roles}
    if flight is None:
        return out

    from ray_tpu.llm.telemetry import dispatch_stamps, dispatch_stamps_before, drain_stamps, timeline

    idle_before, free_at = {}, None  # an execution's start -> how long no program had run before it
    for _, start, dur in sorted(runs, key=lambda r: r[1]):
        if free_at is not None:
            idle_before[start] = start - free_at
        free_at = max(free_at or 0, start + dur)
    clock = _align(runs, dispatch_stamps(flight), idle_before, drain_stamps(flight), dispatch_stamps_before(flight))
    ann = _annotation_offset(planes, flight)
    if ann is not None:
        clock["annotation_offset_ns"] = ann
        clock.setdefault("offset_ns", ann)
    out["clock"] = clock
    if "offset_ns" in clock:
        idle = _idle_by_stage(gaps, timeline(flight), clock["offset_ns"])
    else:
        idle = {"unattributed": {"s": sum(b - a for a, b in gaps) * 1e-9, "gaps": len(gaps)}}
    out["idle"] = idle
    if stalls and "offset_ns" in clock:
        out["captures"] = _captures_on_the_device(stalls, runs, clock["offset_ns"], t_lo, t_hi)
    return out


def tables(summary: dict, programs: tuple = ("prefill", "fused")) -> list[str]:
    """The two tables as lines of text: device seconds by scope of the programs whose name holds
    one of ``programs`` (all of them where none does), and device idle by stage."""
    lines = []
    chosen = {n: r for n, r in summary.get("programs", {}).items() if any(w in n for w in programs)} or summary.get("programs", {})
    for name, row in sorted(chosen.items(), key=lambda kv: -kv[1]["device_s"]):
        lines.append(f"{name}: {row['calls']} calls, {row['device_s']:.4f} s on the device, {row['leaf_s']:.4f} s in its operations")
        for sc, cell in sorted(row["scopes"].items(), key=lambda kv: -kv[1]["s"]):
            share = 100.0 * cell["s"] / row["leaf_s"] if row["leaf_s"] else 0.0
            lines.append(f"  {sc:<14} {SCOPES.get(sc, '-'):<7} {cell['s']:9.4f} s {share:5.1f}%  {cell['calls']:>8} ops  "
                         f"{cell['flops'] / 1e12:9.3f} TFLOP {cell['bytes'] / 1e9:9.3f} GB")
    if "idle" in summary:
        clock = summary.get("clock", {})
        total = summary["window_s"] - summary["busy_s"]
        lines.append(f"idle: {total:.4f} s of a {summary['window_s']:.4f} s window; clock from {clock.get('anchors', 0)} dispatches, "
                     f"residual {clock.get('clock_residual_ms')} ms (at most {clock.get('clock_residual_max_ms')}), {clock.get('late_stamps')} late stamps (by {clock.get('latest_stamp_ms')} ms at most)"
                     + (f"; offset {(clock['offset_ns'] - clock['clock_bracket_ns'][0]) * 1e-6:.3f} ms above the drained reads' bound in a bracket of "
                        f"{clock['clock_bounds_ms']:.3f} ms under the stamps before each dispatch" if clock.get("clock_bracket_ns", [None])[0] is not None
                        else f"; from the reads of a loop the device bounds, {clock['clock_bounds_ms']:.3f} ms under the bound from above" if clock.get("clock_bounds_ms") is not None else ""))
        for label, piece in sorted(summary["idle"].items(), key=lambda kv: -kv[1]["s"]):
            lines.append(f"  {label:<22} {piece['s']:9.4f} s  {piece['gaps']:>6} gaps")
        for cap in summary.get("captures", ()):
            ready = "" if cap["ready"] is None else ", result ready" if all(cap["ready"]) else ", result not ready"
            lines.append(f"  capture: step {cap['step']} {cap['age_s']:.2f} s into {cap['stage']}{ready}: device {cap['device']}")
    return lines


def main(argv: list[str] | None = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(prog="python -m ray_tpu.util.profiling", description="device time by named scope, device idle by engine stage")
    ap.add_argument("logdir", help="a profiler's log directory, or an .xplane.pb file")
    ap.add_argument("--session", type=int, default=None, help="the driver's pid, whose session holds the replica's flight log; without it, no idle table")
    a = ap.parse_args(argv)
    flight = stalls = None
    if a.session is not None:
        from ray_tpu.llm.telemetry import load_flight

        log = load_flight(a.session)
        flight, stalls = log["steps"], log["stalls"]
    summary = summarize(a.logdir, flight, stalls=stalls)
    if not summary:
        print(f"no trace under {a.logdir}")
        return 1
    print("\n".join(tables(summary, programs=())))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
