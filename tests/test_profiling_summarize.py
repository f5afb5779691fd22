"""``ray_tpu/util/profiling.summarize`` (PR 39), chip-free: on the two traces recorded on a v5e
(``benchmark/testdata``), and on a synthetic trace with a flight log whose clocks stand a known
offset apart: the alignment from dispatches alone and from annotations alone, idle seconds by
stage, a gap cut where it straddles two stages."""

import os
import random
import struct
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from ray_tpu.llm import telemetry  # noqa: E402
from ray_tpu.util import profiling  # noqa: E402
from ray_tpu.util.profiling import summarize  # noqa: E402

TESTDATA = os.path.join(ROOT, "benchmark", "testdata")
SMALL, SCOPED = os.path.join(TESTDATA, "small_tpu.xplane.pb"), os.path.join(TESTDATA, "scoped_tpu.xplane.pb")


# ------------------------------------------------------------------ the recorded traces
def test_the_small_trace_by_program_scope_and_metadata():
    s = summarize(SMALL)
    (name, row), = s["programs"].items()
    assert name == "jit_small_step" and row["calls"] == 3
    fusion = row["ops"]["fusion"]
    assert fusion["path"] == "jit(small_step)/dot_general" and fusion["calls"] == 3
    assert fusion["flops"] == 1075314176 and fusion["bytes"] == 3146752  # what the event metadata states, a call
    assert fusion["scope"] == "unscoped" and set(row["scopes"]) == {"unscoped"}
    assert sum(c["s"] for c in row["scopes"].values()) == pytest.approx(row["leaf_s"], rel=1e-9)
    assert row["leaf_s"] == pytest.approx(sum(o["s"] for o in row["ops"].values()), rel=1e-9)
    assert row["leaf_s"] <= row["device_s"] and row["leaf_s"] == pytest.approx(row["device_s"], rel=0.01)
    assert s["chips"] == 1 and 0 < s["busy_s"] < s["window_s"]
    assert "idle" not in s and "clock" not in s  # no flight log, no idle table


def test_the_small_trace_agrees_with_the_harness_reduction():
    from benchmark import xplane

    red = xplane.reduce_planes(xplane.read_planes(SMALL))
    s = summarize(SMALL)
    assert s["window_s"] == pytest.approx(red["window_s"], rel=1e-3) and s["busy_s"] == pytest.approx(red["busy_s"], rel=1e-3)
    calls, secs = red["programs"]["jit_small_step"]
    assert (s["programs"]["jit_small_step"]["calls"], s["programs"]["jit_small_step"]["device_s"]) == (calls, pytest.approx(secs, rel=1e-3))


def test_a_stretch_cuts_the_trace_as_the_harness_cuts_it():
    whole = summarize(SMALL)
    s = summarize(SMALL, stretch_s=0.7e-3)  # the third call starts 1.29 ms after the first
    assert s["programs"]["jit_small_step"]["calls"] == 2 and s["window_s"] < whole["window_s"]


def test_the_scoped_trace_is_read_inside_its_containers():
    """A ``lax.switch`` inside a ``lax.scan``: the harness's by-operation table shows ``while``
    and ``conditional``; the scopes in the branches, the sub-scope and the kernel's are found
    inside them, and their seconds add up to the container's."""
    s = summarize(SCOPED)
    row = s["programs"]["jit_scoped_step"]
    assert row["calls"] == 2
    scopes = row["scopes"]
    # the chip's compiler fused the ``moe`` branch's elementwise work into the matmul under ``moe.blocks``:
    # a fusion that spans two scopes goes to one
    assert set(scopes) == {"attn", "moe.blocks", "mlp", "unscoped"}
    assert scopes["attn"]["calls"] == scopes["moe.blocks"]["calls"] == 4  # two layers of each kind a call
    assert scopes["moe.blocks"]["flops"] == 4 * 1078984704 and scopes["attn"]["flops"] == 4 * 1074790400
    assert row["ops"]["fusion"]["path"].endswith("cond/branch_1_fun/moe/moe.blocks/dot_general")
    kernel = row["ops"]["scoped_double.1"]
    assert kernel["path"] == "jit(scoped_step)/mlp/scoped_double/pallas_call" and kernel["scope"] == "mlp" and kernel["calls"] == 2
    assert sum(c["s"] for c in scopes.values()) == pytest.approx(row["leaf_s"], rel=1e-9)
    assert not [name for name in row["ops"] if name.startswith(("while", "cond"))]  # containers are left out, ``cond.2.clone.2`` too
    assert row["leaf_s"] <= row["device_s"] and row["leaf_s"] == pytest.approx(row["device_s"], rel=0.05)
    # the loop's event spans what runs inside it: the seconds of what runs inside fill it, most of them under the branches' scopes
    planes = profiling.read_xspace(SCOPED, lambda n: n == "/device:TPU:0")
    meta = planes[0]["metadata"]
    loop_s = sum(d for mid, _, d in profiling.line_events(planes[0], profiling.OPS_LINE) if meta[mid]["stats"].get("hlo_category") == "while") * 1e-9
    inside = sum(o["s"] for o in row["ops"].values() if "/while" in o["path"])
    assert loop_s > 0 and inside == pytest.approx(loop_s, rel=0.05) and inside <= loop_s
    assert scopes["attn"]["s"] + scopes["moe.blocks"]["s"] >= 0.75 * loop_s


# ------------------------------------------------------------------ a synthetic trace and its flight log
def _varint(n: int) -> bytes:
    n &= (1 << 64) - 1
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _int(field: int, value: int) -> bytes:
    return _varint(field << 3) + _varint(value)


def _bytes(field: int, payload: bytes) -> bytes:
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


class _Plane:
    """An XPlane under construction: names interned into its metadata maps."""

    def __init__(self, name: str):
        self.name, self.stat_ids, self.event_ids, self.event_stats, self.lines = name, {}, {}, {}, []

    def stat_id(self, name: str) -> int:
        return self.stat_ids.setdefault(name, len(self.stat_ids) + 1)

    def stat(self, name: str, value) -> bytes:
        body = _int(1, self.stat_id(name))
        if isinstance(value, float):
            body += _varint(2 << 3 | 1) + struct.pack("<d", value)
        elif isinstance(value, int):
            body += _int(4, value)
        else:
            body += _bytes(5, str(value).encode())
        return body

    def event_id(self, name: str, **stats) -> int:
        if name not in self.event_ids:
            self.event_ids[name] = len(self.event_ids) + 1
            self.event_stats[name] = stats
        return self.event_ids[name]

    def line(self, name: str, events: list) -> None:
        """events: (event name, start_ns, duration_ns, {stat: value})"""
        body = _bytes(2, name.encode()) + _int(3, 0)
        for ev, start, dur, stats in events:
            e = _int(1, self.event_ids[ev]) + _int(2, int(start) * 1000) + _int(3, int(dur) * 1000)
            body += _bytes(4, e + b"".join(_bytes(4, self.stat(k, v)) for k, v in stats.items()))
        self.lines.append(body)

    def encode(self) -> bytes:
        out = _bytes(2, self.name.encode()) + b"".join(_bytes(3, ln) for ln in self.lines)
        for name, mid in self.event_ids.items():
            meta = _int(1, mid) + _bytes(2, name.encode()) + b"".join(_bytes(5, self.stat(k, v)) for k, v in self.event_stats[name].items())
            out += _bytes(4, _int(1, mid) + _bytes(2, meta))
        for name, sid in self.stat_ids.items():
            out += _bytes(5, _int(1, sid) + _bytes(2, _int(1, sid) + _bytes(2, name.encode())))
        return out


OFFSET_NS = -4_987_654_321_000  # device (trace) clock = host clock + this
FUSED, PREFILL = "jit_llm_fused_step(111)", "jit_llm_prefill(222)"
# the operations of one execution, as shares of it: (event name, tf_op, share); the container spans the first two
OPS = {FUSED: [("%fusion.1 = f32[8]{0} fusion(...)", "jit(llm_fused_step)/jit(main)/while/body/attn/dot_general:", 0.6),
               ("%fusion.2 = f32[8]{0} fusion(...)", "jit(llm_fused_step)/jit(main)/while/body/mlp/dot_general:", 0.3),
               ("%copy.3 = f32[8]{0} copy(...)", "", 0.1)],
       PREFILL: [("%fusion.7 = f32[8]{0} fusion(...)", "jit(llm_prefill)/jit(main)/cache/while/body/attn/dot_general:", 0.5),
                 ("%fusion.8 = f32[8]{0} fusion(...)", "jit(llm_prefill)/jit(main)/cache/while/body/mlp/dot_general:", 0.45),
                 ("%gather.9 = f32[8]{0} gather(...)", "jit(llm_prefill)/jit(main)/embed/gather:", 0.05)]}
CONTAINER = {FUSED: "%while.5 = (f32[8]{0}) while(...)", PREFILL: "%while.6 = (f32[8]{0}) while(...)"}


def simulate(seed: int = 0, steps: int = 40):
    """A replica's host timeline and the device's, made together: -> (step rows as the flight
    log holds them, the host's true spans (label, start, end), the device's executions
    (program, start_s, duration_s) on the HOST's clock)."""
    rnd = random.Random(seed)
    h, spans, rows, runs = 5000.0, [], [], []
    dev_free, fused_end = 0.0, None

    def stage(label, dur):
        nonlocal h
        spans.append((label, h, h + dur))
        h += dur
        return dur

    def enqueue(program, dur):
        nonlocal dev_free
        start = max(dev_free, h + 50e-6 + rnd.random() * 40e-6)  # the device never starts before its dispatch
        runs.append((program, start, dur))
        dev_free = start + dur
        return dev_free

    for n in range(1, steps + 1):
        ms = {f: 0.0 for f in telemetry.STAGES.values()}
        groups = []
        if n > 1:  # the stepper between two steps: the last step's tail, delivery, and now and then a wait for work
            ms["stepper_deliver_ms"] = stage("stepper.deliver", 0.2e-3) * 1e3
            if n % 7 == 0:
                ms["stepper_wait_ms"] = stage("stepper.wait", 3e-3) * 1e3
        t0 = h
        stage(telemetry.IN_STEP, 20e-6)
        ms["admission_ms"] = stage("admission", 0.1e-3) * 1e3
        p0, done = h, 0.0
        if n % 5 == 0:
            stage("prefill", 0.1e-3)
            for _ in range(1 + (n % 10 == 0)):  # one group, every other time two: launched one behind the other, nothing read
                a = h
                stage("prefill.launch", 1e-3)
                d = h
                done = enqueue(PREFILL, 20e-3 + rnd.random() * 5e-3)
                stage("prefill.launch", 0.5e-3)
                ms["state_insert_ms"] += stage("state_insert", 0.25e-3) * 1e3
                ms["prefill_launch_ms"] += (h - a) * 1e3
                groups.append([d, h, 0.0])
            stage("prefill", 50e-6)
        ms["prefill_ms"] = (h - p0) * 1e3
        ms["dispatch_ms"] = stage("dispatch", 0.4e-3) * 1e3
        dispatch_t = h
        # a decode step of 5-6 ms, which the host's round of 1.2 ms hides behind, and every third one of 0.8 ms, which it does not
        last, fused_end = fused_end, enqueue(FUSED, 5e-3 + rnd.random() * 1e-3 if n % 3 else 0.8e-3)
        ms["drain_wait_ms"] = stage("drain_wait", (max(last - h, 0.0) if last else 0.0) + 50e-6) * 1e3
        ms["emit_ms"] = stage("emit", 0.2e-3) * 1e3
        if groups:
            # the wave's ONE readback, behind the dispatch: it waits out the last prefill while the fused step stands queued
            # behind it, then emits the first tokens (every other time to streams that are slow to take them: longer than
            # the step the device runs meanwhile)
            ms["first_token_wait_ms"] = stage("prefill.first_tokens", max(done - h, 0.0) + (7e-3 if n % 10 == 0 else 0.3e-3)) * 1e3
            for g in groups:
                g[2] = h
        ms["outputs_ms"] = stage("outputs", 0.1e-3) * 1e3
        rows.append({"step": n, "t0": t0, "t": h, "wall_ms": (h - t0) * 1e3, "phase": "mixed" if groups else "decode",
                     "admitted": len(groups), "dispatch_t": dispatch_t, **({"prefill_dispatch_t": groups} if groups else {}),
                     **{k: round(v, 4) for k, v in ms.items()}})
        stage(telemetry.IN_STEP, 0.1e-3)  # on_step's own time
    return rows, spans, runs


def write_trace(path, runs, rows=None, annotate: bool = False):
    dev = _Plane("/device:TPU:0")
    for program, pid in ((FUSED, 111), (PREFILL, 222)):
        dev.event_id(program)
        dev.event_id(CONTAINER[program], program_id=pid)
        for name, tf_op, _ in OPS[program]:
            dev.event_id(name, program_id=pid, flops=1000, bytes_accessed=10, **({"tf_op": tf_op} if tf_op else {}))
    modules, ops = [], []
    for program, start, dur in runs:
        at = int(start * 1e9) + OFFSET_NS
        modules.append((program, at, int(dur * 1e9), {}))
        first = at
        for k, (name, _, share) in enumerate(OPS[program]):
            d = int(dur * 1e9 * share)
            ops.append((name, at, d, {}))
            at += d
            if k == 1:
                ops.append((CONTAINER[program], first, at - first, {}))
    dev.line("XLA Modules", modules)
    dev.line("XLA Ops", ops)
    space = _bytes(1, dev.encode())
    if annotate:
        host = _Plane("/host:CPU")
        host.event_id("llm.step")
        host.event_id("llm.step.dispatch")
        host.line("llm-stepper", [("llm.step", int(r["t0"] * 1e9) + OFFSET_NS + 2_000, int((r["t"] - r["t0"]) * 1e9), {"step": r["step"]}) for r in rows]
                  + [("llm.step.dispatch", int(r["dispatch_t"] * 1e9) + OFFSET_NS, 1000, {}) for r in rows])
        space += _bytes(1, host.encode())
    with open(path, "wb") as f:
        f.write(space)
    return path


def _true_idle(spans, runs):
    """Idle seconds of the device by the host's TRUE stage, by brute force: the gaps between the executions' operations."""
    busy = sorted((start, start + sum(int(dur * 1e9 * sh) for _, _, sh in OPS[p]) * 1e-9) for p, start, dur in runs)
    gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:]) if b[0] > a[1]]
    out: dict = {}
    for g0, g1 in gaps:
        for label, a, b in spans:
            lo, hi = max(g0, a), min(g1, b)
            if hi > lo:
                secs, pieces = out.get(label, (0.0, 0))
                out[label] = (secs + hi - lo, pieces + 1)
    return out, sum(g1 - g0 for g0, g1 in gaps)


@pytest.fixture(scope="module")
def synthetic(tmp_path_factory):
    rows, spans, runs = simulate()
    d = tmp_path_factory.mktemp("trace")
    # the trace holds a stretch of the run: executions from the 9th step's on; the log holds every row
    cut = rows[8]["t0"]
    traced = [r for r in runs if r[1] >= cut]
    return {"rows": rows, "spans": spans, "runs": traced,
            "plain": write_trace(str(d / "plain.xplane.pb"), traced),
            "annotated": write_trace(str(d / "annotated.xplane.pb"), traced, rows, annotate=True)}


def test_the_offset_is_found_from_the_dispatch_stamps_alone(synthetic):
    s = summarize(synthetic["plain"], flight=synthetic["rows"])
    clock = s["clock"]
    assert "annotation_offset_ns" not in clock
    assert clock["offset_ns"] == pytest.approx(OFFSET_NS, abs=0.1e6)  # to 0.1 ms
    assert clock["offset_ns"] >= OFFSET_NS  # it lies above the truth by a launch
    assert clock["anchors"] == len(synthetic["runs"]) and 0 <= clock["clock_residual_ms"] < 0.1
    assert clock["anchors_on_an_idle_device"] > 10


def test_one_late_stamp_does_not_move_the_offset(synthetic):
    """A stamp is taken after the dispatching call returns: a thread that loses the interpreter in between stamps
    late, and its execution then starts BEFORE its stamp. The tightest bound would follow it; the median of the
    launches onto an idle device does not, and the summary says how many stamps were late and by how much."""
    late = next(i for i, r in enumerate(synthetic["rows"]) if i > 20 and r.get("dispatch_t"))
    rows = [dict(r, dispatch_t=r["dispatch_t"] + 5e-3) if i == late else r for i, r in enumerate(synthetic["rows"])]
    clock = summarize(synthetic["plain"], flight=rows)["clock"]
    assert clock["offset_ns"] == pytest.approx(OFFSET_NS, abs=0.1e6)
    assert clock["late_stamps"] == 1 and 1.0 < clock["latest_stamp_ms"] <= 5.1  # seen less what its execution waited for the device, plus the launch the offset lies above the truth by
    assert clock["clock_residual_ms"] < 0.1


def test_a_loop_bound_by_the_device_is_aligned_by_the_order_of_its_programs():
    """PR 50: where the device is the only server, every step starts long after its own stamp (it queues behind the
    last one, and behind a wave's prefills) and a steady host round BEFORE the next stamp, so ``start - stamp`` varies
    least under the WRONG pairing. The programs' order by family decides: the device runs them as they were dispatched."""
    stamps, runs, free, h = {"fused": [], "prefill": []}, [], 100.0, 100.0
    for n in range(60):
        for _ in range((n % 9 == 4) + (n % 18 == 4)):  # a wave of one group or two, launched and not read
            h += 2e-3
            stamps["prefill"].append(h)
            runs.append(("jit_llm_prefill(2)", max(free, h + 60e-6), 0.12 + 0.01 * (n % 5)))
            free = runs[-1][1] + runs[-1][2]
        h += 0.4e-3
        stamps["fused"].append(h)
        runs.append(("jit_llm_fused_step(1)", max(free, h + 60e-6), 15e-3))
        free = runs[-1][1] + runs[-1][2]
        h = max(h, runs[-1][1]) + 1.2e-3  # the host's round: it waits for the step before this one, which ended as this one began
    traced = [(name, int(start * 1e9) + OFFSET_NS, int(dur * 1e9)) for name, start, dur in runs[20:55]]  # a stretch of the run
    want = {"fused": sum(1 for r in runs[:20] if "fused" in r[0]), "prefill": sum(1 for r in runs[:20] if "prefill" in r[0])}
    assert profiling._shifts(traced, stamps) == want
    starts = [s for name, s, _ in traced if "fused" in name]
    assert profiling._best_shift(starts, [t * 1e9 for t in stamps["fused"]]) == want["fused"] + 1, "times alone take the next stamp"
    clock = profiling._align(traced, stamps, {})
    assert clock["anchors"] == len(traced) and clock["late_stamps"] == 0
    assert clock["offset_ns"] > OFFSET_NS + 10e6  # no launch of the stretch found the device free: a bound from above, a queue's length off
    # the host, blocked on a step's tokens, has them 0.2 ms after the step ended: the bound from the other side
    ends = {start: start + dur for name, start, dur in runs if "fused" in name}
    drained = [next(e for s0, e in sorted(ends.items()) if s0 >= t) + 0.2e-3 for t in stamps["fused"]]
    clock = profiling._align(traced, stamps, {}, drained)
    assert OFFSET_NS - 0.3e6 <= clock["offset_ns"] <= OFFSET_NS and clock["clock_bounds_ms"] > 10.0
    # PR 55: with a stamp before each call the same reads give the same offset, inside a bracket neither side of which a late stamp can move
    before = {word: [t - 0.3e-3 for t in ts] for word, ts in stamps.items()}
    bracket = profiling._align(traced, stamps, {}, drained, before)
    assert bracket["offset_ns"] == clock["offset_ns"] == bracket["clock_bracket_ns"][0]
    assert bracket["clock_bracket_ns"][1] > OFFSET_NS + 10e6 and bracket["clock_bounds_ms"] == pytest.approx(clock["clock_bounds_ms"] + 0.3, abs=1e-3)


def _with_stamps_before(rows: list, late_every: int = 0) -> list:
    """The rows as a replica of PR 55 writes them: a stamp before each dispatching call (the simulated host works 0.4 ms
    through ``dispatch`` before it enqueues the step, 0.2 ms before a group's prefill); and every ``late_every``-th stamp
    AFTER a call planted 5 ms late, as a thread that lost the interpreter on its way to the stamp takes it."""
    out, n = [], 0
    for r in rows:
        r = dict(r)
        if r.get("dispatch_t"):
            n += 1
            r["dispatch_t0"] = r["dispatch_t"] - 0.4e-3
            r["dispatch_t"] += 5e-3 if late_every and n % late_every == 0 else 0.0
        if r.get("prefill_dispatch_t"):
            r["prefill_dispatch_t0"] = [g[0] - 0.2e-3 for g in r["prefill_dispatch_t"]]
            groups = []
            for g in r["prefill_dispatch_t"]:
                n += 1
                groups.append([g[0] + (5e-3 if late_every and n % late_every == 0 else 0.0), g[1], g[2]])
            r["prefill_dispatch_t"] = groups
        out.append(r)
    return out


@pytest.mark.parametrize("late_every", [0, 3])
def test_the_stamps_before_each_dispatch_bracket_the_offset_whatever_the_later_stamps_lost(synthetic, late_every):
    rows = _with_stamps_before(synthetic["rows"], late_every)
    clock = summarize(synthetic["plain"], flight=rows)["clock"]
    below, above = clock["clock_bracket_ns"]
    assert below <= OFFSET_NS <= above and below <= clock["offset_ns"] <= above
    # from above by a launch and the host's 0.2-0.4 ms between its stamp and the enqueue; from below by the 50 us a read returns after the step's end
    assert clock["clock_bounds_ms"] == pytest.approx((above - below) * 1e-6) and 0.0 < clock["clock_bounds_ms"] < 0.6
    assert clock["offset_ns"] == pytest.approx(OFFSET_NS, abs=0.1e6)
    if late_every:
        assert clock["late_stamps"] >= 5 and 4.0 < clock["latest_stamp_ms"] <= 5.1  # counted against an offset they did not move
    else:
        assert clock["late_stamps"] == 0
    text = "\n".join(profiling.tables(summarize(synthetic["plain"], flight=rows)))
    assert "in a bracket of" in text and "under the stamps before each dispatch" in text


def test_a_log_without_the_stamps_before_is_read_as_it_was(synthetic):
    """A flight log written by the parent: no bracket, the median of the launches onto an idle device, and a bound from the
    reads only where the device bounds the loop (the three tests above hold the numbers)."""
    clock = summarize(synthetic["plain"], flight=synthetic["rows"])["clock"]
    assert "clock_bracket_ns" not in clock and clock["clock_bounds_ms"] is None
    assert telemetry.dispatch_stamps_before(synthetic["rows"]) is None
    some = _with_stamps_before(synthetic["rows"])
    del some[30]["dispatch_t0"]  # a log in which ONE dispatch lacks its stamp is such a log too
    assert summarize(synthetic["plain"], flight=some)["clock"] == clock
    assert "in a bracket of" not in "\n".join(profiling.tables(summarize(synthetic["plain"], flight=synthetic["rows"])))


def test_a_capture_is_told_what_the_device_was_running_at_its_instant(synthetic):
    """The sentinel's captures carry the host's time; the offset sets each on the device's clock, inside an execution or between two."""
    runs = synthetic["runs"]
    inside = next(r for r in runs if r[0] == PREFILL and r[2] > 15e-3)
    gap = next((a[1] + a[2], b[1]) for a, b in zip(runs, runs[1:]) if b[1] - (a[1] + a[2]) > 1e-3)
    stalls = [{"t": inside[1] + 10e-3, "step": 15, "stage": "llm.step.prefill.first_tokens", "age_s": 0.26, "ready": [False, False]},
              {"t": (gap[0] + gap[1]) / 2, "step": 16, "stage": "llm.step.emit", "age_s": 0.3},
              {"t": runs[0][1] - 5.0, "step": 1, "stage": "llm.step.drain_wait", "age_s": 0.5, "ready": [True]}]  # before the traced stretch: left out
    s = summarize(synthetic["plain"], flight=_with_stamps_before(synthetic["rows"]), stalls=stalls)
    assert [(c["step"], c["device"]) for c in s["captures"]] == sorted([(15, "jit_llm_prefill"), (16, "idle")], key=lambda c: stalls[c[0] - 15]["t"])
    text = "\n".join(profiling.tables(s))
    assert "capture: step 15 0.26 s into llm.step.prefill.first_tokens, result not ready: device jit_llm_prefill" in text
    assert "capture: step 16 0.30 s into llm.step.emit: device idle" in text
    assert "captures" not in summarize(synthetic["plain"], flight=synthetic["rows"])


def test_the_annotations_alone_give_the_same_offset(synthetic):
    rows = [{k: v for k, v in r.items() if k not in ("dispatch_t", "prefill_dispatch_t")} for r in synthetic["rows"]]
    s = summarize(synthetic["annotated"], flight=rows)
    assert s["clock"]["offset_ns"] == s["clock"]["annotation_offset_ns"] == pytest.approx(OFFSET_NS, abs=0.1e6)
    both = summarize(synthetic["annotated"], flight=synthetic["rows"])["clock"]
    assert both["offset_ns"] == pytest.approx(both["annotation_offset_ns"], abs=0.1e6)  # the two ways hold each other


def test_idle_by_stage_sums_to_the_window_less_busy_and_follows_the_hosts_true_stages(synthetic):
    s = summarize(synthetic["plain"], flight=synthetic["rows"])
    idle = s["idle"]
    assert sum(p["s"] for p in idle.values()) == pytest.approx(s["window_s"] - s["busy_s"], rel=1e-6)
    assert idle["unattributed"]["s"] == pytest.approx(0.0, abs=1e-9)
    true, total = _true_idle(synthetic["spans"], synthetic["runs"])
    assert total == pytest.approx(s["window_s"] - s["busy_s"], rel=1e-3)
    for label in set(true) | set(idle) - {"unattributed"}:  # each boundary stands within the offset's error (under 0.1 ms) of where it was
        found, (secs, pieces) = idle.get(label, {"s": 0.0, "gaps": 0}), true.get(label, (0.0, 0))
        assert found["s"] == pytest.approx(secs, abs=0.1e-3 * max(found["gaps"], pieces)), label
    # where this device waited: for the host's dispatch behind a short step, for work after a stepper's wait, and
    # where the emits of a wave's first tokens outlasted the step dispatched before them
    assert {"prefill.first_tokens", "stepper.wait", "dispatch"} <= {k for k, (secs, _) in true.items() if secs > 0.5e-3} <= set(idle)


def test_a_gap_that_straddles_two_stages_is_cut(synthetic):
    """Between the end of the step dispatched behind a wave and the next step's start the host finishes
    ``prefill.first_tokens`` (emits that outlast the step), builds the outputs, delivers them, plans an
    admission and works through ``dispatch``: one gap of the device, a piece a stage."""
    s = summarize(synthetic["plain"], flight=synthetic["rows"])
    gaps = sum(1 for a, b in zip(synthetic["runs"], synthetic["runs"][1:]) if b[1] > a[1] + a[2] + 1e-6)
    assert sum(p["gaps"] for p in s["idle"].values()) > gaps  # pieces, not gaps
    row = next(r for r in synthetic["rows"] if len(r.get("prefill_dispatch_t") or ()) == 2 and r["step"] > 9)
    after = next(r for r in synthetic["rows"] if r["step"] == row["step"] + 1)
    read = row["prefill_dispatch_t"][-1][2]
    assert row["dispatch_t"] < read <= row["t"], "the wave's first tokens are read behind the dispatch, inside the step"
    spans = [sp for sp in telemetry.timeline(synthetic["rows"]) if sp[2] > read - 0.3e-3 and sp[1] < after["dispatch_t"]]
    assert [sp[0] for sp in spans] == ["prefill.first_tokens", "outputs", telemetry.IN_STEP, "stepper.deliver", telemetry.IN_STEP, "admission", "dispatch"]


def test_an_admitting_rows_stages_tile_it_in_the_new_order(synthetic):
    """A wave is launched inside ``prefill`` and read behind ``dispatch``: the row's spans stand in that order, without
    overlap, from the row's start to its end, and ``prefill_bubble_ms``' formula (benchmark/metrics) is never negative."""
    spans = telemetry.timeline(synthetic["rows"])
    assert all(a[2] <= b[1] + 1e-9 for a, b in zip(spans, spans[1:]))
    for row in (r for r in synthetic["rows"] if r.get("prefill_dispatch_t")):
        mine = [sp for sp in spans if row["t0"] - 1e-9 <= sp[1] and sp[2] <= row["t"] + 1e-9]
        assert sum(b - a for _, a, b in mine) == pytest.approx(row["t"] - row["t0"], abs=1e-6)
        labels = [sp[0] for sp in mine]
        n = len(row["prefill_dispatch_t"])
        assert labels == ([telemetry.IN_STEP, "admission", "prefill"] + ["prefill.launch", "state_insert"] * n
                          + ["prefill", "dispatch", "drain_wait", "emit", "prefill.first_tokens", "outputs"])
        assert (row["dispatch_t"] - row["t0"]) * 1e3 - row["admission_ms"] - row["prefill_ms"] >= 0.0
        assert all(launched <= row["dispatch_t"] < read for _, launched, read in row["prefill_dispatch_t"])


def test_scopes_and_roles_of_the_synthetic_programs(synthetic):
    s = summarize(synthetic["plain"], flight=synthetic["rows"])
    fused, prefill = s["programs"]["jit_llm_fused_step"], s["programs"]["jit_llm_prefill"]
    assert set(fused["scopes"]) == {"attn", "mlp", "unscoped"} and set(prefill["scopes"]) == {"attn", "mlp", "embed"}
    assert fused["scopes"]["attn"]["s"] == pytest.approx(0.6 * fused["device_s"], rel=1e-3)  # the container is left out
    assert sum(c["s"] for c in fused["scopes"].values()) == pytest.approx(fused["leaf_s"], rel=1e-9) == pytest.approx(fused["device_s"], rel=1e-3)
    assert s["roles"]["jit_llm_prefill"] == {"mixer": pytest.approx(prefill["scopes"]["attn"]["s"]), "ffn": pytest.approx(prefill["scopes"]["mlp"]["s"]),
                                             "embed": pytest.approx(prefill["scopes"]["embed"]["s"])}
    assert fused["scopes"]["mlp"]["flops"] == 1000 * fused["calls"]
    text = "\n".join(profiling.tables(s))
    assert "jit_llm_prefill" in text and "prefill.first_tokens" in text and "unscoped" in text


def test_without_anchors_the_idle_is_unattributed_and_nothing_raises(synthetic):
    rows = [{k: v for k, v in r.items() if k not in ("dispatch_t", "prefill_dispatch_t")} for r in synthetic["rows"]]
    s = summarize(synthetic["plain"], flight=rows)
    assert s["clock"] == {} and s["idle"]["unattributed"]["s"] == pytest.approx(s["window_s"] - s["busy_s"])
    assert summarize(os.path.join(TESTDATA, "no_such_dir")) == {}


def test_the_hlo_proto_names_an_operation_whose_metadata_does_not():
    """The fallback: ``/host:metadata`` holds each program's ``HloProto``; an operation without
    ``tf_op`` takes its instruction's ``op_name`` there, a fusion without one its root's."""
    def instr(name, opcode, iid, op_name="", called=()):
        body = _bytes(1, name.encode()) + _bytes(2, opcode.encode()) + _int(35, iid)
        if op_name:
            body += _bytes(7, _bytes(2, op_name.encode()))
        return body + b"".join(_int(38, c) for c in called)

    fused = _bytes(1, b"fused_computation") + _bytes(2, instr("p0", "parameter", 1)) + _bytes(2, instr("dot.1", "dot", 2, "jit(f)/moe/moe.blocks/dot_general")) + _int(5, 9) + _int(6, 2)
    entry = _bytes(1, b"main") + _bytes(2, instr("fusion.4", "fusion", 3, called=(9,))) + _bytes(2, instr("copy.5", "copy", 4, "jit(f)/cache/copy")) + _int(5, 10) + _int(6, 3)
    proto = _bytes(1, _bytes(1, b"jit_f") + _bytes(3, fused) + _bytes(3, entry))
    assert profiling.hlo_op_names(proto, (0, len(proto))) == {"p0": "", "dot.1": "jit(f)/moe/moe.blocks/dot_general",
                                                               "fusion.4": "jit(f)/moe/moe.blocks/dot_general", "copy.5": "jit(f)/cache/copy"}


def test_the_cli_prints_the_tables(capsys):
    assert profiling.main([SMALL]) == 0
    out = capsys.readouterr().out
    assert "jit_small_step: 3 calls" in out and "unscoped" in out
