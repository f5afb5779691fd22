"""Arithmetic from recorded samples to the numbers the benchmark prints. Pure Python, no clock."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between order statistics
    (numpy's default). Raises on an empty sample: a metric with nothing behind it is left out
    by the caller, never printed as 0."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values) -> float:
    return percentile(values, 50.0)


def serve_summary(records: list[dict], t0: float, t1: float, miss_ms: float) -> dict:
    """End-to-end serving numbers over one window [t0, t1).

    ``records``: one dict per request the generator sent, with ``due`` (when it was due; for a
    closed loop, when it was sent), ``sent``, ``stamps`` (client receive time of each streamed
    token), ``done`` (time the stream ended, None if it never did), ``error`` (None or text),
    ``prompt_tokens`` and ``max_tokens``.

    A request counts in the window when it was DUE in it. One that was shed, errored, timed out
    or had not finished when the drain ended counts in ``failed`` and misses every limit: its
    time to first token and its gaps enter the percentiles as ``miss_ms`` (the drain limit), so
    a failure can only make a tail worse. Tokens per second counts prompt plus generated tokens
    of requests that COMPLETED inside the window, whenever they were due, over the window.

    Printed with the summary and no metric of the benchmark: ``stalled_gap_share``, the share of
    the gaps over twice the median gap (an admission held the stream); ``itl_quantiles``, the
    gaps' 90th to 99th percentiles in ms, which say in what population of gaps the bounded 95th
    rests; ``in_flight_mean``, the requests between sent and done averaged over the window (one
    that never finished counts to the window's end, one that was refused or broke not at all).
    """
    due = [r for r in records if t0 <= r["due"] < t1]
    ok = [r for r in due if r["error"] is None and r["done"] is not None and len(r["stamps"]) == r["max_tokens"]]
    failed = len(due) - len(ok)
    ttft = [(r["stamps"][0] - r["due"]) * 1e3 for r in ok] + [miss_ms] * failed
    gaps = [(b - a) * 1e3 for r in ok for a, b in zip(r["stamps"], r["stamps"][1:])] + [miss_ms] * failed
    late = [(r["sent"] - r["due"]) * 1e3 for r in due]
    completed = [r for r in records if r["error"] is None and r["done"] is not None and t0 <= r["done"] < t1
                 and len(r["stamps"]) == r["max_tokens"]]
    tokens = sum(r["prompt_tokens"] + len(r["stamps"]) for r in completed)
    out = {"attempted": len(due), "failed": failed, "n_gaps": len(gaps),
           "completed_in_window": len(completed), "tokens_completed": tokens,
           "serve_tokens_per_s": tokens / (t1 - t0)}
    if ttft:
        out.update(ttft_p50_ms=median(ttft), ttft_p95_ms=percentile(ttft, 95))
    if gaps:
        out.update(itl_p50_ms=median(gaps), itl_p95_ms=percentile(gaps, 95))
        out["stalled_gap_share"] = sum(g > 2 * out["itl_p50_ms"] for g in gaps) / len(gaps)
        out["itl_quantiles"] = {str(q): percentile(gaps, q) for q in (90, 92.5, 97.5, 99)}
    in_flight_s = sum(max(0.0, min(r["done"] or t1, t1) - max(r["sent"], t0)) for r in records if r["error"] is None)
    out["in_flight_mean"] = in_flight_s / (t1 - t0)
    if late:
        out.update(gen_late_p95_ms=percentile(late, 95))
    return out


def train_summary(step_ends: list[float], t0: float, tokens_per_step: int) -> dict:
    """Training numbers over a window that opens at ``t0`` and closes at the end of the last
    step in ``step_ends`` (each the host time at which that step's loss was ready): all the steps
    and all the time of the window."""
    if not step_ends:
        raise ValueError("no step completed in the window")
    elapsed = step_ends[-1] - t0
    return {"steps": len(step_ends), "window_s": elapsed,
            "train_tokens_per_s": len(step_ends) * tokens_per_step / elapsed,
            "step_p50_ms": median([(b - a) * 1e3 for a, b in zip([t0] + step_ends, step_ends)])}
