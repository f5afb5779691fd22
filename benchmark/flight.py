"""The program's flight log as the per-layer readers take it. The serving replica keeps every
engine step and finished request of its life (``ray_tpu/llm/telemetry.py``) and writes them to
the session's directory when ``serve.shutdown()`` stops it; the readers run in the driver
process after that, and reach through here for the fields that ``serve_worker.bench_observe``
does not pass on: the stages inside a step, and the stamps along a request's way in and out.

A program from before the log (no ``load_flight``), a run whose replica wrote none, an ``obs``
with no worker: nothing to read, and every reader that asks here returns ``None``."""

from __future__ import annotations


def records(obs: dict) -> dict | None:
    """-> {"steps": the window's step records, "requests": {request id: record}} of this run.
    Steps are kept by their end stamp inside ``obs["window"]``, requests by a submit stamp not
    before it: whatever an earlier run left behind under a reused process id has neither."""
    if not obs.get("worker"):
        return None
    try:
        from ray_tpu.llm.telemetry import load_flight
    except ImportError:
        return None
    t0, t1 = obs["window"]
    log = load_flight()
    steps = [s for s in log["steps"] if t0 <= s["t"] < t1]
    requests = {r["request_id"]: r for r in log["requests"] if r["submit_t"] >= t0}
    return {"steps": steps, "requests": requests} if steps or requests else None


def due_in_window(obs: dict) -> list[tuple[dict, dict]]:
    """(the client's record, the log's record) of every request due in the window that the log holds."""
    log, client = records(obs), obs.get("client")
    if log is None or not client:
        return []
    t0, t1 = obs["window"]
    return [(c, log["requests"][c["rid"]]) for c in client["records"]
            if t0 <= c["due"] < t1 and c["rid"] in log["requests"]]


def admitting_steps(obs: dict) -> list[dict]:
    """The window's steps that admitted at least one request and carry their stages."""
    log = records(obs)
    return [s for s in (log or {}).get("steps", ()) if s.get("admitted") and "prefill_ms" in s]
