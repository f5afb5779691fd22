"""Replica admission: median of the flight recorder's submit -> prefill start over the requests
due in the window."""

from benchmark.stats import median


def read(obs):
    client, worker = obs.get("client"), obs.get("worker") or {}
    if not client or not worker.get("requests"):
        return None
    t0, t1 = obs["window"]
    waits = [worker["requests"][r["rid"]]["queue_wait_s"] * 1e3 for r in client["records"]
             if t0 <= r["due"] < t1 and r["rid"] in worker["requests"]
             and worker["requests"][r["rid"]]["queue_wait_s"] is not None]
    return median(waits) if waits else None
