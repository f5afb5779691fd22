"""Replica admission, before the queue: median, over the requests due in the window, of the
engine's submit stamp minus the replica's ingress stamp: body parsed, prompt encoded, admission
checked, ``add_request`` taken. The second of the four parts ``client_overhead_ms`` subtracts."""

from benchmark import flight
from benchmark.stats import median


def read(obs):
    ms = [(r["submit_t"] - r["ingress_t"]) * 1e3 for _, r in flight.due_in_window(obs) if r.get("ingress_t")]
    return median(ms) if ms else None
