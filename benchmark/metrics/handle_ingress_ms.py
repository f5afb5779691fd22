"""Runtime + handle streaming, the way in: median, over the requests due in the window, of the
replica's ingress stamp (``OpenAIServer.__call__`` entered) minus the client's ``sent`` (taken
just before ``handle.remote``). The first of the four parts ``client_overhead_ms`` subtracts."""

from benchmark import flight
from benchmark.stats import median


def read(obs):
    ms = [(r["ingress_t"] - c["sent"]) * 1e3 for c, r in flight.due_in_window(obs) if r.get("ingress_t")]
    return median(ms) if ms else None
