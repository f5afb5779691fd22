"""Runtime + handle streaming, the way out: median, over the requests due in the window, of the
client's first token stamp minus the stream generator's first yield in the replica: replica
streaming, object refs, the handle. The last of the four parts ``client_overhead_ms`` subtracts."""

from benchmark import flight
from benchmark.stats import median


def read(obs):
    ms = [(c["stamps"][0] - r["first_yield_t"]) * 1e3 for c, r in flight.due_in_window(obs)
          if c["stamps"] and r.get("first_yield_t")]
    return median(ms) if ms else None
