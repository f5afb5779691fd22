"""Runtime + handle streaming, per token: 95th percentile, over the consecutive tokens of the
requests due in the window, of the client's gap minus the engine's gap (``itl_s``) for the same
pair of tokens: what the path from the engine's emit to the client adds to, or takes from, a gap."""

from benchmark import flight
from benchmark.stats import percentile


def read(obs):
    added = []
    for c, r in flight.due_in_window(obs):
        gaps = [b - a for a, b in zip(c["stamps"], c["stamps"][1:])]
        added += [(g - e) * 1e3 for g, e in zip(gaps, r.get("itl_s") or ())]
    return percentile(added, 95.0) if added else None
