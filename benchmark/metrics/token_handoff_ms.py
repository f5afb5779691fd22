"""Replica streaming (``serve/llm.py::_stream_tokens``): median, over the requests due in the
window, of the stream generator's first yield minus the engine's first-token emit: the token's
way through ``out_queue`` to the request's thread, under the stepper's GIL. The third of the
four parts ``client_overhead_ms`` subtracts."""

from benchmark import flight
from benchmark.stats import median


def read(obs):
    ms = [(r["first_yield_t"] - r["first_token_t"]) * 1e3 for _, r in flight.due_in_window(obs)
          if r.get("first_yield_t") and r.get("first_token_t")]
    return median(ms) if ms else None
