"""Engine: median, over the window's steps that admitted, of the time from the end of the
``llm.step.prefill`` stage (its last readback returned, so the device's queue is empty) to
``dispatch_t`` (the next fused step enqueued): the device idles for at least that long."""

from benchmark import flight
from benchmark.stats import median


def read(obs):
    ms = [(s["dispatch_t"] - s["t0"]) * 1e3 - s["admission_ms"] - s["prefill_ms"]
          for s in flight.admitting_steps(obs) if s.get("dispatch_t")]
    return median(ms) if ms else None
