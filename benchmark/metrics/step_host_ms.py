"""Engine: median, over the window's decode-phase steps, of the step's wall time minus its
``llm.step.drain_wait`` stage (the host blocked on the device's readback): the host's own work
in a step, which is what a step would take on an infinitely fast device."""

from benchmark import flight
from benchmark.stats import median


def read(obs):
    log = flight.records(obs)
    ms = [s["wall_ms"] - s["drain_wait_ms"] for s in (log or {}).get("steps", ())
          if s["phase"] == "decode" and "drain_wait_ms" in s]
    return median(ms) if ms else None
