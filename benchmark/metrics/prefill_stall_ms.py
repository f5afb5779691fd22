"""Engine: median, over the window's steps that admitted, of the ``llm.step.prefill`` stage (the
prefill programs and their blocking first-token readbacks): how long one admission wave holds
every running stream, the stalled gap behind the ITL tail."""

from benchmark import flight
from benchmark.stats import median


def read(obs):
    ms = [s["prefill_ms"] for s in flight.admitting_steps(obs)]
    return median(ms) if ms else None
