"""Engine: median host wall time of one ``LLMEngine.step`` over the window's non-idle steps
(flight recorder's step ring, polled during the run)."""

from benchmark.stats import median


def read(obs):
    steps = (obs.get("worker") or {}).get("steps")
    walls = [s[2] for s in steps or () if s[1] != "idle"]
    return median(walls) if walls else None
