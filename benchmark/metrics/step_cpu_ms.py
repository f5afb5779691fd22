"""Engine: mean, over the window's decode-phase steps, of the step row's ``cpu_ms``: the stepping thread's own
CPU time (``time.thread_time()``) between the step's two ends. Beside ``step_host_ms`` (the same steps' wall time
less the wait for the device) it says how much of the host's part of a step is work, and how much is a thread
that is runnable and waits for a processor or for the interpreter. The MEAN, where its neighbours are medians:
the thread clock of the machines with the chip ticks in 10 ms (my chip run, PR 55: every ``cpu_ms`` of 7,550 rows
a multiple of 10), so a step of one millisecond reads 0 or 10 and the median of such steps reads 0.0 whatever
they cost, while the ticks a thread is charged add up to its time: the mean over some hundreds of steps is right
to a few percent. None against a program whose step rows carry no ``cpu_ms`` (before PR 55)."""

from benchmark import flight


def read(obs):
    log = flight.records(obs)
    ms = [s["cpu_ms"] for s in (log or {}).get("steps", ()) if s["phase"] == "decode" and s.get("cpu_ms") is not None]
    return sum(ms) / len(ms) if ms else None
