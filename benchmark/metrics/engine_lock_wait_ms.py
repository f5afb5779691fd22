"""Replica admission, inside ``replica_ingress_ms``: median, over the requests due in the window, of the
request record's ``lock_wait_s`` x 1,000: how long the admitting thread waited for the engine's lock, which a
step holds from end to end, its waits for the device included. Beside ``replica_ingress_ms`` (submit stamp less
ingress stamp: parse, encode, admission check AND this wait) it says how much of that lump is the lock. None
against a program whose request records carry no ``lock_wait_s`` (before PR 55)."""

from benchmark import flight
from benchmark.stats import median


def read(obs):
    ms = [r["lock_wait_s"] * 1e3 for _, r in flight.due_in_window(obs) if r.get("lock_wait_s") is not None]
    return median(ms) if ms else None
