"""Step programs: lowerings (``jax.monitoring`` compile listener in the worker) inside the
window. Expected 0; anything else names a shape the warm-up missed."""


def read(obs):
    n = (obs.get("worker") or {}).get("compiles_in_window")
    return None if n is None else float(n)
