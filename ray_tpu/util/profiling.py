"""Profiling helpers: jax.profiler capture around jitted regions.

Reference parity gap (SURVEY §5.1): the reference ships py-spy/torch
profiler plumbing; the TPU-native equivalents are XLA's profiler traces
(TensorBoard-viewable, or read with ``jax.profiler.ProfileData``).

    with profile_trace("/tmp/tb"):        # device trace + host annotations
        step(state, batch)

Only the process that holds a chip can trace it: a serving replica is
traced through ``LLMServer.profile`` (serve/llm.py), which calls
``start_trace``/``stop_trace`` here inside the replica.
"""

from __future__ import annotations

import contextlib

# The host tracer level that records ``jax.profiler.TraceAnnotation``s
# (the serving step's ``llm.step.*`` stages, llm/telemetry.py) and no
# finer host events. The Python tracer stays off: with both the host
# tracer at its default and the Python tracer on, stopping a trace of a
# loaded replica stalled it for tens of seconds (PERF.md, PR 23).
ANNOTATION_LEVEL = 1


def start_trace(logdir: str, host_tracer_level: int = ANNOTATION_LEVEL) -> None:
    """Start a jax.profiler trace into ``logdir``: device planes, host
    annotations at ``host_tracer_level`` (0 = device planes only), the
    Python tracer off."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = int(host_tracer_level)
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)


def stop_trace() -> None:
    import jax

    jax.profiler.stop_trace()


@contextlib.contextmanager
def profile_trace(logdir: str, host_tracer_level: int = ANNOTATION_LEVEL):
    """Trace the block; view with tensorboard --logdir."""
    start_trace(logdir, host_tracer_level)
    try:
        yield logdir
    finally:
        stop_trace()


def start_profiler_server(port: int = 9999):
    """On-demand capture endpoint (tensorboard 'capture profile')."""
    import jax

    jax.profiler.start_server(port)
    return port
