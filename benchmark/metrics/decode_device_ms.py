"""Step programs: device time per call of the fused decode program in the trace's ``XLA Modules``
line. PROVISIONAL until the step programs have stable names (PERF.md, Open questions): the engine
jits them from a ``functools.partial``, so the trace knows prefill, decode and extend alike as
``jit__unknown(<fingerprint>)``. The decode program is taken to be the one without a
flash-attention kernel inside that ran most often (one call per engine step); a program named
``*fused*`` is taken by name. Where a second such program ran at least half as often, the reader
does not guess: it raises, and the run fails."""


def read(obs):
    trace = (obs.get("worker") or {}).get("trace") or {}
    flash = set(trace.get("flash_programs") or ())
    programs = trace.get("programs") or {}
    rows = {k: v for k, v in programs.items() if "fused" in k} or \
           {k: v for k, v in programs.items() if "unknown" in k and k not in flash}
    if not rows:
        return None
    ranked = sorted(rows.items(), key=lambda kv: -kv[1][0])
    if len(ranked) > 1 and 2 * ranked[1][1][0] >= ranked[0][1][0]:
        raise ValueError(f"decode_device_ms: more than one candidate for the decode program: {ranked[:3]}")
    calls, secs = ranked[0][1]
    return secs / calls * 1e3
