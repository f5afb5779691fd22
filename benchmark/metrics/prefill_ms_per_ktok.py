"""Step programs (prefill): device time, in the traced stretch, of the programs that hold the
flash-attention forward kernel (the engine's prefill programs, one per bucket and batch size;
``jit_prefill`` by name where the trace has one) per 1,000 prompt tokens whose prefill ended in
that stretch (flight recorder's admit stamps). Padding to the bucket is in the time, not in the
tokens. PROVISIONAL like ``decode_device_ms``: the trace names no step program, so prefill is known
by the kernel inside it; an extend program that holds the kernel counts as prefill work too."""


def read(obs):
    worker = obs.get("worker") or {}
    trace = worker.get("trace") or {}
    flash = set(trace.get("flash_programs") or ())
    rows = [v for k, v in (trace.get("programs") or {}).items() if k in flash or "prefill" in k]
    if not rows or not trace.get("trace_host"):
        return None
    a, b = trace["trace_host"]
    tokens = sum(r["prompt_tokens"] for r in (worker.get("requests") or {}).values() if a <= (r["admit_t"] or 0) < b)
    return sum(r[1] for r in rows) * 1e3 / (tokens / 1000.0) if tokens else None
