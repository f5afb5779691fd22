"""Collectives: device time of all-reduce operations per engine step (tensor parallel cells only;
one chip has none and the reader returns nothing)."""


def read(obs):
    worker = obs.get("worker") or {}
    kinds = (worker.get("trace") or {}).get("op_kinds") or {}
    secs = sum(v[1] for k, v in kinds.items() if "all-reduce" in k or "all_reduce" in k)
    a, b = (worker.get("trace") or {}).get("trace_host") or (0, 0)
    steps = sum(1 for s in worker.get("steps") or () if a <= s[0] < b and s[1] != "idle")
    return secs / steps * 1e3 if secs and steps else None
