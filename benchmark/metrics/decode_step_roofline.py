"""Step programs: the least time a decode step can take on the chip's published peaks, over the
device time the trace gives the fused decode program (``decode_device_ms``). The least time is
the larger of bytes over HBM bandwidth and FLOPs over peak, counted by the configuration's family
(``decode_step_least``: every weight outside the routed experts once, the routed experts that
were HIT, the recurrent state of the lanes in use read and written, the keys and values those
lanes hold) from what the program's flight log says of the traced stretch's decode steps: lanes
in use (``moe_pairs_total`` over the experts a token chooses), ``experts_hit``, and the positions
held (``occupied_tokens``). A family without such a count, a program whose log lacks the fields
(the parent of PR 29), or a stretch without a drained decode step: nothing to read."""

from benchmark import common, flight
from statistics import fmean as mean


def read(obs):
    trace = (obs.get("worker") or {}).get("trace") or {}
    log = flight.records(obs)
    if not trace.get("trace_host") or "peaks" not in obs or log is None:
        return None
    least = getattr(common.load_family(obs["config"]["family"]), "decode_step_least", None)
    a, b = trace["trace_host"]
    rows = [s for s in log["steps"] if a <= s["t"] < b and s.get("moe_pairs_total")]
    device_ms = common.load_reader("decode_device_ms")(obs)
    if least is None or not rows or not device_ms:
        return None
    c = obs["config"]
    need = least(c, lanes=mean([s["moe_pairs_total"] for s in rows]) / c["num_experts_per_tok"],
                 experts_hit=mean([s["experts_hit"] for s in rows]),
                 kv_tokens=mean([s["occupied_tokens"] for s in rows]))
    least_s = max(need["bytes"] / obs["peaks"]["hbm_bytes_per_s"], need["flops"] / obs["peaks"]["bf16_flops"])
    return 100.0 * least_s * 1e3 / device_ms
