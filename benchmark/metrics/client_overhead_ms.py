"""Runtime + handle streaming: median, over the window's finished requests, of the client's time
to first token (first streamed token received - sent) minus the flight recorder's
submit -> first token for the same request id. What the path around the engine adds."""

from benchmark.stats import median


def read(obs):
    client, worker = obs.get("client"), obs.get("worker") or {}
    if not client or not worker.get("requests"):
        return None
    t0, t1 = obs["window"]
    diffs = []
    for r in client["records"]:
        rec = worker["requests"].get(r["rid"])
        if rec and r["stamps"] and t0 <= r["due"] < t1 and rec["first_token_t"]:
            diffs.append(((r["stamps"][0] - r["sent"]) - (rec["first_token_t"] - rec["submit_t"])) * 1e3)
    return median(diffs) if diffs else None
