"""Engine: the milliseconds the window's steps stood still inside a blocking read of the device, beyond what
such a read takes. Reads the step rows' ``drain_wait_ms`` and ``first_token_wait_ms`` (the two stages that
block on the device) and ``prefill_tokens_padded`` (what the step admitted, as padded; absent for a step that
admitted nothing).

A step is STALLED where one of the two reads took more than 1,000 ms AND more than four times the median of
that column over the window's steps with the same ``prefill_tokens_padded``: a 16k group's first tokens take
1.4 s every time and are no stall, a 3.5k group's 3 s among waits of 100 ms are one. Where fewer than three
steps share the value, the window's median milliseconds a padded token (of that column) times the step's own
padded tokens stands in for the median. The metric is the sum, over the stalled reads, of the read less that
median: 0.0 in a window without one (a value, not a missing reading). None without a flight log. In a closed
loop on a device that is never idle those milliseconds are lost throughput one to one (ROADMAP.md A19); the
sentinel's captures of the same steps (the log's ``stalls`` section) say why."""

from benchmark import flight
from benchmark.stats import median

READS = ("drain_wait_ms", "first_token_wait_ms")
FLOOR_MS, TIMES = 1000.0, 4.0


def read(obs):
    log = flight.records(obs)
    steps = [s for s in (log or {}).get("steps", ()) if all(col in s for col in READS)]
    if not steps:
        return None
    excess = 0.0
    for col in READS:
        alike: dict = {}
        for s in steps:
            alike.setdefault(s.get("prefill_tokens_padded"), []).append(s[col])
        per_token = [s[col] / s["prefill_tokens_padded"] for s in steps if s.get("prefill_tokens_padded")]
        for s in steps:
            padded = s.get("prefill_tokens_padded")
            if s[col] <= FLOOR_MS:
                continue
            if len(alike[padded]) >= 3 or not (padded and per_token):
                usual = median(alike[padded])
            else:
                usual = median(per_token) * padded
            if s[col] > TIMES * usual:
                excess += s[col] - usual
    return excess
