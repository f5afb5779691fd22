"""Kernels: the selective scan's share of its roofline in the traced stretch's prefills. The least
time the recurrence of the Mamba-1 layers can take for the prompts admitted in the stretch at their
TRUE lengths, over all such layers held (the configuration's family counts it,
``selective_scan_least``: the convolution's output read and ``y`` written once at the stream's
width, the step's low-rank source, B and C read once, a sequence's state written once; 7 FLOPs a
(position, channel, state); the larger of bytes over HBM bandwidth and FLOPs over the bf16 peak),
over the device time under the scope ``mamba1.scan`` in the programs with ``prefill`` in their name
(``benchmark/scopes.py``). It reads the same work whatever runs the recurrence under that scope: a
kernel, or XLA's scan where the kernel is refused. Padding to the bucket is in the time, not in the
least.

What the share is a share OF: ``peaks.py`` holds no published peak for the chip's vector unit, on
which all of this work runs (an exponential and a handful of multiply-adds for each of 81,920
(channel, state) pairs a position at Jamba2-3B's widths), so the FLOPs are held against the bf16
matmul peak, which they cannot reach, and at these widths the larger term is the BYTES: 21 KB a
position and layer, 0.026 us at 819 GB/s. A kernel at the vector unit's own pace (0.2-0.3 us a
position and layer by ISSUE 60's count) reads about 10% here. The share cannot read over 100%
whatever implements the scan, and it rises when the scan gets faster. A family without such a
count, a program without the scope, or a stretch that admitted nothing: nothing to read."""

from benchmark import common, scopes


def read(obs):
    s = scopes.summary(obs)
    if not s or "peaks" not in obs:
        return None
    least = getattr(common.load_family(obs["config"]["family"]), "selective_scan_least", None)
    secs = scopes.scope_seconds(s, "prefill", "mamba1.scan")
    if least is None or not secs:
        return None
    a, b = obs["worker"]["trace"]["trace_host"]
    lengths = [r["prompt_tokens"] for r in (obs["worker"].get("requests") or {}).values() if a <= (r["admit_t"] or 0) < b]
    if not lengths:
        return None
    need = least(obs["config"], tokens=sum(lengths), sequences=len(lengths))
    return 100.0 * max(need["bytes"] / obs["peaks"]["hbm_bytes_per_s"], need["flops"] / obs["peaks"]["bf16_flops"]) / secs
