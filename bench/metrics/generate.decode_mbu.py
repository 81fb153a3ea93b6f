"""Bandwidth utilization of generate's decode loop: packed weight bytes
(without the embedding table) over the peak bandwidth, over the time of one
decode step, taken as (generate_ms - prefill_ms) / (new tokens - 1). Host
clock, so launch gaps inside the loop count against it. In %."""

from bench.stats import percentile

ENTRIES = ("generate",)


def read(run):
    pre = run.extra.get("prefill_ms")
    calls = [(r.stamps[0] - r.t_sent) * 1e3 for r in run.requests if r.stamps]
    if not pre or not calls or not run.peak \
            or run.requests[0].max_new < 2:
        return None
    step_s = ((percentile(calls, 50) - percentile(pre, 50)) / 1e3
              / (run.requests[0].max_new - 1))
    return 100.0 * run.weight_bytes / run.peak["hbm_bytes_per_s"] / step_s
