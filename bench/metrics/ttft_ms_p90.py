"""Time to first token, p90 over every request attempted in the window: from
the instant it was DUE (open loop) or sent (closed loop) to its first token
leaving the stream. A failed request waits the whole allowance."""

from bench.stats import ttft_percentile_ms


def read(run):
    return ttft_percentile_ms(run, 90)
