"""Gap between consecutive tokens of one request as the client receives
them, pooled over all requests attempted in the window; p95."""

from bench.stats import percentile, pooled_gaps


def read(run):
    gaps = pooled_gaps(run.requests)
    return percentile(gaps, 95) * 1e3 if gaps else None
