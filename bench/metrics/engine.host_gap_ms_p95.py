"""As engine.host_gap_ms_p50, p95: the gaps that hold an admission (prefill
plus first-token sampling), which is what the ITL tail is made of."""

from bench.stats import percentile

ENTRIES = ("engine",)


def read(run):
    gaps = run.span_gaps_ms("decode_step")
    return percentile(gaps, 95) if gaps else None
