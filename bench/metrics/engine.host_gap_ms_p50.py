"""Host time between decode steps: end of one `decode_step` span to the
start of the next (TraceRecorder), median. Admissions sit in these gaps."""

from bench.stats import percentile

ENTRIES = ("engine",)


def read(run):
    gaps = run.span_gaps_ms("decode_step")
    return percentile(gaps, 50) if gaps else None
