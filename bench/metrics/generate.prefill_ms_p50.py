"""`generate(max_new_tokens=1)`: the prefill alone, three calls after the
traced run's window, median."""

from bench.stats import percentile

ENTRIES = ("generate",)


def read(run):
    ms = run.extra.get("prefill_ms")
    return percentile(ms, 50) if ms else None
