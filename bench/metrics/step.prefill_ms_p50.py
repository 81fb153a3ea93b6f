"""The engine's `prefill` spans (admission to first token sampled: the
prefill program plus the host's first-token sampling), median."""

from bench.stats import percentile

ENTRIES = ("engine",)


def read(run):
    s = run.span_list("prefill")
    return percentile([d * 1e3 for _, d, _ in s], 50) if s else None
