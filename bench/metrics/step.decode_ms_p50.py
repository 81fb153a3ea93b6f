"""The engine's `decode_step` spans (dispatch to the host sync), median."""

from bench.stats import percentile

ENTRIES = ("engine",)


def read(run):
    s = run.span_list("decode_step")
    return percentile([d * 1e3 for _, d, _ in s], 50) if s else None
