"""The second phase of an admission: the engine's `first_token.sample`
spans (entry of `_activate` to the first host sync, the sampled first token
read back: the host traces the sampling while the device runs the prefill,
then waits for both), median."""

from bench.stats import percentile

ENTRIES = ("engine",)


def read(run):
    s = run.span_list("first_token.sample")
    return percentile([d * 1e3 for _, d, _ in s], 50) if s else None
