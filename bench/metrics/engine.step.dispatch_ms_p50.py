"""The host's part of a decode step before the device can start: the
engine's `decode.dispatch` spans (the step's start to the return of the
jitted call: five sampling arrays uploaded, the adapters gathered, the
jit's cache looked up, the program enqueued), median."""

from bench.stats import percentile

ENTRIES = ("engine",)


def read(run):
    s = run.span_list("decode.dispatch")
    return percentile([d * 1e3 for _, d, _ in s], 50) if s else None
