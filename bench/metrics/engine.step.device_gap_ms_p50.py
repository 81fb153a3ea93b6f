"""The device's idle time from the end of one `engine_decode` execution to
the start of the next, median over the traced window's steady gaps (no
admission in them, a request in flight), in ms: the trace's own clock, no
tie. `bench/reduce/steps.py` finds the gaps; the four `engine.step.gap.*`
shares sum to it gap by gap."""

from bench.reduce import steps

ENTRIES = ("engine",)


def read(run):
    acc = steps.account(run)
    return acc.median_ms("gap") if acc is not None else None
