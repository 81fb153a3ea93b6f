"""The part of a steady device gap between the end of `engine.step` N and the
start of `decode_step` N+1: the caller's loop, `step.reap`, `step.admit`,
`step.pages`; median, ms (`bench/reduce/steps.py`)."""

from bench.reduce import steps

ENTRIES = ("engine",)


def read(run):
    acc = steps.account(run)
    return acc.median_ms("schedule") if acc is not None else None
