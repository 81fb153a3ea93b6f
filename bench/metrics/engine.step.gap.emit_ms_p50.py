"""The part of a steady device gap that lies in `step.emit` of step N: the
per-slot loop that advances, emits and finishes; median, ms
(`bench/reduce/steps.py`)."""

from bench.reduce import steps

ENTRIES = ("engine",)


def read(run):
    acc = steps.account(run)
    return acc.median_ms("emit") if acc is not None else None
