"""The part of a steady device gap after `decode_step` N+1 has started:
`decode.args` and what passes of `decode.call` before the device starts;
median, ms (`bench/reduce/steps.py`). Read after the device's plane is moved
by the least that causality asks (no execution starts before its call is
entered): AT LEAST this much; `drain` has the rest."""

from bench.reduce import steps

ENTRIES = ("engine",)


def read(run):
    acc = steps.account(run)
    return acc.median_ms("launch") if acc is not None else None
