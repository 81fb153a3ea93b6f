"""The part of a steady device gap before `decode_step` N has ended: what is
left of `decode.wait` once the device is done, and `decode.read`; median, ms
(`bench/reduce/steps.py`). Read after the device's plane is moved by the least
that causality asks: AT MOST this much; `launch` has the rest."""

from bench.reduce import steps

ENTRIES = ("engine",)


def read(run):
    acc = steps.account(run)
    return acc.median_ms("drain") if acc is not None else None
