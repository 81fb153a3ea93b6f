"""As `step.xla_ms`, for `engine_paged_prefill`: the device time of a
prefill that is no Mosaic kernel, per execution wholly inside the traced
seconds, mean, ms (`bench/reduce/scopes.py`)."""

from bench.reduce import scopes

ENTRIES = ("engine",)


def read(run):
    acc = scopes.account(run)
    return acc.xla_ms("engine_paged_prefill") if acc is not None else None
