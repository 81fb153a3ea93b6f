"""ALL device time, kernels included, of the scope `engine` (the token gather,
positions, what the step program does around `forward`) and the self time of
the layers' `while`; per execution of `engine_decode` wholly inside the traced
seconds, mean, ms (`bench/reduce/scopes.py`). The six `step.scope.*` sum to the
execution's busy time."""

from bench.reduce import scopes

ENTRIES = ("engine",)


def read(run):
    acc = scopes.account(run)
    return acc.group_ms("engine_decode", "engine") if acc is not None else None
