"""ALL device time, kernels included, of the scope `norm`: layer norms, the
final norm, the residual adds that fuse with them; per execution of
`engine_decode` wholly inside the traced seconds, mean, ms
(`bench/reduce/scopes.py`). The six `step.scope.*` sum to the execution's busy
time."""

from bench.reduce import scopes

ENTRIES = ("engine",)


def read(run):
    acc = scopes.account(run)
    return acc.group_ms("engine_decode", "norm") if acc is not None else None
