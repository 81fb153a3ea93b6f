"""ALL device time, kernels included, of the feed-forward's scopes (`ffn*`,
`moe.*`): the router, the dispatch, the experts' kernel, the combine, a shared
or dense MLP; per execution of `engine_decode` wholly inside the traced
seconds, mean, ms (`bench/reduce/scopes.py`). The six `step.scope.*` sum to the
execution's busy time."""

from bench.reduce import scopes

ENTRIES = ("engine",)


def read(run):
    acc = scopes.account(run)
    return acc.group_ms("engine_decode", "ffn") if acc is not None else None
