"""ALL device time, kernels included, of operations whose metadata names no
scope of the vocabulary: what the compiler makes with no name stack, and on a
tree before PR 52 most of XLA's work; per execution of `engine_decode` wholly
inside the traced seconds, mean, ms (`bench/reduce/scopes.py`). The six
`step.scope.*` sum to the execution's busy time."""

from bench.reduce import scopes

ENTRIES = ("engine",)


def read(run):
    acc = scopes.account(run)
    if acc is None:
        return None
    return acc.group_ms("engine_decode", "unscoped")
