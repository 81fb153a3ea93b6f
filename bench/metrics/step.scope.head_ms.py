"""ALL device time, kernels included, of the head's scopes (`lm_head`,
`sample`, `block.reveal`, `block.store`): the LM head and what chooses a token
from it; per execution of `engine_decode` wholly inside the traced seconds,
mean, ms (`bench/reduce/scopes.py`). The six `step.scope.*` sum to the
execution's busy time."""

from bench.reduce import scopes

ENTRIES = ("engine",)


def read(run):
    acc = scopes.account(run)
    return acc.group_ms("engine_decode", "head") if acc is not None else None
