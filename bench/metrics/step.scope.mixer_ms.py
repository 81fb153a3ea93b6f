"""ALL device time, kernels included, of the mixer's scopes (`attn*`, `mamba2`,
the two `*_prefill`): the projections, the rotation, the cache write, the
attention, retention or state kernel and what XLA does between them; per
execution of `engine_decode` wholly inside the traced seconds, mean, ms
(`bench/reduce/scopes.py`). The six `step.scope.*` sum to the execution's busy
time."""

from bench.reduce import scopes

ENTRIES = ("engine",)


def read(run):
    acc = scopes.account(run)
    return acc.group_ms("engine_decode", "mixer") if acc is not None else None
