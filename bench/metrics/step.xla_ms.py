"""Device time of a decode step that is NO Mosaic kernel: the self time of
every XLA operation of an `engine_decode` execution, whatever its scope
(`fusion`, `copy`, `sort`, what is left of a `while`); per execution wholly
inside the traced seconds, mean, ms (`bench/reduce/scopes.py`). ROADMAP
S15's number: what a `perf_opt` on XLA around the kernels drives."""

from bench.reduce import scopes

ENTRIES = ("engine",)


def read(run):
    acc = scopes.account(run)
    return acc.xla_ms("engine_decode") if acc is not None else None
