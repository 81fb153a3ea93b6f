"""The uploads' part of `engine.step.dispatch_ms_p50`: the engine's
`decode.args` spans (the sampling vectors' `jnp.asarray` and the adapters'
gather, before the jitted call), median, ms."""

from bench.stats import percentile

ENTRIES = ("engine",)


def read(run):
    s = run.span_list("decode.args")
    return percentile([d * 1e3 for _, d, _ in s], 50) if s else None
