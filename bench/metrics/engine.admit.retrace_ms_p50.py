"""What JAX's tracing, lowering and compiling or loading cost one
admission: the `retrace_s` the engine's own counter (obs/retrace.py) put on
a request's three child spans (`prefill.dispatch`, `first_token.sample`,
`first_token.arm`), summed per request, median over the admissions of the
window. In ms."""

from bench.stats import percentile

ENTRIES = ("engine",)

PHASES = ("prefill.dispatch", "first_token.sample", "first_token.arm")


def read(run):
    paid = {}
    for name in PHASES:
        for _, _, args in run.span_list(name):
            if "retrace_s" in args:
                paid[args["rid"]] = paid.get(args["rid"], 0.0) \
                    + args["retrace_s"]
    return percentile([s * 1e3 for s in paid.values()], 50) if paid else None
