"""Host seconds JAX spent tracing, lowering and loading programs INSIDE the
window, per request attempted. The engine samples every first token through
an un-jitted `lax.cond`, which is traced, lowered and fetched from the
compile cache again for each request (PERF.md); this is that cost. In ms."""

ENTRIES = ("engine",)


def read(run):
    if not run.requests or run.compile_log is None:
        return None
    return (run.compile_log.seconds(run.t0, run.t1) * 1e3
            / len(run.requests))
