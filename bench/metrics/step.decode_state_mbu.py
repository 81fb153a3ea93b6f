"""Memory-bandwidth utilization of one decode step of a model whose slots
hold recurrent state: the bytes a step must move (the packed parameter tree
without the embedding table, read; and the live slots' state, read and
written: the `state_bytes_moved` argument of the traced `decode_step` spans,
the program's own count, which tests/bench holds to bench/costs_retention.py)
over the peak bandwidth, over the device time of one `engine_decode`
execution in the trace. In %. `step.decode_mbu` counts keys and values, which
such a model does not have. None where the spans lack the argument."""

from bench import costs_retention
from bench.stats import percentile

ENTRIES = ("engine",)


def read(run):
    dev = run.device
    if dev is None:
        return None
    execs = dev.program_seconds("engine_decode")
    steps = costs_retention.traced_steps(run)
    if not execs or not steps:
        return None
    moved = sum(a["state_bytes_moved"] for a in steps) / len(steps)
    need = run.weight_bytes + moved
    return 100.0 * need / run.peak["hbm_bytes_per_s"] / percentile(execs, 50)
