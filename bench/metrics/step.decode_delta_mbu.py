"""Memory-bandwidth utilization of one decode step of a hybrid whose slots
hold a delta rule's matrix state beside KV pages and whose layers hold one
rank's share of the experts: the bytes a step must move (the packed
parameter tree without the embedding table and without the HELD experts
nobody chose, from the `moe_experts_hit` argument of the traced `decode_step`
spans, which counts the experts held and hit here; the live slots' state
rows, read and written, the `state_bytes_moved` argument, the program's own
count, which tests/bench holds to bench/costs_delta.py; the live pages' keys
and values over the GQA layers, from `live_pages` and the cell's page size)
over the peak bandwidth, over the device time of one `engine_decode`
execution in the trace. In %. `step.decode_conv_mbu` is the same account for
a model whose state is a convolution's tail alone. None where the
configuration lacks the keys or the spans the arguments."""

from bench import costs_delta
from bench.stats import percentile

ENTRIES = ("engine",)


def read(run):
    dev = run.device
    if dev is None or not costs_delta.knows(run.hf):
        return None
    execs = dev.program_seconds("engine_decode")
    steps = [a for a in costs_delta.traced_steps(run)
             if "moe_experts_hit" in a and "live_pages" in a]
    if not execs or not steps:
        return None
    n = len(steps)
    need = costs_delta.step_bytes(
        run.hf, run.weight_bytes,
        sum(a["moe_experts_hit"] for a in steps) / n,
        sum(a["state_bytes_moved"] for a in steps) / n,
        sum(a["live_pages"] for a in steps) / n,
        run.cell.config["bench"]["engine"]["page_size"])
    return 100.0 * need / run.peak["hbm_bytes_per_s"] / percentile(execs, 50)
