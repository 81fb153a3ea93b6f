"""Memory-bandwidth utilization of one decode step of a hybrid whose slots
hold a convolution's tail beside KV pages: the bytes a step must move (the
packed parameter tree without the embedding table and without the experts
nobody chose, from the `moe_experts_hit` argument of the traced `decode_step`
spans; the live slots' tails, read and written, the `state_bytes_moved`
argument, the program's own count, which tests/bench holds to
bench/costs_conv.py; the live pages' keys and values over the FIVE attention
layers, from `live_pages` and the cell's page size) over the peak bandwidth,
over the device time of one `engine_decode` execution in the trace. In %.
`step.decode_ssm_mbu` is the same account for a model with experts in every
layer (`num_local_experts`) and a recurrence state; `step.decode_mbu` counts
keys and values and a dense MLP in every layer. None where the configuration
lacks the keys or the spans the arguments."""

from bench import costs_conv
from bench.stats import percentile

ENTRIES = ("engine",)


def read(run):
    dev = run.device
    if dev is None or not costs_conv.knows(run.hf):
        return None
    execs = dev.program_seconds("engine_decode")
    steps = [a for a in costs_conv.traced_steps(run)
             if "moe_experts_hit" in a and "live_pages" in a]
    if not execs or not steps:
        return None
    n = len(steps)
    need = costs_conv.step_bytes(
        run.hf, run.weight_bytes,
        sum(a["moe_experts_hit"] for a in steps) / n,
        sum(a["state_bytes_moved"] for a in steps) / n,
        sum(a["live_pages"] for a in steps) / n,
        run.cell.config["bench"]["engine"]["page_size"])
    return 100.0 * need / run.peak["hbm_bytes_per_s"] / percentile(execs, 50)
