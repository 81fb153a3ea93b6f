"""Memory-bandwidth utilization of one decode step of a model that keeps two
groups of pages: the bytes a step must move (the packed parameter tree
without the embedding table and without the experts nobody chose, from the
`moe_experts_hit` argument of the traced `decode_step` spans; the live pages
of both groups, the full layers' up to each row's position and the window
layers' inside the window, from `live_pages_global` and `live_pages_window`
and the cell's page size: bench/costs_window.py) over the peak bandwidth,
over the device time of one `engine_decode` execution in the trace. In %.
`step.decode_mbu` multiplies one count of live pages by every layer and a
dense MLP. None where the spans lack the arguments."""

from bench import costs_window
from bench.stats import percentile

ENTRIES = ("engine",)


def read(run):
    dev = run.device
    if dev is None or "sliding_window_layout" not in run.hf:
        return None
    execs = dev.program_seconds("engine_decode")
    steps = [a for a in costs_window.traced_steps(run)
             if "moe_experts_hit" in a]
    if not execs or not steps:
        return None
    need = costs_window.step_bytes(
        run.hf, run.weight_bytes,
        costs_window.mean(steps, "moe_experts_hit"),
        costs_window.mean(steps, "live_pages_global"),
        costs_window.mean(steps, "live_pages_window"),
        run.cell.config["bench"]["engine"]["page_size"])
    return 100.0 * need / run.peak["hbm_bytes_per_s"] / percentile(execs, 50)
