"""Memory-bandwidth utilization of one decode step of a model whose two
groups of pages serve layers of different shape and whose first layer is
dense: the bytes a step must move (the packed parameter tree without the
embedding table and without the experts nobody chose in the SPARSE layers,
from the `moe_experts_hit` argument of the traced `decode_step` spans; the
live pages of both groups, the full layers' up to each row's position and
the window layers' inside the window, from `live_pages_global` and
`live_pages_window` and the cell's page size: bench/costs_groups.py, each
group's depth from `layer_types`) over the peak bandwidth, over the device
time of one `engine_decode` execution in the trace (the median). In %.
`step.decode_window_mbu` reads SmallThinker's key names and counts every
layer sparse. None where the configuration lacks the keys or the spans the
arguments."""

from bench import costs_groups
from bench.stats import percentile

ENTRIES = ("engine",)


def read(run):
    dev = run.device
    if dev is None or not costs_groups.knows(run.hf):
        return None
    execs = dev.program_seconds("engine_decode")
    steps = [a for a in costs_groups.traced_steps(run)
             if "moe_experts_hit" in a]
    if not execs or not steps:
        return None
    need = costs_groups.step_bytes(
        run.hf, run.weight_bytes,
        costs_groups.mean(steps, "moe_experts_hit"),
        costs_groups.mean(steps, "live_pages_global"),
        costs_groups.mean(steps, "live_pages_window"),
        run.cell.config["bench"]["engine"]["page_size"])
    return 100.0 * need / run.peak["hbm_bytes_per_s"] / percentile(execs, 50)
