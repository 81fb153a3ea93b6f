"""Share of its roofline the kernel `paged_decode_attention` reaches in the
decode step of a model whose two groups of pages serve layers of different
SHAPE, in %: the least time the chip could take for the pages the step must
load BY GROUP at each group's OWN query heads (bench/costs_groups.py: the
full layers, by `layer_types`, times the global group's live pages plus the
window layers times the window group's, whole pages, the query in and the
context out a live slot and head, heads from `num_attention_heads_per_layer`;
the larger of bytes over peak bandwidth and FLOPs over peak FLOP/s), from the
`live_pages_global`, `live_pages_window` and `occupancy` arguments of the
traced `decode_step` spans and the page size of the cell's engine block, over
the device time of the kernel's events inside `engine_decode`, per step.
Counted by the pages a kernel must load, so skipping the pages behind the
window cannot read over 100%. None where the configuration lacks the keys,
the spans the arguments or the trace the kernel."""

from bench import costs, costs_groups

ENTRIES = ("engine",)


def read(run):
    dev = run.device
    if dev is None or not costs_groups.knows(run.hf):
        return None
    n_steps, secs = dev.kernel_in_program("paged_decode_attention",
                                          "engine_decode")
    steps = costs_groups.traced_steps(run)
    if not n_steps or not secs or not steps:
        return None
    least = costs.roofline_seconds(costs_groups.attn_cost(
        run.hf, run.cell.config["bench"]["engine"]["page_size"],
        costs_groups.mean(steps, "live_pages_global"),
        costs_groups.mean(steps, "live_pages_window"),
        costs_groups.mean(steps, "occupancy")), run.peak)[0]
    return 100.0 * least / (secs / n_steps)
