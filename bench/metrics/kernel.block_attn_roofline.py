"""Share of its roofline the kernel `paged_decode_attention` reaches in a
PASS of a model generated by diffusion over blocks, whose b query positions a
row are b x G rows of each KV head's dot, in %: the least time the chip could
take for the pass's attention (bench/costs_block.py: every live page's K and
V once a layer, whole; the block's queries in and its context out for
`occupancy x b` query positions; FLOPs for b queries a slot; the larger of
bytes over peak bandwidth and FLOPs over peak FLOP/s), from the `live_pages`
and `occupancy` arguments of the traced `decode_step` spans and the page size
of the cell's engine block, over the device time of the kernel's events
inside `engine_decode`, per pass. The pages are counted once for all b
positions, so serving them from one load cannot read over 100%. None where
the configuration lacks the keys, the spans the arguments or the trace the
kernel."""

from bench import costs, costs_block

ENTRIES = ("engine",)


def read(run):
    dev = run.device
    if dev is None or not costs_block.knows(run.hf):
        return None
    n_steps, secs = dev.kernel_in_program("paged_decode_attention",
                                          "engine_decode")
    steps = costs_block.traced_steps(run)
    if not n_steps or not secs or not steps:
        return None
    least = costs.roofline_seconds(costs_block.attn_cost(
        run.hf, run.cell.config["bench"]["engine"]["page_size"],
        costs_block.mean(steps, "live_pages"),
        costs_block.mean(steps, "occupancy")), run.peak)[0]
    return 100.0 * least / (secs / n_steps)
