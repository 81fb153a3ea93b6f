"""Share of its roofline the kernel `paged_decode_attention` reaches in the
decode step of a hybrid whose FEW attention layers keep KV heads of 64 as
lane pairs, in %: the least time the chip could take for the step's live
PAGES over the attention layers alone (bench/costs_conv.py: every live
page's K and V once a layer at the published 8 x 64, the query in and the
context out a live slot; the larger of bytes over peak bandwidth and FLOPs
over peak FLOP/s), with the live pages and slots from the `live_pages` and
`occupancy` arguments of the traced `decode_step` spans and the page size
from the cell's engine block, over the device time of the kernel's events
inside `engine_decode`, per step. `kernel.paged_attn_roofline` counts
`num_hidden_layers` layers of pages, four times this model's. Whole pages are
what a kernel must load and the padded half of a query is not counted, so the
share cannot read over 100%. None where the configuration lacks the keys, the
spans the arguments or the trace the kernel."""

from bench import costs, costs_conv, costs_paged

ENTRIES = ("engine",)


def read(run):
    dev = run.device
    if dev is None or not costs_conv.knows(run.hf):
        return None
    n_steps, secs = dev.kernel_in_program("paged_decode_attention",
                                          "engine_decode")
    steps = costs_paged.traced_steps(run)
    if not n_steps or not secs or not steps:
        return None
    pages = sum(a["live_pages"] for a in steps) / len(steps)
    rows = sum(a["occupancy"] for a in steps) / len(steps)
    page = run.cell.config["bench"]["engine"]["page_size"]
    least = costs.roofline_seconds(
        costs_conv.attn_decode_cost(run.hf, page, pages, rows), run.peak)[0]
    return 100.0 * least / (secs / n_steps)
