"""Share of its roofline the kernel `paged_decode_attention` reaches in the
decode step, in %: the least time the chip could take for the step's live
PAGES (bench/costs_paged.py: every live page's K and V once a layer, the
query in and the context out a live slot; the larger of bytes over peak
bandwidth and FLOPs over peak FLOP/s), with the live pages and slots from the
`live_pages` and `occupancy` arguments of the traced `decode_step` spans and
the page size from the cell's engine block, over the device time of the
kernel's events inside `engine_decode`, per step. Whole pages are what a
kernel must load, so the share cannot read over 100%. None where the spans
lack the arguments or the trace the kernel."""

from bench import costs, costs_paged

ENTRIES = ("engine",)


def read(run):
    dev = run.device
    if dev is None:
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
        costs_paged.decode_cost(run.hf, page, pages, rows), run.peak)[0]
    return 100.0 * least / (secs / n_steps)
