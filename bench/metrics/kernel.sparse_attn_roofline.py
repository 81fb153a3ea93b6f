"""Share of its roofline the kernel `paged_sparse_decode_attention` reaches in
the decode step of a model with block-sparse attention layers, in %: the
least time the chip could take for a step's sparse attention
(bench/costs_sparse.py: each DISTINCT selected page's K and V once a layer as
the pool stores it, the pooled keys of the live rows once, q in and the
context out; FLOPs for the query heads over the selected keys; the larger of
bytes over peak bandwidth and FLOPs over peak FLOP/s), from the
`sparse_pages_read`, `sparse_pages_selected`, `sparse_pages_live` and
`occupancy` arguments of the traced `decode_step` spans and the page size of
the cell's engine block, over the device time of the kernel's events inside
`engine_decode`, per step. What the selection costs around the kernel (XLA's
scores, top-k and list) is in the step and not in the kernel's time, and a
page is counted once however many heads read it, so the share cannot read
over 100%. None where the configuration lacks the keys, the spans the
arguments or the trace the kernel."""

from bench import costs, costs_sparse

ENTRIES = ("engine",)


def read(run):
    dev = run.device
    if dev is None or not costs_sparse.knows(run.hf):
        return None
    n_steps, secs = dev.kernel_in_program("paged_sparse_decode_attention",
                                          "engine_decode")
    steps = costs_sparse.traced_steps(run)
    if not n_steps or not secs or not steps:
        return None
    least = costs.roofline_seconds(costs_sparse.attn_cost(
        run.hf, run.cell.config["bench"]["engine"]["page_size"],
        costs_sparse.mean(steps, "sparse_pages_read"),
        costs_sparse.mean(steps, "sparse_pages_selected"),
        costs_sparse.mean(steps, "sparse_pages_live"),
        costs_sparse.mean(steps, "occupancy")), run.peak)[0]
    return 100.0 * least / (secs / n_steps)
