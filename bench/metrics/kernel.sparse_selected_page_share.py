"""Pages READ over pages LIVE in the sparse layers of the decode steps, in %:
`sparse_pages_read` (the distinct pages the sparse decode kernel DMA'd, both
KV heads' choices joined, summed over the sparse layers) over
`sparse_pages_live` (every live page a sparse layer: what dense attention
would read) of the `decode_step` spans, summed over the spans. What the
selection saves; 100 would be dense, and rows below `dense_len` read all
their pages. None for a program whose steps select nothing."""

from bench import costs_sparse

ENTRIES = ("engine",)


def read(run):
    steps = costs_sparse.traced_steps(run)
    live = sum(a["sparse_pages_live"] for a in steps)
    if not live:
        return None
    return 100.0 * sum(a["sparse_pages_read"] for a in steps) / live
