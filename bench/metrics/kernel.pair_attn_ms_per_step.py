"""Device time of the `paged_decode_attention` kernel per decode step of a
model whose KV heads of 64 lie as lane pairs (all its attention layers), from
the trace: `kernel.paged_attn_ms_per_step`'s reading under this cell's own
name (that entry's list is held to the cells whose roofline counts pages in
EVERY layer). None for a program without the kernel."""

ENTRIES = ("engine",)


def read(run):
    return run.cell.reader("kernel.paged_attn_ms_per_step").read(run)
