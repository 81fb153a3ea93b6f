"""Share of the paged decode kernel's grid (slots x pages per row) that
holds live KV, in %: mean of `live_pages / grid_pages` over the window's
`decode_step` spans. A property of the traffic; the rest of the grid is
what the kernel skips. None where the program's spans lack the arguments."""

ENTRIES = ("engine",)


def read(run):
    s = [a for _, _, a in run.span_list("decode_step")
         if a.get("grid_pages") and "live_pages" in a]
    if not s:
        return None
    return 100.0 * sum(a["live_pages"] / a["grid_pages"] for a in s) / len(s)
