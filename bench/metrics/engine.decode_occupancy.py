"""Mean share of slots busy over the window's `decode_step` spans, in %."""

ENTRIES = ("engine",)


def read(run):
    s = run.span_list("decode_step")
    if not s:
        return None
    return 100.0 * sum(a["occupancy"] / a["slots"] for _, _, a in s) / len(s)
