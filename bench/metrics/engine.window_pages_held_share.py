"""Window pages the live rows hold over the pages the same rows would hold
had none been freed, in %: mean over the window's `decode_step` spans of
`window_pages_held / window_pages_unfreed` (the second is the rows' global
group, which is booked alike and never freed). What the allocator gives
back while requests still decode; 100 where no context passes the window.
None where the program's spans lack the arguments."""

ENTRIES = ("engine",)


def read(run):
    s = [a for _, _, a in run.span_list("decode_step")
         if a.get("window_pages_unfreed") and "window_pages_held" in a]
    if not s:
        return None
    return 100.0 * sum(a["window_pages_held"] / a["window_pages_unfreed"]
                       for a in s) / len(s)
