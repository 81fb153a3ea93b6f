"""Device idle time an admission costs: the idle seconds of the traced
window whose middle lies in a `prefill` span, over the `prefill` spans that
start in the traced window. In ms. `run.device.idle_gaps` finds the gaps on
the trace's clock and asks for a label at each gap's middle on the
benchmark's clock (`run.device.offset` maps the two)."""

import bisect

ENTRIES = ("engine",)


def idle_ms_per_span(run, name: str):
    """Idle ms of the traced window inside spans called `name`, per such
    span starting in the traced window; None without a device trace or a
    span."""
    dev = run.device
    if dev is None or not dev.busy_s:  # no trace, or no device in it
        return None
    lo, hi = dev.begin + dev.offset, dev.end + dev.offset
    spans = [(t, t + d) for t, d, _ in run.span_list(name)]
    n = sum(1 for t, _ in spans if lo <= t < hi)
    if not n:
        return None

    starts = [a for a, _ in spans]  # in time order, one at a time

    def label_at(t):
        i = bisect.bisect_right(starts, t) - 1
        return i >= 0 and t < spans[i][1]

    inside = dict(map(tuple, dev.idle_gaps(label_at, 2))).get(True, 0.0)
    return inside * 1e3 / n


def read(run):
    return idle_ms_per_span(run, "prefill")
