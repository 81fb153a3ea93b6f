"""Device idle time inside a decode step: the idle seconds of the traced
window whose middle lies in a `decode_step` span, over the `decode_step`
spans that start in the traced window. In ms. The arithmetic is
engine.admit.idle_ms's, on another span."""

ENTRIES = ("engine",)


def read(run):
    shared = run.cell.reader("engine.admit.idle_ms")
    return shared.idle_ms_per_span(run, "decode_step")
