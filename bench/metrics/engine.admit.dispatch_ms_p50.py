"""The first phase of an admission: the engine's `prefill.dispatch` spans
(admission stamped to the entry of `_activate`: the host pads the prompt,
enqueues the prefill program and books the pages; with chunked prefill,
every chunk), median. A program without these spans reports nothing."""

from bench.stats import percentile

ENTRIES = ("engine",)


def read(run):
    s = run.span_list("prefill.dispatch")
    return percentile([d * 1e3 for _, d, _ in s], 50) if s else None
