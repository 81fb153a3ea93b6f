"""The engine's own queue wait (submit to admission, `admit_ts - submit_ts`
of its request records), median."""

from bench.stats import percentile

ENTRIES = ("engine",)


def read(run):
    w = [(r.handle.admit_ts - r.handle.submit_ts) * 1e3 for r in run.requests
         if r.handle is not None and r.handle.admit_ts is not None]
    return percentile(w, 50) if w else None
