"""One `TpuModel.generate` call (host clock around it), median."""

from bench.stats import percentile


def read(run):
    ms = [(r.stamps[0] - r.t_sent) * 1e3 for r in run.requests if r.stamps]
    return percentile(ms, 50) if ms else None
