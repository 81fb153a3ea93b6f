"""Output tokens delivered inside the window over its length."""

from bench.stats import tokens_in_window


def read(run):
    return tokens_in_window(run.requests, run.t0, run.t1) / (run.t1 - run.t0)
