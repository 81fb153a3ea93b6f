"""Process start to the first measured instant: loading, weights, warming up
and, in a run that compiles, compilation."""


def read(run):
    return run.setup["setup_s"]
