"""Seconds JAX spent tracing, lowering and compiling (or loading from the
persistent cache) before the window, from jax.monitoring."""


def read(run):
    return run.setup.get("compile_s")
