"""Device time of the `kda_decode` kernel per decode step (all Kimi delta
attention layers), from the trace. None for a program without the kernel."""

ENTRIES = ("engine",)


def read(run):
    dev = run.device
    if dev is None:
        return None
    n_steps, secs = dev.kernel_in_program("kda_decode", "engine_decode")
    return secs / n_steps * 1e3 if n_steps and secs else None
