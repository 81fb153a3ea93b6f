"""Device time of the `mamba2_decode` kernel per decode step (all Mamba
layers), from the trace. None for a program without the kernel."""

ENTRIES = ("engine",)


def read(run):
    dev = run.device
    if dev is None:
        return None
    n_steps, secs = dev.kernel_in_program("mamba2_decode", "engine_decode")
    return secs / n_steps * 1e3 if n_steps and secs else None
