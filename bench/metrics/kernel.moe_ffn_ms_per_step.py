"""Device time of the grouped expert kernel `moe_qmatmul` per decode step
(all layers: the gated first half and the down projection), from the trace.
None for a program whose experts are not on that kernel."""

ENTRIES = ("engine",)


def read(run):
    dev = run.device
    if dev is None:
        return None
    n_steps, secs = dev.kernel_in_program("moe_qmatmul", "engine_decode")
    return secs / n_steps * 1e3 if n_steps and secs else None
