"""Share of its roofline the kernel `mamba2_decode` reaches in the decode
step, in %: the least time the chip could take for the LIVE slots'
recurrence state read once and written once, plus x, dt, B, C and y
(bench/costs_ssm.py; the larger of bytes over peak bandwidth and FLOPs over
peak FLOP/s: the bytes, by two orders), with the live slots from the
`state_rows_live` argument of the traced `decode_step` spans, over the device
time of the `mamba2_decode` events inside `engine_decode`, per step. An idle
slot is neither counted nor read, and the convolution's tail (XLA's, around
the kernel) is not counted, so the share cannot read over 100%. None where
the spans lack the argument or the trace the kernel."""

from bench import costs, costs_ssm

ENTRIES = ("engine",)


def read(run):
    dev = run.device
    if dev is None or "layer_types" not in run.hf:
        return None
    n_steps, secs = dev.kernel_in_program("mamba2_decode", "engine_decode")
    steps = costs_ssm.traced_steps(run)
    if not n_steps or not secs or not steps:
        return None
    rows = sum(a["state_rows_live"] for a in steps) / len(steps)
    least = costs.roofline_seconds(
        costs_ssm.decode_cost(run.hf, rows), run.peak)[0]
    return 100.0 * least / (secs / n_steps)
