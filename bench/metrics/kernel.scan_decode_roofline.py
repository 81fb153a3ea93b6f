"""Share of its roofline the kernel `mamba1_decode` reaches in the decode
step, in %: the least time the chip could take for the LIVE slots' scan state
read once and written once, plus x, dt, B, C and y, and A once a layer
(bench/costs_scan.py; the larger of bytes over peak bandwidth and FLOPs over
peak FLOP/s: the bytes, by an order), with the live slots from the
`state_rows_live` argument of the traced `decode_step` spans, over the device
time of the `mamba1_decode` events inside `engine_decode`, per step. An idle
slot is neither counted nor read, and the convolution's tail (XLA's, around
the kernel) is not counted, so the share cannot read over 100%. None where
the configuration lacks the keys, the spans the argument or the trace the
kernel."""

from bench import costs, costs_scan

ENTRIES = ("engine",)


def read(run):
    dev = run.device
    if dev is None or not costs_scan.knows(run.hf):
        return None
    n_steps, secs = dev.kernel_in_program("mamba1_decode", "engine_decode")
    steps = costs_scan.traced_steps(run)
    if not n_steps or not secs or not steps:
        return None
    rows = sum(a["state_rows_live"] for a in steps) / len(steps)
    least = costs.roofline_seconds(
        costs_scan.decode_cost(run.hf, rows), run.peak)[0]
    return 100.0 * least / (secs / n_steps)
