"""Share of its roofline the kernel `power_retention_decode` reaches in the
decode step, in %: the least time the chip could take for the LIVE slots'
state read once and written once, plus q, k, v, gates and output
(bench/costs_retention.py; the larger of bytes over peak bandwidth and FLOPs
over peak FLOP/s: the bytes, by two orders), with the live slots from the
`state_rows_live` argument of the traced `decode_step` spans, over the device
time of the `power_retention_decode` events inside `engine_decode`, per step.
An idle slot is neither counted nor read, so skipping it cannot read over
100%. None where the spans lack the argument or the trace the kernel."""

from bench import costs, costs_retention

ENTRIES = ("engine",)


def read(run):
    dev = run.device
    if dev is None:
        return None
    n_steps, secs = dev.kernel_in_program("power_retention_decode",
                                          "engine_decode")
    steps = costs_retention.traced_steps(run)
    if not n_steps or not secs or not steps:
        return None
    rows = sum(a["state_rows_live"] for a in steps) / len(steps)
    least = costs.roofline_seconds(
        costs_retention.decode_cost(run.hf, rows), run.peak)[0]
    return 100.0 * least / (secs / n_steps)
