"""Share of its roofline the kernel `kda_decode` reaches in the decode step
of a model with Kimi delta attention layers, in %: the least time the chip
could take for the LIVE slots' state read once and written once a KDA layer,
plus q, k, v, the decay vector and beta in and o out, and the step's FLOPs
(bench/costs_delta.py; the larger of bytes over peak bandwidth and FLOPs
over peak FLOP/s: the bytes, by an order), with the live slots from the
`state_rows_live` argument of the traced `decode_step` spans, over the device
time of the `kda_decode` events inside `engine_decode`, per step. An idle
slot is neither counted nor read, so the share cannot read over 100%. None
where the configuration lacks the keys, the spans the argument or the trace
the kernel."""

from bench import costs, costs_delta

ENTRIES = ("engine",)


def read(run):
    dev = run.device
    if dev is None or not costs_delta.knows(run.hf):
        return None
    n_steps, secs = dev.kernel_in_program("kda_decode", "engine_decode")
    steps = [a for a in costs_delta.traced_steps(run)
             if a.get("state_rows_live")]
    if not n_steps or not secs or not steps:
        return None
    live = sum(a["state_rows_live"] for a in steps) / len(steps)
    least = costs.roofline_seconds(
        costs_delta.kda_decode_cost(run.hf, live), run.peak)[0]
    return 100.0 * least / (secs / n_steps)
