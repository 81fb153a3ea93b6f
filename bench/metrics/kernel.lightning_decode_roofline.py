"""Share of its roofline the kernel `lightning_decode` reaches in the decode
step of a model with lightning (decayed linear attention) layers, in %: the
least time the chip could take for the LIVE slots' state read once and
written once a lightning layer, plus q, k, v and o (bench/costs_sparse.py;
the larger of bytes over peak bandwidth and FLOPs over peak FLOP/s: the
bytes, by two orders), with the live slots from the `state_rows_live`
argument of the traced `decode_step` spans, over the device time of the
`lightning_decode` events inside `engine_decode`, per step. An idle slot is
neither counted nor read, so the share cannot read over 100%. None where the
configuration lacks the keys, the spans the argument or the trace the
kernel."""

from bench import costs, costs_sparse

ENTRIES = ("engine",)


def read(run):
    dev = run.device
    if dev is None or not costs_sparse.knows(run.hf):
        return None
    n_steps, secs = dev.kernel_in_program("lightning_decode", "engine_decode")
    steps = [a for a in costs_sparse.traced_steps(run)
             if a.get("state_rows_live")]
    if not n_steps or not secs or not steps:
        return None
    least = costs.roofline_seconds(costs_sparse.state_cost(
        run.hf, costs_sparse.mean(steps, "state_rows_live")), run.peak)[0]
    return 100.0 * least / (secs / n_steps)
