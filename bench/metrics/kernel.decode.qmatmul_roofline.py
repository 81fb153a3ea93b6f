"""Share of its roofline the fused `qmatmul` kernel reaches in the decode
step: the least time the chip could take for a step's calls (for each call
the larger of bytes over peak bandwidth and FLOPs over peak FLOP/s, summed;
bench/costs.py) over the device time of the `qmatmul` events inside
`engine_decode` executions, per step. In %. M is the number of slots (idle
slots run too: static shapes)."""

from bench import costs

ENTRIES = ("engine",)


def read(run):
    dev = run.device
    if dev is None:
        return None
    n_steps, secs = dev.kernel_in_program("qmatmul", "engine_decode")
    if not n_steps or not secs:
        return None
    m = run.cell.config["bench"]["engine"]["n_slots"]
    least = sum(costs.roofline_seconds(costs.qmatmul_cost(m, k, o),
                                       run.peak)[0]
                for k, o in costs.decode_linears(run.hf))
    return 100.0 * least / (secs / n_steps)
