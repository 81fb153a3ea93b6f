"""Share of its roofline the grouped expert kernel reaches in the decode
step, in %: the least time the chip could take for the experts a step
actually HIT (bench/costs_moe.py: their packed bytes once, plus every
assignment's activations and FLOPs; the larger of bytes over peak bandwidth
and FLOPs over peak FLOP/s), from the `moe_experts_hit` and
`moe_assignments` arguments of the traced `decode_step` spans, over the
device time of the `moe_qmatmul` events inside `engine_decode`, per step.
An expert nobody chose is neither counted nor read, so skipping it cannot
read over 100%. None where the spans lack the arguments or the trace the
kernel."""

from bench import costs, costs_moe

ENTRIES = ("engine",)


def read(run):
    dev = run.device
    if dev is None:
        return None
    n_steps, secs = dev.kernel_in_program("moe_qmatmul", "engine_decode")
    steps = costs_moe.traced_steps(run)
    if not n_steps or not secs or not steps:
        return None
    hit = sum(a["moe_experts_hit"] for a in steps) / len(steps)
    rows = sum(a["moe_assignments"] for a in steps) / len(steps)
    least = costs.roofline_seconds(
        costs_moe.expert_ffn_cost(run.hf, hit, rows), run.peak)[0]
    return 100.0 * least / (secs / n_steps)
