"""Share of its roofline the grouped expert kernel reaches in the decode
step of a model whose config.json names its experts `primary`
(`moe_num_primary_experts` of `moe_ffn_hidden_size`), in %: exactly
`kernel.moe_ffn_roofline`, the least time for the experts a step HIT
(`costs_moe.expert_ffn_cost`, called through bench/costs_window.py under
the names it reads) from the `moe_experts_hit` and `moe_assignments`
arguments of the traced `decode_step` spans, over the device time of the
`moe_qmatmul` events inside `engine_decode`, per step. It stands in until
`costs_moe.expert_shape` reads the source's key. None where the spans lack
the arguments or the trace the kernel."""

from bench import costs, costs_moe, costs_window

ENTRIES = ("engine",)


def read(run):
    dev = run.device
    if dev is None or "moe_ffn_hidden_size" not in run.hf:
        return None
    n_steps, secs = dev.kernel_in_program("moe_qmatmul", "engine_decode")
    steps = costs_moe.traced_steps(run)
    if not n_steps or not secs or not steps:
        return None
    least = costs.roofline_seconds(costs_window.expert_ffn_cost(
        run.hf, costs_window.mean(steps, "moe_experts_hit"),
        costs_window.mean(steps, "moe_assignments")), run.peak)[0]
    return 100.0 * least / (secs / n_steps)
