"""Share of its roofline the grouped expert kernel reaches in the decode
step of a model whose experts sit in SOME layers (`mlp_layer_types`: a dense
first layer, then `num_experts` routed experts of `moe_intermediate_size`),
in %: the least time for the experts a step HIT in the sparse layers
(bench/costs_groups.py over `costs_moe.expert_ffn_cost`: each (layer,
expert) pair's packed weights once, every assignment's rows in and out) from
the `moe_experts_hit` and `moe_assignments` arguments of the traced
`decode_step` spans, over the device time of the `moe_qmatmul` events inside
`engine_decode`, per step. `kernel.moe_ffn_roofline` reads
`num_local_experts`, which this configuration does not carry. An expert
nobody chose is not counted, so skipping it cannot read over 100%. None
where the configuration lacks the keys, the spans the arguments or the trace
the kernel."""

from bench import costs, costs_groups, costs_moe

ENTRIES = ("engine",)


def read(run):
    dev = run.device
    if dev is None or not costs_groups.knows(run.hf):
        return None
    n_steps, secs = dev.kernel_in_program("moe_qmatmul", "engine_decode")
    steps = costs_moe.traced_steps(run)
    if not n_steps or not secs or not steps:
        return None
    least = costs.roofline_seconds(costs_groups.expert_ffn_cost(
        run.hf, costs_groups.mean(steps, "moe_experts_hit"),
        costs_groups.mean(steps, "moe_assignments")), run.peak)[0]
    return 100.0 * least / (secs / n_steps)
