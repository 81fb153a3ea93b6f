"""Share of its roofline the fused `qmatmul` kernel reaches in the decode
step of a model whose layers are block-sparse or lightning attention by
`mixer_types` (minicpm_sala), in %: `kernel.decode.qmatmul_roofline`'s
arithmetic (for each call the larger of bytes over peak bandwidth and FLOPs
over peak FLOP/s, summed; bench/costs.py) over THIS tree's calls
(`costs_sparse.decode_linears`: every matrix by itself, the two gates, the
lightning widths, the head at the vocabulary's own rows), over the device
time of the `qmatmul` events inside `engine_decode`, per step. M is the
number of slots (idle slots run too: static shapes). None where the
configuration lacks the keys or the trace the kernel."""

from bench import costs, costs_sparse

ENTRIES = ("engine",)


def read(run):
    dev = run.device
    if dev is None or not costs_sparse.knows(run.hf):
        return None
    n_steps, secs = dev.kernel_in_program("qmatmul", "engine_decode")
    if not n_steps or not secs:
        return None
    m = run.cell.config["bench"]["engine"]["n_slots"]
    least = sum(costs.roofline_seconds(costs.qmatmul_cost(m, k, o),
                                       run.peak)[0]
                for k, o in costs_sparse.decode_linears(run.hf))
    return 100.0 * least / (secs / n_steps)
