"""Memory-bandwidth utilization of one PASS of a model generated by
diffusion over blocks: the bytes a pass must read (the packed parameter tree
without the embedding table and without the experts nobody chose, from the
`moe_experts_hit` argument of the traced `decode_step` spans; the live pages'
K and V over every layer, from `live_pages` and the cell's page size:
bench/costs_block.py) over the peak bandwidth, over the device time of one
`engine_decode` execution in the trace (the median). In %. A pass of b
positions a row reads what a one-token step reads, so this is the share that
says what the b positions cost beside the weights. None where the
configuration lacks the keys or the spans the arguments."""

from bench import costs_block
from bench.stats import percentile

ENTRIES = ("engine",)


def read(run):
    dev = run.device
    if dev is None or not costs_block.knows(run.hf):
        return None
    execs = dev.program_seconds("engine_decode")
    steps = [a for a in costs_block.traced_steps(run)
             if "moe_experts_hit" in a]
    if not execs or not steps:
        return None
    need = costs_block.pass_bytes(
        run.hf, run.weight_bytes,
        costs_block.mean(steps, "moe_experts_hit"),
        costs_block.mean(steps, "live_pages"),
        run.cell.config["bench"]["engine"]["page_size"])
    return 100.0 * need / run.peak["hbm_bytes_per_s"] / percentile(execs, 50)
