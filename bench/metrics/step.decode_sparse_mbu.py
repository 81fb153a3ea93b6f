"""Memory-bandwidth utilization of one decode step of a model whose slots
hold selected pages beside a lightning state: the bytes a step must move
(the packed parameter tree without the embedding table; the live slots'
state rows, read and written, the `state_bytes_moved` argument, the program's
own count, which tests/bench holds to bench/costs_sparse.py; the DISTINCT
selected pages' keys and values and the live pages' pooled keys over the
sparse layers, from `sparse_pages_read` and `sparse_pages_live` and the cell's
page size) over the peak bandwidth, over the device time of one
`engine_decode` execution in the trace. In %. `step.decode_mbu` counts every
live page's keys and values in every layer, `step.decode_ssm_mbu` a Mamba-2
state. None where the spans lack the arguments."""

from bench import costs_sparse
from bench.stats import percentile

ENTRIES = ("engine",)


def read(run):
    dev = run.device
    if dev is None or not costs_sparse.knows(run.hf):
        return None
    execs = dev.program_seconds("engine_decode")
    steps = [a for a in costs_sparse.traced_steps(run)
             if "state_bytes_moved" in a]
    if not execs or not steps:
        return None
    need = costs_sparse.step_bytes(
        run.hf, run.weight_bytes,
        costs_sparse.mean(steps, "state_bytes_moved"),
        costs_sparse.mean(steps, "sparse_pages_read"),
        costs_sparse.mean(steps, "sparse_pages_live"),
        run.cell.config["bench"]["engine"]["page_size"])
    return 100.0 * need / run.peak["hbm_bytes_per_s"] / percentile(execs, 50)
