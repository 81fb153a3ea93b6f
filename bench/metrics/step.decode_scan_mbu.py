"""Memory-bandwidth utilization of one decode step of a hybrid whose state
layers are Mamba-1: the bytes a step must move (the parameter tree without
the embedding table: packed weights once, the unpacked small projections;
the live slots' state rows, scan state and tails, read and written: the
`state_bytes_moved` argument of the traced `decode_step` spans, the
program's own count, which tests/bench holds to bench/costs_scan.py; the
live pages' keys and values over the ATTENTION layers, from `live_pages` and
the cell's page size) over the peak bandwidth, over the device time of one
`engine_decode` execution in the trace. In %. At hundreds of rows the step
is not bandwidth-bound (its GEMMs are the MXU's), so the share reads well
under the kernels' own. `step.decode_ssm_mbu` subtracts experts nobody
chose, which this model has not. None where the configuration lacks the keys
or the spans the arguments."""

from bench import costs_scan
from bench.stats import percentile

ENTRIES = ("engine",)


def read(run):
    dev = run.device
    if dev is None or not costs_scan.knows(run.hf):
        return None
    execs = dev.program_seconds("engine_decode")
    steps = [a for a in costs_scan.traced_steps(run) if "live_pages" in a]
    if not execs or not steps:
        return None
    n = len(steps)
    need = costs_scan.step_bytes(
        run.hf, run.weight_bytes,
        sum(a["state_bytes_moved"] for a in steps) / n,
        sum(a["live_pages"] for a in steps) / n,
        run.cell.config["bench"]["engine"]["page_size"])
    return 100.0 * need / run.peak["hbm_bytes_per_s"] / percentile(execs, 50)
