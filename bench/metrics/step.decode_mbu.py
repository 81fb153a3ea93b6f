"""Memory-bandwidth utilization of one decode step on the device: the bytes
a step must read (the packed parameter tree without the embedding table, and
the live KV of the slots that were busy) over the peak bandwidth, over the
device time of one `engine_decode` execution in the trace. In %."""

from bench import costs
from bench.stats import percentile

ENTRIES = ("engine",)


def read(run):
    dev = run.device
    if dev is None:
        return None
    execs = dev.program_seconds("engine_decode")
    live = run.extra.get("live_tokens_in_trace")
    if not execs or live is None:
        return None
    need = run.weight_bytes + costs.kv_bytes(run.hf, live)
    return 100.0 * need / run.peak["hbm_bytes_per_s"] / percentile(execs, 50)
