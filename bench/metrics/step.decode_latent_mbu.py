"""Memory-bandwidth utilization of one decode step of a sparse-expert model
that keeps latent pages: the bytes a step must move (the packed parameter
tree without the embedding table and without the experts nobody chose, from
the `moe_experts_hit` argument of the traced `decode_step` spans; and the live
tokens' latents, the `latent_bytes_read` argument, the program's own count,
which tests/bench holds to bench/costs_latent.py) over the peak bandwidth,
over the device time of one `engine_decode` execution in the trace. In %.
`step.decode_mbu` counts keys and values per KV head and every expert, which
such a model does not read. None where the spans lack the arguments."""

from bench import costs_latent
from bench.stats import percentile

ENTRIES = ("engine",)


def read(run):
    dev = run.device
    if dev is None:
        return None
    execs = dev.program_seconds("engine_decode")
    steps = [a for a in costs_latent.traced_steps(run)
             if "moe_experts_hit" in a]
    if not execs or not steps:
        return None
    hit = sum(a["moe_experts_hit"] for a in steps) / len(steps)
    lat = sum(a["latent_bytes_read"] for a in steps) / len(steps)
    need = costs_latent.step_bytes(run.hf, run.weight_bytes, hit, lat)
    return 100.0 * need / run.peak["hbm_bytes_per_s"] / percentile(execs, 50)
