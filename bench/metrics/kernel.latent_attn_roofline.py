"""Share of its roofline the kernel `paged_latent_decode_attention` reaches in
the decode step, in %: the least time the chip could take for the step's live
TOKENS (bench/costs_latent.py: every live token's latent row once a layer,
the absorbed query in and the context out a live slot; the larger of bytes
over peak bandwidth and FLOPs over peak FLOP/s), with the live tokens and
slots from the `latent_live_tokens` and `occupancy` arguments of the traced
`decode_step` spans, over the device time of the kernel's events inside
`engine_decode`, per step. A kernel reads whole pages and cannot do with less
than the tokens, so the share cannot read over 100%. None where the spans lack
the argument or the trace the kernel."""

from bench import costs, costs_latent

ENTRIES = ("engine",)


def read(run):
    dev = run.device
    if dev is None:
        return None
    n_steps, secs = dev.kernel_in_program("paged_latent_decode_attention",
                                          "engine_decode")
    steps = costs_latent.traced_steps(run)
    if not n_steps or not secs or not steps:
        return None
    tokens = sum(a["latent_live_tokens"] for a in steps) / len(steps)
    rows = sum(a["occupancy"] for a in steps) / len(steps)
    least = costs.roofline_seconds(
        costs_latent.decode_cost(run.hf, tokens, rows), run.peak)[0]
    return 100.0 * least / (secs / n_steps)
