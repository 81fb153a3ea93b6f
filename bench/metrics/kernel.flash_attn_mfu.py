"""Share of the chip's bf16 peak the kernel `flash_attention` reaches in
the engine's prefills, in %: the FLOPs of the causal score and context
products of the traced seconds' prefills (bench/costs_flash.py: per traced
`prefill` span `num_hidden_layers * 4 * Hq * D * T * (T + 1) / 2` with T the
span's `prompt_tokens`: the algorithm's count, not the tiles') over
`bf16_flops_per_s` of bench/peaks.json, over the device time of the
`flash_attention` events inside those prefills' `engine_paged_prefill`
executions. Whole tiles on the diagonal and the padding of T are work the
kernel does and the count leaves out, so the share cannot read over 100%.
None without a trace, where the trace lacks the kernel, or where a prefill
program's execution cannot be tied to one span."""

from bench import costs_flash

ENTRIES = ("engine",)
PROGRAM = "engine_paged_prefill"


def read(run):
    dev = run.device
    if dev is None:
        return None
    n, secs = dev.kernel_in_program("flash_attention", PROGRAM)
    prompts = costs_flash.traced_prefills(run, PROGRAM) if n and secs else None
    if not prompts or len(prompts) != n:
        return None
    flops = sum(costs_flash.causal_flops(run.hf, t) for t in prompts)
    return 100.0 * flops / run.peak["bf16_flops_per_s"] / secs
