"""Operations the prefill's attention must do, from the prompt's length
alone: what the kernel `flash_attention` is held against. Beside `costs.py`
(the dense projections) and `costs_paged.py` (the decode step's attention).

Counted as the ALGORITHM needs them: a prompt of T tokens has T * (T + 1) / 2
(query, key) pairs under the causal mask, each one multiply-add of D in the
score product and one in the context product, for every query head and
layer. A kernel's tiles compute more (whole tiles on the diagonal, the
padding of T), never less, so a share of the peak by this count cannot pass
100%. It holds where the whole prompt is prefilled (no prefix hit) and no
sliding window is shorter than it: the cell that reports it."""

from __future__ import annotations


def causal_flops(hf: dict, prompt_tokens: int) -> float:
    """FLOPs of the causal score and context products of one prompt, all
    layers: `num_hidden_layers * 4 * Hq * D * T * (T + 1) / 2`."""
    D = hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]
    T = prompt_tokens
    return (hf["num_hidden_layers"] * 4.0 * hf["num_attention_heads"] * D
            * T * (T + 1) / 2)


def traced_prefills(run, program: str) -> list | None:
    """`prompt_tokens` of the `prefill` span around each execution of
    `program` that `Reduced.kernel_in_program` counts (it starts inside the
    traced seconds and ends inside them), tied by the execution's middle
    on the benchmark's clock. None where an execution lies in no span or
    in two, or a span lacks the argument: then nothing says what the
    kernel's seconds bought."""
    dev = run.device
    spans = run.span_list("prefill")
    out = []
    for mods in dev.loaded.modules.values():
        for m in mods:
            if not (program in m.name and dev.begin <= m.start < dev.end
                    and m.start + m.dur <= dev.end):
                continue
            mid = m.start + m.dur / 2 + dev.offset
            around = [a for t, d, a in spans if t <= mid < t + d]
            if len(around) != 1 or "prompt_tokens" not in around[0]:
                return None
            out.append(around[0]["prompt_tokens"])
    return out
