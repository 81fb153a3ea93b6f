"""Operations and bytes of one decode step of a model whose attention layers
are of two kinds, some over every position (a GLOBAL group of pages) and some
over a window (a WINDOW group whose pages behind the window are freed), from
its shapes. Beside `costs_paged.py`, which multiplies ONE count of live pages
by every layer: here a window layer loads only the pages from
`max(0, pos - window + 1)` on, so the two groups are counted apart, each by
the pages a kernel over it must load (whole pages, `costs_paged.page_bytes`).

The source (PowerInfer's SmallThinker config.json) calls the expert width
`moe_ffn_hidden_size` and the expert count `moe_num_primary_experts`;
`costs_moe` reads `moe_intermediate_size` and `num_local_experts`. `as_moe`
maps the one onto the other and the expert arithmetic stays `costs_moe`'s."""

from __future__ import annotations

from bench import costs_moe, costs_paged


def group_layers(hf: dict) -> tuple:
    """(full layers, window layers): the two groups' depths."""
    window = sum(1 for x in hf["sliding_window_layout"] if x)
    return hf["num_hidden_layers"] - window, window


def as_moe(hf: dict) -> dict:
    """`hf` under the names `costs_moe` reads."""
    return dict(hf, moe_intermediate_size=hf["moe_ffn_hidden_size"],
                num_local_experts=hf["moe_num_primary_experts"])


def attn_cost(hf: dict, page: int, live_global: float, live_window: float,
              rows_live: float) -> dict:
    """One decode step's paged attention by group: `live_global` pages a
    full layer loads and `live_window` a window layer (each of one layer's
    grid), over `rows_live` live slots: `costs_paged.decode_cost` of each
    group's layers at that group's own pages, added."""
    parts = [costs_paged.decode_cost(dict(hf, num_hidden_layers=n), page,
                                     live, rows_live)
             for n, live in zip(group_layers(hf), (live_global, live_window))]
    return {key: parts[0][key] + parts[1][key] for key in ("bytes", "flops")}


def expert_ffn_cost(hf: dict, experts_hit: float, assignments: float) -> dict:
    """`costs_moe.expert_ffn_cost` under the source's key names."""
    return costs_moe.expert_ffn_cost(as_moe(hf), experts_hit, assignments)


def expert_stack_bytes(hf: dict) -> int:
    return costs_moe.expert_stack_bytes(as_moe(hf))


def kv_page_bytes(hf: dict, page: int) -> tuple:
    """Bytes of ONE page over its group's layers: (global, window)."""
    one = costs_paged.page_bytes(hf, page)
    n_g, n_w = group_layers(hf)
    return n_g * one, n_w * one


def step_bytes(hf: dict, weight_bytes: int, experts_hit: float,
               live_global: float, live_window: float, page: int) -> float:
    """What one decode step must move: the packed parameter tree without
    the embedding table (`weight_bytes`), less the experts nobody chose;
    both groups' live pages, whole."""
    g, w = kv_page_bytes(hf, page)
    return (weight_bytes - expert_stack_bytes(hf)
            + experts_hit * costs_moe.expert_bytes(as_moe(hf))
            + live_global * g + live_window * w)


def traced_steps(run) -> list:
    """Arguments of the `decode_step` spans that count live pages by group,
    those inside the traced seconds where the run has a device trace, else
    the whole window's. Empty for a program without such spans."""
    spans = [(t, a) for t, _, a in run.span_list("decode_step")
             if "live_pages_window" in a and "live_pages_global" in a
             and "occupancy" in a]
    dev = run.device
    if dev is not None:
        lo, hi = dev.begin + dev.offset, dev.end + dev.offset
        inside = [(t, a) for t, a in spans if lo <= t < hi]
        spans = inside or spans
    return [a for _, a in spans]


def mean(steps: list, key: str) -> float:
    return sum(a[key] for a in steps) / len(steps)
