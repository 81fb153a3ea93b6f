"""Operations and bytes of the attention of one decode step of a GQA model
served from KV pages, from its shapes: what the kernel
`paged_decode_attention` must do. Beside `costs.py` (the dense projections),
`costs_latent.py` (latent pages) and `costs_retention.py` (a recurrent state).

Counted by live PAGES, whole: a page is what the kernel can fetch through the
block table, and the one page a row is still filling is loaded whole whatever
it holds, so no kernel over this pool does with less. Per layer, every live
page's K and V (bf16) cross HBM once and serve all query heads; per layer and
live slot the query [Hq, D] goes in and the context [Hq, D] comes out (bf16).
Per slot of a live page, layer and query head: a score over D and a value sum
over D, a multiply-add each. The yardstick's copy of
`bigdl_tpu/benchmark/roofline.decode_attention_cost`, which the program may
change (tests/bench holds the two together)."""

from __future__ import annotations

_BPE = 2  # bf16 pages, query and context


def head_dim(hf: dict) -> int:
    return hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]


def page_bytes(hf: dict, page: int) -> int:
    """K and V of one page of one layer."""
    return 2 * page * hf["num_key_value_heads"] * head_dim(hf) * _BPE


def decode_cost(hf: dict, page: int, live_pages: float,
                rows_live: float) -> dict:
    """One decode step's paged attention: `live_pages` pages (of one layer's
    grid) over `rows_live` live slots, all layers."""
    L, Hq, D = hf["num_hidden_layers"], hf["num_attention_heads"], head_dim(hf)
    small = Hq * D * 2 * _BPE  # q in, context out, a live slot
    return {"bytes": L * (live_pages * page_bytes(hf, page)
                          + rows_live * small),
            "flops": L * live_pages * page * Hq * 4 * D}


def traced_steps(run) -> list:
    """Arguments of the `decode_step` spans that count live pages, those
    inside the traced seconds where the run has a device trace, else the
    whole window's. Empty for a program without such spans."""
    spans = [(t, a) for t, _, a in run.span_list("decode_step")
             if "live_pages" in a and "occupancy" in a]
    dev = run.device
    if dev is not None:
        lo, hi = dev.begin + dev.offset, dev.end + dev.offset
        inside = [(t, a) for t, a in spans if lo <= t < hi]
        spans = inside or spans
    return [a for _, a in spans]
