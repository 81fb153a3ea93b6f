"""Operations and bytes of one PASS of a model generated by diffusion over
blocks (JetLM's SDAR-MoE: `block_length` = b positions a row and pass, served
from KV pages, every layer sparse), from its own config.json keys. Beside
`costs_paged.py` and `costs_moe.py`, whose per-layer arithmetic this file
calls with `b` query positions a live row where they count one.

Counted as the ALGORITHM needs them, by what a kernel MUST load, so that no
share read from them can pass 100%:

* a pass reads the packed parameter tree without the embedding table (of
  which it reads `b` rows a live slot), less the experts nobody chose: the
  packed weights of each (layer, expert) pair that got an assignment once;
* every live page's K and V once a layer, WHOLE (a page is what the kernel
  can fetch through the block table): they serve all `b` queries of the row's
  block and every query head, so `b` positions cost the pages of one;
* per layer and live slot the block's queries [b, Hq, D] go in and its
  context [b, Hq, D] comes out (bf16); per slot of a live page, layer, query
  head and query position a score over D and a value sum over D, a
  multiply-add each.
"""

from __future__ import annotations

from bench import costs_moe, costs_paged
from bench.costs_window import mean  # noqa: F401  (the readers' own mean)


def block_length(hf: dict) -> int:
    return int(hf["block_length"])


def knows(hf: dict) -> bool:
    """The configuration carries the keys this file reads."""
    return all(k in hf for k in ("block_length", "num_experts",
                                 "moe_intermediate_size"))


def expert_bytes(hf: dict) -> int:
    """Packed sym_int4 bytes of ONE expert of one layer."""
    return costs_moe.expert_bytes(hf)


def expert_stack_bytes(hf: dict) -> int:
    """All experts of all layers: what the parameter tree holds of them."""
    return expert_bytes(hf) * hf["num_experts"] * hf["num_hidden_layers"]


def kv_page_bytes(hf: dict, page: int) -> int:
    """K and V of ONE page over all layers."""
    return hf["num_hidden_layers"] * costs_paged.page_bytes(hf, page)


def pass_bytes(hf: dict, weight_bytes: int, experts_hit: float,
               live_pages: float, page: int) -> float:
    """What one pass must read: the tree without the embedding
    (`weight_bytes`) less the experts nobody chose, and the live pages."""
    return (weight_bytes - expert_stack_bytes(hf)
            + experts_hit * expert_bytes(hf)
            + live_pages * kv_page_bytes(hf, page))


def attn_cost(hf: dict, page: int, live_pages: float,
              rows_live: float) -> dict:
    """One pass's attention over `live_pages` pages (of one layer's grid) and
    `rows_live` live slots of `b` query positions each, all layers."""
    b = block_length(hf)
    one = costs_paged.decode_cost(hf, page, live_pages, rows_live * b)
    return {"bytes": one["bytes"], "flops": one["flops"] * b}


def traced_steps(run) -> list:
    """Arguments of the `decode_step` spans of passes (those that carry
    `block_length`), inside the traced seconds where the run has a device
    trace, else the whole window's. Empty for a program without them."""
    spans = [(t, a) for t, _, a in run.span_list("decode_step")
             if a.get("block_length") and "live_pages" in a
             and a.get("occupancy")]
    dev = run.device
    if dev is not None:
        lo, hi = dev.begin + dev.offset, dev.end + dev.offset
        inside = [(t, a) for t, a in spans if lo <= t < hi]
        spans = inside or spans
    return [a for _, a in spans]
