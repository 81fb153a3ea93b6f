"""Operations and bytes of one decode step of a model whose attention layers
are of two kinds that differ in SHAPE, some over every position (a GLOBAL
group of pages) and some over a window (a WINDOW group whose pages behind the
window are freed), with a dense first layer before the sparse ones, from its
own config.json keys (poolside's Laguna):

* each group's depth from `layer_types` (`full_attention` |
  `sliding_attention`), the first `num_hidden_layers` entries;
* each group's query heads from `num_attention_heads_per_layer` (the same
  `num_key_value_heads` in both): a page's K and V serve every query head of
  its layer, the query in, the context out and the FLOPs go with the layer's
  own heads;
* the sparse layers from `mlp_layer_types`: the experts are `num_experts` of
  `moe_intermediate_size` in those layers only, a dense layer has none.

Beside `costs_window.py` (SmallThinker's key names, one head count for every
layer, every layer sparse) and `costs_paged.py` / `costs_moe.py`, whose
per-layer arithmetic this file calls a layer at a time. Counted as the
ALGORITHM needs them: whole pages a kernel over a group must load (a window
layer only those from `max(0, pos - window + 1)` on), the packed weights of
each (layer, expert) pair that got an assignment once, an expert nobody chose
not at all."""

from __future__ import annotations

from bench import costs_moe, costs_paged
# the same spans and the same mean as SmallThinker's readers take
from bench.costs_window import mean, traced_steps  # noqa: F401

_KINDS = ("full_attention", "sliding_attention")  # global, window


def _per_layer(hf: dict, key: str) -> list:
    return list(hf[key][:hf["num_hidden_layers"]])


def group_layers(hf: dict) -> tuple:
    """(full layers, window layers): the two groups' depths."""
    kinds = _per_layer(hf, "layer_types")
    return tuple(kinds.count(k) for k in _KINDS)


def group_heads(hf: dict) -> tuple:
    """(query heads of a full layer, of a window layer)."""
    out = []
    for kind in _KINDS:
        heads = {h for h, k in zip(
            _per_layer(hf, "num_attention_heads_per_layer"),
            _per_layer(hf, "layer_types")) if k == kind}
        assert len(heads) == 1, f"{kind}: one head count a kind, not {heads}"
        out.append(heads.pop())
    return tuple(out)


def sparse_layers(hf: dict) -> int:
    return sum(t != "dense" for t in _per_layer(hf, "mlp_layer_types"))


def as_moe(hf: dict) -> dict:
    """`hf` under the names `costs_moe` reads, its depth the SPARSE layers'."""
    return dict(hf, num_local_experts=hf["num_experts"],
                num_hidden_layers=sparse_layers(hf))


def attn_cost(hf: dict, page: int, live_global: float, live_window: float,
              rows_live: float) -> dict:
    """One decode step's paged attention by group: `live_global` pages a
    full layer loads and `live_window` a window layer (each of one layer's
    grid), over `rows_live` live slots: `costs_paged.decode_cost` of each
    group's layers at that group's own pages AND its own query heads."""
    parts = [costs_paged.decode_cost(
        dict(hf, num_hidden_layers=n, num_attention_heads=heads), page,
        live, rows_live)
        for n, heads, live in zip(group_layers(hf), group_heads(hf),
                                  (live_global, live_window))]
    return {key: parts[0][key] + parts[1][key] for key in ("bytes", "flops")}


def expert_ffn_cost(hf: dict, experts_hit: float, assignments: float) -> dict:
    """`costs_moe.expert_ffn_cost`: the (layer, expert) pairs hit over the
    sparse layers and the assignments computed."""
    return costs_moe.expert_ffn_cost(as_moe(hf), experts_hit, assignments)


def expert_bytes(hf: dict) -> int:
    """Packed bytes of ONE expert of one layer."""
    return costs_moe.expert_bytes(as_moe(hf))


def expert_stack_bytes(hf: dict) -> int:
    """All routed experts of the SPARSE layers: what the tree holds of them
    (the shared expert is no part of it: every step reads it)."""
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
            + experts_hit * expert_bytes(hf)
            + live_global * g + live_window * w)


def knows(hf: dict) -> bool:
    """The configuration carries the keys this file reads."""
    return all(k in hf for k in (
        "layer_types", "num_attention_heads_per_layer", "mlp_layer_types",
        "num_experts"))
