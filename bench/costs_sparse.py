"""Operations and bytes of one decode step of a model whose layers are
block-sparse softmax attention or lightning attention by `mixer_types`
(minicpm_sala): what the kernels `paged_sparse_decode_attention` and
`lightning_decode` MUST do, and what a step must move. Beside `costs.py`
and `costs_paged.py` (every live page's keys in every layer: a share of
their roofline over 100% here would be an impossible reading, since a
sparse layer reads a selection and a lightning layer no keys at all) and
`costs_ssm.py` (a Mamba-2 state with one group of B and C).

Counted as the ALGORITHM needs them (bigdl_tpu/kvsparse.py has the
equations): per sparse layer each DISTINCT selected page's K and V once as
the pool stores it (both KV heads' halves: a page is one DMA), the pooled
keys of the live rows' live pages once, q in and the context out; per
lightning layer and live slot the state `[heads x head size, head size]`
(float32) read once and written once, q, k, v in and o out. An idle slot
moves nothing. The counts come from the program's own `decode_step` spans
(`sparse_pages_read`: distinct pages DMA'd, summed over the sparse layers;
`sparse_pages_selected`: (KV head, page) pairs; `sparse_pages_live`: the
live rows' pages x the sparse layers; `state_bytes_moved`; `occupancy`)."""

from __future__ import annotations

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
_KV_BPE = 2  # bf16 pages and pooled keys
_STATE_BPE = 4
_X_BPE = 2  # q in, context out (bf16)
_F32 = 4  # the state kernel's small operands


def knows(hf: dict) -> bool:
    return "mixer_types" in hf and "sparse_config" in hf


def n_layers(hf: dict, kind: str) -> int:
    return sum(k == kind for k in hf["mixer_types"])


def head_dim(hf: dict) -> int:
    return hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]


def page_bytes(hf: dict, page: int) -> int:
    """K and V of ONE page of ONE sparse layer, every KV head's half."""
    return 2 * page * hf["num_key_value_heads"] * head_dim(hf) * _KV_BPE


def pooled_page_bytes(hf: dict, page: int) -> int:
    """The pooled keys that live in ONE page of ONE sparse layer."""
    windows = page // hf["sparse_config"]["kernel_stride"]
    return windows * hf["num_key_value_heads"] * head_dim(hf) * _KV_BPE


def pool_page_bytes(hf: dict, page: int) -> int:
    """One page of the pool over all sparse layers: K, V and pooled keys."""
    return n_layers(hf, SPARSE) * (page_bytes(hf, page)
                                   + pooled_page_bytes(hf, page))


def state_row_bytes(hf: dict) -> int:
    """One slot's lightning state over all lightning layers."""
    d = hf["lightning_head_dim"]
    return n_layers(hf, LIGHTNING) * hf["lightning_nh"] * d * d * _STATE_BPE


def decode_linears(hf: dict) -> list:
    """(K, O) of every `qmatmul` call of one decode step of THIS tree
    (models/minicpm_sala.py projects each matrix by itself): a sparse layer's
    q, k, v, gate and o, a lightning layer's q, k, v (as many KV heads as
    query heads), gate and o, every layer's gate, up and down, then the head
    at the vocabulary's own rows (the program pads it to whole lane tiles;
    the padding is not work the step must do). `costs.decode_linears` counts
    a merged dense layer and neither the gates nor the lightning widths."""
    H, I = hf["hidden_size"], hf["intermediate_size"]
    qd = hf["num_attention_heads"] * head_dim(hf)
    kd = hf["num_key_value_heads"] * head_dim(hf)
    ld = hf["lightning_nh"] * hf["lightning_head_dim"]
    mlp = [(H, I), (H, I), (I, H)]
    mixer = {SPARSE: [(H, qd), (H, kd), (H, kd), (H, qd), (qd, H)],
             LIGHTNING: [(H, ld)] * 4 + [(ld, H)]}
    out = []
    for kind in hf["mixer_types"]:
        out += mixer[kind] + mlp
    return out + [(H, hf["vocab_size"])]


def attn_cost(hf: dict, page: int, pages_read: float, pages_selected: float,
              pages_live: float, rows: float) -> dict:
    """One decode step's sparse attention, all sparse layers: the arguments
    are sums over the sparse layers as the spans give them."""
    D, Hq = head_dim(hf), hf["num_attention_heads"]
    G = Hq // hf["num_key_value_heads"]
    small = n_layers(hf, SPARSE) * rows * 2 * Hq * D * _X_BPE
    return {"bytes": (pages_read * page_bytes(hf, page)
                      + pages_live * pooled_page_bytes(hf, page) + small),
            # a (KV head, page) pair: G query heads over `page` keys, the
            # score dot and the context dot
            "flops": pages_selected * G * page * D * 4}


def state_cost(hf: dict, rows: float) -> dict:
    """One decode step's `lightning_decode` calls with `rows` live slots."""
    H, d = hf["lightning_nh"], hf["lightning_head_dim"]
    small = n_layers(hf, LIGHTNING) * 4 * H * d * _F32  # q, k, v in; o out
    return {"bytes": rows * (2 * state_row_bytes(hf) + small),
            # per state element: the decay, the rank-one update (multiply,
            # add) and the readout's multiply-add
            "flops": rows * n_layers(hf, LIGHTNING) * H * d * d * 5}


def step_bytes(hf: dict, weight_bytes: int, state_moved: float,
               pages_read: float, pages_live: float, page: int) -> float:
    """What one decode step must move: the packed parameter tree without
    the embedding table, the live rows' state read and written (the
    program's own count), the selected pages and the pooled keys."""
    return (weight_bytes + state_moved + pages_read * page_bytes(hf, page)
            + pages_live * pooled_page_bytes(hf, page))


def traced_steps(run) -> list:
    """Arguments of the `decode_step` spans that carry a selection, those
    inside the traced seconds where the run has a device trace, else the
    whole window's. Empty for a program without such spans."""
    spans = [(t, a) for t, _, a in run.span_list("decode_step")
             if a.get("sparse_pages_read") and a.get("sparse_pages_live")]
    dev = run.device
    if dev is not None:
        lo, hi = dev.begin + dev.offset, dev.end + dev.offset
        spans = [(t, a) for t, a in spans if lo <= t < hi] or spans
    return [a for _, a in spans]


def mean(steps: list, key: str) -> float:
    return sum(a.get(key, 0) for a in steps) / len(steps)
