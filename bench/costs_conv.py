"""Operations and bytes of one decode step of a hybrid whose layers are gated
short convolutions or attention by `layer_types` (lfm2_moe): what the paged
kernel must do over the FEW attention layers, this model's `qmatmul` calls, its
routed experts in the layers after `num_dense_layers`, and the state a slot
carries, which is the convolutions' tails and nothing else. Beside `costs.py`
(a GQA model's dense projections: it reads an attention block and an
`intermediate_size`-wide MLP in EVERY layer, which this model has not),
`costs_paged.py` (KV pages in every layer: `decode_cost` takes
`num_hidden_layers` for its layer count, five of this stage's twenty),
`costs_moe.py` (one layer's experts: `expert_ffn_cost` reads this model right
as it stands, hidden size and `moe_intermediate_size`; `expert_stack_bytes`
reads `num_local_experts`, which this model calls `num_experts`) and
`costs_ssm.py` / `costs_scan.py` (a recurrence state beside the tail).

Counted as the ALGORITHM needs them (bigdl_tpu/models/lfm2_moe.py has the
equations): a convolution layer keeps, per slot, the last `conv_L_cache - 1`
values of its gated input over `hidden_size` channels in float32, read and
written once a step by XLA (there is no kernel: the operator is elementwise
around its two packed projections). An attention layer's keys and values are
`num_key_value_heads` heads of `hidden_size / num_attention_heads` (64): the
program stores two heads side by side on a row of 128 lanes, which is the
same bytes; the queries it pads to 128 lanes are not counted (the algorithm
needs 64), so a share of this roofline cannot read over 100%."""

from __future__ import annotations

from bench import costs_moe, costs_paged
from bench.costs import sym_int4_bytes
from bench.costs_paged import head_dim, page_bytes  # noqa: F401  (one layer's)
# the `decode_step` spans that carry state traffic: the same two arguments
# as a model with a state in every layer
from bench.costs_retention import traced_steps  # noqa: F401

_STATE_BPE = 4  # float32 tails
_KV_BPE = 2  # bf16 pages, query and context


def knows(hf: dict) -> bool:
    return "conv_L_cache" in hf and "layer_types" in hf


def n_layers(hf: dict, kind: str) -> int:
    """Layers of `kind`: "conv" or "full_attention"."""
    return sum(k == kind for k in hf["layer_types"])


def n_sparse(hf: dict) -> int:
    """Layers with routed experts: all but the leading dense ones."""
    return hf["num_hidden_layers"] - hf["num_dense_layers"]


def tail_row_bytes(hf: dict) -> int:
    """One slot's state row: the convolution layers' tails, `conv_L_cache -
    1` gated inputs over the hidden channels each."""
    return (n_layers(hf, "conv") * (hf["conv_L_cache"] - 1)
            * hf["hidden_size"] * _STATE_BPE)


def kv_token_bytes(hf: dict) -> int:
    """bf16 K and V of one cached token over the ATTENTION layers."""
    return (2 * hf["num_key_value_heads"] * head_dim(hf) * _KV_BPE
            * n_layers(hf, "full_attention"))


def attn_decode_cost(hf: dict, page: int, live_pages: float,
                     rows_live: float) -> dict:
    """One decode step's paged attention over the attention layers:
    `live_pages` pages (of one layer's grid) over `rows_live` live slots.
    `costs_paged.decode_cost` (whole pages; the query in and the context
    out a live slot at the published head size) over THOSE layers, where it
    would take `num_hidden_layers`."""
    return costs_paged.decode_cost(
        dict(hf, num_hidden_layers=n_layers(hf, "full_attention")), page,
        live_pages, rows_live)


def decode_linears(hf: dict) -> list:
    """(K, O) of every `qmatmul` call of one decode step: a convolution
    layer's in_proj (to B, C, x) and out_proj, an attention layer's q, k, v
    and o, a dense layer's gate, up and down, and the head."""
    hid, F = hf["hidden_size"], hf["intermediate_size"]
    D = head_dim(hf)
    qd, kd = hf["num_attention_heads"] * D, hf["num_key_value_heads"] * D
    conv = [(hid, 3 * hid), (hid, hid)]
    attn = [(hid, qd), (hid, kd), (hid, kd), (qd, hid)]
    dense = [(hid, F), (hid, F), (F, hid)]
    return (conv * n_layers(hf, "conv")
            + attn * n_layers(hf, "full_attention")
            + dense * hf["num_dense_layers"] + [(hid, hf["vocab_size"])])


def linear_bytes(hf: dict) -> int:
    """Packed sym_int4 bytes of `decode_linears`' weights."""
    return sum(sym_int4_bytes(o, k) for k, o in decode_linears(hf))


def expert_stack_bytes(hf: dict) -> int:
    """All experts of the sparse layers: what the parameter tree holds."""
    return costs_moe.expert_bytes(hf) * hf["num_experts"] * n_sparse(hf)


def small_bytes(hf: dict) -> int:
    """What stays unpacked beside the embedding: the routers and selection
    biases (float32), the convolutions (float32), the norms (bf16)."""
    hid, E, D = hf["hidden_size"], hf["num_experts"], head_dim(hf)
    L = hf["num_hidden_layers"]
    return (n_sparse(hf) * (E * hid + E) * 4
            + n_layers(hf, "conv") * hf["conv_L_cache"] * hid * 4
            + (2 * L * hid + hid + n_layers(hf, "full_attention") * 2 * D) * 2)


def step_bytes(hf: dict, weight_bytes: int, experts_hit: float,
               state_moved: float, live_pages: float, page: int) -> float:
    """What one decode step must move: the parameter tree without the
    embedding table (`weight_bytes`), less the experts nobody chose; the
    live slots' tails, read and written (`state_moved`, the program's own
    count); the live pages' keys and values over the attention layers
    (`live_pages` of one layer's grid, whole pages)."""
    kv = live_pages * page * kv_token_bytes(hf)
    return (weight_bytes - expert_stack_bytes(hf)
            + experts_hit * costs_moe.expert_bytes(hf) + state_moved + kv)
