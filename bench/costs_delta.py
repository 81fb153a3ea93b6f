"""Operations and bytes of one decode step of a hybrid whose layers are Kimi
delta attention (KDA) or gated attention by `gqa_layers` (solar_open2), with
routed experts of which this program may hold ONE RANK'S SHARE in every
layer: what the kernel `kda_decode` must do, this model's `qmatmul` calls, the
experts HELD here, and the state a slot carries. Beside `costs.py` (a GQA
model's dense projections in EVERY layer, which this model has not),
`costs_paged.py` (KV pages in every layer: `decode_cost` takes
`num_hidden_layers` for its layer count, twelve of which three keep keys),
`costs_moe.py` (one layer's experts: `expert_ffn_cost` reads this model right
as it stands, hidden size and `moe_intermediate_size`, and the spans count
the experts HELD and hit here) and `costs_sparse.py` (lightning's state: a
fixed decay a head, no read before the write).

Counted as the ALGORITHM needs them (bigdl_tpu/kvhybrid.py has the
equations): per KDA layer and LIVE slot the state `[heads, head size, head
size]` (float32) crosses HBM twice, read once and written once, whatever the
context length; q, k, v, the log-decay g (a vector a head) and beta go in and
o comes out; a head's step is a decay, a read (S'^T k), a rank-one update and
a second read (S^T q): 7 flops a state element. The three convolutions' tails
(`short_conv_kernel_size - 1` inputs of 3 x heads x head size channels,
float32) are read and written by XLA around the kernel: part of a slot's
state row and of a step's bytes, not of the kernel's. An idle slot moves
nothing, so a share of this roofline cannot read over 100%."""

from __future__ import annotations

from bench import costs_moe, costs_paged
from bench.costs import sym_int4_bytes
# the `decode_step` spans that carry state traffic: the same two arguments
# as a model with a state in every layer
from bench.costs_retention import traced_steps  # noqa: F401

_STATE_BPE = 4  # float32 state and tails
_X_BPE = 4  # the kernel's small operands arrive in float32
_KV_BPE = 2  # bf16 pages
LOW_RANK = 128  # of the decay's and the output gate's pairs (`assumed`)
SHARE_KEY = "expert_parallel_share"


def knows(hf: dict) -> bool:
    return "linear_attn_config" in hf and "gqa_layers" in hf


def n_layers(hf: dict, kind: str) -> int:
    """Layers of `kind`: "attention" (`gqa_layers`) or "kda" (the rest)."""
    n = len(hf["gqa_layers"])
    return n if kind == "attention" else hf["num_hidden_layers"] - n


def dims(hf: dict) -> tuple:
    """(KDA heads, head size, taps of the short convolutions)."""
    lin = hf["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]


def kda_row_bytes(hf: dict) -> int:
    """One slot's delta-rule state over all KDA layers: what the kernel
    reads, and writes again, for a live slot."""
    H, D, _ = dims(hf)
    return n_layers(hf, "kda") * H * D * D * _STATE_BPE


def state_row_bytes(hf: dict) -> int:
    """One slot's whole state row: the state and the three convolutions'
    tails, all KDA layers."""
    H, D, K = dims(hf)
    return kda_row_bytes(hf) + (n_layers(hf, "kda") * (K - 1) * 3 * H * D
                                * _STATE_BPE)


def kda_decode_cost(hf: dict, rows_live: float) -> dict:
    """One decode step's `kda_decode` calls with `rows_live` live slots."""
    H, D, _ = dims(hf)
    small = (4 * H * D + H + H * D) * _X_BPE  # q, k, v, g, beta in; o out
    per_row = n_layers(hf, "kda") * (2 * H * D * D * _STATE_BPE + small)
    return {"bytes": rows_live * per_row,
            "flops": rows_live * n_layers(hf, "kda") * 7 * H * D * D}


def kv_token_bytes(hf: dict) -> int:
    """bf16 K and V of one cached token over the GQA layers."""
    return (2 * hf["num_key_value_heads"] * hf["head_dim"] * _KV_BPE
            * n_layers(hf, "attention"))


def attn_decode_cost(hf: dict, page: int, live_pages: float,
                     rows_live: float) -> dict:
    """One decode step's paged attention over the GQA layers
    (`costs_paged.decode_cost` over THOSE layers)."""
    return costs_paged.decode_cost(
        dict(hf, num_hidden_layers=n_layers(hf, "attention")), page,
        live_pages, rows_live)


def experts_held(hf: dict) -> int:
    """Routed experts a layer holds here (`n_routed_experts` counts them)."""
    return hf["n_routed_experts"]


def router_width(hf: dict) -> int:
    return (hf.get(SHARE_KEY) or {}).get("router_experts",
                                         hf["n_routed_experts"])


def decode_linears(hf: dict) -> list:
    """(K, O) of every `qmatmul` call of one decode step: a KDA layer's q,
    k, v and o, a GQA layer's q, k, v, gate and o, every layer's shared
    expert, and the head."""
    hid, I = hf["hidden_size"], hf["moe_intermediate_size"]
    H, D, _ = dims(hf)
    qd = hf["num_attention_heads"] * hf["head_dim"]
    kd = hf["num_key_value_heads"] * hf["head_dim"]
    Is = I * hf["n_shared_experts"]
    kda = [(hid, H * D)] * 3 + [(H * D, hid)]
    attn = [(hid, qd), (hid, kd), (hid, kd), (hid, qd), (qd, hid)]
    shared = [(hid, Is), (hid, Is), (Is, hid)]
    return (kda * n_layers(hf, "kda") + attn * n_layers(hf, "attention")
            + shared * hf["num_hidden_layers"] + [(hid, hf["vocab_size"])])


def linear_bytes(hf: dict) -> int:
    """Packed sym_int4 bytes of `decode_linears`' weights."""
    return sum(sym_int4_bytes(o, k) for k, o in decode_linears(hf))


def expert_stack_bytes(hf: dict) -> int:
    """The experts HELD here, all layers: what the parameter tree holds."""
    return (costs_moe.expert_bytes(hf) * experts_held(hf)
            * hf["num_hidden_layers"])


def small_bytes(hf: dict) -> int:
    """What stays unpacked beside the embedding: the routers and selection
    biases over the router's WHOLE width (float32); a KDA layer's taps,
    `A_log`, `dt_bias` and the gate's bias (float32), its two low-rank pairs,
    `b_proj` and its output norm (bf16); the layer norms and the final
    norm (bf16)."""
    hid, Er, L = hf["hidden_size"], router_width(hf), hf["num_hidden_layers"]
    H, D, K = dims(hf)
    kda = ((K * 3 * H * D + H + 2 * H * D) * 4
           + (2 * (LOW_RANK * hid + H * D * LOW_RANK) + H * hid + D) * 2)
    return (L * (Er * hid + Er) * 4 + n_layers(hf, "kda") * kda
            + (2 * L * hid + hid) * 2)


def step_bytes(hf: dict, weight_bytes: int, experts_hit: float,
               state_moved: float, live_pages: float, page: int) -> float:
    """What one decode step must move: the parameter tree without the
    embedding table (`weight_bytes`), less the held experts nobody chose;
    the live slots' state rows, read and written (`state_moved`, the
    program's own count); the live pages' keys and values over the GQA
    layers (`live_pages` of one layer's grid, whole pages)."""
    kv = live_pages * page * kv_token_bytes(hf)
    return (weight_bytes - expert_stack_bytes(hf)
            + experts_hit * costs_moe.expert_bytes(hf) + state_moved + kv)
