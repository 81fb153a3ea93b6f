"""Operations and bytes of one decode step of a hybrid whose layers are
Mamba-2 or attention by `layer_types` (granitemoehybrid): what the kernel
`mamba2_decode` must do, this model's `qmatmul` calls, and its KV reads with
a few attention layers among many. Beside `costs.py` (a GQA model's dense
projections: it reads an attention block and an `intermediate_size`-wide MLP
in EVERY layer, which this model has not), `costs_moe.py` (routed experts:
right for this model as it stands) and `costs_paged.py` (KV pages in every
layer: not right here).

Counted as the ALGORITHM needs them (bigdl_tpu/kvhybrid.py has the
equations): per Mamba layer and LIVE slot the state `h [heads, head size,
d_state]` (float32) crosses HBM twice, read once and written once, whatever
the context length; x, dt (per head), B, C go in and y comes out. The
convolution's tail (`d_conv - 1` rows of the conv channels, float32) is
read and written by XLA around the kernel: it is part of a slot's state row
and of a step's bytes, not of the kernel's. An idle slot moves nothing."""

from __future__ import annotations

from bench import costs_moe
from bench.costs import sym_int4_bytes
# the `decode_step` spans that carry state traffic: the same two arguments
# as a model with a state in every layer
from bench.costs_retention import traced_steps  # noqa: F401

_STATE_BPE = 4  # float32 state and conv tail
_X_BPE = 4  # the kernel's small operands arrive in float32


def n_layers(hf: dict, kind: str) -> int:
    return sum(k == kind for k in hf["layer_types"])


def dims(hf: dict) -> tuple:
    """(heads, head size, d_state, inner width, conv channels)."""
    H, P, N = hf["mamba_n_heads"], hf["mamba_d_head"], hf["mamba_d_state"]
    inner = H * P
    return H, P, N, inner, inner + 2 * hf["mamba_n_groups"] * N


def ssm_row_bytes(hf: dict) -> int:
    """One slot's recurrence state over all Mamba layers: what the kernel
    reads, and writes again, for a live slot."""
    _, _, N, inner, _ = dims(hf)
    return n_layers(hf, "mamba") * inner * N * _STATE_BPE


def state_row_bytes(hf: dict) -> int:
    """One slot's whole state row: the recurrence state and the
    convolution's tail, all Mamba layers."""
    _, _, _, _, C = dims(hf)
    tail = n_layers(hf, "mamba") * (hf["mamba_d_conv"] - 1) * C * _STATE_BPE
    return ssm_row_bytes(hf) + tail


def decode_cost(hf: dict, rows_live: float) -> dict:
    """One decode step's `mamba2_decode` calls with `rows_live` live
    slots."""
    H, P, N, inner, _ = dims(hf)
    small = (inner + H + 2 * N + inner) * _X_BPE  # x, dt, B, C in; y out
    per_row = 2 * ssm_row_bytes(hf) + n_layers(hf, "mamba") * small
    # per state element: the decay, the rank-one update (multiply, add) and
    # the readout's multiply-add
    flops = n_layers(hf, "mamba") * inner * N * 5
    return {"bytes": rows_live * per_row, "flops": rows_live * flops}


def decode_linears(hf: dict) -> list:
    """(K, O) of every `qmatmul` call of one decode step: a Mamba layer's
    in_proj and out_proj, an attention layer's q, k, v and o, the shared
    MLP's gate, up and down in every layer, and the head."""
    hid, S = hf["hidden_size"], hf["shared_intermediate_size"]
    H, _, _, inner, C = dims(hf)
    D = hf.get("head_dim") or hid // hf["num_attention_heads"]
    qd, kd = hf["num_attention_heads"] * D, hf["num_key_value_heads"] * D
    shared = [(hid, S), (hid, S), (S, hid)]
    mamba = [(hid, inner + C + H), (inner, hid)] + shared
    attn = [(hid, qd), (hid, kd), (hid, kd), (qd, hid)] + shared
    return (mamba * n_layers(hf, "mamba") + attn * n_layers(hf, "attention")
            + [(hid, hf["vocab_size"])])


def linear_bytes(hf: dict) -> int:
    """Packed sym_int4 bytes of `decode_linears`' weights."""
    return sum(sym_int4_bytes(o, k) for k, o in decode_linears(hf))


def expert_stack_bytes(hf: dict) -> int:
    """All experts of all layers (every layer has them)."""
    return (costs_moe.expert_bytes(hf) * hf["num_local_experts"]
            * hf["num_hidden_layers"])


def kv_token_bytes(hf: dict) -> int:
    """bf16 K and V of one cached token over the ATTENTION layers."""
    D = hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]
    return (2 * hf["num_key_value_heads"] * D * 2
            * n_layers(hf, "attention"))


def step_bytes(hf: dict, weight_bytes: int, experts_hit: float,
               state_moved: float, live_pages: float, page: int) -> float:
    """What one decode step must move: the packed parameter tree without
    the embedding table (`weight_bytes`), less the experts nobody chose;
    the live slots' state rows, read and written (`state_moved`, the
    program's own count); the live pages' keys and values (`live_pages` of
    one layer's grid, whole pages as `costs_paged` counts them)."""
    kv = live_pages * page * kv_token_bytes(hf)
    return (weight_bytes - expert_stack_bytes(hf)
            + experts_hit * costs_moe.expert_bytes(hf) + state_moved + kv)

