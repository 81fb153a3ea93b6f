"""Operations and bytes of a hybrid whose state layers are Mamba-1 (jamba:
`attn_layer_period` / `attn_layer_offset` say which layers are attention):
what the selective-scan kernels `mamba1_decode` and `mamba1_prefill` must do,
this model's `qmatmul` calls, and its KV reads with two attention layers
among twenty-eight. Beside `costs_ssm.py` (Mamba-2: a scalar decay a head, a
state `[inner, d_state]`, B and C through the convolution, experts in every
layer: none of it right here).

Counted as the ALGORITHM needs them (bigdl_tpu/kvhybrid.py has the
equations): per Mamba layer and LIVE slot the state `h [d_state, inner]`
(float32) crosses HBM twice in a decode step, read once and written once,
whatever the context; x and dt (a CHANNEL's, both `[inner]`), B and C
(`[d_state]` each) go in and y comes out; A `[d_state, inner]` once a layer.
A prefill of T tokens moves x, dt, B, C in and y out a token and the row's
state once each way. The convolution's tail (`d_conv - 1` inputs of the
inner channels, float32) is read and written by XLA around the kernel: part
of a slot's state row and of a step's bytes, not of the kernel's. An idle
slot moves nothing.

FLOPs a state element and token: the decay's `exp` counted as ONE, the
update's 3 (dt * A, decay * h, + dt x B), the readout's 2: 6. They are the
VPU's and the EUP's; `peaks.json` has the MXU's peak only, so a share of
"peak FLOP/s" computed from them reads LOW (a VPU peak in the table is a
`benchmark` issue's)."""

from __future__ import annotations

from bench.costs import sym_int4_bytes
# the `decode_step` spans that carry state traffic: the same two arguments
# as every kind with a state row
from bench.costs_retention import traced_steps  # noqa: F401

_STATE_BPE = 4  # float32 state and conv tail
_X_BPE = 4  # the kernel's small operands arrive in float32
_DENSE_BPE = 2  # x_proj and dt_proj stay bf16
FLOPS_PER_ELEMENT = 6


def knows(hf: dict) -> bool:
    """Whether `hf` is such a model's config."""
    return "mamba_dt_rank" in hf and "attn_layer_period" in hf


def layer_kinds(hf: dict) -> list:
    period, offset = hf["attn_layer_period"], hf["attn_layer_offset"]
    return ["attention" if i % period == offset else "mamba"
            for i in range(hf["num_hidden_layers"])]


def n_layers(hf: dict, kind: str) -> int:
    return layer_kinds(hf).count(kind)


def dims(hf: dict) -> tuple:
    """(inner width, d_state, dt rank, d_conv)."""
    return (hf["mamba_expand"] * hf["hidden_size"], hf["mamba_d_state"],
            hf["mamba_dt_rank"], hf["mamba_d_conv"])


def ssm_row_bytes(hf: dict) -> int:
    """One slot's scan state over all Mamba layers: what the decode kernel
    reads, and writes again, for a live slot."""
    E, N, _, _ = dims(hf)
    return n_layers(hf, "mamba") * N * E * _STATE_BPE


def state_row_bytes(hf: dict) -> int:
    """One slot's whole state row: the scan state and the convolution's
    tail, all Mamba layers."""
    E, _, _, K = dims(hf)
    return ssm_row_bytes(hf) + n_layers(hf, "mamba") * (K - 1) * E * _STATE_BPE


def _token_bytes(hf: dict) -> int:
    """x, dt in and y out (`[inner]`), B and C (`[d_state]`), one layer."""
    E, N, _, _ = dims(hf)
    return (3 * E + 2 * N) * _X_BPE


def decode_cost(hf: dict, rows_live: float) -> dict:
    """One decode step's `mamba1_decode` calls with `rows_live` live
    slots."""
    E, N, _, _ = dims(hf)
    Lm = n_layers(hf, "mamba")
    per_row = 2 * ssm_row_bytes(hf) + Lm * _token_bytes(hf)
    a_once = Lm * N * E * _X_BPE if rows_live else 0
    return {"bytes": rows_live * per_row + a_once,
            "flops": rows_live * Lm * N * E * FLOPS_PER_ELEMENT}


def prefill_cost(hf: dict, tokens: float, prefills: float = 1.0) -> dict:
    """`mamba1_prefill` over `tokens` tokens in `prefills` calls a layer:
    the tokens' operands, and a row's state and A once each way a call."""
    E, N, _, _ = dims(hf)
    Lm = n_layers(hf, "mamba")
    once = (2 * N * E * _STATE_BPE + N * E * _X_BPE) * Lm
    return {"bytes": tokens * Lm * _token_bytes(hf) + prefills * once,
            "flops": tokens * Lm * N * E * FLOPS_PER_ELEMENT}


def decode_linears(hf: dict) -> list:
    """(K, O) of every `qmatmul` call of one decode step: a Mamba layer's
    in_proj and out_proj, an attention layer's q, k, v and o, the MLP's
    gate, up and down in every layer, and the head."""
    hid, I = hf["hidden_size"], hf["intermediate_size"]
    E = dims(hf)[0]
    D = hf.get("head_dim") or hid // hf["num_attention_heads"]
    qd, kd = hf["num_attention_heads"] * D, hf["num_key_value_heads"] * D
    mlp = [(hid, I), (hid, I), (I, hid)]
    mamba = [(hid, 2 * E), (E, hid)] + mlp
    attn = [(hid, qd), (hid, kd), (hid, kd), (qd, hid)] + mlp
    return (mamba * n_layers(hf, "mamba") + attn * n_layers(hf, "attention")
            + [(hid, hf["vocab_size"])])


def linear_bytes(hf: dict) -> int:
    """Packed sym_int4 bytes of `decode_linears`' weights."""
    return sum(sym_int4_bytes(o, k) for k, o in decode_linears(hf))


def small_projection_bytes(hf: dict) -> int:
    """x_proj and dt_proj of every Mamba layer, bf16."""
    E, N, R, _ = dims(hf)
    return n_layers(hf, "mamba") * ((R + 2 * N) * E + E * R) * _DENSE_BPE


def kv_token_bytes(hf: dict) -> int:
    """bf16 K and V of one cached token over the ATTENTION layers."""
    D = hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]
    return (2 * hf["num_key_value_heads"] * D * 2
            * n_layers(hf, "attention"))


def step_bytes(hf: dict, weight_bytes: int, state_moved: float,
               live_pages: float, page: int) -> float:
    """What one decode step must move: the parameter tree without the
    embedding table (`weight_bytes`: packed weights once, the unpacked
    small ones), the live slots' state rows, read and written
    (`state_moved`, the program's own count: scan state and tails), and the
    live pages' keys and values (`live_pages` of one layer's grid, whole
    pages as `costs_paged` counts them)."""
    return weight_bytes + state_moved + live_pages * page * kv_token_bytes(hf)
