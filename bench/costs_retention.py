"""Operations and bytes of the power-retention layers of one decode step, from
the model's shapes: what the kernel `power_retention_decode` must do. Beside
`costs.py` (the dense projections) and `costs_moe.py` (routed experts).

Counted as the ALGORITHM needs them (bigdl_tpu/kvstate.py has the equations):
per layer and LIVE slot the state S and the key sum z cross HBM twice, read
once and written once, whatever the context length; q, k, v (bf16), the
gates (float32) and the output (bf16) go in and out once. An idle slot moves
nothing. What the kernel happens to fetch besides (the feature maps of q and
k, which XLA hands it ready) is the kernel's own business and not counted.

The state's layout is the program's: per KV head `S [D, P]` and `z [P]`,
float32, with P = (D / 2 + 1) * D lanes, the D (D + 1) / 2 pair products of
the symmetric square padded to whole diagonals of D (8256 -> 8320 at D =
128)."""

from __future__ import annotations

_STATE_BPE = 4  # float32 state
_X_BPE = 2  # bf16 q, k, v and output


def head_dim(hf: dict) -> int:
    return hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]


def phi_lanes(hf: dict) -> int:
    """Lanes of the state's feature axis."""
    D = head_dim(hf)
    return (D // 2 + 1) * D


def state_row_bytes(hf: dict) -> int:
    """One slot's state over all layers: S [D, P] and z [P] per KV head."""
    return (hf["num_hidden_layers"] * hf["num_key_value_heads"]
            * (head_dim(hf) + 1) * phi_lanes(hf) * _STATE_BPE)


def decode_cost(hf: dict, rows_live: float) -> dict:
    """One decode step's retention layers with `rows_live` live slots."""
    D, P = head_dim(hf), phi_lanes(hf)
    Hq, Hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    small = ((Hq + 2 * Hkv) * D * _X_BPE  # q, k, v in
             + Hkv * 4  # the gates
             + Hq * D * _X_BPE)  # y out
    per_row = 2 * state_row_bytes(hf) + hf["num_hidden_layers"] * small
    # per state element: the decay, the rank-one update (multiply, add) and
    # a multiply-add per query head of the group; z the same on one row
    group = Hq // Hkv
    flops = (hf["num_hidden_layers"] * Hkv * (D + 1) * P
             * (3 + 2 * group))
    return {"bytes": rows_live * per_row, "flops": rows_live * flops}


def traced_steps(run) -> list:
    """Arguments of the `decode_step` spans that carry state traffic, those
    inside the traced seconds where the run has a device trace, else the
    whole window's. Empty for a program without such spans."""
    spans = [(t, a) for t, _, a in run.span_list("decode_step")
             if a.get("state_rows_live") and "state_bytes_moved" in a]
    dev = run.device
    if dev is not None:
        lo, hi = dev.begin + dev.offset, dev.end + dev.offset
        inside = [(t, a) for t, a in spans if lo <= t < hi]
        spans = inside or spans
    return [a for _, a in spans]
