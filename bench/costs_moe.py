"""Operations and bytes of the routed experts of a sparse-expert model, from
its shapes: what the grouped kernel `moe_qmatmul` must do in one decode step.
Beside `costs.py`, which counts the dense projections (its docstring's "MoE
expert FFNs are on the XLA route" dates from before the kernel).

Counted as the ALGORITHM needs them: the packed weights of each (layer,
expert) pair that got at least one assignment cross HBM once, an expert
nobody chose is not read, and every assignment's activations go in and out
once per matmul."""

from __future__ import annotations

from bench.costs import _OUT_BPE, _X_BPE, sym_int4_bytes


def expert_shape(hf: dict) -> tuple:
    """(H, I): hidden size and one expert's intermediate size."""
    return hf["hidden_size"], (hf.get("moe_intermediate_size")
                               or hf["intermediate_size"])


def expert_bytes(hf: dict) -> int:
    """Packed sym_int4 bytes of ONE expert of one layer: gate, up [I, H] and
    down [H, I]."""
    H, I = expert_shape(hf)
    return 2 * sym_int4_bytes(I, H) + sym_int4_bytes(H, I)


def expert_stack_bytes(hf: dict) -> int:
    """All experts of all layers: what the parameter tree holds of them."""
    return (expert_bytes(hf) * hf["num_local_experts"]
            * hf["num_hidden_layers"])


def expert_ffn_cost(hf: dict, experts_hit: float, assignments: float) -> dict:
    """One step's routed experts: `experts_hit` (layer, expert) pairs read,
    `assignments` token-expert rows computed. Per row: x in (bf16), the
    gated product out and in again (bf16), y out (float32, what the combine
    reads)."""
    H, I = expert_shape(hf)
    per_row = H * _X_BPE + I * (_OUT_BPE + _X_BPE) + H * 4
    return {"bytes": experts_hit * expert_bytes(hf) + assignments * per_row,
            "flops": assignments * 3 * 2 * H * I}


def traced_steps(run) -> list:
    """Arguments of the `decode_step` spans that carry expert load, those
    inside the traced seconds where the run has a device trace, else the
    whole window's."""
    spans = [(t, a) for t, _, a in run.span_list("decode_step")
             if a.get("moe_experts") and a.get("moe_assignments")]
    dev = run.device
    if dev is not None:
        lo, hi = dev.begin + dev.offset, dev.end + dev.offset
        inside = [(t, a) for t, a in spans if lo <= t < hi]
        spans = inside or spans
    return [a for _, a in spans]
