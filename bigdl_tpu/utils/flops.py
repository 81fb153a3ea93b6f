"""Model FLOP / byte accounting for MFU and bandwidth-utilization reporting.

The reference's benchmark harness records latency only
(/root/reference/python/llm/src/ipex_llm/utils/benchmark_util_4_29.py:489-519);
BASELINE.md's north star additionally demands >=50% MFU for QLoRA
finetuning, which requires knowing the model FLOPs per token and the
chip's peak. Conventions:

* MFU counts *model* FLOPs (the PaLM convention), not hardware FLOPs —
  rematerialized forwards don't inflate it.
* Decode at batch=1 is HBM-bound, so we also report MBU (memory-bandwidth
  utilization): bytes of weights + KV that must stream per token divided
  by (bandwidth * latency).
"""

from __future__ import annotations

from typing import Optional

# device_kind prefix -> (peak bf16 FLOP/s, HBM bytes/s). Public specs:
# v4 275 TF / 1.2 TB/s, v5e 197 TF / 819 GB/s, v5p 459 TF / 2.8 TB/s,
# v6e (Trillium) 918 TF / 1.6 TB/s.
_CHIPS = {
    "TPU v4": (275e12, 1228e9),
    "TPU v5 lite": (197e12, 819e9),
    "TPU v5e": (197e12, 819e9),
    "TPU v5p": (459e12, 2765e9),
    "TPU v6 lite": (918e12, 1640e9),
    "TPU v6e": (918e12, 1640e9),
}


def chip_specs(device=None) -> Optional[tuple[float, float]]:
    """(peak_flops, hbm_bytes_per_s) for the given (default: first)
    device. None on the CPU (tests: there is no peak to compare with);
    an accelerator whose `device_kind` is not in the table is an error,
    not a default."""
    import jax

    if device is None:
        device = jax.devices()[0]
    if device.platform == "cpu":
        return None
    kind = getattr(device, "device_kind", "") or ""
    for prefix, specs in _CHIPS.items():
        if kind.startswith(prefix):
            return specs
    raise ValueError(
        f"no peak FLOP/s / HBM bandwidth on record for device_kind "
        f"{kind!r} (platform {device.platform!r}); add it to "
        "utils/flops._CHIPS with its source"
    )


def matmul_params(config) -> dict:
    """Per-component matmul parameter counts (what streams from HBM and
    what the MXU multiplies). Embedding gather is excluded (one row).

    For MoE configs `active` counts only the top-k routed experts (+ the
    always-on shared expert) — the FLOPs actually spent per token — while
    `total` counts every expert resident in HBM.
    """
    L, H = config.num_hidden_layers, config.hidden_size
    attn = L * (config.q_dim * H + 2 * config.kv_dim * H + H * config.q_dim)
    if config.is_moe:
        I = config.moe_intermediate_size or config.intermediate_size
        expert = 3 * H * I
        mlp_active = L * (config.num_experts_per_tok * expert
                          + config.num_experts * H)  # + router
        mlp_total = L * (config.num_experts * expert + config.num_experts * H)
        shared = config.shared_expert_intermediate_size
        if shared:
            mlp_active += L * (3 * H * shared + H)
            mlp_total += L * (3 * H * shared + H)
    else:
        mlp_active = mlp_total = L * 3 * H * config.intermediate_size
    head = config.vocab_size * H
    return {
        "attn": attn,
        "mlp_active": mlp_active,
        "mlp_total": mlp_total,
        "lm_head": head,
        "active": attn + mlp_active + head,
        "total": attn + mlp_total + head,
    }


def decode_flops_per_token(config, context_len: int = 0, batch: int = 1) -> float:
    """Matmul FLOPs for one decode step per sequence: 2 * active params
    + attention score/value FLOPs against `context_len` cached tokens."""
    p = matmul_params(config)
    attn_ctx = 2 * 2 * config.num_attention_heads * config.head_dim_ * context_len
    return 2 * p["active"] + attn_ctx


def train_flops_per_token(config, full_finetune: bool = False) -> float:
    """QLoRA convention: forward 2P + backward-through-activations 2P; the
    frozen base contributes no weight-gradient matmuls. Full finetune adds
    the 2P weight-gradient term (the standard 6P)."""
    p = matmul_params(config)
    return (6 if full_finetune else 4) * p["active"]


def decode_bytes_per_token(
    config, context_len: int = 0, batch: int = 1,
    weight_bits: float = 4.5, kv_bytes: int = 2,
) -> float:
    """HBM bytes that must stream for one decode step: every weight once
    (shared across the batch) + each sequence's KV read/write.

    weight_bits: effective bits/param incl. scales — sym_int4 with one
    fp16 scale per 32-block is 4 + 16/32 = 4.5.
    """
    p = matmul_params(config)
    weight_bytes = p["total"] * weight_bits / 8
    kv = (config.num_hidden_layers * 2 * config.kv_dim
          * context_len * kv_bytes) * batch
    return weight_bytes + kv


def mfu(flops_per_token: float, tokens_per_s: float, device=None) -> Optional[float]:
    specs = chip_specs(device)
    if specs is None:
        return None
    return flops_per_token * tokens_per_s / specs[0]


def mbu(bytes_per_token: float, tokens_per_s: float, device=None) -> Optional[float]:
    specs = chip_specs(device)
    if specs is None:
        return None
    return bytes_per_token * tokens_per_s / specs[1]
