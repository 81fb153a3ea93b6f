"""Operations and bytes a call must do, from its shapes; and the table of
peaks. The yardstick's copy: `bigdl_tpu/benchmark/roofline.py`
(`qmatmul_cost`, `decode_attention_cost`) and `utils/flops._CHIPS` hold the
originals (PERF.md, Open questions), which the program may change.

Counted as the ALGORITHM needs them, not as a kernel happens to fetch them:
each packed weight and each live KV slot crosses HBM once per call."""

from __future__ import annotations

import json
import os

_X_BPE = 2  # bf16 activations in
_OUT_BPE = 2  # bf16 out


def peaks(device_kind: str) -> dict:
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path, encoding="utf-8") as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"no peaks on record for device_kind {device_kind!r}; add it to "
            "bench/peaks.json with its source")
    return table[device_kind]


def sym_int4_bytes(O: int, K: int) -> int:
    """Packed nibbles plus one float16 scale per 32 weights."""
    return O * (K // 2 + (K // 32) * 2)


def qmatmul_cost(M: int, K: int, O: int) -> dict:
    """y[M, O] = x[M, K] @ dequant(W[O, K])^T, sym_int4: weights once,
    activations in and out once."""
    return {"bytes": sym_int4_bytes(O, K) + M * K * _X_BPE + M * O * _OUT_BPE,
            "flops": 2 * M * K * O}


def roofline_seconds(cost: dict, peak: dict) -> tuple:
    """(least seconds the chip could take, which bound it is)."""
    t_mem = cost["bytes"] / peak["hbm_bytes_per_s"]
    t_mxu = cost["flops"] / peak["bf16_flops_per_s"]
    return (t_mem, "memory") if t_mem >= t_mxu else (t_mxu, "compute")


def decode_linears(hf: dict) -> list:
    """(K, O) of every fused-kernel projection of one decode step of the
    served (merged) layout: per layer wqkv, wo, and for a dense MLP
    w_gateup and w_down; then the LM head. MoE expert FFNs are on the XLA
    route and are not qmatmul calls."""
    H, I = hf["hidden_size"], hf["intermediate_size"]
    D = hf.get("head_dim") or H // hf["num_attention_heads"]
    qd, kd = hf["num_attention_heads"] * D, hf["num_key_value_heads"] * D
    per_layer = [(H, qd + 2 * kd), (qd, H)]
    if not hf.get("num_local_experts"):
        per_layer += [(H, 2 * I), (I, H)]
    return per_layer * hf["num_hidden_layers"] + [(H, hf["vocab_size"])]


def tree_bytes(params) -> int:
    """Bytes of every array of the parameter tree but the embedding table,
    of which a step reads one row per token."""
    import jax

    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        if "embed" in jax.tree_util.keystr(path):
            continue
        total += leaf.size * leaf.dtype.itemsize
    return total


def kv_bytes(hf: dict, live_tokens: int) -> int:
    """bf16 K and V of `live_tokens` cache slots over all layers."""
    D = hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]
    return (2 * live_tokens * hf["num_key_value_heads"] * D * 2
            * hf["num_hidden_layers"])
