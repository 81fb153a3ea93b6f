"""Environment-flag configuration.

The reference's config surface is kwargs plus ~40 env vars (SURVEY.md §5:
IPEX_LLM_QUANTIZE_KV_CACHE, IPEX_LLM_COMPRESS_KV_CACHE, IPEX_LLM_LOW_MEM,
IPEX_LLM_PERFORMANCE_MODE, IPEX_LLM_LAST_LM_HEAD,
KV_CACHE_ALLOC_BLOCK_LENGTH, BIGDL_LLM_LINEAR_THRESHOLD, ...). The TPU
build keeps the same shape — explicit kwargs win; env flags set defaults —
under the BIGDL_TPU_* namespace. All flags are read lazily so tests can
monkeypatch os.environ.
"""

from __future__ import annotations

import os
from typing import Optional


def _bool(name: str, default: bool = False) -> bool:
    v = os.environ.get(name)
    if v is None:
        return default
    return v.strip().lower() not in ("", "0", "false", "no", "off")


def _int(name: str, default: Optional[int] = None) -> Optional[int]:
    v = os.environ.get(name)
    if v is None or not v.strip():
        return default
    return int(v)


def quantize_kv_default() -> bool:
    """FP8 KV cache (reference IPEX_LLM_QUANTIZE_KV_CACHE)."""
    return _bool("BIGDL_TPU_QUANTIZE_KV_CACHE")


def compress_kv_budget() -> Optional[int]:
    """SnapKV budget in slots; unset disables (reference
    IPEX_LLM_COMPRESS_KV_CACHE enables at a built-in threshold)."""
    if _bool("BIGDL_TPU_COMPRESS_KV_CACHE"):
        return _int("BIGDL_TPU_COMPRESS_KV_BUDGET", 1024)
    return None


def performance_mode() -> bool:
    """Auto prompt-lookup decoding for long prompts (reference
    IPEX_LLM_PERFORMANCE_MODE=1 auto-enables lookahead, lookup.py:63-83)."""
    return _bool("BIGDL_TPU_PERFORMANCE_MODE")


def last_lm_head_default() -> bool:
    """Compute lm-head on the last position only during prefill
    (reference IPEX_LLM_LAST_LM_HEAD / reshape_lm_head_input,
    low_bit_linear.py:262-270). Default ON: generate() never reads
    earlier prefill logits."""
    return _bool("BIGDL_TPU_LAST_LM_HEAD", True)


def cache_slot_quantum() -> int:
    """KV cache size rounding (reference KV_CACHE_ALLOC_BLOCK_LENGTH)."""
    return _int("BIGDL_TPU_KV_CACHE_QUANTUM", 64)


def native_disabled() -> bool:
    return _bool("BIGDL_TPU_DISABLE_NATIVE")
