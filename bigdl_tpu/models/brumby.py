"""Brumby (Manifest AI, Brumby-14B-Base): Qwen3's decoder block with the
softmax attention replaced by POWER RETENTION (arXiv 2507.04239).

Nothing of the block is written here: `models/llama.forward` runs it, one
block with two attention kinds chosen by `config.attention_kind`, and
`bigdl_tpu/kvstate.py` holds the retention layer and its state. What this
family adds is the cache hook: a model of this kind keeps a recurrent state
of fixed size and no keys, so `TpuModel.generate` gets a `RetentionState`
from `init_cache` as it gets an `RwkvState` from RWKV's. The serving engine
does not go through the hook: `InferenceEngine(paged=True)` holds the state
as rows of its page table (serving/pages.py).
"""

from __future__ import annotations

from bigdl_tpu import kvstate
from bigdl_tpu.models import llama
from bigdl_tpu.models.config import ModelConfig

init_params = llama.init_params
quantize_params = llama.quantize_params
forward = llama.forward
merge_fused_params = llama.merge_fused_params
unmerge_fused_params = llama.unmerge_fused_params


def init_cache(config: ModelConfig, batch: int, cache_len: int = 0,
               quantize_kv: bool = False) -> kvstate.RetentionState:
    """`generate_tokens`' family hook. `cache_len` only bounds the
    positions (rope scaling reads it): the state does not grow."""
    if quantize_kv:
        raise NotImplementedError(
            f"quantize_kv is not available for {kvstate.KIND}: its cache is "
            "a float32 recurrent state, not keys and values (fp8 for state "
            "is ROADMAP R4)")
    return kvstate.init_state(
        config.num_hidden_layers, batch, config.num_key_value_heads,
        config.head_dim_, max_len=cache_len)
