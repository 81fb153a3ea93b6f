"""Model zoo registry.

The reference dispatches ~70 `model_type` branches in `_optimize_post`
(convert.py:1251-2027) to per-file patched forwards. Here a family
registry maps HF `model_type` to a (init, quantize, forward) triple; one
decoder-family implementation covers the llama-shaped architectures and
further families register alongside it.
"""

from __future__ import annotations

from bigdl_tpu.models.config import ModelConfig, PRESETS
from bigdl_tpu.models import llama

# model_type -> module implementing init_params / quantize_params / forward.
# One decoder-family implementation covers every llama-shaped architecture
# via ModelConfig flags (bigdl_tpu/models/llama.py docstring lists them).
_FAMILIES = {
    "llama": llama,
    "mistral": llama,
    "qwen2": llama,
    "gemma": llama,
    "gemma2": llama,
    "gemma3": llama,  # dual rope via rope_local_theta + layer_types
    "gemma3_text": llama,
    "phi3": llama,
    "baichuan": llama,
    "internlm2": llama,
    "internlm": llama,  # v1: biased qkv+o
    "aquila": llama,  # llama-shaped (BAAI Aquila/Aquila2)
    "starcoder2": llama,
    "stablelm": llama,
    "minicpm": llama,
    "glm": llama,
    # THUDM chatglm2/3 + glm-4 remote-code schema: interleaved half-dim
    # rope + fused checkpoints, translated in config._hf_chatglm and
    # convert/hf._chatglm_layer
    "chatglm": llama,
    "gpt2": llama,
    "mpt": llama,  # alibi + fused Wqkv, translated in config/_hf_mpt
    "bloom": llama,
    "gpt_neox": llama,
    "mixtral": llama,
    "qwen2_moe": llama,
    "qwen3": llama,  # per-head qk RMSNorm via qk_norm flag
    "qwen3_moe": llama,
    "phi": llama,  # parallel residual + shared norm, biased everything
    "cohere": llama,  # parallel residual, interleaved rope, logit scale
    "yi": llama,
    # parallel attn/mlp + grouped fused qkv, translated in
    # config._hf_falcon and convert/hf._falcon_layer
    "falcon": llama,
    "qwen": llama,  # v1: fused c_attn, halved-ff gate/up, logn scaling
    "deci": llama,  # variable GQA replicated to uniform kv heads at ingest
    "gpt_bigcode": llama,  # starcoder v1: MQA + learned positions
    "phixtral": llama,  # phi decoder + MoE over non-gated fc1/fc2 experts
    # phi-3-vision: optimized as phi3 on the text path (reference
    # convert.py:947,1829 treats phi3/phi3_v identically)
    "phi3_v": llama,
    # internlm-xcomposer2: internlm2 decoder; Plora image-row deltas are
    # a vision-path addition (reference convert.py:984,1523) — text path
    # is exactly internlm2
    "internlmxcomposer2": llama,
    # Megrez-3B-Omni: the llm half is llama (reference convert.py:1044
    # rewrites model.llm.config.model_type = "llama"); towers load
    # separately like minicpmv (same `llm.` checkpoint prefix)
    "megrezo": llama,
}

from bigdl_tpu.models import qwen2_vl  # noqa: E402  (delegates text to llama)

_FAMILIES["qwen2_vl"] = qwen2_vl

from bigdl_tpu.models import qwen_vl  # noqa: E402  (delegates text to llama)

# Qwen-VL checkpoints ship model_type "qwen" + a `visual` dict; the
# text side is the qwen v1 decoder, the tower/resampler live here
_FAMILIES["qwen_vl"] = qwen_vl

from bigdl_tpu.models import minicpmv  # noqa: E402  (delegates text to llama)

_FAMILIES["minicpmv"] = minicpmv

from bigdl_tpu.models import minicpmo  # noqa: E402  (adds whisper-apm audio)

# MiniCPM-o 2.6: minicpmv's vision path + a Whisper-encoder audio tower
# projected into the qwen2-shaped LLM (models/minicpmo.py)
_FAMILIES["minicpmo"] = minicpmo

from bigdl_tpu.models import qwen2_audio  # noqa: E402  (whisper-pool tower)

# Qwen2-Audio: whisper-style encoder with an in-encoder AvgPool1d(2) +
# single-linear projector over the qwen2 decoder (models/qwen2_audio.py)
_FAMILIES["qwen2_audio"] = qwen2_audio

from bigdl_tpu.models import mllama  # noqa: E402  (cross-attn decoder)

_FAMILIES["mllama"] = mllama
_FAMILIES["mllama_text_model"] = mllama  # nested text_config model_type

from bigdl_tpu.models import internvl  # noqa: E402  (delegates text to llama)

_FAMILIES["internvl"] = internvl
_FAMILIES["internvl_chat"] = internvl  # trust_remote_code model_type

from bigdl_tpu.models import janus  # noqa: E402  (delegates text to llama)

_FAMILIES["janus"] = janus
_FAMILIES["multi_modality"] = janus  # original janus checkpoints

from bigdl_tpu.models import chatglm4v  # noqa: E402  (delegates text to llama)

# THUDM glm-4v-9b: chatglm text schema + EVA2-CLIP tower/adapter
_FAMILIES["chatglm4v"] = chatglm4v

from bigdl_tpu.models import deepseek  # noqa: E402  (MLA latent-KV cache)

_FAMILIES["deepseek_v2"] = deepseek
_FAMILIES["deepseek_v3"] = deepseek
_FAMILIES["minicpm3"] = deepseek
_FAMILIES["glm4_moe_lite"] = deepseek  # GLM-4.7-Flash: DeepSeek-V3's layers

from bigdl_tpu.models import yuan  # noqa: E402  (LFA conv-filtered attention)

# yuan's cache composes the KV cache with the conv-filter state, so it
# has its own module + init_cache hook (models/yuan.py)
_FAMILIES["yuan"] = yuan

from bigdl_tpu.models import baichuan_m1  # noqa: E402  (conv-enhanced KV)

# baichuan-m1 convolves K/V over time and carries the pre-conv tail in
# its cache (models/baichuan_m1.py), like yuan's filter state
_FAMILIES["baichuan_m1"] = baichuan_m1

from bigdl_tpu.models import rwkv  # noqa: E402  (attention-free recurrence)

# rwkv replaces the KV cache with a recurrent state: it exposes
# `init_cache` returning an RwkvState, which generate.generate_tokens
# consumes through the family cache_init hook
_FAMILIES["rwkv"] = rwkv
_FAMILIES["rwkv5"] = rwkv

from bigdl_tpu.models import brumby  # noqa: E402  (llama block, state cache)

# brumby is the llama block with power-retention attention
# (config.attention_kind): llama.forward runs it, and the family's
# `init_cache` hands generate a recurrent state where the others get a KV
# cache (bigdl_tpu/kvstate.py)
_FAMILIES["brumby"] = brumby

# whisper (models/whisper.py) is an encoder-decoder family with its own
# WhisperConfig and (params, mel, prompt) call shape — deliberately NOT in
# _FAMILIES, whose consumers (optimize_model, TpuModel.generate) assume
# the decoder signature; it is served through the api_server's
# /v1/audio/transcriptions endpoint (whisper= kwarg) instead
#
# sd (models/sd.py) is likewise outside the registry: a diffusion UNet +
# DDIM sampler with (latents, t, context) call shape — pair it with the
# diffusers attention processor in integrations/diffusers.py or drive it
# directly (params_from_state_dict ingests a diffusers UNet checkpoint)


# families imported on first use, so that `import bigdl_tpu` and the other
# models' programs never load (or trace through) their modules
_LAZY_FAMILIES = {
    # Mamba-2 and NoPE attention layers mixed by index, a state row beside
    # KV pages in one slot (bigdl_tpu/kvhybrid.py)
    "granitemoehybrid": "bigdl_tpu.models.granitemoehybrid",
    # window and full attention layers mixed by index, a rope in the window
    # layers only, two groups of pages in one slot (bigdl_tpu/kvwindow.py)
    "smallthinker": "bigdl_tpu.models.smallthinker",
    # full and window attention layers that differ in query heads, rope and
    # projections, a sigmoid gate a head, a dense first layer before the
    # sigmoid-routed experts; smallthinker's two groups of pages
    "laguna": "bigdl_tpu.models.laguna",
    # Qwen3-MoE's network generated by diffusion over blocks: a step is a
    # pass over a block of positions (serving/blocks.py)
    "sdar_moe": "bigdl_tpu.models.sdar",
    # block-sparse attention layers (a selection of pages a row and KV
    # head) between lightning attention layers (a state row a slot)
    # (bigdl_tpu/kvsparse.py)
    "minicpm_sala": "bigdl_tpu.models.minicpm_sala",
    # Mamba-1 layers (a selective scan: the decay a channel's and a state
    # index's) with a multi-query NoPE attention layer every few, granite's
    # state row beside KV pages with the state the other way round
    # (bigdl_tpu/kvhybrid.py)
    "jamba": "bigdl_tpu.models.jamba",
    # gated short-convolution layers (their only state the convolution's
    # tail) with a GQA layer every few on heads of 64, kept two to a row of
    # lanes; dense layers lead, then sigmoid-routed experts with a
    # selection bias; granite's state row beside KV pages with no
    # recurrence state at all (bigdl_tpu/kvhybrid.py)
    "lfm2_moe": "bigdl_tpu.models.lfm2_moe",
    # Kimi delta attention layers (a gated delta rule: the decay a key
    # channel's, a matrix state a head that a step reads before it writes)
    # with a gated NoPE GQA layer every few, sigmoid-routed experts of which
    # this program may hold one rank's share; granite's state row beside KV
    # pages with three convolutions' tails and lightning's state layout
    # (bigdl_tpu/kvhybrid.py)
    "solar_open2": "bigdl_tpu.models.solar_open2",
}


def get_family(model_type: str):
    if model_type in _LAZY_FAMILIES:
        import importlib

        return importlib.import_module(_LAZY_FAMILIES[model_type])
    if model_type not in _FAMILIES:
        raise NotImplementedError(
            f"model_type {model_type!r} not yet supported; have {sorted([*_FAMILIES, *_LAZY_FAMILIES])}"
        )
    return _FAMILIES[model_type]


__all__ = ["ModelConfig", "PRESETS", "get_family", "llama"]
