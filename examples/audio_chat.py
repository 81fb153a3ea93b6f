"""Audio-conditioned generation with the audio model families — the
reference's Multimodal examples (example/GPU/HuggingFace/Multimodal/
{Qwen2-Audio,MiniCPM-o-2_6}), TPU-native.

    python examples/audio_chat.py [qwen2_audio|minicpmo]

Runs on CPU in seconds with a tiny random-weight model: log-mel frames
stand in for a real feature extractor (pass real mel features from
librosa/transformers' WhisperFeatureExtractor at full scale). Shows the
shared flow for both families: audio tower -> projector -> features
scattered over the prompt's audio placeholder tokens -> prefill ->
greedy decode.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu import kvcache
from bigdl_tpu.models import llama, minicpmo, qwen2_audio
from bigdl_tpu.models import whisper as whisper_mod
from bigdl_tpu.models.config import ModelConfig
from bigdl_tpu.models.whisper import WhisperConfig

AUDIO_TOKEN = 102


def tiny_setup(family: str):
    cfg = ModelConfig.from_hf_config({
        "model_type": family, "hidden_size": 48, "intermediate_size": 96,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "vocab_size": 128,
        "image_token_id": 101,
        "audio_token_id" if family == "minicpmo" else "audio_token_index":
            AUDIO_TOKEN,
    })
    wcfg = WhisperConfig(
        vocab_size=64, num_mel_bins=8, hidden_size=32, encoder_layers=2,
        decoder_layers=1, num_heads=4, ffn_dim=64, max_source_positions=16,
        max_target_positions=8,
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    wp = whisper_mod.init_params(wcfg, jax.random.PRNGKey(1))
    aparams = {k: wp[k] for k in (
        "conv1_w", "conv1_b", "conv2_w", "conv2_b", "enc_pos", "enc",
        "enc_ln_w", "enc_ln_b",
    )}
    return cfg, wcfg, params, aparams


def main():
    family = sys.argv[1] if len(sys.argv) > 1 else "qwen2_audio"
    cfg, wcfg, params, aparams = tiny_setup(family)
    k = jax.random.PRNGKey
    # 2 s of audio -> [1, n_mels, 2 * max_source_positions] log-mel frames
    mel = jnp.asarray(
        np.random.default_rng(0).standard_normal((1, 8, 32)), jnp.float32
    )

    if family == "qwen2_audio":
        pparams = {"w": jax.random.normal(k(2), (48, 32)) * 0.1,
                   "b": jnp.zeros(48)}
        audio = qwen2_audio.audio_embed(wcfg, aparams, pparams, mel)
        prefill = lambda ids, cache: qwen2_audio.multimodal_prefill(
            cfg, params, ids, cache, audio=audio, compute_dtype=jnp.float32,
        )
    else:
        pparams = {"w1": jax.random.normal(k(2), (48, 32)) * 0.1,
                   "b1": jnp.zeros(48),
                   "w2": jax.random.normal(k(3), (48, 48)) * 0.1,
                   "b2": jnp.zeros(48)}
        audio = minicpmo.audio_embed(wcfg, aparams, pparams, mel)
        prefill = lambda ids, cache: minicpmo.multimodal_prefill(
            cfg, params, ids, cache, audio=audio, compute_dtype=jnp.float32,
        )

    # prompt: text tokens around a run of audio placeholders (one per
    # pooled audio frame — a real tokenizer emits these for <audio> tags)
    n_frames = audio.shape[1]
    ids = np.full((1, n_frames + 6), 5, np.int64)
    ids[0, 2:2 + n_frames] = cfg.audio_token_id

    cache = kvcache.init_cache(
        cfg.num_hidden_layers, 1, ids.shape[1] + 16,
        cfg.num_key_value_heads, cfg.head_dim_, dtype=jnp.float32,
    )
    logits, cache = prefill(ids, cache)
    tok = int(jnp.argmax(logits[0, -1]))
    out = [tok]
    for _ in range(15):
        logits, cache = llama.forward(
            cfg, params, jnp.asarray([[tok]]), cache, mode="decode",
            compute_dtype=jnp.float32,
        )
        tok = int(jnp.argmax(logits[0, -1]))
        out.append(tok)
    print(f"{family}: {n_frames} audio frames ->", out)


if __name__ == "__main__":
    main()
