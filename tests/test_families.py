"""Model-zoo family equivalence tests.

Mirrors the reference's GPU layer-equivalence pattern
(test_transformers_api_attention.py:44-110, final-logits variant in
test_transformers_api_final_logits.py in /root/reference): run identical
tiny random weights through HF transformers (torch CPU, fp32, eager
attention) and through our JAX forward, and require logits to agree
within tolerance. Each case exercises the architecture flags that family
introduces (softcaps, post-norms, partial rotary, fused checkpoints,
layernorm+bias, non-gated MLP, MoE routing).
"""

import jax.numpy as jnp
import numpy as np
import pytest

torch = pytest.importorskip("torch")

from bigdl_tpu import kvcache
from bigdl_tpu.convert import params_from_state_dict
from bigdl_tpu.models import get_family
from bigdl_tpu.models.config import ModelConfig

TOKENS = np.asarray([[3, 1, 4, 1, 5, 9, 2, 6]], np.int32)


def hf_tiny(cls_name, cfg_name, attn_impl="eager", **kw):
    import transformers

    cfg_cls = getattr(transformers, cfg_name)
    model_cls = getattr(transformers, cls_name)
    cfg = cfg_cls(**kw)
    cfg._attn_implementation = attn_impl
    torch.manual_seed(0)
    model = model_cls(cfg).eval().to(torch.float32)
    return cfg, model


def run_ours(config, sd, tokens, tol=2e-3):
    get = lambda name: sd[name].detach().to(torch.float32).numpy()
    params = params_from_state_dict(config, get, qtype="bf16", dtype=jnp.float32)
    cache = kvcache.init_cache(
        config.num_hidden_layers, tokens.shape[0], tokens.shape[1] + 8,
        config.num_key_value_heads, config.head_dim_, dtype=jnp.float32,
    )
    fam = get_family(config.model_type)
    logits, _ = fam.forward(
        config, params, jnp.asarray(tokens), cache, mode="prefill",
        compute_dtype=jnp.float32,
    )
    return np.asarray(logits)


def check(cfg, model, tokens=TOKENS, tol=2e-3):
    with torch.no_grad():
        hf_logits = model(torch.from_numpy(tokens).long()).logits.numpy()
    config = ModelConfig.from_hf_config(cfg.to_dict())
    ours = run_ours(config, model.state_dict(), tokens)
    np.testing.assert_allclose(ours, hf_logits, rtol=tol, atol=tol)
    return config


COMMON = dict(
    vocab_size=128, hidden_size=64, intermediate_size=128,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    max_position_embeddings=64,
)


# 45 s against a family the `model-configs` guide bars from the benchmark: no
# cell's program rests on it (ROADMAP D13 step (1), PR 57)
@pytest.mark.slow
def test_gemma2_equivalence():
    cfg, model = hf_tiny(
        "Gemma2ForCausalLM", "Gemma2Config",
        **{**COMMON, "head_dim": 16, "query_pre_attn_scalar": 12,
           "sliding_window": 4, "attn_logit_softcapping": 50.0,
           "final_logit_softcapping": 30.0, "hidden_activation": "gelu_pytorch_tanh"},
    )
    config = check(cfg, model)
    assert config.post_attn_norm and config.rms_norm_offset
    assert config.scale_embeddings and config.sliding_window_pattern == 2
    assert config.attn_scale == pytest.approx(12 ** -0.5)


def test_gemma_equivalence():
    cfg, model = hf_tiny(
        "GemmaForCausalLM", "GemmaConfig", **{**COMMON, "head_dim": 16},
    )
    config = check(cfg, model)
    assert config.rms_norm_offset and config.scale_embeddings
    assert not config.post_attn_norm


def test_phi3_equivalence():
    cfg, model = hf_tiny(
        "Phi3ForCausalLM", "Phi3Config", **{**COMMON, "pad_token_id": 0}
    )
    check(cfg, model)  # exercises fused qkv_proj / gate_up_proj split


def test_starcoder2_equivalence():
    cfg, model = hf_tiny(
        "Starcoder2ForCausalLM", "Starcoder2Config",
        **{**COMMON, "use_bias": True, "hidden_act": "gelu_pytorch_tanh"},
    )
    config = check(cfg, model)
    assert config.norm_type == "layernorm" and not config.gated_mlp
    assert config.attention_out_bias and config.mlp_bias


def test_stablelm_equivalence():
    cfg, model = hf_tiny(
        "StableLmForCausalLM", "StableLmConfig",
        **{**COMMON, "use_qkv_bias": True, "partial_rotary_factor": 0.25},
    )
    config = check(cfg, model)
    assert config.norm_type == "layernorm"
    assert config.rotary_dim == 4  # 16 * 0.25


def test_glm_equivalence():
    cfg, model = hf_tiny(
        "GlmForCausalLM", "GlmConfig",
        **{**COMMON, "head_dim": 16, "partial_rotary_factor": 0.5,
           "attention_bias": True, "pad_token_id": 0},
    )
    config = check(cfg, model, tol=5e-3)
    assert config.rope_interleaved and config.rotary_dim == 8


def test_glm_rope_matches_hf_exactly():
    """Unit-scale q/k against HF modeling_glm's interleaved rope — catches
    convention mistakes the tiny-weight logits test cannot (scores there
    are ~1e-3, below logits tolerance)."""
    from transformers.models.glm.modeling_glm import (
        apply_rotary_pos_emb as hf_apply,
    )

    from bigdl_tpu.ops.rope import apply_rotary_emb, default_inv_freq, rope_cos_sin

    B, T, H, D, R = 1, 6, 2, 16, 8
    rng = np.random.default_rng(0)
    q = rng.standard_normal((B, T, H, D)).astype(np.float32)
    k = rng.standard_normal((B, T, H, D)).astype(np.float32)
    pos = np.arange(T, dtype=np.int32)[None]

    inv = default_inv_freq(R, 10000.0)
    cos, sin = rope_cos_sin(jnp.asarray(pos), inv, interleaved=True)
    ours_q, ours_k = apply_rotary_emb(
        jnp.asarray(q), jnp.asarray(k), cos, sin, interleaved=True
    )

    # HF layout: q [B, H, T, D]; cos/sin [B, T, R] from cat(freqs, freqs)
    angles = pos[..., None] * np.asarray(inv)[None, None, :]
    emb = np.concatenate([angles, angles], axis=-1)
    hf_cos = torch.from_numpy(np.cos(emb).astype(np.float32))
    hf_sin = torch.from_numpy(np.sin(emb).astype(np.float32))
    hq, hk = hf_apply(
        torch.from_numpy(q).permute(0, 2, 1, 3),
        torch.from_numpy(k).permute(0, 2, 1, 3),
        hf_cos, hf_sin,
    )
    np.testing.assert_allclose(
        np.asarray(ours_q), hq.permute(0, 2, 1, 3).numpy(), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(ours_k), hk.permute(0, 2, 1, 3).numpy(), rtol=1e-5, atol=1e-5
    )


def test_mixtral_equivalence():
    cfg, model = hf_tiny(
        "MixtralForCausalLM", "MixtralConfig",
        **{**COMMON, "num_local_experts": 4, "num_experts_per_tok": 2},
    )
    config = check(cfg, model, tol=5e-3)
    assert config.is_moe and config.num_experts == 4 and config.norm_topk_prob


def test_qwen2_moe_equivalence():
    cfg, model = hf_tiny(
        "Qwen2MoeForCausalLM", "Qwen2MoeConfig",
        **{**COMMON, "num_experts": 4, "num_experts_per_tok": 2,
           "moe_intermediate_size": 32, "shared_expert_intermediate_size": 64,
           "decoder_sparse_step": 1, "mlp_only_layers": []},
    )
    config = check(cfg, model, tol=5e-3)
    assert config.shared_expert_intermediate_size == 64


def test_mpt_equivalence():
    cfg, model = hf_tiny(
        "MptForCausalLM", "MptConfig",
        d_model=64, n_heads=4, n_layers=2, expansion_ratio=2,
        max_seq_len=64, vocab_size=128,
        attn_config={"alibi": True, "attn_impl": "eager"}, no_bias=True,
    )
    config = check(cfg, model, tol=3e-3)
    assert config.alibi and not config.gated_mlp
    assert config.norm_type == "layernorm" and config.tie_word_embeddings


def test_gpt2_equivalence():
    cfg, model = hf_tiny(
        "GPT2LMHeadModel", "GPT2Config",
        vocab_size=128, n_embd=64, n_layer=2, n_head=4, n_positions=64,
        n_inner=128, resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0,
    )
    config = check(cfg, model)
    assert config.learned_positions and config.norm_type == "layernorm"
    assert not config.gated_mlp and config.tie_word_embeddings


def test_bloom_equivalence():
    cfg, model = hf_tiny(
        "BloomForCausalLM", "BloomConfig",
        vocab_size=128, hidden_size=64, n_layer=2, n_head=4,
        hidden_dropout=0.0, attention_dropout=0.0,
    )
    config = check(cfg, model, tol=5e-3)
    assert config.alibi and config.embed_layernorm
    assert config.norm_type == "layernorm" and not config.gated_mlp


def test_gptneox_equivalence():
    cfg, model = hf_tiny(
        "GPTNeoXForCausalLM", "GPTNeoXConfig",
        vocab_size=128, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=128, rotary_pct=0.25,
        use_parallel_residual=True, max_position_embeddings=64,
        hidden_dropout=0.0, attention_dropout=0.0,
    )
    config = check(cfg, model)
    assert config.parallel_residual and config.rotary_dim == 4
    assert not config.tie_word_embeddings


def test_gptneox_sequential_residual():
    cfg, model = hf_tiny(
        "GPTNeoXForCausalLM", "GPTNeoXConfig",
        vocab_size=128, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=128, rotary_pct=1.0,
        use_parallel_residual=False, max_position_embeddings=64,
        hidden_dropout=0.0, attention_dropout=0.0,
    )
    config = check(cfg, model)
    assert not config.parallel_residual


def test_phi3_longrope_top_level_injection():
    """HF phi3 keeps original/max position embeddings at config top level;
    from_hf_config must fold them into rope_scaling so the longrope
    attention factor is applied (regression: factor was silently 1.0)."""
    from bigdl_tpu.ops.rope import make_inv_freq_scaled

    hf = {
        "model_type": "phi3", "vocab_size": 64, "hidden_size": 64,
        "num_hidden_layers": 1, "num_attention_heads": 4,
        "num_key_value_heads": 4, "max_position_embeddings": 131072,
        "original_max_position_embeddings": 4096,
        "rope_scaling": {
            "type": "longrope",
            "short_factor": [1.0] * 8, "long_factor": [4.0] * 8,
        },
    }
    config = ModelConfig.from_hf_config(hf)
    rs = config.rope_scaling_dict
    assert rs["original_max_position_embeddings"] == 4096
    assert rs["max_position_embeddings"] == 131072
    _, att = make_inv_freq_scaled(16, 10000.0, rs, seq_len=8192)
    import math

    assert att == pytest.approx(math.sqrt(1 + math.log(32) / math.log(4096)))


def test_baichuan_w_pack_split_and_alibi():
    """No HF-builtin baichuan (trust_remote_code); test the W_pack ingest
    split + NormHead + the 13B-style ALiBi path shape/mask behavior."""
    config = ModelConfig(
        model_type="baichuan", vocab_size=128, hidden_size=64,
        intermediate_size=128, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=4, alibi=True, max_position_embeddings=64,
    )
    rng = np.random.default_rng(0)
    H, I, V = 64, 128, 128
    sd = {}
    for i in range(2):
        p = f"model.layers.{i}."
        sd[p + "input_layernorm.weight"] = np.ones(H, np.float32)
        sd[p + "post_attention_layernorm.weight"] = np.ones(H, np.float32)
        sd[p + "self_attn.W_pack.weight"] = rng.standard_normal((3 * H, H)).astype(np.float32) * 0.05
        sd[p + "self_attn.o_proj.weight"] = rng.standard_normal((H, H)).astype(np.float32) * 0.05
        sd[p + "mlp.gate_proj.weight"] = rng.standard_normal((I, H)).astype(np.float32) * 0.05
        sd[p + "mlp.up_proj.weight"] = rng.standard_normal((I, H)).astype(np.float32) * 0.05
        sd[p + "mlp.down_proj.weight"] = rng.standard_normal((H, I)).astype(np.float32) * 0.05
    sd["model.embed_tokens.weight"] = rng.standard_normal((V, H)).astype(np.float32) * 0.05
    sd["model.norm.weight"] = np.ones(H, np.float32)
    sd["lm_head.weight"] = rng.standard_normal((V, H)).astype(np.float32) * 0.05

    params = params_from_state_dict(config, sd.__getitem__, qtype="bf16", dtype=jnp.float32)
    # NormHead rows are unit-norm after ingest
    norms = np.linalg.norm(np.asarray(params["lm_head"]), axis=1)
    np.testing.assert_allclose(norms, 1.0, rtol=1e-5)

    cache = kvcache.init_cache(2, 1, 16, 4, 16, dtype=jnp.float32)
    logits, cache2 = get_family("baichuan").forward(
        config, params, jnp.asarray(TOKENS), cache, mode="prefill",
        compute_dtype=jnp.float32,
    )
    assert logits.shape == (1, 8, V)
    assert np.all(np.isfinite(np.asarray(logits)))
    # decode step continues from the cache (alibi positions from slots)
    logits_d, _ = get_family("baichuan").forward(
        config, params, TOKENS[:, :1], cache2, mode="decode",
        compute_dtype=jnp.float32,
    )
    assert np.all(np.isfinite(np.asarray(logits_d)))


def test_internlm2_wqkv_split():
    """internlm2 grouped wqkv layout → separate q/k/v (shape-level check
    against a hand-built grouped tensor)."""
    config = ModelConfig(
        model_type="internlm2", vocab_size=64, hidden_size=32,
        intermediate_size=64, num_hidden_layers=1, num_attention_heads=4,
        num_key_value_heads=2,
    )
    from bigdl_tpu.convert.hf import layer_tensors

    D, Hkv, g, H = 8, 2, 2, 32
    # grouped layout [Hkv, g+2, D, H]: mark each slice with a distinct value
    grouped = np.zeros((Hkv, g + 2, D, H), np.float32)
    for kv in range(Hkv):
        for s in range(g + 2):
            grouped[kv, s] = kv * 10 + s
    sd = {
        "model.layers.0.attention.wqkv.weight": grouped.reshape(-1, H),
        "model.layers.0.attention.wo.weight": np.zeros((H, H), np.float32),
        "model.layers.0.attention_norm.weight": np.ones(H, np.float32),
        "model.layers.0.ffn_norm.weight": np.ones(H, np.float32),
        "model.layers.0.feed_forward.w1.weight": np.zeros((64, H), np.float32),
        "model.layers.0.feed_forward.w3.weight": np.zeros((64, H), np.float32),
        "model.layers.0.feed_forward.w2.weight": np.zeros((H, 64), np.float32),
    }
    out = layer_tensors(config, 0, sd.__getitem__)
    # q rows: kv0 slices 0..g-1 then kv1 slices 0..g-1
    q = out["wq"].reshape(Hkv, g, D, H)
    assert np.all(q[0, 0] == 0) and np.all(q[0, 1] == 1)
    assert np.all(q[1, 0] == 10) and np.all(q[1, 1] == 11)
    k = out["wk"].reshape(Hkv, D, H)
    assert np.all(k[0] == g) and np.all(k[1] == 10 + g)
    v = out["wv"].reshape(Hkv, D, H)
    assert np.all(v[0] == g + 1) and np.all(v[1] == 10 + g + 1)


def test_falcon7b_style_equivalence():
    """falcon-7b layout: multi-query + parallel attn/mlp sharing one
    input layernorm, bias-free linears, non-gated gelu MLP."""
    cfg, model = hf_tiny(
        "FalconForCausalLM", "FalconConfig",
        vocab_size=128, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, multi_query=True, parallel_attn=True,
        new_decoder_architecture=False, bias=False, alibi=False,
    )
    config = check(cfg, model)
    assert config.num_key_value_heads == 1
    assert config.parallel_residual and not config.gated_mlp


def test_falcon40b_style_equivalence():
    """falcon-40b layout: new_decoder_architecture — GQA with separate
    ln_attn/ln_mlp, still parallel residual."""
    cfg, model = hf_tiny(
        "FalconForCausalLM", "FalconConfig",
        vocab_size=128, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_kv_heads=2, multi_query=False,
        new_decoder_architecture=True, bias=False, alibi=False,
    )
    config = check(cfg, model)
    assert config.num_key_value_heads == 2


def test_falcon_rw_style_equivalence():
    """falcon-rw layout: per-head full attention, biased linears, alibi
    positions, sequential residual with post_attention_layernorm —
    exercises the fused-bias ungrouping and the non-parallel fallback."""
    # sdpa attention: this transformers version's EAGER falcon path
    # double-applies alibi (the bias is folded into the causal mask AND
    # added again in the module) — the sdpa path applies it once, which
    # matches the original tiiuae falcon-rw semantics we implement
    cfg, model = hf_tiny(
        "FalconForCausalLM", "FalconConfig", attn_impl="sdpa",
        vocab_size=128, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, multi_query=False, parallel_attn=False,
        new_decoder_architecture=False, bias=True, alibi=True,
    )
    config = check(cfg, model)
    assert config.alibi and not config.parallel_residual
    assert config.attention_bias and config.mlp_bias


def test_qwen3_equivalence():
    cfg, model = hf_tiny(
        "Qwen3ForCausalLM", "Qwen3Config",
        **{**COMMON, "head_dim": 16, "rope_theta": 1000000.0},
    )
    config = check(cfg, model)
    assert config.qk_norm and not config.attention_bias


def test_qwen3_moe_equivalence():
    cfg, model = hf_tiny(
        "Qwen3MoeForCausalLM", "Qwen3MoeConfig",
        **{**COMMON, "head_dim": 16, "num_experts": 4,
           "num_experts_per_tok": 2, "moe_intermediate_size": 32,
           "norm_topk_prob": True},
    )
    config = check(cfg, model)
    assert config.num_experts == 4 and config.norm_topk_prob


def test_phi_equivalence():
    cfg, model = hf_tiny(
        "PhiForCausalLM", "PhiConfig",
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        partial_rotary_factor=0.5, max_position_embeddings=64,
    )
    config = check(cfg, model)
    assert config.parallel_residual and config.lm_head_bias
    assert config.partial_rotary_factor == 0.5


def test_cohere_equivalence():
    cfg, model = hf_tiny(
        "CohereForCausalLM", "CohereConfig",
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        logit_scale=0.25, use_qk_norm=False, max_position_embeddings=64,
    )
    config = check(cfg, model)
    assert config.parallel_residual and config.rope_interleaved
    assert config.logit_scale == 0.25 and config.tie_word_embeddings


def test_phi_shards_with_lm_head_bias():
    """phi's lm_head_b must survive to_mesh (sharding specs cover it)."""
    import jax as _jax

    from bigdl_tpu.api import TpuModel, optimize_model
    from bigdl_tpu.models import llama as _llama

    config = ModelConfig(
        model_type="phi", vocab_size=128, hidden_size=64,
        intermediate_size=128, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=4, norm_type="layernorm", norm_bias=True,
        parallel_residual=True, gated_mlp=False, mlp_bias=True,
        attention_bias=True, attention_out_bias=True, lm_head_bias=True,
        partial_rotary_factor=0.5, hidden_act="gelu_new",
    )
    params = _llama.init_params(config, _jax.random.PRNGKey(0))
    assert "lm_head_b" in params
    m = TpuModel(config, optimize_model(params, config), "sym_int4")
    single = m.generate([[1, 2, 3, 4]], max_new_tokens=6)
    sharded = m.to_mesh(tp=2)
    np.testing.assert_array_equal(
        single, sharded.generate([[1, 2, 3, 4]], max_new_tokens=6)
    )


def test_gemma3_equivalence():
    """gemma3: qk-norm + DUAL rope (sliding layers at the local base,
    full layers at the scaled global base) + explicit layer_types."""
    cfg, model = hf_tiny(
        "Gemma3ForCausalLM", "Gemma3TextConfig",
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, query_pre_attn_scalar=16, sliding_window=4,
        rope_theta=1000000.0, rope_local_base_freq=10000.0,
        layer_types=["sliding_attention", "full_attention",
                     "sliding_attention", "sliding_attention"],
        rope_scaling={"rope_type": "linear", "factor": 2.0},
        max_position_embeddings=64,
    )
    config = check(cfg, model)
    assert config.qk_norm and config.rope_local_theta == 10000.0
    assert config.sliding_layers == (True, False, True, True)
    assert config.layer_is_sliding(0) and not config.layer_is_sliding(1)


def test_gemma3_config_json_roundtrip_stays_hashable():
    import dataclasses as _dc
    import json as _json

    config = ModelConfig(
        model_type="gemma3_text", sliding_window=4,
        sliding_layers=(True, False), rope_local_theta=10000.0,
    )
    blob = _json.loads(_json.dumps(_dc.asdict(config)))
    rt = ModelConfig(**blob)
    hash(rt)  # must stay a valid static jit argument
    assert rt.sliding_layers == (True, False)


def test_alias_model_types_registered():
    from bigdl_tpu.models import get_family, internvl, janus, llama

    assert get_family("aquila") is llama
    assert get_family("internlm") is llama
    assert get_family("internvl_chat") is internvl
    assert get_family("multi_modality") is janus
    cfg = ModelConfig.from_hf_config(
        {"model_type": "internlm", "hidden_size": 64, "num_hidden_layers": 2,
         "num_attention_heads": 4, "bias": True}
    )
    assert cfg.attention_bias and cfg.attention_out_bias


def test_gptbigcode_equivalence():
    """starcoder v1: MQA (1 kv head), learned positions, layernorm,
    non-gated gelu MLP, fused [H + 2*head_dim] c_attn."""
    cfg, model = hf_tiny(
        "GPTBigCodeForCausalLM", "GPTBigCodeConfig",
        vocab_size=128, n_embd=64, n_layer=2, n_head=4, n_inner=128,
        n_positions=64, multi_query=True,
        activation_function="gelu_pytorch_tanh",
    )
    config = check(cfg, model)
    assert config.model_type == "gpt_bigcode"
    assert config.num_key_value_heads == 1 and config.learned_positions
    assert not config.gated_mlp


def test_deci_kv_replication_exact_and_ingest():
    """DeciLM's variable GQA: (a) math — attention over r-replicated kv
    heads equals GQA with the original head count; (b) plumbing — the
    deci ingest path replicates to the uniform max and matches an HF
    llama oracle holding the replicated weights."""
    rng = np.random.default_rng(0)
    # (a) numpy: GQA(2 kv heads, 4 q heads) == MHA over repeat(kv, 2)
    Hq, Hkv, D, T = 4, 2, 8, 5
    q = rng.standard_normal((T, Hq, D)).astype(np.float64)
    k2 = rng.standard_normal((T, Hkv, D)).astype(np.float64)
    v2 = rng.standard_normal((T, Hkv, D)).astype(np.float64)

    def attn(qh, kh, vh):  # causal single-head
        s = qh @ kh.T / np.sqrt(D)
        s = np.where(np.tril(np.ones((T, T))) == 1, s, -1e30)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        return p @ vh

    gqa = np.stack([attn(q[:, h], k2[:, h // 2], v2[:, h // 2])
                    for h in range(Hq)], 1)
    k4, v4 = np.repeat(k2, 2, axis=1), np.repeat(v2, 2, axis=1)
    rep = np.stack([attn(q[:, h], k4[:, h], v4[:, h]) for h in range(Hq)], 1)
    np.testing.assert_allclose(gqa, rep, rtol=1e-12, atol=1e-12)

    # (b) ingest: deci sd with per-layer kv heads (2 then 4) vs an HF
    # llama oracle whose layer-0 kv weights are head-replicated
    cfg, model = hf_tiny(
        "LlamaForCausalLM", "LlamaConfig",
        **{**COMMON, "num_key_value_heads": 4},
    )
    sd = {k: v.clone() for k, v in model.state_dict().items()}
    D = 64 // 4
    for nm in ("k_proj", "v_proj"):
        w4 = sd[f"model.layers.0.self_attn.{nm}.weight"]
        # deci layer 0 stores only heads 0 and 2; the oracle llama gets
        # them replicated (0,0,2,2)
        w2 = w4.reshape(4, D, -1)[::2].reshape(2 * D, -1)
        sd[f"model.layers.0.self_attn.{nm}.weight"] = w2
        model.state_dict()[f"model.layers.0.self_attn.{nm}.weight"].copy_(
            torch.from_numpy(
                np.repeat(w2.numpy().reshape(2, D, -1), 2, axis=0)
                .reshape(4 * D, -1)
            )
        )
    with torch.no_grad():
        hf_logits = model(torch.from_numpy(TOKENS).long()).logits.numpy()
    hf_cfg = cfg.to_dict()
    hf_cfg["model_type"] = "deci"
    hf_cfg["num_key_value_heads_per_layer"] = [2, 4]
    config = ModelConfig.from_hf_config(hf_cfg)
    assert config.model_type == "deci" and config.num_key_value_heads == 4
    ours = run_ours(config, sd, TOKENS)
    np.testing.assert_allclose(ours, hf_logits, rtol=2e-3, atol=2e-3)


def test_qwen_v1_mlp_and_logn():
    """Qwen v1: (a) the w1/w2 MLP mapping — ours must compute
    c_proj(w1(x) * silu(w2(x))); (b) logn scaling matches HF's
    logn_list definition; (c) fused-c_attn ingest generates."""
    rng = np.random.default_rng(1)
    H, I = 16, 24
    x = rng.standard_normal((3, H)).astype(np.float32)
    w1 = rng.standard_normal((I, H)).astype(np.float32) * 0.1
    w2 = rng.standard_normal((I, H)).astype(np.float32) * 0.1
    cp = rng.standard_normal((H, I)).astype(np.float32) * 0.1

    def silu(a):
        return a / (1 + np.exp(-a))

    want = (w1 @ x.T).T * silu((w2 @ x.T).T) @ cp.T

    from bigdl_tpu.models.llama import _act
    g = jnp.asarray((w2 @ x.T).T)  # our w_gate = qwen w2
    u = jnp.asarray((w1 @ x.T).T)  # our w_up = qwen w1
    ours = np.asarray(_act("silu", g) * u) @ cp.T
    np.testing.assert_allclose(ours, want, rtol=1e-5, atol=1e-5)

    # (b) HF: logn_list[i-1] = log(i, seq_length) if i > seq_length else 1
    seq_len = 16
    cfg = ModelConfig(
        model_type="qwen", vocab_size=64, hidden_size=32,
        intermediate_size=32, num_hidden_layers=1, num_attention_heads=2,
        num_key_value_heads=2, logn_attn=True, logn_train_len=seq_len,
        max_position_embeddings=64, attention_bias=True,
        attention_out_bias=False,
    )
    pos = np.arange(40)
    want_scale = np.asarray([
        np.log(i) / np.log(seq_len) if i > seq_len else 1.0
        for i in pos + 1
    ])
    got = np.maximum(np.log(pos + 1.0) / np.log(float(seq_len)), 1.0)
    np.testing.assert_allclose(got, want_scale, rtol=1e-6)

    # (c) ingest a fused-c_attn state dict and generate
    sd = {}
    L, V = 1, 64
    Hs = 32
    sd["transformer.wte.weight"] = rng.standard_normal((V, Hs)).astype(np.float32)
    sd["transformer.ln_f.weight"] = np.ones(Hs, np.float32)
    sd["lm_head.weight"] = rng.standard_normal((V, Hs)).astype(np.float32)
    p = "transformer.h.0."
    sd[p + "ln_1.weight"] = np.ones(Hs, np.float32)
    sd[p + "ln_2.weight"] = np.ones(Hs, np.float32)
    sd[p + "attn.c_attn.weight"] = rng.standard_normal((3 * Hs, Hs)).astype(np.float32) * 0.05
    sd[p + "attn.c_attn.bias"] = rng.standard_normal(3 * Hs).astype(np.float32) * 0.05
    sd[p + "attn.c_proj.weight"] = rng.standard_normal((Hs, Hs)).astype(np.float32) * 0.05
    sd[p + "mlp.w1.weight"] = rng.standard_normal((48, Hs)).astype(np.float32) * 0.05
    sd[p + "mlp.w2.weight"] = rng.standard_normal((48, Hs)).astype(np.float32) * 0.05
    sd[p + "mlp.c_proj.weight"] = rng.standard_normal((Hs, 48)).astype(np.float32) * 0.05
    qcfg = ModelConfig.from_hf_config({
        "model_type": "qwen", "vocab_size": V, "hidden_size": Hs,
        "intermediate_size": 96, "num_hidden_layers": 1,
        "num_attention_heads": 2, "seq_length": 16, "use_logn_attn": True,
        "layer_norm_epsilon": 1e-6,
    })
    assert qcfg.intermediate_size == 48  # halved-ff convention
    assert qcfg.logn_attn and qcfg.logn_train_len == 16
    params = params_from_state_dict(qcfg, sd.__getitem__, qtype="bf16")
    from bigdl_tpu.api import TpuModel

    out = TpuModel(qcfg, params, "bf16").generate(
        [[3, 1, 4, 1, 5]], max_new_tokens=24  # crosses logn_train_len
    )
    assert out.shape == (1, 24)


def test_phixtral_moe_matches_torch_oracle():
    """Non-gated MoE block vs a torch re-implementation of the phixtral
    routing (softmax -> topk -> renorm -> biased fc1/gelu/fc2 experts,
    reference models/phixtral.py:44-70)."""
    import torch.nn.functional as F

    rng = np.random.default_rng(2)
    B, T, H, I, E, K = 2, 3, 16, 24, 4, 2
    x = rng.standard_normal((B, T, H)).astype(np.float32)
    gate = rng.standard_normal((E, H)).astype(np.float32) * 0.5
    fc1 = rng.standard_normal((E, I, H)).astype(np.float32) * 0.3
    b1 = rng.standard_normal((E, I)).astype(np.float32) * 0.1
    fc2 = rng.standard_normal((E, H, I)).astype(np.float32) * 0.3
    b2 = rng.standard_normal((E, H)).astype(np.float32) * 0.1

    xt = torch.from_numpy(x).reshape(-1, H)
    logits = xt @ torch.from_numpy(gate).T
    weights = F.softmax(logits, dim=1, dtype=torch.float)
    topw, tope = torch.topk(weights, K, dim=-1)
    topw = topw / topw.sum(-1, keepdim=True)
    want = torch.zeros_like(xt)
    for n in range(xt.shape[0]):
        for j in range(K):
            e = int(tope[n, j])
            h = F.gelu(xt[n] @ torch.from_numpy(fc1[e]).T
                       + torch.from_numpy(b1[e]), approximate="tanh")
            want[n] += topw[n, j] * (
                h @ torch.from_numpy(fc2[e]).T + torch.from_numpy(b2[e])
            )
    want = want.reshape(B, T, H).numpy()

    from bigdl_tpu.models.llama import _moe_mlp

    cfg = ModelConfig(
        model_type="phixtral", vocab_size=32, hidden_size=H,
        intermediate_size=I, num_hidden_layers=1, num_attention_heads=2,
        num_key_value_heads=2, num_experts=E, num_experts_per_tok=K,
        norm_topk_prob=True, gated_mlp=False, mlp_bias=True,
        hidden_act="gelu_pytorch_tanh",
    )
    p = {"router": jnp.asarray(gate), "w_up_e": jnp.asarray(fc1),
         "b_up_e": jnp.asarray(b1), "w_down_e": jnp.asarray(fc2),
         "b_down_e": jnp.asarray(b2)}
    for dispatch in ("dense", "ragged"):
        cfg2 = ModelConfig(**{**cfg.__dict__, "moe_dispatch": dispatch,
                              "moe_capacity_factor": 4.0})
        got = np.asarray(_moe_mlp(cfg2, jnp.asarray(x), p, jnp.float32))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_phixtral_ingest_and_generate():
    """Legacy mixformer naming (mixer.Wqkv, moe.mlp.{e}, lm_head.ln)
    ingests and generates."""
    rng = np.random.default_rng(3)
    H, I, V, E = 32, 48, 64, 4
    sd = {}
    sd["transformer.embd.wte.weight"] = rng.standard_normal((V, H)).astype(np.float32)
    sd["lm_head.ln.weight"] = np.ones(H, np.float32)
    sd["lm_head.ln.bias"] = np.zeros(H, np.float32)
    sd["lm_head.linear.weight"] = rng.standard_normal((V, H)).astype(np.float32) * 0.1
    sd["lm_head.linear.bias"] = np.zeros(V, np.float32)
    p = "transformer.h.0."
    sd[p + "ln.weight"] = np.ones(H, np.float32)
    sd[p + "ln.bias"] = np.zeros(H, np.float32)
    sd[p + "mixer.Wqkv.weight"] = rng.standard_normal((3 * H, H)).astype(np.float32) * 0.05
    sd[p + "mixer.Wqkv.bias"] = np.zeros(3 * H, np.float32)
    sd[p + "mixer.out_proj.weight"] = rng.standard_normal((H, H)).astype(np.float32) * 0.05
    sd[p + "mixer.out_proj.bias"] = np.zeros(H, np.float32)
    sd[p + "moe.gate.weight"] = rng.standard_normal((E, H)).astype(np.float32) * 0.1
    for e in range(E):
        ep = f"{p}moe.mlp.{e}."
        sd[ep + "fc1.weight"] = rng.standard_normal((I, H)).astype(np.float32) * 0.05
        sd[ep + "fc1.bias"] = np.zeros(I, np.float32)
        sd[ep + "fc2.weight"] = rng.standard_normal((H, I)).astype(np.float32) * 0.05
        sd[ep + "fc2.bias"] = np.zeros(H, np.float32)
    cfg = ModelConfig.from_hf_config({
        "model_type": "phixtral", "vocab_size": V, "n_embd": H,
        "n_layer": 1, "n_head": 2, "n_inner": I, "n_positions": 64,
        "rotary_dim": 8, "num_local_experts": E, "num_experts_per_tok": 2,
        "layer_norm_epsilon": 1e-5, "activation_function": "gelu_new",
    })
    assert cfg.num_experts == E and not cfg.gated_mlp and cfg.norm_topk_prob
    assert cfg.partial_rotary_factor == pytest.approx(8 / 16)
    params = params_from_state_dict(cfg, sd.__getitem__, qtype="bf16")
    from bigdl_tpu.api import TpuModel

    out = TpuModel(cfg, params, "bf16").generate([[3, 1, 4]], max_new_tokens=5)
    assert out.shape == (1, 5)


def test_legacy_model_type_aliases():
    """Checkpoints ship legacy remote-code ids: 01-ai "Yi" (llama-shaped,
    reference convert.py:1738) and mlabonne phixtral's "phi-msft"
    (convert.py:1685-1687, keyed on num_local_experts to exclude plain
    phi-2). from_hf_config rewrites them to the serving families."""
    yi = ModelConfig.from_hf_config({
        "model_type": "Yi", "vocab_size": 64, "hidden_size": 32,
        "intermediate_size": 64, "num_hidden_layers": 1,
        "num_attention_heads": 4, "num_key_value_heads": 2,
    })
    assert yi.model_type == "yi"
    assert get_family("yi") is not None

    px = ModelConfig.from_hf_config({
        "model_type": "phi-msft", "vocab_size": 64, "n_embd": 32,
        "n_layer": 1, "n_head": 2, "n_inner": 48, "n_positions": 64,
        "rotary_dim": 8, "num_local_experts": 4, "num_experts_per_tok": 2,
    })
    assert px.model_type == "phixtral" and px.num_experts == 4

    with pytest.raises(NotImplementedError, match="phi-msft"):
        ModelConfig.from_hf_config({"model_type": "phi-msft",
                                    "n_embd": 32, "n_layer": 1})


def test_phi3_v_text_path_matches_phi3_oracle():
    """phi-3-vision is optimized as phi3 on the text path (reference
    convert.py:947,1829 `in ["phi3", "phi3_v"]`); the relabeled config
    must produce identical text logits through the phi3 translation."""
    cfg, model = hf_tiny(
        "Phi3ForCausalLM", "Phi3Config",
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, pad_token_id=0,
    )
    hf = cfg.to_dict()
    hf["model_type"] = "phi3_v"
    with torch.no_grad():
        hf_logits = model(torch.from_numpy(TOKENS).long()).logits.numpy()
    config = ModelConfig.from_hf_config(hf)
    assert config.model_type == "phi3_v"
    ours = run_ours(config, model.state_dict(), TOKENS)
    np.testing.assert_allclose(ours, hf_logits, rtol=2e-3, atol=2e-3)


def test_xcomposer2_ingests_ignoring_plora():
    """internlm-xcomposer2 = internlm2 names + Plora_A/B per-linear image
    deltas (reference convert.py:984,1523). The text path ignores the
    Plora keys (im_mask=None) and generates."""
    rng = np.random.default_rng(4)
    H, I, V, D, Hkv, g = 32, 48, 64, 8, 2, 2
    sd = {
        "model.tok_embeddings.weight": rng.standard_normal((V, H)).astype(np.float32),
        "model.norm.weight": np.ones(H, np.float32),
        "output.weight": rng.standard_normal((V, H)).astype(np.float32) * 0.1,
    }
    p = "model.layers.0."
    sd[p + "attention.wqkv.weight"] = rng.standard_normal(
        (Hkv * (g + 2) * D, H)).astype(np.float32) * 0.05
    sd[p + "attention.wo.weight"] = rng.standard_normal((H, H)).astype(np.float32) * 0.05
    sd[p + "attention_norm.weight"] = np.ones(H, np.float32)
    sd[p + "ffn_norm.weight"] = np.ones(H, np.float32)
    sd[p + "feed_forward.w1.weight"] = rng.standard_normal((I, H)).astype(np.float32) * 0.05
    sd[p + "feed_forward.w3.weight"] = rng.standard_normal((I, H)).astype(np.float32) * 0.05
    sd[p + "feed_forward.w2.weight"] = rng.standard_normal((H, I)).astype(np.float32) * 0.05
    # Plora keys present in real checkpoints; must be ignored, not crash
    sd[p + "attention.wqkv.Plora_A.weight"] = np.zeros((8, H), np.float32)
    sd[p + "attention.wqkv.Plora_B.weight"] = np.zeros((Hkv * (g + 2) * D, 8), np.float32)

    config = ModelConfig.from_hf_config({
        "model_type": "internlmxcomposer2", "vocab_size": V, "hidden_size": H,
        "intermediate_size": I, "num_hidden_layers": 1,
        "num_attention_heads": 4, "num_key_value_heads": Hkv,
    })
    from bigdl_tpu.api import TpuModel
    from bigdl_tpu.convert import params_from_state_dict

    params = params_from_state_dict(config, sd.__getitem__, qtype="bf16",
                                    dtype=jnp.float32)
    out = TpuModel(config, params, "bf16").generate([[3, 1, 4]], max_new_tokens=4)
    assert out.shape == (1, 4)


def test_megrezo_text_path_ingests():
    """Megrez-3B-Omni: llama llm under the `llm.` prefix (reference
    convert.py:1042-1047 rewrites llm model_type to llama; towers load
    separately)."""
    rng = np.random.default_rng(5)
    H, I, V = 32, 48, 64
    sd = {
        "llm.model.embed_tokens.weight": rng.standard_normal((V, H)).astype(np.float32),
        "llm.model.norm.weight": np.ones(H, np.float32),
        "llm.lm_head.weight": rng.standard_normal((V, H)).astype(np.float32) * 0.1,
    }
    p = "llm.model.layers.0."
    for name, shape in (
        ("self_attn.q_proj.weight", (H, H)), ("self_attn.k_proj.weight", (16, H)),
        ("self_attn.v_proj.weight", (16, H)), ("self_attn.o_proj.weight", (H, H)),
        ("mlp.gate_proj.weight", (I, H)), ("mlp.up_proj.weight", (I, H)),
        ("mlp.down_proj.weight", (H, I)),
    ):
        sd[p + name] = rng.standard_normal(shape).astype(np.float32) * 0.05
    sd[p + "input_layernorm.weight"] = np.ones(H, np.float32)
    sd[p + "post_attention_layernorm.weight"] = np.ones(H, np.float32)

    config = ModelConfig.from_hf_config({
        "model_type": "megrezo", "vocab_size": V, "hidden_size": H,
        "intermediate_size": I, "num_hidden_layers": 1,
        "num_attention_heads": 4, "num_key_value_heads": 2,
    })
    from bigdl_tpu.api import TpuModel
    from bigdl_tpu.convert import params_from_state_dict

    params = params_from_state_dict(config, sd.__getitem__, qtype="bf16",
                                    dtype=jnp.float32)
    out = TpuModel(config, params, "bf16").generate([[3, 1, 4]], max_new_tokens=4)
    assert out.shape == (1, 4)
