"""Decoder-family model (llama/llama2/llama3, mistral, qwen2, gemma/gemma2,
phi3, baichuan2, starcoder2, stablelm, internlm2, minicpm, glm, and the MoE
variants mixtral/qwen2-moe).

TPU-native re-design of the reference's patched forwards
(`models/llama.py:56-200`, `models/mistral.py`, `models/qwen2.py`,
`models/gemma2.py`, `models/phi3.py`, `models/baichuan.py`,
`models/starcoder2.py`, `models/stablelm.py`, `models/mixtral.py`,
`models/qwen2_moe.py` in /root/reference): instead of monkey-patching HF
modules per architecture, one pure function over a parameter pytree reads
architecture differences from `ModelConfig` flags; dead branches compile
away under jit. Linear-layer leaves may be `QTensor` (packed low-bit).
Layers are **stacked along a leading axis and iterated with `lax.scan`**,
which keeps compile time O(1) in depth and gives the pipeline axis a
natural sharding target.

With a cache, attention always runs over the full cache [0, max_len)
under a validity mask derived from (start, pos) — so multi-chunk prefill
and decode share one code path and chunked prefill sees earlier chunks.
The `mode` argument only labels the jit specialization (prefill T>1 vs
decode T=1), mirroring the reference's prefill/decode kernel split
(low_bit_linear.py:606-716); the Pallas flash-attention prefill fast path
keys off it.

Batch rows are left-padded (see bigdl_tpu/kvcache.py).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from bigdl_tpu import kvcache
from bigdl_tpu.kvcache import KVCache
from bigdl_tpu.models.config import ModelConfig
from bigdl_tpu.obs.scopes import scope
from bigdl_tpu.ops import apply_rotary_emb, attention, linear, rms_norm, rope_cos_sin
from bigdl_tpu.ops.linear import (
    col_parallel_linear, row_parallel_linear, stacks_in,
)
from bigdl_tpu.ops.norms import layer_norm
from bigdl_tpu.ops.rope import alibi_slopes, make_inv_freq_scaled
from bigdl_tpu.quant import QTensor, quantize
from bigdl_tpu.quant.qtypes import resolve_qtype

Params = dict[str, Any]

_NEG_INF = -1e30

# weight name -> its per-shard linear under tensor parallelism, matching
# parallel/sharding.layer_specs (to_mesh splits the merged wqkv/w_gateup
# back before sharding, so only the split names occur)
_TP_PARALLEL = {
    "wq": col_parallel_linear, "wk": col_parallel_linear,
    "wv": col_parallel_linear, "w_gate": col_parallel_linear,
    "w_up": col_parallel_linear,
    "wo": row_parallel_linear, "w_down": row_parallel_linear,
}


# ---------------------------------------------------------------------------
# init / quantize
# ---------------------------------------------------------------------------

def init_params(
    config: ModelConfig,
    key: jax.Array,
    dtype=jnp.bfloat16,
    scale: float = 0.02,
) -> Params:
    """Random dense init (tests/benchmarks run without checkpoints)."""
    L, H, I = config.num_hidden_layers, config.hidden_size, config.intermediate_size
    V, QD, KD = config.vocab_size, config.q_dim, config.kv_dim
    keys = iter(jax.random.split(key, 32))

    def w(shape):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(dtype)

    layers = {
        "attn_norm": jnp.ones((L, H), dtype),
        "mlp_norm": jnp.ones((L, H), dtype),
        "wq": w((L, QD, H)),
        "wk": w((L, KD, H)),
        "wv": w((L, KD, H)),
        "wo": w((L, H, QD)),
    }
    if config.is_moe:
        E = config.num_experts
        EI = config.moe_intermediate_size or I
        layers["router"] = w((L, E, H))
        if config.gated_mlp:
            layers["w_gate_e"] = w((L, E, EI, H))
        layers["w_up_e"] = w((L, E, EI, H))
        layers["w_down_e"] = w((L, E, H, EI))
        if not config.gated_mlp and config.mlp_bias:
            layers["b_up_e"] = jnp.zeros((L, E, EI), dtype)
            layers["b_down_e"] = jnp.zeros((L, E, H), dtype)
        if config.shared_expert_intermediate_size:
            S = config.shared_expert_intermediate_size
            layers["w_gate_s"] = w((L, S, H))
            layers["w_up_s"] = w((L, S, H))
            layers["w_down_s"] = w((L, H, S))
            layers["shared_gate"] = w((L, 1, H))
    elif config.gated_mlp:
        layers["w_gate"] = w((L, I, H))
        layers["w_up"] = w((L, I, H))
        layers["w_down"] = w((L, H, I))
    else:
        layers["w_up"] = w((L, I, H))
        layers["w_down"] = w((L, H, I))
    if config.attention_bias:
        layers["bq"] = jnp.zeros((L, QD), dtype)
        layers["bk"] = jnp.zeros((L, KD), dtype)
        layers["bv"] = jnp.zeros((L, KD), dtype)
    if config.attention_out_bias:
        layers["bo"] = jnp.zeros((L, H), dtype)
    if config.mlp_bias:
        if config.gated_mlp:
            layers["b_gate"] = jnp.zeros((L, I), dtype)
        layers["b_up"] = jnp.zeros((L, I), dtype)
        layers["b_down"] = jnp.zeros((L, H), dtype)
    if config.norm_bias:
        layers["attn_norm_b"] = jnp.zeros((L, H), dtype)
        layers["mlp_norm_b"] = jnp.zeros((L, H), dtype)
    if config.post_attn_norm:
        layers["post_attn_norm"] = jnp.ones((L, H), dtype)
        layers["post_mlp_norm"] = jnp.ones((L, H), dtype)
    if config.qk_norm:
        D = config.head_dim_
        layers["q_norm"] = jnp.ones((L, D), dtype)
        layers["k_norm"] = jnp.ones((L, D), dtype)
    if config.attention_kind == "power_retention":
        # the retention gate: one logit per KV head and token. Dense and
        # outside the fused wqkv on purpose (80 KB a layer at Brumby's
        # sizes; it is read in float32)
        layers["w_g"] = w((L, config.num_key_value_heads, H))
    params: Params = {
        "embed": w((V, H)),
        "layers": layers,
        "final_norm": jnp.ones((H,), dtype),
    }
    if config.norm_bias:
        params["final_norm_b"] = jnp.zeros((H,), dtype)
    if config.learned_positions:
        params["wpe"] = w((config.max_position_embeddings, H))
    if config.embed_layernorm:
        params["embed_norm"] = jnp.ones((H,), dtype)
        params["embed_norm_b"] = jnp.zeros((H,), dtype)
    if not config.tie_word_embeddings:
        params["lm_head"] = w((V, H))
        if config.lm_head_bias:
            params["lm_head_b"] = jnp.zeros((V,), dtype)
    return params


_QUANT_TARGETS = (
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
    "wqkv", "w_gateup",  # fused layout (merge_fused_params)
    "w_gate_e", "w_up_e", "w_down_e", "w_gate_s", "w_up_s", "w_down_s",
)


def quantize_params(params: Params, qtype: str, lm_head_qtype: Optional[str] = None) -> Params:
    """Quantize the linear weights of a dense param tree.

    Equivalent of `ggml_convert_low_bit` walking modules (convert.py:1077):
    norms/biases/router stay dense; the lm head may use a different (higher)
    qtype, mirroring the reference's mixed-precision lm-head handling
    (convert.py:469-750, IPEX_LLM_LAST_LM_HEAD). Mixed aliases (q4_k_m)
    resolve to (body, head) formats here.
    """
    from bigdl_tpu.quant.qtypes import split_mixed_qtype

    qtype, head_default = split_mixed_qtype(qtype)
    lm_head_qtype = lm_head_qtype or head_default
    spec = resolve_qtype(qtype)
    if spec.is_dense:
        return params
    from bigdl_tpu.quant import quantize_or_dense

    out = dict(params)
    out["layers"] = dict(params["layers"])
    for name in _QUANT_TARGETS:
        w = params["layers"].get(name)
        if w is None or isinstance(w, QTensor):  # absent or already low-bit
            continue
        out["layers"][name] = quantize_or_dense(w, spec.name, name)
    if "lm_head" in params and not isinstance(params["lm_head"], QTensor):
        lm_spec = resolve_qtype(lm_head_qtype) if lm_head_qtype else spec
        if not lm_spec.is_dense:
            out["lm_head"] = quantize_or_dense(
                params["lm_head"], lm_spec.name, "lm_head")
    return out


def _concat_weights(ws, axis=-2):
    """Concatenate dense arrays or QTensors along the output axis.
    Returns None when the formats can't merge losslessly (mixed qtypes
    or dense leaves mixed with QTensors)."""
    if all(isinstance(w, jax.Array) for w in ws):
        return jnp.concatenate(ws, axis=axis)
    if not all(isinstance(w, QTensor) for w in ws):
        return None
    q0 = ws[0]
    if any(w.qtype != q0.qtype for w in ws):
        return None
    spec = q0.spec
    if spec.storage not in ("packed_u8", "packed_planes", "int8",
                            "fp8_e4m3", "fp8_e5m2"):
        return None  # every field must be row-leading [O, *]
    from bigdl_tpu.quant.qtensor import map_arrays_multi

    return map_arrays_multi(
        list(ws), lambda arrs: jnp.concatenate(arrs, axis=axis)
    )


def unmerge_fused_params(params: Params, config: ModelConfig) -> Params:
    """Inverse of merge_fused_params: split fused weights back into their
    parts (row slices — lossless). Used before tensor-parallel sharding:
    a column-parallel fused weight would put the q/k/v split boundaries
    off shard boundaries for GQA models, forcing GSPMD resharding
    collectives on every layer."""
    layers = params.get("layers", {})
    if "wqkv" not in layers and "w_gateup" not in layers:
        return params
    out = dict(params)
    lay = dict(layers)

    def rows(w, a, b):
        if isinstance(w, QTensor):
            return w.map_arrays(lambda arr: arr[..., a:b, :])
        return w[..., a:b, :]

    if "wqkv" in lay:
        QD, KD = config.q_dim, config.kv_dim
        w = lay.pop("wqkv")
        lay["wq"] = rows(w, 0, QD)
        lay["wk"] = rows(w, QD, QD + KD)
        lay["wv"] = rows(w, QD + KD, QD + 2 * KD)
        if "bqkv" in lay:
            b = lay.pop("bqkv")
            lay["bq"], lay["bk"], lay["bv"] = (
                b[..., :QD], b[..., QD:QD + KD], b[..., QD + KD:]
            )
    if "w_gateup" in lay:
        w = lay.pop("w_gateup")
        I = (w.shape[-2] if not isinstance(w, QTensor)
             else w.data.shape[-2]) // 2
        lay["w_gate"] = rows(w, 0, I)
        lay["w_up"] = rows(w, I, 2 * I)
        if "b_gateup" in lay:
            b = lay.pop("b_gateup")
            lay["b_gate"], lay["b_up"] = b[..., :I], b[..., I:]
    out["layers"] = lay
    return out


def merge_fused_params(params: Params, config: ModelConfig) -> Params:
    """Fuse qkv and gate/up into single linears (the reference's
    merge_qkv / mlp fusion, models/common.py:22-53 + _optimize_pre
    convert.py:886): one kernel call streams one larger weight — fewer
    per-call fixed costs on the decode hot path. The forward splits the
    fused output, so results are bit-identical to the unmerged layout.
    Falls back silently (returns the tree unchanged) for formats that
    can't concatenate losslessly."""
    layers = params.get("layers", {})
    if "wqkv" in layers or "wq" not in layers:
        return params
    out = dict(params)
    lay = dict(layers)

    wqkv = _concat_weights([lay["wq"], lay["wk"], lay["wv"]])
    if wqkv is not None:
        lay["wqkv"] = wqkv
        for k in ("wq", "wk", "wv"):
            del lay[k]
        if "bq" in lay:
            lay["bqkv"] = jnp.concatenate(
                [lay.pop("bq"), lay.pop("bk"), lay.pop("bv")], axis=-1
            )
    if config.gated_mlp and not config.is_moe and "w_gate" in lay:
        gu = _concat_weights([lay["w_gate"], lay["w_up"]])
        if gu is not None:
            lay["w_gateup"] = gu
            del lay["w_gate"], lay["w_up"]
            if "b_gate" in lay:
                lay["b_gateup"] = jnp.concatenate(
                    [lay.pop("b_gate"), lay.pop("b_up")], axis=-1
                )
    out["layers"] = lay
    return out


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _act(name: str, x: jax.Array) -> jax.Array:
    if name == "silu":
        return jax.nn.silu(x)
    if name == "gelu":  # HF get_activation("gelu") = exact erf gelu
        return jax.nn.gelu(x, approximate=False)
    if name in ("gelu_new", "gelu_pytorch_tanh", "gelu_tanh"):
        return jax.nn.gelu(x, approximate=True)
    if name == "relu":
        return jax.nn.relu(x)
    raise NotImplementedError(f"hidden_act {name}")


def _softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return x
    return jnp.tanh(x / cap) * cap


def embed_tokens(config: ModelConfig, params: Params, tokens: jax.Array,
                 compute_dtype=jnp.bfloat16) -> jax.Array:
    """Token embedding incl. the gemma/minicpm scaling knobs — shared by
    forward() and the pipeline stage program (parallel/pipeline.py).
    The table may be dense, a QTensor (LowBitEmbedding), or a
    HostEmbedding (CPU/disk offload) — see bigdl_tpu/embedding.py."""
    from bigdl_tpu.embedding import embed_lookup

    h = embed_lookup(params["embed"], tokens, compute_dtype)
    if config.scale_embeddings:
        h = h * jnp.asarray(config.hidden_size**0.5, compute_dtype)
    if config.embedding_scale:
        h = h * jnp.asarray(config.embedding_scale, compute_dtype)
    return h


def lm_head_logits(config: ModelConfig, params: Params, h: jax.Array,
                   compute_dtype=jnp.bfloat16) -> jax.Array:
    """Final norm + lm head + logit scaling/softcap — shared by forward()
    and the pipeline stage program."""
    with scope("norm"):
        if config.norm_type == "layernorm":
            h = layer_norm(h, params["final_norm"],
                           params.get("final_norm_b"), config.rms_norm_eps)
        else:
            h = rms_norm(h, params["final_norm"], config.rms_norm_eps,
                         offset=config.rms_norm_offset)
    with scope("lm_head"):
        lm_head = params.get("lm_head", params["embed"])
        logits = linear(
            h, lm_head, params.get("lm_head_b"), compute_dtype
        ).astype(jnp.float32)
        if config.logit_scale:
            logits = logits * config.logit_scale
        return _softcap(logits, config.final_logit_softcap)


def _lora_delta(x, pair, scale, compute_dtype):
    """x [.., in] through a LoRA pair {'a': [r, in], 'b': [out, r]}.
    Batched per-row pairs ({'a': [B, r, in], 'b': [B, out, r]}, scale
    [B]) apply slot i's adapter to row i — the serving engine's
    heterogeneous multi-tenant decode batch (ops/linear.lora_epilogue;
    docs/serving.md §7)."""
    from bigdl_tpu.ops.linear import lora_epilogue

    return lora_epilogue(x, pair["a"], pair["b"], scale, compute_dtype)


def _deq(w, compute_dtype):
    return w.dequantize(compute_dtype) if isinstance(w, QTensor) else w.astype(compute_dtype)


def resolve_moe_dispatch(config: ModelConfig) -> str:
    """The XLA formulation for where the grouped kernel cannot run
    (`_moe_dispatch`): `config.moe_dispatch` if set, else "ragged" above
    8 experts (FLOPs go with k/E, assignments past capacity are DROPPED)
    and "dense" up to 8. Dense is exact and differentiable but not cheap:
    on a v5e Mixtral-8x7B's dense combine took 227 ms a decode step on
    ten layers, 92% of it writing every expert's bf16 weights (PERF.md,
    PR 23), so it is the training and mesh formulation, not a serving
    path."""
    if config.moe_dispatch is not None:
        return config.moe_dispatch
    return "ragged" if config.num_experts > 8 else "dense"


def _moe_router(config: ModelConfig, xc: jax.Array, p: Params):
    """Top-k routing with softmax weights. Returns (topv [B,T,k] f32,
    topi [B,T,k] i32). Mixtral renormalizes the top-k weights
    (norm_topk_prob=True via config), qwen2_moe per its flag.

    The logits are a float32 product at full precision (E x H weights:
    it costs nothing): a TPU's default matmul rounds float32 operands to
    bf16, and a top-k over logits that coarse picks another expert
    wherever the k-th and the next lie within the rounding."""
    router_logits = jnp.einsum(
        "bth,eh->bte", xc.astype(jnp.float32),
        p["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    probs_all = jax.nn.softmax(router_logits, axis=-1)
    topv, topi = jax.lax.top_k(probs_all, config.num_experts_per_tok)
    if config.norm_topk_prob:
        topv = topv / (jnp.sum(topv, axis=-1, keepdims=True) + 1e-20)
    return topv, topi


def _expert_ffn(config: ModelConfig, xe: jax.Array, p: Params, compute_dtype):
    """Per-expert FFN on already-grouped tokens: [E, C, H] -> [E, C, H].
    Gated (mixtral/qwen2-moe) or plain fc->act->proj with biases
    (phixtral's phi-2 experts, gated_mlp=False)."""
    wu = _deq(p["w_up_e"], compute_dtype)  # [E, I, H]
    wd = _deq(p["w_down_e"], compute_dtype)  # [E, H, I]
    u = jnp.einsum("ech,eih->eci", xe, wu, preferred_element_type=compute_dtype)
    if config.gated_mlp:
        wg = _deq(p["w_gate_e"], compute_dtype)  # [E, I, H]
        g = jnp.einsum("ech,eih->eci", xe, wg,
                       preferred_element_type=compute_dtype)
        z = _act(config.hidden_act, g) * u
    else:
        if "b_up_e" in p:
            u = u + p["b_up_e"].astype(compute_dtype)[:, None, :]
        z = _act(config.hidden_act, u)
    out = jnp.einsum("eci,ehi->ech", z, wd, preferred_element_type=compute_dtype)
    if not config.gated_mlp and "b_down_e" in p:
        out = out + p["b_down_e"].astype(compute_dtype)[:, None, :]
    return out


def _moe_dispatch_ragged(
    config: ModelConfig, xc: jax.Array, p: Params, compute_dtype,
    topv: jax.Array, topi: jax.Array,
) -> jax.Array:
    """Capacity-based ragged dispatch (GShard/Switch style): each expert
    computes only its routed tokens, so FLOPs scale with k/E instead of
    1 — the difference between mixtral (E=8, k=2: dense costs 4x) and
    qwen2-moe (E=60, k=4: dense would cost 15x).

    Static-shape formulation for XLA: per-expert slot positions come from
    a cumulative sum over the one-hot assignment matrix; tokens beyond
    expert capacity C = ceil(N*k/E * capacity_factor) are dropped (their
    combine weight is zeroed — router softmax mass simply doesn't arrive,
    matching GShard overflow semantics). Gather/scatter both
    differentiate cleanly for MoE training.
    """
    B, T, H = xc.shape
    E, k = config.num_experts, config.num_experts_per_tok
    N = B * T
    cf = config.moe_capacity_factor
    C = max(1, min(N, int(-(-N * k * cf // E))))

    x_flat = xc.reshape(N, H)
    e_flat = topi.reshape(N * k)  # assignment order: token-major
    w_flat = topv.reshape(N * k).astype(compute_dtype)

    onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)  # [N*k, E]
    pos = jnp.cumsum(onehot, axis=0) - onehot  # prior same-expert count
    pos = jnp.sum(pos * onehot, axis=-1)  # [N*k] slot within expert
    keep = pos < C
    slot = jnp.where(keep, e_flat * C + pos, E * C)  # E*C = overflow bin

    tok = jnp.repeat(jnp.arange(N), k)  # token of each assignment
    x_disp = jnp.zeros((E * C + 1, H), compute_dtype).at[slot].add(
        x_flat[tok], mode="drop"
    )
    y = _expert_ffn(
        config, x_disp[:-1].reshape(E, C, H), p, compute_dtype
    ).reshape(E * C, H)
    y = jnp.concatenate([y, jnp.zeros((1, H), compute_dtype)], axis=0)

    contrib = y[slot] * w_flat[:, None]  # overflow slots read zeros
    out = jnp.zeros((N, H), compute_dtype).at[tok].add(contrib)
    return out.reshape(B, T, H)


def _moe_dispatch_dense(
    config: ModelConfig, xc: jax.Array, p: Params, compute_dtype,
    topv: jax.Array, topi: jax.Array,
) -> jax.Array:
    """Dense combine: every expert computes every token, top-k weights
    (zero for unrouted) scatter into a [B,T,E] combine matrix —
    all-matmul, MXU-friendly, exactly differentiable. Best at small E.
    Shared by the llama-family router and the DeepSeek router
    (models/deepseek.py)."""
    onehot = jax.nn.one_hot(topi, config.num_experts, dtype=jnp.float32)
    combine = jnp.einsum("btk,btke->bte", topv, onehot)
    wu = _deq(p["w_up_e"], compute_dtype)  # [E, I, H]
    wd = _deq(p["w_down_e"], compute_dtype)  # [E, H, I]
    u = jnp.einsum("bth,eih->btei", xc, wu, preferred_element_type=compute_dtype)
    if config.gated_mlp:
        wg = _deq(p["w_gate_e"], compute_dtype)  # [E, I, H]
        g = jnp.einsum("bth,eih->btei", xc, wg,
                       preferred_element_type=compute_dtype)
        z = _act(config.hidden_act, g) * u
    else:  # phixtral: plain biased fc1 -> act; biases ride inside each
        # expert's weighted term, exactly like HF's per-expert MLP call
        if "b_up_e" in p:
            u = u + p["b_up_e"].astype(compute_dtype)[None, None]
        z = _act(config.hidden_act, u)
    d = jnp.einsum("btei,ehi->bteh", z, wd, preferred_element_type=compute_dtype)
    if not config.gated_mlp and "b_down_e" in p:
        d = d + p["b_down_e"].astype(compute_dtype)[None, None]
    return jnp.einsum("bteh,bte->bth", d, combine.astype(compute_dtype))


_EXPERT_STACKS = ("w_gate_e", "w_up_e", "w_down_e")

# the per-layer weights that go through `linear` (the shared expert's go
# through the XLA dequant)
_LINEAR_STACKS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
                  "wqkv", "w_gateup")


def _moe_dispatch_grouped(
    config: ModelConfig, xc: jax.Array, p: Params, compute_dtype,
    topv: jax.Array, topi: jax.Array, layer=None, held=None,
) -> jax.Array:
    """Dropless dispatch on packed weights: the assignments are sorted
    by expert, each expert's rows padded to a whole row tile, and
    gate/up/down run through the grouped fused dequant kernel
    (`ops/pallas/moe_qmatmul.py`) with float32 accumulation. Where one
    tile holds the whole call (every decode step) nothing is sorted or
    copied: each hit expert's tile is the call's rows as they stand. Every
    assignment is computed (the layout has room for all N*k, however
    they fall), an expert nobody chose is never read, and no expert is
    ever dequantized into HBM. Rows are independent from the gather to
    the combine, so a padded or idle row (NaN included) cannot reach a
    live one. The combine gathers k-major, `y[dest.T]` as `[k, N, H]`, and
    sums over the major axis: as `[N, k, H]`, k lies on the sublane axis,
    is padded to a multiple of 8 and every float32 row re-laid once more a
    layer. `layer` says the stacks' packed codes still carry the layer
    axis (forward keeps them out of the scan's slices). `held [B, T, k]`
    (`_held_share`: one rank's share of the experts): an assignment that is
    not held gets no row and no tile, and nothing of the buffer is read for
    it (a tile nobody filled holds no meaning)."""
    from bigdl_tpu.ops.pallas import moe_qmatmul as mq

    B, T, H = xc.shape
    E, k, N = config.num_experts, config.num_experts_per_tok, B * T
    wu, wd = p["w_up_e"], p["w_down_e"]
    block_m = _moe_block_m(xc, p)
    call = functools.partial(mq.moe_qmatmul, block_m=block_m, layer=layer)

    if held is not None:
        held = held.reshape(N, k)
    with scope("moe.dispatch"):
        xs = xc.reshape(N, H)
        if N <= block_m:  # one tile holds the call: the rows as they stand
            dest, tile_expert, n_used = mq.moe_layout_shared(
                topi.reshape(N, k), E, block_m, held)
            if N < block_m:  # (a few rows, to the sublane tile)
                xs = jnp.pad(xs, ((0, block_m - N), (0, 0)))
        else:
            dest, src, tile_expert, n_used = mq.moe_layout(
                topi.reshape(N, k), E, block_m,
                mq.moe_n_tiles(N, k, E, block_m), held)
            xs = xs[src]  # [n_tiles * block_m, H]
        row_expert = jnp.repeat(tile_expert, block_m)
    with scope("moe.experts"):
        if not config.gated_mlp:  # phixtral: biased fc1 -> act -> fc2
            u = call(xs, wu, tile_expert, n_used, out_dtype=jnp.float32)
            if "b_up_e" in p:
                u = u + p["b_up_e"].astype(jnp.float32)[row_expert]
            z = _act(config.hidden_act, u)
        elif config.hidden_act in mq.FUSED_ACTS:
            z = call(xs, (p["w_gate_e"], wu), tile_expert, n_used,
                     act=config.hidden_act)
        else:
            g, u = (call(xs, w, tile_expert, n_used, out_dtype=jnp.float32)
                    for w in (p["w_gate_e"], wu))
            z = _act(config.hidden_act, g) * u
        y = call(z.astype(compute_dtype), wd, tile_expert, n_used,
                 out_dtype=jnp.float32)
        if not config.gated_mlp and "b_down_e" in p:
            y = y + p["b_down_e"].astype(jnp.float32)[row_expert]
    with scope("moe.combine"):
        rows = y[dest.T]
        if held is not None:
            rows = jnp.where(held.T[:, :, None], rows, 0.0)
        out = jnp.sum(rows * topv.reshape(N, k).T[:, :, None], axis=0)
    return out.astype(compute_dtype).reshape(B, T, H)


def _moe_block_m(xc: jax.Array, p: Params) -> int:
    """The row tile of a layer's grouped calls on `xc [B, T, H]`."""
    from bigdl_tpu.ops.pallas import moe_qmatmul as mq

    B, T, H = xc.shape
    return mq.moe_block_m(B * T, max(H, p["w_up_e"].data.shape[-2]))


def _grouped_plan(config: ModelConfig, p: Params) -> str:
    """The tile plan of each call `_moe_dispatch_grouped` makes for this
    layer (`moe_qmatmul.call_plan`: the loop, and the grid steps an
    expert), for the route note: `gate_up words:inplace:paired x1 of 3
    tiles, down words:inplace x1 of 8 tiles`."""
    from bigdl_tpu.ops.pallas import moe_qmatmul as mq

    if not config.gated_mlp:
        first = f"up {mq.call_plan(p['w_up_e'])}"
    elif config.hidden_act in mq.FUSED_ACTS:
        first = f"gate_up {mq.call_plan((p['w_gate_e'], p['w_up_e']))}"
    else:  # two plain calls and the activation in XLA
        first = f"gate, up {mq.call_plan(p['w_up_e'])}"
    return f"{first}, down {mq.call_plan(p['w_down_e'])}"


def _gate_up_fused(config: ModelConfig) -> bool:
    """Is a layer's gate / up ONE grouped call (the activation applied
    in-kernel), as `_moe_dispatch_grouped` makes it?"""
    from bigdl_tpu.ops.pallas import moe_qmatmul as mq

    return config.gated_mlp and config.hidden_act in mq.FUSED_ACTS


def _grouped_calls(config: ModelConfig, p: Params) -> list:
    """The stacks of each grouped call `_moe_dispatch_grouped` makes for
    this layer: the fused gate / up pair, or each stack by itself."""
    if _gate_up_fused(config):
        return [(p["w_gate_e"], p["w_up_e"]), (p["w_down_e"],)]
    return [(p[n],) for n in _EXPERT_STACKS if n in p]


def _reads_bits(config: ModelConfig, p: Params) -> bool:
    """Does every grouped call of this layer read prepared scale bits?"""
    from bigdl_tpu.ops.pallas import moe_qmatmul as mq
    from bigdl_tpu.ops.pallas.qmatmul import prepared_bits

    return all(prepared_bits(w, mq.bits_layout(ws)) is not None
               for ws in _grouped_calls(config, p) for w in ws)


def prepare_kernel_scales(config: ModelConfig, params: Params) -> Params:
    """`params` as a program that serves from it holds it: every packed
    weight a kernel reads carries its scales a second time as the operand
    that kernel reads in place (`linear.prepare_scale_bits`: uint16 bits,
    the output rows on lanes), laid out here, ONCE, by one jitted program.
    An expert stack (`_EXPERT_STACKS`, in whichever group of whichever
    family's tree) is laid out for the grouped call it is part of, every
    other packed weight for `linear`; the float16 fields stay for the XLA
    routes, the references and whoever walks the tree, and a tree no
    kernel reads (a CPU without the interpreter, `BIGDL_TPU_PALLAS=0`)
    comes back as it is. The forwards find the bits by looking
    (`linear.stacks_out`); a tree that was not prepared runs as before."""
    from bigdl_tpu.ops.linear import prepare_scale_bits
    from bigdl_tpu.ops.pallas import why_not_pallas
    from bigdl_tpu.quant.qtensor import ARRAY_FIELDS

    if why_not_pallas() is not None:
        return params
    is_q = lambda x: isinstance(x, QTensor)  # noqa: E731
    paired = _gate_up_fused(config)
    stacks = {"w_gate_e": 1 + paired, "w_up_e": 1 + paired, "w_down_e": 1}

    def bits_of(tree):
        """A QTensor of the new fields alone for each of the tree's: only
        they leave the program, the tree's own arrays are not copied."""
        def one(path, w):
            if not is_q(w):
                return None
            w = prepare_scale_bits(
                w, stacks.get(getattr(path[-1], "key", None)))
            return dataclasses.replace(w, **dict.fromkeys(ARRAY_FIELDS))

        return jax.tree_util.tree_map_with_path(one, tree, is_leaf=is_q)

    return jax.tree.map(
        lambda w, b: dataclasses.replace(
            w, scale_bits=b.scale_bits, min_bits=b.min_bits,
            bits_layout=b.bits_layout) if is_q(w) else w,
        params, jax.jit(bits_of)(params), is_leaf=is_q)


def moe_grouped_why_not(p: Params, differentiable: bool) -> Optional[str]:
    """None when a layer's experts take `_moe_dispatch_grouped`: packed
    stacks the kernels can tile, the kernels in use (a TPU, or the
    interpreter; not under a mesh axis XLA partitions), and nothing to
    differentiate (the grouped kernel has no backward)."""
    from bigdl_tpu.ops.linear import grouped_route

    if differentiable:
        return "adapters are being trained: the XLA formulations differentiate"
    return grouped_route(*(p[n] for n in _EXPERT_STACKS if n in p))


def _held_share(config: ModelConfig, topv: jax.Array, topi: jax.Array):
    """One rank's share of an expert-parallel layer (`ModelConfig.
    expert_share`): of the router's choices `topi` (ids over the router's
    whole width) those that fall on the experts held here, as (weights,
    zero where the expert is held elsewhere; LOCAL ids, `num_experts`, one
    past the last, where it is; which assignments are held). The weights
    are the whole routing's: what normalised them summed over every chosen
    expert, held here or not."""
    first, n_held, _ = config.expert_share
    local = topi.astype(jnp.int32) - first
    held = (local >= 0) & (local < n_held)
    return (jnp.where(held, topv, 0.0), jnp.where(held, local, n_held),
            held)


def _moe_dispatch(
    config: ModelConfig, xc: jax.Array, p: Params, compute_dtype,
    topv: jax.Array, topi: jax.Array, ragged_config=None,
    differentiable: bool = False, layer=None,
) -> jax.Array:
    """Routed experts of one layer, by the one rule every MoE family
    goes through: packed expert stacks at inference take the dropless
    grouped kernel; everything else (dense weights, training, a mesh
    axis XLA partitions, an ineligible shape, the CPU without the
    interpreter) takes the XLA formulation `resolve_moe_dispatch` names.
    `ragged_config` carries a family's capacity adjustment for the
    ragged formulation only (DeepSeek's group-limited routing). Where the
    configuration holds one rank's share of the experts (`_held_share`),
    every formulation computes the held experts' part and nothing stands
    in for the others': the dense one-hot has no column for them, the
    ragged form sends them to its overflow bin, the grouped layout gives
    them no row."""
    from bigdl_tpu.ops import routes

    B, T, H = xc.shape
    detail = (f"N{B * T} k{config.num_experts_per_tok} "
              f"E{config.num_experts} H{H}")
    held = None
    if config.expert_share is not None:
        first, n_held, width = config.expert_share
        detail += f" held {n_held}/{width} first {first}"
        with scope("moe.dispatch"):
            topv, topi, held = _held_share(config, topv, topi)
    why = moe_grouped_why_not(p, differentiable)
    if why is None:
        rows = "shared" if B * T <= _moe_block_m(xc, p) else "sorted"
        routes.note("moe", "pallas:grouped",
                    f"{p['w_up_e'].qtype} {detail} dropless: "
                    f"{_grouped_plan(config, p)} rows:{rows} "
                    f"scales:{'stack' if _reads_bits(config, p) else 'slice'}")
        return _moe_dispatch_grouped(config, xc, p, compute_dtype, topv,
                                     topi, layer, held)
    assert layer is None, "unsliced expert codes are for the grouped path"
    kind = resolve_moe_dispatch(config)
    routes.note("moe", f"xla:{kind}", f"{detail} ({why})")
    if kind == "ragged":
        return _moe_dispatch_ragged(ragged_config or config, xc, p,
                                    compute_dtype, topv, topi)
    return _moe_dispatch_dense(config, xc, p, compute_dtype, topv, topi)


def _moe_mlp(config: ModelConfig, x: jax.Array, p: Params, compute_dtype
             ) -> jax.Array:
    """`_moe_block`'s output alone."""
    return _moe_block(config, x, p, compute_dtype)[0]


def _moe_block(config: ModelConfig, x: jax.Array, p: Params, compute_dtype,
               differentiable: bool = False, layer=None):
    """Mixture-of-experts MLP (reference models/mixtral.py, qwen2_moe.py +
    `xe_linear.get_moe_indexes`): top-k routing with softmax weights in
    float32, then the routed experts by `_moe_dispatch` (docs/kernels.md
    says which path runs when). Returns (out [B,T,H], topi [B,T,k])."""
    B, T, H = x.shape
    xc = x.astype(compute_dtype)
    with scope("moe.router"):
        topv, topi = _moe_router(config, xc, p)
    out = _moe_dispatch(config, xc, p, compute_dtype, topv, topi,
                        differentiable=differentiable, layer=layer)

    if config.shared_expert_intermediate_size:
        # qwen2_moe shared expert, sigmoid-gated (models/qwen2_moe.py)
        with scope("moe.shared"):
            sg = jnp.einsum("bth,ih->bti", xc,
                            _deq(p["w_gate_s"], compute_dtype))
            su = jnp.einsum("bth,ih->bti", xc,
                            _deq(p["w_up_s"], compute_dtype))
            sd = jnp.einsum(
                "bti,hi->bth", _act(config.hidden_act, sg) * su,
                _deq(p["w_down_s"], compute_dtype),
            )
            gate = jax.nn.sigmoid(jnp.einsum(
                "bth,oh->bto", xc, p["shared_gate"].astype(compute_dtype)))
            out = out + sd * gate
    return out, topi


def forward(
    config: ModelConfig,
    params: Params,
    tokens: jax.Array,  # [B, T] int32
    cache: Optional[KVCache],
    mode: str = "prefill",  # static: "prefill" | "decode" | "block" (a
    # model generated by diffusion over blocks, config.block_length = b:
    # tokens [B, b] are each row's block at the row's own offset, written to
    # its pages and attended in both directions inside the block)
    compute_dtype=jnp.bfloat16,
    lora: Optional[Params] = None,  # LoRA adapter tree (see bigdl_tpu.train)
    start: Optional[jax.Array] = None,  # [B] pad offsets when cache is None
    collect_obs: int = 0,  # static: stash the last-N rotated queries per layer
    attention_override=None,  # static: fn(q, k, v, start) for the cache-free
    # path — e.g. sequence-parallel ring attention (parallel/ring.py)
    input_is_hidden: bool = False,  # static: tokens is [B,T,H] hidden states
    return_hidden: bool = False,  # static: skip final norm/head, return h
    layer_offset=0,  # global index of params['layers'][0] (pipeline stages)
    position_grid=None,  # [3, B, T] M-RoPE (t, h, w) positions — multimodal
    # prefill only (models/qwen2_vl.py); None = standard 1-D positions
    positions=None,  # [B, T] explicit 1-D rope/learned positions — remote-
    # code schemes where slot != position (chatglm4v repeats the image
    # span's position across all patches); pair with cache.rope_base so
    # decode continues from the true last position
    last_logits_only: bool = False,  # static: lm head on the last position
    # only — prefill skips the [B,T,V] logits (reference
    # reshape_lm_head_input / IPEX_LLM_LAST_LM_HEAD,
    # low_bit_linear.py:262-270)
    remat: bool = False,  # static: jax.checkpoint each scan layer —
    # backward recomputes the layer instead of saving its activations
    # (long-context training memory lever; make_train_step(remat=True))
    comm=None,  # static: parallel/qcollectives.CommConfig, set by
    # TpuModel.to_mesh for tp > 1 — the per-layer projections run per
    # shard under shard_map, and the row-parallel pair (wo, w_down)
    # reduces through its all-reduce: exact for comm_qtype="none", the
    # block-quantized ring otherwise. None is the single-device path.
    moe_routing: bool = False,  # static: also return every layer's top-k
    # expert ids [L, B, T, k] int32, last (the serving engine counts expert
    # load from them, with the tokens a step already fetches)
) -> tuple[jax.Array, Optional[KVCache]]:
    """Returns (logits [B, T, V] float32, updated cache with pos advanced).

    cache=None runs the cache-free training/scoring path (full block-causal
    attention, no KV writes) — the path QLoRA finetuning differentiates
    through.

    collect_obs=W > 0 (prefill only) additionally returns the observation
    window queries [L, B, W, Hq, D] for SnapKV compression
    (kvcache.compress) as a third element.

    input_is_hidden/return_hidden let a pipeline stage run only its slice
    of the layer stack (parallel/pipeline.py): embedding happens before
    the first stage, final norm + lm head after the last.
    """
    assert mode in ("prefill", "decode", "block")
    B, T = tokens.shape[:2]
    # 0 for every autoregressive model: then nothing below differs
    block_causal = config.block_length
    assert (mode == "block") <= (block_causal == T and cache is not None)
    Hq, Hkv, D = config.num_attention_heads, config.num_key_value_heads, config.head_dim_
    eps = config.rms_norm_eps

    def norm(x, w, b=None):
        if config.norm_type == "layernorm":
            return layer_norm(x, w, b, eps)
        return rms_norm(x, w, eps, offset=config.rms_norm_offset)

    with scope("engine"):  # positions, and the embedding
        if cache is None:
            pos0 = jnp.zeros((), jnp.int32)
            row_start = jnp.zeros((B,), jnp.int32) if start is None else start
        else:
            pos0 = cache.pos
            row_start = cache.start

        # Positions are relative to each row's start (left pad); after SnapKV
        # compression slots ≠ positions and the cache carries the true next
        # position in rope_base. pos may be per-row (serving engine).
        pos_col = pos0[:, None] if pos0.ndim == 1 else pos0
        slots = pos_col + jnp.arange(T)[None, :]  # [B|1, T] global cache slots
        if positions is not None:
            positions = positions.astype(jnp.int32)  # caller-supplied override
        elif cache is not None:
            positions = cache.next_positions(T)  # [B, T]
        else:
            positions = jnp.maximum(slots - row_start[:, None], 0)  # [B, T]

        if input_is_hidden:
            h = tokens.astype(compute_dtype)
        else:
            h = embed_tokens(config, params, tokens, compute_dtype)
            if config.learned_positions:  # gpt2 wpe table
                h = h + params["wpe"].astype(compute_dtype)[positions]
            if config.embed_layernorm:  # bloom word_embeddings_layernorm
                h = layer_norm(
                    h, params["embed_norm"], params.get("embed_norm_b"),
                    config.rms_norm_eps,
                )

    with scope("attn.rope"):  # the tables, once for every layer
        use_rope = not (config.alibi or config.learned_positions)
        cos_local = sin_local = None
        if use_rope:
            inv_freq, att_scale = make_inv_freq_scaled(
                config.rotary_dim, config.rope_theta, config.rope_scaling_dict,
                seq_len=(cache.max_len if cache is not None else T),
            )
            if position_grid is not None and config.mrope_section:
                from bigdl_tpu.ops.rope import mrope_cos_sin

                cos, sin = mrope_cos_sin(
                    position_grid, inv_freq, config.mrope_section,
                    scale=att_scale,
                )
            else:
                cos, sin = rope_cos_sin(
                    positions, inv_freq, interleaved=config.rope_interleaved,
                    scale=att_scale,
                )
            if config.rope_local_theta is not None:
                # gemma3 dual rope: sliding layers use the local base,
                # UNscaled (HF applies rope_scaling to global layers only)
                inv_local, _ = make_inv_freq_scaled(
                    config.rotary_dim, config.rope_local_theta, None
                )
                cos_local, sin_local = rope_cos_sin(
                    positions, inv_local, interleaved=config.rope_interleaved
                )
        else:
            cos = sin = None

        # qwen v1 logn attention (HF modeling_qwen logn_tensor; reference
        # models/qwen.py): queries beyond the training length scale by
        # log_train_len(pos+1) so attention entropy stays flat as the
        # context grows. max(1, .) keeps in-distribution positions exact.
        logn_col = None
        if config.logn_attn and config.logn_train_len:
            i = positions.astype(jnp.float32) + 1.0
            logn = jnp.maximum(
                jnp.log(i) / jnp.log(jnp.float32(config.logn_train_len)), 1.0
            )
            logn_col = logn[:, :, None, None].astype(compute_dtype)

    # Prefill goes through the Pallas flash-attention kernel (no [T,S]
    # score matrix in HBM); decode and the differentiable cache-free
    # training path use the fused XLA attention. Mirrors the reference's
    # sdp_causal vs sdp dispatch (models/common.py:222-258).
    from bigdl_tpu.ops.pallas import use_pallas, why_not_pallas

    uniform_window = (config.sliding_window_pattern is None
                      and config.sliding_layers is None)
    use_flash = (
        cache is not None and mode == "prefill" and T > 1 and use_pallas()
        and uniform_window and not config.alibi
        and cache.pos.ndim == 0  # kernel takes a scalar q_offset
    )
    # training (cache=None): the differentiable flash kernel
    # (ops/pallas/flash_backward.py) — the backward recomputes attention
    # blockwise instead of saving the [T, T] probabilities, which is
    # what lets long-context single-chip finetuning fit in HBM
    use_flash_train = (
        cache is None and T > 1 and use_pallas()
        and uniform_window and not config.alibi
        and attention_override is None
        and config.attn_logit_softcap is None
        and not block_causal  # that kernel's mask is causal by position
    )

    # Attention masks (shared by all layers, computed once outside the scan).
    # With sliding-window alternation (gemma2) both the global and the
    # sliding mask are built; the scan body selects per layer index.
    def last_seen(q_slot):
        """The last slot a query at `q_slot` sees: itself, or under a mask
        causal by block the end of its block."""
        if not block_causal:
            return q_slot
        return q_slot - q_slot % block_causal + (block_causal - 1)

    def build_masks():
        if cache is None:
            tj = jnp.arange(T)
            base = (tj[None, :] <= last_seen(tj[:, None]))[None] & (
                tj[None, None, :] >= row_start[:, None, None]
            )  # [B, T, T]
            k_slot = tj[None, None, :]
            q_slot = tj[None, :, None]
        else:
            S = cache.max_len
            sj = jnp.arange(S)
            base = (sj[None, None, :] <= last_seen(slots[..., None])) & (
                sj[None, None, :] >= row_start[:, None, None]
            )  # [B, T, S]
            k_slot = sj[None, None, :]
            q_slot = slots[..., None]
        if config.sliding_window:
            sliding = base & (k_slot > q_slot - config.sliding_window)
        else:
            sliding = base
        return base, sliding, k_slot, q_slot

    # Paged decode reads KV pages in place via the Pallas paged-attention
    # kernel — the XLA path would gather every page into a dense [B, S]
    # copy per step (3x the HBM traffic; kvpaged.py docstring).
    from bigdl_tpu.kvpaged import PagedKVCache, live_rows

    use_paged_kernel = (
        isinstance(cache, PagedKVCache)
        and (mode == "decode" and T == 1 or mode == "block")
        and use_pallas() and not config.alibi
        and attention_override is None
    )

    from bigdl_tpu.ops import routes

    att_detail = f"mode={mode} B{B} T{T}"
    # the second attention kind: no keys are kept and nothing is masked;
    # what the cache holds is a recurrent state (bigdl_tpu/kvstate.py)
    retention = config.attention_kind == "power_retention"
    if retention:
        from bigdl_tpu import kvstate

        use_flash = use_flash_train = use_paged_kernel = False
        with scope("attn"):
            ret_valid = kvstate.valid_positions(cache, slots, row_start, T)
        why = (kvstate.why_not_kernel(D) if mode == "decode" and T == 1
               and cache is not None else "the chunked form is XLA's")
        routes.note("attention",
                    "pallas:retention" if why is None else "xla:retention",
                    att_detail + (f" ({why})" if why else ""))
    elif use_paged_kernel:
        routes.note("attention", "pallas:paged", att_detail + (
            f" block of {T}: {T * Hq // Hkv} rows a KV head"
            if mode == "block" else ""))
        with scope("attn"):
            row_live = live_rows(cache)  # the table does not change in here
    elif use_flash_train:
        routes.note("attention", "pallas:flash_train", att_detail)
    elif use_flash:
        routes.note("attention", "pallas:flash", att_detail)
    else:
        why = why_not_pallas() or (
            "dense-cache decode: no kernel, fused XLA attention" if T == 1
            else "per-row cache.pos: flash takes a scalar q_offset"
            if cache is not None and cache.pos.ndim != 0
            else "alibi, mixed window layers, softcap or an override")
        routes.note("attention", "xla", f"{att_detail} ({why})")

    if use_flash or use_paged_kernel or use_flash_train or retention:
        mask_global = mask_sliding = None
        alibi_bias = None
    else:
        with scope("attn"):  # the masks, once for every layer
            mask_global, mask_sliding, k_slot, q_slot = build_masks()
            if config.alibi:
                # additive float bias: slope_h * (k_pos - q_pos), 0 on
                # diagonal (start offsets cancel in the difference)
                slopes = alibi_slopes(Hq).reshape(Hkv, Hq // Hkv)
                if config.alibi_scale:  # falcon-rw: bias shares the scale
                    slopes = slopes * config.alibi_scale
                dist = (k_slot - q_slot).astype(jnp.float32)  # [B, T, S]
                alibi_bias = (
                    slopes[None, :, :, None, None] * dist[:, None, None]
                )  # [B, Hkv, G, T, S]
            else:
                alibi_bias = None
            mask_global = mask_global[:, None, None]  # [B,1,1,T,S]
            mask_sliding = mask_sliding[:, None, None]

    lora_scale = lora["scale"] if lora is not None else None
    tp_sharded = comm is not None and comm.axis_size > 1

    # The whole-stack operands of every per-layer weight that goes to a
    # kernel stay OUT of the scan's per-layer slices (`linear.stacks_out`):
    # the packed codes and, where the tree was prepared for serving
    # (`prepare_kernel_scales`), the scales' bits. The kernel reads its
    # blocks from the whole stack by layer index ([L, O, C] through
    # `linear`'s `layer`, the experts' [L, E, O, C] through the grouped
    # kernel's), where a slice handed to a Mosaic call is first copied
    # whole, every layer of every step (and every expert, hit or not). Who
    # keeps today's slices, each by what `forward` sees in its inputs:
    # adapters (the backward's dx kernel takes one layer's weight, and the
    # XLA expert formulations differentiate), projections that run per
    # shard under tensor parallelism, a weight the kernels' shape guard
    # refuses (the XLA dequant fuses its own slice), and fp8 codes, which
    # reach a kernel through a bitcast that would copy the whole stack
    # instead. The float16 scales stay sliced for whoever else reads them
    # (a tree nobody prepared, the XLA routes, a backward); a call that
    # reads the prepared bits leaves that slice dead.
    layers = params["layers"]
    stack_codes = {}
    if lora is None:
        from bigdl_tpu.ops.linear import grouped_route, stacks_out

        names = [n for n in _LINEAR_STACKS if n in layers
                 and not (tp_sharded and n in _TP_PARALLEL)
                 and grouped_route(layers[n]) is None]
        if config.is_moe and moe_grouped_why_not(layers, False) is None:
            names += [n for n in _EXPERT_STACKS if n in layers]
        layers, stack_codes = stacks_out(layers, names)
    moe_stacked = any(n in stack_codes for n in _EXPERT_STACKS)

    def layer_proj(x, p, lp, wname, bname=None, idx=None):
        b = p.get(bname) if bname else None
        pair = lp[wname] if lp is not None and wname in lp else None
        if tp_sharded and wname in _TP_PARALLEL:
            # under tensor parallelism the per-layer projections run
            # per shard (ops/linear.py: a K-sharded packed weight left
            # to GSPMD is gathered whole every call, and a Mosaic call
            # cannot be partitioned); the row-parallel pair reduces
            # through comm's all-reduce, exact or quantized. The
            # lm_head's vocab-shard product and MoE experts stay on
            # GSPMD's; the LoRA delta below reduces implicitly — rank-r
            # traffic is negligible next to the hidden-size epilogue
            y = _TP_PARALLEL[wname](x, p[wname], comm, b, compute_dtype)
            if pair is not None:
                y = y + _lora_delta(x, pair, lora_scale, compute_dtype)
        else:
            # the adapter delta rides INTO linear: eligible quantized
            # shapes fold it into the Pallas dequant-GEMM's writeback
            # (zero extra activation HBM round trips); every other path
            # applies the same lora_epilogue einsums as before
            lo = ((pair["a"], pair["b"], lora_scale)
                  if pair is not None else None)
            y = linear(x, p[wname], b, compute_dtype, lora=lo,
                       layer=idx if wname in stack_codes else None)
        return y

    # per-layer static sliding flags, as a traced vector for the scan body
    with scope("attn"):
        sliding_flags = jnp.asarray(
            [config.layer_is_sliding(l)
             for l in range(config.num_hidden_layers)], jnp.bool_)

    def body(carry, xs):
        hidden, c, idx = carry
        p, lp = xs if lora is not None else (xs, None)
        # the unsliced stacks go back in, with the index that finds this
        # layer in them
        p = stacks_in(p, stack_codes)
        proj = functools.partial(layer_proj, idx=idx)

        with scope("norm"):
            x = norm(hidden, p["attn_norm"], p.get("attn_norm_b"))
        with scope("attn.proj"):
            if "wqkv" in p:  # merged layout (merge_fused_params)
                QD, KD = Hq * D, Hkv * D
                qkv = proj(x, p, None, "wqkv", "bqkv")
                q, k, v = (qkv[..., :QD], qkv[..., QD:QD + KD],
                           qkv[..., QD + KD:])
                if lp is not None:  # lora stays keyed by the unmerged names
                    if "wq" in lp:
                        q = q + _lora_delta(x, lp["wq"], lora_scale, compute_dtype)
                    if "wk" in lp:
                        k = k + _lora_delta(x, lp["wk"], lora_scale, compute_dtype)
                    if "wv" in lp:
                        v = v + _lora_delta(x, lp["wv"], lora_scale, compute_dtype)
                q = q.reshape(B, T, Hq, D)
                k = k.reshape(B, T, Hkv, D)
                v = v.reshape(B, T, Hkv, D)
            else:
                q = proj(x, p, lp, "wq", "bq").reshape(B, T, Hq, D)
                k = proj(x, p, lp, "wk", "bk").reshape(B, T, Hkv, D)
                v = proj(x, p, lp, "wv", "bv").reshape(B, T, Hkv, D)
        with scope("attn.rope"):
            if config.qk_norm:
                q = rms_norm(q, p["q_norm"], eps, offset=config.rms_norm_offset)
                k = rms_norm(k, p["k_norm"], eps, offset=config.rms_norm_offset)
            if use_rope:
                if cos_local is not None:
                    is_sliding_l = sliding_flags[layer_offset + idx]
                    cos_l = jnp.where(is_sliding_l, cos_local, cos)
                    sin_l = jnp.where(is_sliding_l, sin_local, sin)
                else:
                    cos_l, sin_l = cos, sin
                q, k = apply_rotary_emb(q, k, cos_l, sin_l, config.rope_interleaved)
            if logn_col is not None:
                q = q * logn_col

        with scope("attn"):
            k_scale_att = v_scale_att = None
            if retention:
                # log-gates in float32 at full precision, like a router's
                # logits: 8 x H weights, and a gate sums over the context
                gate = jnp.einsum(
                    "bth,jh->btj", x.astype(jnp.float32),
                    p["w_g"].astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)
                attn, c = kvstate.attend(
                    c, idx, q, k, v, jax.nn.log_sigmoid(gate), ret_valid,
                    config.retention_eps, decode=mode == "decode")
                attn = attn.astype(compute_dtype)
            elif c is not None:
                c = kvcache.update_layer(c, idx, k, v)
                if use_flash and c.quantized:
                    # fp8 codes + scales go straight to the flash kernel,
                    # which dequantizes per block in-kernel — never a dense
                    # bf16 copy of the cache in HBM (kvcache.read_layer_raw)
                    k_att, v_att, k_scale_att, v_scale_att = \
                        kvcache.read_layer_raw(c, idx)
                elif not use_paged_kernel:
                    k_att, v_att = kvcache.read_layer(c, idx, compute_dtype)
            else:
                k_att = k.astype(compute_dtype)
                v_att = v.astype(compute_dtype)

            if retention:
                pass  # done above, on the state
            elif use_paged_kernel and mode == "block":
                from bigdl_tpu.ops.pallas import paged_block_attention

                attn = paged_block_attention(
                    q, c.k, c.v, c.block_tables, idx, c.pos, c.start,
                    k_scale=c.k_scale, v_scale=c.v_scale,
                    scale=config.attn_scale,
                    softcap=config.attn_logit_softcap, live=row_live)
            elif use_paged_kernel:
                from bigdl_tpu.ops.pallas import paged_decode_attention

                if config.sliding_window is None:
                    win_l = None
                else:  # traced: sliding layers alternate within the scan
                    win_l = jnp.where(
                        sliding_flags[layer_offset + idx],
                        config.sliding_window, 2 ** 30,
                    ).astype(jnp.int32)
                attn = paged_decode_attention(
                    q[:, 0], c.k, c.v, c.block_tables, idx, c.pos, c.start,
                    k_scale=c.k_scale, v_scale=c.v_scale,
                    scale=config.attn_scale,
                    softcap=config.attn_logit_softcap, window=win_l,
                    live=row_live,
                )[:, None]
            elif attention_override is not None and c is None:
                attn = attention_override(q, k_att, v_att, row_start)
            elif use_flash_train:
                from bigdl_tpu.ops.pallas import flash_attention_trainable

                attn = flash_attention_trainable(
                    q, k_att, v_att, row_start,
                    window=config.sliding_window, scale=config.attn_scale,
                )
            elif use_flash:
                from bigdl_tpu.ops.pallas import flash_attention

                attn = flash_attention(
                    q, k_att, v_att, start=row_start, q_offset=pos0,
                    window=config.sliding_window, softcap=config.attn_logit_softcap,
                    scale=config.attn_scale,
                    k_scale=k_scale_att, v_scale=v_scale_att,
                    block_causal=block_causal or None,
                )
            else:
                is_sliding = sliding_flags[layer_offset + idx]
                mask = jnp.where(is_sliding, mask_sliding, mask_global)
                if alibi_bias is not None:
                    mask = jnp.where(mask, alibi_bias, _NEG_INF)
                attn = attention(
                    q, k_att, v_att, mask,
                    scale=config.attn_scale, softcap=config.attn_logit_softcap,
                )
        with scope("attn.proj"):
            out = proj(attn.reshape(B, T, Hq * D), p, lp, "wo", "bo")
        with scope("norm"):
            if config.post_attn_norm:
                out = norm(out, p["post_attn_norm"])
            rs = config.residual_scale
            if config.parallel_residual:
                # gptneox: attention and MLP both read the SAME layer input;
                # residual adds both at once
                mlp_in = norm(hidden, p["mlp_norm"], p.get("mlp_norm_b"))
            else:
                hidden = hidden + (out * rs if rs else out)
                mlp_in = norm(hidden, p["mlp_norm"], p.get("mlp_norm_b"))

        with scope("ffn"):
            x = mlp_in
            routed = None
            if config.is_moe:
                down, routed = _moe_block(
                    config, x, p, compute_dtype,
                    differentiable=lora is not None,
                    layer=idx if moe_stacked else None)
            elif "w_gateup" in p:  # merged layout (merge_fused_params)
                gu = proj(x, p, None, "w_gateup", "b_gateup")
                I2 = gu.shape[-1] // 2
                gate, up = gu[..., :I2], gu[..., I2:]
                if lp is not None:
                    if "w_gate" in lp:
                        gate = gate + _lora_delta(x, lp["w_gate"], lora_scale,
                                                  compute_dtype)
                    if "w_up" in lp:
                        up = up + _lora_delta(x, lp["w_up"], lora_scale,
                                              compute_dtype)
                down = proj(_act(config.hidden_act, gate) * up, p, lp, "w_down", "b_down")
            elif config.gated_mlp:
                gate = proj(x, p, lp, "w_gate", "b_gate")
                up = proj(x, p, lp, "w_up", "b_up")
                down = proj(_act(config.hidden_act, gate) * up, p, lp, "w_down", "b_down")
            else:
                up = proj(x, p, lp, "w_up", "b_up")
                down = proj(_act(config.hidden_act, up), p, lp, "w_down", "b_down")
        with scope("norm"):  # the residual add fuses with the next norm
            if config.post_attn_norm:
                down = norm(down, p["post_mlp_norm"])
            if config.parallel_residual:
                hidden = hidden + out + down
            else:
                hidden = hidden + (down * rs if rs else down)

        obs_q = None
        if collect_obs:  # the window's queries, for SnapKV
            with scope("attn"):
                obs_q = q[:, T - collect_obs:]
        ys = (obs_q, routed if moe_routing else None)
        with scope("engine"):  # the loop's own count
            return (hidden, c, idx + 1), ys

    xs = (layers, lora["layers"]) if lora is not None else layers
    scan_body = body
    if remat:
        # recompute the layer in the backward instead of saving its
        # activations; prevent_cse is the documented setting for remat
        # inside scan (jax.checkpoint docs)
        scan_body = jax.checkpoint(body, prevent_cse=False)
    (h, cache, _), (obs, routing) = jax.lax.scan(
        scan_body, (h, cache, jnp.zeros((), jnp.int32)), xs
    )

    if return_hidden:
        logits = h
    else:
        with scope("lm_head"):
            if last_logits_only:
                h = h[:, -1:]
            logits = lm_head_logits(config, params, h, compute_dtype)
    with scope("engine"):
        if retention and cache is not None:
            cache = kvstate.advance(cache, T)
        elif cache is not None:
            cache = kvcache.advance(cache, T)
    out = (logits, cache) + ((obs,) if collect_obs else ())
    return out + ((routing,) if moe_routing else ())
