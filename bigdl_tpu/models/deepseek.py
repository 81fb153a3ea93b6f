"""DeepSeek-V2/V3 and MiniCPM3 — Multi-head Latent Attention (MLA)
decoders with DeepSeek-MoE.

TPU-native counterpart of the reference's minicpm3 support
(/root/reference/python/llm/src/ipex_llm/transformers/models/minicpm3.py,
dispatch at convert.py:1010-1025, 1899 — the same MLA attention DeepSeek
V2/V3 use; HF modeling_deepseek_v2/v3 are the behavioral spec).

MLA caches a per-token LATENT instead of full K/V: c_kv [r] (the
compressed kv, r = kv_lora_rank) plus one shared rope key k_pe [dr].
The decode math here is the ABSORBED formulation — the up-projections
W_uk/W_uv fold into the query/output sides, so attention runs directly
against the latent cache:

    q_eff[h]  = W_uk[h]^T q_nope[h]            # [r] per head
    score     = (q_eff · c_kv[s] + q_pe · k_pe[s]) * scale
    ctx[h]    = Σ_s softmax(score)[s] c_kv[s]  # [r]
    out[h]    = W_uv[h] ctx[h]                 # [dv]

— algebraically identical to expanding K/V per head (the HF formulation)
but the cache stays [S, r + dr] per layer: ~576 floats/token for
DeepSeek-V2 vs ~8k for an equivalent MHA, and decode reads latents once
for all heads. Rope on the pe channels is DeepSeek's pair-interleaved
(complex) convention = our rope_interleaved path.

DeepSeek-MoE: softmax (v2) or sigmoid (v3) router scores,
group-limited expert selection (`group_limited_greedy` max-per-group /
`noaux_tc` top2-sum with e_score_correction_bias), routed_scaling_factor
on the combine weights, ungated shared experts, and the first
`first_k_dense_replace` layers dense — realized as two homogeneous scan
segments (dense-MLP layers, then MoE layers), like mllama's segmented
stack. Expert compute reuses the llama family's dense/ragged dispatch.

MiniCPM3 = MLA + dense MLP + the minicpm residual/embedding/logit
scalings (config builder _hf_minicpm3).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from bigdl_tpu.kvcache import _scatter_rows
from bigdl_tpu.models import llama
from bigdl_tpu.models.config import ModelConfig
from bigdl_tpu.ops import linear, rms_norm
from bigdl_tpu.ops.rope import make_inv_freq_scaled, rope_cos_sin

Params = dict[str, Any]

_NEG_INF = -1e30


def _dims(config: ModelConfig):
    H = config.num_attention_heads
    dn = config.qk_nope_head_dim or 128
    dr = config.qk_rope_head_dim or 64
    dv = config.v_head_dim or 128
    r = config.kv_lora_rank or 512
    return H, dn, dr, dv, r


def mla_softmax_scale(config: ModelConfig) -> float:
    """(dn+dr)^-0.5, times the yarn temperature mscale^2 when the checkpoint
    ships `rope_scaling.mscale_all_dim` (all real DeepSeek-V2/V3 and MiniCPM3
    configs do). Official DeepSeek modeling and HF DeepseekV3Attention
    (modeling_deepseek_v3.py:373-377, transformers 4.57) fold
    yarn_get_mscale(factor, mscale_all_dim)^2 into the softmax scale; the
    rope-level attention_factor on cos/sin is the mscale/mscale_all_dim
    ratio (1.0 for these checkpoints), so without this term the attention
    temperature would be dropped entirely (~1.6-1.9x under-scaled scores).
    Note transformers 4.57's *integrated* DeepseekV2Attention omits the
    term — a known fidelity gap vs the official remote code; we follow the
    official checkpoints (and HF V3)."""
    from bigdl_tpu.ops.rope import get_mscale

    _, dn, dr, _, _ = _dims(config)
    scale = (dn + dr) ** -0.5
    rs = config.rope_scaling_dict
    if rs and rs.get("mscale_all_dim"):
        m = get_mscale(rs.get("factor", 1.0), rs["mscale_all_dim"])
        scale = scale * m * m
    return scale


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class MLACache:
    """Latent KV cache: compressed kv + shared rope key per token."""

    ckv: jax.Array  # [L, B, S, r]
    kpe: jax.Array  # [L, B, S, dr]
    pos: jax.Array  # scalar or [B]
    start: jax.Array  # [B]

    @property
    def max_len(self) -> int:
        return self.ckv.shape[2]

    def next_positions(self, t: int) -> jax.Array:
        step = jnp.arange(t, dtype=jnp.int32)[None, :]
        pos = self.pos[:, None] if self.pos.ndim == 1 else self.pos
        return jnp.maximum(pos + step - self.start[:, None], 0)


def init_cache(
    config: ModelConfig,
    batch: int,
    cache_len: int,
    quantize_kv: bool = False,  # latent is already ~14x smaller than MHA KV
    dtype=jnp.bfloat16,
) -> MLACache:
    _, _, dr, _, r = _dims(config)
    L = config.num_hidden_layers
    return MLACache(
        ckv=jnp.zeros((L, batch, cache_len, r), dtype),
        kpe=jnp.zeros((L, batch, cache_len, dr), dtype),
        pos=jnp.zeros((), jnp.int32),
        start=jnp.zeros((batch,), jnp.int32),
    )


# the serving engine's generic dataclass insert/pool path supports this
# family's cache (flat [L, B, S, ...] array fields + real pos/start
# fields) — see serving/engine.py; rwkv/yuan/mllama caches need
# dedicated handling and must NOT set this
SERVABLE_CACHE = True


def _layer_is_moe(config: ModelConfig, idx: int) -> bool:
    return config.is_moe and idx >= config.first_k_dense_replace


def num_dense_layers(config: ModelConfig) -> int:
    if not config.is_moe:
        return config.num_hidden_layers
    return min(config.first_k_dense_replace, config.num_hidden_layers)


def init_params(
    config: ModelConfig,
    key: jax.Array,
    dtype=jnp.bfloat16,
    scale: float = 0.02,
) -> Params:
    """Random init (tests/benchmarks run without checkpoints)."""
    H, dn, dr, dv, r = _dims(config)
    hid = config.hidden_size
    V, I = config.vocab_size, config.intermediate_size
    rq = config.q_lora_rank
    keys = iter(jax.random.split(key, 48))

    def w(shape):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(dtype)

    def attn_block(n):
        out = {
            "attn_norm": jnp.ones((n, hid), dtype),
            "mlp_norm": jnp.ones((n, hid), dtype),
            "w_dkv": w((n, r + dr, hid)),
            "kv_norm": jnp.ones((n, r), dtype),
            "w_uk": w((n, H, dn, r)),
            "w_uv": w((n, H, dv, r)),
            "wo": w((n, hid, H * dv)),
        }
        if rq:
            out["w_dq"] = w((n, rq, hid))
            out["q_norm"] = jnp.ones((n, rq), dtype)
            out["w_uq"] = w((n, H * (dn + dr), rq))
        else:
            out["wq"] = w((n, H * (dn + dr), hid))
        return out

    K = num_dense_layers(config)
    layers = attn_block(K)
    layers["w_gate"] = w((K, I, hid))
    layers["w_up"] = w((K, I, hid))
    layers["w_down"] = w((K, hid, I))

    params: Params = {
        "embed": w((V, hid)),
        "layers": layers,
        "final_norm": jnp.ones((hid,), dtype),
    }
    M = config.num_hidden_layers - K
    if M:
        E = config.num_experts
        Im = config.moe_intermediate_size or I
        moe = attn_block(M)
        moe["router"] = w((M, E, hid))
        if (config.topk_method or "") == "noaux_tc":
            moe["e_bias"] = jnp.zeros((M, E), jnp.float32)
        moe["w_gate_e"] = w((M, E, Im, hid))
        moe["w_up_e"] = w((M, E, Im, hid))
        moe["w_down_e"] = w((M, E, hid, Im))
        if config.n_shared_experts:
            S = config.n_shared_experts * Im
            moe["w_gate_s"] = w((M, S, hid))
            moe["w_up_s"] = w((M, S, hid))
            moe["w_down_s"] = w((M, hid, S))
        params["moe_layers"] = moe
    if not config.tie_word_embeddings:
        params["lm_head"] = w((V, hid))
    return params


_QUANT_TARGETS = ("wq", "w_dq", "w_uq", "w_dkv", "w_uk", "w_uv", "wo",
                  "w_gate", "w_up", "w_down",
                  "w_gate_e", "w_up_e", "w_down_e",
                  "w_gate_s", "w_up_s", "w_down_s")

# per-head absorbed factors that must stay dense under quantization —
# the single source of truth for BOTH the random-init path below and
# the checkpoint path (convert/hf.py leaves them out of its
# _QUANT_TARGETS include-list for the same reason)
MLA_DENSE_FACTORS = ("w_uk", "w_uv")


def quantize_params(params: Params, qtype: str, lm_head_qtype: Optional[str] = None) -> Params:
    from bigdl_tpu.quant import QTensor, quantize, quantize_or_dense
    from bigdl_tpu.quant.qtypes import resolve_qtype, split_mixed_qtype

    qtype, head_default = split_mixed_qtype(qtype)
    lm_head_qtype = lm_head_qtype or head_default
    spec = resolve_qtype(qtype)
    if spec.is_dense:
        return params
    out = dict(params)
    for group in ("layers", "moe_layers"):
        if group not in params:
            continue
        g = dict(params[group])
        for name in _QUANT_TARGETS:
            wv = g.get(name)
            if wv is None or isinstance(wv, QTensor):
                continue
            if name in MLA_DENSE_FACTORS:
                continue  # 4-D per-head factors stay dense (tiny, f32 math)
            g[name] = quantize_or_dense(wv, spec.name, name)
        out[group] = g
    if "lm_head" in params and not isinstance(params["lm_head"], QTensor):
        lm_spec = resolve_qtype(lm_head_qtype) if lm_head_qtype else spec
        if not lm_spec.is_dense:
            out["lm_head"] = quantize_or_dense(
                params["lm_head"], lm_spec.name, "lm_head")
    return out


def _router(config: ModelConfig, xc, p):
    """DeepSeek routing: (topv [N,k] f32, topi [N,k] i32) over flattened
    tokens. Mirrors DeepseekV2MoEGate / DeepseekV3TopkRouter exactly."""
    E, k = config.num_experts, config.num_experts_per_tok
    logits = jnp.einsum(
        "nh,eh->ne", xc.astype(jnp.float32),
        p["router"].astype(jnp.float32),
    )
    if config.scoring_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)

    method = config.topk_method or "greedy"
    if method == "greedy":
        topv, topi = jax.lax.top_k(scores, k)
    else:
        G = config.n_group
        per = E // G
        grouped = scores.reshape(-1, G, per)
        if method == "noaux_tc":
            biased = grouped + p["e_bias"].reshape(G, per)[None]
            group_scores = jnp.sum(jax.lax.top_k(biased, 2)[0], axis=-1)
            choice = biased.reshape(-1, E)
        else:  # group_limited_greedy
            group_scores = jnp.max(grouped, axis=-1)
            choice = scores
        gsel = jax.lax.top_k(group_scores, config.topk_group)[1]
        gmask = jnp.zeros((scores.shape[0], G), jnp.float32)
        gmask = gmask.at[jnp.arange(scores.shape[0])[:, None], gsel].set(1.0)
        emask = jnp.repeat(gmask, per, axis=-1)
        masked = jnp.where(emask > 0, choice.reshape(-1, E), 0.0)
        _, topi = jax.lax.top_k(masked, k)
        # weights come from the UNBIASED scores (v3: bias selects only)
        topv = jnp.take_along_axis(scores, topi, axis=-1)
    # norm_topk_prob: only the v3 router honors it (HF DeepseekV2MoEGate
    # ignores the flag entirely — our oracle; the official v2 remote code
    # normalizes INSTEAD of scaling, a known upstream divergence)
    if config.norm_topk_prob and method == "noaux_tc":
        topv = topv / (jnp.sum(topv, axis=-1, keepdims=True) + 1e-20)
    return topv * config.routed_scaling_factor, topi


def _moe_mlp(config: ModelConfig, x, p, compute_dtype):
    """Routed experts (llama's `_moe_dispatch` over our router: grouped
    kernel on packed stacks at inference, else dense / ragged) + ungated
    shared experts (DeepseekV2MoE.forward)."""
    B, T, hid = x.shape
    xc = x.astype(compute_dtype)
    topv, topi = _router(config, xc.reshape(-1, hid), p)
    topv = topv.reshape(B, T, -1)
    topi = topi.reshape(B, T, -1)

    rcfg = config
    if (config.topk_method or "greedy") != "greedy" and config.n_group:
        # group-limited routing concentrates every token's k experts
        # into topk_group of n_group groups, so per-expert load can
        # exceed the uniform-load capacity by G/topk_group — scale
        # the ragged formulation's capacity factor accordingly or hot
        # experts silently drop tokens (GShard overflow) where HF
        # computes the full sum. The grouped kernel (packed stacks at
        # inference) drops nothing and needs no capacity.
        rcfg = dataclasses.replace(
            config,
            moe_capacity_factor=config.moe_capacity_factor
            * config.n_group / max(config.topk_group or 1, 1),
        )
    out = llama._moe_dispatch(config, xc, p, compute_dtype, topv, topi,
                              ragged_config=rcfg)

    if config.n_shared_experts:
        g = linear(xc, p["w_gate_s"], None, compute_dtype)
        u = linear(xc, p["w_up_s"], None, compute_dtype)
        out = out + linear(jax.nn.silu(g) * u, p["w_down_s"], None, compute_dtype)
    return out


def forward(
    config: ModelConfig,
    params: Params,
    tokens: jax.Array,  # [B, T] int32
    cache: Optional[MLACache],
    mode: str = "prefill",
    compute_dtype=jnp.bfloat16,
    last_logits_only: bool = False,
) -> tuple[jax.Array, Optional[MLACache]]:
    assert mode in ("prefill", "decode")
    B, T = tokens.shape
    H, dn, dr, dv, r = _dims(config)
    eps = config.rms_norm_eps
    scale = mla_softmax_scale(config)

    fresh = cache is None
    if fresh:
        cache = init_cache(config, B, T, dtype=jnp.float32)

    pos_col = cache.pos[:, None] if cache.pos.ndim == 1 else cache.pos
    slots = pos_col + jnp.arange(T)[None, :]
    positions = cache.next_positions(T)

    h = llama.embed_tokens(config, params, tokens, compute_dtype)

    inv_freq, att_scale = make_inv_freq_scaled(
        dr, config.rope_theta, config.rope_scaling_dict,
        seq_len=cache.max_len,
    )
    cos, sin = rope_cos_sin(positions, inv_freq, interleaved=True,
                            scale=att_scale)

    S = cache.max_len
    sj = jnp.arange(S)
    mask = (sj[None, None, :] <= slots[..., None]) & (
        sj[None, None, :] >= cache.start[:, None, None]
    )  # [B, T, S]
    mask = mask[:, None]  # [B, 1, T, S]

    per_row = cache.pos.ndim == 1

    def attn(x, p, ckv_l, kpe_l):
        """MLA with absorbed up-projections over the latent cache.
        Returns (attn_out [B,T,hid], new ckv_l, new kpe_l)."""
        from bigdl_tpu.ops.rope import apply_rotary_emb

        if "w_dq" in p:
            qa = linear(x, p["w_dq"], None, compute_dtype)
            q = linear(rms_norm(qa, p["q_norm"], eps), p["w_uq"], None,
                       compute_dtype)
        else:
            q = linear(x, p["wq"], None, compute_dtype)
        q = q.reshape(B, T, H, dn + dr)
        q_nope, q_pe = q[..., :dn], q[..., dn:]

        ckv_pe = linear(x, p["w_dkv"], None, compute_dtype)  # [B,T,r+dr]
        ckv = rms_norm(ckv_pe[..., :r], p["kv_norm"], eps)
        kpe = ckv_pe[..., None, r:]  # [B,T,1,dr] single shared rope head

        q_pe, kpe = apply_rotary_emb(q_pe, kpe, cos, sin, True)
        kpe = kpe[..., 0, :]  # [B,T,dr]

        # write latents into the cache at this layer's rows
        if per_row:
            ckv_l = _scatter_rows(ckv_l[None], jnp.zeros((), jnp.int32),
                                  cache.pos, ckv)[0]
            kpe_l = _scatter_rows(kpe_l[None], jnp.zeros((), jnp.int32),
                                  cache.pos, kpe)[0]
        else:
            ckv_l = jax.lax.dynamic_update_slice(
                ckv_l, ckv.astype(ckv_l.dtype), (0, cache.pos, 0)
            )
            kpe_l = jax.lax.dynamic_update_slice(
                kpe_l, kpe.astype(kpe_l.dtype), (0, cache.pos, 0)
            )

        CKV = ckv_l.astype(compute_dtype)  # [B,S,r]
        KPE = kpe_l.astype(compute_dtype)  # [B,S,dr]

        # absorbed scores: q_eff = W_uk^T q_nope, dotted with the latent
        q_eff = jnp.einsum("bthd,hdr->bthr", q_nope,
                           p["w_uk"].astype(compute_dtype))
        s_nope = jnp.einsum("bthr,bsr->bhts", q_eff, CKV,
                            preferred_element_type=jnp.float32)
        s_pe = jnp.einsum("bthd,bsd->bhts", q_pe, KPE,
                          preferred_element_type=jnp.float32)
        scores = (s_nope + s_pe).astype(jnp.float32) * scale
        scores = jnp.where(mask, scores, _NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)

        ctx = jnp.einsum("bhts,bsr->bthr", probs.astype(compute_dtype), CKV)
        out = jnp.einsum("bthr,hdr->bthd", ctx,
                         p["w_uv"].astype(compute_dtype))
        return (
            linear(out.reshape(B, T, H * dv), p["wo"], None, compute_dtype),
            ckv_l, kpe_l,
        )

    rs = config.residual_scale

    def make_body(moe: bool):
        def body(hidden, xs):
            p, ckv_l, kpe_l = xs
            x = rms_norm(hidden, p["attn_norm"], eps)
            out, ckv_l, kpe_l = attn(x, p, ckv_l, kpe_l)
            hidden = hidden + (out * rs if rs else out)
            x = rms_norm(hidden, p["mlp_norm"], eps)
            if moe:
                d = _moe_mlp(config, x, p, compute_dtype)
            else:
                g = linear(x, p["w_gate"], None, compute_dtype)
                u = linear(x, p["w_up"], None, compute_dtype)
                d = linear(jax.nn.silu(g) * u, p["w_down"], None, compute_dtype)
            hidden = hidden + (d * rs if rs else d)
            return hidden, (ckv_l, kpe_l)

        return body

    K = num_dense_layers(config)
    new_ckv, new_kpe = [], []
    if K:
        h, (c0, k0) = jax.lax.scan(
            make_body(False), h,
            (params["layers"], cache.ckv[:K], cache.kpe[:K]),
        )
        new_ckv.append(c0)
        new_kpe.append(k0)
    if config.num_hidden_layers - K:
        h, (c1, k1) = jax.lax.scan(
            make_body(True), h,
            (params["moe_layers"], cache.ckv[K:], cache.kpe[K:]),
        )
        new_ckv.append(c1)
        new_kpe.append(k1)

    if last_logits_only:
        h = h[:, -1:]
    logits = llama.lm_head_logits(config, params, h, compute_dtype)

    if fresh:
        return logits, None
    cache = dataclasses.replace(
        cache,
        ckv=jnp.concatenate(new_ckv, axis=0),
        kpe=jnp.concatenate(new_kpe, axis=0),
        pos=cache.pos + T,
    )
    return logits, cache
