"""DeepSeek-V2/V3, GLM-4.7-Flash (`glm4_moe_lite`) and MiniCPM3 —
Multi-head Latent Attention (MLA) decoders with DeepSeek-MoE.

TPU-native counterpart of the reference's minicpm3 support
(/root/reference/python/llm/src/ipex_llm/transformers/models/minicpm3.py,
dispatch at convert.py:1010-1025, 1899 — the same MLA attention DeepSeek
V2/V3 use; HF modeling_deepseek_v2/v3 are the behavioral spec).

MLA caches a per-token LATENT instead of full K/V: c_kv [r] (the
compressed kv, r = kv_lora_rank) plus one shared rope key k_pe [dr].
Two forms of the same attention, by what is being done:

    q_eff[h]  = W_uk[h]^T q_nope[h]            # [r] per head
    score     = (q_eff · c_kv[s] + q_pe · k_pe[s]) * scale
    ctx[h]    = Σ_s softmax(score)[s] c_kv[s]  # [r]
    out[h]    = W_uv[h] ctx[h]                 # [dv]

is the ABSORBED form (the up-projections W_uk/W_uv fold into the
query/output sides, attention runs directly against the latents), and
expanding K = [W_uk c_kv ; k_pe] and V = W_uv c_kv per head is the
EXPANDED one (the HF formulation); they are algebraically identical. The
cache stays [S, r + dr] per layer either way: ~576 values a token for
DeepSeek-V2 or GLM-4.7-Flash against ~10k for the same heads kept as keys
and values, and a decode step reads the latents once for all heads.

Where each runs. A dense `MLACache` [L, B, S, r] (`TpuModel.generate`, a
dense engine pool, a whole sequence with no cache) is absorbed throughout,
in `jnp`. `InferenceEngine(paged=True)` keeps LATENT PAGES
(`kvpaged.PagedLatentCache`, made by `init_paged_cache`; booked, shared,
parked and restored by `serving/pages.PageTable` as KV pages are): a
decode step is absorbed, through the Pallas kernel
`ops/pallas/paged_attention.paged_latent_decode_attention` over the pages
in place; a prefill is expanded from the row's latents and blocked
(`ops/pallas/flash_attention.py` on the chip), since at T of thousands
the absorbed form pays r + dr multiply-adds a score where the expanded
one pays dn + dr (docs/kernels.md#paged-latent). Rope on the pe channels
is DeepSeek's pair-interleaved (complex) convention = our
rope_interleaved path.

DeepSeek-MoE: softmax (v2) or sigmoid (v3) router scores,
group-limited expert selection (`group_limited_greedy` max-per-group /
`noaux_tc` top2-sum with e_score_correction_bias), routed_scaling_factor
on the combine weights, ungated shared experts, and the first
`first_k_dense_replace` layers dense — realized as two homogeneous scan
segments (dense-MLP layers, then MoE layers), like mllama's segmented
stack. Expert compute is the llama family's `_moe_dispatch` (the grouped
kernel on packed stacks, else dense / ragged); packed codes reach their
kernels by layer index, out of the scans' slices, and `moe_routing=`
returns the expert layers' top-k ids, as in `llama.forward`.

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
from bigdl_tpu.obs.scopes import scope
from bigdl_tpu.ops import linear, rms_norm
from bigdl_tpu.ops.linear import stacks_in
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


def init_paged_cache(config: ModelConfig, n_pages: int, page_size: int,
                     batch: int, max_pages_per_row: int):
    """The family's PAGED cache for `InferenceEngine(paged=True)`: latent
    pages (kvpaged.PagedLatentCache), one row of r + dr values a token and
    layer."""
    from bigdl_tpu.kvpaged import init_latent

    _, _, dr, _, r = _dims(config)
    return init_latent(config.num_hidden_layers, n_pages, page_size, r, dr,
                       batch, max_pages_per_row)


def latent_token_nbytes(config: ModelConfig) -> int:
    """One token's latents over all layers as the algorithm needs them
    (r + dr bf16 values a layer; the pool's rows are padded past that)."""
    _, _, dr, _, r = _dims(config)
    return config.num_hidden_layers * (r + dr) * 2


# the serving engine's generic dataclass insert/pool path supports this
# family's DENSE cache (flat [L, B, S, ...] array fields + real pos/start
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


def _router(config: ModelConfig, xc, p, norm_eps: float = 1e-20):
    """DeepSeek routing: (topv [N,k] f32, topi [N,k] i32) over flattened
    tokens (`norm_eps`: what a family's reference adds to the chosen
    scores' sum before dividing; LFM2-MoE's has 1e-6). Mirrors
    DeepseekV2MoEGate / DeepseekV3TopkRouter (and
    Glm4MoeTopkRouter, which is V3's) exactly. The logits are a float32
    product at full precision, as llama's router's are: E x H weights cost
    nothing, and sigmoid scores of 64 experts tie far more often than a
    softmax over 8."""
    E, k = config.num_experts, config.num_experts_per_tok
    logits = jnp.einsum(
        "nh,eh->ne", xc.astype(jnp.float32),
        p["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    if config.scoring_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)

    method = config.topk_method or "greedy"
    G = config.n_group or 1
    if method == "greedy":
        topv, topi = jax.lax.top_k(scores, k)
    elif G == 1:
        # one group holds every expert: nothing is limited, and the bias
        # (noaux_tc) still moves the choice and not the weights. A family
        # without a selection bias (laguna) has no `e_bias` leaf
        biased = method == "noaux_tc" and "e_bias" in p
        choice = scores + p["e_bias"][None] if biased else scores
        _, topi = jax.lax.top_k(choice, k)
        topv = jnp.take_along_axis(scores, topi, axis=-1)
    else:
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
        topv = topv / (jnp.sum(topv, axis=-1, keepdims=True) + norm_eps)
    return topv * config.routed_scaling_factor, topi


def _moe_mlp(config: ModelConfig, x, p, compute_dtype, proj, layer=None):
    """Routed experts (llama's `_moe_dispatch` over our router: grouped
    kernel on packed stacks at inference, else dense / ragged) + ungated
    shared experts (DeepseekV2MoE.forward) through `proj`. Returns
    (out [B,T,hid], topi [B,T,k])."""
    B, T, hid = x.shape
    xc = x.astype(compute_dtype)
    with scope("moe.router"):
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
                              ragged_config=rcfg, layer=layer)

    if config.n_shared_experts:
        with scope("moe.shared"):
            g = proj(xc, p, "w_gate_s")
            u = proj(xc, p, "w_up_s")
            out = out + proj(jax.nn.silu(g) * u, p, "w_down_s")
    return out, topi


# the per-layer weights that go through `linear`: their packed codes stay
# out of the layer scans' slices, as llama.forward keeps its own
_LINEAR_STACKS = ("wq", "w_dq", "w_uq", "w_dkv", "wo",
                  "w_gate", "w_up", "w_down",
                  "w_gate_s", "w_up_s", "w_down_s")

#: query rows of one block of the XLA expanded-prefill form
_PREFILL_BLOCK_Q = 512


def _keep_codes_out(group: Params) -> tuple[Params, dict]:
    """(`group` with the packed codes of every weight that goes to a
    kernel taken out, those codes by name): the scan slices what is left,
    and the body hands the whole stack back with the layer's index
    (`linear(layer=)`, `_moe_dispatch(layer=)`). A slice handed to a Mosaic
    call is first copied whole: 354 MB of expert stacks a layer of
    GLM-4.7-Flash, hit or not. The rule is llama.forward's: weights the
    kernels' shape guard refuses and fp8 codes keep their slices."""
    from bigdl_tpu.ops.linear import grouped_route, stacks_out

    names = [n for n in _LINEAR_STACKS
             if n in group and grouped_route(group[n]) is None]
    if "w_up_e" in group and llama.moe_grouped_why_not(group, False) is None:
        names += [n for n in llama._EXPERT_STACKS if n in group]
    return stacks_out(group, names)


def _expanded_attention(q, k, v, q_slots, start, scale, compute_dtype,
                        flash_offset=None):
    """Causal attention of q [B,T,H,Dq] over EXPANDED keys and values
    k [B,S,H,Dq], v [B,S,H,Dv] (query t sits at cache slot q_slots[b,t] and
    sees slots start[b] .. q_slots[b,t]), blocked so that no [H,T,S] array
    of float32 scores exists: the flash kernel when `flash_offset` (the
    scalar slot of q position 0) is given, else a map over blocks of
    `_PREFILL_BLOCK_Q` query rows."""
    B, T, H, Dq = q.shape
    Dv = v.shape[-1]
    if flash_offset is not None:
        from bigdl_tpu.ops.pallas import flash_attention

        D = max(Dq, Dv)  # the kernel takes one head size: zero lanes add
        # nothing to a score and are sliced off the output

        def widen(a):
            return jnp.pad(a, ((0, 0),) * 3 + ((0, D - a.shape[-1]),))

        out = flash_attention(widen(q), widen(k), widen(v), start=start,
                              q_offset=flash_offset, scale=scale)
        return out[..., :Dv]
    S = k.shape[1]
    bq = min(_PREFILL_BLOCK_Q, T)
    nb = -(-T // bq)
    pad = nb * bq - T
    qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
        B, nb, bq, H, Dq).transpose(1, 0, 2, 3, 4)
    sb = jnp.pad(q_slots, ((0, 0), (0, pad))).reshape(
        B, nb, bq).transpose(1, 0, 2)
    sj = jnp.arange(S)

    def block(xs):
        qi, si = xs
        sc = jnp.einsum("bthd,bshd->bhts", qi, k,
                        preferred_element_type=jnp.float32) * scale
        ok = (sj[None, None, :] <= si[..., None]) & (
            sj[None, None, :] >= start[:, None, None])
        probs = jax.nn.softmax(jnp.where(ok[:, None], sc, _NEG_INF), axis=-1)
        return jnp.einsum("bhts,bshd->bthd", probs.astype(compute_dtype), v)

    out = jax.lax.map(block, (qb, sb))  # [nb, B, bq, H, Dv]
    return out.transpose(1, 0, 2, 3, 4).reshape(B, nb * bq, H, Dv)[:, :T]


def forward(
    config: ModelConfig,
    params: Params,
    tokens: jax.Array,  # [B, T] int32
    cache,  # MLACache | kvpaged.PagedLatentCache | None
    mode: str = "prefill",
    compute_dtype=jnp.bfloat16,
    last_logits_only: bool = False,
    moe_routing: bool = False,  # static: also return every expert layer's
    # top-k expert ids [L_moe, B, T, k] int32 (the serving counterpart of
    # HF's output_router_logits; serving/engine.py asks for it)
):
    """Returns (logits, cache), and the routing third when asked.

    Three caches, two attention forms. A dense `MLACache` (or none: a
    whole sequence from nothing) takes the ABSORBED form in `jnp` over the
    whole cache. A `PagedLatentCache` (the serving engine's) takes the
    absorbed form for a decode step, through the Pallas kernel over pages
    in place where the kernels are in use, and the EXPANDED form for a
    prefill: K and V per head up-projected from the row's latents (the
    prompt's own and, on a prefix hit, the hit pages'), blocked attention,
    and only the latents written to pages. At T of thousands the absorbed
    form pays r + dr = 576 multiply-adds a score and r = 512 a value where
    the expanded one pays 256 and 256."""
    from bigdl_tpu.kvpaged import (PagedLatentCache, live_rows,
                                   read_latent_layer, update_latent_layer)
    from bigdl_tpu.ops import routes
    from bigdl_tpu.ops.pallas import use_pallas, why_not_pallas
    from bigdl_tpu.ops.rope import apply_rotary_emb

    assert mode in ("prefill", "decode")
    B, T = tokens.shape
    H, dn, dr, dv, r = _dims(config)
    eps = config.rms_norm_eps
    scale = mla_softmax_scale(config)

    fresh = cache is None
    if fresh:
        with scope("engine"):
            cache = init_cache(config, B, T, dtype=jnp.float32)
    paged = isinstance(cache, PagedLatentCache)

    with scope("engine"):  # positions, and the embedding
        pos_col = cache.pos[:, None] if cache.pos.ndim == 1 else cache.pos
        slots = pos_col + jnp.arange(T)[None, :]
        positions = cache.next_positions(T)

        h = llama.embed_tokens(config, params, tokens, compute_dtype)

    with scope("attn.rope"):  # the tables, once for every layer
        inv_freq, att_scale = make_inv_freq_scaled(
            dr, config.rope_theta, config.rope_scaling_dict,
            seq_len=cache.max_len,
        )
        cos, sin = rope_cos_sin(positions, inv_freq, interleaved=True,
                                scale=att_scale)

    detail = f"mode={mode} B{B} T{T}"
    use_kernel = paged and mode == "decode" and T == 1 and use_pallas()
    expand = paged and T > 1
    use_flash = expand and B == 1 and use_pallas()
    if use_kernel:
        from bigdl_tpu.ops.pallas.paged_attention import latent_group_pages

        routes.note(
            "attention", "pallas:paged_latent", detail + " grid of %d rows, "
            "groups of %d pages" % (B, latent_group_pages(
                cache.lat, H, cache.block_tables.shape[1])))
        with scope("attn"):
            row_live = live_rows(cache)  # the table does not change in here
    elif use_flash:
        routes.note("attention", "pallas:flash",
                    detail + " expanded from latents")
    else:
        routes.note("attention", "xla", detail + (
            f" expanded from latents ({why_not_pallas() or 'B > 1'})"
            if expand else " absorbed over the latents"))
    if not (use_kernel or expand):
        S = cache.max_len
        with scope("attn"):  # the mask, once for every layer
            sj = jnp.arange(S)
            mask = (sj[None, None, :] <= slots[..., None]) & (
                sj[None, None, :] >= cache.start[:, None, None]
            )  # [B, T, S]
            mask = mask[:, None]  # [B, 1, T, S]

    per_row = cache.pos.ndim == 1

    def absorbed(q_eff, q_pe, CKV, KPE):
        """softmax((q_eff . ckv + q_pe . kpe) * scale) ckv over a dense
        view [B,S,r] / [B,S,dr] of the latents: context [B,T,H,r]."""
        s_nope = jnp.einsum("bthr,bsr->bhts", q_eff, CKV,
                            preferred_element_type=jnp.float32)
        s_pe = jnp.einsum("bthd,bsd->bhts", q_pe, KPE,
                          preferred_element_type=jnp.float32)
        scores = (s_nope + s_pe).astype(jnp.float32) * scale
        scores = jnp.where(mask, scores, _NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhts,bsr->bthr", probs.astype(compute_dtype), CKV)

    def attn(x, p, c, gidx, proj):
        """MLA over the latent cache `c` (the whole paged cache, or this
        layer's (ckv_l, kpe_l) of a dense one), layer `gidx` of the model.
        Returns (attn_out [B,T,hid], the cache with the layer's latents
        written)."""
        with scope("attn.proj"):  # down and up, the norm between them
            if "w_dq" in p:
                qa = proj(x, p, "w_dq")
                q = proj(rms_norm(qa, p["q_norm"], eps), p, "w_uq")
            else:
                q = proj(x, p, "wq")
            q = q.reshape(B, T, H, dn + dr)
            q_nope, q_pe = q[..., :dn], q[..., dn:]

            ckv_pe = proj(x, p, "w_dkv")  # [B,T,r+dr]
        with scope("attn.rope"):
            ckv = rms_norm(ckv_pe[..., :r], p["kv_norm"], eps)
            kpe = ckv_pe[..., None, r:]  # [B,T,1,dr] single shared rope head

            q_pe, kpe = apply_rotary_emb(q_pe, kpe, cos, sin, True)
            kpe = kpe[..., 0, :]  # [B,T,dr]
        with scope("attn.proj"):
            w_uk = p["w_uk"].astype(compute_dtype)
            w_uv = p["w_uv"].astype(compute_dtype)

        if paged:
            c = update_latent_layer(
                c, gidx, jnp.concatenate([ckv, kpe], axis=-1))
        elif per_row:
            c = (_scatter_rows(c[0][None], jnp.zeros((), jnp.int32),
                               cache.pos, ckv)[0],
                 _scatter_rows(c[1][None], jnp.zeros((), jnp.int32),
                               cache.pos, kpe)[0])
        else:
            c = (jax.lax.dynamic_update_slice(
                     c[0], ckv.astype(c[0].dtype), (0, cache.pos, 0)),
                 jax.lax.dynamic_update_slice(
                     c[1], kpe.astype(c[1].dtype), (0, cache.pos, 0)))

        if expand:
            # K and V per head from the row's latents; nothing expanded
            # is kept
            lat = read_latent_layer(c, gidx).astype(compute_dtype)
            CKV, KPE = lat[..., :r], lat[..., r:r + dr]
            with scope("attn.proj"):  # the up-projection of every key
                k_full = jnp.concatenate([
                    jnp.einsum("bsr,hdr->bshd", CKV, w_uk),
                    jnp.broadcast_to(KPE[:, :, None],
                                     KPE.shape[:2] + (H, dr)),
                ], axis=-1)
                v_full = jnp.einsum("bsr,hdr->bshd", CKV, w_uv)
            out = _expanded_attention(
                jnp.concatenate([q_nope, q_pe], axis=-1), k_full, v_full,
                slots, cache.start, scale, compute_dtype,
                flash_offset=cache.pos[0] if use_flash else None)
        else:
            # absorbed scores: q_eff = W_uk^T q_nope, dotted with the latent
            with scope("attn.proj"):
                q_eff = jnp.einsum("bthd,hdr->bthr", q_nope, w_uk)
            if use_kernel:
                from bigdl_tpu.ops.pallas import paged_latent_decode_attention

                ctx = paged_latent_decode_attention(
                    q_eff[:, 0], q_pe[:, 0], c.lat, c.block_tables, gidx,
                    c.pos, c.start, scale=scale, live=row_live)[:, None]
            elif paged:
                lat = read_latent_layer(c, gidx).astype(compute_dtype)
                ctx = absorbed(q_eff, q_pe, lat[..., :r], lat[..., r:r + dr])
            else:
                ctx = absorbed(q_eff, q_pe, c[0].astype(compute_dtype),
                               c[1].astype(compute_dtype))
            with scope("attn.proj"):
                out = jnp.einsum("bthr,hdr->bthd", ctx, w_uv)
        with scope("attn.proj"):
            return proj(out.reshape(B, T, H * dv).astype(compute_dtype), p,
                        "wo"), c

    rs = config.residual_scale

    def segment(hidden, c, group, moe: bool, offset: int):
        """One homogeneous run of layers as a scan. `c` is the whole paged
        cache (carried) or this segment's (ckv, kpe) slices (scanned)."""
        sliced, codes = _keep_codes_out(group)

        def body(carry, xs):
            hidden, pc, idx = carry
            p, dc = xs
            # the unsliced codes go back in, with the index that finds
            # this layer in them
            p = stacks_in(p, codes)

            def proj(x, p, name):
                return linear(x, p[name], None, compute_dtype,
                              layer=idx if name in codes else None)

            with scope("norm"):
                x = rms_norm(hidden, p["attn_norm"], eps)
            with scope("attn"):
                out, new_c = attn(x, p, pc if paged else dc, offset + idx,
                                  proj)
            with scope("norm"):
                hidden = hidden + (out * rs if rs else out)
                x = rms_norm(hidden, p["mlp_norm"], eps)
            routed = None
            with scope("ffn"):
                if moe:
                    d, routed = _moe_mlp(
                        config, x, p, compute_dtype, proj,
                        layer=idx if "w_up_e" in codes else None)
                else:
                    g = proj(x, p, "w_gate")
                    u = proj(x, p, "w_up")
                    d = proj(jax.nn.silu(g) * u, p, "w_down")
            with scope("norm"):  # the add fuses with the next norm
                hidden = hidden + (d * rs if rs else d)
            with scope("engine"):  # the loop's own count
                nxt = idx + 1
            if paged:
                return (hidden, new_c, nxt), (
                    None, routed if moe_routing else None)
            return (hidden, pc, nxt), (
                new_c, routed if moe_routing else None)

        (hidden, pc, _), (dc, routing) = jax.lax.scan(
            body, (hidden, c if paged else None, jnp.zeros((), jnp.int32)),
            (sliced, None if paged else c))
        return hidden, (pc if paged else dc), routing

    K = num_dense_layers(config)
    dense_out, routing = [], None
    c = cache
    if K:
        with scope("attn"):  # a dense cache: the segment's layers of it
            c0 = c if paged else (cache.ckv[:K], cache.kpe[:K])
        h, c0, _ = segment(h, c0, params["layers"], False, 0)
        if paged:
            c = c0
        else:
            dense_out.append(c0)
    if config.num_hidden_layers - K:
        with scope("attn"):
            c1 = c if paged else (cache.ckv[K:], cache.kpe[K:])
        h, c1, routing = segment(h, c1, params["moe_layers"], True, K)
        if paged:
            c = c1
        else:
            dense_out.append(c1)

    with scope("lm_head"):
        if last_logits_only:
            h = h[:, -1:]
        logits = llama.lm_head_logits(config, params, h, compute_dtype)

    with scope("engine"):
        extra = ()
        if moe_routing:
            extra = (routing if routing is not None else jnp.zeros(
                (0, B, T, max(config.num_experts_per_tok, 1)), jnp.int32),)
        if fresh:
            return (logits, None) + extra
        if paged:
            cache = dataclasses.replace(c, pos=cache.pos + T)
        else:
            cache = dataclasses.replace(
                cache,
                ckv=jnp.concatenate([d[0] for d in dense_out], axis=0),
                kpe=jnp.concatenate([d[1] for d in dense_out], axis=0),
                pos=cache.pos + T,
            )
        return (logits, cache) + extra
