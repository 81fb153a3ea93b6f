"""Model configuration.

One frozen dataclass covers the decoder-family architectures the
reference optimizes per-file in `transformers/models/` (llama, mistral,
qwen2, gemma2, phi3, baichuan, starcoder2, stablelm, glm, minicpm, ...;
SURVEY.md §2.2 "Model zoo"): the differences the reference encodes as
separate patched forwards (qkv bias, tied embeddings, rope scaling,
sliding window, logit softcap, partial rotary, pre/post norms, ALiBi,
MoE routing) are config flags here, resolved once at trace time — dead
branches compile away under jit.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    model_type: str = "llama"
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    head_dim: Optional[int] = None  # defaults to hidden // heads
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = None
    max_position_embeddings: int = 4096
    tie_word_embeddings: bool = False
    attention_bias: bool = False  # qwen2-style qkv bias
    attention_out_bias: bool = False  # starcoder2: o_proj bias too
    mlp_bias: bool = False
    sliding_window: Optional[int] = None  # mistral-style local attention
    # gemma2/gemma3: layer l uses sliding attention iff (l+1) % pattern != 0
    # (None = every layer sliding when sliding_window is set, like mistral)
    sliding_window_pattern: Optional[int] = None
    # explicit per-layer sliding flags (gemma3 layer_types); overrides the
    # pattern when set
    sliding_layers: Optional[tuple] = None
    # gemma3: sliding layers rope with this base instead of rope_theta
    # (and without the global layers' rope_scaling)
    rope_local_theta: Optional[float] = None
    attn_logit_softcap: Optional[float] = None  # gemma2
    final_logit_softcap: Optional[float] = None  # gemma2
    # attention scale override (gemma2 query_pre_attn_scalar**-0.5); None =
    # 1/sqrt(head_dim)
    attn_scale: Optional[float] = None
    hidden_act: str = "silu"
    gated_mlp: bool = True  # False: plain fc->act->proj (starcoder2, gpt2)
    # normalization
    norm_type: str = "rmsnorm"  # "rmsnorm" | "layernorm"
    norm_bias: bool = False  # layernorm bias (starcoder2, stablelm)
    rms_norm_offset: bool = False  # gemma (1+w) rmsnorm weights
    post_attn_norm: bool = False  # gemma2 extra norms after attn/mlp blocks
    qk_norm: bool = False  # per-head RMSNorm on q/k (qwen3-style)
    # what the attention block computes from q, k, v: "softmax", or
    # "power_retention" (brumby; bigdl_tpu/kvstate.py has the equations):
    # weights (q . k / sqrt(D)) ** retention_degree decayed by a learned
    # per-token gate of each KV head, normalised by their sum +
    # retention_eps, held as a recurrent state and no keys
    attention_kind: str = "softmax"
    retention_degree: int = 2
    retention_eps: float = 1e-6
    # gemma-style embedding scale
    scale_embeddings: bool = False  # multiply embed output by sqrt(hidden)
    embedding_scale: Optional[float] = None  # minicpm scale_emb multiplier
    # minicpm residual scaling: hidden += scale_depth/sqrt(L) * block_out
    residual_scale: Optional[float] = None
    logit_scale: Optional[float] = None  # minicpm/cohere: logits *= scale
    lm_head_bias: bool = False  # phi-1/2: the lm head carries a bias
    # positions
    partial_rotary_factor: float = 1.0  # stablelm 0.25, glm 0.5
    rope_interleaved: bool = False  # GPT-NeoX/GLM pair-interleaved rope
    alibi: bool = False  # baichuan-13b/bloom attention-bias positions
    # multiplier on the alibi bias: falcon-rw folds the 1/sqrt(head_dim)
    # score scale into the bias too ((scores + alibi) * inv_norm_factor,
    # HF modeling_falcon eager path); bloom/baichuan/mpt add it unscaled
    alibi_scale: Optional[float] = None
    learned_positions: bool = False  # gpt2 wpe table (rope disabled)
    # qwen v1 logn attention: q *= max(1, log_train_len(pos+1)) for
    # positions beyond the training length (HF modeling_qwen logn_tensor)
    logn_attn: bool = False
    logn_train_len: int = 0
    parallel_residual: bool = False  # gptneox: h += attn(x) + mlp(x)
    embed_layernorm: bool = False  # bloom word_embeddings_layernorm
    # MoE (mixtral / qwen2_moe); 0 experts = dense MLP
    num_experts: int = 0
    num_experts_per_tok: int = 2
    moe_intermediate_size: Optional[int] = None
    shared_expert_intermediate_size: Optional[int] = None  # qwen2_moe
    norm_topk_prob: bool = False  # renormalize top-k router weights
    # the XLA dispatch formulation for where the grouped kernel cannot
    # run (training, a mesh, dense weights): None = auto (dense for E<=8,
    # ragged above), or force "dense" / "ragged" (models/llama.py
    # _moe_dispatch; packed experts at inference take the kernel)
    moe_dispatch: Optional[str] = None
    moe_capacity_factor: float = 1.25  # ragged: slots per expert vs even load
    # mllama (llama-3.2 vision): indices of the tanh-gated cross-attention
    # layers interleaved into the decoder (models/mllama.py)
    cross_attention_layers: Optional[tuple] = None
    # MLA (deepseek v2/v3, minicpm3 — models/deepseek.py): latent KV
    # compression ranks and split head dims; kv_lora_rank set = MLA
    q_lora_rank: Optional[int] = None
    kv_lora_rank: Optional[int] = None
    qk_nope_head_dim: Optional[int] = None
    qk_rope_head_dim: Optional[int] = None
    v_head_dim: Optional[int] = None
    # DeepSeek-MoE routing (models/deepseek.py _router)
    n_group: Optional[int] = None
    topk_group: Optional[int] = None
    topk_method: Optional[str] = None  # greedy|group_limited_greedy|noaux_tc
    scoring_func: str = "softmax"  # v3: sigmoid
    routed_scaling_factor: float = 1.0
    first_k_dense_replace: int = 0
    n_shared_experts: Optional[int] = None  # ungated, n * moe_intermediate
    # RWKV (v4/v5): attention-free recurrence (models/rwkv.py). head_size
    # set = v5 multi-head matrix state; None = v4 scalar WKV
    attention_hidden_size: Optional[int] = None
    rwkv_head_size: Optional[int] = None
    rwkv_group_norm_eps: Optional[float] = None  # v5 ln_x GroupNorm eps
    # multimodal (qwen2_vl): M-RoPE channel sections for (t, h, w) position
    # components; standard rope when the three components are equal
    mrope_section: Optional[tuple] = None
    image_token_id: Optional[int] = None
    video_token_id: Optional[int] = None
    vision_start_token_id: Optional[int] = None
    audio_token_id: Optional[int] = None  # minicpmo audio placeholders
    audio_pool_step: Optional[int] = None  # minicpmo post-projection pool
    # granitemoehybrid (models/granitemoehybrid.py): each layer's mixer,
    # "mamba" | "attention", by index; the Mamba-2 mixer's sizes (heads x
    # head size = the inner width; one conv over inner + 2 * groups *
    # state channels); attention without any position encoding; the
    # always-on shared MLP beside the routed experts
    layer_types: Optional[tuple] = None
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_d_conv: int = 4
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256
    position_embedding_type: str = "rope"  # "nope": no rope call at all
    shared_intermediate_size: Optional[int] = None
    # smallthinker (models/smallthinker.py): which layers take a rope, by
    # index (beside `sliding_layers`, which layers take the window); a
    # layer with 0 has no position encoding at all
    rope_layers: Optional[tuple] = None
    # laguna (models/laguna.py): attention layers that differ in SHAPE by
    # kind. Query heads of every layer, by index (one count a kind:
    # `num_attention_heads` is the full layers'); a sigmoid gate on every
    # head's output, one scalar a head and token ("per_head"); the share of
    # a head the WINDOW layers' rope turns (`rope_local_theta` is its base;
    # `partial_rotary_factor`, `rope_theta` and `rope_scaling` are the full
    # layers')
    heads_per_layer: Optional[tuple] = None
    attn_gate: Optional[str] = None
    rope_local_partial_rotary_factor: Optional[float] = None
    # sdar_moe (models/sdar.py): generation by DIFFUSION OVER BLOCKS. A
    # `block_length` of b > 0 makes the attention mask causal BY BLOCK (key
    # j is visible to query i iff j // b <= i // b) and the logits at
    # position i score the token AT i; blocks of b positions are produced
    # left to right, every position of one starting as `mask_token_id` and
    # revealed over `denoising_steps` passes by `remasking_strategy`
    # ("sequential" | "low_confidence_static" | "low_confidence_dynamic",
    # the last revealing what passes `confidence_threshold`). 0 = an
    # autoregressive model (serving/blocks.py has the loop)
    block_length: int = 0
    denoising_steps: int = 4
    remasking_strategy: str = "low_confidence_dynamic"
    confidence_threshold: float = 0.9
    mask_token_id: int = 0
    # minicpm_sala (models/minicpm_sala.py): each layer's mixer by index,
    # "minicpm4" (softmax attention without rope whose long rows read a
    # SELECTION of key blocks, kvsparse.py) | "lightning-attn" (decayed
    # linear attention: `lightning_heads` heads with their own keys and
    # values and a [head size, head size] state each). `sparse_config` is
    # the selection's sizes as sorted (key, value) pairs: kernel_size,
    # kernel_stride, block_size, topk, init_blocks, window_size, dense_len
    mixer_types: Optional[tuple] = None
    lightning_heads: int = 0
    lightning_head_dim: int = 0
    sparse_config: Optional[tuple] = None
    # jamba (models/jamba.py): `layer_types` as granite's, but the "mamba"
    # layers are MAMBA-1: the inner width is `mamba_expand` x hidden, a
    # channel's step dt comes from a projection of rank `mamba_dt_rank`
    # (0 = no such mixer), and the decay differs by channel AND state index
    mamba_expand: int = 2
    mamba_dt_rank: int = 0
    # lfm2_moe (models/lfm2_moe.py): `layer_types` names each layer's
    # operator, "conv" | "attention"; a "conv" layer is LFM2's gated short
    # convolution, depthwise and causal over `conv_l_cache` inputs (0 = no
    # such operator), whose only state is its last `conv_l_cache - 1` inputs
    conv_l_cache: int = 0
    # one rank's share of an expert-parallel layer (models/llama.py
    # `_moe_dispatch`): the router scores `router_experts` experts (0 = the
    # `num_experts` held here: every expert, no share) and this program
    # holds `num_experts` of them from id `first_expert` on. An assignment
    # to an expert held elsewhere is dropped; its weight stays in the
    # normalisation
    router_experts: int = 0
    first_expert: int = 0
    # solar_open2 (models/solar_open2.py): `layer_types` "attention" | "kda";
    # a "kda" layer is Kimi delta attention, `kda_heads` heads of
    # `kda_head_dim` keys and values behind three short convolutions of
    # `conv_l_cache` taps (kvhybrid.kda_mix)
    kda_heads: int = 0
    kda_head_dim: int = 0

    def __post_init__(self):
        if self.attention_kind not in ("softmax", "power_retention"):
            raise ValueError(
                f"attention_kind must be 'softmax' or 'power_retention'; "
                f"got {self.attention_kind!r}"
            )
        if (self.attention_kind == "power_retention"
                and self.retention_degree != 2):
            raise NotImplementedError(
                f"power retention of degree {self.retention_degree}: the "
                "state's feature map is written for degree 2"
            )
        if self.router_experts and not (
                0 <= self.first_expert
                <= self.router_experts - self.num_experts):
            raise ValueError(
                f"a share of {self.num_experts} experts from id "
                f"{self.first_expert} does not lie in a router's "
                f"{self.router_experts}")
        if self.moe_dispatch not in (None, "dense", "ragged"):
            raise ValueError(
                f"moe_dispatch must be None, 'dense' or 'ragged'; "
                f"got {self.moe_dispatch!r}"
            )
        # ModelConfig is a static jit argument and must hash; rope_scaling
        # arrives as a dict from HF config.json (or a list-of-pairs after a
        # JSON round-trip through save_low_bit) — normalize to a tuple.
        sc = self.sparse_config
        if isinstance(sc, dict):
            sc = sorted(sc.items())
        if sc is not None:  # (lists of pairs after a JSON round trip)
            object.__setattr__(self, "sparse_config",
                               tuple((k, int(v)) for k, v in sc))
        rs = self.rope_scaling
        if isinstance(rs, dict):
            rs = tuple(sorted((k, _hashable(v)) for k, v in rs.items()))
        elif isinstance(rs, (list, tuple)):
            rs = tuple((k, _hashable(v)) for k, v in rs)
        object.__setattr__(self, "rope_scaling", rs)
        # list-typed fields arrive as lists after a JSON round-trip
        # (save_low_bit -> load_low_bit) and must re-become tuples or the
        # config stops hashing as a static jit argument
        for f in ("sliding_layers", "cross_attention_layers",
                  "mrope_section", "layer_types", "rope_layers",
                  "heads_per_layer", "mixer_types"):
            v = getattr(self, f)
            if isinstance(v, list):
                object.__setattr__(self, f, tuple(v))

    @property
    def rope_scaling_dict(self) -> Optional[dict]:
        return dict(self.rope_scaling) if self.rope_scaling else None

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_attention_heads

    @property
    def q_dim(self) -> int:
        return self.num_attention_heads * self.head_dim_

    @property
    def kv_dim(self) -> int:
        return self.num_key_value_heads * self.head_dim_

    @property
    def rotary_dim(self) -> int:
        # keep even (rope rotates dim/2 pairs)
        r = int(self.head_dim_ * self.partial_rotary_factor)
        return r - (r % 2)

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def router_width(self) -> int:
        """Experts the router scores: more than `num_experts` where this
        program holds one rank's share of them."""
        return self.router_experts or self.num_experts

    @property
    def expert_share(self) -> Optional[tuple]:
        """None where every routed expert is held here, else (id of the
        first expert held, experts held, the router's width)."""
        if self.router_width == self.num_experts:
            return None
        return self.first_expert, self.num_experts, self.router_width

    def layer_is_sliding(self, layer_idx: int) -> bool:
        """Static per-layer attention kind (gemma2 alternation / gemma3
        explicit layer_types)."""
        if self.sliding_window is None:
            return False
        if self.sliding_layers is not None:
            return bool(self.sliding_layers[layer_idx])
        if self.sliding_window_pattern is None:
            return True
        return (layer_idx + 1) % self.sliding_window_pattern != 0

    @classmethod
    def from_hf_config(cls, hf: dict[str, Any]) -> "ModelConfig":
        """Build from a HuggingFace config.json dict (the ingest path the
        reference drives through transformers AutoConfig, model.py:111)."""
        model_type = hf.get("model_type", "llama")
        if "model_type" not in hf and "moe_num_primary_experts" in hf:
            # PowerInfer's SmallThinker names itself in `model_name` and its
            # experts "primary": such a dict is no llama
            model_type = "smallthinker"
        if model_type == "chatglm" and isinstance(hf.get("vision_config"),
                                                  dict):
            # THUDM glm-4v-9b ships model_type "chatglm" + a vision_config
            # dict; route to the chatglm4v family (EVA2-CLIP tower over
            # the same chatglm text schema)
            model_type = "chatglm4v"
        if model_type == "Yi":
            # legacy 01-ai remote-code id (reference convert.py:1738);
            # the architecture is llama-shaped — served by the yi entry
            model_type = "yi"
        if model_type == "phi-msft":
            # mlabonne phixtral ships phi-2's legacy remote-code id
            # (reference convert.py:1685-1687 keys on num_local_experts
            # exactly this way to exclude plain phi-2)
            if hf.get("num_local_experts"):
                model_type = "phixtral"
            else:
                raise NotImplementedError(
                    "legacy phi-msft (phi-2 remote-code) checkpoints are "
                    "not supported — use the native model_type='phi' "
                    "release of phi-2"
                )
        if isinstance(hf.get("text_config"), dict):
            # multimodal configs nest the decoder fields (HF >= 4.52
            # qwen2_vl etc.); original checkpoints keep them at top level
            # — merge with the nested values winning
            hf = {**hf, **{k: v for k, v in hf["text_config"].items()
                           if v is not None}}
            hf["model_type"] = model_type
        known = {
            "vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rms_norm_eps", "rope_theta", "rope_scaling",
            "max_position_embeddings", "tie_word_embeddings", "sliding_window",
            "hidden_act", "attention_bias", "mlp_bias",
            "partial_rotary_factor",
        }
        kwargs = {k: hf[k] for k in known if k in hf and hf[k] is not None}
        kwargs["model_type"] = model_type
        rs = kwargs.get("rope_scaling")
        if isinstance(rs, dict):
            # longrope/su/dynamic/yarn need the context lengths, which HF
            # stores at the TOP level of config.json (phi3: rope_scaling
            # only carries the factor lists) — inject them.
            rs = dict(rs)
            for src, dst in (
                ("original_max_position_embeddings", "original_max_position_embeddings"),
                ("max_position_embeddings", "max_position_embeddings"),
            ):
                if dst not in rs and hf.get(src) is not None:
                    rs[dst] = hf[src]
            kwargs["rope_scaling"] = rs
        builder = _HF_BUILDERS.get(model_type)
        if builder is not None:
            builder(hf, kwargs)
        if "num_key_value_heads" not in kwargs:
            kwargs["num_key_value_heads"] = kwargs.get(
                "num_attention_heads", cls.num_attention_heads
            )
        return cls(**kwargs)


def _hashable(v):
    if isinstance(v, list):
        return tuple(v)
    return v


# --- per-model_type config translation -------------------------------------
# The reference's per-arch knowledge lives in ~70 `model_type` branches of
# `_optimize_post` (convert.py:1251-2027); here it is a table of small
# config builders (weights-side counterparts live in bigdl_tpu/convert/hf.py).

def _hf_qwen2(hf, kw):
    # qwen2 has qkv bias but no o/mlp bias; HF config lacks the flag
    kw.setdefault("attention_bias", True)


def _hf_gemma(hf, kw):
    kw["scale_embeddings"] = True
    kw["rms_norm_offset"] = True
    kw.setdefault("tie_word_embeddings", True)
    kw.setdefault("hidden_act", hf.get("hidden_activation", "gelu_pytorch_tanh"))


def _hf_gemma2(hf, kw):
    _hf_gemma(hf, kw)
    kw["attn_logit_softcap"] = hf.get("attn_logit_softcapping", 50.0)
    kw["final_logit_softcap"] = hf.get("final_logit_softcapping", 30.0)
    kw["post_attn_norm"] = True
    kw["sliding_window_pattern"] = 2
    if "query_pre_attn_scalar" in hf:
        kw["attn_scale"] = hf["query_pre_attn_scalar"] ** -0.5


def _hf_gemma3(hf, kw):
    """Gemma3 text (HF Gemma3TextConfig): gemma2's norms/scales plus
    per-head q/k RMSNorm and DUAL rope — full-attention layers use
    rope_theta (+rope_scaling), sliding layers rope_local_base_freq
    unscaled. layer_types lists the alternation explicitly."""
    _hf_gemma(hf, kw)
    kw["post_attn_norm"] = True
    kw["qk_norm"] = True
    kw.setdefault("head_dim", hf.get("head_dim", 256))
    kw["rms_norm_eps"] = hf.get("rms_norm_eps", 1e-6)
    if "query_pre_attn_scalar" in hf:
        kw["attn_scale"] = hf["query_pre_attn_scalar"] ** -0.5
    lt = hf.get("layer_types")
    if lt:
        kw["sliding_layers"] = tuple(t == "sliding_attention" for t in lt)
    else:
        kw["sliding_window_pattern"] = hf.get("sliding_window_pattern", 6)
    kw["rope_local_theta"] = hf.get("rope_local_base_freq", 10000.0)


def _hf_phi3(hf, kw):
    # phi3 ships fused qkv/gate_up; split at ingest (convert/hf.py)
    kw.setdefault("tie_word_embeddings", hf.get("tie_word_embeddings", False))


def _hf_stablelm(hf, kw):
    kw["norm_type"] = "layernorm"
    kw["norm_bias"] = True
    kw["attention_bias"] = hf.get("use_qkv_bias", False)
    kw.setdefault("partial_rotary_factor", hf.get("partial_rotary_factor", 0.25))
    kw["rms_norm_eps"] = hf.get("layer_norm_eps", 1e-5)


def _hf_starcoder2(hf, kw):
    kw["norm_type"] = "layernorm"
    kw["norm_bias"] = True
    kw["attention_bias"] = hf.get("use_bias", True)
    kw["attention_out_bias"] = hf.get("use_bias", True)
    kw["mlp_bias"] = hf.get("use_bias", True)
    kw["gated_mlp"] = False
    kw["rms_norm_eps"] = hf.get("norm_epsilon", 1e-5)
    kw.setdefault("tie_word_embeddings", hf.get("tie_word_embeddings", True))


def _hf_baichuan(hf, kw):
    # 7B is rope llama-shaped; 13B (no rope, 40 heads, alibi) detected by
    # position embeddings absence → model_max_length + alibi
    if hf.get("num_attention_heads", 32) >= 40 and "rope_theta" not in hf:
        kw["alibi"] = True
    kw.setdefault(
        "max_position_embeddings",
        hf.get("model_max_length", hf.get("max_position_embeddings", 4096)),
    )


def _hf_internlm2(hf, kw):
    kw.setdefault("attention_bias", hf.get("bias", False))


def _hf_internlm(hf, kw):
    """internlm v1: llama layout with biased qkv AND o projections."""
    kw["attention_bias"] = bool(hf.get("bias", True))
    kw["attention_out_bias"] = bool(hf.get("bias", True))


def _hf_minicpm(hf, kw):
    L = kw.get("num_hidden_layers", 32)
    kw["residual_scale"] = hf.get("scale_depth", 1.0) / (L ** 0.5)
    # runtime multiplier, NOT folded into weights: with tied embeddings the
    # lm head shares the matrix and must stay unscaled
    kw["embedding_scale"] = hf.get("scale_emb", 1.0)
    if "dim_model_base" in hf and hf.get("hidden_size"):
        kw["logit_scale"] = 1.0 / (hf["hidden_size"] / hf["dim_model_base"])


#: the `sparse_config` of the `minicpm4` mixer where a checkpoint gives none:
#: MiniCPM4.1's published sizes (InfLLM-v2)
SPARSE_DEFAULTS = {"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
                   "topk": 64, "init_blocks": 1, "window_size": 2048,
                   "dense_len": 8192}


def _hf_minicpm_sala(hf, kw):
    """MiniCPM-SALA: MiniCPM's three scalings over layers that are, by
    `mixer_types`, block-sparse softmax attention (`minicpm4`) or lightning
    attention (`lightning-attn`). What cannot be served is refused by
    name; a checkpoint's own `sparse_config` wins over the family's."""
    _hf_minicpm(hf, kw)
    L = hf["num_hidden_layers"]
    kinds = tuple(hf.get("mixer_types") or ("minicpm4",) * L)
    if len(kinds) != L or set(kinds) - {"minicpm4", "lightning-attn"}:
        raise ValueError(
            f"mixer_types must name {L} layers as 'minicpm4' or "
            f"'lightning-attn'; got {len(kinds)}: {sorted(set(kinds))}")
    kw["mixer_types"] = kinds
    kw["lightning_heads"] = hf.get("lightning_nh",
                                   hf["num_attention_heads"])
    kw["lightning_head_dim"] = hf.get("lightning_head_dim",
                                      hf.get("head_dim") or 128)
    if hf.get("lightning_nkv", kw["lightning_heads"]) != kw[
            "lightning_heads"]:
        raise NotImplementedError(
            f"minicpm_sala with lightning_nkv {hf['lightning_nkv']} != "
            f"lightning_nh {kw['lightning_heads']}: the lightning state is "
            "written for a key and a value a head")
    if hf.get("attn_use_rope", False):
        raise NotImplementedError(
            "minicpm_sala with attn_use_rope: the sparse layers are "
            "written without a position encoding")
    for flag in ("lightning_use_rope", "qk_norm", "use_output_gate",
                 "use_output_norm", "attn_use_output_gate"):
        if not hf.get(flag, True):
            raise NotImplementedError(
                f"minicpm_sala with {flag} false: the mixers are written "
                "with it")
    if hf.get("lightning_scale", "1/sqrt(d)") != "1/sqrt(d)":
        raise NotImplementedError(
            f"minicpm_sala with lightning_scale {hf['lightning_scale']!r}")
    kw["qk_norm"] = True
    sparse = {**SPARSE_DEFAULTS, **(hf.get("sparse_config") or {})}
    unknown = set(sparse) - set(SPARSE_DEFAULTS)
    if unknown:
        raise ValueError(f"sparse_config keys {sorted(unknown)} are not "
                         f"among {sorted(SPARSE_DEFAULTS)}")
    if (sparse["block_size"] % sparse["kernel_stride"]
            or sparse["kernel_size"] != 2 * sparse["kernel_stride"]
            or sparse["window_size"] % sparse["block_size"]
            or sparse["dense_len"] % sparse["block_size"]):
        raise NotImplementedError(
            f"minicpm_sala with sparse_config {sparse}: windows of two "
            "strides, and a block, a local window and a dense length in "
            "whole blocks, are what the selection is written for")
    kw["sparse_config"] = sparse
    kw.setdefault("tie_word_embeddings", False)


def _hf_glm(hf, kw):
    kw.setdefault("partial_rotary_factor", hf.get("partial_rotary_factor", 0.5))
    kw["rope_interleaved"] = True
    kw["attention_bias"] = hf.get("attention_bias", True)
    kw.setdefault("head_dim", hf.get("head_dim"))


def _hf_gpt2(hf, kw):
    kw["hidden_size"] = hf.get("n_embd", 768)
    kw["num_hidden_layers"] = hf.get("n_layer", 12)
    kw["num_attention_heads"] = hf.get("n_head", 12)
    kw["num_key_value_heads"] = kw["num_attention_heads"]
    kw["intermediate_size"] = hf.get("n_inner") or 4 * kw["hidden_size"]
    kw["max_position_embeddings"] = hf.get("n_positions", 1024)
    kw["rms_norm_eps"] = hf.get("layer_norm_epsilon", 1e-5)
    kw["norm_type"] = "layernorm"
    kw["norm_bias"] = True
    kw["gated_mlp"] = False
    kw["mlp_bias"] = True
    kw["attention_bias"] = True
    kw["attention_out_bias"] = True
    kw["learned_positions"] = True
    kw["hidden_act"] = hf.get("activation_function", "gelu_new")
    kw.setdefault("tie_word_embeddings", True)


def _hf_bloom(hf, kw):
    kw["num_hidden_layers"] = hf.get("n_layer", 24)
    kw["num_attention_heads"] = hf.get("n_head", 16)
    kw["num_key_value_heads"] = kw["num_attention_heads"]
    kw["intermediate_size"] = 4 * kw.get("hidden_size", hf.get("hidden_size", 64))
    kw["rms_norm_eps"] = hf.get("layer_norm_epsilon", 1e-5)
    kw["norm_type"] = "layernorm"
    kw["norm_bias"] = True
    kw["gated_mlp"] = False
    kw["mlp_bias"] = True
    kw["attention_bias"] = True
    kw["attention_out_bias"] = True
    kw["alibi"] = True
    kw["embed_layernorm"] = True
    kw["hidden_act"] = "gelu_pytorch_tanh"
    kw.setdefault("tie_word_embeddings", True)


def _hf_gptneox(hf, kw):
    kw["norm_type"] = "layernorm"
    kw["norm_bias"] = True
    kw["gated_mlp"] = False
    kw["mlp_bias"] = True
    kw["attention_bias"] = True
    kw["attention_out_bias"] = True
    kw["parallel_residual"] = hf.get("use_parallel_residual", True)
    kw.setdefault("partial_rotary_factor", hf.get("rotary_pct", 0.25))
    kw["rope_theta"] = hf.get("rotary_emb_base", 10000.0)
    kw["rms_norm_eps"] = hf.get("layer_norm_eps", 1e-5)
    kw["hidden_act"] = hf.get("hidden_act", "gelu")


def _hf_mixtral(hf, kw):
    kw["num_experts"] = hf.get("num_local_experts", 8)
    kw["num_experts_per_tok"] = hf.get("num_experts_per_tok", 2)
    kw["norm_topk_prob"] = True


def _hf_qwen2_moe(hf, kw):
    kw.setdefault("attention_bias", True)
    kw["num_experts"] = hf.get("num_experts", 60)
    kw["num_experts_per_tok"] = hf.get("num_experts_per_tok", 4)
    kw["moe_intermediate_size"] = hf.get("moe_intermediate_size", 1408)
    kw["shared_expert_intermediate_size"] = hf.get(
        "shared_expert_intermediate_size", 5632
    )
    kw["norm_topk_prob"] = hf.get("norm_topk_prob", False)


def _hf_chatglm(hf, kw):
    """THUDM chatglm2/3 and glm-4 trust_remote_code config schema
    (reference models/chatglm2.py, chatglm4.py: interleaved rope on the
    first half of kv_channels, MQA via multi_query_group_num, fused
    query_key_value / dense_h_to_4h checkpoints)."""
    kw["num_hidden_layers"] = hf.get("num_layers", 28)
    kw["intermediate_size"] = hf.get("ffn_hidden_size", 13696)
    kw["vocab_size"] = hf.get("padded_vocab_size", hf.get("vocab_size", 65024))
    kw["head_dim"] = hf.get("kv_channels")
    if hf.get("multi_query_attention"):
        kw["num_key_value_heads"] = hf.get("multi_query_group_num", 2)
    kw["rms_norm_eps"] = hf.get("layernorm_epsilon", 1e-5)
    kw["partial_rotary_factor"] = 0.5
    kw["rope_interleaved"] = True
    # chatglm2-32k / glm-4 scale the base by rope_ratio
    # (chatglm2.py:102-109: base = 10000 * rope_ratio)
    kw["rope_theta"] = 10000.0 * hf.get("rope_ratio", 1.0)
    kw["attention_bias"] = bool(hf.get("add_qkv_bias", False))
    kw["max_position_embeddings"] = hf.get("seq_length", 8192)
    kw["tie_word_embeddings"] = bool(hf.get("tie_word_embeddings", False))
    if not hf.get("rmsnorm", True):
        kw["norm_type"] = "layernorm"


def _hf_qwen2_vl(hf, kw):
    """Qwen2-VL text side: qwen2 layout + M-RoPE. The mrope inv_freq is
    the standard one — only the application is sectioned — so
    rope_scaling is consumed here, not by make_inv_freq_scaled."""
    kw.setdefault("attention_bias", True)
    rs = kw.pop("rope_scaling", None) or {}
    if isinstance(rs, (list, tuple)):
        rs = dict(rs)
    sections = rs.get("mrope_section")
    if sections:
        kw["mrope_section"] = tuple(int(s) for s in sections)
    kw["image_token_id"] = hf.get("image_token_id", 151655)
    kw["video_token_id"] = hf.get("video_token_id", 151656)
    kw["vision_start_token_id"] = hf.get("vision_start_token_id", 151652)


def _hf_mpt(hf, kw):
    """MPT (reference models/mpt.py): alibi positions, fused Wqkv,
    non-gated gelu MLP, bias-free layernorm, tied head."""
    kw["hidden_size"] = hf.get("d_model", 4096)
    kw["num_attention_heads"] = hf.get("n_heads", 32)
    kw["num_hidden_layers"] = hf.get("n_layers", 32)
    kw["intermediate_size"] = int(
        hf.get("expansion_ratio", 4) * kw["hidden_size"]
    )
    kw["max_position_embeddings"] = hf.get("max_seq_len", 2048)
    attn = hf.get("attn_config") or {}
    kw["alibi"] = bool(attn.get("alibi", True))
    kw["norm_type"] = "layernorm"
    kw["hidden_act"] = "gelu"
    kw["gated_mlp"] = False
    kw["tie_word_embeddings"] = True
    if not hf.get("no_bias", True):
        # the weight translator (_mpt_layer) loads weights only; silently
        # dropping a biased checkpoint's biases would generate garbage
        raise NotImplementedError(
            "mpt with no_bias=False (biased linears/layernorms) is not "
            "supported; released MPT checkpoints use no_bias=True"
        )


def _mla_fields(hf, kw):
    for f in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim"):
        if hf.get(f) is not None:
            kw[f] = hf[f]
    kw["rope_interleaved"] = True  # DeepSeek complex-pair rope


def _hf_deepseek_v2(hf, kw):
    """DeepSeek-V2 (HF modeling_deepseek_v2; the reference's minicpm3.py
    implements the same MLA): latent-KV attention + DeepSeek-MoE with
    group-limited greedy routing and ungated shared experts."""
    _mla_fields(hf, kw)
    kw["num_experts"] = hf.get("n_routed_experts") or 0
    kw["num_experts_per_tok"] = hf.get("num_experts_per_tok") or 2
    kw["moe_intermediate_size"] = hf.get("moe_intermediate_size")
    kw["n_shared_experts"] = hf.get("n_shared_experts")
    kw["first_k_dense_replace"] = hf.get("first_k_dense_replace", 0)
    kw["topk_method"] = hf.get("topk_method", "greedy")
    kw["n_group"] = hf.get("n_group")
    kw["topk_group"] = hf.get("topk_group")
    kw["routed_scaling_factor"] = hf.get("routed_scaling_factor", 1.0)
    kw["norm_topk_prob"] = hf.get("norm_topk_prob", False)
    kw["scoring_func"] = hf.get("scoring_func", "softmax")
    if hf.get("moe_layer_freq", 1) != 1:
        raise NotImplementedError("deepseek moe_layer_freq != 1")


def _hf_deepseek_v3(hf, kw):
    _hf_deepseek_v2(hf, kw)
    kw["topk_method"] = hf.get("topk_method", "noaux_tc")
    kw["scoring_func"] = hf.get("scoring_func", "sigmoid")
    kw["norm_topk_prob"] = hf.get("norm_topk_prob", True)


def _hf_glm4_moe_lite(hf, kw):
    """GLM-4.7-Flash (HF modeling_glm4_moe_lite: DeepseekV3Attention and
    Glm4MoeTopkRouter, so every layer's equations are DeepSeek-V3's). What
    the source's config.json has no key for is set here for the model
    type, as `norm_topk_prob` is for mixtral: sigmoid scores (HF
    hard-codes them for this family) and the pair-interleaved rope (HF's
    `rope_interleave` default). `rope_scaling` is null there, so the
    softmax scale is (192 + 64)^-0.5 with no mscale term.
    `num_nextn_predict_layers` is read and the layer is not run: HF's
    Glm4MoeLiteForCausalLM drops `model.layers.<num_hidden_layers>.*` at
    load, and so does convert/hf.py."""
    _hf_deepseek_v3(hf, kw)
    kw["scoring_func"] = "sigmoid"
    kw["first_k_dense_replace"] = hf.get("first_k_dense_replace", 1)
    kw["n_group"] = hf.get("n_group") or 1
    kw["topk_group"] = hf.get("topk_group") or 1


def _hf_minicpm3(hf, kw):
    """MiniCPM3 (reference models/minicpm3.py): MLA attention + the
    minicpm residual/embedding/logit scalings, dense MLP."""
    _hf_minicpm(hf, kw)
    _mla_fields(hf, kw)


def _hf_qwen3(hf, kw):
    """Qwen3: qwen2 minus the qkv bias plus per-head q/k RMSNorm."""
    kw["qk_norm"] = True
    kw.setdefault("head_dim", hf.get("head_dim"))


def _hf_brumby(hf, kw):
    """Brumby (Manifest AI): Qwen3's block with the softmax attention
    replaced by power retention. The source's config.json names none of
    the retention layer's own sizes; the defaults are the published
    description's (degree 2, a sigmoid gate per KV head and token)."""
    _hf_qwen3(hf, kw)
    kw["attention_kind"] = "power_retention"
    kw["retention_degree"] = hf.get("retention_degree", 2)
    kw["retention_eps"] = hf.get("retention_eps", 1e-6)


def _hf_smallthinker(hf, kw):
    """SmallThinker (PowerInfer/SmallThinker-21BA3B-Instruct): window and
    full attention mixed by `sliding_window_layout`, a rope only where
    `rope_layout` says so, every layer's FFN `moe_num_primary_experts`
    ReLU-gated experts of `moe_ffn_hidden_size`, top
    `moe_num_active_primary_experts`, routed from the layer's INPUT. What
    config.json has no key for (where the router reads, that every layer is
    sparse, the rope's half-split convention) is the model type's, written
    in models/smallthinker.py."""
    L = hf["num_hidden_layers"]
    window = hf.get("sliding_window_size")
    sliding = tuple(int(x) for x in hf.get("sliding_window_layout")
                    or (0,) * L)
    rope = tuple(int(x) for x in hf.get("rope_layout") or (1,) * L)
    if len(sliding) != L or len(rope) != L:
        raise ValueError(
            f"sliding_window_layout and rope_layout must have "
            f"num_hidden_layers = {L} entries; got {len(sliding)} and "
            f"{len(rope)}")
    if any(sliding) and not window:
        raise ValueError("sliding_window_layout names window layers but "
                         "sliding_window_size is not set")
    if not hf.get("moe_primary_router_apply_softmax", True):
        raise NotImplementedError(
            "smallthinker with moe_primary_router_apply_softmax false (a "
            "sigmoid router): the router is written as a softmax")
    kw["sliding_window"] = window if any(sliding) else None
    kw["sliding_layers"], kw["rope_layers"] = sliding, rope
    kw["num_experts"] = hf["moe_num_primary_experts"]
    kw["num_experts_per_tok"] = hf["moe_num_active_primary_experts"]
    kw["moe_intermediate_size"] = hf["moe_ffn_hidden_size"]
    kw["norm_topk_prob"] = bool(hf.get("norm_topk_prob", True))
    kw["hidden_act"] = "relu"
    kw["gated_mlp"] = True
    kw.setdefault("tie_word_embeddings", False)


def _hf_laguna(hf, kw):
    """Laguna (poolside, e.g. Laguna-XS.2): full and window attention mixed
    by `layer_types`, and the two kinds differ in SHAPE: their query heads
    (`num_attention_heads_per_layer`), their rope (`rope_parameters` by
    kind: YaRN over part of the head on the full layers, a plain rope on
    the window layers) while every head's output passes a sigmoid gate
    (`gating`); leading dense layers by `mlp_layer_types`, then
    `num_experts` sigmoid-routed experts (top-k renormalised, times
    `moe_routed_scaling_factor`, no selection bias, no groups) and one
    ungated shared expert. What config.json has no key for (the gate's
    form, the rope convention, no q/k norm) is the model type's, written
    in models/laguna.py."""
    L = hf["num_hidden_layers"]
    kinds = tuple(hf.get("layer_types") or ("full_attention",) * L)
    heads = tuple(int(h) for h in hf.get("num_attention_heads_per_layer")
                  or (hf["num_attention_heads"],) * L)
    mlp = tuple(hf.get("mlp_layer_types") or tuple(
        "dense" if l in (hf.get("mlp_only_layers") or ()) else "sparse"
        for l in range(L)))
    if min(len(kinds), len(heads), len(mlp)) < L or set(kinds) - {
            "full_attention", "sliding_attention"}:
        raise ValueError(
            f"layer_types, num_attention_heads_per_layer and mlp_layer_types "
            f"must name {L} layers ('full_attention' | 'sliding_attention'); "
            f"got {len(kinds)}, {len(heads)} and {len(mlp)} entries")
    # a config cut in depth may keep its per-layer lists: the first L count
    kinds, heads, mlp = kinds[:L], heads[:L], mlp[:L]
    sliding = tuple(int(t == "sliding_attention") for t in kinds)
    for s in (0, 1):
        if len({h for h, t in zip(heads, sliding) if t == s}) > 1:
            raise NotImplementedError(
                "laguna with query heads that differ inside one kind of "
                "layer: the weights are stacked by kind")
    n_dense = next((l for l, t in enumerate(mlp) if t != "dense"), L)
    if set(mlp[n_dense:]) - {"sparse"}:
        raise NotImplementedError(
            f"laguna with a dense feed-forward after a sparse one "
            f"(mlp_layer_types {mlp}): dense layers lead")
    if any(sliding) and not hf.get("sliding_window"):
        raise ValueError("layer_types names window layers but "
                         "sliding_window is not set")
    gating = hf.get("gating")
    if gating not in (None, False, True, "per-head", "per_head"):
        raise NotImplementedError(
            f"laguna with gating {gating!r}: the gate is written per head")
    rp = hf.get("rope_parameters") or {}
    full = dict(rp.get("full_attention") or {})
    local = dict(rp.get("sliding_attention") or {})
    if local.get("rope_type", "default") != "default":
        raise NotImplementedError(
            f"laguna with a scaled rope on the window layers ({local}): "
            "theirs is written plain")
    kw["rope_theta"] = float(full.pop("rope_theta", 10000.0))
    kw["partial_rotary_factor"] = full.pop(
        "partial_rotary_factor", hf.get("partial_rotary_factor", 1.0))
    kw["rope_scaling"] = (
        full if full.get("rope_type", "default") != "default" else None)
    kw["rope_local_theta"] = float(local.get("rope_theta", 10000.0))
    kw["rope_local_partial_rotary_factor"] = local.get(
        "partial_rotary_factor", 1.0)
    kw["sliding_window"] = hf.get("sliding_window") if any(sliding) else None
    kw["sliding_layers"], kw["heads_per_layer"] = sliding, heads
    kw["attn_gate"] = "per_head" if gating else None
    kw["first_k_dense_replace"] = n_dense
    kw["num_experts"] = hf.get("num_experts") or 0
    kw["num_experts_per_tok"] = hf.get("num_experts_per_tok") or 2
    kw["moe_intermediate_size"] = hf.get("moe_intermediate_size")
    kw["shared_expert_intermediate_size"] = hf.get(
        "shared_expert_intermediate_size")
    # `deepseek._router`'s sigmoid branch: one group, no selection bias
    kw["scoring_func"], kw["topk_method"] = "sigmoid", "noaux_tc"
    kw["n_group"] = kw["topk_group"] = 1
    kw["norm_topk_prob"] = bool(hf.get("norm_topk_prob", True))
    kw["routed_scaling_factor"] = hf.get("moe_routed_scaling_factor", 1.0)
    if hf.get("moe_router_logit_softcapping"):
        raise NotImplementedError("laguna with a softcapped router")
    if hf.get("moe_apply_router_weight_on_input"):
        raise NotImplementedError(
            "laguna with the router's weight on the experts' input")
    kw.setdefault("tie_word_embeddings", False)


def _hf_granitemoehybrid(hf, kw):
    """Granite 4.0-H (HF modeling_granitemoehybrid): Mamba-2 and attention
    layers by `layer_types`, every layer followed by top-k routed experts
    plus an always-on shared MLP, Granite's four multipliers, and no
    position encoding in the attention layers. `intermediate_size` is the
    width of ONE expert. What config.json has no key for (the router's
    softmax over the chosen logits, the gate before the mixer's norm) is
    the model type's, written in models/granitemoehybrid.py."""
    L = hf["num_hidden_layers"]
    kinds = tuple(hf.get("layer_types") or ("attention",) * L)
    if len(kinds) != L or set(kinds) - {"mamba", "attention"}:
        raise ValueError(
            f"layer_types must name {L} layers as 'mamba' or 'attention'; "
            f"got {len(kinds)}: {sorted(set(kinds))}")
    kw["layer_types"] = kinds
    pe = hf.get("position_embedding_type", "nope")
    if pe != "nope":
        raise NotImplementedError(
            f"granitemoehybrid with position_embedding_type {pe!r}: the "
            "attention layers are written without a position encoding")
    kw["position_embedding_type"] = pe
    if hf.get("mamba_proj_bias"):
        raise NotImplementedError("granitemoehybrid with mamba_proj_bias")
    if hf.get("mamba_n_groups", 1) != 1:
        raise NotImplementedError(
            f"granitemoehybrid with mamba_n_groups "
            f"{hf['mamba_n_groups']}: the mixer is written for one group "
            "of B and C")
    kw["mamba_n_heads"] = hf.get("mamba_n_heads", 128)
    kw["mamba_d_head"] = hf.get("mamba_d_head", 64)
    kw["mamba_d_state"] = hf.get("mamba_d_state", 128)
    kw["mamba_d_conv"] = hf.get("mamba_d_conv", 4)
    kw["mamba_n_groups"] = hf.get("mamba_n_groups", 1)
    kw["mamba_chunk_size"] = hf.get("mamba_chunk_size", 256)
    inner = hf.get("mamba_expand", 2) * hf["hidden_size"]
    if kw["mamba_n_heads"] * kw["mamba_d_head"] != inner:
        raise ValueError(
            f"mamba_n_heads x mamba_d_head = "
            f"{kw['mamba_n_heads'] * kw['mamba_d_head']} is not "
            f"mamba_expand x hidden_size = {inner}")
    kw["num_experts"] = hf.get("num_local_experts", 0)
    kw["num_experts_per_tok"] = hf.get("num_experts_per_tok", 2)
    kw["moe_intermediate_size"] = hf["intermediate_size"]
    kw["shared_intermediate_size"] = hf.get("shared_intermediate_size")
    kw["embedding_scale"] = hf.get("embedding_multiplier", 1.0)
    kw["residual_scale"] = hf.get("residual_multiplier", 1.0)
    kw["attn_scale"] = hf.get("attention_multiplier")
    kw["logit_scale"] = 1.0 / hf.get("logits_scaling", 1.0)
    kw.setdefault("tie_word_embeddings", True)


def _hf_jamba(hf, kw):
    """Jamba (HF modeling_jamba): Mamba-1 layers with an attention layer
    every `attn_layer_period` (HF's `layers_block_type`: attention where
    `i % period == offset`), a SwiGLU MLP after every mixer, no position
    encoding anywhere. `num_logits_to_keep`, `use_mamba_kernels` and a null
    `sliding_window` are read by nothing. Refused by name: the sparse
    layers of the larger siblings, a projection bias, a window."""
    if hf.get("num_experts", 1) > 1:
        raise NotImplementedError(
            f"jamba with num_experts {hf['num_experts']}: the layers are "
            "written with the dense MLP (HF's JambaMLP, what num_experts 1 "
            "takes)")
    if hf.get("mamba_proj_bias"):
        raise NotImplementedError("jamba with mamba_proj_bias")
    if hf.get("sliding_window") is not None:
        raise NotImplementedError(
            f"jamba with sliding_window {hf['sliding_window']}: the "
            "attention layers are written over the whole context")
    L = hf["num_hidden_layers"]
    period = hf.get("attn_layer_period", 8)
    offset = hf.get("attn_layer_offset", 4)
    kw["layer_types"] = tuple(
        "attention" if i % period == offset else "mamba" for i in range(L))
    kw["position_embedding_type"] = "nope"
    kw["mamba_expand"] = hf.get("mamba_expand", 2)
    kw["mamba_d_state"] = hf.get("mamba_d_state", 16)
    kw["mamba_d_conv"] = hf.get("mamba_d_conv", 4)
    rank = hf.get("mamba_dt_rank", 256)
    kw["mamba_dt_rank"] = (-(-hf["hidden_size"] // 16) if rank == "auto"
                           else rank)
    if not hf.get("mamba_conv_bias", True):
        raise NotImplementedError("jamba without mamba_conv_bias")
    kw.setdefault("rms_norm_eps", 1e-6)
    kw.setdefault("tie_word_embeddings", False)


def _hf_lfm2_moe(hf, kw):
    """LFM2-MoE (HF modeling_lfm2_moe): gated short-convolution layers with
    a GQA layer every few (`layer_types`: "conv" | "full_attention"), q/k
    RMSNorm a head before a plain rope, `num_dense_layers` leading layers
    with a dense SwiGLU and then sigmoid-routed experts with a selection
    bias (`deepseek._router`'s sigmoid branch, one group). Refused by name:
    a convolution bias, a scaled rope."""
    L = hf["num_hidden_layers"]
    kinds = tuple("attention" if t == "full_attention" else t
                  for t in hf.get("layer_types") or ())
    if len(kinds) != L or set(kinds) - {"conv", "attention"}:
        raise ValueError(
            f"layer_types must name {L} layers as 'conv' or "
            f"'full_attention'; got {len(kinds)}: {sorted(set(kinds))}")
    if hf.get("conv_bias"):
        raise NotImplementedError("lfm2_moe with conv_bias")
    rp = hf.get("rope_parameters") or {}
    if rp.get("rope_type", "default") != "default" or hf.get("rope_scaling"):
        raise NotImplementedError(
            f"lfm2_moe with a scaled rope ({rp or hf['rope_scaling']}): the "
            "attention layers' is written plain")
    kw["rope_theta"] = float(rp.get("rope_theta",
                                    hf.get("rope_theta", 1000000.0)))
    kw["layer_types"] = kinds
    kw["conv_l_cache"] = hf.get("conv_L_cache", 3)
    kw["rms_norm_eps"] = hf.get("norm_eps", 1e-5)
    kw["qk_norm"] = True
    kw["first_k_dense_replace"] = hf.get("num_dense_layers", 2)
    kw["num_experts"] = hf.get("num_experts") or 0
    kw["num_experts_per_tok"] = hf.get("num_experts_per_tok") or 2
    kw["moe_intermediate_size"] = hf.get("moe_intermediate_size")
    # the sigmoid branch with one group; `use_expert_bias` false is a bias
    # of zeros (convert/hf.py), which chooses as the scores do
    kw["scoring_func"], kw["topk_method"] = "sigmoid", "noaux_tc"
    kw["n_group"] = kw["topk_group"] = 1
    kw["norm_topk_prob"] = bool(hf.get("norm_topk_prob", True))
    kw["routed_scaling_factor"] = hf.get("routed_scaling_factor", 1.0)
    kw.setdefault("tie_word_embeddings", True)


#: the key a configuration of one expert-parallel rank carries beside the
#: source's own (no public config.json has it): the router's width and the
#: id of the first expert held, `n_routed_experts` then counting the held
EXPERT_SHARE_KEY = "expert_parallel_share"


def _hf_solar_open2(hf, kw):
    """Solar-Open2 (upstage/Solar-Open2-250B): Kimi delta attention layers
    with a gated NoPE GQA layer at each index of `gqa_layers`, every layer
    followed by sigmoid-routed experts with a selection bias and one
    ungated shared expert (`deepseek._router`'s `noaux_tc` branch with one
    group). Refused by name: a rope, leading dense layers, a full-rank
    decay projection, an ungated GQA layer. `EXPERT_SHARE_KEY`
    (`{"router_experts": 320, "first_expert": 0}`) makes `n_routed_experts`
    the count HELD here of a router that wide."""
    L = hf["num_hidden_layers"]
    if hf.get("use_rope"):
        raise NotImplementedError(
            "solar_open2 with use_rope: the GQA layers are written without "
            "positions")
    if hf.get("first_k_dense_replace", 0):
        raise NotImplementedError(
            f"solar_open2 with first_k_dense_replace "
            f"{hf['first_k_dense_replace']}: every layer is written sparse")
    if hf.get("kda_use_full_proj"):
        raise NotImplementedError(
            "solar_open2 with kda_use_full_proj: the decay and the output "
            "gate are written as low-rank pairs")
    if not hf.get("use_gqa_gate", True):
        raise NotImplementedError("solar_open2 without use_gqa_gate")
    if not hf.get("kda_allow_neg_eigval", True):
        raise NotImplementedError(
            "solar_open2 without kda_allow_neg_eigval: beta is written "
            "doubled")
    gqa = set(hf.get("gqa_layers") or ())
    if not gqa <= set(range(L)):
        raise ValueError(f"gqa_layers {sorted(gqa)} name layers past {L}")
    kw["layer_types"] = tuple(
        "attention" if i in gqa else "kda" for i in range(L))
    lin = hf.get("linear_attn_config") or {}
    kw["kda_heads"] = lin.get("num_heads", hf["num_attention_heads"])
    kw["kda_head_dim"] = lin.get("head_dim", 128)
    if lin.get("num_kv_heads") not in (None, kw["kda_heads"]):
        raise NotImplementedError(
            f"solar_open2 with {lin['num_kv_heads']} KDA key heads for "
            f"{kw['kda_heads']}: a head's state is written square")
    kw["conv_l_cache"] = lin.get("short_conv_kernel_size", 4)
    kw["position_embedding_type"] = "nope"
    kw["rms_norm_eps"] = hf.get("rms_norm_eps", 1e-5)
    kw["num_experts"] = hf.get("n_routed_experts") or 0
    kw["num_experts_per_tok"] = hf.get("num_experts_per_tok") or 8
    kw["moe_intermediate_size"] = hf.get("moe_intermediate_size")
    kw["n_shared_experts"] = hf.get("n_shared_experts")
    kw["scoring_func"], kw["topk_method"] = "sigmoid", "noaux_tc"
    kw["n_group"] = kw["topk_group"] = 1
    kw["norm_topk_prob"] = bool(hf.get("norm_topk_prob", True))
    kw["routed_scaling_factor"] = hf.get("routed_scaling_factor", 1.0)
    share = hf.get(EXPERT_SHARE_KEY)
    if share:
        kw["router_experts"] = int(share["router_experts"])
        kw["first_expert"] = int(share.get("first_expert", 0))
    kw.setdefault("tie_word_embeddings", False)


def _hf_qwen3_moe(hf, kw):
    _hf_qwen3(hf, kw)
    kw["num_experts"] = hf.get("num_experts", 128)
    kw["num_experts_per_tok"] = hf.get("num_experts_per_tok", 8)
    kw["moe_intermediate_size"] = hf.get("moe_intermediate_size", 768)
    kw["norm_topk_prob"] = hf.get("norm_topk_prob", False)  # HF default
    if hf.get("mlp_only_layers") or hf.get("decoder_sparse_step", 1) != 1:
        # mixed dense/MoE stacks would hit the translator with dense
        # layers lacking expert weights — fail with a clear message
        raise NotImplementedError(
            "qwen3_moe with mlp_only_layers/decoder_sparse_step != 1"
        )


BLOCK_STRATEGIES = ("sequential", "low_confidence_static",
                    "low_confidence_dynamic")


def _hf_sdar_moe(hf, kw):
    """SDAR-MoE (JetLM SDAR-30B-A3B-Chat): Qwen3-MoE's network, generated
    by diffusion over blocks. The source's config.json gives none of the
    five keys below; the defaults are the family's released chat
    checkpoints' (block of 4, the dynamic low-confidence reveal at 0.9,
    `<|MASK|>` = 151669), and a config may say otherwise: they are
    constants of an engine (serving/blocks.py)."""
    _hf_qwen3_moe(hf, kw)
    kw["block_length"] = int(hf.get("block_length", 4))
    kw["denoising_steps"] = int(hf.get("denoising_steps",
                                       kw["block_length"]))
    kw["remasking_strategy"] = hf.get("remasking_strategy",
                                      "low_confidence_dynamic")
    kw["confidence_threshold"] = float(hf.get("confidence_threshold", 0.9))
    kw["mask_token_id"] = int(hf.get("mask_token_id", 151669))
    if kw["block_length"] < 1:
        raise ValueError("sdar_moe needs a block_length of at least 1")
    if kw["remasking_strategy"] not in BLOCK_STRATEGIES:
        raise ValueError(
            f"remasking_strategy must be one of {BLOCK_STRATEGIES}; got "
            f"{kw['remasking_strategy']!r}")
    if not 0 <= kw["mask_token_id"] < hf["vocab_size"]:
        raise ValueError(
            f"mask_token_id {kw['mask_token_id']} outside the vocabulary "
            f"of {hf['vocab_size']}")


def _hf_phi(hf, kw):
    """Phi-1/1.5/2 (HF modeling_phi): parallel attn+mlp sharing ONE
    input layernorm (the translator duplicates it, like falcon-7b),
    biased linears everywhere incl. the lm head, partial rotary,
    gelu_new MLP."""
    kw["norm_type"] = "layernorm"
    kw["norm_bias"] = True
    kw["parallel_residual"] = True
    kw["gated_mlp"] = False
    kw["mlp_bias"] = True
    kw["attention_bias"] = True
    kw["attention_out_bias"] = True
    kw["lm_head_bias"] = True
    kw["rms_norm_eps"] = hf.get("layer_norm_eps", 1e-5)
    kw.setdefault("partial_rotary_factor", hf.get("partial_rotary_factor", 0.5))
    kw["hidden_act"] = hf.get("hidden_act", "gelu_new")
    if hf.get("qk_layernorm"):
        # the translator would silently drop q/k layernorm weights
        raise NotImplementedError("phi with qk_layernorm=True")


def _hf_baichuan_m1(hf, kw):
    """Baichuan-M1: llama numerics + fused W_pack + kernel-2 K/V conv
    (models/baichuan_m1.py). The reference ignores the config's sliding
    window (baichuan_m1.py:216); so do we."""
    kw.setdefault("attention_bias", False)
    kw.pop("sliding_window", None)


def _hf_qwen(hf, kw):
    """Qwen v1 (Qwen-7B/14B remote code, reference models/qwen.py):
    fused biased c_attn, bias-free c_proj, RMSNorm, MHA, and an MLP
    whose HF intermediate_size is the SUM of the two halves (w1/w2 each
    project to intermediate//2; out = c_proj(w1(x) * silu(w2(x)))).
    Optional logn attention scaling beyond the training length."""
    kw["attention_bias"] = True
    kw["attention_out_bias"] = False
    kw["intermediate_size"] = hf.get("intermediate_size", 22016) // 2
    kw["rms_norm_eps"] = hf.get("layer_norm_epsilon", 1e-6)
    kw["max_position_embeddings"] = hf.get(
        "max_position_embeddings", hf.get("seq_length", 8192))
    if hf.get("use_logn_attn"):
        kw["logn_attn"] = True
        kw["logn_train_len"] = hf.get("seq_length", 8192)
    if "visual" in hf:  # Qwen-VL: <img>pad...pad</img> placeholders
        kw["image_token_id"] = hf["visual"].get("image_start_id", 151857) + 2
    # qwen's dynamic NTK adapts the rope base to the live sequence
    # length; fixed-shape TPU programs pin it at the training length
    # (exact within seq_length; longer contexts need an explicit
    # rope_scaling override)


def _hf_deci(hf, kw):
    """DeciLM: llama with VARIABLE GQA (num_key_value_heads_per_layer).
    Scan-stacked layers need uniform shapes, so ingest replicates each
    layer's kv heads up to the max — numerically exact (repeat_kv
    commutes with GQA grouping; convert/hf._deci_layer)."""
    per_layer = hf.get("num_key_value_heads_per_layer")
    if per_layer:
        kw["num_key_value_heads"] = max(per_layer)
    kw.setdefault("attention_bias", False)


def _hf_gptbigcode(hf, kw):
    """GPT-BigCode (starcoder v1, reference models/gptbigcode.py):
    gpt2-style learned positions + layernorm + non-gated gelu MLP, but
    nn.Linear weights (not Conv1D) and multi-query attention (1 kv
    head) via a [H + 2*head_dim] fused c_attn."""
    kw["hidden_size"] = hf.get("n_embd", 768)
    kw["num_hidden_layers"] = hf.get("n_layer", 12)
    kw["num_attention_heads"] = hf.get("n_head", 12)
    kw["num_key_value_heads"] = 1 if hf.get("multi_query", True) else (
        kw["num_attention_heads"])
    kw["intermediate_size"] = hf.get("n_inner") or 4 * kw["hidden_size"]
    kw["max_position_embeddings"] = hf.get("n_positions", 1024)
    kw["rms_norm_eps"] = hf.get("layer_norm_epsilon", 1e-5)
    kw["norm_type"] = "layernorm"
    kw["norm_bias"] = True
    kw["gated_mlp"] = False
    kw["mlp_bias"] = True
    kw["attention_bias"] = True
    kw["attention_out_bias"] = True
    kw["learned_positions"] = True
    kw["hidden_act"] = hf.get("activation_function", "gelu_pytorch_tanh")
    kw.setdefault("tie_word_embeddings", True)


def _hf_phixtral(hf, kw):
    """Phixtral (mlabonne MoE over phi-2 experts, reference
    models/phixtral.py): phi's parallel-residual/biased/partial-rotary
    decoder with mixtral-style top-k routing over NON-GATED fc1/fc2
    experts; routing weights renormalize after top-k. Configs use the
    legacy mixformer schema (n_embd/n_layer/rotary_dim)."""
    _hf_phi(hf, kw)
    kw["hidden_size"] = hf.get("n_embd", 2560)
    kw["num_hidden_layers"] = hf.get("n_layer", 32)
    kw["num_attention_heads"] = hf.get("n_head", 32)
    kw["num_key_value_heads"] = hf.get("n_head_kv") or kw["num_attention_heads"]
    kw["intermediate_size"] = hf.get("n_inner") or 4 * kw["hidden_size"]
    kw["max_position_embeddings"] = hf.get("n_positions", 2048)
    kw["num_experts"] = hf.get("num_local_experts", 4)
    kw["num_experts_per_tok"] = hf.get("num_experts_per_tok", 2)
    kw["norm_topk_prob"] = True
    kw["rms_norm_eps"] = hf.get("layer_norm_epsilon", 1e-5)
    kw["hidden_act"] = hf.get("activation_function", "gelu_new")
    kw["lm_head_bias"] = True
    if "rotary_dim" in hf:
        head_dim = kw["hidden_size"] // kw["num_attention_heads"]
        kw["partial_rotary_factor"] = hf["rotary_dim"] / head_dim


def _hf_cohere(hf, kw):
    """Cohere / Command-R: bias-free LayerNorm, parallel attn+mlp over
    one shared norm, interleaved rope, logits scaled by logit_scale,
    tied embeddings."""
    kw["norm_type"] = "layernorm"
    kw["parallel_residual"] = True
    kw["rope_interleaved"] = True
    kw["rms_norm_eps"] = hf.get("layer_norm_eps", 1e-5)
    kw["logit_scale"] = hf.get("logit_scale", 0.0625)
    kw["attention_bias"] = bool(hf.get("attention_bias", False))
    kw.setdefault("tie_word_embeddings", hf.get("tie_word_embeddings", True))
    if hf.get("use_qk_norm"):
        raise NotImplementedError(
            "cohere use_qk_norm=True (per-head LayerNorm) is not supported"
        )


def _hf_janus(hf, kw):
    """Janus/Janus-Pro understanding path: the merged text_config is
    llama-shaped; keep the image placeholder id for the feature
    scatter (models/janus.py)."""
    kw["image_token_id"] = hf.get("image_token_id", hf.get("image_token_index"))


def _hf_internvl(hf, kw):
    """InternVL (HF-converted layout): the merged text_config is
    qwen2 or llama shaped; apply the text architecture's defaults and
    keep the image token id (models/internvl.py scatters features
    there)."""
    inner = (hf.get("text_config") or {}).get("model_type", "qwen2")
    if inner == "qwen2":
        kw.setdefault("attention_bias", True)
    kw["image_token_id"] = hf.get("image_token_id", hf.get("image_token_index"))


def _hf_mllama(hf, kw):
    """Mllama / Llama-3.2-Vision text side (reference models/mllama.py;
    HF MllamaTextConfig — from_hf_config already merged the nested
    text_config). The embedding table carries 8 extra special-image rows
    beyond vocab_size (handled by the translator); lm_head stays at
    vocab_size."""
    kw["cross_attention_layers"] = tuple(
        int(i) for i in hf.get("cross_attention_layers", ())
    )


def _hf_minicpmv(hf, kw):
    """MiniCPM-V (reference models/minicpmv.py): the LLM half is
    llama3-shaped (2_5) or qwen2-shaped (2_6, version >= 2.6 in
    config.json); vision/resampler configs are consumed separately by
    models/minicpmv.py. The image placeholder id comes from the
    tokenizer's <unk>/<image> id — overridable at generate time."""
    if float(hf.get("version", 2.6)) >= 2.6:
        kw.setdefault("attention_bias", True)  # qwen2 qkv bias
    kw.setdefault("image_token_id", hf.get("image_token_id", 0))


def _hf_minicpmo(hf, kw):
    """MiniCPM-o 2.6 (reference convert.py:1030-1041, 1963-1983): the
    LLM half is qwen2-shaped at the top level of config.json; vision
    (SigLIP + resampler) and audio (Whisper encoder + projection)
    configs are consumed separately by models/minicpmo.py."""
    kw.setdefault("attention_bias", True)  # qwen2 qkv bias
    kw.setdefault("image_token_id", hf.get("image_token_id", 0))
    # no silent default: the published config carries no audio_token_id,
    # and defaulting it to 0 would collide with the image placeholder —
    # callers set it from their tokenizer (models/minicpmo.py docstring)
    if "audio_token_id" in hf:
        kw.setdefault("audio_token_id", hf["audio_token_id"])
    # default (2) lives in one place: models/minicpmo.DEFAULT_AUDIO_POOL_STEP
    if "audio_pool_step" in hf:
        kw.setdefault("audio_pool_step", hf["audio_pool_step"])


def _hf_qwen2_audio(hf, kw):
    """Qwen2-Audio (reference convert.py:969-971, 1655-1656): the text
    half is qwen2 (nested text_config, merged by from_hf_config); the
    <|AUDIO|> placeholder id is the top-level audio_token_index."""
    kw.setdefault("attention_bias", True)  # qwen2 qkv bias
    if hf.get("audio_token_index") is not None:
        kw.setdefault("audio_token_id", hf["audio_token_index"])


def _hf_yuan(hf, kw):
    """Yuan-2 (reference models/yuan.py; original schema in
    gguf/models/model_implement/yuan2/configuration_yuan.py): llama
    fields + LFA conv filter handled by models/yuan.py."""
    kw.setdefault(
        "max_position_embeddings",
        hf.get("model_max_length", hf.get("max_position_embeddings", 8192)),
    )


def _hf_falcon(hf, kw):
    """Falcon (reference gguf/models/falcon.py; HF modeling_falcon.py).
    Three variants: falcon-rw (alibi, sequential residual), falcon-7b
    (multi-query + parallel attn/mlp sharing ONE input layernorm — the
    translator duplicates it into attn_norm/mlp_norm), falcon-40b/180b
    (new_decoder_architecture: GQA + separate ln_attn/ln_mlp)."""
    kw["num_attention_heads"] = hf.get("num_attention_heads", hf.get("n_head", 71))
    kw["num_hidden_layers"] = hf.get("num_hidden_layers", hf.get("n_layer", 32))
    if hf.get("new_decoder_architecture"):
        kw["num_key_value_heads"] = hf.get("num_kv_heads", 8)
    elif hf.get("multi_query", True):
        kw["num_key_value_heads"] = 1
    else:
        kw["num_key_value_heads"] = kw["num_attention_heads"]
    kw["intermediate_size"] = hf.get("ffn_hidden_size") or 4 * hf.get(
        "hidden_size", 4544
    )
    kw["rms_norm_eps"] = hf.get("layer_norm_epsilon", 1e-5)
    kw["norm_type"] = "layernorm"
    kw["norm_bias"] = True
    kw["gated_mlp"] = False
    kw["hidden_act"] = "gelu"
    kw["mlp_bias"] = bool(hf.get("bias", False))
    kw["attention_bias"] = bool(hf.get("bias", False))
    kw["attention_out_bias"] = bool(hf.get("bias", False))
    kw["parallel_residual"] = bool(
        hf.get("parallel_attn", True) or hf.get("new_decoder_architecture")
    )
    if hf.get("alibi"):
        kw["alibi"] = True
        head_dim = hf.get("hidden_size", 4544) // kw["num_attention_heads"]
        kw["alibi_scale"] = head_dim ** -0.5
    kw.setdefault("tie_word_embeddings", hf.get("tie_word_embeddings", True))


def _hf_rwkv(hf, kw):
    """RWKV v4 (HF `rwkv` config schema: modeling_rwkv.py in
    transformers; reference models/rwkv4.py). layer_norm_epsilon feeds
    every LayerNorm; rescale_every is an fp16-overflow trick HF applies
    only in half precision — exact under LN invariance, skipped here
    (we compute the recurrence in f32)."""
    kw["attention_hidden_size"] = hf.get(
        "attention_hidden_size", hf.get("hidden_size", 4096)
    )
    kw["intermediate_size"] = (
        hf.get("intermediate_size") or 4 * hf.get("hidden_size", 4096)
    )
    kw["rms_norm_eps"] = hf.get("layer_norm_epsilon", 1e-5)
    kw["norm_type"] = "layernorm"
    kw["max_position_embeddings"] = hf.get("context_length", 1024)
    kw.setdefault("num_attention_heads", 1)
    kw["num_key_value_heads"] = kw["num_attention_heads"]
    kw["tie_word_embeddings"] = bool(hf.get("tie_word_embeddings", False))


def _hf_rwkv5(hf, kw):
    """RWKV v5 "Eagle" (trust_remote_code schema, e.g. rwkv-5-world;
    reference models/rwkv5.py): multi-head matrix state with head_size
    (64), gate branch, GroupNorm ln_x whose eps scales with
    head_size_divisor."""
    _hf_rwkv(hf, kw)
    kw["rwkv_head_size"] = hf.get("head_size", 64)
    kw["rwkv_group_norm_eps"] = 1e-5 * float(hf.get("head_size_divisor", 8)) ** 2
    kw["num_attention_heads"] = kw["attention_hidden_size"] // kw["rwkv_head_size"]
    kw["num_key_value_heads"] = kw["num_attention_heads"]


_HF_BUILDERS = {
    "qwen2": _hf_qwen2,
    "qwen2_vl": _hf_qwen2_vl,
    "chatglm": _hf_chatglm,
    "mpt": _hf_mpt,
    "gemma": _hf_gemma,
    "gemma2": _hf_gemma2,
    "gemma3": _hf_gemma3,
    "gemma3_text": _hf_gemma3,
    "phi3": _hf_phi3,
    # phi-3-vision: the reference optimizes it as phi3 (convert.py:947,
    # :1829 `in ["phi3", "phi3_v"]`); text fields are phi3's, the CLIP
    # tower weights are simply not loaded on the text path
    "phi3_v": _hf_phi3,
    "stablelm": _hf_stablelm,
    "starcoder2": _hf_starcoder2,
    "baichuan": _hf_baichuan,
    "internlm2": _hf_internlm2,
    # internlm-xcomposer2: internlm2 decoder + per-linear Plora deltas
    # that apply only to image-token rows (reference convert.py:984,
    # :1523); the text path (im_mask=None) is exactly internlm2, and the
    # Plora_A/B checkpoint keys are ignored by the internlm2 translation
    "internlmxcomposer2": _hf_internlm2,
    "internlm": _hf_internlm,
    "minicpm": _hf_minicpm,
    "minicpm_sala": _hf_minicpm_sala,
    "glm": _hf_glm,
    "gpt2": _hf_gpt2,
    "bloom": _hf_bloom,
    "gpt_neox": _hf_gptneox,
    "mixtral": _hf_mixtral,
    "qwen2_moe": _hf_qwen2_moe,
    "rwkv": _hf_rwkv,
    "rwkv5": _hf_rwkv5,
    "falcon": _hf_falcon,
    "yuan": _hf_yuan,
    "minicpmv": _hf_minicpmv,
    "minicpmo": _hf_minicpmo,
    "qwen2_audio": _hf_qwen2_audio,
    "mllama": _hf_mllama,
    "mllama_text_model": _hf_mllama,
    "deepseek_v2": _hf_deepseek_v2,
    "deepseek_v3": _hf_deepseek_v3,
    "minicpm3": _hf_minicpm3,
    "glm4_moe_lite": _hf_glm4_moe_lite,
    "internvl": _hf_internvl,
    "internvl_chat": _hf_internvl,
    "janus": _hf_janus,
    "multi_modality": _hf_janus,  # janus checkpoints' original model_type
    "qwen3": _hf_qwen3,
    "brumby": _hf_brumby,
    "granitemoehybrid": _hf_granitemoehybrid,
    "jamba": _hf_jamba,
    "lfm2_moe": _hf_lfm2_moe,
    "solar_open2": _hf_solar_open2,
    "smallthinker": _hf_smallthinker,
    "laguna": _hf_laguna,
    "qwen3_moe": _hf_qwen3_moe,
    "sdar_moe": _hf_sdar_moe,
    "phi": _hf_phi,
    "cohere": _hf_cohere,
    "qwen": _hf_qwen,
    "qwen_vl": _hf_qwen,  # Qwen-VL ships model_type "qwen" + visual dict
    "chatglm4v": _hf_chatglm,  # glm-4v: chatglm text schema + vision_config
    "deci": _hf_deci,
    "gpt_bigcode": _hf_gptbigcode,
    "phixtral": _hf_phixtral,
    "baichuan_m1": _hf_baichuan_m1,
}


# Canonical shapes for tests and benchmarks (no checkpoints needed).
PRESETS: dict[str, ModelConfig] = {
    "tiny-llama": ModelConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128,
    ),
    "llama2-7b": ModelConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=11008,
        num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=32,
    ),
    "llama3-8b": ModelConfig(
        vocab_size=128256, hidden_size=4096, intermediate_size=14336,
        num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=8,
        rope_theta=500000.0, max_position_embeddings=8192,
    ),
    "mistral-7b": ModelConfig(
        model_type="mistral", vocab_size=32000, hidden_size=4096,
        intermediate_size=14336, num_hidden_layers=32,
        num_attention_heads=32, num_key_value_heads=8,
        sliding_window=4096, rope_theta=1000000.0,
    ),
    "qwen2-7b": ModelConfig(
        model_type="qwen2", vocab_size=152064, hidden_size=3584,
        intermediate_size=18944, num_hidden_layers=28,
        num_attention_heads=28, num_key_value_heads=4,
        attention_bias=True, rope_theta=1000000.0,
    ),
    "gemma2-9b": ModelConfig(
        model_type="gemma2", vocab_size=256000, hidden_size=3584,
        intermediate_size=14336, num_hidden_layers=42,
        num_attention_heads=16, num_key_value_heads=8, head_dim=256,
        scale_embeddings=True, rms_norm_offset=True, post_attn_norm=True,
        attn_logit_softcap=50.0, final_logit_softcap=30.0,
        sliding_window=4096, sliding_window_pattern=2,
        attn_scale=224.0 ** -0.5, tie_word_embeddings=True,
        hidden_act="gelu_pytorch_tanh",
    ),
    "phi3-mini": ModelConfig(
        model_type="phi3", vocab_size=32064, hidden_size=3072,
        intermediate_size=8192, num_hidden_layers=32,
        num_attention_heads=32, num_key_value_heads=32,
        max_position_embeddings=4096,
    ),
    # Mixtral-8x7B-v0.1's config.json; tests hold it to the `published`
    # block of bench/configs/mixtral-8x7b-int4.json
    "mixtral-8x7b": ModelConfig(
        model_type="mixtral", vocab_size=32000, hidden_size=4096,
        intermediate_size=14336, num_hidden_layers=32,
        num_attention_heads=32, num_key_value_heads=8,
        rope_theta=1000000.0, rms_norm_eps=1e-05,
        max_position_embeddings=32768, sliding_window=None,
        num_experts=8, num_experts_per_tok=2, norm_topk_prob=True,
    ),
    # granite-4.0-h's shape at toy sizes: two runs of Mamba-2 layers around
    # one NoPE attention layer, 8 experts top-3 and a shared MLP
    # (tests/test_granitemoehybrid.py holds it to its config.json form)
    "tiny-granite-hybrid": ModelConfig(
        model_type="granitemoehybrid", vocab_size=256, hidden_size=64,
        intermediate_size=32, num_hidden_layers=5,
        num_attention_heads=4, num_key_value_heads=2,
        tie_word_embeddings=True, rms_norm_eps=1e-5,
        layer_types=("mamba", "mamba", "attention", "mamba", "mamba"),
        mamba_n_heads=4, mamba_d_head=32, mamba_d_state=16, mamba_d_conv=4,
        mamba_n_groups=1, mamba_chunk_size=8,
        position_embedding_type="nope", num_experts=8,
        num_experts_per_tok=3, moe_intermediate_size=32,
        shared_intermediate_size=64, embedding_scale=12,
        residual_scale=0.22, attn_scale=0.0625, logit_scale=0.25,
    ),
    # Jamba's shape at toy sizes: Mamba-1 layers (inner 256, a [16, 256]
    # state, dt of rank 8) with one multi-query NoPE attention layer among
    # them (period 4, offset 2), a SwiGLU MLP after every mixer
    # (tests/test_jamba.py holds it to its config.json form)
    "tiny-jamba": ModelConfig(
        model_type="jamba", vocab_size=256, hidden_size=128,
        intermediate_size=256, num_hidden_layers=4, num_attention_heads=4,
        num_key_value_heads=1, tie_word_embeddings=True, rms_norm_eps=1e-6,
        layer_types=("mamba", "mamba", "attention", "mamba"),
        mamba_expand=2, mamba_d_state=16, mamba_d_conv=4, mamba_dt_rank=8,
        position_embedding_type="nope",
    ),
    # LFM2-MoE's shape at toy sizes: a dense convolution layer, then
    # sparse ones (8 sigmoid-routed experts top-2 with a selection bias)
    # with two attention layers of 4 KV heads of 64 among them, so that the
    # pool keeps two lane pairs a row (tests/test_lfm2_moe.py holds it to
    # its config.json form; the dense combine, so that nothing is dropped)
    "tiny-lfm2-moe": ModelConfig(
        model_type="lfm2_moe", vocab_size=256, hidden_size=512,
        intermediate_size=128, num_hidden_layers=6, num_attention_heads=8,
        num_key_value_heads=4, tie_word_embeddings=True,
        rms_norm_eps=1e-5, rope_theta=1000000.0,
        max_position_embeddings=4096, qk_norm=True, conv_l_cache=3,
        layer_types=("conv", "attention", "conv", "conv", "attention",
                     "conv"),
        first_k_dense_replace=1, num_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=64, scoring_func="sigmoid",
        topk_method="noaux_tc", n_group=1, topk_group=1,
        norm_topk_prob=True, routed_scaling_factor=1.0,
        moe_dispatch="dense",
    ),
    # Solar-Open2's shape at toy sizes: a gated NoPE GQA layer, two Kimi
    # delta attention layers (2 heads of 128 behind three convolutions of 4
    # taps), a GQA layer again; 8 sigmoid-routed experts with a selection
    # bias, top-2, and one shared expert (tests/test_solar_open2.py holds it
    # to an HF config dict, and serves a rank's share of it)
    "tiny-solar-open2": ModelConfig(
        model_type="solar_open2", vocab_size=256, hidden_size=256,
        intermediate_size=512, num_hidden_layers=4, num_attention_heads=4,
        num_key_value_heads=2, head_dim=128, rms_norm_eps=1e-5,
        max_position_embeddings=4096, tie_word_embeddings=False,
        position_embedding_type="nope",
        layer_types=("attention", "kda", "kda", "attention"),
        kda_heads=2, kda_head_dim=128, conv_l_cache=4, num_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=64, n_shared_experts=1,
        scoring_func="sigmoid", topk_method="noaux_tc", n_group=1,
        topk_group=1, norm_topk_prob=True, routed_scaling_factor=1.0,
        moe_dispatch="dense",
    ),
    # SmallThinker's shape at toy sizes: two periods of one full NoPE layer
    # and three window layers with a rope, 8 ReLU-gated experts top-3
    # routed from the layer's input, a window shorter than the tests'
    # sequences (tests/test_smallthinker.py)
    "tiny-smallthinker": ModelConfig(
        model_type="smallthinker", vocab_size=256, hidden_size=64,
        num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2,
        head_dim=32, rms_norm_eps=1e-6, rope_theta=1.5e6,
        max_position_embeddings=256, sliding_window=32,
        sliding_layers=(0, 1, 1, 1) * 2, rope_layers=(0, 1, 1, 1) * 2,
        num_experts=8, num_experts_per_tok=3, moe_intermediate_size=32,
        norm_topk_prob=True, hidden_act="relu",
    ),
    # Laguna's shape at toy sizes: two periods of one full layer (6 gated
    # query heads, YaRN over half the head) and three window layers (8, a
    # plain rope) over 2 KV heads, a dense first layer, then 16
    # sigmoid-routed experts top-4 and a shared one, a window shorter than
    # the tests' sequences (tests/test_laguna.py holds it to its
    # config.json form; the dense combine, so that nothing is dropped)
    "tiny-laguna": ModelConfig(
        model_type="laguna", vocab_size=256, hidden_size=64,
        intermediate_size=128, num_hidden_layers=8, num_attention_heads=6,
        num_key_value_heads=2, head_dim=32, rms_norm_eps=1e-6,
        max_position_embeddings=4096, sliding_window=32,
        sliding_layers=(0, 1, 1, 1) * 2, heads_per_layer=(6, 8, 8, 8) * 2,
        attn_gate="per_head", rope_theta=500000.0,
        rope_scaling={"rope_type": "yarn", "factor": 16.0,
                      "original_max_position_embeddings": 64,
                      "beta_fast": 8.0, "beta_slow": 1.0,
                      "attention_factor": 1.2772588722239782},
        partial_rotary_factor=0.5, rope_local_theta=10000.0,
        rope_local_partial_rotary_factor=1.0, first_k_dense_replace=1,
        num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, scoring_func="sigmoid",
        topk_method="noaux_tc", n_group=1, topk_group=1,
        norm_topk_prob=True, routed_scaling_factor=2.5,
        moe_dispatch="dense",
    ),
}
