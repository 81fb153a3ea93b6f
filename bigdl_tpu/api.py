"""User-facing API.

Mirrors the reference's two entry points (SURVEY.md §3.1):
- `AutoModelForCausalLM.from_pretrained(path, load_in_low_bit=...)`
  (reference transformers/model.py:111) — load an HF checkpoint directory
  and quantize on the fly;
- `optimize_model(...)` (reference optimize.py:197) — quantize an
  already-built dense param tree;
plus `save_low_bit`/`load_low_bit` fast reload (model.py:58-104).

The returned `TpuModel` wraps (config, params, qtype) with a
`generate()` that compiles one XLA program per (bucket, max_new_tokens)
and runs the whole decode loop on device.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.generate import GenerationConfig, generate_tokens, pad_prompts
from bigdl_tpu.models import get_family
from bigdl_tpu.models.config import ModelConfig


def optimize_model(
    params: dict,
    config: ModelConfig,
    low_bit: str = "sym_int4",
    lm_head_qtype: Optional[str] = None,
    merge_fused: bool = True,
) -> dict:
    """Quantize a dense param tree in place of the reference's module
    surgery (optimize.py:197 → ggml_convert_low_bit). merge_fused fuses
    qkv and gate/up into single linears (the reference's merge_qkv,
    models/common.py:22-53) — bit-identical outputs, fewer kernel calls
    on the decode hot path."""
    family = get_family(config.model_type)
    out = family.quantize_params(params, low_bit, lm_head_qtype)
    if merge_fused and hasattr(family, "merge_fused_params"):
        out = family.merge_fused_params(out, config)
    return out


@dataclasses.dataclass
class TpuModel:
    config: ModelConfig
    params: dict
    qtype: str
    # set by to_mesh(): params are sharded over this jax.sharding.Mesh and
    # every generate/serving entry point runs SPMD under it
    mesh: Optional[Any] = None
    # set by to_mesh(): parallel/qcollectives.CommConfig — under tp > 1
    # the forward runs the per-layer projections per shard and reduces
    # the row-parallel pair through its all-reduce (exact, or the
    # block-quantized ring for comm_qtype=...)
    comm: Optional[Any] = None

    def __post_init__(self):
        # a model is what programs serve from: every packed weight a
        # kernel reads gets its scales as the operand that kernel reads in
        # place, once (llama.prepare_kernel_scales; the tree as it is
        # where no kernel runs). to_mesh() drops them again: shards keep
        # the float16 fields.
        if self.mesh is None:
            from bigdl_tpu.models.llama import prepare_kernel_scales

            self.params = prepare_kernel_scales(self.config, self.params)

    @property
    def family(self):
        return get_family(self.config.model_type)

    @property
    def pp_size(self) -> int:
        if self.mesh is not None and "pp" in getattr(self.mesh, "axis_names", ()):
            return self.mesh.shape["pp"]
        return 1

    @property
    def forward_fn(self):
        """The forward used by generate()/the serving engine: the plain
        family forward, or — when the mesh has a pp axis — the pipeline
        step with per-stage KV caches (parallel/pipeline.py), which keeps
        the same (config, params, tokens, cache, mode, last_logits_only)
        call shape so callers don't branch."""
        if self.pp_size <= 1:
            fwd = self.family.forward
            if self.comm is not None and self.comm.axis_size > 1:
                if getattr(self, "_comm_fwd", None) is None:
                    import functools
                    import inspect

                    if "comm" in inspect.signature(fwd).parameters:
                        # cached: a stable callable identity keeps the
                        # jit caches in generate/serving warm across calls
                        self._comm_fwd = functools.partial(
                            fwd, comm=self.comm)
                    elif self.comm.enabled:
                        raise NotImplementedError(
                            f"{self.config.model_type}'s forward does not "
                            "take comm= — quantized TP collectives are "
                            "wired for the llama family only"
                        )
                    else:  # GSPMD partitions this family's forward
                        self._comm_fwd = fwd
                return self._comm_fwd
            return fwd
        if getattr(self, "_pp_step", None) is None:
            from bigdl_tpu.parallel.pipeline import make_pipeline_step

            step = make_pipeline_step(self.config, self.family.forward,
                                      self.mesh)

            def pp_forward(config, params, tokens, cache,
                           mode="prefill", last_logits_only=False,
                           collect_obs: int = 0, **kw):
                # features beyond the cached prefill/decode step (plus
                # SnapKV's collect_obs) must fail loudly, not silently
                # drop their kwargs (array-safe: no truthiness on arrays)
                unsupported = sorted(
                    k for k, v in kw.items()
                    if v is not None and (
                        not isinstance(v, (bool, int, float)) or v
                    )
                )
                if cache is None or unsupported:
                    raise NotImplementedError(
                        "pipeline-parallel forward supports the cached "
                        "prefill/decode step only; got cache=None or "
                        f"kwargs {unsupported} — run this path on "
                        "a tp/dp mesh (pp=1) instead"
                    )
                return step(params, tokens, cache, mode=mode,
                            last_logits_only=last_logits_only,
                            collect_obs=collect_obs)

            self._pp_step = pp_forward
        return self._pp_step

    def to_mesh(self, mesh=None, tp: Optional[int] = None,
                dp: Optional[int] = None, sp: int = 1,
                pp: int = 1,
                comm_qtype: Optional[str] = None) -> "TpuModel":
        """Shard the params for multi-chip inference and make generate()
        / the serving engine run SPMD over the mesh.

        Megatron-style TP: column-parallel qkv/gate/up, row-parallel
        o/down, vocab-sharded embed+head (parallel/sharding.py). The
        reference reaches the same point via DeepSpeed-AutoTP module
        detection + an explicit mp_group.all_reduce
        (convert.py:152-234, low_bit_linear.py:675-682); here the
        PartitionSpecs make XLA insert the psums over ICI.

        pp > 1 (or a mesh with a 'pp' axis) additionally shards the layer
        stacks across pipeline stages — models bigger than one slice's
        HBM serve via make_pipeline_step (the reference's
        pipeline_parallel_stages=N, model.py:352-365).

        mesh=None builds a (pp, dp, sp, tp) mesh over all visible devices
        (tp defaulting to every device).

        comm_qtype ("none"|"int8"|"fp8_e4m3", default "none" — or the
        model's `default_comm_qtype` attribute, which `serve
        --comm-qtype` sets) quantizes the wire format of the per-layer
        TP all-reduce epilogues (parallel/qcollectives.py,
        docs/parallelism.md): block-scaled payloads with error feedback
        replace the implicit fp32 psum behind wo / w_down.
        """
        from bigdl_tpu.parallel import make_mesh, shard_params
        from bigdl_tpu.parallel.mesh import mesh_shape_for
        from bigdl_tpu.parallel.sharding import param_specs
        from bigdl_tpu.quant.qtensor import without_scale_bits

        if getattr(self.config, "expert_share", None) is not None:
            first, held, width = self.config.expert_share
            raise NotImplementedError(
                f"{self.config.model_type} holds one rank's share of its "
                f"experts ({held} of {width} from {first}): under a mesh "
                "the share is shard_map's to make, from a tree that holds "
                "every expert (ROADMAP B2)")
        self.params = without_scale_bits(self.params)
        if mesh is None:
            n = len(jax.devices())
            if pp > 1:
                # pp requires a 4-axis mesh; fill unspecified axes so
                # to_mesh(pp=2) works on its own instead of silently
                # building a pp-less mesh
                dp = dp or 1
                tp = tp or max(1, n // (pp * dp * sp))
                if pp * dp * sp * tp > n:
                    raise ValueError(
                        f"pp*dp*sp*tp = {pp * dp * sp * tp} exceeds {n} devices"
                    )
                mesh = make_mesh(
                    (pp, dp, sp, tp),
                    devices=jax.devices()[: pp * dp * sp * tp],
                    axes=("pp", "dp", "sp", "tp"),
                )
            elif tp is not None and dp is not None:
                # fully specified: use exactly dp*sp*tp devices (a
                # subset of the host's devices is fine)
                if dp * sp * tp > n:
                    raise ValueError(
                        f"dp*sp*tp = {dp * sp * tp} exceeds {n} devices"
                    )
                mesh = make_mesh(
                    (dp, sp, tp), devices=jax.devices()[: dp * sp * tp]
                )
            else:
                mesh = make_mesh(mesh_shape_for(n, tp=tp, dp=dp, sp=sp))
        if "tp" not in mesh.axis_names:
            raise ValueError(
                f"mesh axes {mesh.axis_names} lack 'tp' — param_specs "
                "shard weights over a 'tp' axis (use make_mesh(..., "
                "axes=('dp','sp','tp')))"
            )
        if (
            self.config.num_key_value_heads % (tp_size := mesh.shape["tp"])
            and not hasattr(self.family, "init_cache")
        ):
            # families with their own cache (rwkv's recurrent state,
            # MLA's latent) don't shard a KV pool over kv heads — the
            # divisibility requirement applies to the standard KVCache
            # layout only
            raise ValueError(
                f"num_key_value_heads={self.config.num_key_value_heads} "
                f"not divisible by tp={tp_size}"
            )
        self.mesh = mesh
        if mesh.shape["tp"] > 1 and hasattr(self.family, "unmerge_fused_params"):
            # fused qkv/gate-up boundaries don't align with tp shard
            # boundaries (GQA), which would force GSPMD resharding every
            # layer — split back before sharding (lossless)
            self.params = self.family.unmerge_fused_params(
                self.params, self.config
            )
        specs = param_specs(self.config)
        if "pp" in mesh.axis_names and mesh.shape["pp"] > 1:
            from bigdl_tpu.parallel.pipeline import pp_param_specs

            if self.config.num_hidden_layers % mesh.shape["pp"]:
                raise ValueError(
                    f"num_hidden_layers={self.config.num_hidden_layers} "
                    f"not divisible by pp={mesh.shape['pp']}"
                )
            if self.config.learned_positions or self.config.embed_layernorm:
                # the pipeline stage embeds with embed_tokens only; gpt2's
                # wpe table and bloom's embedding layernorm would be
                # silently skipped — refuse rather than generate garbage
                raise NotImplementedError(
                    f"pipeline parallelism does not yet support "
                    f"{self.config.model_type} (learned positions / "
                    "embedding layernorm)"
                )
            specs = pp_param_specs(self.config, specs)
        self.params = shard_params(self.params, specs, mesh)
        self._pp_step = None  # rebuilt for the new mesh on next use
        self._comm_fwd = None
        from bigdl_tpu.parallel.qcollectives import (
            CommConfig, resolve_comm_qtype,
        )

        cq = resolve_comm_qtype(
            comm_qtype if comm_qtype is not None
            else getattr(self, "default_comm_qtype", None)
        )
        self.comm = None
        if cq != "none" and self.pp_size > 1:
            raise NotImplementedError(
                "comm_qtype is wired for the tp epilogues of the "
                "single-stage forward; pipeline stages keep fp32 "
                "collectives (pp=1 to quantize comms)"
            )
        if self.pp_size == 1:
            # tp > 1: the forward runs its projections per shard and
            # reduces through this (exact psum for "none")
            self.comm = CommConfig(mesh=mesh, axis_name="tp", qtype=cq)
        return self

    def _mesh_ctx(self):
        import contextlib

        if self.mesh is None:
            return contextlib.nullcontext()
        return jax.set_mesh(self.mesh)

    def save_low_bit(self, path: str, *, faults=None) -> None:
        """Atomic, digest-manifested save (convert/low_bit.py): a kill
        mid-save leaves any previous checkpoint at `path` bit-identical,
        and the written artifact carries per-tensor crc32/sha256 digests
        for load-time verification."""
        from bigdl_tpu.convert import save_low_bit

        save_low_bit(path, self.config, self.params, self.qtype,
                     faults=faults)

    def _refuse_block_diffusion(self, what: str) -> None:
        """A diffusion checkpoint decoded one token a step from shifted
        logits would be another model, in silence: refuse by name."""
        if self.config.block_length:
            raise NotImplementedError(
                f"{self.config.model_type} generates by diffusion over "
                f"blocks of {self.config.block_length}: serve it through "
                "InferenceEngine(paged=True) (serving/blocks.py); "
                f"{what} decodes autoregressively and does not run it")
        why = getattr(self.family, "GENERATE_REFUSAL", None)
        if why:  # a family served by the paged engine alone says why
            raise NotImplementedError(
                f"{self.config.model_type}: {why}; {what} does not run it")

    def generate(
        self,
        prompts: Union[Sequence[Sequence[int]], np.ndarray],
        max_new_tokens: int = 32,
        do_sample: bool = False,
        temperature: float = 1.0,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        repetition_penalty: float = 1.0,
        eos_token_id: Optional[int] = None,
        pad_token_id: int = 0,
        seed: int = 0,
        quantize_kv: bool = False,
        compress_kv: Optional[int] = None,  # SnapKV budget (slots kept)
        compress_window: int = 32,
        streaming_window: Optional[int] = None,  # attention-sink ring size
        streaming_sink: int = 4,
    ) -> np.ndarray:
        """prompts: ragged list of token-id lists (or [B, T] array).
        Returns [B, max_new_tokens] generated ids.

        quantize_kv is the reference's IPEX_LLM_QUANTIZE_KV_CACHE (FP8 KV);
        compress_kv the reference's IPEX_LLM_COMPRESS_KV_CACHE (SnapKV) —
        applied only when the prompt is longer than the budget.
        streaming_window enables StreamingLLM-style attention sinks
        (reference example/GPU/Applications/streaming-llm): the cache is
        a fixed `streaming_window` slots — the first `streaming_sink`
        tokens plus a rolling recent region — so max_new_tokens may
        exceed the cache and generation runs in constant memory."""
        from bigdl_tpu.utils import flags

        self._refuse_block_diffusion("generate()")
        if isinstance(prompts, np.ndarray):
            prompts = [list(row) for row in prompts]
        if not prompts:
            raise ValueError("prompts is empty — nothing to generate")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}"
            )
        if top_k is not None:
            # HF semantics: top_k <= 0 disables the filter (the serving
            # kernel's "<=0 disables" convention); larger than vocab caps
            top_k = (None if top_k <= 0
                     else min(top_k, self.config.vocab_size))
        if any(len(p) == 0 for p in prompts):
            raise ValueError(
                "empty prompt row — every prompt needs at least one token"
            )
        lo = min(min(p) for p in prompts)
        hi = max(max(p) for p in prompts)
        if lo < 0 or hi >= self.config.vocab_size:
            raise ValueError(
                f"prompt token ids must be in [0, {self.config.vocab_size}); "
                f"got range [{lo}, {hi}] — wrong tokenizer for this model?"
            )
        # env-flag defaults (reference IPEX_LLM_QUANTIZE_KV_CACHE /
        # IPEX_LLM_COMPRESS_KV_CACHE / IPEX_LLM_PERFORMANCE_MODE)
        explicit_quantize_kv = quantize_kv
        explicit_compress_kv = compress_kv
        if not quantize_kv:
            quantize_kv = flags.quantize_kv_default()
        if compress_kv is None:
            compress_kv = flags.compress_kv_budget()
        cache_init = getattr(self.family, "init_cache", None)
        if cache_init is not None and compress_kv is not None:
            # recurrent-state families (rwkv) have no KV cache to compress
            compress_kv = None
        if (
            compress_kv is not None
            and max(len(p) for p in prompts) > compress_kv  # would apply
            and (self.config.sliding_window or self.config.alibi)
        ):
            # After SnapKV compression cache slots no longer correspond to
            # token positions, so sliding-window masks and ALiBi
            # slot-distance biases become incoherent (the reference gates
            # DynamicCompressCache by model type the same way —
            # models/utils.py:317-331).
            warnings.warn(
                "SnapKV compress_kv skipped: incompatible with "
                "sliding-window/ALiBi attention for this config"
            )
            compress_kv = None
        if (
            flags.performance_mode()
            and streaming_window is None  # lookup has no eviction support
            and cache_init is None  # lookup verify needs a rewindable KV cache
            and not do_sample
            and compress_kv is None  # lookup path has no SnapKV support
            and repetition_penalty == 1.0  # lookup has no penalty support
            and max(len(p) for p in prompts) >= 256
        ):
            return self.generate_lookup(
                prompts, max_new_tokens=max_new_tokens,
                eos_token_id=eos_token_id, pad_token_id=pad_token_id,
                seed=seed, quantize_kv=quantize_kv,
            )
        streaming = None
        if streaming_window is not None:
            from bigdl_tpu.streaming import validate_streaming

            validate_streaming(self.config, streaming_window, streaming_sink)
            if explicit_quantize_kv or explicit_compress_kv is not None:
                raise ValueError(
                    "streaming_window is incompatible with quantize_kv/"
                    "compress_kv — the evicted keys are re-based in place"
                )
            if quantize_kv or compress_kv is not None:
                # env-flag defaults (BIGDL_TPU_QUANTIZE_KV_CACHE /
                # _COMPRESS_KV_CACHE), not a caller choice: disable for
                # this call rather than make streaming unusable under them
                warnings.warn(
                    "streaming_window: ignoring env-default "
                    "quantize_kv/compress_kv for this call"
                )
                quantize_kv, compress_kv = False, None
            if cache_init is not None:
                raise ValueError(
                    "streaming_window supports the standard KV cache only; "
                    f"the {self.config.model_type} family uses a custom "
                    "cache layout (family init_cache hook)"
                )
            lens = {len(p) for p in prompts}
            if len(lens) > 1:
                raise ValueError(
                    "streaming_window needs equal-length prompts (the sink "
                    "slots must hold real tokens in every row) — batch "
                    "equal lengths or generate per prompt"
                )
            if max(lens) >= streaming_window:
                raise ValueError(
                    f"prompt ({max(lens)} tokens) must be shorter than "
                    f"streaming_window ({streaming_window}); raise the "
                    "window or pre-truncate the prompt"
                )
            streaming = (streaming_sink, streaming_window)
        # streaming: pad to the exact (equal) prompt length, not a
        # power-of-two bucket — the sink slots must hold real tokens,
        # and a bucket as large as the window would leave no decode room
        tokens, start = pad_prompts(
            prompts, pad_token_id,
            bucket=(len(prompts[0]) if streaming is not None else None),
        )
        gen = GenerationConfig(
            max_new_tokens=max_new_tokens,
            do_sample=do_sample,
            temperature=temperature,
            top_k=top_k,
            top_p=top_p,
            repetition_penalty=repetition_penalty,
            eos_token_id=eos_token_id,
            pad_token_id=pad_token_id,
        )
        from bigdl_tpu.utils import cache_len_for

        cache_len = (
            streaming_window if streaming is not None
            else cache_len_for(tokens.shape[1], max_new_tokens)
        )
        budget = 0
        if compress_kv is not None and tokens.shape[1] > compress_kv:
            budget = compress_kv
        with self._mesh_ctx():
            out = generate_tokens(
                self.config,
                self.params,
                jnp.asarray(tokens),
                jnp.asarray(start),
                jax.random.PRNGKey(seed),
                gen,
                self.forward_fn,
                cache_len=cache_len,
                quantize_kv=quantize_kv,
                compress_budget=budget,
                compress_window=min(compress_window, max(budget - 1, 1)),
                last_logits=flags.last_lm_head_default(),
                cache_init=cache_init,
                streaming=streaming,
            )
        return np.asarray(out)


    def generate_lookup(
        self,
        prompts,
        max_new_tokens: int = 32,
        lookahead: int = 4,
        max_ngram: int = 3,
        **kw,
    ) -> np.ndarray:
        """Prompt-lookup decoding (reference lookup.py:274 /
        IPEX_LLM_PERFORMANCE_MODE): n-gram candidates, one verify forward."""
        from bigdl_tpu.decode import lookup_generate

        self._refuse_block_diffusion("generate_lookup()")

        # under a pp mesh the verify forward is the pipeline step
        # (forward_fn keeps the family-forward call shape, so the lookup
        # while_loop runs unchanged with per-stage KV caches)
        with self._mesh_ctx():
            return lookup_generate(
                self.config, self.params, prompts, self.forward_fn,
                max_new_tokens=max_new_tokens, lookahead=lookahead,
                max_ngram=max_ngram, **kw,
            )

    def self_draft_params(self):
        """The sym_int4 self-draft of this model's weights (the
        reference's self-speculative draft, model.py:366-379), built once
        and cached. Only meaningful when the model holds higher-precision
        weights — a draft equal to the target is all cost, no speedup."""
        from bigdl_tpu.quant.qtypes import resolve_qtype

        try:
            is_dense = resolve_qtype(self.qtype).is_dense
        except ValueError:  # e.g. "gguf_native" mixed trees
            is_dense = False
        if not is_dense:
            # re-quantizing already-quantized weights is a no-op
            # (quantize_params skips QTensor leaves) — the "draft" would
            # be weight-identical to the target: all cost, no speedup.
            raise ValueError(
                f"model qtype {self.qtype!r} is already quantized; a "
                "sym_int4 self-draft would equal the target. Pass "
                "explicit draft_params or load the target as fp16/bf16."
            )
        draft_params = getattr(self, "_draft_params", None)
        if draft_params is None:
            draft_params = optimize_model(self.params, self.config, "sym_int4")
            object.__setattr__(self, "_draft_params", draft_params)
        return draft_params

    def generate_speculative(
        self,
        prompts,
        draft_params=None,
        max_new_tokens: int = 32,
        draft_k: int = 4,
        **kw,
    ) -> np.ndarray:
        """Self-speculative decoding (reference speculative.py:803). With
        draft_params=None the draft is a sym_int4 re-quantization of this
        model's weights (the reference's self-draft, model.py:366-379) —
        only meaningful when this model holds higher-precision weights.
        The self-draft is built once and cached on the model."""
        from bigdl_tpu.decode import speculative_generate

        self._refuse_block_diffusion("generate_speculative()")

        if self.pp_size > 1:
            raise NotImplementedError(
                "speculative decoding jits the family forward directly "
                "and would gather pp-sharded layer stacks onto every "
                "stage; use plain generate() under pipeline parallelism"
            )

        if draft_params is None:
            draft_params = self.self_draft_params()
        with self._mesh_ctx():
            return speculative_generate(
                self.config, self.params, draft_params, prompts,
                self.family.forward, max_new_tokens=max_new_tokens,
                draft_k=draft_k, **kw,
            )


def _merged_model(config, params, qtype, merge_fused: bool = True) -> TpuModel:
    """Shared loader tail: fuse qkv/gate-up when the family supports it
    (lossless, reference merge_qkv) before wrapping. merge_fused=False
    keeps the split layout — the gguf export path consumes it directly
    and would otherwise pay a full merge+unmerge round trip."""
    family = get_family(config.model_type)
    if merge_fused and hasattr(family, "merge_fused_params"):
        params = family.merge_fused_params(params, config)
    return TpuModel(config=config, params=params, qtype=qtype)


class AutoModelForCausalLM:
    """Loader namespace, reference-compatible spelling
    (ipex_llm.transformers.AutoModelForCausalLM)."""

    @classmethod
    def from_pretrained(
        cls,
        model_path: str,
        load_in_low_bit: str = "sym_int4",
        load_in_4bit: bool = False,
        merge_fused: bool = True,
        **_ignored,
    ) -> TpuModel:
        from bigdl_tpu.convert import load_hf_checkpoint

        qtype = "sym_int4" if load_in_4bit else load_in_low_bit
        config, params, qtype = load_hf_checkpoint(model_path, qtype=qtype)
        return _merged_model(config, params, qtype, merge_fused)

    @classmethod
    def load_low_bit(cls, path: str, verify: str = "fast",
                     salvage: bool = False) -> TpuModel:
        """Load a save_low_bit checkpoint with integrity verification
        (convert/low_bit.py): verify="off"|"fast" (crc32)|"full" (sha256
        + NaN/inf + scale-range validation). Corruption raises a
        structured IntegrityError naming every bad tensor; salvage=True
        loads the valid subset instead and leaves the quarantine report
        on the returned model as `model.salvage_report` (None = clean).
        A salvaged model is for inspection/weight recovery — forward
        passes will fail on the quarantined tensors."""
        from bigdl_tpu.convert import load_low_bit

        if salvage:
            config, params, qtype, report = load_low_bit(
                path, verify=verify, salvage=True,
            )
        else:
            config, params, qtype = load_low_bit(path, verify=verify)
            report = None
        # a quarantined (partial) tree can't run the fused merge — the
        # missing tensors would KeyError mid-surgery
        model = _merged_model(config, params, qtype,
                              merge_fused=report is None)
        model.salvage_report = report
        return model

    @classmethod
    def from_gguf(cls, path: str, qtype: Optional[str] = None) -> TpuModel:
        """Load a llama.cpp GGUF file (reference transformers/model.py:391
        `from_gguf`). qtype=None keeps the file's native low-bit formats
        (q4_0→sym_int4 etc., repacked without dequantization)."""
        from bigdl_tpu.convert.gguf import load_gguf

        config, params = load_gguf(path, qtype=qtype)
        return _merged_model(config, params, qtype or "gguf_native")
