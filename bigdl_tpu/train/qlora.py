"""QLoRA: LoRA adapters over a frozen low-bit base.

Reference: `transformers/qlora.py` (`LoraLowBitLinear`:66-144 — frozen
LowBitLinear base + bf16 LoRA branch; autograd through the quantized
matmul via `MatMulLowBit.backward`, low_bit_linear.py:500-541).

TPU design: the base weights are QTensor leaves that are simply not
differentiated — `jax.grad` w.r.t. the LoRA tree alone gives exactly the
reference's backward (dequantized W^T participates in the VJP as a
constant; XLA rematerializes the dequant, no custom autograd class
needed). One jitted train step covers forward, backward, and the optax
update, sharded over the same (dp, sp, tp) mesh as inference.

The frozen-base matmul runs fused in BOTH directions (ops/linear.py
routes training shapes to the Pallas kernel like any other, under a
custom_vjp): the forward's y = x @ dq(W)^T and the backward's
dx = g @ dq(W) both dequantize base-weight tiles in VMEM
(ops/pallas/qmatmul.py forward, ops/pallas/qbackward.py dx) instead of
materializing a bf16 copy of W in HBM per step. The old XLA
rematerialized-dequant backward survives as the parity oracle behind
`make_train_step(..., fused_backward=False)` /
`ops.linear.fused_backward_scope(False)` (parity:
tests/test_qbackward.py; arxiv 2306.11987).
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import optax

from bigdl_tpu.models.config import ModelConfig

DEFAULT_TARGETS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def _target_dims(config: ModelConfig, name: str) -> tuple[int, int]:
    H, I = config.hidden_size, config.intermediate_size
    return {
        "wq": (config.q_dim, H),
        "wk": (config.kv_dim, H),
        "wv": (config.kv_dim, H),
        "wo": (H, config.q_dim),
        "w_gate": (I, H),
        "w_up": (I, H),
        "w_down": (H, I),
    }[name]


def init_lora(
    config: ModelConfig,
    key: jax.Array,
    rank: int = 8,
    alpha: float = 16.0,
    targets: tuple[str, ...] = DEFAULT_TARGETS,
    dtype=jnp.bfloat16,
) -> dict:
    """LoRA tree: {'layers': {target: {'a': [L,r,in], 'b': [L,out,r]}},
    'scale': alpha/rank}. A ~ N(0, 1/r), B = 0 (standard init: adapter
    starts as identity)."""
    L = config.num_hidden_layers
    layers = {}
    for t in targets:
        out_dim, in_dim = _target_dims(config, t)
        key, k = jax.random.split(key)
        layers[t] = {
            "a": (jax.random.normal(k, (L, rank, in_dim), jnp.float32) / rank).astype(dtype),
            "b": jnp.zeros((L, out_dim, rank), dtype),
        }
    return {"layers": layers, "scale": jnp.asarray(alpha / rank, dtype)}


# lora target -> (merged base name, row-slice index) for the fused layout
# (models/llama.merge_fused_params)
_MERGED_HOME = {
    "wq": ("wqkv", 0), "wk": ("wqkv", 1), "wv": ("wqkv", 2),
    "w_gate": ("w_gateup", 0), "w_up": ("w_gateup", 1),
}


def merge_lora(params: dict, lora: dict, requantize: Optional[str] = None) -> dict:
    """Fold adapters into the base (ReLoRA's merge step, relora.py:64-150).

    Dense bases merge exactly; quantized bases are dequantized, merged,
    and re-quantized to `requantize` (defaults to their own qtype).
    Handles both the split layout and the fused one (merge_fused_params):
    deltas land in each target's row slice of the fused base, located
    from the lora pairs' own output widths, and every base is requantized
    at most once (deltas into the same fused weight are accumulated
    first, so quantization noise doesn't compound per target).
    """
    from bigdl_tpu.quant import QTensor, quantize

    out_layers = dict(params["layers"])
    scale = jnp.asarray(lora["scale"], jnp.float32)

    # row offsets inside fused bases derive from the target's OWN lora B
    # width plus the fused base's total rows — never from peer targets
    # (a lora trained on wk/wv alone must still land in the k/v rows)
    widths = {t: p["b"].shape[-2] for t, p in lora["layers"].items()}

    def base_rows(name: str) -> int:
        # QTensor.shape is the LOGICAL shape for every storage (for
        # packed_u8/packed_planes, data.shape[-1] is bytes, not elements)
        return params["layers"][name].shape[-2]

    def row_start(target: str) -> int:
        name, idx = _MERGED_HOME[target]
        total = base_rows(name)
        if name == "wqkv":
            kd = widths[target] if target in ("wk", "wv") else None
            if target == "wq":
                return 0
            # total = QD + 2*KD with KD = this target's own width
            return total - 2 * kd if target == "wk" else total - kd
        # w_gateup: gate rows first, both halves share width I
        return 0 if target == "w_gate" else total // 2

    # base name -> list of (row_offset|None, delta)
    pending: dict[str, list] = {}
    for t, pair in lora["layers"].items():
        delta = (
            jnp.einsum("lor,lri->loi", pair["b"].astype(jnp.float32),
                       pair["a"].astype(jnp.float32)) * scale
        )
        if t in params["layers"]:
            pending.setdefault(t, []).append((None, delta))
        elif t in _MERGED_HOME and _MERGED_HOME[t][0] in params["layers"]:
            pending.setdefault(_MERGED_HOME[t][0], []).append(
                (row_start(t), delta)
            )
        else:
            raise KeyError(
                f"lora target {t!r} not found in params (neither split nor "
                f"fused layout)"
            )

    for name, deltas in pending.items():
        base = params["layers"][name]
        quantized = isinstance(base, QTensor)
        dense = base.dequantize(jnp.float32) if quantized else base.astype(jnp.float32)
        for off, delta in deltas:
            if off is None:
                dense = dense + delta
            else:
                dense = dense.at[..., off:off + delta.shape[-2], :].add(delta)
        out_layers[name] = (
            quantize(dense, requantize or base.qtype) if quantized
            else dense.astype(base.dtype)
        )
    out = dict(params)
    out["layers"] = out_layers
    return out


def next_token_loss(
    config: ModelConfig,
    forward_fn: Callable,
    params: dict,
    lora: Optional[dict],
    tokens: jax.Array,  # [B, T]
    loss_mask: jax.Array,  # [B, T] 1.0 where the *target* token counts
) -> jax.Array:
    """Causal LM cross-entropy: predict tokens[:, 1:] from tokens[:, :-1]."""
    logits, _ = forward_fn(config, params, tokens[:, :-1], None, lora=lora)
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None].astype(jnp.int32), axis=-1)[..., 0]
    mask = loss_mask[:, 1:].astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def make_train_step(
    config: ModelConfig,
    forward_fn: Callable,
    optimizer: optax.GradientTransformation,
    seq_spec=None,
    ring_mesh=None,
    ring_axis: str = "sp",
    batch_axis: str = "dp",
    remat: bool = False,
    return_grad_norm: bool = False,
    fused_backward: bool = True,
):
    """Returns jittable step(params, lora, opt_state, tokens, loss_mask) ->
    (lora, opt_state, loss). Only lora['layers'] is trained (the alpha/rank
    scale stays fixed); init opt_state with optimizer.init(lora['layers']).
    Donate lora/opt_state at the jit call site — UNLESS the step runs
    under the training supervisor, whose anomaly-skip path must keep
    the previous buffers alive for one step (train/supervisor.py).

    return_grad_norm=True appends optax.global_norm(grads) to the
    outputs — the supervisor's overflow guard (quantized-grad NaN/inf
    shows up in the norm a step before it reaches the loss; arxiv
    2306.11987) — at the cost of one extra reduction per step.

    seq_spec: optional PartitionSpec (e.g. P('dp', 'sp')) constraining the
    input token grid — sequence-parallel training: embedding/norm/MLP run
    on sequence shards; without ring_mesh XLA all-gathers KV around
    attention.

    remat=True checkpoints each decoder layer (jax.checkpoint around the
    scan body): the backward recomputes the layer instead of saving its
    activations — with the flash-train kernel this makes per-layer saved
    state O(B*T*H) instead of O(B*T*(3H+2I)), the long-context lever.

    ring_mesh: pass the Mesh to replace those all-gathers with ring
    attention (parallel/ring.py) — each device keeps 1/sp of the KV and
    shards rotate over ICI, making attention memory O(T/sp) for
    long-context training. Requires an enclosing mesh context (jax.set_mesh) and
    sliding_window/softcap-free attention (llama-family default).

    fused_backward=False traces the step with the XLA
    rematerialized-dequant dx instead of the Pallas fused backward
    (ops/pallas/qbackward.py) — the parity oracle for A/B-ing loss
    curves across the flip. The choice is baked into the jaxpr at trace
    time (ops.linear.fused_backward_scope), so it is per-step-function,
    not per-call; the supervisor EventLog records which path a run used.
    """
    attention_override = None
    if ring_mesh is not None:
        from jax.sharding import PartitionSpec as P

        from bigdl_tpu.parallel.ring import ring_attention

        # features the ring path does not implement — fail loudly instead
        # of silently optimizing a different loss than the dense path
        assert config.attn_logit_softcap is None, "ring: no logit softcap"
        assert config.sliding_window is None, "ring: no sliding window"
        assert not config.alibi, "ring: no alibi"

        n = ring_mesh.shape[ring_axis]
        # shard heads over tp too (when present and divisible): each tp
        # device keeps its own head shard instead of all-gathering q/k/v
        head_axis = None
        if "tp" in ring_mesh.shape and ring_mesh.shape["tp"] > 1:
            tp = ring_mesh.shape["tp"]
            if (config.num_attention_heads % tp == 0
                    and config.num_key_value_heads % tp == 0):
                head_axis = "tp"
        qspec = P(batch_axis, ring_axis, head_axis, None)

        def _local(q, k, v, start):
            return ring_attention(
                q, k, v, axis_name=ring_axis, axis_size=n, causal=True,
                scale=config.attn_scale, start=start,
            )

        attention_override = jax.shard_map(
            _local,
            mesh=ring_mesh,
            in_specs=(qspec, qspec, qspec, P(batch_axis)),
            out_specs=qspec,
            check_vma=False,
        )

    inner_forward = forward_fn
    if seq_spec is not None or attention_override is not None or remat:
        def inner_forward(cfg, params, toks, cache, lora=None):
            if seq_spec is not None:
                toks = jax.lax.with_sharding_constraint(toks, seq_spec)
            kw = {"remat": True} if remat else {}
            return forward_fn(
                cfg, params, toks, cache, lora=lora,
                attention_override=attention_override, **kw,
            )

    def step(params, lora, opt_state, tokens, loss_mask):
        from bigdl_tpu.ops.linear import fused_backward_scope

        scale = lora["scale"]
        # the scope is read at TRACE time inside the custom_vjp bwd
        # rules, so wrapping the value_and_grad call (which runs during
        # jit tracing of `step`) bakes the chosen dx path into the jaxpr
        with fused_backward_scope(fused_backward):
            loss, grads = jax.value_and_grad(
                lambda layers: next_token_loss(
                    config, inner_forward, params,
                    {"layers": layers, "scale": scale}, tokens, loss_mask,
                )
            )(lora["layers"])
        updates, opt_state = optimizer.update(grads, opt_state, lora["layers"])
        layers = optax.apply_updates(lora["layers"], updates)
        new_lora = {"layers": layers, "scale": scale}
        if return_grad_norm:
            return new_lora, opt_state, loss, optax.global_norm(grads)
        return new_lora, opt_state, loss

    return step
