"""Pallas flash attention (TPU) with online softmax.

TPU-native replacement for the reference's fused SDP kernels
(`xe_addons.sdp / sdp_causal / sdp_non_causal`, call sites
models/common.py:222-258 in /root/reference): one kernel covers causal
attention over a left-padded KV cache, GQA head grouping, optional
sliding window and logit softcap (gemma2), without ever materializing
the [T, S] score matrix in HBM.

Layout: q [B, T, Hq, D]; k, v [B, S, Hkv, D] (the KV-cache layout).
`start[b]` is the first valid cache slot of row b (left padding);
`q_offset` is the global cache slot of q position 0 (= cache.pos at
entry). Query slot t attends kv slot j iff
    start[b] <= j <= q_offset + t          (causal)
    and j > q_offset + t - window          (if sliding window).

Grid is (B, Hq, nQ, nK) with the K axis innermost ("arbitrary"
semantics); m/l/acc accumulators live in VMEM scratch and the output
block is written once on the last K step. K blocks entirely above the
causal diagonal are skipped via `pl.when`, so causal costs ~half of
full attention, matching a hand-scheduled kernel.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops.pallas import qdecode
from bigdl_tpu.ops.pallas.tiling import (
    FLASH_BLOCK_K, FLASH_BLOCK_Q, MOSAIC_LANES, flash_blocks,
)
from bigdl_tpu.utils import round_up

_NEG_INF = -1e30
# lane width + block policy live in tiling.py (jax-free) so the
# analytic attention roofline evaluates at the kernel's REAL tiles
_LANES = MOSAIC_LANES



def _kernel(
    start_ref,  # SMEM [B] int32: per-row pad offsets (indexed by program_id)
    qoff_ref,  # SMEM [1] int32: global slot of q position 0
    q_ref,  # VMEM [1, 1, BQ, D]
    k_ref,  # VMEM [1, 1, BK, D]
    v_ref,  # VMEM [1, 1, BK, D]
    *refs,  # (+ ks/vs VMEM [1, 1, BK, 1] f32 when quantized) o, scratch
    scale: float,
    block_q: int,
    block_k: int,
    causal: bool,
    window: Optional[int],
    softcap: Optional[float],
    quantized: bool,
    kv_value: tuple,
):
    if quantized:  # fp8 KV: per-(slot, head) f32 scales ride alongside
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
    b = pl.program_id(0)
    i, j = pl.program_id(2), pl.program_id(3)
    n_k = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    qoff = qoff_ref[0]
    row_max = qoff + (i + 1) * block_q - 1  # largest global q slot in block
    # K block is live unless entirely above the causal diagonal / outside
    # the sliding window of every query row in this Q block.
    live = jnp.bool_(True)
    if causal:
        live = live & (j * block_k <= row_max)
    if window is not None:
        row_min = qoff + i * block_q
        live = live & ((j + 1) * block_k - 1 > row_min - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # [BQ, D]
        # shared KV decode body (fp8 codes cross as uint8 bits and go
        # through the same qdecode bit decoder as fp8 GEMM weights);
        # the [BK, 1] scale broadcasts over D
        k = qdecode.decode_kv(
            k_ref[0, 0], ks_ref[0, 0] if quantized else None, kv_value
        )  # [BK, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [BQ, BK]
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap

        rows = qoff + i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        cols = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        valid = cols >= start_ref[b]
        if causal:
            valid = valid & (cols <= rows)
        if window is not None:
            valid = valid & (cols > rows - window)
        s = jnp.where(valid, s, _NEG_INF)

        m_prev = m_scr[:, :1]  # [BQ, 1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        # exp(-1e30 - (-1e30)) = 1 on fully-masked rows; zero explicitly.
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)  # [BQ, BK]
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)

        v = qdecode.decode_kv(
            v_ref[0, 0], vs_ref[0, 0] if quantized else None, kv_value
        )  # [BK, D]
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == n_k - 1)
    def _finalize():
        l = l_scr[:, :1]
        out = acc_scr[:] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "softcap", "scale", "block_q", "block_k", "interpret"
    ),
)
def _flash(
    q, k, v, start, q_offset, k_scale, v_scale,
    causal: bool, window: Optional[int], softcap: Optional[float],
    scale: float, block_q: int, block_k: int, interpret: bool,
):
    B, Hq, T, D = q.shape
    _, Hkv, S, _ = k.shape
    group = Hq // Hkv
    n_q, n_k = T // block_q, S // block_k
    quantized = k_scale is not None
    kv_value = ("e4m3",) if k.dtype == jnp.float8_e4m3fn else ("e5m2",)
    if quantized:
        # fp8 codes cross the pallas_call boundary as uint8 bit patterns
        # (the qmatmul fp8-weight move): in-kernel they decode through
        # the shared qdecode body, exactly the GEMM formats' decoder
        k = jax.lax.bitcast_convert_type(k, jnp.uint8)
        v = jax.lax.bitcast_convert_type(v, jnp.uint8)

    grid = (B, Hq, n_q, n_k)
    kernel = functools.partial(
        _kernel,
        scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, window=window, softcap=softcap, quantized=quantized,
        kv_value=kv_value,
    )
    kv_spec = pl.BlockSpec(
        (1, 1, block_k, D), lambda b, h, i, j: (b, h // group, j, 0),
        memory_space=pltpu.VMEM,
    )
    in_specs = [
        pl.BlockSpec((B,), lambda b, h, i, j: (0,), memory_space=pltpu.SMEM),
        pl.BlockSpec((1,), lambda b, h, i, j: (0,), memory_space=pltpu.SMEM),
        pl.BlockSpec(
            (1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0),
            memory_space=pltpu.VMEM,
        ),
        kv_spec, kv_spec,
    ]
    args = [start, q_offset, q, k, v]
    if quantized:
        # [B, Hkv, S, 1] f32: a trailing singleton keeps the block rank-2
        # in (sublane, lane) with a full-dim lane (always legal)
        sc_spec = pl.BlockSpec(
            (1, 1, block_k, 1), lambda b, h, i, j: (b, h // group, j, 0),
            memory_space=pltpu.VMEM,
        )
        in_specs += [sc_spec, sc_spec]
        args += [k_scale, v_scale]
    return pl.pallas_call(
        kernel,
        name="flash_attention",
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0),
            memory_space=pltpu.VMEM,
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, T, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*args)


def flash_attention(
    q: jax.Array,  # [B, T, Hq, D]
    k: jax.Array,  # [B, S, Hkv, D] (fp8 codes when k_scale is given)
    v: jax.Array,  # [B, S, Hkv, D]
    start: Optional[jax.Array] = None,  # [B] int32 left-pad offsets
    q_offset: Optional[jax.Array] = None,  # scalar int32 global slot of q[0]
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    k_scale: Optional[jax.Array] = None,  # [B, S, Hkv] fp8 dequant scales
    v_scale: Optional[jax.Array] = None,
    block_q: int = FLASH_BLOCK_Q,
    block_k: int = FLASH_BLOCK_K,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Returns [B, T, Hq, D] in q.dtype. Pads T/S/D to tile multiples
    internally; padding key slots are excluded by the causal mask (they
    lie beyond every query's global slot).

    With k_scale/v_scale, k/v are fp8 codes from a quantized KV cache
    and dequantize per block IN-KERNEL (the paged kernel's fp8 story):
    the cache never materializes as a dense bf16 copy in HBM, which is
    the entire point of fp8 KV. Scales cross as f32 — Mosaic has no f16
    vectors — at 1/D the footprint of the codes."""
    from bigdl_tpu.ops.pallas import interpret_mode

    B, T, Hq, D = q.shape
    _, S, Hkv, _ = k.shape
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    if interpret is None:
        interpret = interpret_mode()
    if start is None:
        start = jnp.zeros((B,), jnp.int32)
    if q_offset is None:
        q_offset = jnp.zeros((), jnp.int32)
    assert causal, "non-causal path uses ops.attention (bidirectional encoders)"

    block_q, block_k = flash_blocks(T, S, block_q, block_k)
    Tp, Sp, Dp = round_up(T, block_q), round_up(S, block_k), round_up(D, _LANES)

    qt = jnp.transpose(q, (0, 2, 1, 3))  # [B, Hq, T, D]
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    qt = jnp.pad(qt, ((0, 0), (0, 0), (0, Tp - T), (0, Dp - D)))
    kt = jnp.pad(kt, ((0, 0), (0, 0), (0, Sp - S), (0, Dp - D)))
    vt = jnp.pad(vt, ((0, 0), (0, 0), (0, Sp - S), (0, Dp - D)))

    def prep_scale(s):
        if s is None:
            return None
        st = jnp.transpose(s.astype(jnp.float32), (0, 2, 1))  # [B, Hkv, S]
        return jnp.pad(st, ((0, 0), (0, 0), (0, Sp - S)))[..., None]

    out = _flash(
        qt, kt, vt,
        start.astype(jnp.int32),
        q_offset.astype(jnp.int32).reshape(1),
        prep_scale(k_scale), prep_scale(v_scale),
        causal, window, softcap, scale, block_q, block_k, interpret,
    )
    return jnp.transpose(out[:, :, :T, :D], (0, 2, 1, 3))
