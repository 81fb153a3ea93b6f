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

Grid is (B, Hkv, nQ, nK) with the K axis innermost ("arbitrary"
semantics). A q block is `block_q` POSITIONS of all `group = Hq / Hkv`
query heads of one KV head, stacked on sublanes as `group * block_q`
rows, so K and V are fetched once a KV head; the tiles are
`tiling.flash_blocks`' (512 keys a step where S allows, from T, S, D,
`group` and the cache's itemsize against `tiling.VMEM_BUDGET`). q, K and
V reach the MXU in the type they are stored in (bfloat16: exact
products), accumulated in float32; the scale, the softcap, the running
max and sum, `alpha` and the accumulator are float32, and the
probabilities are cast to V's type for the context dot alone. A step
whose K block no row of its q block attends (above the causal diagonal,
behind the window) is skipped via `pl.when`, and the K / V index maps
clamp `j` into the q block's live range (`live_k_range`), so a dead step
names the block already resident and fetches nothing. The output block
is written once, on the last K step. docs/kernels.md#flash has the
account; `scripts/flash_kernel_bench.py` times the kernel alone.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops import routes
from bigdl_tpu.ops.pallas import qdecode
from bigdl_tpu.ops.pallas.tiling import (
    MOSAIC_LANES, VMEM_LIMIT_BYTES, flash_blocks, flash_live_blocks,
)
from bigdl_tpu.utils import round_up

_NEG_INF = -1e30
# lane width + block policy live in tiling.py (jax-free) so the
# analytic attention roofline evaluates at the kernel's REAL tiles
_LANES = MOSAIC_LANES


def live_k_range(i, q_offset, block_q: int, block_k: int, n_k: int,
                 window: Optional[int]):
    """(first, last) K blocks that some row of q block `i` attends, both
    inside 0 .. n_k - 1: the kernel's liveness test of a grid step, and
    what its K / V index maps clamp `j` into. Works on Python ints (the
    tests, `tiling.flash_live_blocks`' twin) and on traced scalars."""
    last = (q_offset + (i + 1) * block_q - 1) // block_k
    if isinstance(last, int):
        lo, hi = max, min
    else:
        lo, hi = jnp.maximum, jnp.minimum
    last = hi(last, n_k - 1)
    if window is None:
        return 0, last
    # the first row's window opens at column row_min - window + 1
    first = lo(q_offset + i * block_q - window + 1, 0) // block_k
    return hi(first, last), last


def clamped_k_block(j, i, q_offset, block_q: int, block_k: int, n_k: int,
                    window: Optional[int]):
    """The K block grid step (i, j) points its DMA at: `j` inside the live
    range of q block `i`, the nearer end outside it, so that a dead step
    names the block already resident and Mosaic issues no DMA for it."""
    first, last = live_k_range(i, q_offset, block_q, block_k, n_k, window)
    if isinstance(last, int) and isinstance(j, int):
        return min(max(j, first), last)
    return jnp.minimum(jnp.maximum(j, first), last)


def _lanes(x, n: int):
    """A lane-replicated [rows, 128] value at `n` lanes."""
    if n % _LANES == 0:
        return x if n == _LANES else pltpu.repeat(x, n // _LANES, axis=1)
    if n < _LANES:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _kernel(
    start_ref,  # SMEM [B] int32 (scalar prefetch): per-row pad offsets
    qoff_ref,  # SMEM [1] int32 (scalar prefetch): global slot of q position 0
    q_ref,  # VMEM [1, 1, group, BQ, D]
    k_ref,  # VMEM [1, 1, BK, D]
    v_ref,  # VMEM [1, 1, BK, D]
    *refs,  # (+ ks/vs VMEM [1, 1, BK, 1] f32 when quantized) o, scratch
    scale: float,
    group: int,
    block_q: int,
    block_k: int,
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
    rows = group * block_q

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    qoff = qoff_ref[0]
    # a K block is live unless it lies wholly above the causal diagonal
    # or outside the sliding window of every position of this q block;
    # the index maps hold a dead step on a live block, so it costs no DMA
    first, last = live_k_range(i, qoff, block_q, block_k, n_k, window)

    @pl.when((j >= first) & (j <= last))
    def _compute():
        # the group's heads stacked on sublanes: row r is position
        # r % block_q of head r // block_q
        q = q_ref[0, 0].reshape(rows, q_ref.shape[-1])
        if quantized:
            # fp8 codes cross as uint8 bits and go through the same
            # qdecode bit decoder as fp8 GEMM weights; the [BK, 1] scale
            # broadcasts over D. Decoded tiles are float32, and so is
            # their product
            k = qdecode.decode_kv(k_ref[0, 0], ks_ref[0, 0], kv_value)
            v = qdecode.decode_kv(v_ref[0, 0], vs_ref[0, 0], kv_value)
        else:
            k, v = k_ref[0, 0], v_ref[0, 0]
        # operands as they are stored (bf16 q and cache: exact products
        # on the MXU), float32 accumulation and float32 from there on
        dt = jnp.promote_types(q.dtype, k.dtype)
        s = jax.lax.dot_general(
            q.astype(dt), k.astype(dt), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [rows, BK]
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap

        # one [BQ, BK] mask serves every head of the group
        pos = qoff + i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        cols = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        valid = (cols >= start_ref[b]) & (cols <= pos)
        if window is not None:
            valid = valid & (cols > pos - window)
        s = jnp.where(
            valid[None], s.reshape(group, block_q, block_k), _NEG_INF
        ).reshape(rows, block_k)

        # m, l and alpha stay lane-replicated [rows, 128], as the scratch
        # holds them: a [rows, 1] column fills as many vregs, and every
        # use of one costs a lane broadcast a sublane tile
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # a row that has met no valid column yet reads exp(0) = 1 on its
        # masked ones; its first valid block wipes that (alpha = 0), and
        # a row that never meets one is zeroed in _finalize
        p = jnp.exp(s - _lanes(m_new, block_k))  # [rows, BK]
        l_scr[:] = alpha * l_scr[:] + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[:] = acc_scr[:] * _lanes(alpha, acc_scr.shape[1]) + pv
        m_scr[:] = m_new

    @pl.when(j == n_k - 1)
    def _finalize():
        seen = m_scr[:] > 0.5 * _NEG_INF
        inv = jnp.where(seen, 1.0 / jnp.where(seen, l_scr[:], 1.0), 0.0)
        out = acc_scr[:] * _lanes(inv, acc_scr.shape[1])
        o_ref[0, 0] = out.reshape(o_ref.shape[2:]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "window", "softcap", "scale", "block_q", "block_k", "interpret"
    ),
)
def _flash(
    q, k, v, start, q_offset, k_scale, v_scale,
    window: Optional[int], softcap: Optional[float],
    scale: float, block_q: int, block_k: int, interpret: bool,
):
    B, Hkv, group, T, D = q.shape
    S = k.shape[2]
    n_q, n_k = T // block_q, S // block_k
    rows = group * block_q
    quantized = k_scale is not None
    kv_value = ("e4m3",) if k.dtype == jnp.float8_e4m3fn else ("e5m2",)
    if quantized:
        # fp8 codes cross the pallas_call boundary as uint8 bit patterns
        # (the qmatmul fp8-weight move): in-kernel they decode through
        # the shared qdecode body, exactly the GEMM formats' decoder
        k = jax.lax.bitcast_convert_type(k, jnp.uint8)
        v = jax.lax.bitcast_convert_type(v, jnp.uint8)

    kernel = functools.partial(
        _kernel,
        scale=scale, group=group, block_q=block_q, block_k=block_k,
        window=window, softcap=softcap, quantized=quantized,
        kv_value=kv_value,
    )

    def q_index(b, h, i, j, start_ref, qoff_ref):
        return (b, h, 0, i, 0)

    def kv_index(b, h, i, j, start_ref, qoff_ref):
        return (b, h, clamped_k_block(j, i, qoff_ref[0], block_q, block_k,
                                      n_k, window), 0)

    q_spec = pl.BlockSpec((1, 1, group, block_q, D), q_index)
    in_specs = [q_spec,
                pl.BlockSpec((1, 1, block_k, D), kv_index),
                pl.BlockSpec((1, 1, block_k, D), kv_index)]
    args = [start, q_offset, q, k, v]
    if quantized:
        # [B, Hkv, S, 1] f32: a trailing singleton keeps the block rank-2
        # in (sublane, lane) with a full-dim lane (always legal)
        in_specs += [pl.BlockSpec((1, 1, block_k, 1), kv_index)] * 2
        args += [k_scale, v_scale]
    return pl.pallas_call(
        kernel,
        name="flash_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, Hkv, n_q, n_k),
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((rows, _LANES), jnp.float32),
                pltpu.VMEM((rows, _LANES), jnp.float32),
                pltpu.VMEM((rows, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
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
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Returns [B, T, Hq, D] in q.dtype. Pads T/S/D to tile multiples
    internally; padding key slots are excluded by the causal mask (they
    lie beyond every query's global slot). The tiles are
    `tiling.flash_blocks`'; `block_q` / `block_k` override them (tests,
    scripts/flash_kernel_bench.py).

    With k_scale/v_scale, k/v are fp8 codes from a quantized KV cache
    and dequantize per block IN-KERNEL (the paged kernel's fp8 story):
    the cache never materializes as a dense bf16 copy in HBM, which is
    the entire point of fp8 KV. Scales cross as f32 — Mosaic has no f16
    vectors — at 1/D the footprint of the codes."""
    from bigdl_tpu.ops.pallas import interpret_mode

    B, T, Hq, D = q.shape
    _, S, Hkv, _ = k.shape
    group = Hq // Hkv
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    if interpret is None:
        interpret = interpret_mode()
    if start is None:
        start = jnp.zeros((B,), jnp.int32)
    if q_offset is None:
        q_offset = jnp.zeros((), jnp.int32)
    assert causal, "non-causal path uses ops.attention (bidirectional encoders)"

    block_q, block_k = flash_blocks(T, S, D, group, k.dtype.itemsize,
                                    block_q, block_k)
    Tp, Sp, Dp = round_up(T, block_q), round_up(S, block_k), round_up(D, _LANES)
    steps = B * Hkv * (Tp // block_q) * (Sp // block_k)
    live = B * Hkv * flash_live_blocks(T, S, block_q, block_k, window=window)
    routes.note("flash", f"q {group}x{block_q} x k {block_k}",
                f"{live} of {steps} steps live at T={T} S={S}")

    # [B, Hkv, group, T, D]: query head h is head h % group of KV head
    # h // group
    qt = jnp.transpose(q.reshape(B, T, Hkv, group, D), (0, 2, 3, 1, 4))
    kt = jnp.transpose(k, (0, 2, 1, 3))  # [B, Hkv, S, D]
    vt = jnp.transpose(v, (0, 2, 1, 3))
    qt = jnp.pad(qt, ((0, 0),) * 3 + ((0, Tp - T), (0, Dp - D)))
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
        window, softcap, scale, block_q, block_k, interpret,
    )
    out = jnp.transpose(out[:, :, :, :T, :D], (0, 3, 1, 2, 4))
    return out.reshape(B, T, Hq, D)
