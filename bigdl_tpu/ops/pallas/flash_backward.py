"""Trainable Pallas flash attention: forward with logsumexp residuals +
dq / dkv backward kernels under jax.custom_vjp.

The inference kernel (flash_attention.py) has no backward, so training
(cache=None) previously fell back to XLA attention, which materializes
the [T, S] probability matrix for the backward pass — at T=4096 that is
~2 GB/layer of saved activations, the reason long-context single-chip
QLoRA OOMs. This module recomputes attention blockwise in the backward
(the standard flash recipe): the forward additionally emits per-row
logsumexp, the backward recomputes P = exp(S - lse) per block and
accumulates

    dV = P^T dO
    dS = P * (dO V^T - rowsum(dO * O))
    dQ = dS K * scale        (one kernel, grid over Q blocks)
    dK = dS^T Q * scale      (one kernel, grid over K blocks, inner
                              loop over (q-head-in-group, Q block) so
                              GQA head groups accumulate without racing)

Scope: causal attention with left padding and optional sliding window —
the training shapes (llama-family QLoRA/LoRA/full finetune). Softcap
(gemma2) stays on the XLA path. The forward math duplicates
flash_attention._kernel deliberately: that kernel is silicon-validated
for inference and is not touched; this one adds the lse output (written
as an [.., 8]-lane block to satisfy the Mosaic lane rule: a block's
last dim is a multiple of 128 or the whole array dim).

Layouts follow the inference kernel: kernels run on [B, H, T, D] with
T/S/D padded to block multiples; the public wrapper takes/returns the
model's [B, T, H, D].
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
from bigdl_tpu.ops.pallas.tiling import MOSAIC_LANES
from bigdl_tpu.utils import round_up

_NEG_INF = -1e30
# one source for the lane width (tiling.py), shared with the forward
# kernel and the analytic roofline — the policies cannot drift
_LANES = MOSAIC_LANES
_LSE_LANES = 8  # full-dim lane block: satisfies the (sublane, 128) rule


def _masks(start_b, qoff, i, j, block_q, block_k, causal, window):
    rows = qoff + i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    cols = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    valid = cols >= start_b
    if causal:
        valid = valid & (cols <= rows)
    if window is not None:
        valid = valid & (cols > rows - window)
    return valid


def _block_live(qoff, i, j, block_q, block_k, causal, window):
    live = jnp.bool_(True)
    if causal:
        live = live & (j * block_k <= qoff + (i + 1) * block_q - 1)
    if window is not None:
        live = live & ((j + 1) * block_k - 1 > qoff + i * block_q - window)
    return live


def _fwd_kernel(
    start_ref, qoff_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
    m_scr, l_scr, acc_scr,
    *, scale, block_q, block_k, causal, window,
):
    b = pl.program_id(0)
    i, j = pl.program_id(2), pl.program_id(3)
    n_k = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    qoff = qoff_ref[0]

    @pl.when(_block_live(qoff, i, j, block_q, block_k, causal, window))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale
        k = qdecode.decode_kv(k_ref[0, 0])
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        valid = _masks(start_ref[b], qoff, i, j, block_q, block_k,
                       causal, window)
        s = jnp.where(valid, s, _NEG_INF)

        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)

        v = qdecode.decode_kv(v_ref[0, 0])
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == n_k - 1)
    def _finalize():
        l = l_scr[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
        # lse = m + log(l); fully-masked rows get -inf-ish, exp() -> 0
        lse = m_scr[:, :1] + jnp.log(safe_l)
        lse = jnp.where(l == 0.0, _NEG_INF, lse)
        lse_ref[0, 0] = jnp.broadcast_to(lse, (block_q, _LSE_LANES))


def _dq_kernel(
    start_ref, qoff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref, dq_scr,
    *, scale, block_q, block_k, causal, window,
):
    b = pl.program_id(0)
    i, j = pl.program_id(2), pl.program_id(3)
    n_k = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    qoff = qoff_ref[0]

    @pl.when(_block_live(qoff, i, j, block_q, block_k, causal, window))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = qdecode.decode_kv(k_ref[0, 0])
        s = jax.lax.dot_general(
            q * scale, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        valid = _masks(start_ref[b], qoff, i, j, block_q, block_k,
                       causal, window)
        lse = lse_ref[0, 0][:, :1]
        p = jnp.where(valid, jnp.exp(s - lse), 0.0)  # [BQ, BK]

        do = do_ref[0, 0].astype(jnp.float32)
        v = qdecode.decode_kv(v_ref[0, 0])
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        delta = delta_ref[0, 0][:, :1]
        ds = p * (dp - delta)
        dq_scr[:] = dq_scr[:] + scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(j == n_k - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(
    start_ref, qoff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref, dk_scr, dv_scr,
    *, scale, block_q, block_k, causal, window, n_q,
):
    b = pl.program_id(0)
    j = pl.program_id(2)
    gi = pl.program_id(3)  # inner loop over (q-head-in-group, Q block)
    n_gi = pl.num_programs(3)
    i = jax.lax.rem(gi, n_q)

    @pl.when(gi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    qoff = qoff_ref[0]

    @pl.when(_block_live(qoff, i, j, block_q, block_k, causal, window))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = qdecode.decode_kv(k_ref[0, 0])
        s = jax.lax.dot_general(
            q * scale, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        valid = _masks(start_ref[b], qoff, i, j, block_q, block_k,
                       causal, window)
        lse = lse_ref[0, 0][:, :1]
        p = jnp.where(valid, jnp.exp(s - lse), 0.0)  # [BQ, BK]

        do = do_ref[0, 0].astype(jnp.float32)
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        v = qdecode.decode_kv(v_ref[0, 0])
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        delta = delta_ref[0, 0][:, :1]
        ds = p * (dp - delta)
        dk_scr[:] = dk_scr[:] + scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(gi == n_gi - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _smem(shape):
    return pl.BlockSpec(
        shape, lambda *idx: tuple(0 for _ in shape), memory_space=pltpu.SMEM,
    )


def _fwd(q, k, v, start, qoff, scale, block_q, block_k, causal, window,
         interpret):
    B, Hq, Tp, D = q.shape
    _, Hkv, Sp, _ = k.shape
    group = Hq // Hkv
    n_q, n_k = Tp // block_q, Sp // block_k
    kernel = functools.partial(
        _fwd_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, window=window,
    )
    return pl.pallas_call(
        kernel,
        name="flash_train_fwd",
        grid=(B, Hq, n_q, n_k),
        in_specs=[
            _smem((B,)), _smem((1,)),
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, h // group, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, _LSE_LANES),
                         lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, Tp, D), q.dtype),
            jax.ShapeDtypeStruct((B, Hq, Tp, _LSE_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(start, qoff, q, k, v)


def _bwd(q, k, v, do, lse, delta, start, qoff, scale, block_q, block_k,
         causal, window, interpret):
    B, Hq, Tp, D = q.shape
    _, Hkv, Sp, _ = k.shape
    group = Hq // Hkv
    n_q, n_k = Tp // block_q, Sp // block_k

    dq_kernel = functools.partial(
        _dq_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, window=window,
    )
    dq = pl.pallas_call(
        dq_kernel,
        name="flash_train_dq",
        grid=(B, Hq, n_q, n_k),
        in_specs=[
            _smem((B,)), _smem((1,)),
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, _LSE_LANES),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, _LSE_LANES),
                         lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Tp, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(start, qoff, q, k, v, do, lse, delta)

    dkv_kernel = functools.partial(
        _dkv_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, window=window, n_q=n_q,
    )
    h_of = lambda h, gi: h * group + gi // n_q
    dk, dv = pl.pallas_call(
        dkv_kernel,
        name="flash_train_dkv",
        grid=(B, Hkv, n_k, group * n_q),
        in_specs=[
            _smem((B,)), _smem((1,)),
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, j, gi: (b, h_of(h, gi), gi % n_q, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, j, gi: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, j, gi: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, j, gi: (b, h_of(h, gi), gi % n_q, 0)),
            pl.BlockSpec((1, 1, block_q, _LSE_LANES),
                         lambda b, h, j, gi: (b, h_of(h, gi), gi % n_q, 0)),
            pl.BlockSpec((1, 1, block_q, _LSE_LANES),
                         lambda b, h, j, gi: (b, h_of(h, gi), gi % n_q, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, j, gi: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, j, gi: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, Sp, D), k.dtype),
            jax.ShapeDtypeStruct((B, Hkv, Sp, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(start, qoff, q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9),
)
def flash_attention_train(
    q, k, v, start,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
):
    """Differentiable flash attention. q [B,T,Hq,D]; k,v [B,S,Hkv,D];
    start [B] int32 left-pad offsets. Returns [B,T,Hq,D] in q.dtype.
    Training shapes only: q positions are 0..T-1 (no cache offset)."""
    out, _ = _train_fwd(
        q, k, v, start, causal, window, scale, block_q, block_k, interpret
    )
    return out


def _prep(q, k, v, start, scale, block_q, block_k, interpret):
    from bigdl_tpu.ops.pallas import interpret_mode

    B, T, Hq, D = q.shape
    _, S, Hkv, _ = k.shape
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    if interpret is None:
        interpret = interpret_mode()
    if start is None:
        start = jnp.zeros((B,), jnp.int32)
    block_q = min(block_q, round_up(T, 16))
    block_k = min(block_k, round_up(S, 16))
    Tp, Sp, Dp = round_up(T, block_q), round_up(S, block_k), round_up(D, _LANES)
    tr = lambda x, P, Dp_: jnp.pad(
        jnp.transpose(x, (0, 2, 1, 3)),
        ((0, 0), (0, 0), (0, P - x.shape[1]), (0, Dp_ - x.shape[3])),
    )
    qt, kt, vt = tr(q, Tp, Dp), tr(k, Sp, Dp), tr(v, Sp, Dp)
    qoff = jnp.zeros((1,), jnp.int32)
    return (qt, kt, vt, start.astype(jnp.int32), qoff, float(scale),
            block_q, block_k, bool(interpret), (B, T, Hq, D, S, Hkv))


def _train_fwd(q, k, v, start, causal, window, scale, block_q, block_k,
               interpret):
    (qt, kt, vt, start_i, qoff, scale_f, bq, bk, interp,
     (B, T, Hq, D, S, Hkv)) = _prep(
        q, k, v, start, scale, block_q, block_k, interpret)
    out_p, lse = _fwd(qt, kt, vt, start_i, qoff, scale_f, bq, bk,
                      causal, window, interp)
    out = jnp.transpose(out_p[:, :, :T, :D], (0, 2, 1, 3))
    residuals = (qt, kt, vt, start_i, qoff, out_p, lse,
                 (T, D, S, scale_f, bq, bk, interp))
    return out, residuals


def _train_bwd(causal, window, scale, block_q, block_k, interpret,
               residuals, g):
    qt, kt, vt, start_i, qoff, out_p, lse, shapes = residuals
    T, D, S, scale_f, bq, bk, interp = shapes
    B, Hq, Tp, Dp = qt.shape

    do = jnp.pad(
        jnp.transpose(g, (0, 2, 1, 3)),
        ((0, 0), (0, 0), (0, Tp - T), (0, Dp - D)),
    )
    # delta = rowsum(dO * O) per (b, h, q row) — cheap, computed in XLA
    delta = jnp.sum(do.astype(jnp.float32) * out_p.astype(jnp.float32),
                    axis=-1)  # [B, Hq, Tp]
    delta = jnp.broadcast_to(delta[..., None], (B, Hq, Tp, _LSE_LANES))

    dq_p, dk_p, dv_p = _bwd(
        qt, kt, vt, do, lse, delta, start_i, qoff, scale_f, bq, bk,
        causal, window, interp,
    )
    un = lambda x, L, like: jnp.transpose(
        x[:, :, :L, :D], (0, 2, 1, 3)
    ).astype(like)
    dq = un(dq_p, T, g.dtype)
    dk = un(dk_p, S, g.dtype)
    dv = un(dv_p, S, g.dtype)
    # start is int32: cotangent space is float0
    import numpy as np

    dstart = np.zeros(start_i.shape, jax.dtypes.float0)
    return dq, dk, dv, dstart


flash_attention_train.defvjp(_train_fwd, _train_bwd)


def flash_attention_trainable(
    q, k, v, start=None, causal: bool = True, window=None, scale=None,
    block_q: int = 128, block_k: int = 128, interpret=None,
):
    """start-defaulting wrapper (custom_vjp needs a concrete array for
    every differentiable positional arg)."""
    # without the causal term the mask has no `cols < S` bound, so padded
    # phantom key columns would leak softmax mass (same guard as the
    # inference kernel, flash_attention.py)
    assert causal, "non-causal path uses ops.attention (bidirectional)"
    if start is None:
        start = jnp.zeros((q.shape[0],), jnp.int32)
    return flash_attention_train(
        q, k, v, start, causal, window, scale, block_q, block_k, interpret
    )
