"""Pallas fused low-bit backward: dx and dW kernels for the dequant
matmul family.

PR 9 fused the FORWARD dequant-GEMM behind a custom_vjp but left the
backward on the XLA rematerialized-dequant path: dx = g @ dequant(W)
re-materializes a full bf16 copy of W in HBM every train step — the
exact bytes cliff the forward fusion killed ("Training Transformers
with 4-bit Integers", arxiv 2306.11987; the INT4 composability analysis
of arxiv 2301.12017 makes the same bytes-bound argument). This module
closes the loop:

* ``qmatmul_dx``: dx[M, K] = g[M, O] @ dequant(W)[O, K], dequantizing
  weight tiles per-chunk in VMEM straight into the MXU. The access
  pattern is the TRANSPOSE of the forward's (the contraction runs over
  the weight's O rows, not its K columns), which needs its own tile
  policy (`tiling.pick_block_m_dx` / `chunk_target_dx`): the kernel
  grids over (M tiles, O tiles) with o innermost as the reduction axis
  and keeps a [block_m, K] f32 accumulator in VMEM scratch across the
  whole o sweep — packed weights cross HBM once per M tile, g and dx
  exactly once, and the dequantized copy never exists in HBM.
* ``dw_matmul``: dW[O, K] = g^T @ x as a tiled accumulation (grid over
  (O tiles, M tiles), m innermost), the dW-shaped grad any
  unfrozen/bf16-shadow path needs. No dequant is involved — the value
  is pricing and fusing the train step's third GEMM on the same tile
  policy the roofline model imports.

Both kernels are driven by the same table-driven decoder
(`qdecode.DecodeSpec` / `spec_for`) as the forward, so every registered
format gets a fused backward with ZERO per-format kernel code.

Decode chunks accumulate into the [block_m, K] scratch through static
lane slices; chunk boundaries come from `qdecode.walk`, which aligns
them to the format's plane splits (128-multiples at every real shape),
the same alignment contract the forward kernel's x-slices rely on.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops.pallas import qdecode
from bigdl_tpu.ops.pallas.qdecode import DecodeSpec
from bigdl_tpu.ops.pallas.tiling import (
    DX_ACC_BPE, VMEM_LIMIT_BYTES, chunk_target_dx, finest_split, pick_block_m,
    pick_block_m_dx, pick_block_o, pick_block_o_dw, round_up,
)


def _params_reduce():
    # the innermost grid axis is a sequential reduction into VMEM
    # scratch — it must not be parallelized/reordered
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


# ---------------------------------------------------------------------------
# dx = g @ dequant(W): one [block_m, K] output row tile, any DecodeSpec
# ---------------------------------------------------------------------------

def _dx_kernel(g_ref, w_ref, *rest, K: int, ck: int, spec: DecodeSpec):
    """One (m, o) grid cell: acc[:, chunk] += g_tile @ dq(W_chunk) over
    statically-unrolled chunks of the logical K axis. The [block_m, K]
    accumulator lives in VMEM scratch across the whole o sweep (o is the
    reduction axis here — the transpose of the forward's contract);
    dequant temporaries stay O(block_o * ck), same bound as the forward,
    because each decoded chunk is dead after its dot."""
    side_refs = rest[:-2]
    o_ref, acc_ref = rest[-2], rest[-1]
    o = pl.program_id(1)
    n_o = pl.num_programs(1)

    @pl.when(o == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    side = qdecode.load_side(spec, side_refs)
    w = w_ref[:]  # packed codes [block_o, row_bytes]
    g = g_ref[:].astype(jnp.bfloat16)  # [block_m, block_o]
    for e0, c in qdecode.walk(K, spec.planes, ck):
        wd = qdecode.decode_chunk(spec, K, w, side, e0, c)  # bf16 [bo, c]
        acc_ref[:, e0:e0 + c] += jax.lax.dot_general(
            g, wd, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(o == n_o - 1)
    def _finalize():
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("spec", "out_dtype", "block_m", "block_o",
                              "ck", "K", "interpret")
)
def _dxmm(spec, out_dtype, block_m: int, block_o: int, ck: int, K: int,
          interpret: bool, g2, w, *side):
    Mp = g2.shape[0]
    O = w.shape[0]
    row = lambda m, o: (o, 0)  # weight-side blocks follow the O grid dim
    in_specs = [
        pl.BlockSpec((block_m, block_o), lambda m, o: (m, o),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((block_o, w.shape[1]), row, memory_space=pltpu.VMEM),
    ] + [
        pl.BlockSpec((block_o, a.shape[1]), row, memory_space=pltpu.VMEM)
        for a in side
    ]
    # grid order (m, o): o innermost is the REDUCTION sweep — the dx row
    # tile accumulates in scratch while weight tiles stream through, so
    # packed weights are re-fetched once per M tile (the same fetch
    # pattern benchmark/roofline.bwd_dx_cost prices)
    return pl.pallas_call(
        functools.partial(_dx_kernel, K=K, ck=ck, spec=spec),
        name="qmatmul_dx",
        grid=(Mp // block_m, O // block_o),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (block_m, K), lambda m, o: (m, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((Mp, K), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, K), jnp.float32)],
        compiler_params=_params_reduce(),
        interpret=interpret,
    )(g2, w, *side)


def qmatmul_dx(
    g: jax.Array,  # [..., O] upstream cotangent
    w,  # QTensor (any registered non-dense qtype)
    out_dtype=jnp.bfloat16,
    block_o: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """dx[..., K] = g @ dequant(W), fused, for any QTensor whose format
    the registry covers — the backward twin of `qmatmul.qmatmul`. The
    decode recipe comes from the same `qdecode.spec_for` table, so a
    newly registered format gets a fused backward with no kernel code.

    Parity oracle: the XLA rematerialized dequant
    ``g @ w.dequantize(...)`` (ops/linear._fused_bwd's fallback arm)."""
    from bigdl_tpu.ops.pallas import interpret_mode
    from bigdl_tpu.ops.pallas.qmatmul import _side_arrays, _validate

    if interpret is None:
        interpret = interpret_mode()
    spec = qdecode.spec_for(w.spec)
    data = w.data
    if w.spec.storage.startswith("fp8"):
        data = jax.lax.bitcast_convert_type(data, jnp.uint8)
    side = _side_arrays(spec, w.scales, w.mins, w.sub_scales, w.sub_mins)

    *lead, O = g.shape
    K = w.shape[-1]
    assert data.shape[0] == O, (data.shape, g.shape)
    _validate(spec, K, data)

    M = 1
    for d in lead:
        M *= d
    block_m = pick_block_m_dx(M, K)
    Mp = round_up(max(M, 1), block_m)
    g2 = g.reshape(M, O).astype(jnp.bfloat16)
    if Mp != M:
        g2 = jnp.pad(g2, ((0, Mp - M), (0, 0)))

    persist_row = data.shape[1] * data.dtype.itemsize + sum(
        a.shape[1] * a.dtype.itemsize for a in side)
    bo = pick_block_o(O, persist_row, cap=block_o)
    persist = (block_m * K * DX_ACC_BPE + bo * persist_row
               + block_m * bo * 2)
    ck = chunk_target_dx(bo, block_m, persist,
                         finest_split(K, spec.planes),
                         temp_bpe=20 if spec.mins else 14)
    dx = _dxmm(spec, jnp.dtype(out_dtype), block_m, bo, ck, K,
               bool(interpret), g2, data, *side)
    return dx[:M].reshape(*lead, K)


# ---------------------------------------------------------------------------
# dW = g^T @ x: tiled accumulation for unfrozen / bf16-shadow paths
# ---------------------------------------------------------------------------

def _dw_kernel(g_ref, x_ref, o_ref, acc_ref):
    """One (o, m) grid cell: acc += g_tile^T @ x_tile. The [block_o, K]
    accumulator persists across the m sweep (m innermost = reduction);
    the output is written once on the last m step."""
    m = pl.program_id(1)
    n_m = pl.num_programs(1)

    @pl.when(m == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    g = g_ref[:].astype(jnp.bfloat16)  # [block_m, block_o]
    x = x_ref[:].astype(jnp.bfloat16)  # [block_m, K]
    acc_ref[:] += jax.lax.dot_general(
        g, x, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )

    @pl.when(m == n_m - 1)
    def _finalize():
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("out_dtype", "block_m", "block_o",
                              "interpret")
)
def _dwmm(out_dtype, block_m: int, block_o: int, interpret: bool, g2, x2):
    Mp, Op = g2.shape
    K = x2.shape[1]
    return pl.pallas_call(
        _dw_kernel,
        name="dw_matmul",
        grid=(Op // block_o, Mp // block_m),
        in_specs=[
            pl.BlockSpec((block_m, block_o), lambda o, m: (m, o),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_m, K), lambda o, m: (m, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (block_o, K), lambda o, m: (o, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((Op, K), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_o, K), jnp.float32)],
        compiler_params=_params_reduce(),
        interpret=interpret,
    )(g2, x2)


def dw_matmul(
    g: jax.Array,  # [..., O] upstream cotangent
    x: jax.Array,  # [..., K] saved forward activations
    out_dtype=jnp.bfloat16,
    interpret: bool | None = None,
) -> jax.Array:
    """dW[O, K] = g^T @ x, tiled f32 accumulation over the row axis —
    the weight-shaped grad of y = x @ W^T for any unfrozen or
    bf16-shadow weight. Leading dims of g and x must match (they flatten
    to the shared row axis). Parity oracle: ``jnp.einsum('mo,mk->ok')``
    in f32."""
    from bigdl_tpu.ops.pallas import interpret_mode

    if interpret is None:
        interpret = interpret_mode()
    *lead_g, O = g.shape
    *lead_x, K = x.shape
    assert lead_g == lead_x, (g.shape, x.shape)
    M = 1
    for d in lead_g:
        M *= d

    block_m = pick_block_m(M, max(K, O))
    Mp = round_up(max(M, 1), block_m)
    block_o = pick_block_o_dw(O, K)
    Op = round_up(O, block_o)
    g2 = g.reshape(M, O).astype(jnp.bfloat16)
    x2 = x.reshape(M, K).astype(jnp.bfloat16)
    if Mp != M:  # zero rows contribute exactly 0 to the accumulation
        g2 = jnp.pad(g2, ((0, Mp - M), (0, 0)))
        x2 = jnp.pad(x2, ((0, Mp - M), (0, 0)))
    if Op != O:
        g2 = jnp.pad(g2, ((0, 0), (0, Op - O)))
    dw = _dwmm(jnp.dtype(out_dtype), block_m, block_o, bool(interpret),
               g2, x2)
    return dw[:O]
