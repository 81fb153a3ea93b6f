"""Quantized / dense linear op.

Equivalent of `LowBitLinear.forward` in the reference
(low_bit_linear.py:606-716): one entry point that dispatches on weight
type and shape. The prefill/decode split the reference implements with
two SYCL kernels (`xe_linear.forward_new` vs `xe_batch.batch_forward`)
is ONE kernel here, `ops/pallas/qmatmul.qmatmul`, whose row tile follows
the row count: at decode's few rows packed weights cross HBM as stored,
at larger shapes (prefill, continuous batches, speculative verify, QLoRA
training) weight tiles decode once in VMEM and feed the MXU, and the
dequantized copy never round-trips HBM. Only what `fused_why_not` refuses
(odd O/K, a format with no decoder, the kernels switched off) takes the
in-graph XLA dequant that XLA fuses into the matmul.

The fused paths are wrapped in a custom_vjp so training (QLoRA's frozen
low-bit base) can differentiate through them. The backward is fused
too: dx = g @ dequant(W) routes to the Pallas dx kernel
(ops/pallas/qbackward.py), which dequantizes weight tiles per-chunk in
VMEM straight into the MXU — the bf16 rematerialized copy of W the XLA
remat path writes to HBM every train step never exists ("Training
Transformers with 4-bit Integers", arxiv 2306.11987). It reads every
format through the forward's shared decoder; the XLA remat stays
available under `fused_backward_scope(False)` as the parity oracle.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.ops import routes
from bigdl_tpu.quant import QTensor
from bigdl_tpu.quant.qtensor import KERNEL_FIELDS

# A route note says `gemv` up to this many rows and `gemm` above, the
# reference's `use_batch_forward` split (low_bit_linear.py:272-309): at
# few rows the matmul is bound by the weight's bandwidth, above them each
# decoded weight tile is amortized over a [block_m, K] row tile. ONE
# kernel serves both (`ops/pallas/qmatmul.qmatmul`, whose row tile
# `tiling.pick_block_m` picks): the threshold picks the word, no kernel.
_GEMV_MAX_ROWS = 32


def _rows(shape) -> int:
    n = 1
    for d in shape[:-1]:
        n *= d
    return n


# Every qtype with a decode path, and the multiple its LOGICAL contraction
# dim must be of: every per-format shape rule as one divisibility check.
# Whole quant blocks per packed plane (sym/asym_int4 64, nf4/fp4 128), whole
# super-blocks (k-quants 256), and 128-lane alignment of the finest plane
# split for the multi-plane formats (fp6/q2_k 512; sym_int5/nf3/q5_k 1024:
# the eighth-split 1-bit plane slices at K/8-byte offsets). Forward
# (`qmatmul`, GEMV and GEMM shapes alike) and backward (`qmatmul_dx`, whose
# chunk walk has the forward's plane-split period) read every one of them
# through the shared decoder, `qdecode.spec_for`; graftlint DSP001 / DSP003
# hold the table to the qtype registry.
_QGEMV_QTYPES = {
    "sym_int4": 64,
    "asym_int4": 64,
    "nf4": 128,
    "fp4": 128,
    "sym_int8": 32,
    "asym_int5": 32,
    "fp8_e4m3": 128,
    "fp8_e5m2": 128,
    "sym_int5": 1024,
    "fp6": 512,
    "nf3": 1024,
    "q2_k": 512,
    "q3_k": 256,
    "q4_k": 256,
    "q5_k": 1024,
    "q6_k": 256,
}


def fused_why_not(w: QTensor, lead: Optional[int] = None) -> Optional[str]:
    """None when the packed-matmul kernels take `w`, whatever the row
    count, else the guard that refuses it, in the words a route note
    prints. The kernels tile a weight's last two dims `[O, K]`; `lead` is
    how many leading axes the caller's kernel indexes (`linear`: none, or
    the layer axis), None where any stack will do (`grouped_route`)."""
    from bigdl_tpu.ops.pallas import why_not_pallas
    from bigdl_tpu.ops.pallas.tiling import VMEM_BUDGET

    if lead is not None and w.data.ndim - lead != 2:
        return f"weight is rank {w.data.ndim - lead}, kernels take rank 2"
    k_multiple = _QGEMV_QTYPES.get(w.qtype)
    if k_multiple is None:
        return "no fused kernel registered for this qtype"
    out, kw_ = w.data.shape[-2:]
    if out % 128 != 0:
        return "O not a multiple of 128 lanes"
    # the kernels tile O at >= 128 rows (Mosaic lane rule forbids
    # smaller output tiles); if even a 128-row tile's persistent weight
    # block cannot fit half the scoped-VMEM budget (the other half is
    # the x/acc slabs), fall back to the XLA dequant path rather than
    # compile a kernel that overflows vmem
    row_bytes = kw_ * w.data.dtype.itemsize
    if 128 * row_bytes > VMEM_BUDGET // 2:
        return "a 128-row weight tile exceeds half the VMEM budget"
    if w.shape[-1] % k_multiple != 0:
        return f"K not a multiple of {k_multiple}"
    return why_not_pallas()


def grouped_route(*stacks) -> Optional[str]:
    """None when every [O, K] weight of every stack takes a kernel
    whatever the row count, else the guard that refuses: `linear`'s
    format and shape rules applied to one weight of the stack. The
    stacks are a model's layers `[L, O, K]` (`qmatmul`, reading layer
    `layer` in place) or one MoE layer's experts `[.., E, O, K]` (the
    grouped kernel, `ops/pallas/moe_qmatmul.py`: rows sorted by expert,
    each tile's weights read packed from its expert)."""
    for w in stacks:
        if not isinstance(w, QTensor):
            return "weights are dense, not packed"
        if w.data.ndim < 3:
            return f"weight is rank {w.data.ndim}, not a stack"
        why = fused_why_not(w)
        if why is not None:
            return why
    return None


def _row_bytes(w: QTensor) -> int:
    return w.data.shape[-1] * w.data.dtype.itemsize


def _reads_bits(w: QTensor) -> bool:
    """Does a `linear` call on `w` read prepared scale bits in place?"""
    from bigdl_tpu.ops.pallas.qdecode import spec_for
    from bigdl_tpu.ops.pallas.qmatmul import bits_layout, prepared_bits

    return prepared_bits(w, bits_layout(
        spec_for(w.spec), w.data.shape[-2], _row_bytes(w))) is not None


def _words_note(w: QTensor) -> str:
    """What a `linear` call on `w` (no adapter) says of its decode loop in
    its route note, from the tile plan's own static shapes: nothing on the
    stored-layout loop; on the word path ``" words"``, with ``:inplace``
    where the codes are cut out where they lie (`qdecode.signed_field`)
    and ``:ragged:<tiles>`` where the last of that many word tiles is
    ragged (`tiling.ragged_word_tiles`). A whole-tile call of a format
    with no signed field says nothing, as it always has."""
    from bigdl_tpu.ops.pallas.qdecode import signed_field, spec_for
    from bigdl_tpu.ops.pallas.qmatmul import tile_form
    from bigdl_tpu.ops.pallas.tiling import WORD_BLOCK_O, word_tiles

    spec, O = spec_for(w.spec), w.data.shape[-2]
    if tile_form(spec, O, _row_bytes(w)) != "words":
        return ""
    note = (":inplace" if signed_field(spec) else "") + (
        f":ragged:{word_tiles(O)}" if O % WORD_BLOCK_O else "")
    return " words" + note if note else ""


def prepare_scale_bits(w, stacks: Optional[int] = None):
    """`w` with its float16 `scales` (and `mins`) a second time as the
    operand its kernel reads in place (`QTensor.scale_bits`, `min_bits`,
    `bits_layout`): uint16 bits, one block a word tile with the tile's rows
    on lanes where the word path runs (`qdecode.pack_major_bits`; a ragged
    last tile's block is whole, zeros past O), the stored `[.., O, nb]` on
    the stored-layout loop. What the kernels'
    wrappers otherwise derive from the float16 fields before EVERY call
    (a view XLA materialises, padded to 128 lanes, and the tile's
    transposes every grid step) is derived once, by whoever takes a tree
    to serve from (`llama.prepare_kernel_scales`).

    `stacks`: None for a weight `linear` multiplies by (`[O, C]`, or a
    stack of layers `[L, O, C]` read by layer index), else the number of
    expert stacks of the grouped call it is part of (2: a fused gate / up
    pair), whose plan picks the layout. `w` comes back as it is where no
    kernel would read the bits: not packed, refused by the shape guards,
    fp8 codes, a two-level format."""
    from bigdl_tpu.ops.pallas import moe_qmatmul as mq
    from bigdl_tpu.ops.pallas import qdecode
    from bigdl_tpu.ops.pallas.qmatmul import _f16_bits, bits_layout
    from bigdl_tpu.ops.pallas.tiling import WORD_BLOCK_O

    if not isinstance(w, QTensor) or w.spec.storage.startswith("fp8"):
        return w
    if fused_why_not(w) is not None:
        return w
    if stacks is None:
        layout = bits_layout(qdecode.spec_for(w.spec), w.data.shape[-2],
                             _row_bytes(w))
    else:
        layout = mq.bits_layout((w,) * stacks)
    if layout is None:
        return w
    rows = {"words": WORD_BLOCK_O, "words:paired": WORD_BLOCK_O // 2}
    lay = (_f16_bits if layout == "stored" else
           functools.partial(qdecode.pack_major_bits, rows=rows[layout]))
    return dataclasses.replace(
        w, scale_bits=lay(w.scales), bits_layout=layout,
        min_bits=None if w.mins is None else lay(w.mins))


def stacks_out(group: dict, names) -> tuple[dict, dict]:
    """(`group` with the whole-stack operands of the weights `names` taken
    out, those operands by name and field): what a layer scan must not
    slice, because a slice handed to a Mosaic call is first copied whole.
    The packed codes always; the prepared scale bits where the tree has
    them (`prepare_scale_bits`). fp8 codes keep their slices (they reach a
    kernel through a bitcast that would copy the whole stack instead).
    The scan slices what is left, the float16 scales included (nobody reads
    them where the bits are read), and its body puts the whole stacks back
    with `stacks_in` and hands `linear` / `_moe_dispatch` the layer's
    index."""
    kept = {}
    for n in names:
        w = group[n]
        if w.spec.storage.startswith("fp8"):
            continue
        kept[n] = {f: getattr(w, f) for f in ("data", *KERNEL_FIELDS)
                   if getattr(w, f) is not None}
    return ({n: dataclasses.replace(w, **dict.fromkeys(kept[n]))
             if n in kept else w for n, w in group.items()}, kept)


def stacks_in(p: dict, kept: dict) -> dict:
    """One layer's weights `p` with `stacks_out`'s whole stacks back in."""
    return {**p, **{n: dataclasses.replace(p[n], **fields)
                    for n, fields in kept.items()}}


# Backward-path selector, read at TRACE time inside the custom_vjp bwd
# rules: True routes dx through the fused Pallas kernel, False keeps the
# XLA rematerialized dequant (the parity oracle). Trace-time means the
# flag is baked into the jaxpr — flipping it under an already-jitted
# train step does nothing until retrace, which is exactly the semantics
# a per-run knob (train/qlora.make_train_step(fused_backward=...)) needs.
_FUSED_BACKWARD = True


def fused_backward_enabled() -> bool:
    """Whether custom_vjp backward rules traced now use the fused dx."""
    return _FUSED_BACKWARD


@contextlib.contextmanager
def fused_backward_scope(enabled: bool = True):
    """Scope the backward-path selector around a trace (the train-step
    builder wraps its value_and_grad in this)."""
    global _FUSED_BACKWARD
    prev = _FUSED_BACKWARD
    _FUSED_BACKWARD = bool(enabled)
    try:
        yield
    finally:
        _FUSED_BACKWARD = prev


def _fused_dx(g: jax.Array, w: QTensor):
    """dx = g @ dequant(W) for the custom_vjp bwd rules: the fused Pallas
    kernel (`qmatmul_dx`, the forward's decoder and so its formats and
    its K alignment: the vjp only wraps fused forwards, whose guards
    already held) unless the selector asks for the XLA rematerialized
    dequant."""
    if _FUSED_BACKWARD:
        from bigdl_tpu.ops.pallas import qmatmul_dx
        from bigdl_tpu.ops.pallas.tiling import WORD_BLOCK_O

        return qmatmul_dx(g, w, out_dtype=g.dtype, block_o=WORD_BLOCK_O)
    wd = w.dequantize(g.dtype)
    return jnp.einsum("...o,ok->...k", g, wd, preferred_element_type=g.dtype)


def _zero_cotangent(w: QTensor) -> QTensor:
    """Symbolic-zero cotangent for the frozen quantized weight: float
    leaves get typed zeros, integer code/sub-scale leaves get float0
    (the tangent type jax assigns non-differentiable dtypes)."""
    def z(a):
        if jnp.issubdtype(a.dtype, jnp.inexact):
            return jnp.zeros(a.shape, a.dtype)
        return np.zeros(a.shape, jax.dtypes.float0)

    return jax.tree.map(z, w)


def _layer_of(w: QTensor, layer) -> QTensor:
    """One layer's weight, for the paths that take rank 2: `w` as it is,
    or with its stacked codes sliced at `layer` (a copy of them)."""
    if layer is None:
        return w
    return dataclasses.replace(w, data=jax.lax.dynamic_index_in_dim(
        w.data, layer, keepdims=False))


@jax.custom_vjp
def _fused_matmul(x: jax.Array, w: QTensor, layer):
    from bigdl_tpu.ops.pallas import qmatmul

    return qmatmul(x, w, out_dtype=x.dtype, layer=layer)


def _fused_fwd(x, w, layer):
    return _fused_matmul(x, w, layer), (w, layer)


def _fused_bwd(res, g):
    # dx = g @ dequant(W) through the fused Pallas kernel (or the XLA
    # remat oracle under fused_backward_scope(False)), which takes one
    # layer's weight; W itself is frozen, so its cotangent is a symbolic
    # zero
    w, layer = res
    return _fused_dx(g, _layer_of(w, layer)), _zero_cotangent(w), None


_fused_matmul.defvjp(_fused_fwd, _fused_bwd)


def _lora_cat_operands(x: jax.Array, lora, compute_dtype):
    """Canonicalize a lora triple (a, b, scale) — shared [r, K]/[O, r]
    or batched per-row [B, rb, K]/[B, O, rb]/[B] — into the fused
    epilogue's concatenated operand form (a_cat [R, K], b_cat [O, R],
    gate [M, R]), or None when the shape is ineligible (rank columns
    would blow the epilogue's VMEM allowance, or the batched form does
    not line up with x's rows). Column order is group-major, rank
    within; gate row m carries scale_g in its own group g's columns and
    0 elsewhere, so each row receives exactly its adapter's delta."""
    from bigdl_tpu.ops.pallas.tiling import lora_fused_ok

    a, b, scale = lora
    K = x.shape[-1]
    M = _rows(x.shape)
    if a.ndim == 3:  # batched per-row adapters (serving)
        if x.ndim != 3 or a.shape[0] != x.shape[0]:
            return None
        B, rb, ka = a.shape
        R = B * rb
        if ka != K or rb == 0 or not lora_fused_ok(R, K):
            return None
        T = x.shape[1]
        a_cat = a.reshape(R, K)
        b_cat = jnp.moveaxis(b, 0, 1).reshape(b.shape[1], R)
        grp = jnp.repeat(jnp.arange(B, dtype=jnp.int32), T)  # row -> group
        col = jnp.repeat(jnp.arange(B, dtype=jnp.int32), rb)  # col -> group
        sc = jnp.asarray(scale).astype(compute_dtype)
        gate = ((grp[:, None] == col[None, :]).astype(compute_dtype)
                * sc[grp][:, None])
        return a_cat, b_cat, gate
    r, ka = a.shape
    if ka != K or r == 0 or not lora_fused_ok(r, K):
        return None
    sc = jnp.asarray(scale).astype(compute_dtype)
    gate = jnp.broadcast_to(sc, (M, r))
    return a, b, gate


@jax.custom_vjp
def _fused_lora_matmul(x: jax.Array, w: QTensor, a_cat, b_cat, gate):
    from bigdl_tpu.ops.pallas import qmatmul_lora

    return qmatmul_lora(x, w, a_cat, b_cat, gate, out_dtype=x.dtype)


def _fused_lora_fwd(x, w, a_cat, b_cat, gate):
    y = _fused_lora_matmul(x, w, a_cat, b_cat, gate)
    return y, (x, w, a_cat, b_cat, gate)


def _fused_lora_bwd(res, g):
    # the base-weight dx term routes through the fused kernel exactly
    # like _fused_bwd; the epilogue's product-rule terms stay on XLA
    # (rank-R operands are far below 128-lane tile economics). For
    # v = (x @ A^T) * gate, y = x @ dq(W)^T + v @ B^T
    x, w, a, b, gt = res
    cd = g.dtype
    K = x.shape[-1]
    O = g.shape[-1]
    xf = x.reshape(-1, K).astype(cd)
    gf = g.reshape(-1, O)
    ac, bc, gtc = a.astype(cd), b.astype(cd), gt.astype(cd)
    u = xf @ ac.T  # [M, R]
    dv = gf @ bc  # [M, R]
    du = dv * gtc
    dxw = _fused_dx(gf, w).astype(cd)
    dx = (dxw + du @ ac).reshape(x.shape).astype(x.dtype)
    da = (du.T @ xf).astype(a.dtype)
    db = (gf.T @ (u * gtc)).astype(b.dtype)
    dgate = (dv * u).astype(gt.dtype)
    return dx, _zero_cotangent(w), da, db, dgate


_fused_lora_matmul.defvjp(_fused_lora_fwd, _fused_lora_bwd)


def lora_epilogue(x: jax.Array, a: jax.Array, b: jax.Array,
                  scale: jax.Array, compute_dtype=jnp.bfloat16) -> jax.Array:
    """The multi-tenant LoRA epilogue ``(x @ A^T) @ B^T * scale`` added
    to a (fused dequant-)GEMM's output — the base weight stays packed
    and shared while the adapter applies unquantized on top
    (serving/adapters.py; arxiv 2301.12017's composability argument
    against merge-and-requantize per tenant).

    Two shapes, one contract:

    - shared adapter (training / single-tenant): ``a [r, in]``,
      ``b [out, r]``, scalar ``scale`` — every row of ``x [..., in]``
      goes through the same pair;
    - batched per-row adapters (the serving engine's heterogeneous
      decode batch): ``a [B, r, in]``, ``b [B, out, r]``, ``scale [B]``
      against ``x [B, T, in]`` — slot ``i`` applies ITS adapter; rank
      rows/columns zero-padded to the batch's rank bucket contribute
      exactly 0, so adapter-less slots ride along unchanged and one
      compiled program serves any mix at or below the bucket.
    """
    xc = x.astype(compute_dtype)
    ac, bc = a.astype(compute_dtype), b.astype(compute_dtype)
    if a.ndim == 3:  # batched per-row adapters
        xa = jnp.einsum("btk,brk->btr", xc, ac)
        y = jnp.einsum("btr,bor->bto", xa, bc)
        return y * scale.astype(compute_dtype)[:, None, None]
    xa = jnp.einsum("...k,rk->...r", xc, ac)
    # scale is cast to the compute dtype, never the other way: an f32
    # scale leaf (adapter artifacts store it as f32) must not promote
    # the delta — a promoted residual changes the scan carry's dtype
    # on wo/w_down targets and breaks the layer scan outright
    return (jnp.einsum("...r,or->...o", xa, bc)
            * jnp.asarray(scale).astype(compute_dtype))


def linear(
    x: jax.Array,
    w: Union[QTensor, jax.Array],
    bias: Optional[jax.Array] = None,
    compute_dtype=jnp.bfloat16,
    lora=None,
    layer=None,
) -> jax.Array:
    """y = x @ W^T (+ bias) (+ LoRA delta). W has logical shape
    [out_features, in_features].

    With ``layer`` (a traced index) the packed codes ``w.data`` are those
    of a whole stack of layers ``[L, O, C]`` and the kernel reads layer
    ``layer`` of them in place, and so are the prepared scale bits where
    ``w`` carries them (`prepare_scale_bits`); every other field of ``w``
    is that layer's own. A caller inside a layer scan hands the codes over this
    way because a per-layer slice given to a Mosaic call is copied whole
    first (`models/llama.forward` says which weights it does this for).

    QTensor weights route to the fused Pallas dequant kernel whenever
    `fused_why_not` has no objection; otherwise the dequantization is
    expressed in-graph so XLA fuses unpack+scale into the matmul's
    operand read. Weights stay packed in HBM either way.

    ``lora`` is an optional (a, b, scale) triple in either
    `lora_epilogue` shape. On the fused path it folds into the kernel's
    writeback (`ops/pallas/qmatmul.qmatmul_lora` — zero extra
    activation HBM round trips); everywhere else — XLA fallback, exempt
    formats, dense weights, operand shapes past the epilogue's VMEM
    allowance — it applies as the `lora_epilogue` einsum pair, which
    doubles as the fused path's parity oracle.
    """
    if isinstance(w, QTensor):
        stacked = layer is not None
        assert not (stacked and lora is not None), (
            "an adapter's base weight comes sliced: the backward's dx "
            "kernel takes one layer")
        why = fused_why_not(w, lead=int(stacked))
        shape_class = "gemv" if _rows(x.shape) <= _GEMV_MAX_ROWS else "gemm"
        routes.note(
            "linear", f"pallas:{shape_class}" if why is None else "xla",
            f"{w.qtype} M{_rows(x.shape)} K{w.shape[-1]} "
            f"O{w.data.shape[-2]} " + ("stack" if stacked else "slice")
            + (_words_note(w) if why is None and lora is None else "")
            + (f" ({why})" if why is not None else " scales:stack"
               if lora is None and _reads_bits(w) else " scales:slice"))
        if why is None:
            xc = x.astype(compute_dtype)
            if lora is not None:
                ops = _lora_cat_operands(x, lora, compute_dtype)
                if ops is not None:
                    y = _fused_lora_matmul(xc, w, *ops)
                    if bias is not None:
                        y = y + bias.astype(compute_dtype)
                    return y
            y = _fused_matmul(xc, w, layer)
            if lora is not None:
                y = y + lora_epilogue(x, *lora, compute_dtype)
            if bias is not None:
                y = y + bias.astype(compute_dtype)
            return y
        wd = _layer_of(w, layer).dequantize(compute_dtype)
    else:
        wd = w.astype(compute_dtype)
    y = jnp.einsum(
        "...k,ok->...o",
        x.astype(compute_dtype),
        wd,
        preferred_element_type=compute_dtype,
    )
    if lora is not None:
        y = y + lora_epilogue(x, *lora, compute_dtype)
    if bias is not None:
        y = y + bias.astype(compute_dtype)
    return y


def _half_split_perm(a: jax.Array, n: int) -> jax.Array:
    """Reorder the last axis from half-split to shard-major order.

    `pack_nibbles` stores column j and column j + K/2 in the same byte,
    so shard s of the packed axis holds columns [s*h, (s+1)*h) of EACH
    half (h = K/(2n)). [..., 2, n, h] -> [..., n, 2, h]: after this, a
    contiguous 1/n slice of the last axis is exactly the column set the
    matching packed-byte slice carries. Applied to x and to the
    per-block scales/mins (whose last axis has the same half-block
    structure at K/block granularity)."""
    m = a.shape[-1] // (2 * n)
    a = a.reshape(*a.shape[:-1], 2, n, m)
    return a.swapaxes(-3, -2).reshape(*a.shape[:-3], 2 * n * m)


def row_parallel_linear(
    x: jax.Array,
    w: Union[QTensor, jax.Array],
    comm,
    bias: Optional[jax.Array] = None,
    compute_dtype=jnp.bfloat16,
) -> jax.Array:
    """`linear` for a row-parallel (contraction-sharded) weight, run per
    shard under shard_map with an EXPLICIT all-reduce epilogue
    (parallel/qcollectives.py): ``jax.lax.psum`` for comm qtype "none",
    the block-scaled ring all-reduce with error feedback for a
    quantized `comm_qtype`. A 1-wide axis (or no comm) is plain
    `linear`.

    Left to GSPMD, the XLA dequant of a K-sharded packed weight
    all-gathers the whole packed weight to every device on every call
    (the half-split nibble layout below is not a block sharding of K,
    so the partitioner gives up: seen in the compiled decode step, PR
    21), and a Mosaic call cannot be partitioned at all. Per shard,
    each device decodes its own bytes, with the fused kernel where the
    shard's shape is eligible.

    The shard_map's in_specs shard only `comm.axis_name` (x's
    contraction dim, W's K dim); other mesh axes see the operands
    replicated at this boundary, which is the decode-epilogue regime the
    quantized ring targets (tiny M, weight-stationary). Bias is added
    AFTER the reduce, once.

    QTensor weights need care: unlike GSPMD (where sharding is pure
    layout and XLA sees the whole dequant+matmul), shard_map hands each
    shard a literal byte slice. `pack_nibbles`' half-split layout means
    byte j of the packed axis carries logical columns j AND j + K/2, so
    a contiguous byte slice is a NON-contiguous column set — x and the
    per-block scales are permuted into that same shard-major order
    before slicing (`_half_split_perm`), which keeps every shard's
    sub-QTensor self-consistent and the fused dequant-GEMM path intact.
    Layouts that cannot be sliced consistently (bit planes, k-quant
    superblocks, shards that straddle a scale block) dequantize once and
    take the dense partial-matmul path instead."""
    if comm is None or comm.axis_size <= 1:
        return linear(x, w, bias, compute_dtype)
    import dataclasses

    from bigdl_tpu.parallel import qcollectives as qc
    from jax.sharding import PartitionSpec as P

    ax = comm.axis_name
    n = comm.axis_size
    if isinstance(w, QTensor):
        spec = w.spec
        K = w.shape[-1]
        h = K // (2 * n)  # columns per nibble plane per shard
        if (spec.storage == "packed_u8" and not spec.superblock
                and w.sub_scales is None
                and K % (2 * n) == 0 and h % spec.block_size == 0):
            x = _half_split_perm(x, n)
            w = dataclasses.replace(
                w, scales=_half_split_perm(w.scales, n),
                mins=(None if w.mins is None
                      else _half_split_perm(w.mins, n)),
            )
        elif (spec.storage in ("int8", "fp8_e4m3", "fp8_e5m2")
                and not spec.superblock and w.sub_scales is None
                and K % n == 0 and (K // n) % spec.block_size == 0):
            pass  # unpacked codes: contiguous K slices self-consistent
        else:
            w = w.dequantize(compute_dtype)
    if not isinstance(w, QTensor) and x.shape[-1] % n:
        # contraction dim not shardable: keep the exact implicit psum
        return linear(x, w, bias, compute_dtype)
    xspec = P(*([None] * (x.ndim - 1) + [ax]))
    wspec = P(None, ax)  # [O, K/n]; QTensor leaves take it as a prefix

    def part(xs, ws):
        y = linear(xs, ws, None, compute_dtype)
        return qc.quantized_psum(
            y, ax, qtype=comm.qtype, axis_size=n,
            block_size=comm.block_size,
            error_feedback=comm.error_feedback,
        )

    f = jax.shard_map(part, mesh=comm.mesh, in_specs=(xspec, wspec),
                  out_specs=P(), check_vma=False)
    y = f(x, w)
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y


def col_parallel_linear(
    x: jax.Array,
    w: Union[QTensor, jax.Array],
    comm,
    bias: Optional[jax.Array] = None,
    compute_dtype=jnp.bfloat16,
) -> jax.Array:
    """`linear` for a column-parallel (output-sharded) weight, run per
    shard under shard_map: x is replicated over `comm.axis_name`, each
    device multiplies by its own rows of W (no layout care needed: every
    QTensor field is row-leading) and the output stays sharded on its
    last axis. XLA cannot partition a Mosaic call, so this is how the
    fused kernels run under tensor parallelism. A 1-wide axis (or no
    comm) is plain `linear`."""
    if comm is None or comm.axis_size <= 1:
        return linear(x, w, bias, compute_dtype)
    from jax.sharding import PartitionSpec as P

    ax = comm.axis_name
    f = jax.shard_map(
        lambda xs, ws: linear(xs, ws, None, compute_dtype),
        mesh=comm.mesh, in_specs=(P(), P(ax, None)),
        out_specs=P(*([None] * (x.ndim - 1) + [ax])), check_vma=False)
    y = f(x, w)
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y
