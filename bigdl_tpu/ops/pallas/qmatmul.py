"""Pallas fused dequant matmul (GEMV + tiled GEMM) for packed low-bit
weights.

TPU-native counterpart of the reference's low-bit GEMM/GEMV kernels
(`xe_linear.forward_new` for prefill, `xe_batch.batch_forward` for
decode; dispatch in low_bit_linear.py:606-716 of /root/reference).

ONE kernel body serves every registered qtype and every shape class:

* decode GEMV (rows <= 32): HBM-bandwidth-bound — the win over the XLA
  fallback (dequantize to bf16, then matmul) is that W crosses HBM
  packed, e.g. 0.5 byte/weight + one f16 scale per 32 for nibble
  formats, up to ~6x less weight traffic than bf16. On a v5e the kernel
  reads 43 to 50% of its HBM time where the word path runs (the code
  decode and the product bound it, not HBM: PERF.md section 6, PR 32)
  and a third where it cannot;
* prefill / batched / QLoRA GEMM (rows > 32): the same weight tiles are
  dequantized ONCE per [block_m, block_o] tile in VMEM and fed straight
  to the MXU — no in-graph bf16 weight materialization, no HBM round
  trip of the dequantized copy.

The per-format bit decode lives in `ops/pallas/qdecode.py` (one shared
decoder for GEMV, GEMM and, later, flash epilogues — a format is a
static `DecodeSpec`); tile/chunk policy lives in `ops/pallas/tiling.py`
(pure Python, shared with `benchmark/roofline.py`'s analytic cost
model). This module is tiling + epilogue: grid over (M tiles, O tiles),
and `qdecode`'s chunk loop over K, which bounds live
dequant temporaries to O(block_o * chunk) regardless of K. Where a 512-row tile fits, that loop runs on the tile read as
32-bit words and transposed once (k on sublanes, a block's scale a
sublane broadcast, docs/kernels.md#word-path), the last tile ragged where
O is no multiple of 512 (`tiling.ragged_word_tiles`: an LM head's
vocabulary); elsewhere in the stored layout. On the word path the packed
stack stays in HBM and the kernel copies each tile in itself, as words,
one grid step ahead (`qdecode.copy_tiles_ahead`): the DMA engine re-tiles
what the VALU otherwise would.

Layout contract (quant/numerics.py pack_nibbles / pack_planes): the
m-th split of a b-bit plane is a *contiguous* byte range unpacked with
one static shift — chunks walk logical elements within the finest plane
split, so every chunk reads one contiguous, lane-aligned slice per
plane and one slice of x (never a strided deinterleave, which costs an
XLA prologue per call).

Mosaic constraints found on real TPU (the CPU interpreter accepts all of
these, silently):

* no f16 vector type -> scales cross as uint16 bits and are decoded to
  f32 with integer ops in-kernel (r03);
* no lane-collapsing reshape -> in the stored layout per-block scales
  expand to per-element via a one-hot matmul (iota compare + MXU dot),
  not broadcast+reshape (r03); that matmul cost a third of the kernel's
  time and rounds the scales (PR 32), which is why the word path spreads
  them along sublanes instead;
* the last two dims of every BlockSpec must be (sublane, 128)-aligned
  UNLESS the block covers the whole array dim (r05). This outlaws any
  lane-tiling of the skinny scale arrays (K/32 columns: tiles of
  112/224 lanes). The design that satisfies the rule at every real
  shape: grid over (M, O) with every operand block FULL in the lane
  dim (full-dim blocks are always legal), M tiles a multiple of 8
  sublanes, O tiles a multiple of 128 lanes, and VMEM bounded by the
  in-kernel chunk loop — per-chunk dequant temporaries are dead after
  their dot.
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
    VMEM_LIMIT_BYTES, WORD_BLOCK_O, WORD_ROWS, finest_split, forward_chunk,
    lora_operand_bytes, pick_block_m, pick_block_o, round_up, word_tiles,
    words_ok,
)

def _params(in_order: bool):
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary",) * 2 if in_order
        else ("parallel", "parallel"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _f16_bits(a: jax.Array) -> jax.Array:
    if a.dtype != jnp.float16:
        # bf16/f32 scales round-trip through f16 bits (test paths)
        a = a.astype(jnp.float16)
    return jax.lax.bitcast_convert_type(a, jnp.uint16)


# ---------------------------------------------------------------------------
# the unified kernel: one O x M tile, any DecodeSpec
# ---------------------------------------------------------------------------

def _kernel(layer_ref, x_ref, w_ref, *rest, K: int, ck: int,
            spec: DecodeSpec, lora: bool = False, words: bool = False,
            prepared: bool = False, O: int = 0):
    """One [block_m, block_o] output tile: acc += x_chunk @ dq(W_chunk)^T
    over chunks of the logical contraction axis (`qdecode.tile_product` in
    the stored layout; on the word path `qdecode.copy_tiles_ahead`,
    `qdecode.stage_tile`, then `qdecode.product_of_tile`: each a `jit` of
    the kernel's refs, traced once for refs of these shapes whatever
    instance calls it). In the stored layout `layer_ref` is read by the
    weight's index map alone: the tile arrives as `[block_o, row_bytes]`
    whichever layer of the stack it came from. With ``words`` `w_ref` is
    the whole stack in HBM and the last five refs are the word path's
    scratch, the two buffers a tile's words are copied into and their
    semaphores among them (`O` says where a ragged last tile ends).

    With ``lora`` the multi-tenant LoRA epilogue folds into the same
    tile before writeback (the S-LoRA/Punica batched-adapter GEMM,
    ISSUE 18): the x tile is already in VMEM, so
    ``(x @ A_cat^T) * gate @ B_cat^T`` adds ZERO activation HBM round
    trips — the XLA fallback (ops/linear.lora_epilogue) pays two
    (re-read x, round-trip the delta). ``gate [block_m, R]`` carries the
    per-row adapter selection AND scale: row m holds scale_m in its own
    adapter group's rank-bucket columns and 0 elsewhere, which is how
    one dot pair serves a heterogeneous multi-tenant batch."""
    if words:
        *side_refs, o_ref = rest[:-5]
        scratch, wbuf, sem = tuple(rest[-5:-2]), rest[-2], rest[-1]
        n_o = word_tiles(O)
        # the grid steps in order, o innermost; every step copies
        lead = (layer_ref[0],)
        slot = qdecode.copy_tiles_ahead(
            (w_ref,), (wbuf,), sem, pl.program_id(0), pl.program_id(1),
            pl.num_programs(0), lead, lead, n_o=n_o,
            last_rows=(O - (n_o - 1) * WORD_BLOCK_O) // WORD_ROWS)
        qdecode.stage_tile((wbuf,), slot, (tuple(side_refs),), scratch,
                           spec=spec, prepared=prepared)
        o_ref[:] = qdecode.product_of_tile(
            x_ref, scratch, spec=spec, K=K, ck=ck).astype(o_ref.dtype)
        return
    o_ref = rest[-1]
    if lora:
        a_ref, b_ref, g_ref = rest[-4:-1]
        side_refs = rest[:-4]
    else:
        side_refs = rest[:-1]
    acc = qdecode.tile_product(spec, K, ck, x_ref, w_ref, side_refs)
    if lora:
        xa = jax.lax.dot_general(  # [block_m, R]
            x_ref[:].astype(jnp.bfloat16), a_ref[:], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        xa = xa * g_ref[:].astype(jnp.float32)
        acc += jax.lax.dot_general(  # [block_m, block_o]
            xa.astype(jnp.bfloat16), b_ref[:], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    o_ref[:] = acc.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("spec", "out_dtype", "block_m", "block_o",
                              "ck", "interpret", "lora", "bits")
)
def _qmm(spec, out_dtype, block_m: int, block_o: int, ck: int,
         interpret: bool, lora: bool, bits, layer, x2, w, *rest):
    """`w` is the packed codes of a STACK of weights `[L, O, row_bytes]`
    and `layer [1]` int32 the one to multiply by, scalar-prefetched so
    the index map (the stored-layout loop's) or the kernel's own copy (the
    word path's, `qdecode.copy_tiles_ahead`) can name it: the tile's DMA
    reads layer `layer[0]` out of the whole array. A slice `w[layer]`
    handed to a Mosaic call would first be copied whole, every call.
    Everything else (`rest`: scales,
    LoRA operands) is one layer's own rank-2 array, unless ``bits`` names
    the layout of prepared scale bits (`bits_layout`): those keep their
    layer axis too and are read by the same index.

    The O grid is `cdiv(O, block_o)`: where the plan is the word path over
    an O that is no multiple of 512, the last step's code tile (and the
    float16 view of the scales, where nobody prepared bits) is partial:
    the DMA brings its valid rows, the rest of the buffer is whatever it
    held. A row of the tile is a column of the product from the decode to
    the store (`natural_columns` permutes columns within the tile), so
    nothing past O reaches a column below it, a NaN included, and the
    output block's columns past O are dropped at the store: `out_shape`
    stays `(Mp, O)`, and nobody slices or masks."""
    Mp, K = x2.shape
    O = w.shape[1]
    if lora:
        *side, la, lb, lg = rest
    else:
        side = rest
    row = lambda m, o, l: (o, 0)  # weight-side blocks follow the O grid dim
    in_specs = [
        pl.BlockSpec((block_m, K), lambda m, o, l: (m, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((None, block_o, w.shape[2]),
                     lambda m, o, l: (l[0], o, 0), memory_space=pltpu.VMEM),
    ]
    if bits == "words":  # [L, word_tiles(O), nb, 512]: the tile's own block
        in_specs += [
            pl.BlockSpec((None, None, *a.shape[2:]),
                         lambda m, o, l: (l[0], o, 0, 0),
                         memory_space=pltpu.VMEM) for a in side]
    elif bits == "stored":  # [L, O, nb] uint16
        in_specs += [
            pl.BlockSpec((None, block_o, a.shape[2]),
                         lambda m, o, l: (l[0], o, 0),
                         memory_space=pltpu.VMEM) for a in side]
    else:
        in_specs += [
            pl.BlockSpec((block_o, a.shape[1]), row, memory_space=pltpu.VMEM)
            for a in side
        ]
    if lora:
        # LoRA epilogue operands: A_cat rides as a FULL block (resident
        # across the whole o sweep, like the x tile), B_cat tiles follow
        # the O grid, the gate follows the M grid. Full-dim blocks keep
        # every spec legal at any rank bucket (R need not be
        # lane/sublane aligned when the block covers the whole dim).
        in_specs += [
            pl.BlockSpec((la.shape[0], K), lambda m, o, l: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_o, lb.shape[1]), row,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_m, lg.shape[1]), lambda m, o, l: (m, 0),
                         memory_space=pltpu.VMEM),
        ]
    # the word path: not with LoRA operands (their VMEM beside the
    # transposed tile was never compiled for the chip)
    words = not lora and words_ok(block_o, w.shape[2])
    assert (bits == "words") <= words, (bits, block_o, w.shape)
    scratch = []
    if words:
        # the codes stay in HBM: the kernel brings a tile as words into one
        # of two buffers (what the pipeline's two byte blocks held)
        in_specs[1] = pl.BlockSpec(memory_space=pl.ANY)
        scratch = qdecode.word_scratch(
            spec, block_o, w.shape[2], K // spec.block if bits
            else side[-1].shape[1]) + qdecode.word_buffers(
                1, block_o, w.shape[2])
        if interpret:
            # XLA:CPU folds the interpreter's word view of a stack that is
            # a CONSTANT of the jit around the call to wrong words,
            # silently (PR 62); a compiled kernel never sees it
            w = jax.lax.optimization_barrier(w)
    # grid order (m, o): o innermost, so the x tile stays resident across
    # a full sweep of weight tiles and packed weights are re-fetched only
    # once per M tile (the roofline model in benchmark/roofline.py
    # assumes exactly this fetch pattern; the word path's copies keep it)
    return pl.pallas_call(
        functools.partial(_kernel, K=K, ck=ck, spec=spec, lora=lora,
                          words=words, prepared=bits == "words", O=O),
        name="qmatmul_lora" if lora else "qmatmul",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            # a ragged last word tile (`tiling.ragged_word_tiles`): its
            # code block's rows past O are whatever the buffer held, and
            # the columns they decode to are not stored
            grid=(Mp // block_m, pl.cdiv(O, block_o)),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (block_m, block_o), lambda m, o, l: (m, o),
                memory_space=pltpu.VMEM
            ),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((Mp, O), out_dtype),
        # (the word path's copies name the NEXT step's tile: in order)
        compiler_params=_params(words),
        interpret=interpret,
    )(layer, x2, w, *rest)


def _validate(spec: DecodeSpec, K: int, data) -> None:
    if spec.planes:
        bits = sum(spec.planes)
        assert data.shape[-1] * 8 == K * bits, (data.shape, K, spec)
        for b in spec.planes:
            # each plane split must cover whole quant blocks, or the
            # chunked scale slicing is wrong
            assert (K // (8 // b)) % spec.block == 0, (K, spec)
    else:
        assert data.shape[-1] == K, (data.shape, K)
    assert K % spec.block == 0, (K, spec)
    if spec.super_block:
        assert K % spec.super_block == 0, (K, spec)


def _side_arrays(spec: DecodeSpec, scales, mins, sub_scales, sub_mins):
    """Wrapper-side prep of the scale arrays, in kernel argument order
    (matches qdecode.load_side). f16 scales cross as uint16 bits;
    integer sub-scales cross as stored."""
    if spec.super_block:
        if spec.mins:
            return (_f16_bits(scales), _f16_bits(mins), sub_scales, sub_mins)
        return (_f16_bits(scales), sub_scales)
    if spec.mins:
        return (_f16_bits(scales), _f16_bits(mins))
    return (_f16_bits(scales),)


def tile_form(spec: DecodeSpec, O: int, row_bytes: int,
              cap: int = WORD_BLOCK_O) -> str:
    """Which loop decodes `_fused`'s tiles of a weight `[O, row_bytes]` of
    codes (no adapter), from the static shapes its tile plan is picked
    from: ``"words"`` the word path (over `tiling.word_tiles(O)` tiles, the
    last one ragged where O is no multiple of 512), ``"stored"`` the
    stored-layout loop.
    (A row's side bytes are priced as float16 a block and side array: what
    a single-level format holds, and more than a two-level one does.)"""
    nb = row_bytes * 8 // (sum(spec.planes) or 8) // spec.block
    block_o = pick_block_o(O, row_bytes + spec.n_side * nb * 2, cap=cap,
                           row_bytes=row_bytes)
    return "words" if words_ok(block_o, row_bytes) else "stored"


def bits_layout(spec: DecodeSpec, O: int, row_bytes: int,
                cap: int = WORD_BLOCK_O):
    """How `_fused` reads prepared scale bits for a weight `[O, row_bytes]`
    of codes: `tile_form`'s name (``"words"``: `qdecode.pack_major_bits` of
    512-row tiles, a ragged last one filled with zeros; ``"stored"``: the
    uint16 view, made once), None for the
    two-level formats, whose effective scales are products the kernel forms
    in the stored layout."""
    return None if spec.super_block else tile_form(spec, O, row_bytes, cap)


def _fused(x, data, spec: DecodeSpec, side, out_dtype, block_o, interpret,
           lora=None, layer=None, bits=None):
    """Shared wrapper: flatten/pad rows, pick tiles, run the kernel.

    With ``layer`` (a traced index) ``data`` is the codes of a stack of
    same-shaped weights ``[L, O, row_bytes]`` and the kernel reads layer
    ``layer`` of it in place; ``side`` is that layer's own. A weight of
    its own (``[O, row_bytes]``: the LM head, anything outside a layer
    scan) is the stack of one read at 0, a reshape and no copy. Tiles are
    picked from one layer's ``[O, row_bytes]`` either way.

    With ``bits`` (`bits_layout`'s name) ``side`` holds the weight's
    prepared scale bits, uint16 in that layout and with the leading axes
    ``data`` has: the kernel reads them in place by the same index, where
    a float16 ``side`` is viewed as uint16 (a copy) before every call.

    ``lora`` (optional) is the fused-epilogue operand triple
    ``(a_cat [R, K], b_cat [O, R], gate [M, R])`` — see _kernel; the
    gate is padded alongside x (zero rows contribute exactly 0)."""
    from bigdl_tpu.ops.pallas import interpret_mode

    if interpret is None:
        interpret = interpret_mode()
    *lead, K = x.shape
    if layer is None:
        data, layer = data[None], 0
        if bits:
            side = tuple(a[None] for a in side)
    assert data.ndim == 3, data.shape
    O = data.shape[1]
    _validate(spec, K, data)

    M = 1
    for d in lead:
        M *= d
    block_m = pick_block_m(M, K)
    Mp = round_up(max(M, 1), block_m)
    # cast to bf16 HERE (the kernel's compute dtype anyway): halves the
    # [block_m, K] VMEM slab for GEMM row tiles
    x2 = x.reshape(M, K).astype(jnp.bfloat16)
    if Mp != M:
        x2 = jnp.pad(x2, ((0, Mp - M), (0, 0)))

    extra = ()
    lora_bytes = 0
    if lora is not None:
        assert not bits, "prepared bits are not read beside LoRA operands"
        a_cat, b_cat, gate = lora
        R = a_cat.shape[0]
        assert a_cat.shape == (R, K), (a_cat.shape, K)
        assert b_cat.shape == (O, R), (b_cat.shape, O, R)
        assert gate.shape == (M, R), (gate.shape, M, R)
        gate2 = gate.astype(jnp.bfloat16)
        if Mp != M:
            gate2 = jnp.pad(gate2, ((0, Mp - M), (0, 0)))
        extra = (a_cat.astype(jnp.bfloat16), b_cat.astype(jnp.bfloat16),
                 gate2)
        lora_bytes = lora_operand_bytes(R, K, 256, block_m)

    # (prepared bits: K / block uint16 a row and side array, as stored)
    persist_row = data.shape[2] * data.dtype.itemsize + (
        len(side) * (K // spec.block) * 2 if bits else sum(
            a.shape[1] * a.dtype.itemsize for a in side))
    block_o = pick_block_o(O, persist_row, cap=block_o,
                           row_bytes=0 if lora is not None else data.shape[2])
    persist = (block_o * persist_row + block_m * K * 2
               + block_m * block_o * 4 + lora_bytes)
    ck = forward_chunk(lora is None and words_ok(block_o, data.shape[2]),
                       block_o, persist, finest_split(K, spec.planes),
                       spec.block, spec.mins)
    y = _qmm(spec, jnp.dtype(out_dtype), block_m, block_o, ck,
             bool(interpret), lora is not None, bits,
             jnp.asarray(layer, jnp.int32).reshape(1), x2, data, *side, *extra)
    return y[:M].reshape(*lead, O)


# ---------------------------------------------------------------------------
# generic QTensor entry point
# ---------------------------------------------------------------------------

def qmatmul(
    x: jax.Array,  # [..., K]
    w,  # QTensor (any registered non-dense qtype)
    out_dtype=jnp.bfloat16,
    block_o: int = WORD_BLOCK_O,
    interpret: bool | None = None,
    layer=None,  # traced index: `w.data` is then a stack [L, O, *]
) -> jax.Array:
    """y[..., O] = x @ dequant(W)^T, fused, for any QTensor — GEMV and
    tiled GEMM shapes alike. The decode recipe comes straight from the
    qtype registry (qdecode.spec_for), so a newly registered format with
    standard storage gets a fused kernel with no new kernel code."""
    spec = qdecode.spec_for(w.spec)
    data = w.data
    if w.spec.storage.startswith("fp8"):
        # fp8 bytes cross as stored; the kernel decodes the 256-entry
        # byte codebook arithmetically from the bit fields
        data = jax.lax.bitcast_convert_type(data, jnp.uint8)
    bits = prepared_bits(w, bits_layout(
        spec, data.shape[-2], data.shape[-1] * data.dtype.itemsize, block_o))
    return _fused(x, data, spec, side_operands(spec, w, bits), out_dtype,
                  block_o, interpret, layer=layer, bits=bits)


def prepared_bits(w, layout):
    """`layout` when `w` carries scale bits prepared for it (the layout a
    call's own tile plan reads), else None: the call views the float16
    fields, as it does for every weight nobody prepared."""
    return layout if layout is not None and w.bits_layout == layout else None


def side_operands(spec: DecodeSpec, w, bits):
    """The scale-side operands of a call on `w`, in kernel argument order:
    its prepared bits where `bits` (`prepared_bits`' answer) says the call
    reads them, else the float16 fields viewed as uint16."""
    if bits:
        return (w.scale_bits, w.min_bits)[:spec.n_side]
    return _side_arrays(spec, w.scales, w.mins, w.sub_scales, w.sub_mins)


def qmatmul_lora(
    x: jax.Array,  # [..., K]
    w,  # QTensor (any registered non-dense qtype)
    a_cat: jax.Array,  # [R, K] concatenated adapter A rows (bf16-able)
    b_cat: jax.Array,  # [O, R] concatenated adapter B columns
    gate: jax.Array,  # [M, R] per-row scale-in-own-group selection mask
    out_dtype=jnp.bfloat16,
    block_o: int = WORD_BLOCK_O,
    interpret: bool | None = None,
) -> jax.Array:
    """``qmatmul`` with the multi-tenant LoRA epilogue fused into the
    writeback: y = x @ dq(W)^T + ((x @ A_cat^T) * gate) @ B_cat^T.

    R concatenates the rank-bucket columns of every adapter group in the
    batch (Punica's batched-adapter GEMM realized with two plain dots +
    a gate, no vector gather); ``gate[m, j] = scale_g`` iff column j
    belongs to row m's group g, else 0 — so each row receives exactly
    its own adapter's delta and adapter-less rows (gate row 0) ride
    along unchanged. Parity oracle: ops/linear.lora_epilogue added to
    the unfused qmatmul."""
    spec = qdecode.spec_for(w.spec)
    data = w.data
    if w.spec.storage.startswith("fp8"):
        data = jax.lax.bitcast_convert_type(data, jnp.uint8)
    side = _side_arrays(spec, w.scales, w.mins, w.sub_scales, w.sub_mins)
    return _fused(x, data, spec, side, out_dtype, block_o, interpret,
                  lora=(a_cat, b_cat, gate))
