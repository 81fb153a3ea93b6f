"""The forward kernels' word path (ISSUE 32): `qdecode.tile_product` with
the code tile read as 32-bit words and transposed, through the Pallas
interpreter.

Three things are held:

* the decoded bfloat16 weights are the `quant/` dequantizer's, BIT FOR BIT,
  for every registered packed format, on the word path and on the
  stored-layout loop the backward kernels keep;
* the kernel's product, for every format at GEMV and GEMM row counts, is
  `x.astype(bf16) @ dq(W).astype(bf16).T` accumulated in float32
  (`tests/test_qmatmul_cells.py` does the same at the benchmark cells' own
  K and M);
* the grouped expert kernel does the same with an empty group, one stack
  and the gated pair, on 512-row word tiles and on the PAIRED tile of a
  768-wide gated call (ISSUE 44: 256 rows of `w_gate` beside 256 of `w_up`
  decoded as one 512-row word tile);
* the kernels call the two halves of a word tile as `jit`s of their refs,
  traced once a process for blocks of one shape (ISSUE 63:
  `qdecode.stage_tile`, `qdecode.product_of_tile`, the grouped kernel's
  `_stage_tile` and `_tile_product`), and the product is, bit for bit, what
  `stage_words`, `staged_product` and `natural_columns` give written out in
  a kernel of the test's own (`_one_set` below), whichever instance traced
  them first.

TOLERANCE of the products: both sides multiply the same bf16 operands into
float32, so they differ by float32 summation order alone. Weights are drawn
at K ** -0.5 so that |y| is about 1 at every K (4 at the largest): measured
1e-6 to 6e-6; `atol` 5e-5 stays 100 times under one bf16 step of y, which
is what one wrong weight or a dropped chunk would cost.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from bigdl_tpu.ops.linear import _QGEMV_QTYPES
from bigdl_tpu.ops.pallas import qdecode
from bigdl_tpu.ops.pallas.qmatmul import _side_arrays, qmatmul
from bigdl_tpu.ops.pallas.tiling import (
    WORD_BLOCK_O, WORD_ROWS, chunk_spans, finest_split, grouped_tile,
    word_tiles, words_chunk, words_ok,
)
from bigdl_tpu.quant import quantize

pytestmark = pytest.mark.core

CELL_KS = (2560, 3584, 4096, 5120, 14336, 17408, 18944)


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")


def _weights(qtype, O, K, seed=0):
    w = jax.random.normal(jax.random.PRNGKey(seed), (O, K)) * K ** -0.5
    qt = quantize(w, qtype)
    assert qt.qtype == qtype
    return qt


def _kernel_data(qt):
    data = qt.data
    if qt.spec.storage.startswith("fp8"):
        data = jax.lax.bitcast_convert_type(data, jnp.uint8)
    spec = qdecode.spec_for(qt.spec)
    return spec, data, _side_arrays(spec, qt.scales, qt.mins, qt.sub_scales,
                                    qt.sub_mins)


def _decoded_by_words(qts, K, ck=None, prepared=False):
    """[O, K] bf16: what the word path feeds the MXU for one tile, its
    chunks rolled or unrolled as the kernel would have them. `qts`: the
    tile's one block of 512 rows, or the gated pair's two of 256.
    ``prepared``: the scales as `pack_major_bits` lays them out once."""
    qts = qts if isinstance(qts, (tuple, list)) else (qts,)
    blocks = [_kernel_data(qt) for qt in qts]
    spec, data, side = blocks[0]
    O = sum(b[1].shape[0] for b in blocks)
    assert words_ok(O, data.shape[1])
    q = O // WORD_ROWS
    qmin = finest_split(K, spec.planes)
    ck = ck or words_chunk(qmin, spec.block)
    per = 1 + spec.n_side
    nb = side[-1].shape[1]
    if prepared:
        blocks = [(sp, d, tuple(
            qdecode.pack_major_bits(a, d.shape[0])[0]
            for a in (qt.scales, qt.mins) if a is not None))
            for (sp, d, _), qt in zip(blocks, qts)]

    def kern(*refs):
        o_ref, scratch = refs[len(blocks) * per], refs[len(blocks) * per + 1:]
        qdecode.stage_words(
            spec, [refs[i * per] for i in range(len(blocks))],
            [refs[i * per + 1:(i + 1) * per] for i in range(len(blocks))],
            scratch, prepared=prepared)
        for seg in range(K // qmin):
            for c0, c in chunk_spans(qmin, ck):
                o_ref[seg * qmin + c0:seg * qmin + c0 + c, :] = \
                    qdecode.decode_chunk_words(
                        spec, K, scratch[0], scratch[2], seg, c0, c)

    out = pl.pallas_call(
        kern, out_shape=jax.ShapeDtypeStruct((K, O), jnp.bfloat16),
        scratch_shapes=qdecode.word_scratch(spec, O, data.shape[1], nb),
        interpret=True,
    )(*(a for _, d, sd in blocks for a in (d, *sd)))
    # lane p * q + i of the tile is its row 4i + p
    return jnp.transpose(out.reshape(K, WORD_ROWS, q), (2, 1, 0)).reshape(O, K)


def _decoded_in_place(qt, K, ck=256):
    """[O, K] bf16 from `decode_chunk`, the stored-layout loop."""
    spec, data, side = _kernel_data(qt)

    def kern(w_ref, *refs):
        side_refs, o_ref = refs[:-1], refs[-1]
        s = qdecode.load_side(spec, side_refs)
        w = w_ref[:]
        for e0, c in qdecode.walk(K, spec.planes, ck):
            o_ref[:, e0:e0 + c] = qdecode.decode_chunk(spec, K, w, s, e0, c)

    return pl.pallas_call(
        kern, out_shape=jax.ShapeDtypeStruct((data.shape[0], K),
                                             jnp.bfloat16),
        interpret=True,
    )(data, *side)


@pytest.mark.parametrize("qtype", sorted(_QGEMV_QTYPES))
def test_decoded_weights_are_the_dequantizers_bit_for_bit(qtype):
    K = 1024  # every format's planes and super-blocks divide it
    qt = _weights(qtype, WORD_BLOCK_O, K)
    want = np.asarray(qt.dequantize(jnp.bfloat16).astype(jnp.float32))
    for name, got in (("words", _decoded_by_words(qt, K)),
                      ("in place", _decoded_in_place(qt, K))):
        got = np.asarray(got.astype(jnp.float32))
        bad = np.argwhere(got != want)
        assert bad.size == 0, (qtype, name, len(bad), bad[:4])


def _corner_block(qtype, rows, K, seed):
    """A block whose codes are the format's ends in whole stretches (0 and
    15: a flipped nibble of -8 is the lane's sign bit alone; int8's -128
    and 127) and random elsewhere, under scales at float16's corners; and
    its weights worked out here, `round_bf16(float32(code - offset) *
    float32(scale))`, not by the dequantizer."""
    from bigdl_tpu.quant.qtensor import QTensor

    rng = np.random.default_rng(seed)
    sc = (rng.uniform(0.001, 0.011, (rows, K // 32))
          * rng.choice([-1.0, 1.0], (rows, K // 32))).astype(np.float16)
    corners = np.asarray([2.0 ** -24, -2.0 ** -24, 65504.0, -65504.0, 0.0,
                          -0.0, 2.0 ** -14, 1.0], np.float16)
    sc[:, :8] = corners  # both nibble halves: blocks 0.. and K/64..
    sc[:, K // 64:K // 64 + 8] = corners[::-1]
    if qtype == "sym_int4":
        data = rng.integers(0, 256, (rows, K // 2), dtype=np.uint8)
        for j, byte in enumerate((0x00, 0xFF, 0xF0, 0x0F, 0x88, 0x77)):
            data[j::16, :] = byte
            data[:, 32 * j + 7] = byte
        codes = np.concatenate([data & 15, data >> 4], axis=1).astype(
            np.int32) - 8
    else:
        data = rng.integers(-128, 128, (rows, K), dtype=np.int8)
        for j, byte in enumerate((-128, 127, 0, -1)):
            data[j::16, :] = byte
            data[:, 32 * j + 7] = byte
        codes = data.astype(np.int32)
    want = (codes.astype(np.float32)
            * np.repeat(sc.astype(np.float32), 32, axis=1))
    want = np.asarray(jnp.asarray(want).astype(jnp.bfloat16)
                      .astype(jnp.float32))
    return QTensor(qtype=qtype, data=jnp.asarray(data),
                   scales=jnp.asarray(sc)), want


@pytest.mark.parametrize("prepared", (False, True),
                         ids=("staged", "prepared"))
@pytest.mark.parametrize("qtype,blocks", [("sym_int4", 1), ("sym_int4", 2),
                                          ("sym_int8", 1)],
                         ids=("sym_int4", "sym_int4-paired", "sym_int8"))
def test_signed_fields_at_their_corners_bit_for_bit(qtype, blocks, prepared):
    """ISSUE 49: the word path cuts a sym_int4 nibble out of its word
    signed, as it does an int8 byte (`qdecode.signed_field`). The ends of
    the code range under the ends of float16, one 512-row tile and the
    paired tile of a 768-wide gated call, scales staged and prepared."""
    K = 1024
    made = [_corner_block(qtype, WORD_BLOCK_O // blocks, K, seed=i)
            for i in range(blocks)]
    want = np.concatenate([w for _, w in made])
    assert np.isfinite(want).all() and (want == 0).any()
    got = np.asarray(_decoded_by_words(
        [qt for qt, _ in made], K, prepared=prepared).astype(jnp.float32))
    bad = np.argwhere((got != want) | (np.signbit(got) != np.signbit(want)))
    assert bad.size == 0, (qtype, len(bad), bad[:4])


def _chunk_body_ops(spec, seg):
    """Primitive counts of one chunk's decode on the word path."""
    import collections

    K, O = 1024, WORD_BLOCK_O
    row_bytes = K * (sum(spec.planes) or 8) // 8

    def kern(wT_ref, sT_ref, o_ref):
        o_ref[...] = qdecode.decode_chunk_words(
            spec, K, wT_ref, sT_ref, seg, 0, 256)

    jaxpr = jax.make_jaxpr(lambda wT, sT: pl.pallas_call(
        kern, out_shape=jax.ShapeDtypeStruct((256, O), jnp.bfloat16),
        interpret=True)(wT, sT))(
        jax.ShapeDtypeStruct((row_bytes, O // WORD_ROWS), jnp.int32),
        jax.ShapeDtypeStruct((1, 128, O), jnp.float32))
    (call,) = [e for e in jaxpr.jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    return collections.Counter(
        e.primitive.name for e in call.params["jaxpr"].eqns)


@pytest.mark.parametrize("name,seg", [("sym_int4", 0), ("sym_int4", 1),
                                      ("sym_int8", 0)])
def test_chunk_body_cuts_a_signed_field_in_two_operations(name, seg):
    """The chunk body's equations for the formats that decode where the
    field lies: a shift left and an arithmetic shift right a pack (no
    shift left for the word's last field), then convert, multiply, cast.
    No mask and no subtract: the chain of six ISSUE 49 found is five.
    (The issue's ONE shift is not the dequantizer's weights: the word's
    lower fields stay below the one cut out.)"""
    from bigdl_tpu.quant.qtypes import resolve_qtype as get_qtype

    spec = qdecode.spec_for(get_qtype(name))
    ops = _chunk_body_ops(spec, seg)
    last_field = name == "sym_int8" or seg == 1
    assert ops["shift_right_arithmetic"] == WORD_ROWS
    assert ops["shift_left"] == WORD_ROWS - last_field
    assert ops["mul"] == 1 and ops["convert_element_type"] == 2
    assert not {"and", "sub", "or", "add"} & set(ops), ops
    # what the chain was, and still is for a format with a minimum
    old = _chunk_body_ops(qdecode.spec_for(get_qtype("asym_int4")), seg)
    assert old["and"] == WORD_ROWS and "shift_left" not in old


@pytest.mark.parametrize("which", ("zeros", "subnormals", "normals",
                                   "top-exponent"))
def test_f16_bits_decode_every_pattern_exactly(which):
    """`f16_bits_to_f32` (ISSUE 63: 14 operations where it had 20) over all
    65536 bit patterns, by exponent class: both zeros keep their sign, a
    subnormal is mant * 2^-24 and NOT flushed, a normal is float16's own
    value; the top exponent's patterns (inf / nan, which no encoder here
    stores) come out as the finite values their fields spell, as they
    always did. Plain and through a kernel."""
    bits = np.arange(65536, dtype=np.uint16)
    exp, mant = (bits >> 10) & 31, bits & 1023
    pick = {"zeros": (exp == 0) & (mant == 0),
            "subnormals": (exp == 0) & (mant > 0),
            "normals": (exp > 0) & (exp < 31), "top-exponent": exp == 31}[which]
    bits = bits[pick]
    want = bits.view(np.float16).astype(np.float32)
    if which == "top-exponent":  # (1 + mant / 1024) * 2^16, signed
        want = ((1 + (bits & 1023) / 1024.0) * 65536.0 * np.where(
            bits >> 15, -1.0, 1.0)).astype(np.float32)
    n = -(-len(bits) // 128) * 128
    padded = np.zeros(n, np.uint16)
    padded[:len(bits)] = bits
    tile = jnp.asarray(padded.reshape(-1, 128))

    def kern(b_ref, o_ref):
        o_ref[...] = qdecode.f16_bits_to_f32(b_ref[...])

    through = pl.pallas_call(
        kern, out_shape=jax.ShapeDtypeStruct(tile.shape, jnp.float32),
        interpret=True)(tile)
    for got in (qdecode.f16_bits_to_f32(tile), through):
        got = np.asarray(got).reshape(-1)[:len(bits)]
        np.testing.assert_array_equal(got.view(np.uint32),
                                      want.view(np.uint32))


def _reference(x, qt):
    return jnp.dot(x.astype(jnp.bfloat16), qt.dequantize(jnp.bfloat16).T,
                   preferred_element_type=jnp.float32)


@pytest.mark.parametrize("blocks", (1, 2), ids=("one-block", "paired"))
@pytest.mark.parametrize("K", CELL_KS)
def test_decoded_words_at_the_cells_widths(K, blocks):
    """sym_int4, the cells' format, at each cell's contraction width: odd
    chunk tails, scale columns that do not fill 128 lanes (K / 32 = 80,
    112, 160, 448, 544, 592). `paired`: two blocks of 256 rows (a gated
    768-wide expert call's `w_gate` and `w_up` blocks) staged as one tile:
    its rows 0..255 are the first block's weights, 256..511 the second's,
    the dequantizer's bit for bit."""
    qts = [_weights("sym_int4", WORD_BLOCK_O // blocks, K, seed=K + i)
           for i in range(blocks)]
    want = np.concatenate([np.asarray(
        qt.dequantize(jnp.bfloat16).astype(jnp.float32)) for qt in qts])
    got = np.asarray(_decoded_by_words(qts, K).astype(jnp.float32))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("qtype", sorted(_QGEMV_QTYPES))
def test_paired_tile_decodes_every_format_bit_for_bit(qtype):
    """The paired tile's staging for every packed format (mins, two-level
    scales, byte codes): the dequantizer's weights of both blocks."""
    K = 1024
    qts = [_weights(qtype, WORD_BLOCK_O // 2, K, seed=i) for i in range(2)]
    want = np.concatenate([np.asarray(
        qt.dequantize(jnp.bfloat16).astype(jnp.float32)) for qt in qts])
    got = np.asarray(_decoded_by_words(qts, K).astype(jnp.float32))
    bad = np.argwhere(got != want)
    assert bad.size == 0, (qtype, len(bad), bad[:4])


@pytest.mark.parametrize("qtype", sorted(_QGEMV_QTYPES))
def test_every_format_takes_the_word_path(interpret, qtype):
    """GEMV and GEMM rows through the whole kernel, word path (O = 512)
    against the stored-layout loop (the same weights, capped to 256-row
    tiles): the same bf16 weights either way, so float32 summation order
    is all that may differ."""
    K = 1024
    qt = _weights(qtype, WORD_BLOCK_O, K, seed=3)
    for M in (1, 40):
        x = jax.random.normal(jax.random.PRNGKey(M), (M, K)
                              ).astype(jnp.bfloat16)
        y = qmatmul(x, qt, out_dtype=jnp.float32)
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(_reference(x, qt)), rtol=0, atol=5e-5,
            err_msg=f"{qtype} M={M}")
        old = qmatmul(x, qt, out_dtype=jnp.float32, block_o=256)
        np.testing.assert_allclose(np.asarray(y), np.asarray(old), rtol=0,
                                   atol=5e-5, err_msg=f"{qtype} M={M}")


# ---- the halves as `jit`s traced once a process (ISSUE 63) -----------------

def _one_set(x, qt, block_m=None):
    """float32 [M, O]: the word path written out in a kernel of the test's
    own, grid (M tiles, O tiles), every tile staged and then multiplied;
    the tree's own `stage_words`, `staged_product` and `natural_columns`
    called as plain functions on the tree's tiles and chunk, so the same
    float32 sums in the same order."""
    from bigdl_tpu.ops.pallas.tiling import pick_block_m, round_up

    spec, data, side = _kernel_data(qt)
    (M, K), (O, rb) = x.shape, data.shape
    bm = block_m or pick_block_m(M, K)
    Mp = round_up(M, bm)
    ck = words_chunk(finest_split(K, spec.planes), spec.block)

    def kern(x_ref, w_ref, *rest):
        side_refs, o_ref = rest[:len(side)], rest[len(side)]
        scratch = rest[len(side) + 1:]
        qdecode.stage_words(spec, (w_ref,), (side_refs,), scratch)
        o_ref[:] = qdecode.natural_columns(
            qdecode.staged_product(spec, K, ck, x_ref, scratch))

    rows = lambda m, o: (o, 0)
    y = pl.pallas_call(
        kern, grid=(Mp // bm, word_tiles(O)),
        in_specs=[pl.BlockSpec((bm, K), lambda m, o: (m, 0)),
                  pl.BlockSpec((WORD_BLOCK_O, rb), rows),
                  *(pl.BlockSpec((WORD_BLOCK_O, a.shape[1]), rows)
                    for a in side)],
        out_specs=pl.BlockSpec((bm, WORD_BLOCK_O), lambda m, o: (m, o)),
        out_shape=jax.ShapeDtypeStruct((Mp, O), jnp.float32),
        scratch_shapes=qdecode.word_scratch(
            spec, WORD_BLOCK_O, rb, side[-1].shape[1]),
        interpret=True,
    )(jnp.pad(x.astype(jnp.bfloat16), ((0, Mp - M), (0, 0))), data, *side)
    return y[:M]


def _same_bits(got, want, what):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape, (what, got.shape, want.shape)
    bad = np.argwhere(got.view(np.uint32) != want.view(np.uint32))
    assert bad.size == 0, (what, len(bad), bad[:4])


@pytest.mark.parametrize("qtype", sorted(_QGEMV_QTYPES))
def test_every_format_through_the_cached_halves_bit_for_bit(interpret, qtype):
    """Three word tiles through `_qmm`'s cached halves: every packed
    format's product is the written-out kernel's, to the last bit (same
    codes, same scales, same bf16 weights, the same float32 accumulation
    within a tile)."""
    K, O = 1024, 3 * WORD_BLOCK_O
    qt = _weights(qtype, O, K, seed=6)
    x = jax.random.normal(jax.random.PRNGKey(2), (8, K)).astype(jnp.bfloat16)
    _same_bits(qmatmul(x, qt, out_dtype=jnp.float32), _one_set(x, qt), qtype)


@pytest.mark.parametrize("M", (8, 520), ids=("one-row-tile", "three"))
@pytest.mark.parametrize("O", (512, 1024, 1536, 32000),
                         ids=("one-tile", "two", "odd", "ragged-63"))
def test_tiles_through_the_cached_halves_bit_for_bit(interpret, O, M):
    """sym_int4 at an O of one word tile, of two, of an odd count and of
    Mistral's head (62 tiles and a ragged one of 256 rows: the last step's
    partial blocks), under one row tile and under three: the same traces
    serve every O (no block shows it) and each row tile its own."""
    from bigdl_tpu.ops.pallas.tiling import pick_block_m

    K = 512
    qt = _weights("sym_int4", O, K, seed=O)
    x = jax.random.normal(jax.random.PRNGKey(M), (M, K)).astype(jnp.bfloat16)
    assert -(-M // pick_block_m(M, K)) == (1 if M == 8 else 3)
    got = qmatmul(x, qt, out_dtype=jnp.float32)
    assert got.shape == (M, O)
    _same_bits(got, _one_set(x, qt), (O, M))


# ---- a ragged last word tile (ISSUE 55) ------------------------------------

def _poisoned_from(qt, row):
    """`qt` with every field's rows from `row` on set to all-ones bits:
    codes 0xFF, NaN scales and mins, the largest sub-scales."""
    import dataclasses

    def ones(a):
        if a is None:
            return None
        bits = jnp.full(a.shape[1:], -1, jnp.int8 if a.dtype.itemsize == 1
                        else jnp.int16)
        return a.at[row:].set(jax.lax.bitcast_convert_type(bits, a.dtype))
    return dataclasses.replace(qt, **{
        f: ones(getattr(qt, f))
        for f in ("data", "scales", "mins", "sub_scales", "sub_mins")})


def _staged_and_prepared():
    """Every format with its scales staged; the single-level ones prepared
    too (a two-level format's effective scales are products the kernel
    forms: nothing is prepared for it)."""
    from bigdl_tpu.quant.qtypes import resolve_qtype

    names = sorted(_QGEMV_QTYPES)
    return [(q, False) for q in names] + [
        (q, True) for q in names if not resolve_qtype(q).superblock]


@pytest.mark.parametrize(
    "qtype,prepared", _staged_and_prepared(),
    ids=lambda v: v if isinstance(v, str) else ("staged", "prepared")[v])
def test_rows_past_a_ragged_tiles_end_reach_no_row_before_it(qtype,
                                                             prepared):
    """The last word tile of an O that is no multiple of 512 holds, past O,
    whatever its buffer held. Rows 128.. of a tile POISONED (all-ones bits
    in every field): rows 0..127 decode to the dequantizer's weights bit
    for bit, for every format, scales staged and (single-level formats)
    prepared. The words' transpose, the strided scale reads and the packs
    keep a row of the tile in its own lanes."""
    K, valid = 1024, 128
    qt = _weights(qtype, WORD_BLOCK_O, K, seed=11)
    want = np.asarray(qt.dequantize(jnp.bfloat16).astype(jnp.float32))
    got = np.asarray(_decoded_by_words(
        _poisoned_from(qt, valid), K, prepared=prepared
    ).astype(jnp.float32))
    np.testing.assert_array_equal(got[:valid], want[:valid])
    assert (got[valid:] != want[valid:]).any()  # the poison was decoded


@pytest.mark.parametrize("rows,O", [(512, 640), (512, 1408), (256, 768),
                                    (512, 1024)])
def test_pack_major_bits_fills_a_ragged_tile_with_zeros(rows, O):
    """`[.., O, nb]` -> `[.., ceil(O / rows), nb, rows]`: the whole tiles as
    they always were, the ragged one the pack of its rows over zeros (a
    float16 +0.0: the padded rows decode to 0, whatever their codes)."""
    nb = 24
    a = (jax.random.uniform(jax.random.PRNGKey(O), (3, O, nb)) + 0.5
         ).astype(jnp.float16)
    got = qdecode.pack_major_bits(a, rows)
    tiles = -(-O // rows)
    assert got.shape == (3, tiles, nb, rows) and got.dtype == jnp.uint16
    padded = jnp.pad(a, ((0, 0), (0, tiles * rows - O), (0, 0)))
    for t in range(tiles):
        np.testing.assert_array_equal(
            np.asarray(got[:, t]), np.asarray(qdecode.pack_major_bits(
                padded[:, t * rows:(t + 1) * rows], rows)[:, 0]))
    if rows == WORD_BLOCK_O:
        assert tiles == word_tiles(O)
    # column p * rows / 4 + i of a tile is its row 4 i + p
    last = np.asarray(got[:, -1]).reshape(3, nb, WORD_ROWS, rows // WORD_ROWS)
    n = (O - (tiles - 1) * rows) // WORD_ROWS
    assert last[..., :n].all() and not last[..., n:].any()


@pytest.mark.parametrize("qtype", sorted(_QGEMV_QTYPES))
def test_every_format_takes_the_ragged_tile(interpret, qtype):
    """GEMV rows through the whole kernel at O = 512 + 128, every format
    (the two-level ones bring FOUR partial side blocks): the word path over
    two tiles against the reference and against the stored-layout loop
    (the same weights, capped to 128-row tiles)."""
    K, O = 1024, WORD_BLOCK_O + 128
    qt = _weights(qtype, O, K, seed=4)
    x = jax.random.normal(jax.random.PRNGKey(8), (8, K)).astype(jnp.bfloat16)
    y = qmatmul(x, qt, out_dtype=jnp.float32)
    assert y.shape == (8, O)
    np.testing.assert_allclose(np.asarray(y), np.asarray(_reference(x, qt)),
                               rtol=0, atol=5e-5, err_msg=qtype)
    old = qmatmul(x, qt, out_dtype=jnp.float32, block_o=256)
    np.testing.assert_allclose(np.asarray(y), np.asarray(old), rtol=0,
                               atol=5e-5, err_msg=qtype)


# (K, O, act, block_m): Mixtral's two contractions on 512-row word tiles,
# then the PAIRED tile of granite's (K 4096) and SmallThinker's (K 2560)
# 768-wide gated calls at a decode step's row tiles and a prefill's; then
# (ISSUE 63) experts of several word tiles: two tiles a step and three of a
# gated pair (the walk inside a step over the cached halves), a tile a step
# over two steps, one stack and the gated pair
_GROUPED = [(K, WORD_BLOCK_O, act, 8) for K in (4096, 14336)
            for act in (None, "silu")] + [
    (4096, 768, "silu", 32), (4096, 768, "relu", 16), (4096, 768, "silu", 256),
    (2560, 768, "relu", 16), (2560, 768, "silu", 32), (2560, 768, "relu", 256),
    (1024, 1024, None, 8), (1024, 1536, "silu", 16),
    (8192, 1024, None, 8), (4096, 1024, "silu", 8),
]
_GROUPED_PLANS = {
    (1024, 1024, None): "words:inplace x1 of 2 tiles",
    (1024, 1536, "silu"): "words:inplace x1 of 3 tiles",
    (8192, 1024, None): "words:inplace x2",
    (4096, 1024, "silu"): "words:inplace x2",
}


def _one_set_expert_tile(x_tile, ws, e, act, paired):
    """What the grouped kernel stores for one live row tile of expert `e`,
    from the written-out dense kernel (`_one_set`) on the same rows: each
    stack's product, or the paired tile's (256 rows of gate beside 256 of
    up, a word tile each pair), then the activation in float32."""
    from bigdl_tpu.ops.pallas import moe_qmatmul as mq
    from bigdl_tpu.quant.qtensor import QTensor

    bm = x_tile.shape[0]
    one = [QTensor(qtype=w.qtype, data=w.data[e], scales=w.scales[e])
           for w in ws]
    if paired:
        half = WORD_BLOCK_O // 2
        cut = lambda a: a.reshape(-1, half, a.shape[-1])
        both = QTensor(qtype=one[0].qtype, **{
            f: jnp.stack([cut(getattr(one[0], f)), cut(getattr(one[1], f))],
                         axis=1).reshape(-1, getattr(one[0], f).shape[-1])
            for f in ("data", "scales")})
        y = _one_set(x_tile, both, block_m=bm).reshape(bm, -1, 2, half)
        return (mq.FUSED_ACTS[act](y[:, :, 0]) * y[:, :, 1]).reshape(bm, -1)
    ys = [_one_set(x_tile, w, block_m=bm) for w in one]
    return ys[0] if act is None else mq.FUSED_ACTS[act](ys[0]) * ys[1]


@pytest.mark.parametrize("K,O,act,bm", _GROUPED)
def test_grouped_kernel_on_the_word_path(interpret, K, O, act, bm):
    """A group of size 0 (an expert with no rows), a dead tile past the
    tiles in use, one stack and the (gate, up) pair: rows of expert e are
    x @ dq(W[e])^T. A gated 768-wide call takes the paired tile, and
    agrees with two ungated calls (the stored-layout loop) + XLA. Every
    live row tile is the written-out product of its expert, bit for bit
    (ISSUE 63: the step's tiles are walked over `_stage_tile` and
    `_tile_product`, traced once)."""
    from bigdl_tpu.ops.pallas import moe_qmatmul as mq

    E, gated = 4, act is not None
    groups = [5, 0, 9, 2]
    ws = [quantize(jax.random.normal(jax.random.PRNGKey(i), (E, O, K))
                   * K ** -0.5, "sym_int4") for i in range(2 if gated else 1)]
    paired = O == 768
    assert mq.call_plan(ws if gated else ws[0]) == _GROUPED_PLANS.get(
        (K, O, act),
        "words:inplace:paired x1 of 3 tiles" if paired
        else "loop x2" if gated and K == 14336  # two such tiles: VMEM
        else "words:inplace x1")
    experts = np.repeat(np.arange(E), groups).astype(np.int32)
    N = len(experts)
    n_tiles = mq.moe_n_tiles(N, 1, E, bm)
    dest, src, te, n_used = mq.moe_layout(
        jnp.asarray(experts)[:, None], E, bm, n_tiles)
    assert int(n_used) < n_tiles  # the last tile is dead
    x = jax.random.normal(jax.random.PRNGKey(7), (N, K)).astype(jnp.bfloat16)
    y = mq.moe_qmatmul(x[src], ws if gated else ws[0], te, n_used, bm,
                       act=act, out_dtype=jnp.float32)
    got = np.asarray(y[dest[:, 0]])
    per = [jnp.einsum("nk,nok->no", x, w.dequantize(jnp.bfloat16)[experts],
                      preferred_element_type=jnp.float32) for w in ws]
    want = mq.FUSED_ACTS[act](per[0]) * per[1] if gated else per[0]
    np.testing.assert_allclose(got, np.asarray(want), rtol=0, atol=5e-5)
    if not (gated and K == 14336):  # (the loop has no scratch to compare)
        for m in range(int(n_used)):
            rows = slice(m * bm, (m + 1) * bm)
            _same_bits(y[rows], _one_set_expert_tile(
                x[src][rows], ws, int(te[m]), act, paired), (m, int(te[m])))
    if paired:
        assert mq.call_plan(ws[0]) == "loop x3"
        g, u = (mq.moe_qmatmul(x[src], w, te, n_used, bm,
                               out_dtype=jnp.float32)[dest[:, 0]] for w in ws)
        np.testing.assert_allclose(
            got, np.asarray(mq.FUSED_ACTS[act](g) * u), rtol=0, atol=5e-5)


# (K, O, act, layers): the walks of SEVERAL word tiles a grid step whose
# code tiles the kernel brings itself as words (ISSUE 64): two tiles, GLM's
# gated three of two products each, the five and the eight of the 768-wide
# experts' down projections, the paired three out of a layered stack
_WALKS = [(2048, 1024, None, 0), (2048, 1536, "silu", 0), (768, 2560, None, 0),
          (768, 4096, None, 3), (2560, 768, "silu", 3)]


@pytest.mark.parametrize("prepared", (False, True),
                         ids=("staged", "prepared"))
@pytest.mark.parametrize("K,O,act,layers", _WALKS)
def test_grouped_walk_brings_its_tiles_as_words(interpret, K, O, act, layers,
                                                prepared):
    """ISSUE 64: the word forms' codes stay in HBM and every live grid step
    copies its expert's tiles, as int32 words, one live step ahead
    (`qdecode.copy_tiles_ahead`). Live tiles of three experts in a row (the
    chain of copies crosses experts), an expert with no rows, a dead tile
    past the tiles in use (it copies nothing and nothing waits for it), a
    traced layer of a stack that is a constant of the jit around the call:
    every row is x @ dq(W[layer, expert])^T, and the first and the last
    live row tile (the chain's first copy, and the one after the empty
    expert) are, BIT FOR BIT, the written-out one-set kernel's product of
    their expert on pipelined byte blocks, on prepared scale bits and on
    the float16 fields alike."""
    from bigdl_tpu.ops.linear import prepare_scale_bits
    from bigdl_tpu.ops.pallas import moe_qmatmul as mq

    E, bm, gated = 4, 8, act is not None
    groups = [5, 0, 9, 2]
    lead = (layers,) if layers else ()
    bare = [quantize(jax.random.normal(jax.random.PRNGKey(i),
                                       (*lead, E, O, K)) * K ** -0.5,
                     "sym_int4") for i in range(2 if gated else 1)]
    held = {1024: 2, 1536: 3, 2560: 5, 4096: 8, 768: 3}[O]
    plan = mq.call_plan(bare if gated else bare[0])
    assert plan.endswith(f"of {held} tiles"), plan
    ws = [prepare_scale_bits(w, len(bare)) for w in bare] if prepared else bare
    assert all((w.scale_bits is not None) == prepared for w in ws)
    experts = np.repeat(np.arange(E), groups).astype(np.int32)
    N = len(experts)
    n_tiles = mq.moe_n_tiles(N, 1, E, bm)
    dest, src, te, n_used = mq.moe_layout(
        jnp.asarray(experts)[:, None], E, bm, n_tiles)
    assert 3 < int(n_used) < n_tiles
    x = jax.random.normal(jax.random.PRNGKey(7), (N, K)).astype(jnp.bfloat16)
    layer = layers - 1 if layers else None
    call = jax.jit(lambda x, l: mq.moe_qmatmul(
        x, ws if gated else ws[0], te, n_used, bm, act=act, layer=l,
        out_dtype=jnp.float32))
    y = call(x[src], layer)
    pick = (lambda a: a[layer]) if layers else (lambda a: a)
    per = [jnp.einsum("nk,nok->no", x,
                      pick(w.dequantize(jnp.bfloat16))[experts],
                      preferred_element_type=jnp.float32) for w in bare]
    want = mq.FUSED_ACTS[act](per[0]) * per[1] if gated else per[0]
    np.testing.assert_allclose(np.asarray(y[dest[:, 0]]), np.asarray(want),
                               rtol=0, atol=5e-5)
    flat = [jax.tree.map(pick, w) for w in bare]
    for m in (0, int(n_used) - 1):
        rows = slice(m * bm, (m + 1) * bm)
        _same_bits(y[rows], _one_set_expert_tile(
            x[src][rows], flat, int(te[m]), act, ":paired" in plan),
            (m, int(te[m])))


def test_grouped_tile_chooses_from_shapes_alone():
    """`tiling.grouped_tile`: the paired tile for two stacks at a multiple
    of 256 that is not one of 512, today's plan everywhere else (Mixtral's
    and GLM's block shapes, every down projection), the stored-layout loop
    for one stack at such a width or a row that is not whole lanes."""
    row = lambda K: K // 2 + K // 16  # sym_int4 codes + float16 scales
    # (a step holds the word tiles that divide the call within 3 MiB of
    # codes: a whole 768-wide expert, one of 1280's five 1 MiB tiles)
    for K, O, held in ((4096, 768, 3), (2560, 768, 3), (2048, 256, 1),
                       (4096, 1280, 1), (2048, 1280, 5)):
        assert grouped_tile(O, row(K), K // 2, 2) \
            == ("words:paired", 256, held)
        assert grouped_tile(O, row(K), K // 2, 1) == ("loop", 256, 1)
    for K, O, stacks, held in (
            (4096, 14336, 2, 1), (14336, 4096, 1, 1),  # Mixtral: as before
            (2048, 1536, 2, 3), (1536, 2048, 1, 4),  # GLM: an expert a step
            (768, 4096, 1, 8), (768, 2560, 1, 5), (4096, 4096, 1, 2),
            (2048, 4096, 1, 4)):
        assert grouped_tile(O, row(K), K // 2, stacks) \
            == ("words", 512, held)
    assert grouped_tile(768, row(192), 96, 2) == ("loop", 256, 1)
    assert grouped_tile(640, row(4096), 2048, 2) == ("loop", 128, 1)


def test_natural_columns_puts_pack_major_columns_back():
    y = jnp.arange(8 * 1024, dtype=jnp.float32).reshape(8, 1024)
    # column p * q + i of a 512-wide tile holds the tile's column 4i + p
    perm = y.reshape(8, 2, 128, WORD_ROWS).swapaxes(-1, -2).reshape(8, 1024)
    got = jnp.concatenate([qdecode.natural_columns(perm[:, :512]),
                           qdecode.natural_columns(perm[:, 512:])], axis=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(y))


def test_linear_reaches_the_word_path(interpret, monkeypatch):
    """`ops.linear.linear`, the call the models make, hands the kernel the
    policy's tile and not one of its own: a 512-row weight is decoded by
    `stage_words` (through `stage_tile`), GEMV and GEMM rows alike."""
    from bigdl_tpu.ops.linear import linear

    staged = []
    real = qdecode.stage_tile  # (called a kernel instance; traced once)
    monkeypatch.setattr(qdecode, "stage_tile",
                        lambda *a, **k: staged.append(1) or real(*a, **k))
    qt = _weights("sym_int4", WORD_BLOCK_O, 1280, seed=5)  # 1280: no other
    for M in (3, 40):                                      # test's shape
        x = jax.random.normal(jax.random.PRNGKey(M), (M, 1280)
                              ).astype(jnp.bfloat16)
        y = linear(x, qt, None, jnp.bfloat16)
        assert len(staged) == (1 if M == 3 else 2)
        np.testing.assert_allclose(
            np.asarray(y, np.float32),
            np.asarray(_reference(x, qt).astype(jnp.bfloat16), np.float32),
            rtol=0, atol=0.04)  # one bf16 step of |y| <= 4
