"""`qmatmul` at the benchmark cells' own shapes (ISSUE 32), through the
Pallas interpreter: sym_int4 at every contraction width the four
configurations have (Qwen2 3584 and 18944, Mistral and Mixtral 4096 and
14336, Brumby 5120 and 17408), O cut to two word-path tiles, at the row
counts the cells run (M = 1 `generate`, 8 Brumby, 16 Qwen2, 32 Mistral,
256 a prefill's row tile), against `x.astype(bf16) @ dq(W).astype(bf16).T`
accumulated in float32.

TOLERANCE: as `tests/test_qdecode_words.py`: the same bf16 operands into
float32 on both sides, |y| about 1, float32 summation order alone differs
(measured 1e-6 to 6e-6); `atol` 5e-5.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.ops.pallas.qmatmul import qmatmul
from bigdl_tpu.ops.pallas.tiling import (
    WORD_BLOCK_O, pick_block_o, ragged_word_tiles, word_tiles,
)
from bigdl_tpu.quant import quantize

pytestmark = pytest.mark.core

CELL_KS = (3584, 4096, 5120, 14336, 17408, 18944)
O2 = 2 * WORD_BLOCK_O  # two tiles


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")


@pytest.mark.parametrize("M", (1, 8, 16, 32, 256))
@pytest.mark.parametrize("K", CELL_KS)
def test_qmatmul_at_the_cells_shapes(interpret, K, M):
    w = jax.random.normal(jax.random.PRNGKey(K), (O2, K)) * K ** -0.5
    qt = quantize(w, "sym_int4")
    assert pick_block_o(O2, K // 2 + K // 16, row_bytes=K // 2) \
        == WORD_BLOCK_O
    x = jax.random.normal(jax.random.PRNGKey(M), (M, K)).astype(jnp.bfloat16)
    y = qmatmul(x, qt, out_dtype=jnp.float32)
    want = jnp.dot(x, qt.dequantize(jnp.bfloat16).T,
                   preferred_element_type=jnp.float32)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=0,
                               atol=5e-5)


# ---- a ragged last word tile (ISSUE 55) ------------------------------------

# (K, O, M): the remainders the cells' heads leave past their last whole
# 512-row tile (Mistral's and Mixtral's 32000 leave 256; 151936 and 73472
# leave 384; 154880 and granite's `in_proj`, O = 16768, leave 128), at the
# heads' own contraction widths and the cells' rows; the widest K at a
# prefill's row tile once
RAGGED = [(K, O, M) for K, O, Ms in (
    (2048, 512 + 128, (1, 8, 64)), (2048, 1024 + 384, (256,)),
    (2560, 512 + 256, (1, 16)), (2560, 1024 + 384, (8,)),
    (4096, 512 + 256, (1, 16)), (4096, 1024 + 384, (16, 256)),
    (5120, 1024 + 384, (8,)), (5120, 512 + 128, (64,)),
    (5120, 512 + 256, (256,)),
) for M in Ms]


def _poisoned_past(qt, O):
    """`qt` with its rows padded to whole word tiles by rows that must
    reach no column below O: codes 0xFF under NaN scales (whose bits the
    kernel's integer float16 decode reads as 98304: the columns past O
    come out around 1e7 where every valid one is around 1)."""
    import dataclasses

    pad = word_tiles(O) * WORD_BLOCK_O - O
    return dataclasses.replace(
        qt,
        data=jnp.pad(qt.data, ((0, pad), (0, 0)), constant_values=0xFF),
        scales=jnp.pad(qt.scales, ((0, pad), (0, 0)),
                       constant_values=jnp.nan))


# ---- the code tiles brought as words, by the kernel's own DMA (ISSUE 64) ----

@pytest.mark.parametrize("prepared", (False, True),
                         ids=("staged", "prepared"))
@pytest.mark.parametrize("stacked", (False, True), ids=("plain", "stacked"))
@pytest.mark.parametrize("M", (8, 512), ids=("one-M-tile", "two-M-tiles"))
@pytest.mark.parametrize("O", (512, 1024, 1536, 1152),
                         ids=("one-tile", "two", "three", "ragged"))
def test_word_tiles_brought_as_words(interpret, O, M, stacked, prepared):
    """The word path's codes stay in HBM and `qdecode.copy_tiles_ahead`
    copies a tile's words into one of two buffers one grid step ahead. One
    tile (no step to run ahead of), two, an odd number (the buffers' parity
    turns over at an M tile's end), a ragged last tile (its valid rows
    alone are copied); two M tiles (the sweep starts again while the last
    tile's buffer is still read); a traced layer of a stack, the weights
    constants of the jit around it (the interpreter's barrier); scales
    staged and prepared. The reference's product at the file's tolerance,
    and BIT FOR BIT the written-out one-set kernel on pipelined byte blocks
    that `tests/test_qdecode_words.py` holds, one M tile at a time."""
    import dataclasses

    from test_qdecode_words import _one_set

    from bigdl_tpu.ops.linear import prepare_scale_bits
    from bigdl_tpu.quant.qtensor import without_scale_bits

    K, L = 512, 2
    qt = quantize(jax.random.normal(jax.random.PRNGKey(O + M), (L, O, K))
                  * K ** -0.5, "sym_int4")
    if prepared:
        qt = prepare_scale_bits(qt)
        assert qt.bits_layout == "words"
    one = jax.tree.map(lambda a: a[L - 1], qt)
    x = jax.random.normal(jax.random.PRNGKey(M), (M, K)).astype(jnp.bfloat16)
    if stacked:
        # (the codes of the whole stack; float16 scales are the layer's own)
        w = qt if prepared else dataclasses.replace(qt, scales=one.scales)
        call = jax.jit(lambda x, l: qmatmul(x, w, out_dtype=jnp.float32,
                                            layer=l))
        y = np.asarray(call(x, L - 1))
    else:
        y = np.asarray(qmatmul(x, one, out_dtype=jnp.float32))
    assert y.shape == (M, O)
    bare = without_scale_bits(one)
    want = jnp.dot(x, bare.dequantize(jnp.bfloat16).T,
                   preferred_element_type=jnp.float32)
    np.testing.assert_allclose(y, np.asarray(want), rtol=0, atol=5e-5)
    halves = [x] if M == 8 else [x[:256], x[256:]]
    np.testing.assert_array_equal(
        y, np.concatenate([np.asarray(_one_set(h, bare)) for h in halves]))


@pytest.mark.parametrize("K,O,M", RAGGED)
def test_ragged_word_tile_at_the_heads_remainders(interpret, K, O, M):
    """An O of whole lanes that is no multiple of 512 runs the word path
    over `word_tiles(O)` tiles, `out_shape` still `[M, O]`: the reference's
    product at the file's tolerance, and, bit for bit, the first O columns
    of the call on a copy padded to whole tiles whose rows past O are
    POISON. A row of the tile is a column of the product all the way to
    the store, so what the ragged tile's buffer holds past O (on the chip:
    whatever it held) changes no valid column."""
    qt = quantize(jax.random.normal(jax.random.PRNGKey(K + O), (O, K))
                  * K ** -0.5, "sym_int4")
    assert O % WORD_BLOCK_O and ragged_word_tiles(O)
    assert pick_block_o(O, K // 2 + K // 16, row_bytes=K // 2) \
        == WORD_BLOCK_O
    x = jax.random.normal(jax.random.PRNGKey(M), (M, K)).astype(jnp.bfloat16)
    y = np.asarray(qmatmul(x, qt, out_dtype=jnp.float32))
    assert y.shape == (M, O)
    want = jnp.dot(x, qt.dequantize(jnp.bfloat16).T,
                   preferred_element_type=jnp.float32)
    np.testing.assert_allclose(y, np.asarray(want), rtol=0, atol=5e-5)
    padded = np.asarray(qmatmul(x, _poisoned_past(qt, O),
                                out_dtype=jnp.float32))
    assert np.abs(padded[:, O:]).max() > 1e6  # the poison is in the tile
    np.testing.assert_array_equal(y, padded[:, :O])


# what `pick_block_o` answers for a sym_int4 weight `[O, K]`: 512 the word
# path (ragged where O % 512), else the stored-layout loop's tile
TILE_PLAN = [
    # the cells' heads and granite's `in_proj`: ragged word tiles
    (4096, 32000, 512), (5120, 151936, 512), (2560, 151936, 512),
    (2048, 154880, 512), (4096, 73472, 512), (4096, 16768, 512),
    # one whole tile and a remainder: GLM's O = 768 among them
    (2048, 768, 512), (2048, 640, 512), (4096, 1280, 512),
    # under one whole tile the loop keeps it: MiniCPM-SALA's O = 256
    (4096, 256, 256), (4096, 384, 128), (4096, 128, 128),
    # whole tiles, as before
    (3584, 152064, 512), (4096, 100352, 512), (4096, 512, 512),
    # a row of codes that is not whole lanes keeps the loop at any O
    (192, 768, 256), (192, 1024, 256),
    # rows that are not whole lanes: the full dim (the shape guard sends
    # such a weight to XLA before any tile is picked)
    (4096, 576, 576),
]


@pytest.mark.parametrize("K,O,want", TILE_PLAN)
def test_the_tile_plan_follows_the_static_shape(K, O, want):
    from bigdl_tpu.ops.pallas import qdecode
    from bigdl_tpu.ops.pallas.qmatmul import bits_layout, tile_form

    spec = qdecode.DecodeSpec(planes=(4,), value=("offset", 8), block=32)
    persist = K // 2 + K // 16
    assert pick_block_o(O, persist, row_bytes=K // 2) == want
    form = "words" if want == WORD_BLOCK_O else "stored"
    assert tile_form(spec, O, K // 2) == bits_layout(spec, O, K // 2) == form
    assert ragged_word_tiles(O) == (O % 128 == 0 and O > WORD_BLOCK_O)
    assert word_tiles(O) == -(-O // WORD_BLOCK_O)
    # a cap under the word tile (nobody's today), an adapter's call (no
    # `row_bytes`) and the backward's dx keep the loop's tile whatever O is
    loop = next((bo for bo in (256, 128) if O % bo == 0), O)
    assert pick_block_o(O, persist, cap=256, row_bytes=K // 2) == loop
    assert pick_block_o(O, persist) == loop
    assert tile_form(spec, O, K // 2, cap=256) == "stored"


# ---- prepared scale bits, read in place (ISSUE 48) --------------------------

# (K, O, qtype, where the weight sits): the word path at the cells' widths
# (nb = 64, 128, 448 and Qwen2's 112), a format with mins, a weight outside
# any layer scan; since ISSUE 55 the ragged last word tile, whose block of
# bits is whole and zeros past O (a head, GLM's O = 768, a format with mins,
# granite's stacked Mamba `in_proj`: 16768 = 32 x 512 + 384 there, three
# tiles and 128 rows here); and the stored-layout loop, which keeps what has
# no whole tile before its ragged one (MiniCPM-SALA's O = 256)
PREPARED = {
    "words-2048": (2048, 1024, "sym_int4", "stack"),
    "words-4096": (4096, 1024, "sym_int4", "stack"),
    "words-14336": (14336, 512, "sym_int4", "stack"),
    "words-3584-nb112": (3584, 1024, "sym_int4", "stack"),
    "words-mins": (2048, 1024, "asym_int4", "stack"),
    "ragged-768": (2048, 768, "sym_int4", "stack"),
    "ragged-mins": (1024, 768, "asym_int4", "stack"),
    "head-words": (3584, 1536, "sym_int4", "head"),
    "head-ragged": (4096, 1280, "sym_int4", "head"),
    "ragged-in_proj": (4096, 1664, "sym_int4", "stack"),
    "stored-256": (2048, 256, "sym_int4", "stack"),
    "stored-mins": (1024, 384, "asym_int4", "stack"),
}


@pytest.mark.parametrize("M", (16, 256))
@pytest.mark.parametrize("name", list(PREPARED))
def test_prepared_scale_bits_are_bit_equal_to_the_float16_slice(
        interpret, name, M):
    """A call that reads `prepare_scale_bits`'s uint16 stack by layer index
    gives, bit for bit, what the call on the layer's float16 slice gives:
    the same scale bits reach the same products in the same order."""
    import dataclasses

    from bigdl_tpu.ops.linear import linear, prepare_scale_bits
    from bigdl_tpu.ops.routes import record_routes

    K, O, qtype, where = PREPARED[name]
    lead = (3,) if where == "stack" else ()
    qt = quantize(jax.random.normal(jax.random.PRNGKey(K + O), (*lead, O, K))
                  * K ** -0.5, qtype)
    prep = prepare_scale_bits(qt)
    assert prep.bits_layout == ("stored" if name.startswith("stored")
                                else "words")
    nb = K // 32
    assert prep.scale_bits.dtype == jnp.uint16 and prep.scale_bits.shape == (
        (*lead, word_tiles(O), nb, 512) if prep.bits_layout == "words"
        else (*lead, O, nb))
    if O % 512 and prep.bits_layout == "words":  # zeros past O, pack-major
        tail = np.asarray(prep.scale_bits)[..., -1, :, :].reshape(
            *lead, nb, 4, 128)
        assert not tail[..., O % 512 // 4:].any()
        assert tail[..., :O % 512 // 4].any()
    assert (prep.min_bits is None) == (qt.mins is None)
    x = jax.random.normal(jax.random.PRNGKey(M), (M, K)).astype(jnp.bfloat16)
    if where == "head":
        layer, sliced = None, qt
    else:  # codes with the layer axis, the float16 fields sliced
        layer = jnp.asarray(2)
        sliced = dataclasses.replace(
            qt, scales=qt.scales[2],
            mins=None if qt.mins is None else qt.mins[2])
    with record_routes() as routes:
        y = linear(x, prep, layer=layer)
        want = linear(x, sliced, layer=layer)
    assert sorted(d.split()[-1] for _, _, d in routes) == [
        "scales:slice", "scales:stack"], routes
    # the note before the scales' names the ragged form and its tile count
    is_ragged = prep.bits_layout == "words" and O % 512 > 0
    assert all(d.split()[-2].endswith(f":ragged:{word_tiles(O)}") == is_ragged
               for _, _, d in routes), routes
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(want, np.float32))


def test_prepare_leaves_alone_what_no_kernel_reads(interpret):
    """A two-level format keeps the float16 view (its effective scales are
    products formed in the stored layout), a shape the guard refuses and a
    dense array come back as they are; `without_scale_bits` gives the tree
    `optimize_model` made."""
    from bigdl_tpu.ops.linear import prepare_scale_bits
    from bigdl_tpu.quant.qtensor import without_scale_bits

    w = jax.random.normal(jax.random.PRNGKey(0), (1024, 2048)) * 0.02
    for qt in (quantize(w, "q4_k"), quantize(w[:96], "sym_int4"), w):
        assert prepare_scale_bits(qt) is qt
    qt = quantize(w, "sym_int4")
    prep = prepare_scale_bits(qt)
    assert prep.bits_layout == "words" and prep.scales is qt.scales
    back = without_scale_bits({"w": prep, "b": w})
    assert jax.tree.structure(back) == jax.tree.structure({"w": qt, "b": w})
    assert back["w"].data is qt.data
