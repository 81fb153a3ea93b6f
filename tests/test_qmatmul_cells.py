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
from bigdl_tpu.ops.pallas.tiling import WORD_BLOCK_O, pick_block_o
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


# ---- prepared scale bits, read in place (ISSUE 48) --------------------------

# (K, O, qtype, where the weight sits): the word path at the cells' widths
# (nb = 64, 128, 448 and Qwen2's 112), a format with mins, the stored-layout
# loop (O = 768 has no 512-row tile), and a weight outside any layer scan
PREPARED = {
    "words-2048": (2048, 1024, "sym_int4", "stack"),
    "words-4096": (4096, 1024, "sym_int4", "stack"),
    "words-14336": (14336, 512, "sym_int4", "stack"),
    "words-3584-nb112": (3584, 1024, "sym_int4", "stack"),
    "words-mins": (2048, 1024, "asym_int4", "stack"),
    "stored-768": (2048, 768, "sym_int4", "stack"),
    "stored-mins": (1024, 768, "asym_int4", "stack"),
    "head-words": (3584, 1536, "sym_int4", "head"),
    "head-stored": (4096, 1280, "sym_int4", "head"),
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
    assert prep.bits_layout == ("words" if name.split("-")[0] in
                                ("words", "head") and O % 512 == 0
                                else "stored")
    nb = K // 32
    assert prep.scale_bits.dtype == jnp.uint16 and prep.scale_bits.shape == (
        (*lead, O // 512, nb, 512) if prep.bits_layout == "words"
        else (*lead, O, nb))
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
