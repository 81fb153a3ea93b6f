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
  and the gated pair.

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
    WORD_BLOCK_O, WORD_ROWS, chunk_spans, finest_split, words_chunk, words_ok,
)
from bigdl_tpu.quant import quantize

pytestmark = pytest.mark.core

CELL_KS = (3584, 4096, 5120, 14336, 17408, 18944)


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


def _decoded_by_words(qt, K, ck=None):
    """[O, K] bf16: what the word path feeds the MXU for one tile, its
    chunks rolled or unrolled as the kernel would have them."""
    spec, data, side = _kernel_data(qt)
    O = data.shape[0]
    assert words_ok(O, data.shape[1])
    q = O // WORD_ROWS
    qmin = finest_split(K, spec.planes)
    ck = ck or words_chunk(qmin, spec.block)

    def kern(w_ref, *refs):
        side_refs, o_ref, scratch = refs[:spec.n_side], refs[spec.n_side], \
            refs[spec.n_side + 1:]
        qdecode.stage_words(spec, w_ref, side_refs, scratch)
        signed = jnp.issubdtype(w_ref.dtype, jnp.signedinteger)
        for seg in range(K // qmin):
            for c0, c in chunk_spans(qmin, ck):
                o_ref[seg * qmin + c0:seg * qmin + c0 + c, :] = \
                    qdecode.decode_chunk_words(
                        spec, K, scratch[0], scratch[2], signed, seg, c0, c)

    out = pl.pallas_call(
        kern, out_shape=jax.ShapeDtypeStruct((K, O), jnp.bfloat16),
        scratch_shapes=qdecode.word_scratch(spec, O, data.shape[1],
                                            side[-1].shape[1]),
        interpret=True,
    )(data, *side)
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


def _reference(x, qt):
    return jnp.dot(x.astype(jnp.bfloat16), qt.dequantize(jnp.bfloat16).T,
                   preferred_element_type=jnp.float32)


@pytest.mark.parametrize("K", CELL_KS)
def test_decoded_words_at_the_cells_widths(K):
    """sym_int4, the cells' format, at each cell's contraction width: odd
    chunk tails, scale columns that do not fill 128 lanes (K / 32 = 112,
    160, 448, 544, 592)."""
    qt = _weights("sym_int4", WORD_BLOCK_O, K, seed=K)
    want = np.asarray(qt.dequantize(jnp.bfloat16).astype(jnp.float32))
    got = np.asarray(_decoded_by_words(qt, K).astype(jnp.float32))
    np.testing.assert_array_equal(got, want)


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


@pytest.mark.parametrize("gated", (False, True), ids=("one-stack", "gated"))
@pytest.mark.parametrize("K", (4096, 14336))
def test_grouped_kernel_on_the_word_path(interpret, K, gated):
    """Mixtral's two contractions, a group of size 0, one stack and the
    (gate, up) pair: rows of expert e are x @ dq(W[e])^T."""
    from bigdl_tpu.ops.pallas import moe_qmatmul as mq

    E, O, bm = 4, WORD_BLOCK_O, 8
    groups = [5, 0, 9, 2]
    ws = [quantize(jax.random.normal(jax.random.PRNGKey(i), (E, O, K))
                   * K ** -0.5, "sym_int4") for i in range(2 if gated else 1)]
    experts = np.repeat(np.arange(E), groups).astype(np.int32)
    N = len(experts)
    dest, src, te, n_used = mq.moe_layout(
        jnp.asarray(experts)[:, None], E, bm, mq.moe_n_tiles(N, 1, E, bm))
    x = jax.random.normal(jax.random.PRNGKey(7), (N, K)).astype(jnp.bfloat16)
    y = mq.moe_qmatmul(x[src], ws if gated else ws[0], te, n_used, bm,
                       act="silu" if gated else None, out_dtype=jnp.float32)
    got = np.asarray(y[dest[:, 0]])
    per = [jnp.einsum("nk,nok->no", x, w.dequantize(jnp.bfloat16)[experts],
                      preferred_element_type=jnp.float32) for w in ws]
    want = jax.nn.silu(per[0]) * per[1] if gated else per[0]
    np.testing.assert_allclose(got, np.asarray(want), rtol=0, atol=5e-5)


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
    `stage_words`, through GEMV and GEMM rows alike."""
    from bigdl_tpu.ops.linear import linear

    staged = []
    real = qdecode.stage_words
    monkeypatch.setattr(qdecode, "stage_words",
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
