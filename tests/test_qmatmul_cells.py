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
