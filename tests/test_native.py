"""Native (C++) quantization packer tests: the csrc/quant_kernels.cpp
path must be bit-identical to the pure-jnp numerics — same codes, same
f16 scales — so the ingest fast path never changes model quality
(the reference's equivalent contract between `ggml_quantize_tensor`
variants and their Python callers, low_bit_linear.py:104-258)."""

import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import native
from bigdl_tpu.quant import quantize

pytestmark = [
    pytest.mark.skipif(
        not native.available(), reason="native toolchain unavailable"
    ),
    # fast gate subset: pytest -m core (scripts/ci.sh --core)
    pytest.mark.core,
]


def cases(rng):
    yield rng.standard_normal((8, 128)).astype(np.float32)
    yield (rng.standard_normal((4, 64)) * 100).astype(np.float32)
    z = rng.standard_normal((2, 3, 64)).astype(np.float32)
    z[0, 0, :32] = 0.0  # all-zero block → zero scale path
    yield z
    yield (rng.standard_normal((1, 256)) * 1e-4).astype(np.float32)


@pytest.mark.parametrize("qtype", ["sym_int4", "asym_int4", "sym_int8", "nf4", "fp4"])
def test_native_matches_jnp_bitexact(rng, qtype):
    for x in cases(rng):
        ref = quantize(jnp.asarray(x), qtype)
        out = native.quantize_np(x, qtype)
        assert out is not None
        data, scales, mins = out
        np.testing.assert_array_equal(
            data, np.asarray(ref.data), err_msg=f"{qtype} codes differ"
        )
        np.testing.assert_array_equal(
            scales.view(np.uint16),
            np.asarray(ref.scales).view(np.uint16),
            err_msg=f"{qtype} scales differ",
        )
        if mins is not None:
            np.testing.assert_array_equal(
                mins.view(np.uint16), np.asarray(ref.mins).view(np.uint16)
            )


def test_native_dequant_roundtrip(rng):
    x = rng.standard_normal((4, 64)).astype(np.float32)
    data, scales, _ = native.quantize_np(x, "sym_int4")
    lib = native._load()
    out = np.empty((4, 64), np.float32)
    lib.dequantize_sym_int4(
        np.ascontiguousarray(data), np.ascontiguousarray(scales.view(np.uint16)),
        4, 64, out,
    )
    ref = quantize(jnp.asarray(x), "sym_int4").dequantize(jnp.float32)
    np.testing.assert_allclose(out, np.asarray(ref), rtol=1e-3, atol=1e-3)


def test_qtensor_helper(rng):
    x = rng.standard_normal((4, 64)).astype(np.float32)
    qt = native.quantize_to_qtensor(x, "sym_int4")
    assert qt is not None and qt.qtype == "sym_int4" and qt.shape == (4, 64)


def test_concurrent_first_builds_all_load(tmp_path):
    """Several processes that find no build yet (the test workers of a
    fresh HOME) each end with a loadable library: with one shared
    temporary name all but the first lost theirs to the first's rename,
    fell back to jnp in silence, and this whole file was skipped."""
    import os
    import subprocess
    import sys

    env = dict(os.environ, BIGDL_TPU_NATIVE_CACHE=str(tmp_path))
    code = "from bigdl_tpu import native; print(native.available())"
    procs = [subprocess.Popen([sys.executable, "-c", code], env=env,
                              stdout=subprocess.PIPE, text=True)
             for _ in range(3)]
    assert [p.communicate(timeout=300)[0].strip() for p in procs] \
        == ["True"] * 3
    assert len(list(tmp_path.iterdir())) == 1  # no temporary left behind
