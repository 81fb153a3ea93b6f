"""Pallas kernel correctness vs the XLA reference implementations.

The reference validates its fused SYCL kernels only on real hardware via
layer-equivalence tests (SURVEY.md §4); here the kernels run through the
Pallas interpreter on CPU and are diffed against the plain-jnp ops, so
kernel logic is covered in CI without a chip.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.ops.attention import attention
from bigdl_tpu.ops.pallas.flash_attention import flash_attention
from bigdl_tpu.ops.pallas.qmatmul import qmatmul
from bigdl_tpu.quant import QTensor, quantize


def _masked_reference(q, k, v, start, q_offset, window=None, softcap=None):
    """Build the explicit [B,T,S] validity mask and run plain attention."""
    B, T, _, _ = q.shape
    S = k.shape[1]
    slots = q_offset + jnp.arange(T)[None, :]
    sj = jnp.arange(S)
    mask = (sj[None, None, :] <= slots[..., None]) & (
        sj[None, None, :] >= start[:, None, None]
    )
    if window is not None:
        mask = mask & (sj[None, None, :] > slots[..., None] - window)
    return attention(q, k, v, mask[:, None, None], softcap=softcap)


@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)])
def test_flash_matches_reference(rng, hq, hkv):
    B, T, S, D = 2, 24, 48, 16
    q = jnp.asarray(rng.normal(size=(B, T, hq, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, hkv, D)), jnp.float32)
    start = jnp.asarray([0, 5], jnp.int32)
    q_offset = jnp.asarray(S - T, jnp.int32)  # prefill wrote at slots 24..47

    out = flash_attention(q, k, v, start=start, q_offset=q_offset, interpret=True)
    ref = _masked_reference(q, k, v, start, q_offset)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-2)


def test_flash_sliding_window_and_softcap(rng):
    B, T, hq, hkv, D = 1, 32, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(B, T, hq, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, hkv, D)), jnp.float32)
    start = jnp.zeros((B,), jnp.int32)
    zero = jnp.zeros((), jnp.int32)

    out = flash_attention(
        q, k, v, start=start, q_offset=zero, window=8, softcap=30.0, interpret=True
    )
    ref = _masked_reference(q, k, v, start, zero, window=8, softcap=30.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-2)


def test_flash_multiblock(rng):
    """Sequences longer than one block exercise the online-softmax carry."""
    B, T, hq, hkv, D = 1, 160, 2, 2, 8
    q = jnp.asarray(rng.normal(size=(B, T, hq, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, hkv, D)), jnp.float32)
    start = jnp.zeros((B,), jnp.int32)
    zero = jnp.zeros((), jnp.int32)

    out = flash_attention(
        q, k, v, start=start, q_offset=zero, block_q=64, block_k=64, interpret=True
    )
    ref = _masked_reference(q, k, v, start, zero)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-2)


# ---------------------------------------------------------------------------
# the prefill kernel at the tiles `tiling.flash_blocks` picks (PR 45):
# bf16 operands, the group's heads stacked in one q block, 512 keys a step,
# dead K blocks neither computed nor named by the index maps
# ---------------------------------------------------------------------------

# id: (T, S, q_offset, start, group, D, window, softcap, fp8)
_FLASH_CASES = {
    "t16-d64": (16, 64, 0, 0, 1, 64, None, None, False),
    "t48-offset37-g4": (48, 128, 37, 0, 4, 128, None, None, False),
    "t250-start5-g7-d64": (250, 640, 37, 5, 7, 64, None, None, False),
    "t1024-offset512-g4": (1024, 2048, 512, 0, 4, 128, None, None, False),
    "t1792-g4": (1792, 2048, 0, 0, 4, 128, None, None, False),
    "t1024-d256": (1024, 2048, 0, 0, 1, 256, None, None, False),
    "t250-window100-start3": (250, 512, 0, 3, 4, 128, 100, None, False),
    "t1024-offset512-window300-g7": (1024, 1664, 512, 0, 7, 128, 300, None,
                                     False),
    "t48-softcap": (48, 128, 37, 0, 4, 128, None, 30.0, False),
    "t250-softcap-window-d256": (250, 384, 37, 0, 1, 256, 64, 30.0, False),
    "t250-fp8-g4": (250, 640, 37, 5, 4, 128, None, None, True),
    "t1024-fp8-window": (1024, 1152, 0, 0, 1, 128, 200, None, True),
}


@pytest.mark.parametrize("case", list(_FLASH_CASES))
def test_flash_bf16_at_the_policys_tiles(rng, case):
    """bfloat16 q and cache (or fp8 codes and scales) against the XLA
    masked attention on the float32 values of the same inputs; slots past
    q_offset + T exist in every case and hold noise."""
    from bigdl_tpu.kvcache import _quantize_heads

    T, S, q_offset, start, group, D, window, softcap, fp8 = \
        _FLASH_CASES[case]
    hkv = 1 if T > 512 else 2
    q = jnp.asarray(rng.normal(size=(1, T, hkv * group, D)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, S, hkv, D)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, S, hkv, D)), jnp.bfloat16)
    st = jnp.asarray([start], jnp.int32)
    off = jnp.asarray(q_offset, jnp.int32)
    assert q_offset + T < S
    kw = dict(start=st, q_offset=off, window=window, softcap=softcap,
              interpret=True)
    f32 = jnp.float32
    if fp8:
        kq, ks = _quantize_heads(k.astype(f32))
        vq, vs = _quantize_heads(v.astype(f32))
        out = flash_attention(q, kq, vq, k_scale=ks, v_scale=vs, **kw)
        k = kq.astype(f32) * ks.astype(f32)[..., None]
        v = vq.astype(f32) * vs.astype(f32)[..., None]
    else:
        out = flash_attention(q, k, v, **kw)
    assert out.dtype == jnp.bfloat16 and out.shape == q.shape
    ref = _masked_reference(q.astype(f32), k.astype(f32), v.astype(f32),
                            st, off, window=window, softcap=softcap)
    pad = max(start - q_offset, 0)  # pad positions attend nothing: zeros
    np.testing.assert_allclose(np.asarray(out, np.float32)[:, pad:],
                               np.asarray(ref)[:, pad:], atol=2e-2)


def test_flash_rows_before_start_read_zero(rng):
    """A left-padded row's pad positions attend nothing: zeros, not the
    mean of V a softmax over masked scores alone would give."""
    T, D = 32, 16
    q, k, v = (jnp.asarray(rng.normal(size=(1, T, 2, D)), jnp.bfloat16)
               for _ in range(3))
    out = flash_attention(q, k, v, start=jnp.asarray([5], jnp.int32),
                          interpret=True)
    assert np.all(np.asarray(out[:, :5], np.float32) == 0.0)
    assert np.all(np.any(np.asarray(out[:, 5:], np.float32) != 0.0, -1))


# (T, S, D, group, itemsize): the cells' prefill shapes and the odd ones
_FLASH_SHAPES = [
    (1792, 2048, 128, 4, 2), (1024, 2048, 128, 4, 2), (1024, 1152, 128, 4, 2),
    (512, 2048, 128, 7, 2), (192, 2048, 128, 7, 2), (4096, 5120, 256, 1, 2),
    (8192, 9216, 128, 7, 2), (16, 2048, 128, 4, 2), (250, 2048, 64, 1, 2),
    (1792, 2048, 128, 4, 1), (1024, 1024, 256, 16, 2),
]


@pytest.mark.parametrize("shape", _FLASH_SHAPES, ids=str)
def test_flash_blocks_fit_the_budget_they_state(shape):
    from bigdl_tpu.ops.pallas import tiling

    T, S, D, group, itemsize = shape
    bq, bk = tiling.flash_blocks(T, S, D, group, itemsize)
    assert bq % 16 == 0 and bk % 16 == 0
    if T > 128:  # nothing padded by more than a lane tile
        assert bq % 128 == 0 and tiling.round_up(T, 128) % bq == 0
    if S > 128:
        assert bk % 128 == 0 and tiling.round_up(S, 128) % bk == 0
    assert bk <= tiling.FLASH_BLOCK_K and bq <= tiling.FLASH_BLOCK_Q
    assert (tiling.flash_tile_bytes(bq, bk, D, group, itemsize)
            <= tiling.VMEM_BUDGET) or (bq <= 128 and bk <= 128)
    if min(T, S) >= 1024 and S % 512 == 0 and group * D <= 1024:
        assert bk == 512 and group * bq >= 512  # a step's worth of work


@pytest.mark.parametrize("shape", _FLASH_SHAPES, ids=str)
@pytest.mark.parametrize("window", [None, 300])
def test_flash_live_blocks_counts_the_steps_the_kernel_computes(
        shape, window):
    """`tiling.flash_live_blocks` (the roofline's and the route line's
    count) against the kernel's own test of a grid step, `live_k_range`,
    at the tiles the policy returns; and the K index map names a live
    block at every step: never one past the causal frontier, never one
    behind the window."""
    from bigdl_tpu.ops.pallas import tiling
    from bigdl_tpu.ops.pallas.flash_attention import (
        clamped_k_block, live_k_range,
    )

    T, S, D, group, itemsize = shape
    bq, bk = tiling.flash_blocks(T, S, D, group, itemsize)
    n_q, n_k = -(-T // bq), -(-S // bk)
    for q_offset in (0, 37, S - T):
        computed = 0
        for i in range(n_q):
            first, last = live_k_range(i, q_offset, bq, bk, n_k, window)
            assert 0 <= first <= last <= n_k - 1
            computed += last - first + 1
            row_min, row_max = q_offset + i * bq, q_offset + (i + 1) * bq - 1
            for j in range(n_k):
                jj = clamped_k_block(j, i, q_offset, bq, bk, n_k, window)
                assert first <= jj <= last
                assert jj == j or not first <= j <= last
                # its first key is no later than the block's last row,
                # or it is the row range's last block (T padded past S)
                assert jj * bk <= row_max or jj == n_k - 1
                if window is not None:
                    assert (jj + 1) * bk - 1 > row_min - window
        assert computed == tiling.flash_live_blocks(
            T, S, bq, bk, q_offset=q_offset, window=window)


def _gemv_oracle(x, qt):
    return jnp.einsum(
        "mk,ok->mo", x.astype(jnp.bfloat16), qt.dequantize(jnp.bfloat16),
        preferred_element_type=jnp.bfloat16,
    )


def _core(*case):
    return pytest.param(*case, marks=pytest.mark.core)


# (qtype, K, O, rows, a shift that gives the asymmetric formats a minimum to
# carry, atol): every format's planes, value decode and scale levels at
# GEMV rows. K = 768 is an odd count of super-blocks (a chunk starts mid
# super-block: llama2's K = 11008 has 43); the k-quants of 768 and q3_k,
# which `spec_for` decodes as q6_k (int8 centered codes, int8 sub-scales
# per 16), come back as the format that was asked for.
_FORMAT_CASES = [
    ("sym_int4", 128, 256, 1, 0.0, 0.15), ("sym_int4", 128, 256, 4, 0.0, 0.15),
    ("nf4", 256, 256, 2, 0.0, 0.15), ("fp4", 256, 256, 2, 0.0, 0.15),
    ("sym_int8", 128, 256, 1, 0.0, 0.1), ("sym_int8", 128, 256, 4, 0.0, 0.1),
    *[("q4_k", K, 128, m, 0.0, 0.15) for K in (256, 768) for m in (1, 4)],
    *[("q6_k", K, 128, m, 0.0, 0.1) for K in (256, 768) for m in (1, 4)],
    ("asym_int4", 128, 256, 1, 0.05, 0.15),
    ("asym_int4", 128, 256, 4, 0.05, 0.15),
    *[_core(q, 256, 128, m, 0.0, 0.1)
      for q in ("fp8_e4m3", "fp8_e5m2") for m in (1, 4)],
    _core("asym_int5", 128, 128, 2, 0.05, 0.15),
    _core("sym_int5", 1024, 128, 1, 0.0, 0.15),
    _core("fp6", 512, 128, 1, 0.0, 0.15),
    _core("nf3", 1024, 128, 1, 0.0, 0.15),
    *[_core(q, K, 128, 2, 0.0, 0.15)
      for q, K in (("q2_k", 512), ("q2_k", 768), ("q5_k", 1024), ("q5_k", 768))],
    _core("q3_k", 256, 128, 1, 0.0, 0.1),
]


@pytest.mark.parametrize("qtype,K,O,m,shift,atol", _FORMAT_CASES)
def test_qmatmul_matches_dequant(rng, qtype, K, O, m, shift, atol):
    """The fused matmul == dequantize-then-matmul, format by format (the
    kernel's only rounding is the shared bf16 weight cast)."""
    x = jnp.asarray(rng.normal(size=(m, K)), jnp.float32).astype(jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(O, K)) * 0.1 + shift, jnp.float32)
    qt = quantize(w, qtype)
    assert qt.qtype == qtype
    y = qmatmul(x, qt, block_o=128, interpret=True)
    np.testing.assert_allclose(
        np.asarray(y, jnp.float32),
        np.asarray(_gemv_oracle(x, qt), jnp.float32), atol=atol, rtol=0.05)


def test_qmatmul_leading_dims(rng):
    """[B, T, K] inputs flatten through the kernel and reshape back."""
    K, O = 64, 128
    x = jnp.asarray(rng.normal(size=(2, 3, K)), jnp.float32).astype(jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(O, K)) * 0.1, jnp.float32)
    qt = quantize(w, "sym_int4")

    y = qmatmul(x, qt, block_o=128, interpret=True)
    assert y.shape == (2, 3, O)
    ref = jnp.einsum("btk,ok->bto", x.astype(jnp.float32), qt.dequantize(jnp.float32))
    np.testing.assert_allclose(np.asarray(y, jnp.float32), np.asarray(ref), atol=0.2)


def test_linear_dispatch_uses_kernel(rng, monkeypatch):
    """linear() routes decode-shaped sym_int4 matmuls to the kernel."""
    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    import importlib

    # attribute lookup finds the `linear` *function* exported by ops/__init__
    linear_mod = importlib.import_module("bigdl_tpu.ops.linear")

    K, O = 64, 128
    x = jnp.asarray(rng.normal(size=(1, 1, K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(O, K)) * 0.1, jnp.float32)
    qt = quantize(w, "sym_int4")
    assert linear_mod.fused_why_not(qt, lead=0) is None
    y = linear_mod.linear(x, qt)
    dq = jnp.einsum("btk,ok->bto", x, qt.dequantize(jnp.float32))
    np.testing.assert_allclose(np.asarray(y, jnp.float32), np.asarray(dq), atol=0.2)


def test_flash_prefill_in_model(rng, monkeypatch):
    """End-to-end: llama prefill via flash == prefill via masked XLA path."""
    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    from bigdl_tpu import kvcache
    from bigdl_tpu.models import llama
    from bigdl_tpu.models.config import PRESETS

    config = PRESETS["tiny-llama"]
    params = llama.init_params(config, jax.random.PRNGKey(0))
    tokens = jnp.asarray(rng.integers(0, config.vocab_size, (2, 12)), jnp.int32)

    def run(env):
        monkeypatch.setenv("BIGDL_TPU_PALLAS", env)
        cache = kvcache.init_cache(
            config.num_hidden_layers, 2, 32, config.num_key_value_heads,
            config.head_dim_,
        )
        logits, _ = llama.forward(config, params, tokens, cache, mode="prefill")
        return np.asarray(logits, np.float32)

    flash_logits = run("interpret")
    ref_logits = run("0")
    np.testing.assert_allclose(flash_logits, ref_logits, atol=5e-2)


def test_linear_dispatch_nf4_uses_codebook_kernel(rng, monkeypatch):
    """linear() routes decode-shaped nf4 matmuls to the codebook kernel."""
    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    from bigdl_tpu.ops.linear import fused_why_not, linear

    K, O = 128, 128
    x = jnp.asarray(rng.normal(size=(1, 1, K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(O, K)) * 0.1, jnp.float32)
    qt = quantize(w, "nf4")
    assert fused_why_not(qt, lead=0) is None
    y = linear(x, qt, None, jnp.float32)
    ref = jnp.einsum("btk,ok->bto", x, qt.dequantize(jnp.float32))
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=0.05)


def test_linear_dispatch_int8_uses_kernel(rng, monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    from bigdl_tpu.ops.linear import fused_why_not, linear

    K, O = 64, 128
    x = jnp.asarray(rng.normal(size=(1, 1, K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(O, K)) * 0.1, jnp.float32)
    qt = quantize(w, "sym_int8")
    assert fused_why_not(qt, lead=0) is None
    y = linear(x, qt, None, jnp.float32)
    ref = jnp.einsum("btk,ok->bto", x, qt.dequantize(jnp.float32))
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=0.05)


@pytest.mark.parametrize("qtype", ["q4_k", "q6_k", "asym_int4"])
def test_linear_dispatch_kquant_uses_kernel(rng, monkeypatch, qtype):
    """linear() routes decode-shaped q4_k/q6_k/asym_int4 to the fused
    kernels (these formats used to take the XLA dequant fallback on
    the decode hot path; its cost on the chip: not measured)."""
    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    from bigdl_tpu.ops.linear import fused_why_not, linear

    K, O = 256, 128
    x = jnp.asarray(rng.normal(size=(1, 1, K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(O, K)) * 0.1, jnp.float32)
    qt = quantize(w, qtype)
    assert qt.qtype == qtype
    assert fused_why_not(qt, lead=0) is None
    y = linear(x, qt, None, jnp.float32)
    ref = jnp.einsum("btk,ok->bto", x, qt.dequantize(jnp.float32))
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=0.05)


@pytest.mark.core
def test_gemv_dispatch_coverage(rng, monkeypatch):
    """EVERY qtype in the registry with a decode path must be registered
    in _QGEMV_QTYPES and dispatch to the fused kernel at an eligible
    shape — the acceptance gate against XLA-fallback cliffs
    (their cost on the chip: not measured)."""
    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    from bigdl_tpu.ops.linear import _QGEMV_QTYPES, fused_why_not
    from bigdl_tpu.quant import qtype_registry

    decodable = {n for n, s in qtype_registry().items() if not s.is_dense}
    assert decodable == set(_QGEMV_QTYPES), (
        "fused-GEMV registry out of sync with quant/qtypes.py"
    )
    for name, k_multiple in _QGEMV_QTYPES.items():
        K = max(k_multiple, 256)
        w = jnp.asarray(rng.normal(size=(128, K)) * 0.1, jnp.float32)
        qt = quantize(w, name)
        assert qt.qtype == name, name
        assert fused_why_not(qt, lead=0) is None, (
            f"{name}: eligible shape missed")


def _refused(monkeypatch, reason):
    """A weight `fused_why_not` refuses for `reason` alone: every other
    guard would take it."""
    import importlib

    linear_mod = importlib.import_module("bigdl_tpu.ops.linear")
    zeros = lambda qtype, *shape: quantize(jnp.zeros(shape, jnp.float32), qtype)
    if reason == "O not a multiple of 128 lanes":
        return zeros("sym_int4", 120, 256)
    if reason == "a 128-row weight tile exceeds half the VMEM budget":
        return zeros("sym_int8", 128, 40960 + 32)  # 128 rows: over 5 MiB
    if reason == "K not a multiple of 64":
        return zeros("sym_int4", 128, 96)
    if reason == "weight is rank 3, kernels take rank 2":
        return zeros("sym_int4", 2, 128, 256)  # a stack, and no `layer`
    if reason == "no fused kernel registered for this qtype":
        monkeypatch.delitem(linear_mod._QGEMV_QTYPES, "nf4")
        return zeros("nf4", 128, 256)
    assert reason == "BIGDL_TPU_PALLAS=0"
    monkeypatch.setenv("BIGDL_TPU_PALLAS", "0")
    return zeros("sym_int4", 128, 256)


@pytest.mark.core
@pytest.mark.parametrize("reason", [
    "O not a multiple of 128 lanes",
    "a 128-row weight tile exceeds half the VMEM budget",
    "K not a multiple of 64",
    "weight is rank 3, kernels take rank 2",
    "no fused kernel registered for this qtype",
    "BIGDL_TPU_PALLAS=0",
])
def test_fused_why_not_names_the_guard_that_refuses(monkeypatch, reason):
    """Each guard of the one predicate, by the words an operator reads in
    a route note (`linear ... xla ... (<reason>)`, printed by `bench/run.py`
    in set-up): the same weight with the guard's cause taken away is
    taken, so the reason is the one that refused."""
    from bigdl_tpu.ops.linear import fused_why_not, linear
    from bigdl_tpu.ops.routes import record_routes

    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    assert fused_why_not(
        quantize(jnp.zeros((128, 256), jnp.float32), "sym_int4"),
        lead=0) is None
    qt = _refused(monkeypatch, reason)
    assert fused_why_not(qt, lead=0) == reason
    if qt.data.ndim == 2:  # and `linear` computes it on the XLA route
        x = jnp.zeros((4, qt.shape[-1]), jnp.bfloat16)
        with record_routes() as routes:
            jax.eval_shape(linear, x, qt)
        ((op, route, detail),) = routes
        assert (op, route) == ("linear", "xla")
        assert detail.endswith(f" slice ({reason})"), detail


@pytest.mark.core
def test_flash_fp8_kv_dequant_in_kernel(rng):
    """Dense fp8-KV attention: fp8 codes + per-(slot, head) scales
    dequantize inside the flash kernel, matching dequantize-then-flash
    bitwise (both f32 multiplies)."""
    from bigdl_tpu.kvcache import _quantize_heads

    B, T, S, Hq, Hkv, D = 2, 16, 32, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(B, T, Hq, D)), jnp.float32)
    kf = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    vf = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    kq, ks = _quantize_heads(kf)
    vq, vs = _quantize_heads(vf)
    start = jnp.asarray([0, 3], jnp.int32)
    qoff = jnp.asarray(S - T, jnp.int32)

    kd = kq.astype(jnp.float32) * ks.astype(jnp.float32)[..., None]
    vd = vq.astype(jnp.float32) * vs.astype(jnp.float32)[..., None]
    ref = flash_attention(q, kd, vd, start=start, q_offset=qoff,
                          interpret=True)
    out = flash_attention(q, kq, vq, start=start, q_offset=qoff,
                          k_scale=ks, v_scale=vs, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_llama_fp8_kv_prefill_flash_matches_xla(rng, monkeypatch):
    """End-to-end: fp8-KV prefill through the flash kernel's in-kernel
    dequant == the XLA dequant-and-attend path."""
    from bigdl_tpu import kvcache
    from bigdl_tpu.models import llama
    from bigdl_tpu.models.config import PRESETS

    config = PRESETS["tiny-llama"]
    params = llama.init_params(config, jax.random.PRNGKey(0))
    tokens = jnp.asarray(rng.integers(0, config.vocab_size, (2, 12)), jnp.int32)

    def run(env):
        monkeypatch.setenv("BIGDL_TPU_PALLAS", env)
        cache = kvcache.init_cache(
            config.num_hidden_layers, 2, 32, config.num_key_value_heads,
            config.head_dim_, quantize_kv=True,
        )
        logits, _ = llama.forward(config, params, tokens, cache, mode="prefill")
        return np.asarray(logits, np.float32)

    np.testing.assert_allclose(run("interpret"), run("0"), atol=5e-2)


# ---------------------------------------------------------------------------
# qmatmul on a stack of layers at a traced index (PR 30): the layer scan of
# models/llama.forward hands the kernel the whole [L, O, C] codes and its
# index, where a per-layer slice given to a Mosaic call is copied first
# ---------------------------------------------------------------------------

def _stack_of(rng, qtype, L, O, K):
    """L DIFFERENT weights quantized as one stack [L, O, K], and each
    layer's own QTensor."""
    w = jnp.asarray(rng.normal(size=(L, O, K)) * 0.1, jnp.float32)
    stack = quantize(w, qtype)
    assert stack.qtype == qtype and stack.data.ndim == 3
    return stack, [stack.map_arrays(lambda a, l=l: a[l]) for l in range(L)]


def _at_layer(stack, own):
    """What the scan body hands `linear`: the whole stack's codes, every
    other field one layer's."""
    return dataclasses.replace(own, data=stack.data)


_STACK_CASES = (
    [("sym_int4", 128, m) for m in (1, 16, 32, 256)]  # GEMV rows and a GEMM
    + [("sym_int5", 1024, 16), ("q4_k", 256, 16)]  # two planes; a k-quant
    + [(q, k, 4) for q, k in (  # and the layer reaches every format's kernel
        ("asym_int4", 128), ("nf4", 128), ("fp4", 128), ("sym_int8", 128),
        ("asym_int5", 128), ("fp8_e4m3", 128), ("fp8_e5m2", 128),
        ("fp6", 512), ("nf3", 1024), ("q2_k", 512), ("q3_k", 256),
        ("q5_k", 1024), ("q6_k", 256))]
)


@pytest.mark.core
@pytest.mark.parametrize("qtype,K,m", _STACK_CASES)
def test_linear_on_a_stack_is_bit_equal_to_the_slice(rng, monkeypatch, qtype,
                                                     K, m):
    """`linear(x, w, layer=l)` with `w.data` the codes of three different
    layers, l traced, against `linear` on layer l's own weight: the same
    tiles, the same chunks, the same bits. Neighbouring layers differ, so
    a wrong index cannot pass."""
    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    from bigdl_tpu.ops.linear import _QGEMV_QTYPES, linear
    from bigdl_tpu.ops.routes import record_routes

    assert set(q for q, _, _ in _STACK_CASES) == set(_QGEMV_QTYPES)
    L, O = 3, 256
    stack, own = _stack_of(rng, qtype, L, O, K)
    x = jnp.asarray(rng.normal(size=(m, K)), jnp.float32).astype(jnp.bfloat16)
    with record_routes() as routes:
        at = jax.jit(lambda x, w, l: linear(x, w, layer=l))
        got = [np.asarray(at(x, _at_layer(stack, own[l]), jnp.int32(l)),
                          np.float32) for l in range(L)]
        want = [np.asarray(jax.jit(linear)(x, own[l]), np.float32)
                for l in range(L)]
    kind = "gemv" if m <= 32 else "gemm"
    assert routes == {
        ("linear", f"pallas:{kind}",
         f"{qtype} M{m} K{K} O{O} stack scales:slice"): 1,
        ("linear", f"pallas:{kind}",
         f"{qtype} M{m} K{K} O{O} slice scales:slice"): 1,
    }
    for l in range(L):
        assert np.isfinite(want[l]).all()
        np.testing.assert_array_equal(got[l], want[l])
        assert not np.array_equal(want[l], want[(l + 1) % L])


@pytest.mark.core
def test_qmatmul_stack_index_is_traced_and_rank2_is_the_stack_of_one(rng):
    """One compiled program serves every layer (the index is data, not a
    constant), and a weight of its own goes through the same call as the
    stack of one read at 0."""
    from bigdl_tpu.ops.pallas.qmatmul import qmatmul

    L, O, K = 3, 128, 128
    stack, own = _stack_of(rng, "sym_int4", L, O, K)
    x = jnp.asarray(rng.normal(size=(2, K)), jnp.float32).astype(jnp.bfloat16)
    f = jax.jit(lambda x, w, l: qmatmul(x, w, interpret=True, layer=l))
    ys = [np.asarray(f(x, _at_layer(stack, own[l]), jnp.int32(l)), np.float32)
          for l in range(L)]
    assert f._cache_size() == 1
    for l in range(L):
        np.testing.assert_array_equal(
            ys[l], np.asarray(qmatmul(x, own[l], interpret=True), np.float32))
    text = str(jax.make_jaxpr(
        lambda x, w: qmatmul(x, w, interpret=False))(x, own[0]))
    assert text.count("pallas_call") == 1 and "name=qmatmul" in text
    assert "u8[1,128,64]" in text  # a reshape in front of the one call


@pytest.mark.core
def test_linear_on_a_stack_differentiates_like_the_slice(rng, monkeypatch):
    """The backward's dx kernel takes one layer's weight: a stacked call
    slices its codes there, and dx is the slice's dx."""
    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    from bigdl_tpu.ops.linear import linear

    stack, own = _stack_of(rng, "sym_int4", 3, 128, 128)
    x = jnp.asarray(rng.normal(size=(4, 128)), jnp.float32)
    loss = lambda x, w, l: jnp.sum(linear(x, w, layer=l).astype(
        jnp.float32) ** 2)
    for l in (0, 2):
        got = jax.grad(loss)(x, _at_layer(stack, own[l]), jnp.int32(l))
        want = jax.grad(loss)(x, own[l], None)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    with pytest.raises(AssertionError, match="adapter"):
        linear(x, _at_layer(stack, own[0]), layer=jnp.int32(0),
               lora=(jnp.zeros((2, 128)), jnp.zeros((128, 2)), 1.0))
