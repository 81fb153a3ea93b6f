"""Fused low-bit backward: dx/dW kernel parity + vjp routing (ISSUE 20).

The table-driven dx kernel (ops/pallas/qbackward.py) dequantizes weight
tiles in VMEM straight into the MXU in the TRANSPOSED access pattern
(dx = g @ dequant(W)); dW = g^T @ x is the dense accumulation twin.
Both run through the Pallas interpreter on CPU and are diffed against
the XLA rematerialized-dequant oracle — the exact backward QLoRA used
before this PR, still reachable via `fused_backward_scope(False)`.
All core-marked: scripts/ci.sh --core runs them.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.ops.linear import (
    _QGEMV_QTYPES, fused_backward_scope, fused_why_not, linear,
)
from bigdl_tpu.ops.pallas.qbackward import dw_matmul, qmatmul_dx
from bigdl_tpu.quant import quantize

# per-qtype contraction dims, same ragged-K table as test_qgemm.py:
# non-power-of-two chunk tails, odd super-block counts for the k-quants
_K_FOR = {
    "sym_int4": 320, "asym_int4": 320, "nf4": 384, "fp4": 384,
    "sym_int8": 224, "asym_int5": 224, "fp8_e4m3": 384, "fp8_e5m2": 384,
    "sym_int5": 1024, "fp6": 512, "nf3": 1024,
    "q2_k": 512, "q3_k": 768, "q4_k": 768, "q5_k": 1024, "q6_k": 768,
}
_O = 384  # ragged N: three 128-lane tiles, not a 256 multiple


@pytest.mark.core
def test_backward_dispatch_coverage():
    """The dx kernel reads every registered qtype through the forward's
    decoder, at the forward's K alignment: the parity matrix below walks
    all of them, at a K each that the forward's guard takes."""
    assert set(_K_FOR) == set(_QGEMV_QTYPES), "K table out of sync"
    for name, k_multiple in _QGEMV_QTYPES.items():
        assert k_multiple > 0 and _K_FOR[name] % k_multiple == 0, name


@pytest.mark.core
@pytest.mark.parametrize("qtype", sorted(_QGEMV_QTYPES))
def test_dx_parity_matrix(rng, qtype):
    """dx = g @ dequant(W) for every registered qtype at shapes
    straddling the GEMV/GEMM boundary plus a training batch (M = 1, 32,
    33, 512), ragged K/N. The fused kernel's only rounding vs the
    oracle is the shared bf16 weight cast + bf16 output store."""
    K = _K_FOR[qtype]
    w = jnp.asarray(rng.normal(size=(_O, K)) * 0.1, jnp.float32)
    qt = quantize(w, qtype)
    assert qt.qtype == qtype
    wd = qt.dequantize(jnp.bfloat16)
    g_all = jnp.asarray(rng.normal(size=(512, _O)), jnp.float32
                        ).astype(jnp.bfloat16)
    for m in (1, 32, 33, 512):
        g = g_all[:m]
        dx = qmatmul_dx(g, qt, interpret=True)
        ref = jnp.einsum("mo,ok->mk", g, wd,
                         preferred_element_type=jnp.float32)
        np.testing.assert_allclose(
            np.asarray(dx, jnp.float32), np.asarray(ref, jnp.float32),
            atol=0.2, rtol=0.05, err_msg=f"{qtype} M={m}",
        )


@pytest.mark.core
def test_dx_leading_batch_dims(rng):
    """[B, T, O] cotangents reshape through the kernel like the forward
    does: dx keeps the leading dims."""
    K = _K_FOR["sym_int4"]
    qt = quantize(jnp.asarray(rng.normal(size=(_O, K)) * 0.1, jnp.float32),
                  "sym_int4")
    g = jnp.asarray(rng.normal(size=(2, 17, _O)), jnp.float32
                    ).astype(jnp.bfloat16)
    dx = qmatmul_dx(g, qt, interpret=True)
    assert dx.shape == (2, 17, K)
    ref = jnp.einsum("bto,ok->btk", g, qt.dequantize(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    np.testing.assert_allclose(
        np.asarray(dx, jnp.float32), np.asarray(ref, jnp.float32),
        atol=0.2, rtol=0.05,
    )


@pytest.mark.core
def test_dw_parity(rng):
    """dW = g^T @ x tiled accumulation (the unfrozen/bf16-shadow path)
    at M = 1, 33, 512 with ragged K/N and leading batch dims."""
    K = 320
    for shape in ((1, 1), (1, 33), (2, 256)):  # flattened M: 1, 33, 512
        g = jnp.asarray(rng.normal(size=(*shape, _O)), jnp.float32
                        ).astype(jnp.bfloat16)
        x = jnp.asarray(rng.normal(size=(*shape, K)), jnp.float32
                        ).astype(jnp.bfloat16)
        dw = dw_matmul(g, x, interpret=True)
        assert dw.shape == (_O, K)
        ref = jnp.einsum("bto,btk->ok", g, x,
                         preferred_element_type=jnp.float32)
        np.testing.assert_allclose(
            np.asarray(dw, jnp.float32), np.asarray(ref, jnp.float32),
            atol=5e-2, rtol=5e-2, err_msg=f"shape={shape}",
        )


@pytest.mark.core
def test_vjp_dx_routes_through_fused_kernel(rng, monkeypatch):
    """The custom_vjp backward really dispatches to the Pallas dx kernel
    under fused_backward_scope(True) (call-counted), skips it under
    False, and both paths agree — the parity oracle contract."""
    import bigdl_tpu.ops.pallas as pallas_pkg

    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    calls = []
    real = pallas_pkg.qmatmul_dx

    def counting(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(pallas_pkg, "qmatmul_dx", counting)
    K = O = 256
    qt = quantize(jnp.asarray(rng.normal(size=(O, K)) * 0.1, jnp.float32),
                  "sym_int4")
    for m in (1, 33, 512):
        x = jnp.asarray(rng.normal(size=(1, m, K)), jnp.float32)
        g = jnp.asarray(rng.normal(size=(1, m, O)), jnp.float32)

        def loss(x):
            return jnp.sum(linear(x, qt, None, jnp.float32) * g)

        with fused_backward_scope(True):
            dx_fused = jax.grad(loss)(x)
        n_fused = len(calls)
        with fused_backward_scope(False):
            dx_oracle = jax.grad(loss)(x)
        assert n_fused >= 1, f"M={m}: fused path never hit the kernel"
        assert len(calls) == n_fused, f"M={m}: oracle hit the kernel"
        np.testing.assert_allclose(
            np.asarray(dx_fused), np.asarray(dx_oracle),
            atol=2e-2, rtol=2e-2, err_msg=f"M={m}",
        )
        calls.clear()


@pytest.mark.core
def test_lora_fused_forward_grad_through_fused_dx(rng, monkeypatch):
    """The lora-fused forward (qmatmul_lora epilogue) differentiates
    through the fused dx for its base-weight term: d/dx and d/d(a, b)
    match the XLA-remat oracle on GEMM shapes."""
    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    K, O, r = 256, 256, 4
    qt = quantize(jnp.asarray(rng.normal(size=(O, K)) * 0.1, jnp.float32),
                  "sym_int4")
    a = jnp.asarray(rng.normal(size=(r, K)) * 0.1, jnp.float32)
    b = jnp.asarray(rng.normal(size=(O, r)) * 0.1, jnp.float32)
    scale = jnp.asarray(2.0, jnp.float32)
    x = jnp.asarray(rng.normal(size=(1, 40, K)), jnp.float32)
    assert fused_why_not(qt, lead=0) is None
    g = jnp.asarray(rng.normal(size=(1, 40, O)), jnp.float32)

    def loss(x, a, b):
        y = linear(x, qt, None, jnp.float32, lora=(a, b, scale))
        return jnp.sum(y * g)

    with fused_backward_scope(True):
        grads_fused = jax.grad(loss, argnums=(0, 1, 2))(x, a, b)
    with fused_backward_scope(False):
        grads_oracle = jax.grad(loss, argnums=(0, 1, 2))(x, a, b)
    for gf, gx in zip(grads_fused, grads_oracle):
        np.testing.assert_allclose(
            np.asarray(gf, jnp.float32), np.asarray(gx, jnp.float32),
            atol=2e-2, rtol=2e-2,
        )


@pytest.mark.core
def test_qlora_train_step_fused_backward_loss_parity(monkeypatch):
    """ISSUE 20 acceptance: one QLoRA train step with
    fused_backward=True reproduces the XLA-remat step's loss (~1e-4)
    and LoRA update over a quantized tiny-llama base on GEMM shapes."""
    import optax

    from bigdl_tpu.models import llama
    from bigdl_tpu.models.config import PRESETS
    from bigdl_tpu.train import init_lora, make_train_step

    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    cfg = PRESETS["tiny-llama"]
    params = llama.quantize_params(
        llama.init_params(cfg, jax.random.PRNGKey(0)), "sym_int4")
    lora = init_lora(cfg, jax.random.PRNGKey(1), rank=4)
    opt = optax.sgd(1e-2)
    opt_state = opt.init(lora["layers"])
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(1, cfg.vocab_size, (1, 41)),
        jnp.int32)  # 40 target rows: the GEMM/fused-backward shape class
    mask = jnp.ones((1, 41), jnp.float32)

    step_fused = make_train_step(cfg, llama.forward, opt,
                                 fused_backward=True)
    step_remat = make_train_step(cfg, llama.forward, opt,
                                 fused_backward=False)
    l_fused, _, loss_fused = step_fused(params, lora, opt_state, tokens,
                                        mask)
    l_remat, _, loss_remat = step_remat(params, lora, opt_state, tokens,
                                        mask)
    np.testing.assert_allclose(float(loss_fused), float(loss_remat),
                               rtol=1e-4, atol=1e-4)
    for af, ar in zip(jax.tree.leaves(l_fused["layers"]),
                      jax.tree.leaves(l_remat["layers"])):
        np.testing.assert_allclose(
            np.asarray(af, jnp.float32), np.asarray(ar, jnp.float32),
            atol=1e-3, rtol=1e-2,
        )


@pytest.mark.core
def test_decode_kv_arms_bit_identical():
    """The two decode_kv arms — uint8 arithmetic bit decode (shared with
    the fp8 GEMM weights) and typed-fp8 astype — are byte-equal on every
    finite e5m2 pattern, scaled and unscaled. This is what made rewiring
    flash/paged/flash_backward onto the one decoder body a no-op."""
    from bigdl_tpu.ops.pallas.qdecode import decode_kv

    codes = jnp.arange(256, dtype=jnp.uint8).reshape(2, 128)
    typed = jax.lax.bitcast_convert_type(codes, jnp.float8_e5m2)
    finite = np.isfinite(np.asarray(typed.astype(jnp.float32)))

    raw_bits = np.asarray(decode_kv(codes))
    raw_typed = np.asarray(decode_kv(typed))
    np.testing.assert_array_equal(raw_bits[finite], raw_typed[finite])

    scale = jnp.asarray([[0.5], [3.0]], jnp.float32)
    s_bits = np.asarray(decode_kv(codes, scale))
    s_typed = np.asarray(decode_kv(typed, scale))
    np.testing.assert_array_equal(s_bits[finite], s_typed[finite])


@pytest.mark.core
def test_flash_fp8_kv_parity_bitwise_after_unification(rng):
    """Re-run of the fp8-KV acceptance with the flash kernel's K/V loads
    routed through qdecode.decode_kv: in-kernel dequant still matches
    dequantize-then-flash BITWISE (both f32 multiplies) — the decoder
    unification changed zero bits."""
    from bigdl_tpu.kvcache import _quantize_heads
    from bigdl_tpu.ops.pallas.flash_attention import flash_attention

    B, T, S, Hq, Hkv, D = 1, 8, 16, 2, 2, 16
    q = jnp.asarray(rng.normal(size=(B, T, Hq, D)), jnp.float32)
    kf = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    vf = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    kq, ks = _quantize_heads(kf)
    vq, vs = _quantize_heads(vf)
    start = jnp.zeros((B,), jnp.int32)
    qoff = jnp.asarray(S - T, jnp.int32)

    kd = kq.astype(jnp.float32) * ks.astype(jnp.float32)[..., None]
    vd = vq.astype(jnp.float32) * vs.astype(jnp.float32)[..., None]
    ref = flash_attention(q, kd, vd, start=start, q_offset=qoff,
                          interpret=True)
    out = flash_attention(q, kq, vq, start=start, q_offset=qoff,
                          k_scale=ks, v_scale=vs, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
