"""Tiled dequant-GEMM: dispatch coverage + parity matrix (ISSUE 9).

The fused kernel runs through the Pallas interpreter on CPU and is
diffed against the XLA dequant reference on either side of its own
boundary, the row tile (`tiling.pick_block_m`), and of `_GEMV_MAX_ROWS`,
where the route note's word changes (the old cliff: shapes above it fell
back to materializing the dequantized weights in-graph; its cost on the
chip: not measured). All core-marked: scripts/ci.sh --core runs them.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.ops.linear import (
    _GEMV_MAX_ROWS, _QGEMV_QTYPES, fused_why_not, linear,
)
from bigdl_tpu.ops.pallas.tiling import pick_block_m
from bigdl_tpu.ops.routes import record_routes
from bigdl_tpu.quant import quantize

# per-qtype contraction dims: the smallest k_multiple-eligible K that
# still exercises ragged structure (non-power-of-two chunk tails; odd
# super-block counts for the 256-multiple k-quants, like llama2's
# K=11008 -> 43 super-blocks)
_K_FOR = {
    "sym_int4": 320, "asym_int4": 320, "nf4": 384, "fp4": 384,
    "sym_int8": 224, "asym_int5": 224, "fp8_e4m3": 384, "fp8_e5m2": 384,
    "sym_int5": 1024, "fp6": 512, "nf3": 1024,
    "q2_k": 512, "q3_k": 768, "q4_k": 768, "q5_k": 1024, "q6_k": 768,
}
_O = 384  # ragged N: three 128-lane tiles, not a 256 multiple
# rows within ONE row tile of every K here, and rows across two
_M_TILE, _M_ACROSS = 128, 264


def _route_of(x, qt) -> str:
    """The route `linear` notes for this call (it is noted while tracing,
    so nothing runs)."""
    with record_routes() as routes:
        jax.eval_shape(linear, x, qt)
    ((_, route, _),) = routes
    return route


@pytest.mark.core
def test_gemm_dispatch_coverage(monkeypatch):
    """Every qtype in _QGEMV_QTYPES takes the fused kernel whatever the
    row count — new formats cannot silently regress prefill/batch/QLoRA
    shapes onto the XLA dequant path — and the route note says `gemv` up
    to _GEMV_MAX_ROWS rows and `gemm` above."""
    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    assert set(_K_FOR) == set(_QGEMV_QTYPES), "K table out of sync"
    rng = np.random.default_rng(0)
    for name in _QGEMV_QTYPES:
        K = _K_FOR[name]
        assert pick_block_m(_M_TILE, K) == _M_TILE
        assert pick_block_m(_M_ACROSS, K) < _M_ACROSS
        w = jnp.asarray(rng.normal(size=(_O, K)) * 0.1, jnp.float32)
        qt = quantize(w, name)
        assert qt.qtype == name, name
        assert fused_why_not(qt, lead=0) is None, name
        for m in (1, _GEMV_MAX_ROWS):
            x = jnp.zeros((1, m, K), jnp.float32)
            assert _route_of(x, qt) == "pallas:gemv", (name, m)
        for m in (_GEMV_MAX_ROWS + 1, _M_ACROSS):
            x = jnp.zeros((1, m, K), jnp.float32)
            assert _route_of(x, qt) == "pallas:gemm", (name, m)
        # odd O (not a 128-lane multiple) stays on the XLA path
        x = jnp.zeros((1, 64, K), jnp.float32)
        assert _route_of(x, quantize(w[:120], name)) == "xla", name


@pytest.mark.core
@pytest.mark.parametrize("qtype", sorted(_QGEMV_QTYPES))
def test_gemm_parity_matrix(rng, monkeypatch, qtype):
    """GEMM vs GEMV vs XLA-dequant for every registered qtype at one row,
    at rows within one row tile and at rows across two (the kernel's own
    boundary), and for sym_int4 on either side of _GEMV_MAX_ROWS too
    (the route note's). The fused outputs' only rounding vs the oracle is
    the shared bf16 weight cast; rows of a batched GEMM agree with the
    decode GEMV on the same activation (no numeric cliff at either
    boundary)."""
    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    K = _K_FOR[qtype]
    w = jnp.asarray(rng.normal(size=(_O, K)) * 0.1, jnp.float32)
    qt = quantize(w, qtype)
    assert qt.qtype == qtype
    wd = qt.dequantize(jnp.bfloat16)
    x_all = jnp.asarray(rng.normal(size=(_M_ACROSS, K)), jnp.float32
                        ).astype(jnp.bfloat16)

    y_gemv1 = None
    label = ((_GEMV_MAX_ROWS, _GEMV_MAX_ROWS + 1) if qtype == "sym_int4"
             else ())
    for m in (1, *label, _M_TILE, _M_ACROSS):
        x = x_all[:m]
        y = linear(x, qt, None, jnp.bfloat16)
        ref = jnp.einsum("mk,ok->mo", x, wd,
                         preferred_element_type=jnp.bfloat16)
        np.testing.assert_allclose(
            np.asarray(y, jnp.float32), np.asarray(ref, jnp.float32),
            atol=0.2, rtol=0.05, err_msg=f"{qtype} M={m}",
        )
        if m == 1:
            y_gemv1 = np.asarray(y, jnp.float32)
        else:  # row 0 crosses the GEMV/GEMM boundary without a cliff
            np.testing.assert_allclose(
                np.asarray(y[:1], jnp.float32), y_gemv1,
                atol=0.05, rtol=0.02, err_msg=f"{qtype} M={m} vs GEMV",
            )


@pytest.mark.core
def test_gemm_grad_matches_xla_path(rng, monkeypatch):
    """The fused GEMM is differentiable w.r.t. x (custom_vjp): dx comes
    from the XLA rematerialized-dequant backward, matching autodiff of
    the fallback einsum — the contract QLoRA training relies on."""
    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    K, O = 256, 256
    x = jnp.asarray(rng.normal(size=(2, 33, K)), jnp.float32)
    qt = quantize(jnp.asarray(rng.normal(size=(O, K)) * 0.1, jnp.float32),
                  "sym_int4")
    assert _route_of(x, qt) == "pallas:gemm"
    g = jnp.asarray(rng.normal(size=(2, 33, O)), jnp.float32)

    def loss(x):
        return jnp.sum(linear(x, qt, None, jnp.float32) * g)

    dx = jax.jit(jax.grad(loss))(x)
    # same cotangent through the explicit dequant path
    dx_ref = jax.grad(
        lambda x: jnp.sum(
            jnp.einsum("btk,ok->bto", x, qt.dequantize(jnp.float32)) * g)
    )(x)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_ref),
                               atol=2e-2, rtol=2e-2)


@pytest.mark.core
def test_lora_fused_epilogue_parity(rng, monkeypatch):
    """ISSUE 18: the LoRA epilogue folded into the dequant-GEMM's
    writeback (`qmatmul_lora`, gate-trick batched adapters) matches the
    XLA `lora_epilogue` fallback — logits at bf16 tolerance, exact
    gradients through the custom_vjp product rule — for both the shared
    (training) and batched per-row (serving) adapter shapes, straddling
    the GEMV/GEMM dispatch boundary."""
    K, O, r, B = 256, 256, 4, 3
    qt = quantize(jnp.asarray(rng.normal(size=(O, K)) * 0.1, jnp.float32),
                  "sym_int4")

    # batched per-row adapters: two live tenants + one adapter-less row
    # (zero pair, scale 0 — must ride along unchanged)
    a = jnp.asarray(rng.normal(size=(B, r, K)) * 0.1, jnp.float32)
    b = jnp.asarray(rng.normal(size=(B, O, r)) * 0.1, jnp.float32)
    a = a.at[2].set(0.0)
    b = b.at[2].set(0.0)
    scale = jnp.asarray([2.0, 0.5, 0.0], jnp.float32)
    shared = (a[0], b[0], jnp.asarray(2.0, jnp.float32))

    def run(x, lora):
        return linear(x, qt, None, jnp.bfloat16, lora=lora)

    for t in (1, 40):  # 3 rows -> GEMV; 120 rows -> tiled GEMM
        x = jnp.asarray(rng.normal(size=(B, t, K)), jnp.float32)
        for lora in ((a, b, scale), shared):
            monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
            y_fused = run(x, lora)
            monkeypatch.setenv("BIGDL_TPU_PALLAS", "0")
            y_xla = run(x, lora)
            np.testing.assert_allclose(
                np.asarray(y_fused, jnp.float32),
                np.asarray(y_xla, jnp.float32),
                atol=0.2, rtol=0.05, err_msg=f"T={t}",
            )
    # the adapter-less row equals the plain (no-lora) fused matmul
    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    x = jnp.asarray(rng.normal(size=(B, 8, K)), jnp.float32)
    y = run(x, (a, b, scale))
    y0 = linear(x, qt, None, jnp.bfloat16)
    np.testing.assert_allclose(
        np.asarray(y[2], jnp.float32), np.asarray(y0[2], jnp.float32),
        atol=1e-6, rtol=0,
    )

    # gradients: d/dx and d/d(a, b) agree with the XLA epilogue path
    g = jnp.asarray(rng.normal(size=(B, 8, O)), jnp.float32)

    def loss(x, a, b):
        return jnp.sum(run(x, (a, b, scale)).astype(jnp.float32) * g)

    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    grads_fused = jax.grad(loss, argnums=(0, 1, 2))(x, a, b)
    monkeypatch.setenv("BIGDL_TPU_PALLAS", "0")
    grads_xla = jax.grad(loss, argnums=(0, 1, 2))(x, a, b)
    for gf, gx in zip(grads_fused, grads_xla):
        np.testing.assert_allclose(
            np.asarray(gf, jnp.float32), np.asarray(gx, jnp.float32),
            atol=2e-2, rtol=2e-2,
        )


@pytest.mark.core
def test_qlora_train_step_fused_matches_xla(monkeypatch):
    """QLoRA acceptance (ISSUE 9): one train step over a quantized base
    with rows > _GEMV_MAX_ROWS runs the frozen-base matmuls through the
    fused GEMM (interpret mode) and reproduces the XLA path's loss and
    LoRA update."""
    import optax

    from bigdl_tpu.models import llama
    from bigdl_tpu.models.config import PRESETS
    from bigdl_tpu.train import init_lora, make_train_step

    cfg = PRESETS["tiny-llama"]
    params = llama.quantize_params(
        llama.init_params(cfg, jax.random.PRNGKey(0)), "sym_int4")
    lora = init_lora(cfg, jax.random.PRNGKey(1), rank=4)
    opt = optax.sgd(1e-2)
    opt_state = opt.init(lora["layers"])
    step = make_train_step(cfg, llama.forward, opt)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(1, cfg.vocab_size, (1, 41)),
        jnp.int32)  # 40 target rows > _GEMV_MAX_ROWS -> GEMM path
    mask = jnp.ones((1, 41), jnp.float32)

    # sanity: the quantized MLP up-proj (O=128, K=64) really is
    # GEMM-eligible at these shapes (wq's O=64 is not a lane multiple —
    # tiny-llama exercises mixed fused/XLA dispatch inside one step)
    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    w_up = params["layers"]["w_up"].map_arrays(lambda a: a[0])  # layer 0
    probe = jnp.zeros((1, 40, cfg.hidden_size), jnp.float32)
    assert _route_of(probe, w_up) == "pallas:gemm"

    _, _, loss_fused = step(params, lora, opt_state, tokens, mask)
    l_fused, _, _ = step(params, lora, opt_state, tokens, mask)

    monkeypatch.setenv("BIGDL_TPU_PALLAS", "0")
    l_xla, _, loss_xla = step(params, lora, opt_state, tokens, mask)

    np.testing.assert_allclose(float(loss_fused), float(loss_xla),
                               rtol=1e-3, atol=1e-3)
    for a, b in zip(jax.tree.leaves(l_fused["layers"]),
                    jax.tree.leaves(l_xla["layers"])):
        np.testing.assert_allclose(
            np.asarray(a, jnp.float32), np.asarray(b, jnp.float32),
            atol=1e-3, rtol=1e-2,
        )
