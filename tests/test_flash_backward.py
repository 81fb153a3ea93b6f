"""Trainable flash attention: forward and gradients vs the XLA
attention oracle, interpret mode (the CPU stand-in for Mosaic; the
compile on the chip is covered by `chip_smoke.py --train`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.ops.pallas.flash_backward import flash_attention_trainable


def oracle(q, k, v, start, causal=True, window=None, scale=None):
    """Dense masked attention in fp32, [B,T,H,D] layout, GQA by repeat."""
    B, T, Hq, D = q.shape
    _, S, Hkv, _ = k.shape
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    rep = Hq // Hkv
    kr = jnp.repeat(k, rep, axis=2)
    vr = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32),
                   kr.astype(jnp.float32)) * scale
    rows = jnp.arange(T)[:, None]
    cols = jnp.arange(S)[None, :]
    valid = cols >= start[:, None, None, None]
    if causal:
        valid = valid & (cols <= rows)
    if window is not None:
        valid = valid & (cols > rows - window)
    s = jnp.where(valid, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p, vr.astype(jnp.float32))


CASES = [
    # (B, T, Hq, Hkv, D, window, start)
    (1, 32, 4, 4, 16, None, None),
    (2, 48, 4, 2, 16, None, [0, 13]),  # GQA + left padding
    (1, 64, 2, 2, 16, 24, None),  # sliding window
]


@pytest.mark.parametrize("B,T,Hq,Hkv,D,window,start", CASES)
def test_flash_train_grads_match_oracle(B, T, Hq, Hkv, D, window, start):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, T, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, T, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, T, Hkv, D)), jnp.float32)
    start_a = jnp.asarray(start or [0] * B, jnp.int32)
    w = jnp.asarray(rng.standard_normal((B, T, Hq, D)), jnp.float32)
    # padding query rows (t < start) are meaningless by contract: the
    # kernel zeroes them, the dense oracle's softmax-over-all-masked
    # averages v — exclude them from the loss on both sides
    w = w * (jnp.arange(T)[None, :, None, None]
             >= start_a[:, None, None, None])

    def loss_flash(q, k, v):
        o = flash_attention_trainable(
            q, k, v, start_a, window=window, interpret=True,
            block_q=16, block_k=16,
        )
        return jnp.sum(o * w)

    def loss_oracle(q, k, v):
        return jnp.sum(oracle(q, k, v, start_a, window=window) * w)

    f_val, f_grads = jax.value_and_grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    o_val, o_grads = jax.value_and_grad(loss_oracle, argnums=(0, 1, 2))(q, k, v)

    np.testing.assert_allclose(f_val, o_val, rtol=2e-4, atol=2e-4)
    for fg, og, name in zip(f_grads, o_grads, "qkv"):
        np.testing.assert_allclose(
            np.asarray(fg), np.asarray(og), rtol=2e-3, atol=2e-3,
            err_msg=f"d{name}",
        )


def test_flash_train_forward_matches_inference_kernel():
    from bigdl_tpu.ops.pallas import flash_attention

    rng = np.random.default_rng(1)
    B, T, Hq, Hkv, D = 2, 32, 4, 2, 16
    q = jnp.asarray(rng.standard_normal((B, T, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, T, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, T, Hkv, D)), jnp.float32)
    start = jnp.asarray([0, 5], jnp.int32)
    a = flash_attention_trainable(q, k, v, start, interpret=True,
                                  block_q=16, block_k=16)
    b = flash_attention(q, k, v, start=start, causal=True, interpret=True,
                        block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


def test_flash_train_under_jit_and_value_and_grad():
    rng = np.random.default_rng(2)
    B, T, H, D = 1, 32, 2, 16
    q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)

    @jax.jit
    def step(q, k, v):
        return jax.value_and_grad(
            lambda q: jnp.sum(
                flash_attention_trainable(q, k, v, interpret=True,
                                          block_q=16, block_k=16) ** 2
            )
        )(q)

    val, g = step(q, k, v)
    assert np.isfinite(float(val)) and np.isfinite(np.asarray(g)).all()
