"""Plain float32 reference of the Mistral / Mixtral decoder.

Straightforward `jax.numpy`: RMSNorm, rotary embeddings (HF half-split
convention), grouped-query causal attention with the sliding window, SwiGLU,
and for Mixtral softmax over all experts -> top-k -> renormalise. Its own
nibble unpack and scale multiply; no kernels, no cache, no batching, nothing
imported from the program but the parameter tree it is handed. Sizes come
from the published config keys. Call under
`jax.default_matmul_precision("highest")` (`logits` does).

Departures from the published description: none in the mathematics. The
parameter tree is the served one, so q/k/v and gate/up arrive fused
(`wqkv`, `w_gateup`, rows concatenated in that order) and are split here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

BLOCK = 32  # sym_int4: one float16 scale per 32 weights along K


def unpack_sym_int4(data, scales):
    """[..., O, K/2] uint8 + [..., O, K/32] float16 -> [..., O, K] float32.
    Byte j holds element j in its low nibble and element j + K/2 in its
    high nibble; value = (code - 8) * scale of its block."""
    codes = jnp.concatenate([data & 0x0F, data >> 4], axis=-1)
    vals = codes.astype(jnp.float32) - 8.0
    blocks = vals.reshape(*vals.shape[:-1], vals.shape[-1] // BLOCK, BLOCK)
    return (blocks * scales.astype(jnp.float32)[..., None]).reshape(vals.shape)


def dense(w):
    """A weight of the served tree as float32."""
    if hasattr(w, "qtype"):
        if w.qtype != "sym_int4":
            raise ValueError(f"reference unpacks sym_int4 only, not {w.qtype}")
        return unpack_sym_int4(w.data, w.scales)
    return w.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):  # x [T, H, D]
    T, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    rot = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], -1)
    return x * cos + rot * sin


def _attention(hf, q, k, v):  # q [T, Hq, D]; k, v [T, Hkv, D]
    T, Hq, D = q.shape
    g = Hq // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(D))
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None]
    ok = j <= i
    if hf.get("sliding_window"):
        ok &= j > i - hf["sliding_window"]
    p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v).reshape(T, Hq * D)


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate.T) * (x @ w_up.T)) @ w_down.T


def _moe(hf, x, p):
    probs = jax.nn.softmax(x @ dense(p["router"]).T, axis=-1)  # [T, E]
    top, idx = jax.lax.top_k(probs, hf["num_experts_per_tok"])
    top = top / jnp.sum(top, -1, keepdims=True)
    weight = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], idx].set(top)  # [T, E], 0 unrouted

    def one(acc, e):  # one expert at a time: 0.7 GB of float32 weights
        wg, wu, wd, w_e = e
        y = _swiglu(x, dense(wg), dense(wu), dense(wd))
        return acc + y * w_e[:, None], None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["w_gate_e"], p["w_up_e"], p["w_down_e"], weight.T))
    return out


def _layer(hf, h, p):
    Hq, Hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    D = hf.get("head_dim") or hf["hidden_size"] // Hq
    T, eps = h.shape[0], hf["rms_norm_eps"]
    x = _rms(h, dense(p["attn_norm"]), eps)
    qkv = x @ dense(p["wqkv"]).T
    if "bqkv" in p:
        qkv = qkv + dense(p["bqkv"])
    q = qkv[:, :Hq * D].reshape(T, Hq, D)
    k = qkv[:, Hq * D:(Hq + Hkv) * D].reshape(T, Hkv, D)
    v = qkv[:, (Hq + Hkv) * D:].reshape(T, Hkv, D)
    q, k = _rope(q, hf["rope_theta"]), _rope(k, hf["rope_theta"])
    h = h + _attention(hf, q, k, v) @ dense(p["wo"]).T
    x = _rms(h, dense(p["mlp_norm"]), eps)
    if hf.get("num_local_experts"):
        return h + _moe(hf, x, p)
    gu = dense(p["w_gateup"])
    half = gu.shape[0] // 2
    return h + _swiglu(x, gu[:half], gu[half:], dense(p["w_down"]))


def logits(hf: dict, params, tokens, n_last: int):
    """float32 logits [n_last, V] of the last `n_last` positions of one
    unpadded sequence `tokens` [T]; `hf` holds the published config keys."""
    with jax.default_matmul_precision("highest"):
        h = dense(params["embed"])[tokens]
        h, _ = jax.lax.scan(lambda c, p: (_layer(hf, c, p), None), h,
                            params["layers"])
        h = _rms(h[-n_last:], dense(params["final_norm"]), hf["rms_norm_eps"])
        return h @ dense(params["lm_head"]).T
