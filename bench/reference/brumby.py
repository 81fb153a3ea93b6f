"""Plain float32 reference of the Brumby decoder (Manifest AI,
Brumby-14B-Base): Qwen3's block with POWER RETENTION where the softmax
attention was.

Straightforward `jax.numpy`, quadratic in the sequence: no state, no chunks,
no kernels, no cache, no batching, nothing imported from the program but the
parameter tree it is handed (the nibble unpack, RMSNorm and rotary embedding
are `bench/reference/mistral.py`'s). Call under
`jax.default_matmul_precision("highest")` (`logits` does).

The block: `h += Attn(RMSNorm(h))`, `h += SwiGLU(RMSNorm(h))`, no biases,
untied head. With x_t the normed input of token t, KV head j and a query
head h of j's group (`num_attention_heads / num_key_value_heads` to a
group), D = head_dim:

    q_t^h = RoPE(RMSNorm_D(W_q x_t))   k_t^j = RoPE(RMSNorm_D(W_k x_t))
    v_t^j = W_v x_t                    (as Qwen3: the norms are per head,
                                        learned, before the rotation)
    g_t^j = log sigmoid(W_g x_t)_j     (W_g [Hkv, hidden]: one log-gate <= 0
                                        per KV head and token, no bias)
    a[t, s] = exp(sum_{r=s+1..t} g_r^j) * (q_t^h . k_s^j / sqrt(D)) ** 2
                                       for s <= t: never negative
    y_t^h = sum_s a[t, s] v_s^j / (sum_s a[t, s] + eps)
    Attn = W_o concat_h(y_t^h)

This is the published description of power retention (arXiv 2507.04239,
Manifest AI's `retention` kernels) at degree p = 2. The source's
config.json carries none of: p; the gate's form; the normaliser by the sum
of the weights and its eps (1e-6 here); that RoPE and the q/k norms of the
Qwen3 block are kept; a chunk size; the length at which the source's
inference code goes over from this form to a state. ISSUE 31 fixed them as
above (no network in the sandbox and no copy of `modeling_brumby.py` or the
`retention` package in it, so there was nothing to hold them to), and
`bench/configs/brumby-14b-int4.json` lists each under `assumed`.

`rnd`, where a caller gives it, is applied to BOTH inputs of every matrix
product: `scripts/retention_check_sweep.py` passes a rounding to float8_e4m3
to read what the precision below the served one gives (it has to come out
not correct). The benchmark's check never passes it.

Departures from that description: none in the mathematics. The program
computes the same function from a recurrent state (bigdl_tpu/kvstate.py);
this file never forms one. The parameter tree is the served one, so q/k/v
and gate/up arrive fused (`wqkv`, `w_gateup`) and are split here; `w_g`
arrives dense.
"""

from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp


def _mistral():
    """`bench/reference/mistral.py`, by path (the harness loads reference
    files by path, so this one cannot count on a package)."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "mistral.py")
    spec = importlib.util.spec_from_file_location("bench_reference_mistral",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_m = _mistral()
dense, _rms, _rope = _m.dense, _m._rms, _m._rope

EPS = 1e-6  # of the normaliser; `retention_eps` where the config has it


def _same(x):
    return x


def _retention(q, k, v, g, eps, rnd=_same):
    """q [T, Hq, D]; k, v [T, Hkv, D]; g [T, Hkv] log-gates."""
    T, Hq, D = q.shape
    group = Hq // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    g = jnp.repeat(g, group, axis=1)  # [T, Hq]
    s = jnp.einsum("qhd,khd->hqk", rnd(q), rnd(k)) / jnp.sqrt(jnp.float32(D))
    b = jnp.cumsum(g, axis=0).T  # [Hq, T]: sum of the gates up to t
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None]
    # sum_{r=s+1..t} g_r = b_t - b_s, taken only where s <= t
    decay = jnp.exp(jnp.where(j <= i, b[:, :, None] - b[:, None, :],
                              -jnp.inf))
    a = decay * s * s
    y = jnp.einsum("hqk,khd->qhd", rnd(a), rnd(v))
    return (y / (jnp.sum(a, -1).T[..., None] + eps)).reshape(T, Hq * D)


def _layer(hf, h, p, rnd=_same):
    Hq, Hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    D = hf.get("head_dim") or hf["hidden_size"] // Hq
    T, eps = h.shape[0], hf["rms_norm_eps"]

    def mm(x, w):  # x @ w^T
        return rnd(x) @ rnd(dense(w)).T

    x = _rms(h, dense(p["attn_norm"]), eps)
    qkv = mm(x, p["wqkv"])
    q = qkv[:, :Hq * D].reshape(T, Hq, D)
    k = qkv[:, Hq * D:(Hq + Hkv) * D].reshape(T, Hkv, D)
    v = qkv[:, (Hq + Hkv) * D:].reshape(T, Hkv, D)
    q = _rope(_rms(q, dense(p["q_norm"]), eps), hf["rope_theta"])
    k = _rope(_rms(k, dense(p["k_norm"]), eps), hf["rope_theta"])
    g = jax.nn.log_sigmoid(mm(x, p["w_g"]))
    y = _retention(q, k, v, g, hf.get("retention_eps", EPS), rnd)
    h = h + mm(y, p["wo"])
    x = _rms(h, dense(p["mlp_norm"]), eps)
    gu = mm(x, p["w_gateup"])
    half = gu.shape[1] // 2
    return h + mm(jax.nn.silu(gu[:, :half]) * gu[:, half:], p["w_down"])


def _head(h, w, rnd=_same, block: int = 1 << 14):
    """h @ w^T with the head's rows taken `block` at a time: in float32 the
    whole head of 151936 x 5120 is 3.1 GB, beside a model that fills the
    chip."""
    V = w.data.shape[0] if hasattr(w, "qtype") else w.shape[0]
    out = []
    for lo in range(0, V, block):
        rows = jax.tree.map(lambda a: a[lo:lo + block], w)
        out.append(rnd(h) @ rnd(dense(rows)).T)
    return jnp.concatenate(out, axis=-1)


def logits(hf: dict, params, tokens, n_last: int, rnd=_same):
    """float32 logits [n_last, V] of the last `n_last` positions of one
    unpadded sequence `tokens` [T]; `hf` holds the published config keys."""
    with jax.default_matmul_precision("highest"):
        # rows of the embedding by gather: the whole table in float32 is as
        # large as the head
        h = params["embed"][tokens].astype(jnp.float32)
        h, _ = jax.lax.scan(lambda c, p: (_layer(hf, c, p, rnd), None), h,
                            params["layers"])
        h = _rms(h[-n_last:], dense(params["final_norm"]), hf["rms_norm_eps"])
        return _head(h, params["lm_head"], rnd)
