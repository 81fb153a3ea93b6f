"""Plain float32 reference of the Jamba decoder (`jamba` with `num_experts`
1: Mamba-1 layers, a multi-query attention layer every `attn_layer_period`,
a SwiGLU MLP after every mixer, no position encoding).

Straightforward `jax.numpy`, no cache, no pages, no kernels, no batching, no
chunking. Its own nibble unpack (that of `bench/reference/mistral.py`, with
its RMSNorm); `"highest"` matmul precision (`logits` sets it). HF's
modeling_jamba, to the letter, with x_t the normed input of token t:

    h = embed[tokens]
    h = h + mixer(rmsnorm(h));  h = h + W_down (silu(W_gate u) * W_up u),
                                u = rmsnorm(h)
    logits = rmsnorm(h) @ head^T

Mamba-1 mixer, E = mamba_expand x hidden, N = mamba_d_state, R =
mamba_dt_rank, K = mamba_d_conv, as a plain loop over the tokens
(`lax.scan` over t, the whole `[N, E]` state a step):

    [u | z]       = x_t W_in
    c_t           = silu(b_conv + sum_{k<K} w_conv[k] u_{t-K+1+k})   zeros before 0
    [r | B | C]_t = c_t W_x;   r, B, C = rmsnorm each, with its own weight
    dt_t          = softplus(r_t W_dt + b_dt)                        [E]
    h_t[n, d]     = exp(-dt_t[d] a[n, d]) h_{t-1}[n, d] + dt_t[d] B_t[n] c_t[d]
    y_t[d]        = sum_n C_t[n] h_t[n, d] + D[d] c_t[d]
    out_t         = (y_t * silu(z_t)) W_out

Attention mixer: q [T, Hq, D], k, v [T, Hkv, D] with D = hidden / Hq, NO
rotation, causal softmax(q k^T / sqrt(D)) v, every query head on its KV
head (all of them on the one), W_o.

Departures from HF, each with its reason:

* The parameter tree is the served one: layers stacked by RUN of one kind
  (`runs["00"]`, `["01"]`, ...) in the order period and offset give; the
  decay rate arrives as `a = exp(A_log)` laid `[N, E]` (HF: `A_log [E, N]`)
  and the head as a packed copy of the tied table (`lm_head`), which is what
  the program reads; the convolution's weight arrives as `[K, E]` (HF:
  `[E, 1, K]`).
* HF runs the two small projections in the model's dtype; here they are
  float32 like everything else.
* The head is taken a block of rows at a time, the embedding by gather and
  the attention one head's `[T, T]` scores at a time: this reference runs
  on the chip beside the engine.

`rnd`, where a caller gives it, is applied to BOTH inputs of every matrix
product and to both inputs of the scan's products (`dt B c` and `C h`; the
state itself stays float32): the sweep passes a rounding to float8_e4m3, and
the precision below the served one has to come out not correct.
`state_dtype` computes the scan and keeps the state in that type (the tests'
control: bfloat16 has to fail them). The benchmark's check passes neither.
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
dense, _rms = _m.dense, _m._rms


def _same(x):
    return x


def layer_kinds(hf: dict) -> list:
    """HF's `layers_block_type`."""
    period, offset = hf["attn_layer_period"], hf["attn_layer_offset"]
    return ["attention" if i % period == offset else "mamba"
            for i in range(hf["num_hidden_layers"])]


def _mamba(hf, x, p, rnd, state_dtype=jnp.float32):
    """The Mamba-1 mixer of one layer over the whole sequence x [T, hid],
    token by token. Also returns the state after the last token."""
    E, N = hf["mamba_expand"] * hf["hidden_size"], hf["mamba_d_state"]
    R, K, eps = hf["mamba_dt_rank"], hf["mamba_d_conv"], hf["rms_norm_eps"]
    T = x.shape[0]
    uz = rnd(x) @ rnd(dense(p["w_in"])).T
    u, z = uz[:, :E], uz[:, E:]
    w, b = dense(p["conv_w"]), dense(p["conv_b"])  # [K, E], [E]
    padded = jnp.pad(u, ((K - 1, 0), (0, 0)))
    c = jax.nn.silu(b + sum(padded[k:k + T] * w[k] for k in range(K)))
    rbc = rnd(c) @ rnd(dense(p["w_x"])).T
    r = _rms(rbc[:, :R], dense(p["dt_norm"]), eps)
    Bm = _rms(rbc[:, R:R + N], dense(p["b_norm"]), eps)
    Cm = _rms(rbc[:, R + N:], dense(p["c_norm"]), eps)
    dt = jax.nn.softplus(rnd(r) @ rnd(dense(p["w_dt"])).T
                         + dense(p["dt_bias"]))  # [T, E]
    a, D = dense(p["a"]), dense(p["D"])  # [N, E]: the rate exp(A_log); [E]

    def step(h, t):
        ct, dtt, bt, gt = (v.astype(state_dtype) for v in t)
        dec = jnp.exp(-dtt[None, :] * a.astype(state_dtype))
        h = dec * h + rnd(dtt * ct)[None, :] * rnd(bt)[:, None]
        return h, jnp.sum(rnd(gt)[:, None] * rnd(h), axis=0)

    h, y = jax.lax.scan(step, jnp.zeros((N, E), state_dtype),
                        (c, dt, Bm, Cm))
    y = y.astype(jnp.float32) + D * c
    return rnd(y * jax.nn.silu(z)) @ rnd(dense(p["w_out"])).T, h


def _attention(hf, x, p, rnd):
    """Multi-query attention without positions over the whole sequence."""
    Hq, Hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    T = x.shape[0]
    D = dense(p["wq"]).shape[0] // Hq

    def mm(a, b):
        return rnd(a) @ rnd(b)

    q = mm(x, dense(p["wq"]).T).reshape(T, Hq, D)
    k = jnp.repeat(mm(x, dense(p["wk"]).T).reshape(T, Hkv, D),
                   Hq // Hkv, axis=1)
    v = jnp.repeat(mm(x, dense(p["wv"]).T).reshape(T, Hkv, D),
                   Hq // Hkv, axis=1)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None]

    def head(xs):  # one head at a time: [T, T] float32 scores
        qh, kh, vh = xs
        s = mm(qh, kh.T) * D ** -0.5
        return mm(jax.nn.softmax(jnp.where(j <= i, s, -jnp.inf), -1), vh)

    out = jax.lax.map(head, tuple(a.transpose(1, 0, 2) for a in (q, k, v)))
    return mm(out.transpose(1, 0, 2).reshape(T, Hq * D), dense(p["wo"]).T)


def _mlp(x, p, rnd):
    g = rnd(x) @ rnd(dense(p["w_gate"])).T
    u = rnd(x) @ rnd(dense(p["w_up"])).T
    return rnd(jax.nn.silu(g) * u) @ rnd(dense(p["w_down"])).T


def hidden(hf, params, tokens, rnd=_same, state_dtype=jnp.float32):
    """(the last layer's output [T, hidden], every Mamba layer's state
    after the last token [Lm, N, E])."""
    eps = hf["rms_norm_eps"]
    h = params["embed"][tokens].astype(jnp.float32)
    kinds, l, states = layer_kinds(hf), 0, []

    def layer(kind, h, p):
        x = _rms(h, dense(p["attn_norm"]), eps)
        if kind == "mamba":
            out, s = _mamba(hf, x, p, rnd, state_dtype)
        else:
            out, s = _attention(hf, x, p, rnd), None
        h = h + out
        return h + _mlp(_rms(h, dense(p["mlp_norm"]), eps), p, rnd), s

    for r in sorted(params["runs"]):  # one scan a run of layers of a kind
        group = params["runs"][r]
        n = jax.tree.leaves(group)[0].shape[0]
        assert len(set(kinds[l:l + n])) == 1, "a run is of one kind"
        h, s = jax.lax.scan(
            lambda c, p, kind=kinds[l]: layer(kind, c, p), h, group)
        if s is not None:
            states.append(s)
        l += n
    return h, jnp.concatenate(states, axis=0)


def _head(h, w, rnd=_same, block: int = 1 << 13):
    """h @ w^T with the head's rows taken `block` at a time."""
    V = w.data.shape[0] if hasattr(w, "qtype") else w.shape[0]
    out = []
    for lo in range(0, V, block):
        rows = jax.tree.map(lambda a: a[lo:lo + block], w)
        out.append(rnd(h) @ rnd(dense(rows)).T)
    return jnp.concatenate(out, axis=-1)


def logits(hf: dict, params, tokens, n_last: int, rnd=_same,
           state_dtype=jnp.float32):
    """float32 logits [n_last, V] of the last `n_last` positions of one
    unpadded sequence `tokens` [T]; `hf` holds the published config keys."""
    with jax.default_matmul_precision("highest"):
        h, _ = hidden(hf, params, tokens, rnd, state_dtype)
        h = _rms(h[-n_last:], dense(params["final_norm"]),
                 hf["rms_norm_eps"])
        return _head(h, params.get("lm_head", params["embed"]), rnd)
