"""Plain float32 reference of the Granite 4.0-H decoder (`granitemoehybrid`:
Mamba-2 layers, NoPE attention layers between them, top-k routed experts
and an always-on shared MLP after every mixer), compared AT the program's
expert choice, each choice held to this reference's own router.

Straightforward `jax.numpy`, no cache, no pages, no kernels, no batching.
Its own nibble unpack (that of `bench/reference/mistral.py`, with its
RMSNorm); `"highest"` matmul precision (`logits` sets it). With x_t the
normed input of token t and rs the residual multiplier:

    h = embed[tokens] * embedding_multiplier
    h = h + rs * mixer(rmsnorm(h));  u = rmsnorm(h)
    h = h + rs * (sum_e gate_e SwiGLU_e(u) + SwiGLU_shared(u))
    logits = rmsnorm(h) @ head^T / logits_scaling

Mamba-2 mixer, as the token-by-token RECURRENCE (a `lax.scan` over t; the
program's prefill runs the chunked form, so a fault at a chunk seam or in
a decay sum shows here): `[z | xBC | dt] = W_in x_t`; `xBC` through the
causal depthwise convolution over the last `d_conv` inputs, plus its bias,
silu; `[x | B | C] = xBC`; `dt = softplus(dt + dt_bias)`; per head
`S_t = exp(-dt a) S_{t-1} + dt x_t (x) B_t`, `y_t = S_t C_t + D x_t`;
`y = rmsnorm(y * silu(z)) * w` over the inner width; `W_out y`. Attention
mixer: GQA, NO position encoding, scores times `attention_multiplier`,
causal. Router: `logits = W_r u`, top-k of the logits, gates = softmax over
the chosen k.

Departures, each with its reason:

* The parameter tree is the served one: layers stacked by RUN of one kind
  (`runs["00"]`, `["01"]`, ...), in `layer_types`' order; the decay rate arrives as
  `a = exp(A_log)` in float16 and the head as a packed copy of the tied
  table (`lm_head`), which is what the program reads
  (models/granitemoehybrid.py says why); an expert's `[gate | up]` arrives
  split as the program keeps it.
* The top-k choice is compared as `bench/reference/glm4_moe_lite.py`
  compares it, for its reason (a top-k is discontinuous; ten of 72 logits
  lie closer together than two of eight): this reference takes the expert
  ids the program chose at every position of the sequence being checked
  (`Request.expert_ids`), holds every one of them to its OWN router (the
  chosen expert's logit must lie within `ROUTER_TIE` of this reference's
  k-th best at that position, on this reference's own hidden state; a
  choice that fails is not taken), counts the decisions in which the
  program's experts are not this reference's own top-k, and takes NONE when
  they are more than `FLIP_SHARE` of the sequence's. The gates are this
  reference's own softmax over the chosen logits. A program that reports no
  choice is compared free.
* The head is taken a block of rows at a time and the embedding by gather:
  in float32 each is 1.6 GB beside a model that fills the chip.

`ROUTER_TIE` and `FLIP_SHARE`: bench/configs/granite-4.0-h-small-int4.json
gives both readings of each (`scripts/hybrid_check_sweep.py`).

`rnd`, where a caller gives it, is applied to BOTH inputs of every matrix
product, the recurrence's `x (x) B` and `S C` included (the state itself
stays float32): the sweep passes a rounding to float8_e4m3, and the
precision below the served one has to come out not correct. The
benchmark's check never passes it.
"""

from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np


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

ROUTER_TIE = 1.0  # router-logit units; see the configuration file
FLIP_SHARE = 0.5  # of a sequence's (layer, position) decisions; the same


def _same(x):
    return x


def _program_choice(tokens, n_layers: int, k: int):
    """[L, T, k] int32 expert ids the program chose for exactly this
    sequence, or -1 everywhere (a program without the record, or no such
    request)."""
    tokens = np.asarray(tokens).tolist()
    found = None
    try:
        from bigdl_tpu.serving.engine import last_routed_request

        req = last_routed_request()
        if req is not None and (
                req.prompt + req.out_tokens)[:len(tokens)] == tokens:
            found = req.expert_ids(len(tokens))
    except (ImportError, AttributeError):
        pass
    if found is None or found.shape != (n_layers, len(tokens), k):
        return np.full((n_layers, len(tokens), k), -1, np.int32)
    return found.astype(np.int32)


def _mamba(hf, x, p, rnd):
    """The Mamba-2 mixer of one layer over the whole sequence x [T, hid],
    token by token."""
    H, P, N = hf["mamba_n_heads"], hf["mamba_d_head"], hf["mamba_d_state"]
    K, inner = hf["mamba_d_conv"], H * P
    C = inner + 2 * hf["mamba_n_groups"] * N
    T = x.shape[0]
    zxd = rnd(x) @ rnd(dense(p["w_in"])).T
    z, xbc, dt = zxd[:, :inner], zxd[:, inner:inner + C], zxd[:, inner + C:]
    w, b = dense(p["conv_w"]), dense(p["conv_b"])  # [K, C], [C]
    padded = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(b + sum(padded[k:k + T] * w[k] for k in range(K)))
    xs = xbc[:, :inner].reshape(T, H, P)
    Bm, Cm = xbc[:, inner:inner + N], xbc[:, inner + N:]
    dt = jax.nn.softplus(dt + dense(p["dt_bias"]))  # [T, H]
    a, D = dense(p["a"]), dense(p["D"])  # [H]: the decay rate exp(A_log)

    def step(S, t):
        xt, dtt, bt, ct = t
        S = (jnp.exp(-dtt * a)[:, None, None] * S
             + rnd(dtt[:, None] * xt)[..., None] * rnd(bt)[None, None, :])
        return S, jnp.einsum("hpn,n->hp", rnd(S), rnd(ct)) + D[:, None] * xt

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (xs, dt, Bm, Cm))
    y = _rms(y.reshape(T, inner) * jax.nn.silu(z), dense(p["mixer_norm"]),
             hf["rms_norm_eps"])
    return rnd(y) @ rnd(dense(p["w_out"])).T


def _attention(hf, x, p, rnd):
    """GQA without positions over the whole sequence x [T, hid]."""
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
        s = mm(qh, kh.T) * hf["attention_multiplier"]
        return mm(jax.nn.softmax(jnp.where(j <= i, s, -jnp.inf), -1), vh)

    out = jax.lax.map(head, tuple(a.transpose(1, 0, 2) for a in (q, k, v)))
    return mm(out.transpose(1, 0, 2).reshape(T, Hq * D), dense(p["wo"]).T)


def _swiglu(x, w_gate, w_up, w_down, rnd):
    g, u = rnd(x) @ rnd(w_gate.T), rnd(x) @ rnd(w_up.T)
    return rnd(jax.nn.silu(g) * u) @ rnd(w_down.T)


def _moe(hf, x, p, chosen, rnd):
    """The expert block at the program's choice `chosen` [T, k] where that
    choice is admissible (module docstring), this reference's own top-k
    elsewhere. Also: how many of the T decisions the program made otherwise
    than this reference's router would, how far under this reference's k-th
    best the program's worst choice lies, and this reference's own top-k
    [T, k] (what a program on this trajectory would have chosen)."""
    k = hf["num_experts_per_tok"]
    logit = rnd(x) @ rnd(dense(p["router"]).T)  # [T, E]
    _, own = jax.lax.top_k(logit, k)
    kth = jnp.sort(logit, axis=-1)[:, -k]
    c = jnp.clip(chosen, 0, logit.shape[-1] - 1)
    c_sorted = jnp.sort(c, axis=-1)
    given = jnp.all(chosen >= 0, -1)
    deficit = jnp.where(given, jnp.max(
        kth[:, None] - jnp.take_along_axis(logit, c, -1), -1), 0.0)
    ok = (given
          & jnp.all(c_sorted[:, 1:] != c_sorted[:, :-1], -1)  # k experts
          & (deficit <= ROUTER_TIE))
    differs = given & jnp.any(c_sorted != jnp.sort(own, axis=-1), -1)
    idx = jnp.where(ok[:, None], c, own)
    gate = jax.nn.softmax(jnp.take_along_axis(logit, idx, -1), axis=-1)
    weight = jnp.zeros_like(logit).at[
        jnp.arange(x.shape[0])[:, None], idx].set(gate)  # [T, E], 0 unrouted

    def one(acc, e):  # one expert at a time
        wg, wu, wd, w_e = e
        y = _swiglu(x, dense(wg), dense(wu), dense(wd), rnd)
        return acc + y * w_e[:, None], None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["w_gate_e"], p["w_up_e"], p["w_down_e"], weight.T))
    shared = _swiglu(x, dense(p["w_gate_s"]), dense(p["w_up_s"]),
                     dense(p["w_down_s"]), rnd)
    return out + shared, jnp.sum(differs), jnp.max(deficit), own


def hidden(hf, params, tokens, chosen, rnd=_same):
    """(the last layer's output [T, hidden], the number of decisions in
    which `chosen` [L, T, k] differs from this reference's own top-k on the
    way, the worst deficit of a chosen expert under this reference's k-th
    best, this reference's own top-k along the way [L, T, k])."""
    eps, rs = hf["rms_norm_eps"], hf["residual_multiplier"]
    h = (params["embed"][tokens].astype(jnp.float32)
         * hf["embedding_multiplier"])

    def layer(kind, carry, xs):
        h, n_differ, worst = carry
        p, c = xs
        mixer = _mamba if kind == "mamba" else _attention
        h = h + rs * mixer(hf, _rms(h, dense(p["attn_norm"]), eps), p, rnd)
        y, n, d, own = _moe(hf, _rms(h, dense(p["mlp_norm"]), eps), p, c,
                            rnd)
        return (h + rs * y, n_differ + n, jnp.maximum(worst, d)), own

    carry = (h, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32))
    kinds, l, owns = list(hf["layer_types"]), 0, []
    for r in sorted(params["runs"]):  # one scan a run of layers of a kind
        group = params["runs"][r]
        n = jax.tree.leaves(group)[0].shape[0]
        assert len(set(kinds[l:l + n])) == 1, "a run is of one kind"
        carry, own = jax.lax.scan(
            lambda c, xs, kind=kinds[l]: layer(kind, c, xs), carry,
            (group, chosen[l:l + n]))
        owns.append(own)
        l += n
    return (*carry, jnp.concatenate(owns, axis=0))


def _head(h, w, rnd=_same, block: int = 1 << 13):
    """h @ w^T with the head's rows taken `block` at a time."""
    V = w.data.shape[0] if hasattr(w, "qtype") else w.shape[0]
    out = []
    for lo in range(0, V, block):
        rows = jax.tree.map(lambda a: a[lo:lo + block], w)
        out.append(rnd(h) @ rnd(dense(rows)).T)
    return jnp.concatenate(out, axis=-1)


def logits(hf: dict, params, tokens, n_last: int, rnd=_same):
    """float32 logits [n_last, V] of the last `n_last` positions of one
    unpadded sequence `tokens` [T]; `hf` holds the published config keys."""
    L, k = hf["num_hidden_layers"], hf["num_experts_per_tok"]
    chosen = jax.pure_callback(
        lambda t: _program_choice(t, L, k),
        jax.ShapeDtypeStruct((L, tokens.shape[0], k), jnp.int32), tokens)
    with jax.default_matmul_precision("highest"):
        h, n_differ = hidden(hf, params, tokens, chosen, rnd)[:2]
        # a program that departs from this reference's own router more
        # often than bf16 near-ties explain is compared free, whatever
        # each departure's deficit
        h = jax.lax.cond(
            n_differ <= FLIP_SHARE * L * tokens.shape[0],
            lambda: h,
            lambda: hidden(hf, params, tokens, jnp.full_like(chosen, -1),
                           rnd)[0])
        h = _rms(h[-n_last:], dense(params["final_norm"]),
                 hf["rms_norm_eps"])
        head = params.get("lm_head", params["embed"])
        return _head(h, head, rnd) / hf["logits_scaling"]
