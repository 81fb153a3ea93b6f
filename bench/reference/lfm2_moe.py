"""Plain float32 reference of the LFM2-MoE decoder (`lfm2_moe`: HF's
Lfm2MoeShortConv, Lfm2MoeAttention and Lfm2MoeSparseMoeBlock), compared AT
the program's expert choice, each choice held to this reference's own
router.

Straightforward `jax.numpy`, quadratic in the sequence: no cache, no pages,
no tails, no lane pairs (8 KV heads of 64 as published, each repeated to its
four query heads), no kernels, no batching. Its own nibble unpack (that of
`bench/reference/mistral.py`, with its RMSNorm and rotate-half rope);
`"highest"` matmul precision (`logits` sets it). Blocked so that it fits
beside the engine: the head 8192 rows at a time, one expert's float32
weights at a time, one head's `[T, T]` scores at a time. With u the normed
input of a layer, H the hidden size:

    convolution layer:  [B | C | x] = W_in u            three of H, this order
        g_t = B_t * x_t;   c_t = w[0] g_{t-2} + w[1] g_{t-1} + w[2] g_t
        (g before the first token is zero; `w [3, H]`, a channel each; no
        bias, no activation: the explicit sum of three shifted products)
        out = W_out (C * c)
    attention layer:  q = W_q u [32, 64], k = W_k u, v = W_v u [8, 64];
        q, k = RMSNorm over each head's 64 values, THEN the rope over all 64
        (rotate-half, theta 1e6); causal softmax(q k^T / sqrt(64)) v, four
        query heads a KV head; W_o
    feed-forward:  layers under `num_dense_layers`  w2(silu(w1 u) * w3 u);
        the others  s = sigmoid(W_g u) float32;  choice = top-k of (s +
        expert_bias): the bias chooses and never weighs;
        p = s[choice] / (sum s[choice] + 1e-6) * routed_scaling_factor;
        sum_j p_j SwiGLU_j(u); no shared expert
    logits = RMSNorm(h, embedding_norm) @ head^T

Departures, each with its reason:

* The parameter tree is the served one: `params["runs"]["00"..]` stack the
  layers by run of one operator and one feed-forward
  (`models/lfm2_moe.layer_runs`), `final_norm` is HF's `embedding_norm`, and
  the head is `lm_head`, the packed copy the program reads (with real
  weights it is the tied table packed; bench/weights.py draws it by itself).
* The top-k choice is compared as `bench/reference/glm4_moe_lite.py` compares
  it, for its reason (64 sigmoid scores lie close together and a top-k is
  discontinuous): this reference takes the expert ids the program chose at
  every position (`Request.expert_ids`, the 18 sparse layers in order), holds
  every one to its OWN router (the chosen expert's score + bias within
  `ROUTER_TIE` of this reference's k-th best at that position, on this
  reference's own hidden state; a choice that fails is not taken), counts the
  decisions in which the program's experts are not this reference's own
  top-k, and takes NONE when they are more than `FLIP_SHARE` of the
  sequence's. The combine weights are this reference's own scores of the
  chosen experts. A program that reports no choice is compared free.

`ROUTER_TIE` and `FLIP_SHARE` are GLM-4.7-Flash's (the same router: 64
sigmoid scores, a selection bias, top-4, weights of 0.02), and
`scripts/conv_check_sweep.py` reads this model's margins on the chip
(bench/configs/lfm2-24b-a2b-int4.json has them).

`rnd`, where a caller gives it, is applied to BOTH inputs of every matrix
product (the sweep passes a rounding to float8_e4m3: the precision below the
served one has to come out not correct). The benchmark's check never passes
it.

How the choices get here: as in `bench/reference/mixtral.py`, through
`serving.engine.last_routed_request` until the benchmark's entry hands the
request over (PERF.md section 7).
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
dense, _rms, _rope = _m.dense, _m._rms, _m._rope

ROUTER_TIE = 0.03  # score units; see the docstring
FLIP_SHARE = 0.15  # of a sequence's (layer, position) decisions; the same
ROUTER_EPS = 1e-6  # HF's Lfm2MoeSparseMoeBlock adds it to the chosen sum


def _same(x):
    return x


def _program_choice(tokens, n_layers: int, k: int):
    """[L_moe, T, k] int32 expert ids the program chose for exactly this
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


def _short_conv(hf, u, p, rnd):
    """The gated short convolution of one layer over u [T, H]."""
    H, K = hf["hidden_size"], hf["conv_L_cache"]
    bcx = rnd(u) @ rnd(dense(p["w_in"]).T)  # [T, 3 H]
    B, C, x = bcx[:, :H], bcx[:, H:2 * H], bcx[:, 2 * H:]
    g = B * x
    w = p["conv_w"].astype(jnp.float32)  # [K, H]; w[K - 1] the current input
    padded = jnp.concatenate([jnp.zeros((K - 1, H), g.dtype), g], axis=0)
    T = g.shape[0]
    c = sum(w[k] * padded[k:k + T] for k in range(K))
    return rnd(C * c) @ rnd(dense(p["w_out"]).T)


def _attention(hf, u, p, rnd):
    """GQA of one layer over u [T, H]: q/k norm a head, then the rope."""
    Hq, Hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    D = hf["hidden_size"] // Hq
    T, eps, theta = u.shape[0], hf["norm_eps"], _theta(hf)

    def mm(a, b):
        return rnd(a) @ rnd(b)

    q = mm(u, dense(p["wq"]).T).reshape(T, Hq, D)
    k = mm(u, dense(p["wk"]).T).reshape(T, Hkv, D)
    v = mm(u, dense(p["wv"]).T).reshape(T, Hkv, D)
    q = _rope(_rms(q, dense(p["q_norm"]), eps), theta)
    k = _rope(_rms(k, dense(p["k_norm"]), eps), theta)
    k, v = (jnp.repeat(a, Hq // Hkv, axis=1) for a in (k, v))
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None]

    def head(xs):  # one head at a time: [T, T] float32 scores
        qh, kh, vh = xs
        s = mm(qh, kh.T) * D ** -0.5
        return mm(jax.nn.softmax(jnp.where(j <= i, s, -jnp.inf), -1), vh)

    out = jax.lax.map(head, tuple(a.transpose(1, 0, 2) for a in (q, k, v)))
    return mm(out.transpose(1, 0, 2).reshape(T, Hq * D), dense(p["wo"]).T)


def _theta(hf) -> float:
    return float((hf.get("rope_parameters") or {}).get(
        "rope_theta", hf.get("rope_theta", 1e6)))


def _swiglu(x, w_gate, w_up, w_down, rnd):
    g, u = rnd(x) @ rnd(w_gate.T), rnd(x) @ rnd(w_up.T)
    return rnd(jax.nn.silu(g) * u) @ rnd(w_down.T)


def _moe(hf, x, p, chosen, rnd):
    """The expert block at the program's choice `chosen` [T, k] where that
    choice is admissible (module docstring), this reference's own top-k
    elsewhere. Also: how many of the T decisions the program made otherwise
    than this reference's router would, and how far under this reference's
    k-th best the program's worst choice lies."""
    k = hf["num_experts_per_tok"]
    score = jax.nn.sigmoid(rnd(x) @ rnd(dense(p["router"]).T))  # [T, E]
    biased = score + p["e_bias"].astype(jnp.float32)[None]
    _, own = jax.lax.top_k(biased, k)
    kth = jnp.sort(biased, axis=-1)[:, -k]
    c = jnp.clip(chosen, 0, score.shape[-1] - 1)
    c_sorted = jnp.sort(c, axis=-1)
    given = jnp.all(chosen >= 0, -1)
    deficit = jnp.where(given, jnp.max(
        kth[:, None] - jnp.take_along_axis(biased, c, -1), -1), 0.0)
    ok = (given
          & jnp.all(c_sorted[:, 1:] != c_sorted[:, :-1], -1)  # k experts
          & (deficit <= ROUTER_TIE))
    differs = given & jnp.any(c_sorted != jnp.sort(own, axis=-1), -1)
    idx = jnp.where(ok[:, None], c, own)
    top = jnp.take_along_axis(score, idx, -1)  # the UNBIASED scores
    if hf.get("norm_topk_prob", True):
        top = top / (jnp.sum(top, -1, keepdims=True) + ROUTER_EPS)
    top = top * hf.get("routed_scaling_factor", 1.0)
    weight = jnp.zeros_like(score).at[
        jnp.arange(x.shape[0])[:, None], idx].set(top)  # [T, E], 0 unrouted

    def one(acc, e):  # one expert at a time
        wg, wu, wd, w_e = e
        y = _swiglu(x, dense(wg), dense(wu), dense(wd), rnd)
        return acc + y * w_e[:, None], None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["w_gate_e"], p["w_up_e"], p["w_down_e"], weight.T))
    return out, jnp.sum(differs), jnp.max(deficit)


def hidden(hf, params, tokens, chosen, rnd=_same):
    """(the last layer's output [T, hidden], the number of decisions in
    which `chosen` [L_moe, T, k] differs from this reference's own top-k on
    the way, the worst deficit of a chosen expert under this reference's
    k-th best)."""
    eps = hf["norm_eps"]
    h = params["embed"][tokens].astype(jnp.float32)

    def layer(kind, carry, xs):
        h, n_differ, worst = carry
        p, c = xs
        mixer = _short_conv if kind == "conv" else _attention
        h = h + mixer(hf, _rms(h, dense(p["attn_norm"]), eps), p, rnd)
        x = _rms(h, dense(p["mlp_norm"]), eps)
        if "router" not in p:
            return (h + _swiglu(x, dense(p["w_gate"]), dense(p["w_up"]),
                                dense(p["w_down"]), rnd), n_differ,
                    worst), None
        y, n, d = _moe(hf, x, p, c, rnd)
        return (h + y, n_differ + n, jnp.maximum(worst, d)), None

    carry = (h, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32))
    kinds, l, sparse = list(hf["layer_types"]), 0, 0
    for r in sorted(params["runs"]):  # one scan a run of layers of a kind
        group = params["runs"][r]
        n = jax.tree.leaves(group)[0].shape[0]
        assert len(set(kinds[l:l + n])) == 1, "a run is of one kind"
        routed = "router" in group
        picks = (chosen[sparse:sparse + n] if routed
                 else jnp.zeros((n, 0), jnp.int32))
        carry, _ = jax.lax.scan(
            lambda c, xs, kind=kinds[l]: layer(kind, c, xs), carry,
            (group, picks))
        l, sparse = l + n, sparse + n * routed
    return carry


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
    L = hf["num_hidden_layers"] - hf["num_dense_layers"]
    k = hf["num_experts_per_tok"]
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
        h = _rms(h[-n_last:], dense(params["final_norm"]), hf["norm_eps"])
        return _head(h, params.get("lm_head", params["embed"]), rnd)
