"""Plain float32 reference of the Solar-Open2 decoder (`solar_open2`: Kimi
delta attention layers, gated NoPE GQA layers, sigmoid-routed experts of
which ONE RANK'S SHARE may be held, one shared expert), compared AT the
program's expert choice, each choice held to this reference's own router.

Straightforward `jax.numpy`: no cache, no pages, no tails, no chunks, no
kernels, no batching; the delta rule TOKEN BY TOKEN (`lax.scan` over the
positions), the softmax over the whole context. Its own nibble unpack (that
of `bench/reference/mistral.py`, with its RMSNorm); `"highest"` matmul
precision (`logits` sets it). Blocked so that it fits beside the engine: the
head 8192 rows at a time, one expert's float32 weights at a time, one head's
`[T, T]` scores at a time. With x the normed input of a layer `[T, hidden]`:

    KDA layer (H heads of D = 128, K = 4 taps):
        q^ = Wq x   k^ = Wk x   v^ = Wv x
        c_t = sum_j w[j] a_{t - (K - 1) + j} a channel, a before the first
        token zero (`conv_w [K, 3 H D]`: q | k | v side by side, no bias)
        q' = silu(c(q^))  k' = silu(c(k^))  v = silu(c(v^))
        q = q' / max(|q'|, 1e-6) / sqrt(D)    k = k' / max(|k'|, 1e-6)
        g = -exp(A_log[h]) * softplus(W_fb (W_fa x) + dt_bias)    [T, H, D]
        beta = 2 * sigmoid(W_beta x)                              [T, H]
        per head, S [D, D] (key index, value index) from zero:
            S' = exp(g_t)[:, None] * S
            S  = S' + beta_t * k_t (v_t - S'^T k_t)^T
            o_t = S^T q_t
        y = RMSNorm_head(o; o_norm) * sigmoid(W_gb (W_ga x) + g_bias)
        out = Wo y
    GQA layer: q = Wq x [Hq, D], k, v [Hkv, D], no positions, causal
        softmax(q k^T / sqrt(D)) v, times sigmoid(Wg x), Wo
    experts: s = sigmoid(W_r x) float32 over the ROUTER's width; choice =
        top-k of (s + e_bias); p = s[choice] / (sum s[choice] + 1e-20) *
        routed_scaling_factor; sum over the chosen experts HELD HERE of
        p_j SwiGLU_j(x), plus the shared SwiGLU(x)
    logits = RMSNorm(h, final_norm) @ head^T

The share (`expert_parallel_share` in the configuration: the router's width
and the id of the first expert held; `n_routed_experts` then counts the
held): the expert stacks of the served tree are the held experts', the
router's rows are all of them. A chosen expert held elsewhere keeps its part
of the normalising sum and adds nothing: what the absent experts would have
added is left out here as in the program, and the partial result goes on.

Departures, each with its reason:

* The parameter tree is the served one: `params["runs"]["00"..]` stack the
  layers by run of one kind (`models/solar_open2.layer_runs`).
* The top-k choice is compared as `bench/reference/glm4_moe_lite.py` compares
  it, for its reason (320 sigmoid scores lie close together and a top-k is
  discontinuous): this reference takes the expert ids the program chose at
  every position (`Request.expert_ids`, ids over the router's width), holds
  every one to its OWN router (the chosen expert's score + bias within
  `ROUTER_TIE` of this reference's k-th best at that position, on this
  reference's own hidden state; a choice that fails is not taken), counts the
  decisions in which the program's experts are not this reference's own
  top-k, and takes NONE when they are more than `FLIP_SHARE` of the
  sequence's. The combine weights are this reference's own scores of the
  chosen experts. A program that reports no choice is compared free.
* The second pass (every choice this reference's own) is the other branch
  of a `lax.cond`, as LFM2's is: compiled for a described v5e at the cell's
  sizes the check keeps 0.48 GiB of temporaries that way, and 4.98 as a
  second turn of one `while_loop` body (the loop carried a copy of every
  expert stack).

`ROUTER_TIE` is GLM-4.7-Flash's; `FLIP_SHARE` is this model's own, read at
320 experts and top-8 on the chip by `scripts/delta_check_sweep.py` (PR 65,
bench/configs/solar-open2-250b-int4.json has the table): the eight best of
320 sigmoid scores lie in the saturated tail, so the program's choices are
not this reference's own top-8 in 20.7 to 22.9% of a sequence's decisions
while every one of them lies within 0.019 of this reference's k-th best
(`ROUTER_TIE` 0.03 holds): near-ties, more of them than 64 experts and top-4
make (5 to 6%: `FLIP_SHARE` 0.15 there). At 0.15 every check of this cell
was compared FREE and read 0.08 to 0.91 nats by the choices alone.

`rnd`, where a caller gives it, is applied to BOTH inputs of every matrix
product (the sweep passes a rounding to float8_e4m3: the precision below the
served one has to come out not correct). The benchmark's check never passes
it.
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

ROUTER_TIE = 0.03  # score units; see the docstring
FLIP_SHARE = 0.35  # of a sequence's (layer, position) decisions: 1.5 times
# the most the program read at this width (22.9%)
ROUTER_EPS = 1e-20  # what the DeepSeek-V3 family's router adds to the sum
SHARE_KEY = "expert_parallel_share"


def _same(x):
    return x


def share(hf) -> tuple:
    """(id of the first expert held, experts held, the router's width)."""
    held = hf["n_routed_experts"]
    s = hf.get(SHARE_KEY) or {}
    return (int(s.get("first_expert", 0)), held,
            int(s.get("router_experts", held)))


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


def delta_rule(q, k, v, g, beta):
    """The gated delta rule of every head, token by token from a zero state.
    q, k, g [T, H, D], v [T, H, D], beta [T, H] -> o [T, H, D]. S [H, D
    (key), D (value)]."""
    T, H, D = q.shape

    def one(S, t):
        qt, kt, vt, gt, bt = t
        S = jnp.exp(gt)[:, :, None] * S
        r = jnp.sum(S * kt[:, :, None], axis=1)  # S'^T k: [H, D value]
        S = S + kt[:, :, None] * (bt[:, None] * (vt - r))[:, None, :]
        return S, jnp.sum(S * qt[:, :, None], axis=1)

    _, o = jax.lax.scan(one, jnp.zeros((H, D, D), jnp.float32),
                        (q, k, v, g, beta))
    return o


def _kda(hf, x, p, rnd):
    """Kimi delta attention of one layer over x [T, hidden]."""
    lin = hf["linear_attn_config"]
    H, D, K = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    T, eps = x.shape[0], hf["rms_norm_eps"]

    def mm(a, b):
        return rnd(a) @ rnd(b)

    qkv = jnp.concatenate(
        [mm(x, dense(p[n]).T) for n in ("wq", "wk", "wv")], axis=-1)
    w = p["conv_w"].astype(jnp.float32)  # [K, 3 H D]; w[K - 1]: the current
    padded = jnp.concatenate(
        [jnp.zeros((K - 1, qkv.shape[1]), qkv.dtype), qkv], axis=0)
    c = jax.nn.silu(sum(w[j] * padded[j:j + T] for j in range(K)))
    q, k, v = (c[:, i * H * D:(i + 1) * H * D].reshape(T, H, D)
               for i in range(3))

    def unit(a):
        return a / jnp.maximum(jnp.linalg.norm(a, axis=-1, keepdims=True),
                               1e-6)

    q, k = unit(q) * D ** -0.5, unit(k)
    g = -jnp.exp(p["A_log"].astype(jnp.float32))[:, None] * jax.nn.softplus(
        mm(mm(x, dense(p["f_a"]).T), dense(p["f_b"]).T)
        + p["dt_bias"].astype(jnp.float32)).reshape(T, H, D)
    beta = 2.0 * jax.nn.sigmoid(mm(x, dense(p["w_beta"]).T))
    o = _rms(delta_rule(q, k, v, g, beta), dense(p["o_norm"]), eps)
    gate = jax.nn.sigmoid(
        mm(mm(x, dense(p["g_a"]).T), dense(p["g_b"]).T)
        + p["g_bias"].astype(jnp.float32))
    return mm(o.reshape(T, H * D) * gate, dense(p["wo"]).T)


def _attention(hf, x, p, rnd):
    """Gated GQA of one layer over x [T, hidden], no positions."""
    Hq, Hkv, D = (hf["num_attention_heads"], hf["num_key_value_heads"],
                  hf["head_dim"])
    T = x.shape[0]

    def mm(a, b):
        return rnd(a) @ rnd(b)

    q = mm(x, dense(p["wq"]).T).reshape(T, Hq, D)
    k = mm(x, dense(p["wk"]).T).reshape(T, Hkv, D)
    v = mm(x, dense(p["wv"]).T).reshape(T, Hkv, D)
    k, v = (jnp.repeat(a, Hq // Hkv, axis=1) for a in (k, v))
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None]

    def head(xs):  # one head at a time: [T, T] float32 scores
        qh, kh, vh = xs
        s = mm(qh, kh.T) * D ** -0.5
        return mm(jax.nn.softmax(jnp.where(j <= i, s, -jnp.inf), -1), vh)

    out = jax.lax.map(head, tuple(a.transpose(1, 0, 2) for a in (q, k, v)))
    out = out.transpose(1, 0, 2).reshape(T, Hq * D)
    return mm(out * jax.nn.sigmoid(mm(x, dense(p["wg"]).T)),
              dense(p["wo"]).T)


def _swiglu(x, w_gate, w_up, w_down, rnd):
    g, u = rnd(x) @ rnd(w_gate.T), rnd(x) @ rnd(w_up.T)
    return rnd(jax.nn.silu(g) * u) @ rnd(w_down.T)


def _moe(hf, x, p, chosen, rnd):
    """The expert block at the program's choice `chosen` [T, k] (ids over
    the router's width) where that choice is admissible (module docstring),
    this reference's own top-k elsewhere: the part of it that the experts
    HELD here give, plus the shared expert. Also: how many of the T
    decisions the program made otherwise than this reference's router would,
    and how far under this reference's k-th best the program's worst choice
    lies."""
    k = hf["num_experts_per_tok"]
    first, n_held, _ = share(hf)
    score = jax.nn.sigmoid(rnd(x) @ rnd(dense(p["router"]).T))  # [T, Er]
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
    if hf.get("norm_topk_prob", True):  # over every chosen, held or not
        top = top / (jnp.sum(top, -1, keepdims=True) + ROUTER_EPS)
    top = top * hf.get("routed_scaling_factor", 1.0)
    weight = jnp.zeros_like(score).at[
        jnp.arange(x.shape[0])[:, None], idx].set(top)  # [T, Er], 0 unrouted
    weight = weight[:, first:first + n_held]  # the experts held here

    def one(acc, e):  # one expert at a time
        wg, wu, wd, w_e = e
        y = _swiglu(x, dense(wg), dense(wu), dense(wd), rnd)
        return acc + y * w_e[:, None], None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["w_gate_e"], p["w_up_e"], p["w_down_e"], weight.T))
    if "w_up_s" in p:
        out = out + _swiglu(x, dense(p["w_gate_s"]), dense(p["w_up_s"]),
                            dense(p["w_down_s"]), rnd)
    return out, jnp.sum(differs), jnp.max(deficit)


def layer_kinds(hf) -> list:
    gqa = set(hf["gqa_layers"])
    return ["attention" if i in gqa else "kda"
            for i in range(hf["num_hidden_layers"])]


def hidden(hf, params, tokens, chosen, rnd=_same):
    """(the last layer's output [T, hidden], the number of decisions in
    which `chosen` [L, T, k] differs from this reference's own top-k on the
    way, the worst deficit of a chosen expert under this reference's k-th
    best)."""
    eps = hf["rms_norm_eps"]
    h = params["embed"][tokens].astype(jnp.float32)

    def layer(kind, carry, xs):
        h, n_differ, worst = carry
        p, c = xs
        mixer = _kda if kind == "kda" else _attention
        h = h + mixer(hf, _rms(h, dense(p["attn_norm"]), eps), p, rnd)
        y, n, d = _moe(hf, _rms(h, dense(p["mlp_norm"]), eps), p, c, rnd)
        return (h + y, n_differ + n, jnp.maximum(worst, d)), None

    carry = (h, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32))
    kinds, l = layer_kinds(hf), 0
    for r in sorted(params["runs"]):  # one scan a run of layers of a kind
        group = params["runs"][r]
        n = jax.tree.leaves(group)[0].shape[0]
        assert len(set(kinds[l:l + n])) == 1, "a run is of one kind"
        carry, _ = jax.lax.scan(
            lambda c, xs, kind=kinds[l]: layer(kind, c, xs), carry,
            (group, chosen[l:l + n]))
        l += n
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
    unpadded sequence `tokens` [T]; `hf` holds the configuration's keys as
    run."""
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
        h = _rms(h[-n_last:], dense(params["final_norm"]), hf["rms_norm_eps"])
        return _head(h, params["lm_head"], rnd)
