"""Plain float32 reference of the SmallThinker decoder (`smallthinker`: window
and full attention mixed by layer, a rope in the window layers only, top-k
ReLU-gated experts routed from the layer's input), compared AT the
program's expert choice, each choice held to this reference's own router.

Straightforward `jax.numpy`, no cache, no pages, no kernels, no batching;
its own nibble unpack; `"highest"` matmul precision (`logits` sets it).
With x [T, hid] the input of layer l:

    r = W_r x                      from x BEFORE the attention norm
    I = top-k of r;  w = softmax(r[I])     (= softmax over all E, top-k,
                                              renormalised)
    a = rmsnorm(x);  q, k, v = W_q a, W_k a, W_v a
    rope_layout[l] == 1:  q, k = rope(q, k; theta, position t)  else none
    s_tj = q_t . k_j / sqrt(D),  j <= t,  and where
    sliding_window_layout[l] == 1 also  j > t - sliding_window_size
    h = x + W_o softmax(s) v
    m = rmsnorm(h)
    out = h + sum_{e in I} w_e W_down^e (relu(W_gate^e m) * (W_up^e m))
    logits = W_head rmsnorm(x_L)

Departures from the published description, each with its reason:

* The parameter tree is the served one: layer l is entry `l // P` of
  `params["period"][str(l % P)]`, P the layouts' period (the program scans
  over the periods, models/smallthinker.py); this file scans the same way,
  which IS the model's layer order. A tree without `period` (the parent
  commit builds a dense llama from this configuration's keys) is refused by
  name before any arithmetic.
* The top-k choice is compared as `bench/reference/glm4_moe_lite.py`
  compares it, for its reason (a top-k is discontinuous: six of 64 logits
  lie closer together than bf16 activations can tell). This reference takes
  the expert ids the program chose at every position of the sequence being
  checked (`Request.expert_ids`), holds every one of them to its OWN router
  (the chosen expert's logit must lie within `ROUTER_TIE` of this
  reference's k-th best at that position, on this reference's own hidden
  state; a choice that fails is not taken), counts the decisions in which
  the program's experts are not this reference's own top-k, and takes NONE
  when they are more than `FLIP_SHARE` of the sequence's. The gates are
  this reference's own softmax over the chosen logits. A program that
  reports no choice is compared free.
* Attention runs a head and a block of `Q_BLOCK` queries at a time, the
  head a block of rows at a time and the embedding by gather: 8192
  positions then fit in the 3 GB the engine leaves of the chip.
* The rope is the half-split (`rotate_half`) convention; config.json has no
  key for it (the configuration file's `assumed`).

`ROUTER_TIE` and `FLIP_SHARE`: bench/configs/smallthinker-21ba3b-int4.json
gives both readings of each (`scripts/window_check_sweep.py`).

`rnd`, where a caller gives it, is applied to BOTH inputs of every matrix
product (the router's included): the sweep passes a rounding to
float8_e4m3, and the precision below the served one has to come out not
correct. The benchmark's check never passes it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 32  # sym_int4: one float16 scale per 32 weights along K
Q_BLOCK = 1024  # queries of one head whose scores are held at a time
ROUTER_TIE = 1.0  # router-logit units; see the configuration file
FLIP_SHARE = 0.25  # of a sequence's (layer, position) decisions; the same


def _same(x):
    return x


def dense(w):
    """A weight of the served tree as float32: [..., O, K/2] uint8 codes
    (byte j holds element j low and element j + K/2 high; value = code - 8)
    times [..., O, K/32] float16 scales, or a dense leaf as it is."""
    if not hasattr(w, "qtype"):
        return w.astype(jnp.float32)
    if w.qtype != "sym_int4":
        raise ValueError(f"reference unpacks sym_int4 only, not {w.qtype}")
    codes = jnp.concatenate([w.data & 0x0F, w.data >> 4], axis=-1)
    vals = codes.astype(jnp.float32) - 8.0
    blocks = vals.reshape(*vals.shape[:-1], vals.shape[-1] // BLOCK, BLOCK)
    return (blocks * w.scales.astype(jnp.float32)[..., None]
            ).reshape(vals.shape)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):  # x [T, H, D], half-split
    T, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    rot = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], -1)
    return x * cos + rot * sin


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


def _attention(hf, x, p, window, rope, rnd):
    """GQA over the whole sequence x [T, hid], causal, inside `window`
    where it is not None, with a rope where `rope`."""
    Hq, Hkv, D = (hf["num_attention_heads"], hf["num_key_value_heads"],
                  hf["head_dim"])
    T = x.shape[0]

    def mm(a, b):
        return rnd(a) @ rnd(b)

    q = mm(x, dense(p["wq"]).T).reshape(T, Hq, D)
    k = mm(x, dense(p["wk"]).T).reshape(T, Hkv, D)
    v = mm(x, dense(p["wv"]).T).reshape(T, Hkv, D)
    if rope:
        q, k = _rope(q, hf["rope_theta"]), _rope(k, hf["rope_theta"])
    k, v = (jnp.repeat(a, Hq // Hkv, axis=1) for a in (k, v))
    n_blocks = -(-T // Q_BLOCK)
    pad = n_blocks * Q_BLOCK - T
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        n_blocks, Q_BLOCK, Hq, D).transpose(2, 0, 1, 3)  # [Hq, n, Q, D]
    j = jnp.arange(T)[None]

    def head(xs):
        qh, kh, vh = xs  # [n, Q, D], [T, D], [T, D]

        def block(xs):
            b, qs = xs
            i = (b * Q_BLOCK + jnp.arange(Q_BLOCK))[:, None]
            ok = j <= i
            if window is not None:
                ok &= j > i - window
            s = mm(qs, kh.T) / jnp.sqrt(jnp.float32(D))
            return mm(jax.nn.softmax(jnp.where(ok, s, -jnp.inf), -1), vh)

        return jax.lax.map(block, (jnp.arange(n_blocks), qh))

    out = jax.lax.map(head, (qb, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    out = out.reshape(Hq, n_blocks * Q_BLOCK, D)[:, :T]
    return mm(out.transpose(1, 0, 2).reshape(T, Hq * D), dense(p["wo"]).T)


def _moe(hf, router_in, m, p, chosen, rnd):
    """The expert block over m [T, hid], routed from `router_in` (the
    layer's input), at the program's choice `chosen` [T, k] where that
    choice is admissible (module docstring), this reference's own top-k
    elsewhere. Also: how many of the T decisions the program made otherwise
    than this reference's router would, how far under this reference's k-th
    best the program's worst choice lies, and this reference's own top-k
    [T, k] (what a program on this trajectory would have chosen)."""
    k = hf["moe_num_active_primary_experts"]
    logit = rnd(router_in) @ rnd(dense(p["router"]).T)  # [T, E]
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
        jnp.arange(m.shape[0])[:, None], idx].set(gate)  # [T, E], 0 unrouted

    def one(acc, e):  # one expert at a time
        wg, wu, wd, w_e = e
        g = rnd(m) @ rnd(dense(wg).T)
        u = rnd(m) @ rnd(dense(wu).T)
        y = rnd(jnp.maximum(g, 0.0) * u) @ rnd(dense(wd).T)
        return acc + y * w_e[:, None], None

    out, _ = jax.lax.scan(one, jnp.zeros_like(m), (
        p["w_gate_e"], p["w_up_e"], p["w_down_e"], weight.T))
    return out, jnp.sum(differs), jnp.max(deficit), own


def _stacks(hf, params):
    """The served tree's stacks, one a position of the layouts' period;
    refuses a tree that is not this family's before any arithmetic."""
    if "period" not in params:
        raise KeyError(
            "the parameter tree has no `period`: the program did not build "
            "the smallthinker family (no `router`, `w_gate_e`, `w_up_e`, "
            "`w_down_e` leaves to read); this reference cannot check it")
    stacks = [params["period"][str(j)] for j in range(len(params["period"]))]
    for j, stack in enumerate(stacks):
        for name in ("router", "w_gate_e", "w_up_e", "w_down_e"):
            if name not in stack:
                raise KeyError(f"params['period']['{j}'] has no `{name}`")
    return stacks


def hidden(hf, params, tokens, chosen, rnd=_same):
    """(the last layer's output [T, hidden], the number of decisions in
    which `chosen` [L, T, k] differs from this reference's own top-k on the
    way, the worst deficit of a chosen expert under this reference's k-th
    best, this reference's own top-k along the way [L, T, k]). The layers
    in the model's order: a scan over the periods, the period's layers one
    after another inside it."""
    eps, W = hf["rms_norm_eps"], hf["sliding_window_size"]
    stacks = _stacks(hf, params)
    P = len(stacks)
    windows = hf["sliding_window_layout"][:P]
    ropes = hf["rope_layout"][:P]
    assert list(hf["sliding_window_layout"]) == list(windows) * (
        hf["num_hidden_layers"] // P), "the tree's period is the layouts'"

    def one_period(carry, xs):
        h, n_differ, worst = carry
        ps, cs = xs
        owns = []
        for j in range(P):
            p, x = ps[j], h
            h = x + _attention(
                hf, _rms(x, dense(p["attn_norm"]), eps), p,
                W if windows[j] else None, bool(ropes[j]), rnd)
            y, n, d, own = _moe(hf, x, _rms(h, dense(p["mlp_norm"]), eps),
                                p, cs[j], rnd)
            h, n_differ, worst = h + y, n_differ + n, jnp.maximum(worst, d)
            owns.append(own)
        return (h, n_differ, worst), jnp.stack(owns)

    h = params["embed"][tokens].astype(jnp.float32)
    carry = (h, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32))
    carry, own = jax.lax.scan(
        one_period, carry,
        (tuple(stacks), chosen.reshape(-1, P, *chosen.shape[1:])))
    return (*carry, own.reshape(chosen.shape))


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
    _stacks(hf, params)  # refuse another family's tree by name, first
    L, k = hf["num_hidden_layers"], hf["moe_num_active_primary_experts"]
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
        return _head(h, params["lm_head"], rnd)
