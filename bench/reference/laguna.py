"""Plain float32 reference of the Laguna decoder (`laguna`, e.g.
poolside/Laguna-XS.2: full and window attention layers that differ in query
heads and rope, a sigmoid gate a head, a dense first layer, then
sigmoid-routed experts and a shared one), compared AT the program's expert
choice, each choice held to this reference's own router.

Straightforward `jax.numpy`, no cache, no pages, no kernels, no batching;
its own nibble unpack; `"highest"` matmul precision (`logits` sets it);
nothing of `bigdl_tpu` is imported by this module. The seven steps, with
h [T, hid] the residual stream, l the layer, kind = `layer_types[l]`,
Hq = `num_attention_heads_per_layer[l]`, D = `head_dim`:

    1. x = rmsnorm(h);  q = x W_q as Hq heads;  k, v = x W_k, x W_v as
       `num_key_value_heads` heads
    2. rope, half-split, by `rope_parameters[kind]`: full_attention YaRN
       (HF `_compute_yarn_parameters`: theta, factor, beta_fast, beta_slow,
       original_max_position_embeddings, over the first
       `partial_rotary_factor` of a head, cos / sin times the published
       `attention_factor`, the other lanes untouched); sliding_attention
       the default rope, its own theta, the whole head
    3. s_tj = q_t . k_j / sqrt(D),  j <= t,  a sliding layer also
       j > t - `sliding_window`;  a = softmax(s) v,  Hq / Hkv query heads
       to a KV head
    4. g = sigmoid(x W_g)  [T, Hq]:  a_head *= g_head
    5. h += concat(a) W_o;  y = rmsnorm(h)
    6. `mlp_layer_types[l]` dense:  h += W_d(silu(W_gate y) * (W_up y))
       sparse:  s = sigmoid(y W_r) over all `num_experts`;  I = the
       `num_experts_per_tok` largest;  w = s[I] / sum(s[I]) *
       `moe_routed_scaling_factor`;  h += sum_{e in I} w_e E_e(y) + S(y),
       E_e and S SwiGLU, the shared S ungated at weight 1, the weights on
       the experts' OUTPUT
    7. logits = rmsnorm(h_L) W_head

Departures from the published description, each with its reason:

* What config.json has no key for (the configuration file's `assumed`): the
  gate is PER HEAD (`gating: true`; the sibling Laguna-S-2.1 spells it
  `per-head`); the top-k scores are renormalised (`norm_topk_prob` stands in
  the sibling beside the same scaling factor 2.5); no selection bias and no
  expert groups; no q/k norm; the rope is the half-split (`rotate_half`)
  convention.
* The parameter tree is the served one: `params["first"][str(j)]` are the
  layers of the first period (layer 0's feed-forward is dense), each by
  itself, and layer l of a later period is entry `l // P - 1` of
  `params["period"][str(l % P)]`, P the layouts' period (the program scans
  over those periods, models/laguna.py); this file walks them the same way,
  which IS the model's layer order. A tree without `first` and `period`
  (the parent commit builds a dense llama from this configuration's keys)
  is refused by name before any arithmetic.
* The top-k choice is compared as `bench/reference/glm4_moe_lite.py`
  compares it, for its reason (a top-k is discontinuous: eight of 256
  sigmoid scores lie closer together than bf16 activations can tell). This
  reference takes the expert ids the program chose at every position of the
  sequence being checked (`Request.expert_ids`, the sparse layers only),
  holds every one of them to its OWN router (the chosen expert's score must
  lie within `ROUTER_TIE` of this reference's k-th best at that position,
  on this reference's own hidden state; a choice that fails is not taken),
  counts the decisions in which the program's experts are not this
  reference's own top-k, and takes NONE when they are more than `FLIP_SHARE`
  of the sequence's. The combine weights are this reference's own scores of
  the chosen experts. A program that reports no choice is compared free.
* Attention runs a head and a block of `Q_BLOCK` queries at a time, the
  experts ONE at a time (a float32 layer of 256 experts is 3.2 GB; one
  expert's three matrices are 12.6 MB), the head a block of rows at a time
  and the embedding by gather: 8192 positions then fit in what the engine
  leaves of the chip.

`ROUTER_TIE` and `FLIP_SHARE`: bench/configs/laguna-xs.2-int4.json gives
both readings of each (`scripts/window_check_sweep.py --config
laguna-xs.2-int4`).

`rnd`, where a caller gives it, is applied to BOTH inputs of every matrix
product (the router's and the gate's included): the sweep passes a rounding
to float8_e4m3, and the precision below the served one has to come out not
correct. The benchmark's check never passes it.

How the choices get here: as in `bench/reference/mixtral.py`, through
`serving.engine.last_routed_request`, looked up when called, until the
benchmark's entry hands the request over (PERF.md section 7).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 32  # sym_int4: one float16 scale per 32 weights along K
Q_BLOCK = 1024  # queries of one head whose scores are held at a time
ROUTER_TIE = 0.03  # sigmoid-score units; see the configuration file
FLIP_SHARE = 0.4  # of a sequence's (layer, position) decisions; the same


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


def rope_frequencies(rp: dict, head_dim: int):
    """(inverse frequencies [R / 2], the factor on cos and sin, R the lanes
    of a head the rope turns) of one kind's `rope_parameters` entry."""
    R = int(head_dim * rp.get("partial_rotary_factor", 1.0))
    base = float(rp["rope_theta"])
    freqs = base ** (np.arange(0, R, 2, dtype=np.float64) / R)
    kind = rp.get("rope_type", "default")
    if kind == "default":
        return (1.0 / freqs).astype(np.float32), 1.0, R
    if kind != "yarn":
        raise ValueError(f"reference knows default and yarn ropes, not {kind}")
    factor, orig = rp["factor"], rp["original_max_position_embeddings"]

    def correction(rotations):  # the lane that turns `rotations` times
        return (R * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction(rp.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction(rp.get("beta_slow", 1))), R - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(R // 2) - low) / (high - low), 0, 1)
    inv = (1.0 / (factor * freqs)) * ramp + (1.0 / freqs) * (1 - ramp)
    att = rp.get("attention_factor")
    if att is None:
        att = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0
    return inv.astype(np.float32), float(att), R


def _rope(x, rp):  # x [T, H, D], half-split over the first R lanes
    T, _, D = x.shape
    inv, att, R = rope_frequencies(rp, D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(inv)[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None] * att
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None] * att
    r = x[..., :R]
    rot = jnp.concatenate([-r[..., R // 2:], r[..., :R // 2]], -1)
    return jnp.concatenate([r * cos + rot * sin, x[..., R:]], -1)


def choice_shape(hf: dict) -> tuple:
    """(sparse layers, experts a token): the first and last axis of the
    program's record of its choices (`scripts/window_check_sweep.py`)."""
    L = hf["num_hidden_layers"]
    return (sum(t != "dense" for t in hf["mlp_layer_types"][:L]),
            hf["num_experts_per_tok"])


def _program_choice(tokens, n_layers: int, k: int):
    """[L_sparse, T, k] int32 expert ids the program chose for exactly this
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


def _attention(hf, x, p, l: int, rnd):
    """Steps 1 to 5's `concat(a) W_o` over the whole sequence x [T, hid]
    (already normed), for layer `l`'s kind and head count."""
    kind = hf["layer_types"][l]
    Hq, Hkv, D = (hf["num_attention_heads_per_layer"][l],
                  hf["num_key_value_heads"], hf["head_dim"])
    window = hf["sliding_window"] if kind == "sliding_attention" else None
    rp = hf["rope_parameters"][kind]
    T = x.shape[0]

    def mm(a, b):
        return rnd(a) @ rnd(b)

    q = _rope(mm(x, dense(p["wq"]).T).reshape(T, Hq, D), rp)
    k = _rope(mm(x, dense(p["wk"]).T).reshape(T, Hkv, D), rp)
    v = mm(x, dense(p["wv"]).T).reshape(T, Hkv, D)
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
    out = out.reshape(Hq, n_blocks * Q_BLOCK, D)[:, :T].transpose(1, 0, 2)
    if hf.get("gating"):
        gate = jax.nn.sigmoid(mm(x, dense(p["attn_gate"]).T))  # [T, Hq]
        out = out * gate[..., None]
    return mm(out.reshape(T, Hq * D), dense(p["wo"]).T)


def _swiglu(y, wg, wu, wd, rnd):
    g = rnd(y) @ rnd(dense(wg).T)
    u = rnd(y) @ rnd(dense(wu).T)
    return rnd(jax.nn.silu(g) * u) @ rnd(dense(wd).T)


def _moe(hf, y, p, chosen, rnd):
    """Step 6's sparse form over y [T, hid], at the program's choice
    `chosen` [T, k] where that choice is admissible (module docstring),
    this reference's own top-k elsewhere. Also: how many of the T decisions
    the program made otherwise than this reference's router would, how far
    under this reference's k-th best score the program's worst choice lies,
    and this reference's own top-k [T, k]."""
    k = hf["num_experts_per_tok"]
    score = jax.nn.sigmoid(rnd(y) @ rnd(dense(p["router"]).T))  # [T, E]
    _, own = jax.lax.top_k(score, k)
    kth = jnp.sort(score, axis=-1)[:, -k]
    c = jnp.clip(chosen, 0, score.shape[-1] - 1)
    c_sorted = jnp.sort(c, axis=-1)
    given = jnp.all(chosen >= 0, -1)
    deficit = jnp.where(given, jnp.max(
        kth[:, None] - jnp.take_along_axis(score, c, -1), -1), 0.0)
    ok = (given
          & jnp.all(c_sorted[:, 1:] != c_sorted[:, :-1], -1)  # k experts
          & (deficit <= ROUTER_TIE))
    differs = given & jnp.any(c_sorted != jnp.sort(own, axis=-1), -1)
    idx = jnp.where(ok[:, None], c, own)
    top = jnp.take_along_axis(score, idx, -1)
    top = top / jnp.sum(top, -1, keepdims=True)
    top = top * hf.get("moe_routed_scaling_factor", 1.0)
    weight = jnp.zeros_like(score).at[
        jnp.arange(y.shape[0])[:, None], idx].set(top)  # [T, E], 0 unrouted

    def one(acc, e):  # one expert at a time
        wg, wu, wd, w_e = e
        return acc + _swiglu(y, wg, wu, wd, rnd) * w_e[:, None], None

    out, _ = jax.lax.scan(one, jnp.zeros_like(y), (
        p["w_gate_e"], p["w_up_e"], p["w_down_e"], weight.T))
    if "w_up_s" in p:
        out = out + _swiglu(y, p["w_gate_s"], p["w_up_s"], p["w_down_s"],
                            rnd)
    return out, jnp.sum(differs), jnp.max(deficit), own


def _tree(hf, params):
    """(the first period's layers, the later periods' stacks by position);
    refuses a tree that is not this family's before any arithmetic."""
    for part in ("first", "period"):
        if part not in params:
            raise KeyError(
                f"the parameter tree has no `{part}`: the program did not "
                "build the laguna family (no `attn_gate`, `router`, "
                "`w_gate_e`, `w_up_e`, `w_down_e` leaves to read); this "
                "reference cannot check it")
    P = len(params["period"])
    first = [params["first"][str(j)] for j in range(P)]
    stacks = [params["period"][str(j)] for j in range(P)]
    for j, stack in enumerate(stacks):
        for name in ("router", "w_gate_e", "w_up_e", "w_down_e"):
            if name not in stack:
                raise KeyError(f"params['period']['{j}'] has no `{name}`")
    L = hf["num_hidden_layers"]
    for key in ("layer_types", "num_attention_heads_per_layer",
                "mlp_layer_types"):
        assert list(hf[key][P:L]) == list(hf[key][P:2 * P]) * (L // P - 1), \
            f"the tree's period is `{key}`'s"  # entries past L: not run
    return first, stacks


def hidden(hf, params, tokens, chosen, rnd=_same):
    """(the last layer's output [T, hidden], the number of decisions in
    which `chosen` [L_sparse, T, k] differs from this reference's own top-k
    on the way, the worst deficit of a chosen expert under this reference's
    k-th best, this reference's own top-k along the way [L_sparse, T, k]).
    The layers in the model's order: the first period's one after another,
    then a scan over the later periods, the period's layers one after
    another inside it."""
    eps = hf["rms_norm_eps"]
    first, stacks = _tree(hf, params)
    P = len(stacks)

    def layer(l, h, p, c):
        """Layer `l` (any layer of its position in the period)."""
        x = _rms(h, dense(p["attn_norm"]), eps)
        h = h + _attention(hf, x, p, l, rnd)
        y = _rms(h, dense(p["mlp_norm"]), eps)
        if hf["mlp_layer_types"][l] == "dense":
            return h + _swiglu(y, p["w_gate"], p["w_up"], p["w_down"],
                               rnd), None
        out, n, d, own = _moe(hf, y, p, c, rnd)
        return h + out, (n, d, own)

    h = params["embed"][tokens].astype(jnp.float32)
    n_differ = jnp.zeros((), jnp.int32)
    worst = jnp.zeros((), jnp.float32)
    owns, s = [], 0
    for j in range(P):
        sparse = hf["mlp_layer_types"][j] != "dense"
        h, told = layer(j, h, first[j], chosen[s] if sparse else None)
        if told is not None:
            n_differ, worst = n_differ + told[0], jnp.maximum(worst, told[1])
            owns.append(told[2])
            s += 1

    def one_period(carry, xs):
        h, n_differ, worst = carry
        ps, cs = xs
        own = []
        for j in range(P):
            h, (n, d, o) = layer(P + j, h, ps[j], cs[j])
            n_differ, worst = n_differ + n, jnp.maximum(worst, d)
            own.append(o)
        return (h, n_differ, worst), jnp.stack(own)

    rest = chosen[s:]
    carry, own = jax.lax.scan(
        one_period, (h, n_differ, worst),
        (tuple(stacks), rest.reshape(-1, P, *rest.shape[1:])))
    own = jnp.concatenate(
        [o[None] for o in owns] + [own.reshape(rest.shape)])
    return (*carry, own)


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
    _tree(hf, params)  # refuse another family's tree by name, first
    L, k = choice_shape(hf)
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
