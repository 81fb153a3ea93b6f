"""Plain float32 reference of SDAR-MoE (JetLM, SDAR-30B-A3B-Chat): Qwen3-MoE's
network, generated by DIFFUSION OVER BLOCKS.

Straightforward `jax.numpy`: no cache, no kernels, no batching, a full
forward over the whole sequence for every pass; nothing imported from the
program but the parameter tree it is handed (the nibble unpack, RMSNorm and
rotary embedding are `bench/reference/mistral.py`'s). Everything runs under
`jax.default_matmul_precision("highest")`.

One layer, hidden h [T, H], eps 1e-6, no biases:

    x = rms(h) w_in;  q = x W_q (Hq heads of D), k = x W_k, v = x W_v (Hkv)
    q = rms_D(q) w_qn, k = rms_D(k) w_kn      (over each head's D lanes)
    rope on q and k: theta, all D lanes, HF's half-split, position = the
                     token's absolute index
    a = softmax(q k^T / sqrt(D) + M) v        (Hq / Hkv query heads a KV head)
    h = h + a W_o;  x = rms(h) w_post
    p = softmax(x W_r) over E experts (float32), the k largest, divided by
        their sum;  y = sum_e p_e W_down,e (silu(W_gate,e x) * W_up,e x)
    h = h + y

then rms(h) w_f and the untied head. THE LOGITS AT POSITION i SCORE THE TOKEN
AT POSITION i (a masked position predicts itself: no shift by one). The mask
M, block length b: key j is visible to query i iff j // b <= i // b.

Generation (`generate`), prompt of P tokens, n new: blocks 0 .. P // b - 1
are the prompt's whole blocks; block P // b starts as the prompt's last P % b
tokens followed by `mask_token_id`, every later one as b masks. For a block,
passes s = 0, 1, ..: a forward over everything; at each still-masked
position x0 = argmax of the logits and c = softmax(logits)[x0]; reveal

    "sequential"              the leftmost masked position
    "low_confidence_static"   the m_s masked positions of largest c,
                              m_s = b // steps + 1 in the first b % steps
                              passes of a block
    "low_confidence_dynamic"  every masked position with c > threshold, and
                              the m_s largest where fewer pass it

and go on to the next block when none is masked. A token's logprob is log c
of the pass that revealed it. The request's tokens are positions
P .. P + n - 1; what the last block holds past them is dropped.

Every choice above that the source's config.json does not make (b, the
strategy's names and rules, no logit shift, HF's rope convention, that a
stored block's K and V come from its FINAL ids) is written from memory of
the source's `generate.py` (`block_diffusion_generate`) and listed under
`assumed` in `bench/configs/sdar-30b-a3b-int4.json`: there is no copy of it
here to hold them to.

Departures from that description: none in the mathematics. The source
stores a finished block by one more forward over its final ids; a
reference without a cache recomputes everything in every pass, which is the
same numbers. The parameter tree is the served one (q/k/v arrive fused as
`wqkv`).

`logits(hf, params, seq, n_last)` is what `bench/entries/engine.py:check`
calls, with `seq` = prompt + the request's tokens but the last. A causal
model's check is one forward over `seq`; here token i was revealed by SOME
pass of its block, from the block's ids as they stood then, so the check
REPLAYS the request's passes: for each pass that revealed one of the
`n_last` tokens, one forward over the final ids of everything before the
block followed by the block's input ids of that pass, and row i is that
pass's logits at the token's position: `row[tok_i] - logsumexp(row)` is the
reference's log c. Three discrete choices are taken from the program, as
`mixtral.py` takes the experts, and each is HELD to this reference's own
numbers:

* the experts of every position (prompt, stored blocks, the pass's own):
  each chosen expert's float32 router logit within `ROUTER_TIE` of the
  reference's k-th best there, else the reference keeps its own; more than
  `FLIP_SHARE` of a forward's decisions departing and the record is not a
  rounding of this network's: every row returned is uniform, and the
  comparison fails on the distance (comparing such a record free, on the
  reference's own experts, does NOT always fail: a float8 trajectory read
  0.043 nats at its worst token on one seed of 17 where a sound program
  reads up to 0.037, while its router departed in 72% of decisions);
* WHICH positions a pass revealed: the reference's log-confidence of the
  revealed token must lie within `REVEAL_TIE` of the reference's own
  m_s-th best masked position of that pass (or of log threshold, for the
  dynamic strategy; the leftmost masked exactly, for the sequential), and
  HOW MANY it revealed must be the rule's count exactly (a number no
  near-tie blurs: under random weights it is what tells one rule from
  another), else the row returned is uniform (the reference has no such
  reveal to score), and the distance shows it;
* the revealed id, which `check` compares as it stands.

A program that reports no passes (`Request.passes` empty: the parent commit,
or a causal decode of this model) is compared as a causal model would be
(position i's logits under the block mask for the token at i + 1), and
fails, which is right.

How the request gets here: as `mixtral.py` finds it
(`serving.engine.last_routed_request`), taken only if its tokens are the
ones being checked. `rnd`, where a caller gives it, rounds both inputs of
every matrix product (`scripts/block_check_sweep.py`: float8_e4m3, the
precision below the served one, has to come out not correct).

The three ties lie between two readings on the chip each
(`scripts/block_check_sweep.py`; PERF.md section 6, PR 50), written beside
the constants.
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

# The three ties, with their readings on the chip
# (`scripts/block_check_sweep.py`, PR 50, 34 seeds 2147485301..317 and
# ..401..417 at the cell's sizes: 24 layers, published widths, the check's
# 250-token prompt and 9 tokens, 5 replayed passes a seed, the program and a
# float8 trajectory in its place both through `Driver.check`; PERF.md
# section 6 has the table):
#
# logits. Router logits have a standard deviation of about 0.9 here (a
# 0.02 * N(0, 1) router over 2048 unit-rms inputs). The program's chosen
# experts lie at most 0.025 to 0.041 under the reference's k-th best; the
# choices of a float8 trajectory (both inputs of every matrix product at
# e4m3, the precision below) 1.4 to 3.9 under.
ROUTER_TIE = 0.1
# of one forward's (layer, position) decisions. The program departs from the
# reference's own top-8 in 5.7 to 8.2% of them (top-8 of 128 has eight
# boundaries a token where Mixtral's top-2 of 8 has two), the float8
# trajectory in 60 to 74%: the limit that tells the precisions apart on
# every seed, so breaking it decides the check (`forward_many`).
FLIP_SHARE = 0.2
# nats. Under seeded random weights the masked positions' log-confidences lie
# within a few hundredths of each other (the largest of 151936 probabilities
# is about 1e-4), so the m_s-th best and the next are near-ties in most
# passes: the program's revealed positions lie 0 to 0.028 under the
# reference's m_s-th best, a float8 trajectory's own reveal 0 to 0.28 (under
# this tie on 14 seeds of 17, over it on 3), and a pass that reveals the
# leftmost mask where the most confident was asked lies 0.025 under on a
# tiny model: under random weights this tie has NO reliable upper reading.
# What it catches is a position far down the order of a model whose
# confidences differ (tests/test_sdar.py plants one on a sharpened head).
# Under random weights a wrong RULE is caught by the count of positions a
# pass reveals, which `admissible` holds exactly; `FLIP_SHARE` tells the
# precisions apart.
REVEAL_TIE = 0.08


def _same(x):
    return x


def block_length(hf: dict) -> int:
    return int(hf.get("block_length", 4))


def _visible(T: int, b: int):
    """[T, T] bool: key j is visible to query i iff j // b <= i // b."""
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None]
    return j // b <= i // b


def _attention(q, k, v, visible, rnd):  # q [T, Hq, D]; k, v [T, Hkv, D]
    T, Hq, D = q.shape
    g = Hq // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", rnd(q), rnd(k)) / jnp.sqrt(jnp.float32(D))
    p = jax.nn.softmax(jnp.where(visible[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", rnd(p), rnd(v)).reshape(T, Hq * D)


def _moe(hf, x, p, chosen, rnd):
    """Softmax over all experts in float32 -> top-k -> renormalise -> the
    weighted sum of the chosen experts' SwiGLU, at the program's choice
    `chosen` [T, k] where it is admissible (module docstring), the
    reference's own top-k elsewhere. Also how many of the T decisions the
    program made otherwise than this router would, the deepest any given
    choice lies under the k-th best, and this router's own top-k [T, k]."""
    k = hf["num_experts_per_tok"]
    logits = x @ dense(p["router"]).T  # [T, E] float32: the router is dense
    probs = jax.nn.softmax(logits, axis=-1)
    _, own = jax.lax.top_k(probs, k)
    kth = jnp.sort(logits, axis=-1)[:, -k]
    c = jnp.clip(chosen, 0, logits.shape[-1] - 1)
    c_sorted = jnp.sort(c, axis=-1)
    given = jnp.all(chosen >= 0, -1)
    under = kth[:, None] - jnp.take_along_axis(logits, c, -1)
    ok = (given & jnp.all(c_sorted[:, 1:] != c_sorted[:, :-1], -1)
          & jnp.all(under <= ROUTER_TIE, -1))
    differs = given & jnp.any(c_sorted != jnp.sort(own, axis=-1), -1)
    idx = jnp.where(ok[:, None], c, own)
    top = jnp.take_along_axis(probs, idx, -1)
    top = top / jnp.sum(top, -1, keepdims=True)
    weight = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], idx].set(top)  # [T, E], 0 unrouted

    def one(acc, e):  # one expert at a time
        wg, wu, wd, w_e = e
        y = (jax.nn.silu(rnd(x) @ rnd(dense(wg)).T)
             * (rnd(x) @ rnd(dense(wu)).T))
        return acc + (rnd(y) @ rnd(dense(wd)).T) * w_e[:, None], None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["w_gate_e"], p["w_up_e"], p["w_down_e"], weight.T))
    deepest = jnp.max(jnp.where(given[:, None], under, -jnp.inf))
    return out, jnp.sum(differs), deepest, own


def _layer(hf, carry, p, chosen, visible, rnd):
    h, n_differ, deepest = carry
    Hq, Hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    D = hf.get("head_dim") or hf["hidden_size"] // Hq
    T, eps = h.shape[0], hf["rms_norm_eps"]
    x = _rms(h, dense(p["attn_norm"]), eps)
    qkv = rnd(x) @ rnd(dense(p["wqkv"])).T
    q = qkv[:, :Hq * D].reshape(T, Hq, D)
    k = qkv[:, Hq * D:(Hq + Hkv) * D].reshape(T, Hkv, D)
    v = qkv[:, (Hq + Hkv) * D:].reshape(T, Hkv, D)
    q = _rope(_rms(q, dense(p["q_norm"]), eps), hf["rope_theta"])
    k = _rope(_rms(k, dense(p["k_norm"]), eps), hf["rope_theta"])
    a = _attention(q, k, v, visible, rnd)
    h = h + rnd(a) @ rnd(dense(p["wo"])).T
    y, n, d, own = _moe(hf, _rms(h, dense(p["mlp_norm"]), eps), p, chosen,
                        rnd)
    return (h + y, n_differ + n, jnp.maximum(deepest, d)), own


def _head(h, w, rnd=_same, block: int = 1 << 14):
    """h @ w^T with the head's rows taken `block` at a time: the whole head
    in float32 is 1.2 GB, beside a model that fills the chip."""
    V = w.data.shape[0] if hasattr(w, "qtype") else w.shape[0]
    out = []
    for lo in range(0, V, block):
        rows = jax.tree.map(lambda a: a[lo:lo + block], w)
        out.append(rnd(h) @ rnd(dense(rows)).T)
    return jnp.concatenate(out, axis=-1)


def hidden_many(hf, params, ids, chosen=None, rnd=_same, visible=None):
    """`hidden` of R sequences `ids` [R, T] side by side (each alone under
    the block mask; nothing is shared between them but the weights, which
    the layer loop then slices once for all): (h [R, T, H], decisions that
    differ [R], the deepest given choice [R], own top-k [L, R, T, k])."""
    R, T = ids.shape
    L, k = hf["num_hidden_layers"], hf["num_experts_per_tok"]
    if chosen is None:
        chosen = jnp.full((R, L, T, k), -1, jnp.int32)
    if visible is None:
        visible = _visible(T, block_length(hf))
    h = params["embed"][ids].astype(jnp.float32)

    def body(carry, xs):
        p, ch = xs  # ch [R, T, k]
        return jax.vmap(
            lambda h_r, n_r, d_r, c_r: _layer(
                hf, (h_r, n_r, d_r), p, c_r, visible, rnd))(*carry, ch)

    (h, n_differ, deepest), own = jax.lax.scan(
        body, (h, jnp.zeros((R,), jnp.int32),
               jnp.full((R,), -jnp.inf, jnp.float32)),
        (params["layers"], jnp.swapaxes(chosen, 0, 1)))
    return h, n_differ, deepest, own


def hidden(hf, params, ids, chosen=None, rnd=_same, visible=None,
           with_own: bool = False):
    """(the last layer's output [T, H] of `ids` [T] under the block mask,
    the decisions in which `chosen` [L, T, k] differs from this router's
    own top-k, how far under the k-th best the deepest given choice lies;
    `with_own`: and this router's own top-k [L, T, k]). `chosen` None or
    -1: the reference's own experts."""
    h, n_differ, deepest, own = hidden_many(
        hf, params, ids[None], None if chosen is None else chosen[None], rnd,
        visible)
    return (h[0], n_differ[0], deepest[0]) + (
        (own[:, 0],) if with_own else ())


def forward_many(hf, params, ids, rows, n_rows: int, chosen=None, rnd=_same,
                 visible=None):
    """float32 logits [R, n_rows, V] at positions `rows[r]` .. of each of the
    sequences `ids` [R, T]; where the given experts of any of them depart
    from this router's own in more than `FLIP_SHARE` of its decisions, every
    row is uniform (module docstring). Also (decisions that differ [R], the
    deepest choice [R])."""
    with jax.default_matmul_precision("highest"):
        h, n_differ, deepest, _ = hidden_many(hf, params, ids, chosen, rnd,
                                              visible)
        h = jax.vmap(lambda h_r, at: jax.lax.dynamic_slice_in_dim(
            h_r, at, n_rows, axis=0))(h, rows)
        h = _rms(h, dense(params["final_norm"]), hf["rms_norm_eps"])
        R = ids.shape[0]
        out = _head(h.reshape(R * n_rows, -1), params["lm_head"], rnd)
        sound = jnp.all(
            n_differ <= FLIP_SHARE * hf["num_hidden_layers"] * ids.shape[1])
        return (jnp.where(sound, out, 0.0).reshape(R, n_rows, -1), n_differ,
                deepest)


def forward(hf, params, ids, rows, n_rows: int, chosen=None, rnd=_same,
            visible=None):
    """`forward_many` of one sequence `ids` [T]: (logits [n_rows, V],
    decisions that differ, the deepest choice)."""
    lg, n_differ, deepest = forward_many(
        hf, params, ids[None], jnp.asarray(rows)[None], n_rows,
        None if chosen is None else chosen[None], rnd, visible)
    return lg[0], n_differ[0], deepest[0]


# ---------------------------------------------------------------------------
# generation: the loop, one full forward a pass
# ---------------------------------------------------------------------------

def reveal_count(b: int, steps: int, n_pass: int) -> int:
    """m_s of pass `n_pass` of a block."""
    steps = min(max(int(steps), 1), b)
    return max(b // steps + (n_pass < b % steps), 1)


def choose(conf, masked, strategy: str, m_s: int, threshold: float):
    """Which masked positions a pass reveals, from their log-confidences
    `conf` [b] (numpy): [b] bool."""
    at = np.nonzero(masked)[0]
    out = np.zeros_like(masked)
    if strategy == "sequential":
        out[at[:1]] = True
        return out
    # the m_s largest, the leftmost first of equals
    best = at[np.argsort(-conf[at], kind="stable")[:m_s]]
    if strategy == "low_confidence_dynamic":
        passing = at[conf[at] > np.log(threshold)]
        if len(passing) >= m_s:
            best = passing
    out[best] = True
    return out


def generate(hf: dict, params, prompt, n: int, rnd=_same, fwd=None):
    """The block loop of the module docstring, greedy, by `hf`'s strategy,
    steps and threshold (the family's defaults where it has none; `fwd`:
    another forward in this one's place). Returns (tokens [n],
    logprobs [n], passes): a pass is {"block", "pass", "base", "ids" [b],
    "masked" [b], "revealed" [b], "stored": False, "experts": None}, the
    record `Request.passes` keeps, and after a block's last pass one more
    with "stored": True (what the program's store pass reports)."""
    b = block_length(hf)
    strategy = hf.get("remasking_strategy", "low_confidence_dynamic")
    steps = hf.get("denoising_steps", b)
    threshold = hf.get("confidence_threshold", 0.9)
    mask_id = hf["mask_token_id"]
    P = len(prompt)
    total = -(-(P + n) // b) * b
    ids = np.full((total,), mask_id, np.int32)
    ids[:P] = prompt
    masked = np.arange(total) >= P
    lps = np.zeros((total,), np.float64)
    if fwd is None:
        fwd = jax.jit(lambda p, i, r: forward(hf, p, i, r, b, rnd=rnd)[0])
    passes = []
    for blk in range(P // b, total // b):
        base, n_pass = blk * b, 0
        while masked[base:base + b].any():
            logits = np.asarray(fwd(params, jnp.asarray(ids), base),
                                np.float64)
            x0 = logits.argmax(-1)
            lse = np.log(np.exp(logits - logits.max(-1, keepdims=True))
                         .sum(-1)) + logits.max(-1)
            conf = logits[np.arange(b), x0] - lse
            m = masked[base:base + b].copy()
            shown = choose(conf, m, strategy, reveal_count(b, steps, n_pass),
                           threshold)
            passes.append({"block": blk, "pass": n_pass, "base": base,
                           "ids": ids[base:base + b].copy(), "masked": m,
                           "revealed": shown, "stored": False,
                           "experts": None})
            ids[base:base + b][shown] = x0[shown]
            lps[base:base + b][shown] = conf[shown]
            masked[base:base + b][shown] = False
            n_pass += 1
        passes.append({"block": blk, "pass": n_pass, "base": base,
                       "ids": ids[base:base + b].copy(),
                       "masked": np.zeros((b,), bool),
                       "revealed": np.zeros((b,), bool), "stored": True,
                       "experts": None})
    return ids[P:P + n].tolist(), lps[P:P + n].tolist(), passes


# ---------------------------------------------------------------------------
# the check: replay the request's passes
# ---------------------------------------------------------------------------

def _empty_plan(n_last: int, b: int, L: int, T: int, k: int) -> dict:
    z = lambda *shape: np.zeros(shape, np.int32)  # noqa: E731
    return {"n": z(1), "base": z(n_last), "ids": z(n_last, b),
            "masked": z(n_last, b), "revealed": z(n_last, b),
            "n_pass": z(n_last), "replay_of": z(n_last),
            "chosen": np.full((n_last, L, T, k), -1, np.int32)}


def replay_plan(passes: list, n_total: int, n_last: int, b: int, L: int,
                k: int, experts_before=None):
    """From a request's record of passes, what `logits` replays for the
    tokens at positions n_total - n_last + 1 .. n_total (`seq` holds the
    positions before the last of them): one row a token, padded to
    `n_last` replays. Returns int32 arrays
    (n_replays [1], base [n], ids [n, b], masked [n, b], revealed [n, b],
    n_pass [n], which replay a token is in [n], chosen [n, L, T, k]) with
    T = the sequence as replayed, `n_total + 1` rounded up to whole blocks;
    None where the record does not cover the tokens."""
    T = -(-(n_total + 1) // b) * b
    first = n_total + 1 - n_last
    found = {}  # position -> index of the pass that revealed it
    for i, p in enumerate(passes):
        for j in np.nonzero(p["revealed"])[0]:
            found.setdefault(p["base"] + int(j), i)
    if any(pos not in found for pos in range(first, n_total + 1)):
        return None
    order = sorted({found[pos] for pos in range(first, n_total + 1)})
    out = _empty_plan(n_last, b, L, T, k)
    out["n"][0] = len(order)
    out["replay_of"][:] = [order.index(found[pos])
                           for pos in range(first, n_total + 1)]
    for r, i in enumerate(order):
        p = passes[i]
        out["base"][r], out["n_pass"][r] = p["base"], p["pass"]
        out["ids"][r] = p["ids"]
        out["masked"][r] = p["masked"]
        out["revealed"][r] = p["revealed"]
        if experts_before is not None:
            e = experts_before(p["base"])  # [L, base, k] or None
            if e is not None:
                out["chosen"][r, :, :p["base"]] = e
        if p.get("experts") is not None:
            out["chosen"][r, :, p["base"]:p["base"] + b] = p["experts"]
    return out


def _program_plan(tokens, n_last: int, b: int, L: int, k: int):
    """`replay_plan` of the request whose tokens are being checked, found as
    `mixtral.py` finds it; zeros with n = 0 where there is none."""
    tokens = np.asarray(tokens).tolist()
    n_total = len(tokens)
    plan = None
    try:
        from bigdl_tpu.serving.engine import last_routed_request

        req = last_routed_request()
        if (req is not None and getattr(req, "passes", None)
                and (req.prompt + req.out_tokens)[:n_total] == tokens):
            plan = replay_plan(req.passes, n_total, n_last, b, L, k,
                               req.expert_ids)
    except (ImportError, AttributeError):
        pass
    if plan is None:
        plan = _empty_plan(n_last, b, L, -(-(n_total + 1) // b) * b, k)
    return tuple(plan[f] for f in PLAN_FIELDS)


PLAN_FIELDS = ("n", "base", "ids", "masked", "revealed", "n_pass",
               "replay_of", "chosen")


def admissible(hf, conf_own, conf_tok, masked, revealed, n_pass):
    """Is each revealed position of one pass one this reference could have
    revealed? `conf_own` [b] its log-confidence at each position (of its own
    argmax), `conf_tok` [b] its logprob of the id the block holds AFTER the
    pass (the program's revealed id). [b] bool, True where not revealed.
    HOW MANY a pass reveals is held exactly, whatever the confidences (one
    for the sequential strategy, the m_s of the static, at least m_s for the
    dynamic, never more than are masked): near-ties cannot excuse a pass
    that followed another rule, and a pass with the wrong count has no
    admissible position."""
    b = conf_own.shape[0]
    strategy = hf.get("remasking_strategy", "low_confidence_dynamic")
    steps = min(max(int(hf.get("denoising_steps", b)), 1), b)
    masked, revealed = masked > 0, revealed > 0
    n_masked, n_shown = jnp.sum(masked), jnp.sum(revealed)
    if strategy == "sequential":
        first = jnp.argmax(masked)
        return ~revealed | ((jnp.arange(b) == first) & (n_shown == 1))
    m_s = jnp.minimum(
        jnp.maximum(b // steps + (n_pass < b % steps), 1), n_masked)
    ranked = jnp.sort(jnp.where(masked, conf_own, -jnp.inf))[::-1]
    kth = ranked[m_s - 1]
    ok = conf_tok >= kth - REVEAL_TIE
    count = n_shown == m_s
    if strategy == "low_confidence_dynamic":
        ok |= conf_tok >= jnp.log(hf.get("confidence_threshold", 0.9)) \
            - REVEAL_TIE
        count = n_shown >= m_s
    return ~revealed | (ok & count)


def replay(hf: dict, params, tokens, n_last: int, plan, rnd=_same):
    """Rows [n_last, V] of `logits` from a replay plan (arrays in
    `PLAN_FIELDS` order), and per replay (decisions that differ, the
    deepest taken expert, the worst shortfall of a revealed position under
    the reference's own m_s-th best). The plan's replays run side by side
    in ONE forward (`forward_many`: the unused ones of its `n_last` rows
    run on zeros and are read by nobody), so the layer loop slices the
    weights once."""
    b = block_length(hf)
    n, base, ids, masked, revealed, n_pass, replay_of, chosen = plan
    n_total = tokens.shape[0]
    T = chosen.shape[2]
    mask_id = hf["mask_token_id"]
    seq = jnp.concatenate([tokens.astype(jnp.int32), jnp.full(
        (T - n_total,), mask_id, jnp.int32)])
    at = jnp.arange(T)

    def inputs(base_r, ids_r):
        # final ids before the block, the pass's own in it, masks after
        own = jnp.zeros((T,), jnp.int32).at[
            jnp.clip(base_r + jnp.arange(b), 0, T - 1)].set(ids_r)
        inside = (at >= base_r) & (at < base_r + b)
        return jnp.where(inside, own, jnp.where(at < base_r, seq, mask_id))

    # no record of passes (`n` 0): what a causal model's check computes,
    # position i's logits for the token at i + 1, as `n_last` forwards over
    # the sequence itself, row r from the position before token r's (one
    # program for both cases: two branches over the same weights made the
    # compiler re-lay every expert stack for one of them, 6.75 GB)
    causal = n[0] == 0
    pos = n_total + 1 - n_last + jnp.arange(n_last)
    base = jnp.where(causal, pos - 1, base)
    x = jnp.where(causal, seq[None], jax.vmap(inputs)(base, ids))
    lg, n_differ, deepest = forward_many(hf, params, x, base, b, chosen, rnd)

    def judge(lg_r, base_r, masked_r, revealed_r, n_pass_r):
        lse = jax.scipy.special.logsumexp(lg_r, axis=-1)
        conf_own = jnp.max(lg_r, axis=-1) - lse
        # the ids the block holds after the pass: the sequence's, where it
        # reaches (the last token's own position lies one past it)
        after = jax.lax.dynamic_slice_in_dim(seq, base_r, b)
        conf_tok = jnp.take_along_axis(lg_r, after[:, None], -1)[:, 0] - lse
        ok = admissible(hf, conf_own, conf_tok, masked_r, revealed_r,
                        n_pass_r)
        # the last token is not in `tokens`: its id is what `check`
        # compares, so its position is admitted on its own confidence
        known = base_r + jnp.arange(b) < n_total
        ok = ok | ~known
        # a position this reference would not have revealed in this pass
        # has no log-confidence of its: the row is uniform (log c = -log V,
        # under every confidence there is), and the distance shows it
        short = jnp.max(jnp.where(
            (revealed_r > 0) & known,
            _shortfall(hf, conf_own, conf_tok, masked_r, n_pass_r), -jnp.inf))
        return jnp.where(ok[:, None], lg_r, 0.0), short

    rows, short = jax.vmap(judge)(lg, base, masked, revealed, n_pass)
    stats = jnp.stack([n_differ.astype(jnp.float32), deepest, short], axis=1)
    replay_of = jnp.where(causal, jnp.arange(n_last), replay_of)
    return rows[replay_of, pos - base[replay_of] - causal], stats


def _shortfall(hf, conf_own, conf_tok, masked, n_pass):
    """How far under this reference's m_s-th best masked log-confidence
    each position's revealed id lies (negative: above it)."""
    b = conf_own.shape[0]
    steps = min(max(int(hf.get("denoising_steps", b)), 1), b)
    m_s = jnp.maximum(b // steps + (n_pass < b % steps), 1)
    ranked = jnp.sort(jnp.where(masked > 0, conf_own, -jnp.inf))[::-1]
    kth = ranked[jnp.minimum(m_s, jnp.sum(masked > 0)) - 1]
    return kth - conf_tok


def logits(hf: dict, params, tokens, n_last: int, rnd=_same):
    """float32 logits [n_last, V], row i the reference's for the token at
    position len(tokens) + 1 - n_last + i of the request being checked, from
    the pass that revealed it (module docstring); `hf` holds the published
    config keys and the assumed ones."""
    b = block_length(hf)
    L, k = hf["num_hidden_layers"], hf["num_experts_per_tok"]
    n_total = tokens.shape[0]
    T = -(-(n_total + 1) // b) * b
    shapes = (jax.ShapeDtypeStruct((1,), jnp.int32),
              jax.ShapeDtypeStruct((n_last,), jnp.int32),
              jax.ShapeDtypeStruct((n_last, b), jnp.int32),
              jax.ShapeDtypeStruct((n_last, b), jnp.int32),
              jax.ShapeDtypeStruct((n_last, b), jnp.int32),
              jax.ShapeDtypeStruct((n_last,), jnp.int32),
              jax.ShapeDtypeStruct((n_last,), jnp.int32),
              jax.ShapeDtypeStruct((n_last, L, T, k), jnp.int32))
    plan = jax.pure_callback(
        lambda t: _program_plan(t, n_last, b, L, k), shapes, tokens)
    return replay(hf, params, tokens, n_last, plan, rnd)[0]
