"""Plain float32 reference of the Mixtral decoder, compared AT the program's
expert choice, each choice held to this reference's own router.

The mathematics is `bench/reference/mistral.py`'s, whose pieces it uses
(RMSNorm, rotary embeddings, grouped-query attention, SwiGLU, its own nibble
unpack): softmax over all experts in float32 -> top-k -> renormalise ->
weighted sum of the chosen experts' SwiGLU. No kernels, no cache, no
batching, `"highest"` matmul precision.

One departure, and why. A top-k choice is discontinuous: wherever a token's
k-th and (k+1)-th router logits lie nearer than bf16 activations can tell,
a correct bf16 program and a float32 reference pick different experts, and
from there on they are two different networks. On the chip, at published
widths and ten layers of random weights, the free comparison reads 0.26 to
0.60 nats at the median and 5.2 to 7.1 at the worst token, for the parent's
dense dispatch and for the grouped kernel alike, while every stage of one
block on the SAME input and choice sits 0.005 from float32 (PERF.md section
6, PR 26). No tolerance separates a correct program from a wrong one there.
So this reference takes, besides the parameter tree, ONE thing from the
program: the expert ids it chose at every position of the sequence being
checked (`Request.expert_ids`, the serving counterpart of HF's
`output_router_logits`). It does not take them on trust:

* each choice is held to the reference's own router: every chosen expert's
  float32 logit must lie within `ROUTER_TIE` of the reference's k-th best
  at that position (on the reference's own hidden state). A choice that
  fails is NOT taken; the reference keeps its own there, and the distance
  then shows it, as it does for a program that routes wrong;
* the decisions (a layer at a position) in which the program's experts are
  not the reference's own top-k are counted, and when they are more than
  `FLIP_SHARE` of the sequence's, NONE is taken: near-ties are rare, and a
  router that is a little wrong everywhere is not a near-tie;
* the combine weights are the reference's own softmax of the chosen
  experts, renormalised; nothing else of the program is read;
* a program that does not report its choices (the parent commit) is
  compared free, as `mistral.py` compares.

Both limits from two readings on the chip (`scripts/moe_stage_check.py`,
PERF.md section 6, PR 26: 21 seeds of 2500 decisions each; router logits
have a standard deviation of about 1.3 there). `ROUTER_TIE` = 0.4 logits:
the program's choices lie at most 0.18 to 0.33 under the reference's k-th
best, 3 of 52,500 over 0.3 and none over 0.4 (at 0.3 a right program would
lose a choice at one of a check's 90 last decisions about once in 200
checks); a router on a float8 trajectory (every matmul input of the
reference at e4m3, the precision below) lies up to 1.67 under, 180 to 228 of
its 2500 choices over 0.4. `FLIP_SHARE` = 0.08: the program departs in 103
to 135 of 2500 decisions (4.1 to 5.4%), the float8 trajectory in 634 to 709
(25 to 28%), so it is refused whole and fails on the free distance.

How the choices get here. `bench/entries/engine.py:check` hands a reference
(config, parameters, tokens) and not the request it checked, and is not
this PR's to edit. Until a `benchmark` PR makes it hand the request over,
this file finds it through `serving.engine.last_routed_request` (a weak
reference to the newest finished request), and takes it only if its tokens
are the ones being checked (PERF.md section 7).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import mistral as base

ROUTER_TIE = 0.4  # logits; see the docstring
FLIP_SHARE = 0.08  # of a sequence's (layer, position) decisions; the same


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


def _moe(hf, x, p, chosen):
    """`mistral._moe` at the program's choice `chosen` [T, k] where that
    choice is admissible (see the module docstring), the reference's own
    top-k elsewhere. Also how many of the T decisions the program made
    otherwise than the reference's own router would."""
    k = hf["num_experts_per_tok"]
    logits = x @ base.dense(p["router"]).T  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    _, own = jax.lax.top_k(probs, k)
    kth = jnp.sort(logits, axis=-1)[:, -k]
    c = jnp.clip(chosen, 0, logits.shape[-1] - 1)
    c_sorted = jnp.sort(c, axis=-1)
    given = jnp.all(chosen >= 0, -1)
    ok = (given
          & jnp.all(c_sorted[:, 1:] != c_sorted[:, :-1], -1)  # k experts
          & jnp.all(jnp.take_along_axis(logits, c, -1)
                    >= kth[:, None] - ROUTER_TIE, -1))
    differs = given & jnp.any(c_sorted != jnp.sort(own, axis=-1), -1)
    idx = jnp.where(ok[:, None], c, own)
    top = jnp.take_along_axis(probs, idx, -1)
    top = top / jnp.sum(top, -1, keepdims=True)
    weight = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], idx].set(top)  # [T, E], 0 unrouted

    def one(acc, e):  # one expert at a time: 0.7 GB of float32 weights
        wg, wu, wd, w_e = e
        y = base._swiglu(x, base.dense(wg), base.dense(wu), base.dense(wd))
        return acc + y * w_e[:, None], None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["w_gate_e"], p["w_up_e"], p["w_down_e"], weight.T))
    return out, jnp.sum(differs)


def _attn_half(hf, h, p):
    """`mistral._layer` up to the feed-forward block: (the residual after
    attention, the normed input of the MoE block)."""
    Hq, Hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    D = hf.get("head_dim") or hf["hidden_size"] // Hq
    T, eps = h.shape[0], hf["rms_norm_eps"]
    x = base._rms(h, base.dense(p["attn_norm"]), eps)
    qkv = x @ base.dense(p["wqkv"]).T
    q = qkv[:, :Hq * D].reshape(T, Hq, D)
    k = qkv[:, Hq * D:(Hq + Hkv) * D].reshape(T, Hkv, D)
    v = qkv[:, (Hq + Hkv) * D:].reshape(T, Hkv, D)
    q, k = base._rope(q, hf["rope_theta"]), base._rope(k, hf["rope_theta"])
    h = h + base._attention(hf, q, k, v) @ base.dense(p["wo"]).T
    return h, base._rms(h, base.dense(p["mlp_norm"]), eps)


def _layer(hf, carry, p, chosen):
    h, n_differ = carry
    h, x = _attn_half(hf, h, p)
    y, n = _moe(hf, x, p, chosen)
    return h + y, n_differ + n


def _hidden(hf, params, tokens, chosen):
    """(the last layer's output [T, H], the number of decisions in which
    `chosen` differs from the reference's own top-k along the way)."""
    h = base.dense(params["embed"])[tokens]
    (h, n_differ), _ = jax.lax.scan(
        lambda c, xs: (_layer(hf, c, *xs), None),
        (h, jnp.zeros((), jnp.int32)), (params["layers"], chosen))
    return h, n_differ


def logits(hf: dict, params, tokens, n_last: int):
    """float32 logits [n_last, V] of the last `n_last` positions of one
    unpadded sequence `tokens` [T]; `hf` holds the published config keys."""
    L, k = hf["num_hidden_layers"], hf["num_experts_per_tok"]
    chosen = jax.pure_callback(
        lambda t: _program_choice(t, L, k),
        jax.ShapeDtypeStruct((L, tokens.shape[0], k), jnp.int32), tokens)
    with jax.default_matmul_precision("highest"):
        h, n_differ = _hidden(hf, params, tokens, chosen)
        # a program that departs from the reference's own router more
        # often than bf16 near-ties explain is compared free, whatever
        # each departure's deficit
        h = jax.lax.cond(
            n_differ <= FLIP_SHARE * L * tokens.shape[0],
            lambda: h,
            lambda: _hidden(hf, params, tokens, jnp.full_like(chosen, -1))[0])
        h = base._rms(h[-n_last:], base.dense(params["final_norm"]),
                      hf["rms_norm_eps"])
        return h @ base.dense(params["lm_head"]).T
