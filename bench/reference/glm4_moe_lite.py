"""Plain float32 reference of the GLM-4.7-Flash decoder (`glm4_moe_lite`:
HF's DeepseekV3Attention and Glm4MoeTopkRouter), compared AT the program's
expert choice, each choice held to this reference's own router.

Straightforward `jax.numpy`, quadratic in the sequence: EXPANDED multi-head
latent attention (keys and values per head from the latents, nothing
absorbed, no cache, no pages, no kernels, no batching), the sigmoid router
with its bias in the choice and not in the weights, a shared expert beside
the routed ones, a dense first layer. Its own nibble unpack (that of
`bench/reference/mistral.py`, with its RMSNorm and SwiGLU); `"highest"`
matmul precision (`logits` sets it). With x_t the normed input of token t,
H heads, r = kv_lora_rank, dn / dr / dv the nope, rope and value head sizes:

    q_t      = W_uq RMSNorm(W_dq x_t)                 [H, dn + dr]
    c_t, p_t = split(W_dkv x_t)                       [r], [dr]
    c_t      = RMSNorm(c_t)          the latent the program pages
    k_t^h    = [W_uk^h c_t ; RoPE(p_t)]               one rope key, every head
    v_t^h    = W_uv^h c_t                             [dv]
    s[t, u]  = [q_nope ; RoPE(q_pe)]_t^h . k_u^h * (dn + dr)^-0.5,  u <= t
    Attn     = W_o concat_h(softmax(s) v^h)

RoPE turns the PAIRS (2i, 2i + 1) of the dr rope channels by position x
theta^(-2i / dr) (HF's `rope_interleave`, the complex convention; the
program's `rope_interleaved`). `rope_scaling` is null in the source, so the
softmax scale has no mscale term. The expert block of layers 1 and up:

    score  = sigmoid(W_r x_t)  in float32             [E]
    choice = top-k of (score + e_bias)                the bias moves the
                                                      choice only
    w      = score[choice] / sum(score[choice]) * routed_scaling_factor
    y_t    = sum_e w_e SwiGLU_e(x_t) + SwiGLU_shared(x_t)

`n_group` = `topk_group` = 1 in the source: nothing is group-limited.

Departures, each with its reason:

* The next-token-prediction layer (`num_nextn_predict_layers` 1,
  `model.layers.47.*`) is not run: HF's Glm4MoeLiteForCausalLM drops it at
  load, and the program never reads it.
* The parameter tree is the served one: `w_uk` [H, dn, r] and `w_uv`
  [H, dv, r] are HF's `kv_b_proj` split per head (dense bf16), layer 0 lives
  in `layers` and the expert layers in `moe_layers`.
* The top-k choice is compared as `bench/reference/mixtral.py` compares it,
  for its reason (a top-k is discontinuous, and 64 sigmoid scores near 0.5
  lie far closer together than 8 softmax ones): this reference takes the
  expert ids the program chose at every position of the sequence being
  checked (`Request.expert_ids`), holds every one of them to its OWN
  router (the chosen expert's score + bias must lie within `ROUTER_TIE` of
  this reference's k-th best at that position, on this reference's own
  hidden state; a choice that fails is not taken), counts the decisions in
  which the program's experts are not this reference's own top-k, and takes
  NONE when they are more than `FLIP_SHARE` of the sequence's. The combine
  weights are this reference's own scores of the chosen experts. A program
  that reports no choice is compared free.

Both limits from two readings on the chip (`scripts/latent_check_sweep.py`,
PERF.md section 6, PR 34: 8 seeds, prompts of 1024 and 4096 tokens and 9
decoded ones, 19,608 and 77,976 decisions a sequence), in SCORE units (a
sigmoid's, 0 to 1; the weights' standard deviation 0.02 makes a router
logit's about 0.9 and a score's about 0.2). `ROUTER_TIE` = 0.03: the
program's choices lie at most 0.0090 to 0.0163 under this reference's k-th
best (none of 780,672 decisions over 0.02); a router on a float8 trajectory
(both inputs of every matrix product of this reference at e4m3, the
precision below) lies up to 0.67 to 0.88 under, with 84 to 96% of its
decisions over 0.02. `FLIP_SHARE` = 0.15: the program's experts are not
this reference's own top-4 in 5.77 to 6.74% of a sequence's decisions (its
router is float32 too; its hidden states are bf16, and 64 scores of spread
0.2 leave the fourth and fifth best about 0.01 apart), the float8
trajectory's in 92.0 to 98.3%, so it is refused whole and fails on the
free distance.

`rnd`, where a caller gives it, is applied to BOTH inputs of every matrix
product (`scripts/latent_check_sweep.py` passes a rounding to float8_e4m3:
the precision below the served one has to come out not correct). The
benchmark's check never passes it.

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
dense, _rms = _m.dense, _m._rms

ROUTER_TIE = 0.03  # score units; see the docstring
FLIP_SHARE = 0.15  # of a sequence's (layer, position) decisions; the same


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


def _rope_pairs(x, theta):  # x [T, H, D]: pairs (2i, 2i + 1) are complex
    T, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]  # [T, D/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def _attention(hf, x, p, rnd):
    """Expanded MLA of one layer over the whole sequence x [T, hidden]."""
    H = hf["num_attention_heads"]
    dn, dr = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
    dv, r = hf["v_head_dim"], hf["kv_lora_rank"]
    T, eps, theta = x.shape[0], hf["rms_norm_eps"], hf["rope_theta"]

    def mm(a, b):
        return rnd(a) @ rnd(b)

    qa = _rms(mm(x, dense(p["w_dq"]).T), dense(p["q_norm"]), eps)
    q = mm(qa, dense(p["w_uq"]).T).reshape(T, H, dn + dr)
    ckv_pe = mm(x, dense(p["w_dkv"]).T)
    c = _rms(ckv_pe[:, :r], dense(p["kv_norm"]), eps)  # [T, r]
    k_pe = _rope_pairs(ckv_pe[:, None, r:], theta)  # [T, 1, dr]
    q_pe = _rope_pairs(q[..., dn:], theta)
    w_uk, w_uv = dense(p["w_uk"]), dense(p["w_uv"])  # [H, dn, r], [H, dv, r]
    k_nope = jnp.einsum("tr,hdr->thd", rnd(c), rnd(w_uk))
    v = jnp.einsum("tr,hdr->thd", rnd(c), rnd(w_uv))
    qf = jnp.concatenate([q[..., :dn], q_pe], -1)
    kf = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (T, H, dr))], -1)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None]

    def head(xs):  # one head at a time: [T, T] float32 scores
        qh, kh, vh = xs
        s = mm(qh, kh.T) * (dn + dr) ** -0.5
        pr = jax.nn.softmax(jnp.where(j <= i, s, -jnp.inf), axis=-1)
        return mm(pr, vh)

    out = jax.lax.map(head, (qf.transpose(1, 0, 2), kf.transpose(1, 0, 2),
                             v.transpose(1, 0, 2)))  # [H, T, dv]
    return mm(out.transpose(1, 0, 2).reshape(T, H * dv), dense(p["wo"]).T)


def _swiglu(x, w_gate, w_up, w_down, rnd):
    g, u = rnd(x) @ rnd(w_gate.T), rnd(x) @ rnd(w_up.T)
    return rnd(jax.nn.silu(g) * u) @ rnd(w_down.T)


def _moe(hf, x, p, chosen, rnd):
    """The expert block at the program's choice `chosen` [T, k] where that
    choice is admissible (module docstring), this reference's own top-k
    elsewhere. Also how many of the T decisions the program made otherwise
    than this reference's router would."""
    k = hf["num_experts_per_tok"]
    score = jax.nn.sigmoid(rnd(x) @ rnd(dense(p["router"]).T))  # [T, E]
    biased = score + p["e_bias"].astype(jnp.float32)[None]
    _, own = jax.lax.top_k(biased, k)
    kth = jnp.sort(biased, axis=-1)[:, -k]
    c = jnp.clip(chosen, 0, score.shape[-1] - 1)
    c_sorted = jnp.sort(c, axis=-1)
    given = jnp.all(chosen >= 0, -1)
    ok = (given
          & jnp.all(c_sorted[:, 1:] != c_sorted[:, :-1], -1)  # k experts
          & jnp.all(jnp.take_along_axis(biased, c, -1)
                    >= kth[:, None] - ROUTER_TIE, -1))
    differs = given & jnp.any(c_sorted != jnp.sort(own, axis=-1), -1)
    idx = jnp.where(ok[:, None], c, own)
    top = jnp.take_along_axis(score, idx, -1)  # the UNBIASED scores
    top = top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    top = top * hf["routed_scaling_factor"]
    weight = jnp.zeros_like(score).at[
        jnp.arange(x.shape[0])[:, None], idx].set(top)  # [T, E], 0 unrouted

    def one(acc, e):  # one expert at a time
        wg, wu, wd, w_e = e
        y = _swiglu(x, dense(wg), dense(wu), dense(wd), rnd)
        return acc + y * w_e[:, None], None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["w_gate_e"], p["w_up_e"], p["w_down_e"], weight.T))
    shared = _swiglu(x, dense(p["w_gate_s"]), dense(p["w_up_s"]),
                     dense(p["w_down_s"]), rnd)
    return out + shared, jnp.sum(differs)


def _hidden(hf, params, tokens, chosen, rnd):
    """(the last layer's output [T, hidden], the number of decisions in
    which `chosen` differs from this reference's own top-k on the way)."""
    eps = hf["rms_norm_eps"]
    h = params["embed"][tokens].astype(jnp.float32)

    def dense_layer(h, p):
        h = h + _attention(hf, _rms(h, dense(p["attn_norm"]), eps), p, rnd)
        x = _rms(h, dense(p["mlp_norm"]), eps)
        return h + _swiglu(x, dense(p["w_gate"]), dense(p["w_up"]),
                           dense(p["w_down"]), rnd), None

    def moe_layer(carry, xs):
        h, n_differ = carry
        p, c = xs
        h = h + _attention(hf, _rms(h, dense(p["attn_norm"]), eps), p, rnd)
        y, n = _moe(hf, _rms(h, dense(p["mlp_norm"]), eps), p, c, rnd)
        return (h + y, n_differ + n), None

    h, _ = jax.lax.scan(dense_layer, h, params["layers"])
    (h, n_differ), _ = jax.lax.scan(
        moe_layer, (h, jnp.zeros((), jnp.int32)),
        (params["moe_layers"], chosen))
    return h, n_differ


def logits(hf: dict, params, tokens, n_last: int, rnd=_same):
    """float32 logits [n_last, V] of the last `n_last` positions of one
    unpadded sequence `tokens` [T]; `hf` holds the published config keys."""
    L = hf["num_hidden_layers"] - hf["first_k_dense_replace"]
    k = hf["num_experts_per_tok"]
    chosen = jax.pure_callback(
        lambda t: _program_choice(t, L, k),
        jax.ShapeDtypeStruct((L, tokens.shape[0], k), jnp.int32), tokens)
    with jax.default_matmul_precision("highest"):
        h, n_differ = _hidden(hf, params, tokens, chosen, rnd)
        # a program that departs from this reference's own router more
        # often than bf16 near-ties explain is compared free, whatever
        # each departure's deficit
        h = jax.lax.cond(
            n_differ <= FLIP_SHARE * L * tokens.shape[0],
            lambda: h,
            lambda: _hidden(hf, params, tokens, jnp.full_like(chosen, -1),
                            rnd)[0])
        h = _rms(h[-n_last:], dense(params["final_norm"]),
                 hf["rms_norm_eps"])
        return rnd(h) @ rnd(dense(params["lm_head"]).T)
