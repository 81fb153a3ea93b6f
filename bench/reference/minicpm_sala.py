"""Plain float32 reference of MiniCPM-SALA (`minicpm_sala`): MiniCPM's three
scalings over layers that are block-sparse softmax attention (`minicpm4`) or
lightning attention (`lightning-attn`) by `mixer_types`, each followed by a
SwiGLU. No kernels, no cache, no batching, `"highest"` matmul precision, its
own nibble unpack (`mistral.py`'s); nothing of the program but the parameter
tree it is handed and, as `mixtral.py` takes expert ids, the key blocks the
program's sparse layers chose.

The equations (ISSUE 54, section 1). `h_0 = scale_emb E[tok]`; a layer adds
`c Mixer(rms(h) w_1)` and then `c W_down(silu(W_gate x) * W_up x)`, `c =
scale_depth / sqrt(layers)`; `logits = W_head (rms(h) w_f / (hidden /
dim_model_base))`.

Lightning mixer: q, k, v a head of its own (32 x 128); RMSNorm over a head's
lanes on q and k; rope (HF's half split, all lanes, the token's index) on q
and k; a head h decays by `lam_h = exp(-2^(-8 (h + 1) / H))`; the PLAIN scan
`S_t = lam_h S_(t-1) + k_t^T v_t`, `o_t = (q_t / sqrt(D)) S_t` in float32
(the program's prefill runs the chunked form); RMSNorm over a head's lanes on
o; `y = W_o (sigmoid(x W_g) * o)`.

Sparse mixer: 32 query / 2 KV heads, no rope, the same q / k norm, scores /
sqrt(D), `y = W_o (sigmoid(x W_g) * a)`. The query at position t (sees keys
0 .. t) with t + 1 < dense_len attends to all of them; else, a KV head:
pooled keys `c_j = mean(k_16j .. k_16j+31)` for 16j + 31 <= t; `p_h =
softmax_j(q_h . c_j / sqrt(D))`; `r_j` = the sum of p_h,j over the KV head's
query heads; block score `b_m = max r_j`, j = 4m - 1 .. 4m + 3 (those that
exist); block 0 and blocks t // 64 - 32 .. t // 64 always; the best of the
rest up to 64 in all; softmax over the keys <= t of the chosen blocks.

Written from memory of the source, which is not in the repository (every one
is in bench/configs/minicpm-sala-int4.json `assumed`): the `sparse_config`
sizes (MiniCPM4.1's), the decay slopes (the Lightning Attention paper's,
ALiBi's form), the sigmoid form of both gates, the output norm's form, `qk_norm`
on both mixers, the windows' alignment to blocks. Two departures from the
source's kernels, in the program too: the softmax over windows is exact (the
source finds each head's normaliser from a second, coarser pooling), and the
pooled keys are means of the keys as cached (after `qk_norm`).

**The selection is taken from the program, and held to this reference's
own.** Pooled keys are means of 32 near-independent unit vectors under
`bench/weights.py`'s weights, so p_h is almost flat and neighbouring block
scores differ by less than the bf16 rounding of the cached keys: a correct
bf16 program and a float32 reference choose different blocks, and from there
they are different networks. So, as `mixtral.py` holds the experts
(`ROUTER_TIE`, `FLIP_SHARE`): where the request reports its selection
(`Request.prompt_selection`, `out_selection`: the prompt's last position and
each decode step's input position) it is taken IF it holds block 0 and the
local window, names existing blocks once each, as many as the reference
would, and every free block's score lies within `SELECT_TIE` of the
reference's own last pick; else the reference's own stands there and the
distance shows. The share of chosen free blocks outside the reference's own
pick is bounded by `SELECT_FLIP_SHARE`: past it the logits come back NaN
(not correct). Positions the request does not report (the prompt's earlier
tokens) select themselves; a program that reports nothing is compared
against the reference's own selection. A SERVED engine reports nothing (five
counts a row a step and no ids): the ids cost 4 KB a token, so they are asked
for (`kvsparse.CACHE_KIND.report_ids`, before the engine is built) by
`scripts/sparse_check_sweep.py` and `tests/test_minicpm_sala.py` alone; the
cell's own check stays under `dense_len`, where nothing is selected.

`SELECT_TIE` and `SELECT_FLIP_SHARE`, and the readings they lie between, are
in the configuration's `tolerances` and PERF.md section 6 (PR 54).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.mistral import _rms, _rope, dense

SELECT_TIE = 0.0005  # of a block score, a sum of 16 heads' probabilities
SELECT_FLIP_SHARE = 0.1  # of the reported free choices; see PERF.md
ROWS = 2048  # tokens of one block of a row-wise product
QUERIES = 256  # queries of one block of the attention


def _same(x):
    return x


def float8(x):
    """An operand rounded to float8_e4m3: the precision below bf16, for the
    sweep that sets the tolerances (scripts/sparse_check_sweep.py)."""
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _by_rows(f, x, n: int = ROWS):
    """`f` over blocks of `n` rows of x [T, ..]: bounds the temporaries."""
    T = x.shape[0]
    if T <= n:
        return f(x)
    pad = -T % n
    xs = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    out = jax.lax.map(f, xs.reshape(-1, n, *x.shape[1:]))
    return out.reshape(-1, *out.shape[2:])[:T]


def _mm(x, w, rnd):
    w = rnd(dense(w))
    return _by_rows(lambda a: rnd(a) @ w.T, x)


def sizes(hf) -> dict:
    s = dict(hf["sparse_config"])
    return {"stride": s["kernel_stride"], "span": s["kernel_size"],
            "block": s["block_size"], "topk": s["topk"],
            "init": s["init_blocks"],
            "window": s["window_size"] // s["block_size"],
            "dense_len": s["dense_len"]}


def slopes(n_heads: int):
    return 2.0 ** (-8.0 * jnp.arange(1, n_heads + 1, dtype=jnp.float32)
                   / n_heads)


def _lightning(hf, x, p, rnd, with_state: bool = False):
    H, D = hf["lightning_nh"], hf["lightning_head_dim"]
    T, eps = x.shape[0], hf["rms_norm_eps"]
    q = _mm(x, p["wq"], rnd).reshape(T, H, D)
    k = _mm(x, p["wk"], rnd).reshape(T, H, D)
    v = _mm(x, p["wv"], rnd).reshape(T, H, D)
    q = _rope(_rms(q, dense(p["q_norm"]), eps), hf["rope_theta"])
    k = _rope(_rms(k, dense(p["k_norm"]), eps), hf["rope_theta"])
    q = q / jnp.sqrt(jnp.float32(D))
    lam = jnp.exp(-slopes(H))[:, None, None]

    def one(S, xs):  # S [H, D keys, D values]
        qt, kt, vt = xs
        S = lam * S + kt[:, :, None] * vt[:, None, :]
        return S, jnp.einsum("hn,hnp->hp", qt, S)

    S, o = jax.lax.scan(one, jnp.zeros((H, D, D), jnp.float32), (q, k, v))
    if with_state:
        return S
    o = _rms(o, dense(p["o_norm"]), eps).reshape(T, H * D)
    gate = jax.nn.sigmoid(_mm(x, p["wg"], rnd))
    return _mm(gate * o, p["wo"], rnd)


def _pooled(k, sz):
    """k [T, Hkv, D] -> the means of the windows [W, Hkv, D], W = T //
    stride; window j is whole when stride j + span - 1 <= t."""
    T = k.shape[0]
    pad = -T % sz["stride"] + sz["span"]
    kp = jnp.pad(k, ((0, pad), (0, 0), (0, 0)))
    idx = (jnp.arange(T // sz["stride"])[:, None] * sz["stride"]
           + jnp.arange(sz["span"])[None])
    return kp[idx].mean(axis=1)


def _block_scores(r, sz, M: int):
    """r [.., W] (< 0: no window) -> [.., M] the best window over each
    block: j = 4m - 1 .. 4m + 3."""
    pb = sz["block"] // sz["stride"]
    W = r.shape[-1]
    r = jnp.pad(r, [(0, 0)] * (r.ndim - 1) + [(1, M * pb - W)],
                constant_values=-1.0)  # r[.., j + 1] is window j
    idx = jnp.arange(M)[:, None] * pb + jnp.arange(pb + 1)[None]
    return r[..., idx].max(axis=-1)


def _choose(hf, qc, t, windows, chosen, sz, M: int):
    """The blocks the queries `qc [Q, Hkv, G, D]` at positions `t [Q]` read:
    member [Q, Hkv, M] bool, and `_STATS` of the program's `chosen [Q,
    Hkv, topk]` (-1 where it reports none)."""
    D = qc.shape[-1]
    W = windows.shape[0]
    s = jnp.einsum("qhgd,whd->qhgw", qc, windows) / jnp.sqrt(jnp.float32(D))
    done = (jnp.arange(W) * sz["stride"] + sz["span"] - 1
            <= t[:, None])[:, None, None, :]
    p = jnp.where(done, jax.nn.softmax(jnp.where(done, s, -1e30), -1), 0.0)
    r = jnp.where(done[:, :, 0], p.sum(axis=2), -1.0)
    score = _block_scores(r, sz, M)  # [Q, Hkv, M]
    m = jnp.arange(M)
    cur = (t // sz["block"])[:, None, None]
    exists = m <= cur
    forced = exists & ((m < sz["init"]) | (m >= cur - sz["window"]))
    key = jnp.where(forced, 1e4 + m, score)
    key = jnp.where(exists, key, -jnp.inf)
    k = min(sz["topk"], M)
    vals, ids = jax.lax.top_k(key, k)
    own = jnp.any((ids[..., None] == m) & (vals[..., None] > -jnp.inf), -2)
    n_own = own.sum(-1)
    # the reference's own last FREE pick (inf where it has none to make)
    last = jnp.where(vals[..., -1] < 1e4, vals[..., -1], jnp.inf)
    last = jnp.where(jnp.isfinite(vals[..., -1]), last, -jnp.inf)
    c = jnp.clip(chosen, 0, M - 1)
    named = chosen >= 0
    theirs = jnp.any((c[..., None] == m) & named[..., None], -2)
    given = jnp.any(named, -1)
    c_score = jnp.take_along_axis(score, c, -1)
    c_free = named & ~jnp.take_along_axis(forced, c, -1)
    ok = (given
          & (named.sum(-1) == theirs.sum(-1))  # each block once
          & (theirs.sum(-1) == n_own)
          & jnp.all(~theirs | exists, -1)
          & jnp.all(~forced | theirs, -1)  # block 0 and the local window
          & jnp.all(~c_free | (c_score >= last[..., None] - SELECT_TIE), -1))
    member = jnp.where(ok[..., None], theirs, own)
    dense_row = (t + 1 < sz["dense_len"])[:, None, None]
    member = jnp.where(dense_row, exists, member)
    count = given[..., None] & ~dense_row
    n_free = jnp.sum(c_free & count)
    n_out = jnp.sum(c_free & count & ~jnp.take_along_axis(own, c, -1))
    under = jnp.max(jnp.where(c_free & count & jnp.isfinite(last[..., None]),
                              last[..., None] - c_score, 0.0))
    refused = jnp.sum(given & ~ok & ~dense_row[..., 0])
    return member, jnp.stack([n_free, n_out, under, refused]).astype(
        jnp.float32)


# what a forward says of the program's selection: free choices reported, of
# them outside the reference's own pick, the deepest a free choice's score
# lies under the reference's own last pick, (position, KV head) selections
# that were NOT taken
_STATS = ("reported", "departed", "deepest", "refused")


def _merge(a, b):
    return jnp.stack([a[0] + b[0], a[1] + b[1], jnp.maximum(a[2], b[2]),
                      a[3] + b[3]])


def _sparse(hf, x, p, chosen, rnd):
    """chosen [T, Hkv, topk] int32. Returns (y, `_STATS`)."""
    Hq, Hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    D = hf.get("head_dim") or hf["hidden_size"] // Hq
    G = Hq // Hkv
    T, eps = x.shape[0], hf["rms_norm_eps"]
    sz = sizes(hf)
    q = _rms(_mm(x, p["wq"], rnd).reshape(T, Hq, D), dense(p["q_norm"]), eps)
    k = _rms(_mm(x, p["wk"], rnd).reshape(T, Hkv, D), dense(p["k_norm"]),
             eps)
    v = _mm(x, p["wv"], rnd).reshape(T, Hkv, D)
    windows = _pooled(k, sz)
    slot = jnp.arange(T)
    Q = min(QUERIES, T)
    pad = -T % Q
    qs = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, Q, Hkv, G, D)
    cs = jnp.pad(chosen, ((0, pad), (0, 0), (0, 0)),
                 constant_values=-1).reshape(-1, Q, Hkv, sz["topk"])

    def one(xs):
        qc, cc, i = xs
        t = i * Q + jnp.arange(Q)
        if T < sz["dense_len"]:  # static: every query reads all it may
            allowed = jnp.broadcast_to(
                (slot <= t[:, None])[:, None], (Q, Hkv, T))
            n = jnp.zeros((4,), jnp.float32)
        else:
            member, n = _choose(hf, qc, t, windows, cc, sz,
                                -(-T // sz["block"]))
            allowed = (jnp.repeat(member, sz["block"], axis=-1)[..., :T]
                       & (slot <= t[:, None])[:, None])
        s = jnp.einsum("qhgd,shd->qhgs", rnd(qc), rnd(k)) / jnp.sqrt(
            jnp.float32(D))
        a = jax.nn.softmax(jnp.where(allowed[:, :, None], s, -jnp.inf), -1)
        return jnp.einsum("qhgs,shd->qhgd", rnd(a), rnd(v)), n

    a, n = jax.lax.map(one, (qs, cs, jnp.arange(qs.shape[0])))
    a = a.reshape(-1, Hq * D)[:T]
    gate = jax.nn.sigmoid(_mm(x, p["wg"], rnd))
    n = jnp.stack([n[:, 0].sum(), n[:, 1].sum(), n[:, 2].max(),
                   n[:, 3].sum()])
    return _mm(gate * a, p["wo"], rnd), n


def _mlp(x, p, rnd):
    wg, wu, wd = (rnd(dense(p[n])) for n in ("w_gate", "w_up", "w_down"))

    def rows(a):
        a = rnd(a)
        return rnd(jax.nn.silu(a @ wg.T) * (a @ wu.T)) @ wd.T

    return _by_rows(rows, x)


def runs(hf) -> list:
    """`mixer_types` as (kind, length) runs: the served tree stacks each."""
    out = []
    for kind in hf["mixer_types"]:
        if out and out[-1][0] == kind:
            out[-1][1] += 1
        else:
            out.append([kind, 1])
    return out


def _program_selection(tokens, shape):
    """[Ls, T, Hkv, topk] int32: the blocks the program chose for exactly
    this sequence where it reports them, -1 elsewhere."""
    tokens = np.asarray(tokens).tolist()
    out = np.full(shape, -1, np.int32)
    try:
        from bigdl_tpu.serving.engine import last_routed_request

        req = last_routed_request()
    except (ImportError, AttributeError):
        return out
    if req is None or getattr(req, "prompt_selection", None) is None:
        return out
    if (req.prompt + req.out_tokens)[:len(tokens)] != tokens:
        return out
    n = len(req.prompt)
    rows = [(n - 1, req.prompt_selection)] + [
        (n + i, s) for i, s in enumerate(req.out_selection)]
    for t, s in rows:
        s = np.asarray(s)
        if t < len(tokens) and s.size == shape[0] * shape[2] * shape[3]:
            out[:, t] = s.reshape(shape[0], shape[2], shape[3])
    return out


def hidden(hf, params, tokens, chosen, rnd=_same):
    """(the last layer's output [T, H], `_STATS` over the sparse layers)."""
    L = hf["num_hidden_layers"]
    c = hf["scale_depth"] / np.sqrt(L)
    eps = hf["rms_norm_eps"]
    h = dense(params["embed"])[tokens] * hf["scale_emb"]
    counts = jnp.zeros((4,), jnp.float32)
    at = 0  # the next sparse layer's index among its kind
    for (kind, n), r in zip(runs(hf), sorted(params["runs"])):
        stack = params["runs"][r]

        def layer(carry, xs, kind=kind):
            h, counts = carry
            p, ch = xs
            x = _rms(h, dense(p["attn_norm"]), eps)
            if kind == "minicpm4":
                y, n_ = _sparse(hf, x, p, ch, rnd)
                counts = _merge(counts, n_)
            else:
                y = _lightning(hf, x, p, rnd)
            h = h + c * y
            x = _rms(h, dense(p["mlp_norm"]), eps)
            return (h + c * _mlp(x, p, rnd), counts), None

        if kind == "minicpm4":
            ch = chosen[at:at + n]
            at += n
        else:
            ch = jnp.zeros((n, 1), jnp.int32)
        (h, counts), _ = jax.lax.scan(layer, (h, counts), (stack, ch))
    return h, counts


def first_lightning_state(hf: dict, params, tokens):
    """The state `S [H, D keys, D values]` of the model's FIRST lightning
    layer after all of `tokens` (the layers before it run whole, on the
    reference's own selection): what a slot's state row of that layer must
    hold after the same tokens (scripts/sparse_check_sweep.py holds the
    engine's to it: a state kept in bfloat16 shows there long before it
    shows in a logit)."""
    L = hf["num_hidden_layers"]
    c = hf["scale_depth"] / np.sqrt(L)
    eps = hf["rms_norm_eps"]
    none = jnp.full((tokens.shape[0], hf["num_key_value_heads"],
                     sizes(hf)["topk"]), -1, jnp.int32)
    with jax.default_matmul_precision("highest"):
        h = dense(params["embed"])[tokens] * hf["scale_emb"]
        for (kind, n), r in zip(runs(hf), sorted(params["runs"])):
            for i in range(n):
                p = jax.tree.map(lambda a: a[i], params["runs"][r])
                x = _rms(h, dense(p["attn_norm"]), eps)
                if kind != "minicpm4":
                    return _lightning(hf, x, p, _same, with_state=True)
                h = h + c * _sparse(hf, x, p, none, _same)[0]
                h = h + c * _mlp(_rms(h, dense(p["mlp_norm"]), eps), p,
                                 _same)
    raise ValueError("the model has no lightning layer")


def logits(hf: dict, params, tokens, n_last: int, rnd=_same,
           take_selection: bool = True, with_stats: bool = False):
    """float32 logits [n_last, V] of the last `n_last` positions of one
    unpadded sequence `tokens` [T]; `hf` holds the published config keys.
    `with_stats`: also `_STATS` of the program's selection."""
    sz = sizes(hf)
    shape = (sum(k == "minicpm4" for k in hf["mixer_types"]),
             tokens.shape[0], hf["num_key_value_heads"], sz["topk"])
    if take_selection:
        chosen = jax.pure_callback(
            lambda t: _program_selection(t, shape),
            jax.ShapeDtypeStruct(shape, jnp.int32), tokens)
    else:
        chosen = jnp.full(shape, -1, jnp.int32)
    with jax.default_matmul_precision("highest"):
        h, counts = hidden(hf, params, tokens, chosen, rnd)
        h = _rms(h[-n_last:], dense(params["final_norm"]),
                 hf["rms_norm_eps"])
        h = h / (hf["hidden_size"] / hf["dim_model_base"])
        out = (rnd(h) @ rnd(dense(params["lm_head"])).T)[:, :hf["vocab_size"]]
        # a selection that departs from the reference's own more often than
        # bf16 near-ties explain is not correct
        departs = counts[1] > SELECT_FLIP_SHARE * jnp.maximum(counts[0], 1)
        out = jnp.where(departs, jnp.nan, out)
        return (out, counts) if with_stats else out
