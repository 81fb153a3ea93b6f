"""State beside pages: the cache of a model whose layers are of two kinds.

Beside `kvcache.py` (a dense pool of keys and values), `kvpaged.py` (pages of
them, or of latents) and `kvstate.py` (a recurrent state in EVERY layer and
no keys): a hybrid such as Granite 4.0-H (`models/granitemoehybrid.py`) runs
a few softmax-attention layers between many Mamba-2 layers. An attention
layer keeps keys and values, which grow with the context; a Mamba-2 layer
keeps, per sequence, the last `d_conv - 1` inputs of its causal convolution
and a state `h [heads, head size, d_state]` in float32, which do not. So one
slot of the serving engine holds BOTH: pages, booked by
`serving/pages.PageTable` exactly as a dense model's are, and one STATE ROW,
which is the slot's own index (row b of the pool belongs to batch row b of a
decode step; a prefill names its row in `rows`). Nothing books the rows: a
slot has one whether it is used or not, a row that is idle is neither read
nor written by a step, and an admission's prefill starts its row from zero
(`pos == 0`) whatever the last holder left.

    conv [Lm, d_conv - 1, R, C]   float32, C = inner + 2 * groups * d_state
    ssm  [Lm, R, heads * head size, d_state]   float32, d_state on lanes
    k, v [La, n_pages, page, Hkv, D]           as kvpaged.PagedKVCache's

The state's shape is the FAMILY's (`init_hybrid(state=, conv_rows=)`): what
follows the row axis of `ssm`, the convolution's channels, and which axis of
`conv` is the row. A Mamba-1 layer (Jamba, `models/jamba.py`; `mix1` below)
convolves its E inner channels alone and keeps

    conv [Lm, R, (d_conv - 1) * E]   a row's tail in one piece, its inputs
                                     side by side on lanes (`conv_rows` 1)
    ssm  [Lm, R, d_state, E]         the channels on lanes, the state index
                                     on sublanes

(its d_state of 16 would fill 16 of 128 lanes the other way round; and with
256 rows between a tail's d_conv - 1 inputs XLA re-laid the whole `conv`
array, 0.4 GB, into and out of every program that gathers or scatters rows of
it, wherever an axis of 3 stood: scripts/engine_fit.py shows such a copy). A gated
short-convolution layer (LFM2, `models/lfm2_moe.py`; `tail_conv` below) has NO
recurrence at all: its state is the tail alone,

    conv [Lc, R, (L_cache - 1) * H]  laid as Mamba-1's, `conv_rows` 1
    ssm  None

A Kimi delta attention layer (Solar-Open2, `models/solar_open2.py`;
`kda_mix` below) keeps the tails of THREE convolutions (q, k, v) and a matrix
state a head, laid as lightning's is (kvsparse.py):

    conv [Lk, R, (K - 1) * 3 * H * D]  laid as Mamba-1's, `conv_rows` 1
    ssm  [Lk, R, H * D, D]             `ssm[.., h * D + p, n]` = S_h[n, p]:
                                       the value index on sublanes, the key
                                       index on lanes, so a key channel's
                                       decay is a row broadcast down

Everything that books, parks, restores or counts a row (`row_nbytes`,
`n_rows`, `_spots`, `axes_of`, `row_view`, the spans) reads what is there:
sizes, the row axis, and no array where the family has none. What a prefill
counts for its span is the family's too, handed over with the state's shape
(`init_hybrid(counts=)`).

The Mamba-2 recurrence of one head, with a_t = dt_t * A <= 0:

    h_t = exp(a_t) h_{t-1} + dt_t x_t (x) B_t        y_t = h_t C_t + D x_t

A decode step runs it once, through the Pallas kernel `mamba2_decode`
(ops/pallas/mamba2.py: the live rows' state read and written in place) where
the kernels are in use, else in `jnp`. A prefill runs the CHUNKED form
(`ssd_chunked`: inside a chunk the sum over s <= t of exp(sum a) C_t . B_s
dt_s x_s, across chunks the state) on the XLA route under the scope
`mamba2_prefill`. Decay sums and the state are float32.
A position that is no token (left padding before `start`, the right padding
of a bucket past `valid_len`) carries dt = 0 and a zero convolution input:
no decay, no update, and the convolution's tail is taken at the last real
token.

The Mamba-1 recurrence of one channel d and state index n, with A[n, d] < 0
and dt a CHANNEL's:

    h_t[n, d] = exp(dt_t[d] A[n, d]) h_{t-1}[n, d] + dt_t[d] B_t[n] x_t[d]
    y_t[d]    = sum_n C_t[n] h_t[n, d] + D[d] x_t[d]

has no head and no matrix-product form: both a decode step and a one-row
prefill run it through the kernels of ops/pallas/selective_scan.py (the
state in place), else token by token in `jnp` (`scan1`).

The gated delta rule of one KDA head, S in R^{N x P} (key index n, value
index p), g_t <= 0 a log-decay a KEY CHANNEL and beta_t in (0, 2):

    S' = diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T          o_t = S_t^T q_t

reads the state before it writes it, so its transition is not diagonal. A
decode step runs it through `kda_decode` (ops/pallas/mamba2.py: two passes
over a head's block while it sits in VMEM, one read and one write of it in
HBM), else `kda_step`; a prefill runs the chunked form `kda_chunked` on the
XLA route under the scope `kda_prefill`.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from bigdl_tpu import kvpaged, kvstate
from bigdl_tpu.obs.scopes import scope

KIND = "state_beside_pages"
_HI = jax.lax.Precision.HIGHEST  # float32 operands stay float32 on the MXU


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class HybridCache:
    k: jax.Array  # [La, n_pages, page, Hkv, D]
    v: jax.Array
    conv: jax.Array  # [Lm, d_conv - 1, R, C] float32
    # [Lm, R, *the family's state] float32; None: the tail is all the state
    ssm: Optional[jax.Array]
    block_tables: jax.Array  # [B, max_pages] int32, 0 = nobody's page
    pos: jax.Array  # [B] int32 next slot per row
    start: jax.Array  # [B] int32 first valid slot (left padding)
    # [B] int32 state row of each batch row; None = batch row b holds row b
    rows: Optional[jax.Array] = None
    # [B] int32: how many of the NEXT forward's T positions are tokens
    # (the engine's prefill pads a bucket on the right); None = all
    valid_len: Optional[jax.Array] = None
    # the axis of `conv` that is the state row: 2 (`[Lm, K - 1, R, C]`) or
    # 1 (`[Lm, R, (K - 1) * C]`); static
    conv_rows: int = dataclasses.field(default=2,
                                       metadata=dict(static=True))
    # what a prefill chunk adds to its span, by the family's prefill form:
    # ("state_chunks", the chunk length of a chunked form), ("scan_tokens",)
    # where the form walks the tokens, () where a tail is all the state
    # (read from the engine's pool alone, `note_chunk`); static
    counts: tuple = dataclasses.field(default=(), metadata=dict(static=True))

    @property
    def page_size(self) -> int:
        return self.k.shape[2]

    @property
    def max_len(self) -> int:  # logical capacity per row
        return self.block_tables.shape[1] * self.page_size

    @property
    def n_rows(self) -> int:
        return self.conv.shape[self.conv_rows]

    @property
    def kv(self) -> kvpaged.PagedKVCache:
        """The attention layers' pages as `kvpaged` reads and writes them."""
        return kvpaged.PagedKVCache(
            k=self.k, v=self.v, block_tables=self.block_tables, pos=self.pos,
            start=self.start)

    def state_rows(self) -> tuple[jax.Array, jax.Array]:
        """(state row of each batch row, which batch rows are live): a row
        whose block table maps no page is an idle slot of the engine."""
        B = self.block_tables.shape[0]
        rows = (jnp.arange(B, dtype=jnp.int32) if self.rows is None
                else self.rows.astype(jnp.int32))
        return rows, kvpaged.live_rows(self)


def init_hybrid(n_attn: int, n_mamba: int, n_pages: int, page_size: int,
                n_kv_heads: int, head_dim: int, rows: int,
                max_pages_per_row: int, conv_dim: int, d_conv: int,
                state: Optional[tuple], counts: tuple = (),
                batch: Optional[int] = None, dtype=jnp.bfloat16,
                conv_rows: int = 2) -> HybridCache:
    """Zeros: pages nobody holds and `rows` state rows, each layer's of the
    family's shape `state` (None: a layer keeps its convolution's tail and
    nothing else), the tails with the rows on axis `conv_rows`; `counts`:
    `HybridCache.counts`."""
    b = rows if batch is None else batch
    kv = (n_attn, n_pages, page_size, n_kv_heads, head_dim)
    tails = ((d_conv - 1, rows, conv_dim) if conv_rows == 2
             else (rows, (d_conv - 1) * conv_dim))
    return HybridCache(
        k=jnp.zeros(kv, dtype), v=jnp.zeros(kv, dtype), conv_rows=conv_rows,
        counts=tuple(counts),
        conv=jnp.zeros((n_mamba,) + tails, jnp.float32),
        ssm=(None if state is None else
             jnp.zeros((n_mamba, rows) + tuple(state), jnp.float32)),
        block_tables=jnp.zeros((b, max_pages_per_row), jnp.int32),
        pos=jnp.zeros((b,), jnp.int32), start=jnp.zeros((b,), jnp.int32))


def row_nbytes(cache: HybridCache) -> int:
    """Bytes of ONE state row over all state layers: what a decode step
    reads, and writes again, for each live slot."""
    return sum(a.size for a in (cache.conv, cache.ssm)
               if a is not None) // cache.n_rows * 4


def valid_positions(cache: HybridCache, T: int) -> jax.Array:
    """[B, T] bool: the positions of this forward that are tokens."""
    slots = cache.pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    ok = slots >= cache.start[:, None]
    if cache.valid_len is not None:
        ok = ok & (jnp.arange(T)[None, :] < cache.valid_len[:, None])
    return ok


def advance(cache: HybridCache, n: int) -> HybridCache:
    """`n` positions went through; those past `valid_len` were padding."""
    step = n if cache.valid_len is None else cache.valid_len
    return dataclasses.replace(cache, pos=cache.pos + step, valid_len=None)


# ---------------------------------------------------------------------------
# the Mamba-2 mixer's state arithmetic
# ---------------------------------------------------------------------------

def causal_conv(tail, xbc, w, b, end):
    """Depthwise causal convolution over `tail [B, K-1, C]` (the inputs
    before this forward) followed by `xbc [B, T, C]`, all float32; `w [K,
    C]` (w[k] weighs the input K-1-k positions back), `b [C]`. Returns
    (out [B, T, C], the tail after position `end [B]` - 1: the last K-1
    inputs of the real tokens)."""
    K, T = w.shape[0], xbc.shape[1]
    window = jnp.concatenate([tail, xbc], axis=1)  # [B, T + K - 1, C]
    out = b + sum(window[:, k:k + T] * w[k] for k in range(K))
    at = end[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None, :]
    return out, jnp.take_along_axis(window, at[..., None], axis=1)


def ssm_step(x, dt, A, Bm, Cm, h):
    """One token in `jnp`. x [B, H, P], dt [B, H], A [H], Bm, Cm [B, N],
    h [B, H, P, N], all float32. Returns (y [B, H, P] without the D term,
    h)."""
    dec = jnp.exp(dt * A)
    h = dec[..., None, None] * h + (
        (dt[..., None] * x)[..., None] * Bm[:, None, None, :])
    return jnp.einsum("bhpn,bn->bhp", h, Cm, precision=_HI), h


def ssd_chunked(x, dt, A, Bm, Cm, h, chunk: int):
    """The chunked (SSD) form over T tokens from the state `h`. x [B, T, H,
    P], dt [B, T, H] (0 where the position is no token), A [H], Bm, Cm [B,
    T, N] (one group), h [B, H, P, N], all float32. Returns (y [B, T, H, P]
    without the D term, h after the T tokens)."""
    B, T, H, P = x.shape
    Q = min(chunk, T)
    pad = -T % Q
    if pad:  # dt = 0: a padded position neither decays nor updates
        x, dt, Bm, Cm = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) *
                                 (a.ndim - 2)) for a in (x, dt, Bm, Cm))
    n = (T + pad) // Q

    def chunks(a):  # [B, n * Q, ...] -> [n, B, Q, ...]
        return jnp.moveaxis(a.reshape(B, n, Q, *a.shape[2:]), 1, 0)

    causal = jnp.tril(jnp.ones((Q, Q), jnp.bool_))

    def one(h, xs):
        xc, dtc, bc, cc = xs
        cum = jnp.cumsum(dtc * A, axis=1)  # [B, Q, H] decay sums, inclusive
        ch = jnp.moveaxis(cum, 1, 2)  # [B, H, Q]
        u = dtc[..., None] * xc  # [B, Q, H, P]
        # inside the chunk: token t sees s <= t through C_t . B_s
        cb = jnp.einsum("btn,bsn->bts", cc, bc, precision=_HI)
        decay = jnp.exp(jnp.where(
            causal, ch[..., :, None] - ch[..., None, :], -jnp.inf))
        y = jnp.einsum("bhts,bshp->bthp", cb[:, None] * decay, u,
                       precision=_HI)
        # what came before the chunk: the state
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "btn,bhpn->bthp", cc, h, precision=_HI)
        # the state after the chunk
        tail = jnp.exp(cum[:, -1:] - cum)  # [B, Q, H] decay to the end
        h = jnp.exp(cum[:, -1])[..., None, None] * h + jnp.einsum(
            "bshp,bsn->bhpn", u * tail[..., None], bc, precision=_HI)
        return h, y

    h, y = jax.lax.scan(one, h, tuple(map(chunks, (x, dt, Bm, Cm))))
    return jnp.moveaxis(y, 0, 1).reshape(B, n * Q, H, P)[:, :T], h


def why_not_kernel(d_state: int, inner: int) -> Optional[str]:
    """None when a decode step takes `mamba2_decode`."""
    from bigdl_tpu.ops.pallas import interpret_mode, why_not_pallas
    from bigdl_tpu.ops.pallas.mamba2 import CHUNK

    why = why_not_pallas()
    if why is None and inner % CHUNK:
        why = f"inner width {inner} is not whole chunks of {CHUNK} rows"
    if why is None and d_state % 128 and not interpret_mode():
        why = f"d_state {d_state} is not whole lanes"
    return why


def mix(cache: HybridCache, layer, xbc, dt, A, D, conv_w, conv_b, *,
        n_heads: int, d_head: int, d_state: int, chunk: int, decode: bool):
    """The state part of Mamba layer `layer` (index among the Mamba layers)
    over this forward's T positions: the causal convolution and silu over
    `xbc [B, T, C]`, then the recurrence. `dt [B, T, H]` is the step AFTER
    the softplus; A [H] < 0, D [H]; everything float32. Returns
    (y [B, T, H, P] float32 with the D term, the cache with the layer's
    rows updated). One group of B and C."""
    from bigdl_tpu.ops import routes

    B, T, C = xbc.shape
    H, P, N = n_heads, d_head, d_state
    inner = H * P
    valid = valid_positions(cache, T)
    xbc = jnp.where(valid[..., None], xbc.astype(jnp.float32), 0.0)
    dt = jnp.where(valid[..., None], dt, 0.0)
    end = (jnp.full((B,), T, jnp.int32) if cache.valid_len is None
           else cache.valid_len.astype(jnp.int32))
    rows, live = cache.state_rows()
    at = jnp.clip(rows, 0, cache.n_rows - 1)
    to = jnp.where(live, at, cache.n_rows)  # an idle row writes nowhere
    # a row at position 0 starts from nothing, whatever its last holder left
    fresh = (cache.pos == 0)[:, None, None]
    tail = jnp.where(fresh, 0.0, jnp.moveaxis(cache.conv[layer][:, at], 0, 1))
    out, tail = causal_conv(tail, xbc, conv_w, conv_b, end)
    out = jax.nn.silu(out)
    x = out[..., :inner].reshape(B, T, H, P)
    Bm, Cm = out[..., inner:inner + N], out[..., inner + N:]
    # (the two advanced indices are apart: their axis comes first)
    conv = cache.conv.at[layer, :, to].set(tail, mode="drop")
    detail = f"B{B} T{T} H{H} P{P} N{N}"
    why = why_not_kernel(N, inner)
    if decode and T == 1 and why is None:
        from bigdl_tpu.ops.pallas.mamba2 import mamba2_decode

        routes.note("mamba2", "pallas", detail)
        y, ssm = mamba2_decode(cache.ssm, layer, rows, live, x[:, 0],
                               dt[:, 0], A, Bm[:, 0], Cm[:, 0])
        y = y[:, None]
    else:
        routes.note("mamba2", "xla", detail + (
            f" ({why})" if decode and T == 1 else " chunked prefill"))
        h = jnp.where(fresh[..., None], 0.0,
                      cache.ssm[layer, at].reshape(B, H, P, N))
        if decode and T == 1:
            y, h = ssm_step(x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], h)
            y = y[:, None]
        else:
            with scope("mamba2_prefill"):
                y, h = ssd_chunked(x, dt, A, Bm, Cm, h, chunk)
        ssm = cache.ssm.at[layer, to].set(h.reshape(B, inner, N),
                                          mode="drop")
    y = y + D[:, None] * x
    return y, dataclasses.replace(cache, conv=conv, ssm=ssm)


# ---------------------------------------------------------------------------
# the Mamba-1 mixer's state arithmetic
# ---------------------------------------------------------------------------

def scan1(x, dt, A, Bm, Cm, h):
    """The selective scan in `jnp`, token by token. x, dt [B, T, E] (dt 0
    where the position is no token), A [N, E], Bm, Cm [B, T, N], h [B, N,
    E], all float32. Returns (y [B, T, E] without the D term, h after the T
    tokens)."""
    def one(h, t):
        xt, dtt, bt, ct = t
        h = (jnp.exp(dtt[:, None] * A) * h
             + (dtt * xt)[:, None] * bt[..., None])
        return h, jnp.sum(h * ct[..., None], axis=1)

    h, y = jax.lax.scan(one, h, tuple(
        jnp.moveaxis(a, 1, 0) for a in (x, dt, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1), h


def conv_step(tail, u, w, b):
    """`causal_conv` for ONE token on tails laid side by side: `tail [B,
    (K - 1) * C]` (the K - 1 inputs before this one, oldest first), `u [B,
    C]`, float32. Returns (out [B, C], the tail after this token). Every
    piece is whole lane tiles where C is: no input changes its layout."""
    K, C = w.shape[0], u.shape[-1]
    window = jnp.concatenate([tail, u], axis=1)  # [B, K * C]
    out = b + sum(window[:, k * C:(k + 1) * C] * w[k] for k in range(K))
    return out, window[:, C:]


class _RowTails(NamedTuple):
    """Where each batch row's state lies, and a layer's tails laid flat."""

    rows: jax.Array  # [B] state row of each batch row
    live: jax.Array  # [B] bool
    at: jax.Array  # [B] the row to read, inside the pool
    to: jax.Array  # [B] the row to write; past the pool for an idle row
    fresh: jax.Array  # [B] bool: at position 0, starting from nothing
    whole: bool  # batch row b IS state row b: slices, not gathers
    old: jax.Array  # [B, (K - 1) * C] what the pool holds
    tail: jax.Array  # ... and zeros where the row is fresh


def _flat_tails(cache: HybridCache, layer, B: int) -> _RowTails:
    """`_RowTails` of layer `layer`, whose tails lie in one piece a row."""
    rows, live = cache.state_rows()
    at = jnp.clip(rows, 0, cache.n_rows - 1)
    to = jnp.where(live, at, cache.n_rows)  # an idle row writes nowhere
    # a row at position 0 starts from nothing, whatever its last holder left
    fresh = cache.pos == 0
    assert cache.conv_rows == 1, "the row's tail lies in one piece"
    # batch row b holds state row b (a decode step, `generate`): the layer's
    # tails are a slice of the pool, not a gather
    whole = cache.rows is None and B == cache.n_rows
    old = cache.conv[layer] if whole else cache.conv[layer, at]
    tail = jnp.where(fresh[:, None], 0.0, old)  # [B, (K - 1) * C]
    return _RowTails(rows, live, at, to, fresh, whole, old, tail)


def _conv_from_tail(tail, u, w, b, end, one_token: bool):
    """The convolution of `u [B, T, C]` behind the flat `tail`: `conv_step`
    for a decode step's one token, else `causal_conv`. Returns (out [B, T,
    C], the flat tail after position `end` - 1)."""
    B, _, C = u.shape
    if one_token:
        x, tail = conv_step(tail, u[:, 0], w, b)
        return x[:, None], tail
    x, tail = causal_conv(tail.reshape(B, -1, C), u, w, b, end)
    return x, tail.reshape(B, -1)


def _put_tails(cache: HybridCache, layer, tail, r: _RowTails):
    """`cache.conv` with the live rows' new flat tails of layer `layer`."""
    if r.whole:
        return jax.lax.dynamic_update_slice(
            cache.conv, jnp.where(r.live[:, None], tail, r.old)[None],
            (layer, 0, 0))
    return cache.conv.at[layer, r.to].set(tail, mode="drop")


def tail_conv(cache: HybridCache, layer, g, w, *, decode: bool):
    """The state part of gated short-convolution layer `layer` (LFM2; index
    among the convolution layers) over this forward's T positions: the
    depthwise causal convolution of the gated input `g [B, T, C]` with `w
    [K, C]` (w[K - 1] weighs the current input), no bias, no activation,
    behind the row's tail, which is ALL the state such a layer has. A
    position that is no token contributes a zero and the tail is taken at
    the last real one. Returns (c [B, T, C] float32, the cache with the
    layer's tails updated)."""
    B, T, _ = g.shape
    valid = valid_positions(cache, T)
    g = jnp.where(valid[..., None], g.astype(jnp.float32), 0.0)
    end = (jnp.full((B,), T, jnp.int32) if cache.valid_len is None
           else cache.valid_len.astype(jnp.int32))
    r = _flat_tails(cache, layer, B)
    c, tail = _conv_from_tail(r.tail, g, w, 0.0, end, decode and T == 1)
    return c, dataclasses.replace(cache,
                                  conv=_put_tails(cache, layer, tail, r))


def why_not_scan_kernel(d_state: int, inner: int) -> Optional[str]:
    """None when a Mamba-1 layer takes the kernels of `selective_scan`."""
    from bigdl_tpu.ops.pallas import interpret_mode, why_not_pallas

    why = why_not_pallas()
    if why is None and inner % 128:
        why = f"inner width {inner} is not whole lane tiles"
    if why is None and d_state % 8 and not interpret_mode():
        why = f"d_state {d_state} is not whole sublanes"
    return why


def mix1(cache: HybridCache, layer, u, p, *, dt_rank: int, d_state: int,
         eps: float, decode: bool):
    """The state part of Mamba-1 layer `layer` (index among the Mamba
    layers) over this forward's T positions: the causal convolution and silu
    over the inner channels `u [B, T, E]`, the second projection `w_x` to
    [r | B | C], each through its own RMSNorm, dt = softplus(r w_dt + dt_bias)
    a CHANNEL, then the scan. `p` holds the layer's small weights: `conv_w
    [K, E]`, `conv_b`, `w_x [R + 2 N, E]`, `dt_norm`, `b_norm`, `c_norm`,
    `w_dt [E, R]`, `dt_bias`, `a [N, E]` (A = -a) and `D [E]`. Returns
    (y [B, T, E] float32 with the D term, the cache with the layer's rows
    updated)."""
    from bigdl_tpu.ops import rms_norm, routes

    B, T, E = u.shape
    R, N = dt_rank, d_state
    f32 = jnp.float32
    valid = valid_positions(cache, T)
    u = jnp.where(valid[..., None], u.astype(f32), 0.0)
    end = (jnp.full((B,), T, jnp.int32) if cache.valid_len is None
           else cache.valid_len.astype(jnp.int32))
    r = _flat_tails(cache, layer, B)
    rows, live, at, to, fresh = r[:5]
    x, tail = _conv_from_tail(r.tail, u, p["conv_w"], p["conv_b"], end,
                              decode and T == 1)
    x = jax.nn.silu(x)
    conv = _put_tails(cache, layer, tail, r)

    def proj(a, w):  # bf16 operands as `linear`'s, float32 sums
        return jnp.einsum("bti,oi->bto", a.astype(w.dtype), w,
                          preferred_element_type=f32)

    rbc = proj(x, p["w_x"])
    r = rms_norm(rbc[..., :R], p["dt_norm"], eps)
    Bm = rms_norm(rbc[..., R:R + N], p["b_norm"], eps)
    Cm = rms_norm(rbc[..., R + N:], p["c_norm"], eps)
    dt = jax.nn.softplus(proj(r, p["w_dt"]) + p["dt_bias"])
    dt = jnp.where(valid[..., None], dt, 0.0)
    A = -p["a"].astype(f32)
    detail = f"B{B} T{T} E{E} N{N}"
    why = why_not_scan_kernel(N, E)
    one_token = decode and T == 1
    with contextlib.ExitStack() as under:
        if not one_token:
            under.enter_context(scope("mamba2_prefill"))
        under.enter_context(
            scope("mamba1_decode" if one_token else "mamba1_prefill"))
        if why is None and one_token:
            from bigdl_tpu.ops.pallas.selective_scan import mamba1_decode

            routes.note("mamba1", "pallas", detail + " decode")
            y, ssm = mamba1_decode(cache.ssm, layer, rows, live, x[:, 0],
                                   dt[:, 0], A, Bm[:, 0], Cm[:, 0])
            y = y[:, None]
        elif why is None and B == 1:
            from bigdl_tpu.ops.pallas.selective_scan import mamba1_prefill

            routes.note("mamba1", "pallas", detail + " prefill")
            y, ssm = mamba1_prefill(cache.ssm, layer, at[0], fresh[0],
                                    end[0], x[0], dt[0], A, Bm[0], Cm[0])
            y = y[None]
        else:
            routes.note("mamba1", "xla", f"{detail} ({why or 'B > 1'})")
            h = jnp.where(fresh[:, None, None], 0.0, cache.ssm[layer, at])
            y, h = scan1(x, dt, A, Bm, Cm, h)
            ssm = cache.ssm.at[layer, to].set(h, mode="drop")
    y = y + p["D"] * x
    return y, dataclasses.replace(cache, conv=conv, ssm=ssm)


# ---------------------------------------------------------------------------
# Kimi delta attention (KDA): a gated delta rule's state arithmetic
# ---------------------------------------------------------------------------

KDA_CHUNK = 64  # tokens of one chunk of the prefill's form
KDA_SUB = 16  # ... and of one sub-block of its decay products


def kda_step(q, k, v, g, beta, S):
    """One token in `jnp`. q, k [B, H, N] (q scaled, both L2-normalised), v
    [B, H, P], g [B, H, N] <= 0 the log-decay a KEY CHANNEL, beta [B, H], S
    [B, H, P, N] (the value index before the key index, as stored), all
    float32. The state decays, is READ at k, takes the corrected rank-one
    update and is read again at q. Returns (o [B, H, P], S)."""
    S = S * jnp.exp(g)[:, :, None, :]
    r = jnp.einsum("bhpn,bhn->bhp", S, k, precision=_HI)
    S = S + (beta[..., None] * (v - r))[..., None] * k[:, :, None, :]
    return jnp.einsum("bhpn,bhn->bhp", S, q, precision=_HI), S


def _kda_pairs(a, k, G, strict: bool):
    """[H, C, C] float32: sum_n a_i[n] k_j[n] exp(G_i[n] - G_j[n]) for j < i
    (`strict`) or j <= i, else 0, over one chunk. a, k, G [C, H, N], G the
    running sum of the log-decays, so every exponent taken is of a
    difference with i >= j and is <= 0: inside a sub-block of `KDA_SUB`
    positions the differences are formed pair by pair; a key before the
    query's sub-block is reached in two factors through that sub-block's
    FIRST row r, exp(G_i - G_r) exp(G_r - G_j) with j < r <= i, neither of
    which can grow."""
    C, H, N = k.shape
    s = min(KDA_SUB, C)
    nb = C // s
    blk = lambda x: x.reshape(nb, s, H, N)  # noqa: E731
    ab, kb, Gb = blk(a), blk(k), blk(G)
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    keep = (j < i) if strict else (j <= i)
    diff = Gb[:, :, None] - Gb[:, None, :]  # [nb, s(i), s(j), H, N]
    diag = jnp.sum(
        ab[:, :, None] * kb[:, None, :]
        * jnp.exp(jnp.where(keep[None, :, :, None, None], diff, -jnp.inf)),
        axis=-1)  # [nb, s, s, H]
    G0 = Gb[:, 0]  # [nb, H, N]: each sub-block's first row
    before = (jnp.arange(C)[None, :] < (jnp.arange(nb) * s)[:, None])
    far = k[None] * jnp.exp(jnp.where(
        before[:, :, None, None], G0[:, None] - G[None], -jnp.inf))
    off = jnp.einsum("bihn,bjhn->hbij", ab * jnp.exp(Gb - G0[:, None]), far,
                     precision=_HI)  # [H, nb, s, C]
    out = off.reshape(H, C, C)
    eye = jnp.eye(nb, dtype=diag.dtype)
    # the diagonal sub-blocks, each at its own place
    return out + jnp.einsum("bijh,bc->hbicj", diag, eye).reshape(H, C, C)


def kda_chunked(q, k, v, g, beta, S, chunk: int = KDA_CHUNK):
    """The chunked form of the delta rule over T tokens of ONE sequence from
    the state `S`. q, k, g [T, H, N], v [T, H, P], beta [T, H] (g = 0 and
    beta = 0 where the position is no token), S [H, P, N], all float32.
    With G the running sum of g inside a chunk and S_0 the state at its
    start (written S[n, p] here; stored the other way round):

        A_ij = sum_n k_i[n] k_j[n] exp(G_i[n] - G_j[n])         i > j
        (I + diag(beta) A) U = diag(beta) (V - (K * exp(G)) S_0)
        o_i = (q_i * exp(G_i)) S_0 + sum_{j <= i} QK_ij u_j
        S_C = diag(exp(G_C)) S_0 + sum_j (k_j * exp(G_C - G_j)) u_j^T

    What does not read the state (A, QK, the solve against V and against
    K * exp(G)) is made for every chunk first; the walk over the chunks is
    then four products a chunk. Returns (o [T, H, P], S after the T
    tokens)."""
    from jax.scipy.linalg import solve_triangular

    T, H, N = k.shape
    C = min(chunk, -(-T // KDA_SUB) * KDA_SUB)
    pad = -T % C
    if pad:  # g = 0, beta = 0: a padded position neither decays nor updates
        q, k, v, g, beta = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                            for a in (q, k, v, g, beta))
    n = (T + pad) // C
    eye = jnp.eye(C, dtype=jnp.float32)

    def prepare(xs):  # one chunk, nothing of it reads the state
        qc, kc, vc, gc, bc = xs
        G = jnp.cumsum(gc, axis=0)  # [C, H, N], inclusive
        bh = bc.T[:, :, None]  # [H, C, 1]
        lower = eye + bh * _kda_pairs(kc, kc, G, strict=True)
        kg = kc * jnp.exp(G)
        rhs = jnp.concatenate([jnp.moveaxis(vc, 1, 0),
                               jnp.moveaxis(kg, 1, 0)], axis=-1) * bh
        w = solve_triangular(lower, rhs, lower=True, unit_diagonal=True)
        return (w[..., :vc.shape[-1]], w[..., vc.shape[-1]:],
                _kda_pairs(qc, kc, G, strict=False),
                jnp.moveaxis(qc * jnp.exp(G), 1, 0),
                jnp.moveaxis(kc * jnp.exp(G[-1:] - G), 1, 0),
                jnp.exp(G[-1]))

    def chunks(a):  # [n * C, ...] -> [n, C, ...]
        return a.reshape(n, C, *a.shape[1:])

    parts = jax.lax.map(prepare, tuple(map(chunks, (q, k, v, g, beta))),
                        batch_size=min(n, 8))

    def one(S, xs):
        wv, wk, qk, qg, kend, dec = xs  # [H, C, P] [H, C, N] [H, C, C] ...
        u = wv - jnp.einsum("hcn,hpn->hcp", wk, S, precision=_HI)
        o = (jnp.einsum("hcn,hpn->hcp", qg, S, precision=_HI)
             + jnp.einsum("hij,hjp->hip", qk, u, precision=_HI))
        S = dec[:, None, :] * S + jnp.einsum("hcp,hcn->hpn", u, kend,
                                             precision=_HI)
        return S, o

    S, o = jax.lax.scan(one, S, parts)  # o [n, H, C, P]
    return jnp.moveaxis(o, 1, 2).reshape(n * C, H, -1)[:T], S


def why_not_kda_kernel(d_head: int, inner: int) -> Optional[str]:
    """None when a decode step takes `kda_decode`."""
    from bigdl_tpu.ops.pallas import interpret_mode, why_not_pallas
    from bigdl_tpu.ops.pallas.mamba2 import CHUNK

    why = why_not_pallas()
    if why is None and d_head != CHUNK:
        why = f"a head of {d_head} is not a chunk of {CHUNK} rows"
    if why is None and inner % CHUNK and not interpret_mode():
        why = f"inner width {inner} is not whole chunks of {CHUNK} rows"
    return why


def kda_mix(cache: HybridCache, layer, qkv, g, beta, conv_w, *,
            n_heads: int, d_head: int, decode: bool):
    """The state part of KDA layer `layer` (index among the KDA layers) over
    this forward's T positions: the three depthwise causal convolutions (one
    over `qkv [B, T, 3 * H * D]`, the projections q | k | v side by side,
    `conv_w [K, 3 * H * D]`, no bias) and silu, the L2 norm of a head's q
    and k and q's 1 / sqrt(D), then the delta rule. `g [B, T, H, D]` <= 0
    the log-decay a key channel, `beta [B, T, H]`, both float32; a position
    that is no token gets g = 0 and beta = 0 here and neither decays nor
    updates. Returns (o [B, T, H, D] float32, the cache with the layer's
    rows updated)."""
    from bigdl_tpu.ops import routes

    B, T, _ = qkv.shape
    H, D = n_heads, d_head
    inner = H * D
    valid = valid_positions(cache, T)
    qkv = jnp.where(valid[..., None], qkv.astype(jnp.float32), 0.0)
    g = jnp.where(valid[..., None, None], g, 0.0)
    beta = jnp.where(valid[..., None], beta, 0.0)
    end = (jnp.full((B,), T, jnp.int32) if cache.valid_len is None
           else cache.valid_len.astype(jnp.int32))
    one_token = decode and T == 1
    r = _flat_tails(cache, layer, B)
    with scope("short_conv"):
        x, tail = _conv_from_tail(r.tail, qkv, conv_w, 0.0, end, one_token)
        x = jax.nn.silu(x).reshape(B, T, 3, H, D)
        conv = _put_tails(cache, layer, tail, r)

        def unit(a):  # a / max(|a|, 1e-6) over a head's lanes
            return a * jax.lax.rsqrt(jnp.maximum(
                jnp.sum(a * a, axis=-1, keepdims=True), 1e-12))

        q, k, v = unit(x[:, :, 0]) * D ** -0.5, unit(x[:, :, 1]), x[:, :, 2]
    detail = f"B{B} T{T} H{H} D{D} state float32"
    why = why_not_kda_kernel(D, inner)
    if one_token and why is None:
        from bigdl_tpu.ops.pallas.mamba2 import kda_decode

        routes.note("kda", "pallas:kda_decode", detail)
        with scope("kda_decode"):
            o, ssm = kda_decode(cache.ssm, layer, r.rows, r.live, q[:, 0],
                                k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
        return o[:, None], dataclasses.replace(cache, conv=conv, ssm=ssm)
    routes.note("kda", "xla", detail + (
        f" step ({why})" if one_token else f" chunked prefill C{KDA_CHUNK}"))
    S = jnp.where(r.fresh[:, None, None, None], 0.0,
                  cache.ssm[layer, r.at].reshape(B, H, D, D))
    if one_token:
        with scope("kda_decode"):
            o, S = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], S)
            o = o[:, None]
    else:
        with scope("kda_prefill"):
            o, S = jax.vmap(kda_chunked)(q, k, v, g, beta, S)
    ssm = cache.ssm.at[layer, r.to].set(S.reshape(B, inner, D), mode="drop")
    return o, dataclasses.replace(cache, conv=conv, ssm=ssm)


def _rows_axis(conv) -> int:
    """`HybridCache.conv_rows` of a pool's `conv` leaf: `[Ls, K - 1, R, C]`
    keeps the rows on axis 2, a tail in one piece `[Ls, R, (K - 1) * C]` on
    axis 1."""
    return 2 if conv.ndim == 4 else 1


def prefill_chunks(n_tokens: int, chunk: int) -> int:
    """Chunks of the prefill form over `n_tokens` (a span's argument)."""
    return -(-n_tokens // min(chunk, max(n_tokens, 1)))


# ---------------------------------------------------------------------------
# the cache kind (kvpaged.CacheKind): pages, and the slot's own state row
# ---------------------------------------------------------------------------

class _StateBesidePages(kvpaged.CacheKind):
    name = label = KIND
    arrays = ("k", "v", "conv", "ssm")
    page_arrays = ("k", "v")
    needs_paged = (
        "{kind} is served with paged=True: a slot holds KV pages for the "
        "attention layers and a state row for the others")
    refuses = kvpaged.not_wired("R4", "quantize_kv", "speculative",
                                "adapters")
    # a prefix hit would need the state at the prefix's end
    share_prefixes = tp_sharded = False
    make_pool = kvpaged.CacheKind._family_pool
    metrics = staticmethod(kvstate.state_metrics)

    def row_view(self, leaves, tables, pos0, last_idx, slot, cfg, geo):
        """The pool itself behind the slot's one-row table and its state
        row `slot`: keys and values go into the row's pages, the state runs
        from nothing when `pos0` is 0 and else from the row's own; the
        positions past `last_idx` leave the state and the convolution's
        tail as they were."""
        cache = HybridCache(
            **dict(zip(self.arrays, leaves)), block_tables=tables[0],
            pos=pos0, start=jnp.zeros((1,), jnp.int32), rows=slot,
            valid_len=last_idx[None] + 1, conv_rows=_rows_axis(leaves[2]))
        return cache, cache

    def write_back(self, pool, row, n_tokens, last_idx, cfg):
        return self.leaves(row)

    axes = (1, 1, 2, 1)

    def axes_of(self, cache):
        return (1, 1, cache.conv_rows, 1)

    def _spots(self, pages, slot, window_pages):
        return pages, pages, slot, slot  # the pages, and the slot's own row

    def state_row_nbytes(self, cache):
        return row_nbytes(cache)

    def note_chunk(self, st, cfg, geo, bucket, n, pool):
        """What the family's prefill form counts of a chunk
        (`HybridCache.counts`): the chunks of a chunked form over the
        bucket, the tokens a scan walked, nothing for a tail alone."""
        if pool.counts:
            name, *chunk = pool.counts
            by = prefill_chunks(bucket, *chunk) if chunk else n
            setattr(st, name, getattr(st, name) + by)

    def prefill_args(self, st):
        return {name: getattr(st, name)
                for name in ("state_chunks", "scan_tokens")
                if getattr(st, name)}

    def decode_args(self, cfg, table, live, moved, pool):
        return {**kvstate.state_decode_args(live, moved),
                **super().decode_args(cfg, table, live, moved, pool)}


CACHE_KIND = _StateBesidePages()
