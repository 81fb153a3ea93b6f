"""Selected pages beside a state: the cache of a model whose layers are
block-sparse softmax attention or lightning (decayed linear) attention.

Beside `kvhybrid.py` (KV pages for some layers, a Mamba-2 state row a slot
for the others), whose cache kind, rows and padding rules this file keeps:
MiniCPM-SALA (`models/minicpm_sala.py`) runs a few `minicpm4` layers between
many `lightning-attn` layers. One slot holds

    k, v  [Ls, n_pages, page, Hkv, D]            bf16, as kvpaged's
    kp    [Ls, n_pages, page / stride, Hkv, D]   bf16 POOLED keys
    state [Ll, R, heads * head size, head size]  float32, a slot's own row

**Lightning attention**, a head h with decay lam_h = exp(-s_h) (`slopes`):

    S_t = lam_h S_(t-1) + k_t^T v_t        o_t = (q_t / sqrt(D)) S_t

kept transposed (`state[.., h * D + p, n]` = S[n, p]: the value index on
sublanes, the key index on lanes), which is `kvhybrid`'s recurrence with
dt = 1, x = v, B = k, C = q A HEAD. A decode step runs it through the Pallas
kernel `lightning_decode` (ops/pallas/mamba2.py, `mamba2_decode`'s sibling)
where the kernels are in use, else in `jnp`; a prefill runs the chunked form
(`lightning_chunked`) on the XLA route under the scope `lightning_prefill`.
Decay powers and the state are float32. A position that is no token neither
decays nor updates the state.

**Block-sparse attention** (InfLLM-v2's selection; `sparse_config`: windows
of `kernel_size` = 2 `kernel_stride` keys, blocks of `block_size` = the page,
`topk`, `init_blocks`, `window_size`, `dense_len`). Window j is the mean of
the cached keys 16j .. 16j + 31 of a KV head; it lives in the page of its
first token (`kp[.., j // 4, j % 4]`) and is written when token 16j + 31 is:
by the prefill for the prompt's windows, by the decode step whose token ends
one. A query at position t with t + 1 >= dense_len scores every complete
window (softmax over them a query head, summed over the KV head's query
heads), a block by the best window that overlaps it (j = 4m - 1 .. 4m + 3),
and reads block 0, the blocks of the local window and the best of the rest,
`topk` in all, a KV head. Shorter rows read all their pages: the switch is a
row's own, inside one program.

A decode step hands the kernel `paged_sparse_decode_attention` a LIST of
pages a row: the UNION of its KV heads' choices in ascending order (a page's
DMA brings every head's half), and a bit a head and listed page. A prefill
(a whole prompt from an empty row: this kind refuses chunks and shared
prefixes) computes the same attention exactly: the selection as a mask a
query, KV head and key (`prefill_selection`, in query chunks under the scope
`sparse_select`, the queries under `dense_len` free) handed to
`flash_attention` beside its causal bound, a tile a grid step.

What a step chose leaves with the cache (`report [B, W]`: five counts a
row and, only where `CACHE_KIND.report_ids` asked before the engine was
built, the chosen blocks of every sparse layer and KV head in front of
them), for the engine's one fetch a step.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu import kvhybrid, kvpaged, kvstate
from bigdl_tpu.obs.scopes import scope

KIND = "selected_pages_beside_state"
_HI = jax.lax.Precision.HIGHEST
_NEG = -1e30
N_COUNTS = 5  # the report's tail: `DECODE_COUNTS` (a prefill's: two)
QUERY_CHUNK = 256  # queries of one chunk of the prefill's selection


@dataclasses.dataclass(frozen=True)
class Sizes:
    """`sparse_config` as the selection reads it, in tokens and blocks."""

    stride: int
    block: int
    topk: int
    init_blocks: int
    window_blocks: int
    dense_len: int

    @classmethod
    def of(cls, config) -> "Sizes":
        c = dict(config.sparse_config)
        return cls(c["kernel_stride"], c["block_size"], c["topk"],
                   c["init_blocks"], c["window_size"] // c["block_size"],
                   c["dense_len"])

    @property
    def per_block(self) -> int:  # windows that START in one block
        return self.block // self.stride

    @property
    def forced(self) -> int:  # blocks a long row always reads
        return self.init_blocks + self.window_blocks + 1


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SparseCache:
    k: jax.Array  # [Ls, n_pages, page, Hkv, D]
    v: jax.Array
    kp: jax.Array  # [Ls, n_pages, page / stride, Hkv, D] pooled keys
    state: jax.Array  # [Ll, R, heads * head size, head size] float32
    block_tables: jax.Array  # [B, max_pages] int32, 0 = nobody's page
    pos: jax.Array  # [B] int32 next slot per row
    start: jax.Array  # [B] int32 first valid slot (left padding)
    report: jax.Array  # [B, W] int32: what the last forward chose
    rows: Optional[jax.Array] = None  # as kvhybrid.HybridCache's
    valid_len: Optional[jax.Array] = None

    @property
    def page_size(self) -> int:
        return self.k.shape[2]

    @property
    def max_len(self) -> int:
        return self.block_tables.shape[1] * self.page_size

    @property
    def n_rows(self) -> int:
        return self.state.shape[1]

    @property
    def kv(self) -> kvpaged.PagedKVCache:
        return kvpaged.PagedKVCache(
            k=self.k, v=self.v, block_tables=self.block_tables, pos=self.pos,
            start=self.start)

    state_rows = kvhybrid.HybridCache.state_rows


def report_width(n_sparse: int, n_kv: int, topk: int,
                 ids: bool = False) -> int:
    """The counts and, where asked (`CACHE_KIND.report_ids`), the ids."""
    return n_sparse * n_kv * topk * ids + N_COUNTS


def init_sparse(n_sparse: int, n_lightning: int, n_pages: int, page_size: int,
                n_kv_heads: int, head_dim: int, rows: int,
                max_pages_per_row: int, inner: int, d_state: int,
                stride: int, topk: int, batch: Optional[int] = None,
                dtype=jnp.bfloat16, report_ids: bool = False) -> SparseCache:
    """Zeros: pages nobody holds and `rows` state rows."""
    b = rows if batch is None else batch
    kv = (n_sparse, n_pages, page_size, n_kv_heads, head_dim)
    kp = (n_sparse, n_pages, page_size // stride, n_kv_heads, head_dim)
    return SparseCache(
        k=jnp.zeros(kv, dtype), v=jnp.zeros(kv, dtype),
        kp=jnp.zeros(kp, dtype),
        state=jnp.zeros((n_lightning, rows, inner, d_state), jnp.float32),
        block_tables=jnp.zeros((b, max_pages_per_row), jnp.int32),
        pos=jnp.zeros((b,), jnp.int32), start=jnp.zeros((b,), jnp.int32),
        report=jnp.zeros(
            (b, report_width(n_sparse, n_kv_heads, topk, report_ids)),
            jnp.int32))


def row_nbytes(cache: SparseCache) -> int:
    """Bytes of ONE state row over all lightning layers."""
    return cache.state.size // cache.n_rows * 4


valid_positions = kvhybrid.valid_positions


advance = kvhybrid.advance


# ---------------------------------------------------------------------------
# lightning attention: the state's arithmetic
# ---------------------------------------------------------------------------

def slopes(n_heads: int) -> np.ndarray:
    """s_h = 2^(-8 (h + 1) / H): the decay a token of head h is exp(-s_h)
    (the Lightning Attention paper's, ALiBi's form). A function of the
    config and no leaf of the parameter tree."""
    return (2.0 ** (-8.0 * np.arange(1, n_heads + 1) / n_heads)).astype(
        np.float32)


def lightning_step(q, k, v, decay, h):
    """One token in `jnp`. q, k, v [B, H, D] float32 (q scaled), decay [H],
    h [B, H, P, N]. Returns (y [B, H, P], h)."""
    h = decay[:, None, None] * h + v[..., :, None] * k[..., None, :]
    return jnp.einsum("bhpn,bhn->bhp", h, q, precision=_HI), h


def lightning_chunked(q, k, v, valid, s, h, chunk: int):
    """The chunked form over T tokens from the state `h`. q, k, v [B, T, H,
    D] float32 (q scaled), valid [B, T] bool, s [H] the slopes, h [B, H, P,
    N]. Inside a chunk token i sees j <= i through (q_i . k_j) lam^(i - j),
    across chunks the state. Returns (y [B, T, H, P], h after the tokens)."""
    B, T, H, D = q.shape
    Q = min(chunk, T)
    pad = -T % Q
    step = valid.astype(jnp.float32)
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
        step = jnp.pad(step, ((0, 0), (0, pad)))
    n = (T + pad) // Q

    def chunks(a):  # [B, n * Q, ...] -> [n, B, Q, ...]
        return jnp.moveaxis(a.reshape(B, n, Q, *a.shape[2:]), 1, 0)

    causal = jnp.tril(jnp.ones((Q, Q), jnp.bool_))

    def one(h, xs):
        qc, kc, vc, dc = xs  # dc [B, Q]: 1 where the position is a token
        cum = jnp.cumsum(dc[..., None] * -s, axis=1)  # [B, Q, H], <= 0
        ch = jnp.moveaxis(cum, 1, 2)  # [B, H, Q]
        u = dc[..., None, None] * vc  # a padded position adds nothing
        qk = jnp.einsum("bthn,bshn->bhts", qc, kc, precision=_HI)
        decay = jnp.exp(jnp.where(
            causal, ch[..., :, None] - ch[..., None, :], -jnp.inf))
        y = jnp.einsum("bhts,bshp->bthp", qk * decay, u, precision=_HI)
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "bthn,bhpn->bthp", qc, h, precision=_HI)
        tail = jnp.exp(cum[:, -1:] - cum)  # [B, Q, H] decay to the end
        h = jnp.exp(cum[:, -1])[..., None, None] * h + jnp.einsum(
            "bshp,bshn->bhpn", u * tail[..., None], kc, precision=_HI)
        return h, y

    h, y = jax.lax.scan(one, h, tuple(map(chunks, (q, k, v, step))))
    return jnp.moveaxis(y, 0, 1).reshape(B, n * Q, H, D)[:, :T], h


def why_not_state_kernel(head_dim: int, inner: int) -> Optional[str]:
    """None when a decode step takes `lightning_decode`."""
    from bigdl_tpu.ops.pallas import why_not_pallas
    from bigdl_tpu.ops.pallas.mamba2 import CHUNK

    why = why_not_pallas()
    if why is None and head_dim != CHUNK:
        why = f"a head of {head_dim} is not one chunk of {CHUNK} rows"
    return why


def lightning_mix(cache: SparseCache, layer, q, k, v, *, chunk: int,
                  decode: bool):
    """Lightning layer `layer` (index among the lightning layers) over this
    forward's T positions. q, k, v [B, T, H, D] after the norms and the
    rope; the scale 1 / sqrt(D) is applied here. Returns (o [B, T, H, D]
    float32, the cache with the layer's rows updated); the state is float32
    throughout."""
    from bigdl_tpu.ops import routes

    B, T, H, D = q.shape
    inner = H * D
    q = q.astype(jnp.float32) * D ** -0.5
    k, v = k.astype(jnp.float32), v.astype(jnp.float32)
    s = jnp.asarray(slopes(H))
    valid = valid_positions(cache, T)
    rows, live = cache.state_rows()
    at = jnp.clip(rows, 0, cache.n_rows - 1)
    to = jnp.where(live, at, cache.n_rows)  # an idle row writes nowhere
    fresh = (cache.pos == 0)[:, None, None, None]
    detail = f"B{B} T{T} H{H} D{D} state float32"
    why = why_not_state_kernel(D, inner)
    if decode and T == 1 and why is None:
        from bigdl_tpu.ops.pallas.mamba2 import lightning_decode

        routes.note("lightning", "pallas:lightning_decode", detail)
        with scope("lightning_decode"):
            y, state = lightning_decode(cache.state, layer, rows, live,
                                        v[:, 0], jnp.exp(-s), k[:, 0],
                                        q[:, 0])
        return y[:, None], dataclasses.replace(cache, state=state)
    routes.note("lightning", "xla", detail + (
        f" ({why})" if decode and T == 1 else " chunked prefill"))
    h = jnp.where(fresh, 0.0, cache.state[layer, at].reshape(B, H, D, D))
    if decode and T == 1:
        with scope("lightning_decode"):
            y, h = lightning_step(q[:, 0], k[:, 0], v[:, 0], jnp.exp(-s), h)
            y = y[:, None]
    else:
        with scope("lightning_prefill"):
            y, h = lightning_chunked(q, k, v, valid, s, h, chunk)
    state = cache.state.at[layer, to].set(h.reshape(B, inner, D),
                                          mode="drop")
    return y, dataclasses.replace(cache, state=state)


prefill_chunks = kvhybrid.prefill_chunks


# ---------------------------------------------------------------------------
# block-sparse attention: pooled keys, the selection, the page list
# ---------------------------------------------------------------------------

def pooled_windows(k, stride: int):
    """The means of every window of 2 * stride keys that starts at a
    multiple of `stride`. k [T, Hkv, D] (T whole strides) -> [T / stride,
    Hkv, D] float32; the last window reaches past T and is no window."""
    T = k.shape[0]
    part = k.astype(jnp.float32).reshape(T // stride, stride, *k.shape[1:])
    part = part.sum(axis=1)
    nxt = jnp.concatenate([part[1:], jnp.zeros_like(part[:1])], axis=0)
    return (part + nxt) / (2 * stride)


def complete_windows(k, n_valid, sz: Sizes):
    """(`pooled_windows` of k [T, Hkv, D] with the windows that the row's
    `n_valid` tokens do not fill zero, how many they do fill)."""
    windows = pooled_windows(k, sz.stride)
    n_done = jnp.maximum((n_valid - 2 * sz.stride) // sz.stride + 1, 0)
    done = jnp.arange(windows.shape[0]) < n_done
    return jnp.where(done[:, None, None], windows, 0.0), n_done


def block_scores(r, sz: Sizes):
    """r [.., W] a window's summed probability (< 0 where it is none) ->
    [.., W / per_block] the best of the windows that overlap each block: the
    block's own `per_block` and the last of the block before."""
    pb = sz.per_block
    own = r.reshape(*r.shape[:-1], -1, pb)
    before = jnp.concatenate([
        jnp.full_like(own[..., :1, -1], -1.0), own[..., :-1, -1]], axis=-1)
    return jnp.maximum(own.max(axis=-1), before)


def forced_blocks(m, cur, sz: Sizes):
    """Of blocks `m`, those a query in block `cur` always reads: the
    initial ones and its local window's."""
    return (m < sz.init_blocks) | (m >= cur - sz.window_blocks)


def choose_blocks(score, t, sz: Sizes):
    """The selection of queries at positions `t [..]` from block scores
    `score [.., Hkv, M]`: (ids [.., Hkv, topk] int32 ascending by score
    rank, -1 where fewer exist; member [.., Hkv, M] bool). Block 0 .. and
    the local window's blocks are always taken (`forced_blocks`). A query
    with t + 1 < dense_len takes every block up to its own (ids all -1)."""
    M = score.shape[-1]
    m = jnp.arange(M, dtype=jnp.int32)
    cur = (t // sz.block)[..., None, None]
    exists = m <= cur
    forced = forced_blocks(m, cur, sz)
    # a forced block first (the nearer the sooner), then by score
    key = jnp.where(forced, 1e4 + m.astype(jnp.float32), score)
    key = jnp.where(exists, key, -jnp.inf)
    k = min(sz.topk, M)
    vals, ids = jax.lax.top_k(key, k)
    ids = jnp.where(vals > -jnp.inf, ids, -1).astype(jnp.int32)
    member = jnp.any(ids[..., None] == m, axis=-2)
    dense = (t + 1 < sz.dense_len)[..., None, None]
    member = jnp.where(dense, exists, member)
    ids = jnp.where(dense, -1, ids)
    if k < sz.topk:
        ids = jnp.pad(ids, [(0, 0)] * (ids.ndim - 1) + [(0, sz.topk - k)],
                      constant_values=-1)
    return ids, member


def select(q, windows, t, scale: float, sz: Sizes):
    """The blocks that queries `q [Q, Hkv, G, D]` at positions `t [Q]` read,
    from the pooled keys `windows [W, Hkv, D]` of their row (window j
    complete when 16j + 31 <= t). Returns `choose_blocks`' pair, [Q, Hkv,
    ..]."""
    W = windows.shape[0]
    s = jnp.einsum("qhgd,whd->qhgw", q.astype(jnp.float32),
                   windows.astype(jnp.float32), precision=_HI) * scale
    done = (jnp.arange(W) * sz.stride + 2 * sz.stride - 1
            <= t[:, None])[:, None, None, :]
    s = jnp.where(done, s, _NEG)
    p = jnp.where(done, jax.nn.softmax(s, axis=-1), 0.0)
    r = jnp.where(done[:, :, 0], p.sum(axis=2), -1.0)  # [Q, Hkv, W]
    return choose_blocks(block_scores(r, sz), t, sz)


def _masked_attention(q, k, v, allowed, scale):
    """q [Q, Hkv, G, D], k, v [S, Hkv, D], allowed [Q, Hkv, S] bool ->
    [Q, Hkv, G, D] float32: softmax over the allowed keys, bf16 operands as
    they are cached and float32 sums (a decode step without the kernel)."""
    s = jnp.einsum("qhgd,shd->qhgs", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(allowed[:, :, None, :], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("qhgs,shd->qhgd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


def prefill_selection(q, k, n_valid, scale: float, sz: Sizes):
    """One row's prompt from an empty row: q [T, Hq, D], k [T, Hkv, D] as
    cached (bf16). Returns (mask [Hkv, T, T] int8: the keys a query's
    SELECTION lets it read, all of them for a query under `dense_len`; the
    causal bound is the attention's own; the pooled keys [T' / stride, Hkv,
    D] float32 with the incomplete windows zero; how many are complete;
    ids [Hkv, topk] the selection of the last valid position). Queries under
    `dense_len` cost nothing; the others are scored in chunks of
    `QUERY_CHUNK`."""
    T, Hq, D = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    C = QUERY_CHUNK if T > QUERY_CHUNK else -(-T // sz.block) * sz.block
    pad = -T % C
    if pad:
        q, k = (jnp.pad(a, ((0, pad), (0, 0), (0, 0))) for a in (q, k))
    Tp = T + pad
    windows, n_done = complete_windows(k, n_valid, sz)
    # as the pages keep them, which is what a decode step will score
    cached = windows.astype(k.dtype)
    # every query before `split` reads all it may see
    split = min(max(sz.dense_len - 1, 0) // C * C, Tp)
    qc = q[split:].reshape(-1, C, Hkv, G, D)

    def one(xs):
        qi, i = xs
        t = split + i * C + jnp.arange(C, dtype=jnp.int32)
        ids, member = select(qi, cached, t, scale, sz)
        return ids, jnp.repeat(member.astype(jnp.int8), sz.block, axis=-1)

    ids, chosen = jax.lax.map(
        one, (qc, jnp.arange(qc.shape[0], dtype=jnp.int32)))
    chosen = jnp.moveaxis(chosen.reshape(Tp - split, Hkv, Tp), 1, 0)
    mask = jnp.concatenate(
        [jnp.ones((Hkv, split, Tp), jnp.int8), chosen], axis=1)[:, :T, :T]
    ids = jnp.concatenate([
        jnp.full((split, Hkv, sz.topk), -1, jnp.int32),
        ids.reshape(Tp - split, Hkv, sz.topk)])
    return mask, windows, n_done, ids[jnp.maximum(n_valid - 1, 0)]


def write_row(pool, layer, table_row, rows_of_pages):
    """`rows_of_pages [n, ...]` into pages `table_row[:n]` of layer `layer`
    of `pool [L, n_pages, ...]`, a page a `dynamic_update_slice` (a scatter
    into a pool whose KV heads do not fill a tile has XLA re-lay all of it,
    kvpaged.scatter_row_pages). Entries of the table past the row's
    allocation name page 0, the sink."""
    n = min(rows_of_pages.shape[0], table_row.shape[0])

    def put(i, pool):
        page = jax.lax.dynamic_slice_in_dim(rows_of_pages, i, 1, axis=0)
        return jax.lax.dynamic_update_slice(
            pool, page[None].astype(pool.dtype),
            (layer, table_row[i]) + (0,) * (pool.ndim - 2))

    return jax.lax.fori_loop(0, n, put, pool)


def sparse_prefill_layer(cache: SparseCache, layer, q, k, v, scale: float,
                         sz: Sizes, attend):
    """Sparse layer `layer` over a prompt's T positions FROM AN EMPTY ROW
    (`cache.pos` 0: this kind refuses chunks and shared prefixes): the keys,
    the values and the complete windows' pooled keys go into the row's
    pages, the attention is the model's own. `attend(q, k, v, mask)` is
    causal attention from slot `start` (the flash kernel or XLA's), `mask
    [B, Hkv, T, T]` int8 what a selection lets a query read beside (None for
    a prompt shorter than `dense_len`: every query reads all it may).
    Returns (o [B, T, Hq, D], cache, ids [B, Hkv, topk], counts [B, 2]:
    positions at or past `dense_len`, windows written)."""
    B, T, Hq, D = q.shape
    page = cache.page_size
    kc, vc = k.astype(cache.k.dtype), v.astype(cache.v.dtype)
    # the prompt's tokens lie at slots start .. n_valid - 1
    n_valid = jnp.broadcast_to(jnp.asarray(
        T if cache.valid_len is None else cache.valid_len, jnp.int32), (B,))
    pad = -T % page
    outs, pools = [], (cache.k, cache.v, cache.kp)
    all_dense = T < sz.dense_len
    for b in range(B):
        if all_dense:
            windows, n_done = complete_windows(
                jnp.pad(kc[b], ((0, pad), (0, 0), (0, 0))), n_valid[b], sz)
            mask = None
            ids = jnp.full((kc.shape[2], sz.topk), -1, jnp.int32)
        else:
            with scope("sparse_select"):
                mask, windows, n_done, ids = prefill_selection(
                    q[b], kc[b], n_valid[b], scale, sz)
        paged = [jnp.pad(a, ((0, pad), (0, 0), (0, 0))).reshape(
            -1, page, *a.shape[1:]) for a in (kc[b], vc[b])]
        windows = windows[:(T + pad) // sz.stride]
        paged.append(windows.reshape(-1, page // sz.stride,
                                     *windows.shape[1:]))
        pools = tuple(write_row(pool, layer, cache.block_tables[b], rows)
                      for pool, rows in zip(pools, paged))
        n_sparse = jnp.maximum(n_valid[b] - (sz.dense_len - 1), 0)
        outs.append((mask, ids,
                     jnp.stack([n_sparse, n_done]).astype(jnp.int32)))
    masks, ids, counts = zip(*outs)
    o = attend(q, kc, vc, None if all_dense else jnp.stack(masks))
    cache = dataclasses.replace(cache, k=pools[0], v=pools[1], kp=pools[2])
    return o, cache, jnp.stack(ids), jnp.stack(counts)


def why_not_sparse_kernel(n_kv: int, head_dim: int, itemsize: int
                          ) -> Optional[str]:
    """None when a decode step takes `paged_sparse_decode_attention`."""
    from bigdl_tpu.ops.pallas import interpret_mode, why_not_pallas
    from bigdl_tpu.ops.pallas.paged_attention import pool_tiles_whole

    why = why_not_pallas()
    if (why is None and not interpret_mode()
            and not pool_tiles_whole(n_kv, head_dim, itemsize)):
        why = (f"a pool of {n_kv} KV heads of {head_dim} is not laid out in "
               "whole tiles")
    return why


def list_width(sz: Sizes, n_kv: int, max_pages: int) -> int:
    """Entries of a row's page list: the most a union of `n_kv` selections
    can hold (they share the forced blocks), or a row below `dense_len`."""
    union = n_kv * sz.topk - (n_kv - 1) * min(sz.forced, sz.topk)
    return min(max(union, sz.dense_len // sz.block), max_pages)


def sparse_decode_layer(cache: SparseCache, layer, q, k, v, scale: float,
                        sz: Sizes, use_kernel: bool, live):
    """Sparse layer `layer` for one token a row: q [B, Hq, D], k, v [B, Hkv,
    D]. Writes the token's key and value, and the pooled key of the window
    the token ends (if it ends one); scores the row's windows, chooses, and
    attends over the chosen pages in place. Returns (o [B, Hq, D], cache,
    ids [B, Hkv, topk], counts [B, 5]: selected (head, page) pairs, distinct
    pages read, is the row dense, windows written, live pages)."""
    B, Hq, D = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    page, mp = cache.page_size, cache.block_tables.shape[1]
    pb = sz.per_block
    pos = cache.pos  # the slot of the current token
    kv = kvpaged.update_layer(cache.kv, layer, k[:, None], v[:, None])
    cache = dataclasses.replace(cache, k=kv.k, v=kv.v)
    # the window this token ends: tokens pos - 31 .. pos
    span = 2 * sz.stride
    j = (pos - (span - 1)) // sz.stride
    ends = live & (pos >= span - 1) & ((pos - (span - 1)) % sz.stride == 0)
    s = jnp.maximum(pos[:, None] - (span - 1)
                    + jnp.arange(span, dtype=jnp.int32)[None], 0)
    phys = jnp.take_along_axis(cache.block_tables, s // page, axis=1)
    keys = cache.k[layer, phys, s % page].astype(jnp.float32)  # [B, 32, ..]
    mean = keys.mean(axis=1).astype(cache.kp.dtype)
    jp = jnp.clip(j // pb, 0, mp - 1)
    to = jnp.where(ends, jnp.take_along_axis(
        cache.block_tables, jp[:, None], axis=1)[:, 0], 0)  # else the sink
    kp = cache.kp.at[layer, to, jnp.maximum(j, 0) % pb].set(mean)
    cache = dataclasses.replace(cache, kp=kp)

    with scope("sparse_select"):
        # (the row's pages out of the pool in one gather: a layer sliced
        # out first is a copy of the layer, re-laid twice)
        windows = kp[layer, cache.block_tables].reshape(B, mp * pb, Hkv, D)
        qg = q.reshape(B, 1, Hkv, G, D)
        ids, member = jax.vmap(
            lambda qb, wb, tb: select(qb, wb, tb[None], scale, sz))(
                qg, windows, pos)
        ids, member = ids[:, 0], member[:, 0]  # [B, Hkv, topk], [B, Hkv, mp]
        member = member & live[:, None, None]
        union = jnp.any(member, axis=1)  # [B, mp]
        U = list_width(sz, Hkv, mp)
        order = jnp.argsort(jnp.logical_not(union), axis=1,
                            stable=True)[:, :U].astype(jnp.int32)
        n_listed = jnp.minimum(union.sum(axis=1), U).astype(jnp.int32)
        reads = jnp.take_along_axis(
            member, jnp.broadcast_to(order[:, None], (B, Hkv, U)), axis=2)
        reads = reads & (jnp.arange(U)[None, None] < n_listed[:, None, None])
        dense = live & (pos + 1 < sz.dense_len)
        counts = jnp.stack([
            member.sum(axis=(1, 2)), n_listed, dense, ends,
            jnp.where(live, pos // page + 1, 0)], axis=1).astype(jnp.int32)
    if use_kernel:
        from bigdl_tpu.ops.pallas.paged_attention import (
            paged_sparse_decode_attention,
        )

        page_list = jnp.take_along_axis(cache.block_tables, order, axis=1)
        o = paged_sparse_decode_attention(
            q, cache.k, cache.v, page_list, n_listed, reads, layer,
            pos % page + 1, scale=scale, live=live)
    else:
        kf, vf = kvpaged.read_layer(cache.kv, layer, cache.k.dtype)
        slot = jnp.arange(mp * page, dtype=jnp.int32)
        allowed = (jnp.repeat(member, page, axis=-1)
                   & (slot[None, None] <= pos[:, None, None])
                   & (slot[None, None] >= cache.start[:, None, None]))
        o = jax.vmap(lambda qb, kb, vb, ab: _masked_attention(
            qb, kb, vb, ab[None], scale))(
                q.reshape(B, 1, Hkv, G, D).astype(kf.dtype), kf, vf, allowed)
        o = o.reshape(B, Hq, D)
    return o, cache, ids, counts


def put_report(cache: SparseCache, layer, ids, counts):
    """Layer `layer`'s counts [B, <= N_COUNTS] added to the report's tail
    (which a forward starts from zero: `clear_counts`) and, where the
    report was made with room for them (`CACHE_KIND.report_ids`), its
    chosen blocks into their columns."""
    B = ids.shape[0]
    n = ids.shape[1] * ids.shape[2]
    at = cache.report.shape[1] - N_COUNTS
    rep = cache.report
    if at:
        rep = jax.lax.dynamic_update_slice(
            rep, ids.reshape(B, n), (0, layer * n))
    tail = rep[:, at:] + jnp.pad(
        counts, ((0, 0), (0, N_COUNTS - counts.shape[1])))
    return dataclasses.replace(
        cache, report=jax.lax.dynamic_update_slice(rep, tail, (0, at)))


def clear_counts(cache: SparseCache) -> SparseCache:
    at = cache.report.shape[1] - N_COUNTS
    return dataclasses.replace(cache, report=cache.report.at[:, at:].set(0))


# ---------------------------------------------------------------------------
# the cache kind (kvpaged.CacheKind)
# ---------------------------------------------------------------------------

# (summed over the sparse layers; `sparse_pages_live` is what dense
# attention would read: every live page a sparse layer)
DECODE_COUNTS = ("sparse_pages_selected", "sparse_pages_read",
                 "sparse_rows_dense", "pooled_keys_written",
                 "sparse_pages_live")
PREFILL_COUNTS = ("sparse_tokens", "pooled_keys_written")


class _SelectedPagesBesideState(kvhybrid._StateBesidePages):
    name = label = KIND
    arrays = ("k", "v", "kp", "state")
    page_arrays = ("k", "v", "kp")
    needs_paged = (
        "{kind} is served with paged=True: a slot holds KV pages and pooled "
        "keys for the sparse layers and a state row for the others")
    refuses = {
        **kvpaged.not_wired("R11", "quantize_kv", "speculative", "adapters"),
        # the state and the windows would have to cross the chunks' seams
        "prefill_chunk_tokens": (
            "prefill_chunk_tokens is not wired for {kind} yet (ROADMAP R11): "
            "a prefill runs a whole prompt from an empty row"),
    }
    axes = (1, 1, 1, 1)
    axes_of = kvpaged.CacheKind.axes_of  # its arrays lie one way only
    # True asks for the chosen block ids beside the counts (4 KB a token at
    # 8 layers x 2 heads x 64): `Request.prompt_selection` / `out_selection`
    # for a reference that takes the program's selection. It fixes the
    # report's width, so it is set BEFORE an engine is built
    # (scripts/sparse_check_sweep.py, tests); a served engine reports counts.
    report_ids = False

    def row_view(self, leaves, tables, pos0, last_idx, slot, cfg, geo):
        B = tables[0].shape[0]
        w = report_width(leaves[0].shape[0], leaves[0].shape[3],
                         Sizes.of(cfg).topk, self.report_ids)
        cache = SparseCache(
            **dict(zip(self.arrays, leaves)), block_tables=tables[0],
            pos=pos0, start=jnp.zeros((1,), jnp.int32), rows=slot,
            valid_len=last_idx[None] + 1,
            report=jnp.zeros((B, w), jnp.int32))
        return cache, cache

    def forward_kw(self, last_idx):
        return {"logits_at": last_idx}  # the head on the last token alone

    def _spots(self, pages, slot, window_pages):
        return pages, pages, pages, slot

    def state_row_nbytes(self, cache):
        return row_nbytes(cache)

    def note_chunk(self, st, cfg, geo, bucket, n, pool):
        # the lightning layers' chunked form over the bucket
        st.state_chunks += prefill_chunks(bucket, cfg.mamba_chunk_size)

    # ---- what a forward chose (the engine's one fetch a step) --------------

    def report(self, cache):
        return cache.report

    def read_report(self, report, rows=None, prefill: bool = False):
        """Report rows fetched to the host -> (ids [B, Ls * Hkv * topk] the
        chosen blocks, layer-major, or None where none were asked for
        (`report_ids`); the counts of `rows` (all, or a [B] bool) summed, by
        the span argument's name)."""
        names = PREFILL_COUNTS if prefill else DECODE_COUNTS
        at = report.shape[-1] - N_COUNTS
        tail = report[:, at:] if rows is None else report[rows, at:]
        return (report[:, :at] if at else None,
                {n: int(tail[:, i].sum()) for i, n in enumerate(names)})

    def metrics(self, engine):
        t = engine.report_totals
        read, live = t.get("sparse_pages_read", 0), t.get(
            "sparse_pages_live", 0)
        return kvstate.state_metrics(engine) + [
            ("bigdl_tpu_sparse_pages_selected_total", "counter",
             "(KV head, page) pairs that decode steps' selections chose, "
             "summed over the sparse layers",
             t.get("sparse_pages_selected", 0)),
            ("bigdl_tpu_sparse_pages_read_total", "counter",
             "distinct pages that decode steps' sparse attention read, "
             "summed over the sparse layers", read),
            ("bigdl_tpu_pooled_keys_written_total", "counter",
             "pooled keys (windows of mean keys) written, a layer and "
             "window, by prefills and decode steps",
             t.get("pooled_keys_written", 0)),
            ("bigdl_tpu_sparse_selected_page_share", "gauge",
             "pages read over pages live, decode steps so far (1 = dense)",
             read / live if live else 0.0)]


CACHE_KIND = _SelectedPagesBesideState()
