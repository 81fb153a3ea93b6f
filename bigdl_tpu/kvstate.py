"""Recurrent attention state: the cache of a power-retention layer.

Beside `kvcache.py` (a dense [slots, max_len] pool of keys and values)
and `kvpaged.py` (pages of them): a layer whose attention is POWER
RETENTION (arXiv 2507.04239; `config.attention_kind ==
"power_retention"`) keeps no keys. For token t, KV head j and a query
head h of j's group

    a[t, s] = exp(sum_{r=s+1..t} g_r) * (q_t . k_s / sqrt(D))**2,  s <= t
    y_t     = sum_s a[t, s] v_s / (sum_s a[t, s] + eps)

with g_r <= 0 the token's log-gate. Because the weight is a polynomial of
the score, the sum over s folds into a state of fixed size: with `phi` such
maps with phi_q(u) . phi_k(w) = (u . w / sqrt(D))**2,

    S_t = e^{g_t} S_{t-1} + v_t phi_k(k_t)^T   z_t = e^{g_t} z_{t-1} + phi_k(k_t)
    y_t = S_t phi_q(q_t) / (phi_q(q_t) . z_t + eps)

`phi` is the symmetric square: D squares and D (D - 1) / 2 cross terms
counted twice, 8256 numbers at D = 128. They are laid out by the DIAGONAL d =
0 .. D / 2 of the pair (i, (i + d) mod D), a block of D lanes each, so that
the pair products of x are 1 + D / 2 lane rotations of x times x; the last
block holds each of its pairs twice and its upper half is zero, which pads
8256 to 65 * 128 = 8320 lanes, whole tiles (0.8%). The weights (1 / D on a
square, 2 / D on a cross term) all sit on the KEY's side: `phi_q(x)` is the
bare products x_i x_j, which for a bfloat16 x are exact in 16 bits, so the
decode kernel splits phi_q(q) into two bfloat16 halves without loss;
`phi_k` carries the weights, and phi_q(u) . phi_k(w) = (u . w)**2 / D.

A `RetentionState` holds per layer and ROW the state transposed,
`S [L, R, Hkv, D, P]` (value index on sublanes, phi index on lanes: the
rank-one update then broadcasts a row of phi_k(k) down and a column of v
across, and the readout is a matmul against phi_q(q) with nothing
transposed), and `z [L, R, Hkv, 1, P]`, both float32, whatever the context
length. Who holds which row is the serving engine's business
(`serving/pages.PageTable`: a slot's state row is its one page):
`block_tables[b, 0]` is batch row b's state row PLUS ONE, 0 for a row that
holds none, the convention of `kvpaged` (page 0 is nobody's). Without a
table batch row b holds state row b (`TpuModel.generate`).

Prefill runs the chunked form (`_chunked`: the a[t, s] form inside a chunk,
the state across chunks) on the XLA route under the scope
`power_retention_prefill`; decode runs the Pallas kernel
`power_retention_decode` (ops/pallas/power_retention.py) where the kernels
are in use, else the same update in `jnp`. Gate sums and the state are
float32; q, k and v arrive in the compute dtype.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu import kvpaged
from bigdl_tpu.obs.scopes import scope

KIND = "power_retention"
PREFILL_CHUNK = 128  # tokens of one chunk of the prefill form
_HI = jax.lax.Precision.HIGHEST  # float32 operands stay float32 on the MXU


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RetentionState:
    S: jax.Array  # [L, R, Hkv, D, P] float32
    z: jax.Array  # [L, R, Hkv, 1, P] float32
    # tokens consumed: scalar int32 (rows aligned, generate) or [B] int32
    pos: jax.Array
    start: jax.Array  # [B] int32 first valid slot (left padding)
    # [B, 1] int32: batch row b's state row + 1, 0 = none (an idle slot of
    # the engine: its decode costs nothing and changes nothing). None =
    # batch row b holds state row b
    block_tables: Optional[jax.Array] = None
    # [B] int32: how many of the NEXT forward's T positions are real
    # tokens (the engine's prefill pads a bucket on the right); None = all
    valid_len: Optional[jax.Array] = None
    rope_base: Optional[jax.Array] = None  # as kvcache.KVCache's
    # the longest sequence a row may reach; nothing is allocated by it
    max_len: int = dataclasses.field(default=0, metadata=dict(static=True))

    @property
    def n_rows(self) -> int:
        return self.S.shape[1]

    def next_positions(self, t: int) -> jax.Array:
        step = jnp.arange(t, dtype=jnp.int32)[None, :]
        if self.rope_base is not None:
            return self.rope_base[:, None] + step
        pos = self.pos[:, None] if self.pos.ndim == 1 else self.pos
        return jnp.maximum(pos + step - self.start[:, None], 0)

    def rows(self, batch: int) -> tuple[jax.Array, jax.Array]:
        """(state row of each batch row, which batch rows hold one)."""
        if self.block_tables is None:
            return (jnp.arange(batch, dtype=jnp.int32),
                    jnp.ones((batch,), jnp.bool_))
        row = self.block_tables[:, 0].astype(jnp.int32) - 1
        return row, row >= 0


def phi_dim(head_dim: int) -> int:
    """Lanes of phi at this head size: 1 + D / 2 diagonals of D."""
    return (head_dim // 2 + 1) * head_dim


def _phi_weights(head_dim: int) -> np.ndarray:
    """The weight of each lane, [1 + D / 2, D]: 1 / D on a square, 2 / D on
    a cross term, 0 on the padding."""
    D, half = head_dim, head_dim // 2
    if D % 2:
        raise ValueError(f"power retention needs an even head_dim, got {D}")
    weight = np.full((half + 1, D), 2.0 / D, np.float32)
    weight[0] = 1.0 / D  # the squares
    weight[half, half:] = 0.0  # diagonal D/2 names every pair twice
    return weight


def _pairs(x: jax.Array, weighted: bool) -> jax.Array:
    D = x.shape[-1]
    w = _phi_weights(D)
    x = x.astype(jnp.float32)
    twice = jnp.concatenate([x, x], axis=-1)
    # diagonal d pairs x_i with x_{(i + d) mod D}: static slices, which a
    # TPU takes as lane rotations (a gather by index re-lays the array)
    turned = jnp.stack([twice[..., d:d + D] for d in range(w.shape[0])],
                       axis=-2)
    out = x[..., None, :] * turned * (w if weighted else (w > 0))
    return out.reshape(*x.shape[:-1], w.size)


def phi_q(x: jax.Array) -> jax.Array:
    """[..., D] -> float32 [..., P]: the bare products x_i x_j of each
    pair (zero in the padding lanes)."""
    return _pairs(x, weighted=False)


def phi_k(x: jax.Array) -> jax.Array:
    """[..., D] -> float32 [..., P] with phi_q(u) . phi_k(w) = (u . w)**2
    / D: the pair products times 1 / D (squares) or 2 / D (cross terms)."""
    return _pairs(x, weighted=True)


def init_state(n_layers: int, rows: int, n_kv_heads: int, head_dim: int,
               max_len: int = 0, batch: Optional[int] = None
               ) -> RetentionState:
    """Zeros: `rows` state rows, and a batch of `batch` (default: one batch
    row per state row, no table)."""
    P = phi_dim(head_dim)
    b = rows if batch is None else batch
    return RetentionState(
        S=jnp.zeros((n_layers, rows, n_kv_heads, head_dim, P), jnp.float32),
        z=jnp.zeros((n_layers, rows, n_kv_heads, 1, P), jnp.float32),
        pos=jnp.zeros((), jnp.int32),
        start=jnp.zeros((b,), jnp.int32),
        max_len=int(max_len),
    )


def row_nbytes(state: RetentionState) -> int:
    """Bytes of ONE state row over all layers: what a decode step reads,
    and writes again, for each live slot."""
    L, R = state.S.shape[:2]
    return (state.S.size + state.z.size) // R * 4


def advance(state: RetentionState, n: int) -> RetentionState:
    """`n` positions went through; those past `valid_len` (which goes
    with a per-row `pos`) were padding."""
    step = n if state.valid_len is None else state.valid_len
    base = state.rope_base
    return dataclasses.replace(
        state, pos=state.pos + step, valid_len=None,
        rope_base=None if base is None else base + step)


def valid_positions(state: Optional[RetentionState], slots: jax.Array,
                    row_start: jax.Array, T: int) -> jax.Array:
    """[B, T] bool: the positions of this forward that are tokens, neither
    left padding (before `start`) nor the right padding of a bucket."""
    ok = slots >= row_start[:, None]
    if state is not None and state.valid_len is not None:
        ok = ok & (jnp.arange(T)[None, :] < state.valid_len[:, None])
    return jnp.broadcast_to(ok, (row_start.shape[0], T))


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------

def _chunked(q, k, v, g, S, z, eps: float):
    """The chunked form over T tokens from the state (S, z).
    q [B, T, Hkv, G, D], k [B, T, Hkv, D], v [B, T, Hkv, D] (compute
    dtype), g [B, T, Hkv] float32 log-gates; a padded position carries
    g = 0 and k = 0, which is no update. S [B, Hkv, D, P], z [B, Hkv, P].
    Returns (y [B, T, Hkv, G, D] float32, S, z) after the T tokens."""
    B, T, Hkv, G, D = q.shape
    C = min(PREFILL_CHUNK, T)
    pad = -T % C
    if pad:
        q, k, v, g = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                      for a in (q, k, v, g))
    n = (T + pad) // C

    def chunks(a):  # [B, n * C, ...] -> [n, B, C, ...]
        return jnp.moveaxis(a.reshape(B, n, C, *a.shape[2:]), 1, 0)

    causal = jnp.tril(jnp.ones((C, C), jnp.bool_))
    scale = 1.0 / D

    def one(carry, xs):
        S, z = carry
        qc, kc, vc, gc = xs
        vc = vc.astype(jnp.float32)
        b = jnp.cumsum(gc, axis=1)  # [B, C, Hkv] gate sums, inclusive
        bh = jnp.moveaxis(b, 1, 2)  # [B, Hkv, C]
        # inside the chunk: the a[t, s] form
        s = jnp.einsum("bthgd,bshd->bhgts", qc, kc,
                       preferred_element_type=jnp.float32)
        decay = jnp.exp(jnp.where(
            causal, bh[..., :, None] - bh[..., None, :], -jnp.inf))
        a = s * s * scale * decay[:, :, None]  # [B, Hkv, G, C, C]
        num = jnp.einsum("bhgts,bshv->bthgv", a, vc, precision=_HI)
        den = jnp.moveaxis(jnp.sum(a, axis=-1), 3, 1)  # [B, C, Hkv, G]
        # what came before the chunk: the state
        pq = phi_q(qc)  # [B, C, Hkv, G, P]
        eb = jnp.exp(b)[..., None]  # [B, C, Hkv, 1]
        num = num + eb[..., None] * jnp.einsum(
            "bthgp,bhvp->bthgv", pq, S, precision=_HI)
        den = den + eb * jnp.einsum("bthgp,bhp->bthg", pq, z, precision=_HI)
        y = num / (den[..., None] + eps)
        # the state after the chunk
        pk = phi_k(kc)  # [B, C, Hkv, P]
        tail = jnp.exp(b[:, -1:] - b)  # [B, C, Hkv] decay to the chunk's end
        total = jnp.exp(b[:, -1])  # [B, Hkv]
        S = total[..., None, None] * S + jnp.einsum(
            "bshv,bshp->bhvp", vc * tail[..., None], pk, precision=_HI)
        z = total[..., None] * z + jnp.einsum(
            "bsh,bshp->bhp", tail, pk, precision=_HI)
        return (S, z), y

    (S, z), y = jax.lax.scan(one, (S, z), tuple(map(chunks, (q, k, v, g))))
    y = jnp.moveaxis(y, 0, 1).reshape(B, n * C, Hkv, G, D)
    return y[:, :T], S, z


def _step(q, k, v, g, S, z, eps: float):
    """One token in `jnp`: q [B, Hkv, G, D], k, v [B, Hkv, D], g [B, Hkv];
    S [B, Hkv, D, P], z [B, Hkv, P]. Returns (y [B, Hkv, G, D], S, z)."""
    dec = jnp.exp(g)
    pk = phi_k(k)
    S = dec[..., None, None] * S + (
        v.astype(jnp.float32)[..., :, None] * pk[..., None, :])
    z = dec[..., None] * z + pk
    pq = phi_q(q)
    num = jnp.einsum("bhgp,bhvp->bhgv", pq, S, precision=_HI)
    den = jnp.einsum("bhgp,bhp->bhg", pq, z, precision=_HI)
    return num / (den[..., None] + eps), S, z


def why_not_kernel(head_dim: int) -> Optional[str]:
    """None when a decode step takes `power_retention_decode`."""
    from bigdl_tpu.ops.pallas import interpret_mode, why_not_pallas

    why = why_not_pallas()
    if why is None and head_dim % 128 and not interpret_mode():
        why = f"head_dim {head_dim} is not whole lanes"
    return why


def attend(state: Optional[RetentionState], layer, q, k, v, g, valid,
           eps: float, decode: bool):
    """Power retention of one layer over this forward's T positions.
    q [B, T, Hq, D], k, v [B, T, Hkv, D] (rotated, compute dtype), g
    [B, T, Hkv] float32 log-gates, valid [B, T]. Returns (y [B, T, Hq, D]
    float32, the state with layer `layer`'s rows updated). `state` None is
    the cache-free path: from nothing, and nothing kept."""
    B, T, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    g = jnp.where(valid[..., None], g, 0.0)
    k = jnp.where(valid[..., None, None], k, jnp.zeros((), k.dtype))
    q = q.reshape(B, T, Hkv, G, D)
    if state is None:
        P = phi_dim(D)
        with scope("power_retention_prefill"):
            y, _, _ = _chunked(q, k, v, g, jnp.zeros((B, Hkv, D, P)),
                               jnp.zeros((B, Hkv, P)), eps)
        return y.reshape(B, T, Hq, D), None
    rows, live = state.rows(B)
    if decode and T == 1 and why_not_kernel(D) is None:
        from bigdl_tpu.ops.pallas.power_retention import (
            power_retention_decode,
        )

        y, S, z = power_retention_decode(
            state.S, state.z, layer, rows, live, q[:, 0], k[:, 0], v[:, 0],
            g[:, 0], eps=eps)
        return (y.reshape(B, 1, Hq, D),
                dataclasses.replace(state, S=S, z=z))
    # the rows' state of this layer, gathered; a row at position 0 starts
    # from nothing, whatever its last holder left
    at = jnp.clip(rows, 0, state.n_rows - 1)
    fresh = jnp.broadcast_to(state.pos == 0, (B,))[:, None, None, None]
    S0 = jnp.where(fresh, 0.0, state.S[layer, at])
    z0 = jnp.where(fresh[..., 0], 0.0, state.z[layer, at, :, 0])
    if decode and T == 1:
        y, S1, z1 = _step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], S0, z0, eps)
        y = y[:, None]
    else:
        with scope("power_retention_prefill"):
            y, S1, z1 = _chunked(q, k, v, g, S0, z0, eps)
    to = jnp.where(live, at, state.n_rows)  # an idle row writes nowhere
    state = dataclasses.replace(
        state,
        S=state.S.at[layer, to].set(S1, mode="drop"),
        z=state.z.at[layer, to, :, 0].set(z1, mode="drop"))
    return y.reshape(B, T, Hq, D), state


def prefill_chunks(n_tokens: int) -> int:
    """Chunks of the prefill form over `n_tokens` (a span's argument)."""
    return -(-n_tokens // min(PREFILL_CHUNK, max(n_tokens, 1)))


# ---------------------------------------------------------------------------
# the cache kind (kvpaged.CacheKind): a state row a slot, and no pages
# ---------------------------------------------------------------------------

def state_metrics(engine) -> list:
    """`CacheKind.metrics` of a kind whose slots hold state rows (in every
    layer, here, or beside KV pages, kvhybrid.py): what a decode step reads
    and writes again is the live rows, whatever the contexts."""
    return [
        ("bigdl_tpu_state_rows_live", "gauge", "slots whose state row the "
         "next decode step reads and writes", int(engine.active.sum())),
        ("bigdl_tpu_state_pool_bytes", "gauge", "device bytes of the "
         "recurrent-state pool (every slot's row, all layers)",
         engine.state_row_bytes * engine.n_slots),
        ("bigdl_tpu_state_bytes_moved_total", "counter", "bytes of state "
         "rows that decode steps read and wrote again",
         engine.state_bytes_moved)]


def state_decode_args(live, moved: int) -> dict:
    """`CacheKind.decode_args` of the same kinds: the live rows, and the
    bytes of them the step read and wrote again."""
    return {"state_rows_live": int(live.sum()), "state_bytes_moved": moved}


class _StateRows(kvpaged.CacheKind):
    name = label = KIND
    arrays = page_arrays = ("S", "z")
    needs_paged = (
        "{kind} is served with paged=True: a slot's recurrent state is a "
        "row the page table owns, and there is no dense pool of keys to "
        "fall back on")
    refuses = {
        "quantize_kv": "quantize_kv is not available for {kind}: the cache "
                       "is a float32 recurrent state, not keys and values",
        "speculative": "speculative serving is not available for {kind}: a "
                       "rejected draft cannot be taken back out of a "
                       "recurrent state by moving `pos`"}
    # a prefix hit would need the state at the prefix's end
    share_prefixes = tp_sharded = False
    metrics = staticmethod(state_metrics)

    def page_geometry(self, n_slots, max_len, page_size, n_pages):
        # what a page is here: the slot's state row, whole. It never grows,
        # so one page spans `max_len` tokens and a row of the table has one
        # entry; `page_size` and `n_pages` size nothing (a caller that
        # builds every engine alike may pass them)
        return max_len, n_slots + 1

    def make_pool(self, cfg, geo):
        cache = init_state(cfg.num_hidden_layers, geo.n_slots,
                           cfg.num_key_value_heads, cfg.head_dim_,
                           max_len=geo.max_len)
        # per-row positions, and the table: no slot holds a row yet
        return dataclasses.replace(
            cache, pos=jnp.zeros((geo.n_slots,), jnp.int32),
            block_tables=jnp.zeros((geo.n_slots, 1), jnp.int32))

    def row_view(self, leaves, tables, pos0, last_idx, slot, cfg, geo):
        """The pool itself behind the slot's one-row table: the chunked
        form runs from nothing when `pos0` is 0 and else from the row's
        state; the positions past `last_idx` leave the state as it was."""
        cache = RetentionState(
            S=leaves[0], z=leaves[1], block_tables=tables[0], pos=pos0,
            start=jnp.zeros((1,), jnp.int32), valid_len=last_idx[None] + 1,
            max_len=geo.max_len)
        return cache, cache

    def write_back(self, pool, row, n_tokens, last_idx, cfg):
        return self.leaves(row)

    def _spots(self, pages, slot, window_pages):
        return (pages - 1,) * 2  # page p is state row p - 1

    def state_row_nbytes(self, cache):
        return row_nbytes(cache)

    def note_chunk(self, st, cfg, geo, bucket, n, pool):
        st.state_chunks += prefill_chunks(bucket)

    def prefill_args(self, st):
        return {"state_chunks": st.state_chunks}

    def decode_args(self, cfg, table, live, moved, pool):
        return state_decode_args(live, moved)


CACHE_KIND = _StateRows()
