"""KV-cache subsystem.

TPU-native re-design of the reference's cache classes
(transformers/kv.py: `DynamicNormalCache` block-preallocated cache,
`DynamicFp8Cache` FP8-quantized cache, `DynamicCompressCache` /
`DynamicCompressFp8Cache` SnapKV compression). Under XLA everything is
static-shape: the cache is preallocated at `max_len` (the reference's
KV_CACHE_ALLOC_BLOCK_LENGTH growth policy becomes bucketed prefill
lengths + a fixed decode budget), lives in the jit carry, and is updated
with `lax.dynamic_update_slice` which XLA performs in place when the
buffer is donated.

Batch rows are **left-padded**: `start[b]` marks the first valid slot so
attention masks and rotary positions are exact per row.

FP8 mode stores k/v as float8_e5m2 with one float16 scale per (token,
head) vector — the same granularity as the reference's
`xe_addons.quantize_key_value` (kv.py:32-77) — halving cache HBM and
doubling effective context length.

SnapKV compression (`compress`, reference kv.py:171-245): after prefill,
the last `window` queries score every earlier key; scores are
average-pooled and the top `budget - window` slots per kv head are kept
(plus the observation window), producing a compact cache for decode.
Because keys are stored rotated, compressed slots no longer equal rope
positions — `rope_base` carries each row's true next rope position.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

_FP8_MAX = 57344.0  # float8_e5m2 finite max
_NEG_INF = -1e30


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    k: jax.Array  # [L, B, S, Hkv, D] cache dtype (bf16 or fp8_e5m2)
    v: jax.Array
    k_scale: Optional[jax.Array]  # [L, B, S, Hkv] f16 when quantized, else None
    v_scale: Optional[jax.Array]
    # next write slot: scalar int32 (rows aligned — generate path) or [B]
    # int32 (per-row — the serving engine's continuous batching, where each
    # slot's sequence has its own length; decode writes become scatters)
    pos: jax.Array
    start: jax.Array  # [B] int32: first valid slot per row (left padding)
    # [B] int32 rope position of the token written at slot `pos`, when it
    # differs from (pos - start) — i.e. after SnapKV compression. None =
    # derived (pos - start).
    rope_base: Optional[jax.Array] = None

    @property
    def max_len(self) -> int:
        return self.k.shape[2]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    def next_positions(self, t: int) -> jax.Array:
        """[B, T] rope positions for the next t tokens.

        Derived case: position of slot s is max(s - start, 0) — the clamp
        must apply per slot (not to a per-row base) so that left-padded
        prefill rows get positions 0..len-1 for their real tokens and the
        later decode positions continue them exactly."""
        step = jnp.arange(t, dtype=jnp.int32)[None, :]
        if self.rope_base is not None:
            return self.rope_base[:, None] + step
        pos = self.pos[:, None] if self.pos.ndim == 1 else self.pos
        return jnp.maximum(pos + step - self.start[:, None], 0)


def init_cache(
    n_layers: int,
    batch: int,
    max_len: int,
    n_kv_heads: int,
    head_dim: int,
    dtype=jnp.bfloat16,
    quantize_kv: bool = False,
) -> KVCache:
    shape = (n_layers, batch, max_len, n_kv_heads, head_dim)
    if quantize_kv:
        k = jnp.zeros(shape, jnp.float8_e5m2)
        v = jnp.zeros(shape, jnp.float8_e5m2)
        ks = jnp.zeros(shape[:-1], jnp.float16)
        vs = jnp.zeros(shape[:-1], jnp.float16)
    else:
        k = jnp.zeros(shape, dtype)
        v = jnp.zeros(shape, dtype)
        ks = vs = None
    return KVCache(
        k=k, v=v, k_scale=ks, v_scale=vs,
        pos=jnp.zeros((), jnp.int32),
        start=jnp.zeros((batch,), jnp.int32),
    )


def insert_row(cache: KVCache, pcache: KVCache, slot, pad) -> KVCache:
    """Copy a 1-row prefill cache into row `slot` of a per-row-pos pool:
    k/v (and fp8 scales when quantized) land at slots [0, bucket); the
    row's pos/start become (bucket, pad). Shared by the serving engine's
    dense insert and the family engine_insert adapters (yuan/mllama)."""
    import dataclasses

    bucket = pcache.k.shape[2]
    upd = dict(
        k=jax.lax.dynamic_update_slice(cache.k, pcache.k, (0, slot, 0, 0, 0)),
        v=jax.lax.dynamic_update_slice(cache.v, pcache.v, (0, slot, 0, 0, 0)),
        pos=cache.pos.at[slot].set(bucket),
        start=cache.start.at[slot].set(pad),
    )
    if cache.k_scale is not None:
        upd["k_scale"] = jax.lax.dynamic_update_slice(
            cache.k_scale, pcache.k_scale, (0, slot, 0, 0)
        )
        upd["v_scale"] = jax.lax.dynamic_update_slice(
            cache.v_scale, pcache.v_scale, (0, slot, 0, 0)
        )
    return dataclasses.replace(cache, **upd)


def swap_out_row(cache: KVCache, slot: int, n: Optional[int] = None):
    """Copy one pool row's KV (every layer, first `n` slots — the row's
    live region; None = full row) to host RAM — the dense-engine half of
    serving preemption (the paged twin is kvpaged.CacheKind.swap_out).
    Returns (k, v, k_scale|None, v_scale|None) numpy arrays;
    byte-preserving, so swap-in + decode is bit-exact. Slots past pos
    are never read (attention masks them; decode overwrites at pos), so
    the caller passes n >= pos to skip transferring the idle tail."""
    import numpy as np

    sl = slice(None) if n is None else slice(0, n)
    k = np.asarray(jax.device_get(cache.k[:, slot, sl]))
    v = np.asarray(jax.device_get(cache.v[:, slot, sl]))
    ks = vs = None
    if cache.quantized:
        ks = np.asarray(jax.device_get(cache.k_scale[:, slot, sl]))
        vs = np.asarray(jax.device_get(cache.v_scale[:, slot, sl]))
    return k, v, ks, vs


def swap_in_row(cache: KVCache, k, v, k_scale, v_scale, slot, pos,
                start) -> KVCache:
    """Write a swapped-out row blob back into the first k.shape[1] slots
    of row `slot` (need not be the row it came from; the stale tail
    beyond the blob is masked exactly like the tail insert_row leaves)
    and restore the row's pos/start. jit-friendly with traced
    slot/pos/start — the blob length is static from the array shape, so
    one program compiles per distinct (bucketed) length; the engine
    wraps it with a donated cache so the write is in place."""
    k = jnp.asarray(k, cache.k.dtype)
    n = k.shape[1]
    upd = dict(
        k=cache.k.at[:, slot, :n].set(k),
        v=cache.v.at[:, slot, :n].set(jnp.asarray(v, cache.v.dtype)),
        pos=cache.pos.at[slot].set(pos),
        start=cache.start.at[slot].set(start),
    )
    if cache.quantized:
        upd["k_scale"] = cache.k_scale.at[:, slot, :n].set(
            jnp.asarray(k_scale, cache.k_scale.dtype))
        upd["v_scale"] = cache.v_scale.at[:, slot, :n].set(
            jnp.asarray(v_scale, cache.v_scale.dtype))
    return dataclasses.replace(cache, **upd)


def _quantize_heads(
    x: jax.Array, scale_dtype=jnp.float16
) -> tuple[jax.Array, jax.Array]:
    """[B,T,H,D] -> (fp8 codes, [B,T,H] scales); per-vector absmax.
    The paged pool stores f32 scales (its Pallas kernel has no f16
    vectors), so it asks for scale_dtype=f32 to skip the f16 round-trip."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = absmax / _FP8_MAX
    inv = jnp.where(scale == 0, 0.0, 1.0 / jnp.where(scale == 0, 1.0, scale))
    codes = (x.astype(jnp.float32) * inv[..., None]).astype(jnp.float8_e5m2)
    return codes, scale.astype(scale_dtype)


def _scatter_rows(buf: jax.Array, layer: jax.Array, pos: jax.Array,
                  val: jax.Array) -> jax.Array:
    """buf [L,B,S,...] ← val [B,T,...] at row-dependent slots pos[b]+t.
    Per-row scatter (serving engine decode, T normally 1); XLA performs it
    in place when the buffer is donated."""
    B, T = val.shape[:2]
    rows = jnp.broadcast_to(jnp.arange(B)[:, None], (B, T))
    cols = pos[:, None] + jnp.arange(T)[None, :]
    layer_b = jnp.broadcast_to(layer, (B, T))
    return buf.at[layer_b, rows, cols].set(val.astype(buf.dtype), mode="drop")


def update_layer(
    cache: KVCache, layer: jax.Array, k_new: jax.Array, v_new: jax.Array
) -> KVCache:
    """Write k_new/v_new [B,T,Hkv,D] into layer `layer` at cache.pos.

    Does NOT advance pos (the model advances it once per forward, after the
    layer scan). jit-safe with traced `layer` and `cache.pos`. Scalar pos
    writes one contiguous slice; per-row pos scatters row by row.
    Dispatches to the paged pool for PagedKVCache (bigdl_tpu/kvpaged.py).
    """
    from bigdl_tpu import kvpaged

    if isinstance(cache, kvpaged.PagedKVCache):
        return kvpaged.update_layer(cache, layer, k_new, v_new)
    per_row = cache.pos.ndim == 1
    if cache.quantized:
        # scales in the cache's own type: float16 here, float32 in a row
        # gathered from fp8 pages (kvpaged.gather_row)
        kq, ks = _quantize_heads(k_new, cache.k_scale.dtype)
        vq, vs = _quantize_heads(v_new, cache.v_scale.dtype)
        if per_row:
            k = _scatter_rows(cache.k, layer, cache.pos, kq)
            v = _scatter_rows(cache.v, layer, cache.pos, vq)
            k_scale = _scatter_rows(cache.k_scale, layer, cache.pos, ks)
            v_scale = _scatter_rows(cache.v_scale, layer, cache.pos, vs)
        else:
            idx = (layer, 0, cache.pos, 0, 0)
            k = jax.lax.dynamic_update_slice(cache.k, kq[None], idx)
            v = jax.lax.dynamic_update_slice(cache.v, vq[None], idx)
            k_scale = jax.lax.dynamic_update_slice(
                cache.k_scale, ks[None], (layer, 0, cache.pos, 0)
            )
            v_scale = jax.lax.dynamic_update_slice(
                cache.v_scale, vs[None], (layer, 0, cache.pos, 0)
            )
        return dataclasses.replace(cache, k=k, v=v, k_scale=k_scale, v_scale=v_scale)
    if per_row:
        k = _scatter_rows(cache.k, layer, cache.pos, k_new)
        v = _scatter_rows(cache.v, layer, cache.pos, v_new)
    else:
        idx = (layer, 0, cache.pos, 0, 0)
        k = jax.lax.dynamic_update_slice(
            cache.k, k_new[None].astype(cache.k.dtype), idx
        )
        v = jax.lax.dynamic_update_slice(
            cache.v, v_new[None].astype(cache.v.dtype), idx
        )
    return dataclasses.replace(cache, k=k, v=v)


def read_layer(
    cache: KVCache, layer: jax.Array, dtype=jnp.bfloat16
) -> tuple[jax.Array, jax.Array]:
    """Full [B,S,Hkv,D] k/v for one layer, dequantized to `dtype`."""
    from bigdl_tpu import kvpaged

    if isinstance(cache, kvpaged.PagedKVCache):
        return kvpaged.read_layer(cache, layer, dtype)
    k = jax.lax.dynamic_index_in_dim(cache.k, layer, axis=0, keepdims=False)
    v = jax.lax.dynamic_index_in_dim(cache.v, layer, axis=0, keepdims=False)
    if cache.quantized:
        ks = jax.lax.dynamic_index_in_dim(cache.k_scale, layer, 0, keepdims=False)
        vs = jax.lax.dynamic_index_in_dim(cache.v_scale, layer, 0, keepdims=False)
        k = k.astype(jnp.float32) * ks.astype(jnp.float32)[..., None]
        v = v.astype(jnp.float32) * vs.astype(jnp.float32)[..., None]
    return k.astype(dtype), v.astype(dtype)


def read_layer_raw(
    cache: KVCache, layer: jax.Array
) -> tuple[jax.Array, jax.Array, Optional[jax.Array], Optional[jax.Array]]:
    """One layer's k/v WITHOUT dequantization: ([B,S,Hkv,D] codes,
    [B,S,Hkv] f16 scales or None). The flash kernel dequantizes fp8
    blocks in-kernel (the paged path's fp8 story) — going through
    read_layer instead would materialize the full dense bf16 cache in
    HBM each step, forfeiting exactly the bytes fp8 KV saves (the dense
    `sdp_fp8` caveat)."""
    k = jax.lax.dynamic_index_in_dim(cache.k, layer, axis=0, keepdims=False)
    v = jax.lax.dynamic_index_in_dim(cache.v, layer, axis=0, keepdims=False)
    if not cache.quantized:
        return k, v, None, None
    ks = jax.lax.dynamic_index_in_dim(cache.k_scale, layer, 0, keepdims=False)
    vs = jax.lax.dynamic_index_in_dim(cache.v_scale, layer, 0, keepdims=False)
    return k, v, ks, vs


def advance(cache: KVCache, n: int) -> KVCache:
    rope_base = cache.rope_base
    if rope_base is not None:
        rope_base = rope_base + n
    return dataclasses.replace(cache, pos=cache.pos + n, rope_base=rope_base)


# ---------------------------------------------------------------------------
# SnapKV-style compression (reference kv.py:171-375)
# ---------------------------------------------------------------------------

def _avg_pool_1d(x: jax.Array, kernel: int) -> jax.Array:
    """Mean pool with 'same' padding over the last axis (SnapKV smoothing;
    the reference uses F.avg_pool1d on the summed score vector)."""
    if kernel <= 1:
        return x
    pad = kernel // 2
    summed = jax.lax.reduce_window(
        x, 0.0, jax.lax.add, (1,) * (x.ndim - 1) + (kernel,),
        (1,) * x.ndim, [(0, 0)] * (x.ndim - 1) + [(pad, kernel - 1 - pad)],
    )
    return summed / kernel


def compress(
    cache: KVCache,
    q_obs: jax.Array,  # [L, B, W, Hq, D]: last-window queries per layer
    budget: int,
    out_len: int,
    window: int = 32,
    kernel: int = 7,
) -> KVCache:
    """SnapKV: keep, per kv head, the `budget - window` highest-attention
    prefix slots plus the `window` observation slots; write them compacted
    into a fresh cache of length `out_len` (budget + decode headroom).

    Equivalent of the reference's `compress_kv` (kv.py:171-245): softmax
    scores of the observation-window queries over the prefix, summed over
    the window and the query group, average-pooled, top-k per kv head.
    Selection is per kv head (head h's kept slots differ from head h'),
    which is fine because attention reads heads independently; the
    per-row validity boundary `start` is head-independent.

    Returns a cache with pos=budget, start = budget - kept(b), and
    rope_base = the row's true next rope position (slot indices no longer
    encode positions).
    """
    L, B, S, Hkv, D = cache.k.shape
    W = q_obs.shape[2]
    Hq = q_obs.shape[3]
    G = Hq // Hkv
    keep_k = budget - W
    assert keep_k > 0, "budget must exceed the observation window"
    assert cache.pos.ndim == 0, "compress expects an aligned (scalar-pos) cache"

    P = cache.pos  # prompt end (next slot)
    start = cache.start
    scale = 1.0 / (D ** 0.5)

    sj = jnp.arange(S)
    obs_start = P - W
    # prefix slots only: valid rows of the prompt, before the obs window.
    # Causal masking within the obs window is irrelevant: all prefix slots
    # precede every obs query.
    prefix = (sj[None, :] >= start[:, None]) & (sj[None, :] < obs_start)  # [B,S]

    def one_layer(xs):
        """Score, select, and compact a single layer — mapped over L so the
        fp32 transients ([B,Hkv,G,W,S] scores + dequantized K) stay at 1/L
        of the whole-cache footprint (the long-prompt regime this feature
        targets; the reference also compresses layer by layer)."""
        k_l, v_l, ks_l, vs_l, q_l = xs
        if ks_l is not None:
            kf = k_l.astype(jnp.float32) * ks_l.astype(jnp.float32)[..., None]
        else:
            kf = k_l.astype(jnp.float32)
        qg = q_l.astype(jnp.float32).reshape(B, W, Hkv, G, D)
        scores = jnp.einsum("bwhgd,bshd->bhgws", qg, kf) * scale
        scores = jnp.where(prefix[:, None, None, None, :], scores, _NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        # zero fully-masked rows (softmax of all -inf ~ uniform garbage)
        probs = jnp.where(prefix[:, None, None, None, :], probs, 0.0)
        vote = probs.sum(axis=(2, 3))  # [B,Hkv,S] summed over group+window
        vote = _avg_pool_1d(vote, kernel)
        vote = jnp.where(prefix[:, None, :], vote, _NEG_INF)

        _, idx = jax.lax.top_k(vote, keep_k)  # [B,Hkv,keep_k]
        valid_sel = jnp.take_along_axis(
            jnp.broadcast_to(prefix[:, None, :], vote.shape), idx, axis=-1
        )
        # temporal order with invalid slots pushed left (they land in the
        # pad region delimited by the new start)
        order_key = jnp.where(valid_sel, idx, -1)
        perm = jnp.argsort(order_key, axis=-1)
        idx_sorted = jnp.take_along_axis(idx, perm, axis=-1)

        def compact(x):  # x [B,S,Hkv,*feat]
            xt = jnp.moveaxis(x, 2, 1)  # [B,Hkv,S,*]
            expand = idx_sorted.reshape(idx_sorted.shape + (1,) * (xt.ndim - 3))
            sel = jnp.take_along_axis(
                xt,
                jnp.broadcast_to(expand, idx_sorted.shape + xt.shape[3:]),
                axis=2,
            )
            sel = jnp.moveaxis(sel, 1, 2)  # [B,keep_k,Hkv,*]
            obs = jax.lax.dynamic_slice_in_dim(x, obs_start, W, axis=1)
            merged = jnp.concatenate([sel, obs], axis=1)  # [B,budget,Hkv,*]
            pad = [(0, 0)] * x.ndim
            pad[1] = (0, out_len - budget)
            return jnp.pad(merged, pad)

        return (
            compact(k_l),
            compact(v_l),
            compact(ks_l) if ks_l is not None else None,
            compact(vs_l) if vs_l is not None else None,
        )

    if cache.quantized:
        new_k, new_v, new_ks, new_vs = jax.lax.map(
            one_layer, (cache.k, cache.v, cache.k_scale, cache.v_scale, q_obs)
        )
    else:
        new_k, new_v = jax.lax.map(
            lambda t: one_layer((t[0], t[1], None, None, t[2]))[:2],
            (cache.k, cache.v, q_obs),
        )
        new_ks = new_vs = None

    avail = jnp.maximum(obs_start - start, 0)  # prefix tokens per row
    kept = jnp.minimum(avail, keep_k)
    # invalid selected slots are left-packed; rows shorter than the obs
    # window additionally carry pad slots at the FRONT of the obs region
    # (pads are the leftmost slots), so they extend the same contiguous
    # invalid region past keep_k.
    pad_in_obs = jnp.maximum(start - obs_start, 0)
    new_start = (keep_k - kept + pad_in_obs).astype(jnp.int32)
    rope_base = jnp.maximum(P - start, 0).astype(jnp.int32)  # next position

    return KVCache(
        k=new_k, v=new_v, k_scale=new_ks, v_scale=new_vs,
        pos=jnp.asarray(budget, jnp.int32), start=new_start,
        rope_base=rope_base,
    )
