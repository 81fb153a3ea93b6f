"""Paged KV cache: block tables over a shared physical page pool.

The reference reaches paged attention through its vLLM fork
(vllm/xpu/, 3,992 LoC in /root/reference); our engine's dense
[slots, max_len] pool wastes HBM per idle slot and cannot share prompt
prefixes. Here KV lives in pages of `page_size` tokens:

- `k`/`v` [L, n_pages, page_size, Hkv, D] — one physical pool;
- `block_tables` [B, max_pages] int32 map each row's logical page to a
  physical page (unallocated entries may hold anything: reads beyond
  `pos` are masked by attention, and the engine allocates before
  writes);
- a decode step scatters its one token a row through the table
  (`update_layer`) and attends over the pages where they lie: the Pallas
  kernel `ops/pallas/paged_attention.paged_decode_attention` fetches a
  row's live pages out of the pool by its own DMA, so no dense view of
  the cache is ever built;
- an admission's prefill never handles the pool in its layer loop: it
  gathers ONE row's pages once for every layer (`gather_row`) into a
  dense one-row `kvcache.KVCache` at a scalar position, prefills that
  (a contiguous write, flash attention), and writes back only the pages
  it wrote, a page a `dynamic_update_slice` (`scatter_row_pages`). The
  pool is a few GB: as the carry of a scan that scattered into it and
  sliced a layer out of it, XLA re-laid all of it (a pool whose KV heads
  do not fill a tile, Qwen2's four) or copied a layer of it (any pool)
  on every layer of every admission (PERF.md section 6, PR 39);
- `read_layer`, the gather of every row's pages into the dense
  [B, S, Hkv, D] view, is what is left for the routes without a kernel:
  decode on the XLA route (a CPU, alibi, an attention override), a
  speculative round's verify forward, and the attention layers of
  `kvhybrid.py`'s prefill.

Pages are allocated on demand and refcounted (`PagePool`), so identical
prompt prefixes share both storage and prefill compute — the serving
engine's radix-tree prefix cache (serving/radix.py) holds one reference
per cached page and matches prompts at any token split point.

The class mirrors the KVCache interface surface the model forward uses
(pos/start/max_len/next_positions + update/read/advance dispatched via
bigdl_tpu.kvcache), so llama.forward runs on either cache unchanged.
"""

from __future__ import annotations

import dataclasses
import types
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedKVCache:
    k: jax.Array  # [L, n_pages, page_size, Hkv, D] bf16 or fp8_e5m2
    v: jax.Array
    block_tables: jax.Array  # [B, max_pages] int32 physical page ids
    pos: jax.Array  # [B] int32 next logical slot per row
    start: jax.Array  # [B] int32 first valid slot (left padding)
    rope_base: Optional[jax.Array] = None  # [B] (see kvcache.KVCache)
    # fp8 pages: per-vector absmax scales, f32 (3% of the fp8 codes at
    # D=128 — the fp8 page halves KV HBM traffic AND capacity, the same
    # lever as the dense pool's quantize_kv)
    k_scale: Optional[jax.Array] = None  # [L, n_pages, page_size, Hkv]
    v_scale: Optional[jax.Array] = None

    @property
    def page_size(self) -> int:
        return self.k.shape[2]

    @property
    def max_len(self) -> int:  # logical capacity per row
        return self.block_tables.shape[1] * self.page_size

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    def next_positions(self, t: int) -> jax.Array:
        step = jnp.arange(t, dtype=jnp.int32)[None, :]
        if self.rope_base is not None:
            return self.rope_base[:, None] + step
        pos = self.pos[:, None]
        return jnp.maximum(pos + step - self.start[:, None], 0)


def init_paged(
    n_layers: int,
    n_pages: int,
    page_size: int,
    n_kv_heads: int,
    head_dim: int,
    batch: int,
    max_pages_per_row: int,
    dtype=jnp.bfloat16,
    quantize_kv: bool = False,
) -> PagedKVCache:
    shape = (n_layers, n_pages, page_size, n_kv_heads, head_dim)
    if quantize_kv:
        k = jnp.zeros(shape, jnp.float8_e5m2)
        v = jnp.zeros(shape, jnp.float8_e5m2)
        ks = jnp.zeros(shape[:-1], jnp.float32)
        vs = jnp.zeros(shape[:-1], jnp.float32)
    else:
        k = jnp.zeros(shape, dtype)
        v = jnp.zeros(shape, dtype)
        ks = vs = None
    return PagedKVCache(
        k=k, v=v, k_scale=ks, v_scale=vs,
        block_tables=jnp.zeros((batch, max_pages_per_row), jnp.int32),
        pos=jnp.zeros((batch,), jnp.int32),
        start=jnp.zeros((batch,), jnp.int32),
    )


def live_rows(cache) -> jax.Array:
    """[B] bool: rows whose block table maps a page at all. Physical
    page 0 is the scratch sink (`PagePool` never hands it out) and a
    released slot's row points EVERY entry there while its `pos` keeps
    advancing, so `pos` alone would call ever more of the sink live. The
    paged decode kernel spends nothing on a row this says is idle."""
    return jnp.any(cache.block_tables != 0, axis=1)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedLatentCache:
    """The paged cache of a latent-attention (MLA) model
    (models/deepseek.py): a page holds, for each of its tokens and each
    layer, ONE row of `kv_lora_rank + qk_rope_head_dim` values (the
    compressed kv, then the shared rope key) and no heads. One array, so a
    page is one DMA for the decode kernel
    (ops/pallas/paged_attention.paged_latent_decode_attention) and one
    scatter for a write. The row is padded with zeros to whole tiles of
    128 lanes (576 -> 640): a TPU pads it to that in HBM whatever the shape
    says, and for a width that is NOT whole tiles the compiler prefers a
    layout with the PAGE axis minor-most, which every program would then
    copy whole before the kernel could read a page (bench/tools/fit_latent.py
    showed a 4.2 GB temporary in every step at GLM-4.7-Flash's sizes).
    Booked, shared, parked and restored by
    `serving/pages.PageTable` exactly as a KV page is: the table sees page
    numbers and `page_nbytes`, nothing else."""

    lat: jax.Array  # [L, n_pages, page_size, round_up(r + dr, 128)] bf16
    block_tables: jax.Array  # [B, max_pages] int32 physical page ids
    pos: jax.Array  # [B] int32 next logical slot per row
    start: jax.Array  # [B] int32 first valid slot (left padding)

    @property
    def page_size(self) -> int:
        return self.lat.shape[2]

    @property
    def max_len(self) -> int:  # logical capacity per row
        return self.block_tables.shape[1] * self.page_size

    def next_positions(self, t: int) -> jax.Array:
        step = jnp.arange(t, dtype=jnp.int32)[None, :]
        return jnp.maximum(self.pos[:, None] + step - self.start[:, None], 0)


def init_latent(n_layers: int, n_pages: int, page_size: int, rank: int,
                rope_dim: int, batch: int, max_pages_per_row: int,
                dtype=jnp.bfloat16) -> PagedLatentCache:
    width = -(-(rank + rope_dim) // 128) * 128
    return PagedLatentCache(
        lat=jnp.zeros((n_layers, n_pages, page_size, width), dtype),
        block_tables=jnp.zeros((batch, max_pages_per_row), jnp.int32),
        pos=jnp.zeros((batch,), jnp.int32),
        start=jnp.zeros((batch,), jnp.int32),
    )


def update_latent_layer(cache: PagedLatentCache, layer: jax.Array,
                        lat_new: jax.Array) -> PagedLatentCache:
    """Write lat_new [B, T, r + dr] (zero-padded to the pool's width) at
    each row's pos through the block table. Does NOT advance pos (the
    forward advances once)."""
    T = lat_new.shape[1]
    lat_new = jnp.pad(lat_new, ((0, 0), (0, 0),
                                (0, cache.lat.shape[-1] - lat_new.shape[-1])))
    page = cache.page_size
    s = cache.pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    phys = jnp.take_along_axis(cache.block_tables, s // page, axis=1)
    return dataclasses.replace(cache, lat=cache.lat.at[
        layer, phys, s % page].set(lat_new.astype(cache.lat.dtype)))


def read_latent_layer(cache: PagedLatentCache, layer: jax.Array) -> jax.Array:
    """One layer's pages of every row gathered into [B, S, width]."""
    lat_l = jax.lax.dynamic_index_in_dim(cache.lat, layer, 0, keepdims=False)
    B, mp = cache.block_tables.shape
    return lat_l[cache.block_tables].reshape(
        B, mp * cache.page_size, lat_l.shape[-1])


# ---------------------------------------------------------------------------
# Host-side page accounting (serving/pages.py + serving/radix.py)
# ---------------------------------------------------------------------------


class PagePool:
    """Refcounted free-list accounting for the physical pages of a
    PagedKVCache. Physical page 0 is the reserved scratch sink (idle
    decode slots' masked garbage writes land there) and is never
    allocatable.

    Ownership discipline: every holder of a page carries exactly one
    reference — each slot block-table entry is one hold, and the radix
    prefix cache (serving/radix.py) takes its OWN hold per cached node.
    A page returns to the free list exactly when its count reaches 0,
    so there is no "cached but refcount 0" special case to reconcile at
    release time (the flat prefix cache's `_page_key` membership test)."""

    def __init__(self, n_pages: int):
        self.n_pages = n_pages
        self.free = list(range(1, n_pages))  # page 0 = scratch
        self.ref = [0] * n_pages

    def alloc(self) -> Optional[int]:
        """A free page with its first reference, or None when dry (the
        caller escalates: radix eviction, then preemption)."""
        if not self.free:
            return None
        pg = self.free.pop()
        self.ref[pg] = 1
        return pg

    def incref(self, pg: int) -> None:
        self.ref[pg] += 1

    def decref(self, pg: int) -> int:
        """Drop one hold; a count reaching 0 returns the page to the
        free list. Returns the new count (callers assert-friendly)."""
        n = self.ref[pg] = self.ref[pg] - 1
        if n < 0:  # a double-release corrupts the pool silently later;
            # fail at the exact site instead
            raise AssertionError(f"page {pg} refcount went negative")
        if n == 0:
            self.free.append(pg)
        return n

    @property
    def n_free(self) -> int:
        return len(self.free)


def kv_page_nbytes(cache) -> int:
    """Bytes of ONE physical page across every layer (K + V + fp8
    scales, or the latents) — the unit the unified KV/adapter device
    budget is denominated in (serving/adapters.AdapterPager)."""
    if isinstance(cache, PagedLatentCache):
        L, _, page, width = cache.lat.shape
        return L * page * width * cache.lat.dtype.itemsize
    L, _, page, Hkv, D = cache.k.shape
    n = 2 * L * page * Hkv * D * cache.k.dtype.itemsize
    if cache.quantized:
        n += 2 * L * page * Hkv * cache.k_scale.dtype.itemsize
    return n


class AdapterPageStore:
    """Device residency for LoRA adapter weights, page-framed so it
    draws from the SAME :class:`PagePool` as KV.

    One flat bf16 buffer ``buf [n_pages, page_elems]`` where
    ``page_elems`` is the element count whose byte size matches one KV
    page (``kv_page_nbytes``). The store is a typed VIEW of the page
    frame, not a second allocation pool: page ids come from the shared
    PagePool, so every adapter page resident here is one KV page the
    radix cache / slots cannot hold — a single HBM budget, the S-LoRA
    unified-paging model (docs/serving.md §7).

    The store itself does no accounting; ownership (refcounts, LRU,
    eviction order) lives in ``serving/adapters.AdapterPager``."""

    def __init__(self, n_pages: int, page_nbytes: int):
        self.page_elems = max(page_nbytes // 2, 1)  # bf16 elements/page
        self.buf = jnp.zeros((n_pages, self.page_elems), jnp.bfloat16)

    def n_for(self, n_elems: int) -> int:
        """Pages needed to hold ``n_elems`` bf16 elements."""
        return -(-int(n_elems) // self.page_elems)

    def write(self, pages, flat) -> None:
        """Scatter a flat bf16 host/device vector into physical pages
        `pages` (zero-padded to the page frame)."""
        n = len(pages) * self.page_elems
        v = np.zeros((n,), np.float32)
        v[: flat.size] = np.asarray(flat, np.float32).ravel()
        self.buf = self.buf.at[jnp.asarray(list(pages), jnp.int32)].set(
            jnp.asarray(v.reshape(len(pages), self.page_elems),
                        jnp.bfloat16)
        )

    def read(self, pages, n_elems: int) -> jax.Array:
        """Gather pages back into the leading ``n_elems`` of the flat
        vector (device-side — no host round trip)."""
        ids = jnp.asarray(list(pages), jnp.int32)
        return self.buf[ids].reshape(-1)[:n_elems]


# ---------------------------------------------------------------------------
# Host-RAM page swap (serving preemption)
# ---------------------------------------------------------------------------

class HostPages(types.SimpleNamespace):
    """A preempted request's part of a paged pool parked in host RAM: for
    each array of its cache kind (`CacheKind.arrays`, by name) the slot's
    pages, or its state row, over every layer. The serving engine swaps a
    victim out here (`CacheKind.swap_out`), releases its device pages, and
    swaps back into freshly allocated (possibly different) physical pages
    on resume (`swap_in`): contents are byte-preserved, so decode after
    swap-in is bit-exact with the uninterrupted run. On a real TPU runtime
    the transfer stages through the runtime's host buffers; the arrays are
    plain (pageable) numpy: a pinned-allocation fast path is a perf
    follow-up, not a correctness one."""

    @property
    def nbytes(self) -> int:
        return sum(a.nbytes for a in vars(self).values() if a is not None)


def gather_row(cache: PagedKVCache):
    """ONE row's pages (block table [1, max_pages]) of every layer, out of
    the pool in one gather, as a dense one-row `kvcache.KVCache`
    [L, 1, max_pages * page, Hkv, D] at the row's scalar position: what an
    admission's prefill works on, in place of the pool (the engine's
    `_paged_prefill_impl`). fp8 pages bring codes and scales as they lie."""
    from bigdl_tpu.kvcache import KVCache

    bt = cache.block_tables[0]

    def row(a):  # [L, n_pages, page, ...] -> [L, 1, max_pages * page, ...]
        if a is None:
            return None
        return a[:, bt].reshape(a.shape[0], 1, -1, *a.shape[3:])

    return KVCache(
        k=row(cache.k), v=row(cache.v), k_scale=row(cache.k_scale),
        v_scale=row(cache.v_scale), pos=cache.pos[0], start=cache.start)


def pages_spanned(pos: int, n_tokens: int, page: int, max_pages: int) -> int:
    """Logical pages that `n_tokens` written from slot `pos` touch: what
    `scatter_row_pages` writes back (the host's count, for the admission's
    `prefill` span)."""
    return min((pos + n_tokens - 1) // page, max_pages - 1) - pos // page + 1


def scatter_row_pages(cache: PagedKVCache, row, n_tokens: int, first=None,
                      most: Optional[int] = None) -> PagedKVCache:
    """Write back, through the block table, the pages of `row` (a
    `gather_row` cache after a prefill of `n_tokens` from `cache.pos[0]`)
    that the prefill wrote: logical pages `pos // page .. (pos + n_tokens -
    1) // page`. Their number is static (the most `n_tokens` can span), the
    first is not; what the count has over the span goes to physical page 0,
    the scratch sink, where a table's entries past the row's allocation
    point already. No other page of the pool is written. `first` (traced)
    with `most` (static, a bound on the pages from `first` to the span's
    end) writes back the span's END only: a window layer's pages that a
    later query still reads (kvwindow.scatter_rows)."""
    page = cache.page_size
    mp = cache.block_tables.shape[1]
    pos = cache.pos[0]
    n = min((n_tokens + page - 2) // page + 1, mp)
    if most is not None:
        n = min(n, most)
    logical = (pos // page if first is None else first) + jnp.arange(
        n, dtype=jnp.int32)
    written = logical <= jnp.minimum((pos + n_tokens - 1) // page, mp - 1)
    logical = jnp.minimum(logical, mp - 1)
    phys = jnp.where(written, cache.block_tables[0, logical], 0)

    fields = [f for f in ("k", "v", "k_scale", "v_scale")
              if getattr(cache, f) is not None]
    # the row as its pages [L, max_pages, page, ...], a page an update in
    # place: a scatter into a pool whose KV heads do not fill a tile has
    # XLA re-lay the whole pool around it, there and back
    rows = [r.reshape(r.shape[0], mp, page, *r.shape[3:])
            for r in (getattr(row, f) for f in fields)]

    def put(i, pools):
        return tuple(
            jax.lax.dynamic_update_slice(
                pool, jax.lax.dynamic_slice_in_dim(r, logical[i], 1, axis=1),
                (0, phys[i]) + (0,) * (pool.ndim - 2))
            for pool, r in zip(pools, rows))

    pools = jax.lax.fori_loop(
        0, n, put, tuple(getattr(cache, f) for f in fields))
    return dataclasses.replace(cache, **dict(zip(fields, pools)))


def update_layer(
    cache: PagedKVCache, layer: jax.Array, k_new: jax.Array, v_new: jax.Array
) -> PagedKVCache:
    """Write k_new/v_new [B,T,Hkv,D] at each row's pos through the block
    table. Does NOT advance pos (the model advances once per forward)."""
    B, T = k_new.shape[:2]
    page = cache.page_size
    s = cache.pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]  # [B,T]
    pg = s // page
    off = s % page
    phys = jnp.take_along_axis(cache.block_tables, pg, axis=1)  # [B,T]
    upd = {}
    if cache.quantized:
        from bigdl_tpu.kvcache import _quantize_heads

        kq, ks = _quantize_heads(k_new, scale_dtype=jnp.float32)
        vq, vs = _quantize_heads(v_new, scale_dtype=jnp.float32)
        upd["k"] = cache.k.at[layer, phys, off].set(kq)
        upd["v"] = cache.v.at[layer, phys, off].set(vq)
        upd["k_scale"] = cache.k_scale.at[layer, phys, off].set(ks)
        upd["v_scale"] = cache.v_scale.at[layer, phys, off].set(vs)
    else:
        upd["k"] = cache.k.at[layer, phys, off].set(k_new.astype(cache.k.dtype))
        upd["v"] = cache.v.at[layer, phys, off].set(v_new.astype(cache.v.dtype))
    return dataclasses.replace(cache, **upd)


def read_layer(
    cache: PagedKVCache, layer: jax.Array, dtype=jnp.bfloat16
) -> tuple[jax.Array, jax.Array]:
    """Gather one layer's pages into the dense [B, S, Hkv, D] view
    (dequantizing fp8 pages in-graph)."""
    k_l = jax.lax.dynamic_index_in_dim(cache.k, layer, 0, keepdims=False)
    v_l = jax.lax.dynamic_index_in_dim(cache.v, layer, 0, keepdims=False)
    B, mp = cache.block_tables.shape
    page = cache.page_size
    k = k_l[cache.block_tables]  # [B, max_pages, page, Hkv, D]
    v = v_l[cache.block_tables]
    if cache.quantized:
        ks = jax.lax.dynamic_index_in_dim(
            cache.k_scale, layer, 0, keepdims=False)[cache.block_tables]
        vs = jax.lax.dynamic_index_in_dim(
            cache.v_scale, layer, 0, keepdims=False)[cache.block_tables]
        k = k.astype(jnp.float32) * ks[..., None]
        v = v.astype(jnp.float32) * vs[..., None]
    k = k.reshape(B, mp * page, *k.shape[3:])
    v = v.reshape(B, mp * page, *v.shape[3:])
    return k.astype(dtype), v.astype(dtype)


# ---------------------------------------------------------------------------
# the cache KIND: what `serving/engine.InferenceEngine` asks of a paged cache
# ---------------------------------------------------------------------------

class Geometry(NamedTuple):
    """What an engine's constructor fixes of a paged pool's size."""

    n_slots: int
    max_len: int
    page_size: int
    n_pages: int
    max_pages_per_row: int
    quantize_kv: bool = False


_ASKED = {"quantize_kv": "quantize_kv", "speculative": "speculative serving",
          "adapters": "adapter serving",
          "prefill_chunk_tokens": "prefill_chunk_tokens"}


def not_wired(where: str, *features: str) -> dict:
    """`CacheKind.refuses` for features a kind has not been given yet."""
    return {f: f"{_ASKED[f]} is not wired for {{kind}} yet (ROADMAP {where})"
            for f in features}


class CacheKind:
    """A paged cache kind as the serving engine sees it (docs/serving.md,
    "Cache kinds"): one stateless object beside the cache it describes,
    chosen once (`serving/engine._cache_kind`) and kept as `engine.kind`.
    This class IS the kind of KV pages (`KV_PAGES`); `LATENT_PAGES` below,
    `kvstate.CACHE_KIND`, `kvhybrid.CACHE_KIND` and `kvwindow.CACHE_KIND`
    override what differs."""

    name = label = "kv_pages"  # `label`: what a refusal calls the kind
    arrays: tuple = ("k", "v", "k_scale", "v_scale")  # the pool's leaves
    page_arrays: tuple = arrays  # those a page number of the table indexes
    needs_paged: Optional[str] = None  # the sentence that refuses paged=False
    refuses: dict = {}  # feature -> the sentence that refuses it
    share_prefixes = True  # what it tells `PageTable`, with `window`
    tp_sharded = True  # a mesh shards the pool's KV heads over `tp`

    def check(self, model_type: str, paged: bool, **asked) -> None:
        """Raise the sentence of the first thing asked that it cannot serve."""
        kind = f"{self.label} ({model_type})"
        if not paged:
            if self.needs_paged:
                raise NotImplementedError(self.needs_paged.format(kind=kind))
            return
        for what, sentence in self.refuses.items():
            if asked.get(what):
                raise NotImplementedError(sentence.format(kind=kind))

    def window(self, cfg) -> Optional[int]:
        return None

    def page_geometry(self, n_slots, max_len, page_size, n_pages) -> tuple:
        """(page_size, n_pages) of the table: the caller's, where a page
        holds tokens."""
        return page_size, n_pages

    def make_pool(self, cfg, geo: Geometry):
        return init_paged(
            cfg.num_hidden_layers, geo.n_pages, geo.page_size,
            cfg.num_key_value_heads, cfg.head_dim_, geo.n_slots,
            geo.max_pages_per_row, quantize_kv=geo.quantize_kv)

    def _family_pool(self, cfg, geo: Geometry):
        """`make_pool` of a kind whose family builds its pool."""
        from bigdl_tpu.models import get_family

        return get_family(cfg.model_type).init_paged_cache(
            cfg, geo.n_pages, geo.page_size, geo.n_slots,
            geo.max_pages_per_row)

    def leaves(self, cache) -> tuple:
        """The pool's arrays without tables and positions, as ONE pytree:
        what a program donates."""
        return tuple(getattr(cache, f) for f in self.arrays)

    def with_leaves(self, cache, leaves):
        return dataclasses.replace(cache, **dict(zip(self.arrays, leaves)))

    # ---- an admission's prefill (traced: engine_paged_prefill) -------------

    def row_view(self, leaves, tables, pos0, last_idx, slot, cfg, geo):
        """(the pool behind a one-row table, the one-row cache the prefill
        runs on): here the row's own pages gathered into the dense form."""
        pool = PagedKVCache(
            **dict(zip(self.arrays, leaves)), block_tables=tables[0],
            pos=pos0, start=jnp.zeros((1,), jnp.int32))
        return pool, gather_row(pool)

    def write_back(self, pool, row, n_tokens: int, last_idx, cfg) -> tuple:
        """The pool's leaves after the prefill left `row`."""
        return self.leaves(scatter_row_pages(pool, row, n_tokens))

    def forward_kw(self, last_idx) -> dict:
        """What the prefill's forward takes beside the cache."""
        return {}

    # ---- pages between pools and the host ----------------------------------

    axes: tuple = (1, 1, 1, 1)  # of each array, the axis `_spots` index

    def axes_of(self, cache) -> tuple:
        """`axes` of this cache (a kind whose families lay a leaf out
        differently reads it off the cache)."""
        return self.axes

    def _spots(self, pages, slot, window_pages) -> tuple:
        """Where each array keeps a slot's part, along its axis of `axes`:
        here its pages."""
        return (pages,) * len(self.arrays)

    def copy_page(self, cache, src, dst):
        """One physical page (all layers) into another: the sub-page
        prefix-sharing copy (slots past the shared run are overwritten by
        the tail prefill or masked by pos)."""
        pairs = zip(self.arrays, self._spots(src, None, None),
                    self._spots(dst, None, None))
        return dataclasses.replace(cache, **{
            f: getattr(cache, f).at[:, j].set(getattr(cache, f)[:, i])
            for f, i, j in pairs
            if f in self.page_arrays and getattr(cache, f) is not None})

    def swap_out(self, cache, pages, slot: int, window_pages) -> HostPages:
        """Copy the slot's part of every array to host RAM: its pages
        `pages` (and `window_pages`, physical ids, host lists) and its
        state row, where the kind has them."""
        at = self._spots(jnp.asarray(list(pages), jnp.int32), slot,
                         jnp.asarray(list(window_pages), jnp.int32))
        return HostPages(**{
            f: None if a is None else np.asarray(a[(slice(None),) * ax + (i,)])
            for f, a, i, ax in zip(self.arrays, self.leaves(cache), at,
                                   self.axes_of(cache))})

    def swap_in(self, cache, parked: tuple, into: tuple):
        """Write `parked` (a `swap_out` blob's arrays, in `arrays` order)
        into `into` = (pages, slot, window pages), which need not be where
        the blob came from; jitted by the engine with the cache donated,
        the scatter is in place, one program per distinct page count."""
        return self.with_leaves(cache, tuple(
            None if a is None else
            a.at[(slice(None),) * ax + (i,)].set(jnp.asarray(p, a.dtype))
            for a, p, i, ax in zip(self.leaves(cache), parked,
                                   self._spots(*into),
                                   self.axes_of(cache))))

    # ---- accounting (host) --------------------------------------------------

    def state_row_nbytes(self, cache) -> int:
        """Bytes of one slot's state row over all layers, where it has one."""
        return 0

    def token_nbytes(self, cfg) -> int:
        """Bytes of one token's latents over all layers, where it has them."""
        return 0

    def note_chunk(self, st, cfg, geo: Geometry, bucket: int, n: int, pool):
        """Add a prefill chunk of `n` tokens padded to `bucket`, about to
        run from `st.written`, to the `_PrefillState`'s counts; `pool` is
        the engine's, read for what is static of it alone."""
        st.row_pages += geo.max_pages_per_row
        st.pages_written += pages_spanned(
            st.written, bucket, geo.page_size, geo.max_pages_per_row)

    def prefill_args(self, st) -> dict:
        """The `prefill` span's arguments: with `page_nbytes`, the pool
        bytes the admission touched."""
        return {"row_pages": st.row_pages, "pages_written": st.pages_written}

    def decode_args(self, cfg, table, live, moved: int, pool) -> dict:
        """The `decode_step` span's: the table's pos still holds the
        step's own; `pool` is the engine's, read for its shapes alone."""
        n_live, grid = table.grid_pages(live)
        return {"live_pages": n_live, "grid_pages": grid}

    def metrics(self, engine) -> list:
        """Its `/metrics` families: (name, type, help, value) each."""
        return []

    def report(self, cache):
        """[B, W] int32 that the kind's forward left in `cache` for the
        host (a block-sparse layer's counts, kvsparse.py), or None: the
        decode step appends it to its one fetch, a prefill returns it."""
        return None


class _LatentPages(CacheKind):
    """Pages of latents (`PagedLatentCache`): an MLA family's, made by its
    `init_paged_cache`; the prefill runs on the pool itself."""

    name, label = "latent_pages", "latent pages"
    arrays = page_arrays = ("lat",)
    refuses = not_wired("R1", "quantize_kv", "speculative", "adapters")
    tp_sharded = False
    make_pool = CacheKind._family_pool

    def row_view(self, leaves, tables, pos0, last_idx, slot, cfg, geo):
        pool = PagedLatentCache(lat=leaves[0], block_tables=tables[0],
                                pos=pos0, start=jnp.zeros((1,), jnp.int32))
        return pool, pool

    def write_back(self, pool, row, n_tokens, last_idx, cfg):
        return self.leaves(row)

    def token_nbytes(self, cfg) -> int:
        from bigdl_tpu.models import get_family

        return get_family(cfg.model_type).latent_token_nbytes(cfg)

    def note_chunk(self, st, cfg, geo, bucket, n, pool):
        # the expanded form up-projects the row's whole capacity
        st.upprojected += geo.max_pages_per_row * geo.page_size

    def prefill_args(self, st):
        return {"latent_tokens_upprojected": st.upprojected}

    def decode_args(self, cfg, table, live, moved, pool):
        from bigdl_tpu.ops.pallas.paged_attention import latent_group_pages

        rows = [int(i) for i in np.nonzero(live)[0]]
        n = sum(table.pos[i] + 1 for i in rows)  # slots 0..pos
        mp = table.max_pages_per_row
        group = latent_group_pages(pool.lat, cfg.num_attention_heads, mp)
        return {**super().decode_args(cfg, table, live, moved, pool),
                "latent_live_tokens": int(n),
                "latent_bytes_read": int(n * self.token_nbytes(cfg)),
                # one call of the decode kernel (a layer): a grid step a
                # slot, a loop trip a group of pages from an engine row's
                # slot 0 (its `start`) up to its pos, as `grid_pages` counts
                "attn_grid_steps": table.n_slots,
                "attn_live_groups": sum(
                    min(table.pos[i] // table.page_size, mp - 1) // group + 1
                    for i in rows)}

    def metrics(self, engine):
        pool = engine.pages.pool
        return [
            ("bigdl_tpu_latent_pages_in_use", "gauge", "latent pages held "
             "by slots or the prefix cache (of n_pages - 1)",
             pool.n_pages - 1 - pool.n_free),
            ("bigdl_tpu_latent_token_bytes", "gauge", "bytes of one "
             "token's latents over all layers",
             self.token_nbytes(engine.config))]


KV_PAGES = CacheKind()
LATENT_PAGES = _LatentPages()
