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
from typing import Optional

import jax
import jax.numpy as jnp


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
        import numpy as np

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

@dataclasses.dataclass
class HostKVPages:
    """A preempted request's KV pages parked in host RAM (all layers,
    page-granular). The serving engine swaps a victim out here, releases
    its device pages, and swaps back into freshly allocated (possibly
    different) physical pages on resume — contents are byte-preserved, so
    decode after swap-in is bit-exact with the uninterrupted run. On a
    real TPU runtime `jax.device_get` stages through the runtime's host
    transfer buffers; the arrays below are plain (pageable) numpy — a
    pinned-allocation fast path is a perf follow-up, not a correctness
    one."""

    k: "object"  # np.ndarray [L, n, page, Hkv, D] in the pool dtype
    v: "object"
    k_scale: Optional[object] = None  # [L, n, page, Hkv] when quantized
    v_scale: Optional[object] = None

    @property
    def n_pages(self) -> int:
        return self.k.shape[1]

    @property
    def nbytes(self) -> int:
        n = self.k.nbytes + self.v.nbytes
        if self.k_scale is not None:
            n += self.k_scale.nbytes + self.v_scale.nbytes
        return n


def swap_out_pages(cache: PagedKVCache, pages) -> HostKVPages:
    """Copy the listed physical pages' KV (every layer) to host RAM.
    `pages` is a host-side list/array of physical page ids; the gather +
    device→host transfer is one fused program per distinct page count."""
    import numpy as np

    ids = jnp.asarray(list(pages), jnp.int32)
    k = np.asarray(jax.device_get(cache.k[:, ids]))
    v = np.asarray(jax.device_get(cache.v[:, ids]))
    ks = vs = None
    if cache.quantized:
        ks = np.asarray(jax.device_get(cache.k_scale[:, ids]))
        vs = np.asarray(jax.device_get(cache.v_scale[:, ids]))
    return HostKVPages(k=k, v=v, k_scale=ks, v_scale=vs)


def swap_in_pages(cache: PagedKVCache, k, v, k_scale, v_scale,
                  pages: jax.Array) -> PagedKVCache:
    """Write a host blob's pages back into physical pages `pages` (a [n]
    int32 array; need not be the pages the blob came from). jit-friendly:
    the engine wraps it with donated cache buffers so the scatter happens
    in place; one compiled program per distinct page count."""
    upd = {"k": cache.k.at[:, pages].set(jnp.asarray(k, cache.k.dtype)),
           "v": cache.v.at[:, pages].set(jnp.asarray(v, cache.v.dtype))}
    if cache.quantized:
        upd["k_scale"] = cache.k_scale.at[:, pages].set(
            jnp.asarray(k_scale, cache.k_scale.dtype))
        upd["v_scale"] = cache.v_scale.at[:, pages].set(
            jnp.asarray(v_scale, cache.v_scale.dtype))
    return dataclasses.replace(cache, **upd)


@dataclasses.dataclass
class HostLatentPages:
    """`HostKVPages` for a `PagedLatentCache`: the parked pages' latents,
    every layer, byte for byte."""

    lat: "object"  # np.ndarray [L, n, page, r + dr] in the pool dtype

    @property
    def n_pages(self) -> int:
        return self.lat.shape[1]

    @property
    def nbytes(self) -> int:
        return self.lat.nbytes


def swap_out_latent(cache: PagedLatentCache, pages) -> HostLatentPages:
    import numpy as np

    ids = jnp.asarray(list(pages), jnp.int32)
    return HostLatentPages(lat=np.asarray(jax.device_get(cache.lat[:, ids])))


def swap_in_latent(cache: PagedLatentCache, lat,
                   pages: jax.Array) -> PagedLatentCache:
    """`swap_in_pages` for latent pages (jitted by the engine with the
    cache donated; one program per distinct page count)."""
    return dataclasses.replace(cache, lat=cache.lat.at[:, pages].set(
        jnp.asarray(lat, cache.lat.dtype)))


def copy_latent_page(cache: PagedLatentCache, src, dst) -> PagedLatentCache:
    """One physical page's latents (all layers) into another: the sub-page
    prefix-sharing copy."""
    return dataclasses.replace(
        cache, lat=cache.lat.at[:, dst].set(cache.lat[:, src]))


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
