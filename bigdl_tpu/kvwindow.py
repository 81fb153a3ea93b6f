"""Two groups of pages in one slot: the cache of a model whose attention
layers are of two kinds, some over every position and some over a window.

Beside `kvpaged.py` (ONE pool `[L, n_pages, page, Hkv, D]` and one block
table a row, so every layer keeps every position), `kvhybrid.py` (pages
beside a state row) and `kvstate.py` (a state row alone): a model such as
SmallThinker (`models/smallthinker.py`) attends in one layer of four to
every position and in the other three to the last `window` positions only.
A window layer's keys behind `pos - window` are never read again, so their
pages go back to the pool while the request is still decoding:

    k,  v  [Lg, n_pages,   page, Hkv, D]   GLOBAL group: the full layers
    kw, vw [Lw, n_pages_w, page, Hkv, D]   WINDOW group: the window layers
    block_tables  [B, max_pages]   the global group's, as kvpaged's
    window_tables [B, max_pages]   the window group's: entry j is the page
                                   of positions j * page ..; 0 (the scratch
                                   page) where the slot holds none, which is
                                   every page behind the window
    pos, start [B]                 one position a row, shared

`serving/pages.PageTable` owns the page NUMBERS of both groups (booked,
extended, freed behind the window, parked, restored, released, counted);
this module owns the arrays. The window group's pool takes no argument of
its own: a slot holds at most `window // page + 2` window pages (the window
straddles one page boundary, and the next page is booked before the oldest
is freed), so `window_pool_pages` sizes it from the slots alone.

A decode step writes its token through both tables and attends with
`ops/pallas/paged_attention.paged_decode_attention`, a full layer with no
window over the global group and a window layer with the window over its
own: `live_page_range` starts at `max(start, pos - window + 1)`, so a page
that was freed is never fetched. An admission's prefill gathers the row's
pages of each group into a dense row (`gather_rows`), prefills that at a
scalar position (a contiguous write, the flash kernel with the layer's
window) and writes back what its tokens span, for the window group only the
pages still inside the window at the prompt's end (`scatter_rows`).

The same dataclass without tables is the DENSE form, `[L, B, S, Hkv, D]`
in both groups: the row a prefill works on, and `TpuModel.generate`'s cache
(every position kept, the window a mask).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu import kvcache, kvpaged

KIND = "window_pages_beside_pages"


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PageGroups:
    k: jax.Array  # global group [Lg, n_pages, page, Hkv, D]; dense [Lg, B, S, ..]
    v: jax.Array
    kw: jax.Array  # window group [Lw, n_pages_w, page, Hkv, D]; dense [Lw, B, S, ..]
    vw: jax.Array
    pos: jax.Array  # [B] int32 next slot per row (a scalar in a dense row)
    start: jax.Array  # [B] int32 first valid slot (left padding)
    block_tables: Optional[jax.Array] = None  # [B, max_pages]; None = dense
    window_tables: Optional[jax.Array] = None  # [B, max_pages]

    @property
    def paged(self) -> bool:
        return self.block_tables is not None

    @property
    def page_size(self) -> int:
        return self.k.shape[2]

    @property
    def max_len(self) -> int:  # logical capacity per row
        if self.paged:
            return self.block_tables.shape[1] * self.page_size
        return self.k.shape[2]

    def group(self, window: bool):
        """One group as the cache `kvcache.update_layer` / `read_layer`
        write and read: `kvpaged.PagedKVCache` through that group's table,
        or a dense `kvcache.KVCache`."""
        k, v = (self.kw, self.vw) if window else (self.k, self.v)
        if self.paged:
            return kvpaged.PagedKVCache(
                k=k, v=v, pos=self.pos, start=self.start,
                block_tables=(self.window_tables if window
                              else self.block_tables))
        return kvcache.KVCache(k=k, v=v, k_scale=None, v_scale=None,
                               pos=self.pos, start=self.start)

    def with_group(self, window: bool, c) -> "PageGroups":
        if window:
            return dataclasses.replace(self, kw=c.k, vw=c.v)
        return dataclasses.replace(self, k=c.k, v=c.v)


def window_pool_pages(n_slots: int, window: int, page: int) -> int:
    """Pages of the window group's pool, the scratch page 0 among them: a
    slot never holds more than `window // page + 2`."""
    return n_slots * (window // page + 2) + 1


def first_live_page(pos: int, window: int, page: int) -> int:
    """The first logical page a query at slot `pos` still reads in a window
    layer (it attends to slots > pos - window): every page before it is
    dead for good."""
    return max(pos - window + 1, 0) // page


def init_groups(n_global: int, n_window: int, n_pages: int, page_size: int,
                n_kv_heads: int, head_dim: int, batch: int,
                max_pages_per_row: int, window: int,
                dtype=jnp.bfloat16) -> PageGroups:
    """Zeros: two pools nobody holds a page of. `n_pages` sizes the global
    group, as every KV pool; the window group's follows from the slots."""
    if not (n_global and n_window):
        raise NotImplementedError(
            f"{KIND}: a model with {n_global} full and {n_window} window "
            "layers has one kind of layer; kvpaged.PagedKVCache serves it")
    tail = (page_size, n_kv_heads, head_dim)
    n_w = window_pool_pages(batch, window, page_size)
    table = jnp.zeros((batch, max_pages_per_row), jnp.int32)
    return PageGroups(
        k=jnp.zeros((n_global, n_pages) + tail, dtype),
        v=jnp.zeros((n_global, n_pages) + tail, dtype),
        kw=jnp.zeros((n_window, n_w) + tail, dtype),
        vw=jnp.zeros((n_window, n_w) + tail, dtype),
        block_tables=table, window_tables=table,
        pos=jnp.zeros((batch,), jnp.int32),
        start=jnp.zeros((batch,), jnp.int32))


def init_dense(n_global: int, n_window: int, batch: int, max_len: int,
               n_kv_heads: int, head_dim: int,
               dtype=jnp.bfloat16) -> PageGroups:
    """The dense form at a scalar position: every position of both groups."""
    tail = (batch, max_len, n_kv_heads, head_dim)
    return PageGroups(
        k=jnp.zeros((n_global,) + tail, dtype),
        v=jnp.zeros((n_global,) + tail, dtype),
        kw=jnp.zeros((n_window,) + tail, dtype),
        vw=jnp.zeros((n_window,) + tail, dtype),
        pos=jnp.zeros((), jnp.int32), start=jnp.zeros((batch,), jnp.int32))


def page_nbytes(cache: PageGroups) -> tuple[int, int]:
    """Bytes of ONE page over its group's layers: (global, window)."""
    def one(a):
        return 2 * a.shape[0] * int(np.prod(a.shape[2:])) * a.dtype.itemsize

    return one(cache.k), one(cache.kw)


def advance(cache: PageGroups, n: int) -> PageGroups:
    return dataclasses.replace(cache, pos=cache.pos + n)


# ---------------------------------------------------------------------------
# an admission's prefill: the row's pages out of both pools and back
# ---------------------------------------------------------------------------

def gather_rows(cache: PageGroups) -> PageGroups:
    """ONE row's pages (tables [1, max_pages]) of both groups as the dense
    form at the row's scalar position (`kvpaged.gather_row`, a group
    each). A window entry the slot does not hold brings the scratch page:
    those slots lie behind the window of every query of the prefill."""
    g = kvpaged.gather_row(cache.group(False))
    w = kvpaged.gather_row(cache.group(True))
    return PageGroups(k=g.k, v=g.v, kw=w.k, vw=w.v, pos=g.pos, start=g.start)


def window_pages_most(n_tokens: int, window: int, page: int) -> int:
    """The most window pages a prefill of `n_tokens` padded positions
    writes back: the pages from the window's first at the prompt's end to
    the padded end (the padding is under 16 positions)."""
    return min((n_tokens + page - 2) // page + 1,
               (window + 15) // page + 2)


def scatter_rows(cache: PageGroups, row: PageGroups, n_tokens: int,
                 n_valid: jax.Array, window: int) -> PageGroups:
    """Write back what a prefill of `n_tokens` positions (the first
    `n_valid` of them tokens) from `cache.pos[0]` wrote into `row`: the
    global group's pages whole (`kvpaged.scatter_row_pages`), the window
    group's from the first page a query at the prompt's end still reads."""
    page = cache.page_size
    g = kvpaged.scatter_row_pages(cache.group(False), row.group(False),
                                  n_tokens)
    end = cache.pos[0] + n_valid  # the next position: the first decode's
    first = jnp.maximum(end - window + 1, 0) // page
    w = kvpaged.scatter_row_pages(
        cache.group(True), row.group(True), n_tokens,
        first=jnp.maximum(first, cache.pos[0] // page),
        most=window_pages_most(n_tokens, window, page))
    return dataclasses.replace(cache, k=g.k, v=g.v, kw=w.k, vw=w.v)


def window_pages_spanned(pos: int, n_tokens: int, n_valid: int, window: int,
                         page: int, max_pages: int) -> int:
    """The host's count of the window pages `scatter_rows` writes back."""
    last = min((pos + n_tokens - 1) // page, max_pages - 1)
    first = max(first_live_page(pos + n_valid, window, page), pos // page)
    return max(last - first + 1, 0)


# ---------------------------------------------------------------------------
# the cache kind (kvpaged.CacheKind): two groups of pages, a table each
# ---------------------------------------------------------------------------

class _PageGroups(kvpaged.CacheKind):
    name = label = KIND
    arrays = ("k", "v", "kw", "vw")
    page_arrays = ("k", "v")
    needs_paged = (
        "{kind} is served with paged=True: a slot holds KV pages for the "
        "attention layers in two groups, and frees the window group's "
        "behind the window")
    refuses = kvpaged.not_wired("R3", "quantize_kv", "speculative",
                                "adapters", "prefill_chunk_tokens")
    # a prefix hit would need the window pages of the prefix's last `window`
    # tokens, which the request that wrote them has freed by then
    share_prefixes = tp_sharded = False
    make_pool = kvpaged.CacheKind._family_pool

    def window(self, cfg):
        return cfg.sliding_window

    def row_view(self, leaves, tables, pos0, last_idx, slot, cfg, geo):
        """The row's own pages of both groups, gathered once into the dense
        form at the row's scalar position."""
        pool = PageGroups(
            **dict(zip(self.arrays, leaves)), block_tables=tables[0],
            window_tables=tables[1], pos=pos0,
            start=jnp.zeros((1,), jnp.int32))
        return pool, gather_rows(pool)

    def write_back(self, pool, row, n_tokens, last_idx, cfg):
        """A page at a time: the global group's pages whole, the window
        group's only from the first page a query at the prompt's end
        (`pos0 + last_idx + 1`) still reads."""
        return self.leaves(scatter_rows(pool, row, n_tokens, last_idx + 1,
                                        cfg.sliding_window))

    def forward_kw(self, last_idx):
        return {"logits_at": last_idx}  # the head on the last token alone

    def _spots(self, pages, slot, window_pages):
        return pages, pages, window_pages, window_pages

    def note_chunk(self, st, cfg, geo, bucket, n, pool):
        page, mp = geo.page_size, geo.max_pages_per_row
        st.row_pages += 2 * mp
        st.window_pages_written += window_pages_spanned(
            st.written, bucket, n, cfg.sliding_window, page, mp)
        st.pages_written += kvpaged.pages_spanned(st.written, bucket, page, mp)

    def prefill_args(self, st):  # the pages written back, by group
        return {"row_pages": st.row_pages,
                "pages_written_global": st.pages_written,
                "pages_written_window": st.window_pages_written}

    def decode_args(self, cfg, table, live, moved, pool):
        # by group, and no one-pool count: a window layer loads fewer pages
        # than `pos` spans. freed = since the step before
        return {**table.group_pages(live),
                "window_pages_freed": table.window_pages_freed_since()}

    def metrics(self, engine):
        """The window group's pages go back to their pool behind the
        window, while the request decodes."""
        in_use = engine.pages.pages_in_use()
        return [
            ("bigdl_tpu_global_pages_in_use", "gauge", "pages of the global "
             "group (full-attention layers) some slot holds", in_use[0]),
            ("bigdl_tpu_window_pages_in_use", "gauge", "pages of the window "
             "group (window layers) some slot holds", in_use[1]),
            ("bigdl_tpu_window_pages_freed_total", "counter", "window pages "
             "given back behind the window by requests still decoding",
             engine.pages.window_pages_freed)]


CACHE_KIND = _PageGroups()
