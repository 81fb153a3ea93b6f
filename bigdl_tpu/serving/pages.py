"""The paged KV cache's HOST bookkeeping, behind one owner (ROADMAP D3).

`InferenceEngine` (serving/engine.py) holds one `PageTable` when it is
paged and asks it for everything that is a page NUMBER: which physical
pages a slot holds, how far they are written, which of them the radix
prefix cache (serving/radix.py) shares, and what the device's block
table must say. The engine keeps what is a device ARRAY (the pool
itself, `cache.pos`, the prefill, copy and swap programs) and what is
scheduling (who is admitted, who is preempted).

The rule the table enforces, in one place: every holder of a page
carries exactly one reference in the `kvpaged.PagePool` (one per slot
block-table entry, one per cached radix node, one per resident adapter
page), and a call that takes references either hands all of them to a
slot or gives all of them back. graftlint's PAGE001 / PAGE002
(analysis/flow.py) prove that per function.

Pure host code: no jax, no clock reads, engine thread only.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from bigdl_tpu.kvpaged import PagePool
from bigdl_tpu.kvwindow import first_live_page, window_pool_pages
from bigdl_tpu.serving.faults import NULL_INJECTOR
from bigdl_tpu.serving.radix import RadixPrefixCache
from bigdl_tpu.utils import round_up


def prefill_bucket(n_tokens: int, room: int) -> int:
    """Padded width of one prefill call over `n_tokens` with `room` left
    in the row. 16-token quantum (was 32): post-hit tails are short, and
    halving the pad floor halves the wasted prefill width a mid-page
    split pays — this is what makes sub-page reuse actually engage.
    `reserve` plans a slot's pages by it and the engine pads by it."""
    return min(round_up(max(n_tokens, 16), 16), room)


class NeverFits(Exception):
    """A prompt whose pages the pool could not hold even when empty."""


@dataclasses.dataclass
class Reservation:
    """What `PageTable.reserve` booked for a slot, and what is left for
    the device to do: copy one page (or not), prefill the rest."""

    row: np.ndarray  # the slot's block-table row (scratch page 0 past
    # its pages); `install` it when the slot may be decoded
    covered: int  # prompt tokens the cache holds once `copy` has run
    copy: Optional[tuple[int, int]]  # (source, destination) page to copy
    # on the device before the prefill: a prefix that diverges mid-page
    path: list  # the matched radix nodes, for `register_prefix`
    wrow: Optional[np.ndarray] = None  # two groups of pages: the slot's
    # row of the WINDOW group's table, `install`ed with `row`


class PageTable:
    """Per-slot page lists, written coverage and positions, the pool's
    refcounts, the radix prefix cache and the block table's host mirror.

    Physical page 0 is the scratch sink: idle slots still run the decode
    step (static-shape price) and their masked garbage writes go through
    their block tables, so a released slot's row points every entry at
    page 0 and can never corrupt pages reallocated to live requests.

    With `window` set the slot holds TWO lists of pages (bigdl_tpu/
    kvwindow.py): the global group's, everything above, and the window
    group's, out of a pool of its own (`wpool`, its own scratch page 0).
    The two are booked and extended together to the same logical end; a
    window page goes back to its pool in the step whose `advance` carries
    `pos - window` past its last position (`_free_behind`), and its entry
    of the window table goes back to the scratch page."""

    #: what `rebuilt` carries over: engine totals, not cache state
    TOTALS = ("prefix_hits", "prefix_partial_hits", "prefix_tokens_reused",
              "prefix_evictions")

    def __init__(self, n_slots: int, n_pages: int, page_size: int,
                 max_pages_per_row: int, max_len: int,
                 faults=NULL_INJECTOR, share_prefixes: bool = True,
                 window: Optional[int] = None):
        self.n_slots = n_slots
        # False for a model whose page is a recurrent state ROW
        # (bigdl_tpu/kvstate.py: one page of `max_len` tokens a slot, page
        # p is state row p - 1): the row is booked, parked, restored,
        # released and counted like any page, but it holds the whole
        # context folded together, so a prompt's prefix cannot be shared
        # out of it. A prefix hit would need a snapshot of the state at
        # the prefix's end; none is kept, so `register_prefix` registers
        # nothing, the radix tree stays empty and no admission matches
        self.share_prefixes = share_prefixes
        self.page_size = page_size
        self.max_pages_per_row = max_pages_per_row
        self.max_len = max_len
        self._faults = faults
        self.pool = PagePool(n_pages)
        # full-page descent + mid-page divergence match + leaf-first LRU
        # eviction; one pool reference per cached node
        self.radix = RadixPrefixCache(page_size, self.pool)
        # serving/adapters.AdapterPager, when resident adapters draw on
        # this pool (the engine attaches it): the ladder's third rung
        self.pager = None
        self.slot_pages: list[list[int]] = [[] for _ in range(n_slots)]
        self.written = [0] * n_slots  # logical slots covered, page-ALIGNED
        self.pos = [0] * n_slots  # host mirror of cache.pos
        self._bt = np.zeros((n_slots, max_pages_per_row), np.int32)
        self._bt_dirty = True
        self.prefix_hits = 0  # admissions that reused full cached pages
        # sub-page sharing: cached-page KV copied instead of re-prefilled
        # when a prefix diverges mid-page
        self.prefix_partial_hits = 0
        self.prefix_tokens_reused = 0
        self.prefix_evictions = 0  # radix leaves dropped for pages
        # two groups of pages: the window group's pool, each slot's pages
        # (logical page `win_first[slot] + i` is `win_pages[slot][i]`) and
        # the window table's mirror, uploaded with the block table's
        self.window = window
        self.wpool = None
        self.win_pages: list[list[int]] = [[] for _ in range(n_slots)]
        self.win_first = [0] * n_slots
        if window is not None:
            self.wpool = PagePool(window_pool_pages(n_slots, window,
                                                    page_size))
            self.window_table = np.zeros_like(self._bt)
            self.window_pages_freed = 0  # behind the window, ever
            self._freed_noted = 0  # ... and at `window_pages_freed_since`

    def rebuilt(self) -> "PageTable":
        """The table as the constructor makes it, for a device pool that
        was itself rebuilt (every page this one names is dead); the
        totals and the adapter pager go with it. Resident adapters
        referenced the dead pool's pages: their residency is dropped
        (host copies in the registry survive, the next admission pages
        them in again)."""
        new = PageTable(self.n_slots, self.pool.n_pages, self.page_size,
                        self.max_pages_per_row, self.max_len, self._faults,
                        self.share_prefixes, self.window)
        if self.window is not None:
            new.window_pages_freed = self.window_pages_freed
            new._freed_noted = self._freed_noted
        for name in self.TOTALS:
            setattr(new, name, getattr(self, name))
        if self.pager is not None:
            self.pager.reset(new.pool, new.alloc)
            new.pager = self.pager
        return new

    # ---- allocation --------------------------------------------------------

    def alloc(self) -> Optional[int]:
        """A free page, evicting LRU radix leaves while the free list is
        dry, then paging out holder-free adapters (they share this
        pool's budget, and their host copies make page-out free to
        undo). Eviction only ever drops pages no slot holds, so it
        composes with preemption: the escalation order is free list ->
        cache eviction -> adapter page-out -> host-RAM swap-out (the
        engine's, which chooses a victim: _alloc_page_preempting)."""
        if self._faults.fire("alloc_page") is not None:
            return None  # injected pool exhaustion (serving/faults.py)
        if self.wpool is not None and not self.wpool.n_free:
            return None  # the groups grow together: neither without the other
        pg = self.pool.alloc()
        while pg is None and self.radix.evict_one():
            self.prefix_evictions += 1
            pg = self.pool.alloc()
        while pg is None and self.pager is not None \
                and self.pager.evict_one():
            pg = self.pool.alloc()
        return pg

    # ---- a slot's life -----------------------------------------------------

    def cached_len(self, prompt: list, ns=None) -> int:
        """Prompt tokens the cached full-page run would cover. Read-only:
        scoring a queued request must not LRU-promote its pages."""
        return self.radix.match_len(prompt, ns=ns)

    def reserve(self, slot: int, prompt: list,
                ns=None) -> Optional[Reservation]:
        """Book `slot`'s pages for `prompt`: the longest cached prefix
        from the radix tree (full pages by descent, a mid-page divergence
        through a page copy) in the adapter namespace `ns`, and fresh
        pages for the whole remainder. None = the pool is dry now and
        every reference taken on the way was given back; NeverFits = no
        pool of this size can hold the prompt."""
        page = self.page_size
        # matched nodes are LRU-refreshed. Pages prefilled under a LoRA
        # adapter carry its shifted K/V, so tenants never share pages
        # with each other or with the base (radix.root_for)
        path = self.radix.match(prompt, ns=ns)
        shared = [nd.page for nd in path]
        n_hit = len(shared)
        lp = n_hit * page
        tail = prompt[lp:]
        head_node = path[-1] if path else self.radix.root_for(ns)

        # sub-page sharing: the deepest matched node's child whose page
        # agrees with our tail for t_copy tokens lets us COPY those KV
        # slots instead of re-prefilling them. Capped at len(tail)-1 so
        # the last real token always prefills (its logits seed
        # generation).
        t_copy, src_node = 0, None
        if len(tail) > 1:
            m, child = self.radix.match_partial(head_node, tail)
            t_copy = min(m, len(tail) - 1)
            src_node = child if t_copy > 0 else None

        def plan(cut):
            # prefilling the rest in one piece: its width, and the fresh
            # pages that takes (the copy is skipped unless it shrinks one)
            b = prefill_bucket(len(prompt) - lp - cut,
                               self.max_len - lp - cut)
            return b, -(-(lp + cut + b) // page) - n_hit

        bucket, need = plan(0)
        if src_node is not None:
            bucket1, need1 = plan(t_copy)
            # prefill cost is quantized to the bucket/page plan: a copy
            # that doesn't shrink either is pure added latency (the
            # page-copy dispatch + LRU bookkeeping) — skip it
            if bucket1 >= bucket and need1 >= need:
                src_node = None
            else:
                bucket, need = bucket1, need1
        if src_node is None:
            t_copy = 0
        src_page = src_node.page if src_node is not None else None
        if need > self.pool.n_pages - 1:  # can NEVER be satisfied (page 0
            # is scratch): fail now instead of head-of-line blocking
            raise NeverFits(
                f"prompt needs {need} pages but the pool only has "
                f"{self.pool.n_pages - 1}; raise n_pages or shorten the "
                "prompt"
            )
        w_first = 0
        if self.window is not None:
            # the window group: the pages a query at the prompt's end still
            # reads, up to the global group's end
            w_first = first_live_page(len(prompt), self.window, page)
            if n_hit + need - w_first > self.wpool.n_pages - 1:
                raise NeverFits(
                    f"prompt needs {n_hit + need - w_first} window pages "
                    f"but that pool only has {self.wpool.n_pages - 1}")
        # incref shared pages (and the sub-page copy source) BEFORE
        # allocating fresh ones — alloc's radix eviction must not evict
        # a page out of this very request's prefix (cache-only holds
        # are fair eviction game)
        for pg in shared:
            self.pool.incref(pg)
        if src_page is not None:
            self.pool.incref(src_page)
        fresh: list[int] = []
        for _ in range(need):
            pg = self.alloc()
            if pg is None:  # out of pages: roll back, retry next step
                for q in fresh:
                    self.pool.decref(q)
                for q in shared:
                    self.pool.decref(q)
                if src_page is not None:
                    self.pool.decref(src_page)
                return None
            fresh.append(pg)
        wrow = None
        if self.window is not None:
            held = self._window_pages(n_hit + need - w_first)
            if held is None:  # roll back both groups, retry next step
                for q in fresh:
                    self.pool.decref(q)
                for q in shared:
                    self.pool.decref(q)
                if src_page is not None:
                    self.pool.decref(src_page)
                return None
            wrow = self._seat_window(slot, w_first, held)
        if n_hit:
            self.prefix_hits += 1
        row = self._seat(slot, shared + fresh)
        copy = None
        if src_page is not None:
            # the WHOLE source page is copied (one static-shape program;
            # slots past t_copy are overwritten by the tail prefill or
            # masked by pos). Its node keeps the page alive; the hold
            # taken above only kept eviction off it while allocating
            self.pool.decref(src_page)
            self.prefix_partial_hits += 1
            self.prefix_tokens_reused += t_copy
            self.radix.touch(src_node)  # it just proved hot
            copy = (src_page, fresh[0])
        return Reservation(row, lp + t_copy, copy, path, wrow)

    def _seat(self, slot: int, table: list[int]) -> np.ndarray:
        """`table` becomes the slot's pages; returns its block-table row."""
        self.slot_pages[slot] = table
        # page-ALIGNED coverage: extension is by whole pages, so a
        # non-aligned start would drift the page index
        self.written[slot] = len(table) * self.page_size
        row = np.zeros((self.max_pages_per_row,), np.int32)
        row[: len(table)] = table
        return row

    def _window_pages(self, n: int) -> Optional[list[int]]:
        """`n` fresh pages of the window group, or None with nothing held."""
        held: list[int] = []
        for _ in range(n):
            pg = self.wpool.alloc()
            if pg is None:
                for q in held:
                    self.wpool.decref(q)
                return None
            held.append(pg)
        return held

    def _seat_window(self, slot: int, first: int,
                     held: list[int]) -> np.ndarray:
        """`held` become the slot's window pages from logical page `first`;
        returns its row of the window table."""
        self.win_first[slot], self.win_pages[slot] = first, held
        wrow = np.zeros((self.max_pages_per_row,), np.int32)
        wrow[first: first + len(held)] = held
        return wrow

    def install(self, slot: int, row: np.ndarray, pos: int,
                wrow: Optional[np.ndarray] = None) -> None:
        """The slot's KV is written up to `pos` and the decode step may
        go through its pages: until now its row pointed at scratch."""
        if wrow is not None:
            self.window_table[slot] = wrow
        self._bt[slot] = row
        self._bt_dirty = True
        self.pos[slot] = pos

    def register_prefix(self, slot: int, prompt: list, path: list,
                        ns=None) -> None:
        """Register the prompt's fully-covered pages past the matched
        run as radix nodes (the cache takes its own page reference).
        An existing edge keeps its canonical page — our duplicate stays
        slot-only and frees at release. `ns` = the request's adapter
        name: adapter-prefilled pages register under that tenant's own
        radix root, never the shared base tree."""
        if not self.share_prefixes:
            return
        page = self.page_size
        table = self.slot_pages[slot]
        node = path[-1] if path else self.radix.root_for(ns)
        for i in range(len(path), len(prompt) // page):
            key = tuple(prompt[i * page: (i + 1) * page])
            nxt = node.children.get(key)
            if nxt is None:
                nxt = self.radix.insert(node, key, table[i])
            node = nxt

    def short(self, slot: int, need_tokens: int) -> bool:
        """Would the slot's next `need_tokens` writes run past its pages?"""
        return self.pos[slot] + need_tokens > self.written[slot]

    def row_full(self, slot: int) -> bool:
        """Logical capacity: the row has no entry left for another page."""
        return len(self.slot_pages[slot]) >= self.max_pages_per_row

    def extend(self, slot: int, pg: int) -> None:
        """One more whole page at the end of the slot's row (and of its
        window group's: `alloc` gave `pg` only with a window page free)."""
        if self.window is not None:
            wpg = self.wpool.alloc()
            self.window_table[slot, len(self.slot_pages[slot])] = wpg
            self.win_pages[slot].append(wpg)
        self._bt[slot, len(self.slot_pages[slot])] = pg
        self._bt_dirty = True
        self.slot_pages[slot].append(pg)
        self.written[slot] += self.page_size

    def advance(self, slot: int, n: int = 1) -> None:
        """The decode step wrote `n` more tokens of this slot."""
        self.pos[slot] += n
        if self.window is not None:
            self._free_behind(slot)

    def _free_behind(self, slot: int) -> None:
        """Give back the slot's window pages whose last position the window
        has passed: the next query, at `pos`, reads none of them."""
        live = first_live_page(self.pos[slot], self.window, self.page_size)
        pages = self.win_pages[slot]
        n = min(live - self.win_first[slot], len(pages))
        if n <= 0:
            return
        for pg in pages[:n]:
            self.wpool.decref(pg)
        del pages[:n]
        first = self.win_first[slot]
        self.window_table[slot, first: first + n] = 0
        self.win_first[slot] = first + n
        self.window_pages_freed += n
        self._bt_dirty = True

    def kv_pages(self, slot: int) -> list[int]:
        """The slot's pages that hold real KV, in order: what a swap-out
        to host RAM must carry."""
        return self.slot_pages[slot][: -(-self.pos[slot] // self.page_size)]

    def window_kv_pages(self, slot: int) -> list[int]:
        """The slot's window pages that hold real KV, in order: from the
        window's first page to the one before `pos`."""
        n = -(-self.pos[slot] // self.page_size) - self.win_first[slot]
        return self.win_pages[slot][: max(n, 0)]

    def restore(self, slot: int, n_pages: int,
                pos: int) -> Optional[list[int]]:
        """Fresh pages for a parked request's swap-in (physical placement
        is irrelevant, the block table re-maps it), installed as the
        slot's row. None = the pool cannot hold the restore yet, and
        nothing is held. With two groups the window's pages at `pos` come
        with them (`win_pages[slot]`, as many as `window_kv_pages` gave)."""
        wrow = None
        if self.window is not None:
            first = first_live_page(pos, self.window, self.page_size)
            held = self._window_pages(n_pages - first)
            if held is None:
                return None
            wrow = self._seat_window(slot, first, held)
        fresh: list[int] = []
        for _ in range(n_pages):
            pg = self.alloc()
            if pg is None:  # roll back; retry when pages free up
                for q in fresh:
                    self.pool.decref(q)
                if wrow is not None:
                    self._drop_window(slot)
                return None
            fresh.append(pg)
        self.install(slot, self._seat(slot, fresh), pos, wrow)
        return fresh

    def _drop_window(self, slot: int) -> None:
        for pg in self.win_pages[slot]:
            self.wpool.decref(pg)
        self.win_pages[slot], self.win_first[slot] = [], 0
        self.window_table[slot] = 0

    def release(self, slot: int) -> None:
        """Drop the slot's holds (a count reaching 0 frees the page;
        cached nodes keep theirs), retarget its garbage decode writes at
        the scratch page and park its position."""
        for pg in self.slot_pages[slot]:
            self.pool.decref(pg)
        if self.window is not None:
            self._drop_window(slot)
        self.slot_pages[slot] = []
        self.written[slot] = 0
        self.pos[slot] = 0
        self._bt[slot] = 0
        self._bt_dirty = True

    def block_table(self) -> Optional[np.ndarray]:
        """The host mirror when the device's block table is stale (the
        caller uploads it before the next step), else None."""
        if not self._bt_dirty:
            return None
        self._bt_dirty = False
        return self._bt

    # ---- what /metrics, the sim report and the tests read ------------------

    def utilization(self) -> float:
        """Allocated pages over the allocatable pool (page 0 is scratch);
        of both pools where there are two."""
        cap = self.pool.n_pages - 1
        free = self.pool.n_free
        if self.wpool is not None:
            cap += self.wpool.n_pages - 1
            free += self.wpool.n_free
        return (cap - free) / max(cap, 1)

    def pages_in_use(self) -> tuple[int, int]:
        """(global, window) pages some holder has, of two groups."""
        return (self.pool.n_pages - 1 - self.pool.n_free,
                self.wpool.n_pages - 1 - self.wpool.n_free)

    def group_pages(self, active: np.ndarray) -> dict:
        """`grid_pages` of two groups, the decode step's span arguments:
        live = the pages the paged kernel must load for a layer of that
        group (the global group's up to each active row's `pos`, the window
        group's from `max(0, pos - window + 1)` on), grid = slots x pages a
        row; the window pages the active rows hold, and what they would
        hold had none been freed (the global group's, booked alike)."""
        mp, page = self.max_pages_per_row, self.page_size
        rows = [int(i) for i in np.nonzero(active)[0]]
        last = [min(self.pos[i] // page, mp - 1) for i in rows]
        return {
            "live_pages_global": sum(p + 1 for p in last),
            "grid_pages_global": self.n_slots * mp,
            "live_pages_window": sum(
                p - first_live_page(self.pos[i], self.window, page) + 1
                for i, p in zip(rows, last)),
            "grid_pages_window": self.n_slots * mp,
            "window_pages_held": sum(len(self.win_pages[i]) for i in rows),
            "window_pages_unfreed": sum(len(self.slot_pages[i])
                                        for i in rows),
        }

    def window_pages_freed_since(self) -> int:
        """Window pages freed since the call before: a decode step's span
        argument."""
        freed = self.window_pages_freed - self._freed_noted
        self._freed_noted = self.window_pages_freed
        return freed

    def grid_pages(self, active: np.ndarray) -> tuple[int, int]:
        """(live, grid) pages of the paged decode kernel's next step, from
        the host's mirror (no device read): the pages up to each active
        row's `pos`, and the kernel's whole grid of slots x pages per row.
        Only the live ones cost the kernel a DMA and a softmax update."""
        mp = self.max_pages_per_row
        live = sum(min(self.pos[int(i)] // self.page_size + 1, mp)
                   for i in np.nonzero(active)[0])
        return live, self.n_slots * mp

    def page_leaks(self) -> int:
        """Pages whose refcount disagrees with their accounted holders
        (slot block tables + radix cache nodes + resident adapters) plus
        any page neither free nor held at all. 0 is the invariant; the
        sim report and the chaos tests gate on it at drain."""
        held = [0] * self.pool.n_pages
        for pages in self.slot_pages:
            for pg in pages:
                held[pg] += 1
        for node in self.radix.nodes():
            held[node.page] += 1
        if self.pager is not None:
            for pg in self.pager.held_pages():
                held[pg] += 1
        leaks = sum(1 for pg in range(1, self.pool.n_pages)
                    if self.pool.ref[pg] != held[pg])
        if self.wpool is not None:  # one holder a window page: its slot
            wheld = [0] * self.wpool.n_pages
            for pages in self.win_pages:
                for pg in pages:
                    wheld[pg] += 1
            leaks += sum(1 for pg in range(1, self.wpool.n_pages)
                         if self.wpool.ref[pg] != wheld[pg])
        return leaks
