"""serving/pages.PageTable alone: no model, no jax program. The
refcount discipline, the allocation ladder and the block table's mirror,
which only end-to-end engine runs reached while the engine held them."""

import numpy as np
import pytest

from bigdl_tpu.serving.faults import FaultInjector
from bigdl_tpu.serving.pages import NeverFits, PageTable

pytestmark = pytest.mark.core

PAGE = 4


def table(n_pages=9, n_slots=3, rows=6, max_len=24, faults=None):
    """Small by default; `wide()` has room for a plan a copy can shrink."""
    kw = {} if faults is None else {"faults": faults}
    return PageTable(n_slots, n_pages, PAGE, rows, max_len, **kw)


def wide(**kw):
    return table(n_pages=24, rows=16, max_len=64, **kw)


A = list(range(100, 110))
B = A[:6] + [7] * 15  # shares A's page 0, agrees with its page 1 for 2


def admit(t, slot, prompt, ns=None):
    """What the engine does around one admission, minus the device."""
    plan = t.reserve(slot, prompt, ns=ns)
    if plan is not None:
        t.install(slot, plan.row, len(prompt))
        t.register_prefix(slot, prompt, plan.path, ns=ns)
    return plan


def seq(n, base=100):
    return list(range(base, base + n))


class StubPager:
    """The adapter pager's side of the ladder: holds pages of the pool,
    gives one back per evict_one."""

    def __init__(self, pool, n):
        self.pool, self.calls = pool, 0
        self.pages = [pool.alloc() for _ in range(n)]

    def held_pages(self):
        return iter(self.pages)

    def evict_one(self):
        self.calls += 1
        if not self.pages:
            return False
        self.pool.decref(self.pages.pop())
        return True

    def reset(self, pool, alloc):
        self.pool, self.alloc, self.pages = pool, alloc, []


@pytest.mark.parametrize("n_prompt", [1, 3, 4, 5, 9, 17])
def test_reserve_then_release_returns_every_page(n_prompt):
    t = table()
    plan = admit(t, 0, seq(n_prompt))
    bucket = min(-(-max(n_prompt, 16) // 16) * 16, 24)
    need = -(-bucket // PAGE)
    assert len(t.slot_pages[0]) == need and t.written[0] == need * PAGE
    assert plan.covered == 0 and plan.copy is None
    assert t.page_leaks() == 0
    t.release(0)
    # full pages of the prompt stay cached (one node, one hold, each)
    assert t.radix.n_nodes == n_prompt // PAGE
    assert t.pool.n_free + t.radix.n_nodes == 8
    assert t.page_leaks() == 0


def test_shared_prefix_and_mid_page_copy_plan():
    t = wide()
    admit(t, 0, A)
    plan = t.reserve(1, B)
    assert plan.path and plan.row[0] == t.slot_pages[0][0]  # page 0 shared
    # the 17 tokens left would prefill as 32; copying 2 makes them 15, a
    # bucket of 16: A's page 1 is copied into B's first fresh page
    assert plan.copy == (t.slot_pages[0][1], t.slot_pages[1][1])
    assert plan.covered == PAGE + 2 and len(t.slot_pages[1]) == 6
    assert (t.prefix_hits, t.prefix_partial_hits,
            t.prefix_tokens_reused) == (1, 1, 2)
    assert t.pool.ref[plan.copy[0]] == 2  # a's slot + its node; no copy hold
    assert t.page_leaks() == 0


@pytest.mark.parametrize("dry", ["free_list", "alloc_page_fault"])
def test_dry_reservation_rolls_back_every_hold(dry):
    inj = FaultInjector(seed=0)
    t = wide(faults=inj)
    admit(t, 0, A)
    ref0, free0, lru0 = list(t.pool.ref), list(t.pool.free), t.radix.n_nodes
    if dry == "free_list":
        saved = list(t.pool.free)
        del t.pool.free[2:]  # 2 pages left, the plan needs more
        assert t.reserve(1, B) is None
        t.pool.free.extend(saved[2:])
    else:
        inj.arm("alloc_page", times=1, after=1)  # the second page
        assert t.reserve(1, B) is None
    assert t.pool.ref == ref0 and sorted(t.pool.free) == sorted(free0)
    assert t.slot_pages[1] == [] and t.written[1] == 0
    assert t.radix.n_nodes == lru0 and t.page_leaks() == 0
    plan = t.reserve(1, B)  # "not now", not "never"
    assert plan.copy is not None and t.page_leaks() == 0


def test_never_fits_says_so_before_taking_anything():
    t = table(n_pages=4)
    with pytest.raises(NeverFits, match="needs 4 pages but the pool only "
                                        "has 3"):
        t.reserve(0, seq(5))
    assert t.pool.n_free == 3 and t.page_leaks() == 0


def test_eviction_spares_the_prefix_being_admitted():
    t = table(n_pages=11, rows=10, max_len=40)
    a = seq(8)
    admit(t, 0, a + [1])
    cached_a = t.slot_pages[0][:2]
    t.release(0)  # a's two full pages are the cache's only leaves
    admit(t, 2, seq(3, base=500))
    held = t.alloc()
    assert t.radix.n_nodes == 2 and t.pool.n_free == 3
    # shares a's pages and needs 4 fresh ones. The fourth could only come
    # from evicting a's own leaf, which would hand the request its own
    # prefix page as a fresh one: the holds taken first forbid it
    b = a + seq(13, base=900)
    assert t.reserve(1, b) is None
    assert t.prefix_evictions == 0 and t.radix.n_nodes == 2
    assert [t.pool.ref[pg] for pg in cached_a] == [1, 1]
    t.pool.decref(held)
    plan = t.reserve(1, b)
    assert list(plan.row[:2]) == cached_a and t.prefix_evictions == 0
    assert len(set(t.slot_pages[1])) == 6 and t.page_leaks() == 0


def test_ladder_order_free_list_then_radix_then_adapter_pager():
    t = table(n_pages=6)
    admit(t, 0, seq(4) + [1])  # one cached page once released
    t.release(0)
    t.pager = StubPager(t.pool, 2)
    took = []
    while t.pool.n_free:
        took.append(t.alloc())
    assert t.prefix_evictions == 0 and t.pager.calls == 0  # free list first
    took.append(t.alloc())
    assert t.prefix_evictions == 1 and t.pager.calls == 0  # then the radix
    took.append(t.alloc())
    assert t.prefix_evictions == 1 and t.pager.calls == 1  # then adapters
    took.append(t.alloc())
    assert t.alloc() is None and t.pager.calls == 3  # all three dry
    assert sorted(took) == [1, 2, 3, 4, 5]


def test_extension_is_by_whole_pages_and_keeps_the_index_aligned():
    t = table(n_pages=12)
    admit(t, 0, seq(3))  # bucket 16: pages for tokens 0..15
    t.advance(0, 12)
    assert not t.short(0, 1) and t.short(0, 2)
    idx = len(t.slot_pages[0])
    pg = t.alloc()
    t.extend(0, pg)
    assert t.slot_pages[0][idx] == pg and t.block_table()[0, idx] == pg
    assert t.written[0] == (idx + 1) * PAGE and not t.short(0, 2)
    t.extend(0, t.alloc())
    assert t.row_full(0)  # 6 entries a row
    assert t.page_leaks() == 0


def test_released_row_points_at_scratch_and_upload_is_once_per_change():
    t = table()
    assert t.block_table() is not None and t.block_table() is None
    plan = admit(t, 1, seq(5))
    bt = t.block_table()
    assert list(bt[1][:4]) == t.slot_pages[1] and t.pos[1] == 5
    assert (bt[1] == plan.row).all() and t.block_table() is None
    t.release(1)
    bt = t.block_table()
    assert not bt[1].any() and t.pos[1] == 0 and t.written[1] == 0


def test_a_chunk_plan_keeps_its_row_off_the_table_until_installed():
    t = table()
    t.block_table()
    plan = t.reserve(0, seq(9))
    assert t.slot_pages[0] and t.block_table() is None  # row not installed
    t.install(0, plan.row, 9)
    assert (t.block_table()[0] == plan.row).all()


@pytest.mark.parametrize("room", [True, False])
def test_restore_takes_fresh_pages_or_nothing(room):
    t = table(n_pages=6)
    admit(t, 0, seq(3))  # 4 of the 5 pages
    t.advance(0, 6)
    assert t.kv_pages(0) == t.slot_pages[0][:3]  # pos 9: three pages of KV
    if room:
        t.release(0)
    fresh = t.restore(1, 3, 9)
    if room:
        assert fresh == t.slot_pages[1] and len(set(fresh)) == 3
        assert t.pos[1] == 9 and t.written[1] == 3 * PAGE
        assert list(t.block_table()[1][:3]) == fresh
    else:
        assert fresh is None and t.slot_pages[1] == []
        assert t.pool.n_free == 1
    assert t.page_leaks() == 0


def test_rebuilt_table_is_a_fresh_one_but_for_the_totals():
    inj = FaultInjector(seed=0)
    t = wide(faults=inj)
    pager = t.pager = StubPager(t.pool, 1)
    admit(t, 0, A)
    admit(t, 1, B)
    t.block_table()
    new, fresh = t.rebuilt(), wide(faults=inj)
    assert new.pager is pager and pager.pool is new.pool
    assert pager.alloc == new.alloc
    assert [getattr(new, k) for k in PageTable.TOTALS] == [1, 1, 2, 0]
    for k, want in vars(fresh).items():
        got = getattr(new, k)
        if k in PageTable.TOTALS or k == "pager":
            continue
        if k in ("pool", "radix"):  # rebuilt too, and empty
            assert got is not getattr(t, k)
            assert new.pool.free == fresh.pool.free
            assert new.pool.ref == fresh.pool.ref and not new.radix.n_nodes
        elif isinstance(want, np.ndarray):
            assert (got == want).all(), k
        else:
            assert got == want, k
    assert vars(new).keys() == vars(fresh).keys()


def test_adapter_namespaces_never_share_pages():
    t = table(n_pages=16)
    a = seq(9)
    admit(t, 0, a, ns="tenant-a")
    assert t.cached_len(a, ns="tenant-a") == 8
    assert t.cached_len(a) == 0 and t.cached_len(a, ns="tenant-b") == 0
    plan = t.reserve(1, a)  # the base namespace: nothing to share
    assert plan.covered == 0 and not set(plan.row) & set(t.slot_pages[0])


def test_grid_pages_counts_live_rows_only():
    t = table()
    admit(t, 0, seq(5))
    admit(t, 2, seq(2))
    active = np.array([True, False, True])
    assert t.grid_pages(active) == (2 + 1, 3 * 6)
    assert t.utilization() == 8 / 8


# ---------------------------------------------------------------------------
# two groups of pages in one slot (bigdl_tpu/kvwindow.py): `window=`
# ---------------------------------------------------------------------------

WINDOW = 8  # two pages of PAGE = 4


def groups(n_pages=33, n_slots=2, rows=16, max_len=64, window=WINDOW):
    return PageTable(n_slots, n_pages, PAGE, rows, max_len,
                     share_prefixes=False, window=window)


def admit2(t, slot, prompt):
    plan = t.reserve(slot, prompt)
    if plan is not None:
        t.install(slot, plan.row, len(prompt), plan.wrow)
    return plan


def test_window_pool_is_derived_from_the_slots_and_the_window():
    t = groups(n_slots=3)
    assert t.wpool.n_pages == 3 * (WINDOW // PAGE + 2) + 1
    assert t.pages_in_use() == (0, 0) and t.utilization() == 0.0


@pytest.mark.parametrize("n_prompt,first,held", [
    (3, 0, 4),  # 3 tokens pad to 16 positions: pages 0 .. 3, none dead
    (16, 2, 2),  # a query at 16 reads from 9 on: pages 2, 3 of 0 .. 3
    (21, 3, 5),  # pads to 32: pages 3 .. 7
])
def test_reserve_books_the_windows_pages_only(n_prompt, first, held):
    t = groups()
    plan = admit2(t, 0, seq(n_prompt))
    n = len(t.slot_pages[0])
    assert t.win_first[0] == first and len(t.win_pages[0]) == held == n - first
    assert list(plan.wrow[:first]) == [0] * first
    assert list(plan.wrow[first:n]) == t.win_pages[0]
    assert (t.window_table[0] == plan.wrow).all()
    assert all(t.wpool.ref[pg] == 1 for pg in t.win_pages[0])  # one holder
    t.release(0)
    assert t.pages_in_use() == (0, 0) and t.page_leaks() == 0
    assert not t.window_table.any() and not t.block_table()[0].any()


def test_a_window_page_is_freed_in_the_step_that_passes_it():
    """Decoding one token a step: the slot never holds more than
    W // P + 2 window pages, a page goes back the step `pos - window` passes
    its last position, and its table entry goes back to the scratch page."""
    t = groups()
    admit2(t, 0, seq(5))  # pads to 16: pages 0 .. 3 in both groups
    most, freed_at = 0, []
    for _ in range(40):
        while t.short(0, 2):  # the engine books one step ahead
            t.extend(0, t.alloc())
        before = t.window_pages_freed
        t.advance(0)
        if t.window_pages_freed > before:
            freed_at.append(t.pos[0])
            assert t.block_table() is not None  # an upload is due
        most = max(most, len(t.win_pages[0]))
        first = max(t.pos[0] - WINDOW + 1, 0) // PAGE
        assert t.win_first[0] == first
        assert not t.window_table[0, :first].any()
        assert t.window_table[0, first:len(t.slot_pages[0])].all()
        assert len(t.slot_pages[0]) == first + len(t.win_pages[0])
        assert t.page_leaks() == 0
    assert most <= WINDOW // PAGE + 2
    # page j's last position is 4j + 3: dead for a query at 4j + 3 + WINDOW
    assert freed_at == [11, 15, 19, 23, 27, 31, 35, 39, 43]
    assert t.window_pages_freed == 9
    held = t.pages_in_use()
    assert held[0] == len(t.slot_pages[0]) and held[1] == len(t.win_pages[0])
    t.release(0)
    assert t.page_leaks() == 0 and t.pages_in_use() == (0, 0)


@pytest.mark.parametrize("n_prompt", [32, 45, 64, 96])
def test_a_window_of_eight_pages_frees_a_page_every_page_tokens(n_prompt):
    """A window of eight pages under prompts that are at least the window
    (Laguna's cell: 512 over pages of 64, every prompt 512 or more): the
    slot never holds more than W // P + 2 window pages, whatever the
    prompt, and from its first decoded tokens on one goes back every PAGE
    tokens, so the pool of `n_slots * (W // P + 2) + 1` never runs dry."""
    W = 8 * PAGE
    t = groups(n_pages=65, n_slots=1, rows=64, max_len=256, window=W)
    assert t.wpool.n_pages == W // PAGE + 2 + 1
    admit2(t, 0, seq(n_prompt))
    assert t.win_first[0] == (n_prompt - W + 1) // PAGE
    most, freed_at = len(t.win_pages[0]), []
    for _ in range(60):
        while t.short(0, 2):  # the engine books one step ahead
            t.extend(0, t.alloc())
        before = t.window_pages_freed
        t.advance(0)
        freed_at += [t.pos[0]] * (t.window_pages_freed - before)
        most = max(most, len(t.win_pages[0]))
        assert t.page_leaks() == 0
    assert most <= W // PAGE + 2
    # the first within PAGE tokens of the prompt's end, then one a page
    assert n_prompt < freed_at[0] <= n_prompt + PAGE
    assert set(np.diff(freed_at)) == {PAGE} and len(freed_at) == 60 // PAGE
    t.release(0)
    assert t.page_leaks() == 0 and t.pages_in_use() == (0, 0)


def test_freed_pages_return_to_the_pool_and_serve_another_slot():
    t = groups(n_slots=2)
    cap = t.wpool.n_pages - 1
    admit2(t, 0, seq(5))
    t.advance(0, 20)  # position 25 reads from 18 on: page 4 is the first
    assert t.win_pages[0] == [] and t.win_first[0] == 4  # all four were dead
    assert t.wpool.n_free == cap
    admit2(t, 1, seq(30))
    assert t.page_leaks() == 0


@pytest.mark.parametrize("room", [True, False])
def test_restore_brings_both_groups_or_nothing(room):
    t = groups(n_pages=9 if room else 7)
    admit2(t, 0, seq(14))  # pads to 16: four pages; window from page 1
    t.advance(0, 2)  # 16: window from page 2
    keep, wkeep = t.kv_pages(0), t.window_kv_pages(0)
    assert len(keep) == 4 and len(wkeep) == 2 and t.win_first[0] == 2
    if room:
        t.release(0)
    free = (t.pool.n_free, t.wpool.n_free)
    fresh = t.restore(1, len(keep), 16)
    if room:
        assert len(fresh) == 4 and t.win_first[1] == 2
        assert len(t.win_pages[1]) == len(wkeep)
        assert list(t.window_table[1, :4]) == [0, 0] + t.win_pages[1]
        assert t.pos[1] == 16 and t.written[1] == 16
    else:  # two of six global pages are free: nothing is held of either
        assert fresh is None and t.win_pages[1] == []
        assert (t.pool.n_free, t.wpool.n_free) == free
    assert t.page_leaks() == 0


def test_a_dry_window_pool_refuses_and_rolls_back():
    # a window that is no whole number of pages, and a prompt whose padding
    # spans one page more than `window // page + 2`: refused, nothing held
    t = groups(n_slots=1, n_pages=65, rows=32, max_len=128, window=7)
    assert t.wpool.n_pages - 1 == 3
    with pytest.raises(NeverFits, match="window pages"):
        t.reserve(0, seq(9))  # pads to 16: pages 0 .. 3, all four live
    assert t.pool.n_free == 64 and t.wpool.n_free == 3
    admit2(t, 0, seq(16))  # pages 0 .. 3; position 16 reads from page 2
    t.extend(0, t.alloc())
    assert len(t.win_pages[0]) == 3 and t.wpool.n_free == 0
    assert t.alloc() is None  # neither group grows without the other
    assert t.page_leaks() == 0


def test_group_pages_counts_what_each_kernel_loads():
    t = groups()
    admit2(t, 0, seq(21))  # position 21: pages 0 .. 5 live; window 3 .. 5
    admit2(t, 1, seq(2))
    got = t.group_pages(np.array([True, False]))
    assert got == {"live_pages_global": 6, "grid_pages_global": 2 * 16,
                   "live_pages_window": 3, "grid_pages_window": 2 * 16,
                   "window_pages_held": 5, "window_pages_unfreed": 8}
    both = t.group_pages(np.array([True, True]))
    assert both["live_pages_global"] == 6 + 1
    assert both["live_pages_window"] == 3 + 1


def test_rebuilt_keeps_the_window_and_the_freed_total():
    t = groups()
    admit2(t, 0, seq(5))
    t.advance(0, 20)
    new = t.rebuilt()
    assert new.window == WINDOW and new.window_pages_freed == 4
    assert new.wpool.n_free == new.wpool.n_pages - 1
    assert vars(new).keys() == vars(t).keys()
