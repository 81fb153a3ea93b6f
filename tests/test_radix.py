"""Radix-tree prefix cache unit tests (serving/radix.py; ISSUE 14).

Structure-level coverage — no engine, no model: match/insert/evict
semantics, the O(1) LRU discipline (the flat cache paid an O(n)
list.remove per hit — satellite 1's timing guard), leaf-first eviction
that can never strand interior pages, and the no-dead-nodes invariant
(satellite 2: the flat cache's `_prefix_children` kept keys of evicted
pages forever)."""

import time

import pytest

from bigdl_tpu.kvpaged import PagePool
from bigdl_tpu.serving.radix import RadixPrefixCache

PAGE = 4


def _cache(n_pages=64):
    pool = PagePool(n_pages)
    return RadixPrefixCache(PAGE, pool), pool


def _admit(cache, pool, prompt, ns=None):
    """A minimal engine-admission stand-in: match, allocate fresh pages
    for the uncovered remainder, register fully-covered pages, then
    release the slot holds (the request 'finishes' immediately).
    Returns the number of full-page hits."""
    path = cache.match(prompt, ns=ns)
    shared = [nd.page for nd in path]
    for pg in shared:
        pool.incref(pg)
    n_need = -(-len(prompt) // PAGE) - len(path)
    fresh = []
    for _ in range(n_need):
        pg = pool.alloc()
        while pg is None:
            assert cache.evict_one()
            pg = pool.alloc()
        fresh.append(pg)
    table = shared + fresh
    node = path[-1] if path else cache.root_for(ns)
    for i in range(len(path), len(prompt) // PAGE):
        key = tuple(prompt[i * PAGE:(i + 1) * PAGE])
        nxt = node.children.get(key)
        if nxt is None:
            nxt = cache.insert(node, key, table[i])
        node = nxt
    for pg in table:
        pool.decref(pg)
    return len(path)


# ---------------------------------------------------------------------------
# match / insert semantics
# ---------------------------------------------------------------------------


@pytest.mark.core
def test_match_descends_full_pages_and_leaves_tail():
    cache, pool = _cache()
    _admit(cache, pool, list(range(1, 13)))  # 3 full pages
    # identical prompt: the last page must NOT match (>= 1 tail token
    # always prefills for its logits)
    assert len(cache.match(list(range(1, 13)))) == 2
    # one extra token: all 3 cached pages match
    assert len(cache.match(list(range(1, 14)))) == 3
    # divergence inside page 2 stops the descent after page 1
    p = list(range(1, 13))
    p[5] = 99
    assert len(cache.match(p)) == 1


@pytest.mark.core
def test_match_partial_picks_longest_agreement():
    cache, pool = _cache()
    _admit(cache, pool, [1, 2, 3, 4, 5, 6, 7, 8, 9])
    _admit(cache, pool, [1, 2, 3, 4, 5, 6, 70, 80, 90])
    path = cache.match([1, 2, 3, 4, 5, 6, 7, 77, 777])
    assert len(path) == 1
    # the tail past the matched run, against both cached children
    m, child = cache.match_partial(path[-1], [5, 6, 7, 77, 777])
    assert m == 3 and child is not None  # agrees [5, 6, 7], not [5, 6]
    assert child.tokens == (5, 6, 7, 8)


def test_insert_existing_edge_keeps_canonical_page():
    cache, pool = _cache()
    _admit(cache, pool, [1, 2, 3, 4, 5])
    node0 = next(iter(cache.nodes()))
    _admit(cache, pool, [1, 2, 3, 4, 6])  # same first page content
    assert cache.n_nodes == 1
    assert next(iter(cache.nodes())) is node0


# ---------------------------------------------------------------------------
# eviction: leaf-first, unlink-on-evict, refcount discipline
# ---------------------------------------------------------------------------


@pytest.mark.core
def test_evict_leaf_first_never_strands_interior():
    cache, pool = _cache()
    _admit(cache, pool, list(range(1, 14)))  # chain of 3 nodes
    evicted = []
    while cache.evict_one():
        evicted.append(cache.n_nodes)
        cache.check()  # invariant holds after EVERY eviction
    assert evicted == [2, 1, 0]  # tail-first, one leaf at a time


def test_evicted_node_unlinked_from_parent():
    """Satellite 2 (structure level): eviction must drop the child key
    — the flat cache's divergence scans walked dead entries forever."""
    cache, pool = _cache()
    _admit(cache, pool, [1, 2, 3, 4, 5, 6, 7, 8, 9])
    parent = cache.match([1, 2, 3, 4, 99])[0]
    assert len(parent.children) == 1
    assert cache.evict_one()  # the leaf (page 2 chunk)
    assert parent.children == {}
    m, child = cache.match_partial(parent, [5, 6, 7, 8, 9])
    assert m == 0 and child is None  # no dead entry to walk


def test_slot_held_pages_are_not_evictable():
    cache, pool = _cache(n_pages=8)
    _admit(cache, pool, [1, 2, 3, 4, 5])
    node = next(iter(cache.nodes()))
    pool.incref(node.page)  # a slot's block-table hold
    assert not cache.evict_one()
    pool.decref(node.page)
    assert cache.evict_one()
    assert pool.ref[node.page] == 0 and node.page in pool.free


def test_pool_exhaustion_evicts_until_dry():
    cache, pool = _cache(n_pages=5)  # 4 allocatable
    _admit(cache, pool, list(range(1, 17)))  # 16 tokens -> 4 pages, 4 nodes
    assert pool.n_free == 0 and cache.n_nodes == 4
    # a new disjoint prompt must evict cached leaves to admit
    _admit(cache, pool, [91, 92, 93, 94, 95])
    cache.check()
    assert cache.n_nodes <= 4
    assert sum(pool.ref[1:]) == cache.n_nodes  # only cache holds remain


def test_clear_releases_every_page():
    cache, pool = _cache()
    for s in range(5):
        _admit(cache, pool, [s * 10 + i for i in range(9)])
    assert cache.n_nodes == 10
    cache.clear()
    assert cache.n_nodes == 0
    assert pool.n_free == pool.n_pages - 1
    assert all(r == 0 for r in pool.ref[1:])


def test_pagepool_double_release_raises():
    pool = PagePool(4)
    pg = pool.alloc()
    pool.decref(pg)
    with pytest.raises(AssertionError):
        pool.decref(pg)


# ---------------------------------------------------------------------------
# LRU discipline (satellite 1)
# ---------------------------------------------------------------------------


@pytest.mark.core
def test_lru_hit_refreshes_eviction_order():
    cache, pool = _cache()
    _admit(cache, pool, [1, 2, 3, 4, 5])    # node A (older)
    _admit(cache, pool, [9, 8, 7, 6, 5])    # node B (newer)
    a = cache.match([1, 2, 3, 4, 5])[0]     # hit refreshes A past B
    assert cache.evict_one()
    assert a in set(cache.nodes())          # B was evicted, not A


@pytest.mark.core
def test_lru_hits_scale_constant_time():
    """Satellite 1's regression guard: with a large cache, per-hit LRU
    maintenance must not scan the whole structure. The flat cache's
    `list.remove` made N hits over an N-node cache O(N^2) — at this
    size (~4e8 comparisons) that blows far past the bound; the
    OrderedDict move_to_end discipline stays comfortably inside it."""
    cache, pool = _cache(n_pages=20002)
    prompts = [[s, s, s, s, 1] for s in range(20000)]
    for p in prompts:
        _admit(cache, pool, p)
    assert cache.n_nodes == 20000
    t0 = time.perf_counter()
    for p in prompts:
        assert len(cache.match(p)) == 1
    dt = time.perf_counter() - t0
    assert dt < 2.0, f"20k hits over a 20k-node cache took {dt:.2f}s"


# ---------------------------------------------------------------------------
# cache-aware admission ordering (ISSUE 15 satellite: match_len probe +
# engine._pop_deepest_match)
# ---------------------------------------------------------------------------


@pytest.mark.core
def test_match_len_counts_without_lru_touch():
    cache, pool = _cache()
    pre = list(range(1, 3 * PAGE + 1))
    _admit(cache, pool, pre + [99])
    other = [7] * (2 * PAGE)
    _admit(cache, pool, other + [98])
    # LRU order now: pre-chain nodes older than other-chain nodes.
    order_before = [nd.page for nd in cache.nodes()]
    # probe matches the same bound as match(): full pages, one tail
    # token always left to prefill
    assert cache.match_len(pre + [99]) == 3 * PAGE
    assert cache.match_len(pre[:PAGE] + [50, 51]) == PAGE
    assert cache.match_len([42] * 10) == 0
    # a prompt ENDING flush with a cached run leaves the last page to
    # prefill (its logits seed generation) — same rule as match()
    assert cache.match_len(pre) == 2 * PAGE
    # read-only: scoring promoted nothing
    assert [nd.page for nd in cache.nodes()] == order_before
    # ...whereas a real match() does promote
    cache.match(pre + [99])
    assert [nd.page for nd in cache.nodes()] != order_before


@pytest.mark.core
def test_pop_deepest_match_orders_and_keeps_fifo_ties():
    """engine._pop_deepest_match: deepest cached prefix pops first;
    ties (including all-miss) keep strict FIFO."""
    import jax

    from bigdl_tpu import optimize_model
    from bigdl_tpu.api import TpuModel
    from bigdl_tpu.models import llama
    from bigdl_tpu.models.config import PRESETS
    from bigdl_tpu.serving.engine import InferenceEngine

    cfg = PRESETS["tiny-llama"]
    params = optimize_model(
        llama.init_params(cfg, jax.random.PRNGKey(7)), cfg, "sym_int4"
    )
    eng = InferenceEngine(TpuModel(cfg, params, "sym_int4"), n_slots=2,
                          max_len=128, paged=True, page_size=16)
    pre = list(range(1, 33))  # 2 full pages at page_size 16
    seed = eng.submit(pre + [40, 41], max_new_tokens=2)
    eng.run_until_idle(max_steps=100)
    assert seed.done and eng.pages.radix.n_nodes == 2  # cache primed
    # queue: miss A, 1-page match B, 2-page match C, miss D
    a = eng.submit([9] * 8, max_new_tokens=2)
    b = eng.submit(pre[:16] + [7, 7], max_new_tokens=2)
    c = eng.submit(pre + [8, 8], max_new_tokens=2)
    d = eng.submit([3] * 8, max_new_tokens=2)
    assert eng._pop_deepest_match() is c   # deepest first
    assert eng._pop_deepest_match() is b   # then the 1-page match
    assert eng._pop_deepest_match() is a   # 0-0 tie: FIFO
    assert eng._pop_deepest_match() is d
    assert eng._pop_deepest_match() is None
    for r in (a, b, c, d):  # drain cleanly (they were popped, not run)
        eng._finish_detached(r, "stop")
    assert eng.idle()


# ---------------------------------------------------------------------------
# adapter namespaces: cross-tenant pages unreachable by construction
# ---------------------------------------------------------------------------


@pytest.mark.core
def test_namespaces_isolate_adapter_pages():
    """KV pages prefilled under a LoRA adapter carry its shifted K/V —
    the same token content cached under another tenant (or the base)
    must never match (docs/serving.md §7)."""
    cache, pool = _cache()
    p = list(range(1, 14))  # 3 full pages + tail
    _admit(cache, pool, p)                 # base
    _admit(cache, pool, p, ns="tenant-a")  # same tokens, tenant A
    assert cache.n_nodes == 6  # two disjoint 3-node chains
    # each namespace matches only its own chain
    assert len(cache.match(p)) == 3
    assert len(cache.match(p, ns="tenant-a")) == 3
    assert cache.match(p, ns="tenant-b") == []
    assert {nd.page for nd in cache.match(p)}.isdisjoint(
        {nd.page for nd in cache.match(p, ns="tenant-a")}
    )
    # match_len scores per-namespace and, read-only, materializes no
    # root for a namespace nothing has cached under
    assert cache.match_len(p) == 3 * PAGE
    assert cache.match_len(p, ns="tenant-a") == 3 * PAGE
    assert cache.match_len(p, ns="never-seen") == 0
    assert "never-seen" not in cache._ns_roots
    cache.check()  # invariant walk covers namespace roots


@pytest.mark.core
def test_namespace_nodes_evict_and_clear():
    """Namespace chains ride the shared LRU: leaf-first eviction
    unlinks them from their tenant root, and clear() drops the roots
    themselves (engine _reset_state rebuilds the pool alongside)."""
    cache, pool = _cache()
    _admit(cache, pool, list(range(1, 10)), ns="t")  # 2-node chain
    assert cache.n_nodes == 2
    assert cache.evict_one() and cache.evict_one()
    cache.check()
    assert cache.n_nodes == 0
    assert cache.root_for("t").children == {}
    assert pool.n_free == pool.n_pages - 1  # page 0 = scratch
    _admit(cache, pool, list(range(1, 10)), ns="t")
    cache.clear()
    assert cache.n_nodes == 0 and cache._ns_roots == {}
    assert pool.n_free == pool.n_pages - 1
