"""Chunked prefill + radix-cache engine tests (ISSUE 14).

Chunked prefill splits a prompt's paged prefill into
`prefill_chunk_tokens`-token chunks, advancing at most one chunk of at
most one request per step() so a long prompt cannot stall the running
batch by more than one chunk. These tests pin the contract:

* bit-exactness — token ids AND logprobs match monolithic prefill
  across chunk sizes {one page, odd mid-page, >= whole prompt};
* lifecycle between chunks — cancel / deadline / preempt landing while
  a request is mid-prefill free every page (no leak), and a journaled
  engine killed mid-prefill replays the request cleanly;
* radix composition — evict-then-readmit leaves zero dead nodes
  (satellite 2 at engine level).
"""

import jax
import numpy as np
import pytest

from bigdl_tpu.api import TpuModel, optimize_model
from bigdl_tpu.models import llama
from bigdl_tpu.models.config import PRESETS
from engines import shared_engine

CFG = PRESETS["tiny-llama"]


@pytest.fixture(scope="module")
def model():
    return TpuModel(CFG, optimize_model(
        llama.init_params(CFG, jax.random.PRNGKey(0)), CFG
    ), "sym_int4")


def _run(engine, prompts, maxnt=8):
    reqs = [engine.submit(p, max_new_tokens=maxnt) for p in prompts]
    engine.run_until_idle()
    assert all(r.done for r in reqs), [r.error for r in reqs]
    return reqs


# ---------------------------------------------------------------------------
# bit-exactness vs monolithic prefill
# ---------------------------------------------------------------------------


@pytest.mark.core
@pytest.mark.parametrize("chunk", [16, 13, 512])
def test_chunked_prefill_token_and_logprob_parity(model, chunk):
    """chunk=16: exactly one page; 13: odd, lands mid-page every
    chunk; 512: >= any prompt (degenerates to monolithic). Ids must be
    identical and per-token logprobs must agree to float tolerance."""
    prompts = [list(range(1, 40)), list(range(60, 85)), [7, 8, 9]]
    ref = _run(shared_engine(model, n_slots=2, max_len=128, paged=True,
                             page_size=16), prompts, maxnt=10)
    eng = shared_engine(model, n_slots=2, max_len=128, paged=True,
                        page_size=16, prefill_chunk_tokens=chunk)
    out = _run(eng, prompts, maxnt=10)
    for r, o in zip(ref, out):
        assert o.out_tokens == r.out_tokens
        np.testing.assert_allclose(
            np.asarray(o.out_logprobs), np.asarray(r.out_logprobs),
            rtol=1e-4, atol=1e-4,
        )
    if chunk < 39:  # genuinely chunked for the long prompts
        assert eng.prefill_chunks > len(prompts)
    assert eng.page_leaks() == 0


@pytest.mark.core
def test_chunked_prefill_composes_with_radix_hits(model):
    """A cached prefix shrinks the chunked remainder too: the second
    request hits the radix cache AND chunk-prefills only its tail,
    output byte-identical to dense."""
    eng = shared_engine(model, n_slots=2, max_len=128, paged=True,
                        page_size=8, prefill_chunk_tokens=8)
    p1 = list(range(10, 34))  # 3 full pages
    p2 = list(range(10, 26)) + [90, 91, 92, 93, 94, 95, 96, 97]
    r1 = _run(eng, [p1], maxnt=6)[0]
    hits0 = eng.pages.prefix_hits
    r2 = _run(eng, [p2], maxnt=6)[0]
    assert eng.pages.prefix_hits == hits0 + 1
    dense = shared_engine(model, n_slots=2, max_len=128)
    d1, d2 = _run(dense, [p1, p2], maxnt=6)
    assert r1.out_tokens == d1.out_tokens
    assert r2.out_tokens == d2.out_tokens


def test_chunked_prefill_interleaves_decode(model):
    """A running request keeps emitting while another's prompt
    chunk-prefills: the running slot's token count advances during the
    prefilling stretch (the no-stall property, host-observable)."""
    eng = shared_engine(model, n_slots=2, max_len=256, paged=True,
                        page_size=16, prefill_chunk_tokens=16)
    a = eng.submit([1, 2, 3], max_new_tokens=40)
    eng.step()  # admit + first token
    got0 = len(a.out_tokens)
    b = eng.submit(list(range(1, 129)), max_new_tokens=4)
    # b needs 8 chunks; every step in between must advance a
    grew = 0
    for _ in range(6):
        eng.step()
        if b.done or eng._prefilling is None:
            break
        new = len(a.out_tokens)
        if new > got0:
            grew += 1
        got0 = new
    assert grew >= 4, "decode stalled while a prompt was chunk-prefilling"
    eng.run_until_idle()
    assert a.done and b.done and not b.error
    assert eng.page_leaks() == 0


# ---------------------------------------------------------------------------
# lifecycle landing BETWEEN chunks
# ---------------------------------------------------------------------------


def _start_chunked(eng, prompt, **kw):
    """Submit + step until the request is mid-chunked-prefill."""
    req = eng.submit(prompt, **kw)
    for _ in range(3):
        eng.step()
        if eng._prefilling is not None and eng._prefilling.req is req:
            break
    assert eng._prefilling is not None and eng._prefilling.req is req
    assert not req.done and req.out_tokens == []
    return req


@pytest.mark.core
def test_cancel_between_chunks_frees_pages(model):
    eng = shared_engine(model, n_slots=2, max_len=256, paged=True,
                        page_size=16, prefill_chunk_tokens=16)
    free0 = eng.pages.pool.n_free
    req = _start_chunked(eng, list(range(1, 129)), max_new_tokens=4)
    eng.cancel(req)
    eng.run_until_idle()
    assert req.done and req.finish_reason == "stop"
    assert eng._prefilling is None
    assert eng.pages.pool.n_free + eng.pages.radix.n_nodes == free0
    assert eng.page_leaks() == 0
    # the engine still serves
    nxt = _run(eng, [[5, 6, 7]], maxnt=4)[0]
    assert not nxt.error


def test_deadline_between_chunks_times_out_cleanly(model):
    fake = [0.0]
    eng = shared_engine(model, n_slots=2, max_len=256, paged=True,
                        page_size=16, prefill_chunk_tokens=16,
                        clock=lambda: fake[0])
    free0 = eng.pages.pool.n_free
    req = _start_chunked(eng, list(range(1, 129)), max_new_tokens=4,
                         deadline_s=5.0)
    fake[0] = 10.0  # expire while mid-prefill
    eng.run_until_idle()
    assert req.done and req.finish_reason == "timeout"
    assert eng._prefilling is None
    assert eng.pages.pool.n_free + eng.pages.radix.n_nodes == free0
    assert eng.page_leaks() == 0
    assert eng.request_timeouts == 1


def test_preempt_request_between_chunks_is_noop(model):
    """engine.preempt() on a still-prefilling request has no decode
    state to park: the marker drops, prefill completes, output is
    unaffected."""
    eng = shared_engine(model, n_slots=2, max_len=256, paged=True,
                        page_size=16, prefill_chunk_tokens=16)
    prompt = list(range(1, 129))
    req = _start_chunked(eng, prompt, max_new_tokens=4)
    eng.preempt(req)
    eng.run_until_idle()
    assert req.done and not req.error and req.preemptions == 0
    ref = _run(shared_engine(model, n_slots=2, max_len=256, paged=True,
                             page_size=16), [prompt], maxnt=4)[0]
    assert req.out_tokens == ref.out_tokens
    assert eng.page_leaks() == 0


def test_journal_replay_after_death_mid_chunk(model, tmp_path):
    """Kill the engine between chunks: the journaled request has no
    tombstone, so a successor engine replays and completes it."""
    jpath = str(tmp_path / "journal.jsonl")
    eng = shared_engine(model, n_slots=2, max_len=256, paged=True,
                        page_size=16, prefill_chunk_tokens=16,
                        journal=jpath)
    prompt = list(range(1, 129))
    _start_chunked(eng, prompt, max_new_tokens=4)
    del eng  # process death: no tombstone, no cleanup
    eng2 = shared_engine(model, n_slots=2, max_len=256, paged=True,
                         page_size=16, prefill_chunk_tokens=16,
                         journal=jpath)
    assert len(eng2.recovered_requests) == 1
    rec = eng2.recovered_requests[0]
    assert rec.prompt == prompt
    eng2.run_until_idle()
    assert rec.done and not rec.error and len(rec.out_tokens) == 4
    assert eng2.page_leaks() == 0


def test_fail_all_mid_chunk_releases_everything(model):
    eng = shared_engine(model, n_slots=2, max_len=256, paged=True,
                        page_size=16, prefill_chunk_tokens=16)
    free0 = eng.pages.pool.n_free
    req = _start_chunked(eng, list(range(1, 129)), max_new_tokens=4)
    eng.fail_all("injected crash")
    assert req.done and req.finish_reason == "error"
    assert eng._prefilling is None
    assert eng.pages.pool.n_free + eng.pages.radix.n_nodes == free0
    assert eng.page_leaks() == 0


@pytest.mark.core
def test_chunk_plan_yields_pages_to_decoding_slot(model):
    """A decoding stream crossing a page boundary while an inactive
    chunk plan holds most of the pool must NOT be length-truncated or
    self-preempt-failed: the plan yields (slot released, request back
    at the queue front) and both requests complete in full."""
    eng = shared_engine(model, n_slots=2, max_len=128, paged=True,
                        page_size=8, n_pages=15,  # 14 allocatable
                        prefill_chunk_tokens=8)
    a = eng.submit([1, 2, 3, 4, 5], max_new_tokens=40)
    eng.step()  # A admitted (2 pages), decoding
    # B's 12-page / 12-chunk plan takes every remaining page; A hits
    # its next page boundary (pos 16) several steps before the plan
    # can finish — the pre-fix engine truncated A with "length"
    b = eng.submit(list(range(10, 106)), max_new_tokens=8)
    eng.run_until_idle()
    assert a.done and len(a.out_tokens) == 40, (
        a.finish_reason, a.error, len(a.out_tokens))
    assert b.done and not b.error and len(b.out_tokens) == 8
    assert eng.page_leaks() == 0
    # the yield genuinely fired: B's first attempt burned chunks
    # before restarting (1 for A + 12 for B's full second pass < total)
    assert eng.prefill_chunks >= 14, eng.prefill_chunks
    # output parity with an unpressured engine (same prompts)
    eng2 = shared_engine(model, n_slots=2, max_len=128, paged=True,
                         page_size=8)
    a2 = eng2.submit([1, 2, 3, 4, 5], max_new_tokens=40)
    eng2.step()
    b2 = eng2.submit(list(range(10, 106)), max_new_tokens=8)
    eng2.run_until_idle()
    assert a.out_tokens == a2.out_tokens
    assert b.out_tokens == b2.out_tokens


def test_speculative_rejects_chunked_prefill(model):
    """The draft admission prefill is monolithic: the combo would
    silently break the one-chunk stall bound, so the ctor refuses."""
    with pytest.raises(NotImplementedError, match="draft admission"):
        shared_engine(model, n_slots=2, max_len=128, paged=True,
                      page_size=16, prefill_chunk_tokens=16,
                      speculative=True, draft_params=model.params)


# ---------------------------------------------------------------------------
# radix eviction at engine level (satellite 2)
# ---------------------------------------------------------------------------


@pytest.mark.core
def test_evict_then_readmit_leaves_zero_dead_nodes(model):
    """Pool pressure evicts cached leaves; readmitting the same prompt
    re-registers it. After every round the tree must hold ONLY
    reachable nodes (the flat cache accumulated stale child keys whose
    pages were evicted and scanned them forever)."""
    eng = shared_engine(model, n_slots=1, max_len=64, paged=True,
                        page_size=8, n_pages=7)  # 6 allocatable
    shared = list(range(10, 26))  # 2 full pages when tailed
    for round_i in range(4):
        # disjoint filler churns the pool and forces eviction of the
        # shared chain's leaves...
        _run(eng, [[90 + round_i * 7 + j for j in range(16)] + [5]],
             maxnt=4)
        # ...then the shared prefix is readmitted
        r = _run(eng, [shared + [30 + round_i]], maxnt=4)[0]
        assert not r.error
        eng.pages.radix.check()  # no dead/unreachable nodes, refs consistent
        assert eng.page_leaks() == 0
    assert eng.pages.prefix_evictions > 0
    # drain invariant: every page free or cache-held
    assert eng.pages.pool.n_free + eng.pages.radix.n_nodes == 6


def test_eviction_composes_with_preemption(model):
    """When eviction alone cannot free pages (everything cached is also
    held by slots), allocation escalates to host-RAM preemption and the
    victim resumes bit-exactly — the radix cache must not break PR 6's
    swap path."""
    eng = shared_engine(model, n_slots=2, max_len=64, paged=True,
                        page_size=8, n_pages=7)
    a = eng.submit(list(range(1, 17)), max_new_tokens=24)
    b = eng.submit(list(range(30, 46)), max_new_tokens=24)
    eng.run_until_idle()
    assert a.done and b.done and not a.error and not b.error
    assert len(a.out_tokens) == 24 and len(b.out_tokens) == 24
    assert eng.preemptions > 0  # the pool genuinely could not hold both
    assert eng.page_leaks() == 0
    # parity with an unpressured engine
    eng2 = shared_engine(model, n_slots=2, max_len=64, paged=True,
                         page_size=8)
    a2 = eng2.submit(list(range(1, 17)), max_new_tokens=24)
    b2 = eng2.submit(list(range(30, 46)), max_new_tokens=24)
    eng2.run_until_idle()
    assert a.out_tokens == a2.out_tokens
    assert b.out_tokens == b2.out_tokens
