"""Overload-safe serving: host-RAM KV preemption, bounded admission,
deadlines, and shed semantics (docs/serving.md).

The central invariant: overload NEVER silently truncates output.
Before this layer, `_ensure_decode_pages` hard-finished a request with
"length" the moment the page pool ran dry — wrong output with no
signal, under exactly the load a production engine must survive. Now
pool pressure preempts a victim (KV swapped to host RAM, request
requeued, decode resumed bit-exactly), and queue overload surfaces as
fast explicit "shed" rejections instead of unbounded latency.
"""

import threading
import time

import jax
import numpy as np
import pytest

from bigdl_tpu.api import TpuModel, optimize_model
from bigdl_tpu.models import llama
from bigdl_tpu.models.config import PRESETS
from bigdl_tpu.serving.faults import FaultInjector
from engines import shared_engine

CFG = PRESETS["tiny-llama"]


@pytest.fixture(scope="module")
def model():
    params = optimize_model(
        llama.init_params(CFG, jax.random.PRNGKey(7)), CFG, "sym_int4"
    )
    return TpuModel(CFG, params, "sym_int4")


def _pages_balanced(eng) -> bool:
    """Every page is either free, radix-cached, or the scratch page,
    and every refcount matches its accounted holders."""
    ok = (eng.pages.pool.n_free + eng.pages.radix.n_nodes
          == eng.n_pages - 1)
    return ok and eng.page_leaks() == 0


# ---------------------------------------------------------------------------
# preemption parity: swap-out -> requeue -> swap-in is bit-exact
# ---------------------------------------------------------------------------

@pytest.mark.core
@pytest.mark.chaos
def test_preemption_parity_paged_under_injected_exhaustion(model):
    """A paged request preempted by an injected page-pool exhaustion
    produces token-for-token identical output to the uninterrupted run,
    and the pool balances to zero afterwards."""
    prompt = [3, 1, 4, 1, 5]
    want = model.generate([prompt], max_new_tokens=40)[0].tolist()
    inj = FaultInjector(seed=0)
    eng = shared_engine(model, n_slots=1, max_len=64, paged=True,
                        page_size=8, faults=inj)
    r = eng.submit(prompt, max_new_tokens=40)
    eng.step()  # admit; the next page allocation is the decode extension
    # twice: dry for the page a step AHEAD of the one in flight (the engine
    # only reads first), and dry for the page the next step needs
    inj.arm("alloc_page", times=2)
    eng.run_until_idle()
    assert r.done and not r.error
    assert eng.preemptions == 1 and eng.preemption_resumes == 1
    assert r.preemptions == 1
    assert r.out_tokens == want, (r.out_tokens, want)
    assert _pages_balanced(eng)


@pytest.mark.core
@pytest.mark.chaos
def test_preemption_parity_dense_via_preempt_api(model):
    """The dense-fallback engine preempts too (operator/server-initiated
    `preempt()`): full KV row to host RAM, resumed bit-exactly."""
    prompt = [3, 1, 4, 1, 5]
    want = model.generate([prompt], max_new_tokens=20)[0].tolist()
    # max_len 128 > the 64-slot swap bucket: the blob really is a SLICE
    # of the row (the idle tail stays behind), not a full-row copy
    eng = shared_engine(model, n_slots=1, max_len=128)
    r = eng.submit(prompt, max_new_tokens=20)
    for _ in range(4):
        eng.step()
    assert not r.done
    eng.preempt(r)
    eng.run_until_idle()
    assert eng.preemptions == 1 and eng.preemption_resumes == 1
    assert r.out_tokens == want, (r.out_tokens, want)


@pytest.mark.chaos
def test_preemption_parity_paged_via_preempt_api(model):
    prompt = [9, 9, 8, 2, 4]
    want = model.generate([prompt], max_new_tokens=16)[0].tolist()
    eng = shared_engine(model, n_slots=1, max_len=64, paged=True,
                        page_size=8)
    r = eng.submit(prompt, max_new_tokens=16)
    for _ in range(3):
        eng.step()
    eng.preempt(r)
    eng.run_until_idle()
    assert r.out_tokens == want
    assert _pages_balanced(eng)


@pytest.mark.chaos
def test_preemption_preserves_repetition_penalty_state(model):
    """The seen-token mask rides the swap blob: a penalized request
    resumed after preemption matches its uninterrupted run."""
    prompt = [3, 1, 4, 1, 5]
    ref_eng = shared_engine(model, n_slots=1, max_len=64)
    ref = ref_eng.submit(prompt, max_new_tokens=16, repetition_penalty=1.5)
    ref_eng.run_until_idle()
    eng = shared_engine(model, n_slots=1, max_len=64)
    r = eng.submit(prompt, max_new_tokens=16, repetition_penalty=1.5)
    for _ in range(5):
        eng.step()
    eng.preempt(r)
    eng.run_until_idle()
    assert r.out_tokens == ref.out_tokens


# ---------------------------------------------------------------------------
# pool-exhaustion storms: nobody finishes "length" early
# ---------------------------------------------------------------------------

@pytest.mark.core
@pytest.mark.chaos
def test_pool_exhaustion_storm_no_early_length(model):
    """Concurrent paged requests overcommit the pool several times over:
    with preemption enabled NO request finishes before reaching its own
    max_new_tokens, every output matches its uninterrupted reference,
    and page accounting balances to zero after the storm."""
    prompts = [[3, 1, 4, 1, 5], [9, 9, 8, 2], [2, 7, 1, 8, 3, 6]]
    maxnt = 40
    want = {tuple(p): model.generate([p], max_new_tokens=maxnt)[0].tolist()
            for p in prompts}
    # 3 slots x (up to 6 pages each at the end) >> 9 allocatable pages
    eng = shared_engine(model, n_slots=3, max_len=64, paged=True,
                        page_size=8, n_pages=10)
    reqs = [eng.submit(p, max_new_tokens=maxnt) for p in prompts]
    eng.run_until_idle(max_steps=5000)
    for p, r in zip(prompts, reqs):
        assert r.done and not r.error, (r.finish_reason, r.error)
        assert len(r.out_tokens) == maxnt, (
            f"request finished '{r.finish_reason}' after "
            f"{len(r.out_tokens)}/{maxnt} tokens — silent truncation"
        )
        assert r.out_tokens == want[tuple(p)]
    assert eng.preemptions > 0  # the pool genuinely overcommitted
    assert _pages_balanced(eng)


@pytest.mark.chaos
@pytest.mark.slow
def test_pool_exhaustion_storm_large(model):
    """Bigger storm variant (queue backlog + repeated preemption cycles);
    excluded from the tier-1 budget via the slow marker."""
    prompts = [[i + 2, 5, 6, 7, 8] for i in range(8)]
    maxnt = 40
    eng = shared_engine(model, n_slots=3, max_len=64, paged=True,
                        page_size=8, n_pages=10)
    reqs = [eng.submit(p, max_new_tokens=maxnt) for p in prompts]
    eng.run_until_idle(max_steps=20000)
    for r in reqs:
        assert r.done and not r.error
        assert len(r.out_tokens) == maxnt
    assert _pages_balanced(eng)


@pytest.mark.chaos
def test_preemption_disabled_restores_length_finish(model):
    """preemption=False keeps the old overload behavior (finish "length"
    on pool exhaustion) for operators who prefer truncation to swapping."""
    inj = FaultInjector(seed=0)
    eng = shared_engine(model, n_slots=1, max_len=64, paged=True,
                        page_size=8, faults=inj, preemption=False)
    r = eng.submit([3, 1, 4, 1, 5], max_new_tokens=40)
    eng.step()
    # twice: dry for the page a step AHEAD of the one in flight (the engine
    # only reads first), and dry for the page the next step needs
    inj.arm("alloc_page", times=2)
    eng.run_until_idle()
    assert r.done and r.finish_reason == "length"
    assert len(r.out_tokens) < 40
    assert eng.preemptions == 0


# ---------------------------------------------------------------------------
# bounded admission + deadlines
# ---------------------------------------------------------------------------

@pytest.mark.core
@pytest.mark.chaos
def test_queue_bound_sheds_fast(model):
    eng = shared_engine(model, n_slots=1, max_len=64, max_queue=1)
    a = eng.submit([3, 1, 4], max_new_tokens=30)
    eng.step()  # a occupies the slot
    b = eng.submit([2, 7], max_new_tokens=4)  # queued: 1 == bound
    c = eng.submit([5, 6], max_new_tokens=4)  # over bound
    assert c.done and c.finish_reason == "shed"
    assert c.shed_kind == "queue_full"  # drives the server's 429
    assert "queue full" in c.error
    assert eng.requests_shed == 1
    eng.run_until_idle()
    assert a.done and b.done and not a.error and not b.error


@pytest.mark.chaos
def test_queue_deadline_sheds_instead_of_serving_late(model):
    eng = shared_engine(model, n_slots=1, max_len=64)
    a = eng.submit([3, 1, 4], max_new_tokens=20)
    b = eng.submit([2, 7], max_new_tokens=4, queue_deadline_s=0.0)
    eng.run_until_idle()
    assert a.done and not a.error
    assert b.done and b.finish_reason == "shed"
    assert b.shed_kind == "queue_deadline"  # drives the server's 503
    assert "queue deadline" in b.error
    # b's stream-less shed still delivered; queue-wait histogram only
    # counts ADMITTED requests
    assert sum(eng.queue_wait.counts) == 1


@pytest.mark.chaos
def test_queue_deadline_sheds_while_saturated(model):
    """Expired queued requests are shed by the per-step sweep even when
    no slot frees: a saturated engine must not 429 new clients over a
    queue of already-dead work."""
    eng = shared_engine(model, n_slots=1, max_len=64, max_queue=1)
    a = eng.submit([3, 1, 4], max_new_tokens=30)
    eng.step()  # a occupies the only slot for many steps
    b = eng.submit([2, 7], max_new_tokens=4, queue_deadline_s=0.01)
    time.sleep(0.02)
    eng.step()  # no slot frees here — the sweep sheds b anyway
    assert not a.done
    assert b.done and b.finish_reason == "shed"
    assert "queue deadline" in b.error
    # the queue capacity b held is free again: a new submit is admitted
    c = eng.submit([5, 6], max_new_tokens=4)
    assert not c.done  # queued, not shed
    eng.run_until_idle()
    assert a.done and c.done and not a.error and not c.error


@pytest.mark.chaos
def test_queued_cancel_frees_queue_capacity(model):
    """A cancelled request is dropped from the queue by the per-step
    sweep even when no slot frees — it must stop counting against
    max_queue the moment the engine notices, not when a slot opens."""
    eng = shared_engine(model, n_slots=1, max_len=64, max_queue=1)
    a = eng.submit([3, 1, 4], max_new_tokens=30)
    eng.step()  # a occupies the only slot
    b = eng.submit([2, 7], max_new_tokens=4)  # queued: at the bound
    eng.cancel(b)
    eng.step()  # no slot frees — the sweep drops b anyway
    assert not a.done
    assert b.done and b.finish_reason == "stop"
    c = eng.submit([5, 6], max_new_tokens=4)
    assert not c.done  # admitted: b's capacity was reclaimed
    eng.run_until_idle()
    assert a.done and c.done and not a.error and not c.error
    assert not eng._cancelled  # no leaked cancel marks


@pytest.mark.chaos
def test_cancel_reaches_parked_request(model):
    """A request cancelled while PARKED in host RAM is dropped by the
    per-step sweep (blob freed, stream sentinel delivered) instead of
    lingering behind other parked work until its resume turn."""
    import queue as _q

    eng = shared_engine(model, n_slots=1, max_len=64, paged=True,
                        page_size=8)
    q: _q.SimpleQueue = _q.SimpleQueue()
    r = eng.submit([3, 1, 4], max_new_tokens=30, stream=q)
    for _ in range(3):
        eng.step()
    eng._preempt_slot(0)  # park it (engine-thread context)
    assert len(eng._preempted) == 1
    eng.cancel(r)
    eng.step()  # sweep drops the parked entry before any resume
    assert r.done and r.finish_reason == "stop"
    assert not eng._preempted and not eng._cancelled
    while q.get(timeout=5) is not None:  # sentinel delivered
        pass
    assert _pages_balanced(eng)


@pytest.mark.chaos
def test_shed_stream_gets_sentinel(model):
    import queue as _q

    eng = shared_engine(model, n_slots=1, max_len=64, max_queue=1)
    eng.submit([3, 1, 4], max_new_tokens=30)
    eng.step()
    eng.submit([2, 7], max_new_tokens=4)
    q: _q.SimpleQueue = _q.SimpleQueue()
    c = eng.submit([5, 6], max_new_tokens=4, stream=q)
    assert c.finish_reason == "shed"
    assert q.get_nowait() is None  # client unblocks immediately


def _slow_steps():
    """Every step stalled 20 ms: 100 tokens then take two seconds whatever
    was compiled before, and a deadline of 0.3 s falls mid-decode."""
    return FaultInjector(seed=0).arm("slow_step", times=-1, seconds=0.02)


@pytest.mark.chaos
def test_deadline_mid_decode_finishes_timeout_with_partial_output(model):
    eng = shared_engine(model, n_slots=1, max_len=128, faults=_slow_steps())
    r = eng.submit([3, 1, 4], max_new_tokens=100, deadline_s=0.3)
    eng.run_until_idle(max_steps=100000)
    assert r.done and r.finish_reason == "timeout"
    assert "deadline_s" in r.error
    assert 0 < len(r.out_tokens) < 100  # partial output delivered
    assert eng.request_timeouts == 1


@pytest.mark.chaos
def test_engine_default_deadlines_apply(model):
    eng = shared_engine(model, n_slots=1, max_len=128, deadline_s=0.3,
                        faults=_slow_steps())
    r = eng.submit([3, 1, 4], max_new_tokens=100)
    assert r.deadline_s == 0.3  # engine default resolved at submit
    eng.run_until_idle(max_steps=100000)
    assert r.finish_reason == "timeout"


# ---------------------------------------------------------------------------
# HTTP mapping: 429/503 + Retry-After, metrics exposure
# ---------------------------------------------------------------------------

def test_http_shed_maps_to_429_with_retry_after(model):
    import json
    import urllib.error
    import urllib.request

    from bigdl_tpu.serving.api_server import ApiServer

    inj = FaultInjector(seed=1)
    # pace the engine so the slot stays busy while clients pile up
    inj.arm("slow_step", times=-1, seconds=0.05)
    srv = ApiServer(model, port=0, n_slots=1, max_len=64, max_queue=1,
                    faults=inj)
    srv.start()
    try:
        port = srv.port

        def post(payload, timeout=60):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/generate",
                data=json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"},
            )
            return urllib.request.urlopen(req, timeout=timeout)

        done = []
        threads = [
            threading.Thread(target=lambda: done.append(
                post({"prompt": [3, 1, 4], "max_new_tokens": 30}).read()
            ))
        ]
        threads[0].start()
        deadline = time.time() + 30
        while not srv.engine.active.any() and time.time() < deadline:
            time.sleep(0.01)
        assert srv.engine.active.any()
        threads.append(threading.Thread(target=lambda: done.append(
            post({"prompt": [2, 7], "max_new_tokens": 4}).read()
        )))
        threads[1].start()
        while srv.engine._queue.qsize() < 1 and time.time() < deadline:
            time.sleep(0.01)
        with pytest.raises(urllib.error.HTTPError) as e:
            post({"prompt": [5, 6], "max_new_tokens": 4})
        assert e.value.code == 429
        assert int(e.value.headers["Retry-After"]) >= 1
        assert b"queue full" in e.value.read()
        for t in threads:
            t.join(timeout=120)
        assert len(done) == 2  # the in-bound requests completed
        # overload counters visible to a Prometheus scraper
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=60
        ).read().decode()
        assert "bigdl_tpu_requests_shed_total 1" in text
        assert "bigdl_tpu_preemptions_total" in text
        assert "bigdl_tpu_request_timeouts_total" in text
        assert "bigdl_tpu_queue_wait_seconds_count" in text
    finally:
        srv.shutdown()


def test_http_queue_deadline_maps_to_503(model):
    import json
    import urllib.error
    import urllib.request

    from bigdl_tpu.serving.api_server import ApiServer

    inj = FaultInjector(seed=2)
    inj.arm("slow_step", times=-1, seconds=0.05)
    srv = ApiServer(model, port=0, n_slots=1, max_len=64, faults=inj)
    srv.start()
    try:
        port = srv.port

        def post(payload):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/generate",
                data=json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"},
            )
            return urllib.request.urlopen(req, timeout=60)

        t = threading.Thread(target=lambda: post(
            {"prompt": [3, 1, 4], "max_new_tokens": 20}
        ))
        t.start()
        deadline = time.time() + 30
        while not srv.engine.active.any() and time.time() < deadline:
            time.sleep(0.01)
        assert srv.engine.active.any()
        # this one carries a per-request queue deadline it cannot make
        # while the slot is busy
        with pytest.raises(urllib.error.HTTPError) as e:
            post({"prompt": [2, 7], "max_new_tokens": 4,
                  "queue_deadline_s": 0.001})
        assert e.value.code == 503
        assert "Retry-After" in e.value.headers
        t.join(timeout=120)
    finally:
        srv.shutdown()
