"""Deterministic fault-injection chaos suite (serving/faults.py).

Every injected fault — page-allocation failure, NaN logits, slow/stuck
step, crash-before-journal-done — must be survived with AT MOST the
faulted request failing: never the whole batch, never a hung engine
thread, never leaked pages. Runs entirely on CPU with a seeded
injector, so each scenario replays exactly.
"""

import json
import queue as _q
import threading
import time
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from bigdl_tpu.api import TpuModel, optimize_model
from bigdl_tpu.models import llama
from bigdl_tpu.models.config import PRESETS
from bigdl_tpu.serving.faults import (
    NULL_INJECTOR, FaultError, FaultInjector,
)
from engines import shared_engine

CFG = PRESETS["tiny-llama"]


@pytest.fixture(scope="module")
def model():
    params = optimize_model(
        llama.init_params(CFG, jax.random.PRNGKey(7)), CFG, "sym_int4"
    )
    return TpuModel(CFG, params, "sym_int4")


# ---------------------------------------------------------------------------
# injector semantics
# ---------------------------------------------------------------------------

@pytest.mark.core
@pytest.mark.chaos
def test_injector_deterministic_counting():
    inj = FaultInjector(seed=0)
    inj.arm("alloc_page", times=2, after=1, extra="x")
    assert inj.fire("alloc_page") is None  # skipped (after=1)
    assert inj.fire("alloc_page") == {"extra": "x"}
    assert inj.fire("alloc_page") == {"extra": "x"}
    assert inj.fire("alloc_page") is None  # exhausted
    assert inj.fired["alloc_page"] == 2 and inj.seen["alloc_page"] == 4
    with pytest.raises(ValueError, match="unknown injection point"):
        inj.arm("no_such_point")
    # seeded probabilistic mode replays exactly
    a = FaultInjector(seed=7).arm("slow_step", times=-1, prob=0.5)
    b = FaultInjector(seed=7).arm("slow_step", times=-1, prob=0.5)
    seq_a = [a.fire("slow_step") is not None for _ in range(32)]
    seq_b = [b.fire("slow_step") is not None for _ in range(32)]
    assert seq_a == seq_b and any(seq_a) and not all(seq_a)
    # the shared default injector refuses arming
    with pytest.raises(RuntimeError, match="no-op injector"):
        NULL_INJECTOR.arm("slow_step")


# ---------------------------------------------------------------------------
# NaN logits: quarantine one slot, never the batch
# ---------------------------------------------------------------------------

@pytest.mark.core
@pytest.mark.chaos
def test_nan_logits_quarantines_only_the_poisoned_slot(model):
    want = model.generate([[2, 7, 1, 8]], max_new_tokens=10)[0].tolist()
    inj = FaultInjector(seed=0)
    eng = shared_engine(model, n_slots=2, max_len=64, faults=inj)
    ra = eng.submit([3, 1, 4], max_new_tokens=10)
    rb = eng.submit([2, 7, 1, 8], max_new_tokens=10)
    eng.step()
    inj.arm("nan_logits", times=1, slots=[0])
    eng.run_until_idle()
    assert ra.done and ra.finish_reason == "error"
    assert "non-finite" in ra.error
    # the OTHER slot is untouched — bit-exact with its clean run, not
    # fail_all'd alongside the poisoned one
    assert rb.done and not rb.error
    assert rb.out_tokens == want
    # and the engine keeps serving
    rc = eng.submit([5, 6], max_new_tokens=4)
    eng.run_until_idle()
    assert rc.done and not rc.error and len(rc.out_tokens) == 4


@pytest.mark.chaos
def test_nan_logits_quarantines_speculative_slot(model):
    """The injection point also fires in the speculative verify path:
    the poisoned row is quarantined, the clean row decodes bit-exactly."""
    want = model.generate([[2, 7, 1, 8]], max_new_tokens=10)[0].tolist()
    inj = FaultInjector(seed=0)
    eng = shared_engine(model, n_slots=2, max_len=64, speculative=True,
                        draft_params=model.params, draft_k=4,
                        faults=inj)
    ra = eng.submit([3, 1, 4], max_new_tokens=10)
    rb = eng.submit([2, 7, 1, 8], max_new_tokens=10)
    eng.step()
    inj.arm("nan_logits", times=1, slots=[0])
    eng.run_until_idle()
    assert inj.fired["nan_logits"] == 1  # the spec path reached the hook
    assert ra.done and ra.finish_reason == "error"
    assert "non-finite" in ra.error and "speculative" in ra.error
    assert rb.done and not rb.error
    assert rb.out_tokens == want


@pytest.mark.chaos
def test_nan_logits_paged_releases_pages(model):
    inj = FaultInjector(seed=0)
    eng = shared_engine(model, n_slots=2, max_len=64, paged=True,
                        page_size=8, faults=inj)
    free0 = eng.pages.pool.n_free
    r = eng.submit([3, 1, 4, 1, 5], max_new_tokens=20)
    eng.step()
    inj.arm("nan_logits", times=1)
    eng.run_until_idle()
    assert r.done and r.finish_reason == "error"
    assert eng.pages.pool.n_free + eng.pages.radix.n_nodes == free0
    assert eng.page_leaks() == 0


# ---------------------------------------------------------------------------
# slow/stuck step: server timeouts cancel instead of leaking the slot
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_stuck_step_server_timeout_cancels_and_recovers(model):
    from bigdl_tpu.serving.api_server import ApiServer

    inj = FaultInjector(seed=0)
    srv = ApiServer(model, port=0, n_slots=1, max_len=64, faults=inj)
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

        # warm the compile caches under the generous default timeout so
        # the stall, not compilation, is what the tight timeout sees
        post({"prompt": [5, 6], "max_new_tokens": 2})
        srv.request_timeout_s = 0.3
        inj.arm("slow_step", times=3, seconds=0.4)
        with pytest.raises(urllib.error.HTTPError) as e:
            post({"prompt": [3, 1, 4], "max_new_tokens": 50})
        assert e.value.code == 504  # buffered timeout, not a hang
        assert srv.engine.request_timeouts >= 1
        # the timed-out request was CANCELLED in the engine: once the
        # stall clears, the slot frees and a fresh request completes
        inj.disarm("slow_step")
        srv.request_timeout_s = 60.0
        deadline = time.time() + 30
        while srv.engine.active.any() and time.time() < deadline:
            time.sleep(0.02)
        assert not srv.engine.active.any(), "timed-out request leaked its slot"
        out = json.loads(post({"prompt": [9, 8], "max_new_tokens": 3}).read())
        assert len(out["tokens"]) == 3
    finally:
        srv.shutdown()


@pytest.mark.chaos
def test_stream_stall_emits_error_event_not_fake_done(model):
    """A timeout-truncated SSE stream must end with an error event, not
    the same [DONE]-terminated success shape as a complete stream."""
    from bigdl_tpu.serving.api_server import ApiServer

    inj = FaultInjector(seed=0)
    srv = ApiServer(model, port=0, n_slots=1, max_len=128, faults=inj)
    srv.start()
    try:
        port = srv.port

        def post_stream(payload, timeout=60):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/generate_stream",
                data=json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"},
            )
            return urllib.request.urlopen(req, timeout=timeout).read()

        # warm the compile caches so the stall is what the timeout sees
        post_stream({"prompt": [5, 6, 7], "max_new_tokens": 2})
        srv.request_timeout_s = 0.5
        inj.arm("slow_step", times=-1, after=5, seconds=0.6)
        body = post_stream({"prompt": [3, 1, 4], "max_new_tokens": 50})
        events = [json.loads(l[len(b"data: "):])
                  for l in body.splitlines()
                  if l.startswith(b"data: ") and l != b"data: [DONE]"]
        assert any("error" in e and "stalled" in e["error"]
                   for e in events), events
        assert srv.engine.request_timeouts >= 1
    finally:
        inj.disarm()
        srv.shutdown()


@pytest.mark.chaos
def test_stream_stall_cancels_request(model):
    """A stalled stream consumer's timeout cancels the request in the
    engine rather than letting it decode to nowhere forever."""
    inj = FaultInjector(seed=0)
    eng = shared_engine(model, n_slots=1, max_len=128, faults=inj)
    # engine-level equivalent of _stream_iter's cancel-on-stall
    q: _q.SimpleQueue = _q.SimpleQueue()
    r = eng.submit([3, 1, 4], max_new_tokens=100, stream=q)
    for _ in range(3):
        eng.step()
    eng.cancel(r)  # what the server does on queue.Empty
    eng.run_until_idle(max_steps=50)
    assert r.done and not eng.active.any()


# ---------------------------------------------------------------------------
# crash before the journal tombstone: replay covers the window
# ---------------------------------------------------------------------------

@pytest.mark.core
@pytest.mark.chaos
def test_crash_before_done_is_replayed(model, tmp_path):
    jpath = str(tmp_path / "journal.jsonl")
    inj = FaultInjector(seed=0).arm("crash_before_done", times=1)
    eng = shared_engine(model, n_slots=1, max_len=64, journal=jpath,
                        faults=inj)
    r = eng.submit([3, 1, 4], max_new_tokens=5)
    crashed = False
    for _ in range(100):
        try:
            if not eng.step():
                break
        except FaultError:
            crashed = True
            break
    assert crashed and r.done  # completed, but tombstone never written
    # successor process: the request replays (at-least-once, never lost)
    eng2 = shared_engine(model, n_slots=1, max_len=64, journal=jpath)
    assert len(eng2.recovered_requests) == 1
    assert eng2.recovered_requests[0].prompt == [3, 1, 4]
    eng2.run_until_idle()
    rec = eng2.recovered_requests[0]
    assert rec.done and not rec.error and len(rec.out_tokens) == 5
    # fully tombstoned now: a third engine replays nothing
    eng3 = shared_engine(model, n_slots=1, max_len=64, journal=jpath)
    assert eng3.recovered_requests == []


@pytest.mark.core
@pytest.mark.chaos
def test_crash_cleanup_survives_multi_charge_arm(model, tmp_path):
    """crash_before_done armed with charges LEFT must not re-fire inside
    fail_all's cleanup _finish calls — the server's engine thread handles
    the first crash with fail_all, and a second FaultError there would
    kill the thread and hang every client."""
    jpath = str(tmp_path / "journal.jsonl")
    inj = FaultInjector(seed=0).arm("crash_before_done", times=2)
    eng = shared_engine(model, n_slots=1, max_len=64, journal=jpath,
                        faults=inj)
    r = eng.submit([3, 1, 4], max_new_tokens=5)
    with pytest.raises(FaultError):
        eng.run_until_idle()
    # what _EngineThread does on a crashed step: must NOT re-raise
    eng.fail_all("engine error: injected crash")
    # the crashed-inside-_finish request keeps its completed terminal
    # state — fail_all must not flip it to "error" or (worse) write the
    # journal tombstone the injected crash exists to suppress
    assert r.done and r.finish_reason == "length" and not r.error
    inj.disarm()  # spend no more charges; the engine must still serve
    r2 = eng.submit([2, 7], max_new_tokens=4)
    eng.run_until_idle()
    assert r2.done and not r2.error and len(r2.out_tokens) == 4
    # the at-least-once window survived the live-server cleanup path: a
    # successor engine still replays the un-tombstoned request
    eng2 = shared_engine(model, n_slots=1, max_len=64, journal=jpath)
    assert [e.prompt for e in eng2.recovered_requests] == [[3, 1, 4]]


@pytest.mark.chaos
def test_journal_replay_bypasses_admission_bound(model, tmp_path):
    """A recovered backlog larger than max_queue must replay in FULL:
    every journaled entry was already accepted once, and a shed during
    replay would erase its only journal record (replay tombstones the
    old rid as soon as the replacement submit lands) — permanent loss."""
    jpath = str(tmp_path / "backlog.jsonl")
    eng = shared_engine(model, n_slots=1, max_len=64, journal=jpath)
    reqs = [eng.submit([2 + i, 7], max_new_tokens=3, deadline_s=120.0)
            for i in range(5)]
    # crash before any step: all 5 remain journaled, none tombstoned
    eng2 = shared_engine(model, n_slots=1, max_len=64, journal=jpath,
                         max_queue=2)
    assert len(eng2.recovered_requests) == 5
    # per-request deadlines survive the crash (fresh clock from replay)
    assert all(r.deadline_s == 120.0 for r in eng2.recovered_requests)
    assert not any(r.finish_reason == "shed"
                   for r in eng2.recovered_requests)
    assert eng2.requests_shed == 0
    eng2.run_until_idle()
    for r in eng2.recovered_requests:
        assert r.done and not r.error and len(r.out_tokens) == 3
    # the bound still applies to LIVE traffic after recovery
    assert eng2.max_queue == 2
    del reqs


@pytest.mark.core
@pytest.mark.chaos
def test_journal_tolerates_truncated_trailing_line(tmp_path):
    """Crash mid-append: the torn last line is skipped with a warning,
    the intact entries before it replay normally."""
    from bigdl_tpu.serving.journal import RequestJournal

    jpath = str(tmp_path / "torn.jsonl")
    good = {"op": "submit", "rid": 0, "prompt": [1, 2, 3],
            "max_new_tokens": 4}
    torn = json.dumps({"op": "submit", "rid": 1, "prompt": [7, 8, 9]})
    with open(jpath, "w") as f:
        f.write(json.dumps(good) + "\n")
        f.write(torn[: len(torn) // 2])  # chopped mid-object, no newline
    with pytest.warns(UserWarning, match="truncated trailing"):
        entries, max_rid = RequestJournal.scan(jpath)
    assert [e["rid"] for e in entries] == [0]
    assert max_rid == 0


@pytest.mark.chaos
def test_journal_warns_on_interior_corruption(tmp_path):
    from bigdl_tpu.serving.journal import RequestJournal

    jpath = str(tmp_path / "corrupt.jsonl")
    with open(jpath, "w") as f:
        f.write('{"op": "submit", "rid": 0, "prompt": [1]}\n')
        f.write("xx-not-json-xx\n")
        f.write('{"op": "submit", "rid": 1, "prompt": [2]}\n')
    with pytest.warns(UserWarning, match="interior corruption"):
        entries, max_rid = RequestJournal.scan(jpath)
    assert [e["rid"] for e in entries] == [0, 1] and max_rid == 1


# ---------------------------------------------------------------------------
# the full sweep: every fault, one engine, no leaks, no hangs
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_chaos_sweep_survives_every_fault_class(model, tmp_path):
    """alloc failure + NaN poisoning + stalls through one paged engine:
    at most the faulted request fails, the engine never hangs, and the
    free-page count returns to its initial value."""
    inj = FaultInjector(seed=3)
    eng = shared_engine(model, n_slots=2, max_len=64, paged=True,
                        page_size=8, n_pages=10, faults=inj,
                        journal=str(tmp_path / "sweep.jsonl"))
    free0 = eng.pages.pool.n_free
    reqs = [eng.submit([2 + i, 7, 9, 11], max_new_tokens=30)
            for i in range(4)]
    eng.step()
    inj.arm("alloc_page", times=2)          # exhaustion -> preemption
    inj.arm("slow_step", times=2, seconds=0.01)
    inj.arm("nan_logits", times=1, slots=[1])  # poison one row
    eng.run_until_idle(max_steps=5000)
    assert all(r.done for r in reqs)
    failed = [r for r in reqs if r.finish_reason == "error"]
    assert len(failed) <= 1  # at most the poisoned request
    for r in reqs:
        if r.finish_reason != "error":
            assert len(r.out_tokens) == 30, (
                f"'{r.finish_reason}' after {len(r.out_tokens)} tokens"
            )
    assert eng.pages.pool.n_free + eng.pages.radix.n_nodes == free0
    assert eng.page_leaks() == 0
    assert not eng._preempted and not eng.active.any()
    # still serving after the sweep
    tail = eng.submit([5, 6], max_new_tokens=4)
    eng.run_until_idle()
    assert tail.done and not tail.error


# ---------------------------------------------------------------------------
# graceful shutdown: drain in-flight work, shed new, compact the journal
# ---------------------------------------------------------------------------

@pytest.mark.core
@pytest.mark.chaos
def test_graceful_drain_finishes_inflight_sheds_new_compacts_journal(
        model, tmp_path):
    """SIGTERM semantics at the engine level: begin_drain sheds NEW
    submits (503-mapped "draining", never journaled) while accepted
    work runs to completion; close() then flushes + compacts the
    journal so a clean shutdown leaves NOTHING to replay."""
    jpath = str(tmp_path / "drain.jsonl")
    eng = shared_engine(model, n_slots=2, max_len=64, journal=jpath)
    inflight = [eng.submit([2 + i, 7], max_new_tokens=5)
                for i in range(3)]
    eng.step()  # some admitted, one still queued
    assert eng.drain(timeout_s=30.0)
    late = eng.submit([9, 9], max_new_tokens=3)
    assert late.done and late.finish_reason == "shed"
    assert late.shed_kind == "draining"
    for r in inflight:  # accepted work was never cut short
        assert r.done and not r.error and len(r.out_tokens) == 5
    eng.close()
    eng.close()  # idempotent
    # compacted to the pending tail — which a clean drain makes empty
    from bigdl_tpu.serving.journal import RequestJournal

    assert RequestJournal.pending(jpath) == []
    eng2 = shared_engine(model, n_slots=2, max_len=64, journal=jpath)
    assert eng2.recovered_requests == []


@pytest.mark.chaos
def test_graceful_server_shutdown_drains_via_worker_thread(model, tmp_path):
    """ApiServer.shutdown(graceful=True): the engine thread finishes
    in-flight requests before the journal is closed and compacted —
    a clean SIGTERM relies on replay for nothing."""
    from bigdl_tpu.serving.api_server import ApiServer

    jpath = str(tmp_path / "server.jsonl")
    srv = ApiServer(model, port=0, n_slots=2, max_len=64,
                    journal=jpath).start()
    try:
        reqs = [srv.engine.submit([3 + i, 1], max_new_tokens=4)
                for i in range(3)]
        assert srv.shutdown(graceful=True) is True
        assert all(r.done and not r.error for r in reqs)
        assert srv.engine._journal is None  # closed
        from bigdl_tpu.serving.journal import RequestJournal

        assert RequestJournal.pending(jpath) == []
    finally:
        srv.worker.stop_flag.set()
        srv.httpd.shutdown()


@pytest.mark.chaos
def test_drain_timeout_leaves_unfinished_tail_for_replay(model, tmp_path):
    """A drain that cannot finish in its budget gives up WITHOUT losing
    work: the unfinished requests stay pending in the compacted journal
    and replay at the next start (the crash path as fallback)."""
    jpath = str(tmp_path / "stuck.jsonl")
    inj = FaultInjector(seed=0).arm("slow_step", times=-1, seconds=0.2)
    eng = shared_engine(model, n_slots=1, max_len=64, journal=jpath,
                        faults=inj)
    req = eng.submit([3, 1, 4], max_new_tokens=50)
    assert eng.drain(timeout_s=0.3) is False
    assert not req.done  # not cut short, just not finished
    eng.close()
    eng2 = shared_engine(model, n_slots=1, max_len=64, journal=jpath)
    assert [e.prompt for e in eng2.recovered_requests] == [[3, 1, 4]]
