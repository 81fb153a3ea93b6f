"""Observability-layer tests (ISSUE 11): request-lifecycle tracing,
TTFT/phase latency metrics, profiler hooks.

Acceptance invariants:
- a serving run with tracing enabled exports VALID Chrome trace-event
  JSON (Perfetto-loadable) whose spans are monotonically nested per
  track, including queued/prefill/decode/preempted spans for a
  preempted-and-resumed request;
- /metrics reports TTFT, inter-token-latency, and phase-duration
  histograms consistent (±10%) with the spans of the same run;
- tracing disabled costs < 2% on a synthetic engine step loop;
- every registered metric family appears in the rendered exposition
  and vice versa (drift check, both directions).
"""

import contextlib
import json
import time

import jax
import numpy as np
import pytest

from bigdl_tpu.api import TpuModel, optimize_model
from bigdl_tpu.models import llama
from bigdl_tpu.models.config import PRESETS
from bigdl_tpu.obs.profiler import (
    ProfilerBusy,
    ProfilerIdle,
    ProfilerWindow,
)
from bigdl_tpu.obs.tracing import (
    DECODE_TID,
    RequestLog,
    TraceRecorder,
    format_summary,
    summarize_trace,
    validate_nesting,
)
from bigdl_tpu.serving import engine as engine_mod
from bigdl_tpu.serving.engine import InferenceEngine
from engines import shared_engine
from bigdl_tpu.serving.faults import FaultInjector
from bigdl_tpu.serving.metrics import Metrics, metric_drift

pytestmark = pytest.mark.core

CFG = PRESETS["tiny-llama"]


@pytest.fixture(scope="module")
def model():
    params = optimize_model(
        llama.init_params(CFG, jax.random.PRNGKey(0)), CFG, "sym_int4"
    )
    return TpuModel(CFG, params, "sym_int4")


def _span_total_s(events, name):
    return sum(e["dur"] for e in events
               if e.get("ph") == "X" and e["name"] == name) / 1e6


def _close(a, b, rel=0.10):
    return abs(a - b) <= rel * max(abs(a), abs(b), 1e-9)


def _metric_value(text, prefix):
    """The value of the first sample line starting with `prefix`."""
    for line in text.splitlines():
        if line.startswith(prefix) and not line.startswith("#"):
            return float(line.rsplit(" ", 1)[1])
    raise AssertionError(f"{prefix} not rendered")


# ---------------------------------------------------------------------------
# trace export: golden structure
# ---------------------------------------------------------------------------

def test_trace_export_golden(model, tmp_path, monkeypatch):
    """A traced serving run exports valid Chrome trace JSON with the
    full request-lifecycle span vocabulary, monotonically nested spans
    per track, and a crc-clean derived-timings request log."""
    monkeypatch.setattr(engine_mod, "TRACE_DECODE_EVERY", 3)
    tr = TraceRecorder(enabled=True)
    log_path = str(tmp_path / "requests.jsonl")
    eng = shared_engine(model, n_slots=2, max_len=128, tracer=tr,
                        request_log=log_path)
    reqs = [eng.submit([3, 1, 4, 1, 5], max_new_tokens=8)
            for _ in range(3)]
    eng.run_until_idle()
    eng.close()
    assert all(r.done for r in reqs)

    out = str(tmp_path / "trace.json")
    tr.export(out)
    with open(out) as f:
        obj = json.load(f)  # valid JSON or this raises
    events = obj["traceEvents"]
    assert isinstance(events, list) and events
    for e in events:
        assert {"name", "ph", "pid", "tid"} <= set(e)
        assert e["ph"] in ("X", "i", "C", "M")
        if e["ph"] == "X":
            assert e["dur"] >= 0 and isinstance(e["ts"], int)
    names = {e["name"] for e in events}
    assert {"submit", "queued", "prefill", "decode", "finish",
            "decode_step"} <= names
    # monotonic nesting: no partial overlap on any track
    assert validate_nesting(events) == []
    # tid 0 is RESERVED for the engine track: rids start at 1, so no
    # request's lifecycle spans can interleave with decode_step spans
    assert min(r.rid for r in reqs) >= 1
    assert all(e["name"] in ENGINE_TRACK
               for e in events if e["tid"] == 0 and e["ph"] != "M")
    # every request has its own track with a queued->prefill sequence
    for r in reqs:
        mine = [e for e in events if e["tid"] == r.rid
                and e.get("ph") == "X"]
        assert [e["name"] for e in mine[:2]] == ["queued", "prefill"]

    # derived-timings JSONL: one crc-clean record per finished request
    recs = RequestLog.read(log_path)
    assert len(recs) == 3
    for rec in recs:
        assert rec["finish_reason"] == "length"
        assert rec["output_tokens"] == 8
        assert 0 <= rec["queue_wait_s"] <= rec["ttft_s"]
        assert rec["tpot_s"] >= 0

    # summarize: the CLI's latency table reduces the same trace
    summary = summarize_trace(obj)
    assert summary["spans"]["prefill"]["count"] == 3
    assert summary["requests"]["finish_reasons"] == {"length": 3}
    table = format_summary(summary)
    assert "prefill" in table and "TTFT" in table


def test_trace_export_sanitizes_non_finite_args(tmp_path):
    """A NaN loss (the exact anomaly tracing exists to capture) must
    not turn the export into non-RFC-8259 JSON that Perfetto rejects:
    non-finite arg values export as null."""
    tr = TraceRecorder(enabled=True)
    tr.complete("train.step", 0.0, 1.0, cat="train", step=3,
                loss=float("nan"), skipped=True)
    tr.instant("anomaly", ts=1.0, cat="train", grad_norm=float("inf"))
    out = str(tmp_path / "nan.json")
    tr.export(out)  # allow_nan=False inside: raises if a NaN leaks
    with open(out) as f:
        text = f.read()
    assert "NaN" not in text and "Infinity" not in text
    evts = json.loads(text)["traceEvents"]
    assert evts[0]["args"]["loss"] is None
    assert evts[0]["args"]["step"] == 3  # finite values untouched
    assert evts[1]["args"]["grad_norm"] is None
    # the in-memory ring still holds the raw values (sanitizing is an
    # export concern)
    assert tr.events()[0]["args"]["loss"] != tr.events()[0]["args"]["loss"]


# ---------------------------------------------------------------------------
# acceptance: preempted-and-resumed request, spans vs /metrics (±10%)
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_preempted_request_trace_and_metric_consistency(model, monkeypatch):
    """Chaos-suite run with tracing: injected pool exhaustion preempts
    and resumes a request; the trace carries its queued/prefill/decode/
    preempted spans, and the TTFT/ITL/phase histograms on /metrics agree
    with the spans of the same run within 10%."""
    monkeypatch.setattr(engine_mod, "TRACE_DECODE_EVERY", 4)
    tr = TraceRecorder(enabled=True)
    inj = FaultInjector(seed=0)
    eng = shared_engine(model, n_slots=1, max_len=64, paged=True,
                        page_size=8, faults=inj, tracer=tr)
    r = eng.submit([3, 1, 4, 1, 5], max_new_tokens=40)
    eng.step()  # admit; next page allocation is the decode extension
    # twice: the page a step AHEAD of the one in flight (dry there, the
    # engine only reads first), then the page the next step needs
    inj.arm("alloc_page", times=2)
    eng.run_until_idle()
    assert r.done and not r.error and r.preemptions == 1
    assert eng.preemptions == 1 and eng.preemption_resumes == 1

    events = tr.events()
    assert validate_nesting(events) == []
    mine = [e["name"] for e in events if e.get("tid") == r.rid]
    for name in ("queued", "prefill", "decode", "swap_out", "preempted",
                 "finish"):
        assert name in mine, (name, mine)
    # the preempted span's duration is exactly what resume_wait observed
    parked = _span_total_s(events, "preempted")
    assert sum(eng.resume_wait.counts) == 1
    assert _close(eng.resume_wait.sum, parked)
    assert _close(r.preempted_s, parked)
    # derived tpot excludes the parked stretch (it is reported in
    # preempted_s, not smeared into per-token latency)
    rec = eng._request_record(r, time.time())
    span = r.last_token_ts - r.first_token_ts
    assert _close(rec["tpot_s"],
                  (span - r.preempted_s) / (len(r.out_tokens) - 1))
    assert rec["preempted_s"] > 0
    # resume requeue time is NOT folded into queue_wait (satellite):
    # exactly one admission wait was observed
    assert sum(eng.queue_wait.counts) == 1

    # /metrics vs spans, same run, ±10%
    text = Metrics(eng).render()
    finish = [e for e in events
              if e.get("ph") == "i" and e["name"] == "finish"]
    ttft_spans = sum(e["args"]["ttft_s"] for e in finish
                     if "ttft_s" in e["args"])
    assert _close(_metric_value(text, "bigdl_tpu_ttft_seconds_sum"),
                  ttft_spans)
    assert _close(
        _metric_value(text, "bigdl_tpu_inter_token_seconds_sum"),
        _span_total_s(events, "decode"),
    )
    assert _close(_metric_value(text, "bigdl_tpu_prefill_seconds_sum"),
                  _span_total_s(events, "prefill"))
    assert _close(
        _metric_value(text, "bigdl_tpu_decode_step_seconds_sum"),
        _span_total_s(events, "decode_step"),
    )
    assert _metric_value(
        text, 'bigdl_tpu_requests_finished_total{reason="stop"}'
    ) + _metric_value(
        text, 'bigdl_tpu_requests_finished_total{reason="length"}'
    ) == 1
    assert "bigdl_tpu_resume_wait_seconds_count 1" in text


@pytest.mark.chaos
def test_request_dying_while_parked_closes_preempted_span(model):
    """A request that reaches a terminal state while still parked in
    host RAM (resume impossible) must close its 'preempted' span and
    report the parked stretch in preempted_s — not log preempted_s=0
    with a dangling swap_out instant."""
    tr = TraceRecorder(enabled=True)
    inj = FaultInjector(seed=0)
    eng = shared_engine(model, n_slots=1, max_len=64, paged=True,
                        page_size=8, faults=inj, tracer=tr)
    r = eng.submit([3, 1, 4, 1, 5], max_new_tokens=20)
    eng.step()  # admit + first token
    eng.preempt(r)  # operator-initiated park
    inj.arm("alloc_page", times=-1)  # resume can never get pages back
    eng.run_until_idle(max_steps=50)
    assert r.done and r.finish_reason == "error"  # un-resumable
    assert r.preemptions == 1 and r.preempted_s > 0
    assert r.preempt_ts is None  # stretch was closed at finish
    events = tr.events()
    mine = [e["name"] for e in events if e.get("tid") == r.rid]
    assert "swap_out" in mine and "preempted" in mine
    closing = [e for e in events if e.get("ph") == "X"
               and e["name"] == "preempted"][0]
    assert closing["args"]["outcome"] == "error"
    assert _close(closing["dur"] / 1e6, r.preempted_s)
    assert validate_nesting(events) == []
    rec = eng._request_record(r, time.time())
    assert rec["preempted_s"] > 0


# ---------------------------------------------------------------------------
# TTFT / ITL histogram correctness under an injected slow_step fault
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_ttft_itl_under_injected_slow_step(model):
    """With every step stalled by an injected slow_step fault, the
    inter-token histogram must see gaps of at least the stall, and TTFT
    must include the pre-admission stall — the histograms measure real
    wall time, not optimistic bookkeeping."""
    stall = 0.03
    inj = FaultInjector(seed=0)
    inj.arm("slow_step", times=-1, seconds=stall)
    eng = shared_engine(model, n_slots=1, max_len=128, faults=inj)
    r = eng.submit([2, 7, 1, 8], max_new_tokens=5)
    eng.run_until_idle()
    assert r.done and len(r.out_tokens) == 5
    n_itl = sum(eng.itl.counts)
    assert n_itl == 4  # 5 tokens -> 4 gaps
    assert eng.itl.sum >= n_itl * stall * 0.9
    assert sum(eng.ttft.counts) == 1
    assert eng.ttft.sum >= stall * 0.9  # the admit step stalled too
    # derived tpot agrees with the histogram mean within 10%
    rec = eng._request_record(r, time.time())
    assert _close(rec["tpot_s"], eng.itl.sum / n_itl)


# ---------------------------------------------------------------------------
# phase spans: an admission and a decode step, partitioned (ISSUE 24)
# ---------------------------------------------------------------------------

ADMISSION_PARTS = ["prefill.dispatch", "first_token.sample",
                   "first_token.arm"]
DISPATCH_PARTS = ["decode.args", "decode.call"]
FETCH_PARTS = ["decode.wait", "decode.read"]
#: `engine.step`'s parts, in order: one that admitted and left no slot
#: active has the first three; one that decoded then dispatches the step
#: AFTER the one in flight (after an idle stretch two steps, and none
#: where no slot outlives the one in flight) and reads the one in flight
ENGINE_STEP_PARTS = ["step.reap", "step.admit", "step.pages",
                     "decode.dispatch", "decode.fetch", "step.emit"]
#: every event name of the engine track (tid 0); `decode_step` spans run
#: from one call of `step()` into the next and have a track of their own
ENGINE_TRACK = set(ENGINE_STEP_PARTS + DISPATCH_PARTS + FETCH_PARTS
                   + ["engine.step", "batch"])
#: the phases mirrored onto a profile's host plane, by what they carry
STEP_ANNOTATIONS = DISPATCH_PARTS + FETCH_PARTS + ["step.emit"]
ADMISSION_ANNOTATIONS = ["prefill.dispatch", "first_token.sample"]

ENGINES = {
    "dense": {},
    "paged": {"paged": True, "page_size": 8},
    # every prompt below takes three chunks, with decode steps between
    "paged-chunked": {"paged": True, "page_size": 8,
                      "prefill_chunk_tokens": 8},
    "speculative": {"paged": True, "page_size": 8, "speculative": True,
                    "draft_k": 3},
}


def _engine(model, kind, build=shared_engine, **kw):
    """`build=InferenceEngine` where the test reads what the engine traced."""
    opts = dict(ENGINES[kind])
    if opts.get("speculative"):
        opts["draft_params"] = model.params
    return build(model, n_slots=2, max_len=128, **opts, **kw)


def _serve(eng, n=3):
    reqs = [eng.submit(list(range(1 + i, 21 + i)), max_new_tokens=5)
            for i in range(n)]
    eng.run_until_idle()
    assert all(r.done for r in reqs)
    return reqs


def _children(events, parent, names=None):
    """The spans on `parent`'s track that lie inside it (those called one
    of `names`, if given), in time order."""
    lo, hi = parent["ts"], parent["ts"] + parent["dur"]
    return sorted((e for e in events if e.get("ph") == "X"
                   and e is not parent and e["tid"] == parent["tid"]
                   and lo <= e["ts"] and e["ts"] + e["dur"] <= hi
                   and (names is None or e["name"] in names)),
                  key=lambda e: (e["ts"], e["ts"] + e["dur"]))


def _abut_and_fill(parent, kids):
    assert kids[0]["ts"] == parent["ts"]
    for a, b in zip(kids, kids[1:]):
        assert a["ts"] + a["dur"] == b["ts"]
    assert sum(k["dur"] for k in kids) == parent["dur"]  # within 3 us: 0


@pytest.mark.parametrize("kind", list(ENGINES))
def test_phase_spans_partition_admission_and_step(model, kind):
    """Every `prefill` span holds exactly `prefill.dispatch`,
    `first_token.sample`, `first_token.arm`; every `engine.step` holds
    `step.reap`, `step.admit`, `step.pages` and, when it decoded, the
    `decode.dispatch` (`decode.args`, `decode.call`) of the steps it
    enqueued, then the `decode.fetch` (`decode.wait`, `decode.read`) and
    the `step.emit` of the step it read; at each level the children abut,
    sum to their parent to the microsecond, and carry the retrace seconds
    paid inside them. `decode_step` spans, on their own track, follow one
    another without overlap, and `seq` ties a step's span to its phases.
    The parents keep what they had (bench/ reads them)."""
    tr = TraceRecorder(enabled=True)
    eng = _engine(model, kind, build=InferenceEngine, tracer=tr)
    reqs = _serve(eng)
    eng.close()
    events = tr.events()
    assert validate_nesting(events) == []
    spans = [e for e in events if e.get("ph") == "X"]

    prefills = [e for e in spans if e["name"] == "prefill"]
    assert sorted(e["tid"] for e in prefills) == sorted(r.rid for r in reqs)
    first_admitted = min(prefills, key=lambda e: e["ts"])
    for p in prefills:
        kids = [k for k in _children(events, p)
                if k["name"] in ADMISSION_PARTS]
        assert [k["name"] for k in kids] == ADMISSION_PARTS
        _abut_and_fill(p, kids)
        assert all(k["cat"] == "request" and k["args"]["rid"] == p["tid"]
                   and k["args"]["retrace_s"] >= 0 for k in kids)
        assert kids[0]["args"]["prompt_tokens"] == 20
        # the first-token program is built by the engine's first
        # admission; no later one traces, lowers or compiles anything
        if p is not first_admitted:
            assert kids[1]["args"]["retrace_s"] == 0
            assert kids[2]["args"]["retrace_s"] == 0
        # over KV pages also the pool pages the admission gathered (its
        # row's table, once a chunk) and those it wrote back
        pool = {"row_pages", "pages_written"} if eng.paged else set()
        assert set(p["args"]) == {"rid", "prompt_tokens", "occupancy",
                                  "queue_depth"} | pool
        if pool:
            assert 0 < p["args"]["pages_written"] <= p["args"]["row_pages"]
            assert p["args"]["row_pages"] % eng.max_pages_per_row == 0
        assert 0 <= p["args"]["occupancy"] < eng.n_slots
    # the third request was admitted while the first two were decoding
    assert max(p["args"]["occupancy"] for p in prefills) >= 1
    assert max(p["args"]["queue_depth"] for p in prefills) >= 1

    steps = sorted((e for e in spans if e["name"] == "decode_step"),
                   key=lambda e: e["ts"])
    assert steps and all(e["tid"] == DECODE_TID for e in steps)
    for s in steps:
        assert set(s["args"]) == {"seq", "ahead", "occupancy", "slots",
                                  "queue_depth"} | (
            {"live_pages", "grid_pages"} if eng.paged else set())
    # one counter of decode steps, in the order they ran, and no step's
    # span starts before the one before it has ended
    assert [s["args"]["seq"] for s in steps] == \
        list(range(1, len(steps) + 1))
    for a, b in zip(steps, steps[1:]):
        assert a["ts"] + a["dur"] <= b["ts"]
    # `seq` pairs a step's phases: dispatched once (its uploads, its call)
    # and fetched once (the wait, the read), then emitted
    by_seq = {}
    for e in spans:
        if e["name"] in ("decode.dispatch", "decode.fetch", "step.emit"):
            by_seq.setdefault(e["args"]["seq"], []).append(e)
    assert sorted(by_seq) == [s["args"]["seq"] for s in steps]
    for s in steps:
        mine = sorted(by_seq[s["args"]["seq"]], key=lambda e: e["ts"])
        assert [e["name"] for e in mine] == ENGINE_STEP_PARTS[3:]
        for kid, names in zip(mine, (DISPATCH_PARTS, FETCH_PARTS)):
            grand = _children(events, kid)
            assert [g["name"] for g in grand] == names
            _abut_and_fill(kid, grand)
            assert kid["args"]["retrace_s"] >= 0
        assert grand[1]["args"] == {"arrays": 2 if eng.speculative else 1}
        # the span ends where its fetch does, and a step dispatched with
        # its predecessor unread says so
        assert abs(mine[1]["ts"] + mine[1]["dur"]
                   - s["ts"] - s["dur"]) <= 1  # us: each rounds on its own
        before = by_seq.get(s["args"]["seq"] - 1)
        assert s["args"]["ahead"] == (before is not None and mine[0]["ts"]
                                      < min(e["ts"] for e in before
                                            if e["name"] == "decode.fetch"))
    # plain decode keeps a step in flight; a speculative round is read in
    # the call that dispatched it
    assert any(s["args"]["ahead"] for s in steps) != bool(eng.speculative)

    whole = [e for e in spans if e["name"] == "engine.step"]
    for w in whole:
        kids = _children(events, w, ENGINE_STEP_PARTS)
        names = [k["name"] for k in kids]
        assert set(w["args"]) == {"seq", "admitted", "occupancy"}
        assert names[:3] == ENGINE_STEP_PARTS[:3]
        if w["args"]["seq"] is None:  # admitted or advanced a chunk, and
            # no slot was active yet (a chunked prefill's first chunks)
            assert names == ENGINE_STEP_PARTS[:3]
        else:  # read that step, after dispatching up to two
            assert names[-2:] == ENGINE_STEP_PARTS[-2:]
            assert names[3:-2] in ([], ["decode.dispatch"],
                                   ["decode.dispatch"] * 2)
            assert kids[-1]["args"]["seq"] == kids[-2]["args"]["seq"] \
                == w["args"]["seq"]
        assert kids[2]["args"] == {"bt_uploaded": kids[2]["args"][
            "bt_uploaded"]} and (eng.paged or not kids[2]["args"][
                "bt_uploaded"])
        _abut_and_fill(w, kids)
    # every decode step lies in an `engine.step`, every admission in a
    # `step.admit`; the idle loop's polls record nothing
    assert sorted(w["args"]["seq"] for w in whole
                  if w["args"]["seq"] is not None) == \
        sorted(s["args"]["seq"] for s in steps)
    assert sum(w["args"]["admitted"] for w in whole) == len(prefills)
    admits = [e for e in spans if e["name"] == "step.admit"]
    for p in prefills:
        assert any(a["ts"] <= p["ts"] + p["dur"] <= a["ts"] + a["dur"] + 1
                   for a in admits)
    n = len(events)
    assert eng.step() is False and len(tr.events()) == n
    if kind == "paged-chunked":  # `prefill.dispatch` covers every chunk,
        # and the steps that ran between them
        first = min(prefills, key=lambda e: e["ts"])
        later = [p for p in prefills if p is not first]
        assert any(p["ts"] <= s["ts"] < p["ts"] + p["dur"]
                   for p in later for s in steps)


def test_complete_parts_partitions_a_part_in_turn():
    """A part given as `(name, args, cuts, parts)` is cut again between
    the edges it got: at both levels the children abut and sum to their
    parent in whole microseconds, whatever the stamps' fractions."""
    tr = TraceRecorder(enabled=True)
    t = [10.0000004, 10.0010006, 10.0020009, 10.0030001, 10.0040007,
         10.0050003]
    tr.complete("whole", t[0], t[5] - t[0])
    tr.complete_parts(t[0], t[5] - t[0], (t[1], t[4]), (
        ("a", {}),
        ("b", {"k": 1}, (t[2], t[3]), (("b1", {}), ("b2", {}), ("b3", {}))),
        ("c", {})))
    events = tr.events()
    assert validate_nesting(events) == []
    by = {e["name"]: e for e in events}
    _abut_and_fill(by["whole"], [by["a"], by["b"], by["c"]])
    _abut_and_fill(by["b"], [by["b1"], by["b2"], by["b3"]])
    assert by["b"]["args"] == {"k": 1}
    # a cut outside its parent is held to the parent's edges
    tr.clear()
    tr.complete_parts(1.0, 0.001, (0.5,), (
        ("x", {}, (2.0,), (("x1", {}), ("x2", {}))), ("y", {})))
    by = {e["name"]: e for e in tr.events()}
    assert by["x"]["dur"] == by["x1"]["dur"] == by["x2"]["dur"] == 0
    assert by["y"]["dur"] == 1000


def test_a_step_that_raises_keeps_the_parts_it_closed(model):
    """The decode call fails: `engine.step` is recorded to the instant
    the step gave up, with the three parts that had closed before it and
    no `decode_step`; nothing is left over for the next step."""
    tr = TraceRecorder(enabled=True)
    eng = _engine(model, "paged", tracer=tr)
    eng.submit(list(range(1, 21)), max_new_tokens=5)

    def boom(*a, **kw):
        raise RuntimeError("device said no")

    eng._decode = boom
    with pytest.raises(RuntimeError, match="device said no"):
        eng.step()
    eng.close()
    events = tr.events()
    assert validate_nesting(events) == []
    whole = [e for e in events if e["name"] == "engine.step"]
    assert len(whole) == 1 and whole[0]["args"]["admitted"] == 1
    kids = _children(events, whole[0], ENGINE_STEP_PARTS)
    assert [k["name"] for k in kids] == ENGINE_STEP_PARTS[:3]
    assert kids[0]["ts"] == whole[0]["ts"]
    assert kids[-1]["ts"] + kids[-1]["dur"] <= \
        whole[0]["ts"] + whole[0]["dur"]
    assert eng._step_trace is None


@pytest.mark.parametrize("kind", ["paged", "paged-chunked"])
def test_decode_step_span_counts_live_pages(model, kind):
    """`decode_step` spans of a paged engine carry the kernel's grid
    (`grid_pages` = slots x pages per row) and the part of it that holds
    live KV, counted on the host: it equals sum(pos // page + 1) over the
    active rows of the DEVICE cache the step was given."""
    tr = TraceRecorder(enabled=True)
    eng = _engine(model, kind, tracer=tr)
    seen, dispatch = [], eng._dispatch

    def spy(reqs, *a, **kw):  # the rows this step computes for a request
        pos = np.asarray(eng.cache.pos)[[r is not None for r in reqs]]
        seen.append(int((pos // eng.page_size + 1).sum()))
        return dispatch(reqs, *a, **kw)

    eng._dispatch = spy
    _serve(eng)
    eng.close()
    steps = [e["args"] for e in tr.events()
             if e.get("ph") == "X" and e["name"] == "decode_step"]
    assert [a["live_pages"] for a in steps] == seen and min(seen) >= 1
    grid = eng.n_slots * eng.max_pages_per_row
    assert all(a["live_pages"] <= a["grid_pages"] == grid for a in steps)
    # prompts of 20 tokens on pages of 8: a row starts on its third page
    assert max(seen) >= 2 * 3
    # the operator's gauge is the same ratio; every slot is released now
    assert _metric_value(Metrics(eng).render(),
                         "bigdl_tpu_paged_live_page_share") == 0.0


class _CountingClock:
    def __init__(self):
        self.calls = 0

    def __call__(self):
        self.calls += 1
        return time.time()


def _count_annotations(monkeypatch):
    """Stand in for `jax.profiler.TraceAnnotation`, which the engine
    imports at its first traced step: every one built is listed."""
    built = []

    class Annotation(contextlib.nullcontext):
        def __init__(self, name, **ids):
            super().__init__()
            built.append((name, ids))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    return built


@pytest.mark.parametrize("kind", ["dense", "paged-chunked", "speculative"])
def test_tracing_off_costs_no_clock_call(model, kind, monkeypatch):
    """The off-cost, counted: with no recorder and with a disabled one
    the engine asks its clock equally often over the same requests,
    builds no annotation, and nothing is recorded; turned on, it asks
    exactly twice more per admission (the children's inner edges), three
    times more per call of `step()` (its entry, the ends of `step.reap`
    and `step.admit`), once more where the step ends (`step.pages` of a
    step that left no slot active, `step.emit` of one that decoded) and
    three times more per decode step (the ends of `decode.args`,
    `decode.dispatch` and `decode.wait`); every other edge is a stamp
    the engine already took."""
    built = _count_annotations(monkeypatch)
    calls, stepped = {}, {}
    for name, tracer in (("none", None),
                         ("disabled", TraceRecorder(enabled=False)),
                         ("enabled", TraceRecorder(enabled=True))):
        clock = _CountingClock()
        eng = _engine(model, kind, tracer=tracer, clock=clock)
        step, stepped[name] = eng.step, 0

        def counted():
            stepped[name] += 1
            return step()

        eng.step = counted
        _serve(eng)
        eng.close()
        calls[name] = clock.calls
        if name == "disabled":
            assert tracer.events() == [] and built == []
    assert calls["none"] == calls["disabled"]
    assert stepped["none"] == stepped["disabled"] == stepped["enabled"]
    names = [e["name"] for e in tracer.events() if e.get("ph") == "X"]
    assert calls["enabled"] - calls["none"] == \
        2 * names.count("prefill") + 4 * stepped["enabled"] \
        + 3 * names.count("decode_step")
    # one annotation per mirrored phase, none for a phase with no span
    made = [n for n, _ in built]
    for phase in STEP_ANNOTATIONS:
        assert made.count(phase) == names.count("decode_step")
    assert made.count("first_token.sample") == names.count("prefill")
    assert made.count("prefill.dispatch") == eng.prefill_chunks
    assert set(made) == set(STEP_ANNOTATIONS + ADMISSION_ANNOTATIONS)


@pytest.mark.parametrize("kind", ["paged", "speculative"])
def test_annotations_carry_the_spans_seq(model, kind, monkeypatch):
    """What mirrors a phase onto a profile's host plane is named as the
    span and carries the `seq` of its `decode_step` (an admission's
    phases: the request's `rid`), in the order the spans were cut."""
    built = _count_annotations(monkeypatch)
    tr = TraceRecorder(enabled=True)
    eng = _engine(model, kind, tracer=tr)
    reqs = _serve(eng)
    eng.close()
    spans = [e for e in tr.events() if e.get("ph") == "X"]
    for phase in STEP_ANNOTATIONS:
        assert [ids for n, ids in built if n == phase] == [
            {"seq": e["args"]["seq"]} for e in sorted(
                (e for e in spans if e["name"] == "decode_step"),
                key=lambda e: e["ts"])]
    for phase in ADMISSION_ANNOTATIONS:
        assert [ids for n, ids in built if n == phase] == [
            {"rid": e["args"]["rid"]} for e in sorted(
                (e for e in spans if e["name"] == phase),
                key=lambda e: e["ts"] + e["dur"])]
    assert {ids["rid"] for n, ids in built
            if n == "first_token.sample"} == {r.rid for r in reqs}


def test_retrace_counter_books_to_the_phase_that_paid(model):
    """A program traced inside the decode call lands in `decode_step`
    and nowhere else; the first-token program, built by the engine's
    first admission, lands in `first_token.sample`, and from the second
    admission on neither `first_token` phase grows; a second engine in
    the process does not register a second listener (the seconds are not
    doubled); and /metrics renders both families with no drift."""
    import jax.monitoring as mon
    import jax.numpy as jnp

    from bigdl_tpu.obs import retrace

    eng = InferenceEngine(model, n_slots=2, max_len=128)
    other = InferenceEngine(model, n_slots=1, max_len=64)  # installs again
    seen = []

    def listener(event, secs, **kw):
        if event in (retrace.TRACE, retrace.LOWER, retrace.COMPILE):
            seen.append(secs)

    mon.register_event_duration_secs_listener(listener)
    try:
        eng.submit([5, 4, 3, 2, 1], max_new_tokens=4)
        eng.step()  # admission and the first decode step
        assert eng.retrace_seconds["first_token.sample"] > 0
        assert eng.retraces["first_token.sample"] >= 1
        before = dict(eng.retrace_seconds)
        n_before = dict(eng.retraces)

        decode, x = eng._decode, jnp.arange(7)

        def retracing_decode(*a, **k):
            jax.jit(lambda v: v * 3 + 1)(x)  # one fresh program
            return decode(*a, **k)

        eng._decode = retracing_decode
        del seen[:]
        eng.step()
        eng._decode = decode
        paid = eng.retrace_seconds["decode_step"] - before["decode_step"]
        assert paid > 0
        assert paid == pytest.approx(sum(seen), rel=1e-6)  # once, not twice
        assert eng.retraces["decode_step"] == n_before["decode_step"] + 1
        for phase in ADMISSION_PARTS:
            assert eng.retrace_seconds[phase] == before[phase]
    finally:
        mon.unregister_event_duration_listener(listener)
    eng.run_until_idle()
    for prompt in ([9, 8, 7], [1, 2, 3, 4, 5, 6, 7]):  # further admissions
        eng.submit(prompt, max_new_tokens=2)
        eng.run_until_idle()
    for phase in ("first_token.sample", "first_token.arm"):
        assert eng.retraces[phase] == n_before[phase]
        assert eng.retrace_seconds[phase] == before[phase]
    assert other.retrace_seconds == dict.fromkeys(other.retrace_seconds, 0.0)

    text = Metrics(eng).render()
    assert metric_drift(text, eng) == ([], [])
    for phase in eng.retrace_seconds:
        assert _metric_value(
            text, f'bigdl_tpu_retrace_seconds_total{{phase="{phase}"}}'
        ) == pytest.approx(eng.retrace_seconds[phase], abs=1e-6)
        assert _metric_value(
            text, f'bigdl_tpu_retraces_total{{phase="{phase}"}}'
        ) == eng.retraces[phase]
    eng.close()
    other.close()


# ---------------------------------------------------------------------------
# tracing-disabled overhead guard (< 2% on a synthetic step loop)
# ---------------------------------------------------------------------------

def test_tracing_disabled_overhead_under_2pct():
    """The engine guards every instrumentation site with
    `tracer is not None and tracer.enabled`; a disabled recorder must
    cost < 2% over no recorder at all on a synthetic step loop doing
    engine-shaped work (clock stamps + the guard pattern per step and
    per token).

    Noise discipline: single-threaded workload (np.sort, no BLAS thread
    pool to fight xdist siblings over), interleaved best-of-N trials,
    and the comparison retried — scheduler jitter can only flake a
    single attempt, while a real >2% regression fails every one."""
    a = np.random.default_rng(0).standard_normal(4096).astype(np.float32)
    clock = time.time

    def loop(tracer, iters=800):
        t_start = clock()
        for _ in range(iters):
            t0 = clock()
            x = np.sort(a)  # the "decode step"
            if tracer is not None and tracer.enabled:  # pragma: no cover
                tracer.complete("decode_step", t0, clock() - t0)
            for _tok in range(4):  # per-token emit hooks
                if tracer is not None and tracer.enabled:  # pragma: no cover
                    tracer.instant("emit")
        assert x is not None
        return clock() - t_start

    disabled = TraceRecorder(enabled=False)
    loop(None), loop(disabled)  # warm caches outside the measurement
    ratios = []
    for _attempt in range(4):
        base, traced = [], []
        for _ in range(4):  # interleave to damp drift within a trial
            base.append(loop(None))
            traced.append(loop(disabled))
        ratios.append(min(traced) / min(base))
        if ratios[-1] < 1.02:
            break
    assert min(ratios) < 1.02, ratios
    assert len(disabled.events()) == 0  # nothing recorded


# ---------------------------------------------------------------------------
# metrics drift check: registry <-> exposition, both directions
# ---------------------------------------------------------------------------

def test_metrics_render_drift_engineless():
    missing, unregistered = metric_drift(Metrics().render(), None)
    assert missing == [] and unregistered == []


def test_metrics_render_drift_full_engine(model):
    """A paged + speculative engine renders EVERY registered family and
    nothing unregistered — a new metric can neither silently vanish
    from /metrics nor ship without being added to the registry."""
    eng = shared_engine(model, n_slots=2, max_len=64, paged=True,
                        page_size=8, speculative=True,
                        draft_params=model.params, draft_k=3)
    eng.submit([1, 2, 3, 4, 5], max_new_tokens=4)
    eng.run_until_idle()
    text = Metrics(eng).render()
    missing, unregistered = metric_drift(text, eng)
    assert missing == [] and unregistered == []
    # build-info labels + uptime gauge (satellite)
    import bigdl_tpu

    assert (f'bigdl_tpu_build_info{{version="{bigdl_tpu.__version__}"'
            in text)
    assert 'jax_version="' in text and 'format_version="' in text
    assert _metric_value(text, "bigdl_tpu_uptime_seconds") >= 0
    assert 0 < _metric_value(text, "bigdl_tpu_batch_occupancy") <= 1 \
        or _metric_value(text, "bigdl_tpu_batch_occupancy") == 0


# ---------------------------------------------------------------------------
# profiler window: guarded start/stop
# ---------------------------------------------------------------------------

def test_profiler_window_guards():
    calls = []
    win = ProfilerWindow(start_fn=lambda d: calls.append(("start", d)),
                         stop_fn=lambda: calls.append(("stop",)))
    with pytest.raises(ProfilerIdle):
        win.stop()
    st = win.start("/tmp/prof-x")
    assert st["active"] and st["logdir"] == "/tmp/prof-x"
    with pytest.raises(ProfilerBusy):
        win.start("/tmp/prof-y")
    out = win.stop()
    assert out["logdir"] == "/tmp/prof-x" and not win.status()["active"]
    assert calls == [("start", "/tmp/prof-x"), ("stop",)]
    # a failing stop still frees the window (no permanent ProfilerBusy)
    def bad_stop():
        raise RuntimeError("xla said no")

    win2 = ProfilerWindow(start_fn=lambda d: None, stop_fn=bad_stop)
    win2.start("/tmp/prof-z")
    with pytest.raises(RuntimeError, match="xla said no"):
        win2.stop()
    assert not win2.status()["active"]


def test_profiler_window_ties_its_clock_to_the_recorder():
    """`start(..., recorder=r)` leaves ONE `profiler.sync` instant on
    the recorder's clock (the profile-side annotation is entered only
    with the real profiler), `stop` a `profiler.stop`; a disabled
    recorder records neither, and no recorder is the old behaviour."""
    t = {"now": 500.0}

    def clock():
        t["now"] += 1.0
        return t["now"]

    tr = TraceRecorder(enabled=True, clock=clock)
    win = ProfilerWindow(start_fn=lambda d: None, stop_fn=lambda: None)
    win.start("/tmp/prof-sync", recorder=tr)
    sync = [e for e in tr.events() if e["name"] == "profiler.sync"]
    assert len(sync) == 1 and sync[0]["ph"] == "i"
    assert sync[0]["args"] == {"logdir": "/tmp/prof-sync"}
    assert sync[0]["ts"] == 501 * 10**6  # the recorder's clock, read once
    win.stop()
    names = [e["name"] for e in tr.events()]
    assert names == ["profiler.sync", "profiler.stop"]
    win.start("/tmp/prof-sync")  # no recorder: nothing more is recorded
    win.stop()
    off = TraceRecorder(enabled=False)
    win.start("/tmp/prof-sync", recorder=off)
    win.stop()
    assert len(tr.events()) == 2 and off.events() == []


def test_profiler_start_failure_leaves_idle():
    def bad_start(d):
        raise RuntimeError("no backend")

    win = ProfilerWindow(start_fn=bad_start, stop_fn=lambda: None)
    with pytest.raises(RuntimeError, match="no backend"):
        win.start("/tmp/p")
    assert not win.status()["active"]  # not wedged busy


# ---------------------------------------------------------------------------
# ApiServer debug endpoints
# ---------------------------------------------------------------------------

def test_api_debug_endpoints(model, monkeypatch, tmp_path):
    import urllib.error
    import urllib.request

    from bigdl_tpu.obs import profiler as P
    from bigdl_tpu.serving.api_server import ApiServer

    srv = ApiServer(model, host="127.0.0.1", port=0, n_slots=2,
                    max_len=128, tracing=True)
    srv.start()
    base = f"http://127.0.0.1:{srv.port}"

    def post(path, payload):
        req = urllib.request.Request(
            base + path, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=60) as r:
            return json.loads(r.read())

    try:
        body = json.dumps({"prompt": [3, 1, 4], "max_new_tokens": 4})
        req = urllib.request.Request(
            base + "/generate", data=body.encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=120) as r:
            assert len(json.load(r)["tokens"]) == 4

        with urllib.request.urlopen(base + "/debug/trace",
                                    timeout=60) as r:
            trace = json.load(r)
        names = {e["name"] for e in trace["traceEvents"]}
        assert {"queued", "prefill", "finish"} <= names
        assert validate_nesting(trace["traceEvents"]) == []

        # runtime toggle + clear
        st = post("/debug/trace", {"enabled": False, "clear": True})
        assert st["enabled"] is False and st["events"] == 0

        # guarded profiler window over HTTP (profiler fns stubbed — the
        # endpoint contract is what's under test, not XLA)
        monkeypatch.setattr(P.PROFILER, "_start_fn", lambda d: None)
        monkeypatch.setattr(P.PROFILER, "_stop_fn", lambda: None)
        logdir = str(tmp_path / "prof")
        st = post("/debug/profiler", {"action": "start",
                                      "logdir": logdir})
        assert st["active"] and st["logdir"] == logdir
        with pytest.raises(urllib.error.HTTPError) as e:
            post("/debug/profiler", {"action": "start",
                                     "logdir": logdir})
        assert e.value.code == 409  # busy, not a corrupted window
        st = post("/debug/profiler", {"action": "stop"})
        assert st["active"] is False
        with pytest.raises(urllib.error.HTTPError) as e:
            post("/debug/profiler", {"action": "stop"})
        assert e.value.code == 409
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# training supervisor records into the same trace format
# ---------------------------------------------------------------------------

def test_supervisor_shares_trace_format(tmp_path):
    import jax.numpy as jnp
    import optax

    from bigdl_tpu.train.supervisor import (
        SupervisorConfig,
        TrainSupervisor,
    )

    opt = optax.sgd(0.2)
    lora0 = {"layers": {"w": jnp.zeros((4,), jnp.float32)}}
    opt_state0 = opt.init(lora0["layers"])

    def step_fn(lora, opt_state, target):
        def loss_fn(layers):
            return jnp.sum((layers["w"] - target) ** 2)

        loss, g = jax.value_and_grad(loss_fn)(lora["layers"])
        updates, opt_state = opt.update(g, opt_state, lora["layers"])
        return ({"layers": optax.apply_updates(lora["layers"], updates)},
                opt_state, loss)

    # simulated clock: EVERY trace stamp (spans AND the EventLog-
    # mirrored instants) must live in the tracer's clock domain — a
    # wall-epoch instant next to a simulated-epoch span is unusable
    sim = {"t": 5000.0}

    def fake_clock():
        sim["t"] += 0.25
        return sim["t"]

    tr = TraceRecorder(enabled=True, clock=fake_clock)
    sup = TrainSupervisor(
        step_fn, ckpt_dir=str(tmp_path), lora=lora0,
        opt_state=opt_state0, rng=jax.random.PRNGKey(0),
        config=SupervisorConfig(save_every=100, heartbeat_every=0),
        tracer=tr,
    )
    sup.resume()
    sup.run(lambda step: (jnp.full((4,), 1.0, jnp.float32),), 5)
    events = tr.events()
    assert all(4999 < e["ts"] / 1e6 < 6000 for e in events
               if "ts" in e), "wall-clock stamp leaked into the trace"
    steps = [e for e in events
             if e.get("ph") == "X" and e["name"] == "train.step"]
    assert len(steps) == 5
    assert all(e["cat"] == "train" and not e["args"]["skipped"]
               for e in steps)
    # EventLog events (baseline/final checkpoints) mirror as instants
    kinds = {e["name"] for e in events if e.get("ph") == "i"}
    assert "checkpoint" in kinds
    assert validate_nesting(events) == []
    # a serving trace and this one are the SAME format: the summarizer
    # reduces both
    assert summarize_trace(tr.export())["spans"]["train.step"][
        "count"] == 5


# ---------------------------------------------------------------------------
# injectable clock: spans and histograms follow a simulated clock
# ---------------------------------------------------------------------------

def test_engine_injectable_clock(model):
    """The engine stamps every lifecycle timestamp through its clock
    parameter — the simulated-clock benchmark (ROADMAP) depends on the
    trace/metrics substrate following a fake clock, not wall time."""
    sim = {"t": 1000.0}

    def fake_clock():
        sim["t"] += 0.5  # every observation advances half a simulated s
        return sim["t"]

    tr = TraceRecorder(enabled=True, clock=fake_clock)
    eng = shared_engine(model, n_slots=1, max_len=128, tracer=tr,
                        clock=fake_clock)
    r = eng.submit([9, 9, 8, 2], max_new_tokens=3)
    eng.run_until_idle()
    assert r.done
    # all trace timestamps live in the simulated epoch (~1000s), far
    # from wall time
    ts = [e["ts"] / 1e6 for e in tr.events() if "ts" in e]
    assert ts and all(1000.0 <= t < 2000.0 for t in ts)
    assert 0 < eng.ttft.sum < 100  # simulated seconds, not wall epoch
    assert eng.uptime_seconds() > 0
    # dense pool utilization reads HOST state only (no device fetch that
    # could race the decode jit's cache donation) and reports an idle
    # engine as empty, not the freed slots' ghost positions
    assert eng.kv_utilization() == 0.0


def test_api_server_injectable_clock(model):
    """ISSUE 12 satellite: the ApiServer's own timestamps (`created`,
    uptime, Retry-After rate, wait deadlines) ride the same injectable
    clock it threads into the engine and tracer — the simulated-clock
    benchmark can drive the API layer, not just the engine under it
    (graftlint WCT001 guards the implementation side)."""
    import json as _json
    import urllib.request

    from bigdl_tpu.serving.api_server import ApiServer

    sim = {"t": 50_000.0}

    def fake_clock():
        sim["t"] += 0.01
        return sim["t"]

    srv = ApiServer(model, host="127.0.0.1", port=0, n_slots=2,
                    max_len=128, tracing=True, clock=fake_clock)
    # one clock, threaded everywhere
    assert srv.engine._clock is fake_clock
    assert srv.tracer._clock is fake_clock
    srv.start()
    try:
        body = _json.dumps({"prompt": [9, 9, 8, 2],
                            "max_tokens": 3}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/v1/completions", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            out = _json.loads(r.read())
        # `created` is stamped in the simulated epoch, not wall time
        assert 50_000 <= out["created"] < 60_000
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=30) as r:
            text = r.read().decode()
        up = _metric_value(text, "bigdl_tpu_uptime_seconds")
        assert 0 < up < 10_000  # simulated age, not the wall epoch
    finally:
        srv.shutdown()
