"""The readers of the engine's phase spans (bench/metrics/engine.admit.*,
engine.step.*) on hand-built runs: spans with known children, and the
recorded chip trace of bench/fixtures with `prefill` and `decode_step` spans
laid over its three steps. A program without the spans reads None."""

import gzip
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import cells  # noqa: E402
from bench.records import Run  # noqa: E402
from bench.reduce.xplane import Event, Loaded, Reduced  # noqa: E402

CELL = "mistral-7b.chat-steady"
READERS = ("engine.admit.dispatch_ms_p50", "engine.admit.sample_ms_p50",
           "engine.admit.retrace_ms_p50", "engine.admit.idle_ms",
           "engine.step.dispatch_ms_p50", "engine.step.idle_ms")


@pytest.fixture(scope="module")
def cell():
    return cells.resolve(CELL, ROOT)


def span(name, ts, dur, tid=0, **args):
    return {"name": name, "ph": "X", "tid": tid, "ts": int(round(ts * 1e6)),
            "dur": int(round(dur * 1e6)), "args": args}


def admission(rid, t, dispatch, sample, arm, retrace=(0.0, 0.0, 0.0)):
    """A `prefill` span from `t` and its three children, as the engine
    records them: parent first, children abutting."""
    return [
        span("prefill", t, dispatch + sample + arm, rid, rid=rid),
        span("prefill.dispatch", t, dispatch, rid, rid=rid,
             retrace_s=retrace[0]),
        span("first_token.sample", t + dispatch, sample, rid, rid=rid,
             retrace_s=retrace[1]),
        span("first_token.arm", t + dispatch + sample, arm, rid, rid=rid,
             retrace_s=retrace[2]),
    ]


def step(t, dispatch, fetch):
    return [span("decode_step", t, dispatch + fetch, occupancy=1, slots=2),
            span("decode.dispatch", t, dispatch, retrace_s=0.0),
            span("decode.fetch", t + dispatch, fetch, retrace_s=0.0)]


def make_run(cell, spans, device=None, t0=0.0, t1=100.0):
    return Run(cell=cell, hf={}, peak={}, t0=t0, t1=t1, requests=[],
               spans=spans, device=device)


def read(cell, name, run):
    return cell.reader(name).read(run)


@pytest.fixture(scope="module")
def phases(cell):
    spans = (admission(1, 10.0, 0.010, 0.200, 0.030, (0.001, 0.180, 0.020))
             + admission(2, 20.0, 0.020, 0.240, 0.040, (0.0, 0.210, 0.030))
             + admission(3, 30.0, 0.030, 0.220, 0.050, (0.002, 0.190, 0.040))
             + admission(4, 200.0, 9.0, 9.0, 9.0, (9.0, 9.0, 9.0))  # outside
             + step(11.0, 0.003, 0.110) + step(12.0, 0.002, 0.112)
             + step(13.0, 0.004, 0.109))
    return make_run(cell, spans)


@pytest.mark.parametrize("name, want", [
    ("engine.admit.dispatch_ms_p50", 20.0),
    ("engine.admit.sample_ms_p50", 220.0),
    # per request 201, 240 and 232 ms: the three children summed first
    ("engine.admit.retrace_ms_p50", 232.0),
    ("engine.step.dispatch_ms_p50", 3.0),
])
def test_span_readers_take_the_median_inside_the_window(cell, phases, name,
                                                        want):
    assert read(cell, name, phases) == pytest.approx(want, abs=1e-6)


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_spans_reads_none(cell, name):
    """The parent commit records `prefill` and `decode_step` only, and a
    run without `--trace 1` nothing: no child span, no device, no number."""
    old = [span("prefill", 10.0, 0.25, 1, rid=1, prompt_tokens=64),
           span("decode_step", 11.0, 0.1, occupancy=1, slots=2)]
    assert read(cell, name, make_run(cell, old)) is None
    assert read(cell, name, make_run(cell, [])) is None


def test_retrace_reader_skips_spans_without_the_counter(cell):
    spans = admission(1, 10.0, 0.01, 0.2, 0.03, (0.001, 0.18, 0.02))
    for e in spans:
        e["args"].pop("retrace_s", None)
    assert read(cell, "engine.admit.retrace_ms_p50",
                make_run(cell, spans)) is None


FIXTURE = os.path.join(ROOT, "bench", "fixtures", "v5e_chat_steady.json.gz")


@pytest.fixture(scope="module")
def recorded():
    """The reduced fixture (trace clock = benchmark clock) and its idle
    gaps by plain sums over its events, no code of the reduction."""
    with gzip.open(FIXTURE, "rt", encoding="utf-8") as f:
        raw = json.load(f)
    ld = Loaded(
        {p: [Event(**e) for e in v] for p, v in raw["ops"].items()},
        {p: [Event(**e) for e in v] for p, v in raw["modules"].items()},
        raw["sync"], raw["lines"])
    begin = raw["expect"]["begin"]
    end = begin + raw["expect"]["window_s"]
    gaps, t = [], begin
    for e in sorted(raw["ops"]["/device:TPU:0"], key=lambda e: e["start"]):
        if e["start"] > t:
            gaps.append((t, e["start"]))
        t = max(t, e["start"] + e["dur"])
    gaps.append((t, end))
    return Reduced(ld, t_sync=ld.sync, begin=begin, end=end), gaps


def idle_inside(gaps, spans):
    return sum(b - a for a, b in gaps
               if any(lo <= (a + b) / 2 < hi for lo, hi in spans))


def test_device_idle_per_admission_and_per_step_on_the_recorded_trace(
        cell, recorded):
    """Three decode steps run at 0.0631, 0.1805 and 0.2978 s for 0.1122 s
    each; the 5.2 ms between the first two (three gaps: 2.5, 0.7, 2.0 ms)
    is given to an admission, and each step gets a `decode_step` span from
    just before its execution to just after."""
    dev, gaps = recorded
    pre = [(0.1753, 0.1800)]
    dec = [(0.0625, 0.1753), (0.1800, 0.2928), (0.2972, 0.4101)]
    spans = admission(7, pre[0][0], 0.001, 0.003, 0.0007)
    for a, b in dec:
        spans += step(a, 0.0008, b - a - 0.0008)
    run = make_run(cell, spans, device=dev, t0=0.0, t1=1.0)

    got = read(cell, "engine.admit.idle_ms", run)
    assert got == pytest.approx(idle_inside(gaps, pre) * 1e3, rel=1e-6)
    assert got == pytest.approx(5.213, abs=0.01)  # 2.511 + 0.711 + 1.991
    got = read(cell, "engine.step.idle_ms", run)
    assert got == pytest.approx(idle_inside(gaps, dec) * 1e3 / 3, rel=1e-6)
    assert 0 < got < 1.0  # only the slivers between operations

    # a span that starts before the traced window covers idle time in it
    # but is not one of its admissions: nothing to divide by
    early = admission(7, 0.0500, 0.001, 0.125, 0.005)
    assert read(cell, "engine.admit.idle_ms",
                make_run(cell, early, device=dev, t0=0.0, t1=1.0)) is None
