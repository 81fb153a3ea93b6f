"""bench/reduce/xplane.py: busy/idle union, kernel time per program, self
times and gap attribution on hand-made events; then the same on a small
trace recorded on the chip (bench/fixtures)."""

import gzip
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.records import Req  # noqa: E402
from bench.reduce import xplane  # noqa: E402
from bench.reduce.xplane import Event, Loaded, Reduced  # noqa: E402

DEV = "/device:TPU:0"


def _loaded():
    """Two executions of engine_decode (0.10..0.14, 0.30..0.34) and one
    prefill (0.18..0.26); the trace's clock is the benchmark's minus 100."""
    ops = [
        Event("while.7", 0.10, 0.04),  # contains the three below
        Event("qmatmul.56", 0.10, 0.01),
        Event("fusion.11", 0.11, 0.01),
        Event("paged_decode_attention.13", 0.12, 0.02),
        Event("fusion.12", 0.18, 0.08),
        Event("qmatmul.57", 0.30, 0.03),
        Event("fusion.11", 0.33, 0.01),
    ]
    mods = [Event("jit_engine_decode(123)", 0.10, 0.04),
            Event("jit_engine_paged_prefill(9)", 0.18, 0.08),
            Event("jit_engine_decode(123)", 0.30, 0.04)]
    return Loaded({DEV: ops}, {DEV: mods}, sync=0.05, lines={})


def _reduced():
    # the annotation was made at benchmark second 100.05; traced 100.0..100.4
    return Reduced(_loaded(), t_sync=100.05, begin=100.0, end=100.4)


def test_busy_is_the_union_and_idle_the_rest():
    r = _reduced()
    assert r.window_s == pytest.approx(0.4)
    assert r.busy_s == pytest.approx(0.04 + 0.08 + 0.04)


def test_kernel_time_inside_a_program():
    r = _reduced()
    assert r.program_seconds("engine_decode") == pytest.approx([0.04, 0.04])
    n, secs = r.kernel_in_program("qmatmul", "engine_decode")
    assert n == 2 and secs == pytest.approx(0.04)
    n, secs = r.kernel_in_program("paged_decode_attention", "engine_decode")
    assert n == 2 and secs == pytest.approx(0.02)
    assert r.kernel_in_program("qmatmul", "engine_paged_prefill") == (1, 0.0)


def test_own_name_is_what_stands_before_the_equals_sign():
    hlo = ("%fusion.9 = bf16[32,4096]{1,0:T(8,128)(2,1)} fusion(bf16[32,4096] "
           "%qmatmul.55, s32[] %get-tuple-element.7), kind=kLoop")
    assert xplane.own_name(hlo) == "fusion.9"  # not its operand's
    assert xplane.own_name("jit_engine_decode(123)") == "jit_engine_decode(123)"


def test_top_ops_are_self_times_under_stable_names():
    top = dict(_reduced().top_ops(10))
    assert top["fusion"] == pytest.approx(0.01 + 0.08 + 0.01)
    assert top["qmatmul"] == pytest.approx(0.04)
    assert top["paged_decode_attention"] == pytest.approx(0.02)
    assert top["while"] == pytest.approx(0.0)  # all of it is its children's
    assert _reduced().top_ops(1)[0][0] == "fusion"


def test_a_window_clips_what_lies_outside():
    r = Reduced(_loaded(), t_sync=100.05, begin=100.12, end=100.32)
    assert r.busy_s == pytest.approx(0.02 + 0.08 + 0.02)
    assert r.program_seconds("engine_decode") == pytest.approx([0.04])
    # the second execution does not end inside the window: not a whole step
    assert r.kernel_in_program("qmatmul", "engine_decode")[0] == 0


def test_gaps_are_labelled_by_what_the_host_was_doing():
    def span(name, ts, dur):
        return {"name": name, "ph": "X", "ts": int(ts * 1e6),
                "dur": int(dur * 1e6), "args": {}}

    spans = [span("decode_step", 100.095, 0.05),
             span("prefill", 100.15, 0.14),  # covers gap 0.14..0.18 and
             # most of 0.26..0.30
             span("decode_step", 100.295, 0.05)]
    reqs = [Req(None, 100.0, 8, 4, stamps=[100.2, 100.36], done=True)]
    label_at = xplane.make_labeller(spans, reqs)
    gaps = dict(_reduced().idle_gaps(label_at, 10))
    pre = "prefill span (admission: dispatch, first-token sampling)"
    assert gaps[pre] == pytest.approx(0.04 + 0.04)
    assert gaps["between decode_step spans (emit, admit, scheduling)"] == \
        pytest.approx(0.10)  # 0.00..0.10: before the first step, in flight
    assert gaps["no request in flight"] == pytest.approx(0.06)  # 0.34..0.40
    assert sum(gaps.values()) == pytest.approx(0.4 - 0.16)


def test_without_the_annotation_the_first_event_marks_the_start():
    ld = _loaded()
    ld.sync = None
    r = Reduced(ld, t_sync=0.0, begin=100.0, end=100.4)
    assert r.busy_s == pytest.approx(0.16)


FIXTURE = os.path.join(ROOT, "bench", "fixtures", "v5e_chat_steady.json.gz")


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(FIXTURE, "rt", encoding="utf-8") as f:
        raw = json.load(f)
    return Loaded(
        {p: [Event(**e) for e in v] for p, v in raw["ops"].items()},
        {p: [Event(**e) for e in v] for p, v in raw["modules"].items()},
        raw["sync"], raw["lines"]), raw["expect"]


def test_recorded_trace_reduces_to_what_plain_sums_give(recorded):
    """Three decode steps of mistral-7b.chat-steady on a TPU v5 lite. The
    expectations were computed when the fixture was cut, by plain sums over
    its events (no code of the reduction), and agree with the dump of the
    whole trace: 112 ms a step, of which 91 ms paged attention (32 layers x
    2.84 ms) and 14.9 ms qmatmul."""
    ld, want = recorded
    # trace clock = benchmark clock here: t_sync is the annotation's own time
    r = Reduced(ld, t_sync=ld.sync, begin=want["begin"],
                end=want["begin"] + want["window_s"])
    assert r.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0.9 * r.window_s < r.busy_s <= r.window_s
    assert r.program_seconds("engine_decode") == pytest.approx(
        [0.1122] * 3, abs=2e-4)
    n, secs = r.kernel_in_program("qmatmul", "engine_decode")
    assert n == want["decode_steps"] == 3
    assert secs == pytest.approx(want["qmatmul_s"], rel=1e-9)
    assert secs / n == pytest.approx(0.0149, abs=2e-4)
    n, secs = r.kernel_in_program("paged_decode_attention", "engine_decode")
    assert secs == pytest.approx(want["paged_s"], rel=1e-9)
    assert secs / n == pytest.approx(0.0910, abs=2e-4)
    top = r.top_ops(3)
    assert [name for name, _ in top][:2] == ["paged_decode_attention",
                                             "qmatmul"]
    assert top[0][1] == pytest.approx(want["paged_s"], rel=1e-6)
    gaps = r.idle_gaps(lambda t: "x", 10)
    assert gaps[0][1] == pytest.approx(r.window_s - r.busy_s, rel=1e-6)
