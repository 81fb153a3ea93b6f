"""bench/reduce/steps.py and the six readers on it (engine.step.device_gap_*,
engine.step.gap.*, engine.step.args_*): a device gap between two decode
steps split by overlap with the host's phases, on hand-built runs (spans with
known seams over executions at known times), then on a trace recorded on the
chip (bench/fixtures/v5e_step_gaps.json.gz)."""

import gzip
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import cells  # noqa: E402
from bench.records import Req, Run  # noqa: E402
from bench.reduce import steps  # noqa: E402
from bench.reduce.xplane import Event, Loaded, Reduced  # noqa: E402

CELL = "mistral-7b.chat-steady"
DEV = "/device:TPU:0"
GAP = "engine.step.device_gap_ms_p50"
SHARES = {s: f"engine.step.gap.{s}_ms_p50" for s in steps.SHARES}
ARGS = "engine.step.args_ms_p50"
OFFSET = 100.0  # benchmark clock = trace clock + 100 in the hand-built runs


@pytest.fixture(scope="module")
def cell():
    return cells.resolve(CELL, ROOT)


def span(name, ts, dur, **args):
    return {"name": name, "ph": "X", "tid": 0, "ts": int(round(ts * 1e6)),
            "dur": int(round(dur * 1e6)), "args": args}


def host_step(seq, t, args=0.0004, call=0.0030, wait=0.0101, read=0.0005,
              emit=0.0010, before=0.0010):
    """The spans of one `engine.step` whose `decode_step` starts at trace
    second `t` (recorded on the benchmark's clock), as the engine cuts it;
    and the annotations that mirror five of them, on the trace's clock."""
    t0 = t + OFFSET
    disp, fetch = args + call, wait + read
    spans = [
        span("engine.step", t0 - before, before + disp + fetch + emit,
             seq=seq, admitted=0, occupancy=4),
        span("step.reap", t0 - before, before / 4),
        span("step.admit", t0 - before * 3 / 4, before / 4),
        span("step.pages", t0 - before / 2, before / 2, bt_uploaded=False),
        span("decode_step", t0, disp + fetch, seq=seq, occupancy=4, slots=8),
        span("decode.dispatch", t0, disp, retrace_s=0.0),
        span("decode.args", t0, args),
        span("decode.call", t0 + args, call),
        span("decode.fetch", t0 + disp, fetch, retrace_s=0.0),
        span("decode.wait", t0 + disp, wait),
        span("decode.read", t0 + disp + wait, read, arrays=1),
        span("step.emit", t0 + disp + fetch, emit),
    ]
    ann = [("decode.args", seq, t, args),
           ("decode.read", seq, t + disp + wait, read),
           ("step.emit", seq, t + disp + fetch, emit)]
    return spans, ann


def make_run(cell, spans, modules, annotations=None, ops=None, requests=(),
             begin=0.0, end=1.0):
    """A traced run: the device ran `modules` (name, start, dur); its
    operations fill each execution unless `ops` says otherwise."""
    mods = [Event(*m) for m in modules]
    ops = [Event(f"fusion.{i}", m.start, m.dur) for i, m in enumerate(mods)] \
        if ops is None else [Event(*o) for o in ops]
    ld = Loaded({DEV: ops}, {DEV: mods}, sync=0.0, lines={})
    dev = Reduced(ld, t_sync=OFFSET, begin=begin + OFFSET, end=end + OFFSET)
    extra = {} if annotations is None else {
        "host_annotations": list(annotations)}
    return Run(cell=cell, hf={}, peak={}, t0=0.0, t1=1e9,
               requests=list(requests), spans=spans, device=dev, extra=extra)


DECODE = "jit_engine_decode(1)"
# a step every 16 ms: the device starts 3.0 ms after `decode_step` does (the
# call returns at +3.4) and runs 10 ms; the fetch ends at +14.0, the emit
# loop at +15.0, and the next `decode_step` starts at +16: a gap of 6.0 ms,
# 13.0 .. 19.0 = drain 1.0 + emit 1.0 + schedule 1.0 + launch 3.0, whose
# middle lies ON the seam between "between steps" and "inside the next"
PERIOD, LAUNCH, RUN = 0.016, 0.003, 0.010
WANT = {"drain": 1.0, "emit": 1.0, "schedule": 1.0, "launch": 3.0}


def steady(n=6, t=0.1, helpers=False):
    spans, ann, mods = [], [], []
    for i in range(n):
        s, a = host_step(i + 1, t + i * PERIOD)
        spans += s
        ann += a
        mods.append((DECODE, t + i * PERIOD + LAUNCH, RUN))
        if helpers:  # the key split, while the host is in `step.pages`
            mods.append(("jit__threefry_split(2)",
                         t + i * PERIOD + 0.0152, 4e-6))
    return spans, ann, mods


def read(cell, name, run):
    return cell.reader(name).read(run)


@pytest.mark.parametrize("clock", ["annotations", "spans"])
def test_a_gap_is_split_by_overlap_and_the_shares_sum_to_it(cell, clock):
    spans, ann, mods = steady()
    run = make_run(cell, spans, mods, ann if clock == "annotations" else ())
    acc = steps.account(run)
    assert acc.clock == clock and len(acc.steps) == 6 and len(acc.gaps) == 5
    for g in acc.gaps:
        assert g.seconds == pytest.approx(0.006, abs=1e-9)
        assert sum(g.shares.values()) == pytest.approx(g.seconds, abs=1e-12)
        for s, ms in WANT.items():
            assert g.shares[s] * 1e3 == pytest.approx(ms, abs=2e-3)
    assert read(cell, GAP, run) == pytest.approx(6.0, abs=1e-6)
    for s, ms in WANT.items():
        assert read(cell, SHARES[s], run) == pytest.approx(ms, abs=2e-3)
    assert read(cell, ARGS, run) == pytest.approx(0.4, abs=1e-6)


@pytest.mark.parametrize("shift_us", [-300, -50, 50, 300])
def test_the_middle_may_fall_either_side_of_the_seam(cell, shift_us):
    """The gap's middle lies on the seam between `step.pages` and the next
    `decode_step`: the device finishing a little later or earlier moves the
    middle across it, and the label of the whole gap with it, which is what
    `engine.step.idle_ms` reads: all of the gap or none. By overlap only
    the drain moves, by the shift itself."""
    spans, ann, mods = steady()
    d = shift_us * 1e-6
    run = make_run(cell, spans, [(n, t, dur + d) for n, t, dur in mods], ann)
    assert read(cell, GAP, run) == pytest.approx(6.0 - d * 1e3, abs=1e-6)
    assert read(cell, SHARES["drain"], run) == pytest.approx(
        WANT["drain"] - d * 1e3, abs=2e-3)
    for s in ("emit", "schedule", "launch"):
        assert read(cell, SHARES[s], run) == pytest.approx(WANT[s], abs=2e-3)
    by_middle = read(cell, "engine.step.idle_ms", run)
    if shift_us > 0:  # five gaps over six spans
        assert by_middle == pytest.approx(5 * (6.0 - d * 1e3) / 6, abs=1e-3)
    else:
        assert by_middle == pytest.approx(0.0, abs=1e-9)


def test_pairing_by_seq_and_by_containment_agree(cell):
    spans, ann, mods = steady(helpers=True)
    by_seq = steps.build(make_run(cell, spans, mods), ann)
    by_span = steps.build(make_run(cell, spans, mods), ())
    assert (by_seq.clock, by_span.clock) == ("annotations", "spans")
    assert [(s.seq, s.exec_start) for s in by_seq.steps] == \
        [(s.seq, s.exec_start) for s in by_span.steps]
    for a, b in zip(by_seq.gaps, by_span.gaps):
        assert a.shares == pytest.approx(b.shares, abs=2e-6)
    # the tie is checked at every step: here the clocks agree to the
    # microsecond the spans are rounded to
    med, lo, hi = by_seq.tie_us()
    assert abs(med) <= 1 and abs(lo) <= 1 and abs(hi) <= 1
    assert by_span.tie_us() is None


def test_a_tie_that_is_off_moves_the_spans_seams_not_the_annotations(cell):
    """The recorder's clock read 0.4 ms late against the profile's: through
    the one tie the spans' seams land 0.4 ms late and launch loses what
    drain gains; the annotations' do not move, and `tie_us` shows it."""
    spans, ann, mods = steady()
    late = [dict(e, ts=e["ts"] + 400) for e in spans]
    on_spans = steps.build(make_run(cell, late, mods), ())
    on_ann = steps.build(make_run(cell, late, mods), ann)
    assert on_spans.median_ms("launch") == pytest.approx(2.6, abs=2e-3)
    assert on_spans.median_ms("schedule") == pytest.approx(1.0, abs=2e-3)
    assert on_spans.median_ms("drain") == pytest.approx(1.4, abs=2e-3)
    for s, ms in WANT.items():
        assert on_ann.median_ms(s) == pytest.approx(ms, abs=2e-3)
    assert on_ann.tie_us()[0] == pytest.approx(-400, abs=1)


@pytest.mark.parametrize("early_ms", [0.5, 1.2, 2.5])
def test_a_device_plane_that_runs_early_is_moved_back_by_causality(cell,
                                                                   early_ms):
    """The profile put the device's plane `early_ms` before the host's, as
    it did on the chip: every execution seems to start before the host has
    entered the call that enqueues it (`decode.args` ends at +0.4 ms, the
    device really starts at +3.0). The gap, emit and schedule read the
    same; the plane is moved back until no execution starts before its
    call, which is all causality can say: launch reads at least its true
    value less the 2.6 ms the call's own latency hides, never a start
    before the call."""
    spans, ann, mods = steady()
    d = early_ms * 1e-3
    acc = steps.account(make_run(
        cell, spans, [(n, t - d, dur) for n, t, dur in mods], ann))
    assert acc.median_ms("gap") == pytest.approx(6.0, abs=1e-6)
    assert acc.median_ms("emit") == pytest.approx(WANT["emit"], abs=2e-3)
    assert acc.median_ms("schedule") == pytest.approx(WANT["schedule"],
                                                      abs=2e-3)
    lo, hi = acc.shift_range
    # what causality allows: from "starts as the call is entered" (2.6 ms
    # before the truth) to "ends as the fetch returns" (0.5 ms after it)
    assert (lo * 1e3, hi * 1e3) == (pytest.approx(early_ms - 2.6, abs=2e-3),
                                    pytest.approx(early_ms + 0.5, abs=2e-3))
    moved = max(early_ms - 2.6, 0.0)
    assert acc.shift_s * 1e3 == pytest.approx(moved, abs=2e-3)
    assert acc.median_ms("launch") == pytest.approx(
        WANT["launch"] - early_ms + moved, abs=3e-3)
    assert acc.median_ms("launch") + acc.median_ms("drain") == \
        pytest.approx(WANT["launch"] + WANT["drain"], abs=3e-3)
    for g in acc.gaps:
        assert sum(g.shares.values()) == pytest.approx(g.seconds, abs=1e-12)


def test_a_device_plane_that_runs_late_is_moved_forward(cell):
    """2 ms late, every execution seems to end after the fetch that waited
    for it had returned (by 1.5 ms): the plane is moved back by that, so
    the drain reads what is left, `decode.read` alone."""
    spans, ann, mods = steady()
    acc = steps.account(make_run(
        cell, spans, [(n, t + 0.002, dur) for n, t, dur in mods], ann))
    assert acc.shift_s * 1e3 == pytest.approx(-1.5, abs=2e-3)
    assert acc.median_ms("drain") == pytest.approx(0.5, abs=3e-3)
    assert acc.median_ms("launch") == pytest.approx(3.5, abs=3e-3)
    assert acc.median_ms("gap") == pytest.approx(6.0, abs=1e-6)


def test_the_steps_own_helper_programs_stay_in_the_gap(cell):
    """The key split runs on the device between two steps for microseconds:
    the gap is the idle time around it, still one gap, still steady."""
    spans, ann, mods = steady(helpers=True)
    acc = steps.account(make_run(cell, spans, mods, ann))
    assert len(acc.gaps) == 5
    for g in acc.gaps:
        assert g.seconds == pytest.approx(0.006 - 4e-6, abs=1e-9)
        assert sum(g.shares.values()) == pytest.approx(g.seconds, abs=1e-12)
        # 15.2 ms after the step's start the host is in the next
        # `engine.step`, before its `decode_step`: schedule pays
        assert g.shares["schedule"] * 1e3 == pytest.approx(1.0 - 0.004,
                                                           abs=2e-3)


def test_a_gap_with_an_admission_in_it_is_left_out_and_counted(cell):
    # between steps 3 and 4 a prefill and the first token's program ran
    # (the fourth step and all after it come 30 ms later)
    spans, ann, mods = [], [], []
    for i in range(6):
        t = 0.1 + i * PERIOD + (0.030 if i >= 3 else 0.0)
        s, a = host_step(i + 1, t)
        spans, ann = spans + s, ann + a
        mods.append((DECODE, t + LAUNCH, RUN))
    t_gap = 0.1 + 2 * PERIOD + LAUNCH + RUN  # step 3's execution ends
    mods += [("jit_engine_paged_prefill(7)", t_gap + 0.004, 0.020),
             ("jit_engine_first_token(8)", t_gap + 0.026, 0.00004)]
    acc = steps.account(make_run(cell, spans, mods, ann))
    assert len(acc.steps) == 6 and len(acc.gaps) == 4
    assert acc.median_ms("gap") == pytest.approx(6.0, abs=1e-6)
    idle_there = 0.036 - 0.020 - 0.00004
    assert acc.left_out[steps.ADMISSION] == pytest.approx(idle_there,
                                                          abs=1e-9)
    rows = acc.closing()
    assert sum(rows.values()) == pytest.approx(acc.idle_s, abs=1e-9)
    assert rows[steps.EDGES] == pytest.approx(
        0.1 + LAUNCH + 1.0 - (0.1 + 5 * PERIOD + 0.030 + LAUNCH + RUN),
        abs=1e-9)


def test_idle_inside_a_program_and_without_a_request_is_counted(cell):
    spans, ann, mods = steady()
    # every execution's operations leave 0.2 ms of it idle
    ops = [(f"fusion.{i}", t, dur - 0.0002)
           for i, (_, t, dur) in enumerate(mods)]
    # the one request ended during step 4: no request was in flight over
    # the whole of the gap after it
    t_end = OFFSET + 0.1 + 3 * PERIOD + LAUNCH + RUN + 0.001
    reqs = [Req(None, OFFSET + 0.05, 8, 4, stamps=[OFFSET + 0.12, t_end],
                done=True)]
    acc = steps.account(make_run(cell, spans, mods, ann, ops=ops,
                                 requests=reqs))
    assert len(acc.gaps) == 3
    assert acc.inside_s == pytest.approx(6 * 0.0002, abs=1e-9)
    assert acc.left_out[steps.NO_REQUEST] == pytest.approx(2 * 0.006,
                                                           abs=1e-9)
    assert sum(acc.closing().values()) == pytest.approx(acc.idle_s, abs=1e-9)
    text = "\n".join(acc.lines())
    assert "3 steady gaps" in text and "seams from the annotations" in text


def test_a_step_cut_by_the_profiles_edge_is_not_paired(cell):
    """The profile opened inside step 1 (its `decode.args` is not on the
    host plane): with annotations on the trace the step is left out, its
    gap goes under its own heading and the medians are the others'."""
    spans, ann, mods = steady()
    ann = [a for a in ann if not (a[0] == "decode.args" and a[1] == 1)]
    acc = steps.account(make_run(cell, spans, mods, ann))
    assert [s.seq for s in acc.steps] == [2, 3, 4, 5, 6]
    assert len(acc.gaps) == 4
    assert acc.left_out[steps.NOT_PAIRED] == pytest.approx(0.006, abs=1e-9)
    assert sum(acc.closing().values()) == pytest.approx(acc.idle_s, abs=1e-9)


@pytest.mark.parametrize("name", [GAP, ARGS] + sorted(SHARES.values()))
def test_a_program_without_the_spans_reads_none(cell, name):
    """The parent commit records `decode_step` and its two children only; a
    run without `--trace 1` has no device trace. No number, and no error."""
    _, _, mods = steady()
    old = []
    for i in range(6):
        t = OFFSET + 0.1 + i * PERIOD
        old += [span("decode_step", t, 0.0135, occupancy=4, slots=8),
                span("decode.dispatch", t, 0.002, retrace_s=0.0),
                span("decode.fetch", t + 0.002, 0.0115, retrace_s=0.0)]
    assert read(cell, name, make_run(cell, old, mods)) is None
    spans, ann, _ = steady()
    no_device = Run(cell=cell, hf={}, peak={}, t0=0.0, t1=1e9, requests=[],
                    spans=spans, device=None)
    want = 0.4 if name == ARGS else None  # a span reader needs no device
    got = read(cell, name, no_device)
    assert got == (pytest.approx(want) if want else None)


def test_the_new_metrics_are_listed_for_the_five_engine_cells():
    bench = cells.load_benchmark(ROOT)
    rows = {m["name"]: m for m in bench["per_layer"]}
    closed = ["qwen2-7b.chat-closed", "mixtral-8x7b.chat-closed",
              "brumby-14b.reason-closed", "glm-4.7-flash.longctx-closed"]
    for name in [GAP, ARGS] + sorted(SHARES.values()):
        for key, moves, where in ((name, "itl_ms_p95", [CELL]),
                                  (name + "--closed", "output_tokens_per_s",
                                   closed)):
            m = rows[key]
            assert (m["moves"], m["workloads"]) == (moves, where)
            assert (m["unit"], m["better"], m["layer"]) == (
                "ms", "lower", "engine / scheduler")
            assert m["source"] == ("program_span" if name == ARGS
                                   else "device_trace")


# ---- the recorded trace ---------------------------------------------------

FIXTURE = os.path.join(ROOT, "bench", "fixtures", "v5e_step_gaps.json.gz")


@pytest.fixture(scope="module")
def recorded(cell):
    """A few dozen decode steps of mistral-7b.chat-steady on a TPU v5 lite,
    cut by `bench/tools/keep_steps.py`: the engine track's spans, the
    modules line, the mirrored annotations. The operations line is not kept
    (1500 events a step): its union over the cut is in `expect`."""
    with gzip.open(FIXTURE, "rt", encoding="utf-8") as f:
        raw = json.load(f)
    ld = Loaded({}, {p: [Event(**e) for e in v]
                     for p, v in raw["modules"].items()}, raw["sync"], {})
    dev = Reduced(ld, t_sync=raw["t_sync"], begin=raw["begin"],
                  end=raw["end"])
    dev.busy_s = raw["expect"]["busy_s"]

    def run(annotations):
        return Run(cell=cell, hf={}, peak={}, t0=0.0, t1=1e12, requests=[],
                   spans=raw["spans"], device=dev,
                   extra={"host_annotations": annotations})

    return raw, run


def test_recorded_trace_reads_what_it_read_when_it_was_cut(cell, recorded):
    raw, run = recorded
    want = raw["expect"]
    acc = steps.account(run([tuple(a) for a in raw["annotations"]]))
    assert acc.clock == "annotations"
    assert len(acc.steps) == want["steps"] >= 24
    assert len(acc.gaps) == want["steady_gaps"] >= 12
    # the cut holds an admission: its gap is left out, and counted
    assert len(acc.gaps) < len(acc.steps) - 1
    assert acc.left_out[steps.ADMISSION] > 0.001
    assert acc.median_ms("gap") == pytest.approx(want["device_gap_ms_p50"],
                                                 rel=1e-9)
    # the chip's profile had the device plane over a millisecond early:
    # executions started before `decode.call` was entered. Moved back.
    assert acc.shift_s * 1e3 == pytest.approx(want["shift_ms"], rel=1e-9)
    assert 1.0 < acc.shift_s * 1e3 < acc.shift_range[1] * 1e3 < 3.0
    assert all(s.exec_start + acc.shift_s >= s.t_call - 1e-12
               and s.exec_end + acc.shift_s <= s.t_waited + 1e-12
               for s in acc.steps)
    for s in steps.SHARES:
        assert acc.median_ms(s) == pytest.approx(want[s + "_ms_p50"],
                                                 rel=1e-9, abs=1e-12)
    # the gaps by a plain walk over the modules line, no code of steps.py:
    # idle time between consecutive executions of the decode program that
    # have no other engine program between them
    mods = sorted(raw["modules"][DEV], key=lambda e: e["start"])
    gaps, prev, idle, other, t = [], None, 0.0, False, None
    for e in mods:
        if t is not None and e["start"] > t:
            idle += e["start"] - t
        if "engine_decode" in e["name"]:
            if prev is not None and not other:
                gaps.append(idle)
            prev, idle, other = e, 0.0, False
        elif "engine_" in e["name"]:
            other = True
        t = e["start"] + e["dur"] if t is None else max(
            t, e["start"] + e["dur"])
    steady_gaps = sorted(g.seconds for g in acc.gaps)
    assert len(gaps) >= len(steady_gaps)
    for g in steady_gaps:
        assert any(abs(g - h) < 1e-9 for h in gaps)
    for g in acc.gaps:
        assert sum(g.shares.values()) == pytest.approx(g.seconds, abs=1e-12)
    assert sum(acc.closing().values()) == pytest.approx(acc.idle_s, rel=1e-9)
    assert 2.0 < acc.median_ms("gap") < 8.0  # ms, a v5e's chat-steady


def test_recorded_trace_pairs_the_same_by_seq_and_by_containment(recorded):
    raw, run = recorded
    by_seq = steps.build(run([tuple(a) for a in raw["annotations"]]),
                         [tuple(a) for a in raw["annotations"]])
    by_span = steps.build(run([]), ())
    pairs = {s.seq: s.exec_start for s in by_seq.steps}
    assert pairs and all(pairs[s.seq] == s.exec_start
                         for s in by_span.steps if s.seq in pairs)
    # one clock against two tied once: the shares agree to the tie's error
    tie = max(abs(x) for x in by_seq.tie_us())
    for s in steps.SHARES + ("gap",):
        assert by_span.median_ms(s) == pytest.approx(
            by_seq.median_ms(s), abs=2 * tie / 1e3 + 0.005)
