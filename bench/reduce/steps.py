"""The device's gaps between decode steps, split by what the host was doing.

`xplane.Reduced.idle_gaps` gives a whole gap to the label of its middle. One
gap between two `engine_decode` executions runs over four stretches of the
engine thread, and which one holds the middle is decided by a few hundred
microseconds of jitter. Here each gap is split BY OVERLAP with them:

    execution N ends ......................................... g0
      drain     g0 .. the end of `decode_step` N: what is left of
                `decode.wait` once the device is done, and `decode.read`
      emit      .. the end of `step.emit` N (= of `engine.step` N)
      schedule  .. the start of `decode_step` N+1: the caller's loop,
                `step.reap`, `step.admit`, `step.pages`
      launch    .. g1: `decode.args` and the part of `decode.call` before
                the device starts
    execution N+1 starts ..................................... g1

The three seams are read from the annotations that mirror the phases onto
the profile's host plane (`decode.read`, `step.emit`, `decode.args`, each
carrying the step's `seq`) where the trace has them: one clock for the
host's side, no tie. A trace without them (a `Loaded` built by hand) takes
the seams from the recorder's spans through `Reduced.offset`. A step's
execution is the one that overlaps its `decode_step` most. A program that
records no `step.emit` span has no account: every reader returns None.

The DEVICE's plane is not on the host plane's clock to better than a
millisecond or two, and by how much differs from one profile to the next: on
the chip, executions were found to START up to 1.2 ms before the host had
entered the call that enqueues them (PERF.md section 5, PR 36). The gap's
length, `emit` and `schedule` do not depend on it (one plane alone, or
interior to the gap); `launch` and `drain` do, one gaining what the other
loses. So the device plane is first moved by the smallest shift that
restores causality in every step (`Account.shift_s`): no execution starts
before the end of its step's `decode.args` (the call that enqueues it has not
been entered) nor ends after the start of its `decode.read` (the first fetch
returned, so the program had finished). The range causality allows is
`Account.shift_range`: `launch` is AT LEAST and `drain` AT MOST what is
read, by up to the width of that range less the shift.

Between the two executions the device runs the step's own helpers (the key
split: microseconds); the gap is the idle time around them. Only a gap that
holds no other program of the engine (a prefill, the first token: an
admission), and during which a request was in flight, is split: a STEADY
gap, and the medians are over those. `closing` puts every second of the
traced window's idle time under one heading, so that what is left out is
counted too.

All times are seconds on the TRACE's clock, as in `xplane`.
"""

from __future__ import annotations

import bisect
import dataclasses
import os

from bench.reduce import xplane
from bench.stats import interval_union, percentile

PROGRAM = "engine_decode"
SHARES = ("drain", "emit", "schedule", "launch")
#: the mirrored phases this file reads, of the five the engine annotates
ANNOTATIONS = ("decode.args", "decode.read", "step.emit")
KEY = "steps.account"  # where `account` keeps what it built, in `Run.extra`


@dataclasses.dataclass
class Step:
    """One decode step that ran in the traced window; host times on the
    host plane's clock, the execution's on the device plane's."""
    seq: int | None
    t_start: float  # `decode_step` starts (the start of `decode.args`)
    t_call: float  # `decode.args` ends: the jitted call is entered
    t_waited: float  # `decode.read` starts: the first fetch has returned
    t_fetched: float  # `decode_step` ends (the end of `decode.read`)
    t_out: float  # `step.emit` ends
    exec_start: float  # its execution on the device
    exec_end: float
    tie_s: float | None = None  # annotation start - span start through
    # the one tie, where the step has both


@dataclasses.dataclass
class Gap:
    """The device's idle time from one execution of the program (it ends
    at `g0`) to the next (it starts at `g1`), and its four shares (seconds;
    they sum to `seconds`)."""
    g0: float
    g1: float
    seconds: float
    shares: dict


@dataclasses.dataclass
class Account:
    steps: list  # Step, in time order
    gaps: list  # Gap: the steady ones
    left_out: dict  # heading -> idle seconds between programs, not split
    inside_s: float  # idle seconds inside executions, between operations
    idle_s: float  # the traced window's idle seconds: window - busy
    clock: str  # "annotations" | "spans": where the seams came from
    shift_s: float = 0.0  # added to the device plane's times before the
    # gaps were split, and the range causality allows it
    shift_range: tuple = (0.0, 0.0)
    middles_ms: list = dataclasses.field(default_factory=list)  # of each
    # idle piece of a steady gap AS THE TRACE HAS IT, its middle's distance
    # to the nearer of the two seams that change `idle_gaps`' label

    def median_ms(self, what: str):
        """Median over the steady gaps of a share, or of the whole gap
        (`"gap"`), in ms."""
        if not self.gaps:
            return None
        vals = [g.seconds if what == "gap" else g.shares[what]
                for g in self.gaps]
        return percentile(vals, 50) * 1e3

    def closing(self) -> dict:
        """heading -> idle seconds; together the traced window's."""
        out = {s: sum(g.shares[s] for g in self.gaps) for s in SHARES}
        out.update(self.left_out)
        out[INSIDE] = self.inside_s
        return out

    def tie_us(self):
        """(median, p5, p95) over the steps of annotation start minus span
        start, in us; None where the trace has no annotations."""
        d = [s.tie_s * 1e6 for s in self.steps if s.tie_s is not None]
        if not d:
            return None
        return tuple(percentile(d, q) for q in (50, 5, 95))

    def lines(self) -> list:
        """The account, for the run's log."""
        rows = self.closing()
        total = sum(rows.values())
        out = [f"step account ({len(self.steps)} steps paired, "
               f"{len(self.gaps)} steady gaps, seams from the "
               f"{self.clock}): device gap p50 "
               f"{self.median_ms('gap') or 0.0:.3f} ms = " + " + ".join(
                   f"{s} {self.median_ms(s) or 0.0:.3f}" for s in SHARES)]
        out += [f"  {secs:9.4f} s  {name}" for name, secs in rows.items()]
        out.append(f"  {total:9.4f} s  accounted, of {self.idle_s:.4f} s "
                   f"idle in the traced window")
        lo, hi = self.shift_range
        out.append(f"  device plane moved by {self.shift_s * 1e3:.3f} ms "
                   f"(causality allows {lo * 1e3:.3f} .. {hi * 1e3:.3f}): "
                   f"launch is at least, drain at most, what is read")
        if self.middles_ms:
            near = sum(1 for d in self.middles_ms if d < 0.3)
            out.append(f"  idle pieces of steady gaps as the trace has "
                       f"them: middle {percentile(self.middles_ms, 50):.3f}"
                       f" ms (p50) from the seam that decides its label, "
                       f"{near} of {len(self.middles_ms)} within 0.3 ms")
        tie = self.tie_us()
        if tie is not None:
            out.append("  annotation start - span start through the one "
                       "tie: median %.1f us, p5 %.1f, p95 %.1f" % tie)
        return out


def load_annotations(path: str) -> list:
    """[(name, seq, start, dur)] of the mirrored phases on the host plane
    of the profile at `path`, trace clock."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != xplane.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in ANNOTATIONS:
                    seq = dict(e.stats).get("seq")
                    if seq is not None:
                        out.append((e.name, int(seq), e.start_ns / 1e9,
                                    e.duration_ns / 1e9))
    return out


def _overlap(a0: float, a1: float, b0: float, b1: float) -> float:
    return max(min(a1, b1) - max(a0, b0), 0.0)


def split(g0: float, g1: float, t_fetched: float, t_out: float,
          t_start: float) -> dict:
    """The gap `g0..g1` by overlap with the four stretches the seams cut
    time into. Seams out of order (a tie off by microseconds) are pushed
    up, so the shares always sum to the gap."""
    s1 = t_fetched
    s2 = max(t_out, s1)
    s3 = max(t_start, s2)
    inf = float("inf")
    return {"drain": _overlap(g0, g1, -inf, s1),
            "emit": _overlap(g0, g1, s1, s2),
            "schedule": _overlap(g0, g1, s2, s3),
            "launch": _overlap(g0, g1, s3, inf)}


def _first_plane(dev) -> list:
    """The first chip's program executions, in time order."""
    return sorted(next(iter(dev.loaded.modules.values())),
                  key=lambda e: e.start)


def pair(run, annotations) -> tuple:
    """(the steps of the traced window, each with its execution; where the
    host's times came from). With annotations a step's times are those of
    the three that carry its `seq`, on the trace's clock; without, its
    spans', brought over by the offset. Its execution is the one that
    overlaps `decode_step` most: the planes may be a millisecond or two
    apart, a step is ten times that."""
    dev = run.device
    execs = [e for e in _first_plane(dev) if PROGRAM in e.name
             and e.start + e.dur > dev.begin and e.start < dev.end]
    ends = [e.start + e.dur for e in execs]

    args_end = {t: t + d for t, d, _ in run.span_list("decode.args")}
    reads = [t for t, _, _ in run.span_list("decode.read")]
    whole = [(t, t + d) for t, d, _ in run.span_list("engine.step")]
    whole_ends = [b for _, b in whole]
    by_seq = {}
    for name, seq, t, d in annotations:
        by_seq.setdefault(seq, {})[name] = (t, t + d)
    steps = []
    for t, d, args in run.span_list("decode_step"):
        seq, tie = args.get("seq"), None
        if annotations:
            ann = by_seq.get(seq, {})
            if len(ann) < len(ANNOTATIONS):
                continue  # outside the profile, or cut by its edge
            tie = ann["decode.args"][0] - (t - dev.offset)
            a, call = ann["decode.args"]
            waited, b = ann["decode.read"]
            out = ann["step.emit"][1]
        else:
            i = bisect.bisect_left(whole_ends, t + d)  # its `engine.step`
            out = whole[i][1] if i < len(whole) else t + d
            call = args_end.get(t, t)  # starts with `decode_step`
            i = bisect.bisect_left(reads, t)
            waited = reads[i] if i < len(reads) and reads[i] <= t + d \
                else t + d
            a, call, waited, b, out = (x - dev.offset for x in
                                       (t, call, waited, t + d, out))
        best, most = None, 0.0
        for e in execs[bisect.bisect_right(ends, a):]:
            if e.start >= b:
                break
            both = min(e.start + e.dur, b) - max(e.start, a)
            if both > most:
                best, most = e, both
        if best is not None:
            steps.append(Step(seq, a, call, waited, b, out, best.start,
                              best.start + best.dur, tie))
    return steps, "annotations" if annotations else "spans"


def causal_shift(steps) -> tuple:
    """(what to add to the device plane's times, the range causality
    allows). An execution cannot start before the host enters the call that
    enqueues it, nor end after the fetch that waits for it has returned:
    the shift is the one nearest 0 that every step allows."""
    lo = max((s.t_call - s.exec_start for s in steps), default=0.0)
    hi = min((s.t_waited - s.exec_end for s in steps), default=0.0)
    return (lo if lo > 0 else min(hi, 0.0)), (lo, hi)


ADMISSION = "another program of the engine in the gap (an admission)"
EDGES = "before the window's first step and after its last"
NO_REQUEST = "no request in flight"
NOT_PAIRED = "a step without its spans or annotations"
INSIDE = "inside a program, between operations"


def build(run, annotations=()) -> Account | None:
    """The account of a traced run, or None where there is no device trace
    or the program does not cut its step into phases. Between two
    executions of the program the device may run the step's own helpers
    (the key split: microseconds); the gap is the idle time around them.
    A gap that holds another program of the engine (`jit_engine_*`: a
    prefill, the first token) belongs to an admission and is left out."""
    dev = run.device
    if dev is None or not dev.busy_s or not dev.loaded.modules:
        return None
    if not run.span_list("step.emit"):
        return None
    steps, clock = pair(run, list(annotations))
    shift, allowed = causal_shift(steps)
    by_exec = {s.exec_start: s for s in steps}
    flight = interval_union(
        (r.t_sent - dev.offset,
         (r.stamps[-1] if r.done and r.stamps else float("inf")) - dev.offset)
        for r in run.requests)

    def in_flight(a, b):  # a hand-built run lists no requests: yes
        return not run.requests or any(lo <= a and b <= hi
                                       for lo, hi in flight)

    gaps, middles = [], []
    left = dict.fromkeys((ADMISSION, EDGES, NO_REQUEST, NOT_PAIRED), 0.0)

    def close(prev, e, pieces, admission):
        idle = sum(b - a for a, b in pieces)
        here = by_exec.get(prev.start) if prev is not None else None
        there = by_exec.get(e.start) if e is not None else None
        if prev is None or e is None:
            left[EDGES] += idle
        elif admission:
            left[ADMISSION] += idle
        elif not in_flight(prev.start + prev.dur, e.start):
            left[NO_REQUEST] += idle
        elif here is None or there is None:
            left[NOT_PAIRED] += idle
        else:
            shares = dict.fromkeys(SHARES, 0.0)
            for a, b in pieces:
                for k, v in split(a + shift, b + shift, here.t_fetched,
                                  here.t_out, there.t_start).items():
                    shares[k] += v
                middles.append(min(abs((a + b) / 2 - here.t_fetched),
                                   abs((a + b) / 2 - there.t_start)) * 1e3)
            gaps.append(Gap(prev.start + prev.dur, e.start, idle, shares))

    t, prev, pieces, admission, covered = dev.begin, None, [], False, 0.0
    for e in [e for e in _first_plane(dev) if e.start + e.dur > dev.begin
              and e.start < dev.end] + [None]:
        a = dev.end if e is None else max(e.start, dev.begin)
        if a > t:
            pieces.append((t, a))
        if e is None or PROGRAM in e.name:
            close(prev, e, pieces, admission)
            prev, pieces, admission = e, [], False
        elif "engine_" in e.name:
            admission = True
        if e is not None:
            b = min(e.start + e.dur, dev.end)
            covered += max(b - max(a, t), 0.0)
            t = max(t, b)
    return Account(steps=steps, gaps=gaps, left_out=left,
                   inside_s=covered - dev.busy_s,
                   idle_s=dev.window_s - dev.busy_s, clock=clock,
                   shift_s=shift, shift_range=allowed, middles_ms=middles)


def account(run) -> Account | None:
    """The run's account, built once: the six readers share it. The
    annotations are `run.extra["host_annotations"]` where a run brings its
    own; else they are read from the trace where `bench/run.py` had it
    written (`xplane.load` keeps of the host plane only the one annotation
    that ties the clocks). The account is printed with the run's log."""
    if KEY not in run.extra:
        acc = None
        if run.device is not None and run.span_list("step.emit"):
            annotations = run.extra.get("host_annotations")
            if annotations is None:
                try:
                    annotations = load_annotations(xplane.find_trace(
                        os.path.join(run.cell.root, ".bench_trace")))
                except FileNotFoundError:
                    annotations = ()
            acc = build(run, annotations)
        if acc is not None:
            print("\n".join(acc.lines()), flush=True)
        run.extra[KEY] = acc
    return run.extra[KEY]
