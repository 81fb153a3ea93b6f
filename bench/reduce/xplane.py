"""From a profiler trace (`.xplane.pb`) to numbers: device busy and idle time,
time per kernel and per program, the operations that took most time and the
longest idle gaps by what the host was doing. Reads the file with
`jax.profiler.ProfileData`, nothing else.

What a TPU trace looks like (jax 0.9.0 / libtpu 0.0.34, TPU v5 lite; see
bench/fixtures/): one plane per chip, `/device:TPU:<n>`, whose line
`XLA Modules` has one event per program execution, named after the jit
(`jit_engine_decode(<fingerprint>)`), and whose line `XLA Ops` has one event
per HLO operation executed, nested where an operation (a `while`, a
`conditional`) contains others. An event's name there is the whole HLO
instruction, `%qmatmul.56 = bf16[32,28672]{...} custom-call(...)`: only what
stands before ` = ` is the operation's own name (operands name other
operations), and a Mosaic kernel's is its stable name plus a number
(`qmatmul`, `paged_decode_attention`, `flash_attention`). A third line,
`Async XLA Ops`, holds copies in flight and is not read. A window in which no
operation ran has no such plane at all. The host's threads are lines of the
plane `/host:CPU`; the benchmark's one `TraceAnnotation` is found there and
ties the trace's clock to the benchmark's.

All times inside are seconds on the TRACE's clock; `t_sync` maps them to the
benchmark's clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os

from bench.stats import interval_union

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


@dataclasses.dataclass
class Event:
    name: str  # the operation's own name (`qmatmul.56`) or the module's
    start: float  # seconds, trace clock
    dur: float


def own_name(event_name: str) -> str:
    """`%qmatmul.56 = bf16[...] custom-call(...)` -> `qmatmul.56`."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


@dataclasses.dataclass
class Loaded:
    """The part of a trace the reduction reads, as plain data (this is also
    what bench/fixtures keeps of a recorded trace)."""
    ops: dict  # device plane name -> [Event] of its XLA Ops line
    modules: dict  # device plane name -> [Event] of its XLA Modules line
    sync: float | None  # start of the benchmark's annotation, trace clock
    lines: dict  # plane name -> {line name: number of events}, for the log


def load(path: str, sync_name: str) -> Loaded:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, modules, lines, sync = {}, {}, {}, None
    for plane in pd.planes:
        lines[plane.name] = {}
        for line in plane.lines:
            evs = list(line.events)
            lines[plane.name][line.name] = len(evs)
            if plane.name.startswith(DEVICE_PREFIX) and line.name in (
                    OPS_LINE, MODULES_LINE):
                out = [Event(own_name(e.name), e.start_ns / 1e9,
                             e.duration_ns / 1e9) for e in evs]
                (ops if line.name == OPS_LINE else modules)[plane.name] = out
            elif plane.name == HOST_PLANE and sync is None:
                for e in evs:
                    if e.name == sync_name:
                        sync = e.start_ns / 1e9
                        break
    return Loaded(ops, modules, sync, lines)


def _self_times(events) -> list:
    """(event, seconds not covered by the events nested inside it)."""
    out, stack = [], []
    for e in sorted(events, key=lambda e: (e.start, -e.dur)):
        while stack and stack[-1][0].start + stack[-1][0].dur <= e.start:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][1] -= min(e.dur, stack[-1][0].start + stack[-1][0].dur
                                - e.start)
        stack.append([e, e.dur])
    out.extend(tuple(s) for s in stack)
    return out


def _base(name: str) -> str:
    """`fusion.123` -> `fusion`: one row per kind of operation."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


class Reduced:
    def __init__(self, loaded: Loaded, t_sync: float, begin: float,
                 end: float):
        """`begin`/`end`: the traced window on the benchmark's clock;
        `t_sync`: the benchmark's clock at the annotation."""
        self.loaded = loaded
        # benchmark clock = trace clock + offset
        self.offset = t_sync - loaded.sync if loaded.sync is not None else None
        if self.offset is None:
            lo = min((e.start for evs in loaded.ops.values() for e in evs),
                     default=0.0)
            self.offset = begin - lo
        self.begin, self.end = begin - self.offset, end - self.offset
        self.window_s = end - begin
        self._busy = {p: self._clip(interval_union(
            (e.start, e.start + e.dur) for e in evs))
            for p, evs in loaded.ops.items()}
        n = max(len(self._busy), 1)
        self.busy_s = sum(b - a for iv in self._busy.values()
                          for a, b in iv) / n

    def _clip(self, intervals):
        return [(max(a, self.begin), min(b, self.end)) for a, b in intervals
                if b > self.begin and a < self.end]

    def _inside(self, evs):
        return [e for e in evs if self.begin <= e.start < self.end]

    def program_seconds(self, program: str) -> list:
        """Device seconds of each execution of the jit called `program`."""
        return [e.dur for evs in self.loaded.modules.values()
                for e in self._inside(evs) if program in e.name]

    def kernel_in_program(self, kernel: str, program: str) -> tuple:
        """(executions of `program` wholly inside the window, device seconds
        of the operations named `kernel` inside those executions)."""
        n, secs = 0, 0.0
        for plane, mods in self.loaded.modules.items():
            spans = [(m.start, m.start + m.dur) for m in self._inside(mods)
                     if program in m.name and m.start + m.dur <= self.end]
            n += len(spans)
            ops = sorted((e for e in self.loaded.ops.get(plane, ())
                          if _base(e.name) == kernel),
                         key=lambda e: e.start)
            i = 0
            for a, b in sorted(spans):
                while i < len(ops) and ops[i].start < a:
                    i += 1
                while i < len(ops) and ops[i].start < b:
                    secs += ops[i].dur
                    i += 1
        return n, secs

    def top_ops(self, n: int) -> list:
        """[[name, seconds]]: self time by kind of operation (`fusion.12`
        and `fusion.90` are one row; a kernel's row is its stable name), the
        longest first; averaged over the chips."""
        acc = {}
        for evs in self.loaded.ops.values():
            for e, own in _self_times(self._inside(evs)):
                key = _base(e.name)
                acc[key] = acc.get(key, 0.0) + own
        k = max(len(self.loaded.ops), 1)
        rows = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[name, s / k] for name, s in rows]

    def idle_gaps(self, label_at, n: int) -> list:
        """[[what the host was doing, idle seconds]]: the device's idle
        time on the first chip, by the label of each gap's middle."""
        if not self._busy:
            return []
        busy = next(iter(self._busy.values()))
        acc, t = {}, self.begin
        for a, b in list(busy) + [(self.end, self.end)]:
            if a > t:
                lab = label_at((t + a) / 2 + self.offset)
                acc[lab] = acc.get(lab, 0.0) + (a - t)
            t = max(t, b)
        rows = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[lab, s] for lab, s in rows]

    def summary(self) -> str:
        progs = {}
        for evs in self.loaded.modules.values():
            for e in self._inside(evs):
                k = e.name.split("(")[0]
                c = progs.setdefault(k, [0, 0.0])
                c[0] += 1
                c[1] += e.dur
        return "; ".join(f"{k} x{c} {s:.3f} s" for k, (c, s) in
                         sorted(progs.items(), key=lambda kv: -kv[1][1])[:6])


def find_trace(logdir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def reduce_dir(logdir: str, sync_name: str, t_sync: float, begin: float,
               end: float) -> Reduced:
    return Reduced(load(find_trace(logdir), sync_name), t_sync, begin, end)


def make_labeller(spans: list, requests: list):
    """label_at(t): what the host was doing at benchmark-clock second `t`,
    from the engine's spans (`prefill` on a request's track, `decode_step` on
    the engine's) and the requests in flight."""
    pre = sorted((e["ts"] / 1e6, (e["ts"] + e["dur"]) / 1e6) for e in spans
                 if e.get("ph") == "X" and e["name"] == "prefill")
    dec = sorted((e["ts"] / 1e6, (e["ts"] + e["dur"]) / 1e6) for e in spans
                 if e.get("ph") == "X" and e["name"] == "decode_step")
    flight = sorted((r.t_sent, r.stamps[-1] if r.done and r.stamps
                     else float("inf")) for r in requests)

    def covered(ivs, t):
        return any(a <= t < b for a, b in ivs)

    def label_at(t):
        if covered(pre, t):
            return "prefill span (admission: dispatch, first-token sampling)"
        if covered(dec, t):
            return "inside a decode_step span (dispatch, host sync)"
        if covered(flight, t):
            return ("between decode_step spans (emit, admit, scheduling)"
                    if dec else "inside a call, between programs")
        return "no request in flight"

    return label_at
