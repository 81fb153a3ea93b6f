"""What a run hands to the metric readers."""

from __future__ import annotations

import collections
import dataclasses
from typing import Any, Optional


class Frozen(dict):
    """The published keys as a hashable static argument of a jit."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


@dataclasses.dataclass
class Planned:
    """One request of a plan, as a generator offers it."""
    t_due: float  # seconds from the window's start (open); 0.0 (closed)
    prompt: list
    max_new_tokens: int


@dataclasses.dataclass
class Plan:
    kind: str  # "open" | "closed"
    requests: list  # Planned, in due order (open) or take order (closed)
    clients: int = 0
    think_s: float = 0.0


@dataclasses.dataclass
class Req:
    """One attempted request, on the benchmark's own clock."""
    t_due: Optional[float]  # open loop: when it was due; closed loop: None
    t_sent: float  # when submit() / the call was made
    n_prompt: int
    max_new: int
    stamps: list = dataclasses.field(default_factory=list)  # token arrivals
    done: bool = False
    failed: bool = False  # counts in `failed`, misses every limit
    wrong: bool = False  # failed with a wrong result: `correct` is false
    why: str = ""
    handle: Any = None  # the program's own request object, if any

    @property
    def t_ref(self) -> float:
        """TTFT counts from the instant the request was DUE in an open loop
        (a stall delays later requests and that wait is theirs), and from
        the send in a closed one."""
        return self.t_sent if self.t_due is None else self.t_due


@dataclasses.dataclass
class Run:
    """One measured window and everything read in it."""
    cell: Any  # cells.Cell
    hf: dict  # the published config keys, as run
    peak: dict  # bench/peaks.json entry of this device
    t0: float
    t1: float
    requests: list  # Req, every request attempted in the window
    spans: list = dataclasses.field(default_factory=list)  # TraceRecorder
    # events (Chrome format, microseconds on the benchmark's clock)
    device: Any = None  # reduce.xplane.Reduced of the traced seconds
    setup: dict = dataclasses.field(default_factory=dict)  # seconds by part
    compile_log: Any = None  # CompileLog
    weight_bytes: int = 0  # bytes a step must read of the parameter tree
    extra: dict = dataclasses.field(default_factory=dict)  # entry's own

    def span_list(self, name: str) -> list:
        """(start_s, dur_s, args) of the spans called `name`, inside the
        window, in time order."""
        out = [(e["ts"] / 1e6, e["dur"] / 1e6, e.get("args", {}))
               for e in self.spans
               if e.get("ph") == "X" and e["name"] == name
               and self.t0 <= e["ts"] / 1e6 < self.t1]
        return sorted(out, key=lambda s: s[0])

    def span_gaps_ms(self, name: str) -> list:
        """End of one span called `name` to the start of the next, in ms."""
        s = self.span_list(name)
        return [(b[0] - (a[0] + a[1])) * 1e3 for a, b in zip(s, s[1:])]


class CompileLog:
    """Seconds of tracing, lowering and backend compile (or cache load) and
    the persistent cache's hits and misses, from `jax.monitoring`; each event
    stamped, so that what happened inside the window can be told from
    set-up. After chip_smoke.CompileLog."""

    DURATIONS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
        "/jax/core/compile/backend_compile_duration": "compile",
    }

    def __init__(self, clock):
        import jax.monitoring as mon

        self.clock = clock
        self.durations = []  # (t, kind, seconds, fun_name)
        self.events = []  # (t, "cache_hits" | "cache_misses" | ...)
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **kw):
        kind = self.DURATIONS.get(event)
        if kind is not None:
            self.durations.append(
                (self.clock(), kind, secs, kw.get("fun_name", "?")))

    def _on_event(self, event, **kw):
        if event.startswith("/jax/compilation_cache/"):
            self.events.append((self.clock(), event.rsplit("/", 1)[1]))

    def seconds(self, t0=float("-inf"), t1=float("inf")) -> float:
        return sum(s for t, _, s, _ in self.durations if t0 <= t < t1)

    def count(self, what: str, t0=float("-inf"), t1=float("inf")) -> int:
        return sum(1 for t, e in self.events if e == what and t0 <= t < t1)

    def by_program(self, floor: float = 0.5) -> list:
        acc = collections.defaultdict(float)
        for _, kind, s, name in self.durations:
            acc[name] += s
        return sorted(((n, s) for n, s in acc.items() if s >= floor),
                      key=lambda kv: -kv[1])
