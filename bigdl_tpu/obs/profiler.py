"""Guarded on-demand ``jax.profiler`` windows.

The XLA profiler is the ground truth for *device* time (HLO timelines,
TPU step traces), but ``start_trace`` is process-global and stateful:
two overlapping windows corrupt each other, and a ``stop_trace``
without a live window raises from deep inside XLA. This wrapper makes
the window an explicit, guarded resource so the ApiServer debug
endpoint and the ``bigdl-tpu trace profile-*`` CLI can drive it safely
against a live server: start is rejected while a window is open
(:class:`ProfilerBusy`), stop without a window is a structured
:class:`ProfilerIdle`, and the window's logdir/age are inspectable.

The profiler output (a TensorBoard/XProf logdir) is complementary to
`obs/tracing.py`'s host-side request spans: spans say *which request*
waited, the XLA trace says *which op* the device ran meanwhile.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Callable, Optional

#: the host-plane event of the profile whose start is the instant the
#: recorder's `profiler.sync` instant was stamped
SYNC_ANNOTATION = "bigdl_tpu.clock_sync"


class ProfilerBusy(RuntimeError):
    """start() while a window is already open."""


class ProfilerIdle(RuntimeError):
    """stop() with no window open."""


class ProfilerWindow:
    """One process-wide profiling window. ``start_fn``/``stop_fn``
    default to ``jax.profiler.start_trace``/``stop_trace`` (resolved
    lazily so importing this module never drags the profiler plugin
    in); tests inject stubs."""

    def __init__(self, start_fn: Optional[Callable] = None,
                 stop_fn: Optional[Callable] = None,
                 clock: Callable[[], float] = time.time):
        self._lock = threading.Lock()
        self._start_fn = start_fn
        self._stop_fn = stop_fn
        self._clock = clock  # window-age timestamps (WCT001: injectable)
        self.logdir: Optional[str] = None
        self.started_at: Optional[float] = None
        self._recorder = None  # the open window's, for `profiler.stop`

    def _fns(self):
        if self._start_fn is not None:
            return self._start_fn, self._stop_fn
        import jax.profiler as jp

        return jp.start_trace, jp.stop_trace

    def start(self, logdir: str, recorder=None) -> dict:
        """Open the window. With a `recorder` (obs.tracing.TraceRecorder)
        the two traces get one clock: right after the profiler starts,
        inside a `TraceAnnotation` named :data:`SYNC_ANNOTATION`, the
        recorder's clock is read and an instant `profiler.sync` recorded
        there. The annotation is an event of the profile's host plane,
        so `instant.ts - annotation.start` is what to add to a profile
        timestamp to place it among the spans."""
        if not logdir:
            raise ValueError("profiler window needs a logdir")
        with self._lock:
            if self.logdir is not None:
                raise ProfilerBusy(
                    f"a profiler window is already open (logdir="
                    f"{self.logdir}); stop it first"
                )
            start, _ = self._fns()
            start(logdir)  # raises before any state flips on failure
            self.logdir = logdir
            self.started_at = self._clock()
            self._recorder = recorder
            if recorder is not None:
                with self._annotation():
                    recorder.instant("profiler.sync", logdir=logdir)
            return self.status()

    def _annotation(self):
        """The profile-side half of the clock tie; a window on stub
        functions has no profile to annotate."""
        if self._start_fn is not None:
            return contextlib.nullcontext()
        import jax.profiler as jp

        return jp.TraceAnnotation(SYNC_ANNOTATION)

    def stop(self) -> dict:
        with self._lock:
            if self.logdir is None:
                raise ProfilerIdle("no profiler window is open")
            _, stop = self._fns()
            logdir, t0 = self.logdir, self.started_at
            recorder, self._recorder = self._recorder, None
            try:
                stop()
            finally:
                # the window is spent either way: a failed stop must not
                # wedge every later start behind ProfilerBusy
                self.logdir = None
                self.started_at = None
            if recorder is not None:
                recorder.instant("profiler.stop", logdir=logdir)
            return {"active": False, "logdir": logdir,
                    "seconds": round(self._clock() - (t0 or 0.0), 3)}

    def status(self) -> dict:
        out = {"active": self.logdir is not None, "logdir": self.logdir}
        if self.started_at is not None:
            out["seconds"] = round(self._clock() - self.started_at, 3)
        return out


#: the process-wide window the ApiServer debug endpoint drives
PROFILER = ProfilerWindow()
