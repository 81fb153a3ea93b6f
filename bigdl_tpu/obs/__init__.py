"""Observability layer shared by serving and training (docs/observability.md).

- tracing.py  — bounded ring-buffer span recorder (Chrome trace-event /
  Perfetto export) + crc-suffixed per-request JSONL log + trace summary
- profiler.py — guarded on-demand ``jax.profiler`` windows, their clock
  tied to the recorder's
- retrace.py  — what JAX's tracing, lowering and compiling cost the
  thread that pays (one ``jax.monitoring`` listener per process)

The serving engine (serving/engine.py) and training supervisor
(train/supervisor.py) both record into the same :class:`TraceRecorder`
format, so a serving run and a training run open in the same Perfetto
UI with the same span vocabulary.
"""

from bigdl_tpu.obs.tracing import (
    RequestLog,
    TraceRecorder,
    format_summary,
    summarize_trace,
)

__all__ = [
    "TraceRecorder",
    "RequestLog",
    "summarize_trace",
    "format_summary",
    "ProfilerWindow",
    "PROFILER",
]


def __getattr__(name):
    if name in ("ProfilerWindow", "PROFILER"):  # lazy: keeps the
        # recorder importable in processes that never touch jax.profiler
        from bigdl_tpu.obs import profiler as _p

        return getattr(_p, name)
    raise AttributeError(name)
