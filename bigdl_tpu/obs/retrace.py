"""What JAX's tracing costs the thread that pays it.

An un-jitted JAX call (`lax.cond` over fresh closures, `.at[].set`, a
`jnp` function on the host path) is traced, lowered and compiled — or
fetched from the persistent cache — again on every call, on the calling
thread, while the device may sit idle. The engine's admission paid that
per request until its first-token work became one program built once per
engine (PERF.md, PR 27); the counter is what says it stays so.
`jax.monitoring` reports each such duration; ONE
listener per process adds them to an accumulator of the thread they ran
on, and whoever owns that thread (the engine's step loop) reads it at
its phase boundaries: the difference between two reads is what was paid
between them.

The listener runs only when JAX traces, lowers or compiles, so a step
that retraces nothing pays nothing for it; a read is one thread-local
attribute lookup and takes no clock.
"""

from __future__ import annotations

import threading

#: the durations summed: a function traced to a jaxpr, a jaxpr lowered to
#: an MLIR module, an executable compiled by the backend or loaded from the
#: persistent cache (the events bench/records.CompileLog.DURATIONS names)
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"


class Accumulator:
    """One thread's running totals; only that thread writes them."""
    __slots__ = ("seconds", "programs")

    def __init__(self):
        self.seconds = 0.0  # trace + lower + compile-or-load
        self.programs = 0  # executables built or loaded (COMPILE events)


_local = threading.local()
_install_lock = threading.Lock()
_installed = False


def thread_accumulator() -> Accumulator:
    """The calling thread's accumulator (made on first use)."""
    try:
        return _local.acc
    except AttributeError:
        acc = _local.acc = Accumulator()
        return acc


def _on_duration(event: str, secs: float, **_kw) -> None:
    if event in (TRACE, LOWER, COMPILE):
        acc = thread_accumulator()
        acc.seconds += secs
        acc.programs += event == COMPILE


def install() -> None:
    """Register the process's one listener; later calls do nothing."""
    global _installed
    with _install_lock:
        if _installed:
            return
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(_on_duration)
        _installed = True
