"""Simulated-clock serving simulator: a deterministic scheduler test
that needs no device (docs/benchmarking.md). Its milliseconds are
simulated, never a speed.

Drives the REAL `serving/engine.py` — real scheduler, admission,
deadlines, preemption, prefix cache, journal, metrics, tracing — under
a virtual clock (`sim/clock.py`) and seeded synthetic arrival traces
(`sim/traces.py`). Only two things are fake: time (every engine
timestamp flows through the injectable ``clock=``, enforced statically
by graftlint WCT001) and the per-step latency, which comes from
`sim/cost.py`'s analytic roofline model instead of the host's wall
clock. Entry point: `bigdl-tpu simserve`.
"""

from bigdl_tpu.sim.clock import SimClock
from bigdl_tpu.sim.cost import CostModel
from bigdl_tpu.sim.traces import (
    Arrival, Trace, bursty_trace, named_trace, poisson_trace,
    prefix_heavy_trace,
)

__all__ = [
    "Arrival", "CostModel", "SimClock", "Trace", "bursty_trace",
    "named_trace", "poisson_trace", "prefix_heavy_trace",
]
