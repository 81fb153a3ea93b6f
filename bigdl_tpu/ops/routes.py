"""Which route each dispatching op took: Pallas kernel or XLA.

`ops/linear.linear` and the attention dispatch in `models/llama.forward`
choose between a Pallas kernel and the XLA route from shapes, formats
and the platform, silently by design (an ineligible shape must still
compute). The choice is made while tracing, so a caller that wants to
SEE it opens `record_routes()` around the calls that trace, and reads
the counter afterwards:

    with record_routes() as routes:
        model.generate(...)
    # routes[("linear", "pallas:gemv", "sym_int4 M2 K4096 O6144 stack "
    #         "words:inplace scales:stack")] == 1

Outside a scope `note` costs one global read. The sink is process-wide
on purpose: the serving engine traces on its own thread.
"""

from __future__ import annotations

import collections
import contextlib

_sink = None


def note(op: str, route: str, detail: str = "") -> None:
    """Record one dispatch decision (trace time)."""
    if _sink is not None:
        _sink[(op, route, detail)] += 1


@contextlib.contextmanager
def record_routes():
    """Collect every dispatch decision traced while the scope is open
    into a Counter keyed by (op, route, detail)."""
    global _sink
    prev, _sink = _sink, collections.Counter()
    try:
        yield _sink
    finally:
        _sink = prev
