#!/usr/bin/env python3
"""One benchmark cell exactly as `bench/run.py` runs it, with one line more
before its result line: how often the engine had a decode step in flight.

    python scripts/bench_engine_counters.py --workload mistral-7b.chat-steady \
        --seed 7 --seconds 50 --trace 1

The line gives `InferenceEngine.decode_steps` (steps read, by whether each
was dispatched with its predecessor unread) and `decode_rows_discarded` over
the whole process (warm-up and check included), and, of the traced
`decode_step` spans inside the measured window, how many carry `ahead`
true, and the median of the host's own work in a call that decoded and
admitted nothing (the call's `engine.step` span less its `decode.wait`; the
caller's loop is not in it). bench/ is not touched: `bench.run.prepare` and `bench.run.say` are
wrapped in this process.
"""

from __future__ import annotations

import bisect
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    from bench import run as bench_run

    seen, prepare, say = {}, bench_run.prepare, bench_run.say

    def spy(*a, **kw):
        seen["p"] = prepare(*a, **kw)
        return seen["p"]

    def counted(msg):
        p = seen.get("p")
        eng = getattr(getattr(p, "driver", None), "engine", None)
        if eng is not None and '{"correct"' in msg:
            events = [e for e in (p.tracer.events() if p.tracer is not None
                                  else []) if e.get("ph") == "X"]
            spans = [e["args"] for e in events if e["name"] == "decode_step"]
            # the host's own work a call: a decoding call's length less
            # its `decode.wait` (the part it spent waiting for the device)
            waits = sorted((e["ts"], e["dur"]) for e in events
                           if e["name"] == "decode.wait")
            host = []
            for w in events:
                if (w["name"] != "engine.step" or w["args"].get("admitted")
                        or w["args"].get("seq") is None):
                    continue
                i = bisect.bisect_left(waits, (w["ts"], 0))
                if i < len(waits) and sum(waits[i]) <= w["ts"] + w["dur"]:
                    host.append((w["dur"] - waits[i][1]) / 1e3)
            host.sort()
            say("engine counters: " + json.dumps({
                "decode_steps_read": {"ahead_0": eng.decode_steps[0],
                                      "ahead_1": eng.decode_steps[1]},
                "decode_rows_discarded": eng.decode_rows_discarded,
                "decode_step_spans": len(spans),
                "spans_ahead": sum(bool(a.get("ahead")) for a in spans),
                "host_ms_per_call_p50": (host[len(host) // 2] if host
                                         else None)}))
        say(msg)

    bench_run.prepare, bench_run.say = spy, counted
    try:
        return bench_run.main(argv)
    finally:
        bench_run.prepare, bench_run.say = prepare, say


if __name__ == "__main__":
    sys.exit(main())
