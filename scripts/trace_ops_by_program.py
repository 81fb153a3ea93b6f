#!/usr/bin/env python3
"""Which program do a trace's device operations of a given kind sit in?

    python scripts/trace_ops_by_program.py <logdir or .xplane.pb> [kind ...]

`kind` is a row of the benchmark's `breakdown.device_ops` (`copy`,
`fusion`, ...; default `copy`). Prints each device's programs (`XLA
Modules`) by time, then per (kind, program) the events' count, seconds
and longest, with one event's full name: its shapes and layouts say what
is being moved. An event belongs to the program execution it starts in.
After a traced run of a cell the trace is in `.bench_trace/` (PERF.md
section 5 keeps what this found in `qwen2-7b.chat-closed`).
"""

from __future__ import annotations

import bisect
import collections
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from jax.profiler import ProfileData

    from bench.reduce import xplane

    path = sys.argv[1] if sys.argv[1].endswith(".pb") else \
        xplane.find_trace(sys.argv[1])
    kinds = sys.argv[2:] or ["copy"]
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(xplane.DEVICE_PREFIX):
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                       e.name.split("(")[0])
                      for e in lines.get(xplane.MODULES_LINE, []))
        if not mods:
            continue
        by_mod = collections.defaultdict(lambda: [0, 0.0])
        for lo, hi, name in mods:
            by_mod[name][0] += 1
            by_mod[name][1] += (hi - lo) / 1e9
        print(f"{plane.name}: programs by device time")
        for name, (n, secs) in sorted(by_mod.items(),
                                      key=lambda kv: -kv[1][1])[:12]:
            print(f"  {secs:9.4f} s x{n:<6d} {name}")
        starts = [m[0] for m in mods]
        acc = collections.defaultdict(lambda: [0, 0.0, 0.0, ""])
        for e in lines.get(xplane.OPS_LINE, []):
            kind = xplane._base(xplane.own_name(e.name))
            if kind not in kinds:
                continue
            i = bisect.bisect_right(starts, e.start_ns) - 1
            inside = i >= 0 and e.start_ns < mods[i][1]
            a = acc[kind, mods[i][2] if inside else "<no program>"]
            a[0] += 1
            a[1] += e.duration_ns / 1e9
            if e.duration_ns / 1e6 > a[2]:
                a[2], a[3] = e.duration_ns / 1e6, e.name[:300]
        for (kind, mod), (n, secs, longest, name) in sorted(
                acc.items(), key=lambda kv: -kv[1][1]):
            print(f"  {secs:9.4f} s x{n:<6d} longest {longest:8.3f} ms  "
                  f"{kind} in {mod}\n      {name}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
