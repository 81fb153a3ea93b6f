#!/usr/bin/env python3
"""Print what a profiler trace holds: planes, lines, how many events, the
names that took most time on each device line with a sample of their
statistics. For looking at a trace by hand before trusting
bench/reduce/xplane.py on a new libtpu.

    python bench/tools/dump_trace.py <logdir or .xplane.pb> [--keep out.json.gz]

`--keep` writes the part the reduction reads (bench/reduce/xplane.Loaded),
cut to `--seconds` from the first device event, as gzipped JSON: the format
of bench/fixtures/.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import gzip
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("path")
    ap.add_argument("--keep")
    ap.add_argument("--seconds", type=float, default=0.25)
    args = ap.parse_args()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from jax.profiler import ProfileData

    from bench.reduce import xplane

    path = args.path if args.path.endswith(".pb") else xplane.find_trace(
        args.path)
    print(f"{path}: {os.path.getsize(path)} bytes")
    for plane in ProfileData.from_file(path).planes:
        print(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            if not evs:
                continue
            lo = min(e.start_ns for e in evs)
            hi = max(e.start_ns + e.duration_ns for e in evs)
            print(f"  LINE {line.name!r}: {len(evs)} events, "
                  f"{lo / 1e9:.4f}..{hi / 1e9:.4f} s")
            if not plane.name.startswith("/device") and \
                    "bench_sync" not in {e.name for e in evs}:
                continue
            acc = collections.defaultdict(lambda: [0, 0.0, None])
            for e in evs:
                a = acc[e.name]
                a[0] += 1
                a[1] += e.duration_ns / 1e9
                a[2] = a[2] or [(k, str(v)[:160]) for k, v in e.stats]
            for name, (n, s, st) in sorted(
                    acc.items(), key=lambda kv: -kv[1][1])[:25]:
                print(f"    {s:9.5f} s x{n:<6d} {name[:90]}  {st}")
    if args.keep:
        ld = xplane.load(path, "bench_sync")
        lo = min((e.start for evs in ld.ops.values() for e in evs),
                 default=0.0)
        cut = lambda evs: [dataclasses.asdict(e) for e in evs  # noqa: E731
                           if lo <= e.start < lo + args.seconds]
        with gzip.open(args.keep, "wt", encoding="utf-8") as f:
            json.dump({"ops": {p: cut(v) for p, v in ld.ops.items()},
                       "modules": {p: cut(v) for p, v in ld.modules.items()},
                       "sync": ld.sync, "lines": ld.lines}, f)
        print(f"kept {args.seconds} s in {args.keep}: "
              f"{os.path.getsize(args.keep)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
