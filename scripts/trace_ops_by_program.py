#!/usr/bin/env python3
"""Which program, and which scope of it, do a trace's device operations of a
given kind sit in?

    python scripts/trace_ops_by_program.py <logdir or .xplane.pb> [kind ...] [--by scope]

`kind` is a row of the benchmark's `breakdown.device_ops` (`copy`,
`fusion`, ...; default `copy`). Prints each device's programs (`XLA
Modules`) by time, then per (kind, program), or with `--by scope` per (kind,
program, scope), the operations' count, self seconds and longest, with one
operation's name stack (`tf_op`: the scopes it was traced under, down to the
primitive) and whole instruction: its shapes and layouts say what is being
moved. `bench/reduce/scopes.py` places every operation (`place`: an
operation belongs to the execution it starts in, and to the innermost scope of
`bigdl_tpu/obs/scopes.py` on its name stack); this script only groups what it
is handed. With no kind and `--by scope` it prints the reducer's own table
(device time by scope, an execution of `engine_decode`,
`engine_paged_prefill`, `generate_tokens`).

After a traced run of a cell the trace is in `.bench_trace/`; a server's is
in the logdir its operator gave `/debug/profiler` (docs/observability.md
section 4).
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("logdir")
    ap.add_argument("kinds", nargs="*")
    ap.add_argument("--by", choices=("program", "scope"), default="program")
    args = ap.parse_args()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from bench.reduce import scopes, xplane

    path = args.logdir if args.logdir.endswith(".pb") else \
        xplane.find_trace(args.logdir)
    loaded = dataclasses.replace(xplane.load(path, ""), sync=None)
    metadata = scopes.read_metadata(path)
    by_mod = collections.defaultdict(lambda: [0, 0.0])
    for _, name, a, b, _ in scopes.executions(loaded):
        by_mod[name][0] += 1
        by_mod[name][1] += b - a
    print("programs by device time")
    for name, (n, secs) in sorted(by_mod.items(),
                                  key=lambda kv: -kv[1][1])[:12]:
        print(f"  {secs:9.4f} s x{n:<6d} {name}")
    if not args.kinds and args.by == "scope":
        lo = min((e.start for evs in loaded.ops.values() for e in evs),
                 default=0.0)
        hi = max((e.start + e.dur for evs in loaded.ops.values()
                  for e in evs), default=0.0)
        dev = xplane.Reduced(loaded, 0.0, lo, hi)  # the whole trace
        print("\n".join(scopes.build(dev, metadata).lines()))
        return 0
    kinds = args.kinds or ["copy"]
    acc = collections.defaultdict(lambda: [0, 0.0, 0.0, ""])
    for op in scopes.place(loaded, metadata):
        if op.kind not in kinds:
            continue
        key = (op.kind, op.program or "<no program>") + (
            (op.scope,) if args.by == "scope" else ())
        a = acc[key]
        a[0] += 1
        a[1] += op.self_s
        if op.dur * 1e3 > a[2]:
            a[2] = op.dur * 1e3
            a[3] = f"{op.tf_op}\n      {op.hlo[:300] or op.name}"
    for key, (n, secs, longest, name) in sorted(
            acc.items(), key=lambda kv: -kv[1][1]):
        print(f"  {secs:9.4f} s x{n:<6d} longest {longest:8.3f} ms  "
              f"{key[0]} in {' / '.join(key[1:])}\n      {name}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
