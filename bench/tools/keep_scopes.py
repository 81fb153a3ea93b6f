#!/usr/bin/env python3
"""Keep a quarter of a second of a traced run as a fixture for
bench/reduce/scopes.py: of the first chip, the program executions that lie
whole inside the cut, every operation that starts in one of them and the
name stack (`tf_op`) of each kind of operation among them; and what
scopes.py reads on the cut, as `expect`.

    python bench/tools/keep_scopes.py .bench_trace --seconds 0.25 \
        --out bench/fixtures/v5e_scopes.json.gz

`<logdir>` is where a run of `bench/run.py --trace 1` left its profile
(`.bench_trace/`), or the `.xplane.pb` itself. The cut starts `--lead` seconds
before the shortest `engine_paged_prefill` of the trace, so that it holds a
prefill between decode steps. Nothing runs on a chip here.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def reduced(kept: dict):
    """(`xplane.Reduced`, the metadata) of a cut, as scopes.py takes them
    (the test builds its own the same way). Times are kept as nanoseconds
    from the cut's start."""
    from bench.reduce.xplane import Event, Loaded, Reduced

    def events(rows):
        return [Event(n, a * 1e-9, d * 1e-9) for n, a, d in rows]

    plane = kept["plane"]
    ld = Loaded({plane: events(kept["ops"])},
                {plane: events(kept["modules"])}, sync=0.0, lines={})
    dev = Reduced(ld, t_sync=0.0, begin=0.0, end=kept["seconds"])
    return dev, {plane: {(prog, own): (op, "")
                         for prog, own, op in kept["tf_op"]}}


def expect(kept: dict) -> dict:
    from bench.reduce import scopes

    acc = scopes.build(*reduced(kept))
    print("\n".join(["the cut:"] + acc.lines()), flush=True)
    return {p: {"n": acc.n[p], "busy_ms": acc.busy_s[p] * 1e3 / acc.n[p],
                "xla_ms": acc.xla_ms(p),
                **{g: acc.group_ms(p, g) for g in scopes.GROUPS}}
            for p in scopes.PROGRAMS if acc.n[p]}


def cut(path: str, seconds: float, lead: float) -> dict:
    from bench.reduce import scopes, xplane

    loaded = xplane.load(path, "bench_sync")
    metadata = scopes.read_metadata(path)
    plane, mods = next(iter(loaded.modules.items()))
    # a profile starts and stops in the middle of an execution, whose event
    # holds only a part of it: prefills between two decode steps only
    steps = [m.start for m in mods if "engine_decode" in m.name] or [0.0]
    prefills = [m for m in mods if "engine_paged_prefill" in m.name
                and min(steps) < m.start < max(steps)]
    if not prefills:
        raise SystemExit("the trace holds no engine_paged_prefill between "
                         "two executions of engine_decode")
    lo = min(prefills, key=lambda m: m.dur).start - lead
    hi = lo + seconds
    mods = sorted((m for m in mods if lo <= m.start and m.start + m.dur <= hi),
                  key=lambda m: m.start)
    spans = [(m.start, m.start + m.dur, scopes.program_id(m.name))
             for m in mods]
    ops, tf_op = [], {}
    for e in sorted(loaded.ops[plane], key=lambda e: e.start):
        at = next((s for s in spans if s[0] <= e.start < s[1]), None)
        if at is None:
            continue
        ops.append(e)
        found = metadata.get(plane, {}).get((at[2], e.name))
        if found is not None:
            tf_op[at[2], e.name] = found[0]

    def rows(events):
        return [[e.name, round((e.start - lo) * 1e9), round(e.dur * 1e9)]
                for e in events]

    kept = {"plane": plane, "seconds": seconds, "modules": rows(mods),
            "ops": rows(ops),
            "tf_op": [[p, own, op] for (p, own), op in tf_op.items()]}
    kept["expect"] = expect(kept)
    return kept


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("logdir")
    ap.add_argument("--seconds", type=float, default=0.25)
    ap.add_argument("--lead", type=float, default=0.05)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    from bench.reduce import xplane

    path = args.logdir if args.logdir.endswith(".pb") \
        else xplane.find_trace(args.logdir)
    kept = cut(path, args.seconds, args.lead)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with gzip.open(args.out, "wt", encoding="utf-8") as f:
        json.dump(kept, f, separators=(",", ":"))
    print(f"kept {len(kept['modules'])} executions, {len(kept['ops'])} "
          f"operations in {args.out}: {os.path.getsize(args.out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
