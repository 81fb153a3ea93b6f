#!/usr/bin/env python3
"""Run one cell traced, on the chip, and keep a few dozen of its decode steps
as a fixture for bench/reduce/steps.py: the engine track's spans, the modules
line of the first chip and the annotations that mirror the step's phases onto
the host plane, cut to `--steps` steps: the first stretch from the `--skip`-th
step paired on that holds an admission. The
operations line is not kept (1500 events a step); the seconds its union
covers inside the cut are, with what steps.py reads on the cut, as `expect`.

    python bench/tools/keep_steps.py --workload mistral-7b.chat-steady \
        --seed 7 --seconds 50 --out chiprun_out/v5e_step_gaps.json.gz

The run itself is `bench/run.py --trace 1`, in this process; its result line
is printed as ever.
"""

from __future__ import annotations

import argparse
import dataclasses
import gzip
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

SPANS = ("engine.step", "step.reap", "step.admit", "step.pages",
         "decode_step", "decode.dispatch", "decode.args", "decode.call",
         "decode.fetch", "decode.wait", "decode.read", "step.emit")


def cut(run, annotations, skip: int, n: int) -> dict:
    from bench.records import Run
    from bench.reduce import steps
    from bench.reduce.xplane import Loaded, Reduced
    from bench.stats import interval_union

    dev = run.device
    paired = steps.build(run, annotations).steps
    if len(paired) < skip + n:
        raise SystemExit(f"only {len(paired)} steps paired")
    plane, mods = next(iter(dev.loaded.modules.items()))
    admissions = [e.start for e in mods if "engine_" in e.name
                  and steps.PROGRAM not in e.name]
    # the first stretch of `n` steps from the `skip`-th on that holds an
    # admission, so that the cut has a gap to leave out
    first = next((i for i in range(skip, len(paired) - n + 1) if any(
        paired[i].exec_end < t < paired[i + n - 1].exec_start
        for t in admissions)), skip)
    paired = paired[first:first + n]
    # from a little before the first step's execution to a little after
    # the last one's: the cut's own edges lie inside gaps
    lo, hi = paired[0].exec_start - 1e-4, paired[-1].exec_end + 1e-4
    mods = [e for e in mods if lo <= e.start and e.start + e.dur <= hi]
    busy = sum(max(min(b, hi) - max(a, lo), 0.0) for a, b in interval_union(
        (e.start, e.start + e.dur) for e in dev.loaded.ops[plane]))
    # the spans of every `engine.step` that touches the cut, whole
    a, b = (lo + dev.offset) * 1e6, (hi + dev.offset) * 1e6
    whole = [(e["ts"], e["ts"] + e["dur"]) for e in run.spans
             if e.get("ph") == "X" and e["name"] == "engine.step"
             and e["ts"] + e["dur"] > a and e["ts"] < b]
    a, b = min(w[0] for w in whole), max(w[1] for w in whole)
    spans = [{k: e[k] for k in ("name", "ph", "tid", "ts", "dur", "args")}
             for e in run.spans if e.get("ph") == "X" and e["tid"] == 0
             and e["name"] in SPANS and a <= e["ts"]
             and e["ts"] + e["dur"] <= b]
    seqs = {e["args"]["seq"] for e in spans if e["name"] == "decode_step"}
    kept = {"modules": {plane: [dataclasses.asdict(e) for e in mods]},
            "sync": dev.loaded.sync, "t_sync": dev.loaded.sync + dev.offset,
            "begin": lo + dev.offset, "end": hi + dev.offset, "spans": spans,
            "annotations": [x for x in annotations if x[1] in seqs]}
    # what steps.py reads on the cut alone, as the test will build it
    ld = Loaded({}, {plane: mods}, kept["sync"], {})
    small = Reduced(ld, kept["t_sync"], kept["begin"], kept["end"])
    small.busy_s = busy
    acc = steps.build(
        Run(cell=run.cell, hf={}, peak={}, t0=0.0, t1=1e12, requests=[],
            spans=spans, device=small), kept["annotations"])
    kept["expect"] = dict(
        busy_s=busy, steps=len(acc.steps), steady_gaps=len(acc.gaps),
        shift_ms=acc.shift_s * 1e3, device_gap_ms_p50=acc.median_ms("gap"),
        **{s + "_ms_p50": acc.median_ms(s) for s in steps.SHARES})
    print("\n".join(["the cut:"] + acc.lines()), flush=True)
    return kept


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--skip", type=int, default=20)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    from bench import run as bench_run
    from bench.reduce import steps

    seen, build = {}, steps.build

    def spy(run, annotations=()):
        seen["run"], seen["annotations"] = run, list(annotations)
        return build(run, annotations)

    steps.build = spy  # `steps.account` hands the readers' run over
    try:
        code = bench_run.main(["--workload", args.workload, "--seed",
                               str(args.seed), "--seconds",
                               str(args.seconds), "--trace", "1"])
    finally:
        steps.build = build
    if "run" not in seen:
        raise SystemExit("no reader built the step account: nothing to keep")
    kept = cut(seen["run"], seen["annotations"], args.skip, args.steps)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with gzip.open(args.out, "wt", encoding="utf-8") as f:
        json.dump(kept, f, separators=(",", ":"))
    print(f"kept {args.steps} steps in {args.out}: "
          f"{os.path.getsize(args.out)} bytes")
    return code


if __name__ == "__main__":
    sys.exit(main())
