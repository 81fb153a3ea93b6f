#!/usr/bin/env python3
"""Run one cell traced, on the chip, and keep a few of its prefills as a
fixture for `bench/metrics/kernel.flash_attn_mfu`: the executions of
`engine_paged_prefill` on the first chip's modules line, the
`flash_attention` events of its operations line inside them (32 a prefill of
some 30,000 operations), and the `prefill` spans around them, cut to the
first `--prefills` prefills the traced seconds hold whole; and what the
reader reads on the cut, as `expect`.

    python bench/tools/keep_prefills.py --workload mistral-7b.longprompt-closed \
        --seed 7 --seconds 50 --out chiprun_out/v5e_longprompt_prefills.json.gz

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

METRIC = "kernel.flash_attn_mfu"


def small_run(kept: dict, cell, hf: dict, peak: dict):
    """The `records.Run` a reader is handed on a cut (the test builds its
    own the same way)."""
    from bench.records import Run
    from bench.reduce.xplane import Event, Loaded, Reduced

    def events(d):
        return {p: [Event(**e) for e in evs] for p, evs in d.items()}

    ld = Loaded(events(kept["ops"]), events(kept["modules"]), kept["sync"], {})
    return Run(cell=cell, hf=hf, peak=peak, t0=0.0, t1=1e12, requests=[],
               spans=kept["spans"],
               device=Reduced(ld, kept["t_sync"], kept["begin"], kept["end"]))


def cut(run, reader, n: int) -> dict:
    from bench.reduce.xplane import _base

    dev = run.device
    plane, mods = next(iter(dev.loaded.modules.items()))
    execs = sorted((m for m in mods if reader.PROGRAM in m.name
                    and dev.begin <= m.start
                    and m.start + m.dur <= dev.end), key=lambda m: m.start)
    if len(execs) < n:
        raise SystemExit(f"only {len(execs)} prefills in the traced seconds")
    execs = execs[:n]
    # the cut's edges lie a little outside its first and last execution
    lo, hi = execs[0].start - 1e-4, execs[-1].start + execs[-1].dur + 1e-4
    ops = [e for e in dev.loaded.ops[plane]
           if _base(e.name) == "flash_attention"
           and any(m.start <= e.start < m.start + m.dur for m in execs)]
    spans = [{k: e[k] for k in ("name", "ph", "tid", "ts", "dur", "args")}
             for e in run.spans if e.get("ph") == "X"
             and e["name"] == "prefill" and any(
                 e["ts"] <= (m.start + m.dur / 2 + dev.offset) * 1e6
                 < e["ts"] + e["dur"] for m in execs)]
    kept = {"modules": {plane: [dataclasses.asdict(m) for m in execs]},
            "ops": {plane: [dataclasses.asdict(e) for e in ops]},
            "sync": dev.loaded.sync, "t_sync": dev.loaded.sync + dev.offset,
            "begin": lo + dev.offset, "end": hi + dev.offset, "spans": spans,
            "hf": run.hf}
    kept["expect"] = {
        "prefills": n, "flash_events": len(ops),
        "flash_s": sum(e.dur for e in ops),
        "prompt_tokens": [s["args"]["prompt_tokens"] for s in spans],
        "mfu": reader.read(small_run(kept, run.cell, run.hf, run.peak))}
    return kept


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--prefills", type=int, default=4)
    ap.add_argument("--out", required=True)
    ap.add_argument("--recorded", default="")
    args = ap.parse_args()

    from bench import cells
    from bench import run as bench_run

    reader = cells.load_module(ROOT, "metrics", METRIC)
    seen, read = {}, reader.read

    def spy(run):
        seen["run"] = run
        return read(run)

    reader.read = spy  # the run the command hands its readers
    try:
        code = bench_run.main(["--workload", args.workload, "--seed",
                               str(args.seed), "--seconds",
                               str(args.seconds), "--trace", "1"])
    finally:
        reader.read = read
    if "run" not in seen or seen["run"].device is None:
        raise SystemExit("the reader was handed no traced run: nothing to keep")
    kept = cut(seen["run"], reader, args.prefills)
    kept["recorded"] = args.recorded
    print(f"the cut: {kept['expect']}", flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with gzip.open(args.out, "wt", encoding="utf-8") as f:
        json.dump(kept, f, separators=(",", ":"))
    print(f"kept {args.prefills} prefills in {args.out}: "
          f"{os.path.getsize(args.out)} bytes")
    return code


if __name__ == "__main__":
    sys.exit(main())
