#!/usr/bin/env python3
"""Find the knee of an open-loop cell ONCE, on the chip: several arrival
rates in one process after one set-up. The benchmark itself never searches:
the rate found here (times 0.8) is written into the traffic file as a number,
and PERF.md keeps this tool's table.

    python bench/tools/sweep.py --workload mistral-7b.chat-steady \
        --rates 1.5 2 2.5 3 3.5 4 --seconds 30 --seed 1

The knee is the highest rate at which the backlog does not grow: every
request finishes within the drain, and the queue wait of the window's last
third is no longer than that of its middle third by more than a decode step
or two. Each rate starts from an idle engine.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["BIGDL_TPU_PALLAS"] = "interpret"

    from bench import cells, run as bench_run
    from bench.stats import percentile, pooled_gaps, tokens_in_window

    cell = cells.resolve(args.workload, ROOT)
    p = bench_run.prepare(cell, args.seed, False, args.rehearse)
    if cell.traffic["process"]["kind"] == "closed":
        raise SystemExit("a closed loop has no rate to sweep")
    rows = []
    for rate in args.rates:
        traffic = bench_run.merge(cell.traffic,
                                  {"process": {"rate_rps": rate}})
        plan = p.generator.plan(traffic, args.seed, args.seconds,
                                p.hf["vocab_size"])
        t0, t1, reqs, extra = p.driver.run(plan, args.seconds)
        ttft = [r.stamps[0] - r.t_ref for r in reqs if r.stamps] or [0.0]
        k = max(len(reqs) // 3, 1)

        def wait_ms(part):
            w = [(r.handle.admit_ts - r.handle.submit_ts) * 1e3
                 for r in part if r.handle.admit_ts is not None]
            return percentile(w, 50) if w else None

        gaps = pooled_gaps(reqs) or [0.0]
        row = {
            "rate_rps": rate, "attempted": len(reqs),
            "failed": sum(r.failed for r in reqs),
            "ttft_ms_p50": percentile(ttft, 50) * 1e3,
            "ttft_ms_p90": percentile(ttft, 90) * 1e3,
            "queue_wait_ms_p50_thirds": [wait_ms(reqs[:k]),
                                         wait_ms(reqs[k:2 * k]),
                                         wait_ms(reqs[2 * k:])],
            "itl_ms_p50": percentile(gaps, 50) * 1e3,
            "itl_ms_p95": percentile(gaps, 95) * 1e3,
            "output_tokens_per_s": tokens_in_window(reqs, t0, t1) / (t1 - t0),
            "drain_s": extra["drain_s"],
        }
        deadline = time.perf_counter() + 120  # next rate: from an idle engine
        while not p.driver.engine.idle() and time.perf_counter() < deadline:
            time.sleep(0.05)
        rows.append(row)
        print("sweep " + json.dumps(row), flush=True)
    problems = p.driver.finish()
    print(f"sweep done on {p.info}; problems: {problems}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
