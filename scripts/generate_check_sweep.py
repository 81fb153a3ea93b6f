#!/usr/bin/env python3
"""The reference check of `mistral-7b.generate-1024-128` alone, over many
seeds in one process on the chip: how near the cell's `correct` sits to its
bound `greedy_gap_atol`, seed by seed. The check is `bench/entries/generate.py`'s
own (`Driver.check`), the weights `bench/weights.make_params`'s, nothing is
re-implemented here.

    chiprun -- python3 scripts/generate_check_sweep.py --root .bench_checkout/parent \
        --first 2147484301 --n 30

`--root` is the checkout whose `bigdl_tpu` and `bench` are used (`.` for the
tree). Prints one line a seed and a summary line; exit code 0 whatever the
checks say."""

from __future__ import annotations

import argparse
import os
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=".")
    ap.add_argument("--cell", default="mistral-7b.generate-1024-128")
    ap.add_argument("--first", type=int, default=2147484301)
    ap.add_argument("--n", type=int, default=30)
    ap.add_argument("--rehearse", action="store_true",
                    help="the files' rehearsal sizes on the CPU: tries this "
                         "script, says nothing of the cell")
    args = ap.parse_args()
    root = os.path.abspath(args.root)
    sys.path.insert(0, root)
    if args.rehearse:
        os.environ["BIGDL_TPU_PALLAS"] = "interpret"

    import jax

    from bench import cells, weights
    from bigdl_tpu.api import TpuModel
    from bigdl_tpu.models.config import ModelConfig
    from bigdl_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    print(f"root {root}; device {jax.devices()[0].device_kind}", flush=True)
    cell = cells.resolve(args.cell, root)
    if args.rehearse:
        from bench.run import merge

        cell.config = merge(cell.config, cell.config["bench"].get("rehearsal"))
        cell.traffic = merge(cell.traffic, cell.traffic.get("rehearsal"))
    hf, qtype = cells.as_run(cell.config), cell.config["bench"]["qtype"]
    model_cfg = ModelConfig.from_hf_config(hf)
    shapes = cell.generator().shapes(cell.traffic)
    bad = []
    for seed in range(args.first, args.first + args.n):
        t = time.perf_counter()
        params = jax.block_until_ready(
            weights.make_params(model_cfg, seed, qtype))
        driver = cell.entry().Driver(cell, TpuModel(model_cfg, params, qtype),
                                     time.perf_counter)
        driver.n_prompt = shapes["prompt_lengths"][0]
        driver.max_new = shapes["max_output"]
        ok, msg = driver.check(cell, hf, params, seed)
        if not ok:
            bad.append(seed)
        print(f"seed {seed} ok={ok} {time.perf_counter() - t:.1f} s: {msg}",
              flush=True)
        del driver, params
    print(f"{len(bad)} of {args.n} seeds not correct: {bad}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
