#!/usr/bin/env python3
"""The reference check of `brumby-14b.reason-closed` alone, over many seeds
in one process on the chip: the two readings that `logprob_atol_nats` of
bench/configs/brumby-14b-int4.json lies between.

For each seed: weights from the seed (`bench/weights.make_params`), the cell's
own engine (`bench/entries/engine.Driver`: one is built, the next seed's
parameters are put in its place), the check's own request (a seeded
250-token prompt, 9 new tokens, greedy), and then
 * the PROGRAM's reading: the engine's chosen-token logprobs against the
   float32 reference's log-softmax over the same sequence, |diff| in nats at
   each of the 9 positions (what `Driver.check` compares, by the worst);
 * the reading of the PRECISION BELOW: the same reference with both inputs
   of every matrix product rounded to float8_e4m3 (`rnd=`), against the
   float32 reference, at the same 9 positions of the same sequence. It has
   to come out not correct.

    chiprun -- python3 scripts/retention_check_sweep.py --first 2147485001 --n 12

Prints one line a seed and a summary; exit code 0 whatever the readings say.
`--rehearse`: the files' rehearsal sizes on the CPU, to try this script."""

from __future__ import annotations

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default="brumby-14b.reason-closed")
    ap.add_argument("--first", type=int, default=2147485001)
    ap.add_argument("--n", type=int, default=12)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["BIGDL_TPU_PALLAS"] = "interpret"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import cells, weights
    from bench.records import Frozen, Planned
    from bigdl_tpu.api import TpuModel
    from bigdl_tpu.models.config import ModelConfig
    from bigdl_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    print(f"device {jax.devices()[0].device_kind}", flush=True)
    cell = cells.resolve(args.cell, ROOT)
    if args.rehearse:
        from bench.run import merge

        cell.config = merge(cell.config, cell.config["bench"].get("rehearsal"))
    hf, qtype = cells.as_run(cell.config), cell.config["bench"]["qtype"]
    cfg = ModelConfig.from_hf_config(hf)
    ref = cell.reference()
    n_new = 9
    n_prompt = min(250, cell.config["bench"]["engine"]["max_len"] // 2)

    def fp8(x):
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    plain = jax.jit(ref.logits, static_argnums=(0, 3))
    low = jax.jit(lambda h, p, s, n: ref.logits(h, p, s, n, rnd=fp8),
                  static_argnums=(0, 3))

    def logprobs(logits, toks):
        logits = np.asarray(logits, np.float64)
        top = logits.max(-1)
        lse = np.log(np.exp(logits - top[:, None]).sum(-1)) + top
        return logits[np.arange(len(toks)), toks] - lse

    driver, rows = None, []
    for seed in range(args.first, args.first + args.n):
        t = time.perf_counter()
        if driver is not None:  # two sets of weights do not fit the chip
            driver.engine.model.params = None
        params = jax.block_until_ready(weights.make_params(cfg, seed, qtype))
        if driver is None:
            driver = cell.entry().Driver(cell, TpuModel(cfg, params, qtype),
                                         time.perf_counter)
        else:
            driver.engine.model.params = params
        prompt = np.random.default_rng(seed).integers(
            1, hf["vocab_size"], n_prompt).tolist()
        r = driver._submit(Planned(0.0, prompt, n_new), None)
        if not driver._wait_done([r], time.perf_counter() + 1100):
            print(f"seed {seed}: the request did not finish", flush=True)
            continue
        toks = list(r.handle.out_tokens)
        got = np.asarray(r.handle.out_logprobs, np.float64)
        seq = jnp.asarray(prompt + toks[:-1], jnp.int32)
        want = logprobs(plain(Frozen(hf), params, seq, n_new), toks)
        want8 = logprobs(low(Frozen(hf), params, seq, n_new), toks)
        prog, below = np.abs(got - want), np.abs(want8 - want)
        rows.append((prog.max(), np.median(prog), below.max(),
                     np.median(below)))
        print(f"seed {seed}: program worst {prog.max():.4f} median "
              f"{np.median(prog):.4f} [{' '.join(f'{d:.2f}' for d in prog)}]"
              f"; float8 reference worst {below.max():.4f} median "
              f"{np.median(below):.4f} "
              f"[{' '.join(f'{d:.2f}' for d in below)}] "
              f"({time.perf_counter() - t:.0f} s)", flush=True)
        del params
    problems = driver.finish() if driver is not None else []
    if rows:
        a = np.asarray(rows)
        print(f"{len(rows)} seeds: program worst-of-9 {a[:, 0].min():.4f} .. "
              f"{a[:, 0].max():.4f} (median position {a[:, 1].min():.4f} .. "
              f"{a[:, 1].max():.4f}); float8 reference worst-of-9 "
              f"{a[:, 2].min():.4f} .. {a[:, 2].max():.4f} (median position "
              f"{a[:, 3].min():.4f} .. {a[:, 3].max():.4f}); problems at the "
              f"end: {problems}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
