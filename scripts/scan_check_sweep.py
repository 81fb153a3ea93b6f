#!/usr/bin/env python3
"""The reference check of `jamba2-3b.manychat-closed` at the CELL's sizes,
over many seeds in one process on the chip: the two readings that
`logprob_atol_nats` of bench/configs/jamba2-3b-int4.json lies between.
`hybrid_check_sweep.py`'s sibling for a hybrid without experts (no router to
hold). The entry's own check is a 250-token prompt; this one sends prompts of
`--prompts` tokens (250 and 1000: a prefill bucket of 256 and one of 1024,
eight blocks of the scan kernel) and decodes 9 tokens through them.

For each seed and prompt length: weights from the seed
(`bench/weights.make_params`), the cell's own engine
(`bench/entries/engine.Driver`: one is built, the next seed's parameters are
put in its place), a seeded prompt, 9 new tokens, greedy, and then
 * the PROGRAM's reading: the engine's chosen-token logprobs against the
   float32 reference's log-softmax over the same sequence, |diff| in nats at
   each of the 9 positions (what `Driver.check` compares, by the worst);
 * the reading of the PRECISION BELOW: the same reference with both inputs
   of every matrix product and of the scan's products rounded to float8_e4m3
   (`rnd=`), against the float32 reference at the same 9 positions. It has to
   come out not correct;
 * with `--state`, the reference with the scan and its state in bfloat16
   (`state_dtype=`), the same way.

    chiprun -- python3 scripts/scan_check_sweep.py --first 2147485301 --n 8

Prints one line a seed and length and a summary; exit code 1 if a program's
reading is not finite or a float8 control reads UNDER the program on its
seed. `--rehearse`: the files' rehearsal sizes on the CPU."""

from __future__ import annotations

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default="jamba2-3b.manychat-closed")
    ap.add_argument("--first", type=int, default=2147485301)
    ap.add_argument("--n", type=int, default=8)
    ap.add_argument("--prompts", type=int, nargs="*", default=[250, 1000])
    ap.add_argument("--state", action="store_true",
                    help="also the reference with a bfloat16 scan")
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
        args.prompts = [40, 100]
    hf, qtype = cells.as_run(cell.config), cell.config["bench"]["qtype"]
    cfg = ModelConfig.from_hf_config(hf)
    ref = cell.reference()
    n_new = 9

    def fp8(x):
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    plain = jax.jit(ref.logits, static_argnums=(0, 3))
    low = jax.jit(lambda h, p, t, n: ref.logits(h, p, t, n, rnd=fp8),
                  static_argnums=(0, 3))
    half = jax.jit(
        lambda h, p, t, n: ref.logits(h, p, t, n, state_dtype=jnp.bfloat16),
        static_argnums=(0, 3))

    def logprobs(logits, toks):
        logits = np.asarray(logits, np.float64)
        top = logits.max(-1)
        lse = np.log(np.exp(logits - top[:, None]).sum(-1)) + top
        return logits[np.arange(len(toks)), toks] - lse

    driver, rows, bad = None, [], 0
    for seed in range(args.first, args.first + args.n):
        if driver is not None:  # keep one set of weights on the chip
            driver.engine.model.params = None
        params = jax.block_until_ready(weights.make_params(cfg, seed, qtype))
        if driver is None:
            driver = cell.entry().Driver(cell, TpuModel(cfg, params, qtype),
                                         time.perf_counter)
            served = driver.engine.model.params  # with the kernels' bits
        else:
            from bigdl_tpu.models.llama import prepare_kernel_scales

            served = prepare_kernel_scales(cfg, params)
            driver.engine.model.params = served
        for n_prompt in args.prompts:
            t = time.perf_counter()
            prompt = np.random.default_rng(seed + n_prompt).integers(
                1, hf["vocab_size"], n_prompt).tolist()
            r = driver._submit(Planned(0.0, prompt, n_new), None)
            if not driver._wait_done([r], time.perf_counter() + 1100):
                print(f"seed {seed}: the request did not finish", flush=True)
                bad += 1
                continue
            toks = list(r.handle.out_tokens)
            got = np.asarray(r.handle.out_logprobs, np.float64)
            seq = jnp.asarray(prompt + toks[:-1], jnp.int32)
            want = logprobs(plain(Frozen(hf), params, seq, n_new), toks)
            prog = np.abs(got - want)
            below = np.abs(
                logprobs(low(Frozen(hf), params, seq, n_new), toks) - want)
            state = (np.abs(logprobs(half(Frozen(hf), params, seq, n_new),
                                     toks) - want)
                     if args.state else np.zeros(n_new))
            bad += not np.all(np.isfinite(got)) or below.max() <= prog.max()
            rows.append((n_prompt, prog.max(), np.median(prog), below.max(),
                         np.median(below), state.max(), np.median(state)))
            print(f"seed {seed} prompt {n_prompt}: program worst "
                  f"{prog.max():.4f} median {np.median(prog):.4f} "
                  f"[{' '.join(f'{x:.3f}' for x in prog)}]; float8 reference "
                  f"worst {below.max():.4f} median {np.median(below):.4f} "
                  f"[{' '.join(f'{x:.2f}' for x in below)}]"
                  + (f"; bfloat16 scan worst {state.max():.4f} median "
                     f"{np.median(state):.4f}" if args.state else "")
                  + f" ({time.perf_counter() - t:.0f} s)", flush=True)
        del params, served
    problems = driver.finish() if driver is not None else []
    for n_prompt in args.prompts:
        a = np.asarray([r[1:] for r in rows if r[0] == n_prompt])
        if not len(a):
            continue
        print(f"prompt {n_prompt}, {len(a)} seeds: program worst-of-9 "
              f"{a[:, 0].min():.4f} .. {a[:, 0].max():.4f} (median position "
              f"{a[:, 1].min():.4f} .. {a[:, 1].max():.4f}); float8 "
              f"reference worst-of-9 {a[:, 2].min():.4f} .. "
              f"{a[:, 2].max():.4f} (median position {a[:, 3].min():.4f} .. "
              f"{a[:, 3].max():.4f})"
              + (f"; bfloat16 scan worst-of-9 {a[:, 4].min():.4f} .. "
                 f"{a[:, 4].max():.4f}" if args.state else ""), flush=True)
    print(f"problems at the end: {problems}", flush=True)
    return 1 if bad or problems else 0


if __name__ == "__main__":
    sys.exit(main())
