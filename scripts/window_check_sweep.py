#!/usr/bin/env python3
"""The reference check of `smallthinker-21ba3b.mixedlen-closed` at the
CELL's sizes and through the cell's own engine and pools, over several seeds
in one process on the chip, at lengths where the WINDOW BINDS: the readings
that `logprob_atol_nats` of bench/configs/smallthinker-21ba3b-int4.json and
the two limits of bench/reference/smallthinker.py lie between. The entry's
own check is a 250-token prompt, where a window of 4096 never binds and no
page is freed; this one sends prompts of `--prompts` tokens (2048: inside
the window; 4136: forty past it, so that the prefill books the window's
pages only and 80 decoded tokens carry `pos - window` past page 0's last
position, which frees it mid-way; 8192: two windows, 65 of 129 window pages
booked) and decodes `--new` tokens through them (9, and 80 for the prompt
of 4136).

For each seed and (prompt, new tokens): weights from the seed
(`bench/weights.make_params`), the cell's own engine
(`bench/entries/engine.Driver`: one is built, the next seed's parameters are
put in its place), a seeded prompt, greedy, and then
 * the PROGRAM's reading: the engine's chosen-token logprobs against the
   float32 reference's log-softmax over the same sequence AT the program's
   expert choice, |diff| in nats at each new position (what `Driver.check`
   compares, by the worst);
 * the ROUTER's reading: over the sequence's (layer, position) decisions,
   how far the program's chosen experts lie under the reference's own k-th
   best (router-logit units) and in what share of decisions the program's
   experts are not the reference's own top-k;
 * the reading of the PRECISION BELOW: the same reference with both inputs
   of every matrix product rounded to float8_e4m3 (`rnd=`), compared free,
   against the float32 reference at the same positions, and its own
   router's readings held to the float32 reference's the same way. It has to
   come out not correct;
 * the window pages the slot held at the end and those it freed on the way.

    chiprun -- python3 scripts/window_check_sweep.py --first 2147485201 --n 3

`--cell laguna-xs.2.mixedlen-closed` (PR 47) is the same walk for the other
family on two groups of pages, whose window is 512: `--prompts 1024 4136 4136
8192 --new 9 9 80 9` there, and `--prompts 250 --new 9` for the entry's own
check length (bench/configs/laguna-xs.2-int4.json quotes both tables).

Prints one line a seed and length and a summary; exit code 0 whatever the
readings say. `--rehearse`: the files' rehearsal sizes on the CPU."""

from __future__ import annotations

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default="smallthinker-21ba3b.mixedlen-closed")
    ap.add_argument("--first", type=int, default=2147485201)
    ap.add_argument("--n", type=int, default=3)
    ap.add_argument("--prompts", type=int, nargs="*",
                    default=[2048, 4136, 4136, 8192])
    ap.add_argument("--new", type=int, nargs="*", default=[9, 9, 80, 9])
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
        args.prompts, args.new = [24, 40, 40, 100], [9, 9, 30, 9]
    hf, qtype = cells.as_run(cell.config), cell.config["bench"]["qtype"]
    cfg = ModelConfig.from_hf_config(hf)
    ref = cell.reference()
    # the record's shape: a reference whose sparse layers are not all the
    # layers says so (bench/reference/laguna.py), SmallThinker's is its keys'
    L, k = (ref.choice_shape(hf) if hasattr(ref, "choice_shape") else
            (hf["num_hidden_layers"], hf["moe_num_active_primary_experts"]))
    cases = list(zip(args.prompts, args.new))

    def fp8(x):
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    def walk(hf_, params, tokens, chosen, low, n_new):
        """(logits of the last `n_new` positions, decisions in which `chosen`
        differs from the walk's own router, the worst deficit, the walk's
        own top-k [L, T, k]) along the reference's hidden states at the
        choices it admits; `low`: every matrix product at float8."""
        rnd = fp8 if low else ref._same
        with jax.default_matmul_precision("highest"):
            h, n_differ, worst, own = ref.hidden(hf_, params, tokens, chosen,
                                                 rnd)
            h = ref._rms(h[-n_new:], ref.dense(params["final_norm"]),
                         hf_["rms_norm_eps"])
            out = ref._head(h, params["lm_head"], rnd)
        return out, n_differ, worst, own

    walk_j = jax.jit(walk, static_argnums=(0, 4, 5))

    def logprobs(logits, toks):
        logits = np.asarray(logits, np.float64)
        top = logits.max(-1)
        lse = np.log(np.exp(logits - top[:, None]).sum(-1)) + top
        return logits[np.arange(len(toks)), toks] - lse

    driver, rows = None, []
    for seed in range(args.first, args.first + args.n):
        if driver is not None:  # two sets of weights do not fit the chip
            driver.engine.model.params = None
        params = jax.block_until_ready(weights.make_params(cfg, seed, qtype))
        if driver is None:
            driver = cell.entry().Driver(cell, TpuModel(cfg, params, qtype),
                                         time.perf_counter)
        else:
            driver.engine.model.params = params
        for n_prompt, n_new in cases:
            t = time.perf_counter()
            freed0 = driver.engine.pages.window_pages_freed
            prompt = np.random.default_rng(seed + n_prompt + n_new).integers(
                1, hf["vocab_size"], n_prompt).tolist()
            r = driver._submit(Planned(0.0, prompt, n_new), None)
            if not driver._wait_done([r], time.perf_counter() + 1100):
                print(f"seed {seed}: the request did not finish", flush=True)
                continue
            toks = list(r.handle.out_tokens)
            got = np.asarray(r.handle.out_logprobs, np.float64)
            seq = jnp.asarray(prompt + toks[:-1], jnp.int32)
            n_dec = L * len(seq)
            free = jnp.full((L, len(seq), k), -1, jnp.int32)
            chosen = r.handle.expert_ids(len(seq))
            chosen = free if chosen is None else jnp.asarray(chosen,
                                                             jnp.int32)
            out, n_differ, worst, _ = walk_j(Frozen(hf), params, seq, chosen,
                                             False, n_new)
            want = logprobs(out, toks)
            # the float8 trajectory, compared free, and what ITS router
            # chooses
            out8, _, _, own8 = walk_j(Frozen(hf), params, seq, free, True,
                                      n_new)
            prog = np.abs(got - want)
            below = np.abs(logprobs(out8, toks) - want)
            # held to the float32 reference's router as the program's
            # choice is
            _, nd8, worst8, _ = walk_j(Frozen(hf), params, seq, own8, False,
                                       n_new)
            freed = driver.engine.pages.window_pages_freed - freed0
            rows.append(((n_prompt, n_new), prog.max(), np.median(prog), below.max(),
                         np.median(below), float(worst),
                         int(n_differ) / n_dec, float(worst8),
                         int(nd8) / n_dec))
            print(f"seed {seed} prompt {n_prompt} new {n_new} (window pages "
                  f"freed while decoding: {freed}): program worst "
                  f"{prog.max():.4f} median {np.median(prog):.4f} "
                  f"[{' '.join(f'{x:.3f}' for x in prog[:12])}]; router: worst "
                  f"deficit {float(worst):.4f} logit units, "
                  f"{int(n_differ)} of {n_dec} decisions differ "
                  f"({100 * int(n_differ) / n_dec:.2f}%); a float8 "
                  f"trajectory's router: worst deficit {float(worst8):.4f}, "
                  f"{100 * int(nd8) / n_dec:.2f}% differ; float8 reference "
                  f"worst {below.max():.4f} median {np.median(below):.4f} "
                  f"[{' '.join(f'{x:.2f}' for x in below[:12])}] "
                  f"({time.perf_counter() - t:.0f} s)", flush=True)
        del params
    problems = driver.finish() if driver is not None else []
    for case in dict.fromkeys(cases):
        a = np.asarray([r[1:] for r in rows if r[0] == case])
        if not len(a):
            continue
        print(f"prompt {case[0]} new {case[1]}, {len(a)} seeds: program "
              f"worst-of-{case[1]} "
              f"{a[:, 0].min():.4f} .. {a[:, 0].max():.4f} (median position "
              f"{a[:, 1].min():.4f} .. {a[:, 1].max():.4f}); float8 "
              f"reference worst-of-{case[1]} {a[:, 2].min():.4f} .. "
              f"{a[:, 2].max():.4f} (median position {a[:, 3].min():.4f} .. "
              f"{a[:, 3].max():.4f}); router worst deficit "
              f"{a[:, 4].min():.4f} .. {a[:, 4].max():.4f} logit units, "
              f"share of decisions that differ {100 * a[:, 5].min():.2f} .. "
              f"{100 * a[:, 5].max():.2f}%; a float8 trajectory's router: "
              f"worst deficit {a[:, 6].min():.4f} .. {a[:, 6].max():.4f}, "
              f"share that differs {100 * a[:, 7].min():.2f} .. "
              f"{100 * a[:, 7].max():.2f}%", flush=True)
    print(f"problems at the end: {problems}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
