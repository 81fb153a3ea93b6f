#!/usr/bin/env python3
"""The reference check of `glm-4.7-flash.longctx-closed` at the CELL's sizes,
over many seeds in one process on the chip: the readings that
`logprob_atol_nats` of bench/configs/glm-4.7-flash-int4.json and the two
limits of bench/reference/glm4_moe_lite.py lie between. The entry's own check
is a 250-token prompt (4 pages); this one sends prompts of `--prompts`
tokens (1024 and 4096: 16 to 65 pages, the expanded prefill at its real
widths) and decodes 9 tokens through them.

For each seed and prompt length: weights from the seed
(`bench/weights.make_params`), the cell's own engine
(`bench/entries/engine.Driver`: one is built, the next seed's parameters are
put in its place), a seeded prompt, 9 new tokens, greedy, and then
 * the PROGRAM's reading: the engine's chosen-token logprobs against the
   float32 reference's log-softmax over the same sequence AT the program's
   expert choice, |diff| in nats at each of the 9 positions (what
   `Driver.check` compares, by the worst);
 * the ROUTER's reading: over the sequence's (layer, position) decisions,
   how far the program's chosen experts lie under the reference's own k-th
   best (score + bias, score units) and in what share of decisions the
   program's experts are not the reference's own top-k;
 * the reading of the PRECISION BELOW: the same reference with both inputs
   of every matrix product rounded to float8_e4m3 (`rnd=`), against the
   float32 reference, at the same 9 positions. It has to come out not
   correct.

    chiprun -- python3 scripts/latent_check_sweep.py --first 2147485001 --n 8

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
    ap.add_argument("--cell", default="glm-4.7-flash.longctx-closed")
    ap.add_argument("--first", type=int, default=2147485001)
    ap.add_argument("--n", type=int, default=8)
    ap.add_argument("--prompts", type=int, nargs="*", default=[1024, 4096])
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
    k = hf["num_experts_per_tok"]

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

    def deficits(hf_, params, tokens, chosen, low):
        """Per expert layer: (worst deficit of a chosen expert under the
        reference's own k-th best, decisions that differ, decisions over
        the margin, the trajectory's own top-k), on the reference's hidden
        states at the choices it admits; `low` puts every matrix product of
        the trajectory at float8 (then `chosen` is -1 and the own top-k is
        what a program of the precision below would have chosen)."""
        rnd = fp8 if low else ref._same
        with jax.default_matmul_precision("highest"):
            eps = hf_["rms_norm_eps"]
            h = params["embed"][tokens].astype(jnp.float32)

            def dense_layer(h, p):
                h = h + ref._attention(hf_, ref._rms(
                    h, ref.dense(p["attn_norm"]), eps), p, rnd)
                x = ref._rms(h, ref.dense(p["mlp_norm"]), eps)
                return h + ref._swiglu(
                    x, ref.dense(p["w_gate"]), ref.dense(p["w_up"]),
                    ref.dense(p["w_down"]), rnd), None

            def moe_layer(h, xs):
                p, c = xs
                h = h + ref._attention(hf_, ref._rms(
                    h, ref.dense(p["attn_norm"]), eps), p, rnd)
                x = ref._rms(h, ref.dense(p["mlp_norm"]), eps)
                score = jax.nn.sigmoid(rnd(x) @ rnd(ref.dense(p["router"]).T))
                biased = score + p["e_bias"].astype(jnp.float32)[None]
                kth = jnp.sort(biased, axis=-1)[:, -k]
                mine = jnp.take_along_axis(biased, jnp.maximum(c, 0), -1)
                deficit = jnp.max(kth[:, None] - mine, -1)  # [T], >= 0
                _, own = jax.lax.top_k(biased, k)
                differ = jnp.any(jnp.sort(c, -1) != jnp.sort(own, -1), -1)
                y, _ = ref._moe(hf_, x, p, c, rnd)
                return h + y, (jnp.max(deficit), jnp.sum(differ),
                               jnp.sum(deficit > ref.ROUTER_TIE), own)

            h, _ = jax.lax.scan(dense_layer, h, params["layers"])
            _, out = jax.lax.scan(moe_layer, h,
                                  (params["moe_layers"], chosen))
            return out

    deficits_j = jax.jit(deficits, static_argnums=(0, 4))

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
        for n_prompt in args.prompts:
            t = time.perf_counter()
            prompt = np.random.default_rng(seed + n_prompt).integers(
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
            chosen = r.handle.expert_ids(len(seq))
            worst_def = n_differ = n_over = float("nan")
            n_dec = (hf["num_hidden_layers"] - hf["first_k_dense_replace"]
                     ) * len(seq)
            if chosen is not None:
                d, nd, no, _ = deficits_j(Frozen(hf), params, seq,
                                          jnp.asarray(chosen, jnp.int32),
                                          False)
                worst_def, n_differ = float(jnp.max(d)), int(jnp.sum(nd))
                n_over = int(jnp.sum(no))
            # the router of the precision below: what a float8 trajectory
            # chooses, held to the float32 reference's router the same way
            own8 = deficits_j(Frozen(hf), params, seq, jnp.full(
                (n_dec // len(seq), len(seq), k), -1, jnp.int32), True)[3]
            d8, nd8, no8, _ = deficits_j(Frozen(hf), params, seq, own8, False)
            low_router = (float(jnp.max(d8)), int(jnp.sum(no8)),
                          int(jnp.sum(nd8)) / n_dec)
            rows.append((n_prompt, prog.max(), np.median(prog), below.max(),
                         np.median(below), worst_def, n_differ / n_dec,
                         low_router[0], low_router[2]))
            print(f"seed {seed} prompt {n_prompt}: program worst "
                  f"{prog.max():.4f} median {np.median(prog):.4f} "
                  f"[{' '.join(f'{x:.2f}' for x in prog)}]; router: worst "
                  f"deficit {worst_def:.4f} score units, {n_over} over the "
                  f"margin, {n_differ} of {n_dec} decisions differ "
                  f"({100 * n_differ / n_dec:.2f}%); a float8 trajectory's "
                  f"router: worst deficit {low_router[0]:.4f}, "
                  f"{low_router[1]} over the margin, "
                  f"{100 * low_router[2]:.2f}% differ; float8 reference worst "
                  f"{below.max():.4f} median {np.median(below):.4f} "
                  f"[{' '.join(f'{x:.2f}' for x in below)}] "
                  f"({time.perf_counter() - t:.0f} s)", flush=True)
        del params
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
              f"{a[:, 3].max():.4f}); router worst deficit "
              f"{np.nanmin(a[:, 4]):.4f} .. {np.nanmax(a[:, 4]):.4f} score "
              f"units, share of decisions that differ "
              f"{100 * np.nanmin(a[:, 5]):.2f} .. "
              f"{100 * np.nanmax(a[:, 5]):.2f}%; a float8 trajectory's "
              f"router: worst deficit {a[:, 6].min():.4f} .. "
              f"{a[:, 6].max():.4f}, share that differs "
              f"{100 * a[:, 7].min():.2f} .. {100 * a[:, 7].max():.2f}%",
              flush=True)
    print(f"problems at the end: {problems}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
