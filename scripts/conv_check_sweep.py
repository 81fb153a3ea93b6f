#!/usr/bin/env python3
"""The reference check of `lfm2-24b-a2b.manydocs-closed` at the CELL's sizes,
over many seeds in one process on the chip: the two readings that
`logprob_atol_nats` of bench/configs/lfm2-24b-a2b-int4.json lies between, the
router's margins, and three faults PLANTED in the program's path.
`scan_check_sweep.py`'s sibling for a hybrid whose state is a convolution's
tail and whose experts are routed.

For each seed and prompt length: weights from the seed
(`bench/weights.make_params`), the cell's own engine
(`bench/entries/engine.Driver`: one is built, the next seed's parameters are
put in its place), a seeded prompt, 9 new tokens, greedy, and then
 * the PROGRAM's reading: the engine's chosen-token logprobs against the
   float32 reference's log-softmax over the same sequence, |diff| in nats at
   each of the 9 positions (what `Driver.check` compares, by the worst), and
   how the program's expert choices sit with the reference's own router (the
   share of decisions that differ, the worst deficit under its k-th best);
 * the reading of the PRECISION BELOW: the same reference with both inputs
   of every matrix product rounded to float8_e4m3 (`rnd=`), against the
   float32 reference at the same 9 positions. It has to come out not correct;
 * on the first `--faults` seeds, each of `FAULTS` planted in the program
   (`planted`: the served forward has no switch for them), served by a small
   engine of its own traced with the fault in, the same way: does a logprob
   at these weights SEE it? (The drawn convolution is 0.02 * N(0, 1), so `c`
   is small; what a logprob cannot see here is held by tests/test_lfm2_moe.py,
   which draws its own weights.)

    chiprun -- python3 scripts/conv_check_sweep.py --first 2147485301 --n 6

Prints one line a seed and length and a summary; exit code 1 if a program's
reading is not finite or a float8 control reads UNDER the program on its
seed. `--rehearse`: the files' rehearsal sizes on the CPU."""

from __future__ import annotations

import argparse
import contextlib
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _faults() -> dict:
    """name -> (module, the function of it the fault replaces, by what)."""
    import jax.numpy as jnp

    from bigdl_tpu import kvhybrid
    from bigdl_tpu.models import lfm2_moe
    from bigdl_tpu.ops.attention import pair_queries as pair

    conv, parts = kvhybrid._conv_from_tail, lfm2_moe.gate_parts

    def no_hand_over(tail, u, w, b, end, one_token):
        """A prefill leaves zeros where its last inputs belong: the first
        decode steps convolve with nothing behind them."""
        out, tail = conv(tail, u, w, b, end, one_token)
        return out, tail if one_token else jnp.zeros_like(tail)

    def gates_exchanged(bcx):
        B, C, x = parts(bcx)
        return C, B, x

    def halves_exchanged(q, n_kv):
        """The query heads of KV heads 0 and 1 on each other's lanes: they
        meet the other head's keys, and keep their own half's values."""
        out, G = pair(q, n_kv), q.shape[-2] // n_kv
        return jnp.concatenate(
            [jnp.roll(out[..., :2 * G, :], q.shape[-1], axis=-1),
             out[..., 2 * G:, :]], axis=-2)

    return {"tail dropped at the hand-over": (
                kvhybrid, "_conv_from_tail", no_hand_over),
            "B and C exchanged": (lfm2_moe, "gate_parts", gates_exchanged),
            "a pair's halves exchanged": (
                lfm2_moe, "pair_queries", halves_exchanged)}


#: the faults this script plants (tests/test_lfm2_moe.py plants them too)
FAULTS = ("tail dropped at the hand-over", "B and C exchanged",
          "a pair's halves exchanged")


@contextlib.contextmanager
def planted(name: str):
    """The program with the fault `name` in it, for the programs TRACED
    inside the block (a compiled program keeps what it was traced with)."""
    module, attr, broken = _faults()[name]
    whole = getattr(module, attr)
    setattr(module, attr, broken)
    try:
        yield
    finally:
        setattr(module, attr, whole)


def main(cell: str = "lfm2-24b-a2b.manydocs-closed", first: int = 2147485301,
         faults: tuple = FAULTS, plant=planted,
         sparse_layers=lambda hf: (hf["num_hidden_layers"]
                                   - hf["num_dense_layers"])) -> int:
    """`scripts/delta_check_sweep.py` is this sweep with another cell, its
    own faults and every layer sparse."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default=cell)
    ap.add_argument("--first", type=int, default=first)
    ap.add_argument("--n", type=int, default=6)
    ap.add_argument("--prompts", type=int, nargs="*", default=[250, 1000])
    ap.add_argument("--faults", type=int, default=2,
                    help="seeds that also run the planted faults")
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
    from bigdl_tpu.models.llama import prepare_kernel_scales
    from bigdl_tpu.serving.engine import InferenceEngine
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
    L_moe, k = sparse_layers(hf), hf["num_experts_per_tok"]

    def fp8(x):
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    plain = jax.jit(ref.logits, static_argnums=(0, 3))
    low = jax.jit(lambda h, p, t, n: ref.logits(h, p, t, n, rnd=fp8),
                  static_argnums=(0, 3))

    def margins(h, p, t):  # the program's choices at the reference's router
        chosen = jax.pure_callback(
            lambda tt: ref._program_choice(tt, L_moe, k),
            jax.ShapeDtypeStruct((L_moe, t.shape[0], k), jnp.int32), t)
        with jax.default_matmul_precision("highest"):
            _, n_differ, deficit = ref.hidden(h, p, t, chosen)
        return n_differ, deficit, jnp.all(chosen >= 0)

    margins = jax.jit(margins, static_argnums=(0,))

    def logprobs(logits, toks):
        logits = np.asarray(logits, np.float64)
        top = logits.max(-1)
        lse = np.log(np.exp(logits - top[:, None]).sum(-1)) + top
        return logits[np.arange(len(toks)), toks] - lse

    geo = cell.config["bench"]["engine"]
    small = dict(n_slots=4, max_len=max(args.prompts) + 64 + (
        -(max(args.prompts) + 64) % geo["page_size"]), paged=True,
        page_size=geo["page_size"])
    small["n_pages"] = 4 * small["max_len"] // geo["page_size"] + 1
    broken = {}  # fault -> its engine, traced with the fault in
    kept = []  # the newest request: the reference finds its expert choices
    # through a WEAK reference (`serving.engine.last_routed_request`), and
    # a request nobody holds is compared free

    def serve_small(eng, prompt):
        r = eng.submit(prompt, max_new_tokens=n_new)
        eng.run_until_idle()
        kept[:] = [r]
        return list(r.out_tokens), np.asarray(r.out_logprobs, np.float64)

    driver, rows, seen, bad = None, [], {f: [] for f in faults}, 0
    for i, seed in enumerate(range(args.first, args.first + args.n)):
        if driver is not None:  # keep one set of weights on the chip
            driver.engine.model.params = None
            for eng in broken.values():
                eng.model.params = None
        params = jax.block_until_ready(weights.make_params(cfg, seed, qtype))
        if driver is None:
            driver = cell.entry().Driver(cell, TpuModel(cfg, params, qtype),
                                         time.perf_counter)
            served = driver.engine.model.params  # with the kernels' bits
        else:
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
            n_differ, deficit, given = margins(Frozen(hf), params, seq)
            want = logprobs(plain(Frozen(hf), params, seq, n_new), toks)
            prog = np.abs(got - want)
            below = np.abs(
                logprobs(low(Frozen(hf), params, seq, n_new), toks) - want)
            flips = float(n_differ) / (L_moe * len(seq))
            bad += not np.all(np.isfinite(got)) or below.max() <= prog.max()
            rows.append((n_prompt, prog.max(), np.median(prog), below.max(),
                         np.median(below), flips, float(deficit)))
            print(f"seed {seed} prompt {n_prompt}: program worst "
                  f"{prog.max():.4f} median {np.median(prog):.4f} "
                  f"[{' '.join(f'{x:.3f}' for x in prog)}]; float8 reference "
                  f"worst {below.max():.4f} median {np.median(below):.4f} "
                  f"[{' '.join(f'{x:.2f}' for x in below)}]; router: "
                  f"{'choices given' if bool(given) else 'NO choices'}, "
                  f"{100 * flips:.2f}% of decisions differ, worst deficit "
                  f"{float(deficit):.4f} ({time.perf_counter() - t:.0f} s)",
                  flush=True)
        if i < args.faults:
            n_prompt = args.prompts[0]
            prompt = np.random.default_rng(seed + n_prompt).integers(
                1, hf["vocab_size"], n_prompt).tolist()
            for fault in faults:
                if fault not in broken:
                    with plant(fault):
                        eng = InferenceEngine(TpuModel(cfg, served, qtype),
                                              **small)
                        toks, got = serve_small(eng, prompt)  # traced here
                    broken[fault] = eng
                else:
                    broken[fault].model.params = served
                    toks, got = serve_small(broken[fault], prompt)
                seq = jnp.asarray(prompt + toks[:-1], jnp.int32)
                want = logprobs(plain(Frozen(hf), params, seq, n_new), toks)
                d = np.abs(got - want)
                seen[fault].append(d.max())
                print(f"seed {seed} prompt {n_prompt}, planted '{fault}': "
                      f"worst {d.max():.4f} median {np.median(d):.4f} "
                      f"[{' '.join(f'{x:.3f}' for x in d)}]", flush=True)
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
              f"{a[:, 3].max():.4f}); decisions that differ "
              f"{100 * a[:, 4].min():.2f} .. {100 * a[:, 4].max():.2f}%, "
              f"worst deficit {a[:, 5].min():.4f} .. {a[:, 5].max():.4f}",
              flush=True)
    for fault, worst in seen.items():
        if worst:
            print(f"planted '{fault}', {len(worst)} seeds: worst-of-9 "
                  f"{min(worst):.4f} .. {max(worst):.4f}", flush=True)
    print(f"problems at the end: {problems}", flush=True)
    return 1 if bad or problems else 0


if __name__ == "__main__":
    sys.exit(main())
