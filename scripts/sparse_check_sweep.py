#!/usr/bin/env python3
"""The reference check of `minicpm-sala.longdoc-closed` at the sizes the cell
TIMES, which `bench/entries/engine.py:check` cannot reach: its 250-token
prompt lies under `dense_len` 8192, so `correct` holds the lightning layers,
the gated NoPE attention, the three scalings and the head, and never a
selection (ROADMAP B0d (1); PERF.md 'Left by PR 54' (1)). Here, through the
cell's own engine as `bench/entries/engine.Driver` builds it, over several
seeds in one process on the chip: a seeded prompt of 12288 tokens and 9 new
tokens, the entry's own comparison (the engine's chosen-token logprobs
against `cell.reference().logits` over the same tokens, WORST of 9, held to
`logprob_atol_nats`), the reference taking the program's selection as
bench/reference/minicpm_sala.py says.

For each seed:

 * the PROGRAM. It has to come out correct.
 * the reference's OWN selection in place of the program's (`compared free`):
   a reading, no verdict: what the near-ties would cost.
 * the precision below: the SAME reference with both inputs of every matrix
   product rounded to float8_e4m3, on its own selection, held against the
   float32 reference at the program's tokens by the same statistic. It has
   to come out NOT correct, on every seed.

And on the first `--controls` seeds (all of them by default), three
CONTROLS, each an engine of its own over the same weights with a fault
PLANTED from here (`planted`: `bigdl_tpu.kvsparse`'s own functions wrapped
while the engine's programs are traced; the served forward has no switch for
them): a bfloat16 lightning state, a selection that drops the local window,
pooled keys shifted by one window. Each has to come out NOT correct.

    chiprun --timeout 3400 -- python3 scripts/sparse_check_sweep.py --n 6

Exit code 1 if a program's check fails or a control's passes, else 0.
`--rehearse`: the files' rehearsal sizes on the CPU (a walk of the script;
toy widths tell no precisions apart, so its verdicts decide nothing and it
exits 3)."""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

#: a slot's state row against the float32 scan, as a share of the state's
#: largest entry: between the program's reading and a bfloat16 state's
#: (PERF.md section 6, PR 54)
STATE_TOL = 0.02



def _mix_with_a_rounded_state(cache, layer, q, k, v, *, chunk, decode):
    """`kvsparse.lightning_mix` as the plain scan with the state ROUNDED to
    bfloat16 after every token (`reduce_precision`: a convert there and
    back is excess precision to XLA, and dropped)."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu import kvsparse as ks

    B, T, H, D = q.shape
    q = q.astype(jnp.float32) * D ** -0.5
    k, v = k.astype(jnp.float32), v.astype(jnp.float32)
    lam = jnp.exp(-jnp.asarray(ks.slopes(H)))
    rows, live = cache.state_rows()
    at = jnp.clip(rows, 0, cache.n_rows - 1)
    to = jnp.where(live, at, cache.n_rows)
    h = jnp.where((cache.pos == 0)[:, None, None, None], 0.0,
                  cache.state[layer, at].reshape(B, H, D, D))

    def one(h, xs):
        qt, kt, vt, ok = xs
        y, new = ks.lightning_step(qt, kt, vt, lam, h)
        new = jax.lax.reduce_precision(new, 8, 7)
        return jnp.where(ok[:, None, None, None], new, h), y

    h, y = jax.lax.scan(one, h, tuple(
        jnp.moveaxis(a, 1, 0)
        for a in (q, k, v, ks.valid_positions(cache, T))))
    state = cache.state.at[layer, to].set(h.reshape(B, H * D, D),
                                          mode="drop")
    return jnp.moveaxis(y, 0, 1), dataclasses.replace(cache, state=state)


def _faults() -> dict:
    """name -> (the function of `bigdl_tpu.kvsparse` it replaces, by what)."""
    import jax.numpy as jnp

    from bigdl_tpu import kvsparse as ks

    select = ks.select
    return {
        "bfloat16 state": ("lightning_mix", _mix_with_a_rounded_state),
        "no local window": (
            "forced_blocks", lambda m, cur, sz: m < sz.init_blocks),
        "no initial block": (
            "forced_blocks", lambda m, cur, sz: m >= cur - sz.window_blocks),
        "windows off by one": (
            "select", lambda q, windows, *a: select(
                q, jnp.roll(windows, -1, axis=0), *a)),
    }


#: the controls this script runs (tests/test_minicpm_sala.py plants the
#: fourth, too)
CONTROLS = ("bfloat16 state", "no local window", "windows off by one")


@contextlib.contextmanager
def planted(name: str):
    """`bigdl_tpu.kvsparse` with the fault `name` in it, for the programs
    TRACED inside the block (a compiled program keeps what it was traced
    with)."""
    from bigdl_tpu import kvsparse as ks

    attr, broken = _faults()[name]
    whole = getattr(ks, attr)
    setattr(ks, attr, broken)
    try:
        yield
    finally:
        setattr(ks, attr, whole)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default="minicpm-sala.longdoc-closed")
    ap.add_argument("--first", type=int, default=2147485301)
    ap.add_argument("--n", type=int, default=6)
    ap.add_argument("--prompt", type=int, default=12288)
    ap.add_argument("--new", type=int, default=9)
    ap.add_argument("--controls", type=int, default=None,
                    help="seeds that run the controls too (default: all)")
    ap.add_argument("--no-controls", action="store_true")
    ap.add_argument("--no-float8", action="store_true")
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
    from bigdl_tpu import kvsparse
    from bigdl_tpu.api import TpuModel
    from bigdl_tpu.models.config import ModelConfig
    from bigdl_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    # the chosen blocks beside the counts, for the reference to take: asked
    # before any engine is built (a served engine reports counts alone)
    kvsparse.CACHE_KIND.report_ids = True
    print(f"device {jax.devices()[0].device_kind}", flush=True)
    cell = cells.resolve(args.cell, ROOT)
    n_prompt = args.prompt
    if args.rehearse:
        from bench.run import merge

        cell.config = merge(cell.config, cell.config["bench"].get("rehearsal"))
        n_prompt = min(n_prompt, 150)
    hf, qtype = cells.as_run(cell.config), cell.config["bench"]["qtype"]
    cfg = ModelConfig.from_hf_config(hf)
    ref = cell.reference()
    tol = cell.config["bench"]["tolerances"]["logprob_atol_nats"]["value"]
    n_new = args.new
    selects = n_prompt + 1 >= hf["sparse_config"]["dense_len"]
    if not selects:
        print("the prompt does not reach the selection: the cell's own "
              "check, for its float8 reading", flush=True)
    ref_state = jax.jit(ref.first_lightning_state, static_argnums=(0,))
    H, D = hf["lightning_nh"], hf["lightning_head_dim"]

    def state_error(driver, params, seq):
        """How far slot 0's state row of the first lightning layer lies
        from the reference's plain float32 scan over the same tokens, as a
        share of the state's largest entry (the engine was idle: the
        request took slot 0)."""
        want = np.asarray(ref_state(Frozen(hf), params, seq), np.float64)
        got = np.asarray(driver.engine.cache.state[0, 0], np.float64)
        got = got.reshape(H, D, D).transpose(0, 2, 1)  # [h, key, value]
        return float(np.abs(got - want).max() / np.abs(want).max())
    ref_logits = jax.jit(ref.logits, static_argnums=(0, 3, 4, 5, 6))

    stats = {}

    def logprobs_of(params, seq, toks, rnd=ref._same, take=True):
        lg, st = ref_logits(Frozen(hf), params, seq, n_new, rnd, take, True)
        stats["last"] = dict(zip(ref._STATS, np.asarray(st).tolist()))
        lg = np.asarray(lg, np.float64)
        lse = np.log(np.sum(np.exp(lg - lg.max(-1, keepdims=True)),
                            -1)) + lg.max(-1)
        return lg[np.arange(n_new), toks] - lse

    def serve(driver, seed):
        rng = np.random.default_rng(int(seed))
        prompt = rng.integers(1, hf["vocab_size"], n_prompt).tolist()
        r = driver._submit(Planned(0.0, prompt, n_new), None)
        if not driver._wait_done([r], driver.clock() + 900):
            raise RuntimeError("the request did not finish")
        h = r.handle
        assert len(h.out_tokens) == n_new, h.error
        return h

    def say(seed, who, diff, extra=""):
        worst = float(np.max(diff)) if np.all(np.isfinite(diff)) else np.nan
        ok = bool(worst <= tol)
        print(f"seed {seed}: {who}: |diff| in nats "
              f"{' '.join(f'{d:.3f}' for d in diff)}; worst {worst:.4f} "
              f"(bound {tol}), median {float(np.median(diff)):.4f}{extra}",
              flush=True)
        return ok, worst

    def build(model):
        return cell.entry().Driver(cell, model, time.perf_counter)

    driver, rows, bad, problems = None, [], [], []
    seeds = list(range(args.first, args.first + args.n))
    for seed in seeds:
        t = time.perf_counter()
        if driver is not None:  # two sets of weights do not fit the chip
            driver.engine.model.params = None
        params = jax.block_until_ready(weights.make_params(cfg, seed, qtype))
        model = TpuModel(cfg, params, qtype)
        if driver is None:
            driver = build(model)
        else:
            driver.engine.model.params = model.params
        h = serve(driver, seed)
        seq = jnp.asarray(h.prompt + h.out_tokens[:-1], jnp.int32)
        got = np.asarray(h.out_logprobs, np.float64)
        want = logprobs_of(model.params, seq, h.out_tokens)
        sel = np.stack([h.prompt_selection] + list(h.out_selection))
        st = stats["last"]
        share = st["departed"] / max(st["reported"], 1)
        err = state_error(driver, model.params, seq)
        print(f"seed {seed}: program's state row of the first lightning "
              f"layer: {err:.5f} of its largest entry from the float32 "
              f"scan (STATE_TOL {STATE_TOL})", flush=True)
        if err > STATE_TOL:
            bad.append(f"seed {seed}: the program's state is off")
        ok, worst = say(
            seed, "program", np.abs(got - want),
            f"; {int((sel >= 0).sum())} blocks reported, of "
            f"{st['reported']:.0f} free choices {share:.2%} outside the "
            f"reference's own (SELECT_FLIP_SHARE {ref.SELECT_FLIP_SHARE}), "
            f"the deepest {st['deepest']:.5f} under its last pick "
            f"(SELECT_TIE {ref.SELECT_TIE}), {st['refused']:.0f} selections "
            "refused")
        free = logprobs_of(model.params, seq, h.out_tokens, take=False)
        _, worst_free = say(seed, "program against the reference's OWN "
                            "selection", np.abs(got - free))
        worst8 = np.nan
        if not args.no_float8:
            f8 = logprobs_of(model.params, seq, h.out_tokens, ref.float8,
                             False)
            ok8, worst8 = say(seed, "float8 reference in the program's "
                              "place", np.abs(f8 - free))
            if ok8:
                bad.append(f"seed {seed}: the float8 reference passed")
            if selects:  # the program's selection against a float8 walk's
                logprobs_of(model.params, seq, h.out_tokens, ref.float8)
                s8 = stats["last"]
                print(f"seed {seed}: against the float8 reference's own "
                      f"selection the program's departs in "
                      f"{s8['departed'] / max(s8['reported'], 1):.2%} of "
                      f"its free choices, the deepest {s8['deepest']:.5f} "
                      f"under, {s8['refused']:.0f} refused", flush=True)
        if not ok:
            bad.append(f"seed {seed}: the program's check failed")
        rows.append((worst, worst_free, worst8, share, st["deepest"], err))
        print(f"seed {seed}: {time.perf_counter() - t:.0f} s", flush=True)
        n_ctl = len(seeds) if args.controls is None else args.controls
        if seeds.index(seed) >= n_ctl or args.no_controls:
            continue
        # the controls: an engine each, over the same weights
        problems += driver.finish()
        driver.engine.cache = None
        driver = h = None
        gc.collect()
        for name in CONTROLS:
            with planted(name):  # its programs are traced in here
                d = build(TpuModel(cfg, model.params, qtype))
                hb = serve(d, seed)
            seqb = jnp.asarray(hb.prompt + hb.out_tokens[:-1], jnp.int32)
            diff = np.abs(np.asarray(hb.out_logprobs, np.float64)
                          - logprobs_of(model.params, seqb, hb.out_tokens))
            sb = stats["last"]
            errb = state_error(d, model.params, seqb)
            okb, _ = say(
                seed, f"CONTROL {name}", diff,
                f"; {sb['departed'] / max(sb['reported'], 1):.2%} of "
                f"{sb['reported']:.0f} free choices outside the "
                f"reference's own, the deepest {sb['deepest']:.5f} under, "
                f"{sb['refused']:.0f} selections refused; state row "
                f"{errb:.5f} off")
            okb = okb and errb <= STATE_TOL and not sb["refused"]
            if okb:
                bad.append(f"seed {seed}: the control '{name}' passed")
            problems += d.finish()
            d.engine.cache = None  # its pool, before the next engine's
            del d, hb
            gc.collect()
    if driver is not None:
        problems += driver.finish()
    if rows:
        a = np.asarray(rows)
        print(f"{len(a)} seeds, prompt {n_prompt} + {n_new}, bound {tol}:")
        for i, name in enumerate((
                "program worst-of-9 (nats)",
                "program against the reference's own selection",
                "float8 reference worst-of-9 (nats)",
                "share of free choices outside the reference's own",
                "deepest free choice under the reference's last pick",
                "state row against the float32 scan (share of its "
                "largest entry)")):
            print(f"  {name}: {np.nanmin(a[:, i]):.4f} .. "
                  f"{np.nanmax(a[:, i]):.4f}", flush=True)
    print(f"problems at the end: {problems}", flush=True)
    for line in bad:
        print(line, flush=True)
    if args.rehearse:
        return 3
    return 1 if bad or problems else 0


if __name__ == "__main__":
    sys.exit(main())
