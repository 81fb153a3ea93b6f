#!/usr/bin/env python3
"""The reference check of `sdar-30b-a3b.blockgen-closed` at the CELL's sizes
and through the cell's own engine, over several seeds in one process on the
chip: the readings that `logprob_atol_nats` of
bench/configs/sdar-30b-a3b-int4.json and the ties of
bench/reference/sdar.py (`ROUTER_TIE`, `FLIP_SHARE`, `REVEAL_TIE`) lie
between, BOTH taken by the entry's own comparison.

For each seed: weights from the seed (`bench/weights.make_params`), the
cell's own engine (`bench/entries/engine.Driver`: one is built, the next
seed's parameters are put in its place), and then `Driver.check` twice (a
seeded 250-token prompt, 9 new tokens: three blocks, the first half prompt
and the last cut short), its line printed as it stands:

 * the PROGRAM: the engine serves the request. It has to come out correct.
 * the CONTROL, the precision below: the same request "served" by the
   reference's own block loop (`ref.generate`) with both inputs of every
   matrix product rounded to float8_e4m3, on that trajectory's own experts
   and its own reveal, its tokens, log-confidences, passes and experts put
   in the program's place (`Driver._submit` answered by that record, which
   is also what `last_routed_request` hands the reference). It has to come
   out NOT correct, on every seed.

Beside each verdict, from the same replay (`ref.replay`'s per-pass
figures): how far the given experts lie under the reference's own k-th best
(logits, against `ROUTER_TIE`), in what share of a forward's (layer,
position) decisions they are not the reference's own top-k (against
`FLIP_SHARE`), and how far a revealed position's log-confidence lies under
the reference's own m_s-th best (nats, against `REVEAL_TIE`); the control's
line says which of the limits it broke.

    chiprun --timeout 3000 -- python3 scripts/block_check_sweep.py --n 17

Exit code 1 if a program's check fails or a control's passes, else 0.
`--rehearse`: the files' rehearsal sizes on the CPU (a walk of the script;
toy widths tell no precisions apart, so its verdicts decide nothing and it
exits 3)."""

from __future__ import annotations

import argparse
import os
import re
import sys
import time
import types
import weakref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


class _Record(types.SimpleNamespace):
    """What the check reads of a finished request (a class of its own: the
    engine's module keeps a weak reference to the newest one)."""


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default="sdar-30b-a3b.blockgen-closed")
    ap.add_argument("--first", type=int, default=2147485301)
    ap.add_argument("--n", type=int, default=17)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["BIGDL_TPU_PALLAS"] = "interpret"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import cells, weights
    from bench.records import Frozen
    from bigdl_tpu.api import TpuModel
    from bigdl_tpu.models.config import ModelConfig
    from bigdl_tpu.serving import engine as engine_module
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
    b = ref.block_length(hf)
    L, k = hf["num_hidden_layers"], hf["num_experts_per_tok"]
    tol = cell.config["bench"]["tolerances"]["logprob_atol_nats"]["value"]
    n_new = 9

    def fp8(x):
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    def pass8(params, ids, base):
        """One pass of the float8 trajectory: (logits [b, V] of the block at
        `base`, that walk's own experts [L, T, k])."""
        with jax.default_matmul_precision("highest"):
            h, _, _, own = ref.hidden(hf, params, ids, None, fp8,
                                      with_own=True)
            h = jax.lax.dynamic_slice_in_dim(h, base, b, axis=0)
            h = ref._rms(h, ref.dense(params["final_norm"]),
                         hf["rms_norm_eps"])
            return ref._head(h, params["lm_head"], fp8), own

    pass8_j = jax.jit(pass8)
    replay_j = jax.jit(
        lambda p, seq, plan: ref.replay(Frozen(hf), p, seq, n_new, plan))

    def readings(params, record):
        """`ref.replay`'s own figures for a request's record: the deepest
        given expert (logits), the largest share of a forward's decisions
        departing, the worst reveal shortfall (nats)."""
        n_total = len(record.prompt) + n_new - 1
        plan = ref.replay_plan(record.passes, n_total, n_new, b, L, k,
                               record.expert_ids)
        if plan is None:
            return None
        seq = jnp.asarray((record.prompt + record.out_tokens)[:n_total],
                          jnp.int32)
        _, stats = replay_j(params, seq, tuple(
            jnp.asarray(plan[f]) for f in ref.PLAN_FIELDS))
        n = int(plan["n"][0])
        stats = np.asarray(stats, np.float64)[:n]
        T = plan["chosen"].shape[2]
        return (float(stats[:, 1].max()), float(stats[:, 0].max() / (L * T)),
                float(stats[:, 2].max()))

    def float8_record(params, prompt):
        """The request as the float8 trajectory serves it: the record the
        engine keeps of a request, from `ref.generate` at float8."""
        seen = {}  # a block's first position -> the experts of that forward

        def fwd(p, ids, base):
            lg, own = pass8_j(p, ids, base)
            seen[int(base)] = np.asarray(own)
            return lg

        toks, lps, passes = ref.generate(hf, params, prompt, n_new, rnd=fp8,
                                         fwd=fwd)
        for p in passes:  # a pass's experts: its forward's, at its block
            p["experts"] = seen[p["base"]][:, p["base"]:p["base"] + b]
        return _Record(
            prompt=list(prompt), out_tokens=toks, out_logprobs=lps,
            passes=passes,
            expert_ids=lambda n: (seen[n][:, :n] if n in seen else None))

    def worst_of(msg: str) -> float:
        m = re.search(r"worst ([0-9.eE+-]+|nan|inf)", msg)
        return float(m.group(1)) if m else float("nan")

    driver, rows, bad = None, [], []
    for seed in range(args.first, args.first + args.n):
        t = time.perf_counter()
        if driver is not None:  # two sets of weights do not fit the chip
            driver.engine.model.params = None
        params = jax.block_until_ready(weights.make_params(cfg, seed, qtype))
        model = TpuModel(cfg, params, qtype)
        if driver is None:
            driver = cell.entry().Driver(cell, model, time.perf_counter)
            submit, wait = driver._submit, driver._wait_done
        else:
            driver.engine.model.params = model.params
        # the program
        ok, msg = driver.check(cell, hf, params, seed)
        print(f"seed {seed}: program {'ok' if ok else 'NOT OK'}: {msg}",
              flush=True)
        req = driver.reqs[-1].handle
        got = readings(params, req)
        # the control, in the program's place
        record = float8_record(params, req.prompt)
        driver._submit = lambda planned, due: types.SimpleNamespace(
            handle=record, done=True)
        driver._wait_done = lambda reqs, deadline: True
        engine_module._last_routed = weakref.ref(record)
        try:
            ok8, msg8 = driver.check(cell, hf, params, seed)
        finally:
            driver._submit, driver._wait_done = submit, wait
            engine_module._last_routed = None
        got8 = readings(params, record)
        broke = [name for name, over in (
            (f"logprob_atol_nats {tol}", not ok8),
            (f"ROUTER_TIE {ref.ROUTER_TIE}", got8[0] > ref.ROUTER_TIE),
            (f"FLIP_SHARE {ref.FLIP_SHARE}", got8[1] > ref.FLIP_SHARE),
            (f"REVEAL_TIE {ref.REVEAL_TIE}", got8[2] > ref.REVEAL_TIE))
            if over]
        print(f"seed {seed}: float8 control "
              f"{'PASSES (it must not)' if ok8 else 'not correct'}: {msg8}; "
              f"broke: {', '.join(broke) or 'nothing'}", flush=True)
        fmt = "experts {:.4f} logits under, {:.2%} of decisions depart, " \
              "reveal {:.4f} nats under"
        print(f"seed {seed}: program {fmt.format(*got)}; float8 "
              f"{fmt.format(*got8)} ({time.perf_counter() - t:.0f} s)",
              flush=True)
        rows.append((worst_of(msg), *got, worst_of(msg8), *got8))
        if not ok:
            bad.append(f"seed {seed}: the program's check failed")
        if ok8:
            bad.append(f"seed {seed}: the float8 control passed the check")
        del params, model, record
    problems = driver.finish() if driver is not None else []
    if rows:
        a = np.asarray(rows)
        names = ("program worst-of-9 (nats)", "program experts under (logits)",
                 "program decisions departing", "program reveal under (nats)",
                 "float8 worst-of-9 (nats)", "float8 experts under (logits)",
                 "float8 decisions departing", "float8 reveal under (nats)")
        print(f"{len(a)} seeds, bound {tol}:", flush=True)
        for i, name in enumerate(names):
            print(f"  {name}: {a[:, i].min():.4f} .. {a[:, i].max():.4f}",
                  flush=True)
    print(f"problems at the end: {problems}", flush=True)
    for line in bad:
        print(line, flush=True)
    if args.rehearse:
        return 3
    return 1 if bad or problems else 0


if __name__ == "__main__":
    sys.exit(main())
