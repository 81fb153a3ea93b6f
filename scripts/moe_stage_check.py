#!/usr/bin/env python3
"""Where does a sparse-expert block leave the float32 reference? (ISSUE 26, B1)

On the chip, at a configuration file's published widths, with the benchmark's
seeded weights: run the plain float32 reference (`bench/reference/mistral.py`)
over one prompt, keep every layer's MoE input, and compare ONE MoE block on
that same input, stage by stage, with three programs:

* `parent`: the router as it was (bf16 operands) and the dense combine
  (`_moe_dispatch_dense`: every expert's stack dequantized, bf16 einsums);
* `change`: the float32 router and the grouped kernel
  (`_moe_dispatch_grouped`);
* either one FORCED to the reference's own top-k choice and weights, which
  takes the router out of the distance and leaves the expert arithmetic.

Per layer it prints the router-logit error, how many tokens pick another
expert set than the reference and how near their k-th and (k+1)-th logits
lie, what a router at float8 inputs would choose, and the relative L2
distance of the block's output. Prefill-shaped (the
whole prompt as one batch of rows) and decode-shaped (the last token in row 0
of `n_slots` rows, the rest NaN: idle rows must not reach a live one). Then
the whole program against the reference: full logits of the prompt through
`forward`, and the distance again with the reference forced to the program's
expert choice at every layer, with the count of decisions that differ, how
far each forced choice lies under the reference's own (the deficit), and the
same forced reference with every matmul input at float8 (the precision
below, which the check's bound must tell from float32).

    chiprun -- python scripts/moe_stage_check.py --config mixtral-8x7b-int4 \
        --seeds 11 12 [--layers 10] [--tokens 250]

Writes `chiprun_out/moe_stage_check.json`. `--rehearse` runs the rehearsal
sizes on the CPU through the interpreter (exit code 3, never a result).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def rel(a, b):
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="mixtral-8x7b-int4")
    ap.add_argument("--seeds", type=int, nargs="+", default=[11])
    ap.add_argument("--layers", type=int)
    ap.add_argument("--tokens", type=int, default=250)
    ap.add_argument("--whole-only", action="store_true",
                    help="skip the per-layer stages: the whole program's "
                    "readings only (the limits of bench/reference/mixtral.py)")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["BIGDL_TPU_PALLAS"] = "interpret"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import cells, weights
    from bench.reference import mistral as ref
    from bench.run import merge
    from bigdl_tpu.models import llama
    from bigdl_tpu.models.config import ModelConfig

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind!r}", flush=True)
    if dev.platform != "tpu" and not args.rehearse:
        print("no TPU: this is a chip measurement", file=sys.stderr)
        return 2
    config = cells.load_json(ROOT, "bench", "configs", args.config + ".json")
    if args.rehearse:
        config = merge(config, config["bench"]["rehearsal"])
    hf = cells.as_run(config)
    if args.layers:
        hf["num_hidden_layers"] = args.layers
    cfg = ModelConfig.from_hf_config(hf)
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    n_slots = config["bench"]["engine"]["n_slots"]
    T = min(args.tokens, 64) if args.rehearse else args.tokens
    bf16 = jnp.bfloat16
    hi = jax.default_matmul_precision("highest")

    def layer_of(params, l):
        return jax.tree.map(lambda a: a[l], params["layers"])

    @jax.jit
    def ref_stages(x, p):  # x [N, H] float32: the reference's own block
        with hi:
            logits = x @ ref.dense(p["router"]).T
            probs = jax.nn.softmax(logits, -1)
            top, idx = jax.lax.top_k(probs, k)
            return logits, top / top.sum(-1, keepdims=True), idx, \
                ref._moe(hf, x, p)

    def fp8(x):  # every matmul input at float8_e4m3: the precision below
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    def same(x):
        return x

    @functools.partial(jax.jit, static_argnames="rnd")
    def ref_forced(x, p, topv, topi, rnd=same):  # the reference at a GIVEN
        # choice, optionally with its matmul inputs rounded by `rnd`
        with hi:
            w = jnp.zeros((x.shape[0], E), jnp.float32).at[
                jnp.arange(x.shape[0])[:, None], topi].set(topv)
            x = rnd(x)

            def one(acc, e):
                wg, wu, wd, w_e = e
                z = jax.nn.silu(x @ ref.dense(wg).T) * (x @ ref.dense(wu).T)
                y = rnd(z) @ ref.dense(wd).T
                return acc + y * w_e[:, None], None

            return jax.lax.scan(one, jnp.zeros_like(x), (
                p["w_gate_e"], p["w_up_e"], p["w_down_e"], w.T))[0]

    @jax.jit
    def parent_router(xc, p):  # the router as the parent commit had it
        logits = jnp.einsum("bth,eh->bte", xc, p["router"].astype(xc.dtype),
                            preferred_element_type=jnp.float32)
        probs = jax.nn.softmax(logits.astype(jnp.float32), -1)
        topv, topi = jax.lax.top_k(probs, k)
        return logits, topv / (topv.sum(-1, keepdims=True) + 1e-20), topi

    @jax.jit
    def change_router(xc, p):
        logits = jnp.einsum(
            "bth,eh->bte", xc.astype(jnp.float32),
            p["router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST)
        return (logits,) + llama._moe_router(cfg, xc, p)

    dense = jax.jit(lambda xc, p, tv, ti: llama._moe_dispatch_dense(
        cfg, xc, p, bf16, tv, ti))
    grouped = jax.jit(lambda xc, p, tv, ti: llama._moe_dispatch_grouped(
        cfg, xc, p, bf16, tv, ti))

    def flips(idx_a, idx_b):  # [N] bool: another expert SET was chosen
        a, b = np.sort(np.asarray(idx_a), -1), np.sort(np.asarray(idx_b), -1)
        return np.any(a != b, -1)

    out = {"config": args.config, "layers": cfg.num_hidden_layers,
           "tokens": T, "device": dev.device_kind, "seeds": {}}
    for seed in args.seeds:
        params = jax.block_until_ready(
            weights.make_params(cfg, seed, "sym_int4"))
        rng = np.random.default_rng(seed)
        toks = jnp.asarray(rng.integers(1, hf["vocab_size"], T), jnp.int32)

        def attn_half(h, p, rnd=same):  # ref._layer up to the MoE input
            with hi:
                hq, hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
                D = hf.get("head_dim") or hf["hidden_size"] // hq
                x = ref._rms(h, ref.dense(p["attn_norm"]), hf["rms_norm_eps"])
                qkv = rnd(x) @ ref.dense(p["wqkv"]).T
                q = qkv[:, :hq * D].reshape(T, hq, D)
                kk = qkv[:, hq * D:(hq + hkv) * D].reshape(T, hkv, D)
                v = qkv[:, (hq + hkv) * D:].reshape(T, hkv, D)
                q, kk = (ref._rope(q, hf["rope_theta"]),
                         ref._rope(kk, hf["rope_theta"]))
                h = h + rnd(ref._attention(hf, q, kk, v)) @ ref.dense(
                    p["wo"]).T
                return h, ref._rms(h, ref.dense(p["mlp_norm"]),
                                   hf["rms_norm_eps"])

        def ref_forward(forced=None, rnd=same):
            """The reference layer by layer: (each MoE block's input, final
            logits, per layer the tokens whose own top-k differs from
            `forced`, per layer each token's deficit: how far the worst
            forced expert's router logit lies under the reference's k-th
            best, per layer the trajectory's own top-k). With `forced` [L, T, k] every block takes THAT expert
            choice, with the reference's own softmax weights of it; `rnd`
            rounds every matmul input (the reading in a lower precision)."""
            with hi:
                h = ref.dense(params["embed"])[toks]
            xs, differ, deficit, own = [], [], [], []
            for l in range(cfg.num_hidden_layers):
                p = layer_of(params, l)
                h, x = attn_half(h, p, rnd)
                xs.append(x)
                logits, topv, idx, out = ref_stages(x, p)
                own.append(idx)  # the top-k of THIS trajectory
                if forced is not None:
                    differ.append(flips(forced[l], idx))
                    lg = np.asarray(logits, np.float64)
                    kth = np.sort(lg, -1)[:, -k]
                    deficit.append(kth - np.take_along_axis(
                        lg, np.asarray(forced[l], np.int64), -1).min(-1))
                    pr = jnp.take_along_axis(jax.nn.softmax(logits, -1),
                                             forced[l], -1)
                    topv, idx = pr / pr.sum(-1, keepdims=True), forced[l]
                if forced is not None or rnd is not same:
                    out = ref_forced(x, p, topv, idx, rnd=rnd)
                h = h + out
            with hi:
                z = rnd(ref._rms(h, ref.dense(params["final_norm"]),
                                 hf["rms_norm_eps"])) @ ref.dense(
                                     params["lm_head"]).T
            return xs, np.asarray(z, np.float64), differ, deficit, own

        xs, want, _, _, _ = ref_forward()
        rows = []
        for l, x in enumerate([] if args.whole_only else xs):
            p = layer_of(params, l)
            r_logits, r_topv, r_topi, r_out = ref_stages(x, p)
            srt = np.sort(np.asarray(r_logits), -1)
            gap = srt[:, -k] - srt[:, -k - 1]  # k-th over (k+1)-th logit
            xc = x.astype(bf16)[None]  # [1, T, H]: what the program is fed
            row = {"layer": l, "gap_min": float(gap.min()),
                   "gap_p10": float(np.percentile(gap, 10))}
            for name, router, dispatch in (
                    ("parent", parent_router, dense),
                    ("change", change_router, grouped)):
                lg, tv, ti = router(xc, p)
                f = flips(ti[0], r_topi)
                row[name] = {
                    "router_logit_abs_err": float(np.max(np.abs(
                        np.asarray(lg[0], np.float64) - np.asarray(r_logits)))),
                    "flipped_tokens": int(f.sum()),
                    "flipped_gap_max": float(gap[f].max()) if f.any() else 0.0,
                    "out_rel": rel(dispatch(xc, p, tv, ti)[0], r_out),
                    "out_rel_unflipped": rel(
                        np.asarray(dispatch(xc, p, tv, ti)[0],
                                   np.float64)[~f], np.asarray(r_out)[~f]),
                    "out_rel_forced": rel(
                        dispatch(xc, p, r_topv[None], r_topi[None])[0], r_out),
                }
            # the reference on the bf16-rounded input: what input rounding
            # alone costs, and whether IT flips a choice
            b_logits, _, b_topi, b_out = ref_stages(
                x.astype(bf16).astype(jnp.float32), p)
            row["bf16_input"] = {
                "flipped_tokens": int(flips(b_topi, r_topi).sum()),
                "out_rel": rel(b_out, r_out)}
            # a router at the precision below: its choice's deficit
            f_logits = np.asarray(ref_stages(fp8(x), p)[0], np.float64)
            f_idx = np.argsort(f_logits, -1)[:, -k:]
            row["fp8_router_deficit_max"] = float(np.max(
                srt[:, -k] - np.take_along_axis(
                    np.asarray(r_logits, np.float64), f_idx, -1).min(-1)))
            # decode-shaped: the last token in row 0, idle rows NaN
            xd = jnp.full((n_slots, 1, x.shape[-1]), jnp.nan, bf16
                          ).at[0, 0].set(x[-1].astype(bf16))
            _, tv, ti = change_router(xd, p)
            yd = grouped(xd, p, tv, ti)
            row["decode_row0_rel"] = rel(yd[0, 0], r_out[-1])
            row["decode_row0_finite"] = bool(jnp.all(jnp.isfinite(yd[0, 0])))
            rows.append(row)
            print(json.dumps(row), flush=True)

        # the whole program: full logits of the prompt against the
        # reference, free and then forced to the program's expert choice
        got, _, routing = jax.jit(
            lambda p, t: llama.forward(cfg, p, t[None], None, mode="prefill",
                                       moe_routing=True))(params, toks)
        got = np.asarray(got[0], np.float64)
        _, want_forced, differ, deficit, _ = ref_forward(routing[:, 0])
        differ, deficit = np.stack(differ), np.stack(deficit)  # [L, T]
        # the reading in the precision below: the SAME reference and choice
        # with every matmul input at float8_e4m3 must come out not correct
        _, want_fp8, _, _, _ = ref_forward(routing[:, 0], rnd=fp8)
        # and a program at that precision routes on its own trajectory:
        # its choices, held to the float32 reference's router like ours
        fp8_own = jnp.stack(ref_forward(rnd=fp8)[4])
        _, _, fp8_differ, fp8_deficit, _ = ref_forward(fp8_own)
        fp8_differ, fp8_deficit = np.stack(fp8_differ), np.stack(fp8_deficit)

        def lp(z):
            m = z.max(-1, keepdims=True)
            return z - m - np.log(np.sum(np.exp(z - m), -1, keepdims=True))

        def against(w):
            best = np.argmax(w, -1)
            d = np.abs(lp(got) - lp(w))[np.arange(T), best]
            return {"logits_rel": rel(got, w),
                    "logit_abs_err_max": float(np.max(np.abs(got - w))),
                    "best_token_logprob_diff_max": float(d.max()),
                    "best_token_logprob_diff_p90": float(np.percentile(d, 90)),
                    "best_token_logprob_diff_p50": float(np.median(d)),
                    "argmax_agree": float(np.mean(np.argmax(got, -1) == best))}

        def lp_dist(a, b):  # |logprob diff| of b's best token, per position
            best = np.argmax(b, -1)
            return np.abs(lp(a) - lp(b))[np.arange(T), best]

        d8 = lp_dist(want_fp8, want_forced)
        whole = {"free": against(want), "forced": against(want_forced),
                 "fp8_reference_vs_float32": {
                     "logits_rel": rel(want_fp8, want_forced),
                     "best_token_logprob_diff_max": float(d8.max()),
                     "best_token_logprob_diff_p50": float(np.median(d8)),
                     "worst_of_9_min_over_windows": float(min(
                         d8[i:i + 9].max() for i in range(T - 8))),
                     "worst_of_9_p50_over_windows": float(np.median(
                         [d8[i:i + 9].max() for i in range(T - 8)]))},
                 "fp8_trajectory_deficit_max": float(fp8_deficit.max()),
                 "fp8_trajectory_deficit_p99": float(
                     np.percentile(fp8_deficit, 99)),
                 "fp8_trajectory_deficit_over": {
                     str(t): int((fp8_deficit > t).sum())
                     for t in (0.3, 0.4, 0.5)},
                 "fp8_trajectory_decisions_flipped": int(fp8_differ.sum()),
                 "deficit_over": {str(t): int((deficit > t).sum())
                                  for t in (0.2, 0.3, 0.4, 0.5)},
                 "deficit_max": float(deficit.max()),
                 "deficit_p999": float(np.percentile(deficit, 99.9)),
                 "deficit_of_flipped_p50": float(np.median(
                     deficit[differ])) if differ.any() else 0.0,
                 "decisions": int(differ.size),
                 "decisions_flipped": int(differ.sum()),
                 "tokens_with_a_flip": int(differ.any(0).sum()),
                 "flipped_in_last_9_tokens": int(differ[:, -9:].sum())}
        print(json.dumps({"seed": seed, "whole_program": whole}), flush=True)
        out["seeds"][str(seed)] = {"layers": rows, "whole_program": whole,
                                   "routing_shape": list(routing.shape)}
        del params
    if args.rehearse:
        return 3
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "moe_stage_check.json"),
              "w", encoding="utf-8") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
