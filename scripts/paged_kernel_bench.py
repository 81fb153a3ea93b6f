#!/usr/bin/env python3
"""`paged_decode_attention` alone on the chip, at the shapes of the three
configurations that serve from KV pages (pages of 64 tokens, 32 a row, the
cells' own pools; `--shape jamba2-3b`: ONE KV head, 256 rows of 10 pages of
256, PR 57; `--shape lfm2-24b-a2b`: 8 KV heads of 64 as 4 LANE PAIRS of 128,
64 rows of 80 pages of 64 over 5 layers, PR 61): seconds per decode step's
worth of calls (one per layer,
each fed the last one's output so that none overlaps the next, inside one jit
with the pool carried and donated as the engine's is; host clock over several
such steps), the bytes the LIVE pages hold, and the kernel's output against
the float32 `jnp` masked dense attention. By hand, through the chip tool:

    python scripts/paged_kernel_bench.py [--plan cells] [--group 4 8 16]
    python scripts/paged_kernel_bench.py --tree .bench_checkout/parent

`--plan cells`: the four cells' mixes of live pages a row (PERF_LEDGER, PR 34:
`kernel.paged_live_page_share`), then every row at 0, 1, 3, 8, 20 and 28 live
pages, from which the cost of an idle row, of a group and of a live page
follow. `--tree` times the kernel of another checkout (the parent's) with the
same script; `--group` rebinds the kernel's own constants (`_GROUP_TOKENS`,
`_GROUP_COLUMNS`) before tracing, where the kernel has them: the kernel takes
no such option.
`--fp8` times an fp8 pool (codes and float32 scales; no cell serves from one),
`--shape head-64` a pool whose tiles XLA pads (pages through Pallas's pipeline,
the kernel's other way in). `--lower` compiles every plan entry for a
described v5e and runs nothing; `--rehearse` walks the script on a CPU through
the Pallas interpreter.
One line per (shape, mix, pages a group): ms a step, us a live page and layer,
GB/s of live pages, share of 819 GB/s; the same as JSON lines under
`chiprun_out/`. Not part of the benchmark: the cells measure the kernel inside
`engine_decode`.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# name: (rows, KV heads, query heads a KV head, layers, pages in the pool,
# head size[, tokens a page: 64, pages a row: 32]). `head-64` is no cell's: a
# head of 64 (Llama-3.2-1B's attention), whose pool XLA pads, so that its
# pages reach the kernel's body through Pallas's pipeline and not the kernel's
# own DMA. `jamba2-3b` is its cell's pool of ONE KV head (the row loop through
# `one_head_view` since PR 57, that pipeline before). `lfm2-24b-a2b` is its
# cell's pool of 8 KV heads of 64 AS THE KERNEL SEES IT: 4 rows of 128 lanes,
# two heads side by side (`ops/attention.lane_pairs`), 8 query rows a wide
# head, the row loop; stored `[.., 8, 64]` it would be `head-64`'s arm. All
# three by name only
SHAPES = {
    "mistral-7b": (32, 8, 4, 32, 1025, 128),
    "qwen2-7b": (16, 4, 7, 28, 1025, 128),
    "mixtral-8x7b": (16, 8, 4, 10, 1025, 128),
    "head-64": (16, 8, 4, 16, 513, 64),
    "jamba2-3b": (256, 1, 20, 2, 2561, 128, 256, 10),
    "lfm2-24b-a2b": (64, 4, 8, 5, 5121, 128, 64, 80),
}


def shape_of(name):
    return (*SHAPES[name], 64, 32)[:8]


# live pages a row (0 = an idle row), as the cells' traced steps show them:
# chat-steady 22 of 1024, longprompt 182 of 1024 in 8 rows, qwen2 127 of 512
# and mixtral 124 of 512 at full occupancy
CELL_MIXES = {
    "mistral-7b": {
        "chat-steady": [8, 0, 0, 3, 0, 0, 0, 0, 8, 0, 0, 0, 0, 0, 0, 0,
                        0, 0, 0, 0, 3, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0],
        "longprompt": [20, 0, 0, 0, 28, 0, 0, 0, 17, 0, 0, 0, 24, 0, 0, 0,
                       29, 0, 0, 0, 21, 0, 0, 0, 25, 0, 0, 0, 18, 0, 0, 0],
    },
    "qwen2-7b": {
        "chat-closed": [3, 5, 6, 7, 7, 8, 8, 8, 8, 8, 8, 9, 9, 10, 11, 12],
    },
    "mixtral-8x7b": {
        "chat-closed": [3, 5, 6, 7, 7, 8, 8, 8, 8, 8, 8, 8, 9, 9, 10, 12],
    },
    "head-64": {
        "half idle": [3, 0, 6, 0, 7, 0, 8, 0, 8, 0, 8, 0, 9, 0, 10, 0],
    },
    # 770 of 2560 pages live (`kernel.paged_live_page_share--closed` 29.8%,
    # ledger, PR 56): contexts of a log-normal prompt plus half an output
    "jamba2-3b": {
        "manychat-closed": [
            sorted(n for n, rows in ((1, 40), (2, 70), (3, 65), (4, 40),
                                     (5, 22), (6, 11), (7, 5), (8, 3))
                   for _ in range(rows))[i * 37 % 256] for i in range(256)],
    },
}
# contexts of a log-normal prompt (median 2048, 1024..4096) plus half an
# output, in pages of 64: 2493 of 5120 live
_LFM2_PAGES = (
    18, 18, 18, 18, 18, 18, 19, 20, 21, 21, 22, 23, 23, 24, 25, 25,
    26, 26, 27, 28, 28, 29, 29, 30, 31, 31, 32, 33, 33, 34, 34, 35,
    36, 37, 37, 38, 39, 40, 40, 41, 42, 43, 44, 45, 46, 47, 48, 49,
    50, 51, 53, 54, 56, 58, 59, 62, 64, 67, 70, 72, 72, 72, 72, 72)
CELL_MIXES["lfm2-24b-a2b"] = {
    "manydocs-closed": [_LFM2_PAGES[i * 37 % 64] for i in range(64)]}
UNIFORM = (0, 1, 3, 8, 20, 28)  # (those a row of the shape can hold)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--plan", choices=("cells",), default="cells")
    ap.add_argument("--shape", nargs="*", default=list(SHAPES)[:3])
    ap.add_argument("--group", type=int, nargs="*", default=None)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--tree", default=ROOT,
                    help="the checkout whose kernel is timed")
    ap.add_argument("--lower", action="store_true")
    ap.add_argument("--rehearse", action="store_true",
                    help="2 layers, one step, two mixes: the script's own "
                    "control flow on a CPU under BIGDL_TPU_PALLAS=interpret")
    ap.add_argument("--fp8", action="store_true",
                    help="an fp8 pool (e5m2 codes, float32 scales a slot "
                    "and head): no cell serves from one")
    ap.add_argument("--tag", default=None, help="label of the JSON lines")
    args = ap.parse_args()
    tree = os.path.abspath(args.tree)
    sys.path.insert(0, tree)
    tag = args.tag or os.path.basename(tree)

    if args.lower:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.ops.pallas import paged_attention as pa

    takes_group = hasattr(pa, "_GROUP_TOKENS")
    groups = args.group if takes_group and args.group else [None]
    rule = (pa._GROUP_TOKENS, pa._GROUP_COLUMNS) if takes_group else None

    def attention(q, k, v, bt, layer, pos, start, live, group, scales=()):
        if group:  # `group_pages` reads these while tracing: the jit that
            # calls this is made anew for each group
            pa._GROUP_TOKENS = group * k.shape[2]
            pa._GROUP_COLUMNS = max(rule[1], pa._GROUP_TOKENS * k.shape[3])
            pa.paged_decode_attention.clear_cache()
        try:
            return pa.paged_decode_attention(q, k, v, bt, layer, pos, start,
                                             *scales, live=live)
        finally:
            if group:
                pa._GROUP_TOKENS, pa._GROUP_COLUMNS = rule

    def step(k, v, scales, q, bt, pos, start, live, *, layers, group):
        # the pool (an fp8 pool's scales with it) rides the scan as a carry
        # and is donated, as the engine's does: a loop-invariant operand of
        # 4 GB would be copied into the loop once a call (PERF.md section 6,
        # PR 34)
        def one(carry, layer):
            k, v, scales, q = carry
            out = attention(q, k, v, bt, layer, pos, start, live, group,
                            scales)
            return (k, v, scales, (q + 1e-3 * out).astype(q.dtype)), None
        return jax.lax.scan(one, (k, v, scales, q), jnp.arange(layers))[0]

    if args.lower:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        chip = SingleDeviceSharding(topo.devices[0])

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

        for name in args.shape:
            B, Hkv, G, L, NP, HEAD_DIM, PAGE, MAX_PAGES = shape_of(name)
            kv = sds((L, NP, PAGE, Hkv, HEAD_DIM),
                     jnp.float8_e5m2 if args.fp8 else jnp.bfloat16)
            scales = (sds((L, NP, PAGE, Hkv), jnp.float32),) * 2 \
                if args.fp8 else ()
            for group in groups:
                t = time.perf_counter()
                c = jax.jit(functools.partial(step, layers=L, group=group),
                            donate_argnums=(0, 1, 2)).lower(
                    kv, kv, scales, sds((B, Hkv * G, HEAD_DIM), jnp.bfloat16),
                    sds((B, MAX_PAGES), jnp.int32), sds((B,), jnp.int32),
                    sds((B,), jnp.int32), sds((B,), jnp.bool_)).compile()
                print(f"{name} group {group}: compiles for a described v5e "
                      f"in {time.perf_counter() - t:.1f} s, temporaries "
                      f"{c.memory_analysis().temp_size_in_bytes} bytes",
                      flush=True)
        return 0

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind}; tree {tree}; "
          f"pages a group {'from the shapes' if groups == [None] else groups}"
          f"{'' if takes_group else ' (this kernel has no groups)'}",
          flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    sink = open(os.path.join(ROOT, "chiprun_out", "paged_kernel_bench.jsonl"),
                "a", encoding="utf-8")

    @functools.partial(jax.jit, static_argnames=("shape",))
    def make_pool(key, shape):
        # one layer's worth of values, every layer a shifted copy: 8 GB of
        # normal draws would need as much again in float32 on the way
        one = jax.random.normal(key, shape[1:], jnp.bfloat16)
        shift = jnp.arange(shape[0], dtype=jnp.bfloat16) / shape[0]
        return one[None] + shift.reshape((-1,) + (1,) * (len(shape) - 1))

    def reference(k, v, q, bt, layer, pos, live, scales=()):
        # the pool is an ARGUMENT everywhere: a jit that captured it would
        # copy gigabytes into the program
        B, mp = bt.shape
        PAGE, Hkv, D = k.shape[2:]

        def rows(pool, scale):
            r = pool[layer][bt].astype(jnp.float32)
            if scale is not None:
                r = r * scale[layer][bt][..., None]
            return r.reshape(B, mp * PAGE, Hkv, D)

        rows_k = rows(k, scales[0] if scales else None)
        rows_v = rows(v, scales[1] if scales else None)
        qh = q.reshape(B, Hkv, -1, D).astype(jnp.float32)
        s = jnp.einsum("bhgd,bshd->bhgs", qh, rows_k,
                       precision="highest") * D ** -0.5
        ok = (jnp.arange(mp * PAGE)[None] <= pos[:, None]) & live[:, None]
        ok = ok[:, None, None]
        p = jnp.where(ok, jax.nn.softmax(jnp.where(ok, s, -1e30), -1), 0.0)
        out = jnp.einsum("bhgs,bshd->bhgd", p, rows_v, precision="highest")
        return out.reshape(q.shape)

    for name in args.shape:
        B, Hkv, G, L, NP, HEAD_DIM, PAGE, MAX_PAGES = shape_of(name)
        if args.rehearse:
            L, args.steps = 2, 1
        keys = jax.random.split(jax.random.key(0), 3)
        shape = (L, NP, PAGE, Hkv, HEAD_DIM)
        k = make_pool(keys[0], shape)
        v = make_pool(keys[1], shape)
        scales = ()
        if args.fp8:
            k, v = k.astype(jnp.float8_e5m2), v.astype(jnp.float8_e5m2)
            scales = tuple(
                jax.random.uniform(key, shape[:4], jnp.float32, 0.5, 1.5)
                for key in jax.random.split(keys[2]))
        q = jax.random.normal(keys[2], (B, Hkv * G, HEAD_DIM), jnp.bfloat16)
        # every row's pages scattered over the pool, page 0 the scratch sink
        bt = jnp.asarray(1 + np.random.default_rng(0).permutation(NP - 1)
                         [:B * MAX_PAGES].reshape(B, MAX_PAGES), jnp.int32)
        start = jnp.zeros((B,), jnp.int32)
        # K and V of a page: bf16, or fp8 codes and a float32 scale a vector
        page_bytes = 2 * PAGE * Hkv * (HEAD_DIM + 4 if args.fp8
                                       else HEAD_DIM * 2)

        def rows(mix):
            n = np.asarray(mix)
            # the last live page holds all but 17 of its slots
            return (jnp.asarray(np.maximum(n * PAGE - 18, 0), jnp.int32),
                    jnp.asarray(n > 0))

        mixes = dict(CELL_MIXES[name])
        mixes.update({f"every row {n}": [n] * B for n in UNIFORM
                      if n <= MAX_PAGES})
        if args.rehearse:
            mixes = dict(list(mixes.items())[:2])
        for group in groups:
            pos, live = rows(next(iter(mixes.values())))
            got = attention(q, k, v, bt, jnp.asarray(L - 1), pos, start, live,
                            group, scales).astype(jnp.float32)
            want = jax.jit(reference)(k, v, q, bt, L - 1, pos, live, scales)
            idle = ~np.asarray(live)
            print(f"{name}{' fp8' if args.fp8 else ''} group {group}: "
                  f"kernel vs float32 jnp attention: "
                  f"worst {float(jnp.abs(got - want).max()):.3e} of "
                  f"{float(jnp.abs(want).max()):.3e}; idle rows "
                  f"{float(jnp.abs(got[idle]).max()) if idle.any() else 0:.1e}",
                  flush=True)
            run = jax.jit(functools.partial(step, layers=L, group=group),
                          donate_argnums=(0, 1, 2))
            for mix_name, mix in mixes.items():
                pos, live = rows(mix)
                k, v, scales, out = run(k, v, scales, q, bt, pos, start, live)
                jax.block_until_ready(out)
                t = time.perf_counter()
                for _ in range(args.steps):
                    k, v, scales, out = run(k, v, scales, q, bt, pos, start,
                                            live)
                jax.block_until_ready(out)
                dt = (time.perf_counter() - t) / args.steps
                pages = int(sum(mix))
                moved = pages * L * page_bytes
                line = {
                    "tree": tag, "shape": name, "mix": mix_name,
                    "fp8": args.fp8,
                    "pages_per_group": group, "live_rows": int(sum(
                        1 for n in mix if n)), "live_pages": pages,
                    "layers": L, "ms_per_step": dt * 1e3,
                    "us_per_live_page_layer": dt * 1e6 / (pages * L)
                    if pages else None,
                    "live_gbytes": moved / 1e9,
                    "share_of_819": 100 * moved / 819e9 / dt,
                    "device": dev.device_kind,
                }
                sink.write(json.dumps(line) + "\n")
                sink.flush()
                per = (f"{line['us_per_live_page_layer']:6.3f} us a live "
                       f"page and layer, " if pages else "")
                print(f"{name}{' fp8' if args.fp8 else ''} group {group} "
                      f"{mix_name:>13}: "
                      f"{line['live_rows']:2d} live rows, {pages:3d} live "
                      f"pages: {dt * 1e3:7.3f} ms a step ({L} layers, host "
                      f"clock over {args.steps}), {per}"
                      f"{moved / 1e9:.3f} GB live, "
                      f"{line['share_of_819']:5.1f}% of 819 GB/s", flush=True)
        del k, v, scales
    sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
