#!/usr/bin/env python3
"""`flash_attention` alone on the chip, at the prefill shapes of the cells
(one row, the row's whole slot range as S, bfloat16 q and cache): a layer
stack's worth of calls inside one jit, each fed the last one's output so that
none overlaps the next; host clock over several such stacks, and the device
time of the `flash_attention` events of one traced stack (what the cells'
`kernel.flash_attn_mfu` divides by), against the causal half's FLOPs
(4 * Hq * D * T * (T + 1) / 2 a call: the algorithm's count, not the tiles').
By hand, through the chip tool:

    python scripts/flash_kernel_bench.py [--shape mistral-7b ...]
    python scripts/flash_kernel_bench.py --tree .bench_checkout/parent
    python scripts/flash_kernel_bench.py --tiles 128x512 256x256

`--tree` times the kernel of another checkout (the parent's) with the same
script; `--tiles BQxBK` hands the kernel those tiles in place of
`tiling.flash_blocks`' (the kernel reads no such option from anywhere else).
`--check` compares each shape's output with the float32 masked `ops.attention`.
`--lower` compiles every plan entry for a described v5e and runs nothing;
`--rehearse` walks the script on a CPU through the Pallas interpreter at a
tiny size. One line per (shape, T, tiles): ms a call on the host's clock, ms a
call on the device, TFLOP/s and share of 197; the same as JSON lines under
`chiprun_out/`. Not part of the benchmark: the cells measure the kernel inside
`engine_paged_prefill`.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# name: (query heads, KV heads, head size, slots of a row, window, layers
# timed, prompt lengths). GLM's heads are its expanded prefill's (q, k and
# v widened to 256); SmallThinker's window is its window layers'
SHAPES = {
    "mistral-7b": (32, 8, 128, 2048, 4096, 32, (1024, 1280, 1536, 1792)),
    "generate": (32, 8, 128, 1152, 4096, 32, (1024,)),
    "qwen2-7b": (28, 4, 128, 2048, None, 28, (64, 256, 1024)),
    "glm-4.7-flash": (20, 20, 256, 5120, None, 20, (1024, 4096)),
    "smallthinker": (28, 4, 128, 9216, None, 6, (512, 2048, 8192)),
    "smallthinker-window": (28, 4, 128, 9216, 4096, 18, (512, 8192)),
}
PEAK = 197e12


def flash_device_seconds(logdir: str) -> tuple:
    """(events, seconds) of the `flash_attention` operations of a trace."""
    sys.path.insert(0, ROOT)
    from bench.reduce import xplane

    loaded = xplane.load(xplane.find_trace(logdir), "none")
    evs = [e for ops in loaded.ops.values() for e in ops
           if xplane._base(e.name) == "flash_attention"]
    return len(evs), sum(e.dur for e in evs)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", nargs="*", default=list(SHAPES))
    ap.add_argument("--tree", default=None)
    ap.add_argument("--tiles", nargs="*", default=[None])
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--lower", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--tag", default="flash")
    args = ap.parse_args()

    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ["BIGDL_TPU_PALLAS"] = "interpret"
    if args.lower:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.path.abspath(args.tree) if args.tree else ROOT)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.ops.attention import attention
    from bigdl_tpu.ops.pallas.flash_attention import flash_attention

    one_chip = None
    if args.lower:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        one_chip = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
    elif not args.rehearse and jax.devices()[0].platform != "tpu":
        print("no TPU: nothing to time (use --lower or --rehearse)")
        return 2
    print(f"device {jax.devices()[0].device_kind}, tree "
          f"{args.tree or '.'}", flush=True)

    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, f"{args.tag}_kernel_bench.jsonl")
    for name in args.shape:
        Hq, Hkv, D, S, window, layers, Ts = SHAPES[name]
        if args.rehearse:
            D, S, layers, Ts = 32, 96, 2, (24, 48)
            window = 40 if window else None
        for T in Ts:
            for tiles in args.tiles:
                kw = {}
                if tiles:
                    bq, bk = (int(x) for x in tiles.split("x"))
                    kw = dict(block_q=bq, block_k=bk)

                def call(q, k, v):
                    return flash_attention(
                        q, k, v, start=jnp.zeros((1,), jnp.int32),
                        q_offset=jnp.zeros((), jnp.int32), window=window,
                        interpret=False if args.lower else None, **kw)

                def stack(q, k, v):
                    def body(_, q):
                        return call(q, k, v)
                    return jax.lax.fori_loop(0, layers, body, q)

                shapes = ((1, T, Hq, D), (1, S, Hkv, D), (1, S, Hkv, D))
                head = f"{name} T={T} S={S} tiles {tiles or 'policy'}: "
                if args.lower:
                    t = time.perf_counter()
                    try:
                        jax.jit(stack).lower(*(
                            jax.ShapeDtypeStruct(s, jnp.bfloat16,
                                                 sharding=one_chip)
                            for s in shapes)).compile()
                    except Exception as e:  # noqa: BLE001  (Mosaic's refusal)
                        print(head + "REFUSED: "
                              + str(e).split("\n")[0][:300], flush=True)
                        continue
                    print(head + f"compiled in {time.perf_counter() - t:.1f}"
                          " s", flush=True)
                    continue
                keys = jax.random.split(jax.random.PRNGKey(T), 3)
                q, k, v = (jax.random.normal(kk, s, jnp.bfloat16)
                           for kk, s in zip(keys, shapes))
                fn = jax.jit(stack)
                try:
                    out = jax.block_until_ready(fn(q, k, v))
                except Exception as e:  # noqa: BLE001  (Mosaic's refusal)
                    print(head + "REFUSED: " + str(e).split("\n")[0][:300],
                          flush=True)
                    continue
                t = time.perf_counter()
                for _ in range(args.reps):
                    out = fn(q, k, v)
                jax.block_until_ready(out)
                host_ms = (time.perf_counter() - t) / args.reps / layers * 1e3
                dev_ms = None
                if not args.rehearse:
                    logdir = os.path.join(ROOT, ".bench_trace",
                                          f"{args.tag}_{name}_{T}_{tiles}")
                    jax.profiler.start_trace(logdir)
                    jax.block_until_ready(fn(q, k, v))
                    jax.profiler.stop_trace()
                    n, secs = flash_device_seconds(logdir)
                    dev_ms = secs / max(n, 1) * 1e3
                flops = 4 * Hq * D * T * (T + 1) / 2
                if window is not None and window < T:
                    w = window  # rows past the window see `window` columns
                    flops = 4 * Hq * D * (w * (w + 1) / 2 + (T - w) * w)
                row = {"shape": name, "T": T, "S": S, "tiles": tiles,
                       "tree": args.tree or ".", "host_ms": host_ms,
                       "device_ms": dev_ms, "flops_a_call": flops}
                line = head + f"{host_ms:.3f} ms a call (host)"
                if dev_ms:
                    tf = flops / (dev_ms * 1e-3)
                    row["tflops"], row["mfu"] = tf / 1e12, tf / PEAK
                    line += (f", {dev_ms:.3f} ms (device), "
                             f"{tf / 1e12:.1f} TFLOP/s = "
                             f"{100 * tf / PEAK:.1f}% of 197")
                # the reference holds [Hq, T, S] float32 scores: up to 2 GiB
                if args.check and Hq * T * S * 4 <= 2 ** 31:
                    one = jax.jit(call)(q, k, v).astype(jnp.float32)
                    slots = jnp.arange(T)[:, None]
                    sj = jnp.arange(S)[None, :]
                    mask = sj <= slots
                    if window is not None:
                        mask = mask & (sj > slots - window)
                    ref = attention(q.astype(jnp.float32),
                                    k.astype(jnp.float32),
                                    v.astype(jnp.float32),
                                    mask[None, None, None])
                    err = float(jnp.max(jnp.abs(one - ref)))
                    row["max_abs_err"] = err
                    line += f", max |err| {err:.4f}"
                    assert np.isfinite(err)
                print(line, flush=True)
                with open(out_path, "a", encoding="utf-8") as f:
                    f.write(json.dumps(row) + "\n")
    return 3 if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
