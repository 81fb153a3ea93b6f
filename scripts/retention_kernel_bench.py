#!/usr/bin/env python3
"""`power_retention_decode` alone on the chip, at Brumby's sizes: seconds per
decode step's worth of calls (one per layer, the pool donated and updated in
place), the bytes that must move over them, and the kernel's output against
`kvstate._step` at full precision. By hand, through the chip tool:

    python scripts/retention_kernel_bench.py [--layers 20] [--slots 8]

Prints one line per number of live slots. Not part of the benchmark: the
cell `brumby-14b.reason-closed` measures the kernel inside `engine_decode`.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=20)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--steps", type=int, default=20)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu import kvstate
    from bigdl_tpu.ops.pallas.power_retention import power_retention_decode

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind}", flush=True)
    L, B, Hkv, G, D = args.layers, args.slots, 8, 5, 128
    P = kvstate.phi_dim(D)
    ks = jax.random.split(jax.random.key(0), 6)
    q = jax.random.normal(ks[0], (B, Hkv, G, D)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, Hkv, D)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, Hkv, D)).astype(jnp.bfloat16)
    g = -jnp.abs(jax.random.normal(ks[3], (B, Hkv)))

    # the kernel's output against float32 at full precision, one layer
    S1 = 0.1 * jax.random.normal(ks[4], (1, B, Hkv, D, P), jnp.float32)
    z1 = jnp.abs(jax.random.normal(ks[5], (1, B, Hkv, 1, P))) * 0.01
    rows = jnp.arange(B, dtype=jnp.int32)
    yr, Sr, zr = jax.jit(kvstate._step, static_argnums=6)(
        q, k, v, g, S1[0], z1[0, :, :, 0], 1e-6)
    y, S2, z2 = power_retention_decode(
        S1, z1, jnp.asarray(0), rows, rows >= 0, q, k, v, g)
    scale = float(jnp.abs(yr).max())
    print(f"kernel vs float32 step: y worst {float(jnp.abs(y - yr).max()):.3e}"
          f" of {scale:.3e}, S worst {float(jnp.abs(S2[0] - Sr).max()):.3e},"
          f" z worst {float(jnp.abs(z2[0, :, :, 0] - zr).max()):.3e}",
          flush=True)
    del S1, z1, S2, z2, Sr, zr

    def step(S, z, live):
        def one(carry, layer):
            S, z = carry
            y, S, z = power_retention_decode(S, z, layer, rows, live, q, k,
                                             v, g)
            return (S, z), y[0, 0, 0, 0]
        (S, z), ys = jax.lax.scan(one, (S, z), jnp.arange(L))
        return S, z, ys

    run = jax.jit(step, donate_argnums=(0, 1))
    S = jnp.zeros((L, B, Hkv, D, P), jnp.float32)
    z = jnp.zeros((L, B, Hkv, 1, P), jnp.float32)
    row_bytes = L * Hkv * (D + 1) * P * 4
    for n_live in sorted({B, B // 2, 1, 0}, reverse=True):
        live = jnp.asarray(np.arange(B) % max(B // max(n_live, 1), 1) == 0
                           if n_live else np.zeros(B, bool))
        live = live & (jnp.cumsum(live) <= n_live)
        S, z, ys = run(S, z, live)  # compile, warm
        jax.block_until_ready(ys)
        t = time.perf_counter()
        for _ in range(args.steps):
            S, z, ys = run(S, z, live)
        jax.block_until_ready(ys)
        dt = (time.perf_counter() - t) / args.steps
        moved = 2 * int(live.sum()) * row_bytes
        print(f"live {int(live.sum())} of {B}: {dt * 1e3:.2f} ms a step "
              f"(host clock, {args.steps} steps, {L} layers), "
              f"{moved / 1e9:.2f} GB of state to move, "
              f"{moved / dt / 1e9:.0f} GB/s, "
              f"{100 * moved / 819e9 / dt:.1f}% of 819 GB/s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
