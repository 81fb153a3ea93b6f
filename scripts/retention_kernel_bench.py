#!/usr/bin/env python3
"""`power_retention_decode` alone on the chip, at Brumby's sizes: seconds per
decode step's worth of calls (one per layer, the pool donated and updated in
place), the bytes that must move over them, and the kernel's output against
`kvstate._step` at full precision. By hand, through the chip tool:

    python scripts/retention_kernel_bench.py [--layers 20] [--slots 8]

`--shape solar-open2` is its sibling `kda_decode` (Kimi delta attention,
ops/pallas/mamba2.py) at that cell's sizes instead: 9 layers, 32 slots, 64
heads of 128, a `[8192, 128]` float32 state a layer and slot, against
`kvhybrid.kda_step`, at 32, 16 and 1 live rows and with every row idle.

Prints one line per number of live slots. Not part of the benchmark: the
cells `brumby-14b.reason-closed` and `solar-open2-250b.longctx-closed`
measure the kernels inside `engine_decode`.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _timed(run, state, live, steps: int):
    """Seconds a call of `run(*state, live) -> (*state, ys)`, warmed."""
    import jax

    *state, ys = run(*state, live)  # compile, warm
    jax.block_until_ready(ys)
    t = time.perf_counter()
    for _ in range(steps):
        *state, ys = run(*state, live)
    jax.block_until_ready(ys)
    return (time.perf_counter() - t) / steps, state


def solar_open2(steps: int, layers: int = 9, B: int = 32, H: int = 64,
                D: int = 128) -> int:
    """`kda_decode` alone at the Solar-Open2 cell's sizes."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu import kvhybrid
    from bigdl_tpu.ops.pallas.mamba2 import kda_decode

    ks = jax.random.split(jax.random.key(0), 7)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa
    q = unit(jax.random.normal(ks[0], (B, H, D))) * D ** -0.5
    k = unit(jax.random.normal(ks[1], (B, H, D)))
    v = jax.random.normal(ks[2], (B, H, D))
    g = -jax.random.uniform(ks[3], (B, H, D), minval=0.01, maxval=1.0)
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (B, H)))
    rows = jnp.arange(B, dtype=jnp.int32)
    S1 = 0.1 * jax.random.normal(ks[5], (1, B, H * D, D), jnp.float32)
    with jax.default_matmul_precision("highest"):
        yr, Sr = jax.jit(kvhybrid.kda_step)(q, k, v, g, beta,
                                            S1[0].reshape(B, H, D, D))
    y, S2 = kda_decode(S1, jnp.asarray(0), rows, rows >= 0, q, k, v, g, beta)
    print(f"kernel vs float32 step: y worst {float(jnp.abs(y - yr).max()):.3e}"
          f" of {float(jnp.abs(yr).max()):.3e}, S worst "
          f"{float(jnp.abs(S2[0].reshape(Sr.shape) - Sr).max()):.3e}",
          flush=True)
    del S1, S2, Sr

    def step(S, live):
        def one(S, layer):
            y, S = kda_decode(S, layer, rows, live, q, k, v, g, beta)
            return S, y[0, 0, 0]
        return jax.lax.scan(one, S, jnp.arange(layers))

    run = jax.jit(step, donate_argnums=0)
    state = [jnp.zeros((layers, B, H * D, D), jnp.float32)]
    row_bytes = layers * H * D * D * 4
    for n_live in sorted({B, B // 2, 1, 0}, reverse=True):
        dt, state = _timed(run, state, jnp.arange(B) < n_live, steps)
        moved = 2 * n_live * row_bytes
        print(f"kda_decode live {n_live} of {B}: {dt * 1e3:.2f} ms a step "
              f"(host clock, {steps} steps, {layers} layers), "
              f"{moved / 1e9:.2f} GB of state to move, "
              f"{100 * moved / 819e9 / dt:.1f}% of 819 GB/s", flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", choices=("brumby", "solar-open2"),
                    default="brumby")
    ap.add_argument("--layers", type=int, default=20)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--steps", type=int, default=20)
    args = ap.parse_args()

    import jax
    if args.shape == "solar-open2":
        dev = jax.devices()[0]
        print(f"device: {dev.platform} {dev.device_kind}", flush=True)
        return solar_open2(args.steps)

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
