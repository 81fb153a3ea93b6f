#!/usr/bin/env python3
"""`paged_latent_decode_attention` alone on the chip, at GLM-4.7-Flash's sizes
(20 heads, rank 512 + 64 rope channels, pages of 64 tokens, 80 pages a row,
a pool of 2561 pages): seconds per decode step's worth of calls (one per
layer, each fed the last one's output so that none overlaps the next), the
latent bytes the live TOKENS hold, and the kernel's output against the `jnp`
absorbed form. By hand, through the chip tool:

    python scripts/latent_kernel_bench.py [--layers 20] [--group 1 4 8 16]

One line per (live slots, live pages a slot, pages a grid step): us a live
page and layer, GB/s of latents, share of 819 GB/s. Not part of the
benchmark: the cell `glm-4.7-flash.longctx-closed` measures the kernel inside
`engine_decode`.
"""

from __future__ import annotations

import argparse
import functools
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=20)
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--group", type=int, nargs="*", default=None)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.ops.pallas import paged_attention as pa

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind}", flush=True)
    L, B, H, r, dr, page, mp = args.layers, args.slots, 20, 512, 64, 64, 80
    n_pages = B * mp + 1
    scale = (192 + 64) ** -0.5
    ks = jax.random.split(jax.random.key(0), 3)
    # the pool's rows as the engine keeps them: zero-padded to whole tiles
    # of 128 lanes (kvpaged.PagedLatentCache)
    lat = jnp.pad(
        jax.random.normal(ks[0], (L, n_pages, page, r + dr), jnp.bfloat16),
        ((0, 0),) * 3 + ((0, -(r + dr) % 128),))
    q_eff = jax.random.normal(ks[1], (B, H, r), jnp.bfloat16)
    q_pe = jax.random.normal(ks[2], (B, H, dr), jnp.bfloat16)
    bt = jnp.asarray(1 + np.random.default_rng(0).permutation(B * mp)
                     .reshape(B, mp), jnp.int32)
    start = jnp.zeros((B,), jnp.int32)

    def reference(lat, layer, pos, live):  # the pool is an ARGUMENT
        # everywhere: a jit that captured it would copy 4 GB into the program
        rows = lat[layer][bt].reshape(B, mp * page, -1)[..., :r + dr]
        q = jnp.concatenate([q_eff, q_pe], -1)
        s = jnp.einsum("bhw,bsw->bhs", q, rows,
                       preferred_element_type=jnp.float32) * scale
        ok = (jnp.arange(mp * page)[None] <= pos[:, None]) & live[:, None]
        p = jax.nn.softmax(jnp.where(ok[:, None], s, -1e30), -1)
        p = jnp.where(ok[:, None], p, 0.0)
        return jnp.einsum("bhs,bsr->bhr", p.astype(jnp.bfloat16),
                          rows[..., :r], preferred_element_type=jnp.float32)

    pos = jnp.full((B,), 40 * page - 7, jnp.int32)
    live = jnp.arange(B) % 2 == 0
    got = pa.paged_latent_decode_attention(
        q_eff, q_pe, lat, bt, jnp.asarray(3), pos, start, scale=scale,
        live=live).astype(jnp.float32)
    want = jax.jit(reference)(lat, 3, pos, live)
    print(f"kernel vs jnp absorbed form: worst "
          f"{float(jnp.abs(got - want).max()):.3e} of "
          f"{float(jnp.abs(want).max()):.3e}; idle rows "
          f"{float(jnp.abs(got[1::2]).max()):.1e}", flush=True)

    def step(lat, q_eff, pos, live, group):
        # the pool rides the scan as a carry and is donated, as the engine's
        # does: a loop-invariant operand of 4.2 GB is copied into the loop
        # once a call (16 ms of every reading, the first time this ran)
        def one(carry, layer):
            lat, q = carry
            ctx = pa.paged_latent_decode_attention(
                q, q_pe, lat, bt, layer, pos, start, scale=scale, live=live,
                pages_per_step=group)
            return (lat, (q + 1e-3 * ctx).astype(q.dtype)), None
        return jax.lax.scan(one, (lat, q_eff), jnp.arange(L))[0]

    for group in args.group or [pa.LATENT_PAGES_PER_STEP]:
        run = jax.jit(functools.partial(step, group=group),
                      donate_argnums=(0,))
        for n_live in sorted({1, min(8, B), B}):
            for live_pages in (16, 40, 80):
                live = jnp.arange(B) < n_live
                pos = jnp.full((B,), live_pages * page - 1, jnp.int32)
                lat, out = run(lat, q_eff, pos, live)
                jax.block_until_ready(out)
                t = time.perf_counter()
                for _ in range(args.steps):
                    lat, out = run(lat, q_eff, pos, live)
                jax.block_until_ready(out)
                dt = (time.perf_counter() - t) / args.steps
                tokens = n_live * live_pages * page
                moved = tokens * L * (r + dr) * 2
                print(f"group {group:2d} live slots {n_live:2d} x "
                      f"{live_pages} pages: {dt * 1e3:7.3f} ms a step "
                      f"({L} layers, host clock over {args.steps}), "
                      f"{dt * 1e6 / (n_live * live_pages * L):6.3f} us a "
                      f"live page and layer, {moved / 1e9:.3f} GB of "
                      f"latents, {moved / dt / 1e9:6.1f} GB/s, "
                      f"{100 * moved / 819e9 / dt:5.1f}% of 819 GB/s",
                      flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
