#!/usr/bin/env python3
"""`paged_latent_decode_attention` alone on the chip, at GLM-4.7-Flash's sizes
(20 heads, rank 512 + 64 rope channels, pages of 64 tokens, 80 pages a row,
a pool of 2561 pages): seconds per decode step's worth of calls (one per
layer, each fed the last one's output so that none overlaps the next), the
latent bytes the live TOKENS hold, and the kernel's output against the `jnp`
absorbed form. By hand, through the chip tool:

    python scripts/latent_kernel_bench.py [--form grid rows] [--group 4 8 16]

Two forms, so that ONE run on one tree reads before and after:

- `rows`: the tree's kernel (since PR 51: grid (B,), the pool in HBM, a loop
  over the row's live groups, a DMA a live page), at `--group` pages a group
  (0 = what `tiling.latent_group_pages` gives);
- `grid`: this script's OWN copy of the kernel the tree dropped in PR 51: one
  grid step a (row, group of pages), every page a BlockSpec of its own on the
  same pool, a dead page's block clamped onto a live one.

One line per (form, pages a group, mix of rows): ms a step, us a live page
and layer, GB/s of latents, the share of the HBM time of the live tokens'
576 values (what `kernel.latent_attn_roofline` counts) and of the 640 lanes a
row is stored as. The mix `cell` is `glm-4.7-flash.longctx-closed`'s: 32 rows
at contexts spread over 1.2k to 5k. `--lower` compiles the plan for a
described v5e and runs nothing (no chip); `--rehearse` walks it on the CPU at
a tiny size, interpreted. Not part of the benchmark: the cell
measures the kernel inside `engine_decode`.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

H, R, DR, PAGE, MAX_PAGES = 20, 512, 64, 64, 80
WIDTH = 640  # a row of R + DR values as the pool stores it
SCALE = (192 + 64) ** -0.5
HBM_BYTES_PER_S = 819e9


def grid_form(q_eff, q_pe, lat_pages, block_tables, layer, pos, start, scale,
              live, pages_per_step, interpret=None):
    """The (row, page group) grid as `bigdl_tpu` had it until PR 51."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from bigdl_tpu.ops.pallas import interpret_mode
    from bigdl_tpu.ops.pallas import paged_attention as pa

    if interpret is None:
        interpret = interpret_mode()
    B, n_heads, r = q_eff.shape
    page, width = lat_pages.shape[2:]
    mp = block_tables.shape[1]
    G = min(pages_per_step, mp)
    Hp = -(-n_heads // 16) * 16

    def kernel(bt_ref, meta_ref, q_ref, *refs):
        lat_refs, (o_ref, acc_ref, m_ref, l_ref) = refs[:G], refs[G:]
        b, p = pl.program_id(0), pl.program_id(1)

        @pl.when(p == 0)
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)
            m_ref[:] = jnp.full_like(m_ref, -1e30)
            l_ref[:] = jnp.zeros_like(l_ref)

        first_b, last_b = meta_ref[1 + 2 * B + b], meta_ref[1 + 3 * B + b]

        @pl.when((first_b <= last_b) & (p * G <= last_b)
                 & (p * G + G - 1 >= first_b))
        def _live_step():
            pos_b, start_b = meta_ref[1 + b], meta_ref[1 + B + b]
            lat = jnp.concatenate([x[0, 0] for x in lat_refs], axis=0) \
                if G > 1 else lat_refs[0][0, 0]
            s = jax.lax.dot_general(
                q_ref[0], lat, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            slot = p * (G * page) + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            valid = (slot >= start_b) & (slot <= pos_b)
            s = jnp.where(valid, s, -1e30)
            m_prev = m_ref[:]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            pexp = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[:] = l_ref[:] * alpha + jnp.sum(pexp, axis=1, keepdims=True)
            acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
                pexp.astype(lat.dtype), lat[:, :r], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[:] = m_new

        @pl.when(p == pl.num_programs(1) - 1)
        def _finish():
            l = l_ref[:]
            o_ref[0] = (acc_ref[:] / jnp.where(l == 0.0, 1.0, l)
                        ).astype(o_ref.dtype)

    q = jnp.concatenate([q_eff, q_pe], axis=-1).astype(lat_pages.dtype)
    q = jnp.pad(q, ((0, 0), (0, Hp - n_heads), (0, width - q.shape[-1])))
    pos, start = pos.astype(jnp.int32), start.astype(jnp.int32)
    first, last = pa.live_page_range(pos, start, 2 ** 30, page, mp, live)
    meta = jnp.concatenate([
        jnp.reshape(layer, (1,)).astype(jnp.int32), pos, start, first, last])

    def lat_spec(j):
        def index(b, p, bt, meta):
            pg = pa.clamped_page(p * G + j, meta[1 + 2 * B + b],
                                 meta[1 + 3 * B + b])
            return meta[0], bt[b, pg], 0, 0
        return pl.BlockSpec((1, 1, page, width), index)

    out = pl.pallas_call(
        kernel,
        name="paged_latent_decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, -(-mp // G)),
            in_specs=[pl.BlockSpec((1, Hp, width), lambda b, p, *_: (b, 0, 0))]
            + [lat_spec(j) for j in range(G)],
            out_specs=pl.BlockSpec((1, Hp, r), lambda b, p, *_: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((Hp, r), jnp.float32),
                            pltpu.VMEM((Hp, 1), jnp.float32),
                            pltpu.VMEM((Hp, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, Hp, r), jnp.bfloat16),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables, meta, q, *([lat_pages] * G))
    return out[:, :n_heads]


def kernel_of(form: str, group: int, interpret=None):
    """`f(q_eff, q_pe, lat, bt, layer, pos, start, live)` of one variant."""
    from bigdl_tpu.ops.pallas import paged_attention as pa

    if form == "grid":
        return lambda qe, qp, lat, bt, layer, pos, start, live: grid_form(
            qe, qp, lat, bt, layer, pos, start, SCALE, live, group,
            interpret=interpret)
    return lambda qe, qp, lat, bt, layer, pos, start, live: \
        pa.paged_latent_decode_attention(
            qe, qp, lat, bt, layer, pos, start, scale=SCALE, live=live,
            pages_per_group=group or None, interpret=interpret)


def step_of(kernel, n_layers: int):
    """A decode step's worth of calls. The pool rides the scan as a carry
    and is donated, as the engine's is: a loop-invariant operand of 4.2 GB
    is copied into the loop once a call (16 ms of every reading, the first
    time this ran)."""
    import jax
    import jax.numpy as jnp

    def step(lat, q_eff, q_pe, bt, pos, start, live):
        def one(carry, layer):
            lat, q = carry
            ctx = kernel(q, q_pe, lat, bt, layer, pos, start, live)
            return (lat, (q + 1e-3 * ctx).astype(q.dtype)), None
        return jax.lax.scan(one, (lat, q_eff), jnp.arange(n_layers))[0]

    return jax.jit(step, donate_argnums=(0,))


def mixes(B: int):
    """[(name, live [B] bool, pos [B])]: the cell's mix first (contexts
    spread evenly over 1.2k .. 5k, every row live), then the same number of
    pages on every live row."""
    import numpy as np

    cap, mp = MAX_PAGES * PAGE, MAX_PAGES
    out = [("cell", np.ones(B, bool),  # 1200 .. 5000 of 5120
            np.linspace(cap * 15 // 64, cap * 125 // 128, B).astype(np.int32))]
    for n_live, pages in ((1, mp // 2), (B, mp // 5), (B, mp // 2), (B, mp)):
        out.append((f"{n_live}x{pages}", np.arange(B) < n_live,
                    np.full(B, pages * PAGE - 1, np.int32)))
    return out


def lower(args, variants) -> int:
    """Compile every variant for a described v5e at the run's shapes."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    L, B = args.layers, args.slots
    for form, group in variants:
        c = step_of(kernel_of(form, group), L).lower(
            sds((L, B * MAX_PAGES + 1, PAGE, WIDTH), jnp.bfloat16),
            sds((B, H, R), jnp.bfloat16), sds((B, H, DR), jnp.bfloat16),
            sds((B, MAX_PAGES), jnp.int32), sds((B,), jnp.int32),
            sds((B,), jnp.int32), sds((B,), jnp.bool_)).compile()
        print(f"{form} group {group}: compiles, "
              f"{c.memory_analysis().temp_size_in_bytes / 2**20:.2f} MiB of "
              f"temporaries", flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=20)
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--form", nargs="*", choices=("grid", "rows"),
                    default=["grid", "rows"])
    ap.add_argument("--group", type=int, nargs="*", default=[0],
                    help="pages a group; 0 = the tree's own rule (rows), "
                    "8 as the old grid had it (grid)")
    ap.add_argument("--lower", action="store_true")
    ap.add_argument("--rehearse", action="store_true",
                    help="walk the plan on the CPU at a tiny size, "
                    "interpreted: no time printed means anything")
    args = ap.parse_args()
    if args.rehearse:
        global R, PAGE, MAX_PAGES, WIDTH
        R, PAGE, MAX_PAGES, WIDTH = 128, 16, 20, 256
        args.layers, args.slots, args.steps = 2, 4, 1
        os.environ["BIGDL_TPU_PALLAS"] = "interpret"
    variants = [(f, g or (8 if f == "grid" else 0))
                for f in args.form for g in args.group]
    if args.lower:
        return lower(args, variants)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.ops.pallas import tiling

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind}", flush=True)
    L, B = args.layers, args.slots
    ks = jax.random.split(jax.random.key(0), 3)
    # the pool's rows as the engine keeps them: zero-padded to whole tiles
    # of 128 lanes (kvpaged.PagedLatentCache)
    lat = jnp.pad(
        jax.random.normal(ks[0], (L, B * MAX_PAGES + 1, PAGE, R + DR),
                          jnp.bfloat16),
        ((0, 0),) * 3 + ((0, WIDTH - R - DR),))
    q_eff = jax.random.normal(ks[1], (B, H, R), jnp.bfloat16)
    q_pe = jax.random.normal(ks[2], (B, H, DR), jnp.bfloat16)
    bt = jnp.asarray(1 + np.random.default_rng(0).permutation(B * MAX_PAGES)
                     .reshape(B, MAX_PAGES), jnp.int32)
    start = jnp.zeros((B,), jnp.int32)

    @jax.jit
    def reference(lat, layer, pos, live):  # the pool is an ARGUMENT
        # everywhere: a jit that captured it would copy 4 GB into the program
        rows = lat[layer][bt].reshape(B, MAX_PAGES * PAGE, -1)[..., :R + DR]
        q = jnp.concatenate([q_eff, q_pe], -1)
        s = jnp.einsum("bhw,bsw->bhs", q, rows,
                       preferred_element_type=jnp.float32) * SCALE
        ok = (jnp.arange(MAX_PAGES * PAGE)[None] <= pos[:, None]) \
            & live[:, None]
        p = jax.nn.softmax(jnp.where(ok[:, None], s, -1e30), -1)
        p = jnp.where(ok[:, None], p, 0.0)
        return jnp.einsum("bhs,bsr->bhr", p.astype(jnp.bfloat16),
                          rows[..., :R], preferred_element_type=jnp.float32)

    pos = jnp.asarray(np.linspace(1, MAX_PAGES // 2 * PAGE - 7, B), jnp.int32)
    live = jnp.arange(B) % 2 == 0
    want = reference(lat, 3, pos, live)
    seen = {}  # pages a group -> the first form's output at it
    for form, group in variants:
        got = jax.jit(kernel_of(form, group))(
            q_eff, q_pe, lat, bt, jnp.asarray(3), pos, start, live)
        pages = group or tiling.latent_group_pages(PAGE, WIDTH, 2, H,
                                                   MAX_PAGES)
        first = seen.setdefault(pages, got)
        print(f"{form} group {group} vs jnp absorbed form: worst "
              f"{float(jnp.abs(got.astype(jnp.float32) - want).max()):.3e} "
              f"of {float(jnp.abs(want).max()):.3e}; idle rows "
              f"{float(jnp.abs(got[1::2].astype(jnp.float32)).max()):.1e}; "
              f"bit-equal to the first form at {pages} pages a group: "
              f"{bool(jnp.array_equal(got, first))}", flush=True)

    for form, group in variants:
        run = step_of(kernel_of(form, group), L)
        for name, live, pos in mixes(B):
            live, pos = jnp.asarray(live), jnp.asarray(pos)
            lat, out = run(lat, q_eff, q_pe, bt, pos, start, live)
            jax.block_until_ready(out)
            t = time.perf_counter()
            for _ in range(args.steps):
                lat, out = run(lat, q_eff, q_pe, bt, pos, start, live)
            jax.block_until_ready(out)
            dt = (time.perf_counter() - t) / args.steps
            tokens = int(jnp.sum(jnp.where(live, pos + 1, 0)))
            pages = int(jnp.sum(jnp.where(live, pos // PAGE + 1, 0)))
            moved = tokens * L * (R + DR) * 2
            stored = pages * PAGE * L * WIDTH * 2
            print(f"{form} group {group:2d} mix {name:>5}: "
                  f"{dt * 1e3:7.3f} ms a step ({L} layers, host clock over "
                  f"{args.steps}), {dt * 1e6 / (pages * L):6.3f} us a live "
                  f"page and layer, {100 * pages / (B * MAX_PAGES):5.1f}% of "
                  f"pages live, {moved / 1e9:.3f} GB of latents, "
                  f"{moved / dt / 1e9:6.1f} GB/s, "
                  f"{100 * moved / HBM_BYTES_PER_S / dt:5.1f}% of HBM time "
                  f"by tokens, {100 * stored / HBM_BYTES_PER_S / dt:5.1f}% "
                  f"by pages as stored", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
