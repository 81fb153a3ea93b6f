#!/usr/bin/env python3
"""Do a cell's engine programs fit the chip, and what do they keep beside
their arguments? Compile `engine_decode` and `engine_paged_prefill` of a
configuration of bench/configs/ at their real sizes with the TPU compiler
for a DESCRIBED v5e (no chip attached, no chip time) and read
`memory_analysis()`. ONE tool for every paged cache kind: the pool comes from
the engine's own `_make_pool` under `jax.eval_shape` (so from
`engine.kind.make_pool`), the prefill's pool argument is
`engine.kind.leaves(pool)`, a table for each group. Nothing runs, so this
says nothing about time. A minute or two a configuration.

    JAX_PLATFORMS=cpu python scripts/engine_fit.py \
        --config granite-4.0-h-small-int4 --prefill 256 2048 [--n-slots N]

The tree is the one a `TpuModel` serves from: `optimize_model`'s shapes with
the kernels' scale bits prepared beside them (`llama.prepare_kernel_scales`;
`--unprepared` compiles the tree without them, as before PR 48). It counts
the prepared arrays among `engine_decode`'s arguments and prints, from the
optimized HLO, what the decode step's LOOP BODIES copy, view or slice out
(`copy`, `bitcast-convert`, `dynamic-slice` and the fusions named after them),
each with the bytes its result holds in HBM, tiles padded: a scale stack
re-laid every layer of every step shows here (12.7 ms of Laguna's 28 ms step,
PR 47) and costs no chip time to find.

It is what `bench/tools/fit.py`, `fit_state.py`, `fit_latent.py`,
`fit_hybrid.py` and `fit_window.py` each do for one kind (ROADMAP B11, D2):
those pass the pool to the prefill as positional arrays, the signature it
had before PR 46, so their prefill rows fail until a `benchmark` PR makes
them this.
"""

from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

GIB = 2.0 ** 30
MIB = 2.0 ** 20

_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
             "s16": 2, "u16": 2, "f16": 2, "bf16": 2, "s32": 4, "u32": 4,
             "f32": 4, "s64": 8, "u64": 8, "f64": 8}


def padded_bytes(shape: str) -> int:
    """Bytes an HLO result `u16[256,512,64]{2,1,0:T(8,128)(2,1)}` holds in
    HBM: the two minor dimensions rounded up to the layout's tile (a second
    tile `(2,1)` packs two rows of 16-bit values into one sublane)."""
    import re

    m = re.match(r"(\w+)\[([\d,]*)\](?:\{([\d,]*)(?::([^}]*))?\})?", shape)
    if not m or m.group(1) not in _ITEMSIZE:
        return 0
    dims = [int(d) for d in m.group(2).split(",") if d]
    order = [int(d) for d in (m.group(3) or "").split(",") if d] \
        or list(range(len(dims) - 1, -1, -1))
    tiles = re.findall(r"\(([\d,]+)\)", m.group(4) or "")
    if tiles and dims:
        tile = [int(t) for t in tiles[0].split(",")]
        if len(tiles) > 1:
            tile[0] *= int(tiles[1].split(",")[0])
        for t, axis in zip(reversed(tile), order):
            dims[axis] = -(-dims[axis] // t) * t
    n = _ITEMSIZE[m.group(1)]
    for d in dims:
        n *= d
    return n


def loop_body_moves(hlo: str) -> list:
    """(padded bytes, name, opcode, result shape) of every instruction in
    a `while` body of the optimized module `hlo` (and in what such a body
    calls, fused computations aside: what a fusion holds inside is never
    in HBM by itself) that copies, views or slices: `copy`,
    `bitcast-convert`, `dynamic-slice`, and a fusion named after one."""
    import re

    comps, name = {}, None
    for line in hlo.splitlines():
        m = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif name is not None and line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    calls = re.compile(
        r"(?:body|condition|to_apply|branch_computations|"
        r"true_computation|false_computation)=\{?%?([\w.\-,% ]+)\}?")
    todo = [b for lines in comps.values() for line in lines
            for b in re.findall(r"body=%?([\w.\-]+)", line)]
    seen = set()
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for line in comps[c]:
            for group in calls.findall(line):
                todo += [g.strip(" %") for g in group.split(",")]
    moves = []
    for c in sorted(seen):
        for line in comps[c]:
            m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\S+) ([\w\-]+)\(",
                         line)
            if not m:
                continue
            inst, shape, op = m.groups()
            if op in ("copy", "bitcast-convert", "dynamic-slice") or (
                    op == "fusion" and re.search(
                        "copy|bitcast|slice", inst)
                    and "dynamic-update-slice" not in inst):
                moves.append((padded_bytes(shape), inst, op, shape))
    return sorted(moves, reverse=True)


def pool_leaf_as_compiled(hlo: str, shape: tuple) -> tuple:
    """(padded bytes, layout, [opcode x count]) of the pool leaf of `shape`
    in the optimized module `hlo`: the layout its `parameter` has, and every
    OTHER instruction whose result has the leaf's dimensions and is no view
    of it (a `copy` of a pool is a pool's bytes moved every step; the
    kernels' aliased results and tuple plumbing are the pool itself)."""
    import collections
    import re

    dims = ",".join(map(str, shape))
    pat = re.compile(
        r"\s*(?:ROOT )?%?[\w.\-]+ = \(?(\w+\[" + re.escape(dims)
        + r"\](?:\{[^}]*\})?)[^=]*? ([\w\-]+)\(")
    n, layout, others = 0, "", collections.Counter()
    for line in hlo.splitlines():
        m = pat.match(line)
        if not m:
            continue
        if m.group(2) == "parameter":
            n, layout = padded_bytes(m.group(1)), m.group(1).split("]")[1]
        elif m.group(2) not in ("get-tuple-element", "bitcast", "tuple",
                                "custom-call", "while", "conditional",
                                "dynamic-update-slice"):
            others[m.group(2)] += 1
    return n, layout, [f"{op} x{k}" for op, k in sorted(others.items())]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--n-slots", type=int)
    ap.add_argument("--prefill", type=int, nargs="*", default=[1024])
    ap.add_argument("--unprepared", action="store_true",
                    help="the tree without the kernels' scale bits")
    ap.add_argument("--hlo", metavar="DIR",
                    help="write each program's optimized HLO there")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import cells, weights
    from bigdl_tpu.api import TpuModel
    from bigdl_tpu.models.config import ModelConfig
    from bigdl_tpu.serving.engine import InferenceEngine

    config = cells.load_json(ROOT, "bench", "configs", args.config + ".json")
    cfg = ModelConfig.from_hf_config(cells.as_run(config))
    e, qtype = dict(config["bench"]["engine"]), config["bench"]["qtype"]
    B = args.n_slots or e["n_slots"]

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def on_chip(tree):
        return jax.tree.map(lambda s: arr(s.shape, s.dtype), tree)

    from bigdl_tpu.models.llama import prepare_kernel_scales

    jax.default_backend = lambda: "tpu"  # the target, not where this runs
    params = weights.param_shapes(cfg, qtype)
    w_bytes = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(params))
    if not args.unprepared:
        params = jax.eval_shape(
            lambda p: prepare_kernel_scales(cfg, p), params)
    params = on_chip(params)
    bits = [s for s in jax.tree.leaves(params) if s.dtype == jnp.uint16]
    bits_bytes = sum(  # in (16, 128) tiles of uint16
        2 * s.size // (s.shape[-2] * s.shape[-1])
        * -(-s.shape[-2] // 16) * 16 * -(-s.shape[-1] // 128) * 128
        for s in bits)

    # the engine lends its programs and its pool's SHAPE: the pool it holds
    # itself is one slot's, and the cell's geometry is set on it afterwards
    eng = InferenceEngine(TpuModel(cfg, None, qtype), n_slots=1,
                          max_len=e["max_len"], paged=True,
                          page_size=e["page_size"], n_pages=2)
    eng.n_slots = B
    eng.page_size, eng.n_pages = eng.kind.page_geometry(
        B, e["max_len"], e["page_size"], e["n_pages"])
    pool = on_chip(jax.eval_shape(eng._make_pool))
    pool_bytes = sum(s.size * s.dtype.itemsize
                     for s in jax.tree.leaves(eng.kind.leaves(pool)))

    rows = []
    sampling = (arr((B,), jnp.float32), arr((B,), jnp.int32),
                arr((B,), jnp.float32), arr((B,), jnp.bool_))
    if eng.blocks is not None:  # a pass over every row's block
        from bigdl_tpu.serving.blocks import BlockState

        b = cfg.block_length
        state = BlockState(ids=arr((B, b), jnp.int32),
                           revealed=arr((B, b), jnp.bool_),
                           n_pass=arr((B,), jnp.int32))
        dec = eng._decode.lower(
            params, state, pool, arr((2,), jnp.uint32), *sampling).compile()
    else:
        dec = eng._decode.lower(
            params, arr((B,), jnp.int32), pool, arr((2,), jnp.uint32),
            *sampling, arr((B, cfg.vocab_size), jnp.bool_),
            arr((B,), jnp.float32), lora=None).compile()
    rows.append((f"engine_decode B={B}", dec.memory_analysis()))
    table = arr((1, eng.max_pages_per_row), jnp.int32)
    pres = []
    for T in args.prefill:
        pre = eng._paged_prefill.lower(
            params, eng.kind.leaves(pool), (table, table),
            arr((1,), jnp.int32), arr((1, T), jnp.int32), arr((), jnp.int32),
            arr((1,), jnp.int32), lora=None).compile()
        rows.append((f"engine_paged_prefill T={T}", pre.memory_analysis()))
        pres.append((f"engine_paged_prefill_T{T}", pre))

    print(f"{args.config} ({eng.kind.name}): {cfg.num_hidden_layers} layers, "
          f"weights {w_bytes / GIB:.2f} GiB ({w_bytes / 1e9:.2f} GB), pool "
          f"{pool_bytes / GIB:.2f} GiB ({pool_bytes / 1e9:.2f} GB as shaped; "
          "the compiler's own count, tiles padded, is each program's aliased "
          f"argument), {B} slots")
    for name, m in rows:
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 - m.alias_size_in_bytes + m.temp_size_in_bytes)
        print(f"  {name:32s} temporaries {m.temp_size_in_bytes / GIB:6.2f} "
              f"GiB, arguments {m.argument_size_in_bytes / GIB:6.2f}, "
              f"outputs not aliased "
              f"{(m.output_size_in_bytes - m.alias_size_in_bytes) / GIB:6.2f}"
              f", in all {total / GIB:6.2f} GiB ({total / 1e9:.2f} GB)")
    print(f"  prepared scale bits among the arguments: {len(bits)} arrays, "
          f"{bits_bytes / GIB:.2f} GiB ({bits_bytes / 1e9:.2f} GB) in "
          "(16, 128) tiles")
    hlo = dec.as_text()
    if args.hlo:
        os.makedirs(args.hlo, exist_ok=True)
        for name, program in [("engine_decode", dec)] + pres:
            with open(os.path.join(args.hlo, name + ".hlo.txt"), "w") as f:
                f.write(program.as_text())
    print("  the pool as engine_decode's compiled arguments hold it (tiles "
          "padded), and what else of a leaf's size the program makes:")
    for leaf, s in zip(eng.kind.arrays, eng.kind.leaves(pool)):
        if s is None:
            continue
        n, as_arg, others = pool_leaf_as_compiled(hlo, s.shape)
        print(f"    {leaf:8s} {str(tuple(s.shape)):28s} "
              f"{s.size * s.dtype.itemsize / GIB:5.2f} GiB as shaped, "
              f"{n / GIB:5.2f} as compiled {as_arg}; other results of its "
              f"shape: {others or 'none'}")
    moves = loop_body_moves(hlo)
    print(f"  engine_decode's loop bodies copy, view or slice "
          f"{sum(m[0] for m in moves) / MIB:.1f} MiB a turn in "
          f"{len(moves)} operations (padded bytes of each result):")
    for n, inst, op, shape in moves[:24]:
        print(f"    {n / MIB:9.2f} MiB  {op:16s} {inst:44s} {shape}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
