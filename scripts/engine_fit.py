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


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--n-slots", type=int)
    ap.add_argument("--prefill", type=int, nargs="*", default=[1024])
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

    params = on_chip(weights.param_shapes(cfg, qtype))
    w_bytes = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(params))

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

    jax.default_backend = lambda: "tpu"  # the target, not where this runs
    rows = []
    dec = eng._decode.lower(
        params, arr((B,), jnp.int32), pool, arr((2,), jnp.uint32),
        arr((B,), jnp.float32), arr((B,), jnp.int32), arr((B,), jnp.float32),
        arr((B,), jnp.bool_), arr((B, cfg.vocab_size), jnp.bool_),
        arr((B,), jnp.float32), lora=None).compile()
    rows.append((f"engine_decode B={B}", dec.memory_analysis()))
    table = arr((1, eng.max_pages_per_row), jnp.int32)
    for T in args.prefill:
        pre = eng._paged_prefill.lower(
            params, eng.kind.leaves(pool), (table, table),
            arr((1,), jnp.int32), arr((1, T), jnp.int32), arr((), jnp.int32),
            arr((1,), jnp.int32), lora=None).compile()
        rows.append((f"engine_paged_prefill T={T}", pre.memory_analysis()))

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
    return 0


if __name__ == "__main__":
    sys.exit(main())
