#!/usr/bin/env python3
"""`fit.py` for a configuration whose slots hold TWO GROUPS of pages (a
family that offers `init_paged_cache` with `kvwindow.KIND`,
models/smallthinker.py): compile its engine programs at their real sizes
with the TPU compiler for a DESCRIBED v5e (no chip attached, no chip time)
and read `memory_analysis()`. As `fit_latent.py`, it takes the pool from the
engine's own `_make_pool` under `jax.eval_shape`, so the window group's pool
is the one the engine derives from the slots and the window; the prefill's
arguments are this kind's (four pools, a table each). Nothing runs, so this
says nothing about time.

    JAX_PLATFORMS=cpu python bench/tools/fit_window.py \
        --config smallthinker-21ba3b-int4 --prefill 4096 8192 [--layers N]
"""

from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

GIB = 2.0 ** 30


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--layers", type=int)
    ap.add_argument("--n-slots", type=int)
    ap.add_argument("--prefill", type=int, nargs="*", default=[1024])
    args = ap.parse_args()

    import dataclasses

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import cells, weights
    from bigdl_tpu.api import TpuModel
    from bigdl_tpu.models.config import ModelConfig
    from bigdl_tpu.serving.engine import InferenceEngine

    config = cells.load_json(ROOT, "bench", "configs", args.config + ".json")
    hf = cells.as_run(config)
    if args.layers:  # whole periods of the layouts
        hf["num_hidden_layers"] = args.layers
        for key in ("sliding_window_layout", "rope_layout"):
            hf[key] = hf[key][:args.layers]
    e = dict(config["bench"]["engine"])
    if args.n_slots:
        e["n_slots"] = args.n_slots
    cfg = ModelConfig.from_hf_config(hf)

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=chip), tree)

    params = on_chip(weights.param_shapes(cfg, config["bench"]["qtype"]))
    w_bytes = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(params))

    B = e["n_slots"]
    # the engine object only lends its step functions and its pool's shape;
    # the pool it holds itself is one slot of a model cut to two layers
    small = dataclasses.replace(
        cfg, num_hidden_layers=2, sliding_layers=cfg.sliding_layers[:2],
        rope_layers=cfg.rope_layers[:2])
    eng = InferenceEngine(
        TpuModel(small, None, config["bench"]["qtype"]),
        n_slots=1, max_len=e["max_len"], paged=True,
        page_size=e["page_size"], n_pages=2)
    eng.config, eng.n_slots, eng.n_pages = cfg, B, e["n_pages"]
    cache = on_chip(jax.eval_shape(eng._make_pool))
    pool_bytes = sum(s.size * s.dtype.itemsize
                     for s in jax.tree.leaves(cache))

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    jax.default_backend = lambda: "tpu"  # the target, not where this runs
    rows = []
    dec = eng._decode.lower(
        params, arr((B,), jnp.int32), cache, arr((2,), jnp.uint32),
        arr((B,), jnp.float32), arr((B,), jnp.int32), arr((B,), jnp.float32),
        arr((B,), jnp.bool_), arr((B, cfg.vocab_size), jnp.bool_),
        arr((B,), jnp.float32), lora=None).compile()
    rows.append((f"engine_decode B={B}", dec.memory_analysis()))
    for T in args.prefill:
        table = arr((1, eng.max_pages_per_row), jnp.int32)
        pre = eng._paged_prefill.lower(
            params, cache.k, cache.v, cache.kw, cache.vw, table, table,
            arr((1,), jnp.int32), arr((1, T), jnp.int32),
            arr((), jnp.int32)).compile()
        rows.append((f"engine_paged_prefill T={T}", pre.memory_analysis()))

    print(f"{args.config}: {cfg.num_hidden_layers} layers, weights "
          f"{w_bytes / GIB:.2f} GiB ({w_bytes / 1e9:.2f} GB), pools of "
          f"{cache.k.shape[1]} global and {cache.kw.shape[1]} window pages "
          f"{pool_bytes / GIB:.2f} GiB "
          f"({pool_bytes / 1e9:.2f} GB as shaped; the compiler's own count, "
          f"tiles padded, is each program's aliased argument), {B} slots")
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
