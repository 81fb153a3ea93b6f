#!/usr/bin/env python3
"""`fit_latent.py` for a configuration whose slots hold a STATE ROW BESIDE
KV PAGES (`kvhybrid.HybridCache`, models/granitemoehybrid.py): compile its
engine programs at their real sizes with the TPU compiler for a DESCRIBED
v5e (no chip attached, no chip time) and read `memory_analysis()`. The pool
comes from the engine's own `_make_pool` under `jax.eval_shape`, as in
`fit_latent.py`; what differs is the prefill program's arguments (that file
hands it `cache.lat`, this one k, v, the conv tails, the states and the
slot). Nothing runs, so this says nothing about time.

    JAX_PLATFORMS=cpu python bench/tools/fit_hybrid.py \
        --config granite-4.0-h-small-int4 --prefill 256 2048 [--layers N]
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
    if args.layers:
        hf["num_hidden_layers"] = args.layers
        hf["layer_types"] = hf["layer_types"][:args.layers]
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
        cfg, num_hidden_layers=2, layer_types=("mamba", "attention"))
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
        pre = eng._paged_prefill.lower(
            params, cache.k, cache.v, cache.conv, cache.ssm,
            arr((1, eng.max_pages_per_row), jnp.int32), arr((1,), jnp.int32),
            arr((1, T), jnp.int32), arr((), jnp.int32),
            arr((1,), jnp.int32)).compile()
        rows.append((f"engine_paged_prefill T={T}", pre.memory_analysis()))

    print(f"{args.config}: {cfg.num_hidden_layers} layers, weights "
          f"{w_bytes / GIB:.2f} GiB ({w_bytes / 1e9:.2f} GB), pool of "
          f"{e['n_pages']} pages {pool_bytes / GIB:.2f} GiB "
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
