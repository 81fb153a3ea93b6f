#!/usr/bin/env python3
"""`fit.py` for a configuration whose slots hold recurrent STATE rows and no
pages of keys (`attention_kind` power_retention): compile its engine programs
at their real sizes with the TPU compiler for a DESCRIBED v5e (no chip
attached, no chip time) and read `memory_analysis()`. `fit.py` builds a pool
of KV pages by hand and hands the prefill its k / v / scale arrays, so it
cannot describe this kind; this file builds the state pool the engine itself
would (`kvstate.init_state`, a row a slot). Nothing runs, so this says
nothing about time.

    JAX_PLATFORMS=cpu python bench/tools/fit_state.py \
        --config brumby-14b-int4 --prefill 256 1024 [--layers N] [--n-slots N]
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
    from bigdl_tpu import kvstate
    from bigdl_tpu.api import TpuModel
    from bigdl_tpu.models.config import ModelConfig
    from bigdl_tpu.serving.engine import InferenceEngine

    config = cells.load_json(ROOT, "bench", "configs", args.config + ".json")
    hf = cells.as_run(config)
    if args.layers:
        hf["num_hidden_layers"] = args.layers
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
    # the engine object only lends its step functions; its own pool is one
    # row of a model cut to one layer
    eng = InferenceEngine(
        TpuModel(dataclasses.replace(cfg, num_hidden_layers=1), None,
                 config["bench"]["qtype"]),
        n_slots=1, max_len=e["max_len"], paged=True,
        page_size=e["page_size"], n_pages=e["n_pages"])
    eng.config = cfg
    cache = on_chip(jax.eval_shape(lambda: dataclasses.replace(
        kvstate.init_state(cfg.num_hidden_layers, B,
                           cfg.num_key_value_heads, cfg.head_dim_,
                           max_len=e["max_len"]),
        pos=jnp.zeros((B,), jnp.int32),
        block_tables=jnp.zeros((B, 1), jnp.int32))))
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
            params, cache.S, cache.z, arr((1, 1), jnp.int32),
            arr((1,), jnp.int32), arr((1, T), jnp.int32),
            arr((), jnp.int32), lora=None).compile()
        rows.append((f"engine_paged_prefill T={T}", pre.memory_analysis()))

    print(f"{args.config}: {cfg.num_hidden_layers} layers, weights "
          f"{w_bytes / GIB:.2f} GiB, state pool of {B} rows "
          f"{pool_bytes / GIB:.2f} GiB ({pool_bytes / B / 2**20:.1f} MiB a "
          f"row), {B} slots")
    for name, m in rows:
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 - m.alias_size_in_bytes + m.temp_size_in_bytes)
        print(f"  {name:32s} temporaries {m.temp_size_in_bytes / GIB:6.2f} "
              f"GiB, arguments {m.argument_size_in_bytes / GIB:6.2f}, "
              f"outputs not aliased "
              f"{(m.output_size_in_bytes - m.alias_size_in_bytes) / GIB:6.2f}"
              f", in all {total / GIB:6.2f} GiB")
    return 0


if __name__ == "__main__":
    sys.exit(main())
