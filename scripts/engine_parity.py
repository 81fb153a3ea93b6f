#!/usr/bin/env python3
"""Has a change to the serving engine's HOST code left its device programs
and its scheduling alone? Run this on two trees and compare what it prints
(CPU, no chip, about a minute):

    JAX_PLATFORMS=cpu python scripts/engine_parity.py > /tmp/change.json
    (cd <parent checkout> && JAX_PLATFORMS=cpu python <this file>) > /tmp/parent.json
    cmp /tmp/parent.json /tmp/change.json

`programs`: sha256 of the lowered StableHLO text of `engine_decode`,
`engine_first_token`, `engine_paged_prefill` (one bucket) and the page-copy
program, for each configuration of bench/configs/ at depth 2 (the text of a
layer does not depend on how many follow it). Lowered for the CPU, so the
kernels take their XLA route: a change to a Pallas kernel is NOT seen here.

`run`: a seeded paged engine on the tiny preset (mixed prompt lengths, a
shared prefix that diverges mid-page, a pool small enough to force radix
eviction and preemption, chunked prefill): per request its tokens, finish
reason and preemptions, and per step the physical pages of every slot.
The module is imported from the working directory, so the same file reads
either tree.

`--v5e NAME ...` instead: has a change to a MODEL's or a cache kind's code
left another configuration's device programs alone? sha256 of `engine_decode`
and `engine_paged_prefill` (T = 1024) of each named file of bench/configs/,
at its own depth, slots and pool, lowered for a DESCRIBED v5e (no chip; one
to three minutes a configuration), so the kernels take their Pallas route and
the text holds their names, grids and operands' shapes. A kernel's BODY is
left out of the hash: the serialized Mosaic module carries source locations,
which move with any line added above a call; `git diff` on `ops/pallas/` says
whether a body changed. `--dump DIR` keeps the texts, for `diff`.

    python scripts/engine_parity.py --v5e granite-4.0-h-small-int4 \\
        jamba2-3b-int4 qwen2-7b-int4 glm-4.7-flash-int4 > /tmp/change.json
"""

from __future__ import annotations

import hashlib
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.getcwd())


def _sha(lowered) -> str:
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()


def programs() -> dict:
    import jax
    import jax.numpy as jnp

    from bench import cells, weights
    from bigdl_tpu import kvpaged
    from bigdl_tpu.api import TpuModel
    from bigdl_tpu.models.config import ModelConfig
    from bigdl_tpu.serving.engine import InferenceEngine

    out = {}
    for name in ("mistral-7b-int4", "qwen2-7b-int4", "mixtral-8x7b-int4"):
        config = cells.load_json(os.getcwd(), "bench", "configs",
                                 name + ".json")
        hf = cells.as_run(config)
        hf["num_hidden_layers"] = 2
        e = config["bench"]["engine"]
        cfg = ModelConfig.from_hf_config(hf)
        params = weights.param_shapes(cfg, config["bench"]["qtype"])
        eng = InferenceEngine(TpuModel(cfg, None, config["bench"]["qtype"]),
                              n_slots=e["n_slots"], max_len=e["max_len"],
                              paged=True, page_size=e["page_size"],
                              n_pages=e["n_slots"] + 1)
        B, V = e["n_slots"], cfg.vocab_size
        cache = jax.eval_shape(lambda: kvpaged.init_paged(
            cfg.num_hidden_layers, e["n_pages"], e["page_size"],
            cfg.num_key_value_heads, cfg.head_dim_, B,
            eng.max_pages_per_row))
        arr = jax.ShapeDtypeStruct
        f32, i32 = jnp.float32, jnp.int32
        out[name] = {
            "engine_decode": _sha(eng._decode.lower(
                params, arr((B,), i32), cache, arr((2,), jnp.uint32),
                arr((B,), f32), arr((B,), i32), arr((B,), f32),
                arr((B,), jnp.bool_), arr((B, V), jnp.bool_),
                arr((B,), f32), lora=None)),
            "engine_first_token": _sha(eng._first_token.lower(
                arr((V,), f32), arr((2,), jnp.uint32), arr((), f32),
                arr((), i32), arr((), f32), arr((), jnp.bool_),
                arr((), f32), arr((V,), jnp.bool_), arr((), i32),
                cur=arr((B,), i32), seen=arr((B, V), jnp.bool_))),
            "engine_paged_prefill T=256": _sha(eng._paged_prefill.lower(
                params, eng.kind.leaves(cache),
                (arr((1, eng.max_pages_per_row), i32), None),
                arr((1,), i32), arr((1, 256), i32), arr((), i32),
                arr((1,), i32), lora=None)),
            "copy_page": _sha(eng._copy_page.lower(
                cache, arr((), i32), arr((), i32))),
        }
    return out


def described(names, dump=None) -> dict:
    import re

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import cells, weights
    from bigdl_tpu.api import TpuModel
    from bigdl_tpu.models.config import ModelConfig
    from bigdl_tpu.models.llama import prepare_kernel_scales
    from bigdl_tpu.serving.engine import InferenceEngine

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    jax.default_backend = lambda: "tpu"  # the target, not where this runs

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def on_chip(tree):
        return jax.tree.map(lambda s: arr(s.shape, s.dtype), tree)

    def sha(name, lowered):
        text = lowered.as_text()
        if dump:
            with open(os.path.join(dump, name + ".txt"), "w") as f:
                f.write(text)
        text = re.sub(r'body\\22: \\22[^\\]*\\22', "body", text)
        return hashlib.sha256(text.encode()).hexdigest()

    f32, i32, out = jnp.float32, jnp.int32, {}
    for name in names:
        config = cells.load_json(os.getcwd(), "bench", "configs",
                                 name + ".json")
        cfg = ModelConfig.from_hf_config(cells.as_run(config))
        e, qtype = config["bench"]["engine"], config["bench"]["qtype"]
        B, V = e["n_slots"], cfg.vocab_size
        params = on_chip(jax.eval_shape(
            lambda p: prepare_kernel_scales(cfg, p),
            weights.param_shapes(cfg, qtype)))
        # built small (nothing is allocated at the cell's size), then told
        # the cell's slots and pool
        eng = InferenceEngine(TpuModel(cfg, None, qtype), n_slots=1,
                              max_len=e["max_len"], paged=True,
                              page_size=e["page_size"], n_pages=2)
        eng.n_slots = B
        eng.page_size, eng.n_pages = eng.kind.page_geometry(
            B, e["max_len"], e["page_size"], e["n_pages"])
        pool = on_chip(jax.eval_shape(eng._make_pool))
        table = arr((1, eng.max_pages_per_row), i32)
        out[name] = {
            "engine_decode": sha(name + ".engine_decode", eng._decode.lower(
                params, arr((B,), i32), pool, arr((2,), jnp.uint32),
                arr((B,), f32), arr((B,), i32), arr((B,), f32),
                arr((B,), jnp.bool_), arr((B, V), jnp.bool_),
                arr((B,), f32), lora=None)),
            "engine_paged_prefill T=1024": sha(
                name + ".engine_paged_prefill", eng._paged_prefill.lower(
                    params, eng.kind.leaves(pool), (table, table),
                    arr((1,), i32), arr((1, 1024), i32), arr((), i32),
                    arr((1,), i32), lora=None)),
        }
        print(name, "lowered", file=sys.stderr, flush=True)
    return out


def _slot_pages(eng) -> list:
    # a tree from before PR 29 keeps the lists on the engine itself
    table = getattr(eng, "pages", None)
    rows = table.slot_pages if table is not None else eng._slot_pages
    return [list(r) for r in rows]


def run() -> dict:
    import jax
    import numpy as np

    from bigdl_tpu.api import TpuModel, optimize_model
    from bigdl_tpu.models import llama
    from bigdl_tpu.models.config import PRESETS
    from bigdl_tpu.serving.engine import InferenceEngine

    cfg = PRESETS["tiny-llama"]
    model = TpuModel(
        cfg, optimize_model(llama.init_params(cfg, jax.random.PRNGKey(7)),
                            cfg), "sym_int4")
    eng = InferenceEngine(model, n_slots=3, max_len=96, paged=True,
                          page_size=8, n_pages=14, prefill_chunk_tokens=16,
                          seed=3)
    rng = np.random.default_rng(11)
    shared = [int(t) for t in rng.integers(1, cfg.vocab_size, 21)]
    prompts = [shared + [int(t) for t in rng.integers(1, cfg.vocab_size, n)]
               for n in (3, 14, 30)]
    # diverges from prompts[0] in the middle of its third page
    prompts.append(prompts[0][:19] + [5, 6, 7, 8])
    prompts += [[int(t) for t in rng.integers(1, cfg.vocab_size, n)]
                for n in (5, 40, 9, 26)]
    reqs, pages = [], []
    for i, p in enumerate(prompts):
        reqs.append(eng.submit(p, max_new_tokens=20 + 3 * i))
        if i % 3 == 2:  # arrivals spread over the run
            for _ in range(4):
                eng.step()
                pages.append(_slot_pages(eng))
    for _ in range(2000):
        more = eng.step()
        pages.append(_slot_pages(eng))
        if not more:
            break
    table = getattr(eng, "pages", eng)
    return {
        "requests": [{"tokens": list(r.out_tokens), "finish": r.finish_reason,
                      "preemptions": r.preemptions} for r in reqs],
        "pages_per_step": pages,
        "preemptions": eng.preemptions,
        "prefix": [table.prefix_hits, table.prefix_partial_hits,
                   table.prefix_tokens_reused, table.prefix_evictions],
        "page_leaks": eng.page_leaks(),
    }


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--v5e", nargs="+", metavar="NAME")
    ap.add_argument("--dump", metavar="DIR")
    args = ap.parse_args()
    json.dump(described(args.v5e, args.dump) if args.v5e
              else {"programs": programs(), "run": run()}, sys.stdout,
              indent=1, sort_keys=True)
    print()
