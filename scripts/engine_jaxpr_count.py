#!/usr/bin/env python3
"""Equation counts of the engine's three programs for tiny dense models, to
hold two trees against each other: a change that is meant to leave the dense
models' trace path alone gives the same numbers as its parent.

    cd <tree> && JAX_PLATFORMS=cpu python scripts/engine_jaxpr_count.py

Prints one line a (model type, program): the number of equations of
`jax.make_jaxpr` of `engine_decode`, `engine_first_token` and
`engine_paged_prefill`, nested jaxprs (scans, conds, pjit bodies) counted in.
Run it from each tree's own root and `cmp` the outputs (PERF.md section 6,
PR 34: what tracing these programs costs is most of a warm `setup_s`)."""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.getcwd())


def n_eqns(jaxpr) -> int:
    n = 0
    for e in jaxpr.eqns:
        n += 1
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)  # a closed one's own
                if hasattr(sub, "eqns"):
                    n += n_eqns(sub)
    return n


def main() -> int:
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.api import TpuModel, optimize_model
    from bigdl_tpu.models import llama
    from bigdl_tpu.models.config import ModelConfig
    from bigdl_tpu.serving.engine import InferenceEngine

    for model_type in ("mistral", "qwen2"):
        cfg = ModelConfig.from_hf_config(dict(
            model_type=model_type, hidden_size=128, intermediate_size=256,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            vocab_size=512, rms_norm_eps=1e-5, rope_theta=1e4,
            max_position_embeddings=2048, tie_word_embeddings=False))
        params = optimize_model(
            llama.init_params(cfg, jax.random.PRNGKey(0)), cfg, "sym_int4")
        eng = InferenceEngine(TpuModel(cfg, params, "sym_int4"), n_slots=4,
                              max_len=256, paged=True, page_size=16,
                              n_pages=33)
        B, c = 4, eng.cache
        z = jnp.zeros
        programs = {
            "engine_decode": (eng._decode, (
                params, z((B,), jnp.int32), c, jax.random.PRNGKey(0),
                z((B,)), z((B,), jnp.int32), z((B,)), z((B,), bool),
                eng.seen, z((B,)))),
            "engine_first_token": (eng._first_token, (
                z((cfg.vocab_size,)), jax.random.PRNGKey(0), z(()),
                z((), jnp.int32), z(()), z((), bool), z(()),
                z((cfg.vocab_size,), bool), z((), jnp.int32),
                z((B,), jnp.int32), eng.seen)),
            "engine_paged_prefill": (eng._paged_prefill, (
                params, eng.kind.leaves(c),
                (z((1, eng.max_pages_per_row), jnp.int32), None),
                z((1,), jnp.int32), z((1, 64), jnp.int32), z((), jnp.int32),
                z((1,), jnp.int32))),
        }
        for name, (fn, args) in programs.items():
            fn = getattr(fn, "__wrapped__", fn)
            print(f"{model_type:8s} {name:22s} "
                  f"{n_eqns(jax.make_jaxpr(fn)(*args).jaxpr)} equations",
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
