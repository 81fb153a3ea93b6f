"""Packed weights made ON THE DEVICE from a seed, in one jitted call.

Same tree and same rules as `bigdl_tpu/quant/synth.synth_params` (which
builds 3.97 GiB with one-core numpy in 54 s, PERF.md): the structure comes
from `jax.eval_shape` over the public init -> quantize -> merge path, so a
change to the layout shows here without an edit. Leaves by dtype:

* uint8 (packed sym_int4 nibbles): random bytes with the one unpaired code
  (0, value -8) moved to 8 (value 0) in each nibble, so weights are
  zero-mean (a common offset makes a rank-one term dominate the forward and
  every logit vector look alike, which blinds the correctness check);
* float16 (per-block scales): 0.02 / 4.18 * U(0.5, 1.5), so a dequantized
  weight has the init's standard deviation of about 0.02 (codes -7..7 with
  a double share of 0 have standard deviation 4.18);
* norm weights: 1; every other dense leaf (embedding, router): 0.02 * N(0,1).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

CODE_STD = 4.18
WEIGHT_STD = 0.02
# leaves with at least this many elements are generated one slice of their
# leading (layer) axis at a time, which bounds the generator's temporaries
_MAP_OVER = 1 << 24


def param_shapes(config, qtype: str = "sym_int4"):
    """ShapeDtypeStruct tree of the served parameter layout."""
    from bigdl_tpu.api import optimize_model
    from bigdl_tpu.models import get_family

    family = get_family(config.model_type)
    return jax.eval_shape(
        lambda k: optimize_model(family.init_params(config, k), config, qtype),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
    )


def _codes(key, shape):
    b = jax.random.bits(key, shape, jnp.uint8)
    b = b | (((b & 0x0F) == 0).astype(jnp.uint8) << 3)
    return b | (((b & 0xF0) == 0).astype(jnp.uint8) << 7)


def _leaf(key, path, shape, dtype):
    if dtype == jnp.uint8:
        return _codes(key, shape)
    if dtype == jnp.float16:
        u = jax.random.uniform(key, shape, jnp.float32, 0.5, 1.5)
        return (WEIGHT_STD / CODE_STD * u).astype(dtype)
    if "norm" in jax.tree_util.keystr(path):
        return jnp.ones(shape, dtype)
    return (WEIGHT_STD * jax.random.normal(key, shape, jnp.float32)
            ).astype(dtype)


def make_params(config, seed: int, qtype: str = "sym_int4"):
    """The parameter tree on the default device, a pure function of `seed`."""
    if qtype != "sym_int4":
        raise ValueError(f"the weight maker knows sym_int4 only, not {qtype}")
    shapes = param_shapes(config, qtype)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        out = []
        for i, (path, s) in enumerate(flat):
            k = jax.random.fold_in(key, i)
            if s.size >= _MAP_OVER and len(s.shape) >= 3:
                out.append(jax.lax.map(
                    lambda kk: _leaf(kk, path, s.shape[1:], s.dtype),
                    jax.random.split(k, s.shape[0])))
            else:
                out.append(_leaf(k, path, s.shape, s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    build.__name__ = "bench_make_params"
    return jax.jit(build)(jax.random.key(int(seed)))
