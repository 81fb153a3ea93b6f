"""Synthetic quantized weights, made host-side from a seed.

The fused kernels only see packed fields, and a machine with a chip may
have no network and no checkpoint. Running the real host-side quantizer
at 7B shapes costs minutes; random-but-valid fields cost seconds and
exercise the identical compiled program. `synth_qtensor` makes one
weight in any registered format (the kernel matrix of
`chip_smoke.py --kernels`); `synth_params` makes a whole llama-family
parameter tree (`chip_smoke.py`'s model)."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from bigdl_tpu.quant.qtensor import QTensor
from bigdl_tpu.quant.qtypes import resolve_qtype


def synth_qtensor(qtype: str, O: int, K: int,
                  rng: np.random.Generator | None = None) -> QTensor:
    """Random-but-valid QTensor host-side fields (not device-put)."""
    rng = rng or np.random.default_rng(0)
    spec = resolve_qtype(qtype)
    f16 = jnp.float16

    def scales(nb, mag=0.01):
        return jnp.asarray(rng.random((O, nb), np.float32) * mag, f16)

    if qtype in ("sym_int8", "q3_k"):
        sub = spec.block_size if spec.superblock else None
        fields = dict(
            data=jnp.asarray(rng.integers(-127, 128, (O, K), np.int8)
                             if qtype == "sym_int8"
                             else rng.integers(-4, 4, (O, K), np.int8)),
            scales=scales(K // (spec.superblock or spec.block_size)),
        )
        if sub:
            fields["sub_scales"] = jnp.asarray(
                rng.integers(-32, 32, (O, K // sub), np.int8))
    elif qtype == "asym_int5":
        fields = dict(
            data=jnp.asarray(rng.integers(0, 32, (O, K), np.int8)),
            scales=scales(K // 32),
            mins=scales(K // 32, mag=-0.08),
        )
    elif qtype in ("fp8_e4m3", "fp8_e5m2"):
        dt = jnp.float8_e4m3fn if qtype == "fp8_e4m3" else jnp.float8_e5m2
        fields = dict(
            data=jnp.asarray(rng.normal(size=(O, K)), np.float32).astype(dt),
            scales=scales(K // 128),
        )
    elif qtype == "q6_k":
        fields = dict(
            data=jnp.asarray(rng.integers(-32, 32, (O, K), np.int8)),
            scales=scales(K // 256),
            sub_scales=jnp.asarray(
                rng.integers(-64, 64, (O, K // 16), np.int8)),
        )
    elif qtype in ("q4_k", "q5_k", "q2_k"):
        sub = spec.block_size  # 32 / 32 / 16
        nbytes = K * spec.bits // 8 if spec.storage == "packed_planes" \
            else K // 2
        smax = 16 if qtype == "q2_k" else 64
        fields = dict(
            data=jnp.asarray(rng.integers(0, 256, (O, nbytes), np.uint8)),
            scales=scales(K // 256),
            mins=scales(K // 256),
            sub_scales=jnp.asarray(rng.integers(0, smax, (O, K // sub),
                                                np.uint8)),
            sub_mins=jnp.asarray(rng.integers(0, smax, (O, K // sub),
                                              np.uint8)),
        )
    elif qtype == "asym_int4":
        fields = dict(
            data=jnp.asarray(rng.integers(0, 256, (O, K // 2), np.uint8)),
            scales=scales(K // 32),
            mins=scales(K // 32, mag=-0.08),
        )
    elif spec.storage == "packed_planes":  # sym_int5 / fp6 / nf3
        fields = dict(
            data=jnp.asarray(rng.integers(0, 256, (O, K * spec.bits // 8),
                                          np.uint8)),
            scales=scales(K // spec.block_size),
        )
    else:  # sym_int4 / nf4 / fp4: packed nibbles + one scale per block
        nb = K // spec.block_size
        fields = dict(
            data=jnp.asarray(rng.integers(0, 256, (O, K // 2), np.uint8)),
            scales=scales(nb),
        )
    return QTensor(qtype=qtype, **fields)


def synth_params(config, seed: int = 0) -> dict:
    """Host-numpy sym_int4 parameter tree for a llama-family config, in
    exactly the layout `models/llama.forward` expects after
    `optimize_model(...)`: the tree's structure comes from
    `jax.eval_shape` over the real init + quantize + merge path, so no
    device op and no compilation runs. Packed codes are random
    nibbles with the one unpaired code (0, value -8) moved to 8 (value
    0), so weights have zero mean: a common offset in every weight
    would make a rank-one term dominate the forward and every logit
    vector look alike, whatever the prompt. Scales are sized so that a
    dequantized weight has the init's standard deviation of about 0.02
    and the forward stays in range through any depth; norm weights are
    1."""
    import jax

    from bigdl_tpu.models import llama

    shapes = jax.eval_shape(
        lambda k: llama.merge_fused_params(
            llama.quantize_params(llama.init_params(config, k), "sym_int4"),
            config),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
    )
    rng = np.random.default_rng(seed)
    # code values: -7..7 uniform plus a double share of 0: std 4.18
    scale = np.float32(0.02 / 4.18)

    def leaf(path, x):
        dt = np.dtype(x.dtype)
        if dt == np.uint8:  # packed nibbles, code 0 -> code 8
            b = rng.integers(0, 256, x.shape, np.uint8)
            b |= ((b & 0x0F) == 0).astype(np.uint8) << 3
            b |= ((b & 0xF0) == 0).astype(np.uint8) << 7
            return b
        if dt == np.float16:  # per-block scales
            return (scale * rng.uniform(0.5, 1.5, x.shape).astype(np.float32)
                    ).astype(dt)
        if "norm" in jax.tree_util.keystr(path):
            return np.ones(x.shape, dt)
        return (0.02 * rng.standard_normal(x.shape, np.float32)).astype(dt)

    return jax.tree_util.tree_map_with_path(leaf, shapes)
