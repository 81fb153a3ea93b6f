"""Blockwise quantize/dequantize numerics, in pure jnp.

These are the TPU-native equivalents of the reference's native entry points
`ggml_quantize_tensor` / `ggml_dequantize_*` (ctypes surface enumerated in
/root/reference python/llm/src/ipex_llm/ggml/model/llama/llama_cpp.py:955-1065,
used from transformers/low_bit_linear.py:104-258). Numerics follow the ggml
block formats (Q4_0/Q4_1/Q5_0/Q5_1/Q8_0) and the bitsandbytes NF4/FP4
codebook scheme so that quantized-model quality lands in the same perplexity
band as the reference's README table.

Everything here is shape-polymorphic jnp and jit-safe: it runs on host CPU
during checkpoint conversion and on TPU when re-quantizing (e.g. FP8 KV
cache). Packing layout: 4-bit codes are packed two-per-uint8 along the last
(contraction) axis in half-split order — element j in the low nibble of
byte j, element j + K/2 in its high nibble (see pack_nibbles).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.quant.qtypes import QTypeSpec, resolve_qtype

_FP8_MAX = {"fp8_e4m3": 448.0, "fp8_e5m2": 57344.0}
_FP8_DTYPE = {"fp8_e4m3": jnp.float8_e4m3fn, "fp8_e5m2": jnp.float8_e5m2}


def _blocked(x: jax.Array, block_size: int) -> jax.Array:
    k = x.shape[-1]
    if k % block_size != 0:
        raise ValueError(
            f"last dim {k} not divisible by block_size {block_size}; "
            "pad the weight before quantizing"
        )
    return x.reshape(*x.shape[:-1], k // block_size, block_size)


def pack_nibbles(codes: jax.Array) -> jax.Array:
    """[..., K] uint8 codes in [0,16) -> [..., K//2] packed uint8.

    Half-split layout: byte j carries element j (low nibble) and element
    j + K/2 (high nibble). Chosen for the TPU hot path: the fused GEMV
    kernel (ops/pallas/qmatmul.py) then reads the activations for the two
    nibble planes as two *contiguous* halves of x — an interleaved layout
    (2i, 2i+1 per byte) would need a strided lane deinterleave per call,
    which Mosaic can't express and XLA charges ~40us/call for.
    """
    k = codes.shape[-1]
    lo = codes[..., : k // 2]
    hi = codes[..., k // 2:]
    return (lo | (hi << 4)).astype(jnp.uint8)


def unpack_nibbles(packed: jax.Array) -> jax.Array:
    """[..., K//2] packed uint8 -> [..., K] uint8 codes (element order).

    Written as broadcast-shift + reshape: one expression for any
    number of splits (unpack_planes shares it), bit-identical to
    ``concatenate([lo, hi], -1)``. An older jaxlib miscompiled that
    concatenate along a sharded axis on multi-axis meshes; under jax
    0.9.0 both spellings are right (re-tested on dp x tp meshes, PR 21).
    """
    shifts = jnp.asarray([0, 4], jnp.uint8)[:, None]
    out = (packed[..., None, :] >> shifts) & 0xF
    return out.reshape(*packed.shape[:-1], 2 * packed.shape[-1])


def pack_planes(codes: jax.Array, planes: tuple) -> jax.Array:
    """[..., K] uint8 codes -> concatenated bit planes (uint8).

    The multi-split generalization of pack_nibbles: a b-bit plane over K
    elements is K*b/8 bytes where byte j carries elements j + m*(K*b/8)
    at bit offset b*m (m = 0 .. 8/b - 1). `planes` lists each plane's
    bit width, LOW bits of the code first (fp6 = (4, 2); sym_int5 =
    (4, 1); nf3 = (2, 1)); plane arrays concatenate along the last axis.
    Every unpack — XLA or the Pallas fused GEMV — is static shifts of
    contiguous slices, never a strided deinterleave.
    """
    k = codes.shape[-1]
    shift = 0
    outs = []
    for bits in planes:
        s = 8 // bits
        q = k // s
        sub = (codes >> shift) & ((1 << bits) - 1)
        acc = sub[..., :q].astype(jnp.uint8)
        for m in range(1, s):
            acc = acc | (sub[..., m * q:(m + 1) * q] << (bits * m)).astype(
                jnp.uint8)
        outs.append(acc)
        shift += bits
    return jnp.concatenate(outs, axis=-1)


def unpack_planes(data: jax.Array, planes: tuple, k: int) -> jax.Array:
    """Inverse of pack_planes: concatenated planes -> [..., K] uint8.

    Same broadcast-shift + reshape spelling as unpack_nibbles.
    """
    off = 0
    shift = 0
    code = None
    for bits in planes:
        s = 8 // bits
        q = k // s
        plane = data[..., off:off + q]
        shifts = (bits * jnp.arange(s, dtype=jnp.uint8))[:, None]
        vals = (plane[..., None, :] >> shifts) & ((1 << bits) - 1)
        vals = vals.reshape(*plane.shape[:-1], s * q)
        part = (vals.astype(jnp.uint8) << shift).astype(jnp.uint8)
        code = part if code is None else code | part
        off += q
        shift += bits
    return code


def _signed_absmax(xb: jax.Array) -> jax.Array:
    """Per-block value with the largest magnitude, keeping its sign (ggml Q4_0)."""
    idx = jnp.argmax(jnp.abs(xb), axis=-1, keepdims=True)
    return jnp.take_along_axis(xb, idx, axis=-1)[..., 0]


def _safe_inv(d: jax.Array) -> jax.Array:
    return jnp.where(d == 0, 0.0, 1.0 / jnp.where(d == 0, 1.0, d))


@functools.lru_cache(maxsize=None)
def _codebook_tables(qtype_name: str):
    """(codebook, sorted-order permutation, decision boundaries) as numpy."""
    spec = resolve_qtype(qtype_name)
    cb = spec.codebook
    order = np.argsort(cb)
    sorted_cb = cb[order]
    boundaries = (sorted_cb[1:] + sorted_cb[:-1]) / 2.0
    return cb, order.astype(np.int32), boundaries


def quantize_blockwise(x: jax.Array, spec: QTypeSpec) -> dict:
    """Quantize x along its last axis. Returns a dict of QTensor array
    fields: always data/scales (+ mins for asymmetric types, +
    sub_scales/sub_mins for two-level k-quants).

    Single-level scales/mins are float16 with shape [..., K //
    block_size], matching the reference's half-precision block headers.
    K-quants encode on host (numpy) through the llama.cpp codec
    (quant/kquants.py) and repack into the TPU planar layout
    (quant/kq_planar.py) that the fused Pallas GEMV reads.
    """
    x = x.astype(jnp.float32)
    name = spec.name

    if spec.superblock:  # k-quants: host codec + planar repack
        from bigdl_tpu.quant import kq_planar, kquants

        xh = np.asarray(x)  # host-side encode (ingest path)
        enc = getattr(kquants, f"quantize_{name}")
        repack = getattr(kq_planar, f"from_{name.replace('_', '')}_blocks")
        fields = repack(enc(xh))
        return {k: jnp.asarray(v) for k, v in fields.items()}

    if spec.storage.startswith("fp8"):
        xb = _blocked(x, spec.block_size)
        absmax = jnp.max(jnp.abs(xb), axis=-1)
        scale = absmax / _FP8_MAX[name]
        q = (xb * _safe_inv(scale)[..., None]).astype(_FP8_DTYPE[name])
        return dict(data=q.reshape(x.shape), scales=scale.astype(jnp.float16))

    xb = _blocked(x, spec.block_size)

    if spec.codebook is not None:
        cb, order, boundaries = _codebook_tables(name)
        cb_max = float(np.max(np.abs(cb)))
        absmax = jnp.max(jnp.abs(xb), axis=-1)
        scale = absmax / cb_max
        xn = xb * _safe_inv(scale)[..., None]
        idx_sorted = jnp.searchsorted(jnp.asarray(boundaries), xn)
        codes = jnp.asarray(order)[idx_sorted]
        codes = codes.reshape(x.shape)
        if spec.storage == "packed_u8":
            data = pack_nibbles(codes.astype(jnp.uint8))
        elif spec.storage == "packed_planes":
            data = pack_planes(codes.astype(jnp.uint8), spec.planes)
        else:
            data = codes.astype(jnp.int8)
        return dict(data=data, scales=scale.astype(jnp.float16))

    if name == "sym_int4":
        smax = _signed_absmax(xb)
        d = smax / -8.0
        q = jnp.clip(jnp.round(xb * _safe_inv(d)[..., None]) + 8.0, 0, 15)
        data = pack_nibbles(q.reshape(x.shape).astype(jnp.uint8))
        return dict(data=data, scales=d.astype(jnp.float16))

    if name == "asym_int4":
        mins = jnp.min(xb, axis=-1)
        d = (jnp.max(xb, axis=-1) - mins) / 15.0
        q = jnp.clip(jnp.round((xb - mins[..., None]) * _safe_inv(d)[..., None]), 0, 15)
        data = pack_nibbles(q.reshape(x.shape).astype(jnp.uint8))
        return dict(data=data, scales=d.astype(jnp.float16),
                    mins=mins.astype(jnp.float16))

    if name == "sym_int5":
        smax = _signed_absmax(xb)
        d = smax / -16.0
        q = jnp.clip(jnp.round(xb * _safe_inv(d)[..., None]) + 16.0, 0, 31)
        data = pack_planes(q.reshape(x.shape).astype(jnp.uint8), spec.planes)
        return dict(data=data, scales=d.astype(jnp.float16))

    if name == "asym_int5":
        mins = jnp.min(xb, axis=-1)
        d = (jnp.max(xb, axis=-1) - mins) / 31.0
        q = jnp.clip(jnp.round((xb - mins[..., None]) * _safe_inv(d)[..., None]), 0, 31)
        return dict(data=q.reshape(x.shape).astype(jnp.int8),
                    scales=d.astype(jnp.float16), mins=mins.astype(jnp.float16))

    if name == "sym_int8":
        d = jnp.max(jnp.abs(xb), axis=-1) / 127.0
        q = jnp.clip(jnp.round(xb * _safe_inv(d)[..., None]), -127, 127)
        return dict(data=q.reshape(x.shape).astype(jnp.int8),
                    scales=d.astype(jnp.float16))

    raise NotImplementedError(f"quantize: qtype {name}")


def kq_effective_scales(
    scales: jax.Array,  # f16 super-scales d [..., K/superblock]
    sub_scales: jax.Array,  # integer sub-scales [..., K/block_size]
) -> jax.Array:
    """Per-sub-block f32 effective scale d*sc of a planar k-quant.
    Exact: f16 (11-bit mantissa) x <=8-bit integer fits f32."""
    reps = sub_scales.shape[-1] // scales.shape[-1]
    return (
        jnp.repeat(scales.astype(jnp.float32), reps, axis=-1)
        * sub_scales.astype(jnp.float32)
    )


def dequantize_blockwise(
    data: jax.Array,
    scales: jax.Array,
    mins: jax.Array | None,
    spec: QTypeSpec,
    dtype=jnp.float32,
    sub_scales: jax.Array | None = None,
    sub_mins: jax.Array | None = None,
) -> jax.Array:
    """Inverse of quantize_blockwise; returns [..., K] in `dtype`."""
    name = spec.name

    if name in ("q4_k", "q2_k", "q5_k"):
        # planar two-level asym: w = (d*sc)*q - (dmin*mn); matches the
        # kquants.dequant_* byte decoders bit-for-bit (f32, same grouping)
        if spec.storage == "packed_u8":
            codes = unpack_nibbles(data)
        else:
            k = data.shape[-1] * 8 // spec.bits
            codes = unpack_planes(data, spec.planes, k)
        codes = codes.astype(jnp.float32)
        s = kq_effective_scales(scales, sub_scales)
        m = kq_effective_scales(mins, sub_mins)
        vb = _blocked(codes, spec.block_size)
        y = vb * s[..., None] - m[..., None]
        return y.reshape(codes.shape).astype(dtype)

    if name in ("q6_k", "q3_k"):
        # planar two-level sym: w = (d*sc)*q, codes already centered
        s = kq_effective_scales(scales, sub_scales)
        vb = _blocked(data.astype(jnp.float32), spec.block_size)
        y = vb * s[..., None]
        return y.reshape(data.shape).astype(dtype)

    if spec.storage.startswith("fp8"):
        xb = _blocked(data.astype(jnp.float32), spec.block_size)
        y = xb * scales.astype(jnp.float32)[..., None]
        return y.reshape(data.shape).astype(dtype)

    if spec.storage == "packed_u8":
        codes = unpack_nibbles(data)
    elif spec.storage == "packed_planes":
        codes = unpack_planes(data, spec.planes,
                              data.shape[-1] * 8 // spec.bits)
    else:
        codes = data

    if spec.codebook is not None:
        cb = jnp.asarray(spec.codebook)
        vals = cb[codes.astype(jnp.int32) & ((1 << max(spec.bits, 4)) - 1)]
    elif name == "sym_int4":
        vals = codes.astype(jnp.float32) - 8.0
    elif name == "asym_int4":
        vals = codes.astype(jnp.float32)
    elif name == "sym_int5":
        vals = codes.astype(jnp.float32) - 16.0
    elif name == "asym_int5":
        vals = codes.astype(jnp.float32)
    elif name == "sym_int8":
        vals = codes.astype(jnp.float32)
    else:
        raise NotImplementedError(f"dequantize: qtype {name}")

    vb = _blocked(vals, spec.block_size)
    y = vb * scales.astype(jnp.float32)[..., None]
    if mins is not None:
        y = y + mins.astype(jnp.float32)[..., None]
    return y.reshape(vals.shape).astype(dtype)
