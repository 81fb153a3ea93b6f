"""Exact repack between llama.cpp k-quant super-block bytes and the
TPU planar layout (numpy, host-side).

llama.cpp's k-quant byte layouts interleave codes, packed sub-scales
and fp16 super-scales inside 84..210-byte super-blocks — a CPU-SIMD
artifact. A Pallas kernel cannot slice those byte offsets (Mosaic lane
alignment), and XLA's in-graph byte decode materializes bf16 weights in
HBM (its cost on the chip: not measured). So on TPU a k-quant QTensor
stores PLANES:

  q2_k: data      [.., K/4]   uint8  quarter-split packed 2-bit codes
        scales    [.., K/256] f16    super-scale d
        mins      [.., K/256] f16    super-scale dmin
        sub_scales[.., K/16]  uint8  4-bit sc
        sub_mins  [.., K/16]  uint8  4-bit mn
        w[e] = (d*sc[e/16]) * q[e] - (dmin*mn[e/16])
  q3_k: data      [.., K]     int8   codes (q-4, element order)
        scales    [.., K/256] f16    super-scale d
        sub_scales[.., K/16]  int8   sc (6-bit, bias 32 removed)
        w[e] = (d*sc[e/16]) * q[e]      (== q6_k's structure)
  q4_k: data      [.., K/2]   uint8  half-split packed 4-bit codes
        scales    [.., K/256] f16    super-scale d
        mins      [.., K/256] f16    super-scale dmin
        sub_scales[.., K/32]  uint8  6-bit sc (element-order sub-blocks)
        sub_mins  [.., K/32]  uint8  6-bit mn
        w[e] = (d*sc[e/32]) * q[e] - (dmin*mn[e/32])
  q5_k: data      [.., 5K/8]  uint8  half-split nibbles ++ eighth-split
                                     1-bit plane (codes 0..31)
        (scales/mins/sub_scales/sub_mins as q4_k)
        w[e] = (d*sc[e/32]) * q[e] - (dmin*mn[e/32])
  q6_k: data      [.., K]     int8   codes (q-32, element order)
        scales    [.., K/256] f16    super-scale d
        sub_scales[.., K/16]  int8   sc
        w[e] = (d*sc[e/16]) * q[e]

The repack is pure integer/f16-view work — bit-exact both ways — and
runs once at the GGUF import / encoder boundary (reference counterpart:
the verbatim ggml byte carry in transformers/gguf/models/*.py of
/root/reference, which XPU kernels can consume directly; TPU cannot).
Dequantized values are identical to quant/kquants.dequant_* because
f32(d)*f32(sc) is exact (11-bit x 6-bit mantissa) and evaluation order
matches.
"""

from __future__ import annotations

import numpy as np

QK_K = 256


def pack_planes_np(codes: np.ndarray, planes: tuple) -> np.ndarray:
    """numpy mirror of quant/numerics.pack_planes ([.., K] codes ->
    concatenated multi-split bit planes, low bits first)."""
    k = codes.shape[-1]
    shift = 0
    outs = []
    for bits in planes:
        s = 8 // bits
        q = k // s
        sub = (codes >> shift) & ((1 << bits) - 1)
        acc = sub[..., :q].astype(np.uint8)
        for m in range(1, s):
            acc = acc | (sub[..., m * q:(m + 1) * q] << (bits * m)).astype(
                np.uint8)
        outs.append(acc)
        shift += bits
    return np.concatenate(outs, axis=-1)


def _f16_at(blocks: np.ndarray, off: int) -> np.ndarray:
    """fp16 scalar at byte offset `off` of each super-block."""
    return (
        blocks[..., off:off + 2].copy().view(np.float16)[..., 0]
    )


def _unpack_q4k_scales_np(sc_raw: np.ndarray):
    """12 packed bytes -> (sc [., 8], mn [., 8]) uint8 6-bit values
    (llama.cpp get_scale_min_k4; numpy mirror of kquants jnp version)."""
    sc = np.empty((*sc_raw.shape[:-1], 8), np.uint8)
    mn = np.empty_like(sc)
    for j in range(8):
        if j < 4:
            sc[..., j] = sc_raw[..., j] & 63
            mn[..., j] = sc_raw[..., j + 4] & 63
        else:
            sc[..., j] = (sc_raw[..., j + 4] & 0xF) | (
                (sc_raw[..., j - 4] >> 6) << 4
            )
            mn[..., j] = (sc_raw[..., j + 4] >> 4) | (
                (sc_raw[..., j] >> 6) << 4
            )
    return sc, mn


def q4k_codes(blocks: np.ndarray) -> np.ndarray:
    """[.., n_sb, 144] -> element-order codes [.., n_sb, 256] uint8."""
    qs = blocks[..., 16:144]
    out = np.empty((*blocks.shape[:-1], QK_K), np.uint8)
    for pair in range(4):
        grp = qs[..., 32 * pair:32 * (pair + 1)]
        out[..., 64 * pair:64 * pair + 32] = grp & 0xF
        out[..., 64 * pair + 32:64 * pair + 64] = grp >> 4
    return out


def from_q4k_blocks(blocks: np.ndarray) -> dict:
    """[.., n_sb, 144] super-block bytes -> planar QTensor fields."""
    d = _f16_at(blocks, 0)  # [.., n_sb]
    dmin = _f16_at(blocks, 2)
    sc, mn = _unpack_q4k_scales_np(blocks[..., 4:16])  # [.., n_sb, 8]
    codes = q4k_codes(blocks)

    lead = blocks.shape[:-2]
    k = blocks.shape[-2] * QK_K
    codes = codes.reshape(*lead, k)
    half = k // 2
    data = codes[..., :half] | (codes[..., half:] << 4)
    return dict(
        data=data,
        scales=d,
        mins=dmin,
        sub_scales=sc.reshape(*lead, k // 32),
        sub_mins=mn.reshape(*lead, k // 32),
    )


def q6k_codes(blocks: np.ndarray) -> np.ndarray:
    """[.., n_sb, 210] -> element-order centered codes [.., n_sb, 256]
    int8 (q - 32 in [-32, 31])."""
    ql = blocks[..., 0:128]
    qh = blocks[..., 128:192]
    out = np.empty((*blocks.shape[:-1], QK_K), np.int8)
    for half in range(2):
        l1 = ql[..., 64 * half:64 * half + 32]
        l2 = ql[..., 64 * half + 32:64 * half + 64]
        h = qh[..., 32 * half:32 * half + 32]
        base = 128 * half
        out[..., base:base + 32] = (
            ((l1 & 0xF) | ((h & 3) << 4)).astype(np.int8) - 32
        )
        out[..., base + 32:base + 64] = (
            ((l2 & 0xF) | (((h >> 2) & 3) << 4)).astype(np.int8) - 32
        )
        out[..., base + 64:base + 96] = (
            ((l1 >> 4) | (((h >> 4) & 3) << 4)).astype(np.int8) - 32
        )
        out[..., base + 96:base + 128] = (
            ((l2 >> 4) | (((h >> 6) & 3) << 4)).astype(np.int8) - 32
        )
    return out


def from_q6k_blocks(blocks: np.ndarray) -> dict:
    """[.., n_sb, 210] super-block bytes -> planar QTensor fields."""
    d = _f16_at(blocks, 208)
    sc = blocks[..., 192:208].view(np.int8)  # [.., n_sb, 16]
    codes = q6k_codes(blocks)

    lead = blocks.shape[:-2]
    k = blocks.shape[-2] * QK_K
    return dict(
        data=codes.reshape(*lead, k),
        scales=d,
        sub_scales=np.ascontiguousarray(sc).reshape(*lead, k // 16),
    )


def q2k_codes(blocks: np.ndarray) -> np.ndarray:
    """[.., n_sb, 84] -> element-order codes [.., n_sb, 256] uint8
    (0..3). Element 128h + 32j + l comes from bits 2j of qs[32h + l]."""
    qs = blocks[..., 16:80]
    out = np.empty((*blocks.shape[:-1], QK_K), np.uint8)
    for h in range(2):
        grp = qs[..., 32 * h:32 * (h + 1)]
        for j in range(4):
            e0 = 128 * h + 32 * j
            out[..., e0:e0 + 32] = (grp >> (2 * j)) & 3
    return out


def from_q2k_blocks(blocks: np.ndarray) -> dict:
    """[.., n_sb, 84] super-block bytes -> planar QTensor fields."""
    d = _f16_at(blocks, 80)
    dmin = _f16_at(blocks, 82)
    sc_raw = blocks[..., 0:16]  # [.., n_sb, 16]: sc | mn << 4 per sub
    codes = q2k_codes(blocks)

    lead = blocks.shape[:-2]
    k = blocks.shape[-2] * QK_K
    return dict(
        data=pack_planes_np(codes.reshape(*lead, k), (2,)),
        scales=d,
        mins=dmin,
        sub_scales=(sc_raw & 0xF).reshape(*lead, k // 16),
        sub_mins=(sc_raw >> 4).reshape(*lead, k // 16),
    )


def _unpack_q3k_scales_np(sc_raw: np.ndarray) -> np.ndarray:
    """12 bytes -> 16 6-bit scales, still biased by +32 (numpy mirror of
    kquants._unpack_q3k_scales)."""
    sc = np.empty((*sc_raw.shape[:-1], 16), np.uint8)
    for i in range(16):
        j, grp = i & 3, i >> 2
        if grp == 0:
            lo4 = sc_raw[..., j] & 0xF
        elif grp == 1:
            lo4 = sc_raw[..., 4 + j] & 0xF
        elif grp == 2:
            lo4 = sc_raw[..., j] >> 4
        else:
            lo4 = sc_raw[..., 4 + j] >> 4
        hi2 = (sc_raw[..., 8 + j] >> (2 * grp)) & 3
        sc[..., i] = lo4 | (hi2 << 4)
    return sc


def q3k_codes(blocks: np.ndarray) -> np.ndarray:
    """[.., n_sb, 110] -> element-order centered codes [.., n_sb, 256]
    int8 (q - 4 in [-4, 3]). Element 128h + 32j + l = (qs[32h+l] >> 2j
    & 3) - (hmask[l] bit (4h+j) ? 0 : 4)."""
    hmask = blocks[..., 0:32]
    qs = blocks[..., 32:96]
    out = np.empty((*blocks.shape[:-1], QK_K), np.int8)
    for h in range(2):
        grp = qs[..., 32 * h:32 * (h + 1)]
        for j in range(4):
            q2 = ((grp >> (2 * j)) & 3).astype(np.int8)
            hb = ((hmask >> (4 * h + j)) & 1).astype(np.int8)
            e0 = 128 * h + 32 * j
            out[..., e0:e0 + 32] = q2 + 4 * hb - 4
    return out


def from_q3k_blocks(blocks: np.ndarray) -> dict:
    """[.., n_sb, 110] super-block bytes -> planar QTensor fields
    (q6_k's structure: int8 centered codes + int8 sub-scales per 16)."""
    d = _f16_at(blocks, 108)
    sc = (_unpack_q3k_scales_np(blocks[..., 96:108]).astype(np.int16)
          - 32).astype(np.int8)
    codes = q3k_codes(blocks)

    lead = blocks.shape[:-2]
    k = blocks.shape[-2] * QK_K
    return dict(
        data=codes.reshape(*lead, k),
        scales=d,
        sub_scales=sc.reshape(*lead, k // 16),
    )


def q5k_codes(blocks: np.ndarray) -> np.ndarray:
    """[.., n_sb, 176] -> element-order codes [.., n_sb, 256] uint8
    (0..31): q4_K nibble groups + the qh 5th-bit plane."""
    qh = blocks[..., 16:48]
    qs = blocks[..., 48:176]
    out = np.empty((*blocks.shape[:-1], QK_K), np.uint8)
    for pair in range(4):
        grp = qs[..., 32 * pair:32 * (pair + 1)]
        out[..., 64 * pair:64 * pair + 32] = (
            (grp & 0xF) | (((qh >> (2 * pair)) & 1) << 4)
        )
        out[..., 64 * pair + 32:64 * pair + 64] = (
            (grp >> 4) | (((qh >> (2 * pair + 1)) & 1) << 4)
        )
    return out


def from_q5k_blocks(blocks: np.ndarray) -> dict:
    """[.., n_sb, 176] super-block bytes -> planar QTensor fields
    (q4_k's fields, with the 5th code bit as an extra packed plane)."""
    d = _f16_at(blocks, 0)
    dmin = _f16_at(blocks, 2)
    sc, mn = _unpack_q4k_scales_np(blocks[..., 4:16])  # [.., n_sb, 8]
    codes = q5k_codes(blocks)

    lead = blocks.shape[:-2]
    k = blocks.shape[-2] * QK_K
    return dict(
        data=pack_planes_np(codes.reshape(*lead, k), (4, 1)),
        scales=d,
        mins=dmin,
        sub_scales=sc.reshape(*lead, k // 32),
        sub_mins=mn.reshape(*lead, k // 32),
    )
