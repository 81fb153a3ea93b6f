"""Shared in-kernel dequant decoder for every packed low-bit format.

One implementation of the per-format bit decode, consumed by BOTH the
fused dequant-GEMV and the tiled dequant-GEMM kernels in
`ops/pallas/qmatmul.py` (and, later, by flash-attention epilogues) — the
format decode lives here exactly once, the matmul kernels are tiling +
epilogue.

A format is described by a static, hashable `DecodeSpec`:

* how codes are STORED — `planes=()` means one code byte per element
  (int8 codes, or fp8 bitcast to uint8) read directly from the weight
  tile; a non-empty `planes` tuple is the multi-split packed-plane
  layout of `quant/numerics.pack_planes` (half-split nibbles are just
  `planes=(4,)`);
* how codes become VALUES — `value` tag: `("offset", n)` integer codes
  minus n, `("lut", codebook)` compare/select tree (Mosaic has no
  vector gather), `("e2m3",)` fp6 arithmetic decode, `("e4m3",)` /
  `("e5m2",)` fp8 bit-field decode;
* how values are SCALED — single-level per-`block` f16 scales
  (+ optional per-block mins: w = v*d + m), or two-level k-quant
  factorization (`super_block`=256): w = (d*sc)*v [- (dmin*mn)] per
  `block`-element sub-block.

Mosaic constraints baked in (found on real TPU — the CPU interpreter
accepts everything, silently; see qmatmul.py's module docstring for the
measurement history):

* no f16 vector type -> f16 scales cross as uint16 bits, decoded to f32
  with integer ops (`f16_bits_to_f32`); subnormals decode exactly — NOT
  flushed (k-quant super-scales routinely land below 6.1e-5);
* no lane-collapsing reshape -> per-block scales expand to per-element
  via a one-hot matmul (iota compare + MXU dot), not broadcast+reshape;
* no vector gather -> codebooks are compare/select trees, fp8/fp6 decode
  arithmetically from their bit fields.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from bigdl_tpu.ops.pallas.tiling import chunk_spans, finest_split


@dataclasses.dataclass(frozen=True)
class DecodeSpec:
    """Static decode recipe for one qtype (hashable: jit/kernel key)."""
    planes: tuple  # () = byte-per-element codes; else packed bit planes
    value: tuple  # ("offset", n) | ("lut", codes) | ("e2m3",) | ("e4m3",) | ("e5m2",)
    block: int  # scale block (single-level) or sub-block (two-level)
    mins: bool = False  # per-(sub-)block min/offset term
    super_block: int = 0  # 256 for k-quants, 0 = single-level scales

    @property
    def n_side(self) -> int:
        """Number of scale-side arrays accompanying the weight tile."""
        if self.super_block:
            return 4 if self.mins else 2
        return 2 if self.mins else 1


def spec_for(qspec) -> DecodeSpec:
    """DecodeSpec for a `quant.qtypes.QTypeSpec` — the one mapping from
    storage metadata to in-kernel decode recipe."""
    if qspec.storage == "packed_u8":
        planes = (4,)
    elif qspec.storage == "packed_planes":
        planes = tuple(qspec.planes)
    else:  # int8 / fp8_* byte codes
        planes = ()
    if qspec.storage == "fp8_e4m3":
        value = ("e4m3",)
    elif qspec.storage == "fp8_e5m2":
        value = ("e5m2",)
    elif qspec.name == "fp6":
        value = ("e2m3",)  # exact arithmetic form of FP6_CODEBOOK
    elif qspec.codebook is not None:  # nf4 / fp4 / nf3
        value = ("lut", tuple(float(c) for c in qspec.codebook))
    elif qspec.name == "sym_int4":
        value = ("offset", 8)
    elif qspec.name == "sym_int5":
        value = ("offset", 16)
    else:  # raw codes: asym (mins carry the offset) / centered int8
        value = ("offset", 0)
    return DecodeSpec(
        planes=planes, value=value, block=qspec.block_size,
        mins=qspec.asymmetric, super_block=qspec.superblock or 0,
    )


# ---------------------------------------------------------------------------
# bit-level helpers (integer ops only — Mosaic vector-type constraints)
# ---------------------------------------------------------------------------

def f16_bits_to_f32(bits):
    """uint16 float16 bit pattern -> f32, integer ops only (Mosaic has no
    f16 vectors). Subnormal f16 decodes exactly as sign * mant * 2^-24 —
    NOT flushed: k-quant super-scales d = max|sub_scale|/127 routinely
    land below 6.1e-5 for real checkpoint magnitudes (caught by the q6_k
    kernel equivalence test: flushing zeroed whole super-blocks)."""
    b = bits.astype(jnp.int32)
    sign = (b >> 15) & 1
    exp = (b >> 10) & 0x1F
    mant = b & 0x3FF
    f32_bits = (sign << 31) | ((exp + 127 - 15) << 23) | (mant << 13)
    val = jax.lax.bitcast_convert_type(f32_bits, jnp.float32)
    sub = (1.0 - 2.0 * sign.astype(jnp.float32)) * (
        mant.astype(jnp.float32) * jnp.float32(2.0 ** -24)
    )
    return jnp.where(exp == 0, sub, val)


def fp8_bits_to_f32(b, exp_bits: int, mant_bits: int, bias: int):
    """uint8 fp8 bit pattern (as int32) -> f32, integer ops only.
    Exact for every finite pattern; the encoder saturates, so inf/nan
    patterns never occur in stored weights. Subnormals decode exactly as
    sign * mant * 2^(1 - bias - mant_bits)."""
    sign = (b >> 7) & 1
    exp = (b >> mant_bits) & ((1 << exp_bits) - 1)
    mant = b & ((1 << mant_bits) - 1)
    f32_bits = (sign << 31) | ((exp + 127 - bias) << 23) | (
        mant << (23 - mant_bits))
    val = jax.lax.bitcast_convert_type(f32_bits, jnp.float32)
    sub = (1.0 - 2.0 * sign.astype(jnp.float32)) * (
        mant.astype(jnp.float32)
        * jnp.float32(2.0 ** (1 - bias - mant_bits))
    )
    return jnp.where(exp == 0, sub, val)


def expand_scales(s, ck: int, block: int):
    """[rows, nbc] per-block scales -> [rows, ck] per-element for one
    chunk whose start is block-aligned: element j belongs to local block
    j // block. One-hot matmul: iota/compare/dot only."""
    nbc = s.shape[-1]
    sel = (
        jax.lax.broadcasted_iota(jnp.int32, (nbc, ck), 1) // block
        == jax.lax.broadcasted_iota(jnp.int32, (nbc, ck), 0)
    ).astype(jnp.float32)
    return jax.lax.dot_general(
        s, sel, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def expand_super(d, n_sub: int, offset_sub: int, per_super: int):
    """[bo, nb_super] f32 super-scales -> [bo, n_sub] per-sub-block:
    sub-block s (global index s + offset_sub) belongs to super-block
    (s + offset_sub) // per_super. One-hot matmul (iota/compare/dot);
    the offset form handles chunks that start mid-super-block (odd
    super-block counts, e.g. llama2's K=11008 -> 43 blocks per row)."""
    nb = d.shape[-1]
    sel = (
        (jax.lax.broadcasted_iota(jnp.int32, (nb, n_sub), 1) + offset_sub)
        // per_super
        == jax.lax.broadcasted_iota(jnp.int32, (nb, n_sub), 0)
    ).astype(jnp.float32)
    return jax.lax.dot_general(
        d, sel, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def slc(a, c0: int, ck: int):
    """Static lane-dim slice of a loaded rank-2 array."""
    return jax.lax.slice(a, (0, c0), (a.shape[0], c0 + ck))


# ---------------------------------------------------------------------------
# packed-plane layout (the multi-split generalization of pack_nibbles)
# ---------------------------------------------------------------------------
#
# A b-bit plane over N elements stores byte j = elements j + m*(N*b/8)
# at bit offset b*m, so the m-th split of every plane is a *contiguous*
# byte range unpacked with one static shift — never a strided
# deinterleave. Chunk walks stay WITHIN the finest split (all coarser
# splits are multiples of it), so each chunk reads one contiguous,
# lane-aligned slice per plane and one slice of x.

def plane_layout(K: int, planes: tuple):
    """Static per-plane (data col offset, bits, splits, split elems)."""
    out = []
    off = 0
    for bits in planes:
        s = 8 // bits
        out.append((off, bits, s, K // s))
        off += K // s
    return out


def plane_chunk_code(w, layout, e0: int, c: int):
    """Decode elements [e0, e0+c) of every plane from the concatenated
    plane array `w` [bo, total_bytes] -> int32 codes [bo, c]. e0 must not
    cross a split boundary of any plane (guaranteed by chunking within
    the finest split)."""
    code = None
    shift = 0
    for off, bits, _s, q in layout:
        mp = e0 // q
        piece = (
            slc(w, off + e0 - mp * q, c).astype(jnp.int32) >> (bits * mp)
        ) & ((1 << bits) - 1)
        code = piece if code is None else code | (piece << shift)
        shift += bits
    return code


def walk(K: int, planes: tuple, ck: int):
    """Static (e0, c) chunk spans over the logical element axis, never
    crossing a plane-split boundary."""
    qmin = finest_split(K, planes)
    for m0 in range(K // qmin):
        for c0, c in chunk_spans(qmin, ck):
            yield m0 * qmin + c0, c


# ---------------------------------------------------------------------------
# code -> value decode
# ---------------------------------------------------------------------------

def decode_values(code, value: tuple):
    """Codes (int32 plane codes, or raw int8/uint8 byte codes) -> f32
    values, per the static `value` tag."""
    kind = value[0]
    if kind == "offset":
        if value[1] == 0:
            return code.astype(jnp.float32)
        return (code.astype(jnp.int32) - value[1]).astype(jnp.float32)
    if kind == "lut":  # select tree: Mosaic has no vector gather
        c = code.astype(jnp.int32)
        v = jnp.zeros(c.shape, jnp.float32)
        for i, ci in enumerate(value[1]):
            if ci != 0.0:
                v = jnp.where(c == i, jnp.float32(ci), v)
        return v
    if kind == "e2m3":  # fp6: exact arithmetic form of FP6_CODEBOOK
        c = code.astype(jnp.int32)
        sign = 1.0 - 2.0 * ((c >> 5) & 1).astype(jnp.float32)
        e = (c >> 3) & 3
        m = (c & 7).astype(jnp.float32)
        pow2 = jnp.where(e == 3, 4.0, jnp.where(e == 2, 2.0, 1.0))
        mag = jnp.where(e == 0, m, (8.0 + m) * pow2) * jnp.float32(1 / 16)
        return sign * mag
    if kind == "e4m3":
        return fp8_bits_to_f32(code.astype(jnp.int32), 4, 3, 7)
    if kind == "e5m2":
        return fp8_bits_to_f32(code.astype(jnp.int32), 5, 2, 15)
    raise ValueError(value)


# ---------------------------------------------------------------------------
# the decoder: weight tile + side arrays -> bf16 weight chunk
# ---------------------------------------------------------------------------

def load_side(spec: DecodeSpec, refs):
    """Load + bit-decode the scale-side refs once per kernel invocation
    (persistent across the chunk loop). Returns the in-VMEM f32 arrays
    `decode_chunk` slices per chunk."""
    def as_f32(ref):
        # integer sub-scales go through int32: Mosaic has no
        # uint8 -> float32 cast (PR 21 chip run: q2_k / q4_k / q5_k)
        return ref[:].astype(jnp.int32).astype(jnp.float32)

    if spec.super_block:
        if spec.mins:
            d, dmin, sc, mn = refs
            return (f16_bits_to_f32(d[:]), f16_bits_to_f32(dmin[:]),
                    as_f32(sc), as_f32(mn))
        d, sc = refs
        return (f16_bits_to_f32(d[:]), as_f32(sc))
    if spec.mins:
        s, m = refs
        return (f16_bits_to_f32(s[:]), f16_bits_to_f32(m[:]))
    (s,) = refs
    return (f16_bits_to_f32(s[:]),)


def decode_kv(codes, scale=None, value: tuple = ("e5m2",)):
    """The ONE attention-epilogue KV decode body, shared by
    flash_attention / paged_attention / flash_backward (the in-kernel
    fp8 dequant used to be duplicated in each kernel; graftlint's
    dispatch-consistency family guards against it reappearing).

    `codes` is a loaded KV tile in any layout:

    * uint8 — fp8 bit patterns (the flash wrapper bitcasts the fp8 cache
      before pallas_call, the same move qmatmul makes for fp8 weight
      storage): decoded through `decode_values`/`fp8_bits_to_f32`, the
      SAME bit decoder the fused GEMM/GEMV/backward kernels use for fp8
      weights, so attention and GEMM formats cannot drift;
    * typed fp8 — decoded by dtype conversion (paged attention keeps the
      pool typed: bitcasting [L, n_pages, ...] per decode step would
      copy the whole pool in HBM). Both arms are EXACT on every finite
      fp8 pattern, so they are bit-identical by construction (asserted
      by tests/test_qbackward.py's unification parity test);
    * anything else (bf16 cache) — f32 passthrough, `scale` normally
      None.

    `scale` broadcasts against the decoded tile (trailing singleton
    conventions are the caller's); None skips the multiply entirely, so
    unquantized paths pay nothing."""
    if codes.dtype == jnp.uint8:
        vals = decode_values(codes.astype(jnp.int32), value)
    else:
        vals = codes.astype(jnp.float32)
    if scale is None:
        return vals
    return vals * scale


def decode_chunk(spec: DecodeSpec, K: int, w, side, e0: int, c: int):
    """bf16 weight chunk [bo, c] for logical elements [e0, e0+c) of an
    O-tile: codes from the weight tile, values per the decode tag,
    scales expanded per-element via one-hot dots. e0 is block-aligned
    (walk() chunks within plane splits at 128-multiples)."""
    if spec.planes:
        code = plane_chunk_code(w, plane_layout(K, spec.planes), e0, c)
    else:
        code = slc(w, e0, c)
    vals = decode_values(code, spec.value)
    bo = w.shape[0]
    sb0, nsc = e0 // spec.block, c // spec.block

    if spec.super_block:
        per_super = spec.super_block // spec.block
        d32 = side[0]
        if spec.mins:
            _, dmin32, scf, mnf = side
            s_eff = expand_super(d32, nsc, sb0, per_super) * slc(scf, sb0, nsc)
            m_eff = expand_super(dmin32, nsc, sb0, per_super) * slc(mnf, sb0, nsc)
            # the two per-element expansions share one (nsc, c) sel via a
            # single stacked dot
            exp = expand_scales(
                jnp.concatenate([s_eff, m_eff], axis=0), c, spec.block)
            return (vals * exp[:bo] - exp[bo:]).astype(jnp.bfloat16)
        scf = side[1]
        s_eff = expand_super(d32, nsc, sb0, per_super) * slc(scf, sb0, nsc)
        return (vals * expand_scales(s_eff, c, spec.block)
                ).astype(jnp.bfloat16)

    if spec.mins:  # w = v*d + m (raw block minimum, `+ m` convention)
        s, m = side
        exp = expand_scales(
            jnp.concatenate([slc(s, sb0, nsc), slc(m, sb0, nsc)], axis=0),
            c, spec.block)
        return (vals * exp[:bo] + exp[bo:]).astype(jnp.bfloat16)
    (s,) = side
    return (vals * expand_scales(slc(s, sb0, nsc), c, spec.block)
            ).astype(jnp.bfloat16)
