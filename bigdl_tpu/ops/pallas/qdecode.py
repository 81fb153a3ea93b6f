"""Shared in-kernel dequant decoder for every packed low-bit format.

One implementation of the per-format bit decode, consumed by BOTH the
fused dequant-GEMV and the tiled dequant-GEMM kernels in
`ops/pallas/qmatmul.py` (and, later, by flash-attention epilogues) — the
format decode lives here exactly once, the matmul kernels are tiling +
epilogue. The forward kernels' chunk loop decodes a tile either in the
stored [o, k] layout (`tile_product` over `decode_chunk`, which
`qbackward` also calls) or, where the tile's shape allows, as 32-bit
words transposed once a tile (`stage_words`, then `staged_product` over
`decode_chunk_words`, bottom of this file), the same values bit for bit.

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
* no lane-collapsing reshape -> in the stored layout per-block scales
  expand to per-element via a one-hot matmul (iota compare + MXU dot),
  not broadcast+reshape; the word path has them on sublanes, where
  broadcast + reshape IS a layout no-op;
* no vector gather of a table -> codebooks are compare/select trees, fp8/fp6 decode
  arithmetically from their bit fields.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops.pallas.tiling import (
    WORD_ROWS, chunk_spans, finest_split, round_up, words_chunk_loops,
)


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
    kernel equivalence test: flushing zeroed whole super-blocks).

    14 operations a vreg (20 until PR 63; a word tile's scales are a
    fifth to a third of what staging it costs, and staging is 17% of the
    kernel: PERF.md section 6, PR 63): the magnitude's 15 bits shifted into
    place with the exponent's rebias ADDED to them (`+ (112 << 23)`: no
    field is cut out), the sign masked and shifted once for both arms, and
    a subnormal's mantissa converted and scaled with that sign set in its
    bits. Every finite pattern is exact (`tests/test_qdecode_words.py`
    walks all 65536; the chip's own arithmetic, which flushes float32
    subnormals, read 0 wrong of them too: the subnormal arm never holds
    one, mant * 2^-24 >= 2^-24)."""
    b = bits.astype(jnp.int32)
    mag = b & 0x7FFF
    sign = (b & 0x8000) << 16
    val = jax.lax.bitcast_convert_type(
        sign | ((mag << 13) + ((127 - 15) << 23)), jnp.float32)
    sub = (b & 0x3FF).astype(jnp.float32) * jnp.float32(2.0 ** -24)
    sub = jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(sub, jnp.int32) | sign, jnp.float32)
    return jnp.where(mag < 0x400, sub, val)  # (exponent field 0)


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


def expand_scales(s, ck: int, block: int, from_f16: bool = False):
    """[rows, nbc] per-block scales -> [rows, ck] per-element for one
    chunk whose start is block-aligned: element j belongs to local block
    j // block. One-hot matmul: iota/compare/dot only.

    The MXU's default-precision float32 matmul rounds its operands (one
    scale in seven came out a bf16 step off on a v5e, PR 32; the CPU
    interpreter's dot is exact). With ``from_f16`` the scales are float16
    values, 11 significant bits: their bf16 rounding and the remainder
    (3 bits) ride ONE bf16 pass side by side, contraction 2 * nbc, and the
    float32 accumulator adds the two back exactly, at the old cost. The
    k-quants' 24-bit products keep the float32 dot and its rounding."""
    nbc = s.shape[-1]
    if from_f16:
        hi = s.astype(jnp.bfloat16)
        lo = (s - hi.astype(jnp.float32)).astype(jnp.bfloat16)
        s = jnp.concatenate([hi, lo], axis=1)
    n = s.shape[-1]
    sel = (
        jax.lax.broadcasted_iota(jnp.int32, (n, ck), 1) // block
        == jax.lax.broadcasted_iota(jnp.int32, (n, ck), 0) % nbc
    ).astype(s.dtype)
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
            c, spec.block, from_f16=True)
        return (vals * exp[:bo] + exp[bo:]).astype(jnp.bfloat16)
    (s,) = side
    return (vals * expand_scales(slc(s, sb0, nsc), c, spec.block,
                                 from_f16=True)).astype(jnp.bfloat16)


# ---------------------------------------------------------------------------
# the forward kernels' decode: the tile read as 32-bit words, transposed
# ---------------------------------------------------------------------------
#
# What bounded the old loop (scripts/qmatmul_kernel_bench.py, PR 32, on the
# chip): not the code decode and not HBM, but the per-element scales. In the
# stored [o, k] layout a block's scale is constant over `block` LANES, and
# every way to spread it there is dear: the one-hot matmul streams one MXU
# row and pops one result vreg per 8 rows x 128 lanes of scales (a third to
# two fifths of the kernel's time at every shape), a lane gather is slower
# still. With k on SUBLANES a block's scale is a sublane broadcast of one
# row, shared by block / 8 vregs.
#
# So the tile is turned once per grid step, as 32-bit words: a byte tile
# [bo, row_bytes] viewed as int32 holds WORD_ROWS = 4 consecutive O rows in
# one lane, and the XLU transposes [bo / 4, row_bytes] words, an eighth of
# the vregs the decoded float32 tile has. The view is free only where the
# tile ARRIVES as words: a byte block out of Pallas's pipeline lies in VMEM
# in XLA's `(8, 128)(4, 1)` byte tiling, and `pltpu.bitcast(..., int32)` of
# it makes Mosaic re-lay every vreg to `(32, 128)` with four unpacks and
# three packs, 2,048 VALU operations a tile at K = 4096 beside the chunk
# chain's 9,984 in a loop VALU issue bounds (PR 62's bundle dump,
# `scripts/kernel_bundles.py`). The forward kernels therefore leave the
# stack in HBM and copy each tile in themselves as int32 words
# (`copy_tiles_ahead`): those byte tiles ARE `(2, 128)` word tiles, and a
# DMA into an `(8, 128)`-tiled int32 buffer re-tiles in the DMA engine.
# Pack p (bits 8p .. 8p+7 of a word) then decodes to rows 4i + p of the
# tile with no uint8 -> int32 widening at all, so the result's columns come
# out pack-major: `staged_product` returns them so, and the kernels put them
# back once per tile before the store (`natural_columns`).
#
# What bounds the chunk loop now is VALU issue (the same script, PR 49: the
# kernel's time falls by about 7 us for every operation a decoded vreg
# sheds, at K x O = 14336 x 4096). So a field whose value is signed is cut
# out of its word signed (`signed_field`): shift left, arithmetic shift
# right, convert, multiply, cast, five operations where shift, mask,
# subtract made six. ONE shift does not do it: a shift left clears what
# lies above a field and leaves the word's lower fields below it.

def word_scratch(spec: DecodeSpec, block_o: int, row_bytes: int, nb: int):
    """Scratch shapes of one weight stack's word path: the transposed
    words, the effective scales (and mins) staged as 128-lane groups for
    the strided row reads, and their transposes, the four packs side by
    side on lanes."""
    nbp = round_up(nb, 128)
    return [
        pltpu.VMEM((row_bytes, block_o // WORD_ROWS), jnp.int32),
        pltpu.VMEM((nbp // 128, block_o, 128), jnp.float32),
        pltpu.VMEM((2 if spec.mins else 1, nbp, block_o), jnp.float32),
    ]


def effective_side(spec: DecodeSpec, side):
    """`load_side`'s arrays -> the per-(sub-)block float32 factors the
    decode multiplies and adds, in the stored [bo, nb] layout:
    w = v * scale (+ offset). Two-level formats fold their super-scales in
    here, once per tile (d * sc, and -(dmin * mn))."""
    if spec.super_block:
        per_super = spec.super_block // spec.block
        n_sub = side[-1].shape[-1]
        if spec.mins:
            d32, dmin32, scf, mnf = side
            return (expand_super(d32, n_sub, 0, per_super) * scf,
                    -(expand_super(dmin32, n_sub, 0, per_super) * mnf))
        d32, scf = side
        return (expand_super(d32, n_sub, 0, per_super) * scf,)
    return tuple(side)


def _pad_lanes(a, mult: int = 128):
    n = a.shape[-1]
    if n % mult == 0:
        return a
    return jnp.concatenate(
        [a, jnp.zeros((a.shape[0], mult - n % mult), a.dtype)], axis=1)


def pack_major_bits(a, rows: int):
    """Float16 `[..., O, nb]` scales (or mins) -> uint16 bits
    `[..., ceil(O / rows), nb, rows]`, the operand a word tile of `rows`
    rows reads in place (`stage_words(prepared=True)`): one block a tile,
    the K/32 blocks on sublanes and the tile's rows on lanes in the order
    the word decode leaves them, column `p * rows / 4 + i` row `4 i + p`.
    A ragged last tile's rows past O are zeros, so that its block is whole
    and they decode to 0. Run once, when a program takes its weights
    (`ops/linear.prepare_scale_bits`), where `stage_words` otherwise builds
    the same array every grid step."""
    *lead, O, nb = a.shape
    bits = jax.lax.bitcast_convert_type(a.astype(jnp.float16), jnp.uint16)
    tiles = round_up(O, rows) // rows
    if tiles * rows != O:
        bits = jnp.pad(
            bits, [(0, 0)] * len(lead) + [(0, tiles * rows - O), (0, 0)])
    bits = bits.reshape(*lead, tiles, rows // WORD_ROWS, WORD_ROWS, nb)
    n = len(lead)
    return bits.transpose(*range(n + 1), n + 3, n + 2, n + 1).reshape(
        *lead, tiles, nb, rows)


def _pair_columns(g, u):
    """The `[nb, 256]` blocks of a paired tile's two stacks (each
    pack-major over its own 256 rows: 64 columns a pack) -> the `[nb, 512]`
    of the one word tile they decode as: pack p's 128 lanes are gate's 64
    beside up's 64. A lane roll and a select for every 128 lanes."""
    lanes = 128
    first = jax.lax.broadcasted_iota(
        jnp.int32, (g.shape[0], lanes), 1) < lanes // 2
    out = []
    for j in range(g.shape[1] // lanes):
        ga, ua = slc(g, j * lanes, lanes), slc(u, j * lanes, lanes)
        out += [jnp.where(first, ga, pltpu.roll(ua, lanes // 2, 1)),
                jnp.where(first, pltpu.roll(ga, lanes // 2, 1), ua)]
    return jnp.concatenate(out, axis=1)


def signed_field(spec: DecodeSpec) -> int:
    """The width b of the codes the word path cuts out of the word SIGNED,
    where they lie (`_packs`: a shift left and an arithmetic shift right),
    or 0. Static, from the spec alone:

    * byte codes that are integers (sym_int8, asym_int5, q3_k, q6_k: stored
      int8): b = 8, the byte sign-extended;
    * one plane of b-bit fields whose value is `code - 2^(b-1)` (sym_int4,
      PR 49): `stage_words` flips every field's top bit once a tile, which
      makes the field `code - 2^(b-1)` in two's complement, where the
      unsigned field costs a shift, a mask and `decode_values`' subtract.
      The same int32 either way, so the same float32 and the same bf16.

    An asymmetric plane (its minimum would have to be re-centred, and
    would round differently), two planes, a codebook and the float formats
    keep the unsigned field."""
    if not spec.planes:
        return 8 if spec.value == ("offset", 0) else 0
    if (len(spec.planes) == 1
            and spec.value == ("offset", 1 << (spec.planes[0] - 1))):
        return spec.planes[0]
    return 0


def _top_bits(bits: int):
    """int32 with the top bit of every `bits`-wide field set."""
    mask = sum(1 << j for j in range(bits - 1, 32, bits))
    return jnp.int32(mask - (1 << 32) if mask >> 31 else mask)


def stage_words(spec: DecodeSpec, w_refs, side_refs, scratch,
                piece: int = 2048, prepared: bool = False):
    """Once a word tile: its words and its effective scales, transposed
    into `scratch` (see `word_scratch`), the words with their
    fields' top bits flipped where a plane's fields are cut out signed
    (`signed_field`: one operation a WORD vreg, an eighth of one a decoded
    vreg, before the transpose). `w_refs` holds the
    tile's code blocks (the int32 words `copy_tiles_ahead` brought, loaded
    as they are; a uint8 block out of a pipeline is viewed as words, which
    is NOT free: see the note above `word_scratch`) and `side_refs` each
    block's side refs: one block of
    512 rows, or the 256-row gate and up blocks of a gated expert call
    (`tiling.grouped_tile`), whose 64 + 64 word rows are stacked on
    sublanes and turned as one, so that the tile's rows 0..255 are gate
    and 256..511 up. With ``prepared`` the side blocks are
    `pack_major_bits`'s `[nb, rows]`, already turned: a load and
    `f16_bits_to_f32` (single-level formats; no `effective_side`)."""
    wT_ref, s32_ref, sT_ref = scratch
    row_bytes = w_refs[0].shape[1]
    q = wT_ref.shape[1]
    # (a byte code is signed as stored: only a plane's fields are flipped)
    flip = signed_field(spec) if spec.planes else 0
    for j0 in range(0, row_bytes, piece):
        cw = min(piece, row_bytes - j0)
        words = [r[:, j0:j0 + cw] if r.dtype == jnp.int32
                 else pltpu.bitcast(r[:, j0:j0 + cw], jnp.int32)
                 for r in w_refs]
        words = words[0] if len(words) == 1 else jnp.concatenate(words, axis=0)
        wT_ref[j0:j0 + cw, :] = (words ^ _top_bits(flip) if flip else words).T
    if prepared:
        for i in range(spec.n_side):
            a = [f16_bits_to_f32(refs[i][...]) for refs in side_refs]
            sT_ref[i, :a[0].shape[0], :] = (
                a[0] if len(a) == 1 else _pair_columns(*a))
        return
    effs = [effective_side(spec, load_side(spec, refs)) for refs in side_refs]
    for i, a in enumerate(zip(*effs)):
        a = _pad_lanes(a[0] if len(a) == 1 else jnp.concatenate(a, axis=0))
        for g in range(a.shape[-1] // 128):
            s32_ref[g] = slc(a, g * 128, 128)
            # rows 4i + p of the tile are pack p: a strided sublane read
            # (the staging ref's last dim is 128, the only width Mosaic
            # strides)
            for p in range(WORD_ROWS):
                sT_ref[i, g * 128:(g + 1) * 128, p * q:(p + 1) * q] = s32_ref[
                    g, pl.ds(p, q, stride=WORD_ROWS), :].T


def _packs(words, shift: int, bits: int, signed: bool = False):
    """[c, bo/4] words -> [c, bo] int32: the `bits`-wide field at
    `shift` of each of the four bytes, pack p on lanes p*bo/4 .. (a
    128-aligned lane concatenation places vregs, it moves nothing)."""
    if signed:  # `signed_field`: sign-extend the field where it lies
        up = [32 - bits - shift - 8 * p for p in range(WORD_ROWS)]
        return jnp.concatenate(
            [(words << u if u else words) >> (32 - bits) for u in up], axis=1)
    return jnp.concatenate(
        [(words >> (8 * p + shift)) & ((1 << bits) - 1)
         for p in range(WORD_ROWS)], axis=1)


def word_codes(spec: DecodeSpec, K: int, wT_ref, seg: int, off, c: int):
    """int32 codes [c, bo] (pack-major lanes) of the `c` logical elements
    at `off` (a Python int or a traced index, a multiple of 8) within
    segment `seg` of the finest plane split: `plane_chunk_code` with the
    byte axis on sublanes. Within a segment every plane's split index is
    static, so the shifts are. A signed field (`signed_field`) comes out
    as the value itself, `code - 2^(b-1)` or the byte."""
    signed = bool(signed_field(spec))
    if not spec.planes:
        return _packs(wT_ref[pl.ds(off, c), :], 0, 8, signed)
    e_seg = seg * finest_split(K, spec.planes)
    code = None
    shift = 0
    for r_plane, bits, _s, qel in plane_layout(K, spec.planes):
        mp = e_seg // qel
        words = wT_ref[pl.ds(r_plane + e_seg - mp * qel + off, c), :]
        piece = _packs(words, bits * mp, bits, signed)
        code = piece if code is None else code | (piece << shift)
        shift += bits
    return code


def _rows_repeat(a, block: int):
    """[n, q] -> [n * block, q], each row `block` times: a sublane
    broadcast (block is a multiple of 8)."""
    n, q = a.shape
    return jnp.broadcast_to(a[:, None, :], (n, block, q)).reshape(n * block, q)


def decode_chunk_words(spec: DecodeSpec, K: int, wT_ref, sT_ref, seg: int,
                       off, c: int):
    """bf16 weights [c, bo] (k on sublanes, the tile's rows on lanes,
    pack-major) of the `c` elements at `off` within segment `seg`: the
    values `decode_chunk` gives, bit for bit."""
    vals = decode_values(word_codes(spec, K, wT_ref, seg, off, c),
                         ("offset", 0) if signed_field(spec) else spec.value)
    e_seg = seg * finest_split(K, spec.planes)
    nsc = c // spec.block
    if isinstance(off, int):
        sb0 = (e_seg + off) // spec.block
    else:  # chunk off / c of a loop: c covers 8 whole blocks a row
        sb0 = pl.multiple_of(
            e_seg // spec.block + jax.lax.div(off, c) * nsc, 8)
    w = vals * _rows_repeat(sT_ref[0, pl.ds(sb0, nsc), :], spec.block)
    if spec.mins:
        w = w + _rows_repeat(sT_ref[1, pl.ds(sb0, nsc), :], spec.block)
    return w.astype(jnp.bfloat16)


def tile_product(spec: DecodeSpec, K: int, ck: int, x_ref, w_ref, side_refs):
    """float32 [block_m, block_o] = x @ dq(W tile)^T in the stored layout,
    the chunk loop of every forward tile the word path does not take
    (`staged_product` is the word path's): chunks of the logical
    contraction axis, statically unrolled, each decoded to bf16 and fed to
    the MXU, so live dequant temporaries stay O(block_o * ck) whatever K
    is."""
    bm, bo = x_ref.shape[0], w_ref.shape[0]
    x = x_ref[:].astype(jnp.bfloat16)
    side = load_side(spec, side_refs)
    w = w_ref[:]  # packed codes [block_o, row_bytes]
    acc = jnp.zeros((bm, bo), jnp.float32)
    for e0, c in walk(K, spec.planes, ck):
        wd = decode_chunk(spec, K, w, side, e0, c)  # bf16 [bo, c]
        acc += jax.lax.dot_general(
            slc(x, e0, c), wd, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    return acc


def staged_product(spec: DecodeSpec, K: int, ck: int, x_ref, scratch):
    """float32 [block_m, 512] = x @ dq(W tile)^T over the word tile
    `stage_words` left in `scratch`, columns pack-major
    (see `natural_columns`): the word path's chunk loop. The chunks of one
    segment of the finest plane split are ONE `fori_loop` body wherever
    `ck` divides the segment into chunks whose scale rows start on a
    sublane tile (`tiling.words_chunk`), unrolled when it is LOWERED
    (`unroll=True`): Python traces the body once per segment whatever K
    is, which is what keeps a program's set-up (an end-to-end metric:
    every program is traced anew in every process) near the stored-layout
    loop's, and Mosaic still sees straight-line code. Left rolled, the
    loop read 33% slower on the chip (38.4 -> 51.5 us at 4096 -> 6144, PR
    32): one chunk's decode does not overlap the next one's product across
    a loop's back edge."""
    wT_ref, _, sT_ref = scratch
    qmin = finest_split(K, spec.planes)

    def chunk(acc, seg, off, c):
        wd = decode_chunk_words(spec, K, wT_ref, sT_ref, seg, off, c)
        xs = x_ref[:, pl.ds(pl.multiple_of(seg * qmin + off, 128), c)]
        return acc + jax.lax.dot_general(
            xs.astype(jnp.bfloat16), wd, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    acc = jnp.zeros((x_ref.shape[0], wT_ref.shape[1] * WORD_ROWS),
                    jnp.float32)
    one_body = words_chunk_loops(qmin, ck, spec.block)
    for seg in range(K // qmin):
        if one_body:
            acc = jax.lax.fori_loop(
                0, qmin // ck,
                lambda i, acc, seg=seg: chunk(
                    acc, seg, pl.multiple_of(i * ck, ck), ck),
                acc, unroll=True)
        else:
            for c0, c in chunk_spans(qmin, ck):
                acc = chunk(acc, seg, c0, c)
    return acc


# The two halves and the copy chain as `jit`s of the kernel's refs. A
# kernel's body is traced anew for every `pallas_call` instance, and a
# process traces dozens whose blocks have the same shapes: a cell's prefill
# buckets share a row tile, and the four K = 4096 projections of a layer
# differ in O alone, which no block shows. A module-level `jit` is traced
# ONCE for each set of block shapes (and `spec`, K, chunk), whatever kernel
# instance calls it, and is lowered in line where it is called:
# chat-steady's 45 `_qmm` instances trace the chunk loop 8 times and the
# staging twice (PERF.md section 6, PR 63). `natural_columns` is one for
# the same reason, and so is the copy chain (`copy_tiles_ahead`, PR 64),
# whose HBM ref does show O: once a weight stack's shape, five times for
# those 45.

@functools.partial(jax.jit, static_argnames=("spec", "prepared"))
def stage_tile(w_bufs, slot, side_refs, scratch, *, spec: DecodeSpec,
               prepared: bool = False):
    """`stage_words` of the tile in buffer `slot` of each of `w_bufs`
    (`copy_tiles_ahead`'s), traced once for blocks of these shapes."""
    stage_words(spec, [b.at[slot] for b in w_bufs], side_refs, scratch,
                prepared=prepared)


def word_buffers(n_w: int, block_o: int, row_bytes: int):
    """Scratch shapes of `copy_tiles_ahead`: two buffers of a grid step's
    words for each of the `n_w` stacks (what the pipeline's two byte blocks
    held: `tiling.words_tile_bytes` stands), then a DMA semaphore a buffer
    and stack."""
    return [pltpu.VMEM((2, block_o // WORD_ROWS, row_bytes), jnp.int32)
            ] * n_w + [pltpu.SemaphoreType.DMA((2, n_w))]


def copy_tile(words, bufs, sem, at, lead, start: bool, *, n_o: int,
              last_rows: int):
    """Start (or wait for) the copies of grid step `at` of
    `copy_tiles_ahead`'s chain: word rows `(at % n_o) * rows ..` of
    `words[lead]` into buffer `at % 2`, for each stack's word view; the
    last tile's `last_rows` alone where it is ragged (two static copy
    shapes, picked by the tile)."""
    rows = bufs[0].shape[1]
    tile, slot = jax.lax.rem(at, n_o), jax.lax.rem(at, 2)

    def run(n):
        for i, (w, b) in enumerate(zip(words, bufs)):
            dma = pltpu.make_async_copy(
                w.at[(*lead, pl.ds(tile * rows, n), slice(None))],
                b.at[slot, pl.ds(0, n), :], sem.at[slot, i])
            dma.start() if start else dma.wait()

    if last_rows == rows:
        run(rows)
    else:
        pl.when(tile < n_o - 1)(lambda: run(rows))
        pl.when(tile == n_o - 1)(lambda: run(last_rows))


@functools.partial(jax.jit, static_argnames=("n_o", "last_rows"))
def copy_tiles_ahead(stacks, bufs, sem, m, o, n_m, lead, lead_next, *,
                     n_o: int, last_rows: int):
    """The forward kernels' code tiles, brought by the kernel's own DMA AS
    WORDS, one grid step ahead (PR 62's `m`). Each of `stacks` stays in HBM
    (`pl.ANY`), `[..., O, row_bytes]` bytes viewed as int32 `[..., O / 4,
    row_bytes]`. Grid step `(m, o)` is step `m * n_o + o` of the `n_m *
    n_o` that copy (run in order, `n_o` a row tile; `n_m` the row tiles
    that do: all of a dense call's, the live ones of a grouped call's),
    and brings a tile of `stack[lead]` (the leading indices: a layer, or a
    layer and the row tile's expert; `lead_next` those of step + 1) into
    buffer `step % 2` of that stack's `bufs` (`[2, rows, row_bytes]`
    int32). A step starts step + 1's copies (where there is one) BEFORE it
    waits for its own; the first starts both, so nothing but the call's
    first tile is waited for in full. A ragged last tile (`last_rows` <
    rows) brings its valid rows alone: the rest of its buffer is whatever
    it held (`qmatmul._qmm`). Traced once for stacks of one shape, whatever
    kernel instance calls it (a prefill's buckets, a decode step), the
    step's arithmetic with it.
    -> the buffer that holds this step's words, `bufs[i].at[slot]`."""
    step, n_steps = m * n_o + o, n_m * n_o
    copy = functools.partial(
        copy_tile, [w.bitcast(jnp.int32) for w in stacks], bufs, sem, n_o=n_o)
    # (a call's first tile is whole: a ragged one has a whole one before it)
    pl.when(step == 0)(
        lambda: copy(step, lead, True, last_rows=bufs[0].shape[1]))
    pl.when(step + 1 < n_steps)(
        lambda: copy(step + 1, lead_next, True, last_rows=last_rows))
    copy(step, lead, False, last_rows=last_rows)
    return jax.lax.rem(step, 2)


def _interpreter_follows_jits():
    """The CPU interpreter gives a kernel's semaphores a type XLA has
    (int16) and carries that into the bodies of `cond`, `scan` and `while`,
    not into a `jit`'s: `copy_tiles_ahead` takes its semaphores as
    arguments, so the interpreter is told to follow a `jit` the same way.
    A compiled kernel never comes here."""
    from jax._src import pjit
    from jax._src.pallas import hlo_interpreter as interpreter

    interpreter._eval_jaxpr_hop_rules.setdefault(
        pjit.jit_p, interpreter.make_hop_rule(pjit.jit_p, "jaxpr"))


_interpreter_follows_jits()


@functools.partial(jax.jit, static_argnames=("spec", "K", "ck"))
def product_of_tile(x_ref, scratch, *, spec: DecodeSpec, K: int, ck: int):
    """`staged_product` with its columns put back (`natural_columns`),
    traced once for blocks of these shapes: one `jit` an instance calls,
    not two (a call of a `jit` costs a kernel's trace 2 to 3 ms, as much as
    `copy_tiles_ahead`'s does: PERF.md section 6, PR 64)."""
    return natural_columns(staged_product(spec, K, ck, x_ref, scratch))


# out[r, l] = x[r, idx[r, l, 0]] on one 128-lane group: the gather
# `jnp.take_along_axis(x, idx, axis=1)` traces to (Mosaic's `dynamic_gather`)
# without the dozen equations of index bookkeeping around it
_LANE_GATHER = jax.lax.GatherDimensionNumbers(
    offset_dims=(), collapsed_slice_dims=(1,), start_index_map=(1,),
    operand_batching_dims=(0,), start_indices_batching_dims=(0,))


@jax.jit
def natural_columns(y):
    """[block_m, 512] with pack-major columns (column 128 p + i holds row
    4i + p of the tile) -> natural order, once per tile before the store:
    output lane l of the j-th 128-lane group is pack l % 4, column
    32j + l // 4: a lane gather per pack and a select. 16 gathers for
    every 8 rows of the tile; a transpose of the [M, O] result in XLA
    instead costs less at M <= 32 (1.7 us a call, chip, PR 32) and a
    quarter of the GEMM itself at M >= 256."""
    bm, bo = y.shape
    assert bo == WORD_ROWS * 128, bo  # one 128-lane group a pack
    packs = [slc(y, p * 128, 128) for p in range(WORD_ROWS)]
    lane = jax.lax.broadcasted_iota(jnp.int32, (bm, 128), 1)
    is_pack = [(lane & (WORD_ROWS - 1)) == p for p in range(1, WORD_ROWS)]
    col = jax.lax.shift_right_logical(lane, 2)  # l // WORD_ROWS
    out = []
    for j in range(WORD_ROWS):
        idx = (col + j * (128 // WORD_ROWS))[..., None]
        o = None
        for p, pack in enumerate(packs):
            t = jax.lax.gather(
                pack, idx, _LANE_GATHER, slice_sizes=(1, 1),
                mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)
            o = t if o is None else jax.lax.select(is_pack[p - 1], t, o)
        out.append(o)
    return jnp.concatenate(out, axis=1)
