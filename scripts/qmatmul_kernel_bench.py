#!/usr/bin/env python3
"""`qmatmul` alone on the chip, at the benchmark cells' own (K, O) and M,
whole and with parts of its body taken out, IN THIS SCRIPT'S OWN COPIES of
the kernel bodies (nothing in `bigdl_tpu/` has a switch for it). By hand,
through the chip tool:

    python scripts/qmatmul_kernel_bench.py [--plan cells|mistral|quick]
    python scripts/qmatmul_kernel_bench.py --plan experts   # the grouped kernel
    python scripts/qmatmul_kernel_bench.py --plan experts --variants tree shared
    python scripts/qmatmul_kernel_bench.py --plan ragged    # O no multiple of 512
    python scripts/qmatmul_kernel_bench.py --plan ahead     # one set / ahead / unstaged
    python scripts/qmatmul_kernel_bench.py --plan dma       # the tile copied in as words
    python scripts/qmatmul_kernel_bench.py --lower   # compile only, no chip

Each line is one (body, variant, K, O, M): 64 dependent calls inside one
jit (the next call's layer index is computed from the last one's output),
host clock at 16, 32 and 64 calls, least squares for the time of one; the
bytes that must move (packed codes, scales, x, y) over 819 GB/s is
`hbm_us`, and `share` their quotient.

Bodies:

* `tree`: `bigdl_tpu.ops.pallas.qmatmul._qmm` as it stands, the float16
  scales viewed as uint16 and staged every grid step; `prep`: the same on
  prepared scale bits (`qdecode.pack_major_bits`), which is what a cell
  runs since PR 48. A word tile is staged and THEN multiplied, out of one
  scratch set. Three variants price the staging, in `--plan mistral`,
  `--plan experts` and, alone, `--plan ahead`: **one set** (`tree` /
  `prep`; this script's copy `words` variant `s`; experts `tree` /
  `signed`), **ahead** (`words` variant `s-h`, experts `ahead`: each tile
  staged beside the product of the one before it out of a SECOND scratch
  set; PR 63 read this form and nine siblings on the chip, all 0 to 20%
  SLOWER than one set: PERF.md section 6) and **nothing staged**, the
  bound (`words` variant `s-t`, experts `unstaged`: a call's first tile
  staged, every other multiplied out of what is there);
* `loop` and `ragged` (`--plan ragged`, PR 55), the tree's `_qmm` on
  prepared bits in the two forms an O that is no multiple of 512 can take:
  the stored-layout loop at `pick_block_o`'s 256- or 128-row tile (what the
  cells' heads ran until PR 55), and the word path over `word_tiles(O)`
  tiles, the last one ragged, WHATEVER `tiling.ragged_word_tiles` says of
  that O: the plan's small O (under one tile, then one to four whole tiles
  and a remainder) are where that rule's threshold was read from. These
  calls take microseconds, so the plan times 64, 128 and 256 calls, best of
  five, and still repeats to 10% only: read a ratio from several runs;
* `words`: this script's copy of the word path (`qdecode.tile_product`
  with scratch): the tile read as 32-bit words, transposed once, decoded
  with k on sublanes. A variant is letters joined by `-`. The decode of a
  weight: `d` the chain the tree ran until PR 49 (shift, mask, subtract,
  convert, multiply, cast); `s` the nibbles' top bits flipped once a tile
  and the field cut out signed (shift left, arithmetic shift right: what
  the tree runs since PR 49); `i` the flipped field masked where it lies
  and the scale carrying 2^-28 (shift left, mask); `i-1` ISSUE 49's one
  shift alone, which is NOT the dequantizer's weights (the word's lower
  nibbles stay below the field) and is kept as a time only. What is taken
  out: `a` scales not spread (one broadcast row); `b` the SUBTRACT alone
  (the raw byte of the word: it still shifts, masks and converts, so it
  prices one operation of the chain and not "the code decode"); `t` the
  tile staged on a call's first grid step alone (no transpose of words or
  scales on any other); `h` each tile staged AHEAD: the O grid one step
  longer, step o stages tile o into one of two scratch sets and multiplies
  tile o - 1 out of the other (the sets static, by the step's parity, both
  ends peeled: the best of the forms PR 63 measured, and slower than one
  set); `g` no `natural_columns` (the columns stored
  pack-major); `c` nothing computed (tiles fetched, output zero). `--plan
  dma` (PR 62's `m`, in the tree since PR 64): `p` the scales as prepared
  bits `[nb, 512]` (what a cell's tree reads since PR 48; the copy's default
  is the stored `[512, nb]`, staged and turned), so that `s-p` is the word
  path as the tree ran it until PR 64, the code block a pipelined uint8
  block that `pltpu.bitcast` re-lays on the VALU; `m` the stack left in HBM
  and each tile copied in AS WORDS by the kernel's own DMA, one grid step
  ahead, into two `(8, 128)` int32 buffers (`words_by_dma`, the chain
  written out in the kernel; the tree's `prep` holds the same chain in
  `qdecode.copy_tiles_ahead`); `m-t` prices what staging is left;
* `rows`: the copy of the loop as it was before PR 32 (and still is where
  no 512-row tile fits, and in `qbackward`): stored [o, k] layout, scales
  spread over lanes by a float32 one-hot matmul per chunk. Variants `d`,
  `a`, `b` and `a-b` (widen, convert, cast and the product alone).

`--plan experts` (PR 44) does the same for the grouped expert kernel
`moe_qmatmul` at the four MoE cells' expert shapes, one line a (shape,
variant): `hit` experts of E each with one row tile, as a decode step has
them. It prints us a hit expert, the share of HBM time, and what a grid
step costs over its bytes' time (`step_over_us`). Variants, in
`experts_kernel` below: `tree` the kernel as it stands, rows sorted by
expert (an x tile a live expert); `shared` the same on the step's rows as
they stand, one `[block_m, K]` block every tile reads (what a decode step's
gate / up call runs since PR 53: the difference is the x re-fetch); `loop` the
stored-layout loop at 256-row tiles (what a 768-wide gated call ran before
PR 44); `fetch` nothing computed; `paired` the gated call's two 256-row
blocks decoded as ONE 512-row word tile; `mb` / `one` several word tiles a
grid step (about 1 MB of codes; a whole expert); `chain`, `signed`,
`inplace`: the tree's own plan (`tiling.grouped_tile`) on this script's
copies of the decode, `d`, `s` and `i` above; `ahead`: `signed` with the
tiles a step holds staged each beside the product of the one before it,
out of two scratch sets; `unstaged`: `signed` with the call's first tile
alone staged.

It also checks, on the device it runs on, that what each body feeds the
MXU is the dequantizer's weights bit for bit. The CPU interpreter cannot
say: its float32 dot is exact, the MXU's default-precision one is not.

Not part of the benchmark: the cells measure the kernel inside their
programs (`kernel.decode.qmatmul_roofline`, `generate.decode_mbu`).
"""

from __future__ import annotations

import argparse
import functools
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops.pallas import qdecode
from bigdl_tpu.ops.pallas.qdecode import DecodeSpec
from bigdl_tpu.ops.pallas.tiling import (
    VMEM_LIMIT_BYTES, finest_split, forward_chunk, pick_block_m, pick_block_o,
    round_up, word_tiles, words_ok,
)

SPEC = DecodeSpec(planes=(4,), value=("offset", 8), block=32)
HBM_BYTES_PER_S = 819e9  # TPU v5e, Google Cloud documentation


# ----------------------------------------------------------- the two bodies

def rows_body(x, w_ref, s_ref, *, K, ck, flags):
    """The stored-layout loop (`qdecode.tile_product`), sym_int4."""
    bo, kh = w_ref.shape[0], K // 2
    s = qdecode.f16_bits_to_f32(s_ref[:])
    w = w_ref[:]
    acc = jnp.zeros((x.shape[0], bo), jnp.float32)
    for e0, c in qdecode.walk(K, SPEC.planes, ck):
        mp = e0 // kh
        wb = qdecode.slc(w, e0 - mp * kh, c).astype(jnp.int32)
        if "b" in flags:
            v = wb.astype(jnp.float32)
        else:
            v = (((wb >> (4 * mp)) & 15) - 8).astype(jnp.float32)
        if "a" in flags:
            sx = jnp.broadcast_to(qdecode.slc(s, e0 // 32, 1), (bo, c))
        else:
            sx = qdecode.expand_scales(qdecode.slc(s, e0 // 32, c // 32), c, 32,
                                       from_f16=True)
        acc += jax.lax.dot_general(
            qdecode.slc(x, e0, c), (v * sx).astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    return acc


FLIP = np.int32(-0x77777778)  # 0x88888888: the top bit of every nibble
TOP = np.int32(-0x10000000)  # 0xF0000000: a word's last nibble


# this script's copies of the word path, by name: the chain of six, and the
# two forms that convert a flipped nibble (`pack_values`)
COPIES = {"chain": "d", "signed": "s", "inplace": "i", "ahead": "s",
          "unstaged": "s"}


def flags_of(variant):
    """`a-b`, `i-t`: a variant's letters, joined by `-`."""
    return frozenset(variant.split("-"))


def stage_copy(w_refs, s_refs, scratch, flags):
    """`qdecode.stage_words` for sym_int4's stored scales (one 512-row block,
    or a gated pair's two 256-row blocks), as it was before PR 49. `i` and
    `s` flip the nibbles' top bits on the way (the field is then `code - 8`
    in two's complement), and `i` has the scales carry 2^-28. `p`: the
    scale block is `qdecode.pack_major_bits`'s `[nb, 512]` (a load and the
    float16 decode, no transpose). `m`: the code refs hold words already
    (`words_by_dma`), where a byte block is viewed as words on the VALU."""
    wT_ref, s32_ref, sT_ref = scratch
    row_bytes = w_refs[0].shape[1]
    q = wT_ref.shape[1]
    for j0 in range(0, row_bytes, 2048):
        cw = min(2048, row_bytes - j0)
        words = [r[:, j0:j0 + cw] if "m" in flags
                 else pltpu.bitcast(r[:, j0:j0 + cw], jnp.int32)
                 for r in w_refs]
        words = words[0] if len(words) == 1 else jnp.concatenate(words, axis=0)
        wT_ref[j0:j0 + cw, :] = (words ^ FLIP if flags & {"i", "s"}
                                 else words).T
    a = [qdecode.f16_bits_to_f32(r[...]) for r in s_refs]
    if "p" in flags:
        (a,) = a
        sT_ref[0, :a.shape[0], :] = (a * jnp.float32(2.0 ** -28)
                                     if "i" in flags else a)
        return
    a = qdecode._pad_lanes(a[0] if len(a) == 1 else jnp.concatenate(a, axis=0))
    if "i" in flags:
        a = a * jnp.float32(2.0 ** -28)
    for g in range(a.shape[-1] // 128):
        s32_ref[g] = qdecode.slc(a, g * 128, 128)
        for p in range(4):
            sT_ref[0, g * 128:(g + 1) * 128, p * q:(p + 1) * q] = s32_ref[
                g, pl.ds(p, q, stride=4), :].T


def pack_values(words, p, h, flags):
    """float32 values of pack p, nibble half h of a chunk's words. `d`: the
    chain of shift, mask, subtract and convert; `b`: without the subtract
    and on the whole byte; on flipped nibbles `s`: shift left and
    arithmetic shift right, `code - 8` sign-extended, and `i`: shift left
    and mask where the field lies, `(code - 8) * 2^28` (the word's first
    nibble needs no mask, its last no shift)."""
    up = 28 - 8 * p - 4 * h
    if "i" in flags:
        u = words << up if up else words
        if "1" in flags:  # ISSUE 49's one shift alone: NOT the values (the
            return u.astype(jnp.float32)  # nibbles below stay), a time only
        return (u & TOP if up < 28 else u).astype(jnp.float32)
    if "s" in flags:
        return ((words << up if up else words) >> 28).astype(jnp.float32)
    if "b" in flags:
        return ((words >> (8 * p)) & 0xFF).astype(jnp.float32)
    return (((words >> (8 * p + 4 * h)) & 15) - 8).astype(jnp.float32)


def words_product(x_ref, scratch, *, K, ck, flags):
    """The word path's chunk loop (`qdecode.staged_product`), sym_int4: the
    chunks of each nibble half one loop body, unrolled when it is lowered;
    the four packs side by side on lanes. (`split`: a chain and a dot per
    pack instead; `roll`: the loop left rolled.)"""
    wT_ref, _, sT_ref = scratch
    kh = K // 2
    q = wT_ref.shape[1]
    split = "split" in flags

    def weights(words, sx, h, packs):
        v = [pack_values(words, p, h, flags) for p in packs]
        v = v[0] if len(v) == 1 else jnp.concatenate(v, axis=1)
        return (v * sx).astype(jnp.bfloat16)

    acc = tuple(jnp.zeros((x_ref.shape[0], q), jnp.float32) for _ in range(4)
                ) if split else jnp.zeros((x_ref.shape[0], 4 * q), jnp.float32)
    for h in range(2):
        def chunk(i, acc, h=h):
            if kh == ck:  # one chunk a half (K = 768): static offsets
                off, sb0, x0 = 0, (h * kh) // 32, h * kh
            else:
                off = pl.multiple_of(i * ck, ck)
                sb0 = pl.multiple_of((h * kh) // 32 + off // 32, 8)
                x0 = pl.multiple_of(h * kh + off, 128)
            words = wT_ref[pl.ds(off, ck), :]
            rows = pl.ds(0, 1) if "a" in flags else pl.ds(sb0, ck // 32)
            xs = x_ref[:, pl.ds(x0, ck)].astype(jnp.bfloat16)
            dot = lambda wd: jax.lax.dot_general(
                xs, wd, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            spread = lambda s: (jnp.broadcast_to(s, (ck, s.shape[1]))
                                if "a" in flags
                                else qdecode._rows_repeat(s, 32))
            if split:
                return tuple(
                    acc[p] + dot(weights(
                        words, spread(sT_ref[0, rows, p * q:(p + 1) * q]),
                        h, (p,))) for p in range(4))
            return acc + dot(weights(words, spread(sT_ref[0, rows, :]), h,
                                     range(4)))
        acc = chunk(0, acc) if kh == ck else jax.lax.fori_loop(
            0, kh // ck, chunk, acc, unroll="roll" not in flags)
    return jnp.concatenate(acc, axis=1) if split else acc


def words_body(x_ref, w_ref, s_ref, scratch, *, K, ck, flags):
    """The word path (`stage_words`, then `staged_product`). `t`: the tile is staged
    on the call's first grid step alone, so every other step runs the chunk
    loop on what is there and transposes nothing."""
    def stage():
        stage_copy((w_ref,), (s_ref,), scratch, flags)

    if "t" in flags:
        pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))(stage)
    else:
        stage()
    return words_product(x_ref, scratch, K=K, ck=ck, flags=flags)


def words_by_dma(layer_ref, w_hbm, wbuf, sem, n_o):
    """`m`: the code tile brought by the kernel's own DMA as WORDS. The stack
    stays in HBM (`pl.ANY`), its REF viewed as int32 `[L, O / 4, row_bytes]`
    (XLA's `(8, 128)(4, 1)` byte tiles are `(2, 128)` word tiles), and a
    tile's `[128, row_bytes]` words are copied into one of two `(8, 128)`
    tiled buffers: the DMA engine does the re-tiling that costs the VALU
    seven operations a vreg. Tile s + 1 is asked for before tile s is waited
    for; the call's first step asks for both. The tree's chain
    (`qdecode.copy_tiles_ahead`) written out in the kernel.
    -> the buffer that holds this step's words."""
    step = pl.program_id(0) * n_o + pl.program_id(1)
    words = w_hbm.bitcast(jnp.int32)

    def copy(at):
        return pltpu.make_async_copy(
            words.at[layer_ref[0], pl.ds((at % n_o) * 128, 128), :],
            wbuf.at[at % 2], sem.at[at % 2])

    pl.when(step == 0)(lambda: copy(step).start())
    pl.when(step + 1 < pl.num_programs(0) * n_o)(
        lambda: copy(step + 1).start())
    copy(step).wait()
    return wbuf.at[step % 2]


def ahead_body(x_ref, w_ref, s_ref, o_ref, scratch, *, K, ck, flags, n):
    """`h`: step o of n + 1 stages tile o and multiplies tile o - 1, the two
    scratch sets picked by the step's parity (static), both ends peeled."""
    A, B = scratch[:3], scratch[3:]
    o = pl.program_id(1)
    even = (o & 1) == 0

    def stage(sc):
        stage_copy((w_ref,), (s_ref,), sc, flags)

    def product(sc):
        acc = words_product(x_ref, sc, K=K, ck=ck, flags=flags)
        o_ref[:] = qdecode.natural_columns(acc).astype(o_ref.dtype)

    pl.when(o == 0)(lambda: stage(A))
    pl.when(o == n)(lambda: product(A if (n - 1) % 2 == 0 else B))

    @pl.when((o > 0) & (o < n) & even)
    def _even():
        stage(A)
        product(B)

    @pl.when((o < n) & jnp.logical_not(even))
    def _odd():
        stage(B)
        product(A)


def _kernel(layer_ref, x_ref, w_ref, s_ref, o_ref, *scratch, K, ck, body,
            variant, n=0):
    flags = flags_of(variant)
    if "m" in flags:
        *scratch, wbuf, sem = scratch
        w_ref = words_by_dma(layer_ref, w_ref, wbuf, sem, n)
    if "c" in flags:
        o_ref[:] = jnp.zeros(o_ref.shape, o_ref.dtype)
        return
    if "h" in flags:
        ahead_body(x_ref, w_ref, s_ref, o_ref, scratch, K=K, ck=ck,
                   flags=flags, n=n)
        return
    if body == "words":
        acc = words_body(x_ref, w_ref, s_ref, scratch, K=K, ck=ck,
                         flags=flags)
        if "g" not in flags:  # `g`: the columns left pack-major
            acc = qdecode.natural_columns(acc)
    else:
        acc = rows_body(x_ref[:].astype(jnp.bfloat16), w_ref, s_ref, K=K,
                        ck=ck, flags=flags)
    o_ref[:] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_o", "ck",
                                             "body", "variant"))
def qmm_copy(layer, x2, w, s, *, block_m, block_o, ck, body, variant):
    """`qmatmul._qmm` with this script's body (the word body puts its
    columns back in order as the tree's does, `natural_columns`)."""
    Mp, K = x2.shape
    O = w.shape[1]
    n = O // block_o
    # `h`: one step more; the weight-side blocks held at the last tile for
    # it (a repeated block index is not fetched again), the output block
    # one step behind (not written back in between), two scratch sets
    flags = flags_of(variant)
    ahead = "h" in flags
    tile = (lambda o: jnp.minimum(o, n - 1)) if ahead else (lambda o: o)
    scratch = (qdecode.word_scratch(SPEC, block_o, w.shape[2], K // 32)
               * (2 if ahead else 1) if body == "words" else [])
    if "m" in flags:  # two buffers of a tile's words, a semaphore each
        scratch = [*scratch,
                   pltpu.VMEM((2, block_o // 4, w.shape[2]), jnp.int32),
                   pltpu.SemaphoreType.DMA((2,))]
    return pl.pallas_call(
        functools.partial(_kernel, K=K, ck=ck, body=body, variant=variant,
                          n=n),
        name=f"qmatmul_{body}_{variant}".replace("-", "_"),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Mp // block_m, n + ahead),
            in_specs=[
                pl.BlockSpec((block_m, K), lambda m, o, l: (m, 0)),
                pl.BlockSpec(memory_space=pl.ANY) if "m" in flags else
                pl.BlockSpec((None, block_o, w.shape[2]),
                             lambda m, o, l: (l[0], tile(o), 0)),
                # `p`: prepared bits [L, tiles, nb, 512], the tile's block
                pl.BlockSpec((None, None, *s.shape[2:]),
                             lambda m, o, l: (l[0], tile(o), 0, 0))
                if "p" in flags else
                pl.BlockSpec((block_o, s.shape[1]),
                             lambda m, o, l: (tile(o), 0)),
            ],
            out_specs=pl.BlockSpec(
                (block_m, block_o),
                (lambda m, o, l: (m, jnp.maximum(o - 1, 0))) if ahead
                else (lambda m, o, l: (m, o))),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((Mp, O), jnp.bfloat16),
        compiler_params=pltpu.CompilerParams(
            # `t` stages on the first step alone, `h` carries a staged
            # tile to the next step, `m` asks for the next step's tile: the
            # steps run in order
            dimension_semantics=("arbitrary",) * 2
            if flags & {"t", "h", "m"} else ("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
    )(layer, x2, w, s)


# the bodies that are the tree's `_qmm` on prepared scale bits, and the
# layout each reads (`qmatmul.bits_layout`)
BITS = {"prep": "words", "ragged": "words", "loop": "stored"}


def bits_of(body, variant):
    """The layout of the scale bits a case reads (`operands`), or None for
    one layer's stored `[O, nb]`: a tree body's own, `p` of the copy."""
    return "words" if body == "words" and "p" in flags_of(variant) \
        else BITS.get(body)


def tiles(body, M, K, O):
    """The policy's tiles: the tree's for `tree`, `prep` and `words`, the
    stored-layout loop's (256 rows at most) for `rows` and `loop`, the word
    tile whatever the policy says of that O for `ragged`."""
    block_m = pick_block_m(M, K)
    persist_row = K // 2 + (K // 32) * 2
    block_o = 512 if body == "ragged" else pick_block_o(
        O, persist_row, cap=256 if body in ("rows", "loop") else 512,
        row_bytes=K // 2)
    persist = block_o * persist_row + block_m * K * 2 + block_m * block_o * 4
    ck = forward_chunk(words_ok(block_o, K // 2), block_o, persist,
                       finest_split(K, SPEC.planes), SPEC.block, False)
    return block_m, block_o, ck


def build(body, variant, M, K, O):
    """-> (run(n, x, w, s): n dependent calls, call(layer, x, w, s), tiles)."""
    block_m, block_o, ck = tiles(body, M, K, O)
    if body in ("words", "prep") and not words_ok(block_o, K // 2):
        return None
    if body in ("tree", *BITS):
        qm = importlib.import_module("bigdl_tpu.ops.pallas.qmatmul")

        def call(layer, x, w, s):
            return qm._qmm(SPEC, jnp.dtype(jnp.bfloat16), block_m, block_o,
                           ck, False, False, BITS.get(body), layer, x, w, s)
    else:
        def call(layer, x, w, s):
            return qmm_copy(layer, x, w, s, block_m=block_m, block_o=block_o,
                            ck=ck, body=body, variant=variant)

    @jax.jit
    def run(n, x, w, s):
        def one(i, carry):
            layer, acc = carry
            y = call(layer, x, w, s)
            flag = (y[0, 0] != y[0, 0]).astype(jnp.int32)  # 0; needs y
            return (jnp.reshape((i + 1) % w.shape[0] + flag, (1,)),
                    acc + y[0, 0].astype(jnp.float32))
        return jax.lax.fori_loop(
            0, n, one, (jnp.zeros((1,), jnp.int32), jnp.float32(0)))[1]

    return run, call, (block_m, block_o, ck)


def operands(M, K, O, block_m, key, sharding=None, prepared=None):
    """x, the codes of two layers and one layer's scale bits as stored
    `[O, nb]`, or both layers' ``prepared`` in a layout of `BITS`
    (`qdecode.pack_major_bits`; the stored `[2, O, nb]`)."""
    Mp = round_up(M, block_m)
    if sharding is not None:  # shapes alone, for a described device
        sds = lambda sh, dt: jax.ShapeDtypeStruct(sh, dt, sharding=sharding)
        return (sds((Mp, K), jnp.bfloat16), sds((2, O, K // 2), jnp.uint8),
                sds({"words": (2, word_tiles(O), K // 32, 512),
                     "stored": (2, O, K // 32), None: (O, K // 32)}[prepared],
                    jnp.uint16))
    k1, k2, k3 = jax.random.split(key, 3)
    x = jax.random.normal(k1, (Mp, K), jnp.float32).astype(jnp.bfloat16)
    w = jax.random.randint(k2, (2, O, K // 2), 0, 256, jnp.int32
                           ).astype(jnp.uint8)
    s = (jax.random.uniform(k3, (O, K // 32)) * 0.01 + 0.001
         ).astype(jnp.float16)
    if prepared == "words":
        return x, w, qdecode.pack_major_bits(jnp.stack([s, s]), 512)
    bits = jax.lax.bitcast_convert_type(s, jnp.uint16)
    return x, w, jnp.stack([bits, bits]) if prepared else bits


def measure(body, variant, M, K, O, key, ns=(16, 32, 64), reps=3):
    built = build(body, variant, M, K, O)
    if built is None:
        return None
    run, _, (block_m, block_o, ck) = built
    x, w, s = operands(M, K, O, block_m, key, prepared=bits_of(body, variant))
    jax.block_until_ready(run(2, x, w, s))
    ts = []
    for n in ns:
        best = float("inf")
        for _ in range(reps):
            t = time.perf_counter()
            jax.block_until_ready(run(n, x, w, s))
            best = min(best, time.perf_counter() - t)
        ts.append(best)
    per_call = float(np.polyfit(np.asarray(ns, float), np.asarray(ts), 1)[0])
    nbytes = O * (K // 2 + K // 32 * 2) + x.size * 2 + x.shape[0] * O * 2
    return dict(body=body, variant=variant, M=M, K=K, O=O, block_m=block_m,
                block_o=block_o, ck=ck, us=per_call * 1e6,
                hbm_us=nbytes / HBM_BYTES_PER_S * 1e6,
                share=100 * nbytes / HBM_BYTES_PER_S / per_call)


# ------------------------------------------- what the MXU is fed, on device

def fed_weights_check():
    """Each body's decoded bf16 tile [512, 4096] against
    round_bf16(float32(code - 8) * float32(scale)), computed on the host."""
    K, bo = 4096, 512

    def rows_kern(w_ref, s_ref, o_ref):
        side = qdecode.load_side(SPEC, (s_ref,))
        w = w_ref[:]
        for e0, c in qdecode.walk(K, SPEC.planes, 2048):
            o_ref[:, e0:e0 + c] = qdecode.decode_chunk(SPEC, K, w, side, e0, c)

    def words_kern(w_ref, s_ref, o_ref, *scratch, form):
        """`words`: the tree's word path; a name of `COPIES`: this
        script's copy of it (`stage_copy`, `pack_values`)."""
        flags = flags_of(COPIES.get(form, ""))
        if form == "words":
            qdecode.stage_words(SPEC, (w_ref,), ((s_ref,),), scratch)
        else:
            stage_copy((w_ref,), (s_ref,), scratch, flags)
        for seg in range(2):
            for c0 in range(0, K // 2, 512):
                if form == "words":
                    wd = qdecode.decode_chunk_words(
                        SPEC, K, scratch[0], scratch[2], seg, c0, 512)
                else:
                    v = jnp.concatenate(
                        [pack_values(scratch[0][c0:c0 + 512, :], p, seg, flags)
                         for p in range(4)], axis=1)
                    sb0 = (seg * (K // 2) + c0) // 32
                    wd = (v * qdecode._rows_repeat(
                        scratch[2][0, sb0:sb0 + 16, :], 32)
                          ).astype(jnp.bfloat16)
                o_ref[seg * (K // 2) + c0:seg * (K // 2) + c0 + 512, :] = wd

    k2, k3 = jax.random.split(jax.random.key(1))
    w = jax.random.randint(k2, (bo, K // 2), 0, 256, jnp.int32
                           ).astype(jnp.uint8)
    sc = (jax.random.uniform(k3, (bo, K // 32)) * 0.01 + 0.001
          ).astype(jnp.float16)
    # float16's corners too: `i` carries 2^-28 on the scale
    sc = sc.at[:8, :8].set(jnp.asarray(
        [2.0 ** -24, 65504.0, -65504.0, 0.0, -0.0, -2.0 ** -24, 2.0 ** -14,
         -0.005], jnp.float16)[:, None])
    bits = jax.lax.bitcast_convert_type(sc, jnp.uint16)
    params = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)
    rows = pl.pallas_call(
        rows_kern, out_shape=jax.ShapeDtypeStruct((bo, K), jnp.bfloat16),
        compiler_params=params)(w, bits)
    by_words = lambda form: pl.pallas_call(
        functools.partial(words_kern, form=form),
        out_shape=jax.ShapeDtypeStruct((K, bo), jnp.bfloat16),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=(1,),
            in_specs=[pl.BlockSpec((bo, K // 2), lambda i: (0, 0)),
                      pl.BlockSpec((bo, K // 32), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((K, bo), lambda i: (0, 0)),
            scratch_shapes=qdecode.word_scratch(SPEC, bo, K // 2, K // 32)),
        compiler_params=params)(w, bits)
    # lane p * bo/4 + i of the tile is its row 4i + p
    natural = lambda t: jnp.transpose(t.reshape(K, 4, bo // 4), (2, 1, 0)
                                      ).reshape(bo, K)
    wn = np.asarray(w).astype(np.int32)
    codes = np.concatenate([wn & 15, wn >> 4], axis=1) - 8
    sf = np.repeat(np.asarray(sc).astype(np.float32), 32, axis=1)
    want = np.asarray(jnp.asarray(codes.astype(np.float32) * sf
                                  ).astype(jnp.bfloat16).astype(jnp.float32))
    for name, got in (("rows", rows), *(
            (form, natural(by_words(form)))
            for form in ("words", *COPIES))):
        g = np.asarray(got.astype(jnp.float32))
        bad = g != want
        rel = (np.abs(g - want)[bad] / np.abs(want)[bad]).max() if bad.any() \
            else 0.0
        yield dict(check="fed_weights_vs_dequantizer", body=name,
                   mismatched=int(bad.sum()), of=int(bad.size),
                   worst_rel=float(rel))


def product_check():
    """The tree's kernel against XLA on the same device: y in float32 from
    the dequantizer's bf16 weights at HIGHEST precision. What may differ is
    float32 summation order: a few 1e-6 of |y|."""
    from bigdl_tpu.quant.qtensor import QTensor

    qm = importlib.import_module("bigdl_tpu.ops.pallas.qmatmul")
    for M, K, O in ((1, 4096, 6144), (32, 14336, 4096), (256, 4096, 6144),
                    (32, 4096, 32000)):
        x, w, s = operands(M, K, O, pick_block_m(M, K), jax.random.key(M))
        x = x[:M]
        scales = jax.lax.bitcast_convert_type(s, jnp.float16)
        y = qm.qmatmul(x, QTensor(data=w, scales=scales, qtype="sym_int4"),
                       out_dtype=jnp.float32, layer=jnp.int32(1))
        codes = jnp.concatenate([w[1] & 15, w[1] >> 4], axis=1
                                ).astype(jnp.float32) - 8
        wd = (codes * jnp.repeat(scales.astype(jnp.float32), 32, axis=1)
              ).astype(jnp.bfloat16).astype(jnp.float32)
        want = jnp.dot(x.astype(jnp.float32), wd.T,
                       precision=jax.lax.Precision.HIGHEST)
        yield dict(check="product_vs_xla", M=M, K=K, O=O,
                   worst=float(jnp.abs(y - want).max()),
                   of=float(jnp.abs(want).max()))


def dma_check():
    """The tree's `_qmm` on prepared bits (its tiles copied in as words,
    PR 64) and this script's `s-p-m` against `s-p`, the same word path on a
    pipelined byte block (the tree until PR 64), on the same operands, on
    the device: bit for bit. One M tile and two, float16's corners among
    the scales."""
    cases = (("words", "s-p"), ("prep", "d"), ("words", "s-p-m"))
    for M, K, O in ((32, 4096, 1536), (8, 14336, 1024), (512, 4096, 2048)):
        ys = []
        for body, v in cases:
            _, call, (block_m, _, _) = build(body, v, M, K, O)
            x, w, s = operands(M, K, O, block_m, jax.random.key(O + M),
                               prepared="words")
            s = s.at[:, :, :2, :8].set(jnp.asarray(
                [1, 0x03FF, 0x8001, 0, 0x8000, 0x0400, 0x7BFF, 0xFBFF],
                jnp.uint16))
            ys.append(np.asarray(call(jnp.ones((1,), jnp.int32), x, w, s
                                      ).astype(jnp.float32)))
        yield dict(check="dma_vs_s_p", M=M, K=K, O=O,
                   variants=["-".join(c) for c in cases[1:]],
                   mismatched=[int((y != ys[0]).sum()) for y in ys[1:]],
                   of=int(ys[0].size))


def ragged_check():
    """The ragged word tile against the stored-layout loop on the same
    operands, both the tree's `_qmm` on prepared bits: bf16 outputs of the
    same float32 sums in another order, a bf16 step apart at most, and
    every column finite (nothing of the ragged tile's buffer past O shows)."""
    for K, O, M in ((4096, 32000, 32), (4096, 16768, 32), (2048, 768, 32),
                    (5120, 151936, 8), (4096, 32000, 256)):
        ys = []
        for body in ("loop", "ragged"):
            _, call, (block_m, _, _) = build(body, "d", M, K, O)
            x, w, s = operands(M, K, O, block_m, jax.random.key(O + M),
                               prepared=BITS[body])
            ys.append(np.asarray(call(jnp.ones((1,), jnp.int32), x, w, s
                                      ).astype(jnp.float32)))
        yield dict(check="ragged_vs_loop", M=M, K=K, O=O,
                   worst=float(np.abs(ys[1] - ys[0]).max()),
                   of=float(np.abs(ys[0]).max()),
                   finite=bool(np.isfinite(ys[1]).all()))


# ------------------------------------------------- the grouped expert kernel

# (E, k, rows of a decode step, experts hit a layer (the traced decode_step
# spans' `moe_experts_hit` a MoE layer: ledger and PERF.md, PR 41; GLM's
# from its roofline's bytes), ((K, O, gated), ...)) of the four MoE cells
EXPERT_SHAPES = {
    "granite": (72, 10, 32, 72, ((4096, 768, True), (768, 4096, False))),
    "smallthinker": (64, 6, 16, 50, ((2560, 768, True), (768, 2560, False))),
    "glm": (64, 4, 32, 55, ((2048, 1536, True), (1536, 2048, False))),
    "mixtral": (8, 2, 16, 8, ((4096, 14336, True), (14336, 4096, False))),
    # SDAR's pass: 64 rows (16 blocks of 4), 85 live tiles a layer (PR 53)
    "sdar": (128, 8, 64, 85, ((2048, 768, True), (768, 2048, False))),
}
STEP_BYTES = 1 << 20  # `mb`: codes a grid step may hold, all stacks


def experts_kernel(te_ref, meta_ref, x_ref, *refs, K, ck, n_w, variant, tiles,
                   paired):
    """One grid step of the grouped kernel: `tiles` word tiles of one
    expert (each 512 rows of a stack, or 256 + 256 of the gated pair)."""
    del te_ref
    o_ref, scratch = refs[2 * n_w], refs[2 * n_w + 1:]
    silu = jax.nn.silu
    first_step = (pl.program_id(0) == 0) & (pl.program_id(1) == 0)

    @pl.when(pl.program_id(0) < meta_ref[0])
    def _live_tile():
        if variant == "fetch":
            o_ref[:] = jnp.zeros(o_ref.shape, o_ref.dtype)
            return
        if variant == "loop":
            accs = [qdecode.tile_product(SPEC, K, ck, x_ref, refs[2 * i],
                                         (refs[2 * i + 1],))
                    for i in range(n_w)]
            y = accs[0] if n_w == 1 else silu(accs[0]) * accs[1]
            o_ref[:] = y.astype(o_ref.dtype)
            return
        rows = 256 if paired else 512  # of a stack, a word tile
        if variant in COPIES:  # this script's copies
            flags = flags_of(COPIES[variant])
            stage = lambda ws, ss, sc: stage_copy(
                ws, [s[0] for s in ss], sc, flags)
            product = lambda sc: words_product(x_ref, sc, K=K, ck=ck,
                                               flags=flags)
            if variant == "unstaged":  # the call's first tile alone
                every = stage
                stage = lambda *a: pl.when(first_step)(lambda: every(*a))
        else:
            stage = lambda ws, ss, sc: qdecode.stage_words(SPEC, ws, ss, sc)
            product = lambda sc: qdecode.staged_product(SPEC, K, ck, x_ref,
                                                        sc)
        per = 3 if paired else 3 * n_w  # scratch refs a set
        sets = len(scratch) // per  # two for `ahead`

        def stage_tile(j):
            """Tile j of the step's into scratch set j % sets."""
            def cut(r):
                return r if tiles == 1 else r.at[pl.ds(j * rows, rows), :]
            ws = [cut(refs[2 * i]) for i in range(n_w)]
            # (a row slice of a scale REF 24 lanes wide does not lower)
            ss = [(refs[2 * i + 1][:][j * rows:(j + 1) * rows],)
                  for i in range(n_w)]
            sc = scratch[j % sets * per:(j % sets + 1) * per]
            if paired:
                stage(ws, ss, sc)
            else:
                for i in range(n_w):
                    stage(ws[i:i + 1], ss[i:i + 1], sc[3 * i:3 * i + 3])

        if sets == 2:  # `ahead`: the first tile in the open
            stage_tile(0)
        for j in range(tiles):
            if sets == 2 and j + 1 < tiles:
                stage_tile(j + 1)
            elif sets == 1 and not (variant == "unstaged" and j):
                stage_tile(j)
            sc = scratch[j % sets * per:(j % sets + 1) * per]
            if paired:
                y = qdecode.natural_columns(product(sc))
                y = silu(y[:, :256]) * y[:, 256:]
            else:
                accs = [product(sc[3 * i:3 * i + 3]) for i in range(n_w)]
                y = qdecode.natural_columns(
                    accs[0] if n_w == 1 else silu(accs[0]) * accs[1])
            o_ref[:, j * rows:(j + 1) * rows] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_o", "ck",
                                             "variant", "tiles"))
def experts_copy(te, meta, x, *arrays, block_m, block_o, ck, variant, tiles):
    """`moe_qmatmul._moe_qmm` with this script's body. `arrays`: (codes
    [L, E, O, C], scale bits [E, O, nb]) a stack; `block_o` rows of each
    stack a grid step."""
    Mp, K = x.shape
    n_w = len(arrays) // 2
    O = arrays[0].shape[2]
    n_o = O // block_o

    def o_of(m, o, meta):  # a dead tile names the block already held
        return jnp.where(m < meta[0], o, n_o - 1)

    in_specs = [pl.BlockSpec((block_m, K), lambda m, o, te, meta: (
        jnp.minimum(m, meta[0] - 1), 0))]
    for i in range(n_w):
        in_specs += [
            pl.BlockSpec((None, None, block_o, arrays[2 * i].shape[3]),
                         lambda m, o, te, meta: (meta[1], te[m],
                                                 o_of(m, o, meta), 0)),
            pl.BlockSpec((None, block_o, arrays[2 * i + 1].shape[2]),
                         lambda m, o, te, meta: (te[m], o_of(m, o, meta), 0)),
        ]
    paired = n_w == 2 and O % 512 == 256
    sets = 0 if variant in ("loop", "fetch") else 1 if paired else n_w
    if variant == "ahead" and tiles > 1:  # a second set to stage into
        sets *= 2
    scratch = qdecode.word_scratch(SPEC, 512, K // 2, K // 32) * sets
    return pl.pallas_call(
        functools.partial(experts_kernel, K=K, ck=ck, n_w=n_w,
                          variant=variant, tiles=tiles, paired=paired),
        name=f"moe_qmatmul_{variant}",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(Mp // block_m, n_o),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((block_m, block_o),
                                   lambda m, o, te, meta: (m, o)),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((Mp, O), jnp.bfloat16),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
    )(te, meta, x, *arrays)


def experts_tiles(variant, K, O, n_w):
    """(block_o a stack, word tiles a step, chunk) of a variant, or None
    where the shape has no such form."""
    from bigdl_tpu.ops.pallas.tiling import words_chunk

    row = K // 2 + (K // 32) * 2
    qmin = finest_split(K, SPEC.planes)
    if variant in ("loop", "fetch"):
        bo = pick_block_o(O, row * n_w, cap=256 if variant == "loop" else 512,
                          row_bytes=K // 2 * n_w)
        if variant == "fetch" and n_w == 2 and O % 512:
            bo = 256
        return bo, 1, forward_chunk(False, bo * n_w, 0, qmin, SPEC.block,
                                    False)
    if variant in COPIES:  # the tree's own plan
        from bigdl_tpu.ops.pallas.tiling import grouped_tile

        form, rows, held = grouped_tile(O, row, K // 2, n_w)
        if form == "loop":
            return None
        return rows * held, held, words_chunk(qmin, SPEC.block)
    paired = n_w == 2 and O % 512 == 256
    if variant == "paired" and not paired:
        return None
    rows = 256 if paired else 512  # of a stack, a word tile
    if O % rows:
        return None
    n = O // rows
    if variant == "paired":
        t = 1
    elif variant == "mb":
        t = max(d for d in range(1, n + 1) if n % d == 0
                and (d == 1 or d * rows * (K // 2) * n_w <= STEP_BYTES))
    else:  # one: a whole expert, where its blocks fit twice
        t = n
        if 2 * n_w * O * row > 12 * 1024 * 1024:
            return None
    if variant in ("mb", "one") and t == 1:
        return None  # the tree's own plan, or `paired`
    if variant == "one" and experts_tiles("mb", K, O, n_w) == (
            rows * t, t, words_chunk(qmin, SPEC.block)):
        return None  # `mb` holds the whole expert already
    return rows * t, t, words_chunk(qmin, SPEC.block)


def experts_operands(name, K, O, gated, key, sharding=None, shared=False):
    """``shared``: x is the step's rows as they stand, `[block_m, K]`."""
    from bigdl_tpu.ops.pallas import moe_qmatmul as mq

    E, k, N, hit, shapes = EXPERT_SHAPES[name]
    block_m = mq.moe_block_m(N, max(max(s[0], s[1]) for s in shapes))
    n_tiles = mq.moe_n_tiles(N, k, E, block_m)
    n_w = 2 if gated else 1
    L = 2
    shapes_ = ([((n_tiles,), jnp.int32), ((2,), jnp.int32),
                (((1 if shared else n_tiles) * block_m, K), jnp.bfloat16)]
               + [((L, E, O, K // 2), jnp.uint8),
                  ((E, O, K // 32), jnp.uint16)] * n_w)
    if sharding is not None:
        return block_m, hit, [jax.ShapeDtypeStruct(s, d, sharding=sharding)
                              for s, d in shapes_]
    te = jnp.minimum(jnp.arange(n_tiles, dtype=jnp.int32), hit - 1)
    keys = jax.random.split(key, 1 + 2 * n_w)
    x = jax.random.normal(keys[0], shapes_[2][0], jnp.float32
                          ).astype(jnp.bfloat16)
    arrays = []
    for i in range(n_w):
        arrays.append(jax.random.randint(
            keys[1 + 2 * i], (L, E, O, K // 2), 0, 256, jnp.int32
        ).astype(jnp.uint8))
        s = (jax.random.uniform(keys[2 + 2 * i], (E, O, K // 32)) * 0.01
             + 0.001).astype(jnp.float16)
        arrays.append(jax.lax.bitcast_convert_type(s, jnp.uint16))
    return block_m, hit, [te, jnp.asarray([hit, 0], jnp.int32), x, *arrays]


def experts_build(variant, name, K, O, gated):
    """-> (run(n, te, meta, x, *arrays): n dependent calls, one call, plan)."""
    from bigdl_tpu.ops.pallas import moe_qmatmul as mq
    from bigdl_tpu.quant.qtensor import QTensor

    n_w = 2 if gated else 1
    E, k, N, hit, shapes = EXPERT_SHAPES[name]
    block_m = mq.moe_block_m(N, max(max(s[0], s[1]) for s in shapes))
    if variant == "shared" and not gated:
        return None  # the down call's rows are the gate / up call's tiles
    if variant in ("tree", "shared"):  # (the form is x's shape)
        plan = None

        def call(te, meta, x, *arrays):
            ws = [QTensor(qtype="sym_int4", data=arrays[2 * i],
                          scales=jax.lax.bitcast_convert_type(
                              arrays[2 * i + 1], jnp.float16))
                  for i in range(n_w)]
            return mq.moe_qmatmul(x, ws if gated else ws[0], te, meta[0],
                                  block_m, act="silu" if gated else None,
                                  layer=meta[1], interpret=False)
    else:
        plan = experts_tiles(variant, K, O, n_w)
        if plan is None:
            return None
        block_o, tiles, ck = plan

        def call(te, meta, x, *arrays):
            return experts_copy(te, meta, x, *arrays, block_m=block_m,
                                block_o=block_o, ck=ck, variant=variant,
                                tiles=tiles)

    @jax.jit
    def run(n, te, meta, x, *arrays):
        def one(i, carry):
            layer, acc = carry
            y = call(te, jnp.stack([meta[0], layer]), x, *arrays)
            flag = (y[0, 0] != y[0, 0]).astype(jnp.int32)  # 0; needs y
            return ((i + 1) % arrays[0].shape[0] + flag,
                    acc + y[0, 0].astype(jnp.float32))
        return jax.lax.fori_loop(0, n, one, (jnp.int32(0), jnp.float32(0)))[1]

    return run, call, plan


def experts_measure(variant, name, K, O, gated, key, ns=(16, 32, 64), reps=3):
    built = experts_build(variant, name, K, O, gated)
    if built is None:
        return None
    run, _, plan = built
    block_m, hit, args = experts_operands(name, K, O, gated, key,
                                          shared=variant == "shared")
    jax.block_until_ready(run(2, *args))
    ts = []
    for n in ns:
        best = float("inf")
        for _ in range(reps):
            t = time.perf_counter()
            jax.block_until_ready(run(n, *args))
            best = min(best, time.perf_counter() - t)
        ts.append(best)
    per_call = float(np.polyfit(np.asarray(ns, float), np.asarray(ts), 1)[0])
    n_w = 2 if gated else 1
    nbytes = (n_w * O * (K // 2 + K // 32 * 2) + block_m * K * 2
              + block_m * O * 2)  # a hit expert
    us = per_call * 1e6 / hit
    hbm_us = nbytes / HBM_BYTES_PER_S * 1e6
    out = dict(plan="experts", cell=name, variant=variant, K=K, O=O,
               gated=gated, hit=hit, block_m=block_m, call_us=per_call * 1e6,
               us_expert=us, hbm_us=hbm_us, share=100 * hbm_us / us)
    if plan is not None:
        steps = O // plan[0]
        out.update(block_o=plan[0], tiles=plan[1], ck=plan[2], steps=steps,
                   step_over_us=(us - hbm_us) / steps)
    return out


def experts_check():
    """Each variant against the tree's kernel on the same operands, on the
    device (granite's two shapes, rows of live tiles): the same bf16
    weights into the same float32 sums."""
    # (`signed` is the tree's own arithmetic on PIPELINED byte blocks, where
    # the tree copies its tiles in as words since PR 64: 0 mismatched is the
    # grouped kernel's bit check; Mixtral's is a tile a grid step over many
    # steps, the chain of copies)
    variants = {"granite": ("loop", "paired", "mb", "one", *(
        c for c in COPIES if c != "unstaged")),  # (a time only)
                "mixtral": ("signed",)}
    for name, vs in variants.items():
        for K, O, gated in EXPERT_SHAPES[name][4]:
            _, hit, args = experts_operands(name, K, O, gated,
                                            jax.random.key(3))
            block_m = args[2].shape[0] // args[0].shape[0]
            want = experts_build("tree", name, K, O, gated)[1](*args)
            want = np.asarray(want[:hit * block_m].astype(jnp.float32))
            for v in vs:
                built = experts_build(v, name, K, O, gated)
                if built is None:
                    continue
                got = np.asarray(built[1](*args)[:hit * block_m
                                                 ].astype(jnp.float32))
                yield dict(check="experts_vs_tree", cell=name, variant=v,
                           K=K, O=O, worst=float(np.abs(got - want).max()),
                           mismatched=int((got != want).sum()),
                           of=float(np.abs(want).max()))


def experts_plan():
    plan = []
    for name, (_, _, _, _, shapes) in EXPERT_SHAPES.items():
        for K, O, gated in shapes:
            plan += [(v, name, K, O, gated)
                     for v in ("tree", "shared", *COPIES, "loop", "fetch",
                               "paired", "mb", "one")]
    return plan


# ----------------------------------------------------------------- the plans

# (K, O, M) of every `linear` of a cell whose O is no multiple of 512 (the
# route tables of the twelve cells' set-up logs, PR 54), at the rows the
# cell's decode step has: the seven heads (Mistral's at chat-steady's 32
# rows, `generate`'s one and Mixtral's 16), granite's Mamba `in_proj`, GLM's
# O = 768 and MiniCPM-SALA's O = 256; then, for the threshold, under one
# tile and one to four whole tiles and a remainder, at two K
RAGGED_SHAPES = (
    (4096, 32000, 32), (4096, 32000, 1), (4096, 32000, 16),
    (5120, 151936, 8), (2560, 151936, 16), (2048, 151936, 64),
    (2048, 154880, 32), (4096, 73472, 16), (4096, 16768, 32),
    (2048, 768, 32), (4096, 256, 16),
    *((K, O, M) for K, M in ((2048, 32), (4096, 16))
      for O in (128, 256, 384, 640, 896, 1152, 1408, 1664, 2176)),
)

def cell_shapes():
    """{configuration: ((K, O) of its decode step's distinct projections,
    the rows its cell's decode step has)}."""
    from bench import costs

    rows = {"mistral-7b-int4": 32, "qwen2-7b-int4": 16,
            "mixtral-8x7b-int4": 16, "brumby-14b-int4": 8}
    out = {}
    for name, M in rows.items():
        with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
            hf = json.load(f)
        out[name] = (sorted(set(costs.decode_linears(hf))), M)
    return out


def plan_of(name):
    plan = []
    if name == "quick":
        for K, O in ((4096, 6144), (14336, 4096)):
            for M in (1, 32, 256):
                plan += [("tree", "d", M, K, O), ("rows", "d", M, K, O)]
        return plan
    if name == "forms":  # the word body's loop forms against each other
        for K, O in ((4096, 6144), (14336, 4096)):
            for M in (1, 32, 256):
                plan += [("words", v, M, K, O)
                         for v in ("d", "d-split", "d-roll", "d-split-roll")]
        return plan
    if name == "ragged":
        return [(b, "d", M, K, O) for K, O, M in RAGGED_SHAPES
                for b in ("loop", "ragged")]
    shapes = cell_shapes()
    if name == "ahead":  # one set (the tree, the copy), ahead, nothing staged
        return [(b, v, M, K, O) for K, O in shapes["mistral-7b-int4"][0]
                for M in (1, 32, 256)
                for b, v in (("prep", "d"), ("words", "s"), ("words", "s-h"),
                             ("words", "s-t"))]
    if name == "dma":  # the tree (tiles copied in as words), the copy on a
        # pipelined byte block, on its own copies, and what staging is left
        for K, O, Ms in ((14336, 4096, (32, 1, 256, 1024)),
                         (4096, 28672, (32, 256, 1024)), (4096, 6144, (32,)),
                         (4096, 4096, (32,)), (18944, 3584, (16,))):
            for M in Ms:
                plan += [("prep", "d", M, K, O)] + [
                    ("words", "s-p" + v, M, K, O) for v in ("", "-m", "-m-t")]
        return plan
    if name == "mistral":  # every variant, at the cells' M
        for K, O in shapes["mistral-7b-int4"][0]:
            for M in (1, 8, 16, 32):
                plan += [("tree", "d", M, K, O), ("prep", "d", M, K, O),
                         ("rows", "d", M, K, O)]
                plan += [("words", v, M, K, O) for v in (
                    "d", "s", "i", "i-1", "a", "b", "c")]
                if M in (1, 32):  # what is left beside the chain
                    plan += [("words", v, M, K, O) for v in (
                        "i-a", "t", "s-t", "s-h", "i-t", "g", "i-g")]
                    plan += [("rows", v, M, K, O) for v in ("a", "b", "a-b")]
        return plan
    for cfg, (kos, M) in shapes.items():  # the before / after table
        for K, O in kos:
            for m in sorted({1, M} if cfg.startswith("mistral") else {M}):
                plan += [("rows", "d", m, K, O), ("tree", "d", m, K, O),
                         ("words", "c", m, K, O)]
    plan += [(b, "d", 256, K, O) for b in ("rows", "tree")
             for K, O in ((4096, 6144), (14336, 4096))]
    return plan


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--plan", default="cells",
                    choices=("cells", "mistral", "quick", "forms", "experts",
                             "ragged", "ahead", "dma"))
    ap.add_argument("--lower", "--fit", action="store_true",
                    help="compile the plan for a described v5e; no chip")
    ap.add_argument("--variants", nargs="+",
                    help="of --plan experts: these variants alone")
    ap.add_argument("--out", default="chiprun_out/qmatmul_kernel_bench.jsonl")
    args = ap.parse_args()
    experts = args.plan == "experts"
    plan = experts_plan() if experts else plan_of(args.plan)
    if experts and args.variants:
        plan = [case for case in plan if case[0] in args.variants]

    if args.lower:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        one = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
        for v, name, K, O, gated in plan if experts else ():
            built = experts_build(v, name, K, O, gated)
            if built is None:
                print(f"skip {v} {name} K={K} O={O}: no such form")
                continue
            _, _, args_ = experts_operands(name, K, O, gated, None, one,
                                           shared=v == "shared")
            built[0].lower(jax.ShapeDtypeStruct((), jnp.int32, sharding=one),
                           *args_).compile()
            print(f"ok {v} {name} K={K} O={O} {built[2]}", flush=True)
        for body, v, M, K, O in () if experts else plan:
            built = build(body, v, M, K, O)
            if built is None:
                print(f"skip {body} {v} M={M} K={K} O={O}: no 512-row tile")
                continue
            run, _, (block_m, _, _) = built
            run.lower(jax.ShapeDtypeStruct((), jnp.int32, sharding=one),
                      *operands(M, K, O, block_m, None, one,
                                bits_of(body, v))).compile()
            print(f"ok {body} {v} M={M} K={K} O={O}", flush=True)
        return 0

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind}", flush=True)
    if dev.platform != "tpu":
        print("no TPU: a kernel's time comes only from the chip")
        return 2
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    key = jax.random.key(0)
    with open(args.out, "a") as f:
        if experts:
            checks = experts_check()
            results = (experts_measure(*case, key) for case in plan)
        else:
            checks = (ragged_check() if args.plan == "ragged"
                      else dma_check() if args.plan == "dma"
                      else (*fed_weights_check(), *product_check()))
            times = (dict(ns=(64, 128, 256), reps=5)
                     if args.plan == "ragged" else {})
            results = (measure(*case, key, **times) for case in plan)
        for r in (*checks, *results):
            if r is not None:
                print(json.dumps(r), flush=True)
                f.write(json.dumps(r) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
