"""Tile / chunk policy for the fused dequant matmul family — pure
Python, no jax use at module level.

Shared by two consumers that must never disagree:

* `ops/pallas/qmatmul.py` picks its real Pallas block shapes here;
* `benchmark/roofline.py` evaluates the analytic bytes-moved / FLOPs
  model **at the same block shapes** on any machine, no device (and no
  jax) required — the first increment of the ROADMAP
  "hardware-independent perf gate".

The policy encodes the Mosaic rules the kernels were built around
(module docstring of qmatmul.py): output tiles never below 128 lanes,
full-lane operand blocks, and live VMEM bounded by an in-kernel
statically-unrolled chunk loop over K. Where a 512-row tile fits
(`words_ok`), the forward kernels run that loop on the tile read as
32-bit words and transposed (docs/kernels.md#word-path), and
`pick_block_o` picks the tile for it.
"""

from __future__ import annotations

from bigdl_tpu.utils import round_up  # noqa: F401  (re-exported policy dep)

VMEM_BUDGET = 10 * 1024 * 1024  # what the tile policy prices, see below

# Scoped-VMEM limit the matmul family asks Mosaic for. VMEM_BUDGET
# prices every operand block ONCE; the pipeline double-buffers each
# input and output block and the kernel body holds loaded copies
# besides, so the compiler's stack is about twice the priced figure: on
# a v5e the forward at block_m=128, K=14336 allocated 18.15 MiB and the
# dx kernel 17.73 MiB against the 16 MiB default and were refused (PR
# 21 chip run). A v5e core has 128 MiB of VMEM.
VMEM_LIMIT_BYTES = 32 * 1024 * 1024

# What a word-path tile (`words_tile_bytes`) may hold of that limit; the
# rest is the x and output blocks (twice) and the chunk loop's
# temporaries. The widest row the cells have, Qwen2's K = 18944, holds
# 19.7 MiB and compiles (tests/test_tpu_lowering.py).
WORDS_VMEM_BYTES = 20 * 1024 * 1024

# x row-tile slab cap: the [block_m, K] activation block must leave room
# for the weight tile + per-chunk dequant temporaries in the budget
_X_SLAB_BYTES = 3 * 1024 * 1024 + 512 * 1024


def finest_split(K: int, planes: tuple) -> int:
    """Elements per split of the finest packed plane — the chunk-walk
    period of the dequant kernels. Byte-per-element storage (planes=())
    has a single 'split' covering all of K."""
    if not planes:
        return K
    return K // max(8 // b for b in planes)


def chunk_spans(total: int, target: int):
    """Static chunk spans (start, size) covering [0, total); every
    boundary is a multiple of 128 (x/w lane alignment) when total is,
    and therefore aligned to the 16/32/64-element scale blocks.
    256-element SUPER-block boundaries are NOT respected (128-multiples
    can start mid-super-block, e.g. c0=6144 in kh=7168) — super-scale
    expansion must use the offset form of `qdecode.expand_super`."""
    spans = []
    c0 = 0
    while c0 < total:
        ck = min(target, total - c0)
        spans.append((c0, ck))
        c0 += ck
    return spans


#: O rows that share one 32-bit lane when an 8-bit code tile is read as
#: words (`qdecode`'s word path): the tile's rows 4i .. 4i+3
WORD_ROWS = 4

#: the word path's O tile: bo / WORD_ROWS lanes must fill a 128-lane
#: transpose, so 512 rows (1024 would only add VMEM)
WORD_BLOCK_O = 512

def words_tile_bytes(row_bytes: int, persist_per_row: int) -> int:
    """VMEM a word-path tile holds across its chunk loop: the packed
    codes twice (the two buffers `qdecode.copy_tiles_ahead` copies tiles
    into, what the pipeline's two byte blocks held) and the side blocks as
    the pipeline keeps them (twice), the transposed words (the
    code bytes again) and the scales staged and transposed in float32
    (8 bytes for every stored side byte covers the f16 and the 8-bit
    sub-scale formats, lane padding included at real widths)."""
    side = persist_per_row - row_bytes
    return WORD_BLOCK_O * (2 * persist_per_row + row_bytes + 8 * side)


def words_ok(block_o: int, row_bytes: int) -> bool:
    """Can a [block_o, row_bytes] code tile take `qdecode`'s word path?
    Its words [block_o / 4, row_bytes] must transpose in whole 128 x 128
    pieces. Static, from the tile's shape alone."""
    return block_o == WORD_BLOCK_O and row_bytes % 128 == 0


def word_tiles(O: int) -> int:
    """Word tiles that cover O rows, the last one ragged where
    `WORD_BLOCK_O` does not divide O."""
    return -(-O // WORD_BLOCK_O)


def ragged_word_tiles(O: int) -> bool:
    """May an O that is no multiple of `WORD_BLOCK_O` run the word path
    over `word_tiles(O)` tiles, the last one ragged? Its rows must be whole
    128-lane groups (the output block's valid columns, the code block's
    valid rows), and at least one whole tile must come before the ragged
    one, so that under half of what is decoded is padding. The threshold
    is where `scripts/qmatmul_kernel_bench.py --plan ragged` put it (a
    v5e, PR 55, the stored-layout loop's time over the ragged form's at
    K = 2048 / 4096): O = 128 0.72 / 0.79, 256 0.88 / 0.93, 384 1.03 /
    1.09 (one tile, mostly padding: the loop keeps them), 640 1.23 / 1.06,
    768 1.10 / 1.15, 1152 1.27 / 1.22, and 1.55 to 2.67 at the LM heads'
    32000 to 154880 rows, which waste 128 to 384 of them."""
    return O % 128 == 0 and O > WORD_BLOCK_O


def pick_block_o(O: int, persist_per_row: int, cap: int = WORD_BLOCK_O,
                 row_bytes: int = 0) -> int:
    """The O tile. `WORD_BLOCK_O` rows where the word path can run
    (`row_bytes` given and lane-aligned, the tile and its transposed copy
    within `WORDS_VMEM_BYTES`, and O a multiple of the tile or
    `ragged_word_tiles`: the grid is then `word_tiles(O)` and the last
    tile's rows past O are never stored); else the largest lane-legal tile
    for the stored-layout loop: a multiple of 128 dividing O (256
    preferred, 128 if the per-row persistent footprint is large or the
    caller caps it), else the full dim (always legal: Mosaic pads)."""
    if (cap >= WORD_BLOCK_O
            and (O % WORD_BLOCK_O == 0 or ragged_word_tiles(O))
            and row_bytes and words_ok(WORD_BLOCK_O, row_bytes)
            and words_tile_bytes(row_bytes, persist_per_row)
            <= WORDS_VMEM_BYTES):
        return WORD_BLOCK_O
    for bo in (256, 128):
        if bo <= cap and O % bo == 0 and (
            bo * persist_per_row <= VMEM_BUDGET // 2
        ):
            return bo
    if O % 128 == 0:
        return 128
    return O


#: packed codes one grid step of the grouped expert kernel may hold (all
#: stacks): a step costs 0.8 to 1.1 us over its bytes' time (chip, PR 44),
#: so an expert whose word tiles are small takes several a step (a whole
#: 768-wide expert: 3 MiB gate / up, 1.5 MiB down). Mixtral's tiles are
#: 2 MiB (gate / up) and 3.5 MiB and stay one a step.
GROUPED_STEP_BYTES = 3 * 1024 * 1024


def grouped_tile(O: int, persist_per_row: int, row_bytes: int,
                 stacks: int) -> tuple:
    """The grouped expert kernel's plan of one call, from O, one stack's
    row bytes and the number of stacks alone: (the loop that decodes a
    tile, the tile's rows of EACH stack, the tiles a grid step holds).

    * ``"words"``: `pick_block_o`'s 512-row word tile of each stack;
    * ``"words:paired"``: a gated call (two stacks) whose O is a multiple
      of 256 and not of 512 (768-wide experts) takes 256 rows of each
      stack, decoded as ONE 512-row word tile: 64 + 64 word rows side by
      side (`qdecode.stage_words`), one product, gate in columns 0..255
      and up in 256..511;
    * ``"loop"``: the stored-layout loop at `pick_block_o`'s 256- or
      128-row tile (an ungated call at such a width, a row of codes that
      is not whole 128-byte lanes).

    On the word path a step holds as many word tiles as divide the call
    within `GROUPED_STEP_BYTES` of codes, and the kernel's body walks
    them. `moe_qmatmul` alone calls this; the dense kernels keep
    `pick_block_o`."""
    rows, form = WORD_BLOCK_O // 2, "words:paired"
    if not (stacks == 2 and O % WORD_BLOCK_O == rows
            and words_ok(WORD_BLOCK_O, row_bytes)
            and words_tile_bytes(row_bytes, persist_per_row)
            <= WORDS_VMEM_BYTES):
        # (whole tiles only: the grouped kernel has no ragged one)
        rows = pick_block_o(
            O, persist_per_row * stacks,
            row_bytes=row_bytes * stacks if O % WORD_BLOCK_O == 0 else 0)
        if not words_ok(rows, row_bytes):
            return "loop", rows, 1
        form = "words"
    n = O // rows
    held = max(t for t in range(1, n + 1) if n % t == 0 and (
        t == 1 or t * rows * row_bytes * stacks <= GROUPED_STEP_BYTES))
    return form, rows, held


def pick_block_m(M: int, K: int, x_bpe: int = 2) -> int:
    """Row tile for the M grid dimension.

    Decode shapes (M <= ~32) keep the established GEMV contract: the
    whole padded-M extent as ONE block (grid_m == 1), identical to the
    silicon-validated 1-D-grid kernels. Above that, the largest
    MXU-friendly power-of-two tile whose [block_m, K] x-slab fits the
    VMEM allowance — weights are re-fetched once per M tile, so bigger
    tiles amortize packed-weight HBM traffic."""
    mp8 = round_up(max(M, 1), 8)
    if mp8 <= 256 and mp8 * K * x_bpe <= _X_SLAB_BYTES:
        return mp8
    for bm in (256, 128, 64, 32, 16):
        if bm < mp8 and bm * K * x_bpe <= _X_SLAB_BYTES:
            return bm
    return 8


# ---------------------------------------------------------------------------
# backward tile policy — shared by ops/pallas/qbackward.py (the fused
# low-bit dx/dW kernels) and benchmark/roofline.py's analytic backward
# costs. The dx kernel's transposed access pattern (contract over the
# weight's O rows, accumulate a full-K output row tile across the o
# sweep) keeps a [block_m, K] f32 accumulator PLUS the bf16 output
# block resident per grid cell, so its row-tile slab is priced at
# DX_ACC_BPE, not the forward's 2 B/element x slab.
# ---------------------------------------------------------------------------

#: resident bytes per dx element per grid cell: the f32 accumulator the
#: o sweep updates (4) + the bf16 output block written on the last step
#: (2). The forward's bf16 x slab has no cross-step accumulator.
DX_ACC_BPE = 6

#: dx accumulator-slab allowance: larger than the forward's x slab
#: (the acc IS the kernel's working set — weight tiles and dequant
#: temporaries are the small residents here), but strictly inside
#: VMEM_BUDGET so the chunk loop always has headroom (DSP005 audits
#: this invariant).
_DX_SLAB_BYTES = 6 * 1024 * 1024 + 512 * 1024


def pick_block_m_dx(M: int, K: int) -> int:
    """Row tile of the fused dx kernel's (m, o) grid.

    Same shape rules as `pick_block_m` (8-sublane multiples, prefer the
    whole padded extent for decode-class M, else the largest power of
    two) but sized against the [block_m, K] f32-accumulator + bf16-out
    slab at DX_ACC_BPE. Bigger tiles matter MORE here than in the
    forward: packed weights are re-fetched once per M tile, and the
    backward's weight sweep is the traffic the fusion exists to kill."""
    mp8 = round_up(max(M, 1), 8)
    if mp8 <= 256 and mp8 * K * DX_ACC_BPE <= _DX_SLAB_BYTES:
        return mp8
    for bm in (256, 128, 64, 32, 16):
        if bm < mp8 and bm * K * DX_ACC_BPE <= _DX_SLAB_BYTES:
            return bm
    return 8


def chunk_target_dx(block_o: int, block_m: int, persist_bytes: int,
                    kh: int, temp_bpe: int = 14) -> int:
    """`chunk_target` for the dx kernel: the per-chunk temporaries gain
    the [block_m, ck] f32 partial-product tile (the dot's result before
    it folds into the accumulator) on top of the dequant intermediates,
    so the chunk budget must charge both."""
    for ck in (2048, 1024, 512, 256, 128):
        if ck > kh:
            continue
        temp = (block_o * ck * temp_bpe + (ck // 16) * ck * 4
                + block_m * ck * 4)
        if persist_bytes + temp <= VMEM_BUDGET:
            return ck
    return 128


def pick_block_o_dw(O: int, K: int) -> int:
    """Output-row tile of the fused dW kernel's (o, m) grid: dW[O, K] =
    g^T @ x accumulates a [block_o, K] f32 tile across the m sweep —
    the same accumulator-slab shape as dx with O in the row seat."""
    op8 = round_up(max(O, 1), 8)
    if op8 <= 256 and op8 * K * DX_ACC_BPE <= _DX_SLAB_BYTES:
        return op8
    # block_o is the LANE dim of g's [block_m, block_o] block: a tile
    # that does not cover all of O must be a multiple of 128 (Mosaic
    # refused the former 64/32/16 tiles at K=14336, PR 21 chip run);
    # the 128 floor may exceed the slab allowance, which
    # VMEM_LIMIT_BYTES absorbs
    if 256 < op8 and 256 * K * DX_ACC_BPE <= _DX_SLAB_BYTES:
        return 256
    return 128


# ---------------------------------------------------------------------------
# LoRA epilogue policy — shared by ops/pallas/qmatmul.py (the fused
# epilogue's operand blocks) and benchmark/roofline.py / sim/cost.py's
# analytic LoRA cost, extending the "never disagree" contract to the
# S-LoRA serving path (ISSUE 18)
# ---------------------------------------------------------------------------

#: bytes/element of the LoRA operands inside the kernel (A/B/gate cross
#: as bf16; the xa intermediate is f32)
LORA_BPE = 2

#: persistent-VMEM allowance for the fused epilogue's operands: they
#: ride INSIDE the dequant-GEMM's existing budget, so they must stay a
#: small fraction of it or the chunk loop collapses to its floor
LORA_VMEM_CAP = 4 * 1024 * 1024


def lora_operand_bytes(R: int, K: int, O_block: int, M_block: int) -> int:
    """Persistent VMEM the fused LoRA epilogue adds to one grid step:
    A_cat [R, K] (full block, resident across the o sweep), one B_cat
    tile [O_block, R], the per-row gate tile [M_block, R], and the f32
    xa intermediate [M_block, R]."""
    return (R * K * LORA_BPE + O_block * R * LORA_BPE
            + M_block * R * LORA_BPE + M_block * R * 4)


def lora_fused_ok(R: int, K: int) -> bool:
    """Eligibility of the fused-epilogue path for a total LoRA width R
    (= sum of rank-bucket columns across the batch's adapter groups):
    the operands must fit the epilogue allowance at the largest tiles
    the GEMM can pick (256 x 256)."""
    return R > 0 and lora_operand_bytes(R, K, 256, 256) <= LORA_VMEM_CAP


# ---------------------------------------------------------------------------
# attention tile policy — shared by ops/pallas/flash_attention.py (the
# kernel's default block shapes) and benchmark/roofline.py's analytic
# attention costs, so the sim's cost model and the implementation cannot
# drift (the qmatmul/roofline contract, extended to attention; ISSUE 13)
# ---------------------------------------------------------------------------

#: Mosaic lane width: flash pads head_dim to a multiple of this, and no
#: operand tile goes below it in the lane dimension
MOSAIC_LANES = 128

#: flash attention's tile caps. A grid step costs about 0.35 us on a v5e
#: whatever it holds, so a step holds `FLASH_BLOCK_K` keys against up to
#: `FLASH_ROWS` q rows (the `group` query heads of one KV head stacked on
#: `block_q` positions). Positions stop at `FLASH_BLOCK_Q`: a tile on the
#: causal diagonal computes about (block_q + block_k) / 2 dead columns a
#: row, so rows come from heads before positions.
FLASH_BLOCK_Q = 512
FLASH_BLOCK_K = 512
FLASH_ROWS = 1024


def flash_tile_bytes(block_q: int, block_k: int, D: int, group: int = 1,
                     itemsize: int = 2) -> int:
    """VMEM one flash grid step is priced at: the q and output blocks
    (2-byte, double-buffered), the K and V blocks at the cache's
    `itemsize` (double-buffered; a 1-byte cache also holds its decoded
    float32 tiles and its scales), the float32 m / l / acc scratch, and
    the [rows, block_k] scores, probabilities (float32) and the
    probabilities as the context dot's operand."""
    rows, Dp = group * block_q, round_up(D, MOSAIC_LANES)
    q_out = 2 * 2 * rows * Dp * 2
    kv = 2 * 2 * block_k * Dp * itemsize
    if itemsize == 1:
        kv += 2 * block_k * Dp * 4 + 2 * 2 * block_k * MOSAIC_LANES * 4
    scratch = rows * Dp * 4 + 2 * rows * MOSAIC_LANES * 4
    return q_out + kv + scratch + rows * block_k * (4 + 4 + 2)


def _flash_tile(n: int, cap: int) -> int:
    """A tile edge for an extent of `n`: all of it, 16-padded, up to one
    lane tile; beyond, the largest multiple of 128 up to `cap` that
    divides the 128-padded extent, so that nothing is padded (and
    copied) by more than a lane tile."""
    if n <= MOSAIC_LANES:
        return round_up(n, 16)
    tiles = round_up(n, MOSAIC_LANES) // MOSAIC_LANES
    return MOSAIC_LANES * max(
        d for d in range(1, max(cap // MOSAIC_LANES, 1) + 1)
        if tiles % d == 0)


def flash_blocks(T: int, S: int, D: int = MOSAIC_LANES, group: int = 1,
                 itemsize: int = 2, block_q=None, block_k=None) -> tuple:
    """The (block_q, block_k) flash_attention runs a [T] x [S] problem
    at, from what it can observe: `block_q` POSITIONS of the `group`
    query heads that share a KV head (group * block_q q rows a step)
    against `block_k` keys. The caps above, cut until
    `flash_tile_bytes` fits `VMEM_BUDGET` (positions first, then keys:
    wide heads, many heads a group, a 1-byte cache), and clamped to the
    extents (short prefills run one small block an axis). A `block_q` /
    `block_k` given is taken as it is, clamped to the 16-padded extent
    (tests and the kernel bench)."""
    cap_q = max(
        MOSAIC_LANES,
        min(FLASH_BLOCK_Q, FLASH_ROWS // group // MOSAIC_LANES * MOSAIC_LANES))
    cap_k = FLASH_BLOCK_K

    def fit(cap, given, n):
        return (min(given, round_up(n, 16)) if given
                else _flash_tile(n, cap))

    while True:
        bq, bk = fit(cap_q, block_q, T), fit(cap_k, block_k, S)
        if flash_tile_bytes(bq, bk, D, group, itemsize) <= VMEM_BUDGET:
            return bq, bk
        if not block_q and cap_q > MOSAIC_LANES and bq > MOSAIC_LANES:
            cap_q -= MOSAIC_LANES
        elif not block_k and cap_k > MOSAIC_LANES and bk > MOSAIC_LANES:
            cap_k -= MOSAIC_LANES
        else:
            return bq, bk


def flash_live_blocks(T: int, S: int, block_q: int, block_k: int,
                      q_offset: int = 0, causal: bool = True,
                      window=None) -> int:
    """Number of (i, j) grid blocks the flash kernel COMPUTES (the rest
    are skipped via pl.when) — the same liveness predicate as
    flash_attention._kernel, evaluated statically. q slot t attends kv
    slot j iff j <= q_offset + t (causal) and j > q_offset + t - window.
    Per-row `start` padding is ignored (it masks lanes, not blocks)."""
    Tp, Sp = round_up(T, block_q), round_up(S, block_k)
    n_q, n_k = Tp // block_q, Sp // block_k
    live = 0
    for i in range(n_q):
        for j in range(n_k):
            ok = True
            if causal:
                row_max = q_offset + (i + 1) * block_q - 1
                ok = j * block_k <= row_max
            if ok and window is not None:
                row_min = q_offset + i * block_q
                ok = (j + 1) * block_k - 1 > row_min - window
            live += bool(ok)
    return live


#: what one trip of the latent decode kernel's row loop may hold of VMEM
#: (`latent_group_bytes`). A trip's fixed cost (a copy to start and to wait
#: for a page, the softmax state's update) is paid a group and the dots run
#: over a whole group whatever it holds, so a row's last group wastes half
#: a group on average: at GLM-4.7-Flash's rows (640 lanes of bf16, 20
#: heads, pages of 64) 16 pages, 5.3 MiB, beat 4, 8 and 32 on the cell's
#: mix of rows and on full ones (4.00 ms a decode step against 6.15, 4.55
#: and 4.10; PERF.md section 6, PR 51, the kernel alone on a v5e)
LATENT_GROUP_BYTES = 6 * 1024 * 1024


def latent_group_bytes(pages: int, page: int, width: int, itemsize: int,
                       rows: int) -> int:
    """VMEM one group of `pages` latent pages is priced at: both buffers
    of the group's rows [slots, width] and the copy the dots read, and the
    dozen [rows, slots] float32 temporaries of the softmax between them."""
    slots = pages * page
    return 3 * slots * width * itemsize + 12 * max(rows, 8) * slots * 4


def latent_group_pages(page: int, width: int, itemsize: int, heads: int,
                       max_pages: int) -> int:
    """Logical pages of one unit of the latent decode kernel's work (one
    score dot and one context dot over all heads, the heads padded to
    whole tiles of 16 rows), from the static shapes alone: the largest
    power of two whose `latent_group_bytes` stays within
    `LATENT_GROUP_BYTES`. Never more than a row has, never fewer than
    one."""
    rows, pages = round_up(heads, 16), 1
    while 2 * pages <= max_pages and latent_group_bytes(
            2 * pages, page, width, itemsize, rows) <= LATENT_GROUP_BYTES:
        pages *= 2
    return pages


#: the word path's chunk: [512, block_o] of codes, values, repeated
#: scales, products (float32) and the bf16 result are 6 MiB of
#: temporaries (8 with mins); smaller chunks only add loop turns, larger
#: ones VMEM (both read the same on the chip, PR 32)
WORDS_CHUNK = 512


def words_chunk_loops(qmin: int, ck: int, block: int) -> bool:
    """Can the chunks of a `qmin`-element segment be one loop body with a
    traced chunk index? They must tile the segment, and each must cover 8
    whole blocks, so that its scale rows start on a sublane tile."""
    return qmin % ck == 0 and (ck // block) % 8 == 0


def words_chunk(qmin: int, block: int) -> int:
    """The word path's chunk for segments of `qmin` elements (the finest
    plane split) and `block` elements a scale: the largest multiple of
    128 up to `WORDS_CHUNK` that divides the segment and lets
    `qdecode.tile_product` trace the segment's chunks as one loop body
    (`words_chunk_loops`); where there is none (a 64-element block at
    K = 3584), the largest that divides it, and the loop is Python's."""
    fits = [ck for ck in range(128, WORDS_CHUNK + 1, 128) if qmin % ck == 0]
    loops = [ck for ck in fits if words_chunk_loops(qmin, ck, block)]
    return max(loops or fits or [128])


def forward_chunk(words: bool, rows: int, persist_bytes: int, qmin: int,
                  block: int, mins: bool) -> int:
    """The forward kernels' chunk over segments of `qmin` elements:
    `words_chunk` on the word path, else `chunk_target` for `rows` rows
    of stored-layout temporaries (14 B an element, 20 with mins)."""
    if words:
        return words_chunk(qmin, block)
    return chunk_target(rows, persist_bytes, qmin,
                        temp_bpe=20 if mins else 14)


def chunk_target(block_o: int, persist_bytes: int, kh: int,
                 temp_bpe: int = 12) -> int:
    """The stored-layout loop's chunk: the largest whose per-chunk
    temporaries (`block_o` rows at temp_bpe B/element of dequant
    intermediates: widened codes, float32 values and expanded scales,
    the bf16 result; plus the one-hot sel) fit beside the persistent
    blocks in the scoped-VMEM budget. (`words_chunk` is the word
    path's.)"""
    for ck in (2048, 1024, 512, 256, 128):
        if ck > kh:
            continue
        temp = block_o * ck * temp_bpe + (ck // 16) * ck * 4
        if persist_bytes + temp <= VMEM_BUDGET:
            return ck
    return 128
