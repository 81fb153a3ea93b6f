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
statically-unrolled chunk loop over K.
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


def pick_block_o(O: int, persist_per_row: int, cap: int = 256) -> int:
    """Largest lane-legal O tile: a multiple of 128 dividing O (256
    preferred, 128 if the per-row persistent footprint is large or the
    caller caps it), else the full dim (always legal — Mosaic pads)."""
    for bo in (256, 128):
        if bo <= cap and O % bo == 0 and (
            bo * persist_per_row <= VMEM_BUDGET // 2
        ):
            return bo
    if O % 128 == 0:
        return 128
    return O


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

#: flash attention default q/k block edge (clamped to the padded
#: sequence extents by `flash_blocks`)
FLASH_BLOCK_Q = 128
FLASH_BLOCK_K = 128


def flash_blocks(T: int, S: int,
                 block_q: int = FLASH_BLOCK_Q,
                 block_k: int = FLASH_BLOCK_K) -> tuple:
    """The (block_q, block_k) flash_attention actually runs at for a
    [T] x [S] problem: the policy default clamped to the 16-padded
    sequence extents (short prefills run one small block per axis)."""
    return (min(block_q, round_up(T, 16)), min(block_k, round_up(S, 16)))


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


def chunk_target(block_o: int, persist_bytes: int, kh: int,
                 temp_bpe: int = 12) -> int:
    """Largest chunk whose per-chunk temporaries (temp_bpe B/element of
    dequant intermediates — decoded codes + expanded scales in f32 plus
    the bf16 weight tile — plus the one-hot sel) fit beside the
    persistent blocks in the scoped-VMEM budget."""
    for ck in (2048, 1024, 512, 256, 128):
        if ck > kh:
            continue
        temp = block_o * ck * temp_bpe + (ck // 16) * ck * 4
        if persist_bytes + temp <= VMEM_BUDGET:
            return ck
    return 128
