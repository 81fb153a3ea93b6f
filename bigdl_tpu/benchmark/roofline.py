"""Analytic bytes-moved / FLOPs model for the fused dequant matmul
family: counts computed from shapes, never a speed.

Evaluates, on any machine with no device attached, the HBM traffic and
FLOP count of

* the fused Pallas kernel at the REAL block shapes it would pick (the
  tile policy is imported from `ops/pallas/tiling.py`, the same module
  the kernels use — the model cannot drift from the implementation), and
* the XLA dequant fallback it replaces (materialize a bf16 copy of W,
  then matmul),

so a kernel's roofline share can be computed from a device trace
(bytes and FLOPs here, kernel time from the chip). The ratios are the
bandwidth-bound prediction; what the chip achieves is not measured.

This module's own code needs no jax (only `quant.qtypes` + the tile
policy); importing it still initializes the bigdl_tpu package.
"""

from __future__ import annotations

from bigdl_tpu.ops.pallas.tiling import (
    DX_ACC_BPE, chunk_target_dx, finest_split, flash_blocks,
    flash_live_blocks, pick_block_m, pick_block_m_dx, pick_block_o,
    pick_block_o_dw, round_up,
)
from bigdl_tpu.quant.qtypes import resolve_qtype

_X_BPE = 2  # activations cross as bf16 (the kernels' compute dtype)
_OUT_BPE = 2


def code_bytes_per_row(qtype: str, K: int) -> int:
    """Bytes of packed codes per output row (the `data` field alone)."""
    spec = resolve_qtype(qtype)
    if spec.storage == "packed_u8":
        return K // 2
    if spec.storage == "packed_planes":
        return K * sum(spec.planes) // 8
    return K  # int8 / fp8: one code byte per element


def weight_bytes_per_row(qtype: str, K: int) -> int:
    """Stored bytes per output row: packed codes + every scale field —
    exactly what the kernel's weight-side BlockSpecs fetch."""
    spec = resolve_qtype(qtype)
    data = code_bytes_per_row(qtype, K)
    if spec.superblock:
        nsuper = K // spec.superblock
        nsub = K // spec.block_size
        scales = nsuper * 2 + nsub  # f16 d + integer sc
        if spec.asymmetric:
            scales += nsuper * 2 + nsub  # f16 dmin + integer mn
    else:
        scales = (K // spec.block_size) * 2  # f16 d
        if spec.asymmetric:
            scales += (K // spec.block_size) * 2  # f16 m
    return data + scales


def qmatmul_cost(qtype: str, M: int, K: int, O: int) -> dict:
    """Analytic cost of the fused dequant matmul y[M,O] = x[M,K] @ W^T.

    HBM traffic follows the kernel's actual fetch pattern (qmatmul._qmm):
    grid (m, o) with o innermost — the x row tile stays resident across a
    full sweep of weight tiles (fetched once per M tile == once total),
    packed weights are re-fetched once per M tile, the output is written
    once."""
    spec = resolve_qtype(qtype)
    row_bytes = weight_bytes_per_row(qtype, K)
    w_total = O * row_bytes

    block_m = pick_block_m(M, K)
    mp = round_up(max(M, 1), block_m)
    block_o = pick_block_o(O, row_bytes,
                           row_bytes=code_bytes_per_row(qtype, K))
    grid_m = mp // block_m

    fused_bytes = w_total * grid_m + mp * K * _X_BPE + mp * O * _OUT_BPE
    # XLA fallback: read packed W + scales, write the dequantized bf16
    # copy, read it back into the matmul, plus the same x/out traffic
    xla_bytes = (w_total + 2 * K * O * 2 + M * K * _X_BPE
                 + M * O * _OUT_BPE)
    flops = 2 * M * K * O
    return {
        "qtype": qtype,
        "shape": f"m{M}xk{K}xo{O}",
        "block_m": block_m,
        "block_o": block_o,
        "grid_m": grid_m,
        "weight_bits_per_el": round(row_bytes * 8 / K, 3),
        "fused_bytes": fused_bytes,
        "xla_dequant_bytes": xla_bytes,
        "flops": flops,
        "fused_intensity": round(flops / fused_bytes, 2),
        # bandwidth-bound speedup prediction for the fused path; > 1
        # means the fused kernel moves fewer HBM bytes for the same math
        "bytes_ratio_vs_xla": round(xla_bytes / fused_bytes, 2),
    }


def _storage_planes(spec) -> tuple:
    """The packed-plane tuple of a qtype's storage — the jax-free twin
    of ops/pallas/qdecode.spec_for's planes field (this module must not
    import jax; the mapping is 3 lines and covered by the DSP003
    storage-coverage check on the real spec_for)."""
    if spec.storage == "packed_u8":
        return (4,)
    if spec.storage == "packed_planes":
        return tuple(spec.planes)
    return ()


def bwd_dx_cost(qtype: str, M: int, K: int, O: int) -> dict:
    """Analytic cost of the fused backward dx[M,K] = g[M,O] @ dq(W) at
    qbackward's REAL tiles (tiling.pick_block_m_dx / chunk_target_dx —
    the same policy the kernel resolves, so model and implementation
    cannot drift).

    Fetch pattern (qbackward._dxmm): grid (m, o) with o innermost as the
    reduction sweep — the [block_m, K] f32 accumulator stays in VMEM
    scratch across a full weight sweep, so packed weights cross HBM once
    per M tile, g and dx exactly once, and the dequantized bf16 copy of
    W never exists in HBM. The XLA remat path it replaces writes that
    copy and reads it back (2*K*O*2) every train step."""
    spec = resolve_qtype(qtype)
    row_bytes = weight_bytes_per_row(qtype, K)
    w_total = O * row_bytes

    block_m = pick_block_m_dx(M, K)
    mp = round_up(max(M, 1), block_m)
    block_o = pick_block_o(O, row_bytes, cap=256)
    grid_m = mp // block_m
    persist = (block_m * K * DX_ACC_BPE + block_o * row_bytes
               + block_m * block_o * _X_BPE)
    ck = chunk_target_dx(block_o, block_m, persist,
                         finest_split(K, _storage_planes(spec)),
                         temp_bpe=20 if spec.asymmetric else 14)

    fused_bytes = w_total * grid_m + mp * O * _X_BPE + mp * K * _OUT_BPE
    xla_bytes = (w_total + 2 * K * O * 2 + M * O * _X_BPE
                 + M * K * _OUT_BPE)
    flops = 2 * M * K * O
    return {
        "kernel": "bwd_dx", "qtype": qtype,
        "shape": f"m{M}xk{K}xo{O}",
        "block_m": block_m, "block_o": block_o,
        "chunk": ck, "grid_m": grid_m,
        "fused_bytes": fused_bytes,
        "xla_remat_bytes": xla_bytes,
        "flops": flops,
        "fused_intensity": round(flops / fused_bytes, 2),
        "bytes_ratio_vs_xla": round(xla_bytes / fused_bytes, 2),
    }


def bwd_dw_cost(M: int, K: int, O: int) -> dict:
    """Analytic cost of the fused dW[O,K] = g^T @ x tiled accumulation
    (qbackward._dwmm) at its real tiles: grid (o, m) with m innermost,
    a [block_o, K] f32 accumulator per O tile. No dequant is involved —
    x is re-fetched once per O tile (the reduction-bound shape of any
    real tiled g^T @ x), so the honest ratio vs an ideal single-pass
    einsum sits near or below 1. The row exists for train-step pricing
    (sim/cost.train_step_s) and the unfrozen/bf16-shadow hook, not as a
    bytes win."""
    block_m = pick_block_m(M, max(K, O))
    mp = round_up(max(M, 1), block_m)
    block_o = pick_block_o_dw(O, K)
    op = round_up(O, block_o)
    grid_o = op // block_o
    fused_bytes = mp * op * _X_BPE + grid_o * mp * K * _X_BPE + op * K * _OUT_BPE
    xla_bytes = M * O * _X_BPE + M * K * _X_BPE + O * K * _OUT_BPE
    flops = 2 * M * K * O
    return {
        "kernel": "bwd_dw", "shape": f"m{M}xk{K}xo{O}",
        "block_m": block_m, "block_o": block_o, "grid_o": grid_o,
        "fused_bytes": fused_bytes,
        "xla_bytes": xla_bytes,
        "flops": flops,
        "fused_intensity": round(flops / fused_bytes, 2),
        "bytes_ratio_vs_xla": round(xla_bytes / fused_bytes, 2),
    }


def backward_matrix(qtypes, Ms=(1, 32, 512, 2048), K: int = 4096,
                    O: int = 4096) -> dict:
    """The analytic backward sweep: the fused dx kernel for every
    fused format at train-step row counts, plus the qtype-independent
    dW accumulation rows. Pure host math (scripts/ci.sh gates the dx
    bytes ratio at M=512, sym_int4)."""
    out = {}
    for qt in qtypes:
        spec = resolve_qtype(qt)
        if K % (spec.superblock or spec.block_size):
            continue
        for m in Ms:
            out[f"dx_{qt}_m{m}"] = bwd_dx_cost(qt, m, K, O)
    for m in Ms:
        out[f"dw_m{m}"] = bwd_dw_cost(m, K, O)
    return out


def lora_epilogue_cost(M: int, K: int, O: int, R: int,
                       fused: bool = True) -> dict:
    """Analytic cost of the multi-tenant LoRA epilogue
    ``((x @ A_cat^T) * gate) @ B_cat^T`` added to a y[M,O] = x[M,K]
    matmul, at the dequant-GEMM's real M tiles (the epilogue rides
    inside qmatmul's grid — ops/pallas/tiling.py is the shared policy).
    ``R`` is the total adapter width: the rank bucket for one shared
    adapter, or batch * rank-bucket for the serving engine's
    concatenated per-row form.

    Fused (qmatmul_lora): the x tile is already in VMEM, so the only
    NEW traffic is the adapter operands — A_cat once per M tile, B_cat
    tiles once per M-tile sweep, the gate once. Activation HBM round
    trips: **0**.

    XLA fallback (ops/linear.lora_epilogue): two extra activation round
    trips on top of the adapter stream — x is re-read by the first
    einsum, and the [M, O] delta is written then read back by the add
    (the [M, R] xa intermediate round-trips too, a third, rank-thin
    trip the summary number ignores)."""
    block_m = pick_block_m(M, K)
    mp = round_up(max(M, 1), block_m)
    grid_m = mp // block_m
    adapter_bytes = (R * K + O * R) * _X_BPE
    gate_bytes = mp * R * _X_BPE
    flops = 2 * M * R * (K + O)
    if fused:
        bytes_ = adapter_bytes * grid_m + gate_bytes
        round_trips = 0
    else:
        bytes_ = (adapter_bytes + M * K * _X_BPE
                  + 2 * M * R * _X_BPE + 2 * M * O * _OUT_BPE)
        round_trips = 2
    return {
        "kernel": "lora_epilogue",
        "shape": f"m{M}xk{K}xo{O}xr{R}",
        "fused": fused,
        "block_m": block_m,
        "grid_m": grid_m,
        "activation_round_trips": round_trips,
        "adapter_bytes": adapter_bytes,
        "bytes": bytes_,
        "flops": flops,
    }


# ---------------------------------------------------------------------------
# attention kernels (ISSUE 13 satellite): flash prefill +
# paged/dense decode attention, fp8-KV variants. Block/tile policy is
# imported from ops/pallas/tiling.py — the same module the kernels
# resolve their shapes from — so the sim's cost model (sim/cost.py) and
# the implementation cannot drift.
# ---------------------------------------------------------------------------


def flash_prefill_cost(T: int, S: int, Hq: int, Hkv: int, D: int,
                       B: int = 1, layers: int = 1,
                       quantize_kv: bool = False,
                       q_offset: int = 0, window=None) -> dict:
    """Analytic cost of the flash prefill kernel for a [T]-token chunk
    attending an [S]-slot cache, at the REAL (block_q, block_k) the
    kernel picks (tiling.flash_blocks) and with the kernel's own causal
    block-skip predicate (tiling.flash_live_blocks).

    Fetch pattern (flash_attention._flash BlockSpecs): a q block holds
    the positions of all the query heads of ONE KV head and its index
    map ignores j, so it is fetched once per (b, kv head, i); k/v tiles
    are fetched once per live (i, j) pair and KV head (a dead step names
    the block already resident). fp8 KV halves the k/v code bytes and
    adds f32 per-(slot, head) scales."""
    kv_bpe = 1 if quantize_kv else 2
    block_q, block_k = flash_blocks(T, S, D, Hq // Hkv, kv_bpe)
    live = flash_live_blocks(T, S, block_q, block_k,
                             q_offset=q_offset, window=window)
    Tp = round_up(T, block_q)
    q_bytes = B * Hq * Tp * D * _X_BPE
    kv_tile = block_k * D * kv_bpe + (block_k * 4 if quantize_kv else 0)
    kv_bytes = B * Hkv * live * 2 * kv_tile  # k AND v
    o_bytes = B * Hq * Tp * D * _OUT_BPE
    # qk^T + av over the live blocks (the skipped blocks cost nothing —
    # the kernel's pl.when elides the whole compute body)
    flops = 4 * B * Hq * live * block_q * block_k * D
    total = layers * (q_bytes + kv_bytes + o_bytes)
    return {
        "kernel": "flash_prefill", "shape": f"t{T}xs{S}",
        "block_q": block_q, "block_k": block_k,
        "live_blocks": live, "quantize_kv": quantize_kv,
        "bytes": total, "flops": layers * flops,
        "intensity": round(layers * flops / max(total, 1), 2),
    }


def decode_attention_cost(pos, page: int, Hq: int, Hkv: int, D: int,
                          layers: int = 1, paged: bool = True,
                          quantize_kv: bool = False,
                          max_len: int = 0) -> dict:
    """Analytic cost of one batched decode-attention step over the rows'
    live KV. `pos` is the per-row written position (int or list of
    ints — the engine's cache.pos for the active slots).

    Paged (ops/pallas/paged_attention): grid (B,), and per row a loop
    over groups of its LIVE pages, one DMA of a (page, Hkv, D) k and v
    tile per live page through the block table — a page outside a row's
    live range is never fetched and an idle row runs no loop at all, so
    the traffic model counts live pages only; q goes in as the bf16 it is.
    Dense: each row streams its [max_len] cache rows (the dense decode
    path has no page table to skip dead slots by block). fp8 KV halves
    code bytes and adds the f32 per-(slot, head) scale planes."""
    rows = [pos] if isinstance(pos, int) else list(pos)
    kv_bpe = 1 if quantize_kv else 2
    if paged:
        pages = sum(-(-max(p, 1) // page) for p in rows)
        slots = pages * page
    else:
        if not max_len:
            raise ValueError("dense decode attention needs max_len")
        slots = len(rows) * max_len
        pages = 0
    slot_bytes = Hkv * D * kv_bpe + (Hkv * 4 if quantize_kv else 0)
    kv_bytes = 2 * slots * slot_bytes  # k AND v
    q_bytes = len(rows) * Hq * D * _X_BPE
    o_bytes = len(rows) * Hq * D * _OUT_BPE
    flops = 4 * sum(max(p, 1) for p in rows) * Hq * D
    total = layers * (kv_bytes + q_bytes + o_bytes)
    return {
        "kernel": "paged_decode" if paged else "dense_decode",
        "batch": len(rows), "page": page if paged else None,
        "live_pages": pages, "kv_slots_touched": slots,
        "quantize_kv": quantize_kv,
        "bytes": total, "flops": layers * flops,
        "intensity": round(layers * flops / max(total, 1), 4),
    }


def attention_matrix(Ts=(128, 512, 2048), S_extra: int = 0,
                     Hq: int = 32, Hkv: int = 8, D: int = 128,
                     page: int = 64) -> dict:
    """The analytic attention sweep: flash prefill chunks and batched
    paged decode at llama3-class GQA shapes, bf16 and fp8 KV — pure
    host math."""
    out = {}
    for T in Ts:
        for qkv in (False, True):
            c = flash_prefill_cost(T, T + S_extra, Hq, Hkv, D,
                                   quantize_kv=qkv)
            out[f"flash_t{T}{'_fp8' if qkv else ''}"] = c
    for B in (1, 8, 32):
        for qkv in (False, True):
            c = decode_attention_cost([1024] * B, page, Hq, Hkv, D,
                                      quantize_kv=qkv)
            out[f"decode_b{B}{'_fp8' if qkv else ''}"] = c
    return out


def gemm_matrix(qtypes, Ms=(1, 128, 512, 2048), K: int = 4096,
                O: int = 4096) -> dict:
    """The analytic GEMM sweep: every fused format at decode and
    prefill shapes. Pure host math."""
    out = {}
    for qt in qtypes:
        spec = resolve_qtype(qt)
        if K % (spec.superblock or spec.block_size):
            continue
        for m in Ms:
            c = qmatmul_cost(qt, m, K, O)
            out[f"{qt}_m{m}"] = c
    return out


# ---------------------------------------------------------------------------
# quantized ICI collectives (parallel/qcollectives.py): bytes on the
# interconnect per algorithm x payload format. `ici_gbps` is the
# calibration knob twin of sim/cost.py's `hbm_gbps` — the achievable
# per-chip ring bandwidth the next live-TPU window tunes against
# measured hop times.
# ---------------------------------------------------------------------------

_SCALE_BPE = 2  # f16 per-block absmax scales (the codec's sidecar)
_COMM_BLOCK = 256  # qcollectives.DEFAULT_BLOCK (kept in sync by test)


def collective_payload_bytes(n_elems: int, comm_qtype: str = "none",
                             block_size: int = _COMM_BLOCK) -> int:
    """Wire bytes of one encoded payload of `n_elems` fp32 values:
    fp32 as-is for "none", 1 byte/elem + one f16 scale per block for
    the int8 and fp8_e4m3 codecs (identical wire size — fp8 trades
    precision for range, not bytes)."""
    if comm_qtype == "none":
        return n_elems * 4
    if comm_qtype in ("int8", "fp8_e4m3"):
        blocks = -(-n_elems // block_size)
        return n_elems + blocks * _SCALE_BPE
    raise ValueError(
        f"unknown comm_qtype {comm_qtype!r}; expected none|int8|fp8_e4m3"
    )


def all_reduce_cost(n_elems: int, axis_size: int,
                    comm_qtype: str = "none",
                    block_size: int = _COMM_BLOCK,
                    ici_gbps=None) -> dict:
    """Ring all-reduce of `n_elems` over an `axis_size` ring:
    reduce-scatter (n-1 hops) + all-gather (n-1 hops), each hop moving
    one 1/n chunk — per-device ICI bytes = 2*(n-1)/n * payload. The
    quantized ring sends codes+scales on every hop (the error-feedback
    residual stays device-local, costing nothing on the wire)."""
    n = max(int(axis_size), 1)
    payload = collective_payload_bytes(n_elems, comm_qtype, block_size)
    fp32 = collective_payload_bytes(n_elems, "none")
    ici = 2 * (n - 1) * payload / n
    out = {
        "algorithm": "ring_all_reduce", "qtype": comm_qtype,
        "axis_size": n, "elems": n_elems,
        "payload_bytes": payload,
        "ici_bytes_per_device": round(ici, 1),
        "bytes_ratio_vs_fp32": round(fp32 / max(payload, 1), 3),
    }
    if ici_gbps:
        out["ring_time_s"] = ici / (float(ici_gbps) * 1e9)
    return out


def all_gather_cost(n_elems_local: int, axis_size: int,
                    comm_qtype: str = "none",
                    block_size: int = _COMM_BLOCK,
                    ici_gbps=None) -> dict:
    """Ring all-gather of an `n_elems_local` shard over `axis_size`
    ranks: each shard's payload is encoded ONCE and forwarded n-1 hops
    (per-device ICI bytes = (n-1) * payload) — PP/multihost weight and
    KV-page distribution (sharding.gather_array)."""
    n = max(int(axis_size), 1)
    payload = collective_payload_bytes(n_elems_local, comm_qtype,
                                       block_size)
    fp32 = collective_payload_bytes(n_elems_local, "none")
    ici = (n - 1) * payload
    out = {
        "algorithm": "ring_all_gather", "qtype": comm_qtype,
        "axis_size": n, "elems_local": n_elems_local,
        "payload_bytes": payload,
        "ici_bytes_per_device": ici,
        "bytes_ratio_vs_fp32": round(fp32 / max(payload, 1), 3),
    }
    if ici_gbps:
        out["ring_time_s"] = ici / (float(ici_gbps) * 1e9)
    return out


def collective_matrix(hidden: int = 4096, layers: int = 32, tp: int = 4,
                      ici_gbps: float = 45.0, Ms=(1, 8, 32)) -> dict:
    """The analytic collective sweep at llama2-7b decode shapes: the
    per-layer TP all-reduce (o-proj + down-proj epilogues, M rows x
    hidden) at fp32 vs int8 vs fp8_e4m3, with the modeled per-decode-
    step ring time at `ici_gbps`. Pure host math — byte counts, not a
    measured time (ISSUE 17)."""
    out = {}
    for m in Ms:
        for qt in ("none", "int8", "fp8_e4m3"):
            c = all_reduce_cost(m * hidden, tp, qt, ici_gbps=ici_gbps)
            # 2 row-parallel epilogues per layer (wo, w_down)
            c["per_step_s"] = 2 * layers * c["ring_time_s"]
            tag = "fp32" if qt == "none" else qt
            out[f"allreduce_tp{tp}_m{m}_{tag}"] = c
    return out
