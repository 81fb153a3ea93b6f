"""Per-step latency model for the serving simulator, fed by
`benchmark/roofline.py`'s analytic bytes/FLOPs at the kernels' real
tile shapes (docs/benchmarking.md).

The modeled hardware/model pair is INDEPENDENT of the tiny model that
produces token dynamics on CPU: the engine executes tiny-llama to keep
every cache/scheduler path real, while each jitted call's duration is
priced as if it were `config` (default llama2-7b) at `qtype` on an
HBM with `hbm_gbps` — a model input (the chip's published peak by
default), not a measurement.

Pricing follows the roofline: a phase costs
``max(bytes / HBM_BW, flops / peak)`` plus a fixed per-dispatch host
overhead. Decode is bytes-bound (weight streaming + KV touched ∝ batch
occupancy and positions); prefill cost is ∝ chunk tokens through the
same qmatmul model at M=chunk plus the flash-prefill attention cost at
the kernel's real (block_q, block_k).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from bigdl_tpu.benchmark.roofline import (
    all_reduce_cost, bwd_dw_cost, bwd_dx_cost, decode_attention_cost,
    flash_prefill_cost, lora_epilogue_cost, qmatmul_cost,
)
from bigdl_tpu.models.config import ModelConfig
from bigdl_tpu.quant.qtypes import resolve_qtype


@dataclasses.dataclass
class CostModel:
    config: ModelConfig
    qtype: Optional[str] = "sym_int4"  # None = dense bf16 weights
    #: HBM GB/s of the modeled chip (docs/benchmarking.md); the default
    #: is the v5e's published peak. What the kernels achieve on the
    #: chip is not measured.
    hbm_gbps: float = 819.0
    #: bf16 MXU peak — the compute-bound floor of every phase
    peak_tflops: float = 197.0
    #: host dispatch + engine bookkeeping per jitted call (the sim's
    #: step() host work happens between modeled device calls)
    step_overhead_s: float = 5e-4
    #: host<->HBM link for preemption swap traffic (PCIe/ICI class)
    swap_gbps: float = 32.0
    #: modeled KV page (the engine's real page_size is passed per call;
    #: this is only the default for standalone queries)
    page_size: int = 64
    quantize_kv: bool = False
    label: str = ""
    #: tensor-parallel degree of the MODELED deployment. tp > 1 adds the
    #: per-layer TP all-reduce epilogues (wo + w_down, M x hidden each)
    #: over the ICI ring to every decode step / prefill chunk. The charge
    #: is purely ADDITIVE — compute is deliberately NOT divided by tp, so
    #: this knob prices the communication OVERHEAD of going multi-chip
    #: (decode_step_s rises with tp at fp32; quantized comms claw it
    #: back), not the compute speedup. tp=1 (default) charges nothing and
    #: keeps every banked report byte-identical.
    tp: int = 1
    #: achievable per-chip ICI GB/s — the collective calibration knob
    #: twin of hbm_gbps (benchmark/roofline.py collective cost model);
    #: default is a v5e-class 45 GB/s per link direction
    ici_gbps: float = 45.0
    #: wire format of the TP all-reduce ("none"|"int8"|"fp8_e4m3") —
    #: parallel/qcollectives.py's comm_qtype knob, priced here
    comm_qtype: str = "none"
    #: whether the LoRA epilogue is priced as the fused Pallas writeback
    #: (qmatmul_lora: zero activation HBM round trips) or the XLA einsum
    #: fallback (two round trips — re-read x, round-trip the delta).
    #: True matches the serving engine's dispatch on eligible shapes;
    #: False reproduces the pre-fusion path for before/after comparisons
    #: (docs/benchmarking.md §3 banks the seed-0 pair)
    fused_lora: bool = True
    #: whether the train-step backward is priced at the fused Pallas dx
    #: kernel (ops/pallas/qbackward.py: packed weights re-decoded
    #: per-chunk in VMEM) or the XLA remat path (a full bf16 dequant of
    #: W written to + read back from HBM per projection per step) —
    #: train/qlora.make_train_step's fused_backward knob, priced here so
    #: the supervisor path is sim-gateable like serving
    fused_backward: bool = True

    # -- pieces --------------------------------------------------------------

    def _supported_qtype(self) -> Optional[str]:
        """The matmul-model qtype, or None when the modeled config's
        contractions don't align to the format's scale blocks (tiny
        configs) — then weights price as dense bf16."""
        if self.qtype is None:
            return None
        spec = resolve_qtype(self.qtype)
        blk = spec.superblock or spec.block_size
        cfg = self.config
        for k in (cfg.hidden_size, cfg.q_dim, cfg.intermediate_size):
            if k % blk:
                return None
        return self.qtype

    def linear_cost(self, M: int) -> dict:
        """bytes/flops of every projection of one full forward at M
        rows: L x (merged qkv, o, gate_up, down) + the lm_head."""
        cfg = self.config
        shapes = [
            (cfg.hidden_size, cfg.q_dim + 2 * cfg.kv_dim),  # qkv
            (cfg.q_dim, cfg.hidden_size),                   # o
            (cfg.hidden_size, 2 * cfg.intermediate_size),   # gate_up
            (cfg.intermediate_size, cfg.hidden_size),       # down
        ]
        qt = self._supported_qtype()
        total_b = total_f = 0
        for K, O in shapes:
            if qt is not None:
                c = qmatmul_cost(qt, M, K, O)
                total_b += c["fused_bytes"]
                total_f += c["flops"]
            else:
                total_b += K * O * 2 + M * (K + O) * 2
                total_f += 2 * M * K * O
        total_b *= cfg.num_hidden_layers
        total_f *= cfg.num_hidden_layers
        # lm_head stays bf16 (the stack's convention: output head is
        # not quantized)
        K, O = cfg.hidden_size, cfg.vocab_size
        total_b += K * O * 2 + M * (K + O) * 2
        total_f += 2 * M * K * O
        return {"bytes": total_b, "flops": total_f}

    def _seconds(self, nbytes: float, flops: float) -> float:
        bw = self.hbm_gbps * 1e9
        peak = self.peak_tflops * 1e12
        return max(nbytes / bw, flops / peak)

    def _lora_target_dims(self, targets=None):
        """(in, out) per target of the adapter's target set (None = all
        seven) — the per-layer shapes of its A [r, in] / B [out, r]
        pairs."""
        cfg = self.config
        H, I = cfg.hidden_size, cfg.intermediate_size
        dims = {
            "wq": (H, cfg.q_dim),
            "wk": (H, cfg.kv_dim),
            "wv": (H, cfg.kv_dim),
            "wo": (cfg.q_dim, H),
            "w_gate": (H, I),
            "w_up": (H, I),
            "w_down": (I, H),
        }
        names = dims.keys() if targets is None else targets
        return [dims[t] for t in names if t in dims]

    def lora_cost(self, ranks, M: int = 1, fused=None) -> dict:
        """The multi-tenant LoRA epilogue's extra traffic per forward,
        priced by `roofline.lora_epilogue_cost` per target per layer at
        the dequant-GEMM's real M tiles. `ranks` = one entry per
        adapter-carrying row — a bare rank (priced over all seven
        targets) or a (rank, targets) pair priced over the adapter's
        ACTUAL target set; adapter-less rows cost nothing (their
        zero-padded rows still move with the batch's bucket, but the
        dominant term — distinct adapters' weights — is what's priced).

        ``fused`` (default: the model's `fused_lora` field) switches
        between the fused-writeback pricing (adapter stream only, zero
        activation round trips) and the XLA fallback's two extra
        activation HBM round trips per target — the ISSUE 18 perf delta
        the adapter-zipf before/after banks."""
        if fused is None:
            fused = self.fused_lora
        nbytes = flops = 0
        for r in ranks:
            rank, targets = r if isinstance(r, tuple) else (r, None)
            if not rank:
                continue
            for K, O in self._lora_target_dims(targets):
                c = lora_epilogue_cost(M, K, O, rank, fused=fused)
                nbytes += c["bytes"]
                flops += c["flops"]
        L = self.config.num_hidden_layers
        return {"bytes": nbytes * L, "flops": flops * L}

    def tp_comm_s(self, M: int) -> float:
        """Seconds of per-forward TP collective traffic at M rows: two
        ring all-reduces per layer (the wo and w_down row-parallel
        epilogues parallel/qcollectives.py makes explicit), each over
        [M, hidden] at `comm_qtype`'s wire format, serialized on the
        ICI ring at `ici_gbps`. Zero at tp=1."""
        if self.tp <= 1 or M <= 0:
            return 0.0
        c = all_reduce_cost(M * self.config.hidden_size, self.tp,
                            self.comm_qtype, ici_gbps=self.ici_gbps)
        return 2 * self.config.num_hidden_layers * c["ring_time_s"]

    def kv_token_bytes(self) -> int:
        """HBM bytes one token's K+V occupies across all layers."""
        cfg = self.config
        bpe = 1 if self.quantize_kv else 2
        scale = 4 if self.quantize_kv else 0
        return 2 * cfg.num_hidden_layers * cfg.num_key_value_heads * (
            cfg.head_dim_ * bpe + scale
        )

    # -- phases (what the driver's wrappers charge) --------------------------

    def decode_step_s(self, positions, page: int,
                      paged: bool = True, max_len: int = 0,
                      adapter_ranks=()) -> float:
        """One batched decode step: M=occupancy through every
        projection + the decode-attention KV sweep at the rows' actual
        positions. `adapter_ranks` (one LoRA rank per adapter-carrying
        row) adds the multi-tenant epilogue's weight stream + einsum
        FLOPs (serving/adapters.py)."""
        rows = list(positions)
        if not rows:
            return self.step_overhead_s
        cfg = self.config
        lin = self.linear_cost(len(rows))
        att = decode_attention_cost(
            rows, page, cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim_, layers=cfg.num_hidden_layers, paged=paged,
            quantize_kv=self.quantize_kv, max_len=max_len,
        )
        lo = self.lora_cost(adapter_ranks, M=1)
        return self._seconds(lin["bytes"] + att["bytes"] + lo["bytes"],
                             lin["flops"] + att["flops"] + lo["flops"]) \
            + self.tp_comm_s(len(rows)) + self.step_overhead_s

    def spec_round_s(self, positions, page: int, draft_k: int,
                     paged: bool = True, max_len: int = 0,
                     adapter_ranks=()) -> float:
        """One speculative round (serving/engine.py `_spec_decode`):
        `draft_k` sequential per-token draft steps at advancing
        positions, then ONE batched verify forward over each row's
        draft_k+1 candidate tokens through the target. Monotonically
        increasing in draft_k (each extra draft adds a full decode-step
        charge plus a wider verify).

        Approximation (documented in docs/benchmarking.md): the draft
        model is priced at this CostModel's own qtype/config — the
        engine's self-draft shares the target's architecture, and the
        sym_int4 default IS the self-draft's format; a separately-sized
        draft model would need its own CostModel."""
        rows = list(positions)
        if not rows:
            return self.step_overhead_s
        if draft_k < 1:
            raise ValueError(f"draft_k must be >= 1, got {draft_k}")
        cfg = self.config
        total = 0.0
        for i in range(draft_k):
            total += self.decode_step_s(
                [p + i for p in rows], page, paged=paged,
                max_len=max_len, adapter_ranks=adapter_ranks,
            )
        # verify: M = rows * (K+1) candidate tokens through every
        # projection; each candidate's attention sweeps its row's KV at
        # the post-draft depth (the verify writes K drafts first, so
        # every query sees the full speculated context)
        M = len(rows) * (draft_k + 1)
        lin = self.linear_cost(M)
        vrows = [p + draft_k for p in rows for _ in range(draft_k + 1)]
        att = decode_attention_cost(
            vrows, page, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim_,
            layers=cfg.num_hidden_layers, paged=paged,
            quantize_kv=self.quantize_kv, max_len=max_len,
        )
        lo = self.lora_cost(adapter_ranks, M=draft_k + 1)
        total += self._seconds(
            lin["bytes"] + att["bytes"] + lo["bytes"],
            lin["flops"] + att["flops"] + lo["flops"],
        ) + self.tp_comm_s(M) + self.step_overhead_s
        return total

    def prefill_s(self, chunk_tokens: int, prior_tokens: int = 0,
                  adapter_rank=0) -> float:
        """A prefill chunk of `chunk_tokens` attending `prior_tokens`
        of existing context (prefix-cache hits shrink the chunk, which
        is exactly how the cache saves simulated time). `adapter_rank`
        (a rank or a (rank, targets) pair) prices the request's LoRA
        epilogue over the chunk."""
        cfg = self.config
        lin = self.linear_cost(chunk_tokens)
        att = flash_prefill_cost(
            chunk_tokens, prior_tokens + chunk_tokens,
            cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim_, layers=cfg.num_hidden_layers,
            quantize_kv=self.quantize_kv, q_offset=prior_tokens,
        )
        lo = self.lora_cost([adapter_rank], M=chunk_tokens)
        return self._seconds(lin["bytes"] + att["bytes"] + lo["bytes"],
                             lin["flops"] + att["flops"] + lo["flops"]) \
            + self.tp_comm_s(chunk_tokens) + self.step_overhead_s

    def suggest_prefill_chunk(self, occupancy: int = 4,
                              context_tokens: int = 1024,
                              decode_steps: float = 4.0,
                              page: Optional[int] = None) -> int:
        """The roofline-derived `prefill_chunk_tokens` default
        (docs/serving.md §6): the largest page-multiple chunk whose
        prefill charge stays within ~`decode_steps` decode steps of a
        batch at `occupancy` rows around `context_tokens` of context —
        so an arriving long prompt stalls the running batch's streams
        by a few tokens' worth of time per chunk, never by the whole
        prompt."""
        page = page or self.page_size
        rows = [context_tokens] * max(occupancy, 1)
        target = decode_steps * self.decode_step_s(rows, page)
        chunk = page
        while (self.prefill_s(chunk * 2, prior_tokens=context_tokens)
               <= target):
            chunk *= 2
        return chunk

    def train_step_s(self, tokens: int, adapter_rank: int = 8) -> float:
        """Price one QLoRA train step over a `tokens`-row batch —
        forward + backward — so the supervisor path is sim-gateable
        like serving (train/qlora.make_train_step is the real thing).

        Forward: the serving prefill charge (fused dequant GEMMs +
        flash attention + the LoRA epilogue). Backward, per projection:
        the dx term at `roofline.bwd_dx_cost`'s real tile shapes —
        fused (qbackward kernel) or the XLA remat that writes a bf16
        copy of W to HBM and reads it back, per the `fused_backward`
        field; dense (unquantized) configs charge dx plus the fused dW
        accumulation instead. Flash backward is priced at 2x the
        forward attention bytes and 2.5x its FLOPs (the dq and dkv
        passes each re-sweep KV, and the kernel recomputes the
        probabilities from the saved LSE rather than loading a [T, S]
        matrix); adapter grads (da/db) double the LoRA epilogue stream.
        The lm_head (dense bf16 by convention) charges a same-shape dx."""
        cfg = self.config
        M = int(tokens)
        if M <= 0:
            return self.step_overhead_s
        lin = self.linear_cost(M)
        att = flash_prefill_cost(
            M, M, cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim_, layers=cfg.num_hidden_layers,
            quantize_kv=False,
        )
        lo = self.lora_cost([adapter_rank], M=M)

        qt = self._supported_qtype()
        shapes = [
            (cfg.hidden_size, cfg.q_dim + 2 * cfg.kv_dim),
            (cfg.q_dim, cfg.hidden_size),
            (cfg.hidden_size, 2 * cfg.intermediate_size),
            (cfg.intermediate_size, cfg.hidden_size),
        ]
        bwd_b = bwd_f = 0
        for K, O in shapes:
            if qt is not None:  # frozen low-bit base: dx only
                c = bwd_dx_cost(qt, M, K, O)
                bwd_b += (c["fused_bytes"] if self.fused_backward
                          else c["xla_remat_bytes"])
                bwd_f += c["flops"]
            else:  # dense trainable weights: dx + the dW accumulation
                dw = bwd_dw_cost(M, K, O)
                bwd_b += K * O * 2 + M * (K + O) * 2 + dw["fused_bytes"]
                bwd_f += 2 * M * K * O + dw["flops"]
        bwd_b *= cfg.num_hidden_layers
        bwd_f *= cfg.num_hidden_layers
        K, O = cfg.hidden_size, cfg.vocab_size  # lm_head dx, dense bf16
        bwd_b += K * O * 2 + M * (K + O) * 2
        bwd_f += 2 * M * K * O
        bwd_b += 2 * att["bytes"]
        bwd_f += int(2.5 * att["flops"])
        bwd_b += 2 * lo["bytes"]
        bwd_f += 2 * lo["flops"]

        total_b = lin["bytes"] + att["bytes"] + lo["bytes"] + bwd_b
        total_f = lin["flops"] + att["flops"] + lo["flops"] + bwd_f
        return (self._seconds(total_b, total_f)
                + 2 * self.tp_comm_s(M) + self.step_overhead_s)

    def kv_copy_s(self, tokens: int) -> float:
        """HBM->HBM KV move (prefill-insert, sub-page prefix copy)."""
        nbytes = 2 * tokens * self.kv_token_bytes()  # read + write
        return nbytes / (self.hbm_gbps * 1e9)

    def swap_s(self, tokens: int) -> float:
        """Preemption swap round trip (out at preempt + in at resume,
        charged together at resume) over the host link."""
        nbytes = 2 * tokens * self.kv_token_bytes()
        return nbytes / (self.swap_gbps * 1e9)

    def describe(self) -> dict:
        return {
            "model": self.label or self.config.model_type,
            "hidden": self.config.hidden_size,
            "layers": self.config.num_hidden_layers,
            "qtype": self.qtype,
            "effective_qtype": self._supported_qtype(),
            "quantize_kv": self.quantize_kv,
            "hbm_gbps": self.hbm_gbps,
            "peak_tflops": self.peak_tflops,
            "step_overhead_s": self.step_overhead_s,
            "swap_gbps": self.swap_gbps,
            "tp": self.tp,
            "ici_gbps": self.ici_gbps,
            "comm_qtype": self.comm_qtype,
            "fused_lora": self.fused_lora,
            "fused_backward": self.fused_backward,
        }
