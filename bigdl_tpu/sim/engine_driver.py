"""Discrete-event driver: the REAL `serving/engine.py` under a
`SimClock` and a roofline cost model (docs/benchmarking.md).

What is real: the scheduler, admission bounds, queue/request deadlines,
preemption + host-RAM swap, the paged prefix cache (full-page and
sub-page sharing), fault injection, finish-reason accounting, /metrics
histograms and the tracer — every host-side code path a production
engine runs. What is fake: **time** (the engine's injectable ``clock=``
reads a `SimClock` that only the event loop advances) and **per-call
latency** (each jitted model call still executes — a tiny CPU model
provides token/cache dynamics — but its simulated duration comes from
`sim/cost.py`, charged by wrappers installed over the engine's jitted
entry points). The result: engine-level TTFT/p99/shed/preemption
numbers with zero devices, byte-identical across runs of the same
seeded trace.

Event loop: time advances only at discrete events — trace arrivals,
modeled phase completions (decode step, prefill chunk, KV copy, swap),
injected ``slow_step`` stalls, and a small host-step epsilon for
engine iterations that dispatch no device work (so queue sweeps and
deadline reaps always make progress).
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Optional

import numpy as np

from bigdl_tpu.serving.metrics import Histogram
from bigdl_tpu.sim.clock import SimClock
from bigdl_tpu.sim.cost import CostModel
from bigdl_tpu.sim.traces import Trace, named_trace

REPORT_FORMAT = "bigdl-tpu-sim-report"
REPORT_VERSION = 1


class RecordingHistogram(Histogram):
    """The engine's Histogram plus the raw sample list, so the report
    computes EXACT percentiles while /metrics renders the same
    observations through the same buckets — the fidelity tests compare
    the two views of one stream."""

    def __init__(self, buckets):
        super().__init__(buckets=buckets)
        self.samples: list = []

    def observe(self, x: float) -> None:
        self.samples.append(float(x))
        super().observe(x)


def _summary(samples: list) -> dict:
    """Deterministic percentile summary (nearest-rank on the sorted
    sample list; no interpolation, no float-order sensitivity)."""
    if not samples:
        return {"n": 0}
    s = sorted(samples)
    n = len(s)

    def pct(q: float) -> float:
        return round(s[min(max(int(np.ceil(q * n)) - 1, 0), n - 1)], 6)

    return {
        "n": n, "mean": round(float(np.sum(s)) / n, 6),
        "p50": pct(0.50), "p90": pct(0.90), "p99": pct(0.99),
        "max": round(s[-1], 6),
    }


_MODEL_CACHE: dict = {}


def tiny_model(qtype: str = "sym_int4", seed: int = 7):
    """The CPU token-dynamics model (tiny-llama): shared per process —
    its compiled programs are the dominant sim start-up cost."""
    key = (qtype, seed)
    if key not in _MODEL_CACHE:
        import jax

        from bigdl_tpu import optimize_model
        from bigdl_tpu.api import TpuModel
        from bigdl_tpu.models import llama
        from bigdl_tpu.models.config import PRESETS

        cfg = PRESETS["tiny-llama"]
        params = optimize_model(
            llama.init_params(cfg, jax.random.PRNGKey(seed)), cfg, qtype
        )
        _MODEL_CACHE[key] = TpuModel(cfg, params, qtype)
    return _MODEL_CACHE[key]


def default_cost_model(hbm_gbps: Optional[float] = None,
                       quantize_kv: bool = False,
                       ici_gbps: Optional[float] = None,
                       tp: Optional[int] = None,
                       comm_qtype: Optional[str] = None) -> CostModel:
    """The modeled target: llama2-7b sym_int4 on a v5e-class HBM (the
    BASELINE.json headline pair). `hbm_gbps` is the calibration knob;
    `ici_gbps`/`tp`/`comm_qtype` are its collective-side twins (simserve
    --ici-gbps): tp > 1 prices the per-layer TP all-reduce into every
    step, at fp32 or quantized wire format."""
    from bigdl_tpu.models.config import PRESETS

    kw: dict = {"label": "llama2-7b"}
    if hbm_gbps is not None:
        kw["hbm_gbps"] = float(hbm_gbps)
    if ici_gbps is not None:
        kw["ici_gbps"] = float(ici_gbps)
    if tp is not None:
        kw["tp"] = int(tp)
    if comm_qtype is not None:
        kw["comm_qtype"] = comm_qtype
    return CostModel(config=PRESETS["llama2-7b"], qtype="sym_int4",
                     quantize_kv=quantize_kv, **kw)


@dataclasses.dataclass
class SimConfig:
    """Engine shape for a simulated deployment (tiny-llama scaled:
    max_len 128 is the preset's position ceiling)."""

    n_slots: int = 4
    max_len: int = 128
    paged: bool = True
    page_size: int = 16
    n_pages: Optional[int] = None  # None = full coverage (no pressure)
    max_queue: Optional[int] = None
    queue_deadline_s: Optional[float] = None
    deadline_s: Optional[float] = None
    preemption: bool = True
    # chunked prefill (docs/serving.md §6): None = monolithic
    prefill_chunk_tokens: Optional[int] = None
    # multi-tenant LoRA (serving/adapters.py §7): when the trace's
    # arrivals carry adapter ids, the driver mints one synthetic
    # rank-4 adapter artifact per tenant and serves through a real
    # AdapterRegistry whose budget holds this many adapters (None =
    # unbounded — no eviction churn)
    adapter_budget: Optional[int] = None
    # in-engine speculative decoding (serving/engine.py §spec): the
    # engine runs REAL draft+verify rounds on the tiny model (which
    # must be dense — bf16/fp16 — for the sym_int4 self-draft) while
    # cost.spec_round_s prices each round as draft_k draft steps + one
    # batched verify. Composes with adapter traces (base draft,
    # adapter-applied verify); chunked prefill the engine still refuses.
    speculative: bool = False
    draft_k: int = 4
    seed: int = 0


class SimDriver:
    """One simulation run: a Trace through a fresh engine."""

    def __init__(self, trace: Trace, model=None,
                 sim: Optional[SimConfig] = None,
                 cost: Optional[CostModel] = None,
                 faults: Optional[Any] = None,
                 tracer: Optional[Any] = None,
                 host_step_s: float = 5e-5,
                 max_steps: int = 200_000):
        from bigdl_tpu.serving.engine import InferenceEngine

        self.trace = trace
        self.sim = sim or SimConfig()
        self.cost = cost or default_cost_model()
        self.clock = SimClock()
        self.host_step_s = host_step_s
        self.max_steps = max_steps
        s = self.sim
        if model is not None:
            self.model = model
        elif s.speculative:
            # the self-draft needs a dense target (api.self_draft_params
            # re-quantizes to sym_int4); token dynamics stay tiny-llama
            self.model = tiny_model("bf16")
        else:
            self.model = tiny_model()
        self._adapter_dir = None
        self.adapters = self._make_adapters()
        self.engine = InferenceEngine(
            self.model, n_slots=s.n_slots, max_len=s.max_len,
            paged=s.paged, page_size=s.page_size, n_pages=s.n_pages,
            max_queue=s.max_queue, queue_deadline_s=s.queue_deadline_s,
            deadline_s=s.deadline_s, preemption=s.preemption,
            prefill_chunk_tokens=s.prefill_chunk_tokens,
            seed=s.seed, faults=faults, tracer=tracer, clock=self.clock,
            adapters=self.adapters,
            speculative=s.speculative, draft_k=s.draft_k,
        )
        self._install_recorders()
        self._install_cost_wrappers()
        if faults is not None:
            self._wrap_faults(faults)

    # -- multi-tenant adapters (serving/adapters.py §7) ----------------------

    def _make_adapters(self):
        """When the trace's arrivals name adapters, mint one synthetic
        rank-4 LoRA artifact per tenant (seeded, B=0 identity init —
        token dynamics stay those of the tiny model while the engine
        runs the REAL batched-epilogue decode program and the cost
        model prices its extra bytes/FLOPs) and serve through a real
        AdapterRegistry: verify-on-load, LRU, refcounts, and — under
        `SimConfig.adapter_budget` — genuine eviction/reload churn, on
        the same SimClock as everything else."""
        names = sorted({a.adapter for a in self.trace.arrivals
                        if a.adapter})
        if not names:
            return None
        import os
        import tempfile

        import jax

        from bigdl_tpu.serving.adapters import (
            AdapterRegistry, lora_nbytes, save_adapter,
        )
        from bigdl_tpu.train.qlora import init_lora

        self._adapter_dir = tempfile.TemporaryDirectory(
            prefix="bigdl-tpu-sim-adapters-"
        )
        cfg = self.model.config
        nbytes = 0
        for i, name in enumerate(names):
            lora = init_lora(
                cfg, jax.random.PRNGKey(self.sim.seed * 1009 + i),
                rank=4, alpha=8.0, targets=("wq", "wv"),
            )
            nbytes = lora_nbytes(lora)
            save_adapter(
                os.path.join(self._adapter_dir.name, f"{name}.npz"), lora
            )
        budget = (None if self.sim.adapter_budget is None
                  else self.sim.adapter_budget * nbytes)
        return AdapterRegistry(dir=self._adapter_dir.name,
                               budget_bytes=budget, clock=self.clock)

    def _active_adapter_ranks(self) -> list:
        """(rank, targets) per ACTIVE adapter-carrying slot — the
        decode-step epilogue cost's input, priced over each adapter's
        ACTUAL target set (a wq/wv-only adapter must not charge all
        seven projections)."""
        eng = self.engine
        out = []
        for i in np.nonzero(eng.active)[0]:
            e = eng._slot_adapter[int(i)]
            if e is not None:
                out.append((e.rank, e.targets))
        return out

    # -- instrumentation ----------------------------------------------------

    def _install_recorders(self) -> None:
        eng = self.engine
        for name in ("ttft", "itl", "queue_wait", "prefill_seconds",
                     "decode_step_seconds", "resume_wait"):
            h = getattr(eng, name)
            setattr(eng, name, RecordingHistogram(h.buckets))

    def _active_positions(self) -> list:
        """Written tokens per ACTIVE slot — the decode-attention cost's
        per-row context. Paged keeps a host mirror; dense is estimated
        from request progress (cache.pos is donated away mid-step)."""
        eng = self.engine
        out = []
        for i in np.nonzero(eng.active)[0]:
            s = eng._slots[int(i)]
            if eng.paged:
                out.append(int(eng.pages.pos[int(i)]))
            elif s.req is not None:
                out.append(len(s.req.prompt) + len(s.req.out_tokens))
        return out

    def _install_cost_wrappers(self) -> None:
        """Replace each jitted engine entry point with itself + a
        simulated-latency charge. The charge lands INSIDE the engine's
        own t0/t1 clock reads, so decode_step_seconds / prefill_seconds
        / TTFT all measure modeled device time, not host wall time."""
        eng, cost, clock = self.engine, self.cost, self.clock
        page = self.sim.page_size

        decode0 = eng._decode

        def decode(*a, **k):
            rows = self._active_positions()
            ranks = self._active_adapter_ranks()
            out = decode0(*a, **k)
            clock.advance(cost.decode_step_s(
                rows, page, paged=eng.paged, max_len=eng.max_len,
                adapter_ranks=ranks))
            return out

        eng._decode = decode

        prefill0 = eng._prefill

        def prefill(*a, **k):
            out = prefill0(*a, **k)
            chunk = int(a[1].shape[1])
            self._last_prefill_tokens = chunk
            clock.advance(cost.prefill_s(
                chunk, prior_tokens=0,
                adapter_rank=(eng._last_prefill_rank,
                              eng._last_prefill_targets)))
            return out

        eng._prefill = prefill
        self._last_prefill_tokens = 0

        insert0 = eng._insert

        def insert(*a, **k):
            out = insert0(*a, **k)
            clock.advance(cost.kv_copy_s(self._last_prefill_tokens))
            return out

        eng._insert = insert

        paged_prefill0 = eng._paged_prefill

        def paged_prefill(*a, **k):
            out = paged_prefill0(*a, **k)
            chunk = int(a[4].shape[1])  # bucketed tail tokens
            prior = int(np.asarray(a[3])[0])  # prefix-cache coverage
            clock.advance(cost.prefill_s(
                chunk, prior_tokens=prior,
                adapter_rank=(eng._last_prefill_rank,
                              eng._last_prefill_targets)))
            return out

        eng._paged_prefill = paged_prefill

        if getattr(eng, "_copy_page", None) is not None:  # a paged engine
            copy_page0 = eng._copy_page

            def copy_page(*a, **k):
                out = copy_page0(*a, **k)
                clock.advance(cost.kv_copy_s(page))
                return out

            eng._copy_page = copy_page

        # speculative rounds: the engine's real draft+verify program
        # runs on the tiny model; the charge is K draft steps + one
        # batched verify at the modeled config (cost.spec_round_s)
        if getattr(eng, "_spec_decode", None) is not None:
            spec0 = eng._spec_decode

            def spec_decode(k_draft, *a, **kw):
                rows = self._active_positions()
                ranks = self._active_adapter_ranks()
                out = spec0(k_draft, *a, **kw)
                clock.advance(cost.spec_round_s(
                    rows, page, int(k_draft), paged=eng.paged,
                    max_len=eng.max_len, adapter_ranks=ranks))
                return out

            eng._spec_decode = spec_decode

        # preemption swap traffic (round trip charged at swap-in; the
        # swap-out device_get has no jitted hook)
        if getattr(eng, "_swap_in", None) is not None:
            swap_in0 = eng._swap_in

            def swap_in(*a, **k):
                out = swap_in0(*a, **k)
                clock.advance(cost.swap_s(int(a[2][0].shape[0]) * page))
                return out

            eng._swap_in = swap_in
        if getattr(eng, "_dense_swap_in", None) is not None:
            dswap0 = eng._dense_swap_in

            def dense_swap_in(*a, **k):
                out = dswap0(*a, **k)
                clock.advance(cost.swap_s(int(a[1].shape[1])))
                return out

            eng._dense_swap_in = dense_swap_in

    def _wrap_faults(self, inj) -> None:
        """Compose serving/faults.py with the SimClock: an injected
        slow_step stall advances SIMULATED time by its payload (the
        engine's real sleep is wall time the sim never sees), so chaos
        runs shift TTFT/ITL exactly as a stalled device would."""
        clock = self.clock
        fire0 = inj.fire

        def fire(point: str):
            p = fire0(point)
            if p is not None and point == "slow_step":
                clock.advance(float(p.get("seconds", 0.05)))
            return p

        inj.fire = fire

    # -- the event loop -----------------------------------------------------

    def run(self) -> dict:
        eng = self.engine
        arrivals = self.trace.arrivals
        n = len(arrivals)
        i = 0
        requests = []
        steps = 0
        # (sim-time weight, occupancy, kv utilization) per iteration:
        # means must be TIME-weighted, or the thousands of cheap
        # host-epsilon iterations of a blocked stretch would swamp the
        # few hundred decode steps that carry almost all simulated time
        samples: list = []
        while True:
            while i < n and arrivals[i].t <= self.clock.now:
                requests.append(eng.submit(
                    arrivals[i].prompt,
                    max_new_tokens=arrivals[i].max_new_tokens,
                    adapter=arrivals[i].adapter,
                ))
                i += 1
            t_before = self.clock.now
            busy = eng.step()
            steps += 1
            if self.clock.now <= t_before:
                # pure host iteration (admission blocked, sweeps only):
                # charge the host epsilon so deadline machinery always
                # sees time move and the loop cannot spin at one instant
                self.clock.advance(self.host_step_s)
            samples.append((self.clock.now - t_before,
                            int(eng.active.sum()),
                            float(eng.kv_utilization())))
            if not busy:
                if i < n:
                    self.clock.advance_to(arrivals[i].t)
                    continue
                if eng.idle():
                    break
            if steps >= self.max_steps:
                raise RuntimeError(
                    f"sim exceeded max_steps={self.max_steps} "
                    f"(t={self.clock.now:.3f}s, {i}/{n} arrivals)"
                )
        return self._report(requests, steps, samples)

    # -- reporting ----------------------------------------------------------

    def _report(self, requests: list, steps: int,
                samples: list) -> dict:
        eng = self.engine
        tr = self.trace
        sim_s = self.clock.now
        wsum = sum(w for w, _, _ in samples) or 1.0
        occ_mean = sum(w * o for w, o, _ in samples) / wsum
        kvu_mean = sum(w * u for w, _, u in samples) / wsum
        occ_peak = max((o for _, o, _ in samples), default=0)
        kvu_peak = max((u for _, _, u in samples), default=0.0)
        done = [r for r in requests if r.done]
        completed = [r for r in done if r.finish_reason in ("stop", "length")]
        out_tokens = sum(len(r.out_tokens) for r in requests)
        offered_s = max(tr.duration_s, 1e-9)
        reasons = {k: v for k, v in sorted(eng.finish_reasons.items())}
        n_req = max(len(requests), 1)
        page_leak = 0
        kv_extra: dict = {}
        if eng.paged:
            # refcount-vs-holders reconciliation (slot tables + radix
            # nodes), not a bare ref>0 scan: cached pages legitimately
            # hold the radix's own reference at drain
            page_leak = eng.page_leaks()
            kv_extra = {
                "free_pages_at_drain": eng.pages.pool.n_free,
                "cached_prefix_pages": eng.pages.radix.n_nodes,
                "prefix_hits": eng.pages.prefix_hits,
                "prefix_partial_hits": eng.pages.prefix_partial_hits,
                "prefix_tokens_reused": eng.pages.prefix_tokens_reused,
                "prefix_evictions": eng.pages.prefix_evictions,
            }
        adapter_extra: dict = {}
        if self.adapters is not None:
            # registry churn counters (adapter hit/evict — the
            # scheduler-level cost of multi-tenant adapter traffic,
            # gated on CPU like everything else)
            st = self.adapters.stats()
            pager = getattr(eng, "_pager", None)
            adapter_extra["adapters"] = {
                "n_tenants": len({a.adapter for a in tr.arrivals
                                  if a.adapter}),
                "budget": self.sim.adapter_budget,
                "loads": st["loads"],
                "hits": st["hits"],
                "evictions": st["evictions"],
                "load_failures": st["load_failures"],
                "resident_at_drain": st["resident"],
                # unified HBM paging churn (serving/adapters.AdapterPager):
                # device pages in the SHARED KV pool; 0s when the engine
                # runs dense (no pager)
                "page_ins": pager.page_ins if pager is not None else 0,
                "page_outs": pager.page_outs if pager is not None else 0,
                "pages_resident_at_drain": (
                    pager.pages_resident if pager is not None else 0),
            }
        spec_extra: dict = {}
        if getattr(eng, "speculative", False):
            rounds = eng.spec_rounds
            spec_extra["speculative"] = {
                "draft_k": self.sim.draft_k,
                "rounds": rounds,
                "emitted": eng.spec_emitted,
                # tokens per verify round (1.0 = nothing accepted,
                # draft_k = every draft accepted + the bonus token)
                "tokens_per_round": round(
                    eng.spec_emitted / rounds, 4) if rounds else 0.0,
            }
        s = self.sim
        return {
            "format": REPORT_FORMAT, "version": REPORT_VERSION,
            **adapter_extra,
            **spec_extra,
            "trace": {
                "name": tr.name, "seed": tr.seed, "n_requests": len(tr.arrivals),
                "duration_s": round(tr.duration_s, 6),
                "offered_rps": round(len(tr.arrivals) / offered_s, 3),
                "offered_tokens": tr.offered_tokens(),
            },
            "engine": {
                "n_slots": s.n_slots, "max_len": s.max_len,
                "paged": s.paged, "page_size": s.page_size,
                "n_pages": eng.n_pages if eng.paged else None,
                "max_queue": s.max_queue,
                "queue_deadline_s": s.queue_deadline_s,
                "deadline_s": s.deadline_s,
                "prefill_chunk_tokens": s.prefill_chunk_tokens,
            },
            "cost_model": self.cost.describe(),
            "sim": {"steps": steps, "sim_seconds": round(sim_s, 6)},
            "throughput": {
                "achieved_rps": round(len(completed) / max(sim_s, 1e-9), 3),
                "offered_rps": round(len(tr.arrivals) / offered_s, 3),
                "completed": len(completed),
                "output_tokens": out_tokens,
                "output_tokens_per_s": round(out_tokens / max(sim_s, 1e-9), 2),
            },
            "latency": {
                "ttft_s": _summary(eng.ttft.samples),
                "itl_s": _summary(eng.itl.samples),
                "queue_wait_s": _summary(eng.queue_wait.samples),
                "prefill_s": _summary(eng.prefill_seconds.samples),
                "decode_step_s": _summary(eng.decode_step_seconds.samples),
                "resume_wait_s": _summary(eng.resume_wait.samples),
            },
            "counters": {
                "finish_reasons": reasons,
                "preemptions": eng.preemptions,
                "preemption_resumes": eng.preemption_resumes,
                "requests_shed": eng.requests_shed,
                "request_timeouts": eng.request_timeouts,
                "requests_completed": eng.requests_completed,
                "prefill_chunks": eng.prefill_chunks,
            },
            "rates": {
                "shed_rate": round(eng.requests_shed / n_req, 4),
                "timeout_rate": round(eng.request_timeouts / n_req, 4),
                "preemption_rate": round(eng.preemptions / n_req, 4),
            },
            "kv": {
                "utilization_mean": round(kvu_mean, 4),
                "utilization_peak": round(kvu_peak, 4),
                "page_leak_at_drain": page_leak,
                **kv_extra,
            },
            "occupancy": {
                "mean": round(occ_mean, 3),
                "peak": occ_peak,
            },
        }


# ---------------------------------------------------------------------------
# scenario registry: trace mix + the engine shape that makes it tell its
# story. "overload" pairs ~4x-capacity offered load with a small page
# pool and bounded admission so preemption AND shed AND deadline kills
# all fire — the acceptance workload for every future scheduler PR.
# ---------------------------------------------------------------------------

SCENARIOS: dict = {
    "poisson": SimConfig(),
    "bursty": SimConfig(),
    # bounded pool: the radix cache runs under genuine eviction
    # pressure (leaf-first LRU vs a working set larger than the pool).
    # Chunking stays OFF here — this mix is the TTFT acceptance number
    # and chunked prefill deliberately trades admission latency for
    # decode smoothness (the overload mix's ITL tells that story)
    "prefix-heavy": SimConfig(n_pages=24),
    "overload": SimConfig(
        n_pages=18, max_queue=6, queue_deadline_s=0.75, deadline_s=3.0,
        prefill_chunk_tokens=32,
    ),
    # 4 Zipf-popular tenants over a 2-adapter host-RAM budget: the
    # hot tenants stay resident, the tail churns — loads, hits AND
    # evictions all fire (serving/adapters.py §7)
    "adapter-zipf": SimConfig(adapter_budget=2),
    # real self-draft + verify rounds on a dense tiny model, each round
    # priced as draft_k decode steps + one batched verify
    # (cost.spec_round_s) — the ROADMAP sim-calibration remainder that
    # previously made SimDriver refuse speculative engines
    "speculative": SimConfig(speculative=True, draft_k=4),
    # S-LoRA completion: Zipf adapter traffic THROUGH speculative
    # decoding (base draft, adapter-applied verify) over a page pool
    # tight enough that adapter pages and KV fight for the same budget
    # — acceptance, adapter page churn AND zero-leak drain all gate on
    # this mix (scripts/ci.sh --core). Host-RAM budget covers all 4
    # tenants (host churn is adapter-zipf's story); the pressure here
    # is DEVICE pages: 16 shared pages force holder-free adapter
    # page-outs when concurrent KV demand spikes
    "adapter-spec": SimConfig(adapter_budget=4, speculative=True,
                              draft_k=4, n_pages=16),
}


def run_scenario(name: str, seed: int = 0, model=None,
                 hbm_gbps: Optional[float] = None,
                 sim: Optional[SimConfig] = None,
                 trace: Optional[Trace] = None,
                 faults: Optional[Any] = None,
                 tracer: Optional[Any] = None) -> dict:
    """One named mix end to end: generate (or take) the trace, drive a
    fresh engine, return the report dict (json.dumps(sort_keys=True)
    of it is the banked artifact)."""
    if sim is None:
        if name not in SCENARIOS:
            raise ValueError(
                f"unknown scenario {name!r}; known: {sorted(SCENARIOS)}"
            )
        sim = SCENARIOS[name]
    trace = trace if trace is not None else named_trace(name, seed=seed)
    driver = SimDriver(trace, model=model, sim=sim,
                       cost=default_cost_model(hbm_gbps=hbm_gbps),
                       faults=faults, tracer=tracer)
    return driver.run()


def report_json(report: dict) -> str:
    """The canonical serialized form — sorted keys, no whitespace
    variance, so identical runs are byte-identical."""
    return json.dumps(report, sort_keys=True, separators=(",", ":"))
