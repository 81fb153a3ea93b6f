"""Slot-based continuous-batching inference engine.

TPU-native re-design of the reference's serving scheduler
(`PPModelWorker.process_step`, pipeline_parallel.py:482-929 in
/root/reference: dynamic batching with `max_num_seqs`, split prefill,
per-rank p2p hops; and `serving/fastapi/model_worker.py:28-200`'s async
queue loop). Here the whole batch lives in ONE static-shape XLA program:

- a fixed pool of `n_slots` decode slots shares one KV cache with
  **per-row write positions** (kvcache.KVCache with pos: [B]);
- prefill runs per request on bucketed lengths (its own small cache),
  then a jitted `insert` copies the prompt KV into the slot's rows —
  so a new request joins mid-flight without recompiling or disturbing
  running rows (the reference's "dynamic batching" without its Python
  per-step re-batching);
- one jitted `decode_step` advances every active slot one token and
  samples on device; idle slots compute masked garbage (the static-shape
  price, paid instead of recompilation).

The host-side loop (`step()`) only moves tokens in/out and does
bookkeeping — the reference's asyncio request queue maps onto it
directly (serving/api_server.py).
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import itertools
import queue
import threading
import time
import weakref
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu import kvcache, kvpaged
from bigdl_tpu.generate import GenerationConfig, sample_token_per_row
from bigdl_tpu.models.config import ModelConfig
from bigdl_tpu.obs import retrace
from bigdl_tpu.obs.scopes import scope
from bigdl_tpu.obs.tracing import DECODE_TID
from bigdl_tpu.serving.faults import NULL_INJECTOR, FaultError
from bigdl_tpu.serving.hostrow import bits as _bits
from bigdl_tpu.serving.hostrow import expert_id_dtype as _expert_id_dtype
from bigdl_tpu.serving.metrics import Histogram
from bigdl_tpu.serving.pages import NeverFits, PageTable, prefill_bucket
from bigdl_tpu.utils import round_up

#: where the stepping thread can pay for a retrace: the three child spans
#: of an admission, a decode step, and everything between them
RETRACE_PHASES = ("prefill.dispatch", "first_token.sample",
                  "first_token.arm", "decode_step", "other")

#: decode tokens coalesced into one `decode` span of a request's track
TRACE_DECODE_EVERY = 8

#: prompt tokens one `step()` admits before it decodes the rows it has: a
#: further prompt is admitted only while the prompts of this call, with it,
#: stay within the budget (the first always is). A queue of long prompts
#: (16 of 8k to 16k tokens prefill for 40 s) gets a decode step between
#: two of them, where the admit-all loop let the rows already admitted
#: stand still until the last free slot was filled; prompts that sum to
#: less are admitted together as before (vLLM: `max_num_batched_tokens`)
ADMIT_TOKENS_PER_STEP = 16384

#: what a phase runs under while tracing is off: one shared context that
#: does nothing, so an untraced step builds no annotation
_NO_PHASE = contextlib.nullcontext()


def _named(name: str, fn, *bound):
    """functools.partial with a name: JAX labels its compile log, its
    monitoring events and the profiler's trace with it, so each engine
    program is told apart (an unnamed partial is `jit(<unknown>)`)."""
    p = functools.partial(fn, *bound)
    p.__name__ = name
    return p


def _read_first_token(out, n_top: int) -> tuple:
    """Unpack what `_first_token_impl` packs for the host, int32
    [2 + 2 * n_top]: the token, its logprob's bits, then the `n_top` most
    likely ids and their logprobs' bits. Returns (token, logprob, {id:
    logprob} or None)."""
    out = np.asarray(out)  # the admission's one fetch
    top = None
    if n_top:
        top = {int(t): float(l) for t, l in zip(
            out[2:2 + n_top], out[2 + n_top:].view(np.float32))}
    return int(out[0]), float(out[1:2].view(np.float32)[0]), top


def _moe_load(choices: "np.ndarray", n_experts: int, first: int = 0) -> dict:
    """Span arguments from the expert choices [L, n, k] of a step's live
    rows (or an admission's prompt tokens): how many assignments there
    were, how many (layer, expert) pairs got any (each is one expert's
    packed weights read), the busiest pair's, and how many pairs there
    are. Of a router wider than the `n_experts` held here from id `first`
    on, only what is HELD and computed here counts."""
    L = choices.shape[0]
    local = choices.reshape(L, -1).astype(np.int64) - first
    flat = (np.arange(L)[:, None] * n_experts + local)[
        (local >= 0) & (local < n_experts)]
    c = np.bincount(flat, minlength=L * n_experts)
    return {"moe_assignments": int(c.sum()),
            "moe_experts_hit": int(np.count_nonzero(c)),
            "moe_max_expert_load": int(c.max(initial=0)),
            "moe_experts": int(c.size)}


# The newest finished request that carries its expert choices, held WEAKLY:
# nothing is copied and nothing is kept alive, so once its caller lets go
# of the Request this is empty again. It is how a caller that holds only a
# token sequence (not the Request) finds the choices made on it. Its one
# reader is bench/reference/mixtral.py, whose caller does not hand it the
# request; when the benchmark's entry does (PERF.md section 7), this and
# `last_routed_request` go.
_last_routed: Optional["weakref.ref"] = None


def last_routed_request() -> Optional["Request"]:
    """The newest request a sparse-expert engine of this process finished
    with a whole record of its expert choices (`Request.expert_ids`), if
    its caller still holds it; None otherwise."""
    return None if _last_routed is None else _last_routed()


def _cache_kind(model) -> kvpaged.CacheKind:
    """The paged cache kind of `model` (docs/serving.md, "Cache kinds"),
    chosen here and nowhere else: a recurrent state in every layer by the
    config's `attention_kind`; then the family's own, which it names
    (`PAGED_CACHE_KIND`: a state row beside the pages, kvhybrid.py; a
    window group of pages beside the global one, kvwindow.py; pages read
    by selection beside a state row, kvsparse.py) or, offering
    an `init_paged_cache` and no name, has as latent pages (MLA); then KV
    pages (those of a model generated by diffusion over blocks as their own
    kind, for what it refuses: serving/blocks.py). The page table books,
    parks and restores a page of any kind alike."""
    from bigdl_tpu import kvhybrid, kvsparse, kvstate, kvwindow

    if model.config.attention_kind == kvstate.KIND:
        return kvstate.CACHE_KIND
    if model.config.block_length:  # KV pages, generated by blocks
        from bigdl_tpu.serving import blocks

        return blocks.CACHE_KIND
    own = getattr(model.family, "PAGED_CACHE_KIND", None)
    for mod in (kvhybrid, kvwindow, kvsparse):
        if own == mod.KIND:
            return mod.CACHE_KIND
    if hasattr(model.family, "init_paged_cache"):
        return kvpaged.LATENT_PAGES
    return kvpaged.KV_PAGES


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new_tokens: int = 64
    # per-request sampling (None = engine default). These become traced
    # per-slot tensors in the decode step, so two concurrent requests can
    # sample with different temperatures in the same XLA program.
    do_sample: Optional[bool] = None
    temperature: Optional[float] = None
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    repetition_penalty: Optional[float] = None
    eos_token_id: Optional[int] = None
    # multi-tenant LoRA: the named adapter this request decodes with
    # (serving/adapters.py; None = the shared base). Resolved +
    # refcounted at admission; applied as a batched epilogue on the
    # shared fused dequant-GEMM (docs/serving.md §7).
    adapter: Optional[str] = None
    # filled by the engine
    out_tokens: list[int] = dataclasses.field(default_factory=list)
    # chosen-token logprob per emitted token (log softmax of the model's
    # pre-filtering distribution — OpenAI "logprobs" semantics)
    out_logprobs: list[float] = dataclasses.field(default_factory=list)
    # when the engine runs with logprobs_top_k=N: per emitted token, the
    # N most likely {token_id: logprob} alternatives
    out_top_logprobs: list[dict] = dataclasses.field(default_factory=list)
    # sparse-expert models: the top-k expert ids chosen at every prompt
    # position [L, T, k] int8 (None when part of the prompt came from the
    # prefix cache and was not computed here) and at each decode step's
    # input position ([L, k] per step); read them with expert_ids()
    prompt_experts: Optional["np.ndarray"] = None
    out_experts: list = dataclasses.field(default_factory=list)
    # generation by blocks: one record a pass of this request's row, in
    # order: {"block", "pass", "base" (the block's first position), "ids"
    # [b] the pass ran on, "masked" and "revealed" [b] bool, "stored",
    # "experts" [L, b, k] or None}. A token's logprob is the log-confidence of the
    # pass that revealed it; `prompt_experts` then covers the prompt's
    # whole blocks and `out_experts` every position of each STORED block
    passes: list = dataclasses.field(default_factory=list)
    # block-sparse attention (kvsparse.py), only where the kind was asked
    # (`CACHE_KIND.report_ids`): the key blocks each sparse layer and KV
    # head chose, flat [Ls * Hkv * topk] layer-major (-1: fewer, or a row
    # read whole), at the prompt's last position and at each decode step's
    # input position
    prompt_selection: Optional["np.ndarray"] = None
    out_selection: list = dataclasses.field(default_factory=list)
    done: bool = False
    finish_reason: str = ""  # "stop" (EOS) | "length" (budget) |
    # "invalid" (rejected at submit — over-long prompt) | "error" |
    # "shed" (overload: queue bound / queue deadline — retryable) |
    # "timeout" (per-request deadline expired mid-flight)
    error: Optional[str] = None
    # which admission limit shed the request ("queue_full" |
    # "queue_deadline") — structured so the HTTP layer's 429-vs-503
    # choice never depends on parsing the human-readable error text
    shed_kind: Optional[str] = None
    stream: Optional[queue.SimpleQueue] = None  # receives (token|None=EOS)
    # overload controls (None = engine default): how long the request may
    # wait for a slot, and its total wall-clock budget from submit
    queue_deadline_s: Optional[float] = None
    deadline_s: Optional[float] = None
    submit_ts: float = 0.0  # stamped by submit()
    admit_ts: Optional[float] = None  # first admission (pre-prefill)
    preemptions: int = 0  # times this request was swapped to host RAM
    # ---- lifecycle timing (obs/tracing.py; engine clock domain) ----
    first_token_ts: Optional[float] = None
    last_token_ts: Optional[float] = None
    preempt_ts: Optional[float] = None  # set while parked in host RAM
    preempted_s: float = 0.0  # total seconds spent parked (all swaps)

    def expert_ids(self, n_positions: int) -> Optional["np.ndarray"]:
        """The top-k expert ids [L, n_positions, k] chosen at the first
        `n_positions` positions of `prompt + out_tokens` (the serving
        counterpart of HF's `output_router_logits`), or None: a dense
        model, a prompt served partly from the prefix cache, or positions
        not decoded yet. The last emitted token was never an input, so
        there are len(prompt) + len(out_tokens) - 1 positions at most."""
        if self.prompt_experts is None:
            return None
        n_out = n_positions - self.prompt_experts.shape[1]
        if not 0 <= n_out <= len(self.out_experts):
            return None
        steps = [e[:, None] for e in self.out_experts[:n_out]]
        return np.concatenate([self.prompt_experts] + steps, axis=1)


@dataclasses.dataclass
class _Slot:
    req: Optional[Request] = None
    remaining: int = 0
    eos: Optional[int] = None  # resolved per-request EOS id
    seq: int = 0  # admission order — the preemption victim policy's age
    # pos at the last swap-in; -1 = never preempted. A slot that cannot
    # extend AND has emitted nothing since its resume proves the pool
    # cannot support it (self-preempting again would livelock).
    resumed_pos: int = -1
    # decode-window trace state: tokens since the last emitted "decode"
    # span and that window's start timestamp (obs/tracing.py)
    t_win: float = 0.0
    n_win: int = 0


class _StepTrace:
    """One `step()` while tracing is on: the parts of its `engine.step`
    span closed so far, each `(name, args)` or, partitioned in turn,
    `(name, args, cuts, parts)` with the instant it ended in `cuts`
    (`TraceRecorder.complete_parts` takes them as they stand), and the
    number of the decode step it read, which its span carries."""

    __slots__ = ("t_in", "cuts", "parts", "chunks", "admitted", "seq",
                 "decoded")

    def __init__(self, t_in: float, chunks: int):
        self.t_in = t_in
        self.cuts: list = []
        self.parts: list = []
        self.chunks = chunks  # `prefill_chunks` at entry
        self.admitted = 0  # admissions completed in this step
        self.seq: Optional[int] = None  # of the decode step it read
        self.decoded = False  # it dispatched or read a decode step

    def close(self, t: float, *part) -> None:
        self.cuts.append(t)
        self.parts.append(part)


@dataclasses.dataclass
class _Flight:
    """A decode step that was dispatched and whose tokens the host has not
    read yet. Plain decode keeps one in flight: the device runs it (and
    has the next one queued behind it) while the host emits the step
    before, reaps, admits and books pages."""

    out: Any  # what `_decode_impl` packs for the host, on the device
    reqs: list  # per slot, the request its row was computed for; None for
    # an idle row and for a slot whose LAST step, by its count of tokens,
    # was still unread at dispatch. A slot that holds another request (or
    # none) when the step is read finished in between: its row is dropped
    t0: float  # where `step.pages` of the dispatching call ended
    ahead: bool  # dispatched with its predecessor unread
    seq: Optional[int] = None  # its number, while tracing


@dataclasses.dataclass
class _Preempted:
    """A request parked in host RAM: everything needed to resume decode
    bit-exactly — the KV blob plus the slot-side sampling/progress state
    that normally lives in the engine's per-slot arrays."""

    req: Request
    cur: int  # last emitted token (next decode input)
    remaining: int
    eos: Optional[int]
    pos: int  # tokens written (prompt + emitted)
    start: int  # dense left-pad offset (0 for paged)
    seq: int  # original admission age (kept: resumed requests stay old)
    temp: float
    topk: int
    topp: float
    dosample: bool
    penalty: float
    seen: Any  # [V] bool host row (repetition-penalty state)
    blob: Any  # kvpaged.HostPages | dense (k, v, ks, vs) tuple
    n_pages: int = 0  # paged: pages to reallocate on resume


@dataclasses.dataclass
class _PrefillState:
    """A request's prefill, in chunks (a monolithic prefill is one,
    run at once): mid-plan it owns its slot and its fully allocated
    page table, but `active` stays False (no decode) and the
    engine's block-table row stays pointed at the scratch page until
    the last chunk lands — idle-slot garbage decode writes must never
    reach the half-filled (possibly shared) real pages. Chunks write
    through `row` directly (the jitted prefill takes its own block-
    table argument)."""

    req: Request
    slot: int
    row: "np.ndarray"  # the slot's REAL block-table row
    written: int  # prompt tokens whose KV is in the pool (incl. cache
    # hits + the sub-page copy)
    path: list  # the matched radix nodes (root-first) — kept so the
    # final chunk registers under them without re-walking the tree;
    # they cannot be evicted meanwhile (the slot holds their pages)
    chunk: int  # token budget per chunk
    moe: list = dataclasses.field(default_factory=list)  # sparse-expert
    # models: (expert choices on the device, tokens) of the chunks so far
    start: int = 0  # `written` at the first chunk (0: the whole prompt)
    state_chunks: int = 0  # a recurrent-state model: chunks of the
    # prefill form run so far (the `prefill` span's `state_chunks`)
    scan_tokens: int = 0  # ... or, where the form is a selective scan,
    # tokens that went through it (the span's `scan_tokens`)
    upprojected: int = 0  # a latent-page model: tokens whose K and V the
    # chunks so far expanded from latents (`latent_tokens_upprojected`)
    row_pages: int = 0  # KV pages: pool pages the chunks so far gathered
    pages_written: int = 0  # ... and pool pages they wrote back
    wrow: Optional[Any] = None  # two groups of pages: the slot's row of
    # the window group's table, and that group's pages written back
    # (`pages_written` is then the global group's)
    window_pages_written: int = 0


class InferenceEngine:
    """model: a TpuModel (api.py). Sampling params (do_sample /
    temperature / top-k / top-p / eos) are PER REQUEST: they ride the
    decode step as traced per-slot tensors, so concurrent requests with
    different configs share one compiled program. The engine-level
    GenerationConfig only provides defaults."""

    def __init__(
        self,
        model,
        n_slots: int = 8,
        max_len: int = 1024,
        gen: Optional[GenerationConfig] = None,
        seed: int = 0,
        paged: bool = False,
        page_size: int = 64,
        n_pages: Optional[int] = None,
        speculative: bool = False,
        draft_params=None,
        draft_k: int = 4,
        adaptive_draft: bool = False,
        truncate_prompts: bool = False,  # opt-in: keep over-long tails
        logprobs_top_k: int = 0,  # also return the N most likely
        # alternatives per emitted token (OpenAI top_logprobs); static
        # so the top-k pass compiles only into engines that opt in
        quantize_kv: bool = False,
        prefill_chunk_tokens: Optional[int] = None,  # paged only: split
        # prompt prefill into chunks of at most this many tokens and
        # advance AT MOST ONE chunk of ONE prefilling request per
        # step() — a 32k prompt arriving mid-decode then bounds the
        # running batch's inter-token stall by one chunk instead of one
        # prompt (docs/serving.md §6). None = monolithic prefill.
        journal: Optional[str] = None,
        # ---- overload protection (docs/serving.md) ----
        max_queue: Optional[int] = None,  # bound on waiting submits;
        # over-capacity submits fail fast with finish_reason="shed"
        queue_deadline_s: Optional[float] = None,  # default max wait for
        # a slot; expired-in-queue requests are shed, not served late
        deadline_s: Optional[float] = None,  # default total wall-clock
        # budget per request; expiry mid-decode finishes "timeout"
        preemption: bool = True,  # page-pool exhaustion mid-decode swaps
        # a victim's KV to host RAM and requeues it instead of silently
        # truncating its output with "length"
        faults: Optional[Any] = None,  # FaultInjector (serving/faults.py);
        # None = the shared inert injector (zero-cost hooks)
        adapters: Optional[Any] = None,  # AdapterRegistry
        # (serving/adapters.py): requests may name a LoRA adapter and
        # decode with it applied as a batched unquantized epilogue on
        # the shared base — one forward serves a heterogeneous adapter
        # batch (docs/serving.md §7). None = adapter= submits are
        # rejected as invalid.
        # ---- observability (docs/observability.md) ----
        tracer: Optional[Any] = None,  # obs.tracing.TraceRecorder; spans
        # recorded only while tracer.enabled (off = one attr check)
        request_log: Optional[str] = None,  # JSONL path: one derived-
        # timings record per finished request (crc-suffixed lines)
        clock: Callable[[], float] = time.time,  # every lifecycle
        # timestamp (deadlines, spans, histograms) flows through this —
        # the simulated-clock benchmark drives the engine with a fake one
    ):
        self.model = model
        # clock + observability sinks FIRST: submit()/journal replay at
        # the end of __init__ already stamp timestamps and record finishes
        self._clock = clock
        self.tracer = tracer
        self._request_log = None
        if request_log is not None:
            from bigdl_tpu.obs.tracing import RequestLog

            self._request_log = RequestLog(request_log)
        self._t_start = clock()
        # terminal finish_reason -> count (metrics.py renders the family;
        # handler threads insert via _note_finish, the scrape thread
        # snapshots under the same lock)
        # guarded-by: _stat_lock
        self.finish_reasons: "collections.defaultdict[str, int]" = \
            collections.defaultdict(int)
        self._journal = None  # attached at the END of __init__ (it
        # replays the previous process's unfinished tail, which needs
        # the queue and rid counter live)
        self.recovered_requests: list[Request] = []
        self.config: ModelConfig = model.config
        self.n_slots = n_slots
        self.max_len = max_len
        self.gen = gen or GenerationConfig()
        # paged KV (kvpaged.py): pages allocated on demand + refcounted
        # prefix cache, so the pool can be smaller than slots*max_len and
        # identical prompt prefixes share storage AND prefill compute
        # (the reference's paged attention + prefix caching live in its
        # vLLM fork, vllm/xpu/)
        if logprobs_top_k and speculative:
            # checked BEFORE any pool allocation / AOT compile below —
            # failing after seconds of compile and GBs of HBM is hostile
            raise NotImplementedError(
                "logprobs_top_k is not wired through the speculative "
                "verify round yet; use speculative=False"
            )
        self.paged = paged
        # fp8 KV storage for the shared pool (dense or paged): halves KV
        # HBM capacity + traffic, the reference's fp8 kv-cache lever
        self.quantize_kv = quantize_kv
        # families with their own cache serve through (a) the generic
        # dataclass insert path when they declare SERVABLE_CACHE (MLA's
        # latent as a dense pool — flat [L, B, S, ...] fields with real
        # pos/start; models/deepseek.py), (b) their own engine_pool /
        # engine_insert adapter when the cache has nested pools or property
        # pos (rwkv recurrent state, yuan localized-filter hiddens, mllama
        # cross-attention; the generic path would silently corrupt them),
        # or (c), paged, through a page pool of their own KIND
        # (`_cache_kind`): what varies between paged engines is behind
        # `self.kind`, None for a dense pool
        fam = model.family
        kind = _cache_kind(model)
        kind.check(model.config.model_type, paged, quantize_kv=quantize_kv,
                   speculative=speculative, adapters=adapters is not None,
                   prefill_chunk_tokens=prefill_chunk_tokens is not None)
        self.kind = kind if paged else None
        self._family_cache = None
        self._family_pool = getattr(fam, "engine_pool", None)
        self._family_insert = getattr(fam, "engine_insert", None)
        if (self._family_pool is None) != (self._family_insert is None):
            # half an adapter would silently mix the custom and generic
            # cache paths (e.g. a pool without per-row pos fed through
            # the generic dataclass insert)
            raise TypeError(
                f"{model.config.model_type}: engine_pool and engine_insert "
                "must be defined together"
            )
        if (hasattr(fam, "init_cache")
                and self.kind in (None, kvpaged.KV_PAGES)):
            custom = (self._family_pool is not None
                      and self._family_insert is not None)
            if not custom and not getattr(fam, "SERVABLE_CACHE", False):
                raise NotImplementedError(
                    f"the serving engine does not support "
                    f"{model.config.model_type}'s cache layout yet; use "
                    "TpuModel.generate()"
                )
            self._family_cache = fam.init_cache
        if paged and self._family_cache is not None:
            raise NotImplementedError(
                f"paged serving is not available for "
                f"{model.config.model_type}: its cache is not a KV pool"
            )
        if quantize_kv and self._family_cache is not None:
            # the family init_cache/engine_pool signatures don't thread
            # quantize_kv; silently serving bf16 KV would misreport the
            # memory footprint the caller asked for (ADVICE r04)
            raise NotImplementedError(
                f"quantize_kv is not wired for "
                f"{model.config.model_type}'s family cache; use "
                "quantize_kv=False"
            )
        if paged:  # a kind whose page is a row sizes the table itself
            page_size, n_pages = kind.page_geometry(
                n_slots, max_len, page_size, n_pages)
        self.page_size = page_size
        # physical reserve past max_len: a speculative verify round writes
        # draft_k tokens at pos..pos+K-1 before rolling back; a request
        # whose decode window ends flush with max_len would otherwise lose
        # those writes (out-of-bounds scatters drop silently and the
        # emitted tokens attend with missing keys — ADVICE r04). Extra
        # PHYSICAL slots keep outputs byte-identical to plain serving,
        # unlike shrinking the logical window (which re-truncates prompts).
        self._reserve = max(draft_k - 1, 0) if speculative else 0
        self.max_pages_per_row = -(-(max_len + self._reserve) // page_size)
        # +1: physical page 0 is the reserved scratch sink, so the default
        # pool still covers every slot at full logical length
        self.n_pages = n_pages or n_slots * self.max_pages_per_row + 1
        if prefill_chunk_tokens is not None:
            if not paged:
                raise ValueError(
                    "prefill_chunk_tokens requires paged=True (chunks "
                    "write straight into the shared page pool)"
                )
            if prefill_chunk_tokens < 1:
                raise ValueError(
                    f"prefill_chunk_tokens must be >= 1, got "
                    f"{prefill_chunk_tokens}"
                )
            if speculative:
                # the draft pool's _admit_draft prefill is monolithic
                # (full prompt through the draft model at activation) —
                # it would break the one-chunk stall bound this knob
                # promises. Refuse honestly instead of jittering
                # silently; chunking the draft admission is the
                # follow-up.
                raise NotImplementedError(
                    "prefill_chunk_tokens is not wired through the "
                    "speculative draft admission yet; use "
                    "speculative=False or monolithic prefill"
                )
        self.prefill_chunk_tokens = prefill_chunk_tokens
        # prefill invocations (each chunk is one; a monolithic prefill
        # counts 1) — bigdl_tpu_prefill_chunks_total
        self.prefill_chunks = 0
        # the at-most-one request currently mid-chunked-prefill: it
        # holds its slot and pages but is NOT decoded (active stays
        # False) until its last chunk lands. Engine-thread only.
        self._prefilling: Optional[_PrefillState] = None
        self._faults = faults if faults is not None else NULL_INJECTOR
        # the paged cache's host bookkeeping (serving/pages.py): page
        # lists, refcounts, the radix prefix cache, the block table's
        # mirror. _reset_state rebuilds it through the same constructor
        self.pages = PageTable(
            n_slots, self.n_pages, page_size, self.max_pages_per_row,
            max_len, faults=self._faults,
            share_prefixes=kind.share_prefixes,
            window=kind.window(self.config),
        ) if paged else None
        self._rng = jax.random.PRNGKey(seed)
        # queue.Queue (not SimpleQueue): the queue-deadline sweep filters
        # the backing deque in place under .mutex
        self._queue: "queue.Queue[Request]" = queue.Queue()
        self._slots = [_Slot() for _ in range(n_slots)]
        # rids start at 1: a request's trace track is tid=rid, and tid 0
        # is the engine track (decode_step spans, batch counter) — a
        # rid-0 request would interleave its lifecycle spans with the
        # engine's and break per-track monotonic nesting
        self._rid = itertools.count(1)
        # model sharded via TpuModel.to_mesh(): all jitted steps run SPMD
        # under the mesh, with the KV pool sharded over kv heads ('tp')
        self._mesh = getattr(model, "mesh", None)

        self.cache = self._make_pool()
        # bytes of one slot's recurrent state over all layers (0 for a
        # model that keeps keys): a decode step moves twice that a live row
        self.state_row_bytes = (
            kind.state_row_nbytes(self.cache) if paged else 0)
        # bytes of one token's latents over all layers (0 for a model that
        # keeps keys and values): what a decode step must read a live token
        self.latent_token_bytes = (
            kind.token_nbytes(self.config) if paged else 0)
        # bytes of state rows that decode steps read and wrote again
        self.state_bytes_moved = 0
        self.cur = jnp.zeros((n_slots,), jnp.int32)  # last token per slot
        self.active = np.zeros((n_slots,), bool)  # host-side mask
        # per-slot sampling params (host mirrors, shipped traced each step)
        g = self.gen
        self._temp = np.full((n_slots,), g.temperature, np.float32)
        self._topk = np.full((n_slots,), g.top_k or 0, np.int32)
        self._topp = np.full((n_slots,), g.top_p if g.top_p is not None else 1.0,
                             np.float32)
        self._dosample = np.full((n_slots,), g.do_sample, bool)
        self._penalty = np.full((n_slots,), 1.0, np.float32)
        # per-slot seen-token masks for the HF repetition penalty
        # (reference xe_addons.repetition_penalty_logits_process_inplaced);
        # the all-1.0 common case skips the rewrite via a lax.cond in
        # _decode_impl
        self.seen = jnp.zeros((n_slots, self.config.vocab_size), jnp.bool_)
        # the `seen` row of a request with no penalty, made once
        self._no_seen_row = jnp.zeros((self.config.vocab_size,), jnp.bool_)

        # ---- multi-tenant LoRA adapters (serving/adapters.py) ----
        self.adapters = adapters
        # rid -> AdapterEntry: ONE reference per in-flight request that
        # resolved an adapter (held across preemption parking and the
        # paged OOM-retry wait; released at the terminal finish in
        # _note_finish — the kvpaged.PagePool one-hold-per-holder rule)
        self._adapter_refs: dict[int, Any] = {}
        self._slot_adapter: list[Optional[Any]] = [None] * n_slots
        # the decode step's batched per-slot adapter tree, rebuilt only
        # when a slot's adapter assignment changes (not per token)
        self._blora: Optional[dict] = None
        self._blora_dirty = True
        # rank + target set of the adapter the CURRENT prefill dispatch
        # serves (0/() = base-only) — observability the sim's cost
        # wrappers price
        self._last_prefill_rank = 0
        self._last_prefill_targets: tuple = ()
        # unified HBM paging (docs/serving.md §7): resident adapters'
        # (A, B) leaves live in pages drawn from the SAME PagePool as
        # KV — one device budget. Under page pressure the allocator's
        # escalation is radix leaf -> holder-free adapter page-out ->
        # preemption (PageTable.alloc); _gather_blora reads the pages
        # instead of re-transferring host weights per assignment change
        self._pager = None
        if adapters is not None and paged and self._family_pool is None:
            from bigdl_tpu.serving.adapters import AdapterPager

            self._adapter_store = kvpaged.AdapterPageStore(
                self.n_pages, kvpaged.kv_page_nbytes(self.cache)
            )
            self._pager = self.pages.pager = AdapterPager(
                self._adapter_store, self.pages.pool, self.pages.alloc,
                faults=faults,
            )

        # forward_fn: the family forward, or the pipeline step when the
        # mesh has a pp axis (api.TpuModel.forward_fn)
        fwd = getattr(model, "forward_fn", None) or model.family.forward
        if adapters is not None:
            # speculative + adapters: the draft scan stays base/dense
            # (advisory — any draft content yields the same emitted
            # tokens; an adapter-shifted target only lowers acceptance)
            # while the VERIFY forward applies the batched adapter tree
            # at the draft's proposed positions, so emitted tokens match
            # non-speculative adapter decode exactly (_spec_decode_impl)
            import inspect

            try:
                fwd_params = inspect.signature(fwd).parameters
            except (TypeError, ValueError):  # pragma: no cover - exotic
                fwd_params = {"lora": None}
            if "lora" not in fwd_params:
                raise NotImplementedError(
                    f"{model.config.model_type}'s forward has no lora= "
                    "epilogue path; adapter serving needs a llama-family "
                    "forward"
                )
        # sparse-expert models: a step also returns the top-k expert ids
        # of every row ([L, B, k] int8), fetched with the tokens: the
        # expert-load span arguments and gauges (docs/observability.md)
        # and each request's record of its choices (Request.expert_ids).
        # A family forward says that it reports its routing by taking
        # moe_routing= (models/llama.forward and deepseek.forward do).
        # Dense models, and forwards that do not report, pay nothing.
        self.moe_routing = False
        import inspect

        try:
            takes = inspect.signature(fwd).parameters
        except (TypeError, ValueError):  # pragma: no cover - exotic
            takes = ()
        if getattr(self.config, "is_moe", False):
            self.moe_routing = "moe_routing" in takes
        # a forward that takes `logits_at=` runs the head on that one
        # position of a paged prefill, whatever its cache kind ([T, V]
        # logits of a 196608-row head are 3.2 GB at T = 4096)
        self._head_at_last = paged and "logits_at" in takes
        # one rank's share of an expert-parallel layer: the ids a forward
        # reports are the router's (over its whole width), and a step's
        # load counts the experts HELD here (`_moe_load`)
        self._expert_ids = getattr(self.config, "router_width", 0)
        self._first_expert = getattr(self.config, "first_expert", 0)
        # a kind whose forward reports what it did (`CacheKind.report`: a
        # block-sparse layer's counts of pages, and the chosen ids where it
        # was asked) appends that many int32 columns to a step's one fetch;
        # the counts, summed, are `report_totals`
        chose = kind.report(self.cache) if paged else None
        self._report_width = 0 if chose is None else chose.shape[1]
        self.report_totals: collections.Counter = collections.Counter()
        self._moe_last: Optional[tuple] = None  # newest decode step's
        # expert ids [L, B, k] and its live rows; see moe_load
        # (choices on the device, tokens) per chunk of the prefill being
        # activated, and the prompt position its first chunk started at
        self._admit_moe: list = []
        self._admit_moe_start = 0
        # what the kind counted of that prefill (`CacheKind.prefill_args`):
        # its `prefill` span's arguments
        self._admit_args: dict = {}
        self._decode = self._with_mesh(jax.jit(
            _named("engine_decode", self._decode_impl, fwd),
            donate_argnames=("cache", "seen"),
        ))
        # the admission's own program (_first_token_impl): like the decode
        # step's, built once per engine, so an admission compiles nothing
        self._first_token = self._with_mesh(jax.jit(
            _named("engine_first_token", self._first_token_impl),
            donate_argnames=("cur", "seen"),
        ))
        self._prefill = self._with_mesh(jax.jit(
            _named("engine_prefill", self._prefill_impl, fwd),
            static_argnames=("bucket",),
        ))
        self._insert = self._with_mesh(jax.jit(
            self._insert_impl, donate_argnames=("cache",)
        ))
        self._paged_prefill = self._with_mesh(jax.jit(
            _named("engine_paged_prefill", self._paged_prefill_impl, fwd),
            donate_argnames=("pool",)))
        if paged:
            self._copy_page = self._with_mesh(jax.jit(
                kind.copy_page, donate_argnames=("cache",)))
        # THE place that chooses the step: a model generated by diffusion
        # over blocks is stepped by a pass over every row's block. Its
        # scheduler (serving/blocks.py) holds the block state and the host
        # half; the engine builds the pass as its decode program and the
        # program that opens a slot's first block beside it (no
        # `engine_first_token` runs for this kind), and every site below
        # that differs asks `self.blocks`
        self.blocks = None
        self.logprobs_top_k = logprobs_top_k
        if self.config.block_length:
            from bigdl_tpu.serving.blocks import BlockScheduler

            self.blocks = BlockScheduler(self)
            self._decode = self._with_mesh(jax.jit(
                _named("engine_decode", self.blocks.pass_impl, fwd),
                donate_argnames=("state", "cache")))
            self._arm_block = self._with_mesh(jax.jit(
                _named("engine_block_arm", self.blocks.arm_impl),
                donate_argnames=("state",)))
        # --- in-engine speculative decoding (reference serves it through
        # ipex_llm_worker.py:72-99; SURVEY §7 names "continuous batching +
        # speculative interaction" a hard part). Slot-pool design: a
        # SECOND KV pool for the draft model, a scan of per-row greedy
        # draft steps, then ONE batched verify forward over the shared
        # target pool; per-row `pos` makes per-slot acceptance rollback a
        # vector subtraction. Greedy slots emit the target's greedy
        # tokens — byte-identical to non-speculative serving; sampling
        # slots accept drafts by rejection sampling (exact output law);
        # repetition-penalty slots ride along accepting 0 drafts (their
        # position-0 token is the regular sampler's).
        self.speculative = speculative
        self.draft_k = draft_k
        self.dcache = None
        self._draft_params = draft_params
        if speculative:
            if draft_k < 2:
                # K-1 draft tokens are verifiable; K=1 would pay a draft
                # forward whose token can never be accepted
                raise ValueError(f"draft_k must be >= 2, got {draft_k}")
            if self._family_pool is not None:
                # engine_pool adapters (rwkv recurrence, yuan filter
                # state, mllama cross-attn) have nested pools / property
                # pos — the vector rollback below cannot express their
                # crop. SERVABLE_CACHE dataclasses (MLA latents) carry
                # real per-row pos and speculate like the standard pool.
                raise NotImplementedError(
                    f"speculative serving is not wired for "
                    f"{model.config.model_type}'s custom cache adapter"
                )
            if draft_params is None:
                self._draft_params = model.self_draft_params()
            # the draft pool is ALWAYS dense (even when the target pool is
            # paged): the draft model needs full prompt context, and a
            # dense [slots, max_len] draft pool keeps the verify-round
            # rollback a per-row pos subtraction in both pools
            self.dcache = self._make_pool(force_dense=True)
            spec_jit = jax.jit(
                _named("engine_spec_decode", self._spec_decode_impl, fwd),
                static_argnums=(0,),  # k_draft: ladder of compiled programs
                donate_argnames=("cache", "dcache", "seen"),
            )
            self._spec_decode = self._with_mesh(spec_jit)
            self.spec_rounds = 0  # verify rounds run
            self.spec_emitted = 0  # tokens emitted by those rounds
            # adaptive draft length (reference speculative.py's adaptive
            # th_stop_draft tunes drafting from recent acceptance; a
            # static-K XLA program cannot stop mid-draft, so this
            # switches between a few compiled K programs instead)
            ks = {draft_k}
            if adaptive_draft:
                k_ = draft_k
                while k_ > 2:
                    k_ = max(2, k_ // 2)
                    ks.add(k_)
            self._k_ladder = sorted(ks)
            self._cur_k = draft_k
            self._accept_ema: Optional[float] = None
            self._spec_exec = None
            if adaptive_draft and adapters is None:
                # AOT-compile every ladder program NOW: the first ladder
                # switch must not stall in-flight streams on a
                # mid-serving XLA compile. lower() only reads avals (no
                # donation of the live pools); the compiled executables
                # stay valid across _reset_state (same shapes).
                import contextlib

                ctx = (jax.set_mesh(self._mesh) if self._mesh is not None
                       else contextlib.nullcontext())
                args = (self.model.params, self._draft_params, self.cur,
                        self.cache, self.dcache, jax.random.PRNGKey(0),
                        jnp.asarray(self._temp), jnp.asarray(self._topk),
                        jnp.asarray(self._topp),
                        jnp.asarray(self._dosample), self.seen,
                        jnp.asarray(self._penalty))
                with ctx:
                    self._spec_exec = {
                        k_: spec_jit.lower(k_, *args).compile()
                        for k_ in self._k_ladder
                    }
        elif adaptive_draft:
            raise ValueError(
                "adaptive_draft steers the speculative draft length — "
                "pass speculative=True (CLI: --speculative) to enable it"
            )
        self.adaptive_draft = adaptive_draft
        self.truncate_prompts = truncate_prompts
        self._waiting: Optional[Request] = None  # paged OOM retry slot
        # rid -> Request whose client went away (stop-string hit,
        # disconnect, server timeout): handler threads add, the engine
        # thread frees the slot at the top of its next step — no
        # cross-thread _finish races. The Request is kept (not just the
        # rid) so the reaper can prune entries that lost the race with a
        # normal finish; a bare rid set would grow forever in a
        # long-running server.
        self._cancelled: dict[int, Request] = {}

        # ---- overload protection state ----
        self.max_queue = max_queue
        self.queue_deadline_s = queue_deadline_s
        self.deadline_s = deadline_s
        self.preemption = preemption
        # graceful-shutdown latch (begin_drain): new submits shed with
        # kind "draining" (503 + Retry-After) while in-flight work runs
        # to completion. Plain bool store/read across threads — a submit
        # racing the latch lands at most one extra request in the drain.
        self._draining = False
        # accepted-but-unfinished request count (under _stat_lock): the
        # drain's completion signal. Structural emptiness (queue/slots/
        # parked) is NOT a substitute — a request mid-admission sits in
        # none of those containers for a moment, and a drain poll in
        # that window would declare an idle engine with work in hand.
        self._inflight = 0  # guarded-by: _stat_lock
        # True while fail_all tears down after an (injected) crash:
        # crash points must not re-fire inside the cleanup's _finish
        # calls or the cleanup itself dies and the engine thread hangs
        self._cleanup = False
        # serializes the max_queue check-then-put across handler threads
        # so the admission bound is exact, not best-effort
        self._admission_lock = threading.Lock()
        # guards counters bumped from handler threads AND the engine
        # thread (requests_shed, request_timeouts) — see _bump
        self._stat_lock = threading.Lock()
        # one deadline-bearing submit arms the per-step queue sweep for
        # the engine's lifetime; deployments that never set a deadline
        # never pay the O(queue) scan under queue.mutex each step
        self._deadlines_seen = (queue_deadline_s is not None
                                or deadline_s is not None)
        # preempted requests parked in host RAM, FIFO: the resume order.
        # Only the engine thread touches it.
        self._preempted: "collections.deque[_Preempted]" = collections.deque()
        # operator/server-initiated preemption (thread-safe, like cancel)
        self._preempt_requested: set[int] = set()
        self._seq = itertools.count(1)  # slot admission age
        # observability (serving/metrics.py renders these)
        self.preemptions = 0
        self.preemption_resumes = 0
        self.requests_shed = 0  # guarded-by: _stat_lock
        self.request_timeouts = 0  # guarded-by: _stat_lock
        self.requests_completed = 0
        # exceptions out of step() that the caller's loop survived
        # (api_server._EngineThread counts them here; only that thread
        # writes) and the newest one's text
        self.step_errors = 0
        self.last_step_error: Optional[str] = None
        self.journal_corrupt_lines = 0  # set at journal attach below
        self.queue_wait = Histogram()
        # phase-latency histograms (docs/observability.md): observed
        # unconditionally — metrics are always on, tracing is opt-in
        from bigdl_tpu.serving.metrics import FAST_BUCKETS

        self.ttft = Histogram()  # submit -> first emitted token
        self.itl = Histogram(buckets=FAST_BUCKETS)  # inter-token gap
        self.prefill_seconds = Histogram(buckets=FAST_BUCKETS)
        self.decode_step_seconds = Histogram(buckets=FAST_BUCKETS)
        # satellite (ISSUE 11): resume requeue time is its OWN family —
        # folding it into queue_wait would hide preemption stalls inside
        # the admission-wait signal operators alert on
        self.resume_wait = Histogram()
        # what JAX's tracing, lowering and compiling-or-loading cost the
        # stepping thread, by the phase that paid (obs/retrace.py;
        # always on, like every counter). Only the stepping thread
        # writes; a scrape reads whole values.
        retrace.install()
        self.retrace_seconds = dict.fromkeys(RETRACE_PHASES, 0.0)
        self.retraces = dict.fromkeys(RETRACE_PHASES, 0)
        self._rt_acc: Optional[retrace.Accumulator] = None
        self._rt_seconds = 0.0
        self._rt_programs = 0
        self._chunk_retrace_s = 0.0  # paid by earlier chunks of the one
        # chunked prefill in flight; its `prefill.dispatch` span reports it
        self._step_trace: Optional[_StepTrace] = None  # the step under
        # way, while tracing: None is what every traced-only site checks
        self._step_seq = 0  # decode steps traced so far
        # the decode step in flight (plain decode keeps one, `_Flight`),
        # when the newest step read returned its tokens, and what
        # /metrics counts: steps read, by whether each was dispatched
        # ahead of its predecessor's read, and rows computed for a slot
        # that had finished by the time its step was read
        self._flight: Optional[_Flight] = None
        self._t_read = 0.0
        self.decode_steps = [0, 0]
        self.decode_rows_discarded = 0
        self._annotation = None  # jax.profiler.TraceAnnotation, imported
        # by the first traced step
        # swap-in programs (swap-OUT is a plain device_get, no jit). The
        # donated cache makes the restore an in-place scatter. Family
        # caches (nested pools / property pos) have no row-swap story:
        # preemption is gated off for them. So it is for a model generated
        # by blocks (parking would have to keep a block half revealed):
        # the option is on by default all the way up from the server, so it
        # is switched off here and not refused; `preempt()` raises by name
        if self._family_cache is not None or self.blocks is not None:
            self.preemption = False
        elif paged:
            self._swap_in = self._with_mesh(jax.jit(
                kind.swap_in, donate_argnames=("cache",)
            ))
        else:
            self._dense_swap_in = self._with_mesh(jax.jit(
                kvcache.swap_in_row, donate_argnames=("cache",)
            ))

        # crash-recovery request journal (serving/journal.py): accepted
        # requests are appended as JSONL, completions tombstoned.
        # Attaching to an existing journal AUTO-REPLAYS the previous
        # process's unfinished tail (into self.recovered_requests) with
        # the rid counter seeded past every journaled rid — replay-first
        # is an engine invariant, not a per-caller dance, because a
        # fresh rid=0 tombstone would otherwise cancel the old pending
        # rid-0 entry and silently lose it.
        if journal is not None:
            from bigdl_tpu.serving.journal import RequestJournal, replay

            stats: dict = {}
            entries, max_rid = RequestJournal.scan(journal, stats=stats)
            # corrupt lines seen at attach (interior rot / crc
            # mismatches) — exported as
            # bigdl_tpu_journal_corrupt_lines_total
            self.journal_corrupt_lines = stats.get("corrupt_lines", 0)
            # startup compaction: rewrite the journal down to its
            # pending tail (tombstoned pairs and corrupt lines dropped,
            # atomic rename) BEFORE the append handle opens — the one
            # moment compaction cannot race a live writer. The rid
            # counter still seeds from the PRE-compaction max so a rid
            # whose lines were just dropped is never reissued into any
            # overlapping recovery window.
            RequestJournal.compact(journal, entries=entries)
            self._rid = itertools.count(max_rid + 1)
            self._journal = RequestJournal(journal)
            # replay bypasses the admission bound: every entry was ACCEPTED
            # by the previous process, and a shed here would erase its only
            # journal record (replay tombstones the old rid the moment the
            # replacement submit lands) — recovery must never shrink to
            # max_queue. No thread races: __init__ hasn't returned, so no
            # handler thread can interleave a live submit.
            bound, self.max_queue = self.max_queue, None
            try:
                self.recovered_requests = replay(self, entries)
            finally:
                self.max_queue = bound

    @property
    def _geo(self) -> kvpaged.Geometry:
        """The paged pool's size as a cache kind takes it."""
        return kvpaged.Geometry(
            self.n_slots, self.max_len, self.page_size, self.n_pages,
            self.max_pages_per_row, self.quantize_kv)

    def _with_mesh(self, fn):
        if self._mesh is None:
            return fn

        @functools.wraps(fn)  # keeps the jit reachable (__wrapped__)
        def wrapped(*a, **k):
            with jax.set_mesh(self._mesh):
                return fn(*a, **k)

        return wrapped

    def _make_pool(self, force_dense: bool = False):
        """The shared KV pool, per-row positions from the start (idle rows
        park at 0); sharded over kv heads when the model is on a mesh.
        force_dense: the speculative draft pool stays dense even when the
        target pool is paged."""
        cfg = self.config
        if self._family_pool is not None:
            return self._family_pool(cfg, self.n_slots, self.max_len)
        if self._family_cache is not None:
            cache = self._family_cache(cfg, self.n_slots, self.max_len)
            return dataclasses.replace(
                cache, pos=jnp.zeros((self.n_slots,), jnp.int32)
            )
        if self.paged and not force_dense:
            cache = self.kind.make_pool(cfg, self._geo)
            if not self.kind.tp_sharded:
                return cache
        else:
            cache = kvcache.init_cache(
                cfg.num_hidden_layers, self.n_slots,
                self.max_len + self._reserve,
                cfg.num_key_value_heads, cfg.head_dim_,
                quantize_kv=self.quantize_kv,
            )
            cache = dataclasses.replace(
                cache, pos=jnp.zeros((self.n_slots,), jnp.int32)
            )
        if self._mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            # layer axis over pp stages (when present), kv heads over
            # tp: axis 3 in both layouts (dense [L, B, S, Hkv, D], paged
            # [L, n_pages, page, Hkv, D]); everything else replicates
            pp = "pp" if "pp" in self._mesh.axis_names else None
            kv_sh = NamedSharding(self._mesh, P(pp, None, None, "tp", None))
            sc_sh = NamedSharding(self._mesh, P(pp, None, None, "tp"))
            rep = NamedSharding(self._mesh, P())
            cache = jax.tree.map(
                lambda a: jax.device_put(
                    a, {5: kv_sh, 4: sc_sh}.get(a.ndim, rep)),
                cache,
            )
        return cache

    # ---- jitted pieces ----------------------------------------------------

    def _prefill_impl(self, forward, params, tokens, start, bucket,
                      lora=None):
        """Single-request prefill on its own scalar-pos cache. `lora`
        is the request's rank-bucketed adapter tree (None = base): the
        prompt's KV and first-token logits must carry the adapter or
        decode parity with the offline-merged weights breaks at token
        one."""
        cfg = self.config
        with scope("engine"):
            if self._family_cache is not None:
                cache = self._family_cache(cfg, 1, bucket)
            else:
                cache = kvcache.init_cache(
                    cfg.num_hidden_layers, 1, bucket,
                    cfg.num_key_value_heads, cfg.head_dim_,
                    quantize_kv=self.quantize_kv,
                )
            cache = dataclasses.replace(cache, start=start)
        kw = {} if lora is None else {"lora": lora}
        logits, cache = forward(
            cfg, params, tokens, cache, mode="prefill",
            last_logits_only=True, **kw
        )
        with scope("engine"):
            return logits[:, -1], cache

    def _insert_impl(self, cache, pcache, slot, pad):
        """Copy a prefilled request's KV (length `bucket`) into slot row at
        slots [0, bucket); per-row pos/start updated. Family caches (MLA
        latents) insert generically: every [L, B, ...] array field of the
        dataclass takes the prefill cache's row at the slot index.
        Families with nested/recurrent caches provide engine_insert."""
        if self._family_insert is not None:
            return self._family_insert(cache, pcache, slot, pad)
        if self._family_cache is not None:
            bucket = None
            upd = {}
            for f in dataclasses.fields(cache):
                v = getattr(cache, f.name)
                pv = getattr(pcache, f.name)
                if f.name in ("pos", "start"):
                    continue
                if isinstance(v, jax.Array) and v.ndim >= 2:
                    if bucket is None and v.ndim >= 3:
                        bucket = pv.shape[2]
                    idx = (0, slot) + (0,) * (v.ndim - 2)
                    upd[f.name] = jax.lax.dynamic_update_slice(
                        v, pv.astype(v.dtype), idx
                    )
            upd["pos"] = cache.pos.at[slot].set(bucket)
            upd["start"] = cache.start.at[slot].set(pad)
            return dataclasses.replace(cache, **upd)
        return kvcache.insert_row(cache, pcache, slot, pad)

    def _paged_prefill_impl(self, forward, params, pool, tables, pos0,
                            tokens, last_idx, slot, lora=None):
        """Tail prefill for ONE slot, on what its cache kind makes of the
        pool's arrays `pool` (donated) behind the row's own `tables`
        (`CacheKind.row_view`): for KV pages the row's OWN pages, gathered
        once for every layer into a dense one-row cache at the row's scalar
        position (what a radix hit shares and what earlier chunks wrote
        come with them), prefilled as `_prefill_impl` prefills a dense
        engine's row (a contiguous write and the flash kernel, where
        `forward` takes it), and only the pages this call wrote scattered
        back (`write_back`). The pool is the operand of that gather and of
        that scatter and of nothing else: never the layer loop's carry,
        never sliced by layer. A kind whose forward writes through the
        table runs on the pool itself, into state row `slot` where it
        keeps one.
        tokens are RIGHT-padded to a bucket; last_idx selects the real
        last token's logits (pad writes land at slots >= pos and are
        overwritten by decode; a state leaves them out). `lora` = the
        request's rank-bucketed adapter tree (every chunk of a chunked
        prefill carries it)."""
        kind, cfg = self.kind, self.config
        with scope("engine"):
            pool, row = kind.row_view(pool, tables, pos0, last_idx, slot,
                                      cfg, self._geo)
        kw = dict(kind.forward_kw(last_idx))
        if self._head_at_last:
            kw.setdefault("logits_at", last_idx)
        at = 0 if "logits_at" in kw else last_idx
        if lora is not None:
            kw["lora"] = lora
        logits, row, experts = self._forward_routing(
            forward, params, tokens, row, "prefill", kw)
        with scope("engine"):
            pool = kind.write_back(pool, row, tokens.shape[1], last_idx, cfg)
            if experts is None:  # or what the kind's forward chose
                return logits[0, at], pool, kind.report(row)
            return logits[0, at], pool, experts[:, 0]

    def _forward_routing(self, forward, params, tokens, cache, mode, kw):
        """`forward`, and for a sparse-expert model every position's top-k
        expert ids [L, B, T, k] as small integers (None for a dense
        model: nothing is traced for it)."""
        if not self.moe_routing:
            return forward(self.config, params, tokens, cache, mode=mode,
                           **kw) + (None,)
        logits, cache, routing = forward(
            self.config, params, tokens, cache, mode=mode, moe_routing=True,
            **kw)
        with scope("engine"):
            return logits, cache, routing.astype(
                _expert_id_dtype(self._expert_ids))

    def _first_token_impl(self, logits, rng, temp, topk, topp, dosample,
                          penalty, row, slot, cur, seen):
        """An admission's device work as ONE program: split the engine's
        key, penalise and sample the first token from the prefill's
        last-row logits, arm the slot's `cur` and `seen` (both donated),
        and pack what the host reads into one int32 vector
        (_read_first_token). Everything a request chooses is a traced input, so
        one executable serves greedy, sampled and penalised requests;
        sample_token_per_row's cond keeps the all-greedy case off the
        full-vocab sort. The key stream is the eager one's: the engine's
        key is split once per admission, as once per decode step, so an
        engine seeded alike and fed alike samples alike."""
        from bigdl_tpu.generate import apply_repetition_penalty

        with scope("engine"):
            logits = logits.reshape(1, -1)
            rng, key = jax.random.split(rng)
        with scope("sample"):
            # no penalty arrives as 1.0 with an all-False row: the identity
            logits = apply_repetition_penalty(logits, row[None], penalty)
            first = sample_token_per_row(
                logits, key, temp[None], topk[None], topp[None],
                dosample[None])[0]
        with scope("engine"):
            row_lp = jax.nn.log_softmax(
                logits.astype(jnp.float32).reshape(-1))

            out = [first[None], _bits(row_lp[first])[None]]
            if self.logprobs_top_k:  # static: an engine constant
                tv, ti = jax.lax.top_k(row_lp, self.logprobs_top_k)
                out += [ti.astype(jnp.int32), _bits(tv)]
            cur = cur.at[slot].set(first)
            seen = seen.at[slot].set(row).at[slot, first].set(True)
            return cur, seen, rng, jnp.concatenate(out)

    def _decode_impl(self, forward, params, cur, cache, key,
                     temp, topk, topp, dosample, seen, penalty,
                     lora=None):
        from bigdl_tpu.generate import apply_repetition_penalty

        # lora = the batched per-slot adapter tree (_gather_blora):
        # [L, B, rb, in]/[L, B, out, rb] leaves + a [B] scale, applied
        # as an einsum epilogue on each projection's fused dequant-GEMM
        # output (ops/linear.lora_epilogue) — adapter-less slots carry
        # zero-padded rows and a 0 scale, contributing exactly nothing
        kw = {} if lora is None else {"lora": lora}
        with scope("engine"):
            tokens = cur[:, None]
        logits, cache, experts = self._forward_routing(
            forward, params, tokens, cache, "decode", kw)
        # all-default batches (every penalty 1.0) skip the O(slots x V)
        # rewrite, mirroring sample_token_per_row's all-greedy guard
        with scope("sample"):
            last = logits[:, -1]
            step = jax.lax.cond(
                jnp.any(penalty != 1.0),
                lambda: apply_repetition_penalty(last, seen, penalty),
                lambda: last,
            )
            nxt = sample_token_per_row(step, key, temp, topk, topp,
                                       dosample)
        with scope("engine"):
            # chosen-token logprob without materializing [B, V]
            # log-softmax: gather the logit, subtract the row's logsumexp
            step32 = step.astype(jnp.float32)
            lse = jax.scipy.special.logsumexp(step32, axis=-1)
            lp = (jnp.take_along_axis(step32, nxt[:, None], axis=-1)[:, 0]
                  - lse)
            seen = seen.at[jnp.arange(seen.shape[0]), nxt].set(True)

            # what the host reads, one int32 row a slot (_read_step): `nxt`
            # is the next step's input and never leaves the device
            out = [nxt[:, None], _bits(lp)[:, None]]
            if self.logprobs_top_k:  # static: compiles only when opted in
                tv, ti = jax.lax.top_k(step32, self.logprobs_top_k)
                out += [ti.astype(jnp.int32), _bits(tv - lse[:, None])]
            if experts is not None:  # [L, B, 1, k] -> [B, L * k]
                out.append(jnp.swapaxes(experts[:, :, 0], 0, 1).reshape(
                    nxt.shape[0], -1).astype(jnp.int32))
            chose = None if self.kind is None else self.kind.report(cache)
            if chose is not None:  # a kind whose forward reports (static)
                out.append(chose)
            return nxt, jnp.concatenate(out, axis=1), cache, seen

    def _spec_decode_impl(self, forward, k_draft, params, dparams, cur, cache,
                          dcache, key, temp, topk, topp, dosample, seen,
                          penalty, lora=None):
        """One speculative round for the whole slot pool. Returns
        (choice [B, K], lp_all [B, K], n_acc [B], cur' [B], cache,
        dcache, seen): slot b emits choice[b, :n_acc[b]+1], with
        lp_all carrying each token's target logprob.

        Cache discipline (decode/speculative.py's crop, per-row): the
        draft scan advances dcache.pos by K and the verify forward
        advances cache.pos by K; both roll back to pos + n_acc + 1 — a
        vector op thanks to per-row positions. Entries above pos hold
        stale drafts that are masked out and overwritten next round.
        Acceptance caps at K-1 because the draft pool only holds KV for
        cur, d0..d_{K-2}.

        Acceptance rule per row: greedy rows match the target argmax
        (byte-identical to plain serving); sampling rows run rejection
        acceptance (exact sampling law, decode/speculative.py's
        rejection_accept); repetition-penalty rows accept 0."""
        from bigdl_tpu.generate import apply_repetition_penalty

        cfg = self.config
        K = k_draft  # static: one compiled program per ladder value

        def draft_step(carry, _):
            tok, dc = carry
            with scope("engine"):
                tok = tok[:, None]
            lg, dc = forward(cfg, dparams, tok, dc, mode="decode")
            with scope("sample"):
                nxt = jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)
            return (nxt, dc), nxt

        (_, dcache), drafts = jax.lax.scan(
            draft_step, (cur, dcache), None, length=K
        )
        with scope("engine"):
            drafts = jnp.swapaxes(drafts, 0, 1)  # [B, K]
            verify_in = jnp.concatenate(
                [cur[:, None], drafts[:, :K - 1]], axis=1)
        # adapter-aware verification: the TARGET forward applies the
        # batched per-slot adapter tree (the same one plain decode
        # uses), so accepted tokens follow the adapter-shifted target
        # law exactly — emitted tokens match non-speculative adapter
        # decode token-for-token. The draft above stays base/dense
        # (drafts are advisory: any draft content yields the same
        # output law, only the acceptance RATE moves)
        kw = {} if lora is None else {"lora": lora}
        tlogits, cache = forward(
            cfg, params, verify_in, cache, mode="prefill", **kw
        )
        with scope("sample"):  # what each row accepts
            tlogits = tlogits.astype(jnp.float32)
            greedy = jnp.argmax(tlogits, axis=-1).astype(jnp.int32)  # [B, K]

            # acceptance per decode mode: greedy rows match the target's
            # argmax (byte-identical to plain serving); sampling rows run
            # rejection acceptance against the full per-position sampling
            # distribution (exact output law — decode/speculative.py);
            # repetition-penalty rows accept 0 and take the penalty-adjusted
            # sampler token at position 0 (their distribution depends on
            # tokens emitted earlier in the same round)
            from bigdl_tpu.decode.speculative import rejection_accept
            from bigdl_tpu.generate import filter_logits_per_row

            pen1 = penalty == 1.0
            row_greedy = ~dosample & pen1
            row_sampled = dosample & pen1
            k_acc, k_pen = jax.random.split(key)

            def accept_mixed():
                probs = jax.nn.softmax(
                    filter_logits_per_row(tlogits, temp, topk, topp), axis=-1
                )
                return rejection_accept(
                    k_acc, probs, drafts, greedy, row_greedy, row_sampled
                )

            def accept_greedy_only():
                # all-greedy pools (the common serving case) skip the two
                # full [B, K, V] sorts + softmax of the filtered-probs path
                acc = (drafts[:, : K - 1] == greedy[:, : K - 1]) \
                    & row_greedy[:, None]
                n = jnp.sum(jnp.cumprod(acc.astype(jnp.int32), axis=1), axis=1)
                return n, jnp.take_along_axis(greedy, n[:, None], axis=1)[:, 0]

            n_acc, extra = jax.lax.cond(
                jnp.any(row_sampled), accept_mixed, accept_greedy_only
            )

            def penalty_sample():
                step0 = apply_repetition_penalty(tlogits[:, 0], seen, penalty)
                return sample_token_per_row(
                    step0, k_pen, temp, topk, topp, dosample
                )

            # penalty rows accept 0 and take the penalty-adjusted sampler
            # token at position 0; all-pen1 batches skip the extra sampler
            samp0 = jax.lax.cond(
                jnp.any(~pen1), penalty_sample, lambda: extra
            )
            extra = jnp.where(pen1, extra, samp0)

        with scope("engine"):
            pos = jnp.arange(K, dtype=jnp.int32)[None, :]
            choice = jnp.where(
                pos < n_acc[:, None], drafts,
                jnp.where(pos == n_acc[:, None], extra[:, None], greedy),
            )
            cur2 = extra
            # [B, K] target logprob of each emitted token (gather - logsumexp,
            # no [B, K, V] log-softmax materialization)
            lp_all = (
                jnp.take_along_axis(
                    tlogits, choice[..., None], axis=-1)[..., 0]
                - jax.scipy.special.logsumexp(tlogits, axis=-1)
            )

            def lp0_penalized():
                # penalty rows sampled position 0 from the penalty-adjusted
                # distribution — report the logprob they were drawn from,
                # matching the plain path (review finding, round 5)
                step0 = apply_repetition_penalty(tlogits[:, 0], seen, penalty)
                return (jnp.take_along_axis(
                    step0, choice[:, 0][:, None], axis=-1)[:, 0]
                    - jax.scipy.special.logsumexp(step0, axis=-1))

            lp0 = jax.lax.cond(
                jnp.any(penalty != 1.0), lp0_penalized, lambda: lp_all[:, 0]
            )
            lp_all = lp_all.at[:, 0].set(
                jnp.where(penalty != 1.0, lp0, lp_all[:, 0])
            )

            cache = dataclasses.replace(cache, pos=cache.pos - K + n_acc + 1)
            dcache = dataclasses.replace(
                dcache, pos=dcache.pos - K + n_acc + 1)
            rows = jnp.arange(seen.shape[0])
            # penalty rows emit exactly cur2; spec rows don't read `seen`
            seen = seen.at[rows, cur2].set(True)
            return choice, lp_all, n_acc, cur2, cache, dcache, seen

    # ---- host API ---------------------------------------------------------

    def submit(
        self,
        prompt: list[int],
        max_new_tokens: int = 64,
        stream: Optional[queue.SimpleQueue] = None,
        do_sample: Optional[bool] = None,
        temperature: Optional[float] = None,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        repetition_penalty: Optional[float] = None,
        eos_token_id: Optional[int] = None,
        queue_deadline_s: Optional[float] = None,
        deadline_s: Optional[float] = None,
        adapter: Optional[str] = None,
    ) -> Request:
        if repetition_penalty is not None and repetition_penalty <= 0:
            raise ValueError(
                f"repetition_penalty must be > 0, got {repetition_penalty}"
            )
        if top_k is not None:
            # <=0 disables (the stack-wide convention); > vocab caps
            top_k = (None if top_k <= 0
                     else min(top_k, self.config.vocab_size))
        # the decode window must fit the cache alongside a minimal prompt
        # bucket; clamp instead of letting _admit derive a zero/negative
        # bucket (which would crash the engine thread)
        max_new_tokens = max(1, min(max_new_tokens, self.max_len - 16))
        req = Request(
            rid=next(self._rid), prompt=list(prompt),
            max_new_tokens=max_new_tokens, stream=stream,
            do_sample=do_sample, temperature=temperature,
            top_k=top_k, top_p=top_p,
            repetition_penalty=repetition_penalty,
            eos_token_id=eos_token_id,
            adapter=adapter,
            queue_deadline_s=(queue_deadline_s
                              if queue_deadline_s is not None
                              else self.queue_deadline_s),
            deadline_s=(deadline_s if deadline_s is not None
                        else self.deadline_s),
            submit_ts=self._clock(),
        )
        tr = self.tracer
        if tr is not None and tr.enabled:
            tr.instant("submit", ts=req.submit_ts, tid=req.rid,
                       cat="request", rid=req.rid,
                       prompt_tokens=len(req.prompt))
        if req.queue_deadline_s is not None or req.deadline_s is not None:
            self._deadlines_seen = True  # benign handler-thread race: a
            # plain bool store, read by the engine thread next step
        if not req.prompt:
            req.error = "empty prompt — nothing to generate"
            req.finish_reason = "invalid"
            req.done = True
            self._note_finish(req, req.submit_ts)
            if stream is not None:
                stream.put(None)
            return req
        bad = [t for t in req.prompt
               if not 0 <= t < self.config.vocab_size]
        if bad:
            # wrong-tokenizer ids would silently index-clip into garbage
            # generation; fail the request like the over-long case
            req.error = (
                f"prompt token id {bad[0]} outside [0, "
                f"{self.config.vocab_size}) — wrong tokenizer for this "
                "model?"
            )
            req.finish_reason = "invalid"
            req.done = True
            self._note_finish(req, req.submit_ts)
            if stream is not None:
                stream.put(None)
            return req
        if req.adapter is not None and self.adapters is None:
            # a config mistake, not overload: the caller named an
            # adapter on an engine with no registry — serving the base
            # silently would be the wrong model for that tenant
            req.error = (
                f"request names adapter {req.adapter!r} but this engine "
                "has no adapter registry (construct it with adapters=)"
            )
            req.finish_reason = "invalid"
            req.done = True
            self._note_finish(req, req.submit_ts)
            if stream is not None:
                stream.put(None)
            return req
        refused = None if self.blocks is None else self.blocks.refuses(req)
        if refused is not None:
            req.error = refused
            req.finish_reason = "invalid"
            req.done = True
            self._note_finish(req, req.submit_ts)
            if stream is not None:
                stream.put(None)
            return req
        limit = self.max_len - max_new_tokens
        if len(req.prompt) > limit and not self.truncate_prompts:
            # FAIL FAST: admission used to tail-truncate silently, which
            # generates from a different context than the caller sent —
            # wrong output with no signal (round-5 stress finding).
            # vLLM-style rejection is the default; truncation is opt-in.
            req.error = (
                f"prompt ({len(req.prompt)} tokens) exceeds the slot "
                f"capacity ({limit} = max_len {self.max_len} - "
                f"max_new_tokens {max_new_tokens}); shorten the prompt, "
                "raise max_len, or construct the engine with "
                "truncate_prompts=True to keep the prompt tail"
            )
            req.finish_reason = "invalid"
            req.done = True
            self._note_finish(req, req.submit_ts)
            if stream is not None:
                stream.put(None)
            return req
        if self._draining:
            # graceful shutdown in progress: reject BEFORE the journal
            # append (a drained request was never accepted, and its
            # entry would resurrect it at the next start as work the
            # client already re-sent elsewhere)
            self._shed_request(req, "draining", (
                "server is draining for shutdown; retry against a "
                "fresh instance"
            ), journaled=False)
            return req
        if self.max_queue is None:
            # unbounded admission needs no check-then-put atomicity:
            # don't serialize every handler thread's submit (journal
            # append + flush included) behind one lock for a bound that
            # can never reject
            with self._stat_lock:
                self._inflight += 1
            if self._journal is not None:
                self._journal.record_submit(req)
            self._queue.put(req)
            return req
        shed_qsize = None
        with self._admission_lock:
            qsize = self._queue.qsize()
            if qsize >= self.max_queue:
                # bounded admission: overload surfaces as a fast explicit
                # rejection the client can retry, not as unbounded queue
                # latency. Checked BEFORE the journal append — a shed
                # request was never accepted, so a crash must not replay
                # it. Only the DECISION needs the lock's check-then-put
                # atomicity; the rejection itself (request-log write,
                # stream put) is blocking work that must not convoy
                # every other submit behind it (graftlint LCK102), so
                # it runs after release.
                shed_qsize = qsize
            else:
                with self._stat_lock:
                    self._inflight += 1
                if self._journal is not None:
                    self._journal.record_submit(req)
                self._queue.put(req)
        if shed_qsize is not None:
            self._shed_request(req, "queue_full", (
                f"queue full: {shed_qsize} waiting >= "
                f"max_queue {self.max_queue}; retry later"
            ), journaled=False)
        return req

    def _slot_sampling(self, req: Request) -> tuple[float, int, float, bool]:
        """Resolve a request's sampling params against engine defaults."""
        g = self.gen
        temp = req.temperature if req.temperature is not None else g.temperature
        topk = req.top_k if req.top_k is not None else (g.top_k or 0)
        topp = req.top_p if req.top_p is not None else (
            g.top_p if g.top_p is not None else 1.0
        )
        dosample = req.do_sample if req.do_sample is not None else g.do_sample
        return float(temp), int(topk or 0), float(topp), bool(dosample)

    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self._slots):
            if s.req is None:
                return i
        return None

    # ---- paged page management -------------------------------------------

    def _admit_paged(self, req: Request, slot: int) -> bool:
        """Tail-truncate, book the slot's pages (PageTable.reserve: the
        longest cached prefix shared, fresh pages for the remainder),
        copy the page a mid-page divergence shares, then prefill —
        monolithically, or as a chunk plan the step loop advances one
        chunk at a time (prefill_chunk_tokens). False = not enough
        pages; retry later."""
        limit = self.max_len - req.max_new_tokens
        if len(req.prompt) > limit:
            req.prompt = req.prompt[-limit:]
        prompt = req.prompt
        try:
            plan = self.pages.reserve(slot, prompt, ns=req.adapter)
        except NeverFits as e:
            self._fail_request(req, str(e))
            return True  # consumed (failed), keep admitting others
        if plan is None:
            return False
        # admission is committed from here on (every later path prefills
        # and activates) — stamp it so queue_wait/queued exclude prefill
        self._mark_admitted(req)
        if plan.copy is not None:
            self.cache = self._copy_page(
                self.cache, jnp.asarray(plan.copy[0]),
                jnp.asarray(plan.copy[1]),
            )
        rest = self._prefill_len(prompt) - plan.covered
        chunk = self.prefill_chunk_tokens
        chunked = chunk is not None and rest > chunk
        st = _PrefillState(
            req=req, slot=slot, row=plan.row, written=plan.covered,
            path=plan.path, chunk=chunk if chunked else rest,
            start=plan.covered, wrow=plan.wrow,
        )
        if chunked:
            # chunk plan: the slot is HELD (req set, active False, its
            # engine block-table row left at the scratch page) and
            # step() advances one chunk per iteration via
            # _advance_prefill — decode of the running batch proceeds
            # between chunks, so this prompt cannot stall it by more
            # than one chunk
            self._slots[slot] = _Slot(req=req, seq=next(self._seq))
            self._prefilling = st
        else:
            self._prefill_chunk(st)  # monolithic: the rest is one chunk
        return True

    def _prefill_len(self, prompt: list) -> int:
        """Tokens of `prompt` an admission's prefill stores: all of them,
        or the whole blocks of a model generated by blocks (the rest open
        its first block)."""
        if self.blocks is None:
            return len(prompt)
        return self.blocks.prefill_len(len(prompt))

    def _advance_prefill(self) -> None:
        """Run AT MOST ONE chunk of the at-most-one in-flight chunked
        prefill: the per-step decode stall a new prompt can inflict is
        bounded by one chunk."""
        if self._prefilling is not None:
            self._retrace_mark("other")  # this step's sweeps and admissions
            self._prefill_chunk(self._prefilling)

    def _prefill_chunk(self, st: _PrefillState) -> None:
        """Prefill the next `st.chunk` tokens of the prompt through the
        slot's own row. The final chunk (of a monolithic prefill, the
        only one) installs the real block table, registers radix nodes,
        and activates the slot (first token emits — TTFT closes here)."""
        with self._phase("prefill.dispatch", st.req.rid):
            logits_last = self._dispatch_chunk(st)
        if logits_last is not None:
            self._activate(st.slot, st.req, logits_last)

    def _dispatch_chunk(self, st: _PrefillState):
        """The chunk's host work up to its activation; returns the last
        chunk's logits, None after an earlier one."""
        prompt = st.req.prompt
        stored = self._prefill_len(prompt)
        rem = stored - st.written
        n = min(st.chunk, rem)
        last = n == rem
        bucket = prefill_bucket(n, self.max_len - st.written)
        toks = np.full((1, bucket), self.gen.pad_token_id, np.int32)
        toks[0, :n] = prompt[st.written: st.written + n]  # RIGHT pad:
        # writes past pos get overwritten by decode, masked meanwhile
        self.prefill_chunks += 1
        tables = (jnp.asarray(st.row[None]),
                  None if st.wrow is None else jnp.asarray(st.wrow[None]))
        kind = self.kind
        logits_last, pool, moe = self._paged_prefill(
            self.model.params, kind.leaves(self.cache), tables,
            jnp.asarray([st.written], jnp.int32), jnp.asarray(toks),
            jnp.asarray(max(n - 1, 0)), np.asarray([st.slot], np.int32),  # sent
            # only where the kind's program reads it
            lora=self._prefill_lora(st.req))
        self.cache = kind.with_leaves(self.cache, pool)
        if moe is not None:
            st.moe.append((moe, n))
        kind.note_chunk(st, self.config, self._geo, bucket, n, self.cache)
        st.written += n
        if not last:
            self._chunk_retrace_s += self._retrace_mark("prefill.dispatch")
            return None
        slot = st.slot
        self._prefilling = None
        self.pages.install(slot, st.row, stored, st.wrow)
        self.cache = dataclasses.replace(
            self.cache,
            pos=self.cache.pos.at[slot].set(stored),
            start=self.cache.start.at[slot].set(0),
        )
        self.pages.register_prefix(slot, prompt, st.path,
                                   ns=st.req.adapter)
        self._admit_moe, self._admit_moe_start = st.moe, st.start
        self._admit_args = kind.prefill_args(st)
        if self.speculative:
            # prefix-cache hits only save TARGET prefill; the draft
            # always prefills its full context into the dense draft pool
            self._admit_draft(slot, prompt,
                              self.max_len - st.req.max_new_tokens)
        return logits_last

    def _admit_draft(self, slot: int, prompt: list[int], limit: int) -> None:
        """Left-pad-prefill the speculative draft pool's row for a newly
        admitted request — one definition shared by the dense and paged
        admission paths so their draft discipline can never drift."""
        bucket = min(round_up(max(len(prompt), 16), 64), limit)
        dprompt = prompt[-bucket:]
        tokens = np.full((1, bucket), self.gen.pad_token_id, np.int32)
        tokens[0, bucket - len(dprompt):] = dprompt
        pad = bucket - len(dprompt)
        _, dpcache = self._prefill(
            self._draft_params, jnp.asarray(tokens),
            jnp.asarray([pad], jnp.int32), bucket=bucket,
        )
        self.dcache = self._insert(
            self.dcache, dpcache, jnp.asarray(slot), jnp.asarray(pad)
        )

    def _ensure_decode_pages(self, need_tokens: int = 1) -> None:
        """Before a decode step, every active slot whose next `need_tokens`
        writes would run past its allocation gets more pages (speculative
        verify writes draft_k tokens before rolling back — the pages must
        exist or the scatter clamps into a neighbour page). A slot that
        cannot extend because the POOL is dry preempts a victim to host
        RAM (youngest-first) instead of silently truncating its output;
        'length' remains only for true logical capacity (max_pages_per_row)
        or a pool that provably cannot support the request at all."""
        for i in np.nonzero(self.active)[0]:
            slot = int(i)
            while self.active[slot] and self.pages.short(slot, need_tokens):
                if self.pages.row_full(slot):  # logical capacity hit
                    self._finish(slot, "length")
                    break
                pg = self._alloc_page_preempting(slot)
                if pg is None:
                    if self.active[slot]:  # not self-preempted: stuck
                        self._finish(slot, "length")
                    break
                self.pages.extend(slot, pg)

    # ---- preemption (host-RAM KV swap) ------------------------------------

    def _alloc_page_preempting(self, slot: int) -> Optional[int]:
        """PageTable.alloc, escalating to preemption under pool pressure:
        swap victims out (youngest first) until a page frees. With no other
        victim, the requesting slot preempts ITSELF — but only if it has
        made progress since its last resume; a no-progress self-preempt
        proves the pool cannot support the request (swap-in would need
        the very pages that are missing) and would livelock."""
        while True:
            pg = self.pages.alloc()
            if pg is not None or not self.preemption:
                return pg
            victim = self._pick_victim(exclude=slot)
            if victim is not None:
                self._preempt_slot(victim)
                continue
            if self._abort_prefill_for_pages():
                continue  # the chunk plan yielded its pages
            s = self._slots[slot]
            if s.resumed_pos < 0 or self.pages.pos[slot] > s.resumed_pos:
                self._preempt_slot(slot)  # caller sees the slot inactive
            return None

    def _abort_prefill_for_pages(self) -> bool:
        """Yield a mid-chunked-prefill plan's pages to allocation
        pressure: a decoding stream must not be truncated (nor a
        parked request failed) while an inactive chunk plan sits on
        the very pages it needs. The plan has no decode state yet, so
        'preempting' it is simply releasing its slot and putting the
        request back at the queue's FRONT (it was the most recent pop
        — FIFO order is preserved); prefill restarts later from
        whatever the cache still covers, and output is unaffected
        because nothing was emitted. The re-wait is not re-counted in
        queue_wait (admit_ts stays from the first admission)."""
        st = self._prefilling
        if st is None:
            return False
        self._free_slot_state(st.slot)  # releases pages + clears plan
        with self._queue.mutex:  # raw deque surgery, _sweep_queue style
            self._queue.queue.appendleft(st.req)
        return True

    def _pick_victim(self, exclude: int) -> Optional[int]:
        """The youngest slot, the one most recently (re)admitted:
        it loses the least progress and, being FIFO-resumed behind older
        preempted work, cannot starve the oldest request — the oldest is
        never chosen while anyone else is active, so it always completes
        and frees its pages."""
        cands = [(s.seq, i) for i, s in enumerate(self._slots)
                 if s.req is not None and i != exclude
                 and self.active[i]]  # a mid-chunked-prefill slot has
        # no resumable decode state to swap; it is never a victim
        if not cands:
            return None
        return max(cands)[1]

    def _preempt_slot(self, slot: int) -> None:
        """Swap a slot's KV to host RAM and requeue its request with the
        tokens generated so far; the slot frees WITHOUT finishing the
        request (its stream sees a pause, never a sentinel). Decode after
        the matching swap-in is bit-exact: the blob preserves the cache
        bytes and the resume restores cur/seen/sampling state untouched."""
        req = self._slots[slot].req
        # the copy needs the pool at rest and exact positions: read the
        # step in flight first. It may have been this request's last
        self._drain()
        s = self._slots[slot]
        if s.req is not req or not self.active[slot]:
            return
        now = self._clock()
        self._flush_decode_window(slot, now)
        if self.paged:
            pos = self.pages.pos[slot]
            keep = self.pages.kv_pages(slot)
            n_keep = len(keep)
            # the pages, with a state row or a second group's where the
            # kind has them
            blob = self.kind.swap_out(self.cache, keep, slot,
                                      self.pages.window_kv_pages(slot))
            start = 0
        else:
            pos = int(np.asarray(self.cache.pos[slot]))
            start = int(np.asarray(self.cache.start[slot]))
            # only the live region [0, pos) travels; bucketing to 64
            # bounds the distinct swap-in program shapes (mirrors the
            # paged twin's one-program-per-page-count)
            n = min(round_up(max(pos, 1), 64), self.cache.max_len)
            blob = kvcache.swap_out_row(self.cache, slot, n)
            n_keep = 0
        entry = _Preempted(
            req=req, cur=int(np.asarray(self.cur[slot])),
            remaining=s.remaining, eos=s.eos, pos=pos, start=start,
            seq=s.seq, temp=float(self._temp[slot]),
            topk=int(self._topk[slot]), topp=float(self._topp[slot]),
            dosample=bool(self._dosample[slot]),
            penalty=float(self._penalty[slot]),
            seen=np.asarray(self.seen[slot]), blob=blob, n_pages=n_keep,
        )
        req.preemptions += 1
        self.preemptions += 1
        req.preempt_ts = now  # the "preempted" span + resume_wait
        # histogram close on this stamp at swap-in
        tr = self.tracer
        if tr is not None and tr.enabled:
            tr.instant("swap_out", ts=now, tid=req.rid, cat="request",
                       rid=req.rid, pos=pos, pages=n_keep)
        self._preempted.append(entry)
        # free the slot WITHOUT _finish: the request is alive, just parked
        self._free_slot_state(slot)
        if not self.paged:
            self.cache = dataclasses.replace(
                self.cache, pos=self.cache.pos.at[slot].set(0)
            )

    def _resume_preempted(self, entry: _Preempted, slot: int) -> bool:
        """Swap a parked request back into `slot` (fresh pages / any free
        row — physical placement is irrelevant, the block table / row
        index re-maps it). False = the pool cannot hold the restore yet;
        the entry stays queued and newer admissions wait behind it."""
        req = entry.req
        if self.paged:
            fresh = self.pages.restore(slot, entry.n_pages, entry.pos)
            if fresh is None:  # retry when pages free up
                return False
            into = (jnp.asarray(fresh, jnp.int32), jnp.asarray(slot),
                    jnp.asarray(self.pages.win_pages[slot], jnp.int32))
            self.cache = self._swap_in(
                self.cache, self.kind.leaves(entry.blob), into)
            self.cache = dataclasses.replace(
                self.cache,
                pos=self.cache.pos.at[slot].set(entry.pos),
                start=self.cache.start.at[slot].set(0),
            )
        else:
            k, v, ks, vs = entry.blob
            self.cache = self._dense_swap_in(
                self.cache, k, v, ks, vs, jnp.asarray(slot),
                jnp.asarray(entry.pos, jnp.int32),
                jnp.asarray(entry.start, jnp.int32),
            )
        self.cur = self.cur.at[slot].set(entry.cur)
        self.seen = self.seen.at[slot].set(jnp.asarray(entry.seen))
        self._temp[slot], self._topk[slot] = entry.temp, entry.topk
        self._topp[slot], self._dosample[slot] = entry.topp, entry.dosample
        self._penalty[slot] = entry.penalty
        self._slots[slot] = _Slot(
            req=req, remaining=entry.remaining, eos=entry.eos,
            seq=entry.seq, resumed_pos=entry.pos,
        )
        # the parked request kept its adapter reference (host-RAM
        # residency survived the swap); re-point the slot at it
        self._set_slot_adapter(slot, req)
        self.active[slot] = True
        if self.speculative:
            # the draft pool was not swapped (drafts are advisory — any
            # draft content yields the same emitted tokens); rebuild the
            # row from the full context so acceptance rates stay healthy
            self._admit_draft(slot, req.prompt + req.out_tokens,
                              self.max_len - req.max_new_tokens)
        now = self._clock()
        if req.preempt_ts is not None:
            parked = max(now - req.preempt_ts, 0.0)
            # satellite (ISSUE 11): the requeue wait of a preempted-and-
            # resumed request is its own histogram — it was previously
            # invisible (admit_ts is already set, so queue_wait never
            # fires again for a resume)
            self.resume_wait.observe(parked)
            req.preempted_s += parked
            tr = self.tracer
            if tr is not None and tr.enabled:
                tr.complete("preempted", req.preempt_ts, parked,
                            tid=req.rid, cat="request", rid=req.rid,
                            pages=entry.n_pages)
            req.preempt_ts = None
        if req.last_token_ts is not None:
            # rebase the inter-token clock past the parked stretch: the
            # stall is accounted in resume_wait_seconds, and the next
            # decode window must open after the "preempted" span closes
            req.last_token_ts = now
        self.preemption_resumes += 1
        return True

    def preempt(self, req: Request) -> None:
        """Thread-safe operator/server-initiated preemption: park the
        request's KV in host RAM at the engine thread's next step and
        requeue it for resume. Works for dense and paged pools. Only a
        request currently DECODING in a slot is acted on — one that is
        still queued, already parked, or finished has no device KV to
        swap, so the call is a no-op for it (the marker is dropped at the
        next step rather than lingering to ambush a later admission)."""
        if self._family_cache is not None:
            raise NotImplementedError(
                f"preemption is not wired for "
                f"{self.config.model_type}'s family cache"
            )
        if self.blocks is not None:
            raise NotImplementedError(
                f"preemption inside a block is not wired for "
                f"{self.config.model_type} yet (ROADMAP R9)")
        self._preempt_requested.add(req.rid)

    def _reap_preempt_requests(self) -> None:
        if not self._preempt_requested:
            return
        # swap-then-clear: rids that don't match a live slot are dropped,
        # not kept — handler threads may add() concurrently and those
        # land in the fresh set for the next step
        pending, self._preempt_requested = self._preempt_requested, set()
        for i, s in enumerate(self._slots):
            if (s.req is not None and s.req.rid in pending
                    and self.active[i]):
                # mid-chunked-prefill slots are skipped like queued
                # requests: no decode state exists to park yet (the
                # marker drops; re-request once decoding)
                self._preempt_slot(i)

    # ---- multi-tenant LoRA adapters (serving/adapters.py; §7) -------------

    def _resolve_adapter(self, req: Request) -> bool:
        """Acquire the request's named adapter at admission: load/verify
        through the registry (LRU-refreshing it) and take the request's
        ONE reference — held across preemption parking and the paged
        OOM-retry wait, released at the terminal finish. False = the
        adapter is missing/corrupt/mismatched: the request finishes
        "error" with the structured message and the caller admits the
        next one (a bad tenant artifact must never fail_all a batch)."""
        from bigdl_tpu.serving.adapters import AdapterError

        if req.rid in self._adapter_refs:  # OOM-retry / prefill-abort
            # re-admission: the reference is already held; re-page-in
            # best-effort (the pages may have been evicted while the
            # request was parked — a dry pool just means the gather
            # falls back to the registry's host copy)
            if self._pager is not None:
                try:
                    self._pager.ensure(self._adapter_refs[req.rid],
                                       req.rid)
                except AdapterError:
                    pass
            return True
        try:
            entry = self.adapters.acquire(req.adapter)
        except AdapterError as e:
            self._fail_request(req, str(e))
            return False
        try:
            self._check_adapter_dims(entry)
        except AdapterError as e:
            # wrong-base artifact: count it as a load failure and drop
            # it from residency (reject) — a resident entry every
            # request errors on would read as a healthy registry in
            # /metrics while squatting on budget
            self.adapters.reject(entry)
            self._fail_request(req, str(e))
            return False
        self._adapter_refs[req.rid] = entry
        if self._pager is not None:
            try:
                self._pager.ensure(entry, req.rid)
            except AdapterError as e:
                # injected page-in stall (serving/faults.py): quarantine
                # exactly this request — release the reference we just
                # took so the registry's refcounts stay exact
                del self._adapter_refs[req.rid]
                self.adapters.release(entry)
                self._fail_request(req, str(e))
                return False
            # ensure() returning False (pool dry even after eviction) is
            # NOT an error: the gather reads the host copy instead —
            # adapter paging never preempts KV to make room
        return True

    def _check_adapter_dims(self, entry) -> None:
        """An adapter trained against a different base would scatter
        garbage through the epilogue einsum (or fail deep inside XLA);
        fail it structurally at admission instead."""
        from bigdl_tpu.serving.adapters import AdapterError
        from bigdl_tpu.train.qlora import _target_dims

        L = self.config.num_hidden_layers
        for t in entry.targets:
            try:
                out_d, in_d = _target_dims(self.config, t)
            except KeyError:
                raise AdapterError(
                    entry.name, "rank_mismatch",
                    f"unknown lora target {t!r} for this model family",
                ) from None
            a = entry.layers[t]["a"]
            b = entry.layers[t]["b"]
            if (tuple(a.shape) != (L, entry.rank, in_d)
                    or tuple(b.shape) != (L, out_d, entry.rank)):
                raise AdapterError(
                    entry.name, "rank_mismatch",
                    f"target {t}: a{tuple(a.shape)} / b{tuple(b.shape)} "
                    f"do not fit this model's [L={L}, r={entry.rank}, "
                    f"in={in_d}] / [L, out={out_d}, r] — adapter trained "
                    "on a different base?",
                )

    def _set_slot_adapter(self, slot: int, req: Request) -> None:
        """Point the slot at the request's (possibly absent) adapter
        entry and invalidate the batched decode tree only when the
        assignment actually changed."""
        if self.adapters is None:
            return
        entry = self._adapter_refs.get(req.rid)
        if self._slot_adapter[slot] is not entry:
            self._slot_adapter[slot] = entry
            self._blora_dirty = True

    def _prefill_lora(self, req: Request):
        """The request's single-row rank-bucketed adapter tree for the
        prefill kernels (None = base). Also stamps _last_prefill_rank /
        _last_prefill_targets for the sim's cost wrappers."""
        entry = self._adapter_refs.get(req.rid)
        self._last_prefill_rank = entry.rank if entry is not None else 0
        self._last_prefill_targets = (entry.targets if entry is not None
                                      else ())
        if entry is None:
            return None
        return entry.tree()

    def _gather_blora(self) -> Optional[dict]:
        """The decode step's batched adapter tree: per target,
        [L, B, rb, in] A-stacks and [L, B, out, rb] B-stacks over every
        slot (zero rows + scale 0 for adapter-less slots), rb = the
        power-of-two bucket of the max rank in the batch
        (adapters.rank_bucket) — compile variants are bounded by
        (target-set, bucket), never by which tenants happen to share a
        step. Rebuilt only when the slot->adapter assignment changes;
        None when no active slot carries an adapter (the base-only
        program keeps serving)."""
        if self.adapters is None:
            return None
        if not self._blora_dirty:
            return self._blora
        self._blora_dirty = False
        entries = self._slot_adapter
        live = [e for e in entries if e is not None]
        if not live:
            self._blora = None
            return None
        from bigdl_tpu.serving.adapters import rank_bucket

        B = self.n_slots
        L = self.config.num_hidden_layers
        rb = rank_bucket(max(e.rank for e in live))
        targets = sorted({t for e in live for t in e.targets})
        # unified paging: adapters resident in the shared page pool are
        # read straight out of their device pages — the host->device
        # transfer below shrinks to only the non-resident stragglers
        # (dry-pool fallbacks). Device reads round through the same
        # bf16 the host path casts to, so the two sources are
        # bit-identical in the epilogue.
        dev: dict = {}
        if self._pager is not None:
            for e in live:
                if e.name not in dev:
                    lv = self._pager.leaves(e.name)
                    if lv is not None:
                        dev[e.name] = lv
        layers: dict = {}
        for t in targets:
            ref = next(e.layers[t] for e in live if t in e.layers)
            in_d = int(np.asarray(ref["a"]).shape[-1])
            out_d = int(np.asarray(ref["b"]).shape[-2])
            a = np.zeros((L, B, rb, in_d), np.float32)
            b = np.zeros((L, B, out_d, rb), np.float32)
            for i, e in enumerate(entries):
                if e is None or t not in e.layers or e.name in dev:
                    continue
                a[:, i, : e.rank, :] = np.asarray(
                    e.layers[t]["a"], np.float32
                )
                b[:, i, :, : e.rank] = np.asarray(
                    e.layers[t]["b"], np.float32
                )
            ja = jnp.asarray(a, jnp.bfloat16)
            jb = jnp.asarray(b, jnp.bfloat16)
            for i, e in enumerate(entries):
                if e is None or t not in e.layers or e.name not in dev:
                    continue
                lv = dev[e.name][t]
                ja = ja.at[:, i, : e.rank, :].set(lv["a"])
                jb = jb.at[:, i, :, : e.rank].set(lv["b"])
            layers[t] = {"a": ja, "b": jb}
        scale = np.zeros((B,), np.float32)
        for i, e in enumerate(entries):
            if e is not None:
                scale[i] = e.scale
        self._blora = {"layers": layers, "scale": jnp.asarray(scale)}
        return self._blora

    # ---- admission --------------------------------------------------------

    # cache-aware admission: oldest entries scored per pop (bounds the
    # under-mutex radix probe; see _pop_deepest_match)
    _ADMIT_SCAN_WINDOW = 64

    def _pop_request(self) -> Optional[Request]:
        if self._waiting is not None:
            req, self._waiting = self._waiting, None
            return req
        if self.paged:
            return self._pop_deepest_match()
        try:
            return self._queue.get_nowait()
        except queue.Empty:
            return None

    def _pop_deepest_match(self) -> Optional[Request]:
        """Cache-aware admission ordering (docs/serving.md §6): among
        the queued admissible requests, admit the one with the DEEPEST
        radix prefix match first — it frees the most prefill work and
        touches its cached pages before eviction pressure can drop
        them. Strict-greater comparison keeps ties (including the
        all-miss common case) in FIFO order, so a workload with no
        shared prefixes schedules exactly as before; queue/request
        deadlines still bound how long a 0-match request can be
        out-prioritized. Probe is read-only (radix.match_len): scoring
        must not LRU-promote pages for requests that stay queued.

        The scan holds the queue mutex (raw deque surgery, _sweep_queue
        style), so it is BOUNDED: only the oldest _ADMIT_SCAN_WINDOW
        entries are scored — an unbounded queue under overload must not
        turn every admission into an O(queue x prompt) stall that also
        blocks handler-thread submits for the scan's duration."""
        with self._queue.mutex:
            q = self._queue.queue
            if not q:
                return None
            if len(q) > 1 and self.pages.radix.n_nodes:
                n = min(len(q), self._ADMIT_SCAN_WINDOW)
                best_i, best_d = 0, self.pages.cached_len(
                    q[0].prompt, ns=q[0].adapter)
                for i in range(1, n):
                    d = self.pages.cached_len(q[i].prompt, ns=q[i].adapter)
                    if d > best_d:
                        best_i, best_d = i, d
                if best_i:
                    req = q[best_i]
                    del q[best_i]
                    return req
            return q.popleft()

    def _shed_request(self, req: Request, kind: str, msg: str,
                      journaled: bool = True) -> None:
        """Overload rejection: explicit, fast, retryable (the API server
        maps kind "queue_full" to 429 and "queue_deadline" to 503, both
        with Retry-After)."""
        req.shed_kind = kind
        self._finish_detached(req, "shed", error=msg, journaled=journaled)
        self._bump("requests_shed")

    def _finish_detached(self, req: Request, reason: str,
                         error: Optional[str] = None,
                         journaled: bool = True) -> None:
        """Terminal state for a request NOT currently in a slot (queued /
        parked): mirrors _finish's journal + stream discipline.
        journaled=False is for requests that were never accepted (shed at
        submit) — they have no journal entry to tombstone and no
        in-flight charge to release."""
        if journaled:
            with self._stat_lock:
                self._inflight -= 1
        if error is not None:
            req.error = error
        req.finish_reason = reason
        req.done = True
        self._note_finish(req, self._clock())
        if journaled and self._journal is not None:
            self._journal.record_done(req.rid)
        if req.stream is not None:
            req.stream.put(None)

    def _note_finish(self, req: Request, now: float) -> None:
        """Terminal-state accounting shared by every finish path (slot,
        detached, submit-time rejection): per-reason counter, trace
        events, and the derived-timings request-log record. Handler
        threads reach this via shed/invalid, hence the lock on the
        counter dict."""
        reason = req.finish_reason or "?"
        with self._stat_lock:
            self.finish_reasons[reason] += 1
        if req.prompt_experts is not None or req.prompt_selection is not None:
            global _last_routed
            _last_routed = weakref.ref(req)
        entry = self._adapter_refs.pop(req.rid, None)
        if entry is not None:
            # the request's one adapter hold releases exactly at its
            # terminal state (every finish path funnels through here);
            # a refcount-0 adapter becomes fair eviction game
            self.adapters.release(entry)
            if self._pager is not None:
                # the device pages mirror the hold: holder-free pages
                # become page-out candidates for PageTable.alloc
                self._pager.drop_holder(req.rid)
        tr = self.tracer
        if req.preempt_ts is not None:
            # died while PARKED (deadline/cancel/fail_all before any
            # resume): close the preempted stretch here or the record
            # reports preempted_s=0 for a request that spent its whole
            # life in host RAM, and the trace dangles a swap_out with
            # no span. Engine-thread only: handler threads reach
            # _note_finish solely for never-admitted requests.
            parked = max(now - req.preempt_ts, 0.0)
            req.preempted_s += parked
            if tr is not None and tr.enabled:
                tr.complete("preempted", req.preempt_ts, parked,
                            tid=req.rid, cat="request", rid=req.rid,
                            outcome=reason)
            req.preempt_ts = None
        if tr is not None and tr.enabled:
            if req.admit_ts is None and reason != "invalid":
                # died waiting (shed / queue timeout / cancelled while
                # queued): close its queued span so the wait is visible
                tr.complete("queued", req.submit_ts,
                            now - req.submit_ts, tid=req.rid,
                            cat="request", rid=req.rid, outcome=reason)
            args = {"rid": req.rid, "finish_reason": reason,
                    "tokens": len(req.out_tokens)}
            if req.first_token_ts is not None:
                args["ttft_s"] = round(
                    req.first_token_ts - req.submit_ts, 6)
            if req.admit_ts is not None:
                args["queue_wait_s"] = round(
                    req.admit_ts - req.submit_ts, 6)
            if req.preempted_s:
                args["preempted_s"] = round(req.preempted_s, 6)
            tr.instant("finish", ts=now, tid=req.rid, cat="request",
                       **args)
        if self._request_log is not None:
            self._request_log.write(self._request_record(req, now))

    def _request_record(self, req: Request, now: float) -> dict:
        """The structured per-request JSONL record: every timing the
        TTFT/ITL/queue-wait dashboards derive, attached to one rid."""
        rec = {
            "ts": round(now, 6), "rid": req.rid,
            "finish_reason": req.finish_reason,
            "prompt_tokens": len(req.prompt),
            "output_tokens": len(req.out_tokens),
        }
        if req.admit_ts is not None:
            rec["queue_wait_s"] = round(req.admit_ts - req.submit_ts, 6)
        if req.first_token_ts is not None:
            rec["ttft_s"] = round(req.first_token_ts - req.submit_ts, 6)
            n = len(req.out_tokens)
            if n > 1 and req.last_token_ts is not None:
                # time-per-output-token over the decode stretch. Parked
                # time is SUBTRACTED (it is reported separately below) —
                # first->last spans any host-RAM stretch even though the
                # ITL clock rebases at resume
                decoding = max(req.last_token_ts - req.first_token_ts
                               - req.preempted_s, 0.0)
                rec["tpot_s"] = round(decoding / (n - 1), 6)
        if req.preemptions:
            rec["preemptions"] = req.preemptions
            rec["preempted_s"] = round(req.preempted_s, 6)
        if req.shed_kind is not None:
            rec["shed_kind"] = req.shed_kind
        if req.error:
            rec["error"] = req.error
        return rec

    @staticmethod
    def _expired(req: Request, now: float) -> Optional[str]:
        """The deadline a request has blown, if any."""
        if (req.deadline_s is not None
                and now - req.submit_ts > req.deadline_s):
            return "deadline_s"
        if (req.admit_ts is None and req.queue_deadline_s is not None
                and now - req.submit_ts > req.queue_deadline_s):
            return "queue_deadline_s"
        return None

    def _retrace_mark(self, phase: str) -> float:
        """Book to `phase` what JAX traced, lowered and compiled or
        loaded on this thread since the last mark; returns those
        seconds. No clock and, where nothing was traced, no write."""
        acc = retrace.thread_accumulator()
        if acc is not self._rt_acc:  # the first mark, or another thread
            # took over the stepping: what it paid before is not ours
            self._rt_acc = acc
            self._rt_seconds, self._rt_programs = acc.seconds, acc.programs
            return 0.0
        paid = acc.seconds - self._rt_seconds
        if paid:
            self.retrace_seconds[phase] += paid
            self.retraces[phase] += acc.programs - self._rt_programs
            self._rt_seconds, self._rt_programs = acc.seconds, acc.programs
        return paid

    def _mark_admitted(self, req: Request) -> None:
        """Stamp the request's (first) admission: the moment it left the
        queue and prefill work began. queue_wait therefore measures pure
        waiting — prefill time is its own phase (prefill_seconds and the
        "prefill" span) — and the "queued" span ends exactly where the
        prefill span starts."""
        self._retrace_mark("other")  # the admission's phases start here
        if req.admit_ts is not None:
            return
        req.admit_ts = self._clock()
        self.queue_wait.observe(req.admit_ts - req.submit_ts)
        tr = self.tracer
        if tr is not None and tr.enabled:
            tr.complete("queued", req.submit_ts,
                        req.admit_ts - req.submit_ts, tid=req.rid,
                        cat="request", rid=req.rid)

    def _activate(self, slot: int, req: Request, logits_last) -> None:
        """Shared post-prefill bookkeeping: sample the first token, arm
        the slot's sampling params, emit."""
        tr = self.tracer
        rt_dispatch = self._chunk_retrace_s + self._retrace_mark(
            "prefill.dispatch")
        self._chunk_retrace_s = 0.0
        t_enter = t_sampled = None
        if tr is not None and tr.enabled:
            t_enter = self._clock()
        if self._step_trace is not None:
            self._step_trace.admitted += 1
        temp, topk, topp, dosample = self._slot_sampling(req)
        penalty = (req.repetition_penalty
                   if req.repetition_penalty is not None
                   else self.gen.repetition_penalty)
        row = self._no_seen_row
        if penalty != 1.0:
            # the prompt's presence mask, built here: a program over the
            # prompt would be one per distinct length
            row = np.zeros((self.config.vocab_size,), bool)
            ids = np.asarray(req.prompt, np.int64)
            row[ids[(ids >= 0) & (ids < row.size)]] = True
        by_blocks = self.blocks is not None
        with self._phase("prefill.wait" if by_blocks
                         else "first_token.sample", req.rid):
            if by_blocks:
                # no token comes of a prefill here: open the slot's first
                # block. Nothing is fetched, so the next pass is enqueued
                # behind the prefill; only a traced admission waits for it,
                # so that its span is the work's and not the enqueueing's
                self.blocks.state = self._arm_block(
                    self.blocks.state, np.int32(slot),
                    *self.blocks.arm(slot, req))
                if tr is not None and tr.enabled:
                    jax.block_until_ready(logits_last)
            else:
                self.cur, self.seen, self._rng, out = self._first_token(
                    logits_last, self._rng, np.float32(temp),
                    np.int32(topk), np.float32(topp), np.bool_(dosample),
                    np.float32(penalty), row, np.int32(slot), cur=self.cur,
                    seen=self.seen,
                )
                # the admission's one host sync: the prefill program has
                # run by now
                first, first_lp, first_top = _read_first_token(
                    out, self.logprobs_top_k)
        rt_sample = self._retrace_mark("first_token.sample")
        if t_enter is not None:
            t_sampled = self._clock()
        eos = (req.eos_token_id if req.eos_token_id is not None
               else self.gen.eos_token_id)
        self._slots[slot] = _Slot(
            req=req, remaining=req.max_new_tokens - (not by_blocks), eos=eos,
            seq=next(self._seq),
        )
        self._temp[slot], self._topk[slot] = temp, topk
        self._topp[slot], self._dosample[slot] = topp, dosample
        self._penalty[slot] = penalty
        self._set_slot_adapter(slot, req)
        self.active[slot] = True
        # prefill phase closes HERE (the first-token sample above was a
        # host sync, so the span covers real work), strictly before the
        # first emit — the request track stays monotonically nested:
        # queued | prefill | decode windows ...
        now = self._clock()
        rt_arm = self._retrace_mark("first_token.arm")
        moe_args = {}
        if self._admit_moe and self._report_width:
            # what the kind's prefill chose at the prompt's last position
            chose, moe_args = self.kind.read_report(
                np.asarray(self._admit_moe[-1][0]), prefill=True)
            if chose is not None:  # the kind was asked for them
                req.prompt_selection = chose[0]
            self.report_totals.update(moe_args)
            self._admit_moe = []
        if self._admit_moe:  # the prefill has run (first-token sync above)
            chosen = np.concatenate(
                [np.asarray(a)[:, :n] for a, n in self._admit_moe], axis=1)
            if self._admit_moe_start == 0:  # nothing came from the cache
                req.prompt_experts = chosen
            if tr is not None and tr.enabled:
                moe_args = _moe_load(chosen, self.config.num_experts,
                                     self._first_expert)
            self._admit_moe = []
        moe_args.update(self._admit_args)
        if by_blocks:
            stored = self._prefill_len(req.prompt)
            moe_args.update(blocks_stored=stored // self.blocks.b,
                            tail_tokens=len(req.prompt) - stored)
        if req.admit_ts is not None:
            self.prefill_seconds.observe(now - req.admit_ts)
            if tr is not None and tr.enabled:
                tr.complete("prefill", req.admit_ts, now - req.admit_ts,
                            tid=req.rid, cat="request", rid=req.rid,
                            prompt_tokens=len(req.prompt),
                            # the streams this admission stalled
                            occupancy=int(self.active.sum()) - 1,
                            queue_depth=self._queue.qsize(), **moe_args)
                if t_sampled is not None:
                    tr.complete_parts(
                        req.admit_ts, now - req.admit_ts,
                        (t_enter, t_sampled),
                        (("prefill.dispatch",
                          {"rid": req.rid, "prompt_tokens": len(req.prompt),
                           "retrace_s": rt_dispatch}),
                         ("prefill.wait" if by_blocks
                          else "first_token.sample",
                          {"rid": req.rid, "retrace_s": rt_sample}),
                         ("first_token.arm",
                          {"rid": req.rid, "retrace_s": rt_arm})),
                        tid=req.rid, cat="request")
        if not by_blocks:
            self._emit(slot, first, first_lp, first_top)

    def _admit_dense(self, req: Request, slot: int) -> None:
        self._mark_admitted(req)
        # decode writes land at [bucket, bucket + max_new_tokens): keep
        # that window inside the cache, tail-truncating over-long prompts
        limit = self.max_len - req.max_new_tokens
        bucket = min(round_up(max(len(req.prompt), 16), 64), limit)
        if len(req.prompt) > bucket:
            req.prompt = req.prompt[-bucket:]
        tokens = np.full((1, bucket), self.gen.pad_token_id, np.int32)
        tokens[0, bucket - len(req.prompt):] = req.prompt
        pad = bucket - len(req.prompt)
        self.prefill_chunks += 1  # a monolithic prefill is one chunk
        with self._phase("prefill.dispatch", req.rid):
            logits_last, pcache = self._prefill(
                self.model.params, jnp.asarray(tokens),
                jnp.asarray([pad], jnp.int32), bucket=bucket,
                lora=self._prefill_lora(req),
            )
            self.cache = self._insert(
                self.cache, pcache, jnp.asarray(slot), jnp.asarray(pad)
            )
            if self.speculative:
                self._admit_draft(slot, req.prompt, limit)
        self._activate(slot, req, logits_last)

    def _admit(self) -> None:
        spent = 0  # prompt tokens admitted by this call
        while True:
            slot = self._free_slot()
            if slot is None:
                return
            # preempted requests resume FIRST, in preemption order: they
            # are the oldest in-flight work, and admitting new requests
            # past a blocked resume would starve it of the very pages it
            # waits for
            if self._preempted:
                # dead entries (cancelled / expired) at ANY depth were
                # already dropped by _sweep_preempted this step
                entry = self._preempted[0]
                req = entry.req
                if self._resume_preempted(entry, slot):
                    self._preempted.popleft()
                    continue
                if not self.active.any() and self._prefilling is None:
                    # nothing left to free pages: the pool cannot hold
                    # the restore, ever — fail instead of hanging. A
                    # live chunk plan is future page supply (its slot
                    # activates, decodes, and frees), so the resume
                    # waits it out rather than failing spuriously.
                    self._preempted.popleft()
                    self._fail_request(req, (
                        f"cannot resume preempted request: restoring "
                        f"{entry.n_pages} pages exceeds the free pool; "
                        "raise n_pages"
                    ))
                    continue
                return  # wait for pages before admitting anything newer
            if self._prefilling is not None:
                # at most ONE request prefills at a time: admitting
                # another would either stack a second monolithic
                # prefill into this step (the stall chunking bounds) or
                # need a second chunk plan — queued work waits the few
                # steps until the current plan lands
                return
            req = self._pop_request()
            if req is None:
                return
            if req.rid in self._cancelled:  # cancelled while queued: a
                # timed-out/disconnected client must not burn the slot
                self._cancelled.pop(req.rid, None)
                self._finish_detached(req, "stop")
                continue
            now = self._clock()
            which = self._expired(req, now)
            if which is not None:
                self._expire_queued(req, which, now)
                continue
            if spent and spent + len(req.prompt) > ADMIT_TOKENS_PER_STEP:
                self._waiting = req  # first in line after a decode step
                return
            if req.adapter is not None and not self._resolve_adapter(req):
                continue  # structured failure: ONE request errors, the
                # batch keeps serving (never fail_all for a bad adapter)
            if self.paged:
                if not self._admit_paged(req, slot):
                    self._waiting = req  # pool full: retry after frees
                    return
            else:
                self._admit_dense(req, slot)
            spent += len(req.prompt)

    def _emit(self, slot: int, token: int,
              logprob: Optional[float] = None,
              top_logprobs: Optional[dict] = None) -> None:
        s = self._slots[slot]
        eos = s.eos
        if eos is not None and token == eos:
            # the EOS id terminates the stream but is not generated text
            self._finish(slot, "stop")
            return
        req = s.req
        now = self._clock()
        prev = req.last_token_ts
        if req.first_token_ts is None:
            req.first_token_ts = now
            self.ttft.observe(now - req.submit_ts)
            prev = now
        else:
            # wall-clock gap between consecutive emits as a streaming
            # client sees them (a speculative burst yields ~0 gaps —
            # that IS the client experience). Parked time is excluded:
            # resume rebases last_token_ts, and the stall is accounted
            # in resume_wait_seconds instead.
            self.itl.observe(now - prev)
        req.last_token_ts = now
        tr = self.tracer
        if tr is not None and tr.enabled:
            # coalesce decode into one span per TRACE_DECODE_EVERY
            # tokens; each window opens where the previous span closed,
            # keeping the request track monotonically nested
            if s.n_win == 0:
                s.t_win = prev
            s.n_win += 1
            if s.n_win >= TRACE_DECODE_EVERY:
                tr.complete("decode", s.t_win, now - s.t_win,
                            tid=req.rid, cat="request", rid=req.rid,
                            tokens=s.n_win)
                s.n_win = 0
        s.req.out_tokens.append(token)
        if logprob is not None:
            s.req.out_logprobs.append(logprob)
        if top_logprobs is not None:
            s.req.out_top_logprobs.append(top_logprobs)
        if s.req.stream is not None:
            s.req.stream.put(token)
        if s.remaining <= 0:
            self._finish(slot, "length")

    def _flush_decode_window(self, slot: int, now: float) -> None:
        """Emit the slot's partial decode-window span (finish/preempt
        must not drop the tail tokens' span)."""
        s = self._slots[slot]
        tr = self.tracer
        if (tr is not None and tr.enabled and s.n_win > 0
                and s.req is not None):
            tr.complete("decode", s.t_win, now - s.t_win, tid=s.req.rid,
                        cat="request", rid=s.req.rid, tokens=s.n_win)
        s.n_win = 0

    def _finish(self, slot: int, reason: str = "stop",
                counted: bool = True) -> None:
        s = self._slots[slot]
        now = self._clock()
        self._flush_decode_window(slot, now)
        s.req.finish_reason = reason
        s.req.done = True
        # before the injected crash point: a crash inside _finish leaves
        # the request terminal (fail_all preserves it), so its in-flight
        # charge must already be released. Same for the finish
        # accounting below: the request IS terminal either way, and a
        # replayed request counts again in the successor process (the
        # request log is at-least-once across the crash window, like
        # the journal).
        with self._stat_lock:
            self._inflight -= 1
        self._note_finish(s.req, now)
        if counted and reason in ("stop", "length"):
            # genuine completions only: cancelled/timed-out requests also
            # land here as "stop" but must not inflate the throughput
            # that _retry_after derives Retry-After from
            self.requests_completed += 1
        if (not self._cleanup
                and self._faults.fire("crash_before_done") is not None):
            # simulated process death in the journal's at-least-once
            # window: the request completed but its tombstone was never
            # written, so a successor engine must replay it
            raise FaultError(
                "injected crash before journal tombstone "
                f"(rid {s.req.rid})"
            )
        if self._journal is not None:
            self._journal.record_done(s.req.rid)
        if s.req.stream is not None:
            s.req.stream.put(None)
        self._free_slot_state(slot)

    def _free_slot_state(self, slot: int) -> None:
        """Release a slot's engine-side state (sampling rows, pages)
        without touching the request's terminal fields."""
        if (self._prefilling is not None
                and self._prefilling.slot == slot):
            # the request died mid-chunked-prefill (cancel / deadline /
            # fail_all): every finish path funnels through here, so
            # clearing the plan here is what guarantees no orphaned
            # chunk ever runs for a freed slot
            self._prefilling = None
        self._slots[slot] = _Slot()
        self.active[slot] = False
        if self._slot_adapter[slot] is not None:
            # the slot's adapter row leaves the batched tree; the
            # request's registry reference (if still alive — parked)
            # is _adapter_refs' business, not the slot's
            self._slot_adapter[slot] = None
            self._blora_dirty = True
        self._dosample[slot] = False  # idle rows decode deterministic garbage
        self._penalty[slot] = 1.0
        self.seen = self.seen.at[slot].set(False)
        if self.paged:
            self.pages.release(slot)
            self.cache = dataclasses.replace(
                self.cache, pos=self.cache.pos.at[slot].set(0)
            )

    def _reset_state(self) -> None:
        """Rebuild the (possibly donated-away) cache after a failed decode
        so the engine can keep serving new requests."""
        self._drop_flight()
        self.cache = self._make_pool()
        if self.speculative:
            self.dcache = self._make_pool(force_dense=True)
        self.cur = jnp.zeros((self.n_slots,), jnp.int32)
        self.seen = jnp.zeros(
            (self.n_slots, self.config.vocab_size), jnp.bool_
        )
        self._penalty[:] = 1.0
        self.active[:] = False
        self._preempted.clear()  # blobs reference the old pool's layout
        self._prefilling = None  # a half-run chunk plan died with the pool
        if self.blocks is not None:
            self.blocks.reset()
        self._slot_adapter = [None] * self.n_slots
        self._blora, self._blora_dirty = None, True
        if self.paged:
            # cached nodes and resident adapters reference the old
            # pool's pages; the hit and eviction totals survive
            self.pages = self.pages.rebuilt()

    def cancel(self, req: Request) -> None:
        """Thread-safe: stop generating for a request whose consumer is
        gone (stop-string cut, client disconnect). The slot frees on the
        engine thread's next step."""
        if req.done:  # lost the race with a normal finish: nothing to do
            return
        self._cancelled[req.rid] = req

    def _reap_cancelled(self) -> None:
        # prune marks that lost the cancel-vs-finish race (the request
        # finished between the caller's done-check and its cancel()).
        # list() snapshots the items atomically (C-level copy) — handler
        # threads insert concurrently, and iterating the live dict here
        # would intermittently die with 'dict changed size'.
        for rid, q in list(self._cancelled.items()):
            if q.done:
                self._cancelled.pop(rid, None)
        for i, s in enumerate(self._slots):
            if s.req is not None and s.req.rid in self._cancelled:
                self._cancelled.pop(s.req.rid, None)
                self._finish(i, "stop", counted=False)

    def _inject_nan(self, lps: "np.ndarray",
                    live: "np.ndarray") -> "np.ndarray":
        """Chaos hook shared by the plain and speculative decode paths:
        when nan_logits is armed, poison the victim rows' host-side
        logprobs as if the model had produced non-finite values for
        them (the quarantine guard downstream must catch it). `live`:
        the rows of the step being read."""
        f = self._faults.fire("nan_logits")
        if f is None:
            return lps
        lps = lps.copy()
        victims = f.get("slots")
        if victims is None:
            act = np.nonzero(live)[0]
            victims = [int(act[0])] if act.size else []
        for v in victims:
            lps[v] = np.nan
        return lps

    def _bump(self, counter: str) -> None:
        """Increment an overload counter race-free: requests_shed and
        request_timeouts are bumped from HTTP handler threads AND the
        engine thread, and `+=` on an attribute is not atomic."""
        with self._stat_lock:
            setattr(self, counter, getattr(self, counter) + 1)

    def _expire_queued(self, req: Request, which: str, now: float) -> None:
        """Terminal handling for a request that expired before admission:
        queue-deadline → shed (retryable 503), total deadline → timeout.
        One copy — the admission pop and the saturation sweep must never
        drift in message or counter discipline."""
        if which == "queue_deadline_s":
            self._shed_request(req, "queue_deadline", (
                f"queue deadline: waited {now - req.submit_ts:.2f}s > "
                f"queue_deadline_s={req.queue_deadline_s}"
            ))
        else:
            self._finish_detached(
                req, "timeout",
                error=f"deadline_s={req.deadline_s} exceeded before "
                "admission",
            )
            self._bump("request_timeouts")

    def _sweep_preempted(self) -> None:
        """Drop parked requests whose client cancelled or whose deadline
        expired, at ANY depth of the deque — a blocked head must not
        keep an already-dead request (and its host KV blob) parked
        indefinitely behind it. Engine-thread only, like _preempted."""
        if not self._preempted:
            return
        now = self._clock()
        keep: "collections.deque[_Preempted]" = collections.deque()
        for entry in self._preempted:
            req = entry.req
            if req.rid in self._cancelled:
                self._cancelled.pop(req.rid, None)
                self._finish_detached(req, "stop")
                continue
            if self._expired(req, now) is not None:
                # finish BEFORE the bump: once done is set, a racing
                # server-side wait timeout sees it and stands down, so
                # the counter records the request exactly once
                self._finish_detached(
                    req, "timeout",
                    error=f"deadline_s={req.deadline_s} exceeded "
                    "while preempted",
                )
                self._bump("request_timeouts")
                continue
            keep.append(entry)
        self._preempted = keep

    def _sweep_queue(self) -> None:
        """Drop requests that died while still WAITING in the queue —
        expired deadlines AND cancelled clients — even when no slot
        frees: a saturated engine must not 429 new clients over a queue
        of already-dead work. A deadline-dead request's client gets its
        promised fast 503 instead of waiting for a slot that may be
        minutes away; a cancelled entry (server timeout, disconnect)
        stops counting against max_queue the next step, not when a slot
        eventually frees."""
        if not self._deadlines_seen and not self._cancelled:
            return
        now = self._clock()
        # the paged OOM-retry slot waits like a queue entry and gets the
        # same dead-work treatment — _admit can return early (blocked
        # preemption resume) for many steps without ever popping it
        if self._waiting is not None:
            req = self._waiting
            if req.rid in self._cancelled:
                self._waiting = None
                self._cancelled.pop(req.rid, None)
                self._finish_detached(req, "stop")
            else:
                which = self._expired(req, now)
                if which is not None:
                    self._waiting = None
                    self._expire_queued(req, which, now)
        if self._queue.empty():
            return
        expired: list[tuple[Request, str]] = []
        cancelled: list[Request] = []
        with self._queue.mutex:  # surgery on the deque under the queue's
            # own lock; qsize()/put() stay consistent, FIFO order is
            # kept. One partition pass: each verdict computed once, and
            # the mutex (which blocks handler-thread submits) is held for
            # a single scan
            q = self._queue.queue
            keep = []
            for r in q:
                which = self._expired(r, now)
                if r.rid in self._cancelled:
                    cancelled.append(r)
                elif which is not None:
                    expired.append((r, which))
                else:
                    keep.append(r)
            if expired or cancelled:
                q.clear()
                q.extend(keep)
        for req in cancelled:  # journal/stream work outside the lock
            self._cancelled.pop(req.rid, None)
            self._finish_detached(req, "stop")
        for req, which in expired:
            self._expire_queued(req, which, now)

    def _reap_deadlines(self) -> None:
        """Kill in-flight requests past their total wall-clock budget:
        partial output is delivered, finish_reason records 'timeout'."""
        now = self._clock()
        for i, s in enumerate(self._slots):
            if s.req is None or s.req.deadline_s is None:
                continue
            if s.req.rid in self._cancelled:
                # a server-side wait timeout got here first: it already
                # counted the timeout, and the next _reap_cancelled will
                # free the slot — bumping again would double-count the
                # one request in request_timeouts_total
                continue
            if now - s.req.submit_ts > s.req.deadline_s:
                s.req.error = (
                    f"deadline_s={s.req.deadline_s} exceeded after "
                    f"{len(s.req.out_tokens)} tokens"
                )
                # finish (sets done) BEFORE the bump: a racing _wait
                # timeout stands down on done, so one timed-out request
                # is never counted twice
                self._finish(i, "timeout")
                self._bump("request_timeouts")

    def step(self) -> bool:
        """Admit queued requests, advance every active slot one token.
        Returns True if any work remains. While tracing, the step's
        phases are stamped as they pass and recorded when it ends
        (`_note_step`); off, this is `_step` and one check."""
        tr = self.tracer
        if tr is None or not tr.enabled:
            return self._step()
        if self._annotation is None:
            from jax.profiler import TraceAnnotation

            self._annotation = TraceAnnotation
        st = self._step_trace = _StepTrace(self._clock(),
                                           self.prefill_chunks)
        ok = False
        try:
            more = self._step()
            ok = True
            return more
        finally:
            self._step_trace = None
            self._note_step(tr, st, ok)

    def _phase(self, name: str, rid: Optional[int] = None,
               seq: Optional[int] = None):
        """What a phase of the step runs under. While tracing, a
        `TraceAnnotation` of the span's name with its decode step's `seq`
        (an admission's: its `rid`): an event of an open profile's host
        plane, a flag check when none is open. Off, nothing is built."""
        if self._step_trace is None:
            return _NO_PHASE
        if rid is None:
            return self._annotation(name, seq=seq)
        return self._annotation(name, rid=rid)

    def _note_step(self, tr, st: _StepTrace, ok: bool) -> None:
        """The engine track's `engine.step` span and the parts that
        partition it, for a step that admitted, advanced a prefill chunk
        or decoded (the idle loop's polls record nothing). A step that
        raised keeps the parts it had closed."""
        if not st.decoded and self.prefill_chunks == st.chunks:
            return
        cuts = st.cuts  # a step that raised: the part left open is bare
        end = cuts[-1] if ok else self._clock()
        tr.complete("engine.step", st.t_in, end - st.t_in, tid=0,
                    cat="engine", seq=st.seq, admitted=st.admitted,
                    occupancy=int(self.active.sum()))
        if st.parts:
            tr.complete_parts(st.t_in, cuts[-1] - st.t_in, cuts[:-1],
                              st.parts, tid=0, cat="engine")

    def _step(self) -> bool:
        """Reap, admit, book pages; then, for plain decode, dispatch the
        step AFTER the one in flight and only then fetch and emit the one
        in flight, so that the device always has its next program queued
        and everything the host does for a step runs beside the device's
        work on the step before. One step in flight and no more: the
        host's work a step is shorter than any device step, so a second
        would buy nothing and cost a step of admission latency and a
        dropped row more per finish nobody could foresee."""
        st = self._step_trace  # None unless tracing
        f = self._faults.fire("slow_step")
        if f is not None:  # injected device stall (serving/faults.py)
            time.sleep(float(f.get("seconds", 0.05)))
        unread = self._flight
        self._reap_cancelled()
        self._reap_preempt_requests()
        self._reap_deadlines()
        self._sweep_preempted()
        self._sweep_queue()
        if st is not None:
            st.close(self._clock(), "step.reap", {})
        self._admit()
        self._advance_prefill()  # at most one chunk per step
        if st is not None:
            st.close(self._clock(), "step.admit", {})
        if self.speculative:
            return self._step_speculative()
        # a call reads one step: where a preemption already drained the
        # one in flight (`_preempt_slot`), the next call dispatches again
        drained = unread is not None and self._flight is None
        plan = [] if drained else self._plan_decode()
        bt_uploaded = self._upload_block_table()
        first = newest = self._flight
        if first is None and not plan:
            if st is not None:
                st.close(self._clock(), "step.pages",
                         {"bt_uploaded": bt_uploaded})
            return self._more_work()
        keys = []
        for _ in plan:  # one split a dispatched step, in dispatch order
            self._rng, k = jax.random.split(self._rng)
            keys.append(k)
        self._retrace_mark("other")
        t0 = self._start_decode(bt_uploaded)
        for reqs, k in zip(plan, keys):
            newest = self._dispatch(reqs, k, t0, ahead=newest is not None)
            if first is None:  # after an idle stretch: the step to read
                first = newest
        self._flight = newest if newest is not first else None
        self._read_step(first, parts=True)
        return True

    def _more_work(self) -> bool:
        """What `step()` returns: a caller that stops at False leaves no
        request waiting and no decode step unread."""
        return bool(self.active.any() or self._flight is not None
                    or not self._queue.empty() or self._waiting is not None
                    or self._preempted or self._prefilling is not None)

    def _plan_decode(self) -> list:
        """The decode steps this call dispatches, each as the requests its
        rows are computed for (`_Flight.reqs`), with their pages booked.
        With a step in flight: the one after it, where some slot outlives
        the one in flight and the pool gives its pages without a victim
        (`_book_ahead`). With none (the engine was idle, or the last call
        could not run ahead): the next step, its pages booked as ever
        (`_ensure_decode_pages`, which may preempt), and the one after
        it on the same terms as above."""
        plan: list = []
        unread = None if self._flight is None else self._flight.reqs
        if unread is None and self.active.any():
            if self.paged:
                self._ensure_decode_pages(
                    1 if self.blocks is None else self.blocks.b)
            if self.active.any():
                unread = [s.req if a else None
                          for s, a in zip(self._slots, self.active)]
                plan.append(unread)
        if unread is not None:
            rows = self._book_ahead(unread)
            if rows is not None:
                plan.append(rows)
        return plan

    def _book_ahead(self, unread: list) -> Optional[list]:
        """The rows of the step after the one whose rows are `unread`
        (dispatched, its tokens not read yet), or None where that step is
        not to be dispatched before `unread` is read. A slot whose unread
        step is its last by its count of tokens gets no row, no page and
        no `"length"`: its row of the next step writes where the block
        table's unbooked entries point, the scratch page, or inside the
        slot's own last page. With no row at all nothing is dispatched.
        A row's next write must lie inside pages the slot holds at
        dispatch, one token further than the host's mirror says for a
        slot with a token unread; such a page is taken where the pool
        has one free or a cached prefix to evict, never from a victim:
        under that pressure the caller reads the step in flight first and
        the next call books with exact positions."""
        rows: list = [None] * self.n_slots
        need = []
        for i in np.nonzero(self.active)[0]:
            s = self._slots[int(i)]
            behind = unread[i] is s.req
            if self.blocks is not None:
                # a pass yields 0 to b tokens: no count says which is a
                # row's last, so every row gets one (dropped at its read if
                # the request ended in between) and the pages of the block
                # it will write
                rows[i] = s.req
                n = self.blocks.need_tokens(int(i), behind)
                if n > 0:
                    need.append((int(i), n))
                continue
            if behind and s.remaining <= 1:
                continue
            rows[i] = s.req
            need.append((int(i), 1 + behind))
        if not any(r is not None for r in rows):
            return None
        if self.paged:
            for slot, n in need:
                while self.pages.short(slot, n):
                    pg = (None if self.pages.row_full(slot)
                          else self.pages.alloc())
                    if pg is None:
                        return None
                    self.pages.extend(slot, pg)
        return rows

    def _upload_block_table(self) -> bool:
        """Send the block table where the host's mirror has changed."""
        bt = self.pages.block_table() if self.paged else None
        if bt is not None:
            both = ({} if self.pages.window is None else
                    {"window_tables": jnp.asarray(self.pages.window_table)})
            self.cache = dataclasses.replace(
                self.cache, block_tables=jnp.asarray(bt), **both)
        return bt is not None

    def _upload_sampling(self) -> tuple:
        """The per-slot sampling vectors as the step's program takes them,
        sent from their host mirrors: (temperature, top-k, top-p,
        do-sample) and the repetition penalty."""
        return (jnp.asarray(self._temp), jnp.asarray(self._topk),
                jnp.asarray(self._topp), jnp.asarray(self._dosample)), \
            jnp.asarray(self._penalty)

    def _start_decode(self, bt_uploaded: bool) -> float:
        """The clock where the call starts to dispatch and read decode
        steps: a step dispatched with none unread has its `decode_step`
        span start here. While tracing, also where `step.pages` ends."""
        t0 = self._clock()
        st = self._step_trace
        if st is not None:
            st.decoded = True
            st.close(t0, "step.pages", {"bt_uploaded": bt_uploaded})
        return t0

    def _next_seq(self) -> Optional[int]:
        """While tracing, the number of the decode step being dispatched:
        its span, its phases' spans and their annotations carry it."""
        if self._step_trace is None:
            return None
        self._step_seq += 1
        return self._step_seq

    def _note_dispatched(self, seq: Optional[int], t_args) -> None:
        """While tracing, close `decode.dispatch` of step `seq` where its
        program has been enqueued."""
        st = self._step_trace
        if st is not None:
            st.close(self._clock(), "decode.dispatch",
                     {"seq": seq,
                      "retrace_s": self._retrace_mark("decode_step")},
                     (t_args,), (("decode.args", {}), ("decode.call", {})))

    def _dispatch(self, reqs: list, key, t0: float, ahead: bool) -> _Flight:
        """Enqueue one decode step over the slot pool; `reqs` are the
        requests whose rows it computes. What it returns for the host is
        read later (`_read_step`); `cur`, the cache and `seen` are the
        next step's inputs and stay on the device."""
        st = self._step_trace
        seq, t_args = self._next_seq(), None
        try:
            with self._phase("decode.args", seq=seq):
                sampling, penalty = self._upload_sampling()
                lora = self._gather_blora()
            if st is not None:
                t_args = self._clock()
            with self._phase("decode.call", seq=seq):
                if self.blocks is not None:
                    self.blocks.state, out, self.cache = self._decode(
                        self.model.params, self.blocks.state, self.cache,
                        key, *sampling)
                else:
                    self.cur, out, self.cache, self.seen = self._decode(
                        self.model.params, self.cur, self.cache, key,
                        *sampling, self.seen, penalty, lora=lora,
                    )
        except Exception:
            # the donated cache buffer is gone — rebuild before re-raising
            # (the server's guard fails the in-flight requests)
            self.fail_all("decode step failed")
            self._reset_state()
            raise
        self._note_dispatched(seq, t_args)
        return _Flight(out, reqs, t0, ahead, seq)

    def _drain(self) -> None:
        """Read the step in flight now, through the path every read takes:
        for what needs the pool at rest and the host's mirrors exact (a
        preemption's copy to host RAM, a speculative round, shutdown)."""
        fl, self._flight = self._flight, None
        if fl is not None:
            self._read_step(fl)

    def _read_step(self, fl: _Flight, parts: bool = False) -> None:
        """Fetch what step `fl` left for the host and apply it: advance,
        emit and finish its live rows. A row whose slot no longer holds
        the request it was computed for (EOS, a stop, a cancel, a
        deadline, a quarantine seen after dispatch) is dropped. `parts`:
        the call's own read, whose phases are parts of `engine.step`."""
        st = self._step_trace if parts else None
        t_wait = None
        try:
            with self._phase("decode.wait", seq=fl.seq):
                fl.out.block_until_ready()  # the program has run
            if st is not None:
                t_wait = self._clock()
            with self._phase("decode.read", seq=fl.seq):
                host = np.asarray(fl.out)  # the step's one fetch
        except Exception:
            # a device failure surfaces here, with the next step queued
            # on what this one left: both are lost with the pool
            self.fail_all("decode step failed")
            self._reset_state()
            raise
        live = np.zeros((self.n_slots,), bool)
        for i, r in enumerate(fl.reqs):
            live[i] = (r is not None and self._slots[i].req is r
                       and self.active[i])
        self.decode_rows_discarded += (
            sum(r is not None for r in fl.reqs) - int(live.sum()))
        self.decode_steps[fl.ahead] += 1
        if self.blocks is not None:
            self.blocks.read(fl, host, live, t_wait)
        else:
            self._read_rows(fl, host, live, t_wait)
        if st is not None:
            st.seq = fl.seq
            st.close(self._clock(), "step.emit", {"seq": fl.seq})

    def _read_rows(self, fl: _Flight, host: "np.ndarray",
                   live: "np.ndarray", t_wait: Optional[float]) -> None:
        """A plain decode step's rows as fetched: one token a live row."""
        n_top = self.logprobs_top_k
        toks = host[:, 0]
        lps = self._inject_nan(
            np.ascontiguousarray(host[:, 1]).view(np.float32), live)
        tops_h = experts_h = None
        if n_top:
            tops_h = (host[:, 2:2 + n_top], np.ascontiguousarray(
                host[:, 2 + n_top:2 + 2 * n_top]).view(np.float32))
        if self.moe_routing:  # [B, L * k] -> [L, B, k]
            experts_h = host[:, 2 + 2 * n_top:].reshape(
                self.n_slots, -1, self.config.num_experts_per_tok
            ).transpose(1, 0, 2).astype(
                _expert_id_dtype(self._expert_ids))
            # counted only when a span or a gauge reads it (moe_load)
            self._moe_last = (experts_h, live)
        chose, extra = None, None
        if self._report_width:  # what the kind's forward chose, a row
            chose, extra = self.kind.read_report(
                host[:, host.shape[1] - self._report_width:], live)
            self.report_totals.update(extra)
        # the fetch above is the host sync: the step's device work is
        # really done here, so the duration is honest
        self._note_decode_step(fl, live, t_wait, extra=extra)
        with self._phase("step.emit", seq=fl.seq):
            for i in np.nonzero(live)[0]:
                i = int(i)
                s = self._slots[i]
                if not np.isfinite(lps[i]):
                    # non-finite logits guard: quarantine the ONE
                    # poisoned slot (its sampled token/logprob are
                    # garbage) instead of letting the exception path
                    # fail_all the whole batch — per-row decode means
                    # other slots' math is untouched
                    s.req.error = (
                        "non-finite logits in decode step; request "
                        "quarantined (other slots unaffected)"
                    )
                    self._finish(i, "error")
                    continue
                s.remaining -= 1
                if self.paged:
                    self.pages.advance(i)
                if experts_h is not None:
                    s.req.out_experts.append(experts_h[:, i])
                if chose is not None:
                    s.req.out_selection.append(chose[i])
                alt = None
                if tops_h is not None:
                    alt = {int(t): float(l)
                           for t, l in zip(tops_h[0][i], tops_h[1][i])}
                self._emit(i, int(toks[i]), float(lps[i]), alt)

    def _note_decode_step(self, fl: _Flight, live: "np.ndarray",
                          t_wait: Optional[float] = None,
                          arrays: int = 1,
                          extra: Optional[dict] = None) -> None:
        """Per-step accounting where step `fl` has been fetched: the
        duration histogram and, on the decode track, its `decode_step`
        span, from the later of its dispatch and the fetch before it to
        this fetch, so that consecutive spans never overlap and, with a
        step in flight, its length is what the step cost the device.
        `extra`: what a pass over blocks adds to the span's arguments. The
        span's arguments describe `fl`: they are taken before the host's
        mirrors advance. `t_wait`: where `decode.wait` ended, for a read
        whose phases are parts of `engine.step`."""
        t1 = self._clock()
        t_start = max(fl.t0, self._t_read)
        self._t_read = t1
        self.decode_step_seconds.observe(t1 - t_start)
        rt_rest = self._retrace_mark("decode_step")
        busy = int(live.sum())
        # what the step read and wrote again of state rows (0 without them)
        moved = 2 * busy * self.state_row_bytes
        self.state_bytes_moved += moved
        tr = self.tracer
        if fl.seq is None or tr is None or not tr.enabled:
            return
        st = self._step_trace
        if st is not None:
            st.decoded = True
        pages = (self.kind.decode_args(self.config, self.pages, live, moved,
                                       self.cache) if self.paged else {})
        tr.complete(
            "decode_step", t_start, t1 - t_start, tid=DECODE_TID,
            cat="engine", seq=fl.seq, ahead=fl.ahead, occupancy=busy,
            slots=self.n_slots, queue_depth=self._queue.qsize(), **pages,
            **self.moe_load(), **(extra or {}))
        tr.counter("batch", ts=t1, occupancy=busy,
                   queued=self._queue.qsize(),
                   preempted=len(self._preempted))
        if st is not None and t_wait is not None:
            st.close(t1, "decode.fetch",
                     {"seq": fl.seq, "retrace_s": rt_rest}, (t_wait,),
                     (("decode.wait", {}), ("decode.read", {"arrays": arrays})))

    def _step_speculative(self) -> bool:
        """Draft-K-then-verify round: each live slot emits 1..draft_k
        tokens (its accepted prefix + the target's bonus token). A
        round's accepted counts decide the next positions, so a round is
        dispatched and read in one call, with nothing in flight."""
        st = self._step_trace
        self._drain()
        if self.paged:
            # reserve for the CURRENT ladder K (== draft_k when not
            # adaptive): after a downshift the round writes at most
            # _cur_k tokens before rollback, so tighter is still safe
            self._ensure_decode_pages(self._cur_k)
        bt_uploaded = self._upload_block_table()
        if not self.active.any():
            if st is not None:
                st.close(self._clock(), "step.pages",
                         {"bt_uploaded": bt_uploaded})
            return self._more_work()
        self._rng, k = jax.random.split(self._rng)
        if self._spec_exec is not None:  # pre-compiled ladder program
            fn = self._spec_exec[self._cur_k]
        else:
            fn = functools.partial(self._spec_decode, self._cur_k)
        kw = {}
        if self.adapters is not None:
            # verify with the slots' adapters applied (None when no
            # active slot carries one). AOT executables have no lora
            # slot, but adapter engines never build them (_spec_exec
            # stays None — the jit path retraces per tree structure)
            kw["lora"] = self._gather_blora()
        self._retrace_mark("other")
        t0 = self._start_decode(bt_uploaded)
        seq, t_args, t_wait = self._next_seq(), None, None
        try:
            with self._phase("decode.args", seq=seq):
                sampling, penalty = self._upload_sampling()
            if st is not None:
                t_args = self._clock()
            with self._phase("decode.call", seq=seq):
                (choice, lp_all, n_acc, cur2, self.cache, self.dcache,
                 self.seen) = fn(
                    self.model.params, self._draft_params, self.cur,
                    self.cache, self.dcache, k, *sampling,
                    self.seen, penalty, **kw,
                )
        except Exception:
            self.fail_all("speculative decode step failed")
            self._reset_state()
            raise
        self._note_dispatched(seq, t_args)
        self.cur = cur2
        with self._phase("decode.wait", seq=seq):
            choice_h = np.asarray(choice)
        if st is not None:
            t_wait = self._clock()
        live = self.active.copy()
        with self._phase("decode.read", seq=seq):
            lp_h = self._inject_nan(np.asarray(lp_all), live)
            n_acc_h = np.asarray(n_acc)
        self.decode_steps[0] += 1
        self._note_decode_step(
            _Flight(None, [], t0, False, seq), live, t_wait, arrays=2)
        self.spec_rounds += 1
        if self.adaptive_draft:
            self._adapt_draft_k(n_acc_h[self.active])
        with self._phase("step.emit", seq=seq):
            for i in np.nonzero(self.active)[0]:
                i = int(i)
                s = self._slots[i]
                if not np.all(np.isfinite(lp_h[i, : int(n_acc_h[i]) + 1])):
                    # same quarantine as the plain path: one poisoned row
                    # must not take the batch down
                    s.req.error = (
                        "non-finite logits in speculative verify; request "
                        "quarantined (other slots unaffected)"
                    )
                    self._finish(i, "error")
                    continue
                if self.paged:  # mirror the post-rollback pool position
                    self.pages.advance(i, int(n_acc_h[i]) + 1)
                for t in range(int(n_acc_h[i]) + 1):
                    s.remaining -= 1
                    self.spec_emitted += 1
                    self._emit(i, int(choice_h[i, t]), float(lp_h[i, t]))
                    if not self.active[i]:  # EOS or budget hit mid-round
                        break
        if st is not None:
            st.seq = seq
            st.close(self._clock(), "step.emit", {"seq": seq})
        return True

    def _adapt_draft_k(self, n_acc: np.ndarray) -> None:
        """Steer the draft length along the compiled-K ladder from an
        EMA of the per-round acceptance fraction. Output is unchanged by
        construction (speculative decoding is exact at any K); only the
        draft-compute : emitted-token ratio moves."""
        if n_acc.size == 0:
            return
        frac = float(np.mean(n_acc)) / max(self._cur_k - 1, 1)
        self._accept_ema = (
            frac if self._accept_ema is None
            else 0.7 * self._accept_ema + 0.3 * frac
        )
        idx = self._k_ladder.index(self._cur_k)
        if self._accept_ema < 0.35 and idx > 0:
            self._cur_k = self._k_ladder[idx - 1]
            self._accept_ema = None  # re-measure at the new K
        elif self._accept_ema > 0.75 and idx < len(self._k_ladder) - 1:
            self._cur_k = self._k_ladder[idx + 1]
            self._accept_ema = None

    def _fail_request(self, req: Request, msg: str) -> None:
        """Terminal failure for a request not (or no longer) in a slot."""
        self._finish_detached(req, "error", error=msg)

    def fail_all(self, msg: str) -> None:
        """Mark every in-flight and queued request failed (engine-thread
        crash path — streams get their sentinel so clients unblock).
        Injected crash points are suppressed for the duration: cleanup
        after a crash must not itself crash (an armed crash_before_done
        with charges left would otherwise kill the engine thread)."""
        self._cleanup = True
        self._drop_flight()
        try:
            for i, s in enumerate(self._slots):
                if s.req is None:
                    continue
                if s.req.done:
                    # crashed INSIDE _finish (injected crash_before_done):
                    # the request completed — deliver the sentinel it
                    # never got and free the slot, but do NOT rewrite its
                    # terminal state or journal a tombstone; the whole
                    # point of the crash window is that a successor
                    # engine replays this request (at-least-once)
                    if s.req.stream is not None:
                        s.req.stream.put(None)
                    self._free_slot_state(i)
                    continue
                s.req.error = msg
                self._finish(i, "error")
            if self._waiting is not None:
                req, self._waiting = self._waiting, None
                self._fail_request(req, msg)
            while self._preempted:  # parked requests die with the engine
                self._fail_request(self._preempted.popleft().req, msg)
            while True:
                try:
                    req = self._queue.get_nowait()
                except queue.Empty:
                    break
                self._fail_request(req, msg)
            self.active[:] = False
        finally:
            self._cleanup = False

    def _drop_flight(self) -> None:
        """Forget the step in flight, its tokens unread: what failed may
        have left some slots a token behind the others (a crash inside
        one slot's finish), so nothing computed on top of it is emitted.
        Its writes lie inside pages its slots held at dispatch, and
        whatever uses those pages next is enqueued behind it."""
        fl, self._flight = self._flight, None
        if fl is not None:
            self.decode_rows_discarded += sum(
                r is not None for r in fl.reqs)

    def run_until_idle(self, max_steps: int = 100000) -> None:
        for _ in range(max_steps):
            if not self.step():
                return

    # ---- graceful shutdown (docs/serving.md) -------------------------------

    def begin_drain(self) -> None:
        """Stop admitting (new submits shed as "draining" -> 503 +
        Retry-After) while in-flight and queued work keeps stepping.
        Thread-safe; whoever steps the engine keeps stepping it."""
        self._draining = True

    def idle(self) -> bool:
        """No accepted-but-unfinished work remains. Based on the
        in-flight charge counter, not container emptiness — a request
        mid-admission is momentarily in no container but still holds
        its charge, so a concurrent drain poll cannot miss it."""
        with self._stat_lock:
            return self._inflight == 0

    def drain(self, timeout_s: Optional[float] = None) -> bool:
        """begin_drain + step to completion from the CALLING thread —
        for engines driven without an _EngineThread (the ApiServer
        instead begin_drain()s and lets its worker thread finish the
        work). Returns True when fully drained; False on timeout, with
        the unfinished requests left pending (journaled engines replay
        them at the next start — the crash-recovery path is the
        fallback, not the plan)."""
        self.begin_drain()
        deadline = (None if timeout_s is None
                    else self._clock() + timeout_s)
        while not self.idle():
            if deadline is not None and self._clock() > deadline:
                return False
            self.step()
        self._drain()  # a step whose every row finished meanwhile
        return True

    def close(self) -> None:
        """Flush, COMPACT, and detach the journal (and close the
        request log). Call only after the stepping thread has stopped:
        compaction os.replace()s the file under any live append handle.
        After a clean drain the rewrite holds zero entries — the next
        start replays nothing; after a timed-out drain it holds exactly
        the unfinished tail. Idempotent."""
        self._drain()
        if self._request_log is not None:
            self._request_log.close()
        if self._journal is None:
            return
        from bigdl_tpu.serving.journal import RequestJournal

        path = self._journal.path
        self._journal.close()
        self._journal = None
        RequestJournal.compact(path)

    # ---- observability helpers (serving/metrics.py renders these) ----------

    def uptime_seconds(self) -> float:
        """Engine age in its own clock domain (simulated clocks report
        simulated uptime — by design)."""
        return max(self._clock() - self._t_start, 0.0)

    def page_leaks(self) -> int:
        """PageTable.page_leaks of a paged engine (0 is the invariant at
        drain); a dense engine has no pages to leak."""
        return self.pages.page_leaks() if self.paged else 0

    def moe_load(self) -> dict:
        """The newest decode step's expert load (the `moe_*` arguments of
        its `decode_step` span, and the `/metrics` gauges): {} for a dense
        model or before the first step."""
        if self._moe_last is None:
            return {}
        experts, live = self._moe_last
        return _moe_load(experts[:, live], self.config.num_experts,
                         self._first_expert)

    def kv_utilization(self) -> float:
        """Fraction of the KV pool holding live state: allocated pages
        over the allocatable pool (paged; page 0 is scratch), or written
        positions over total row capacity (dense). Family caches without
        a standard pos vector report 0 rather than guessing."""
        if self.paged:
            return self.pages.utilization()
        # HOST-side estimate only: reading cache.pos here would race the
        # decode jit's cache donation (the buffers are deleted for most
        # of every step, and /metrics scrapes from a handler thread).
        # Active slots' written content ≈ prompt + emitted tokens; freed
        # slots count zero (their stale device pos is a ghost).
        used = 0
        for i, s in enumerate(self._slots):
            if s.req is not None and self.active[i]:
                used += min(len(s.req.prompt) + len(s.req.out_tokens),
                            self.max_len)
        return used / max(self.n_slots * self.max_len, 1)
