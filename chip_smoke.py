#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that bigdl-tpu still starts on the chip.

    python chip_smoke.py             # one TPU chip: generate() and the paged server
    python chip_smoke.py --kernels   # every fused format, compile + numerics
    python chip_smoke.py --train     # two QLoRA steps, fused backward vs XLA remat
    python chip_smoke.py --tp 4      # the paged server over a 4-chip tp mesh
    python chip_smoke.py --rehearse  # tiny model, CPU, interpreter: never a pass

The default run drives the two public inference surfaces once, through
the entry points a user calls, at the full width and depth of the
`mistral-7b` preset (sym_int4, weights from a seed; the machine has no
network):

  path A  `TpuModel.generate`: 2 prompts of 96 tokens, 32 new tokens.
          Dense KV, flash-attention prefill, fused GEMM, the
          single-program `lax.while_loop` decode with fused GEMV.
  path B  what `bigdl-tpu serve --paged` builds: `ApiServer(paged=True,
          n_slots=8, max_len=2048)`, 8 concurrent HTTP requests of
          token-id prompts in three prefill buckets, 32 new tokens each
          (six greedy and one sampled on /v1/completions, one streamed on
          /generate_stream: /v1/completions has no streaming form), then
          /metrics, then a graceful shutdown.

It checks results, not liveness: shapes, token counts, finite logprobs,
zero failed requests and zero engine step errors, logits against the
XLA route, and the kernels' presence in the lowered programs. Any failed
check and any exception in any phase ends the run with a non-zero exit
code; nothing is caught and carried past. One process, no children: a
chip belongs to one process at a time.

Every timing it prints names the device it came from and is
information about set-up, not a benchmark.

The last line of standard output is one JSON object,
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}.
Without a TPU it exits non-zero and prints no such line.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import json
import os
import re
import sys
import threading
import time
import urllib.request

# Logits, not tokens. Three computations of the same last-position logits
# on the same packed weights:
#   truth    the XLA route (QTensor.dequantize + einsum,
#            ops/attention.attention) computing in float32 at "highest";
#   xla      the same route in bf16, what the library runs without kernels;
#   kernels  what the program under test ran.
# Both bf16 pipelines round activations after every op (2^-9 each) in
# different orders, and a network of random zero-mean weights amplifies
# the difference layer by layer: on the v5e each sits 6-8% (rel L2) from
# the truth after 32 layers (PR 21 chip runs), far more than a trained
# checkpoint would show. So the kernels are held to the XLA route's own
# distance from the truth: no more than LOGITS_FACTOR times it (with a
# floor for shallow models, where both are nearly exact), and never more
# than LOGITS_CAP. A wrong kernel (a shifted scale block, a dropped chunk,
# a bad mask) decorrelates the logits: rel L2 near 1.4.
LOGITS_FACTOR = 2.0
LOGITS_FLOOR = 1e-2
LOGITS_CAP = 0.25

# Chosen-token logprobs the engine reported, against the truth's
# teacher-forced logprobs of the same tokens: absolute difference in
# nats, worst of the tokens compared. Logits of about unit scale that
# differ by 8% rel L2 move a log-softmax by one or two tenths of a nat; a
# wrong program is off by whole nats (an uncorrelated model scores its
# tokens near ln(vocabulary) = 10.4).
LOGPROB_ATOL = 0.5

# --kernels: max |y - ref| / max |ref| per kernel call, ref in f32 at
# "highest" from QTensor.dequantize. The kernel rounds x and the decoded
# weight tile to bf16 (2^-9 each) and accumulates in f32; measured
# 3e-3 to 5e-3 on the v5e for sym_int4 (PR 21).
KERNEL_RTOL = 2e-2

# --train: fused backward against the XLA remat, same step, same data,
# plain SGD so that an update is its gradient. The two steps share the
# whole forward, so the loss of step 1 must agree to f32 noise. The
# backward differs where dx = g @ dequant(W) is computed: bf16 weight
# tiles with f32 accumulation in the kernel, a bf16 einsum in the remat.
# The same random network that puts two correct bf16 forwards 6-8% apart
# put the two backwards 7% apart on the v5e (rel L2 over the LoRA B
# factors after two updates; they start at zero, so they are the
# accumulated gradient; PR 21 chip run). The bound leaves that figure
# almost three times its room; a wrong dx decorrelates the gradient (rel
# L2 near 1.4). The loss after one update moved by 5e-4.
TRAIN_LOSS_RTOL = 6e-3
TRAIN_GRAD_RTOL = 0.2


def say(msg: str = "") -> None:
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    """A failed check ends the run: raise, never warn."""
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


# --------------------------------------------------------------------------
# set-up: device, versions, compile cache and compile accounting
# --------------------------------------------------------------------------

class CompileLog:
    """Per-program compile seconds and persistent-cache counts, from
    `jax.monitoring` (JAX names the program in `fun_name`)."""

    _DURATIONS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
        "/jax/core/compile/backend_compile_duration": "compile",
    }

    def __init__(self):
        import jax.monitoring as mon

        self.programs = collections.defaultdict(
            lambda: collections.Counter())
        self.events = collections.Counter()
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **kw):
        kind = self._DURATIONS.get(event)
        if kind is not None:
            # lowering and compiling say "jit(f)", tracing says "f"
            name = re.sub(r"^jit\((.*)\)$", r"\1", kw.get("fun_name", "?"))
            p = self.programs[name]
            p[kind] += secs
            p["n_" + kind] += 1

    def _on_event(self, event, **kw):
        if event.startswith("/jax/compilation_cache/"):
            self.events[event.rsplit("/", 1)[1]] += 1

    def report(self, cache_dir, floor: float = 0.5) -> None:
        say(f"compile cache directory: {cache_dir}")
        ev = self.events
        say(f"persistent cache: {ev['compile_requests_use_cache']} "
            f"requests, {ev['cache_hits']} hits, "
            f"{ev['cache_misses']} misses (a miss is counted when the "
            "entry is written)")
        say("seconds per compiled program (trace / lower / "
            f"backend compile or cache load), programs over {floor} s:")
        small = collections.Counter()
        for name, p in sorted(self.programs.items(),
                              key=lambda kv: -kv[1]["compile"]):
            total = p["trace"] + p["lower"] + p["compile"]
            if total < floor:
                small["n"] += p["n_compile"]
                small["s"] += total
                continue
            say(f"  {name:34s} x{p['n_compile']:<3d} "
                f"{p['trace']:7.2f} / {p['lower']:7.2f} / "
                f"{p['compile']:7.2f}")
        say(f"  ({small['n']} smaller programs, {small['s']:.2f} s in all)")


def device_report(rehearse: bool) -> dict:
    """Refuse to start without a TPU; print what JAX found."""
    import importlib.metadata as md

    import jax

    backend = jax.default_backend()
    if backend != "tpu" and not rehearse:
        print(f"chip_smoke: JAX found no TPU (default backend is "
              f"{backend!r}). This program runs on the chip only; "
              "`--rehearse` debugs it on the CPU and is never a pass.",
              file=sys.stderr)
        raise SystemExit(2)
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    say(f"device: platform={info['platform']} "
        f"device_kind={info['kind']!r} count={info['count']}")

    def version(pkg):
        try:
            return md.version(pkg)
        except md.PackageNotFoundError:
            return "not installed"

    say(f"versions: python {sys.version.split()[0]}, "
        f"jax {version('jax')}, jaxlib {version('jaxlib')}, "
        f"libtpu {version('libtpu')}")

    from bigdl_tpu.utils.flops import chip_specs

    specs = chip_specs(dev)  # raises on an accelerator it does not know
    if specs is not None:
        say(f"chip table: {specs[0] / 1e12:.0f} TFLOP/s bf16, "
            f"{specs[1] / 1e9:.0f} GB/s HBM (utils/flops._CHIPS)")

    from bigdl_tpu.ops import pallas

    if rehearse:
        check(pallas.interpret_mode() and pallas.use_pallas(),
              "rehearsal must interpret the kernels")
    else:
        check(not pallas.interpret_mode(),
              "interpret_mode() is true on the chip")
        check(pallas.use_pallas(),
              f"use_pallas() is false: {pallas.why_not_pallas()}")
    say(f"kernels: use_pallas={pallas.use_pallas()} "
        f"interpret_mode={pallas.interpret_mode()}")
    return info


def memory_line(tag: str) -> list:
    import jax

    rows = []
    for d in jax.devices():
        st = d.memory_stats() or {}
        rows.append((st.get("bytes_in_use", 0),
                     st.get("peak_bytes_in_use", 0)))
    say(f"memory after {tag}: " + "; ".join(
        f"dev{i} {u / 2**30:.2f} GiB in use, peak {p / 2**30:.2f}"
        for i, (u, p) in enumerate(rows)))
    return rows


# --------------------------------------------------------------------------
# the model
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the run is cut to. `full` is the contract; `rehearsal` only
    debugs this file on a CPU."""
    a_prompt: int
    a_new: int
    n_slots: int
    max_len: int
    buckets: tuple  # three engine prefill buckets (multiples of 16)
    b_new: int
    check_prompt: int  # the hand-driven request's prompt length
    train_len: int
    mosaic: bool = True  # kernels lower to Mosaic calls (not interpreted)


FULL = Sizes(a_prompt=96, a_new=32, n_slots=8, max_len=2048,
             buckets=(80, 256, 512), b_new=32, check_prompt=250,
             train_len=1024)
REHEARSAL = Sizes(a_prompt=24, a_new=6, n_slots=8, max_len=256,
                  buckets=(32, 48, 64), b_new=6, check_prompt=44,
                  train_len=64, mosaic=False)


def smoke_config(rehearse: bool):
    from bigdl_tpu.models.config import PRESETS

    cfg = PRESETS["mistral-7b"]
    if rehearse:
        # mistral-shaped, and every dimension still eligible for the
        # kernels (O multiples of 128, K multiples of 64, head_dim 128)
        cfg = dataclasses.replace(
            cfg, vocab_size=512, hidden_size=256, intermediate_size=512,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=128, sliding_window=128)
    return cfg


def build_model(cfg, seed: int = 0, device=None):
    """Packed weights from a seed, made on the host and placed."""
    import jax

    from bigdl_tpu import native
    from bigdl_tpu.api import TpuModel
    from bigdl_tpu.quant.synth import synth_params

    t0 = time.perf_counter()
    host = synth_params(cfg, seed=seed)
    t1 = time.perf_counter()
    nbytes = sum(a.nbytes for a in jax.tree.leaves(host))
    params = jax.block_until_ready(jax.device_put(host, device))
    t2 = time.perf_counter()
    say(f"weights: hidden {cfg.hidden_size}, intermediate "
        f"{cfg.intermediate_size}, vocabulary {cfg.vocab_size}, "
        f"{cfg.num_hidden_layers} layers, sym_int4, seed {seed}: "
        f"{nbytes / 2**30:.2f} GiB packed; "
        f"{t1 - t0:.1f} s to build on the host, {t2 - t1:.1f} s to place")
    say("host quantizer: not used (packed fields are synthesized); "
        f"native library available: {native.available()}")
    return TpuModel(cfg, params, "sym_int4")


def seeded_prompt(rng, n: int, vocab: int) -> list:
    return [int(t) for t in rng.integers(1, vocab, n)]


# --------------------------------------------------------------------------
# routes and lowered programs
# --------------------------------------------------------------------------

KERNEL_NAMES = ("qmatmul", "qmatmul_lora", "flash_attention",
                "paged_decode_attention", "flash_train_fwd",
                "flash_train_dq", "flash_train_dkv", "qmatmul_dx",
                "dw_matmul")


def kernels_in(text: str) -> collections.Counter:
    """Mosaic custom calls in a lowered program, by kernel name. A scan
    body is lowered once, so a layer's kernels count once."""
    found = collections.Counter(
        re.findall(r'kernel_name = "([A-Za-z_0-9]+)"', text))
    n_calls = text.count("@tpu_custom_call")
    check(sum(found.values()) == n_calls,
          f"{n_calls} tpu_custom_call sites but names {dict(found)}")
    return found


def collectives_in(hlo: str) -> list:
    """(op, result shape, result bytes) of every collective in a
    compiled program's text; for a gather the result is the gathered
    size."""
    out = []
    for line in hlo.splitlines():
        m = re.search(
            r"= (.*?) (all-gather|all-reduce|reduce-scatter|all-to-all|"
            r"collective-permute)(?:-start)?\(", line)
        if m is None:
            continue
        nbytes = 0
        for dt, dims in re.findall(r"\b([a-z]+[0-9]+)\[([0-9,]*)\]",
                                   m.group(1)):
            n = 1
            for d in dims.split(","):
                n *= int(d) if d else 1
            nbytes += n * int(re.sub(r"\D", "", dt)) // 8
        out.append((m.group(2), m.group(1), nbytes))
    return out


def print_routes(tag: str, routes) -> None:
    say(f"routes traced in {tag} (count  op  route  detail):")
    for (op, route, detail), n in sorted(routes.items()):
        say(f"  {n:3d}  {op:9s} {route:22s} {detail}")


def check_all_linears_fused(tag: str, routes) -> None:
    bad = [k for k in routes if k[0] == "linear" and k[1] == "xla"]
    check(not bad, f"{tag}: quantized projections on the XLA route: {bad}")


@contextlib.contextmanager
def xla_route():
    """Trace with the kernels off and matmuls at "highest" (which only
    matters to float32 operands): the route every kernel names as its
    parity oracle (QTensor.dequantize + einsum,
    ops/attention.attention)."""
    import jax

    prev = os.environ.get("BIGDL_TPU_PALLAS")
    os.environ["BIGDL_TPU_PALLAS"] = "0"
    try:
        with jax.default_matmul_precision("highest"):
            yield
    finally:
        if prev is None:
            del os.environ["BIGDL_TPU_PALLAS"]
        else:
            os.environ["BIGDL_TPU_PALLAS"] = prev


def rel_l2(a, b) -> float:
    import numpy as np

    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    check(bool(np.all(np.isfinite(a)) and np.all(np.isfinite(b))),
          "non-finite logits")
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def logits_verdict(tag: str, kernels, xla, truth) -> None:
    """Hold the kernel route to the XLA route's own distance from the
    float32 truth (see LOGITS_FACTOR)."""
    import numpy as np

    e_k, e_x = rel_l2(kernels, truth), rel_l2(xla, truth)
    bound = min(LOGITS_CAP, LOGITS_FACTOR * max(e_x, LOGITS_FLOOR))
    say(f"{tag}: rel L2 from the float32 truth: kernels {e_k:.4f}, XLA "
        f"route in bf16 {e_x:.4f} (bound for the kernels {bound:.4f}); "
        f"kernels vs XLA route {rel_l2(kernels, xla):.4f}; |logits| rms "
        f"{float(np.sqrt(np.mean(np.square(truth)))):.3f}; argmax "
        f"{int(np.argmax(kernels))} / {int(np.argmax(xla))} / "
        f"{int(np.argmax(truth))}")
    check(e_k <= bound, f"{tag}: kernels are {e_k:.4f} from the truth, "
          f"the XLA route {e_x:.4f}")


def dense_logits_fn(cfg, compute_dtype=None):
    """logits [T, V] of one unpadded prompt through a dense cache of
    its own, prefill mode: a fresh function per call site, so no two
    routes share a trace. compute_dtype=None is the forward's bf16."""
    from bigdl_tpu import kvcache
    from bigdl_tpu.models import llama
    from bigdl_tpu.utils import round_up

    def dense_logits(params, tokens):  # tokens [1, T]
        cache = kvcache.init_cache(
            cfg.num_hidden_layers, 1, round_up(tokens.shape[1], 64),
            cfg.num_key_value_heads, cfg.head_dim_)
        kw = {} if compute_dtype is None else {
            "compute_dtype": compute_dtype}
        logits, _ = llama.forward(cfg, params, tokens, cache,
                                  mode="prefill", **kw)
        return logits[0]

    return dense_logits


# --------------------------------------------------------------------------
# path A: TpuModel.generate
# --------------------------------------------------------------------------

def path_a(model, sz: Sizes, rng) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.generate import (
        GenerationConfig, generate_tokens, pad_prompts,
    )
    from bigdl_tpu.ops.routes import record_routes
    from bigdl_tpu.utils import cache_len_for, flags

    cfg = model.config
    say("\n== path A: TpuModel.generate ==")
    prompts = [seeded_prompt(rng, sz.a_prompt, cfg.vocab_size)
               for _ in range(2)]
    with record_routes() as routes:
        t0 = time.perf_counter()
        out = model.generate(prompts, max_new_tokens=sz.a_new)
        t1 = time.perf_counter()
    out2 = model.generate(prompts, max_new_tokens=sz.a_new)
    t2 = time.perf_counter()
    check(out.shape == (2, sz.a_new) and out.dtype.kind == "i",
          f"generate returned {out.dtype}{out.shape}")
    check(bool(np.all((out >= 0) & (out < cfg.vocab_size))),
          "generated ids out of range")
    check(bool(np.array_equal(out, out2)),
          "greedy generate is not deterministic across two calls")
    say(f"generate: 2 x {sz.a_prompt} prompt tokens -> {out.shape} ids; "
        f"first call {t1 - t0:.1f} s (compile + run), second "
        f"{t2 - t1:.2f} s (run)")
    print_routes("path A", routes)
    check_all_linears_fused("path A", routes)
    check(any(k[:2] == ("attention", "pallas:flash") for k in routes),
          "path A prefill did not take the flash kernel")

    # the program generate() ran, lowered again from the same arguments
    tokens, start = pad_prompts(prompts, 0)
    text = generate_tokens.lower(
        cfg, model.params, jnp.asarray(tokens), jnp.asarray(start),
        jax.random.PRNGKey(0), GenerationConfig(max_new_tokens=sz.a_new),
        model.forward_fn,
        cache_len=cache_len_for(tokens.shape[1], sz.a_new),
        quantize_kv=False, compress_budget=0,
        compress_window=1, last_logits=flags.last_lm_head_default(),
        cache_init=None, streaming=None,
    ).as_text()
    found = kernels_in(text)
    say(f"lowered generate_tokens: Mosaic calls {dict(found)}")
    # prefill body 4 projections + LM head, decode body the same again
    check(not sz.mosaic or (found["qmatmul"] == 10
                            and found["flash_attention"] == 1),
          f"path A lowered kernels: {dict(found)}")

    # logits, not tokens: last position of one prompt after prefill
    toks = jnp.asarray([prompts[0]], jnp.int32)
    with record_routes() as r_k:
        lk = jax.jit(dense_logits_fn(cfg))(model.params, toks)[-1]
    check(any(k[:2] == ("attention", "pallas:flash") for k in r_k),
          "logits check: prefill did not take the flash kernel")
    with xla_route(), record_routes() as r_x:
        lx = jax.jit(dense_logits_fn(cfg))(model.params, toks)[-1]
        lt = jax.jit(dense_logits_fn(cfg, jnp.float32))(
            model.params, toks)[-1]
    check(not any("pallas" in k[1] for k in r_x),
          f"the oracle took a kernel: {list(r_x)}")
    logits_verdict("logits after prefill", lk, lx, lt)


# --------------------------------------------------------------------------
# path B: the paged server
# --------------------------------------------------------------------------

def _post(port: int, path: str, payload: dict, timeout: float):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=timeout)


def _jit_of(fn):
    """The jit behind an engine step (`_with_mesh` wraps it)."""
    return fn if hasattr(fn, "lower") else fn.__wrapped__


def hand_driven_check(server, sz: Sizes, rng, oracle_params=None):
    """One request through the engine's own programs, stepped from this
    thread before the server starts: paged prefill, then decode steps,
    then (a) the engine's reported logprobs against the XLA route's
    teacher-forced logprobs of the same tokens and (b) the full
    last-position logits after 8 decode steps, from the engine's live
    page pool through the paged-attention kernel, against a dense
    prefill of prompt + emitted tokens on the XLA route."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.ops.routes import record_routes

    eng = server.engine
    cfg = eng.config
    prompt = seeded_prompt(rng, sz.check_prompt, cfg.vocab_size)
    n_steps = 8
    with record_routes() as routes:
        t0 = time.perf_counter()
        req = eng.submit(prompt, max_new_tokens=n_steps + 4)
        while len(req.out_tokens) < n_steps + 1:
            check(not req.done, f"hand-driven request ended early: "
                  f"{req.finish_reason} {req.error}")
            eng.step()
        t1 = time.perf_counter()
    check(len(req.out_tokens) == n_steps + 1,
          f"expected {n_steps + 1} tokens, got {len(req.out_tokens)}")
    emitted = list(req.out_tokens)
    reported = np.asarray(req.out_logprobs, np.float64)
    slot = next(i for i, s in enumerate(eng._slots) if s.req is req)
    say(f"hand-driven request: {len(prompt)} prompt tokens, prefill + "
        f"{n_steps} decode steps in {t1 - t0:.1f} s (compile + run)")
    print_routes("path B (engine prefill + decode step)", routes)
    if oracle_params is None:  # one chip: the kernels must be in it
        check_all_linears_fused("path B", routes)
        check(any(k[:2] == ("attention", "pallas:paged") for k in routes),
              "the decode step did not take the paged-attention kernel")

    # the state after 8 decode steps, read through the kernel route:
    # the same forward the engine's decode step jits, on its live pool
    fwd = eng.model.forward_fn

    def next_logits(params, cur, cache):
        logits, _ = fwd(cfg, params, cur[:, None], cache, mode="decode")
        return logits[:, -1]

    with eng.model._mesh_ctx():
        lk = np.asarray(jax.jit(next_logits)(
            eng.model.params, eng.cur, eng.cache))[slot]

    # the oracles: dense prefill of prompt + emitted tokens, XLA route,
    # on one device (for --tp: the one-chip reference, in this process)
    seq = jnp.asarray([prompt + emitted], jnp.int32)
    params = eng.model.params if oracle_params is None else oracle_params
    with xla_route():
        lx = np.asarray(jax.jit(dense_logits_fn(cfg))(params, seq))[-1]
        lt_all = np.asarray(
            jax.jit(dense_logits_fn(cfg, jnp.float32))(params, seq))
    logits_verdict(f"logits after {n_steps} paged decode steps, engine "
                   "pool vs dense prefill", lk, lx, lt_all[-1])

    lp = lt_all[len(prompt) - 1: len(prompt) + n_steps].astype(np.float64)
    top = lp.max(-1, keepdims=True)
    lp = lp - top - np.log(np.sum(np.exp(lp - top), -1, keepdims=True))
    want = lp[np.arange(n_steps + 1), emitted]
    worst = float(np.max(np.abs(reported - want)))
    say(f"engine-reported logprobs of its {n_steps + 1} tokens vs the "
        f"truth's: max |diff| {worst:.4f} nats (bound {LOGPROB_ATOL})")
    check(worst <= LOGPROB_ATOL, f"path B logprob diff {worst}")

    eng.run_until_idle()
    check(req.done and req.finish_reason == "length" and not req.error,
          f"hand-driven request finished {req.finish_reason!r} "
          f"{req.error!r}")
    return routes


def lowered_engine_programs(server, sz: Sizes, tp: int) -> None:
    """Lower the engine's decode step and one prefill bucket from the
    arguments the engine passes, and count the kernels in the text."""
    import jax
    import jax.numpy as jnp

    eng = server.engine
    with eng.model._mesh_ctx():
        decode = _jit_of(eng._decode).lower(
            eng.model.params, eng.cur, eng.cache, jax.random.PRNGKey(0),
            jnp.asarray(eng._temp), jnp.asarray(eng._topk),
            jnp.asarray(eng._topp), jnp.asarray(eng._dosample), eng.seen,
            jnp.asarray(eng._penalty), lora=None)
        prefill = _jit_of(eng._paged_prefill).lower(
            eng.model.params, eng.kind.leaves(eng.cache),
            (jnp.zeros((1, eng.max_pages_per_row), jnp.int32), None),
            jnp.zeros((1,), jnp.int32),
            jnp.zeros((1, sz.buckets[1]), jnp.int32), jnp.asarray(0),
            jnp.zeros((1,), jnp.int32), lora=None)
        d_found = kernels_in(decode.as_text())
        p_found = kernels_in(prefill.as_text())
        say(f"lowered engine decode step: Mosaic calls {dict(d_found)}")
        say(f"lowered engine prefill (bucket {sz.buckets[1]}): Mosaic "
            f"calls {dict(p_found)}; it works on the row's own pages at a "
            "scalar position, so its attention is flash")
        if tp == 1:
            if not sz.mosaic:
                return
            check(d_found["qmatmul"] == 5
                  and d_found["paged_decode_attention"] == 1,
                  f"decode step lowered kernels: {dict(d_found)}")
            check(p_found["qmatmul"] == 5 and p_found["flash_attention"] == 1
                  and len(p_found) == 2,
                  f"prefill lowered kernels: {dict(p_found)}")
            return
        # tp > 1: the projections run per shard under shard_map (so
        # their kernels are in the text); attention and the LM head
        # stay with XLA's partitioner, where the kernels are off by rule
        check(not sz.mosaic or (
            d_found["qmatmul"] > 0
            and d_found["paged_decode_attention"] == 0),
            f"decode step lowered kernels under tp: {dict(d_found)}")
        # tp > 1: XLA partitions the program; no collective may move a
        # weight. The smallest per-layer weight (wk) is K*O/2 packed.
        cfg = eng.config
        weight_bytes = cfg.kv_dim * cfg.hidden_size // 2
        colls = collectives_in(decode.compile().as_text())
        n_coll = len(colls)
        big = [c for c in colls if c[2] >= weight_bytes]
        say(f"compiled decode step under tp={tp}: {n_coll} collectives, "
            f"{len(big)} of them at least one packed weight "
            f"({weight_bytes} bytes)")
        check(n_coll > 0, "no collective found in a tp > 1 decode step")
        check(not big, f"collectives the size of a weight: {big}")


def http_phase(server, sz: Sizes, rng) -> None:
    import numpy as np

    cfg = server.engine.config
    port = server.port
    say(f"\nserver on 127.0.0.1:{port}; {sz.n_slots} concurrent requests")
    # seeded lengths 64..512 falling into three prefill buckets
    lens = [int(b - rng.integers(0, 16)) for b in
            [sz.buckets[i % 3] for i in range(8)]]
    prompts = [seeded_prompt(rng, n, cfg.vocab_size) for n in lens]
    kinds = ["greedy"] * 6 + ["sampled", "streamed"]
    results: list = [None] * 8
    errors: list = []

    def client(i):
        try:
            if kinds[i] == "streamed":
                resp = _post(port, "/generate_stream", {
                    "prompt": prompts[i], "max_new_tokens": sz.b_new,
                }, 900)
                toks, done, err = [], False, None
                for raw in resp:
                    line = raw.decode().strip()
                    if not line.startswith("data: "):
                        continue
                    body = line[len("data: "):]
                    if body == "[DONE]":
                        done = True
                        break
                    evt = json.loads(body)
                    if "error" in evt:
                        err = evt["error"]
                    else:
                        toks.append(evt["token"])
                results[i] = (resp.status, {"tokens": toks, "done": done,
                                            "error": err})
                return
            payload = {"prompt": prompts[i], "max_tokens": sz.b_new,
                       "logprobs": 1, "temperature": 0}
            if kinds[i] == "sampled":
                payload.update(temperature=0.8, top_p=0.9)
            resp = _post(port, "/v1/completions", payload, 900)
            results[i] = (resp.status, json.loads(resp.read()))
        except Exception as e:  # re-raised on the main thread below
            errors.append((i, repr(e)))

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=1000)
    check(not any(t.is_alive() for t in threads), "a client never returned")
    check(not errors, f"client errors: {errors}")
    say(f"8 responses in {time.perf_counter() - t0:.1f} s (three prefill "
        f"buckets compile inside this window); prompt lengths {lens}")
    for i, (status, body) in enumerate(results):
        check(status == 200, f"request {i} ({kinds[i]}): HTTP {status}")
        if kinds[i] == "streamed":
            check(body["done"] and body["error"] is None
                  and len(body["tokens"]) == sz.b_new
                  and all(0 <= t < cfg.vocab_size for t in body["tokens"]),
                  f"streamed request: {body}")
            continue
        ch = body["choices"][0]
        lps = ch["logprobs"]["token_logprobs"]
        check(body["usage"]["completion_tokens"] == sz.b_new
              and body["usage"]["prompt_tokens"] == lens[i]
              and len(lps) == sz.b_new and ch["finish_reason"] == "length",
              f"request {i} ({kinds[i]}): {body['usage']} {ch}")
        check(bool(np.all(np.isfinite(lps)) and np.all(np.asarray(lps) < 1e-5)),
              f"request {i} ({kinds[i]}): logprobs {lps}")
    say("every response: 200, exactly the tokens asked for, finite "
        "logprobs")

    text = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=30).read().decode()

    def metric(pattern):
        m = re.search(rf"^{pattern} (\S+)$", text, re.M)
        return None if m is None else float(m.group(1))

    failed = metric("bigdl_tpu_requests_failed_total")
    step_errors = metric("bigdl_tpu_engine_step_errors_total")
    finished = {r: metric('bigdl_tpu_requests_finished_total'
                          rf'\{{reason="{r}"\}}') or 0
                for r in ("length", "stop", "error", "shed", "timeout",
                          "invalid")}
    say(f"/metrics: requests_failed_total {failed}, "
        f"engine_step_errors_total {step_errors}, finished {finished}")
    check(failed == 0 and step_errors == 0,
          f"failed requests {failed}, engine step errors {step_errors} "
          f"({server.engine.last_step_error})")
    check(finished["length"] == 9 and sum(finished.values()) == 9,
          f"finish reasons {finished} (8 served + 1 hand-driven)")


def path_b(model, sz: Sizes, rng, tp: int = 1, oracle_params=None) -> None:
    from bigdl_tpu.serving.api_server import ApiServer

    say(f"\n== path B: ApiServer(paged=True, n_slots={sz.n_slots}, "
        f"max_len={sz.max_len})" + (f" over tp={tp}" if tp > 1 else "")
        + " ==")
    t0 = time.perf_counter()
    # what cmd_serve builds for `bigdl-tpu serve --paged` with no
    # tokenizer in the model directory
    server = ApiServer(model, tokenizer=None, paged=True,
                       n_slots=sz.n_slots, max_len=sz.max_len, port=0)
    say(f"server object (page pool of {server.engine.n_pages} pages) in "
        f"{time.perf_counter() - t0:.1f} s")
    hand_driven_check(server, sz, rng, oracle_params)
    lowered_engine_programs(server, sz, tp)
    server.start()
    try:
        http_phase(server, sz, rng)
    finally:
        drained = server.shutdown(graceful=True, drain_timeout_s=60)
    check(drained and not server.worker.is_alive(),
          "graceful shutdown did not drain and stop the engine thread")
    check(server.engine.page_leaks() == 0, "page leak at drain")
    say("graceful shutdown: drained, engine thread stopped, no page leak")


# --------------------------------------------------------------------------
# the flags: each an explicit extra run with its own exit code
# --------------------------------------------------------------------------

def run_main(sz: Sizes, rehearse: bool) -> None:
    import numpy as np

    rng = np.random.default_rng(0)
    model = build_model(smoke_config(rehearse))
    memory_line("placing the weights")
    path_a(model, sz, rng)
    memory_line("path A")
    path_b(model, sz, rng)
    memory_line("path B")


def run_tp(sz: Sizes, rehearse: bool, tp: int) -> None:
    """Path B over a tp mesh. Under a mesh axis that XLA partitions the
    kernels are off by rule (ops/pallas/__init__.py), so this run is on
    record as the XLA route; what it proves is that the sharded server
    is right and that nothing is gathered or parked on one device."""
    import jax
    import numpy as np

    check(len(jax.devices()) >= tp,
          f"--tp {tp} needs {tp} devices, JAX sees {len(jax.devices())}")
    rng = np.random.default_rng(0)
    cfg = smoke_config(rehearse)
    one = build_model(cfg, device=jax.devices()[0])
    one_chip = memory_line("placing the weights on device 0")[0][0]
    model = dataclasses.replace(one)  # same host-made weights, resharded
    model.to_mesh(tp=tp, dp=1)
    jax.block_until_ready(model.params)
    path_b(model, sz, rng, tp=tp, oracle_params=one.params)
    del one
    rows = memory_line(f"path B over tp={tp} (one-chip copy dropped)")
    used = [u for u, _ in rows[:tp]]
    say(f"bytes in use per device: {used}; one chip held {one_chip} "
        "with the weights alone")
    if not sz.mosaic:  # the CPU backend reports no memory statistics
        return
    check(max(used) <= 1.2 * min(used),
          f"memory not spread evenly over the {tp} devices: {used}")
    check(max(used) < one_chip,
          f"a device holds {max(used)} bytes, no less than the weights "
          f"alone on one chip ({one_chip})")


def run_train(sz: Sizes, rehearse: bool) -> None:
    """Two steps of train/qlora.make_train_step, fused backward against
    the XLA remat the kernels keep as their oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from bigdl_tpu.models import llama
    from bigdl_tpu.ops.routes import record_routes
    from bigdl_tpu.train.qlora import init_lora, make_train_step

    cfg = smoke_config(rehearse)
    model = build_model(cfg)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(
        [seeded_prompt(rng, sz.train_len, cfg.vocab_size)], jnp.int32)
    mask = jnp.ones(tokens.shape, jnp.float32)
    opt = optax.sgd(1e-2)
    say(f"\n== train: 2 QLoRA steps, B=1 T={sz.train_len} rank 8, "
        "remat per layer ==")
    got = {}
    for fused in (True, False):
        lora = init_lora(cfg, jax.random.PRNGKey(1), rank=8)
        state = opt.init(lora["layers"])
        step = jax.jit(make_train_step(
            cfg, llama.forward, opt, remat=True, return_grad_norm=True,
            fused_backward=fused))
        rows = []
        with record_routes() as routes:
            text = step.lower(model.params, lora, state, tokens,
                              mask).as_text()
        found = kernels_in(text)
        say(f"fused_backward={fused}: lowered Mosaic calls {dict(found)}")
        if fused:
            print_routes("the train step", routes)
            check_all_linears_fused("train", routes)
            check(not sz.mosaic or (
                found["qmatmul_dx"] > 0 and found["flash_train_dq"] > 0
                and found["flash_train_dkv"] > 0),
                f"backward kernels missing: {dict(found)}")
        else:
            check(found["qmatmul_dx"] == 0,
                  "the oracle step contains the dx kernel")
        for i in range(2):
            t0 = time.perf_counter()
            lora, state, loss, gnorm = step(model.params, lora, state,
                                            tokens, mask)
            loss, gnorm = float(loss), float(gnorm)
            say(f"  step {i + 1}: loss {loss:.5f} grad norm {gnorm:.5f} "
                f"({time.perf_counter() - t0:.1f} s"
                f"{', compile + run' if i == 0 else ''})")
            check(np.isfinite(loss) and np.isfinite(gnorm),
                  "non-finite loss or gradient norm")
            rows.append((loss, gnorm))
        got[fused] = rows, jax.tree.map(np.asarray, lora["layers"])
        memory_line(f"train, fused_backward={fused}")
    for i, ((lf, gf), (lx, gx)) in enumerate(zip(got[True][0],
                                                 got[False][0])):
        dl = abs(lf - lx) / abs(lx)
        dg = abs(gf - gx) / abs(gx)
        say(f"step {i + 1}: loss rel diff {dl:.2e} (bound "
            f"{TRAIN_LOSS_RTOL}), grad norm rel diff {dg:.2e} (bound "
            f"{TRAIN_GRAD_RTOL})")
        check(dl <= TRAIN_LOSS_RTOL and dg <= TRAIN_GRAD_RTOL,
              f"fused and remat steps disagree at step {i + 1}")
    # the B factors start at zero, so they ARE the accumulated updates
    flat = [np.concatenate([np.ravel(pair["b"]).astype(np.float64)
                            for pair in got[f][1].values()])
            for f in (True, False)]
    check(bool(np.linalg.norm(flat[1]) > 0), "the adapters never moved")
    err = rel_l2(flat[0], flat[1])
    say(f"LoRA B factors after 2 SGD updates, fused vs remat: rel L2 "
        f"{err:.2e} (bound {TRAIN_GRAD_RTOL})")
    check(err <= TRAIN_GRAD_RTOL, f"adapter trees differ by {err}")


def run_kernels(rehearse: bool) -> None:
    """Every format of the registry at K=4096 and K=14336, M in
    {1, 8, 512}: compile plus numerics against QTensor.dequantize; the
    dx kernel per format; dw_matmul; the fp8-KV attention epilogues.
    Every row runs; any failed row makes the exit code non-zero."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.ops import pallas
    from bigdl_tpu.ops.attention import attention
    from bigdl_tpu.ops.linear import _QGEMV_QTYPES, fused_why_not, linear
    from bigdl_tpu.quant.synth import synth_qtensor

    ks, ms, O = ((4096, 14336), (1, 8, 512), 4096)
    if rehearse:
        ks, ms, O = ((1024,), (1, 40), 256)
    rows = []

    def row(name, fn):
        t0 = time.perf_counter()
        try:
            err = fn()
            ok = err <= KERNEL_RTOL
            msg = f"rel err {err:.2e}"
        except Exception as e:  # the matrix records every outcome
            ok, msg = False, f"{type(e).__name__}: {str(e)[:600]}"
        rows.append((name, ok, msg))
        say(f"  {'ok  ' if ok else 'FAIL'} {name:44s} "
            f"{time.perf_counter() - t0:6.1f} s  {msg}")

    def rel(y, ref):
        y = np.asarray(y, np.float32)
        ref = np.asarray(ref, np.float32)
        if not np.all(np.isfinite(y)):
            return float("inf")
        return float(np.max(np.abs(y - ref)) / np.max(np.abs(ref)))

    say("\n== kernels: forward (ops/linear.linear route) ==")
    for qtype in _QGEMV_QTYPES:
        for K in ks:
            rng = np.random.default_rng(0)
            w = jax.device_put(synth_qtensor(qtype, O, K, rng))
            with jax.default_matmul_precision("highest"):
                wd = w.dequantize(jnp.float32)
            for M in ms:
                x = jnp.asarray(rng.normal(size=(M, K)), jnp.bfloat16)

                def fwd():
                    why = fused_why_not(w, lead=0)
                    if why is not None:
                        raise RuntimeError(f"XLA route: {why}")
                    y = jax.jit(linear)(x, w)
                    with jax.default_matmul_precision("highest"):
                        ref = x.astype(jnp.float32) @ wd.T
                    return rel(y, ref)

                row(f"qmatmul {qtype} M{M} K{K} O{O}", fwd)
            g = jnp.asarray(rng.normal(size=(ms[-1], O)), jnp.bfloat16)

            def dx():
                y = pallas.qmatmul_dx(g, w)
                with jax.default_matmul_precision("highest"):
                    ref = g.astype(jnp.float32) @ wd
                return rel(y, ref)

            row(f"qmatmul_dx {qtype} M{ms[-1]} K{K} O{O}", dx)

    say("\n== kernels: dW and the attention epilogues ==")
    for K in ks:
        rng = np.random.default_rng(1)
        g = jnp.asarray(rng.normal(size=(ms[-1], O)), jnp.bfloat16)
        x = jnp.asarray(rng.normal(size=(ms[-1], K)), jnp.bfloat16)

        def dw():
            y = pallas.dw_matmul(g, x)
            with jax.default_matmul_precision("highest"):
                ref = g.astype(jnp.float32).T @ x.astype(jnp.float32)
            return rel(y, ref)

        row(f"dw_matmul M{ms[-1]} K{K} O{O}", dw)

    from bigdl_tpu import kvcache, kvpaged

    Hq, Hkv, D = (32, 8, 128) if not rehearse else (2, 1, 128)
    for quant in (False, True):
        rng = np.random.default_rng(2)
        B, T = 2, 128 if not rehearse else 32
        q = jnp.asarray(rng.normal(size=(B, T, Hq, D)), jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(B, T, Hkv, D)), jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(B, T, Hkv, D)), jnp.bfloat16)
        tj = jnp.arange(T)
        mask = (tj[None, :] <= tj[:, None])[None, None, None]

        def flash():
            cache = kvcache.init_cache(1, B, T, Hkv, D, quantize_kv=quant)
            cache = kvcache.update_layer(cache, jnp.asarray(0), k, v)
            if quant:
                ka, va, ksc, vsc = kvcache.read_layer_raw(
                    cache, jnp.asarray(0))
            else:
                ka, va = kvcache.read_layer(cache, jnp.asarray(0))
                ksc = vsc = None
            y = pallas.flash_attention(q, ka, va, k_scale=ksc, v_scale=vsc)
            kd, vd = kvcache.read_layer(cache, jnp.asarray(0))
            with jax.default_matmul_precision("highest"):
                ref = attention(q, kd, vd, mask)
            return rel(y, ref)

        row(f"flash_attention fp8_kv={quant} B{B} T{T} Hq{Hq} Hkv{Hkv}",
            flash)

        def paged():
            page, mp, nb = 64, 4, 8
            cache = kvpaged.init_paged(1, nb * mp + 1, page, Hkv, D, nb, mp,
                                       quantize_kv=quant)
            bt = 1 + np.arange(nb * mp, dtype=np.int32).reshape(nb, mp)
            n0 = 100
            cache = dataclasses.replace(cache, block_tables=jnp.asarray(bt))
            kk = jnp.asarray(rng.normal(size=(nb, n0, Hkv, D)), jnp.bfloat16)
            vv = jnp.asarray(rng.normal(size=(nb, n0, Hkv, D)), jnp.bfloat16)
            cache = kvpaged.update_layer(cache, jnp.asarray(0), kk, vv)
            pos = jnp.asarray(rng.integers(0, n0, nb), jnp.int32)
            qq = jnp.asarray(rng.normal(size=(nb, Hq, D)), jnp.bfloat16)
            y = pallas.paged_decode_attention(
                qq, cache.k, cache.v, cache.block_tables, jnp.asarray(0),
                pos, cache.start, k_scale=cache.k_scale,
                v_scale=cache.v_scale)
            kd, vd = kvpaged.read_layer(cache, jnp.asarray(0))
            sj = jnp.arange(kd.shape[1])
            m = (sj[None, :] <= pos[:, None])[:, None, None, None]
            with jax.default_matmul_precision("highest"):
                ref = attention(qq[:, None], kd, vd, m)[:, 0]
            return rel(y, ref)

        row(f"paged_decode_attention fp8_kv={quant} B8 Hq{Hq} Hkv{Hkv}",
            paged)

    bad = [r for r in rows if not r[1]]
    say(f"\nkernels: {len(rows) - len(bad)} of {len(rows)} rows passed")
    for name, _, msg in bad:
        say(f"  FAILED {name}: {msg}")
    check(not bad, f"{len(bad)} kernel rows failed")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--kernels", action="store_true")
    ap.add_argument("--train", action="store_true")
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny model on the CPU through the interpreter; "
                         "output headed REHEARSAL, exit code 3, never a pass")
    args = ap.parse_args(argv)
    check(sum([args.kernels, args.train, args.tp > 1]) <= 1,
          "--kernels, --train and --tp are separate runs")
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["BIGDL_TPU_PALLAS"] = "interpret"
        if args.tp > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={args.tp}")
        say("REHEARSAL: CPU, tiny model, kernels through the Pallas "
            "interpreter. Nothing below is a result.")

    t_start = time.perf_counter()
    info = device_report(args.rehearse)

    from bigdl_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    log = CompileLog()
    sz = REHEARSAL if args.rehearse else FULL
    if args.kernels:
        run_kernels(args.rehearse)
    elif args.train:
        run_train(sz, args.rehearse)
    elif args.tp > 1:
        run_tp(sz, args.rehearse, args.tp)
    else:
        run_main(sz, args.rehearse)

    say("\n== set-up ==")
    log.report(cache_dir)
    say(f"wall time {time.perf_counter() - t_start:.0f} s on "
        f"{info['count']} x {info['kind']}")
    if args.rehearse:
        say("REHEARSAL complete: not a pass.")
        return 3
    say(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
