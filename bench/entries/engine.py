"""Entry `engine`: the paged `InferenceEngine` in this process, stepped by the
program's own loop (`serving.api_server._EngineThread`, what `bigdl-tpu serve
--paged` runs), requests by `submit()` with a stream. Every token is stamped
by the benchmark's clock as it leaves the stream queue, in one collector
thread; the main thread is the only one that submits (at due times in an open
loop, when a client's last request ended in a closed one).

Greedy decoding, EOS ignored: every request yields exactly its
`max_new_tokens`.
"""

from __future__ import annotations

import heapq
import itertools
import math
import queue
import threading
import time

import numpy as np

from bench.records import Frozen, Planned, Req

KIND = "engine"
DRAIN_S = 90.0  # allowance after the window for requests in flight: the
# longest output (256 tokens) at the slowest step seen (0.2 s) is 51 s


class _Stream:
    """What `Request.stream` needs: put(). All requests share one queue."""
    __slots__ = ("q", "i")

    def __init__(self, q, i):
        self.q, self.i = q, i

    def put(self, tok):
        self.q.put((self.i, tok))


class Driver:
    def __init__(self, cell, model, clock, tracer=None):
        from bigdl_tpu.generate import GenerationConfig
        from bigdl_tpu.serving.api_server import _EngineThread
        from bigdl_tpu.serving.engine import InferenceEngine

        e = cell.config["bench"]["engine"]
        self.settings = e
        self.clock = clock
        self.engine = InferenceEngine(
            model, n_slots=e["n_slots"], max_len=e["max_len"], paged=True,
            page_size=e["page_size"], n_pages=e["n_pages"],
            gen=GenerationConfig(eos_token_id=None), tracer=tracer,
            clock=clock)
        self.thread = _EngineThread(self.engine)
        self.thread.start()
        self.q = queue.SimpleQueue()
        self.reqs: list = []
        self._lock = threading.Condition()
        self._ready: list = []  # heap of (due, seq, planned | None)
        self._seq = 0
        self._next_closed = None  # iterator over a closed plan's requests
        self._think = 0.0
        self._t_end = math.inf
        self.collector = threading.Thread(target=self._collect, daemon=True)
        self.collector.start()

    # ---- the two threads --------------------------------------------------

    def _collect(self):
        while True:
            i, tok = self.q.get()
            now = self.clock()
            if i < 0:
                return
            r = self.reqs[i]
            if tok is not None:
                r.stamps.append(now)
                continue
            r.done = True
            with self._lock:
                if self._next_closed is not None and now < self._t_end:
                    self._push(now + self._think)
                self._lock.notify()

    def _push(self, due):  # under self._lock
        self._seq += 1
        heapq.heappush(self._ready, (due, self._seq))

    def _submit(self, planned, t_due):
        i = len(self.reqs)
        r = Req(t_due=t_due, t_sent=self.clock(), n_prompt=len(planned.prompt),
                max_new=planned.max_new_tokens)
        self.reqs.append(r)
        r.handle = self.engine.submit(
            planned.prompt, max_new_tokens=planned.max_new_tokens,
            stream=_Stream(self.q, i))
        return r

    def _wait_done(self, reqs, deadline):
        with self._lock:
            while not all(r.done for r in reqs):
                left = deadline - self.clock()
                if left <= 0:
                    return False
                self._lock.wait(min(left, 0.5))
        return True

    # ---- set-up -----------------------------------------------------------

    def warm(self, shapes: dict):
        """One request per prompt length the cell can offer (a prefill
        program per 16-token bucket), which also runs the decode step and
        the first-token sampling."""
        first = len(self.reqs)
        rng = np.random.default_rng(0)
        vocab = self.engine.config.vocab_size
        for n in shapes["prompt_lengths"]:
            # fresh random ids: a prefix shared with an earlier warm-up
            # prompt would hit the radix cache and prefill only the tail,
            # leaving this length's program uncompiled
            self._submit(Planned(0.0, rng.integers(1, vocab, n).tolist(), 3),
                         None)
        ok = self._wait_done(self.reqs[first:], self.clock() + 1100)
        if not ok or any(len(r.stamps) != 3 for r in self.reqs[first:]):
            raise RuntimeError("warm-up requests did not finish")

    def check(self, cell, hf, params, seed: int) -> tuple:
        """The system against the plain reference (part (a) of `correct`):
        a seeded 250-token prompt and 9 new tokens. The engine's chosen-token
        logprobs (the first from the prefill's logits, the rest from decode
        steps through the pages) against the reference's log-softmax of a
        float32 forward over the same token sequence, held by the WORST
        token to the configuration's `logprob_atol_nats`."""
        import jax
        import jax.numpy as jnp

        tol = cell.config["bench"]["tolerances"]["logprob_atol_nats"]["value"]
        n_new = 9
        n_prompt = min(250, self.settings["max_len"] // 2)
        rng = np.random.default_rng(int(seed))
        prompt = rng.integers(1, hf["vocab_size"], n_prompt).tolist()
        r = self._submit(Planned(0.0, prompt, n_new), None)
        if not self._wait_done([r], self.clock() + 600):
            return False, "check request did not finish"
        toks = list(r.handle.out_tokens)
        got = np.asarray(r.handle.out_logprobs, np.float64)
        if len(toks) != n_new or len(got) != n_new:
            return False, f"check request gave {len(toks)} tokens"
        seq = jnp.asarray(prompt + toks[:-1], jnp.int32)
        ref = jax.jit(cell.reference().logits, static_argnums=(0, 3))
        logits = np.asarray(
            ref(Frozen(hf), params, seq, n_new), np.float64)
        lse = np.log(np.sum(np.exp(logits - logits.max(-1, keepdims=True)),
                            -1)) + logits.max(-1)
        want = logits[np.arange(n_new), toks] - lse
        diff = np.abs(got - want)
        worst, median = float(np.max(diff)), float(np.median(diff))
        msg = (f"engine logprobs of its {n_new} tokens vs the float32 "
               f"reference, |diff| in nats: "
               f"{' '.join(f'{d:.2f}' for d in diff)}; worst "
               f"{worst:.4f} (bound {tol}), median {median:.4f}")
        ok = np.all(np.isfinite(got)) and worst <= tol
        return bool(ok), msg

    # ---- the window -------------------------------------------------------

    def run(self, plan, seconds: float, on_tick=None) -> tuple:
        """Offer `plan` for `seconds`; returns (t0, t1, requests)."""
        first = len(self.reqs)
        t0 = self.clock()
        t_end = t0 + seconds
        with self._lock:
            self._t_end = t_end
            self._ready = []
            if plan.kind == "closed":
                self._next_closed = iter(itertools.cycle(plan.requests))
                self._think = plan.think_s
                for _ in range(plan.clients):
                    self._push(t0)
                pending = None
            else:
                pending = iter(plan.requests)
        nxt = next(pending, None) if pending is not None else None
        while True:
            now = self.clock()
            if now >= t_end:
                break
            if on_tick is not None:
                on_tick(now - t0)
            if plan.kind == "open":
                if nxt is None:
                    time.sleep(min(0.05, t_end - now))
                    continue
                due = t0 + nxt.t_due
                if due > now:
                    time.sleep(min(due - now, 0.05))
                    continue
                self._submit(nxt, due)
                nxt = next(pending, None)
                continue
            with self._lock:
                if not self._ready or self._ready[0][0] > now:
                    wait = (self._ready[0][0] - now if self._ready else 0.05)
                    self._lock.wait(min(wait, 0.05, t_end - now))
                    continue
                heapq.heappop(self._ready)
                planned = next(self._next_closed)
            self._submit(planned, None)
        with self._lock:
            self._next_closed = None
        reqs = self.reqs[first:]
        self._wait_done(reqs, self.clock() + DRAIN_S)
        t_stop = self.clock()
        for r in reqs:
            h = r.handle
            if not r.done:
                r.failed, r.why = True, "not finished within the drain"
            elif h.finish_reason != "length" or h.error:
                r.failed = r.wrong = True
                r.why = f"{h.finish_reason}: {h.error}"
            elif len(r.stamps) != r.max_new or len(h.out_tokens) != r.max_new:
                r.failed = r.wrong = True
                r.why = f"{len(r.stamps)} tokens, asked {r.max_new}"
            elif not np.all(np.isfinite(h.out_logprobs)):
                r.failed = r.wrong = True
                r.why = "non-finite logprobs"
        return t0, t_end, reqs, {"drain_s": t_stop - t_end,
                                 "drain_allowance_s": DRAIN_S}

    # ---- the end ----------------------------------------------------------

    def finish(self) -> list:
        """Part (c) of `correct`, after the drain; stops both threads."""
        problems = []
        eng = self.engine
        eng.begin_drain()
        deadline = self.clock() + DRAIN_S
        while not eng.idle() and self.clock() < deadline:
            time.sleep(0.01)
        self.thread.stop_flag.set()
        self.thread.join(timeout=60)
        self.q.put((-1, None))
        self.collector.join(timeout=60)
        if self.thread.is_alive() or self.collector.is_alive():
            problems.append("a benchmark thread did not stop")
        if eng.step_errors:
            problems.append(f"engine_step_errors_total {eng.step_errors}: "
                            f"{eng.last_step_error}")
        if not eng.idle():
            problems.append("engine not idle after the drain")
        elif eng.page_leaks():
            problems.append(f"page_leaks() = {eng.page_leaks()}")
        eng.close()
        return problems
