"""Entry `generate`: `TpuModel.generate`, one caller, host clock around the
call (it returns host arrays, so the device's work is done when it returns).
The reference's own all-in-one protocol (`in_out_pairs`): one prompt length,
one output length, batch 1. Bypasses the engine entirely: flash prefill, dense
cache, one program with a `lax.while_loop` decode."""

from __future__ import annotations

import itertools

import numpy as np

from bench.records import Frozen, Req

KIND = "generate"


class Driver:
    def __init__(self, cell, model, clock, tracer=None):
        self.model = model
        self.clock = clock
        self.traced = tracer is not None
        self.vocab = model.config.vocab_size

    def _call(self, prompt, max_new):
        t0 = self.clock()
        out = self.model.generate([prompt], max_new_tokens=max_new)
        t1 = self.clock()
        r = Req(t_due=None, t_sent=t0, n_prompt=len(prompt), max_new=max_new,
                stamps=[t1], done=True)
        if (out.shape != (1, max_new) or out.dtype.kind != "i"
                or not np.all((out >= 0) & (out < self.vocab))):
            r.failed = r.wrong = True
            r.why = f"generate returned {out.dtype}{out.shape}"
        return r, out

    def warm(self, shapes: dict):
        if len(shapes["prompt_lengths"]) != 1:
            raise ValueError("the generate entry takes one prompt length")
        self.n_prompt = shapes["prompt_lengths"][0]
        self.max_new = shapes["max_output"]
        prompt = [1 + (j % 100) for j in range(self.n_prompt)]
        self._call(prompt, self.max_new)
        if self.traced:  # the prefill-only program of the traced run
            self._call(prompt, 1)

    def check(self, cell, hf, params, seed: int) -> tuple:
        """`generate` yields tokens, no logits; so the reference is run over
        the prompt and the tokens generated (the cell's own shape: no other
        program compiles) and each greedy token must be the reference's
        best or within the tolerance of it, in logit units."""
        import jax
        import jax.numpy as jnp

        tol = cell.config["bench"]["tolerances"]["greedy_gap_atol"]["value"]
        rng = np.random.default_rng(int(seed))
        prompt = rng.integers(1, hf["vocab_size"], self.n_prompt).tolist()
        r, out = self._call(prompt, self.max_new)
        if r.failed:
            return False, r.why
        toks = [int(t) for t in out[0]]
        seq = jnp.asarray(prompt + toks[:-1], jnp.int32)
        ref = jax.jit(cell.reference().logits, static_argnums=(0, 3))
        logits = np.asarray(ref(Frozen(hf), params, seq, self.max_new),
                            np.float64)
        gaps = logits.max(-1) - logits[np.arange(self.max_new), toks]
        n_best = int(np.sum(gaps == 0))
        msg = (f"generate's {self.max_new} greedy tokens under the float32 "
               f"reference: {n_best} are its best, the others trail its best "
               f"logit by at most {gaps.max():.4f} (bound {tol})")
        return bool(np.all(np.isfinite(logits)) and gaps.max() <= tol), msg

    def run(self, plan, seconds: float) -> tuple:
        if plan.kind != "closed" or plan.clients != 1:
            raise ValueError("the generate entry is one closed-loop caller")
        reqs, extra = [], {}
        t0 = self.clock()
        t_end = t0 + seconds
        for planned in itertools.cycle(plan.requests):
            if self.clock() >= t_end:
                break
            reqs.append(self._call(planned.prompt, planned.max_new_tokens)[0])
        t_stop = self.clock()
        if self.traced:  # after the window: prefill alone, three calls
            p = plan.requests[0].prompt
            extra["prefill_ms"] = [
                (r.stamps[0] - r.t_sent) * 1e3
                for r in (self._call(p, 1)[0] for _ in range(3))]
        extra["drain_s"] = t_stop - t_end
        return t0, t_end, reqs, extra

    def finish(self) -> list:
        return []

