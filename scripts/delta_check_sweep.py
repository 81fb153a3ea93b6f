#!/usr/bin/env python3
"""The reference check of `solar-open2-250b.longctx-closed` at the CELL's
sizes, over many seeds in one process on the chip: the two readings that
`logprob_atol_nats` of bench/configs/solar-open2-250b-int4.json lies between,
the router's margins at 320 experts and top-8, and five faults PLANTED in the
program's path. `scripts/conv_check_sweep.py`'s sweep (its `main`, which says
what a line holds) with this cell, these faults, and every layer sparse.

The faults (`planted`: the served forward has no switch for them; the
benchmark's drawn decays are about a half a token, bench/configs/..
`assumed.weights` says what a logprob can see there):

 * the state dropped at the hand-over: a prefill leaves a ZERO state, so
   the first decode steps read nothing of the prompt;
 * q and k exchanged: the convolutions' outputs of q and of k on each
   other's place in front of the delta rule;
 * the decay applied after the update in place of before: S = diag(exp(g))
   (S + beta k (v - S^T k)^T), token by token in both phases;
 * beta not doubled: sigmoid(b_proj x), in (0, 1);
 * one share's first id off by one: the experts held are taken for ids
   first + 1 .. first + held.

    chiprun -- python3 scripts/delta_check_sweep.py --first 2147485301 --n 5

Exit code 1 if a program's reading is not finite or a float8 control reads
UNDER the program on its seed. `--rehearse`: the files' rehearsal sizes on
the CPU."""

from __future__ import annotations

import contextlib
import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "scripts"))


def _faults() -> dict:
    """name -> [(module, the function of it the fault replaces, by what)]."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu import kvhybrid
    from bigdl_tpu.models import llama

    chunked, step, mix = (kvhybrid.kda_chunked, kvhybrid.kda_step,
                          kvhybrid.kda_mix)
    held = llama._held_share

    def no_hand_over(q, k, v, g, beta, S):
        o, S = chunked(q, k, v, g, beta, S)
        return o, jnp.zeros_like(S)

    def qk_exchanged(cache, layer, qkv, g, beta, conv_w, **kw):
        def swap(a):  # [.., q | k | v] -> [.., k | q | v]
            q, k, v = jnp.split(a, 3, axis=-1)
            return jnp.concatenate([k, q, v], axis=-1)

        return mix(cache, layer, swap(qkv), g, beta, swap(conv_w), **kw)

    def late_decay_step(q, k, v, g, beta, S):
        r = jnp.einsum("bhpn,bhn->bhp", S, k)
        S = S + (beta[..., None] * (v - r))[..., None] * k[:, :, None, :]
        S = S * jnp.exp(g)[:, :, None, :]
        return jnp.einsum("bhpn,bhn->bhp", S, q), S

    def late_decay_chunked(q, k, v, g, beta, S):
        def one(S, t):
            o, S = late_decay_step(*(a[None] for a in t), S[None])
            return S[0], o[0]

        S, o = jax.lax.scan(one, S, (q, k, v, g, beta))
        return o, S

    def beta_single(cache, layer, qkv, g, beta, conv_w, **kw):
        return mix(cache, layer, qkv, g, beta / 2, conv_w, **kw)

    def first_off_by_one(config, topv, topi):
        return held(dataclasses.replace(
            config, first_expert=config.first_expert + 1,
            router_experts=config.router_width + 1), topv, topi)

    return {
        "state dropped at the hand-over": [
            (kvhybrid, "kda_chunked", no_hand_over)],
        "q and k exchanged": [(kvhybrid, "kda_mix", qk_exchanged)],
        "decay after the update": [
            (kvhybrid, "kda_step", late_decay_step),
            (kvhybrid, "kda_chunked", late_decay_chunked),
            # the kernel has the rule inside: the step runs in `jnp`
            (kvhybrid, "why_not_kda_kernel", lambda d, inner: "planted")],
        "beta not doubled": [(kvhybrid, "kda_mix", beta_single)],
        "a share's first id off by one": [
            (llama, "_held_share", first_off_by_one)]}


#: the faults this script plants (tests/test_solar_open2.py plants them too)
FAULTS = ("state dropped at the hand-over", "q and k exchanged",
          "decay after the update", "beta not doubled",
          "a share's first id off by one")


@contextlib.contextmanager
def planted(name: str):
    """The program with the fault `name` in it, for the programs TRACED
    inside the block (a compiled program keeps what it was traced with)."""
    with contextlib.ExitStack() as stack:
        for module, attr, broken in _faults()[name]:
            whole = getattr(module, attr)
            setattr(module, attr, broken)
            stack.callback(setattr, module, attr, whole)
        yield


if __name__ == "__main__":
    import conv_check_sweep

    sys.exit(conv_check_sweep.main(
        "solar-open2-250b.longctx-closed", faults=FAULTS, plant=planted,
        sparse_layers=lambda hf: hf["num_hidden_layers"]))
