"""The one general traffic generator. A traffic file (bench/traffic/*.json)
names it and gives its parameters; a new mix is a new data file.

    {"generator": "arrivals", "entry": "engine",
     "process": {"kind": "poisson", "rate_rps": 2.4}
              | {"kind": "closed", "clients": 16, "think_s": 0, "block": 32},
     "prompt": <lengths>, "output": <lengths>}

A plan is a pure function of (parameters, seed, seconds). Sizes and gaps are
fixed quantile sets shuffled by the seed (generators/lengths.py): each seed
offers the same work in another order. Prompts are fresh random token ids
(1..vocab-1), so no two share a prefix. After sim/traces.py's poisson_trace,
which draws uniform lengths and has no closed loop.

An open process yields requests with due times inside [0, seconds). A closed
process yields a pool of requests that the clients take in order, as many
as they get through. They get through a part of the pool only, and which
sizes fall in that part is the seed's doing: where the sizes differ much,
"block" makes every run of that many consecutive requests a full quantile
set, so that every seed's window holds the same work (PERF.md section 6). A mix that needs more (bursts, shared prefixes, several
classes of request in one queue) brings a generator file of its own, named
by its traffic files.
"""

from __future__ import annotations

import math

import numpy as np

from bench.generators.lengths import distinct_lengths, quantile_lengths
from bench.records import Plan, Planned

CLOSED_POOL = 256  # requests a closed loop cycles through


def _exp_gaps(n: int, mean: float) -> list:
    """n exponential gaps at their mid-quantiles, rescaled to the mean."""
    g = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    k = mean * n / sum(g)
    return [x * k for x in g]


def _sizes(params: dict, n: int, rng, block: int = 0) -> list:
    """n (prompt_len, max_new) pairs in blocks of `block` (one block of n
    where it is 0): each block holds a full quantile set of prompt and of
    output lengths, paired at random and shuffled."""
    out = []
    for start in range(0, n, block or n):
        k = min(block or n, n - start)
        p = quantile_lengths(params["prompt"], k)
        o = quantile_lengths(params["output"], k)
        part = list(zip(p, [o[j] for j in rng.permutation(k)]))
        out.extend(part[j] for j in rng.permutation(k))
    return out


def _prompts(sizes, vocab: int, rng) -> list:
    return [rng.integers(1, vocab, plen).tolist() for plen, _ in sizes]


def _due_times(proc: dict, seconds: float, rng) -> list:
    if proc["kind"] != "poisson":
        raise ValueError(f"unknown open process {proc['kind']!r}")
    n = max(1, round(proc["rate_rps"] * seconds))
    gaps = _exp_gaps(n, seconds / n)
    gaps = [gaps[j] for j in rng.permutation(n)]
    # the first request is due at 0, the last one gap before the end
    return [float(t) for t in np.cumsum(gaps) - gaps[0]]


def plan(params: dict, seed: int, seconds: float, vocab: int) -> Plan:
    rng = np.random.default_rng(int(seed))
    proc = params["process"]
    if proc["kind"] == "closed":
        sizes = _sizes(params, CLOSED_POOL, rng, int(proc.get("block", 0)))
        prompts = _prompts(sizes, vocab, rng)
        reqs = [Planned(0.0, p, int(s[1])) for p, s in zip(prompts, sizes)]
        return Plan("closed", reqs, clients=int(proc["clients"]),
                    think_s=float(proc.get("think_s", 0.0)))
    ts = _due_times(proc, seconds, rng)
    sizes = _sizes(params, len(ts), rng)
    prompts = _prompts(sizes, vocab, rng)
    return Plan("open", [Planned(t, p, int(s[1]))
                         for t, p, s in zip(ts, prompts, sizes)])


def shapes(params: dict) -> dict:
    """Every prompt length and the largest output length the mix can offer:
    what a cell warms up, and no more."""
    return {"prompt_lengths": distinct_lengths(params["prompt"]),
            "max_output": max(distinct_lengths(params["output"]))}
