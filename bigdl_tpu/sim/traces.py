"""Seeded synthetic arrival-trace generators + the replayable JSONL
trace format (docs/benchmarking.md).

Three workload shapes, mirroring how continuous-batching serving is
characterized by request-level TTFT/TPOT (arxiv 2311.00502) and the
radix-cache workload the ROADMAP scheduler item targets:

* `poisson_trace` — memoryless arrivals at a constant offered rate;
* `bursty_trace` — on/off modulated Poisson (exponential on/off
  periods), the queue-depth stressor;
* `prefix_heavy_trace` — a pool of shared system-prompt prefixes with
  divergence at configurable split points, the prefix-cache workload.

Every generator is a pure function of its seed (numpy Generator,
PCG64): the same call produces a byte-identical trace, and the trace
file round-trips byte-identically through `Trace.save`/`Trace.load`.
Lines carry the journal's crc suffix (serving/journal.crc_line) so
interior rot in a banked trace is detectable, and writes commit
atomically (utils/durability.atomic_write).
"""

from __future__ import annotations

import dataclasses
import json
from typing import Optional

import numpy as np

from bigdl_tpu.serving.journal import crc_line, split_crc_line

FORMAT = "bigdl-tpu-sim-trace"
VERSION = 1


@dataclasses.dataclass
class Arrival:
    """One request of the offered load: submit at simulated second `t`.
    `adapter` names the LoRA fine-tune this tenant decodes with (None =
    the shared base; serving/adapters.py)."""

    t: float
    prompt: list
    max_new_tokens: int
    adapter: Optional[str] = None

    def tokens_offered(self) -> int:
        return len(self.prompt) + self.max_new_tokens


@dataclasses.dataclass
class Trace:
    """An ordered offered-load trace plus the header that regenerates
    it (name/seed/params — the report embeds it so a banked number is
    traceable to its workload)."""

    name: str
    seed: int
    arrivals: list
    params: dict = dataclasses.field(default_factory=dict)

    @property
    def duration_s(self) -> float:
        return self.arrivals[-1].t if self.arrivals else 0.0

    def offered_tokens(self) -> int:
        return sum(a.tokens_offered() for a in self.arrivals)

    # -- JSONL serialization ------------------------------------------------

    def to_lines(self) -> list:
        head = {"format": FORMAT, "version": VERSION, "name": self.name,
                "seed": self.seed, "n": len(self.arrivals),
                "params": self.params}
        lines = [crc_line(json.dumps(head, sort_keys=True))]
        for a in self.arrivals:
            rec = {"t": round(a.t, 6), "prompt": a.prompt,
                   "max_new_tokens": a.max_new_tokens}
            if a.adapter is not None:
                rec["adapter"] = a.adapter
            lines.append(crc_line(json.dumps(rec, sort_keys=True)))
        return lines

    def save(self, path: str) -> None:
        from bigdl_tpu.utils.durability import atomic_write

        payload = ("\n".join(self.to_lines()) + "\n").encode("utf-8")
        atomic_write(path, lambda f: f.write(payload))

    @classmethod
    def load(cls, path: str) -> "Trace":
        with open(path, encoding="utf-8") as f:
            raw = [ln for ln in f.read().splitlines() if ln]
        if not raw:
            raise ValueError(f"{path}: empty trace file")
        bodies = []
        for i, line in enumerate(raw):
            body, ok = split_crc_line(line)
            if ok is not True:
                # a trace is a generated artifact, not an append-under-
                # crash journal: ANY bad line means the workload is not
                # the one the header claims — refuse, don't salvage
                raise ValueError(
                    f"{path}:{i + 1}: corrupt trace line (crc "
                    f"{'mismatch' if ok is False else 'missing'})"
                )
            bodies.append(json.loads(body))
        head = bodies[0]
        if head.get("format") != FORMAT:
            raise ValueError(f"{path}: not a {FORMAT} file")
        arrivals = [Arrival(t=b["t"], prompt=list(b["prompt"]),
                            max_new_tokens=b["max_new_tokens"],
                            adapter=b.get("adapter"))
                    for b in bodies[1:]]
        if head.get("n") != len(arrivals):
            raise ValueError(
                f"{path}: header claims {head.get('n')} arrivals, file "
                f"holds {len(arrivals)} — truncated trace"
            )
        return cls(name=head["name"], seed=head["seed"],
                   arrivals=arrivals, params=head.get("params", {}))


# ---------------------------------------------------------------------------
# generators
# ---------------------------------------------------------------------------


def _lengths(rng, n: int, lo: int, hi: int) -> np.ndarray:
    return rng.integers(lo, hi + 1, size=n)


def _prompt(rng, length: int, vocab: int) -> list:
    # token ids in [1, vocab): id 0 is the conventional pad id and a
    # pad-leading prompt would left-pad differently than intended
    return rng.integers(1, vocab, size=int(length)).tolist()


def poisson_trace(rate_rps: float, n_requests: int, seed: int = 0,
                  vocab: int = 256, prompt_len=(8, 48),
                  out_tokens=(4, 24), name: str = "poisson",
                  t0: float = 0.0, params: Optional[dict] = None) -> Trace:
    """Memoryless arrivals: exponential inter-arrival gaps at
    `rate_rps`, uniform prompt/output-length marginals."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate_rps, size=n_requests)
    ts = t0 + np.cumsum(gaps)
    plens = _lengths(rng, n_requests, *prompt_len)
    olens = _lengths(rng, n_requests, *out_tokens)
    arrivals = [
        Arrival(t=round(float(ts[i]), 6),
                prompt=_prompt(rng, plens[i], vocab),
                max_new_tokens=int(olens[i]))
        for i in range(n_requests)
    ]
    p = {"rate_rps": rate_rps, "vocab": vocab,
         "prompt_len": list(prompt_len), "out_tokens": list(out_tokens)}
    p.update(params or {})
    return Trace(name=name, seed=seed, arrivals=arrivals, params=p)


def bursty_trace(rate_on_rps: float, n_requests: int, seed: int = 0,
                 mean_on_s: float = 1.0, mean_off_s: float = 2.0,
                 vocab: int = 256, prompt_len=(8, 48),
                 out_tokens=(4, 24), name: str = "bursty") -> Trace:
    """On/off modulated Poisson: exponential ON windows at
    `rate_on_rps` separated by exponential OFF gaps with no arrivals —
    the queue fills in bursts and drains in the silences, the shape
    that separates a p99 story from a mean-throughput story."""
    rng = np.random.default_rng(seed)
    arrivals = []
    t = 0.0
    while len(arrivals) < n_requests:
        on_end = t + float(rng.exponential(mean_on_s))
        while len(arrivals) < n_requests:
            t += float(rng.exponential(1.0 / rate_on_rps))
            if t > on_end:
                break
            arrivals.append(Arrival(
                t=round(t, 6),
                prompt=_prompt(rng, int(_lengths(rng, 1, *prompt_len)[0]),
                               vocab),
                max_new_tokens=int(_lengths(rng, 1, *out_tokens)[0]),
            ))
        t = on_end + float(rng.exponential(mean_off_s))
    return Trace(name=name, seed=seed, arrivals=arrivals, params={
        "rate_on_rps": rate_on_rps, "mean_on_s": mean_on_s,
        "mean_off_s": mean_off_s, "vocab": vocab,
        "prompt_len": list(prompt_len), "out_tokens": list(out_tokens),
    })


def prefix_heavy_trace(rate_rps: float, n_requests: int, seed: int = 0,
                       n_prefixes: int = 3, split_points=(16, 32, 48),
                       share_p: float = 0.85, vocab: int = 256,
                       tail_len=(4, 16), out_tokens=(4, 16),
                       name: str = "prefix-heavy") -> Trace:
    """The radix-cache workload: a pool of `n_prefixes` shared system
    prompts; each arrival reuses one with probability `share_p`,
    cutting it at a seeded choice of `split_points` and appending a
    unique tail — so shared prefixes hit the paged prefix cache at
    page-aligned AND mid-page split points (the sub-page copy path)."""
    rng = np.random.default_rng(seed)
    prefixes = [_prompt(rng, max(split_points), vocab)
                for _ in range(n_prefixes)]
    gaps = rng.exponential(1.0 / rate_rps, size=n_requests)
    ts = np.cumsum(gaps)
    arrivals = []
    for i in range(n_requests):
        tail = _prompt(rng, int(_lengths(rng, 1, *tail_len)[0]), vocab)
        if rng.random() < share_p:
            pre = prefixes[int(rng.integers(0, n_prefixes))]
            cut = int(split_points[int(rng.integers(0, len(split_points)))])
            prompt = pre[:cut] + tail
        else:
            prompt = tail
        arrivals.append(Arrival(
            t=round(float(ts[i]), 6), prompt=prompt,
            max_new_tokens=int(_lengths(rng, 1, *out_tokens)[0]),
        ))
    return Trace(name=name, seed=seed, arrivals=arrivals, params={
        "rate_rps": rate_rps, "n_prefixes": n_prefixes,
        "split_points": list(split_points), "share_p": share_p,
        "vocab": vocab, "tail_len": list(tail_len),
        "out_tokens": list(out_tokens),
    })


def assign_adapters(trace: Trace, n_adapters: int, seed: int = 0,
                    zipf_a: float = 1.3,
                    name_fmt: str = "tenant-{:02d}") -> Trace:
    """Stamp every arrival with an adapter id drawn from a seeded,
    truncated Zipf over `n_adapters` tenants — the multi-tenant
    popularity law (a few hot fine-tunes, a long cold tail) that makes
    the registry's LRU/eviction behavior measurable: a budget below
    n_adapters forces churn exactly on the tail. Deterministic in
    `seed`; mutates + returns `trace` (its params record the draw)."""
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, n_adapters + 1, dtype=np.float64)
    p = ranks ** -zipf_a
    p /= p.sum()
    draws = rng.choice(n_adapters, size=len(trace.arrivals), p=p)
    for a, k in zip(trace.arrivals, draws):
        a.adapter = name_fmt.format(int(k))
    trace.params["n_adapters"] = n_adapters
    trace.params["zipf_a"] = zipf_a
    return trace


# ---------------------------------------------------------------------------
# named mixes: the CLI's vocabulary. Sizes are chosen so every
# mix completes on CPU (tiny-llama token dynamics) in seconds while
# still exercising its target path; "overload" offers ~4x the modeled
# capacity so admission bounds, queue deadlines, preemption and shed
# all fire (sim/engine_driver.py pairs it with a small page pool).
# ---------------------------------------------------------------------------

TRACE_NAMES = ("poisson", "bursty", "prefix-heavy", "overload",
               "adapter-zipf", "speculative", "adapter-spec")


def named_trace(name: str, seed: int = 0) -> Trace:
    if name == "poisson":
        return poisson_trace(rate_rps=6.0, n_requests=40, seed=seed)
    if name == "bursty":
        return bursty_trace(rate_on_rps=20.0, n_requests=40, seed=seed)
    if name == "prefix-heavy":
        # long shared system prompts (up to 6 pages at the sim's
        # page_size 16) cut at page-aligned AND mid-page points: the
        # radix workload — full-page descent, sub-page copy, and (with
        # the scenario's bounded pool) leaf eviction all fire
        return prefix_heavy_trace(
            rate_rps=12.0, n_requests=40, seed=seed, n_prefixes=4,
            split_points=(24, 48, 72, 96), tail_len=(4, 16),
            out_tokens=(4, 16),
        )
    if name == "overload":
        return poisson_trace(
            rate_rps=40.0, n_requests=48, seed=seed, name="overload",
            prompt_len=(24, 56), out_tokens=(16, 32),
        )
    if name == "adapter-zipf":
        # the multi-tenant workload (serving/adapters.py §7): Poisson
        # arrivals, each naming one of 4 tenants' LoRA adapters under a
        # Zipf popularity law — the scenario pairs it with a 2-adapter
        # registry budget so LRU eviction + reload churn genuinely fire
        return assign_adapters(
            poisson_trace(rate_rps=8.0, n_requests=40, seed=seed,
                          name="adapter-zipf"),
            n_adapters=4, seed=seed,
        )
    if name == "speculative":
        # greedy long-ish generations — the acceptance-friendly regime
        # where draft+verify rounds dominate (sim prices each round via
        # cost.spec_round_s; the engine's rollback machinery is real)
        return poisson_trace(
            rate_rps=6.0, n_requests=24, seed=seed, name="speculative",
            prompt_len=(8, 24), out_tokens=(16, 48),
        )
    if name == "adapter-spec":
        # S-LoRA completion: Zipf adapter traffic THROUGH speculative
        # decoding — base-model draft, adapter-applied verify. The
        # scenario's tight shared page pool makes adapter pages and KV
        # fight for one budget, so unified-paging churn fires alongside
        # acceptance (engine_driver SCENARIOS["adapter-spec"])
        return assign_adapters(
            poisson_trace(rate_rps=16.0, n_requests=24, seed=seed,
                          name="adapter-spec", prompt_len=(8, 24),
                          out_tokens=(16, 48)),
            n_adapters=4, seed=seed,
        )
    raise ValueError(f"unknown trace mix {name!r}; known: {TRACE_NAMES}")
