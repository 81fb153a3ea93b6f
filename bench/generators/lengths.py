"""Length distributions, drawn as a FIXED set of quantiles and then shuffled
by the seed: every seed gets the same multiset of sizes in another order, so
runs with different seeds do the same work (the contract's cure for seeds
that change the work)."""

from __future__ import annotations

import math
import statistics


def _snap_up(x: float, ladder) -> int:
    for step in ladder:
        if x <= step:
            return int(step)
    return int(ladder[-1])


def quantile_lengths(spec: dict, n: int) -> list:
    """`n` lengths at the mid-quantiles (i + 0.5) / n of the distribution.

    spec["dist"]:
      "const":     {"value": v}
      "choice":    {"values": [...]}  uniform over the list, cycled
      "uniform":   {"min": a, "max": b}
      "lognormal": {"median": m, "sigma": s}
    Optional for all: "min"/"max" clip, "ladder" snaps UP to its next step
    (the engine compiles a prefill program per 16-token bucket, so free
    lengths would warm one program per bucket)."""
    dist = spec["dist"]
    if dist == "const":
        xs = [spec["value"]] * n
    elif dist == "choice":
        vals = spec["values"]
        xs = [vals[i * len(vals) // n] for i in range(n)]
    elif dist == "uniform":
        a, b = spec["min"], spec["max"]
        xs = [a + (b - a) * (i + 0.5) / n for i in range(n)]
    elif dist == "lognormal":
        nd = statistics.NormalDist()
        mu = math.log(spec["median"])
        xs = [math.exp(mu + spec["sigma"] * nd.inv_cdf((i + 0.5) / n))
              for i in range(n)]
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    lo, hi = spec.get("min"), spec.get("max")
    out = []
    for x in xs:
        if lo is not None:
            x = max(x, lo)
        if hi is not None:
            x = min(x, hi)
        out.append(_snap_up(x, spec["ladder"]) if spec.get("ladder")
                   else int(round(x)))
    return out


def distinct_lengths(spec: dict) -> list:
    """Every value the spec can yield (for warming up its shapes)."""
    return sorted(set(quantile_lengths(spec, 512)))
