"""bench/generators: a plan is a pure function of (parameters, seed, seconds);
ladder, clips and rate are honoured; every seed offers the same work."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import cells  # noqa: E402
from bench.generators import arrivals, lengths  # noqa: E402

LADDER = [64, 128, 192, 256, 384, 512, 768, 1024]
CHAT = {
    "process": {"kind": "poisson", "rate_rps": 2.5},
    "prompt": {"dist": "lognormal", "median": 256, "sigma": 0.8,
               "min": 64, "max": 1024, "ladder": LADDER},
    "output": {"dist": "lognormal", "median": 96, "sigma": 0.6,
               "min": 16, "max": 256},
}


def _key(plan):
    return [(r.t_due, tuple(r.prompt), r.max_new_tokens) for r in plan.requests]


def test_plan_is_a_pure_function_of_the_seed():
    a = arrivals.plan(CHAT, 2**31 + 5, 40, 32000)
    b = arrivals.plan(CHAT, 2**31 + 5, 40, 32000)
    c = arrivals.plan(CHAT, 7, 40, 32000)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)


def test_every_seed_offers_the_same_work_in_another_order():
    a = arrivals.plan(CHAT, 1, 40, 32000)
    b = arrivals.plan(CHAT, 2, 40, 32000)
    assert sorted(len(r.prompt) for r in a.requests) == \
        sorted(len(r.prompt) for r in b.requests)
    assert sorted(r.max_new_tokens for r in a.requests) == \
        sorted(r.max_new_tokens for r in b.requests)
    assert [len(r.prompt) for r in a.requests] != \
        [len(r.prompt) for r in b.requests]


def test_rate_ladder_and_clips_are_honoured():
    plan = arrivals.plan(CHAT, 3, 40, 32000)
    assert plan.kind == "open" and len(plan.requests) == 100  # 2.5/s x 40 s
    ts = [r.t_due for r in plan.requests]
    assert ts == sorted(ts) and ts[0] == 0.0 and ts[-1] < 40
    assert {len(r.prompt) for r in plan.requests} <= set(LADDER)
    assert all(16 <= r.max_new_tokens <= 256 for r in plan.requests)
    assert all(1 <= t < 32000 for r in plan.requests for t in r.prompt)
    med = sorted(r.max_new_tokens for r in plan.requests)[50]
    assert 85 <= med <= 110  # the output median is 96


def test_lognormal_quantiles_snap_up_not_down():
    xs = lengths.quantile_lengths(CHAT["prompt"], 200)
    assert min(xs) == 64 and max(xs) == 1024
    assert lengths.quantile_lengths(
        {"dist": "const", "value": 100, "ladder": LADDER}, 3) == [128] * 3
    assert lengths.distinct_lengths(CHAT["prompt"]) == LADDER


def test_closed_loop_pool_and_shapes():
    p = dict(CHAT, process={"kind": "closed", "clients": 16, "think_s": 0})
    plan = arrivals.plan(p, 5, 40, 32000)
    assert plan.kind == "closed" and plan.clients == 16
    assert len(plan.requests) == arrivals.CLOSED_POOL
    sh = arrivals.shapes(p)
    assert sh["prompt_lengths"] == LADDER and sh["max_output"] == 256


def test_blocks_give_every_seed_the_same_work_in_every_stretch():
    p = dict(CHAT, process={"kind": "closed", "clients": 16, "block": 32})
    a, b = (arrivals.plan(p, seed, 40, 32000).requests for seed in (1, 2))
    for lo in range(0, arrivals.CLOSED_POOL, 32):
        for key in (lambda r: len(r.prompt), lambda r: r.max_new_tokens):
            assert sorted(map(key, a[lo:lo + 32])) == \
                sorted(map(key, b[lo:lo + 32]))
    assert [len(r.prompt) for r in a[:32]] != [len(r.prompt) for r in b[:32]]
    # without blocks the first 64 of the pool are some 64, not the same 64
    p0 = dict(CHAT, process={"kind": "closed", "clients": 16})
    c, d = (arrivals.plan(p0, seed, 40, 32000).requests for seed in (1, 2))
    assert sorted(r.max_new_tokens for r in c[:64]) != \
        sorted(r.max_new_tokens for r in d[:64])


def test_prompts_share_no_prefix_and_an_unknown_process_is_an_error():
    plan = arrivals.plan(CHAT, 4, 40, 32000)
    assert len({tuple(r.prompt[:16]) for r in plan.requests}) == \
        len(plan.requests)
    with pytest.raises(ValueError, match="unknown open process"):
        arrivals.plan(dict(CHAT, process={"kind": "onoff", "rate_rps": 1}),
                      4, 40, 32000)


def _all_cells():
    return [w["name"] for w in cells.load_benchmark(ROOT)["workloads"]]


@pytest.mark.parametrize("cell", _all_cells())
def test_committed_traffic_files_fit_their_engine(cell):
    c = cells.resolve(cell, ROOT)
    sh = c.generator().shapes(c.traffic)
    e = c.config["bench"]["engine"]
    assert all(n % 16 == 0 for n in sh["prompt_lengths"])  # prefill buckets
    if c.entry_name == "engine":
        assert max(sh["prompt_lengths"]) + sh["max_output"] <= e["max_len"]
        worst = e["n_slots"] * -(-(max(sh["prompt_lengths"])
                                   + sh["max_output"]) // e["page_size"])
        assert worst < e["n_pages"]  # no preemption by construction
    plan = c.generator().plan(c.traffic, 2**31 + 1, 40,
                              c.config["vocab_size"])
    assert plan.requests
