"""The reader of the prefill kernel's share of the chip's peak
(bench/metrics/kernel.flash_attn_mfu) and its count of operations
(bench/costs_flash.py): on a few prefills recorded on the chip
(bench/fixtures, cut by bench/tools/keep_prefills.py), on made-up runs, and
against the tiles the kernel really computes."""

import gzip
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import cells, costs, costs_flash  # noqa: E402
from bench.records import Run  # noqa: E402
from bench.reduce.xplane import Event, Loaded, Reduced  # noqa: E402
from bench.tools import keep_prefills  # noqa: E402

CELL = "mistral-7b.longprompt-closed"
METRIC = "kernel.flash_attn_mfu"
FIXTURE = os.path.join(ROOT, "bench", "fixtures",
                       "v5e_longprompt_prefills.json.gz")
PLANE = "/device:TPU:0"
PROGRAM = "jit_engine_paged_prefill(123)"


@pytest.fixture(scope="module")
def cell():
    return cells.resolve(CELL, ROOT)


@pytest.fixture(scope="module")
def peak():
    return costs.peaks("TPU v5 lite")


def made_up(cell, peak, prefills, flash_s_each, layers=32, begin=100.0,
            end=106.0, spans=None):
    """A run whose traced seconds hold `prefills` = [(start, dur, prompt
    tokens)] executions on the trace's clock (the benchmark's is 1000 s
    ahead), `layers` kernel events of `flash_s_each` / layers seconds in
    each, and a `prefill` span around each."""
    mods, ops, made = [], [], []
    for k, (t, d, tokens) in enumerate(prefills):
        mods.append(Event(PROGRAM, t, d))
        ops += [Event(f"flash_attention.{k * layers + i}",
                      t + (i + 0.25) * d / layers, flash_s_each / layers)
                for i in range(layers)]
        ops.append(Event(f"fusion.{k}", t, d / 4))
        made.append({"name": "prefill", "ph": "X", "tid": k,
                     "ts": int((t + 1000.0 - 0.01) * 1e6),
                     "dur": int((d + 0.03) * 1e6),
                     "args": {"rid": k, "prompt_tokens": tokens}})
    ld = Loaded({PLANE: ops}, {PLANE: mods}, 50.0, {})
    return Run(cell=cell, hf=cells.as_run(cell.config), peak=peak, t0=0.0,
               t1=1e12, requests=[], spans=made if spans is None else spans,
               device=Reduced(ld, 1050.0, begin + 1000.0, end + 1000.0))


def test_the_count_is_the_causal_halfs(cell):
    hf = cells.as_run(cell.config)
    # Mistral-7B: 32 layers, 32 query heads of 128; T = 1792
    assert costs_flash.causal_flops(hf, 1792) == \
        32 * 4 * 32 * 128 * 1792 * 1793 / 2
    assert costs_flash.causal_flops(hf, 1) == 32 * 4 * 32 * 128


def test_share_of_the_peak_on_a_made_up_run(cell, peak):
    reader = cell.reader(METRIC)
    run = made_up(cell, peak, [(101.0, 0.2, 1792), (102.0, 0.15, 1024)],
                  flash_s_each=0.02)
    flops = (costs_flash.causal_flops(run.hf, 1792)
             + costs_flash.causal_flops(run.hf, 1024))
    assert reader.read(run) == pytest.approx(100 * flops / 197e12 / 0.04)
    # a prefill that began before the traced seconds, and one that ends
    # after them, are neither timed nor counted
    run = made_up(cell, peak, [(99.9, 0.2, 1536), (101.0, 0.2, 1792),
                               (105.9, 0.2, 1280)], flash_s_each=0.02)
    assert reader.read(run) == pytest.approx(
        100 * costs_flash.causal_flops(run.hf, 1792) / 197e12 / 0.02)


def test_none_without_a_trace_a_kernel_or_a_span(cell, peak):
    reader = cell.reader(METRIC)
    run = made_up(cell, peak, [(101.0, 0.2, 1792)], flash_s_each=0.02)
    assert reader.read(run) is not None
    run.device = None
    assert reader.read(run) is None  # --trace 0
    # a program whose prefill runs no such kernel (XLA's attention)
    run = made_up(cell, peak, [(101.0, 0.2, 1792)], flash_s_each=0.02,
                  layers=0)
    assert reader.read(run) is None
    # no span around the execution, or a span without the argument
    run = made_up(cell, peak, [(101.0, 0.2, 1792)], flash_s_each=0.02,
                  spans=[])
    assert reader.read(run) is None
    run = made_up(cell, peak, [(101.0, 0.2, 1792)], flash_s_each=0.02)
    del run.spans[0]["args"]["prompt_tokens"]
    assert reader.read(run) is None
    # the traced seconds hold no prefill
    run = made_up(cell, peak, [(90.0, 0.2, 1792)], flash_s_each=0.02)
    assert reader.read(run) is None


@pytest.mark.parametrize("tokens", [1024, 1280, 1536, 1792])
def test_cannot_pass_100_the_tiles_compute_more_than_the_count(
        cell, peak, tokens):
    """A kernel that ran its tiles' products at the chip's peak and did
    nothing else: the share is the count's part of the tiles' FLOPs, under
    100% at every prompt length of the cell's traffic."""
    from bigdl_tpu.ops.pallas import tiling

    assert tokens in cell.traffic["prompt"]["values"]
    hf = cells.as_run(cell.config)
    Hq, Hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    D = hf["hidden_size"] // Hq
    S = cell.config["bench"]["engine"]["max_len"]
    bq, bk = tiling.flash_blocks(tokens, S, D, Hq // Hkv, 2)
    live = tiling.flash_live_blocks(tokens, S, bq, bk)
    tile_flops = hf["num_hidden_layers"] * 4.0 * Hq * live * bq * bk * D
    assert costs_flash.causal_flops(hf, tokens) < tile_flops
    run = made_up(cell, peak, [(101.0, 0.2, tokens)],
                  flash_s_each=tile_flops / peak["bf16_flops_per_s"])
    share = cell.reader(METRIC).read(run)
    assert 50.0 < share < 100.0
    assert share == pytest.approx(
        100 * costs_flash.causal_flops(hf, tokens) / tile_flops)


def test_the_cut_reads_as_the_run_it_was_cut_from(cell, peak):
    """bench/tools/keep_prefills.py on a made-up run: the first two whole
    prefills, their kernel events and spans, and nothing else."""
    reader = cell.reader(METRIC)
    run = made_up(cell, peak, [(99.9, 0.2, 1536), (101.0, 0.2, 1792),
                               (102.0, 0.15, 1024), (103.0, 0.2, 1280)],
                  flash_s_each=0.02)
    kept = json.loads(json.dumps(keep_prefills.cut(run, reader, 2)))
    assert kept["expect"]["prompt_tokens"] == [1792, 1024]
    assert kept["expect"]["flash_events"] == 64
    assert len(kept["modules"][PLANE]) == 2 and len(kept["spans"]) == 2
    small = keep_prefills.small_run(kept, cell, run.hf, peak)
    assert reader.read(small) == pytest.approx(kept["expect"]["mfu"])
    flops = (costs_flash.causal_flops(run.hf, 1792)
             + costs_flash.causal_flops(run.hf, 1024))
    assert kept["expect"]["mfu"] == pytest.approx(
        100 * flops / 197e12 / 0.04)


def test_share_on_the_recorded_prefills(cell, peak):
    """Four prefills of `mistral-7b.longprompt-closed` recorded on a v5e:
    the reader's share is what plain sums over the cut give, and it lies
    between the parent's kernel (7%) and the peak."""
    with gzip.open(FIXTURE, "rt", encoding="utf-8") as f:
        kept = json.load(f)
    run = keep_prefills.small_run(kept, cell, kept["hf"], peak)
    share = cell.reader(METRIC).read(run)
    expect = kept["expect"]
    assert share == pytest.approx(expect["mfu"])
    secs = sum(e["dur"] for e in kept["ops"][PLANE])
    assert secs == pytest.approx(expect["flash_s"])
    assert len(kept["ops"][PLANE]) == 32 * expect["prefills"]
    flops = sum(costs_flash.causal_flops(kept["hf"], t)
                for t in expect["prompt_tokens"])
    assert share == pytest.approx(100 * flops / 197e12 / secs)
    assert 7.0 < share < 100.0
