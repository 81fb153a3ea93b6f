"""The paged decode kernel's share of its roofline (ISSUE 35): the cost
arithmetic of `bench/costs_paged.py` against the program's own
(`bigdl_tpu/benchmark/roofline.decode_attention_cost`) and against the pool
the program builds, and the reader on recorded spans and a recorded trace,
with and without what it reads."""

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import cells, costs, costs_paged  # noqa: E402
from bench.records import Run  # noqa: E402

# cell: (the metric's name there, KV heads, query heads, layers as run)
CELLS = {
    "mistral-7b.chat-steady": ("kernel.paged_attn_roofline", 8, 32, 32),
    "qwen2-7b.chat-closed": ("kernel.paged_attn_roofline--closed", 4, 28, 28),
    "mixtral-8x7b.chat-closed": ("kernel.paged_attn_roofline--closed",
                                 8, 32, 10),
}


def _cell(name):
    cell = cells.resolve(name, ROOT)
    return cell, cells.as_run(cell.config)


@pytest.mark.parametrize("name", list(CELLS))
def test_the_metric_is_listed_where_the_kernels_time_is(name):
    cell, _ = _cell(name)
    names = {m["name"]: m for m in cell.per_layer}
    metric = CELLS[name][0]
    ms = metric.replace("roofline", "ms_per_step")
    assert names[metric]["workloads"] == names[ms]["workloads"]
    assert names[metric]["moves"] == names[ms]["moves"]
    assert names[metric]["layer"] == "kernels"
    assert names[metric]["source"] == "device_trace"
    assert getattr(cell.reader(metric), "ENTRIES") == ("engine",)


@pytest.mark.parametrize("name", list(CELLS))
def test_cost_is_the_programs_own_for_whole_pages(name):
    """Rows of whole pages (the program counts FLOPs by tokens, the
    yardstick by the slots of the pages a kernel must load): the same bytes
    and operations as `roofline.decode_attention_cost`."""
    from bigdl_tpu.benchmark import roofline

    cell, hf = _cell(name)
    _, Hkv, Hq, L = CELLS[name]
    page = cell.config["bench"]["engine"]["page_size"]
    assert (hf["num_key_value_heads"], hf["num_attention_heads"],
            hf["num_hidden_layers"], costs_paged.head_dim(hf), page) \
        == (Hkv, Hq, L, 128, 64)
    rows = [3 * page, 8 * page, 8 * page, 20 * page]
    own = roofline.decode_attention_cost(rows, page, Hq, Hkv, 128, layers=L)
    ours = costs_paged.decode_cost(hf, page, 39, 4)
    assert own["live_pages"] == 39
    assert ours == {"bytes": own["bytes"], "flops": own["flops"]}
    assert costs_paged.page_bytes(hf, page) == 2 * 64 * Hkv * 128 * 2
    assert costs_paged.decode_cost(hf, page, 0, 0) == {"bytes": 0, "flops": 0}
    # 4 to 7 FLOP a byte: memory-bound on a v5e (240 at the ridge)
    t, bound = costs.roofline_seconds(ours, costs.peaks("TPU v5 lite"))
    assert bound == "memory" and 3 < ours["flops"] / ours["bytes"] < 8
    assert t == pytest.approx(ours["bytes"] / 819e9)


@pytest.mark.parametrize("name", list(CELLS))
def test_page_bytes_are_the_pools_own(name):
    """Against the pool the program builds (shapes only)."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu import kvpaged

    cell, hf = _cell(name)
    e = cell.config["bench"]["engine"]
    _, Hkv, _, L = CELLS[name]
    pool = jax.eval_shape(lambda: kvpaged.init_paged(
        L, e["n_pages"], e["page_size"], Hkv, 128, e["n_slots"],
        e["max_len"] // e["page_size"], dtype=jnp.bfloat16))
    assert kvpaged.kv_page_nbytes(pool) \
        == L * costs_paged.page_bytes(hf, e["page_size"])


def test_the_ledgers_parent_shares_follow_from_its_numbers():
    """ISSUE 35's table: the least time for the live pages the ledger's
    PR 34 lines show, over the kernel time they show."""
    peak = costs.peaks("TPU v5 lite")
    for name, pages, rows, ms, share in (
            ("mistral-7b.chat-steady", 22.5, 3, 6.06, 3.8),
            ("qwen2-7b.chat-closed", 127.5, 16, 11.45, 5.0),
            ("mixtral-8x7b.chat-closed", 124.9, 16, 4.24, 9.5)):
        _, hf = _cell(name)
        t = costs.roofline_seconds(
            costs_paged.decode_cost(hf, 64, pages, rows), peak)[0]
        assert 100 * t / (ms * 1e-3) == pytest.approx(share, abs=0.3)


# ---- the reader ------------------------------------------------------------

def _run(cell, steps, device=None):
    spans = [{"ph": "X", "name": "decode_step", "ts": (10 + i) * 1e6,
              "dur": 3e4, "args": a} for i, a in enumerate(steps)]
    return Run(cell=cell, hf=cells.as_run(cell.config),
               peak=costs.peaks("TPU v5 lite"), t0=0.0, t1=100.0,
               requests=[], spans=spans, device=device)


def _device(n_steps, kernel_s, begin=0.0, end=100.0):
    """What the reader asks of a reduced trace."""
    return types.SimpleNamespace(
        begin=begin, end=end, offset=0.0,
        kernel_in_program=lambda kernel, program: (
            (n_steps, kernel_s) if (kernel, program) == (
                "paged_decode_attention", "engine_decode") else (0, 0.0)))


def _step(rows, pages, slots=16):
    return {"occupancy": rows, "slots": slots, "queue_depth": 0,
            "live_pages": pages, "grid_pages": slots * 32}


@pytest.mark.parametrize("name", list(CELLS))
def test_reader_on_recorded_spans_and_kernel_time(name):
    cell, hf = _cell(name)
    run = _run(cell, [_step(16, 160), _step(8, 40)],
               _device(n_steps=2, kernel_s=0.004))
    need = costs_paged.decode_cost(hf, 64, 100, 12)
    share = cell.reader(CELLS[name][0]).read(run)
    assert share == pytest.approx(
        100 * need["bytes"] / run.peak["hbm_bytes_per_s"] / 0.002)
    assert 2 < share < 100


def test_reader_counts_the_traced_seconds_steps_only():
    cell, _ = _cell("qwen2-7b.chat-closed")
    steps = [_step(16, 400), _step(4, 12), _step(4, 12)]
    reader = cell.reader("kernel.paged_attn_roofline--closed")
    a = reader.read(_run(cell, steps, _device(2, 0.004, 10.5, 12.5)))
    assert a < reader.read(_run(cell, steps, _device(2, 0.004)))


@pytest.mark.parametrize("name", list(CELLS))
def test_reader_returns_nothing_where_there_is_nothing_to_read(name):
    """No device trace, a trace without the kernel, spans without the
    counts (a dense engine's): the metric is left out, nothing raises."""
    cell, _ = _cell(name)
    reader = cell.reader(CELLS[name][0])
    bare = {"occupancy": 8, "slots": 8, "queue_depth": 0}
    assert reader.read(_run(cell, [_step(8, 40)])) is None
    assert reader.read(_run(cell, [_step(8, 40)], _device(0, 0.0))) is None
    assert reader.read(_run(cell, [bare], _device(2, 0.004))) is None
    assert reader.read(_run(cell, [], _device(2, 0.004))) is None


def test_the_programs_spans_carry_what_the_reader_reads():
    """A tiny paged engine's own `decode_step` spans through the reader's
    helper: live pages and live slots, step by step."""
    import jax

    from bigdl_tpu.api import TpuModel, optimize_model
    from bigdl_tpu.models import llama
    from bigdl_tpu.models.config import PRESETS
    from bigdl_tpu.obs.tracing import TraceRecorder
    from bigdl_tpu.serving.engine import InferenceEngine

    cfg = PRESETS["tiny-llama"]
    model = TpuModel(cfg, optimize_model(
        llama.init_params(cfg, jax.random.PRNGKey(0)), cfg, "sym_int4"),
        "sym_int4")
    tr = TraceRecorder(capacity=1024)
    eng = InferenceEngine(model, n_slots=2, max_len=64, paged=True,
                          page_size=16, n_pages=9, tracer=tr)
    eng.submit(list(range(1, 20)), max_new_tokens=3)
    eng.run_until_idle()
    cell, _ = _cell("mistral-7b.chat-steady")
    run = Run(cell=cell, hf={}, peak={}, t0=0.0, t1=float("inf"),
              requests=[], spans=tr.events())
    steps = costs_paged.traced_steps(run)
    assert steps and all(a["live_pages"] == 2 and a["occupancy"] == 1
                         and a["grid_pages"] == 2 * 4 for a in steps)
