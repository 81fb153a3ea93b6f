"""bench/costs_sparse.py and the five readers of the cell
`minicpm-sala.longdoc-closed` (PR 54): the arithmetic, the configuration's
byte counts against the program's own shapes, and that no reading of a
synthetic run can pass 100%."""

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import cells, costs, costs_sparse  # noqa: E402

CELL = "minicpm-sala.longdoc-closed"
PAGE = 64


@pytest.fixture(scope="module")
def cell():
    return cells.resolve(CELL, ROOT)


@pytest.fixture(scope="module")
def hf(cell):
    return cells.as_run(cell.config)


def test_the_layers_and_the_bytes_of_the_configuration(hf, cell):
    assert costs_sparse.knows(hf) and not costs_sparse.knows({"a": 1})
    assert costs_sparse.n_layers(hf, costs_sparse.SPARSE) == 8
    assert costs_sparse.n_layers(hf, costs_sparse.LIGHTNING) == 24
    assert [i for i, k in enumerate(hf["mixer_types"])
            if k == costs_sparse.SPARSE] == [0, 9, 16, 17, 22, 29, 30, 31]
    # a page of one layer: 64 tokens x 2 heads x 128 x bf16, K and V
    assert costs_sparse.page_bytes(hf, PAGE) == 2 * 64 * 2 * 128 * 2 == 65536
    assert costs_sparse.pooled_page_bytes(hf, PAGE) == 4 * 2 * 128 * 2
    # the configuration's sentences: 0.524 + 0.016 MB a page, 50.3 MB a row
    assert costs_sparse.pool_page_bytes(hf, PAGE) == 8 * (65536 + 2048)
    assert round(8 * 65536 / 1e6, 3) == 0.524
    assert round(8 * 2048 / 1e6, 3) == 0.016
    assert costs_sparse.state_row_bytes(hf) == 24 * 32 * 128 * 128 * 4
    assert round(costs_sparse.state_row_bytes(hf) / 1e6, 1) == 50.3
    e = cell.config["bench"]["engine"]
    assert e["page_size"] == hf["sparse_config"]["block_size"] == PAGE
    assert e["n_pages"] == e["n_slots"] * (e["max_len"] // PAGE) + 1
    pool = e["n_pages"] * costs_sparse.pool_page_bytes(hf, PAGE)
    assert round(pool / 1e9, 2) == 2.35
    assert round(e["n_slots"] * costs_sparse.state_row_bytes(hf) / 1e9,
                 2) == 0.81


def test_the_bytes_are_the_programs_own_shapes(hf, cell):
    """The pool as `init_paged_cache` shapes it, and the packed tree as
    `bench/weights.param_shapes` does: no array is made."""
    import jax

    from bench import weights
    from bigdl_tpu import kvsparse
    from bigdl_tpu.models import get_family
    from bigdl_tpu.models.config import ModelConfig

    cfg = ModelConfig.from_hf_config(hf)
    e = cell.config["bench"]["engine"]
    fam = get_family(cfg.model_type)
    pool = jax.eval_shape(lambda: fam.init_paged_cache(
        cfg, e["n_pages"], PAGE, e["n_slots"], e["max_len"] // PAGE))
    assert pool.k.shape == (8, 4353, 64, 2, 128)
    assert pool.kp.shape == (8, 4353, 4, 2, 128)
    assert pool.state.shape == (24, 16, 32 * 128, 128)
    page = sum(a.size // a.shape[1] * a.dtype.itemsize
               for a in (pool.k, pool.v, pool.kp))
    assert page == costs_sparse.pool_page_bytes(hf, PAGE)
    assert kvsparse.row_nbytes(pool) == costs_sparse.state_row_bytes(hf)
    # a served engine fetches five counts a row a step, and no ids
    assert pool.report.shape == (16, kvsparse.N_COUNTS)
    assert kvsparse.report_width(8, 2, 64, ids=True) == 1024 + 5
    tree = weights.param_shapes(cfg)
    packed = costs.tree_bytes(tree)
    # 8.87 B parameters at 0.5625 B: 4.99 GB, and the packed head's 0.17
    layers = 8 * 253.8e6 + 24 * 285.2e6
    assert abs(layers - 8.87e9) < 0.02e9
    assert abs(packed - (layers + 73472 * 4096) * 0.5625) < 0.02e9
    assert tree["lm_head"].shape == (73472, 4096)
    assert tree["embed"].shape == (73448, 4096)


def test_the_kernels_costs(hf):
    a = costs_sparse.attn_cost(hf, PAGE, pages_read=100, pages_selected=150,
                               pages_live=400, rows=2)
    assert a["bytes"] == 100 * 65536 + 400 * 2048 + 8 * 2 * 2 * 32 * 128 * 2
    assert a["flops"] == 150 * 16 * 64 * 128 * 4
    s = costs_sparse.state_cost(hf, 3)
    row = 24 * 32 * 128 * 128 * 4
    assert s["bytes"] == 3 * (2 * row + 24 * 4 * 32 * 128 * 4)
    assert s["flops"] == 3 * 24 * 32 * 128 * 128 * 5
    need = costs_sparse.step_bytes(hf, 1000, 2 * 3 * row, 100, 400, PAGE)
    assert need == 1000 + 6 * row + 100 * 65536 + 400 * 2048
    # the step's qmatmul calls: ISSUE 54's 253.8 M and 285.2 M a layer, and
    # the head at the vocabulary's own 73448 rows
    calls = costs_sparse.decode_linears(hf)
    assert len(calls) == 32 * 8 + 1 and calls[-1] == (4096, 73448)
    assert calls[:8] == [(4096, 4096), (4096, 256), (4096, 256),
                         (4096, 4096), (4096, 4096), (4096, 16384),
                         (4096, 16384), (16384, 4096)]
    assert calls[8:13] == [(4096, 4096)] * 5  # layer 1: lightning
    weights = sum(k * o for k, o in calls[:-1])
    assert abs(weights - (8 * 253.8e6 + 24 * 285.2e6)) < 0.01e9


class _Device:
    begin, end, offset = 0.0, 10.0, 0.0

    def __init__(self, kernels, execs):
        self.kernels, self.execs = kernels, execs

    def kernel_in_program(self, kernel, program):
        return self.kernels.get(kernel, (0, 0.0))

    def program_seconds(self, program):
        return self.execs


def _run(cell, hf, spans, device):
    peak = costs.peaks("TPU v5 lite")
    return types.SimpleNamespace(
        cell=cell, hf=hf, peak=peak, device=device, weight_bytes=5_300_000_000,
        span_list=lambda name: [(1.0, 0.01, a) for a in spans]
        if name == "decode_step" else [])


def _span(rows=16, pages=200):
    """A step of `rows` rows of `pages` live pages each: every row and KV
    head chooses 64, a row's union holds 94."""
    return {"occupancy": rows, "state_rows_live": rows,
            "state_bytes_moved": 2 * rows * 24 * 32 * 128 * 128 * 4,
            "live_pages": rows * pages, "sparse_pages_live": 8 * rows * pages,
            "sparse_pages_selected": 8 * rows * 2 * 64,
            "sparse_pages_read": 8 * rows * 94}


QMM = "kernel.sparse_decode.qmatmul_roofline"


@pytest.mark.parametrize("metric", [
    "kernel.sparse_attn_roofline", "kernel.lightning_decode_roofline",
    "step.decode_sparse_mbu", "kernel.sparse_selected_page_share", QMM])
def test_a_synthetic_run_reads_under_100(cell, hf, metric):
    """Kernels that run AT their rooflines read 100 (the step a little
    under, for what it does beside its bytes); slower ones less."""
    peak = costs.peaks("TPU v5 lite")
    span = _span()
    attn = costs.roofline_seconds(costs_sparse.attn_cost(
        hf, PAGE, span["sparse_pages_read"], span["sparse_pages_selected"],
        span["sparse_pages_live"], 16), peak)[0]
    state = costs.roofline_seconds(costs_sparse.state_cost(hf, 16), peak)[0]
    step = costs_sparse.step_bytes(
        hf, 5_300_000_000, span["state_bytes_moved"],
        span["sparse_pages_read"], span["sparse_pages_live"],
        PAGE) / peak["hbm_bytes_per_s"]
    qmm = sum(costs.roofline_seconds(costs.qmatmul_cost(16, k, o), peak)[0]
              for k, o in costs_sparse.decode_linears(hf))
    reader = cell.reader(metric)
    assert reader.ENTRIES == ("engine",)
    for slow in (1.0, 2.5):
        dev = _Device({"paged_sparse_decode_attention": (4, 4 * attn * slow),
                       "lightning_decode": (4, 4 * state * slow),
                       "qmatmul": (4, 4 * qmm * slow)},
                      [step * slow * 1.01] * 5)
        got = reader.read(_run(cell, hf, [span] * 3, dev))
        if metric == "kernel.sparse_selected_page_share":
            assert got == pytest.approx(100 * 94 / 200)
        else:
            assert got == pytest.approx(100 / slow, rel=0.02) and got <= 100


@pytest.mark.parametrize("metric", [
    "kernel.sparse_attn_roofline", "kernel.lightning_decode_roofline",
    "step.decode_sparse_mbu", "kernel.sparse_selected_page_share", QMM])
def test_a_program_without_the_spans_or_the_kernels_reads_nothing(
        cell, hf, metric):
    reader = cell.reader(metric)
    dev = _Device({}, [0.02])
    assert reader.read(_run(cell, hf, [], dev)) is None
    if metric == QMM:  # it reads no span: the keys and the kernel alone
        full = _Device({"qmatmul": (4, 0.06)}, [0.02])
        assert reader.read(_run(cell, {"hidden_size": 1}, [], full)) is None
        assert reader.read(_run(cell, hf, [], None)) is None
        return
    assert reader.read(_run(cell, {"hidden_size": 1}, [_span()], dev)) in (
        None, pytest.approx(47.0))  # the share reads spans alone
    if metric != "kernel.sparse_selected_page_share":
        assert reader.read(_run(cell, hf, [_span()], None)) is None
        if metric.startswith("kernel."):
            assert reader.read(_run(cell, hf, [_span()], dev)) is None


def test_the_benchmark_lists_the_cell_where_the_issue_says():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    on = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
          if CELL in m.get("workloads", [])}
    assert {"output_tokens_per_s", "itl_ms_p95", QMM,
            "kernel.sparse_attn_roofline",
            "kernel.lightning_decode_roofline", "step.decode_sparse_mbu",
            "kernel.sparse_selected_page_share", "engine.decode_occupancy",
            "step.decode_ms_p50--closed", "step.prefill_ms_p50--closed"} <= on
    # costs that count every live page in every layer would read an
    # impossible share here; `ttft_ms_p90` of a burst of sixteen prefills is
    # the order of the seed's last two prompts (PERF.md section 6, PR 54)
    assert not on & {"ttft_ms_p90","step.decode_mbu--closed", "step.xla_ms--closed",
                     "kernel.paged_attn_roofline--closed",
                     "kernel.paged_live_page_share--closed",
                     "kernel.decode.qmatmul_roofline--closed"}
    (cfg,) = [c for c in bench["configs"] if c["name"] == "minicpm-sala-int4"]
    assert cfg["reduced"] == []
