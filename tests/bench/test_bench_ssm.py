"""The hybrid cell's own pieces (ISSUE 38): the configuration against its
`published` block, the file's arithmetic (state bytes a slot, pool, pages,
weights) against the program's own shapes, `bench/costs_ssm.py`, the three
readers on recorded spans and a recorded trace, the reference's rounding
hook, and `bench/run.py --rehearse` on the cell."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import cells, costs, costs_moe, costs_ssm  # noqa: E402
from bench.records import Run  # noqa: E402

CELL = "granite-4.0-h-small.concurrent-closed"
NEW = ("kernel.ssm_decode_ms_per_step", "kernel.ssm_decode_roofline",
       "step.decode_ssm_mbu")


@pytest.fixture(scope="module")
def cell():
    return cells.resolve(CELL, ROOT)


@pytest.fixture(scope="module")
def hf(cell):
    return cells.as_run(cell.config)


# ---- the configuration -----------------------------------------------------

def test_the_cell_is_granite_at_published_widths(cell, hf):
    pub = cell.config["published"]
    assert cell.traffic_name == "concurrent-closed" and cell.chips == 1
    assert cell.entry_name == "engine"
    assert cell.config["reduced"] == ["num_hidden_layers", "layer_types"]
    assert set(hf) == set(pub)  # every key of the source runs
    assert {k for k in pub if hf[k] != pub[k]} == {"num_hidden_layers",
                                                   "layer_types"}
    # the cut: the first 20 entries of the published list, two whole periods
    assert hf["layer_types"] == pub["layer_types"][:20]
    assert hf["layer_types"] == pub["layer_types"][20:40] or \
        pub["layer_types"][10:20] == pub["layer_types"][:10]
    assert [i for i, k in enumerate(hf["layer_types"])
            if k == "attention"] == [5, 15]
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= names and "kernel.moe_ffn_roofline" in names
    # readers that count keys and values, or a dense MLP, in EVERY layer
    # are not this cell's (PERF.md section 7)
    assert not {"kernel.paged_attn_roofline--closed", "step.decode_mbu--closed",
                "kernel.decode.qmatmul_roofline--closed"} & names
    assert [m["name"] for m in cell.end_to_end] == ["output_tokens_per_s",
                                                    "setup_s"]
    assumed = cell.config["assumed"]
    assert {"router", "gate_before_norm", "input_linear",
            "moe_after_attention", "state_dtype", "mamba_chunk_size",
            "decay_rate", "weights"} <= set(assumed)


def test_the_file_runs_as_the_program_reads_it(cell, hf):
    from bigdl_tpu import kvhybrid
    from bigdl_tpu.models import get_family
    from bigdl_tpu.models.config import ModelConfig

    cfg = ModelConfig.from_hf_config(hf)
    fam = get_family(cfg.model_type)
    assert fam.PAGED_CACHE_KIND == kvhybrid.KIND
    assert fam.layer_runs(cfg) == [
        ("mamba", 0, 5), ("attention", 0, 1), ("mamba", 5, 9),
        ("attention", 1, 1), ("mamba", 14, 4)]
    assert fam.dims(cfg) == costs_ssm.dims(hf) == (128, 64, 128, 8192, 8448)
    assert (cfg.num_experts, cfg.num_experts_per_tok) == (72, 10)
    assert cfg.moe_intermediate_size == 768
    assert cfg.shared_intermediate_size == 1536
    assert cfg.attn_scale == 1 / 128 and cfg.logit_scale == 1 / 16
    assert cfg.embedding_scale == 12 and cfg.residual_scale == 0.22
    assert cfg.position_embedding_type == "nope" and cfg.tie_word_embeddings


def test_traffic_is_the_issues(cell):
    t, e = cell.traffic, cell.config["bench"]["engine"]
    assert t["process"] == {"kind": "closed", "clients": 32, "think_s": 0,
                            "block": 32}
    assert t["process"]["clients"] == e["n_slots"] == 32
    assert t["prompt"] == {"dist": "lognormal", "median": 512, "sigma": 0.8,
                           "min": 128, "max": 2048,
                           "ladder": [128, 256, 512, 1024, 2048]}
    assert t["output"] == {"dist": "lognormal", "median": 384, "sigma": 0.5,
                           "min": 128, "max": 1024}
    assert t["trace_seconds"] == 6.0 and "rehearsal" in t
    assert t["prompt"]["max"] + t["output"]["max"] == e["max_len"] == 3072
    # the traffic's worst case in every slot, and the scratch page
    assert e["n_pages"] == 32 * (3072 // e["page_size"]) + 1 == 1537


# ---- the file's arithmetic, against the program's shapes -------------------

def test_state_pool_pages_and_weights_are_the_programs_own(cell, hf):
    """Shapes only: nothing is allocated."""
    import jax

    from bench import weights
    from bigdl_tpu import kvhybrid
    from bigdl_tpu.models import get_family
    from bigdl_tpu.models.config import ModelConfig

    cfg = ModelConfig.from_hf_config(hf)
    e = cell.config["bench"]["engine"]
    pool = jax.eval_shape(lambda: get_family(cfg.model_type).init_paged_cache(
        cfg, e["n_pages"], e["page_size"], e["n_slots"],
        e["max_len"] // e["page_size"]))
    # a state row: 18 layers x (128 x 64 x 128 + 3 x 8448) float32
    row = costs_ssm.state_row_bytes(hf)
    assert row == kvhybrid.row_nbytes(pool) == 18 * (8192 * 128 + 3 * 8448) * 4
    assert round(row / 18 / 1e6, 2) == 4.30  # a layer and slot
    assert costs_ssm.ssm_row_bytes(hf) == pool.ssm.size // 32 * 4
    assert round(32 * row / 1e9, 2) == 2.47  # the pool's state
    # pages: 2 attention layers x K and V x 8 heads x 128 x bf16 a token
    assert costs_ssm.kv_token_bytes(hf) == 8192
    pages = (pool.k.size + pool.v.size) * 2
    assert pages == 1537 * 64 * costs_ssm.kv_token_bytes(hf)
    assert round(pages / 1e9, 2) == 0.81
    # weights: the packed projections and experts as costs_ssm counts them
    # are what the tree holds beside the embedding, the router and the
    # small float leaves
    tree = weights.param_shapes(cfg, "sym_int4")
    packed = costs_ssm.linear_bytes(hf) + costs_ssm.expert_stack_bytes(hf)
    total = costs.tree_bytes(tree)
    assert 0 < total - packed < 0.002 * total  # routers, conv, norms
    assert round(packed / 1e9, 2) == 9.17
    embed = hf["vocab_size"] * hf["hidden_size"] * 2
    assert round((total + embed) / 1e9, 1) == 10.0
    d = cell.config["bench"]["engine_derivation"]
    assert "2.47 GB" in d and "0.81 GB" in d and "10.0" in d
    # and the kernels take every packed weight: shapes the guards accept
    from bigdl_tpu.ops.linear import grouped_route

    for run in tree["runs"].values():
        assert grouped_route(run["w_gate_e"], run["w_up_e"],
                             run["w_down_e"]) in (
            None, "backend is cpu, not tpu")
        assert run["w_up_e"].data.shape[1:] == (72, 768, 2048)
        assert run["w_down_e"].data.shape[1:] == (72, 4096, 384)


def test_decode_cost_is_state_twice_plus_the_tokens_own(hf):
    ssm = costs_ssm.ssm_row_bytes(hf)
    one = costs_ssm.decode_cost(hf, 1)
    small = one["bytes"] - 2 * ssm
    assert small == 18 * (8192 + 128 + 256 + 8192) * 4 and small < 0.02 * ssm
    full = costs_ssm.decode_cost(hf, 32)
    assert full["bytes"] == 32 * one["bytes"]
    assert costs_ssm.decode_cost(hf, 0) == {"bytes": 0, "flops": 0}
    peak = costs.peaks("TPU v5 lite")
    t, bound = costs.roofline_seconds(full, peak)
    assert bound == "memory" and 0.0058 < t < 0.0062  # 4.9 GB at 819 GB/s
    assert full["flops"] / peak["bf16_flops_per_s"] < t / 50


def test_the_call_list_is_this_models(hf):
    calls = costs_ssm.decode_linears(hf)
    assert len(calls) == 18 * 5 + 2 * 7 + 1
    assert calls[0] == (4096, 8192 + 8448 + 128) and calls[1] == (8192, 4096)
    assert calls[-1] == (4096, 100352)
    assert (4096, 1024) in calls and calls.count((1536, 4096)) == 20


def test_step_bytes_add_up(hf):
    w = 9_200_000_000
    every = 20 * 72
    assert costs_ssm.step_bytes(hf, w, every, 0, 0, 64) == w
    one = costs_moe.expert_bytes(hf)
    assert costs_ssm.step_bytes(hf, w, every - 3, 0, 0, 64) == w - 3 * one
    moved = 2 * 32 * costs_ssm.state_row_bytes(hf)
    assert costs_ssm.step_bytes(hf, w, every, moved, 100, 64) == \
        w + moved + 100 * 64 * 8192


# ---- the readers -----------------------------------------------------------

def _run(cell, steps, device=None, weight_bytes=0):
    spans = [{"ph": "X", "name": "decode_step", "ts": (10 + i) * 1e6,
              "dur": 3e4, "args": a} for i, a in enumerate(steps)]
    return Run(cell=cell, hf=cells.as_run(cell.config),
               peak=costs.peaks("TPU v5 lite"), t0=0.0, t1=100.0,
               requests=[], spans=spans, device=device,
               weight_bytes=weight_bytes)


def _device(n_steps, kernel_s, step_s=0.05, begin=0.0, end=100.0):
    """What the readers ask of a reduced trace."""
    return types.SimpleNamespace(
        begin=begin, end=end, offset=0.0,
        kernel_in_program=lambda kernel, program: (
            (n_steps, kernel_s) if (kernel, program) == (
                "mamba2_decode", "engine_decode") else (0, 0.0)),
        program_seconds=lambda program: (
            [step_s] * n_steps if program == "engine_decode" else []))


def _step(hf, rows, pages=300, hit=1440):
    return {"occupancy": rows, "slots": 32, "state_rows_live": rows,
            "state_bytes_moved": 2 * rows * costs_ssm.state_row_bytes(hf),
            "live_pages": pages, "grid_pages": 1536, "moe_experts": 1440,
            "moe_experts_hit": hit, "moe_assignments": 10 * 20 * rows}


def test_readers_on_recorded_spans_and_kernel_time(cell, hf):
    run = _run(cell, [_step(hf, 32), _step(hf, 16)],
               _device(n_steps=2, kernel_s=0.012, step_s=0.040),
               weight_bytes=9 * 10 ** 9)
    assert cell.reader(NEW[0]).read(run) == pytest.approx(6.0)
    # 24 live rows a step on average: their state twice over 819 GB/s, over
    # the kernel's 6 ms
    need = costs_ssm.decode_cost(hf, 24)
    share = cell.reader(NEW[1]).read(run)
    assert share == pytest.approx(
        100 * need["bytes"] / run.peak["hbm_bytes_per_s"] / 0.006)
    assert 60 < share < 100
    moved = 2 * 24 * costs_ssm.state_row_bytes(hf)
    assert cell.reader(NEW[2]).read(run) == pytest.approx(
        100 * (9e9 + moved + 300 * 64 * 8192)
        / run.peak["hbm_bytes_per_s"] / 0.040)


def test_roofline_counts_the_traced_seconds_steps_only(cell, hf):
    steps = [_step(hf, 32), _step(hf, 8), _step(hf, 8)]
    run = _run(cell, steps, _device(2, 0.012, begin=10.5, end=12.5))
    a = cell.reader(NEW[1]).read(run)  # the two steps at 8 rows
    run.device = _device(2, 0.012)  # all three
    assert a < cell.reader(NEW[1]).read(run)


@pytest.mark.parametrize("name", NEW)
def test_readers_return_nothing_where_there_is_nothing_to_read(cell, name):
    """The parent's program (it cannot run the configuration; were it
    handed other spans): spans without the arguments, a trace without the
    kernel, a configuration without `layer_types`. The metric is left out
    of the line, nothing raises."""
    bare = {"occupancy": 8, "slots": 8, "live_pages": 3, "grid_pages": 256}
    assert cell.reader(name).read(_run(cell, [bare])) is None
    assert cell.reader(name).read(
        _run(cell, [bare], _device(n_steps=0, kernel_s=0.0))) is None
    other = _run(cell, [bare], _device(n_steps=2, kernel_s=0.01))
    other.hf = {"hidden_size": 64}
    if name != NEW[0]:  # a time needs no shapes
        assert cell.reader(name).read(other) is None
    assert getattr(cell.reader(name), "ENTRIES") == ("engine",)


def test_the_programs_spans_carry_what_the_readers_read(cell):
    """A tiny engine's own `decode_step` spans through the same readers'
    helper: the program's count of the bytes is the yardstick's."""
    import jax

    from bigdl_tpu.api import TpuModel, optimize_model
    from bigdl_tpu.models import get_family
    from bigdl_tpu.models.config import ModelConfig
    from bigdl_tpu.obs.tracing import TraceRecorder
    from bigdl_tpu.serving.engine import InferenceEngine

    hf = dict(cells.as_run(cell.config), **{
        k: v for k, v in cell.config["bench"]["rehearsal"].items()
        if k != "bench"})
    cfg = ModelConfig.from_hf_config(hf)
    fam = get_family(cfg.model_type)
    model = TpuModel(cfg, optimize_model(
        fam.init_params(cfg, jax.random.PRNGKey(0)), cfg, "sym_int4"),
        "sym_int4")
    tr = TraceRecorder(capacity=1024)
    eng = InferenceEngine(model, n_slots=2, max_len=64, paged=True,
                          page_size=16, tracer=tr)
    assert eng.state_row_bytes == costs_ssm.state_row_bytes(hf)
    eng.submit(list(range(1, 20)), max_new_tokens=3)
    eng.run_until_idle()
    run = Run(cell=cell, hf=hf, peak=costs.peaks("TPU v5 lite"), t0=0.0,
              t1=float("inf"), requests=[], spans=tr.events())
    steps = costs_ssm.traced_steps(run)
    assert steps and all(
        a["state_bytes_moved"] == 2 * a["state_rows_live"]
        * costs_ssm.state_row_bytes(hf) and "live_pages" in a
        and a["moe_experts"] == 4 * 8 for a in steps)


# ---- the reference ---------------------------------------------------------

def test_reference_rounding_hook_moves_the_logits(cell):
    """`rnd` reaches every matrix product: at float8 the logits move, with
    the identity they do not."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.api import optimize_model
    from bigdl_tpu.models import get_family
    from bigdl_tpu.models.config import ModelConfig

    hf = dict(cells.as_run(cell.config), **{
        k: v for k, v in cell.config["bench"]["rehearsal"].items()
        if k != "bench"})
    cfg = ModelConfig.from_hf_config(hf)
    params = optimize_model(get_family(cfg.model_type).init_params(
        cfg, jax.random.PRNGKey(2)), cfg, "sym_int4")
    ref = cell.reference()
    toks = jnp.asarray(np.random.default_rng(2).integers(1, 512, 24))
    plain = np.asarray(ref.logits(hf, params, toks, 5))
    same = np.asarray(ref.logits(hf, params, toks, 5, rnd=lambda x: x))
    np.testing.assert_array_equal(plain, same)

    def fp8(x):
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    low = np.asarray(ref.logits(hf, params, toks, 5, rnd=fp8))
    assert plain.shape == (5, 512) and np.abs(low - plain).max() > 1e-4


# ---- the command -----------------------------------------------------------

def test_rehearsal_runs_the_cell_end_to_end(tmp_path):
    """`bench/run.py --rehearse` on the cell: CPU, tiny sizes, the kernels in
    the interpreter, exit code 3, and a line with the new metrics' sources
    in place (no device on a CPU, so the device-trace readers stay out).
    Run from a COPY of the benchmark's files: a traced run empties
    `<root>/.bench_trace` when it starts, and the other cells' rehearsals
    may be under way in the checkout at the same moment."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "bigdl_tpu"), tmp_path / "bigdl_tpu")
    out = subprocess.run(
        [sys.executable, str(tmp_path / "bench" / "run.py"), "--workload",
         CELL, "--seed", str(2 ** 31 + 77), "--seconds", "3", "--trace", "1",
         "--rehearse"],
        capture_output=True, text=True, timeout=900, cwd=str(tmp_path),
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 3, out.stderr[-2000:]
    last = out.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL complete, not a result: ")
    line = json.loads(last.split(": ", 1)[1])
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0, \
        out.stdout[-3000:]
    assert line["compiles_in_window"] == 0
    assert "mamba2    pallas" in out.stdout
    assert "mamba2    xla" in out.stdout and "chunked prefill" in out.stdout
    assert "pallas:grouped" in out.stdout and "pallas:paged" in out.stdout
    assert {"engine.decode_occupancy", "step.decode_ms_p50--closed",
            "engine.moe_load_imbalance", "kernel.paged_live_page_share--closed",
            "engine.admit.retrace_ms_p50--closed"} <= set(line["metrics"])
    assert line["metrics"]["engine.admit.retrace_ms_p50--closed"][
        "value"] == 0.0
