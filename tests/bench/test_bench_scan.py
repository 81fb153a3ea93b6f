"""The Jamba cell's own pieces (ISSUE 56): the configuration against its
`published` block, the file's arithmetic (state bytes a slot, pool, pages,
weights) against the program's own shapes, `bench/costs_scan.py` against
hand counts, the four readers on recorded spans and a recorded trace, the
reference's rounding hook, and `bench/run.py --rehearse` on the cell."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import cells, costs, costs_scan  # noqa: E402
from bench.records import Run  # noqa: E402

CELL = "jamba2-3b.manychat-closed"
NEW = ("kernel.scan_decode_ms_per_step", "kernel.scan_decode_roofline",
       "kernel.scan_prefill_roofline", "step.decode_scan_mbu")


@pytest.fixture(scope="module")
def cell():
    return cells.resolve(CELL, ROOT)


@pytest.fixture(scope="module")
def hf(cell):
    return cells.as_run(cell.config)


# ---- the configuration -----------------------------------------------------

def test_the_cell_is_jamba_uncut(cell, hf):
    pub = cell.config["published"]
    assert cell.traffic_name == "manychat-closed" and cell.chips == 1
    assert cell.entry_name == "engine"
    assert cell.config["reduced"] == [] and hf == pub  # every key, as is
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = [json.loads(line) for line in f if "AI21-Jamba2-3B" in line]
    if row:  # where the catalog is at hand: the row's `config`, key for key
        assert pub == row[0]["config"]
        assert cell.config["source"] == row[0]["source_url"]
    assert costs_scan.knows(hf)
    kinds = costs_scan.layer_kinds(hf)
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [7, 21]
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= names
    # readers that count keys and values, or experts, in EVERY layer are
    # not this cell's
    assert not {"kernel.paged_attn_roofline--closed", "step.decode_mbu--closed",
                "step.decode_ssm_mbu", "kernel.ssm_decode_roofline",
                "kernel.decode.qmatmul_roofline--closed"} & names
    assert [m["name"] for m in cell.end_to_end] == ["output_tokens_per_s",
                                                    "setup_s"]
    assert {"layer_order", "dense_mlp", "head_dim", "no_position_term",
            "state_dtype", "packed", "decay_rate", "inner_norms",
            "weights"} <= set(cell.config["assumed"])


def test_the_four_metrics_are_this_cells_alone():
    bench = cells.load_benchmark(ROOT)
    rows = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        m = rows[name]
        assert (m["moves"], m["workloads"], m["source"]) == (
            "output_tokens_per_s", [CELL], "device_trace")
        assert m["layer"] == ("model step" if name.startswith("step.")
                              else "kernels")
        assert m["unit"] == ("ms" if name.endswith("ms_per_step") else "%")
    assert [m["name"] for m in bench["per_layer"][-4:]] == [
        NEW[1], NEW[0], NEW[2], NEW[3]]
    assert bench["workloads"][-1]["name"] == CELL
    assert len(bench["workloads"]) == 13


def test_the_file_runs_as_the_program_reads_it(cell, hf):
    from bigdl_tpu import kvhybrid
    from bigdl_tpu.models import get_family
    from bigdl_tpu.models.config import ModelConfig

    cfg = ModelConfig.from_hf_config(hf)
    fam = get_family(cfg.model_type)
    assert fam.PAGED_CACHE_KIND == kvhybrid.KIND
    assert list(cfg.layer_types) == costs_scan.layer_kinds(hf)
    assert fam.layer_runs(cfg) == [
        ("mamba", 0, 7), ("attention", 0, 1), ("mamba", 7, 13),
        ("attention", 1, 1), ("mamba", 20, 6)]
    assert fam.dims(cfg) == costs_scan.dims(hf)[:3] == (5120, 16, 160)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim_) == (20, 1, 128)
    assert cfg.position_embedding_type == "nope" and cfg.tie_word_embeddings
    assert not cfg.is_moe and cfg.intermediate_size == 8192


def test_traffic_is_the_issues(cell):
    t, e = cell.traffic, cell.config["bench"]["engine"]
    assert t["generator"] == "arrivals" and t["entry"] == "engine"
    assert t["process"] == {"kind": "closed", "clients": 256, "think_s": 0,
                            "block": 256}
    assert t["process"]["clients"] == e["n_slots"] == 256
    assert t["prompt"] == {"dist": "lognormal", "median": 192, "sigma": 0.8,
                           "min": 64, "max": 1024,
                           "ladder": [64, 128, 256, 512, 1024]}
    assert t["output"] == {"dist": "lognormal", "median": 512, "sigma": 0.5,
                           "min": 128, "max": 1536}
    assert t["trace_seconds"] == 6.0 and "rehearsal" in t
    assert t["prompt"]["max"] + t["output"]["max"] == e["max_len"] == 2560
    # the traffic's worst case in every slot, and the scratch page
    assert e["page_size"] == 256
    assert e["n_pages"] == 256 * (2560 // e["page_size"]) + 1 == 2561


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
    assert pool.ssm.shape == (26, 256, 16, 5120)
    assert pool.conv.shape == (26, 256, 3 * 5120)
    assert pool.k.shape == (2, 2561, 256, 1, 128)
    # a state row: 26 layers x (16 x 5120 + 3 x 5120) float32
    row = costs_scan.state_row_bytes(hf)
    assert row == kvhybrid.row_nbytes(pool) == 26 * (16 + 3) * 5120 * 4
    assert costs_scan.ssm_row_bytes(hf) == pool.ssm.size // 256 * 4
    assert round(costs_scan.ssm_row_bytes(hf) / 1e6, 2) == 8.52
    assert round(row / 1e6, 2) == 10.12  # a slot, whatever the context
    assert round(256 * row / 1e9, 2) == 2.59  # the pool's state
    # pages: 2 attention layers x K and V x 1 head x 128 x bf16 a token
    assert costs_scan.kv_token_bytes(hf) == 1024
    pages = (pool.k.size + pool.v.size) * 2
    assert pages == 2561 * 256 * costs_scan.kv_token_bytes(hf)
    assert round(pages / 1e9, 2) == 0.67
    # weights: the packed projections as costs_scan counts them and the two
    # small bf16 projections are what the tree holds beside the embedding,
    # the convolution, `a`, D and the norms
    tree = weights.param_shapes(cfg, "sym_int4")
    packed = costs_scan.linear_bytes(hf)
    small = costs_scan.small_projection_bytes(hf)
    total = costs.tree_bytes(tree)
    assert 0 < total - packed - small < 0.006 * total
    assert round(packed / 1e9, 2) == 1.68 and round(small / 1e6) == 94
    embed = hf["vocab_size"] * hf["hidden_size"] * 2
    assert round((total + embed) / 1e9, 2) == 2.11
    d = cell.config["bench"]["engine_derivation"]
    assert "2.59 GB" in d and "0.67 GB" in d and "2.11 GB" in d
    assert "10.12 MB" in d
    # 3.03 B parameters, the catalog's 3B (the head is the tied table's
    # packed copy: one set of parameters)
    n = sum(k * o for k, o in costs_scan.decode_linears(hf)[:-1]) \
        + small // 2 + embed // 2
    assert round(n / 1e9, 2) == 3.03
    for run in tree["runs"].values():
        if "w_x" in run:  # the small projections stay unpacked
            assert run["w_x"].shape[1:] == (192, 5120)
            assert run["w_dt"].shape[1:] == (5120, 160)
            assert run["a"].shape[1:] == (16, 5120)
            assert str(run["a"].dtype) == "float16"
            assert run["w_in"].data.shape[1:] == (10240, 1280)


# ---- the cost functions, against hand counts --------------------------------

def test_decode_cost_is_state_twice_plus_the_tokens_own(hf):
    ssm = costs_scan.ssm_row_bytes(hf)
    one = costs_scan.decode_cost(hf, 1)
    a_once = 26 * 16 * 5120 * 4
    small = one["bytes"] - 2 * ssm - a_once
    assert small == 26 * (3 * 5120 + 2 * 16) * 4 and small < 0.2 * ssm
    full = costs_scan.decode_cost(hf, 256)
    assert full["bytes"] == 256 * (2 * ssm + small) + a_once
    assert full["flops"] == 256 * 26 * 16 * 5120 * 6
    assert costs_scan.decode_cost(hf, 0) == {"bytes": 0, "flops": 0}
    peak = costs.peaks("TPU v5 lite")
    t, bound = costs.roofline_seconds(full, peak)
    assert bound == "memory" and 0.0056 < t < 0.0060  # 4.8 GB at 819 GB/s
    assert full["flops"] / peak["bf16_flops_per_s"] < t / 50


def test_prefill_cost_is_the_tokens_operands_and_a_row_once(hf):
    one = costs_scan.prefill_cost(hf, 1024)
    token = (3 * 5120 + 2 * 16) * 4
    once = 26 * 3 * 16 * 5120 * 4  # the row in, the row out, A
    assert one["bytes"] == 1024 * 26 * token + once
    assert one["flops"] == 1024 * 26 * 16 * 5120 * 6
    assert costs_scan.prefill_cost(hf, 2048, 2)["bytes"] == 2 * one["bytes"]
    # memory-bound against the table's peaks: the VPU's work over the MXU's
    # peak is nothing (the reader says why the share reads low)
    t, bound = costs.roofline_seconds(one, costs.peaks("TPU v5 lite"))
    assert bound == "memory" and 0.0019 < t < 0.0022


def test_the_call_list_is_this_models(hf):
    calls = costs_scan.decode_linears(hf)
    assert len(calls) == 26 * 5 + 2 * 7 + 1
    assert calls[0] == (2560, 10240) and calls[1] == (5120, 2560)
    assert calls[-1] == (2560, 65536)
    assert calls.count((2560, 128)) == 4 and calls.count((8192, 2560)) == 28


def test_step_bytes_add_up(hf):
    w = 1_800_000_000
    assert costs_scan.step_bytes(hf, w, 0, 0, 256) == w
    moved = 2 * 256 * costs_scan.state_row_bytes(hf)
    assert costs_scan.step_bytes(hf, w, moved, 700, 256) == \
        w + moved + 700 * 256 * 1024


# ---- the readers -----------------------------------------------------------

def _run(cell, steps, device=None, weight_bytes=0, prefills=()):
    spans = [{"ph": "X", "name": "decode_step", "ts": (10 + i) * 1e6,
              "dur": 3e4, "args": a} for i, a in enumerate(steps)]
    spans += [{"ph": "X", "name": "prefill", "ts": (10.5 + i) * 1e6,
               "dur": 4e4, "args": a} for i, a in enumerate(prefills)]
    return Run(cell=cell, hf=cells.as_run(cell.config),
               peak=costs.peaks("TPU v5 lite"), t0=0.0, t1=100.0,
               requests=[], spans=spans, device=device,
               weight_bytes=weight_bytes)


def _device(n_steps, kernel_s, step_s=0.03, n_prefills=0, prefill_s=0.0,
            begin=0.0, end=100.0):
    """What the readers ask of a reduced trace."""
    kernels = {("mamba1_decode", "engine_decode"): (n_steps, kernel_s),
               ("mamba1_prefill", "engine_paged_prefill"): (n_prefills,
                                                            prefill_s)}
    return types.SimpleNamespace(
        begin=begin, end=end, offset=0.0,
        kernel_in_program=lambda kernel, program: kernels.get(
            (kernel, program), (0, 0.0)),
        program_seconds=lambda program: (
            [step_s] * n_steps if program == "engine_decode" else []))


def _step(hf, rows, pages=700):
    return {"occupancy": rows, "slots": 256, "state_rows_live": rows,
            "state_bytes_moved": 2 * rows * costs_scan.state_row_bytes(hf),
            "live_pages": pages, "grid_pages": 2560}


def test_readers_on_recorded_spans_and_kernel_time(cell, hf):
    run = _run(cell, [_step(hf, 256), _step(hf, 128)],
               _device(n_steps=2, kernel_s=0.012, step_s=0.030, n_prefills=2,
                       prefill_s=0.020),
               weight_bytes=18 * 10 ** 8,
               prefills=[{"prompt_tokens": 1000, "scan_tokens": 1000},
                         {"prompt_tokens": 200, "scan_tokens": 200}])
    assert cell.reader(NEW[0]).read(run) == pytest.approx(6.0)
    # 192 live rows a step on average: their state twice over 819 GB/s, over
    # the kernel's 6 ms
    need = costs_scan.decode_cost(hf, 192)
    share = cell.reader(NEW[1]).read(run)
    assert share == pytest.approx(
        100 * need["bytes"] / run.peak["hbm_bytes_per_s"] / 0.006)
    assert 60 < share < 100
    # 600 tokens a prefill on average over 10 ms of the kernel a prefill
    least = costs_scan.prefill_cost(hf, 600)["bytes"] / 819e9
    assert cell.reader(NEW[2]).read(run) == pytest.approx(
        100 * least / 0.010)
    assert cell.reader(NEW[2]).read(run) < 20
    moved = 2 * 192 * costs_scan.state_row_bytes(hf)
    assert cell.reader(NEW[3]).read(run) == pytest.approx(
        100 * (18e8 + moved + 700 * 256 * 1024)
        / run.peak["hbm_bytes_per_s"] / 0.030)


def test_roofline_counts_the_traced_seconds_steps_only(cell, hf):
    steps = [_step(hf, 256), _step(hf, 64), _step(hf, 64)]
    run = _run(cell, steps, _device(2, 0.012, begin=10.5, end=12.5))
    a = cell.reader(NEW[1]).read(run)  # the two steps at 64 rows
    run.device = _device(2, 0.012)  # all three
    assert a < cell.reader(NEW[1]).read(run)


@pytest.mark.parametrize("name", NEW)
def test_readers_return_nothing_where_there_is_nothing_to_read(cell, name):
    """The parent's program (it cannot run the configuration; were it
    handed other spans): spans without the arguments, a trace without the
    kernels, a configuration without the keys. The metric is left out of
    the line, nothing raises."""
    bare = {"occupancy": 8, "slots": 8, "live_pages": 3, "grid_pages": 256}
    assert cell.reader(name).read(_run(cell, [bare])) is None
    assert cell.reader(name).read(
        _run(cell, [bare], _device(n_steps=0, kernel_s=0.0))) is None
    other = _run(cell, [bare], _device(2, 0.01, n_prefills=2,
                                       prefill_s=0.01))
    other.hf = {"hidden_size": 64}
    if name != NEW[0]:  # a time needs no shapes
        assert cell.reader(name).read(other) is None
    assert getattr(cell.reader(name), "ENTRIES") == ("engine",)


def test_the_programs_spans_carry_what_the_readers_read(cell):
    """A tiny engine's own spans through the same readers' helper: the
    program's count of the bytes is the yardstick's."""
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
    assert eng.state_row_bytes == costs_scan.state_row_bytes(hf)
    eng.submit(list(range(1, 20)), max_new_tokens=3)
    eng.run_until_idle()
    run = Run(cell=cell, hf=hf, peak=costs.peaks("TPU v5 lite"), t0=0.0,
              t1=float("inf"), requests=[], spans=tr.events())
    steps = costs_scan.traced_steps(run)
    assert steps and all(
        a["state_bytes_moved"] == 2 * a["state_rows_live"]
        * costs_scan.state_row_bytes(hf) and "live_pages" in a
        for a in steps)
    (pre,) = [a for _, _, a in run.span_list("prefill")]
    assert pre["scan_tokens"] == pre["prompt_tokens"] == 19


# ---- the reference ---------------------------------------------------------

def test_reference_rounding_hooks_move_the_logits(cell):
    """`rnd` reaches every matrix product and the scan's products, and
    `state_dtype` the scan: at float8 and with a bfloat16 state the logits
    move, with the identity they do not."""
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
        cfg, jax.random.PRNGKey(2), scale=0.08), cfg, "sym_int4")
    ref = cell.reference()
    toks = jnp.asarray(np.random.default_rng(2).integers(1, 512, 24))
    plain = np.asarray(ref.logits(hf, params, toks, 5))
    same = np.asarray(ref.logits(hf, params, toks, 5, rnd=lambda x: x))
    np.testing.assert_array_equal(plain, same)

    def fp8(x):
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    low = np.asarray(ref.logits(hf, params, toks, 5, rnd=fp8))
    assert plain.shape == (5, 512) and np.abs(low - plain).max() > 1e-3
    half = np.asarray(ref.logits(hf, params, toks, 5,
                                 state_dtype=jnp.bfloat16))
    assert np.abs(half - plain).max() > 1e-4
    # and it reads nothing of the program
    with open(os.path.join(ROOT, "bench", "reference", "jamba.py")) as f:
        assert "bigdl_tpu" not in f.read()


# ---- the command -----------------------------------------------------------

def test_rehearsal_runs_the_cell_end_to_end(tmp_path):
    """`bench/run.py --rehearse` on the cell: CPU, tiny sizes, the kernels in
    the interpreter, exit code 3, and a line with the new metrics' sources
    in place (no device on a CPU, so the device-trace readers stay out).
    Run from a COPY of the benchmark's files, as granite's is."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "bigdl_tpu"), tmp_path / "bigdl_tpu")
    out = subprocess.run(
        [sys.executable, str(tmp_path / "bench" / "run.py"), "--workload",
         CELL, "--seed", str(2 ** 31 + 56), "--seconds", "3", "--trace", "1",
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
    assert "mamba1    pallas" in out.stdout and "decode" in out.stdout
    assert "mamba1    xla" not in out.stdout and "prefill" in out.stdout
    assert "pallas:paged" in out.stdout and "pallas:flash" in out.stdout
    assert {"engine.decode_occupancy", "step.decode_ms_p50--closed",
            "kernel.paged_live_page_share--closed",
            "step.prefill_ms_p50--closed",
            "engine.admit.retrace_ms_p50--closed"} <= set(line["metrics"])
    assert line["metrics"]["engine.admit.retrace_ms_p50--closed"][
        "value"] == 0.0
