"""The latent-page cell's own pieces (ISSUE 34): the configuration against its
`published` block, the cost arithmetic against the program's own pool and
counters, the three readers on recorded spans and a recorded trace, the
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

from bench import cells, costs, costs_latent, costs_moe  # noqa: E402
from bench.records import Run  # noqa: E402

CELL = "glm-4.7-flash.longctx-closed"
NEW = ("kernel.latent_attn_ms_per_step", "kernel.latent_attn_roofline",
       "step.decode_latent_mbu")


@pytest.fixture(scope="module")
def cell():
    return cells.resolve(CELL, ROOT)


# ---- the configuration -----------------------------------------------------

def test_the_cell_is_glm_at_published_widths_under_longctx_closed(cell):
    hf, pub = cells.as_run(cell.config), cell.config["published"]
    assert cell.traffic_name == "longctx-closed" and cell.chips == 1
    assert cell.entry_name == "engine"
    assert cell.config["reduced"] == ["num_hidden_layers"]
    assert set(hf) == set(pub)  # every key of the source runs, no other
    assert {k for k in pub if hf[k] != pub[k]} == {"num_hidden_layers"}
    assert hf["num_hidden_layers"] == 20 and pub["num_hidden_layers"] == 47
    assert hf["first_k_dense_replace"] == 1  # the dense layer stays
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= names and "setup.weights_s" in names
    assert {"kernel.moe_ffn_roofline", "kernel.moe_ffn_ms_per_step",
            "engine.moe_load_imbalance", "engine.decode_occupancy",
            "kernel.paged_live_page_share--closed"} <= names
    # bench/costs.py counts q/k/v/o of a GQA model and a dense MLP in every
    # layer: its readers are not this cell's
    assert not {n for n in names if n.startswith((
        "kernel.decode.qmatmul_roofline", "step.decode_mbu",
        "kernel.paged_attn_ms_per_step"))}
    assert [m["name"] for m in cell.end_to_end] == ["output_tokens_per_s",
                                                    "setup_s"]
    assumed = cell.config["assumed"]
    assert {"scoring_func", "rope_interleave", "num_nextn_predict_layers",
            "published", "weights"} <= set(assumed)
    assert "deployment" in cell.config["bench"]
    assert "pipeline" in cell.config["bench"]["deployment"].lower()


def test_the_file_runs_as_the_program_reads_it(cell):
    from bigdl_tpu.models import deepseek, get_family
    from bigdl_tpu.models.config import ModelConfig

    cfg = ModelConfig.from_hf_config(cells.as_run(cell.config))
    assert get_family(cfg.model_type) is deepseek
    assert cfg.scoring_func == "sigmoid" and cfg.rope_interleaved
    assert deepseek.num_dense_layers(cfg) == 1 and cfg.num_hidden_layers == 20
    assert deepseek.mla_softmax_scale(cfg) == pytest.approx(1 / 16)


def test_traffic_is_the_issues(cell):
    t, e = cell.traffic, cell.config["bench"]["engine"]
    assert t["process"] == {"kind": "closed", "clients": 32, "think_s": 0,
                            "block": 32}
    assert t["process"]["clients"] == e["n_slots"] == 32
    assert t["prompt"] == {"dist": "lognormal", "median": 2048, "sigma": 0.5,
                           "min": 1024, "max": 4096,
                           "ladder": [1024, 1536, 2048, 3072, 4096]}
    assert t["output"] == cells.load_json(
        ROOT, "bench", "traffic", "reason-closed.json")["output"]
    assert t["prompt"]["max"] + t["output"]["max"] == e["max_len"] == 5120
    # the worst case of every slot at once, and the scratch page
    assert e["n_pages"] == 32 * (5120 // e["page_size"]) + 1 == 2561
    assert t["trace_seconds"] == 6.0


# ---- bench/costs_latent.py -------------------------------------------------

def test_latent_bytes_are_the_programs_own(cell):
    """The yardstick's arithmetic against the pool the program builds
    (shapes only: nothing is allocated) and its own per-token count."""
    import jax

    from bigdl_tpu import kvpaged
    from bigdl_tpu.models import deepseek
    from bigdl_tpu.models.config import ModelConfig

    hf = cells.as_run(cell.config)
    e = cell.config["bench"]["engine"]
    cfg = ModelConfig.from_hf_config(hf)
    assert costs_latent.latent_width(hf) == 576
    assert costs_latent.latent_token_bytes(hf) == 20 * 576 * 2 \
        == deepseek.latent_token_nbytes(cfg)
    pool = jax.eval_shape(lambda: deepseek.init_paged_cache(
        cfg, e["n_pages"], e["page_size"], e["n_slots"], 80))
    # the row is padded to whole lane tiles: 576 -> 640 values
    assert pool.lat.shape == (20, 2561, 64, 640)
    page = kvpaged.kv_page_nbytes(pool)
    assert page == 20 * 64 * 640 * 2
    assert round(2561 * page / 1e9, 2) == 4.20
    assert "4.20 GB" in cell.config["bench"]["engine_derivation"]


def test_decode_cost_is_the_live_tokens_once_a_layer(cell):
    hf = cells.as_run(cell.config)
    one = costs_latent.decode_cost(hf, 2800, 1)
    assert one["bytes"] == 2800 * 20 * 1152 + 20 * 20 * (576 + 512) * 2
    assert one["flops"] == 2800 * 20 * 20 * 2 * (576 + 512)
    full = costs_latent.decode_cost(hf, 32 * 2800, 32)
    assert full["bytes"] == pytest.approx(32 * one["bytes"])
    assert costs_latent.decode_cost(hf, 0, 0) == {"bytes": 0, "flops": 0}
    # 38 FLOP a byte: memory-bound on a v5e (240 FLOP a byte at the ridge)
    peak = costs.peaks("TPU v5 lite")
    t, bound = costs.roofline_seconds(full, peak)
    assert bound == "memory" and 0.0024 < t < 0.0027
    assert 36 < full["flops"] / full["bytes"] < 39


def test_step_bytes_leave_out_the_experts_nobody_chose(cell):
    from bench import weights
    from bigdl_tpu.models.config import ModelConfig

    hf = cells.as_run(cell.config)
    # costs_moe reads moe_intermediate_size: one expert is three packed
    # [1536, 2048] matrices
    assert costs_moe.expert_shape(hf) == (2048, 1536)
    assert costs_moe.expert_bytes(hf) == 3 * costs.sym_int4_bytes(1536, 2048)
    assert costs_latent.expert_layers(hf) == 19
    w = costs.tree_bytes(weights.param_shapes(
        ModelConfig.from_hf_config(hf), "sym_int4"))
    every = costs_latent.step_bytes(hf, w, 19 * 64, 0)
    assert every == w
    some = costs_latent.step_bytes(hf, w, 19 * 55, 2.0e9)
    assert some == w - 19 * 9 * costs_moe.expert_bytes(hf) + 2.0e9
    assert 0.8 * w < some - 2.0e9 < w


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
                "paged_latent_decode_attention", "engine_decode")
            else (0, 0.0)),
        program_seconds=lambda program: (
            [step_s] * n_steps if program == "engine_decode" else []))


def _step(cell, rows, tokens, hit=19 * 55):
    per = costs_latent.latent_token_bytes(cells.as_run(cell.config))
    return {"occupancy": rows, "slots": 32, "live_pages": tokens // 64 + rows,
            "grid_pages": 32 * 80, "latent_live_tokens": tokens,
            "latent_bytes_read": tokens * per, "moe_experts_hit": hit,
            "moe_assignments": rows * 4 * 19, "moe_experts": 19 * 64}


def test_readers_on_recorded_spans_and_kernel_time(cell):
    run = _run(cell, [_step(cell, 32, 90000), _step(cell, 16, 30000)],
               _device(n_steps=2, kernel_s=0.010, step_s=0.040),
               weight_bytes=7 * 10 ** 9)
    assert cell.reader(NEW[0]).read(run) == pytest.approx(5.0)
    need = costs_latent.decode_cost(run.hf, 60000, 24)
    share = cell.reader(NEW[1]).read(run)
    assert share == pytest.approx(
        100 * need["bytes"] / run.peak["hbm_bytes_per_s"] / 0.005)
    assert 20 < share < 100
    lat = 60000 * costs_latent.latent_token_bytes(run.hf)
    assert cell.reader(NEW[2]).read(run) == pytest.approx(
        100 * costs_latent.step_bytes(run.hf, 7e9, 19 * 55, lat)
        / run.peak["hbm_bytes_per_s"] / 0.040)


def test_roofline_counts_the_traced_seconds_steps_only(cell):
    steps = [_step(cell, 32, 90000), _step(cell, 8, 9000),
             _step(cell, 8, 9000)]
    run = _run(cell, steps, _device(2, 0.010, begin=10.5, end=12.5))
    a = cell.reader(NEW[1]).read(run)  # the two small steps
    run.device = _device(2, 0.010)  # all three
    assert a < cell.reader(NEW[1]).read(run)


@pytest.mark.parametrize("name", NEW)
def test_readers_return_nothing_where_there_is_nothing_to_read(cell, name):
    """A program without the spans' arguments or the kernel (the parent's
    MLA has no paged path at all): the metric is left out of the line,
    nothing raises."""
    bare = {"occupancy": 8, "slots": 8, "live_pages": 3, "grid_pages": 256}
    assert cell.reader(name).read(_run(cell, [bare])) is None
    assert cell.reader(name).read(
        _run(cell, [bare], _device(n_steps=0, kernel_s=0.0))) is None
    assert getattr(cell.reader(name), "ENTRIES") == ("engine",)


def test_the_programs_spans_carry_what_the_readers_read(cell):
    """A tiny engine's own `decode_step` spans through the readers' helper:
    the program's count of the bytes is the yardstick's."""
    import jax

    from bigdl_tpu.api import TpuModel, optimize_model
    from bigdl_tpu.models import deepseek
    from bigdl_tpu.models.config import ModelConfig
    from bigdl_tpu.obs.tracing import TraceRecorder
    from bigdl_tpu.serving.engine import InferenceEngine

    hf = dict(cells.as_run(cell.config), **{
        k: v for k, v in cell.config["bench"]["rehearsal"].items()
        if k != "bench"})
    cfg = ModelConfig.from_hf_config(hf)
    model = TpuModel(cfg, optimize_model(
        deepseek.init_params(cfg, jax.random.PRNGKey(0)), cfg, "sym_int4"),
        "sym_int4")
    tr = TraceRecorder(capacity=1024)
    eng = InferenceEngine(model, n_slots=2, max_len=64, paged=True,
                          page_size=16, n_pages=9, tracer=tr)
    eng.submit(list(range(1, 20)), max_new_tokens=3)
    eng.run_until_idle()
    run = Run(cell=cell, hf=hf, peak=costs.peaks("TPU v5 lite"), t0=0.0,
              t1=float("inf"), requests=[], spans=tr.events())
    steps = costs_latent.traced_steps(run)
    assert steps and all(
        a["latent_bytes_read"] == a["latent_live_tokens"]
        * costs_latent.latent_token_bytes(hf) for a in steps)
    assert all(a["latent_live_tokens"] in (20, 21) and a["occupancy"] == 1
               and a["moe_experts"] == 2 * 8 for a in steps)


# ---- the reference ---------------------------------------------------------

def test_reference_rounding_hook_moves_the_logits(cell):
    """`rnd` reaches every matrix product: at float8 the logits move, with
    the identity they do not."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.api import optimize_model
    from bigdl_tpu.models import deepseek
    from bigdl_tpu.models.config import ModelConfig

    hf = dict(cells.as_run(cell.config), **{
        k: v for k, v in cell.config["bench"]["rehearsal"].items()
        if k != "bench"})
    cfg = ModelConfig.from_hf_config(hf)
    params = optimize_model(deepseek.init_params(cfg, jax.random.PRNGKey(2)),
                            cfg, "sym_int4")
    ref = cell.reference()
    toks = jnp.asarray(np.random.default_rng(2).integers(1, 512, 24))
    plain = np.asarray(ref.logits(hf, params, toks, 5))
    same = np.asarray(ref.logits(hf, params, toks, 5, rnd=lambda x: x))
    np.testing.assert_array_equal(plain, same)

    def fp8(x):
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    low = np.asarray(ref.logits(hf, params, toks, 5, rnd=fp8))
    assert plain.shape == (5, 512) and np.abs(low - plain).max() > 1e-3


# ---- the command -----------------------------------------------------------

def test_rehearsal_runs_the_cell_end_to_end():
    """`bench/run.py --rehearse` on the cell: CPU, tiny sizes, the kernels in
    the interpreter, exit code 3, and a line with the new metrics' sources
    in place (no device on a CPU, so the device-trace readers stay out)."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--workload",
         CELL, "--seed", str(2 ** 31 + 77), "--seconds", "3", "--trace", "1",
         "--rehearse"],
        capture_output=True, text=True, timeout=900, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 3, out.stderr[-2000:]
    last = out.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL complete, not a result: ")
    line = json.loads(last.split(": ", 1)[1])
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert line["compiles_in_window"] == 0
    assert "pallas:paged_latent" in out.stdout
    assert "pallas:grouped" in out.stdout
    assert {"engine.decode_occupancy", "step.decode_ms_p50--closed",
            "engine.moe_load_imbalance", "kernel.paged_live_page_share--closed",
            "engine.admit.retrace_ms_p50--closed"} <= set(line["metrics"])
    assert line["metrics"]["engine.admit.retrace_ms_p50--closed"][
        "value"] == 0.0
