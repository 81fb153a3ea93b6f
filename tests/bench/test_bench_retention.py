"""The recurrent-state cell's own pieces (ISSUE 31): the configuration against
its `published` block, the cost arithmetic against the program's own state,
the three readers on recorded spans and a recorded trace, the reference's
rounding hook, and `bench/run.py --rehearse` on the cell."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import cells, costs, costs_retention  # noqa: E402
from bench.records import Run  # noqa: E402

CELL = "brumby-14b.reason-closed"
NEW = ("kernel.retention_decode_ms_per_step",
       "kernel.retention_decode_roofline", "step.decode_state_mbu")
GIB = 2.0 ** 30


@pytest.fixture(scope="module")
def cell():
    return cells.resolve(CELL, ROOT)


# ---- the configuration -----------------------------------------------------

def test_the_cell_is_brumby_at_published_widths_under_reason_closed(cell):
    hf, pub = cells.as_run(cell.config), cell.config["published"]
    assert cell.traffic_name == "reason-closed" and cell.chips == 1
    assert cell.entry_name == "engine"
    assert cell.config["reduced"] == ["num_hidden_layers"]
    assert set(hf) == set(pub)  # every key of the source runs
    assert {k for k in pub if hf[k] != pub[k]} == {"num_hidden_layers"}
    assert hf["num_hidden_layers"] == 20 and pub["num_hidden_layers"] == 40
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= names and "setup.weights_s" in names
    # keys and values it does not have: the paged-attention metrics and the
    # step's KV bandwidth are not this cell's
    assert not {n for n in names if "paged" in n or n.startswith(
        "step.decode_mbu")}
    assert [m["name"] for m in cell.end_to_end] == ["output_tokens_per_s",
                                                    "setup_s"]
    # what the source's config.json lacks is listed, each with its reason
    assumed = cell.config["assumed"]
    assert {"retention_degree", "retention_gate", "retention_eps",
            "rope_and_qk_norm", "prefill_chunk", "switch_over_length",
            "state_layout", "weights"} <= set(assumed)
    assert all("ISSUE 31" in assumed[k] or "config.json" in assumed[k]
               for k in ("retention_degree", "retention_gate",
                         "retention_eps", "rope_and_qk_norm"))


def test_the_file_runs_as_the_program_reads_it(cell):
    from bigdl_tpu import kvstate
    from bigdl_tpu.models import get_family, llama
    from bigdl_tpu.models.config import ModelConfig

    cfg = ModelConfig.from_hf_config(cells.as_run(cell.config))
    assert cfg.attention_kind == kvstate.KIND and cfg.retention_degree == 2
    assert cfg.qk_norm and cfg.head_dim_ == 128 and not cfg.attention_bias
    assert (cfg.num_attention_heads // cfg.num_key_value_heads) == 5
    assert get_family(cfg.model_type).forward is llama.forward
    assert cfg.retention_eps == cell.reference().EPS


def test_traffic_is_the_issues(cell):
    t = cell.traffic
    e = cell.config["bench"]["engine"]
    assert t["process"] == {"kind": "closed", "clients": 8, "think_s": 0,
                            "block": 16}
    assert t["process"]["clients"] == e["n_slots"] == 8
    assert t["prompt"]["ladder"] == cells.load_json(
        ROOT, "bench", "traffic", "chat-closed.json")["prompt"]["ladder"]
    assert (t["output"]["median"], t["output"]["min"],
            t["output"]["max"]) == (384, 128, 1024)
    assert t["prompt"]["max"] + t["output"]["max"] <= e["max_len"] == 2048


# ---- bench/costs_retention.py ----------------------------------------------

def test_state_bytes_are_the_programs_own(cell):
    """The yardstick's arithmetic against the state the program builds
    (shapes only: nothing is allocated), and against the file's derivation."""
    import jax

    from bigdl_tpu import kvstate

    hf = cells.as_run(cell.config)
    assert costs_retention.phi_lanes(hf) == kvstate.phi_dim(128) == 8320
    state = jax.eval_shape(lambda: kvstate.init_state(
        hf["num_hidden_layers"], 8, hf["num_key_value_heads"], 128))
    assert costs_retention.state_row_bytes(hf) == kvstate.row_nbytes(state) \
        == 20 * 8 * 129 * 8320 * 4
    pool = 8 * costs_retention.state_row_bytes(hf)
    assert round(pool / GIB, 2) == 5.12  # what engine_derivation says
    assert "5.12 GiB" in cell.config["bench"]["engine_derivation"]
    # the whole model's one slot, of the deployment's arithmetic
    assert round(2 * costs_retention.state_row_bytes(hf) / GIB, 2) == 1.28


def test_decode_cost_is_state_twice_plus_the_tokens_own(cell):
    hf = cells.as_run(cell.config)
    row = costs_retention.state_row_bytes(hf)
    one = costs_retention.decode_cost(hf, 1)
    small = one["bytes"] - 2 * row
    # q, k, v in, gates, y out: 20 layers x (56 + 40) heads x 128 x 2 B + ...
    assert small == 20 * ((40 + 16) * 128 * 2 + 8 * 4 + 40 * 128 * 2)
    assert small < 1e-3 * row
    eight = costs_retention.decode_cost(hf, 8)
    assert eight["bytes"] == 8 * one["bytes"]
    assert costs_retention.decode_cost(hf, 0) == {"bytes": 0, "flops": 0}
    # memory-bound by two orders: 11 GB against 18 GFLOP
    peak = costs.peaks("TPU v5 lite")
    t, bound = costs.roofline_seconds(eight, peak)
    assert bound == "memory" and 0.0130 < t < 0.0140
    assert eight["flops"] / peak["bf16_flops_per_s"] < t / 100
    # 72% of a full step's bytes are state, as the cell's `why` says
    from bench import weights
    from bigdl_tpu.models.config import ModelConfig

    w = costs.tree_bytes(weights.param_shapes(
        ModelConfig.from_hf_config(hf), "sym_int4"))
    assert 0.70 < eight["bytes"] / (eight["bytes"] + w) < 0.74


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
                "power_retention_decode", "engine_decode") else (0, 0.0)),
        program_seconds=lambda program: (
            [step_s] * n_steps if program == "engine_decode" else []))


def _step(cell, rows):
    row = costs_retention.state_row_bytes(cells.as_run(cell.config))
    return {"occupancy": rows, "slots": 8, "state_rows_live": rows,
            "state_bytes_moved": 2 * rows * row}


def test_readers_on_recorded_spans_and_kernel_time(cell):
    run = _run(cell, [_step(cell, 8), _step(cell, 4)],
               _device(n_steps=2, kernel_s=0.030, step_s=0.040),
               weight_bytes=4 * 10 ** 9)
    assert cell.reader(NEW[0]).read(run) == pytest.approx(15.0)
    # 6 live rows a step on average: their state twice over 819 GB/s, over
    # the kernel's 15 ms
    need = costs_retention.decode_cost(run.hf, 6)
    share = cell.reader(NEW[1]).read(run)
    assert share == pytest.approx(
        100 * need["bytes"] / run.peak["hbm_bytes_per_s"] / 0.015)
    assert 60 < share < 100
    moved = 2 * 6 * costs_retention.state_row_bytes(run.hf)
    assert cell.reader(NEW[2]).read(run) == pytest.approx(
        100 * (4e9 + moved) / run.peak["hbm_bytes_per_s"] / 0.040)


def test_roofline_counts_the_traced_seconds_steps_only(cell):
    steps = [_step(cell, 8), _step(cell, 2), _step(cell, 2)]
    run = _run(cell, steps, _device(2, 0.030, begin=10.5, end=12.5))
    a = cell.reader(NEW[1]).read(run)  # the two steps at 2 rows
    run.device = _device(2, 0.030)  # all three
    assert a < cell.reader(NEW[1]).read(run)


@pytest.mark.parametrize("name", NEW)
def test_readers_return_nothing_where_there_is_nothing_to_read(cell, name):
    """The parent's program: spans without the arguments, a trace without
    the kernel. The metric is left out of the line, nothing raises."""
    bare = {"occupancy": 8, "slots": 8, "live_pages": 3, "grid_pages": 256}
    assert cell.reader(name).read(_run(cell, [bare])) is None
    assert cell.reader(name).read(
        _run(cell, [bare], _device(n_steps=0, kernel_s=0.0))) is None
    assert getattr(cell.reader(name), "ENTRIES") == ("engine",)


def test_the_programs_spans_carry_what_the_readers_read(cell):
    """A tiny engine's own `decode_step` spans through the same readers'
    helper: the program's count of the bytes is the yardstick's."""
    import jax

    from bigdl_tpu.api import TpuModel, optimize_model
    from bigdl_tpu.models import llama
    from bigdl_tpu.models.config import ModelConfig
    from bigdl_tpu.obs.tracing import TraceRecorder
    from bigdl_tpu.serving.engine import InferenceEngine

    hf = dict(cells.as_run(cell.config), **{
        k: v for k, v in cell.config["bench"]["rehearsal"].items()
        if k != "bench"})
    cfg = ModelConfig.from_hf_config(hf)
    model = TpuModel(cfg, optimize_model(
        llama.init_params(cfg, jax.random.PRNGKey(0)), cfg, "sym_int4"),
        "sym_int4")
    tr = TraceRecorder(capacity=1024)
    eng = InferenceEngine(model, n_slots=2, max_len=64, paged=True,
                          page_size=64, n_pages=3, tracer=tr)
    eng.submit(list(range(1, 20)), max_new_tokens=3)
    eng.run_until_idle()
    run = Run(cell=cell, hf=hf, peak=costs.peaks("TPU v5 lite"), t0=0.0,
              t1=float("inf"), requests=[], spans=tr.events())
    steps = costs_retention.traced_steps(run)
    assert steps and all(
        a["state_bytes_moved"] == 2 * a["state_rows_live"]
        * costs_retention.state_row_bytes(hf) for a in steps)


# ---- the reference ---------------------------------------------------------

def test_reference_rounding_hook_moves_the_logits():
    """`rnd` reaches every matrix product: at float8 the logits move, with
    the identity they do not."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.api import optimize_model
    from bigdl_tpu.models import llama
    from bigdl_tpu.models.config import ModelConfig

    hf = dict(model_type="brumby", hidden_size=64, intermediate_size=128,
              num_hidden_layers=2, num_attention_heads=4,
              num_key_value_heads=2, head_dim=16, vocab_size=128,
              rms_norm_eps=1e-6, rope_theta=1e6, tie_word_embeddings=False)
    cfg = ModelConfig.from_hf_config(hf)
    params = optimize_model(llama.init_params(cfg, jax.random.PRNGKey(2)),
                            cfg, "sym_int4")
    ref = cells.load_module(ROOT, "reference", "brumby")
    toks = jnp.asarray(np.random.default_rng(2).integers(1, 128, 24))
    plain = np.asarray(ref.logits(hf, params, toks, 5))
    same = np.asarray(ref.logits(hf, params, toks, 5, rnd=lambda x: x))
    np.testing.assert_array_equal(plain, same)

    def fp8(x):
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    low = np.asarray(ref.logits(hf, params, toks, 5, rnd=fp8))
    assert plain.shape == (5, 128) and np.abs(low - plain).max() > 1e-3


# ---- the command -----------------------------------------------------------

def test_rehearsal_runs_the_cell_end_to_end():
    """`bench/run.py --rehearse` on the cell: CPU, tiny sizes, the kernel in
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
    assert "pallas:retention" in out.stdout
    assert {"engine.decode_occupancy", "step.decode_ms_p50--closed",
            "engine.admit.retrace_ms_p50--closed"} <= set(line["metrics"])
    assert line["metrics"]["engine.admit.retrace_ms_p50--closed"][
        "value"] == 0.0
