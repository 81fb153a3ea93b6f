"""The LFM2-24B-A2B cell's own pieces (ISSUE 61): the configuration against
its `published` block, the file's arithmetic (tails a slot, pool, pages,
weights) against the program's own shapes, `bench/costs_conv.py` against hand
counts, the four readers on recorded spans and a recorded trace, the
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

from bench import cells, costs, costs_conv, costs_moe  # noqa: E402
from bench.records import Run  # noqa: E402
from bench.reduce.xplane import Event, Loaded, Reduced  # noqa: E402

CELL = "lfm2-24b-a2b.manydocs-closed"
NEW = ("kernel.pair_attn_ms_per_step", "kernel.pair_attn_roofline",
       "kernel.short_conv_ms_per_step", "step.decode_conv_mbu")
JOINED = ("kernel.moe_ffn_ms_per_step", "kernel.moe_ffn_roofline",
          "engine.moe_load_imbalance", "engine.decode_occupancy",
          "engine.host_gap_ms_p50--closed", "engine.host_gap_ms_p95--closed",
          "step.decode_ms_p50--closed", "step.prefill_ms_p50--closed",
          "engine.admit.dispatch_ms_p50--closed",
          "engine.admit.sample_ms_p50--closed",
          "engine.admit.retrace_ms_p50--closed",
          "engine.admit.idle_ms--closed",
          "engine.step.dispatch_ms_p50--closed",
          "engine.step.idle_ms--closed",
          "kernel.paged_live_page_share--closed")
ATTENTION = [2, 6, 10, 14, 18]


@pytest.fixture(scope="module")
def cell():
    return cells.resolve(CELL, ROOT)


@pytest.fixture(scope="module")
def hf(cell):
    return cells.as_run(cell.config)


# ---- the configuration -----------------------------------------------------

def test_the_cell_is_the_first_stage_cut_in_depth_alone(cell, hf):
    pub = cell.config["published"]
    assert cell.traffic_name == "manydocs-closed" and cell.chips == 1
    assert cell.entry_name == "engine"
    assert cell.config["reduced"] == ["num_hidden_layers", "layer_types"]
    assert {k for k in pub if hf[k] != pub[k]} == set(cell.config["reduced"])
    assert set(hf) == set(pub)
    assert hf["num_hidden_layers"] == 20 and pub["num_hidden_layers"] == 40
    assert hf["layer_types"] == pub["layer_types"][:20]  # layers 0..19
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = [json.loads(line) for line in f if '"LFM2-24B-A2B"' in line]
    if row:  # where the catalog is at hand: the row's `config`, key for key
        assert pub == row[0]["config"]
        assert cell.config["source"] == row[0]["source_url"]
    # every width as published
    assert (hf["hidden_size"], hf["num_attention_heads"],
            hf["num_key_value_heads"], hf["intermediate_size"],
            hf["num_experts"], hf["moe_intermediate_size"],
            hf["num_experts_per_tok"], hf["conv_L_cache"], hf["vocab_size"],
            hf["num_dense_layers"]) == (2048, 32, 8, 11776, 64, 1536, 4, 3,
                                        65536, 2)
    assert costs_conv.knows(hf) and costs_conv.head_dim(hf) == 64
    assert [i for i, k in enumerate(hf["layer_types"])
            if k == "full_attention"] == ATTENTION  # five whole periods
    assert (costs_conv.n_layers(hf, "conv"), costs_conv.n_sparse(hf)) \
        == (15, 18)
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) | set(JOINED) <= names
    # readers that count keys and values, or a dense MLP, in EVERY layer,
    # or experts under another key, are not this cell's
    assert not {"kernel.paged_attn_roofline--closed", "step.decode_mbu--closed",
                "step.decode_ssm_mbu", "kernel.ssm_decode_roofline",
                "kernel.decode.qmatmul_roofline--closed",
                "kernel.paged_attn_ms_per_step--closed"} & names
    assert [m["name"] for m in cell.end_to_end] == ["output_tokens_per_s",
                                                    "setup_s"]
    assert {"tie_word_embeddings", "head_dim", "split_order", "router",
            "state_dtype", "lane_pairs", "packed", "weights"} \
        <= set(cell.config["assumed"])


def test_the_entries_are_appended():
    """The cell's entries in `BENCHMARK.json`: the cell after the thirteen
    that were there, its four metrics its own, its name on the lists whose
    readers count right for it. (`test_bench_datadriven.py` and
    `test_bench_generators.py` hold its configuration's cut and its traffic
    as they hold every registered cell's.)"""
    bench = cells.load_benchmark(ROOT)
    assert [w["name"] for w in bench["workloads"]].index(CELL) == 13
    assert [c["name"] for c in bench["configs"]].index(
        "lfm2-24b-a2b-int4") == 11
    rows = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    for name in NEW:
        m = rows[name]
        assert (m["moves"], m["workloads"], m["source"]) == (
            "output_tokens_per_s", [CELL], "device_trace")
        assert m["layer"] == ("model step" if name.startswith("step.")
                              else "kernels")
        assert m["unit"] == ("ms" if name.endswith("ms_per_step") else "%")
    for name in JOINED:
        assert CELL in rows[name]["workloads"]
        assert rows[name]["moves"] == "output_tokens_per_s"
    assert CELL in rows["output_tokens_per_s"]["workloads"]
    (w,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (w["config"], w["traffic"], w["chips"]) == (
        "lfm2-24b-a2b-int4", "manydocs-closed", 1)
    assert len(w["why"]) <= 200


def test_the_file_runs_as_the_program_reads_it(cell, hf):
    from bigdl_tpu import kvhybrid
    from bigdl_tpu.models import get_family
    from bigdl_tpu.models.config import ModelConfig

    cfg = ModelConfig.from_hf_config(hf)
    fam = get_family(cfg.model_type)
    assert fam.PAGED_CACHE_KIND == kvhybrid.KIND
    runs = fam.layer_runs(cfg)
    assert runs[0] == ("conv", 0, 2, True)  # the two dense layers, their own
    assert runs[1:] == [
        ("attention", 0, 1, False), ("conv", 2, 3, False),
        ("attention", 1, 1, False), ("conv", 5, 3, False),
        ("attention", 2, 1, False), ("conv", 8, 3, False),
        ("attention", 3, 1, False), ("conv", 11, 3, False),
        ("attention", 4, 1, False), ("conv", 14, 1, False)]
    assert (cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim_) == (32, 8, 64)
    assert fam.kv_layout(cfg) == (4, 128)  # lane pairs
    assert cfg.qk_norm and cfg.tie_word_embeddings and cfg.rope_theta == 1e6
    assert (cfg.scoring_func, cfg.topk_method, cfg.n_group,
            cfg.norm_topk_prob, cfg.routed_scaling_factor) == (
        "sigmoid", "noaux_tc", 1, True, 1)
    assert fam.ROUTER_EPS == cell.reference().ROUTER_EPS == 1e-6


def test_traffic_is_the_issues(cell):
    t, e = cell.traffic, cell.config["bench"]["engine"]
    assert t["generator"] == "arrivals" and t["entry"] == "engine"
    assert t["process"] == {"kind": "closed", "clients": 64, "think_s": 0,
                            "block": 64}
    assert t["process"]["clients"] == e["n_slots"] == 64
    assert t["prompt"] == {"dist": "lognormal", "median": 2048, "sigma": 0.5,
                           "min": 1024, "max": 4096,
                           "ladder": [1024, 1536, 2048, 3072, 4096]}
    assert t["output"] == {"dist": "lognormal", "median": 384, "sigma": 0.5,
                           "min": 128, "max": 1024}
    assert t["trace_seconds"] == 6.0 and "rehearsal" in t
    # `longctx-closed`'s lengths at twice its clients
    other = cells.load_json(ROOT, "bench", "traffic", "longctx-closed.json")
    assert (t["prompt"], t["output"]) == (other["prompt"], other["output"])
    assert t["process"]["clients"] == 2 * other["process"]["clients"]
    assert t["prompt"]["max"] + t["output"]["max"] == e["max_len"] == 5120
    # the traffic's worst case in every slot, and the scratch page
    assert e["page_size"] == 64
    assert e["n_pages"] == 64 * (5120 // e["page_size"]) + 1 == 5121


# ---- the file's arithmetic, against the program's shapes -------------------

def test_tails_pool_pages_and_weights_are_the_programs_own(cell, hf):
    """Shapes only: nothing is allocated."""
    import jax

    from bench import weights
    from bigdl_tpu import kvhybrid, kvpaged
    from bigdl_tpu.models import get_family
    from bigdl_tpu.models.config import ModelConfig

    cfg = ModelConfig.from_hf_config(hf)
    e = cell.config["bench"]["engine"]
    pool = jax.eval_shape(lambda: get_family(cfg.model_type).init_paged_cache(
        cfg, e["n_pages"], e["page_size"], e["n_slots"],
        e["max_len"] // e["page_size"]))
    assert pool.ssm is None  # the tail is all the state
    assert pool.conv.shape == (15, 64, 2 * 2048)
    assert pool.k.shape == pool.v.shape == (5, 5121, 64, 4, 128)
    # a state row: 15 layers x 2 inputs x 2048 channels, float32
    row = costs_conv.tail_row_bytes(hf)
    assert row == kvhybrid.row_nbytes(pool) == 15 * 2 * 2048 * 4 == 245760
    # a token's keys and values: the pairs hold the published bytes
    assert costs_conv.kv_token_bytes(hf) == 5 * 2 * 8 * 64 * 2 == 10240
    assert kvpaged.kv_page_nbytes(pool.kv) == 64 * 10240 == 655360
    assert costs_conv.page_bytes(hf, 64) * 5 == 655360
    pages = 2 * pool.k.size * 2
    assert pages == 5121 * 655360 and 3.35e9 < pages < 3.36e9
    # the parameter tree: every packed array, the small ones, the embedding
    shapes = weights.param_shapes(cfg, "sym_int4")
    tree = costs.tree_bytes(shapes)
    assert tree == (costs_conv.linear_bytes(hf)
                    + costs_conv.expert_stack_bytes(hf)
                    + costs_conv.small_bytes(hf)) == 6453217024
    assert costs_moe.expert_bytes(hf) * 64 == 339738624  # a layer's experts
    assert costs_conv.expert_stack_bytes(hf) == 18 * 339738624
    assert shapes["embed"].shape == (65536, 2048)
    # one expert's three matrices are GLM-4.7-Flash's shape
    glm = cells.as_run(cells.load_json(
        ROOT, "bench", "configs", "glm-4.7-flash-int4.json"))
    assert costs_moe.expert_shape(hf) == costs_moe.expert_shape(glm)


def test_costs_against_hand_counts(hf):
    # 300 live pages over 64 rows: five layers of K and V at 8 x 64 bf16
    c = costs_conv.attn_decode_cost(hf, 64, 300, 64)
    assert c["bytes"] == 5 * (300 * 2 * 64 * 8 * 64 * 2
                              + 64 * 32 * 64 * 2 * 2)
    assert c["flops"] == 5 * 300 * 64 * 32 * 4 * 64
    # a quarter of what a reader that counts all twenty layers would say
    from bench import costs_paged

    assert costs_paged.decode_cost(hf, 64, 300, 64)["bytes"] == 4 * c["bytes"]
    lin = costs_conv.decode_linears(hf)
    assert len(lin) == 15 * 2 + 5 * 4 + 2 * 3 + 1
    assert lin.count((2048, 6144)) == 15 and lin[-1] == (2048, 65536)
    # a step: everything but the experts nobody chose
    need = costs_conv.step_bytes(hf, 6453217024, 1000, 2 * 64 * 245760,
                                 2500, 64)
    assert need == (6453217024 - (18 * 64 - 1000) * 5308416
                    + 2 * 64 * 245760 + 2500 * 64 * 10240)


# ---- the readers -----------------------------------------------------------

def _run(cell, steps, device=None, weight_bytes=0):
    spans = [{"ph": "X", "name": "decode_step", "ts": (10 + i) * 1e6,
              "dur": 3e4, "args": a} for i, a in enumerate(steps)]
    return Run(cell=cell, hf=cells.as_run(cell.config),
               peak=costs.peaks("TPU v5 lite"), t0=0.0, t1=100.0,
               requests=[], spans=spans, device=device,
               weight_bytes=weight_bytes)


def _device(n_steps, kernel_s, step_s=0.02, begin=0.0, end=100.0):
    """What the readers ask of a reduced trace."""
    kernels = {("paged_decode_attention", "engine_decode"):
               (n_steps, kernel_s)}
    return types.SimpleNamespace(
        begin=begin, end=end, offset=0.0,
        kernel_in_program=lambda kernel, program: kernels.get(
            (kernel, program), (0, 0.0)),
        program_seconds=lambda program: (
            [step_s] * n_steps if program == "engine_decode" else []))


def _step(hf, rows, pages=2500, hit=1100):
    return {"occupancy": rows, "slots": 64, "state_rows_live": rows,
            "state_bytes_moved": 2 * rows * costs_conv.tail_row_bytes(hf),
            "live_pages": pages, "grid_pages": 5120, "moe_experts": 18 * 64,
            "moe_experts_hit": hit, "moe_assignments": 18 * 4 * rows,
            "moe_max_expert_load": 9}


def test_readers_on_recorded_spans_and_kernel_time(cell, hf):
    run = _run(cell, [_step(hf, 64, 2600), _step(hf, 32, 1400)],
               _device(n_steps=2, kernel_s=0.006, step_s=0.020),
               weight_bytes=6453217024)
    assert cell.reader(NEW[0]).read(run) == pytest.approx(3.0)
    # 2000 live pages a step on average over five layers, over 3 ms
    need = costs_conv.attn_decode_cost(hf, 64, 2000, 48)
    share = cell.reader(NEW[1]).read(run)
    assert share == pytest.approx(
        100 * need["bytes"] / run.peak["hbm_bytes_per_s"] / 0.003)
    assert 40 < share < 100
    moved = 2 * 48 * costs_conv.tail_row_bytes(hf)
    want = costs_conv.step_bytes(hf, 6453217024, 1100, moved, 2000, 64)
    assert cell.reader(NEW[3]).read(run) == pytest.approx(
        100 * want / run.peak["hbm_bytes_per_s"] / 0.020)
    assert 30 < cell.reader(NEW[3]).read(run) < 100
    # the experts' readers count this configuration right as they stand
    assert cell.reader("engine.moe_load_imbalance").read(run) \
        == pytest.approx((9 * 1152 / (72 * 64) + 9 * 1152 / (72 * 32)) / 2)


def test_the_scope_reader_takes_the_mixers_xla_time_of_whole_executions(cell):
    """`kernel.short_conv_ms_per_step` on a hand-made trace: two executions
    of `engine_decode` whole in the window and one cut by its end. What
    counts is XLA's under the mixer's scope `mamba2` in the whole ones,
    wherever inside it a fusion's root stands: not the projections' kernel,
    not another scope's fusion."""
    dev_name = "/device:TPU:0"
    mods = [Event("jit_engine_decode(7)", t, 0.5) for t in (1.0, 2.0, 9.8)]
    ops = [Event(name, t + at, dur) for t in (1.0, 2.0) for name, at, dur in (
        ("fusion.1", 0.1, 0.01), ("fusion.2", 0.15, 0.004),
        ("qmatmul.3", 0.2, 0.03), ("fusion.4", 0.3, 0.02))]
    ops.append(Event("fusion.1", 9.9, 0.01))
    stack = "jit(engine_decode)/while/body/mamba2/"
    names = {"fusion.1": stack + "short_conv/mul",
             "fusion.2": stack + "convert_element_type",
             "qmatmul.3": stack + "jit(_qmm)/pallas_call",
             "fusion.4": "jit(engine_decode)/while/body/norm/mul"}
    meta = {dev_name: {(7, k): (v, "") for k, v in names.items()}}
    dev = Reduced(Loaded({dev_name: ops}, {dev_name: mods}, sync=0.0,
                         lines={}), t_sync=0.0, begin=0.0, end=10.0)
    run = _run(cell, [], dev)
    run.extra["scope_metadata"] = meta
    assert cell.reader(NEW[2]).read(run) == pytest.approx(14.0)
    other = _run(cell, [], dev)  # the parent's trace: no such scope
    other.extra["scope_metadata"] = {dev_name: {}}
    assert cell.reader(NEW[2]).read(other) is None


def test_roofline_counts_the_traced_seconds_steps_only(cell, hf):
    steps = [_step(hf, 64, 3000), _step(hf, 16, 500), _step(hf, 16, 500)]
    run = _run(cell, steps, _device(2, 0.006, begin=10.5, end=12.5))
    a = cell.reader(NEW[1]).read(run)  # the two steps at 500 pages
    run.device = _device(2, 0.006)  # all three
    assert a < cell.reader(NEW[1]).read(run)


@pytest.mark.parametrize("name", NEW)
def test_readers_return_nothing_where_there_is_nothing_to_read(cell, name):
    """The parent's program (it cannot run the configuration; were it
    handed other spans): spans without the arguments, a trace without the
    kernel, a configuration without the keys. The metric is left out of
    the line, nothing raises."""
    bare = {"slots": 8, "grid_pages": 256}
    assert cell.reader(name).read(_run(cell, [bare])) is None
    assert cell.reader(name).read(
        _run(cell, [bare], _device(n_steps=0, kernel_s=0.0))) is None
    full = {"occupancy": 8, "slots": 8, "live_pages": 3, "grid_pages": 256}
    other = _run(cell, [full], _device(2, 0.01))
    other.hf = {"hidden_size": 64}
    if name not in (NEW[0], NEW[2]):  # a time needs no shapes
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
    assert eng.state_row_bytes == costs_conv.tail_row_bytes(hf)
    eng.submit(list(range(1, 20)), max_new_tokens=3)
    eng.run_until_idle()
    run = Run(cell=cell, hf=hf, peak=costs.peaks("TPU v5 lite"), t0=0.0,
              t1=float("inf"), requests=[], spans=tr.events())
    steps = costs_conv.traced_steps(run)
    assert steps and all(
        a["state_bytes_moved"] == 2 * a["state_rows_live"]
        * costs_conv.tail_row_bytes(hf) and "live_pages" in a
        and a["moe_experts"] == costs_conv.n_sparse(hf) * hf["num_experts"]
        and a["moe_assignments"] == costs_conv.n_sparse(hf)
        * hf["num_experts_per_tok"] * a["state_rows_live"]
        for a in steps)
    # a tail is not a prefill form: the span counts no state work
    (pre,) = [a for _, _, a in run.span_list("prefill")]
    assert pre["prompt_tokens"] == 19
    assert not {"state_chunks", "scan_tokens", "conv_tokens"} & set(pre)


# ---- the reference ---------------------------------------------------------

def test_reference_rounding_hook_moves_the_logits(cell):
    """`rnd` reaches every matrix product: at float8 the logits move, with
    the identity they do not; and the reference reads nothing of the
    program but the tree it is handed."""
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
    with open(os.path.join(ROOT, "bench", "reference", "lfm2_moe.py")) as f:
        text = f.read()
    # the one thing it takes from the program is the request's record of
    # its expert choices
    assert "from bigdl_tpu.serving.engine import last_routed_request" in text
    assert text.count("bigdl_tpu") == 1


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
         CELL, "--seed", str(2 ** 31 + 61), "--seconds", "3", "--trace", "1",
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
    assert "paged     rows" in out.stdout and "piped" not in out.stdout
    assert "lane pairs" in out.stdout
    assert "pallas:paged" in out.stdout and "pallas:flash" in out.stdout
    assert {"engine.decode_occupancy", "step.decode_ms_p50--closed",
            "kernel.paged_live_page_share--closed",
            "step.prefill_ms_p50--closed", "engine.moe_load_imbalance",
            "engine.admit.retrace_ms_p50--closed"} <= set(line["metrics"])
    assert line["metrics"]["engine.admit.retrace_ms_p50--closed"][
        "value"] == 0.0
