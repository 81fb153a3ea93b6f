"""The window cell's own pieces (ISSUE 41): the configuration against its
`published` block, the file's arithmetic (a page of each group, the two
pools, weights) against the program's own shapes, `bench/costs_window.py`
against the program's own counts, the four readers on recorded spans and a
recorded trace, the reference's refusal of another family's tree, and
`bench/run.py --rehearse` on the cell."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import cells, costs, costs_moe, costs_paged, costs_window  # noqa: E402
from bench.records import Run  # noqa: E402

CELL = "smallthinker-21ba3b.mixedlen-closed"
NEW = ("kernel.window_attn_roofline", "step.decode_window_mbu",
       "engine.window_pages_held_share", "kernel.primary_experts_roofline")


@pytest.fixture(scope="module")
def cell():
    return cells.resolve(CELL, ROOT)


@pytest.fixture(scope="module")
def hf(cell):
    return cells.as_run(cell.config)


# ---- the configuration -----------------------------------------------------

def test_the_cell_is_smallthinker_at_published_widths(cell, hf):
    pub = cell.config["published"]
    assert cell.traffic_name == "mixedlen-closed" and cell.chips == 1
    assert cell.entry_name == "engine"
    cut = ["num_hidden_layers", "sliding_window_layout", "rope_layout"]
    assert cell.config["reduced"] == cut
    assert set(hf) == set(pub) and "model_type" not in hf
    assert {k for k in pub if hf[k] != pub[k]} == set(cut)
    for key, want in (("hidden_size", 2560), ("num_attention_heads", 28),
                      ("num_key_value_heads", 4), ("head_dim", 128),
                      ("moe_num_primary_experts", 64),
                      ("moe_ffn_hidden_size", 768),
                      ("moe_num_active_primary_experts", 6),
                      ("sliding_window_size", 4096), ("vocab_size", 151936)):
        assert hf[key] == pub[key] == want
    # the cut: six whole periods of [full, window, window, window]
    assert hf["num_hidden_layers"] == 24 and pub["num_hidden_layers"] == 52
    for key in cut[1:]:
        assert hf[key] == pub[key][:24] == [0, 1, 1, 1] * 6
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= names
    assert {"kernel.moe_ffn_ms_per_step", "engine.moe_load_imbalance"} <= names
    # readers that multiply ONE count of live pages by every layer, or read
    # an expert width under another key, are not this cell's; nor the
    # kernel's milliseconds a step, whose list tests/bench/test_bench_paged.py
    # holds equal to its roofline's (PERF.md section 7)
    assert not {"kernel.paged_attn_roofline--closed",
                "kernel.paged_attn_ms_per_step--closed",
                "step.decode_mbu--closed", "kernel.moe_ffn_roofline",
                "kernel.paged_live_page_share--closed"} & names
    assert [m["name"] for m in cell.end_to_end] == ["output_tokens_per_s",
                                                    "setup_s"]
    assert {"every_layer_sparse", "router_input", "rope_convention",
            "model_type", "weights"} <= set(cell.config["assumed"])


def test_the_file_runs_as_the_program_reads_it(hf):
    from bigdl_tpu import kvwindow
    from bigdl_tpu.models import get_family
    from bigdl_tpu.models.config import ModelConfig

    cfg = ModelConfig.from_hf_config(hf)  # no `model_type` in the file
    assert cfg.model_type == "smallthinker"
    fam = get_family(cfg.model_type)
    assert fam.PAGED_CACHE_KIND == kvwindow.KIND
    assert fam.period(cfg) == 4 and fam.group_layers(cfg) == (6, 18)
    assert fam.group_layers(cfg) == costs_window.group_layers(hf)
    assert (cfg.num_experts, cfg.num_experts_per_tok) == (64, 6)
    assert cfg.moe_intermediate_size == 768 and cfg.hidden_act == "relu"
    assert cfg.sliding_window == 4096 and cfg.rope_theta == 1.5e6
    assert cfg.norm_topk_prob and not cfg.tie_word_embeddings


def test_traffic_is_the_issues(cell):
    t, e = cell.traffic, cell.config["bench"]["engine"]
    assert t["process"] == {"kind": "closed", "clients": 16, "think_s": 0,
                            "block": 16}
    assert t["process"]["clients"] == e["n_slots"] == 16
    assert t["prompt"] == {"dist": "lognormal", "median": 3072, "sigma": 0.7,
                           "min": 512, "max": 8192,
                           "ladder": [512, 1024, 2048, 4096, 8192]}
    assert t["output"] == {"dist": "lognormal", "median": 512, "sigma": 0.5,
                           "min": 128, "max": 1024}
    assert t["trace_seconds"] == 6.0 and "rehearsal" in t
    assert t["generator"] == "arrivals" and t["entry"] == "engine"
    assert t["prompt"]["max"] + t["output"]["max"] == e["max_len"] == 9216
    # the global group at the traffic's worst case, and the scratch page
    assert e["n_pages"] == 16 * (9216 // e["page_size"]) + 1 == 2305
    # prompts on both sides of the window, so that some rows read a bound
    # window in a step and others do not
    lengths = cell.generator().shapes(t)["prompt_lengths"]
    assert min(lengths) < 4096 < max(lengths)
    # the rehearsal's window is shorter than its prompts
    r = cell.config["bench"]["rehearsal"]
    assert r["sliding_window_size"] <= min(
        t["rehearsal"]["prompt"]["values"])
    assert r["bench"]["engine"]["page_size"] < r["sliding_window_size"]


# ---- the file's arithmetic, against the program's shapes -------------------

def test_pools_pages_and_weights_are_the_programs_own(cell, hf):
    """Shapes only: nothing is allocated."""
    import jax

    from bench import weights
    from bigdl_tpu import kvwindow
    from bigdl_tpu.models import get_family
    from bigdl_tpu.models.config import ModelConfig

    cfg = ModelConfig.from_hf_config(hf)
    e = cell.config["bench"]["engine"]
    page = e["page_size"]
    pool = jax.eval_shape(lambda: get_family(cfg.model_type).init_paged_cache(
        cfg, e["n_pages"], page, e["n_slots"], e["max_len"] // page))
    # a token and layer: K and V x 4 heads x 128 x bf16
    assert costs_paged.page_bytes(hf, page) == page * 2048
    g, w = costs_window.kv_page_bytes(hf, page)
    assert (g, w) == kvwindow.page_nbytes(pool) == (6 * page * 2048,
                                                    18 * page * 2048)
    assert round(g / 1e3) == 786 and round(w / 1e6, 2) == 2.36
    # the window group's pool follows from the slots and the window
    assert pool.k.shape[:2] == (6, 2305)
    assert pool.kw.shape[:2] == (18, 16 * (4096 // page + 2) + 1) == (18, 1057)
    nbytes = [sum(a.size * 2 for a in pair)
              for pair in ((pool.k, pool.v), (pool.kw, pool.vw))]
    assert nbytes == [2305 * g, 1057 * w]
    assert [round(n / 1e9, 2) for n in nbytes] == [1.81, 2.49]
    one_pool = 2305 * (g + w)  # every position of every layer
    assert round(one_pool / 1e9, 2) == 7.25
    # weights: attention and the experts as the file counts them are what
    # the tree holds beside the embedding, the routers and the norms
    tree = weights.param_shapes(cfg, "sym_int4")
    H, Hq, Hkv, D = 2560, 28, 4, 128
    attn = 24 * (2 * costs.sym_int4_bytes(Hq * D, H)
                 + 2 * costs.sym_int4_bytes(Hkv * D, H))
    head = costs.sym_int4_bytes(hf["vocab_size"], H)
    experts = costs_window.expert_stack_bytes(hf)
    assert experts == 24 * 64 * costs_moe.expert_bytes(
        costs_window.as_moe(hf))
    assert round(costs_moe.expert_bytes(costs_window.as_moe(hf)) / 1e6,
                 2) == 3.32  # 5.898 M weights
    total = costs.tree_bytes(tree)
    packed = attn + head + experts
    assert 0 < total - packed < 0.002 * total  # routers and norms
    embed = hf["vocab_size"] * H * 2
    assert round((total + embed) / 1e9, 2) == 6.38
    d = cell.config["bench"]["engine_derivation"]
    for figure in ("786 KB", "2.36 MB", "1.81 GB", "2.49 GB", "7.25 GB",
                   "6.38 GB"):
        assert figure in d, figure
    # and the kernels take every packed weight: shapes the guards accept
    from bigdl_tpu.ops.linear import grouped_route

    for stack in tree["period"].values():
        assert grouped_route(stack["w_gate_e"], stack["w_up_e"],
                             stack["w_down_e"]) in (
            None, "backend is cpu, not tpu")
        assert stack["w_up_e"].data.shape == (6, 64, 768, 1280)
        assert stack["w_down_e"].data.shape == (6, 64, 2560, 384)


# ---- costs_window against the program's counts ------------------------------

def test_attention_cost_counts_each_group_by_its_own_pages(hf):
    one = costs_paged.page_bytes(hf, 64)
    small = 28 * 128 * 2 * 2
    c = costs_window.attn_cost(hf, 64, live_global=100, live_window=40,
                               rows_live=4)
    assert c["bytes"] == (6 * 100 + 18 * 40) * one + 24 * 4 * small
    assert c["flops"] == (6 * 100 + 18 * 40) * 64 * 28 * 4 * 128
    # where no row passes the window the groups load alike, and the count
    # is `costs_paged.decode_cost`'s
    same = costs_window.attn_cost(hf, 64, 100, 100, 4)
    assert same == costs_paged.decode_cost(hf, 64, 100, 4)


def test_step_bytes_add_up(hf):
    experts = costs_window.expert_stack_bytes(hf)
    one = costs_moe.expert_bytes(costs_window.as_moe(hf))
    g, w = costs_window.kv_page_bytes(hf, 64)
    got = costs_window.step_bytes(hf, 6 * 10 ** 9, 1200, 1000, 700, 64)
    assert got == 6 * 10 ** 9 - experts + 1200 * one + 1000 * g + 700 * w
    # the stand-in calls costs_moe's arithmetic under the names it reads
    assert costs_window.expert_ffn_cost(hf, 1200, 96 * 24) == \
        costs_moe.expert_ffn_cost(
            dict(hf, moe_intermediate_size=768, num_local_experts=64),
            1200, 96 * 24)
    with pytest.raises(KeyError):  # why the accepted reader is not listed
        costs_moe.expert_shape(hf)


def test_the_programs_spans_carry_what_costs_window_counts():
    """A tiny engine's own `decode_step` spans against the table's
    arithmetic: live pages by group are what `live_page_range` loads."""
    import jax

    from bigdl_tpu.api import TpuModel, optimize_model
    from bigdl_tpu.models import get_family
    from bigdl_tpu.models.config import PRESETS
    from bigdl_tpu.obs.tracing import TraceRecorder
    from bigdl_tpu.ops.pallas.paged_attention import live_page_range
    from bigdl_tpu.serving.engine import InferenceEngine

    cfg = PRESETS["tiny-smallthinker"]
    fam = get_family("smallthinker")
    params = optimize_model(fam.init_params(cfg, jax.random.PRNGKey(0)), cfg,
                            "sym_int4")
    tr = TraceRecorder(capacity=4096)
    eng = InferenceEngine(TpuModel(cfg, params, "sym_int4"), n_slots=2,
                          max_len=128, paged=True, page_size=8, tracer=tr)
    rng = np.random.default_rng(0)
    eng.submit(rng.integers(1, 256, 60).tolist(), max_new_tokens=20)
    eng.submit(rng.integers(1, 256, 9).tolist(), max_new_tokens=20)
    seen = compared = 0
    while True:
        # the step about to be read was dispatched at these positions
        pos = np.asarray(eng.pages.pos)
        act = eng.active.copy()
        more = eng.step()
        steps = [e["args"] for e in tr.events() if e["name"] == "decode_step"]
        for a in steps[seen:]:
            if a["occupancy"] != int(act.sum()) or not act.any():
                continue  # an admission changed the rows under way
            import jax.numpy as jnp

            for window, key in ((2 ** 30, "live_pages_global"),
                                (cfg.sliding_window, "live_pages_window")):
                first, last = live_page_range(
                    jnp.asarray(pos), jnp.zeros_like(jnp.asarray(pos)),
                    jnp.asarray(window, jnp.int32), 8, 16, jnp.asarray(act))
                n = int(jnp.sum(jnp.where(jnp.asarray(act),
                                          last - first + 1, 0)))
                assert a[key] == n, (key, pos, a)
            compared += 1
        seen = len(steps)
        if not more:
            break
    assert compared > 8 and eng.page_leaks() == 0


# ---- the readers -----------------------------------------------------------

def _run(cell, steps, device=None, weight_bytes=0):
    spans = [{"ph": "X", "name": "decode_step", "ts": (10 + i) * 1e6,
              "dur": 3e4, "args": a} for i, a in enumerate(steps)]
    return Run(cell=cell, hf=cells.as_run(cell.config),
               peak=costs.peaks("TPU v5 lite"), t0=0.0, t1=100.0,
               requests=[], spans=spans, device=device,
               weight_bytes=weight_bytes)


def _device(n_steps, attn_s, moe_s, step_s=0.03, begin=0.0, end=100.0):
    """What the readers ask of a reduced trace."""
    kernels = {"paged_decode_attention": attn_s, "moe_qmatmul": moe_s}
    return types.SimpleNamespace(
        begin=begin, end=end, offset=0.0,
        kernel_in_program=lambda kernel, program: (
            (n_steps, kernels[kernel])
            if program == "engine_decode" and kernels.get(kernel)
            else (0, 0.0)),
        program_seconds=lambda program: (
            [step_s] * n_steps if program == "engine_decode" else []))


def _step(rows, live_g=1200, live_w=800, held=900, unfreed=1250, hit=1200):
    return {"occupancy": rows, "slots": 16, "live_pages_global": live_g,
            "grid_pages_global": 2304, "live_pages_window": live_w,
            "grid_pages_window": 2304, "window_pages_held": held,
            "window_pages_unfreed": unfreed, "window_pages_freed": 1,
            "moe_experts": 24 * 64, "moe_experts_hit": hit,
            "moe_assignments": 6 * 24 * rows}


def test_readers_on_recorded_spans_and_kernel_time(cell, hf):
    run = _run(cell, [_step(16), _step(8, live_g=600, live_w=400)],
               _device(n_steps=2, attn_s=0.012, moe_s=0.016, step_s=0.030),
               weight_bytes=5.6 * 10 ** 9)
    bw = run.peak["hbm_bytes_per_s"]
    need = costs_window.attn_cost(hf, 64, 900, 600, 12)
    attn = cell.reader(NEW[0]).read(run)
    assert attn == pytest.approx(100 * need["bytes"] / bw / 0.006)
    assert 40 < attn < 100
    step = costs_window.step_bytes(hf, 5.6 * 10 ** 9, 1200, 900, 600, 64)
    assert cell.reader(NEW[1]).read(run) == pytest.approx(
        100 * step / bw / 0.030)
    assert cell.reader(NEW[2]).read(run) == pytest.approx(100 * 900 / 1250)
    moe = costs_window.expert_ffn_cost(hf, 1200, 6 * 24 * 12)
    assert cell.reader(NEW[3]).read(run) == pytest.approx(
        100 * moe["bytes"] / bw / 0.008)


def test_rooflines_count_the_traced_seconds_steps_only(cell):
    steps = [_step(16), _step(4, live_g=200, live_w=100),
             _step(4, live_g=200, live_w=100)]
    run = _run(cell, steps, _device(2, 0.012, 0.016, begin=10.5, end=12.5))
    a = cell.reader(NEW[0]).read(run)  # the two short steps
    run.device = _device(2, 0.012, 0.016)  # all three
    assert a < cell.reader(NEW[0]).read(run)


def test_pages_held_share_is_100_where_nothing_is_freed(cell):
    run = _run(cell, [_step(16, held=700, unfreed=700)])
    assert cell.reader(NEW[2]).read(run) == 100.0


@pytest.mark.parametrize("name", NEW)
def test_readers_return_nothing_where_there_is_nothing_to_read(cell, name):
    """The parent's program (it cannot run the configuration; were it
    handed other spans): spans without the arguments, a trace without the
    kernels, a configuration without the source's keys. The metric is left
    out of the line, nothing raises."""
    bare = {"occupancy": 8, "slots": 8, "live_pages": 3, "grid_pages": 256,
            "moe_experts": 4, "moe_assignments": 8, "moe_experts_hit": 4}
    assert cell.reader(name).read(_run(cell, [bare])) is None
    assert cell.reader(name).read(
        _run(cell, [bare], _device(0, 0.0, 0.0))) is None
    other = _run(cell, [bare], _device(2, 0.01, 0.01))
    other.hf = {"hidden_size": 64}
    assert cell.reader(name).read(other) is None
    assert getattr(cell.reader(name), "ENTRIES") == ("engine",)


def test_the_reference_refuses_another_familys_tree_by_name(cell, hf):
    """What the parent commit meets on this cell: it builds a dense llama
    from these keys, and the reference says which leaves it lacks before
    any arithmetic."""
    import jax.numpy as jnp

    ref = cell.reference()
    llama_tree = {"layers": {"wqkv": jnp.zeros((2, 4, 4))},
                  "embed": jnp.zeros((8, 4)), "final_norm": jnp.ones((4,)),
                  "lm_head": jnp.zeros((8, 4))}
    with pytest.raises(KeyError, match="router"):
        ref.logits(hf, llama_tree, jnp.zeros((5,), jnp.int32), 2)


def test_rehearsal_runs_the_cell_end_to_end(tmp_path):
    """`bench/run.py --rehearse` on the cell: CPU, tiny sizes, the kernels in
    the interpreter, exit code 3, a check whose prompt is four windows long,
    and a line with the new span reader in place (no device on a CPU, so the
    device-trace readers stay out). Run from a COPY of the benchmark's
    files, as the other cells' rehearsals are."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "bigdl_tpu"), tmp_path / "bigdl_tpu")
    out = subprocess.run(
        [sys.executable, str(tmp_path / "bench" / "run.py"), "--workload",
         CELL, "--seed", str(2 ** 31 + 41), "--seconds", "3", "--trace", "1",
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
    assert "window 32 x3 rope" in out.stdout
    assert "pallas:grouped" in out.stdout and "pallas:paged" in out.stdout
    assert "pallas:flash" in out.stdout
    assert {"engine.decode_occupancy", "step.decode_ms_p50--closed",
            "engine.moe_load_imbalance", "engine.window_pages_held_share",
            "engine.admit.retrace_ms_p50--closed"} <= set(line["metrics"])
    assert 0 < line["metrics"]["engine.window_pages_held_share"][
        "value"] < 100
    assert "kernel.paged_live_page_share--closed" not in line["metrics"]
