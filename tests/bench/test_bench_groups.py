"""The Laguna cell's own pieces (ISSUE 47): the configuration against its
`published` block, the file's arithmetic (a page of each group, the two
pools, weights) against the program's own shapes, `bench/costs_groups.py`
against hand counts and against `costs_paged` / `costs_moe` where the groups
coincide, the three readers on recorded spans and a recorded trace, the
reference's refusal of another family's tree, and `bench/run.py --rehearse`
on the cell."""

import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import cells, costs, costs_groups, costs_moe, costs_paged  # noqa: E402
from bench.records import Run  # noqa: E402

CELL = "laguna-xs.2.mixedlen-closed"
NEW = ("kernel.grouped_attn_roofline", "step.decode_groups_mbu",
       "kernel.routed_experts_roofline")
LAYERS = 16  # four periods of [full, window, window, window]


@pytest.fixture(scope="module")
def cell():
    return cells.resolve(CELL, ROOT)


@pytest.fixture(scope="module")
def hf(cell):
    return cells.as_run(cell.config)


# ---- the configuration -----------------------------------------------------

def test_the_cell_is_laguna_at_published_widths(cell, hf):
    pub = cell.config["published"]
    assert cell.traffic_name == "mixedlen-closed" and cell.chips == 1
    assert cell.entry_name == "engine"
    cut = ["num_hidden_layers", "layer_types", "mlp_layer_types"]
    assert cell.config["reduced"] == cut
    assert set(hf) == set(pub) and hf["model_type"] == "laguna"
    assert {k for k in pub if hf[k] != pub[k]} == set(cut)
    for key, want in (("hidden_size", 2048), ("intermediate_size", 8192),
                      ("num_attention_heads", 48),
                      ("num_key_value_heads", 8), ("head_dim", 128),
                      ("num_experts", 256), ("num_experts_per_tok", 8),
                      ("moe_intermediate_size", 512),
                      ("shared_expert_intermediate_size", 512),
                      ("sliding_window", 512), ("vocab_size", 100352),
                      ("moe_routed_scaling_factor", 2.5), ("gating", True)):
        assert hf[key] == pub[key] == want
    assert hf["rope_parameters"] == pub["rope_parameters"]  # copied whole
    # the cut: whole periods of [full, window, window, window], layer 0 the
    # published dense one; the head counts' list keeps its 40 entries
    assert hf["num_hidden_layers"] == LAYERS and pub["num_hidden_layers"] == 40
    for key in cut[1:]:
        assert hf[key] == pub[key][:LAYERS]
    assert hf["layer_types"] == (["full_attention"]
                                 + ["sliding_attention"] * 3) * (LAYERS // 4)
    assert hf["mlp_layer_types"] == ["dense"] + ["sparse"] * (LAYERS - 1)
    assert hf["num_attention_heads_per_layer"] == [48, 64, 64, 64] * 10
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= names
    assert {"kernel.moe_ffn_ms_per_step", "engine.moe_load_imbalance",
            "engine.window_pages_held_share", "engine.decode_occupancy",
            "step.decode_ms_p50--closed",
            "step.prefill_ms_p50--closed"} <= names
    # readers keyed to SmallThinker's key names, or that multiply one head
    # count or one count of live pages by every layer, are not this cell's
    assert not {"kernel.window_attn_roofline", "step.decode_window_mbu",
                "kernel.primary_experts_roofline", "kernel.moe_ffn_roofline",
                "kernel.paged_attn_roofline--closed",
                "step.decode_mbu--closed"} & names
    assert [m["name"] for m in cell.end_to_end] == ["output_tokens_per_s",
                                                    "setup_s"]
    assert {"gate_form", "norm_topk_prob", "no_selection_bias", "no_qk_norm",
            "rope_convention", "published", "weights"} <= set(
        cell.config["assumed"])
    # the traffic file is SmallThinker's cell's, as it stands
    other = cells.resolve("smallthinker-21ba3b.mixedlen-closed", ROOT)
    assert other.traffic == cell.traffic
    e = cell.config["bench"]["engine"]
    assert e == other.config["bench"]["engine"] == {
        "n_slots": 16, "max_len": 9216, "page_size": 64, "n_pages": 2305}
    # every prompt is at least the window: all rows read a BOUND window
    lengths = cell.generator().shapes(cell.traffic)["prompt_lengths"]
    assert min(lengths) >= hf["sliding_window"]
    r = cell.config["bench"]["rehearsal"]
    assert r["sliding_window"] <= min(
        cell.traffic["rehearsal"]["prompt"]["values"])
    assert r["bench"]["engine"]["page_size"] < r["sliding_window"]


def test_the_file_runs_as_the_program_reads_it(hf):
    from bigdl_tpu import kvwindow
    from bigdl_tpu.models import get_family
    from bigdl_tpu.models.config import ModelConfig

    cfg = ModelConfig.from_hf_config(hf)
    assert cfg.model_type == "laguna"
    fam = get_family(cfg.model_type)
    assert fam.PAGED_CACHE_KIND == kvwindow.KIND
    assert fam.period(cfg) == 4
    assert fam.group_layers(cfg) == costs_groups.group_layers(hf) == (4, 12)
    assert (fam.layouts(cfg)[1][0], fam.layouts(cfg)[1][1]) == \
        costs_groups.group_heads(hf) == (48, 64)
    assert costs_groups.sparse_layers(hf) == LAYERS - 1 == \
        cfg.num_hidden_layers - cfg.first_k_dense_replace
    assert (cfg.num_experts, cfg.num_experts_per_tok) == (256, 8)
    assert cfg.moe_intermediate_size == 512 and cfg.hidden_act == "silu"
    assert cfg.shared_expert_intermediate_size == 512
    assert cfg.sliding_window == 512 and cfg.attn_gate == "per_head"
    assert (cfg.rope_theta, cfg.rope_local_theta) == (500000.0, 10000.0)
    assert (cfg.rotary_dim, cfg.rope_local_partial_rotary_factor) == (64, 1)
    rs = cfg.rope_scaling_dict
    assert rs["rope_type"] == "yarn" and rs["factor"] == 64
    assert rs["attention_factor"] == 1.4158883083359672
    assert (cfg.scoring_func, cfg.norm_topk_prob,
            cfg.routed_scaling_factor) == ("sigmoid", True, 2.5)
    assert not cfg.tie_word_embeddings


def test_the_two_ropes_are_the_published_ones(cell, hf):
    """The reference's own YaRN (HF's `_compute_yarn_parameters`, written
    out) against the program's table, and the plain rope beside it."""
    import numpy as np

    from bigdl_tpu.models.config import ModelConfig
    from bigdl_tpu.ops.rope import default_inv_freq, make_inv_freq_scaled

    cfg = ModelConfig.from_hf_config(hf)
    ref = cell.reference()
    rp = hf["rope_parameters"]
    inv, att, R = ref.rope_frequencies(rp["full_attention"], 128)
    got, got_att = make_inv_freq_scaled(
        cfg.rotary_dim, cfg.rope_theta, cfg.rope_scaling_dict)
    assert R == 64 and att == got_att == 1.4158883083359672
    np.testing.assert_allclose(np.asarray(got), inv, rtol=1e-6)
    # the ramp: pairs under 5 turn as published, from 16 on 64 times slower
    plain = 1.0 / 500000 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:5], plain[:5], rtol=1e-6)
    np.testing.assert_allclose(inv[16:], plain[16:] / 64, rtol=1e-6)
    inv_w, att_w, R_w = ref.rope_frequencies(rp["sliding_attention"], 128)
    assert (att_w, R_w) == (1.0, 128)
    np.testing.assert_allclose(np.asarray(default_inv_freq(128, 10000.0)),
                               inv_w, rtol=1e-6)


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
    # a token and layer: K and V x 8 heads x 128 x bf16, in BOTH groups
    assert costs_paged.page_bytes(hf, page) == page * 4096
    g, w = costs_groups.kv_page_bytes(hf, page)
    assert (g, w) == kvwindow.page_nbytes(pool) == (4 * page * 4096,
                                                    12 * page * 4096)
    assert round(g / 1e6, 2) == 1.05 and round(w / 1e6, 2) == 3.15
    # the window group's pool follows from the slots and the window
    assert pool.k.shape == (4, 2305, 64, 8, 128)
    assert pool.kw.shape == (12, 16 * (512 // page + 2) + 1, 64, 8, 128)
    assert pool.kw.shape[1] == kvwindow.window_pool_pages(16, 512, 64) == 161
    nbytes = [sum(a.size * 2 for a in pair)
              for pair in ((pool.k, pool.v), (pool.kw, pool.vw))]
    assert nbytes == [2305 * g, 161 * w]
    assert [round(n / 1e9, 2) for n in nbytes] == [2.42, 0.51]
    one_pool = 2305 * (g + w)  # every position of every layer
    assert round(one_pool / 1e9, 2) == 9.67
    # weights: what the file counts is what the tree holds
    tree = weights.param_shapes(cfg, "sym_int4")
    H, Hkv, D = 2048, 8, 128

    def attention(Hq):
        return (2 * costs.sym_int4_bytes(Hq * D, H)
                + 2 * costs.sym_int4_bytes(Hkv * D, H))

    assert round(attention(48) / 1e6, 1) == 16.5
    assert round(attention(64) / 1e6, 1) == 21.2
    one = costs_groups.expert_bytes(hf)
    assert one == 3 * 512 * 2048 * 9 // 16 and round(one / 1e6, 2) == 1.77
    experts = costs_groups.expert_stack_bytes(hf)
    assert experts == 15 * 256 * one
    shared = 15 * one
    dense0 = 3 * costs.sym_int4_bytes(8192, 2048)
    assert round(dense0 / 1e6, 1) == 28.3
    head = costs.sym_int4_bytes(hf["vocab_size"], H)
    packed = (4 * attention(48) + 12 * attention(64) + experts + shared
              + dense0 + head)
    total = costs.tree_bytes(tree)
    # the rest: the bf16 routers (1.05 MB a layer), gates and norms
    assert 0 < total - packed < 0.003 * total
    assert round((total - packed) / 1e6) == 20
    embed = hf["vocab_size"] * H * 2
    assert round((total + embed) / 1e9, 2) == 7.72
    d = cell.config["bench"]["engine_derivation"]
    for figure in ("1.05 MB", "3.15 MB", "2.42 GB", "0.51 GB", "9.67 GB",
                   "7.72 GB", "161"):
        assert figure in d, figure
    # and the kernels take every packed weight: shapes the guards accept
    from bigdl_tpu.ops.linear import grouped_route

    stacks = list(tree["period"].values()) + [tree["first"][j]
                                              for j in "123"]
    for stack in stacks:
        assert grouped_route(stack["w_gate_e"], stack["w_up_e"],
                             stack["w_down_e"]) in (
            None, "backend is cpu, not tpu")
        assert stack["w_up_e"].data.shape[-3:] == (256, 512, 1024)
        assert stack["w_down_e"].data.shape[-3:] == (256, 2048, 256)
    assert tree["period"]["0"]["wq"].data.shape == (3, 48 * 128, 1024)
    assert tree["period"]["2"]["wo"].data.shape == (3, 2048, 64 * 64)
    assert "router" not in tree["first"]["0"]


# ---- costs_groups against hand counts ---------------------------------------

def test_attention_cost_counts_each_group_by_its_own_pages_and_heads(hf):
    one = costs_paged.page_bytes(hf, 64)
    c = costs_groups.attn_cost(hf, 64, live_global=100, live_window=40,
                               rows_live=4)
    small = 128 * 2 * 2  # q in, context out, a head and live slot
    assert c["bytes"] == ((4 * 100 + 12 * 40) * one
                          + 4 * (4 * 48 + 12 * 64) * small)
    assert c["flops"] == (4 * 100 * 48 + 12 * 40 * 64) * 64 * 4 * 128
    # one head count for every layer and the groups loading alike: the
    # count is `costs_paged.decode_cost`'s
    flat = dict(hf, num_attention_heads_per_layer=[48] * LAYERS)
    assert costs_groups.attn_cost(flat, 64, 100, 100, 4) == \
        costs_paged.decode_cost(flat, 64, 100, 4)
    # a per-layer list longer than the depth: the first entries count
    assert costs_groups.group_layers(dict(hf, num_hidden_layers=8)) == (2, 6)
    with pytest.raises(AssertionError):  # heads that differ inside a kind
        costs_groups.group_heads(dict(
            hf, num_attention_heads_per_layer=[48, 64, 64, 32] * 10))


def test_step_bytes_add_up(hf):
    experts = costs_groups.expert_stack_bytes(hf)
    one = costs_groups.expert_bytes(hf)
    g, w = costs_groups.kv_page_bytes(hf, 64)
    got = costs_groups.step_bytes(hf, 7 * 10 ** 9, 1500, 1000, 140, 64)
    assert got == 7 * 10 ** 9 - experts + 1500 * one + 1000 * g + 140 * w
    # the experts' arithmetic is costs_moe's, over the SPARSE layers
    assert costs_groups.expert_ffn_cost(hf, 1500, 128 * 15) == \
        costs_moe.expert_ffn_cost(
            dict(hf, num_local_experts=256, num_hidden_layers=15),
            1500, 128 * 15)
    with pytest.raises(KeyError):  # why the accepted reader is not listed
        costs_moe.expert_stack_bytes(hf)
    assert costs_groups.knows(hf) and not costs_groups.knows(
        {"hidden_size": 64})


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


def _step(rows, live_g=1300, live_w=140, hit=1500):
    return {"occupancy": rows, "slots": 16, "live_pages_global": live_g,
            "grid_pages_global": 2304, "live_pages_window": live_w,
            "grid_pages_window": 2304, "window_pages_held": 150,
            "window_pages_unfreed": 1300, "window_pages_freed": 1,
            "moe_experts": 15 * 256, "moe_experts_hit": hit,
            "moe_assignments": 8 * 15 * rows}


def test_readers_on_recorded_spans_and_kernel_time(cell, hf):
    run = _run(cell, [_step(16), _step(8, live_g=700, live_w=70, hit=900)],
               _device(n_steps=2, attn_s=0.010, moe_s=0.016, step_s=0.020),
               weight_bytes=7.3 * 10 ** 9)
    bw = run.peak["hbm_bytes_per_s"]
    need = costs_groups.attn_cost(hf, 64, 1000, 105, 12)
    attn = cell.reader(NEW[0]).read(run)
    assert attn == pytest.approx(100 * need["bytes"] / bw / 0.005)
    assert 20 < attn < 100
    step = costs_groups.step_bytes(hf, 7.3 * 10 ** 9, 1200, 1000, 105, 64)
    mbu = cell.reader(NEW[1]).read(run)
    assert mbu == pytest.approx(100 * step / bw / 0.020)
    assert 10 < mbu < 100
    moe = costs_groups.expert_ffn_cost(hf, 1200, 8 * 15 * 12)
    got = cell.reader(NEW[2]).read(run)
    assert got == pytest.approx(100 * moe["bytes"] / bw / 0.008)
    assert 10 < got < 100
    # the generic readers the cell lists read the same run
    assert cell.reader("engine.window_pages_held_share").read(run) == \
        pytest.approx(100 * 150 / 1300)
    assert cell.reader("kernel.moe_ffn_ms_per_step").read(run) == \
        pytest.approx(8.0)


def test_rooflines_count_the_traced_seconds_steps_only(cell):
    steps = [_step(16), _step(4, live_g=200, live_w=30),
             _step(4, live_g=200, live_w=30)]
    run = _run(cell, steps, _device(2, 0.012, 0.016, begin=10.5, end=12.5))
    a = cell.reader(NEW[0]).read(run)  # the two short steps
    run.device = _device(2, 0.012, 0.016)  # all three
    assert a < cell.reader(NEW[0]).read(run)


@pytest.mark.parametrize("name", NEW)
def test_readers_return_nothing_where_there_is_nothing_to_read(cell, name):
    """The parent's program (it cannot run the configuration; were it
    handed other spans): spans without the arguments, a trace without the
    kernels, a configuration without the source's keys. The metric is left
    out of the line, nothing raises."""
    bare = {"occupancy": 8, "slots": 8, "live_pages": 3, "grid_pages": 256}
    assert cell.reader(name).read(_run(cell, [bare])) is None
    assert cell.reader(name).read(
        _run(cell, [bare], _device(0, 0.0, 0.0))) is None
    assert cell.reader(name).read(
        _run(cell, [bare], _device(2, 0.01, 0.01))) is None
    other = _run(cell, [_step(8)], _device(2, 0.01, 0.01))
    assert cell.reader(name).read(other) is not None
    other.hf = {"hidden_size": 64}
    assert cell.reader(name).read(other) is None
    assert getattr(cell.reader(name), "ENTRIES") == ("engine",)


@pytest.mark.parametrize("name", NEW)
def test_the_new_readers_stay_silent_in_the_other_cells(name):
    """SmallThinker's cell has the spans and the kernels and not the keys."""
    other = cells.resolve("smallthinker-21ba3b.mixedlen-closed", ROOT)
    run = _run(other, [_step(8)], _device(2, 0.01, 0.01))
    assert cells.resolve(CELL, ROOT).reader(name).read(run) is None


def test_the_reference_refuses_another_familys_tree_by_name(cell, hf):
    """What a program without the family would meet on this cell (the
    parent commit does not get that far: it knows no `laguna` and ends at
    `get_family`): the reference says which part it lacks before any
    arithmetic, and imports nothing of the program."""
    import jax.numpy as jnp

    ref = cell.reference()
    llama_tree = {"layers": {"wqkv": jnp.zeros((2, 4, 4))},
                  "embed": jnp.zeros((8, 4)), "final_norm": jnp.ones((4,)),
                  "lm_head": jnp.zeros((8, 4))}
    with pytest.raises(KeyError, match="first"):
        ref.logits(hf, llama_tree, jnp.zeros((5,), jnp.int32), 2)
    with open(ref.__file__, encoding="utf-8") as f:
        top = [line for line in f.read().splitlines()
               if line.startswith(("import ", "from "))]
    assert top and not [line for line in top if "bigdl_tpu" in line]
    assert ref.choice_shape(hf) == (LAYERS - 1, 8)


def test_rehearsal_runs_the_cell_end_to_end(tmp_path):
    """`bench/run.py --rehearse` on the cell: CPU, tiny sizes, the kernels in
    the interpreter, exit code 3, a check whose prompt is several windows
    long, and a line with the span readers in place (no device on a CPU, so
    the device-trace readers stay out). Run from a COPY of the benchmark's
    files, as the other cells' rehearsals are."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "bigdl_tpu"), tmp_path / "bigdl_tpu")
    out = subprocess.run(
        [sys.executable, str(tmp_path / "bench" / "run.py"), "--workload",
         CELL, "--seed", str(2 ** 31 + 47), "--seconds", "3", "--trace", "1",
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
    assert "window 32 x3 rope 10000, 8 heads on 2, gated" in out.stdout
    assert "full x1 yarn over 16 of 32, 6 heads on 2, gated" in out.stdout
    assert "pallas:grouped" in out.stdout and "pallas:paged" in out.stdout
    assert "pallas:flash" in out.stdout
    assert {"engine.decode_occupancy", "step.decode_ms_p50--closed",
            "engine.moe_load_imbalance", "engine.window_pages_held_share",
            "engine.admit.retrace_ms_p50--closed"} <= set(line["metrics"])
    assert 0 < line["metrics"]["engine.window_pages_held_share"][
        "value"] < 100
    assert not set(NEW) & set(line["metrics"])  # device-trace readers
