"""The Solar-Open2-250B cell's own pieces (ISSUE 65): the configuration
against its `published` block and the catalog's row, the file's arithmetic
(state a slot, pool, pages, weights, the share) against the program's own
shapes, `bench/costs_delta.py` against hand counts, the four readers on
recorded spans and a recorded trace, the reference's rounding hook and its
share, and `bench/run.py --rehearse` on the cell. Nothing here counts the
benchmark's cells or says which is last."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import cells, costs, costs_delta, costs_moe  # noqa: E402
from bench.records import Run  # noqa: E402
from bench.reduce.xplane import Event, Loaded, Reduced  # noqa: E402

CELL = "solar-open2-250b.longctx-closed"
NEW = ("kernel.kda_decode_ms_per_step", "kernel.kda_decode_roofline",
       "kernel.kda_prefill_ms_p50", "step.decode_delta_mbu")
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
TREE = 5797998080  # bytes of the served tree without the embedding
ROW = 40402944  # bytes of one slot's state row


@pytest.fixture(scope="module")
def cell():
    return cells.resolve(CELL, ROOT)


@pytest.fixture(scope="module")
def hf(cell):
    return cells.as_run(cell.config)


# ---- the configuration -----------------------------------------------------

def test_the_cell_is_chip_0_of_8_of_the_first_stage(cell, hf):
    pub = cell.config["published"]
    assert cell.traffic_name == "longctx-closed" and cell.chips == 1
    assert cell.entry_name == "engine"
    assert cell.config["reduced"] == ["num_hidden_layers", "gqa_layers",
                                      "n_routed_experts"]
    assert {k for k in pub if hf[k] != pub[k]} == set(cell.config["reduced"])
    assert set(hf) == set(pub)
    assert (hf["num_hidden_layers"], pub["num_hidden_layers"]) == (12, 48)
    assert hf["gqa_layers"] == pub["gqa_layers"][:3] == [0, 4, 8]
    assert (hf["n_routed_experts"], pub["n_routed_experts"]) == (40, 320)
    # the one key the source lacks: the same on both sides (a router of 320
    # from expert 0 on), a share only beside `n_routed_experts` 40
    assert hf["expert_parallel_share"] == pub["expert_parallel_share"] == {
        "router_experts": 320, "first_expert": 0}
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = [json.loads(line) for line in f if '"Solar-Open2-250B"' in line]
    if row:  # where the catalog is at hand: the row's `config`, key for key
        assert {k: v for k, v in pub.items()
                if k != "expert_parallel_share"} == row[0]["config"]
        assert cell.config["source"] == row[0]["source_url"]
    # every width as published
    assert (hf["hidden_size"], hf["num_attention_heads"],
            hf["num_key_value_heads"], hf["head_dim"],
            hf["moe_intermediate_size"], hf["num_experts_per_tok"],
            hf["n_shared_experts"], hf["vocab_size"],
            hf["linear_attn_config"]) == (
        4096, 64, 8, 128, 1280, 8, 1, 196608,
        {"short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
         "num_kv_heads": None})
    assert costs_delta.knows(hf) and costs_delta.dims(hf) == (64, 128, 4)
    assert (costs_delta.n_layers(hf, "kda"),
            costs_delta.n_layers(hf, "attention")) == (9, 3)  # three periods
    assert (costs_delta.experts_held(hf), costs_delta.router_width(hf)) \
        == (40, 320)
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) | set(JOINED) <= names
    # readers that count keys and values, or a dense MLP, in EVERY layer,
    # or another state's bytes, are not this cell's
    assert not {"kernel.paged_attn_roofline--closed", "step.decode_mbu--closed",
                "step.decode_ssm_mbu", "step.decode_conv_mbu",
                "kernel.lightning_decode_roofline",
                "kernel.decode.qmatmul_roofline--closed",
                "kernel.paged_attn_ms_per_step--closed"} & names
    assert [m["name"] for m in cell.end_to_end] == ["output_tokens_per_s",
                                                    "setup_s"]
    assert {"expert_parallel_share", "low_rank", "kda_use_full_proj",
            "kda_allow_neg_eigval", "kda_mixer", "gqa_mixer", "router",
            "shared_expert", "intermediate_size", "state_dtype", "packed",
            "weights"} <= set(cell.config["assumed"])
    dep = cell.config["bench"]["deployment"]
    assert all(s in dep for s in ("32 chips", "8 chips share each layer",
                                  "chip 0", "8 times their share"))


def test_the_entries_are_appended():
    """The cell's entries in `BENCHMARK.json`: its four metrics its own,
    its name on the lists whose readers count right for it, each entry with
    exactly the keys it may have. (`test_bench_datadriven.py` and
    `test_bench_generators.py` hold its configuration's cut and its traffic
    as they hold every registered cell's.)"""
    bench = cells.load_benchmark(ROOT)
    rows = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    for name in NEW:
        m = rows[name]
        assert (m["moves"], m["workloads"], m["source"]) == (
            "output_tokens_per_s", [CELL], "device_trace")
        assert m["layer"] == ("model step" if name.startswith("step.")
                              else "kernels")
        assert m["unit"] == ("%" if name.endswith(("roofline", "mbu"))
                             else "ms")
        assert m["better"] == ("higher" if m["unit"] == "%" else "lower")
    for name in JOINED:
        assert rows[name]["workloads"][-1] == CELL or \
            CELL in rows[name]["workloads"]
        assert rows[name]["moves"] == "output_tokens_per_s"
    assert CELL in rows["output_tokens_per_s"]["workloads"]
    (w,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (w["config"], w["traffic"], w["chips"]) == (
        "solar-open2-250b-int4", "longctx-closed", 1)
    assert len(w["why"]) <= 200 and "8x share" in w["why"]
    (c,) = [c for c in bench["configs"]
            if c["name"] == "solar-open2-250b-int4"]
    assert c["reduced"] == ["num_hidden_layers", "gqa_layers",
                            "n_routed_experts"]
    assert all(w["chips"] == 1 for w in bench["workloads"])


def test_the_file_runs_as_the_program_reads_it(cell, hf):
    from bigdl_tpu import kvhybrid
    from bigdl_tpu.models import get_family
    from bigdl_tpu.models.config import ModelConfig

    cfg = ModelConfig.from_hf_config(hf)
    fam = get_family(cfg.model_type)
    assert fam.PAGED_CACHE_KIND == kvhybrid.KIND
    assert fam.layer_runs(cfg) == [
        ("attention", 0, 1), ("kda", 0, 3), ("attention", 1, 1),
        ("kda", 3, 3), ("attention", 2, 1), ("kda", 6, 3)]
    assert cfg.expert_share == (0, 40, 320)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim_,
            cfg.kda_heads, cfg.kda_head_dim, cfg.conv_l_cache) == (
        64, 8, 128, 64, 128, 4)
    assert (cfg.scoring_func, cfg.topk_method, cfg.n_group,
            cfg.norm_topk_prob, cfg.routed_scaling_factor,
            cfg.n_shared_experts, cfg.position_embedding_type) == (
        "sigmoid", "noaux_tc", 1, True, 1, 1, "nope")
    assert fam.LOW_RANK == costs_delta.LOW_RANK == 128
    assert cell.reference().ROUTER_EPS == 1e-20
    assert cell.reference().share(hf) == (0, 40, 320)
    uncut = ModelConfig.from_hf_config(cell.config["published"])
    assert uncut.expert_share is None and uncut.num_experts == 320


def test_traffic_is_glms_file_as_it_stands(cell):
    t, e = cell.traffic, cell.config["bench"]["engine"]
    assert t["process"] == {"kind": "closed", "clients": 32, "think_s": 0,
                            "block": 32}
    assert t["process"]["clients"] == e["n_slots"] == 32
    assert t["prompt"]["ladder"] == [1024, 1536, 2048, 3072, 4096]
    assert t["prompt"]["max"] + t["output"]["max"] == e["max_len"] == 5120
    # the traffic's worst case in every slot, and the scratch page
    assert e["page_size"] == 64
    assert e["n_pages"] == 32 * (5120 // e["page_size"]) + 1 == 2561
    glm = cells.resolve("glm-4.7-flash.longctx-closed", ROOT)
    assert glm.traffic == t and glm.config["bench"]["engine"] == e


# ---- the file's arithmetic, against the program's shapes -------------------

def test_state_pool_pages_and_weights_are_the_programs_own(cell, hf):
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
    assert pool.ssm.shape == (9, 32, 64 * 128, 128)
    assert pool.conv.shape == (9, 32, 3 * 3 * 8192) and pool.conv_rows == 1
    assert pool.k.shape == pool.v.shape == (3, 2561, 64, 8, 128)
    assert pool.counts == ("state_chunks", kvhybrid.KDA_CHUNK)
    # a state row: 9 layers x (64 heads x 128 x 128 + 3 inputs x 24576), f32
    assert costs_delta.kda_row_bytes(hf) == 9 * 64 * 128 * 128 * 4
    assert costs_delta.state_row_bytes(hf) == kvhybrid.row_nbytes(pool) \
        == 9 * (4194304 + 294912) == ROW
    assert 32 * ROW == 1292894208  # 1.29 GB of state rows
    assert costs_delta.kv_token_bytes(hf) == 3 * 2 * 8 * 128 * 2 == 12288
    assert kvpaged.kv_page_nbytes(pool.kv) == 64 * 12288
    pages = 2 * pool.k.size * 2
    assert pages == 2561 * 64 * 12288 and 2.01e9 < pages < 2.02e9
    # the parameter tree: every packed array, the small ones, the embedding
    shapes = weights.param_shapes(cfg, "sym_int4")
    tree = costs.tree_bytes(shapes)
    assert tree == (costs_delta.linear_bytes(hf)
                    + costs_delta.expert_stack_bytes(hf)
                    + costs_delta.small_bytes(hf)) == TREE
    assert costs_moe.expert_bytes(hf) == 8847360  # one expert, 8.85 MB
    assert costs_delta.expert_stack_bytes(hf) == 12 * 40 * 8847360
    assert shapes["embed"].shape == shapes["lm_head"].data.shape[:1] + (
        4096,) == (196608, 4096)
    run = shapes["runs"]["01"]  # three KDA layers
    assert run["router"].shape == (3, 320, 4096)  # the router's whole width
    assert run["e_bias"].shape == (3, 320)
    assert run["w_up_e"].data.shape[:3] == (3, 40, 1280)  # the 40 held
    assert run["conv_w"].shape == (3, 4, 24576)
    assert (run["f_a"].shape, run["f_b"].shape, run["w_beta"].shape) == (
        (3, 128, 4096), (3, 8192, 128), (3, 64, 4096))
    assert not any(hasattr(run[n], "qtype") for n in (
        "router", "e_bias", "conv_w", "A_log", "dt_bias", "g_bias", "f_a",
        "f_b", "g_a", "g_b", "w_beta", "o_norm"))
    assert all(run[n].qtype == "sym_int4" for n in (
        "wq", "wk", "wv", "wo", "w_gate_s", "w_up_s", "w_down_s", "w_up_e"))
    assert shapes["runs"]["00"]["wg"].qtype == "sym_int4"
    # weights, pages and state rows: what the file's derivation adds up
    embed = 196608 * 4096 * 2
    assert 11.2e9 < TREE + embed + 0.66e9 + pages + 32 * ROW < 11.5e9


def test_costs_against_hand_counts(hf):
    # 32 live rows: the state twice and the small operands, nine layers
    c = costs_delta.kda_decode_cost(hf, 32)
    small = (4 * 8192 + 64 + 8192) * 4
    assert c["bytes"] == 32 * 9 * (2 * 4194304 + small)
    assert c["flops"] == 32 * 9 * 7 * 64 * 128 * 128
    peak = costs.peaks("TPU v5 lite")
    least, bound = costs.roofline_seconds(c, peak)[:2]
    assert 2.9e-3 < least < 3.1e-3  # 2.4 GB at 819 GB/s: the bytes bound it
    assert c["bytes"] / peak["hbm_bytes_per_s"] > 10 * c["flops"] / peak[
        "bf16_flops_per_s"]
    # 300 live pages over 32 rows: three layers of K and V at 8 x 128 bf16
    a = costs_delta.attn_decode_cost(hf, 64, 300, 32)
    assert a["bytes"] == 3 * (300 * 2 * 64 * 8 * 128 * 2
                              + 32 * 64 * 128 * 2 * 2)
    from bench import costs_paged

    assert costs_paged.decode_cost(hf, 64, 300, 32)["bytes"] == 4 * a["bytes"]
    lin = costs_delta.decode_linears(hf)
    assert len(lin) == 9 * 4 + 3 * 5 + 12 * 3 + 1
    assert lin.count((4096, 8192)) == 9 * 3 + 3 * 2 and lin[-1] == (
        4096, 196608)
    # a step: everything but the held experts nobody chose
    need = costs_delta.step_bytes(hf, TREE, 260, 2 * 32 * ROW, 1280, 64)
    assert need == (TREE - (12 * 40 - 260) * 8847360 + 2 * 32 * ROW
                    + 1280 * 64 * 12288)
    assert 7.0e9 < need < 7.5e9  # ISSUE 65's 7.2 GB a step


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
    kernels = {("kda_decode", "engine_decode"): (n_steps, kernel_s)}
    return types.SimpleNamespace(
        begin=begin, end=end, offset=0.0,
        kernel_in_program=lambda kernel, program: kernels.get(
            (kernel, program), (0, 0.0)),
        program_seconds=lambda program: (
            [step_s] * n_steps if program == "engine_decode" else []))


def _step(rows, pages=1300, hit=260):
    return {"occupancy": rows, "slots": 32, "state_rows_live": rows,
            "state_bytes_moved": 2 * rows * ROW, "live_pages": pages,
            "grid_pages": 2560, "moe_experts": 12 * 40,
            "moe_experts_hit": hit, "moe_assignments": 12 * rows,
            "moe_max_expert_load": 4}


def test_readers_on_recorded_spans_and_kernel_time(cell, hf):
    run = _run(cell, [_step(32, 1400), _step(16, 600)],
               _device(n_steps=2, kernel_s=0.008, step_s=0.018),
               weight_bytes=TREE)
    assert cell.reader(NEW[0]).read(run) == pytest.approx(4.0)
    # 24 live rows a step on average, over 4 ms
    need = costs_delta.kda_decode_cost(hf, 24)
    share = cell.reader(NEW[1]).read(run)
    assert share == pytest.approx(
        100 * need["bytes"] / run.peak["hbm_bytes_per_s"] / 0.004)
    assert 40 < share < 100
    want = costs_delta.step_bytes(hf, TREE, 260, 2 * 24 * ROW, 1000, 64)
    mbu = cell.reader(NEW[3]).read(run)
    assert mbu == pytest.approx(
        100 * want / run.peak["hbm_bytes_per_s"] / 0.018)
    assert 30 < mbu < 100
    # the experts' readers count the HELD experts as the spans give them
    rf = costs.roofline_seconds(
        costs_moe.expert_ffn_cost(hf, 260, 12 * 24), run.peak)[0]
    assert 2.7e-3 < rf < 3.0e-3  # 260 experts of 8.85 MB at 819 GB/s
    assert cell.reader("engine.moe_load_imbalance").read(run) \
        == pytest.approx((4 * 480 / (12 * 32) + 4 * 480 / (12 * 16)) / 2)


def test_the_scope_reader_sums_what_stands_under_kda_prefill(cell):
    """`kernel.kda_prefill_ms_p50` on a hand-made trace: two executions of
    `engine_paged_prefill` whole in the window and one cut by its end. What
    counts is every operation whose name stack passes through `kda_prefill`
    in the whole ones, the loops' bodies too: not the projections' kernel,
    not the convolutions, not a decode step's `kda_decode`."""
    dev_name = "/device:TPU:0"
    mods = [Event("jit_engine_paged_prefill(7)", t, 0.5)
            for t in (1.0, 2.0, 9.8)]
    mods.append(Event("jit_engine_decode(8)", 3.0, 0.1))
    per = (("fusion.1", 0.1, 0.010), ("fusion.2", 0.15, 0.004),
           ("qmatmul.3", 0.2, 0.03), ("fusion.4", 0.3, 0.02))
    ops = [Event(name, 1.0 + at, dur) for name, at, dur in per]
    ops += [Event(name, 2.0 + at, 2 * dur) for name, at, dur in per]
    ops.append(Event("fusion.1", 9.9, 0.01))
    ops.append(Event("kda_decode.5", 3.01, 0.05))
    stack = "jit(engine_paged_prefill)/while/body/attn/"
    names = {"fusion.1": stack + "kda_prefill/while/body/dot_general",
             "fusion.2": stack + "kda_prefill/triangular_solve",
             "qmatmul.3": stack + "attn.proj/jit(_qmm)/pallas_call",
             "fusion.4": stack + "short_conv/mul"}
    meta = {dev_name: {
        **{(7, k): (v, "") for k, v in names.items()},
        (8, "kda_decode.5"): (
            "jit(engine_decode)/while/body/attn/kda_decode/pallas_call", "")}}
    dev = Reduced(Loaded({dev_name: ops}, {dev_name: mods}, sync=0.0,
                         lines={}), t_sync=0.0, begin=0.0, end=10.0)
    run = _run(cell, [], dev)
    run.extra["scope_metadata"] = meta
    # 14 ms in the first whole execution, 28 in the second: the median
    assert cell.reader(NEW[2]).read(run) == pytest.approx(21.0)
    other = _run(cell, [], dev)  # the parent's trace: no such scope
    other.extra["scope_metadata"] = {dev_name: {
        (7, "fusion.1"): (stack + "mul", "")}}
    assert cell.reader(NEW[2]).read(other) is None


def test_roofline_counts_the_traced_seconds_steps_only(cell):
    steps = [_step(32), _step(8), _step(8)]
    run = _run(cell, steps, _device(2, 0.008, begin=10.5, end=12.5))
    a = cell.reader(NEW[1]).read(run)  # the two steps at 8 rows
    run.device = _device(2, 0.008)  # all three
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
    program's count of the bytes is the yardstick's, and its expert load is
    of the experts HELD here (rehearsal: 4 of a router's 16, from id 4)."""
    import jax

    from bench.run import merge
    from bigdl_tpu.api import TpuModel, optimize_model
    from bigdl_tpu.models import get_family
    from bigdl_tpu.models.config import ModelConfig
    from bigdl_tpu.obs.tracing import TraceRecorder
    from bigdl_tpu.serving.engine import InferenceEngine

    hf = cells.as_run(merge(cell.config, cell.config["bench"]["rehearsal"]))
    cfg = ModelConfig.from_hf_config(hf)
    assert cfg.expert_share == (4, 4, 16)
    fam = get_family(cfg.model_type)
    model = TpuModel(cfg, optimize_model(
        fam.init_params(cfg, jax.random.PRNGKey(0)), cfg, "sym_int4"),
        "sym_int4")
    tr = TraceRecorder(capacity=1024)
    eng = InferenceEngine(model, n_slots=2, max_len=128, paged=True,
                          page_size=16, tracer=tr)
    assert eng.state_row_bytes == costs_delta.state_row_bytes(hf)
    eng.submit(list(range(1, 80)), max_new_tokens=3)
    eng.run_until_idle()
    run = Run(cell=cell, hf=hf, peak=costs.peaks("TPU v5 lite"), t0=0.0,
              t1=float("inf"), requests=[], spans=tr.events())
    steps = costs_delta.traced_steps(run)
    L, held = hf["num_hidden_layers"], costs_delta.experts_held(hf)
    assert steps and all(
        a["state_bytes_moved"] == 2 * a["state_rows_live"]
        * costs_delta.state_row_bytes(hf) and "live_pages" in a
        and a["moe_experts"] == L * held
        and a["moe_experts_hit"] <= a["moe_assignments"]
        <= L * hf["num_experts_per_tok"] * a["state_rows_live"]
        for a in steps)
    # two chunks of the delta rule's form over a bucket of 80
    (pre,) = [a for _, _, a in run.span_list("prefill")]
    assert pre["prompt_tokens"] == 79 and pre["state_chunks"] == 2


# ---- the reference ---------------------------------------------------------

def test_reference_rounding_hook_and_share(cell):
    """`rnd` reaches every matrix product: at float8 the logits move, with
    the identity they do not; the reference reads nothing of the program but
    the tree it is handed; and handed another share of the same tree it
    gives other logits."""
    import jax
    import jax.numpy as jnp

    from bench.run import merge
    from bigdl_tpu.api import optimize_model
    from bigdl_tpu.models import get_family
    from bigdl_tpu.models.config import ModelConfig

    hf = cells.as_run(merge(cell.config, cell.config["bench"]["rehearsal"]))
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
    moved = dict(hf, expert_parallel_share={"router_experts": 16,
                                            "first_expert": 9})
    assert np.abs(np.asarray(ref.logits(moved, params, toks, 5))
                  - plain).max() > 1e-3
    with open(os.path.join(ROOT, "bench", "reference",
                           "solar_open2.py")) as f:
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
         CELL, "--seed", str(2 ** 31 + 65), "--seconds", "3", "--trace", "1",
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
    assert "pallas:kda_decode" in out.stdout
    assert "chunked prefill C64" in out.stdout
    assert "held 4/16 first 4" in out.stdout
    assert "pallas:paged" in out.stdout and "pallas:flash" in out.stdout
    assert {"engine.decode_occupancy", "step.decode_ms_p50--closed",
            "kernel.paged_live_page_share--closed",
            "step.prefill_ms_p50--closed", "engine.moe_load_imbalance",
            "engine.admit.retrace_ms_p50--closed"} <= set(line["metrics"])
    assert line["metrics"]["engine.admit.retrace_ms_p50--closed"][
        "value"] == 0.0
