"""The SDAR cell's own pieces (ISSUE 50): the configuration against its
`published` block and the catalog's widths, the file's arithmetic (a page, the
pool, the weights) against the program's own shapes, `bench/costs_block.py`
against hand counts and against `costs_paged` / `costs_moe` at one query
position, the three readers on recorded spans and a recorded trace (no
reading of a pass that is physically possible exceeds 100), the reference's
replay against its own `generate`, and `bench/run.py --rehearse` on the
cell."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import cells, costs, costs_block, costs_moe, costs_paged  # noqa: E402
from bench.records import Run  # noqa: E402

CELL = "sdar-30b-a3b.blockgen-closed"
NEW = ("engine.tokens_per_pass", "step.block_pass_mbu",
       "kernel.block_attn_roofline")
GENERATION = ("block_length", "denoising_steps", "remasking_strategy",
              "confidence_threshold", "mask_token_id")


@pytest.fixture(scope="module")
def cell():
    return cells.resolve(CELL, ROOT)


@pytest.fixture(scope="module")
def hf(cell):
    return cells.as_run(cell.config)


# ---- the configuration -----------------------------------------------------

def test_the_cell_is_sdar_at_published_widths(cell, hf):
    pub = cell.config["published"]
    assert cell.traffic_name == "blockgen-closed" and cell.chips == 1
    assert cell.entry_name == "engine"
    assert cell.config["reduced"] == ["num_hidden_layers"]
    assert set(hf) == set(pub) and hf["model_type"] == "sdar_moe"
    # what runs otherwise than `published`: the cut, and the two generation
    # keys `assumed` explains
    assert {k for k in pub if hf[k] != pub[k]} == {
        "num_hidden_layers", "remasking_strategy", "denoising_steps"}
    for key, want in (("hidden_size", 2048), ("intermediate_size", 6144),
                      ("num_attention_heads", 32), ("num_key_value_heads", 4),
                      ("head_dim", 128), ("num_experts", 128),
                      ("num_experts_per_tok", 8),
                      ("moe_intermediate_size", 768), ("vocab_size", 151936),
                      ("rope_theta", 1000000), ("rms_norm_eps", 1e-06),
                      ("norm_topk_prob", True),
                      ("tie_word_embeddings", False),
                      ("max_position_embeddings", 32768)):
        assert hf[key] == pub[key] == want
    assert (hf["num_hidden_layers"], pub["num_hidden_layers"]) == (24, 48)
    # the five keys the source's config.json does not have: the family's
    # defaults in `published`, each explained under `assumed`
    assert [pub[k] for k in GENERATION] == [
        4, 4, "low_confidence_dynamic", 0.9, 151669]
    assert [hf[k] for k in GENERATION] == [
        4, 2, "low_confidence_static", 0.9, 151669]
    assert set(GENERATION) | {"no_logit_shift", "store_pass",
                              "block_causal_mask", "rope_convention",
                              "noise_schedule", "published", "weights"} \
        <= set(cell.config["assumed"])
    assert "from memory" in cell.config["assumed"]["block_length"]
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= names
    assert {"kernel.moe_ffn_ms_per_step", "kernel.moe_ffn_roofline",
            "engine.moe_load_imbalance", "engine.decode_occupancy",
            "step.decode_ms_p50--closed", "step.prefill_ms_p50--closed",
            "kernel.paged_live_page_share--closed"} <= names
    # shares whose cost files count one query a row, or a dense MLP in every
    # layer (over 100% here), and a span this kind does not emit
    # (`kernel.paged_attn_ms_per_step--closed` would read fine: its list is
    # pinned equal to the roofline's by tests/bench, PERF.md 'Left by PR 50')
    assert not {"step.decode_mbu--closed",
                "kernel.decode.qmatmul_roofline--closed",
                "kernel.paged_attn_roofline--closed",
                "engine.admit.sample_ms_p50--closed"} & names
    assert [m["name"] for m in cell.end_to_end] == ["output_tokens_per_s",
                                                    "setup_s"]
    e = cell.config["bench"]["engine"]
    assert e == {"n_slots": 16, "max_len": 2048, "page_size": 64,
                 "n_pages": 513}
    assert e["n_pages"] == e["n_slots"] * e["max_len"] // e["page_size"] + 1
    assert e["page_size"] % hf["block_length"] == 0
    t = cell.traffic
    assert t["process"] == {"kind": "closed", "clients": 16, "think_s": 0,
                            "block": 16}
    reason = cells.load_json(ROOT, "bench", "traffic", "reason-closed.json")
    assert t["prompt"] == reason["prompt"] and t["output"] == reason["output"]
    shapes = cell.generator().shapes(t)
    assert max(shapes["prompt_lengths"]) + shapes["max_output"] \
        <= e["max_len"]
    r = cell.config["bench"]["rehearsal"]
    assert r["block_length"] == 4 and r["mask_token_id"] < r["vocab_size"]


def test_the_file_runs_as_the_program_reads_it(hf):
    from bigdl_tpu.models import get_family
    from bigdl_tpu.models.config import ModelConfig
    from bigdl_tpu.serving import blocks
    from bigdl_tpu.serving.engine import _cache_kind

    cfg = ModelConfig.from_hf_config(hf)
    assert cfg.model_type == "sdar_moe"
    assert (cfg.block_length, cfg.denoising_steps, cfg.remasking_strategy,
            cfg.confidence_threshold, cfg.mask_token_id) == (
        4, 2, "low_confidence_static", 0.9, 151669)
    assert (cfg.num_experts, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size) == (128, 8, 768)
    assert cfg.qk_norm and cfg.norm_topk_prob and cfg.head_dim_ == 128
    assert cfg.sliding_window is None and not cfg.tie_word_embeddings
    fam = get_family(cfg.model_type)
    assert fam.__name__ == "bigdl_tpu.models.sdar"
    kind = _cache_kind(types.SimpleNamespace(config=cfg, family=fam))
    assert kind is blocks.CACHE_KIND and kind.name == "kv_pages"
    assert not kind.share_prefixes
    assert costs_block.knows(hf) and not costs_block.knows(
        {"hidden_size": 64})


# ---- the file's arithmetic, against the program's shapes -------------------

def test_pool_pages_and_weights_are_the_programs_own(cell, hf):
    """Shapes only: nothing is allocated."""
    import jax

    from bench import weights
    from bigdl_tpu import kvpaged
    from bigdl_tpu.models.config import ModelConfig
    from bigdl_tpu.serving import blocks

    cfg = ModelConfig.from_hf_config(hf)
    e = cell.config["bench"]["engine"]
    page = e["page_size"]
    geo = kvpaged.Geometry(e["n_slots"], e["max_len"], page, e["n_pages"],
                           e["max_len"] // page)
    pool = jax.eval_shape(lambda: blocks.CACHE_KIND.make_pool(cfg, geo))
    assert pool.k.shape == (24, 513, 64, 4, 128)
    # a page: K and V x 4 heads x 128 x bf16 x 24 layers
    one = costs_block.kv_page_bytes(hf, page)
    assert one == kvpaged.kv_page_nbytes(pool) == 24 * 2 * 64 * 4 * 128 * 2
    assert round(one / 1e6, 3) == 3.146
    assert round(513 * one / 1e9, 2) == 1.61
    # weights: what the file counts is what the tree holds
    tree = weights.param_shapes(cfg, "sym_int4")
    H, D = 2048, 128
    expert = costs_block.expert_bytes(hf)
    assert expert == 3 * 768 * 2048 * 9 // 16
    layer_experts = 128 * expert
    assert round(layer_experts / 1e6, 1) == 339.7
    attention = (costs.sym_int4_bytes((32 + 2 * 4) * D, H)
                 + costs.sym_int4_bytes(H, 32 * D))
    assert round(attention / 1e6, 1) == 10.6
    router = 128 * H * 2
    assert round((layer_experts + attention + router) / 1e6, 1) == 350.9
    head = costs.sym_int4_bytes(hf["vocab_size"], H)
    embed = hf["vocab_size"] * H * 2
    assert (round(head / 1e6, 1), round(embed / 1e6, 1)) == (175.0, 622.3)
    assert costs_block.expert_stack_bytes(hf) == 24 * layer_experts
    total = costs.tree_bytes(tree)
    packed = 24 * (layer_experts + attention + router) + head
    assert 0 < total - packed < 0.001 * total  # the norms
    assert round((total + embed) / 1e9, 2) == 9.22
    assert round((48 * (layer_experts + attention + router) + head + embed)
                 / 1e9, 2) == 17.64
    d = cell.config["bench"]["deployment"] \
        + cell.config["bench"]["engine_derivation"]
    for figure in ("339.7 MB", "10.6 MB", "350.9 MB", "622.3 MB", "175.0 MB",
                   "17.64 GB", "3.146 MB", "1.61 GB", "9.22 GB", "513"):
        assert figure in d, figure
    # the kernels take every packed weight: shapes the guards accept
    from bigdl_tpu.ops.linear import grouped_route

    layers = tree["layers"]
    assert grouped_route(layers["w_gate_e"], layers["w_up_e"],
                         layers["w_down_e"]) in (None,
                                                 "backend is cpu, not tpu")
    assert layers["w_up_e"].data.shape == (24, 128, 768, 1024)
    assert layers["w_down_e"].data.shape == (24, 128, 2048, 384)
    assert layers["q_norm"].shape == (24, 128)


# ---- costs_block against hand counts ---------------------------------------

def test_a_pass_costs_the_pages_of_one_position_and_the_queries_of_b(hf):
    one = costs_paged.page_bytes(hf, 64)
    assert one == 2 * 64 * 4 * 128 * 2
    c = costs_block.attn_cost(hf, 64, live_pages=160, rows_live=16)
    small = 32 * 128 * 2 * 2  # q in, context out, a query position
    assert c["bytes"] == 24 * (160 * one + 16 * 4 * small)
    assert c["flops"] == 24 * 160 * 64 * 32 * 4 * 128 * 4
    # a block of one position is a one-token step
    assert costs_block.attn_cost(dict(hf, block_length=1), 64, 160, 16) == \
        costs_paged.decode_cost(hf, 64, 160, 16)
    # the pages are counted once for all b positions: b times the queries
    # cost far less than b times the step
    plain = costs_paged.decode_cost(hf, 64, 160, 16)
    assert c["bytes"] < 1.05 * plain["bytes"]


def test_pass_bytes_add_up(hf):
    experts = costs_block.expert_stack_bytes(hf)
    one = costs_block.expert_bytes(hf)
    page = costs_block.kv_page_bytes(hf, 64)
    got = costs_block.pass_bytes(hf, 8.6 * 10 ** 9, 3000, 160, 64)
    assert got == 8.6 * 10 ** 9 - experts + 3000 * one + 160 * page
    # the experts' arithmetic is costs_moe's under this family's key
    assert costs_block.expert_bytes(hf) == costs_moe.expert_bytes(hf)
    with pytest.raises(KeyError):  # why `step.decode_mbu` is not listed
        costs_moe.expert_stack_bytes(hf)
    assert costs_moe.expert_ffn_cost(hf, 3000, 512 * 24)["flops"] == \
        512 * 24 * 3 * 2 * 2048 * 768


# ---- the readers -----------------------------------------------------------

def _run(cell, steps, device=None, weight_bytes=0):
    spans = [{"ph": "X", "name": "decode_step", "ts": (10 + i) * 1e6,
              "dur": 3e4, "args": a} for i, a in enumerate(steps)]
    return Run(cell=cell, hf=cells.as_run(cell.config),
               peak=costs.peaks("TPU v5 lite"), t0=0.0, t1=100.0,
               requests=[], spans=spans, device=device,
               weight_bytes=weight_bytes)


def _device(n_steps, attn_s, step_s, begin=0.0, end=100.0):
    """What the readers ask of a reduced trace."""
    return types.SimpleNamespace(
        begin=begin, end=end, offset=0.0,
        kernel_in_program=lambda kernel, program: (
            (n_steps, attn_s) if (kernel, program) == (
                "paged_decode_attention", "engine_decode") and attn_s
            else (0, 0.0)),
        program_seconds=lambda program: (
            [step_s] * n_steps if program == "engine_decode" else []))


def _step(rows, live=160, hit=3000, emitted=None, b=4):
    return {"occupancy": rows, "slots": 16, "live_pages": live,
            "grid_pages": 512, "block_length": b, "passes": [rows, 0, 0, 0],
            "tokens_revealed": 2 * rows,
            "tokens_emitted": 2 * rows if emitted is None else emitted,
            "blocks_stored": 0, "moe_experts": 24 * 128,
            "moe_experts_hit": hit, "moe_assignments": 8 * 24 * 4 * rows}


def test_readers_on_recorded_spans_and_kernel_time(cell, hf):
    steps = [_step(16), _step(8, live=80, hit=2600, emitted=0),
             _step(16, emitted=32)]
    run = _run(cell, steps, _device(n_steps=3, attn_s=0.009, step_s=0.022),
               weight_bytes=8.6 * 10 ** 9)
    bw = run.peak["hbm_bytes_per_s"]
    assert cell.reader(NEW[0]).read(run) == pytest.approx((2 + 0 + 2) / 3)
    need = costs_block.pass_bytes(hf, 8.6 * 10 ** 9, (3000 * 2 + 2600) / 3,
                                  (160 * 2 + 80) / 3, 64)
    mbu = cell.reader(NEW[1]).read(run)
    assert mbu == pytest.approx(100 * need / bw / 0.022)
    assert 30 < mbu < 100
    attn = costs_block.attn_cost(hf, 64, (160 * 2 + 80) / 3, 40 / 3)
    got = cell.reader(NEW[2]).read(run)
    assert got == pytest.approx(100 * attn["bytes"] / bw / 0.003)
    assert 5 < got < 100
    # the generic readers the cell lists read the same spans
    assert cell.reader("kernel.paged_live_page_share").read(run) == \
        pytest.approx(100 * (160 * 2 + 80) / 3 / 512)
    assert cell.reader("engine.decode_occupancy").read(run) == \
        pytest.approx(100 * 40 / 48)


@pytest.mark.parametrize("rows,live,hit", [(16, 512, 3072), (16, 160, 3000),
                                           (1, 1, 200), (8, 40, 2300)])
def test_no_reading_of_a_possible_pass_exceeds_100(cell, hf, rows, live, hit):
    """A pass cannot run faster than the chip can read what it must: at a
    device time of exactly those bytes over the peak bandwidth both shares
    read 100, and any real pass takes longer."""
    bw = costs.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    weight_bytes = 8.6 * 10 ** 9
    need = costs_block.pass_bytes(hf, weight_bytes, hit, live, 64)
    attn = costs.roofline_seconds(
        costs_block.attn_cost(hf, 64, live, rows), costs.peaks("TPU v5 lite"))
    for slack in (1.0, 1.7):
        run = _run(cell, [_step(rows, live=live, hit=hit)],
                   _device(1, attn[0] * slack, need / bw * slack),
                   weight_bytes=weight_bytes)
        assert cell.reader(NEW[1]).read(run) == pytest.approx(100 / slack)
        assert cell.reader(NEW[2]).read(run) == pytest.approx(100 / slack)
    # and what it must read never exceeds what the tree and the pool hold
    assert need <= weight_bytes + 512 * costs_block.kv_page_bytes(hf, 64)
    assert hit <= 24 * 128 and attn[1] == "memory"


def test_the_parent_reports_nothing_and_does_not_raise(cell):
    """A program whose steps are not passes: its spans lack the arguments,
    and a run without a trace has no device."""
    plain = {"occupancy": 16, "slots": 16, "live_pages": 160,
             "grid_pages": 512}
    run = _run(cell, [plain], _device(1, 0.003, 0.02), weight_bytes=10 ** 9)
    assert [cell.reader(n).read(run) for n in NEW] == [None, None, None]
    run = _run(cell, [_step(16)], None, weight_bytes=10 ** 9)
    assert cell.reader(NEW[0]).read(run) == 2.0
    assert cell.reader(NEW[1]).read(run) is None
    assert cell.reader(NEW[2]).read(run) is None
    other = cells.resolve("mixtral-8x7b.chat-closed", ROOT)
    run = _run(other, [_step(16)], _device(1, 0.003, 0.02), 10 ** 9)
    assert cell.reader(NEW[1]).read(run) is None  # another family's keys


def test_rooflines_count_the_traced_seconds_steps_only(cell):
    steps = [_step(16), _step(4, live=20), _step(16)]
    dev = _device(n_steps=1, attn_s=0.003, step_s=0.02, begin=10.5, end=11.5)
    run = _run(cell, steps, dev, weight_bytes=8.6 * 10 ** 9)
    inside = costs_block.traced_steps(run)
    assert [a["occupancy"] for a in inside] == [4]
    assert len(costs_block.traced_steps(_run(cell, steps))) == 3


# ---- the reference's replay against its own generate ------------------------

TINY = dict(model_type="sdar_moe", vocab_size=128, hidden_size=32,
            intermediate_size=64, num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=8, num_experts=4,
            num_experts_per_tok=2, moe_intermediate_size=16,
            norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=1e6,
            max_position_embeddings=256, tie_word_embeddings=False,
            mask_token_id=127, block_length=4, confidence_threshold=0.9)


@pytest.fixture(scope="module")
def tiny_params():
    import jax

    from bigdl_tpu.api import optimize_model
    from bigdl_tpu.models import get_family
    from bigdl_tpu.models.config import ModelConfig

    cfg = ModelConfig.from_hf_config(TINY)
    return optimize_model(
        get_family("sdar_moe").init_params(cfg, jax.random.PRNGKey(3)), cfg,
        "sym_int4")


@pytest.mark.parametrize("strategy,steps,n_prompt", [
    ("low_confidence_static", 2, 14), ("low_confidence_static", 2, 12),
    ("sequential", 4, 13), ("low_confidence_dynamic", 4, 15)])
def test_the_replay_gives_back_what_generate_computed(cell, tiny_params,
                                                      strategy, steps,
                                                      n_prompt):
    """`logits` replays a request's passes: fed the record `generate` keeps
    of its own run, row i is the logits of the pass that revealed token i, so
    its log-softmax at the token is generate's logprob, and every revealed
    position is one the reference admits."""
    import jax
    import jax.numpy as jnp

    from bench.records import Frozen

    ref = cell.reference()
    hf = dict(TINY, remasking_strategy=strategy, denoising_steps=steps)
    n = 9
    prompt = np.random.default_rng(n_prompt).integers(1, 120,
                                                      n_prompt).tolist()
    toks, lps, passes = ref.generate(hf, tiny_params, prompt, n)
    assert len(toks) == n and len(lps) == n
    n_total = n_prompt + n - 1
    plan = ref.replay_plan(passes, n_total, n, 4, 2, 2)
    assert plan is not None and 1 <= plan["n"][0] <= n
    assert ref.replay_plan(passes[:1], n_total, n, 4, 2, 2) is None
    seq = jnp.asarray(prompt + toks[:-1], jnp.int32)
    rows, stats = jax.jit(
        lambda p, s, pl: ref.replay(Frozen(hf), p, s, n, pl))(
        tiny_params, seq, tuple(jnp.asarray(plan[f])
                                for f in ref.PLAN_FIELDS))
    rows = np.asarray(rows, np.float64)
    lse = np.log(np.exp(rows - rows.max(-1, keepdims=True)).sum(-1)) \
        + rows.max(-1)
    np.testing.assert_allclose(rows[np.arange(n), toks] - lse, lps,
                               atol=1e-5)
    # its own reveal lies at or above its own m_s-th best: no shortfall
    # (the sequential strategy reveals the leftmost, whatever its confidence)
    if strategy != "sequential":
        assert float(np.asarray(stats)[:int(plan["n"][0]), 2].max()) <= 1e-5
    if strategy != "low_confidence_static":
        return
    # a record that reveals the positions this reference would NOT have (a
    # whole block's first pass, the two it left masked): the shortfall shows
    wrong = [dict(p) for p in passes]
    first = next(p for p in wrong if p["masked"].all()
                 and p["base"] + 4 <= n_total)
    first["revealed"] = first["masked"] & ~first["revealed"]
    plan2 = dict(plan)
    r = [i for i in range(int(plan["n"][0]))
         if plan["base"][i] == first["base"] and plan["masked"][i].all()][0]
    plan2["revealed"] = plan["revealed"].copy()
    plan2["revealed"][r] = first["revealed"]
    _, stats2 = jax.jit(
        lambda p, s, pl: ref.replay(Frozen(hf), p, s, n, pl))(
        tiny_params, seq, tuple(jnp.asarray(plan2[f])
                                for f in ref.PLAN_FIELDS))
    assert float(np.asarray(stats2)[r, 2]) > 0


def test_a_record_whose_experts_depart_too_often_fails_outright(cell,
                                                                tiny_params):
    """Experts (0, 1) at every position are this router's own top-2 in one
    decision of six: more than `FLIP_SHARE` depart, the record is no
    rounding of this network's, and every row comes back uniform (comparing
    it free would only MOSTLY fail: PERF.md section 6, PR 50)."""
    import jax
    import jax.numpy as jnp

    from bench.records import Frozen

    ref = cell.reference()
    hf = dict(TINY, remasking_strategy="low_confidence_static",
              denoising_steps=2)
    n, prompt = 9, np.random.default_rng(14).integers(1, 120, 14).tolist()
    toks, lps, passes = ref.generate(hf, tiny_params, prompt, n)
    plan = ref.replay_plan(passes, len(prompt) + n - 1, n, 4, 2, 2)
    plan["chosen"][:int(plan["n"][0])] = np.arange(2)
    rows, stats = jax.jit(
        lambda p, s, pl: ref.replay(Frozen(hf), p, s, n, pl))(
        tiny_params, jnp.asarray(prompt + toks[:-1], jnp.int32),
        tuple(jnp.asarray(plan[f]) for f in ref.PLAN_FIELDS))
    T = plan["chosen"].shape[2]
    assert float(np.asarray(stats)[0, 0]) > ref.FLIP_SHARE * 2 * T
    assert not np.asarray(rows).any()  # log c = -log V at every token
    assert np.abs(-np.log(128) - np.asarray(lps)).min() > 0.07


def test_without_a_record_of_passes_the_check_is_a_causal_models(cell,
                                                                 tiny_params):
    """No request to replay: one forward over the sequence, row i the logits
    at position i for the token at i + 1, which is another number."""
    import jax
    import jax.numpy as jnp

    from bench.records import Frozen

    ref = cell.reference()
    hf = dict(TINY, remasking_strategy="low_confidence_static",
              denoising_steps=2)
    prompt = np.random.default_rng(5).integers(1, 120, 14).tolist()
    toks, lps, _ = ref.generate(hf, tiny_params, prompt, 9)
    seq = jnp.asarray(prompt + toks[:-1], jnp.int32)
    rows = np.asarray(jax.jit(ref.logits, static_argnums=(0, 3))(
        Frozen(hf), tiny_params, seq, 9), np.float64)
    assert rows.shape == (9, 128)
    lse = np.log(np.exp(rows - rows.max(-1, keepdims=True)).sum(-1)) \
        + rows.max(-1)
    got = rows[np.arange(9), toks] - lse
    assert np.abs(got - np.asarray(lps)).max() > 1e-3


# ---- the cell, end to end on the CPU ----------------------------------------

def test_the_cell_rehearses_through_the_block_path():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--workload",
         CELL, "--seed", "3000000007", "--seconds", "3", "--trace", "1",
         "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 3, r.stderr[-3000:]
    assert "block of 4: 8 rows a KV head" in r.stdout
    assert "store own pass" in r.stdout
    assert "causal by blocks of 4" in r.stdout
    last = r.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL complete, not a result: ")
    result = json.loads(last.split(": ", 1)[1])
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] > 0 and result["compiles_in_window"] == 0
    m = result["metrics"]
    # outputs of 3 to 6 tokens end inside their first blocks: up to 2 a pass
    assert 1.0 < m["engine.tokens_per_pass"]["value"] <= 2.0
    assert "step.decode_ms_p50--closed" in m
    assert "engine.moe_load_imbalance" in m
