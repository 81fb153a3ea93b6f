"""The sparse-expert cell's own pieces (ISSUE 26): the cost arithmetic against
the parameter tree's sizes, the three readers on recorded spans and a
recorded trace, the reference that compares AT the program's expert choice,
and `bench/run.py --rehearse` on the cell."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import cells, costs, costs_moe  # noqa: E402
from bench.records import Frozen, Run  # noqa: E402

CELL = "mixtral-8x7b.chat-closed"
NEW = ("kernel.moe_ffn_ms_per_step", "kernel.moe_ffn_roofline",
       "engine.moe_load_imbalance")


@pytest.fixture(scope="module")
def cell():
    return cells.resolve(CELL, ROOT)


def test_the_cell_is_mixtral_at_published_widths_under_chat_closed(cell):
    hf = cells.as_run(cell.config)
    pub = cell.config["published"]
    assert cell.traffic_name == "chat-closed" and cell.chips == 1
    assert cell.entry_name == "engine"
    assert cell.config["reduced"] == ["num_hidden_layers"]
    for key in ("hidden_size", "intermediate_size", "num_local_experts",
                "num_experts_per_tok", "num_attention_heads",
                "num_key_value_heads", "vocab_size"):
        assert hf[key] == pub[key], key
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= names and "setup.weights_s" in names
    assert [m["name"] for m in cell.end_to_end] == ["output_tokens_per_s",
                                                    "setup_s"]


def test_the_preset_is_the_published_config():
    """`PRESETS["mixtral-8x7b"]` and the config file's `published` block
    say the same (the preset had neither the source's epsilon nor its
    context length)."""
    from bigdl_tpu.models.config import PRESETS, ModelConfig

    pub = cells.load_json(ROOT, "bench", "configs",
                          "mixtral-8x7b-int4.json")["published"]
    assert PRESETS["mixtral-8x7b"] == ModelConfig.from_hf_config(pub)


def test_expert_bytes_are_the_parameter_trees_own(cell):
    """bench/costs_moe.py's arithmetic against the sizes of the served
    tree's expert leaves (shapes only: nothing is allocated)."""
    import jax

    from bench import weights
    from bigdl_tpu.models.config import ModelConfig

    hf = cells.as_run(cell.config)
    shapes = weights.param_shapes(ModelConfig.from_hf_config(hf), "sym_int4")
    tree = sum(leaf.size * leaf.dtype.itemsize
               for name in ("w_gate_e", "w_up_e", "w_down_e")
               for leaf in jax.tree.leaves(shapes["layers"][name]))
    assert costs_moe.expert_stack_bytes(hf) == tree
    one = costs_moe.expert_ffn_cost(hf, experts_hit=1, assignments=0)
    assert one == {"bytes": tree // (8 * hf["num_hidden_layers"]), "flops": 0}
    # the experts are what a step reads: 93% of the packed tree
    assert tree > 0.9 * costs.tree_bytes(shapes)


def _run(cell, steps, device=None):
    spans = [{"ph": "X", "name": "decode_step", "ts": (10 + i) * 1e6,
              "dur": 3e4, "args": a} for i, a in enumerate(steps)]
    return Run(cell=cell, hf=cells.as_run(cell.config),
               peak=costs.peaks("TPU v5 lite"), t0=0.0, t1=100.0,
               requests=[], spans=spans, device=device)


def _device(n_steps, kernel_s, begin=0.0, end=100.0):
    """What the readers ask of a reduced trace."""
    return types.SimpleNamespace(
        begin=begin, end=end, offset=0.0,
        kernel_in_program=lambda kernel, program: (
            (n_steps, kernel_s) if (kernel, program) == (
                "moe_qmatmul", "engine_decode") else (0, 0.0)))


STEP = {"moe_assignments": 320, "moe_experts_hit": 80,
        "moe_max_expert_load": 8, "moe_experts": 80}


def test_readers_on_recorded_spans_and_kernel_time(cell):
    run = _run(cell, [STEP, dict(STEP, moe_experts_hit=40)],
               _device(n_steps=2, kernel_s=0.060))
    assert cell.reader("kernel.moe_ffn_ms_per_step").read(run) == \
        pytest.approx(30.0)
    assert cell.reader("engine.moe_load_imbalance").read(run) == \
        pytest.approx(8 * 80 / 320)
    # 60 of 80 (layer, expert) pairs a step on average, memory-bound: their
    # packed bytes over 819 GB/s, over 30 ms
    need = costs_moe.expert_ffn_cost(run.hf, 60, 320)
    t_mem = need["bytes"] / run.peak["hbm_bytes_per_s"]
    assert need["flops"] / run.peak["bf16_flops_per_s"] < t_mem
    share = cell.reader("kernel.moe_ffn_roofline").read(run)
    assert share == pytest.approx(100 * t_mem / 0.030) and share < 100


def test_roofline_counts_the_traced_seconds_steps_only(cell):
    half = dict(STEP, moe_experts_hit=40)
    run = _run(cell, [STEP, half, half],
               _device(n_steps=2, kernel_s=0.060, begin=10.5, end=12.5))
    a = cell.reader("kernel.moe_ffn_roofline").read(run)
    run.device = _device(n_steps=2, kernel_s=0.060)  # all three
    assert a < cell.reader("kernel.moe_ffn_roofline").read(run)


@pytest.mark.parametrize("name", NEW)
def test_readers_return_nothing_where_there_is_nothing_to_read(cell, name):
    """The parent's program: spans without the arguments, a trace without
    the kernel. The metric is left out of the line, nothing raises."""
    bare = {"occupancy": 16, "slots": 16}
    assert cell.reader(name).read(_run(cell, [bare])) is None
    assert cell.reader(name).read(
        _run(cell, [bare], _device(n_steps=0, kernel_s=0.0))) is None
    assert getattr(cell.reader(name), "ENTRIES") == ("engine",)


# ---- bench/reference/mixtral.py -------------------------------------------

TINY = dict(model_type="mixtral", vocab_size=256, hidden_size=128,
            intermediate_size=256, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2,
            num_local_experts=8, num_experts_per_tok=2, rms_norm_eps=1e-5,
            rope_theta=1e6, max_position_embeddings=256, sliding_window=None,
            hidden_act="silu", tie_word_embeddings=False)


@pytest.fixture(scope="module")
def tiny():
    import jax

    from bigdl_tpu.api import optimize_model
    from bigdl_tpu.models import llama
    from bigdl_tpu.models.config import ModelConfig

    cfg = ModelConfig.from_hf_config(TINY)
    params = optimize_model(
        llama.init_params(cfg, jax.random.PRNGKey(3)), cfg, "sym_int4")
    # router logits as wide as the published widths make them (a standard
    # deviation of 1.3 at H = 4096), so that ROUTER_TIE means what it means
    params["layers"]["router"] = params["layers"]["router"] * 6
    toks = np.random.default_rng(3).integers(1, 256, 12)
    return params, toks


def _router_logits(params, toks):
    """The free reference's router logits [L, T, E] (its own trajectory)."""
    import jax
    import jax.numpy as jnp

    from bench.reference import mistral as ref, mixtral

    out = []
    with jax.default_matmul_precision("highest"):
        h = ref.dense(params["embed"])[jnp.asarray(toks)]
        for l in range(TINY["num_hidden_layers"]):
            p = jax.tree.map(lambda a: a[l], params["layers"])
            h, x = mixtral._attn_half(TINY, h, p)
            out.append(np.asarray(x @ ref.dense(p["router"]).T))
            h = h + ref._moe(TINY, x, p)
    return np.stack(out)


class _Routed:
    """What the reference reads of a finished request."""

    def __init__(self, toks, chosen):
        self.prompt, self.out_tokens = [int(t) for t in toks[:-2]], \
            [int(t) for t in toks[-2:]] + [0]
        self.chosen = chosen

    def expert_ids(self, n):
        return np.asarray(self.chosen)[:, :n]


def _logits(module, params, toks, monkeypatch, chosen):
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.serving import engine

    monkeypatch.setattr(
        engine, "last_routed_request",
        lambda: None if chosen is None else _Routed(toks, chosen))
    return np.asarray(jax.jit(module.logits, static_argnums=(0, 3))(
        Frozen(TINY), params, jnp.asarray(toks, jnp.int32), 4))


def test_reference_compares_free_where_the_program_reports_nothing(
        tiny, monkeypatch):
    from bench.reference import mistral, mixtral
    from bigdl_tpu.serving import engine

    params, toks = tiny
    free = _logits(mistral, params, toks, monkeypatch, None)
    np.testing.assert_allclose(
        _logits(mixtral, params, toks, monkeypatch, None), free, atol=1e-6)
    # nor does another sequence's record count
    own = np.zeros((TINY["num_hidden_layers"], len(toks), 2), np.int8)
    own[..., 1] = 1
    monkeypatch.setattr(engine, "last_routed_request",
                        lambda: _Routed(toks[::-1], own))
    import jax
    import jax.numpy as jnp

    np.testing.assert_allclose(np.asarray(jax.jit(
        mixtral.logits, static_argnums=(0, 3))(
            Frozen(TINY), params, jnp.asarray(toks, jnp.int32), 4)),
        free, atol=1e-6)


def test_reference_takes_a_choice_only_within_the_tie_of_its_own_router(
        tiny, monkeypatch):
    """A choice whose experts' logits lie within ROUTER_TIE of the
    reference's k-th best is taken (and moves the logits: it IS another
    network); one that picks the two WORST experts is refused and the
    reference keeps its own choice, so a program that routes wrong is
    compared with the free reference and fails on the distance. And a
    program that departs from the reference's own choice, each time within
    the tie, in more than FLIP_SHARE of the decisions is compared free
    too: near-ties are rare, a router that is wrong a little is not."""
    from bench.reference import mistral, mixtral

    params, toks = tiny
    free = _logits(mistral, params, toks, monkeypatch, None)
    lg = _router_logits(params, toks)  # [L, T, E]
    order = np.argsort(lg, -1)
    own = order[..., -2:][..., ::-1]
    np.testing.assert_allclose(
        _logits(mixtral, params, toks, monkeypatch, own.astype(np.int8)),
        free, atol=1e-5)
    # swap the second for the third where they lie within the tie, in
    # the first layer (later layers' logits move once it is taken)
    gap = lg[0, :, :][np.arange(len(toks)), order[0, :, -2]] - \
        lg[0, :, :][np.arange(len(toks)), order[0, :, -3]]
    near = np.nonzero(gap < mixtral.ROUTER_TIE)[0]
    cap = int(mixtral.FLIP_SHARE * lg.shape[0] * lg.shape[1])
    assert cap >= 1 and len(near) > cap
    swapped = own.copy()
    swapped[0, near[:cap], 1] = order[0, near[:cap], -3]
    moved = _logits(mixtral, params, toks, monkeypatch, swapped)
    assert np.abs(moved - free).max() > 1e-3
    swapped[0, near, 1] = order[0, near, -3]  # each admissible; too many
    np.testing.assert_allclose(
        _logits(mixtral, params, toks, monkeypatch, swapped), free,
        atol=1e-5)
    one_bad = own.copy()  # the WORST expert for the second, in one place
    one_bad[0, 0, 1] = order[0, 0, 0]
    kth = np.take_along_axis(lg, order[..., -2:-1], -1)[..., 0]
    assert (kth - lg.min(-1) > mixtral.ROUTER_TIE).all()
    np.testing.assert_allclose(
        _logits(mixtral, params, toks, monkeypatch, one_bad), free, atol=1e-5)
    dup = own.copy()  # one expert twice is no choice
    dup[0, 0, 1] = dup[0, 0, 0]
    np.testing.assert_allclose(
        _logits(mixtral, params, toks, monkeypatch, dup), free, atol=1e-5)


# ---- the command ------------------------------------------------------------

def test_rehearsal_of_the_cell_reports_the_new_metrics():
    """`bench/run.py --workload mixtral-8x7b.chat-closed --rehearse`: the
    tiny rehearsal model through the grouped kernel (interpreted), checked
    against the reference at the engine's own expert choices; exit code 3,
    never a result."""
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--workload",
         CELL, "--seed", "2147483999", "--seconds", "3", "--trace", "1",
         "--rehearse"], cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 3, r.stderr[-3000:]
    assert "moe       pallas:grouped" in r.stdout
    last = r.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL complete, not a result: ")
    result = json.loads(last.split(": ", 1)[1])
    assert result["correct"] and result["attempted"] > 0
    assert result["failed"] == 0 and result["compiles_in_window"] == 0
    got = result["metrics"]
    assert got["engine.moe_load_imbalance"]["value"] >= 1.0
    # the CPU has no device plane: the two trace readers find nothing and
    # are left out, as on any run whose trace lacks the kernel
    assert "kernel.moe_ffn_roofline" not in got
    for name in ("step.decode_ms_p50--closed", "step.prefill_ms_p50--closed",
                 "engine.decode_occupancy", "setup.weights_s",
                 "kernel.paged_live_page_share--closed"):
        assert name in got, name
