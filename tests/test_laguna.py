"""Laguna (`laguna`): full and window attention layers that differ in query
heads and rope, a sigmoid gate a head, a dense first layer, then
sigmoid-routed experts and a shared one, on two groups of pages in one
engine slot (models/laguna.py, bigdl_tpu/kvwindow.py).

The yardstick is bench/reference/laguna.py: plain float32, no cache, no
pages. The tiny model's window (32) is SHORTER than the tests' sequences and
its pages (8) smaller than the window, so window pages are freed while a
request decodes; its full layers have 6 query heads and its window layers 8
over 2 KV heads. Tolerances: float32 against float32 holds to 2e-4 on logits
of size 1 (sums in another order); the packed model in bf16 through the
engine is held at the LOGPROB level, as the benchmark's check holds it: the
worst of a request's tokens to 0.2 nats and their median to 0.03 (this
model's experts weigh 2.5 and a shared one beside them, so its residual
stream is hotter than SmallThinker's at the same weights: the program reads
0.02 to 0.16 at the worst token and 0.006 to 0.009 at the median one; the
same reference with float8 inputs 0.25 to 0.28 and 0.08 to 0.15, so the
median tells them apart by a factor of ten and the worst bounds an
outlier)."""

import dataclasses
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import cells  # noqa: E402
from bigdl_tpu import kvpaged, kvwindow  # noqa: E402
from bigdl_tpu.api import TpuModel, optimize_model  # noqa: E402
from bigdl_tpu.models import get_family  # noqa: E402
from bigdl_tpu.models.config import PRESETS, ModelConfig  # noqa: E402
from bigdl_tpu.serving.engine import InferenceEngine  # noqa: E402
from engines import shared_engine  # noqa: E402

pytestmark = pytest.mark.core

CFG = PRESETS["tiny-laguna"]
# the preset as the source's config.json keys (what the reference reads)
HF = dict(
    model_type="laguna", vocab_size=256, hidden_size=64,
    intermediate_size=128, num_hidden_layers=8, num_attention_heads=6,
    num_key_value_heads=2, head_dim=32, max_position_embeddings=4096,
    attention_bias=False, rms_norm_eps=1e-6, num_experts=16,
    num_experts_per_tok=4, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, tie_word_embeddings=False,
    gating=True, sliding_window=32,
    rope_parameters={
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 16,
            "original_max_position_embeddings": 64, "beta_slow": 1,
            "beta_fast": 8, "attention_factor": 1.2772588722239782,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 64},
    layer_types=["full_attention"] + ["sliding_attention"] * 3
    + ["full_attention"] + ["sliding_attention"] * 3,
    moe_apply_router_weight_on_input=False, partial_rotary_factor=0.5,
    mlp_layer_types=["dense"] + ["sparse"] * 7,
    moe_routed_scaling_factor=2.5,
    num_attention_heads_per_layer=[6, 8, 8, 8] * 2)
W, PAGE = CFG.sliding_window, 8


@pytest.fixture(scope="module")
def fam():
    return get_family("laguna")


@pytest.fixture(scope="module")
def ref():
    return cells.load_module(ROOT, "reference", "laguna")


@pytest.fixture(scope="module")
def dense(fam):
    """float32 weights large enough (0.08) that logits have a spread of
    about 1 and greedy tokens differ."""
    return _init(fam, CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def params(dense):
    return optimize_model(dense, CFG, "sym_int4")


@pytest.fixture(scope="module")
def model(params):
    return TpuModel(CFG, params, "sym_int4")


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(1, 256, n)


_JITTED = {}  # the reference, compiled once a (config, n_last, rounding)


def _ref_logits(ref, p, seq, n_last, hf=HF, rnd=None):
    """The reference under `jax.jit`: run eagerly it compiles every scan and
    map of its own again at every call (15 s where this takes 4)."""
    key = (json.dumps(hf, sort_keys=True), n_last, rnd)
    if key not in _JITTED:
        kw = {"rnd": rnd} if rnd else {}
        _JITTED[key] = jax.jit(
            lambda p, t: ref.logits(hf, p, t, n_last, **kw))
    return np.asarray(_JITTED[key](p, jnp.asarray(seq, jnp.int32)))


@functools.partial(jax.jit, static_argnums=(0, 1, 5, 6))
def _f32(fam, cfg, p, toks, cache=None, mode="prefill", moe_routing=False):
    return fam.forward(cfg, p, jnp.asarray(toks, jnp.int32), cache, mode=mode,
                       compute_dtype=jnp.float32, moe_routing=moe_routing)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _init(fam, cfg, key):
    return fam.init_params(cfg, key, dtype=jnp.float32, scale=0.08)


def _engine(model, fresh=False, **kw):
    """`fresh`: with programs of its own, for a test of what it traces."""
    kw = {"n_slots": 3, "max_len": 128, "paged": True, "page_size": PAGE,
          **kw}
    return (InferenceEngine if fresh else shared_engine)(model, **kw)


def test_preset_is_the_hf_config(fam):
    # the preset asks for the dense combine (16 experts would take the
    # ragged one, which drops past its capacity); nothing else differs
    assert ModelConfig.from_hf_config(HF) == dataclasses.replace(
        CFG, moe_dispatch=None)
    assert fam.period(CFG) == 4 and fam.group_layers(CFG) == (2, 6)
    assert fam.layouts(CFG)[1] == (6, 8, 8, 8) * 2
    assert CFG.rotary_dim == 16 and CFG.first_k_dense_replace == 1
    assert dict(CFG.rope_scaling)["rope_type"] == "yarn"
    # the sibling's spelling of the gate is this one's
    assert ModelConfig.from_hf_config(dict(HF, gating="per-head")) == \
        ModelConfig.from_hf_config(HF)
    for bad, match in (
            (dict(gating="per-element"), "gating"),
            (dict(mlp_layer_types=["sparse", "dense"] + ["sparse"] * 6),
             "dense layers lead"),
            (dict(num_attention_heads_per_layer=[6, 8, 8, 4] * 2),
             "inside one kind"),
            (dict(moe_apply_router_weight_on_input=True), "input")):
        with pytest.raises(NotImplementedError, match=match):
            ModelConfig.from_hf_config(dict(HF, **bad))
    with pytest.raises(ValueError, match="layer_types"):
        ModelConfig.from_hf_config(dict(HF, layer_types=["full_attention"]))


def test_the_published_config_builds(fam):
    """Laguna-XS.2's own keys (the benchmark's configuration file, cut to
    16 layers) through `from_hf_config`: the shapes the family stacks."""
    hf = cells.as_run(cells.load_json(
        ROOT, "bench", "configs", "laguna-xs.2-int4.json"))
    cfg = ModelConfig.from_hf_config(hf)
    assert cfg.model_type == "laguna" and fam.period(cfg) == 4
    assert fam.group_layers(cfg) == (4, 12) and cfg.sliding_window == 512
    assert set(fam.layouts(cfg)[1][1:4]) == {64} and cfg.rotary_dim == 64
    assert (cfg.num_experts, cfg.num_experts_per_tok) == (256, 8)
    shapes = jax.eval_shape(
        lambda k: fam.init_params(cfg, k), jax.ShapeDtypeStruct(
            (2,), jnp.uint32))
    assert shapes["first"]["0"]["wq"].shape == (48 * 128, 2048)
    assert shapes["first"]["0"]["w_gate"].shape == (8192, 2048)
    assert shapes["period"]["0"]["wq"].shape == (3, 48 * 128, 2048)
    assert shapes["period"]["1"]["wo"].shape == (3, 2048, 64 * 128)
    assert shapes["period"]["2"]["attn_gate"].shape == (3, 64, 2048)
    assert shapes["period"]["3"]["w_up_e"].shape == (3, 256, 512, 2048)
    assert shapes["period"]["0"]["w_down_s"].shape == (3, 2048, 512)


# ---------------------------------------------------------------------------
# forward against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [5, W, W + 1, 90])
def test_forward_matches_the_reference(fam, ref, dense, n):
    seq = _tokens(n, n)
    got, _ = _f32(fam, CFG, dense, seq[None])
    np.testing.assert_allclose(np.asarray(got[0]),
                               _ref_logits(ref, dense, seq, n), atol=2e-4)


def test_prefill_hands_over_to_decode_on_the_dense_cache(fam, ref, dense):
    seq = _tokens(70, 3)
    cache = fam.init_cache(CFG, 1, 80, dtype=jnp.float32)
    got, cache = _f32(fam, CFG, dense, seq[None, :50], cache)
    out = [got[0]]
    for t in range(50, 70):
        step, cache = _f32(fam, CFG, dense, seq[None, t:t + 1], cache,
                           "decode")
        out.append(step[0])
    assert int(cache.pos) == 70
    np.testing.assert_allclose(np.asarray(jnp.concatenate(out)),
                               _ref_logits(ref, dense, seq, 70), atol=2e-4)


def _without_shared(fam, tree):
    return {**tree, **{part: {j: {k: v for k, v in p.items()
                                  if not k.endswith("_s")}
                              for j, p in tree[part].items()}
                       for part in ("first", "period")}}


def _swap_ropes(fam, monkeypatch):
    """The window layers' plain rope on the full layers and the reverse."""
    real = fam._rope_tables
    monkeypatch.setattr(fam, "_rope_tables", lambda *a: real(*a)[::-1])


# what: (config fields replaced, a change to the tree, a patch of the family)
DEPARTURES = {
    "ropes_swapped": ({}, None, _swap_ropes),
    "gate_left_out": (dict(attn_gate=None), None, None),
    "router_unnormalised": (dict(norm_topk_prob=False), None, None),
    "router_unscaled": (dict(routed_scaling_factor=1.0), None, None),
    "shared_expert_dropped": ({}, _without_shared, None),
    "window_on_the_full_layers": (dict(sliding_layers=(1,) * 8), None, None),
    "no_partial_rotary": (dict(partial_rotary_factor=1.0), None, None),
}


@pytest.fixture(scope="module")
def published(fam, ref, dense):
    """(tokens, the reference's logits of them), which the published form
    holds to the tolerance: the yardstick of every departure."""
    seq = _tokens(60, 9)
    want = _ref_logits(ref, dense, seq, 60)
    good, _ = _f32(fam, CFG, dense, seq[None])
    np.testing.assert_allclose(np.asarray(good[0]), want, atol=2e-4)
    return jnp.asarray(seq[None], jnp.int32), want


@pytest.mark.parametrize("what", sorted(DEPARTURES))
def test_a_departure_is_another_model(fam, dense, published, what,
                                      monkeypatch):
    """Each step of the layer taken otherwise departs from the reference by
    far more than the tolerance that holds the published form."""
    toks, want = published
    fields, retree, patch = DEPARTURES[what]
    if patch:
        patch(fam, monkeypatch)
    cfg = dataclasses.replace(CFG, **fields)
    # a program of its own: `_f32`'s cache does not see a patched family
    bad, _ = jax.jit(lambda p: fam.forward(
        cfg, p, toks, None, compute_dtype=jnp.float32))(
            retree(fam, dense) if retree else dense)
    assert np.abs(np.asarray(bad[0]) - want).max() > 0.05, what


def test_head_counts_belong_to_their_kind(fam, ref, dense):
    """Six heads on the full layers and eight on the window layers: the
    counts swapped do not fit the published tree, and a tree built for the
    swapped counts is the model the reference reads from the swapped key."""
    seq = _tokens(40, 13)
    swapped = dataclasses.replace(CFG, heads_per_layer=(8, 6, 6, 6) * 2)
    with pytest.raises((TypeError, ValueError)):
        _f32(fam, swapped, dense, seq[None])
    other = _init(fam, swapped, jax.random.PRNGKey(1))
    assert other["first"]["0"]["wq"].shape == (8 * 32, 64)
    assert other["period"]["1"]["attn_gate"].shape == (1, 6, 64)
    got, _ = _f32(fam, swapped, other, seq[None])
    np.testing.assert_allclose(
        np.asarray(got[0]),
        _ref_logits(ref, other, seq, 40, hf=dict(
            HF, num_attention_heads_per_layer=[8, 6, 6, 6] * 2)), atol=2e-4)
    with pytest.raises((TypeError, ValueError)):
        _ref_logits(ref, other, seq, 40)


def test_the_gate_is_one_scalar_a_head(fam, dense):
    """W_g = 0 opens every gate to exactly one half: the same logits as the
    ungated model with W_o halved, so the gate multiplies the heads' outputs
    ahead of W_o. A gate an element would need Hq x D rows of W_g, which the
    forward refuses by shape (and `from_hf_config` by name)."""
    seq = jnp.asarray(_tokens(30, 21)[None], jnp.int32)

    def every_layer(fn):
        return {**dense, **{part: {j: fn(p) for j, p in dense[part].items()}
                            for part in ("first", "period")}}

    half = every_layer(lambda p: dict(p, attn_gate=p["attn_gate"] * 0))
    plain = every_layer(lambda p: dict(p, wo=p["wo"] * 0.5))
    got, _ = _f32(fam, CFG, half, seq)
    want, _ = _f32(fam, dataclasses.replace(CFG, attn_gate=None), plain, seq)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    p = dense["first"]["1"]  # a window layer: 8 heads of 32
    per_element = dict(p, attn_gate=jnp.zeros((8 * 32, 64), jnp.float32))
    with pytest.raises((TypeError, ValueError)):
        _f32(fam, CFG, {**dense, "first": {**dense["first"],
                                           "1": per_element}}, seq)


def test_layer_zero_is_dense_and_routes_nothing(fam, ref, dense):
    """The routing record covers the 7 sparse layers; layer 0's
    feed-forward is the SwiGLU of `intermediate_size`; a tree whose layer 0
    is sparse is not the published model, and the reference says so."""
    seq = _tokens(20, 17)
    _, _, routing = _f32(fam, CFG, dense, seq[None], None, "prefill", True)
    assert routing.shape == (7, 1, 20, 4)
    assert "router" not in dense["first"]["0"]
    assert dense["first"]["0"]["w_gate"].shape == (128, 64)
    own = jax.jit(lambda p, t: ref.hidden(
        HF, p, t, jnp.full((7, 20, 4), -1, jnp.int32))[3])(
            dense, jnp.asarray(seq, jnp.int32))
    np.testing.assert_array_equal(np.sort(np.asarray(routing[:, 0]), -1),
                                  np.sort(np.asarray(own), -1))
    sparse0 = _init(fam, dataclasses.replace(CFG, first_k_dense_replace=0),
                    jax.random.PRNGKey(0))
    assert "router" in sparse0["first"]["0"]
    with pytest.raises(KeyError, match="w_gate"):
        _ref_logits(ref, sparse0, seq, 20)


def test_generate_left_pads_a_batch(model, ref, params):
    prompts = [_tokens(40, 5).tolist(), _tokens(11, 6).tolist()]
    out = np.asarray(model.generate(prompts, max_new_tokens=6))
    for prompt, toks in zip(prompts, out):
        seq = prompt + toks.tolist()
        logits = _ref_logits(ref, params, seq[:-1], 6)
        best = logits.max(-1)
        assert np.all(best - logits[np.arange(6), toks] < 0.15)


# ---------------------------------------------------------------------------
# the engine: two groups of pages in one slot
# ---------------------------------------------------------------------------

def _check_request(ref, params, r, tol=0.2, tol_median=0.03, **kw):
    """The engine's chosen-token logprobs against the reference's
    log-softmax over the same tokens: the benchmark's statistic, and the
    median token beside the worst (module docstring)."""
    n = len(r.out_tokens)
    seq = r.prompt + r.out_tokens[:-1]
    lp = jax.nn.log_softmax(_ref_logits(ref, params, seq, n, **kw), -1)
    want = np.asarray(lp)[np.arange(n), r.out_tokens]
    diff = np.abs(want - np.asarray(r.out_logprobs))
    assert diff.max() < tol and np.median(diff) < tol_median, diff
    return diff.max()


@pytest.mark.parametrize("pallas", ["0", "interpret"])
def test_engine_serves_two_groups_of_pages(model, ref, params, monkeypatch,
                                           pallas):
    """Prefill then decode through both groups with a window shorter than
    the sequences and pages smaller than the window, across several
    freeings; a slot never holds more than W // P + 2 window pages; the
    paged kernel takes 3 and 4 query heads to a KV head in one program."""
    monkeypatch.setenv("BIGDL_TPU_PALLAS", pallas)
    eng = _engine(model)
    reqs = [eng.submit(_tokens(n, n).tolist(), max_new_tokens=30)
            for n in (50, 20, 70)]
    most = 0
    while eng.step():
        most = max(most, *(len(w) for w in eng.pages.win_pages))
        assert eng.page_leaks() == 0
    assert most == W // PAGE + 2
    # 50 -> 80: the window's first page goes from 2 to 6; 20 -> 50: 0 to 2;
    # 70 -> 100: 4 to 8
    assert eng.pages.window_pages_freed == 4 + 2 + 4
    assert eng.pages.pages_in_use() == (0, 0) and eng.page_leaks() == 0
    for r in reqs:
        assert r.finish_reason == "length"
        # the sparse layers' choices, layer 0 not among them
        assert r.expert_ids(len(r.prompt) + 29).shape == (
            7, len(r.prompt) + 29, 4)
        _check_request(ref, params, r)


def test_a_lower_precision_fails_the_engines_tolerance(model, ref, params):
    """The same reference with both inputs of every product at float8 is
    not within the tolerance the engine is held to."""
    eng = _engine(model)
    r = eng.submit(_tokens(50, 50).tolist(), max_new_tokens=30)
    eng.run_until_idle()
    good = _check_request(ref, params, r)

    def f8(x):
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    with pytest.raises(AssertionError):
        _check_request(ref, params, r, rnd=f8)
    assert good < 0.2


def test_park_and_resume_carries_both_groups(model):
    prompt = _tokens(50, 31).tolist()
    plain = _engine(model)
    want = plain.submit(prompt, max_new_tokens=30)
    plain.run_until_idle()
    eng = _engine(model)
    other = eng.submit(_tokens(10, 32).tolist(), max_new_tokens=30)
    r = eng.submit(prompt, max_new_tokens=30)
    for _ in range(12):  # past a freeing: position 61 reads from page 3 on
        eng.step()
    assert eng.pages.win_first[1] >= 3
    eng.preempt(r)
    eng._reap_preempt_requests()  # the head of the next step: parks it
    assert eng.preemptions == 1 and eng.pages.slot_pages[1] == []
    assert eng.pages.win_pages[1] == []
    parked = eng._preempted[0].blob
    assert isinstance(parked, kvpaged.HostPages)
    n_g, n_w, pos = parked.k.shape[1], parked.kw.shape[1], \
        eng._preempted[0].pos
    assert parked.k.shape[0] == 2 and parked.kw.shape[0] == 6
    assert n_g == -(-pos // PAGE)
    assert n_w == n_g - kvwindow.first_live_page(pos, W, PAGE) < n_g
    eng.run_until_idle()
    assert eng.preemption_resumes == 1
    assert r.out_tokens == want.out_tokens
    assert r.out_logprobs == want.out_logprobs  # bit-equal, not close
    assert other.finish_reason == "length" and eng.page_leaks() == 0


def test_the_refusals_name_the_kind(model):
    kind = kvwindow.KIND
    for what, kw in (("quantize_kv", {"quantize_kv": True}),
                     ("speculative", {"speculative": True}),
                     ("prefill_chunk_tokens", {"prefill_chunk_tokens": 16})):
        with pytest.raises(NotImplementedError,
                           match=f"{what}.*{kind}.*laguna"):
            shared_engine(model, n_slots=1, max_len=64, paged=True, **kw)
    with pytest.raises(NotImplementedError, match=f"{kind}.*paged=True"):
        shared_engine(model, n_slots=1, max_len=64)
    with pytest.raises(NotImplementedError, match=f"quantize_kv.*{kind}"):
        model.generate([[1, 2, 3]], max_new_tokens=2, quantize_kv=True)


def test_the_prefix_cache_stays_empty(model):
    eng = _engine(model)
    prompt = _tokens(40, 7).tolist()
    for _ in range(2):
        eng.submit(prompt, max_new_tokens=2)
        eng.run_until_idle()
    assert eng.pages.radix.n_nodes == 0 and eng.pages.prefix_hits == 0


def test_spans_counters_routes_and_scopes(model, params, monkeypatch):
    from bigdl_tpu.obs.tracing import TraceRecorder
    from bigdl_tpu.ops.routes import record_routes
    from bigdl_tpu.serving.metrics import Metrics, metric_drift

    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    tr = TraceRecorder(capacity=4096)
    with record_routes() as routes:
        eng = _engine(model, fresh=True, n_slots=2, tracer=tr)
        eng.submit(_tokens(50, 41).tolist(), max_new_tokens=12)
        eng.submit(_tokens(7, 42).tolist(), max_new_tokens=4)
        eng.run_until_idle()
    paged = sorted(d for op, route, d in routes
                   if (op, route) == ("attention", "pallas:paged"))
    # a line each kind of layer: its heads and its window
    assert len(paged) == 2
    assert "full x1 yarn over 16 of 32, 6 heads on 2, gated" in paged[0]
    assert "window 32 x3 rope 10000, 8 heads on 2, gated" in paged[1]
    assert any((op, route) == ("attention", "pallas:flash")
               for op, route, _ in routes)
    ev = tr.events()
    steps = [e["args"] for e in ev if e["name"] == "decode_step"]
    assert steps and all("live_pages" not in a for a in steps)
    for a in steps:
        assert a["grid_pages_global"] == a["grid_pages_window"] == 2 * 16
        assert 0 < a["live_pages_window"] <= a["live_pages_global"]
        assert a["moe_experts"] == 7 * 16  # layer 0 routes nothing
        assert a["moe_assignments"] == 7 * 4 * a["occupancy"]
    last = steps[-1]
    assert (last["live_pages_global"], last["live_pages_window"]) == (
        61 // PAGE + 1, 61 // PAGE - (61 - W + 1) // PAGE + 1)
    text = Metrics(eng).render()
    assert metric_drift(text, eng) == ([], [])
    assert "bigdl_tpu_window_pages_freed_total 1" in text
    # the scopes a profile's op names carry
    fam = get_family("laguna")
    hlo = jax.jit(lambda p, t: fam.forward(CFG, p, t, None)[0]).lower(
        params, jnp.zeros((1, 8), jnp.int32)).as_text(debug_info=True)
    for scope in ("attn.gate", "moe.shared", "ffn.dense", "moe.router"):
        assert scope in hlo, scope


def test_save_low_bit_round_trips_the_tree(model, tmp_path):
    from bigdl_tpu.api import AutoModelForCausalLM

    model.save_low_bit(str(tmp_path))
    back = AutoModelForCausalLM.load_low_bit(str(tmp_path))
    assert back.config == CFG
    assert sorted(back.params["period"]) == ["0", "1", "2", "3"]
    assert sorted(back.params["first"]) == ["0", "1", "2", "3"]
    prompt = [_tokens(9, 3).tolist()]
    np.testing.assert_array_equal(
        np.asarray(back.generate(prompt, max_new_tokens=4)),
        np.asarray(model.generate(prompt, max_new_tokens=4)))


def test_hf_names_map_onto_the_tree(fam, dense):
    """A state dict under the checkpoint's (assumed) names gives the logits
    of the tree it was written from."""
    from bigdl_tpu.convert.hf import params_from_state_dict

    sd = {"model.embed_tokens.weight": dense["embed"],
          "model.norm.weight": dense["final_norm"],
          "lm_head.weight": dense["lm_head"]}
    for l in range(CFG.num_hidden_layers):
        if l < 4:
            g = dense["first"][str(l)]
        else:
            g = {k: v[l // 4 - 1]
                 for k, v in dense["period"][str(l % 4)].items()}
        p = f"model.layers.{l}."
        sd[p + "input_layernorm.weight"] = g["attn_norm"]
        sd[p + "post_attention_layernorm.weight"] = g["mlp_norm"]
        for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                             ("wv", "v_proj"), ("wo", "o_proj"),
                             ("attn_gate", "g_proj")):
            sd[p + f"self_attn.{theirs}.weight"] = g[ours]
        m = p + "mlp."
        for ours in ("gate", "up", "down"):
            if l == 0:
                sd[f"{m}{ours}_proj.weight"] = g[f"w_{ours}"]
                continue
            sd[f"{m}shared_expert.{ours}_proj.weight"] = g[f"w_{ours}_s"]
            for x in range(CFG.num_experts):
                sd[f"{m}experts.{x}.{ours}_proj.weight"] = g[f"w_{ours}_e"][x]
        if l:
            sd[m + "gate.weight"] = g["router"]
    sd = {k: np.asarray(v) for k, v in sd.items()}
    tree = params_from_state_dict(CFG, sd.__getitem__, qtype="bf16",
                                  dtype=jnp.float32)
    toks = _tokens(40, 77)[None]
    got, _ = _f32(fam, CFG, tree, toks)
    want, _ = _f32(fam, CFG, dense, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    packed = params_from_state_dict(CFG, sd.__getitem__, qtype="sym_int4")
    assert packed["lm_head"].qtype == "sym_int4"
    assert packed["first"]["0"]["w_gate"].qtype == "sym_int4"
    assert packed["period"]["3"]["w_gate_e"].data.shape[:2] == (1, 16)
    assert packed["period"]["1"]["w_down_s"].qtype == "sym_int4"
    for name in ("router", "attn_gate"):
        assert not hasattr(packed["period"]["0"][name], "qtype")


def test_the_reference_refuses_another_familys_tree(ref):
    with pytest.raises(KeyError, match="first"):
        ref.logits(HF, {"layers": {}, "embed": jnp.zeros((4, 4))},
                   jnp.zeros((3,), jnp.int32), 1)
