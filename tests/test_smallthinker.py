"""SmallThinker (`smallthinker`): window and full attention mixed by layer,
a rope in the window layers only, ReLU-gated experts routed from the layer's
input, and two groups of pages in one engine slot (bigdl_tpu/kvwindow.py,
models/smallthinker.py, serving/pages.PageTable's window group).

The yardstick is bench/reference/smallthinker.py: plain float32, no cache,
no pages. The tiny model's window (32) is SHORTER than the tests' sequences
and its pages (8) smaller than the window, so window pages are freed while a
request decodes. Tolerances: float32 against float32 holds to 2e-4 on logits
of size 1 (sums in another order); the packed model in bf16 through the
engine is held at the LOGPROB level, as the benchmark's check holds it, to
0.08 nats (bf16 activations through 8 layers on logits of spread ~1:
Granite's and Brumby's tests hold the same statistic to the same bound); the
same reference with float8 inputs reads several times that."""

import dataclasses
import functools
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
from bigdl_tpu.models import get_family, llama  # noqa: E402
from bigdl_tpu.models.config import PRESETS, ModelConfig  # noqa: E402
from bigdl_tpu.serving.engine import InferenceEngine  # noqa: E402
from engines import shared_engine  # noqa: E402

pytestmark = pytest.mark.core

CFG = PRESETS["tiny-smallthinker"]
# the preset as the source's config.json keys (what the reference reads):
# no `model_type`, as the catalog row has none
HF = dict(
    model_name="tiny", vocab_size=256, hidden_size=64, head_dim=32,
    num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2,
    moe_num_primary_experts=8, moe_num_active_primary_experts=3,
    moe_ffn_hidden_size=32, moe_primary_router_apply_softmax=True,
    norm_topk_prob=True, sliding_window_size=32,
    sliding_window_layout=[0, 1, 1, 1] * 2, rope_layout=[0, 1, 1, 1] * 2,
    rope_theta=1.5e6, rope_scaling=None, max_position_embeddings=256,
    tie_word_embeddings=False, rms_norm_eps=1e-6)
W, PAGE = CFG.sliding_window, 8


@pytest.fixture(scope="module")
def fam():
    return get_family("smallthinker")


@pytest.fixture(scope="module")
def ref():
    return cells.load_module(ROOT, "reference", "smallthinker")


@pytest.fixture(scope="module")
def dense(fam):
    """float32 weights large enough (0.08) that logits have a spread of
    about 1 and greedy tokens differ."""
    return fam.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32,
                           scale=0.08)


@pytest.fixture(scope="module")
def params(dense):
    return optimize_model(dense, CFG, "sym_int4")


@pytest.fixture(scope="module")
def model(params):
    return TpuModel(CFG, params, "sym_int4")


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(1, 256, n)


_JITTED = {}  # the reference, compiled once a (config, n_last, options)


def _ref_logits(ref, p, seq, n_last, hf=HF, **kw):
    """The reference under `jax.jit`, as tests/test_laguna.py runs its own:
    run eagerly it compiles every scan and map of its own again at every
    call (ROADMAP D13)."""
    key = (repr(sorted(hf.items())), n_last, repr(sorted(kw.items())))
    if key not in _JITTED:
        _JITTED[key] = jax.jit(
            lambda p, t: ref.logits(hf, p, t, n_last, **kw))
    return np.asarray(_JITTED[key](p, jnp.asarray(seq, jnp.int32)))


@functools.partial(jax.jit, static_argnums=(0, 1, 5))
def _f32(fam, cfg, p, toks, cache=None, mode="prefill"):
    return fam.forward(cfg, p, jnp.asarray(toks, jnp.int32), cache, mode=mode,
                       compute_dtype=jnp.float32)


def _engine(model, fresh=False, **kw):
    """`fresh`: with programs of its own, for a test of what it traces."""
    kw = {"n_slots": 3, "max_len": 128, "paged": True, "page_size": PAGE,
          **kw}
    return (InferenceEngine if fresh else shared_engine)(model, **kw)


def test_preset_is_the_hf_config(fam):
    assert ModelConfig.from_hf_config(HF) == CFG  # found without model_type
    assert ModelConfig.from_hf_config(dict(HF, model_type="smallthinker")) \
        == CFG
    assert fam.period(CFG) == 4 and fam.group_layers(CFG) == (2, 6)
    assert CFG.hidden_act == "relu" and CFG.norm_topk_prob
    with pytest.raises(NotImplementedError, match="apply_softmax"):
        ModelConfig.from_hf_config(
            dict(HF, moe_primary_router_apply_softmax=False))
    with pytest.raises(ValueError, match="rope_layout"):
        ModelConfig.from_hf_config(dict(HF, rope_layout=[0, 1]))


def test_importing_the_package_does_not_load_the_family():
    import subprocess

    code = ("import sys, bigdl_tpu, bigdl_tpu.api, bigdl_tpu.serving.engine;"
            "bad = [m for m in sys.modules if m.endswith('smallthinker')];"
            "assert not bad, bad")
    subprocess.run([sys.executable, "-c", code], check=True, cwd=ROOT,
                   env=dict(os.environ, JAX_PLATFORMS="cpu"))


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


@pytest.mark.parametrize("swap", ["sliding_layers", "rope_layers"])
def test_swapped_layouts_are_another_model(fam, ref, dense, swap):
    """A full layer takes NO rope and a window layer does; the window binds
    in the layers the layout names and in no other. Each layout inverted is
    held against the reference, which reads the published one."""
    seq = _tokens(60, 9)
    wrong = dataclasses.replace(
        CFG, **{swap: tuple(1 - x for x in getattr(CFG, swap))})
    assert fam.period(wrong) == 4
    got, _ = _f32(fam, wrong, dense, seq[None])
    want = _ref_logits(ref, dense, seq, 60)
    assert np.abs(np.asarray(got[0]) - want).max() > 0.05
    # and the reference moves with its own keys, the other way round
    key = {"sliding_layers": "sliding_window_layout",
           "rope_layers": "rope_layout"}[swap]
    other = _ref_logits(ref, dense, seq, 60,
                        hf=dict(HF, **{key: [1 - x for x in HF[key]]}))
    np.testing.assert_allclose(np.asarray(got[0]), other, atol=2e-4)


@pytest.mark.parametrize("where", ["normed", "post_attention"])
def test_the_router_reads_the_layers_input(fam, ref, dense, where,
                                           monkeypatch):
    """`_moe_router` is handed the residual stream BEFORE the attention
    norm: a forward that hands it the normed input, or the stream after
    attention, departs from the reference."""
    seq = _tokens(40, 13)
    want = _ref_logits(ref, dense, seq, 40)
    seen = []
    real = llama._moe_router

    def other_input(config, xc, p):
        seen.append(xc)
        if where == "normed":
            from bigdl_tpu.ops import rms_norm

            xc = rms_norm(xc, p["attn_norm"], config.rms_norm_eps)
        else:  # what a router after attention would read: anything else
            xc = xc + 1.0
        return real(config, xc, p)

    got, _ = fam.forward(CFG, dense, jnp.asarray(seq[None], jnp.int32), None,
                         compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=2e-4)
    monkeypatch.setattr(llama, "_moe_router", other_input)
    bad, _ = fam.forward(CFG, dense, jnp.asarray(seq[None], jnp.int32), None,
                         compute_dtype=jnp.float32)
    assert len(seen) == fam.period(CFG)  # one trace a position of the period
    assert np.abs(np.asarray(bad[0]) - want).max() > 0.05


def test_the_first_layers_router_reads_the_embedding(fam, dense):
    """Direct: layer 0's chosen experts are the top-k of W_r over the
    EMBEDDING rows (the layer's input), which no norm has touched."""
    seq = _tokens(20, 17)
    _, _, routing = fam.forward(
        CFG, dense, jnp.asarray(seq[None], jnp.int32), None,
        compute_dtype=jnp.float32, moe_routing=True)
    assert routing.shape == (8, 1, 20, 3)
    x = dense["embed"][seq]
    logits = x @ dense["period"]["0"]["router"][0].T
    want = jax.lax.top_k(logits, 3)[1]
    np.testing.assert_array_equal(np.sort(np.asarray(routing[0, 0]), -1),
                                  np.sort(np.asarray(want), -1))


def test_relu_gated_experts_through_the_grouped_kernel(fam, monkeypatch):
    """`relu` is gated IN the kernel (`moe_qmatmul.FUSED_ACTS`): the grouped
    dispatch in the interpreter against the dense combine on the same
    packed stacks (at widths the kernel tiles: 128)."""
    from bigdl_tpu.ops.pallas import moe_qmatmul
    from bigdl_tpu.ops.routes import record_routes

    assert "relu" in moe_qmatmul.FUSED_ACTS
    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    wide = dataclasses.replace(CFG, hidden_size=128, moe_intermediate_size=128)
    params = optimize_model(
        fam.init_params(wide, jax.random.PRNGKey(2), dtype=jnp.float32,
                        scale=0.08), wide, "sym_int4")
    p = jax.tree.map(lambda a: a[0], params["period"]["1"])
    x = (jax.random.normal(jax.random.PRNGKey(5), (2, 9, 128)) * 0.5
         ).astype(jnp.bfloat16)
    topv, topi = llama._moe_router(wide, x, p)
    with record_routes() as routes:
        got = llama._moe_dispatch(wide, x, p, jnp.bfloat16, topv, topi)
    assert {(op, r) for op, r, _ in routes} == {("moe", "pallas:grouped")}
    want = llama._moe_dispatch_dense(wide, x, p, jnp.float32, topv, topi)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=2e-2)
    # a gate that were silu, or no gate at all, is further off than that
    silu = llama._moe_dispatch_dense(
        dataclasses.replace(wide, hidden_act="silu"), x, p, jnp.float32,
        topv, topi)
    assert np.abs(np.asarray(silu) - np.asarray(want)).max() > 5e-2


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

def _check_request(ref, params, r, tol=0.08, **kw):
    """The engine's chosen-token logprobs against the reference's
    log-softmax over the same tokens: the benchmark's statistic."""
    n = len(r.out_tokens)
    seq = r.prompt + r.out_tokens[:-1]
    lp = jax.nn.log_softmax(_ref_logits(ref, params, seq, n, **kw), -1)
    want = np.asarray(lp)[np.arange(n), r.out_tokens]
    diff = np.abs(want - np.asarray(r.out_logprobs))
    assert diff.max() < tol, diff
    return diff.max()


@pytest.mark.parametrize("pallas", ["0", "interpret"])
def test_engine_serves_two_groups_of_pages(model, ref, params, monkeypatch,
                                           pallas):
    """Prefill then decode through both groups with a window shorter than
    the sequences and pages smaller than the window, across several
    freeings; a slot never holds more than W // P + 2 window pages."""
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
    assert good < 0.08


def test_a_prefill_books_only_the_windows_pages(model):
    eng = _engine(model, max_len=256, n_slots=1)
    r = eng.submit(_tokens(100, 1).tolist(), max_new_tokens=8)
    eng.step()
    # 100 tokens pad to 112 positions = 14 pages; a query at position 100
    # reads from 69 on: pages 8 .. 13, W // P + 2 of them
    assert len(eng.pages.slot_pages[0]) == 14
    assert eng.pages.win_first[0] == 8
    assert len(eng.pages.win_pages[0]) == W // PAGE + 2
    table = np.asarray(eng.cache.window_tables[0])
    assert np.all(table[:8] == 0) and np.all(table[8:14] > 0)
    eng.run_until_idle()
    assert r.finish_reason == "length" and eng.page_leaks() == 0


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
        with pytest.raises(NotImplementedError, match=f"{what}.*{kind}"):
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


def test_spans_counters_and_routes(model, monkeypatch):
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
    notes = {(op, route): detail for op, route, detail in routes}
    assert "full x1 nope, window 32 x3 rope, 2 periods" in \
        notes[("attention", "pallas:paged")]
    assert ("attention", "pallas:flash") in notes
    ev = tr.events()
    steps = [e["args"] for e in ev if e["name"] == "decode_step"]
    assert steps and all("live_pages" not in a and "grid_pages" not in a
                         for a in steps)
    for a in steps:
        assert a["grid_pages_global"] == a["grid_pages_window"] == 2 * 16
        assert 0 < a["live_pages_window"] <= a["live_pages_global"]
        assert a["window_pages_held"] <= a["window_pages_unfreed"]
    # the long row alone, once the short one is done: position 61 reads from
    # 30 on, pages 3 .. 7 of 0 .. 7
    last = steps[-1]
    assert (last["live_pages_global"], last["live_pages_window"]) == (
        61 // PAGE + 1, 61 // PAGE - (61 - W + 1) // PAGE + 1)
    assert sum(a["window_pages_freed"] for a in steps) <= \
        eng.pages.window_pages_freed == 1  # page 2, at position 56
    pre = {e["args"]["prompt_tokens"]: e["args"] for e in ev
           if e["name"] == "prefill"}
    # 50 tokens pad to 64 positions = 8 pages; the window's first is page 2
    assert pre[50]["pages_written_global"] == 8
    assert pre[50]["pages_written_window"] == 6
    assert pre[7]["pages_written_global"] == pre[7]["pages_written_window"]
    text = Metrics(eng).render()
    assert metric_drift(text, eng) == ([], [])
    assert "bigdl_tpu_window_pages_freed_total 1" in text
    assert "bigdl_tpu_window_pages_in_use 0" in text


def test_save_low_bit_round_trips_the_period(model, tmp_path):
    from bigdl_tpu.api import AutoModelForCausalLM

    model.save_low_bit(str(tmp_path))
    back = AutoModelForCausalLM.load_low_bit(str(tmp_path))
    assert back.config == CFG
    assert sorted(back.params["period"]) == ["0", "1", "2", "3"]
    prompt = [_tokens(9, 3).tolist()]
    np.testing.assert_array_equal(
        np.asarray(back.generate(prompt, max_new_tokens=4)),
        np.asarray(model.generate(prompt, max_new_tokens=4)))


def test_hf_names_map_onto_the_tree(fam, dense):
    """A state dict under the checkpoint's names gives the logits of the
    tree it was written from."""
    from bigdl_tpu.convert.hf import params_from_state_dict

    sd = {"model.embed_tokens.weight": dense["embed"],
          "model.norm.weight": dense["final_norm"],
          "lm_head.weight": dense["lm_head"]}
    for l in range(CFG.num_hidden_layers):
        stack = dense["period"][str(l % 4)]
        p, g = f"model.layers.{l}.", {k: v[l // 4] for k, v in stack.items()}
        sd[p + "input_layernorm.weight"] = g["attn_norm"]
        sd[p + "post_attention_layernorm.weight"] = g["mlp_norm"]
        for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                             ("wv", "v_proj"), ("wo", "o_proj")):
            sd[p + f"self_attn.{theirs}.weight"] = g[ours]
        e = p + "block_sparse_moe."
        sd[e + "primary_router.weight"] = g["router"]
        for x in range(CFG.num_experts):
            for ours, theirs in (("w_gate_e", "gate"), ("w_up_e", "up"),
                                 ("w_down_e", "down")):
                sd[f"{e}experts.{x}.{theirs}.weight"] = g[ours][x]
    sd = {k: np.asarray(v) for k, v in sd.items()}
    tree = params_from_state_dict(CFG, sd.__getitem__, qtype="bf16",
                                  dtype=jnp.float32)
    assert sorted(tree["period"]) == ["0", "1", "2", "3"]
    toks = _tokens(40, 77)[None]
    got, _ = _f32(fam, CFG, tree, toks)
    want, _ = _f32(fam, CFG, dense, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    packed = params_from_state_dict(CFG, sd.__getitem__, qtype="sym_int4")
    assert packed["lm_head"].qtype == "sym_int4"
    assert packed["period"]["0"]["wq"].qtype == "sym_int4"
    assert packed["period"]["3"]["w_gate_e"].data.shape[:2] == (2, 8)
    assert not hasattr(packed["period"]["0"]["router"], "qtype")


def test_the_reference_refuses_another_familys_tree(ref):
    with pytest.raises(KeyError, match="period"):
        ref.logits(HF, {"layers": {}, "embed": jnp.zeros((4, 4))},
                   jnp.zeros((3,), jnp.int32), 1)
