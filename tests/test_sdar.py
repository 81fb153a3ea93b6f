"""SDAR-MoE (models/sdar.py): Qwen3-MoE's network generated by diffusion over
blocks, served by `InferenceEngine(paged=True)` through `serving/blocks.py`,
against the plain float32 reference `bench/reference/sdar.py`.

Small sizes on the CPU, seeded random weights. Two models: `tiny`, whose
confidences all lie near 1 / V (what seeded random weights give at any size),
and `sharp`, the same with the head scaled until some confidences pass the
dynamic strategy's threshold. Steps and strategy are the model config's,
constants of an engine, so each model is served by ONE engine a `GROUPS`
entry (the same weights under four configs), each running its prompt shapes
side by side; the cases below read the results and compile nothing of their
own.

Tolerances, and why. `LOGPROB_ATOL`, nats, by model: the engine computes in
bfloat16 from sym_int4 weights, the reference in float32 from the same
weights. On `tiny` the worst revealed token of all the cases reads 0.0033
apart, and the three departures below read 0.076 (no backward direction in
a block), 0.58 (logits shifted by one) and 0.19 (a block stored from masked
ids) at their worst token: the bound is 0.01, and a departure has to read
over twice that. On `sharp` the head's scale of 80 multiplies the rounding
with the logits: 0.10 at the worst token, a bound of 0.25 (the departures
read 8.8, 69 and 7.4 there). `REVEAL_TIE`, the same way: a revealed
position's reference log-confidence may lie that far under the reference's
own m_s-th best, because bfloat16 reorders near-ties.
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from engines import shared_engine

from bigdl_tpu.api import TpuModel, optimize_model
from bigdl_tpu.generate import GenerationConfig
from bigdl_tpu.models import get_family
from bigdl_tpu.models.config import BLOCK_STRATEGIES, ModelConfig
from bigdl_tpu.serving.engine import InferenceEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
B = 4  # block length
MASK = 255
HF = dict(model_type="sdar_moe", vocab_size=256, hidden_size=64,
          intermediate_size=128, num_hidden_layers=2, num_attention_heads=4,
          num_key_value_heads=2, head_dim=16, num_experts=8,
          num_experts_per_tok=2, moe_intermediate_size=32,
          norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=1e6,
          max_position_embeddings=512, tie_word_embeddings=False,
          mask_token_id=MASK)
HEAD_SCALE = 80
LOGPROB_ATOL = {"tiny": 0.01, "sharp": 0.25}
REVEAL_TIE = {"tiny": 0.02, "sharp": 0.25}
T_REPLAY = 48  # every replayed sequence is padded to this many positions

# an engine's (strategy, steps): its model config's
GROUPS = {"seq": ("sequential", 4), "static2": ("low_confidence_static", 2),
          "static3": ("low_confidence_static", 3),
          "dynamic": ("low_confidence_dynamic", 4)}
# (prompt tokens, new tokens, group): P % b in {0, 1, 3, 2}; ends inside a
# block (P + n not a multiple of b) and on a block's end; a prompt shorter
# than a block
CASES = [
    (16, 12, "seq"), (13, 9, "seq"),
    (16, 12, "static2"), (13, 10, "static2"), (18, 7, "static2"),
    (21, 11, "static2"), (15, 13, "static3"),
    (16, 12, "dynamic"), (15, 9, "dynamic"), (3, 6, "dynamic"),
]
IDS = [f"P{p}-n{n}-{g}" for p, n, g in CASES]


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "bench_reference_sdar",
        os.path.join(ROOT, "bench", "reference", "sdar.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _hf(group: str) -> dict:
    strategy, steps = GROUPS[group]
    return dict(HF, block_length=B, remasking_strategy=strategy,
                denoising_steps=steps, confidence_threshold=0.9)


def _models(sharp: bool) -> dict:
    """The same weights under each group's config."""
    cfg = ModelConfig.from_hf_config(HF)
    params = optimize_model(
        get_family("sdar_moe").init_params(cfg, jax.random.PRNGKey(0)), cfg,
        "sym_int4")
    if sharp:  # logits 80 times as far apart: confidences up to ~1
        head = params["lm_head"]
        params = dict(params, lm_head=dataclasses.replace(
            head, scales=head.scales * HEAD_SCALE))
    return {g: TpuModel(ModelConfig.from_hf_config(_hf(g)), params,
                        "sym_int4") for g in GROUPS}


def _serve(models: dict, cases, trace: bool = True):
    """Each group's cases through that group's engine: (engines by group,
    the requests in `cases`' order, every engine's spans)."""
    from bigdl_tpu.obs.tracing import TraceRecorder

    engines, reqs, spans = {}, [None] * len(cases), []
    for group in dict.fromkeys(g for _, _, g in cases):
        tracer = TraceRecorder(capacity=1 << 14)
        tracer.enabled = trace
        eng = engines[group] = shared_engine(
            models[group], n_slots=4, max_len=64, paged=True, page_size=16,
            gen=GenerationConfig(eos_token_id=None), tracer=tracer)
        for i, (p, n, g) in enumerate(cases):
            if g == group:  # a case's prompt is its own, whoever it runs beside
                reqs[i] = eng.submit(np.random.default_rng(
                    p * 64 + n).integers(1, MASK, p).tolist(),
                    max_new_tokens=n)
        eng.run_until_idle()
        spans += tracer.events()
    return engines, reqs, spans


@pytest.fixture(scope="module")
def tiny():
    return _models(False)


@pytest.fixture(scope="module")
def sharp():
    return _models(True)


@pytest.fixture(scope="module")
def served(tiny):
    return _serve(tiny, CASES)


@pytest.fixture(scope="module")
def served_sharp(sharp):
    return _serve(sharp, CASES)


@pytest.fixture(scope="module")
def replay(ref, tiny):
    """jitted: logits [b, V] of the block at `base` of a padded sequence."""
    hf = dict(HF, block_length=B)

    def make(visible=None, shift=0):
        return jax.jit(lambda p, x, base: ref.forward(
            hf, p, x, base - shift, B, visible=visible)[0])

    return make


def _replay_distances(req, params, fwd, ref, steps, mask_stored=False):
    """Per revealed token of `req`: |engine log c - reference log c|, and how
    far the reference's log-confidence of it lies under the reference's own
    m_s-th best masked position of that pass."""
    n_prompt = len(req.prompt)
    final = np.asarray(req.prompt + req.out_tokens)
    lp_of = dict(zip(range(n_prompt, len(final)), req.out_logprobs))
    out = []
    for p in req.passes:
        if p["stored"] or not p["revealed"].any():
            continue
        base = p["base"]
        x = np.full((T_REPLAY,), MASK, np.int32)
        x[:base] = MASK if mask_stored else final[:base]
        x[:min(base, n_prompt)] = final[:min(base, n_prompt)]
        x[base:base + B] = p["ids"]
        lg = np.asarray(fwd(params, jnp.asarray(x), base), np.float64)
        lsm = lg - (np.log(np.exp(lg - lg.max(-1, keepdims=True)).sum(-1))
                    + lg.max(-1))[:, None]
        own = lsm.max(-1)
        m_s = ref.reveal_count(B, steps, p["pass"])
        ranked = np.sort(own[p["masked"]])[::-1]
        kth = ranked[min(m_s, len(ranked)) - 1]
        for j in np.nonzero(p["revealed"])[0]:
            pos = base + int(j)
            if pos not in lp_of:  # past the request's last token: dropped
                continue
            want = lsm[j, final[pos]]
            out.append((abs(want - lp_of[pos]), kth - want, p, int(j)))
    return out


# ---- the engine against the reference ---------------------------------------

@pytest.mark.parametrize("which", ["tiny", "sharp"])
@pytest.mark.parametrize("case", range(len(CASES)), ids=IDS)
def test_engine_agrees_with_the_reference(case, which, served, served_sharp,
                                          tiny, sharp, replay, ref):
    """Logits and revealed positions, every strategy and prompt shape."""
    _, reqs, _ = served if which == "tiny" else served_sharp
    n_prompt, n, group = CASES[case]
    model = (tiny if which == "tiny" else sharp)[group]
    strategy, steps = GROUPS[group]
    req = reqs[case]
    assert req.finish_reason == "length" and len(req.out_tokens) == n
    assert np.all(np.isfinite(req.out_logprobs))
    dist = _replay_distances(req, model.params, replay(), ref, steps)
    assert len(dist) == n
    assert max(d for d, _, _, _ in dist) <= LOGPROB_ATOL[which]
    for _, short, p, j in dist:
        if strategy == "sequential":
            assert j == int(np.argmax(p["masked"]))
        elif strategy == "low_confidence_static":
            assert short <= REVEAL_TIE[which]
    # a block's passes: the first holds the prompt's tail, the last may be cut
    first = [p for p in req.passes if p["block"] == 0]
    assert first[0]["base"] == n_prompt - n_prompt % B
    assert int((~first[0]["masked"]).sum()) == n_prompt % B
    assert list(first[0]["ids"][:n_prompt % B]) == req.prompt[
        n_prompt - n_prompt % B:]
    if strategy == "low_confidence_static" and steps in (1, 2, 4):
        full = [p for p in req.passes if not p["stored"]
                and p["masked"].all()]
        assert all(int(p["revealed"].sum()) == B // steps for p in full[:1])


@pytest.mark.parametrize("case", range(len(CASES)), ids=IDS)
def test_engine_generates_what_the_reference_generates(case, sharp, ref,
                                                       served_sharp, replay):
    """Free-running, on the model whose confidences are far apart: the same
    positions revealed by the same passes and the same tokens, until a
    near-tie parts the two (saturated confidences tie at log 1; from there on
    they are two sequences). Where they part, the pass ran on the same ids and
    what the engine revealed is what this reference could have."""
    _, reqs, _ = served_sharp
    n_prompt, n, group = CASES[case]
    params, steps = sharp[group].params, GROUPS[group][1]
    req = reqs[case]
    assert GROUPS[group][0] in BLOCK_STRATEGIES
    toks, lps, passes = ref.generate(_hf(group), params, req.prompt, n)
    key = lambda p, first: (p["block"] - first, p["pass"], p["stored"],  # noqa: E731
                            p["revealed"].tolist(), p["ids"].tolist())
    mine = [key(p, 0) for p in req.passes]
    theirs = [key(p, n_prompt // B) for p in passes]
    same = next((i for i, (a, b) in enumerate(zip(mine, theirs)) if a != b),
                len(mine))
    if same == len(mine):  # the request ends inside its last block
        assert toks == req.out_tokens
        np.testing.assert_allclose(lps, req.out_logprobs,
                                   atol=LOGPROB_ATOL["sharp"])
        return
    assert mine[same][:3] == theirs[same][:3]
    assert mine[same][4] == theirs[same][4]  # the same ids, another choice
    parted = dataclasses.replace(req, passes=[req.passes[same]])
    for _, short, _, _ in _replay_distances(parted, params, replay(), ref,
                                            steps):
        assert short <= REVEAL_TIE["sharp"]


def test_the_dynamic_strategy_reveals_one_to_four_a_pass(served_sharp):
    _, reqs, _ = served_sharp
    shown = [int(p["revealed"].sum()) for r, c in zip(reqs, CASES)
             if c[2] == "dynamic" for p in r.passes if not p["stored"]]
    assert min(shown) == 1 and max(shown) >= 3 and len(set(shown)) >= 3


def test_under_random_weights_the_dynamic_strategy_runs_at_its_floor(served):
    """No confidence passes 0.9 where the largest of V probabilities is about
    1 / V: m_s positions a pass, like the static strategy (why the cell's
    configuration assumes the static one)."""
    _, reqs, _ = served
    for req, (_, _, group) in zip(reqs, CASES):
        if group == "dynamic":
            assert all(int(p["revealed"].sum()) <= -(-B // GROUPS[group][1])
                       for p in req.passes)


def test_rows_at_different_passes_share_a_step(served):
    _, _, spans = served
    steps = [e["args"] for e in spans if e["name"] == "decode_step"]
    assert steps and all(a["block_length"] == B for a in steps)
    # rows at two pass numbers, or a row storing beside rows denoising
    assert any(sum(c > 0 for c in a["passes"]) + (a["blocks_stored"] > 0) >= 2
               for a in steps)
    assert sum(a["tokens_emitted"] for a in steps) == sum(
        n for _, n, _ in CASES)
    assert all(a["tokens_emitted"] <= a["occupancy"] * B for a in steps)
    prefills = [e["args"] for e in spans if e["name"] == "prefill"]
    assert sorted((a["blocks_stored"], a["tail_tokens"]) for a in prefills) \
        == sorted((p // B, p % B) for p, _, _ in CASES)


def test_a_request_ends_inside_a_block(served):
    _, reqs, _ = served
    req = reqs[1]  # 13 + 9 = 22: the last block holds positions 20 .. 23
    assert len(req.out_tokens) == 9 and not req.passes[-1]["stored"]
    assert req.passes[-1]["base"] == 20


def test_no_page_leaks_and_the_counters_count(served):
    from bigdl_tpu.serving.metrics import Metrics

    engines, reqs, _ = served
    for group, eng in engines.items():
        mine = [r for r, c in zip(reqs, CASES) if c[2] == group]
        assert eng.idle() and eng.page_leaks() == 0
        assert eng.pages.radix.n_nodes == 0  # prefixes are not shared
        assert not eng.preemption  # off for the kind, whatever was passed
        sched = eng.blocks
        assert sched.tokens_emitted == sum(len(r.out_tokens) for r in mine)
        assert sched.tokens_revealed >= sched.tokens_emitted
        assert sched.blocks_stored == sum(
            sum(p["stored"] for p in r.passes) for r in mine)
    text = Metrics(engines["seq"]).render()
    for name in ("bigdl_tpu_block_passes_total",
                 "bigdl_tpu_block_tokens_revealed_total",
                 "bigdl_tpu_blocks_stored_total",
                 "bigdl_tpu_tokens_per_pass"):
        assert name in text
    # the record a reference reads: every stored position's experts
    req = reqs[0]
    n = req.prompt_experts.shape[1] + len(req.out_experts)
    assert req.prompt_experts.shape[1] == 16
    assert req.expert_ids(n).shape == (2, n, 2)


# ---- a departure is another model: each fails the comparison ------------------

def _causal():
    i, j = np.arange(T_REPLAY)[:, None], np.arange(T_REPLAY)[None]
    return jnp.asarray(j <= i)


@pytest.mark.parametrize("what", ["no_backward_direction", "shifted_by_one",
                                  "stored_from_masked_ids"])
def test_a_departure_fails_the_comparison(what, served, tiny, replay, ref):
    """A forward that drops the in-block backward direction, shifts the
    logits by one, or stores a block's K and V from masked ids is further
    from the engine than the tolerance, at its worst token."""
    _, reqs, _ = served
    fwd = {"no_backward_direction": lambda: replay(visible=_causal()),
           "shifted_by_one": lambda: replay(shift=1),
           "stored_from_masked_ids": lambda: replay()}[what]()
    worst = max(d for case in (3, 7, 9) for d, _, _, _ in _replay_distances(
        reqs[case], tiny["seq"].params, fwd, ref,
        GROUPS[CASES[case][2]][1],
        mask_stored=what == "stored_from_masked_ids"))
    assert worst > 2 * LOGPROB_ATOL["tiny"]


@pytest.mark.parametrize("which", ["tiny", "sharp"])
@pytest.mark.parametrize("asked,holds", [("seq", True), ("static2", False)])
def test_a_wrong_reveal_rule_fails_the_checks_comparison(
        asked, holds, which, served, served_sharp, tiny, sharp, ref):
    """A program that reveals the LEFTMOST mask where the static rule was
    asked. The check's replay (`ref.replay`, what `logits` runs for
    `bench/entries/engine.py:check`) holds each pass to the asked rule: it
    reveals one position where two were due (on any weights), and on the
    sharpened head one lies further than `ref.REVEAL_TIE` under the
    reference's own m_s-th best; the row returned is then uniform, and
    the logprob's distance fails the comparison. The same record held to the rule it was served under
    passes. (On `tiny`, as under any seeded random weights, the shortfall
    alone would not show it: the confidences are near-ties.)"""
    _, reqs, _ = served if which == "tiny" else served_sharp
    params = (tiny if which == "tiny" else sharp)["seq"].params
    req, n = reqs[0], 9  # 16 + 12, served sequentially; the check's 9 tokens
    toks = req.out_tokens[:n]
    n_total = len(req.prompt) + n - 1
    passes = [dict(p, experts=None) for p in req.passes]  # its own router
    plan = ref.replay_plan(passes, n_total, n, B, 2, 2)
    assert plan is not None
    hf = _hf(asked)
    rows, stats = jax.jit(lambda p, s, pl: ref.replay(hf, p, s, n, pl))(
        params, jnp.asarray(req.prompt + toks[:-1], jnp.int32),
        tuple(jnp.asarray(plan[f]) for f in ref.PLAN_FIELDS))
    rows = np.asarray(rows, np.float64)
    lse = np.log(np.exp(rows - rows.max(-1, keepdims=True)).sum(-1)) \
        + rows.max(-1)
    worst = np.abs(rows[np.arange(n), toks] - lse
                   - np.asarray(req.out_logprobs[:n])).max()
    short = float(np.asarray(stats)[:int(plan["n"][0]), 2].max())
    if holds:
        assert worst <= LOGPROB_ATOL[which]
        return
    assert worst > 10 * LOGPROB_ATOL[which]
    assert (short > ref.REVEAL_TIE) == (which == "sharp")


# ---- the two kernels against masked float32 attention (interpreted) ---------

def _masked_attention(q, k, v, visible):
    """q [T, Hq, D]; k, v [S, Hkv, D]; visible [T, S]: float32."""
    g = q.shape[1] // k.shape[1]
    k, v = np.repeat(k, g, axis=1), np.repeat(v, g, axis=1)
    s = np.einsum("qhd,khd->hqk", q, k) / np.sqrt(q.shape[-1])
    s = np.where(visible[None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("hqk,khd->qhd", p / p.sum(-1, keepdims=True), v)


@pytest.mark.parametrize("q_offset,T,S", [(0, 40, 64), (16, 24, 64),
                                          (0, 128, 128)])
def test_block_causal_flash_attention(q_offset, T, S):
    from bigdl_tpu.ops.pallas import flash_attention

    rng = np.random.default_rng(0)
    Hq, Hkv, D = 8, 2, 128
    q = rng.standard_normal((1, T, Hq, D)).astype(np.float32)
    k = rng.standard_normal((1, S, Hkv, D)).astype(np.float32)
    v = rng.standard_normal((1, S, Hkv, D)).astype(np.float32)
    as_bf = lambda a: jnp.asarray(a, jnp.bfloat16)  # noqa: E731
    got = flash_attention(
        as_bf(q), as_bf(k), as_bf(v), q_offset=jnp.asarray(q_offset),
        block_causal=B, block_q=16 if T < 128 else 64, block_k=32,
        interpret=True)
    i = q_offset + np.arange(T)[:, None]
    want = _masked_attention(
        *(np.asarray(as_bf(a), np.float32)[0] for a in (q, k, v)),
        np.arange(S)[None] // B <= i // B)
    np.testing.assert_allclose(np.asarray(got, np.float32)[0], want,
                               atol=2e-2)
    # and it is not the causal kernel: the in-block backward direction counts
    causal = flash_attention(
        as_bf(q), as_bf(k), as_bf(v), q_offset=jnp.asarray(q_offset),
        block_q=16 if T < 128 else 64, block_k=32, interpret=True)
    assert np.abs(np.asarray(causal, np.float32)[0] - want).max() > 0.1


@pytest.mark.parametrize("Hq,Hkv", [(8, 2), (32, 4)])
def test_a_blocks_queries_are_rows_of_one_paged_dot(Hq, Hkv):
    from bigdl_tpu.ops.pallas import paged_block_attention

    rng = np.random.default_rng(0)
    D, page, n_pages, L = 128, 16, 12, 2
    rows = 3
    pool_k = rng.standard_normal((L, n_pages, page, Hkv, D)).astype(np.float32)
    pool_v = rng.standard_normal((L, n_pages, page, Hkv, D)).astype(np.float32)
    tables = np.asarray([[3, 5, 1, 0], [7, 2, 0, 0], [0, 0, 0, 0]], np.int32)
    pos = np.asarray([36, 12, 0], np.int32)  # the blocks' first slots
    q = rng.standard_normal((rows, B, Hq, D)).astype(np.float32)
    as_bf = lambda a: jnp.asarray(a, jnp.bfloat16)  # noqa: E731
    got = np.asarray(paged_block_attention(
        as_bf(q), as_bf(pool_k), as_bf(pool_v), jnp.asarray(tables),
        jnp.asarray(1), jnp.asarray(pos), jnp.zeros((rows,), jnp.int32),
        live=jnp.asarray([True, True, False]), interpret=True), np.float32)
    f32 = lambda a: np.asarray(as_bf(a), np.float32)  # noqa: E731
    for r in range(2):
        keys = f32(pool_k)[1][tables[r]].reshape(-1, Hkv, D)
        vals = f32(pool_v)[1][tables[r]].reshape(-1, Hkv, D)
        S = keys.shape[0]
        # every query of the block sees slots 0 .. the block's last
        visible = np.broadcast_to(np.arange(S)[None] <= pos[r] + B - 1, (B, S))
        want = _masked_attention(f32(q)[r], keys, vals, visible)
        np.testing.assert_allclose(got[r], want, atol=2e-2)
    assert not got[2].any()  # an idle row costs nothing and reads zeros


def test_the_kernels_serve_what_the_reference_computes(tiny, replay, ref,
                                                       monkeypatch):
    """The same requests with the kernels on (interpreted): flash under the
    block mask in the prefill, the paged kernel's b x G rows in a pass."""
    from bigdl_tpu.ops.routes import record_routes

    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    cases = [CASES[2], CASES[3], CASES[5]]  # static x2, the cell's
    donor = tiny["static2"]
    with record_routes() as routes:
        model = TpuModel(donor.config, donor.params, "sym_int4")
        # untraced: an admission then waits for nothing
        engines, reqs, _ = _serve({"static2": model}, cases, trace=False)
    noted = {(op, route): detail for op, route, detail in routes}
    assert any(op == "attention" and route == "pallas:paged"
               and "block of 4: 8 rows a KV head" in d
               for (op, route), d in noted.items())
    assert any(op == "flash" and "causal by blocks of 4" in d
               for (op, _), d in noted.items())
    assert any(op == "blocks" and "store own pass" in d
               for (op, _), d in noted.items())
    assert any(op == "blocks" and "low_confidence_static x2" in d
               for (op, _), d in noted.items())
    for req, case in zip(reqs, (2, 3, 5)):
        assert len(req.out_tokens) == CASES[case][1]
        dist = _replay_distances(req, donor.params, replay(), ref, 2)
        assert max(d for d, _, _, _ in dist) <= LOGPROB_ATOL["tiny"]
    assert engines["static2"].page_leaks() == 0


# ---- what is refused, by name -------------------------------------------------

@pytest.mark.parametrize("kw,match", [
    (dict(speculative=True), "speculative serving is not wired"),
    (dict(quantize_kv=True), "quantize_kv is not wired"),
    (dict(adapters=object()), "adapter serving is not wired"),
    (dict(prefill_chunk_tokens=16), "prefill_chunk_tokens is not wired"),
    (dict(logprobs_top_k=3), "logprobs_top_k is not wired"),
    (dict(paged=False), "served from pages"),
    (dict(page_size=6), "multiples of block_length"),
    (dict(gen=GenerationConfig(repetition_penalty=1.2)),
     "repetition penalty is not wired"),
])
def test_the_refusals_name_the_kind(tiny, kw, match):
    opts = dict(n_slots=2, max_len=64, paged=True, page_size=16)
    opts.update(kw)
    with pytest.raises((NotImplementedError, ValueError), match=match):
        InferenceEngine(tiny["static2"], **opts)


def test_a_request_is_refused_by_name_and_generate_never_decodes(tiny,
                                                                 served):
    engines, reqs, _ = served
    eng, model = engines["seq"], tiny["seq"]
    bad = eng.submit([1, 2, 3], max_new_tokens=4, repetition_penalty=1.3)
    assert bad.finish_reason == "invalid" and "repetition penalty" in bad.error
    with pytest.raises(NotImplementedError, match="preemption inside a block"):
        eng.preempt(reqs[0])
    with pytest.raises(ValueError, match="remasking_strategy"):
        InferenceEngine(TpuModel(dataclasses.replace(
            model.config, remasking_strategy="random"), model.params,
            "sym_int4"), n_slots=2, max_len=64, paged=True, page_size=16)
    for call in (model.generate, model.generate_lookup,
                 model.generate_speculative):
        with pytest.raises(NotImplementedError, match="diffusion over blocks"):
            call([[1, 2, 3]], max_new_tokens=4)


# ---- the configuration ------------------------------------------------------

def test_the_family_defaults_and_a_config_that_says_otherwise():
    hf = {k: v for k, v in HF.items() if k != "mask_token_id"}
    cfg = ModelConfig.from_hf_config(dict(hf, vocab_size=151936))
    assert (cfg.block_length, cfg.denoising_steps, cfg.remasking_strategy,
            cfg.confidence_threshold, cfg.mask_token_id) == (
        4, 4, "low_confidence_dynamic", 0.9, 151669)
    assert cfg.qk_norm and cfg.norm_topk_prob and cfg.is_moe
    cfg = ModelConfig.from_hf_config(dict(
        HF, block_length=8, denoising_steps=2,
        remasking_strategy="low_confidence_static"))
    assert (cfg.block_length, cfg.denoising_steps) == (8, 2)
    with pytest.raises(ValueError, match="outside the vocabulary"):
        ModelConfig.from_hf_config(hf)
    with pytest.raises(ValueError, match="remasking_strategy"):
        ModelConfig.from_hf_config(dict(HF, remasking_strategy="random"))
    # an autoregressive model has no block: nothing of the path is taken
    assert ModelConfig.from_hf_config(
        dict(HF, model_type="qwen3_moe")).block_length == 0


def test_hf_names_map_onto_the_qwen3_moe_tree():
    from bigdl_tpu.convert import hf as convert

    assert convert._FAMILY_LAYER["sdar_moe"] \
        is convert._FAMILY_LAYER["qwen3_moe"]
