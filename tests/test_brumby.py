"""Brumby: power-retention layers through `llama.forward`, the recurrent
state (`bigdl_tpu/kvstate.py`), the kernel `power_retention_decode` and the
paged engine's state rows, at a small size on seeded weights, by logits
against the benchmark's plain reference (`bench/reference/brumby.py`: the
quadratic a[t, s] form, no state, no chunks).

float32 compute where the program is held to the reference's mathematics
(the chunk seams, the hand-over from prefill to decode, the state rows); the
engine runs bfloat16 as it is served and is held by logprobs.
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import kvstate
from bigdl_tpu.api import TpuModel, optimize_model
from bigdl_tpu.models import get_family, llama
from bigdl_tpu.models.config import ModelConfig
from bigdl_tpu.serving.engine import InferenceEngine
from engines import shared_engine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

HF = dict(model_type="brumby", hidden_size=64, intermediate_size=128,
          num_hidden_layers=2, num_attention_heads=10, num_key_value_heads=2,
          head_dim=16, vocab_size=256, rms_norm_eps=1e-6, rope_theta=1e6,
          tie_word_embeddings=False, max_position_embeddings=4096)
CFG = ModelConfig.from_hf_config(HF)
FAMILY = get_family("brumby")


def _reference():
    path = os.path.join(ROOT, "bench", "reference", "brumby.py")
    spec = importlib.util.spec_from_file_location("ref_brumby_t", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _params(seed: int = 1):
    return FAMILY.init_params(CFG, jax.random.PRNGKey(seed),
                              dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return optimize_model(_params(), CFG, "sym_int4")


def _ref_logits(params, toks, n_last=None):
    toks = np.asarray(toks)
    return np.asarray(REF.logits(HF, params, jnp.asarray(toks),
                                 n_last or len(toks)))


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, CFG.vocab_size, n)


# ---- config, family, parameters ------------------------------------------

def test_config_and_family():
    assert CFG.attention_kind == "power_retention" and CFG.qk_norm
    assert CFG.retention_degree == 2 and CFG.head_dim_ == 16
    assert FAMILY.forward is llama.forward  # one forward, two kinds
    raw = _params()
    assert raw["layers"]["w_g"].shape == (2, 2, 64)
    served = optimize_model(raw, CFG, "sym_int4")
    # the gate stays dense and outside the fused projection
    assert "w_g" in served["layers"] and "wqkv" in served["layers"]
    assert not hasattr(served["layers"]["w_g"], "qtype")
    assert served["layers"]["wqkv"].shape[-2] == (10 + 2 * 2) * 16
    with pytest.raises(NotImplementedError, match="degree 3"):
        dataclasses.replace(CFG, retention_degree=3)
    with pytest.raises(ValueError, match="attention_kind"):
        dataclasses.replace(CFG, attention_kind="linear")


@pytest.mark.parametrize("D", [16, 128])
def test_phi_is_the_squared_score(D):
    u, w = jax.random.normal(jax.random.PRNGKey(D), (2, 7, D))
    got = jnp.einsum("np,mp->nm", kvstate.phi_q(u), kvstate.phi_k(w))
    want = (u @ w.T) ** 2 / D
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-5)
    assert kvstate.phi_q(u).shape[-1] == kvstate.phi_dim(D) \
        == (D // 2 + 1) * D
    # 8256 of the 8320 lanes carry a pair at D = 128; the rest are zero
    assert int((kvstate._phi_weights(D) > 0).sum()) \
        == D * (D + 1) // 2
    # a bfloat16 query's pair products are exact in two bfloat16 halves
    pq = kvstate.phi_q(u.astype(jnp.bfloat16))
    hi = pq.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (pq - hi).astype(jnp.bfloat16).astype(jnp.float32)
    np.testing.assert_array_equal(np.asarray(hi + lo), np.asarray(pq))


# ---- the model against the reference --------------------------------------

@pytest.mark.parametrize("T", [1, 16, 127, 128, 129, 200, 300])
def test_prefill_alone_matches_reference(params, T):
    """Lengths on, under and over the chunk (128) and the bucket (16)."""
    toks = _tokens(T, seed=T)
    want = _ref_logits(params, toks)
    got, _ = llama.forward(CFG, params, jnp.asarray(toks)[None], None,
                           compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=2e-5)
    state = FAMILY.init_cache(CFG, 1, 512)
    got, state = llama.forward(CFG, params, jnp.asarray(toks)[None], state,
                               mode="prefill", compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=2e-5)
    assert int(state.pos) == T


def _scaled_gates(monkeypatch, factor):
    """Gates near 1 without touching the weights: every log-gate of the
    PROGRAM and of the REFERENCE is multiplied by `factor` (log-gates of
    about -0.7 become about -0.01), through the one function both call."""
    real = jax.nn.log_sigmoid
    monkeypatch.setattr(jax.nn, "log_sigmoid", lambda x: real(x) * factor)


@pytest.mark.parametrize("n_prompt,n_new", [(150, 40), (128, 3), (131, 130)])
def test_prefill_then_decode_through_the_state(params, monkeypatch, n_prompt,
                                               n_new):
    """Memory that crosses every chunk seam and the hand-over: with
    log-gates of about -0.01 a token still weighs 0.2 after 150 more."""
    _scaled_gates(monkeypatch, 0.015)
    toks = _tokens(n_prompt + n_new, seed=n_prompt)
    want = _ref_logits(params, toks)
    state = FAMILY.init_cache(CFG, 1, 512)
    got, state = llama.forward(CFG, params, jnp.asarray(toks[:n_prompt])[None],
                               state, mode="prefill",
                               compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got[0]), want[:n_prompt], atol=3e-5)
    step = jax.jit(lambda t, s: llama.forward(
        CFG, params, t, s, mode="decode", compute_dtype=jnp.float32))
    for t in range(n_prompt, n_prompt + n_new):
        got, state = step(jnp.asarray(toks[t:t + 1])[None], state)
        np.testing.assert_allclose(np.asarray(got[0, 0]), want[t], atol=3e-5)
    # the gates really were near 1: dropping the first half of the context
    # moves the last logits
    short = _ref_logits(params, toks[len(toks) // 2:], 1)
    assert np.abs(short[0] - want[-1]).max() > 1e-3


def test_left_padded_batch_and_right_padded_bucket(params):
    """Two rows of different lengths in one forward: the padded positions
    (left of `start`, right of `valid_len`) leave the state untouched."""
    a, b = _tokens(37, 1), _tokens(20, 2)
    wa, wb = _ref_logits(params, a), _ref_logits(params, b)
    batch = np.zeros((2, 48), np.int32)
    batch[0, 48 - 37:], batch[1, 48 - 20:] = a, b
    state = dataclasses.replace(
        FAMILY.init_cache(CFG, 2, 512),
        start=jnp.asarray([48 - 37, 48 - 20], jnp.int32))
    got, state = llama.forward(CFG, params, jnp.asarray(batch), state,
                               mode="prefill", compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got[0, 48 - 37:]), wa, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got[1, 48 - 20:]), wb, atol=2e-5)
    # right padding: 20 tokens in a bucket of 32, as the engine prefills
    padded = np.zeros((1, 32), np.int32)
    padded[0, :20] = b
    one = dataclasses.replace(
        FAMILY.init_cache(CFG, 1, 512), pos=jnp.zeros((1,), jnp.int32),
        valid_len=jnp.asarray([20], jnp.int32))
    got, one = llama.forward(CFG, params, jnp.asarray(padded), one,
                             mode="prefill", compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got[0, :20]), wb, atol=2e-5)
    assert one.pos.tolist() == [20] and one.valid_len is None
    np.testing.assert_allclose(np.asarray(one.S[:, 0]),
                               np.asarray(state.S[:, 1]), atol=1e-5)


# ---- the kernel ------------------------------------------------------------

@pytest.mark.parametrize("rows", [
    [2, -1, 0, 3, -1], [-1, -1, 1, -1, -1], [-1] * 5, [0, 1, 2, 3, -1],
    [3, 2, 1, 0, -1]])
def test_power_retention_decode_kernel_against_jnp(rows):
    """The Pallas kernel in the interpreter against `kvstate._step`, at the
    served head size (D = 128, 5 query heads to a KV head): live rows are
    updated in place, idle rows (-1) cost and change nothing, in any
    arrangement of the two."""
    from bigdl_tpu.ops.pallas.power_retention import power_retention_decode

    L, R, Hkv, G, D, B = 2, 4, 2, 5, 128, 5
    P = kvstate.phi_dim(D)
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    S = jax.random.normal(ks[0], (L, R, Hkv, D, P), jnp.float32)
    z = jnp.abs(jax.random.normal(ks[1], (L, R, Hkv, 1, P))) + 1.0
    q = jax.random.normal(ks[2], (B, Hkv, G, D)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[3], (B, Hkv, D)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[4], (B, Hkv, D)).astype(jnp.bfloat16)
    g = -jnp.abs(jax.random.normal(ks[5], (B, Hkv))) * 0.3
    rows = np.asarray(rows, np.int32)
    live = rows >= 0
    y, S1, z1 = power_retention_decode(
        S, z, jnp.asarray(1), jnp.asarray(rows), jnp.asarray(live), q, k, v,
        g, interpret=True)
    at = np.clip(rows, 0, R - 1)
    yr, Sr, zr = kvstate._step(q, k, v, g, S[1, at], z[1, at, :, 0], 1e-6)
    S_want, z_want = np.array(S), np.array(z)
    for b in np.nonzero(live)[0]:
        S_want[1, rows[b]], z_want[1, rows[b], :, 0] = Sr[b], zr[b]
    np.testing.assert_array_equal(np.asarray(S1)[0], np.asarray(S)[0])
    np.testing.assert_allclose(np.asarray(S1), S_want, atol=1e-5)
    np.testing.assert_allclose(np.asarray(z1), z_want, atol=1e-5)
    want = np.where(live[:, None, None, None], np.asarray(yr), 0.0)
    # three bfloat16 passes keep about 16 bits of each factor
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-4 *
                               max(np.abs(want).max(), 1.0))


def test_kernel_route_matches_xla_route_in_the_model(params, monkeypatch):
    """A decode step through the kernel (interpreted) and through `jnp`,
    from the same state: the layer alone held tightly, then the whole
    `llama.forward` (whose projections change route with the switch too)."""
    from bigdl_tpu.ops.routes import record_routes

    toks = _tokens(40, 9)
    state = FAMILY.init_cache(CFG, 2, 512)
    _, state = llama.forward(
        CFG, params, jnp.asarray(np.stack([toks[:32], toks[8:]])), state,
        mode="prefill", compute_dtype=jnp.float32)
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(ks[0], (2, 1, 10, 16)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (2, 1, 2, 16)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (2, 1, 2, 16)).astype(jnp.bfloat16)
    g = -jnp.abs(jax.random.normal(ks[3], (2, 1, 2)))
    args = (state, jnp.asarray(1), q, k, v, g, jnp.ones((2, 1), bool), 1e-6)
    cur = jnp.asarray([[5], [7]], jnp.int32)
    y_xla, s_xla = kvstate.attend(*args, decode=True)
    want, _ = llama.forward(CFG, params, cur, state, mode="decode",
                            compute_dtype=jnp.float32)
    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    y_krn, s_krn = kvstate.attend(*args, decode=True)
    np.testing.assert_allclose(np.asarray(y_krn), np.asarray(y_xla),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_krn.S), np.asarray(s_xla.S),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(s_krn.z), np.asarray(s_xla.z),
                               atol=1e-6)
    with record_routes() as routes:
        got, _ = llama.forward(CFG, params, cur, state, mode="decode",
                               compute_dtype=jnp.float32)
    assert any(r == "pallas:retention" for _, r, _ in routes), routes
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-3)


# ---- the library loop ------------------------------------------------------

def test_generate_matches_reference_greedy(params):
    """`TpuModel.generate` (the family's cache hook, as RWKV): every greedy
    token is the reference's best, or trails it by a rounding."""
    model = TpuModel(CFG, params, "sym_int4")
    prompts = [_tokens(23, 4).tolist(), _tokens(9, 5).tolist()]
    out = model.generate(prompts, max_new_tokens=8)
    for p, toks in zip(prompts, out.tolist()):
        seq = list(p)
        for t in toks:
            row = _ref_logits(params, seq, 1)[0]
            assert row.max() - row[t] < 0.05, (t, int(row.argmax()))
            seq.append(t)


# ---- the engine ------------------------------------------------------------

@pytest.fixture(scope="module")
def model(params):
    return TpuModel(CFG, params, "sym_int4")


def _check_request(params, req, atol=0.08):
    """The engine's chosen-token logprobs against the reference's
    log-softmax over the same sequence (what the benchmark's check does)."""
    seq = list(req.prompt) + list(req.out_tokens[:-1])
    n = len(req.out_tokens)
    logits = _ref_logits(params, seq, n).astype(np.float64)
    lse = np.log(np.exp(logits - logits.max(-1, keepdims=True)).sum(-1)) \
        + logits.max(-1)
    want = logits[np.arange(n), req.out_tokens] - lse
    np.testing.assert_allclose(np.asarray(req.out_logprobs), want, atol=atol)


def test_engine_serves_from_state_rows(model, params):
    """`shared_engine(paged=True, page_size=, n_pages=)` as the benchmark
    builds it: two requests of different lengths in flight, a third that
    reuses a slot, every logprob against the reference, nothing leaked."""
    eng = shared_engine(model, n_slots=2, max_len=256, paged=True,
                        page_size=64, n_pages=9)
    assert eng.pages.page_size == 256 and eng.pages.max_pages_per_row == 1
    assert eng.cache.S.shape[1] == 2  # a row a slot, none for scratch
    a = eng.submit(_tokens(70, 11).tolist(), max_new_tokens=12)
    b = eng.submit(_tokens(33, 12).tolist(), max_new_tokens=5)
    c = eng.submit(_tokens(150, 13).tolist(), max_new_tokens=9)
    eng.run_until_idle()
    for r in (a, b, c):
        assert r.finish_reason == "length", (r.finish_reason, r.error)
        _check_request(params, r)
    assert eng.page_leaks() == 0 and eng.pages.pool.n_free == 2
    assert eng.pages.radix.n_nodes == 0 and eng.pages.prefix_hits == 0
    # the same prompt again shares nothing and gives the same tokens
    again = eng.submit(list(a.prompt), max_new_tokens=12)
    eng.run_until_idle()
    assert again.out_tokens == a.out_tokens
    assert eng.pages.prefix_hits == 0 and eng.pages.prefix_tokens_reused == 0


def test_engine_chunked_prefill_continues_from_the_row(model, params):
    eng = shared_engine(model, n_slots=2, max_len=256, paged=True,
                        prefill_chunk_tokens=48)
    r = eng.submit(_tokens(130, 21).tolist(), max_new_tokens=6)
    eng.run_until_idle()
    assert eng.prefill_chunks == 3
    _check_request(params, r)


def test_engine_max_len_bounds_a_request(model):
    """`max_len` bounds a request as it does where there are pages: an
    over-long prompt is refused, or cut to its tail where the engine is
    told to."""
    eng = shared_engine(model, n_slots=1, max_len=48, paged=True)
    r = eng.submit(_tokens(60, 3).tolist(), max_new_tokens=8)
    assert r.finish_reason == "invalid" and "max_len 48" in r.error
    eng = shared_engine(model, n_slots=1, max_len=48, paged=True,
                        truncate_prompts=True)
    r = eng.submit(_tokens(60, 3).tolist(), max_new_tokens=8)
    eng.run_until_idle()
    assert len(r.prompt) == 40 and len(r.out_tokens) == 8
    assert r.finish_reason == "length" and eng.page_leaks() == 0


def test_park_and_resume_is_bit_equal(model):
    prompt = _tokens(50, 31).tolist()
    plain = shared_engine(model, n_slots=2, max_len=128, paged=True)
    want = plain.submit(prompt, max_new_tokens=14)
    plain.run_until_idle()
    eng = shared_engine(model, n_slots=2, max_len=128, paged=True)
    other = eng.submit(_tokens(20, 32).tolist(), max_new_tokens=14)
    r = eng.submit(prompt, max_new_tokens=14)
    for _ in range(4):
        eng.step()
    row = eng.pages.slot_pages[1][0] - 1
    before = np.asarray(eng.cache.S[:, row]).copy()
    eng.preempt(r)
    eng._reap_preempt_requests()  # the head of the next step: parks it
    assert eng.preemptions == 1 and eng.pages.slot_pages[1] == []
    parked = eng._preempted[0].blob
    np.testing.assert_array_equal(parked.S[:, 0], before)
    assert parked.nbytes == eng.state_row_bytes
    eng.run_until_idle()
    assert eng.preemption_resumes == 1
    assert r.out_tokens == want.out_tokens
    assert r.out_logprobs == want.out_logprobs  # bit-equal, not close
    assert other.finish_reason == "length" and eng.page_leaks() == 0


def test_the_three_refusals(model):
    kind = "power_retention"
    with pytest.raises(NotImplementedError, match=f"quantize_kv.*{kind}"):
        shared_engine(model, n_slots=1, max_len=64, paged=True,
                      quantize_kv=True)
    with pytest.raises(NotImplementedError, match=f"speculative.*{kind}"):
        shared_engine(model, n_slots=1, max_len=64, paged=True,
                      speculative=True)
    with pytest.raises(NotImplementedError, match=f"{kind}.*paged=True"):
        shared_engine(model, n_slots=1, max_len=64)
    with pytest.raises(NotImplementedError, match=f"quantize_kv.*{kind}"):
        model.generate([[1, 2, 3]], max_new_tokens=2, quantize_kv=True)


def test_spans_counters_and_admission_compiles_nothing(model):
    from bigdl_tpu.obs.tracing import TraceRecorder
    from bigdl_tpu.serving.metrics import Metrics, metric_drift

    tr = TraceRecorder(capacity=4096)
    eng = InferenceEngine(model, n_slots=2, max_len=256, paged=True,
                          tracer=tr)
    eng.submit(_tokens(40, 41).tolist(), max_new_tokens=3)
    eng.run_until_idle()  # warms bucket 48, the decode step, the sampler
    before = sum(eng.retraces.values())
    eng.submit(_tokens(200, 42).tolist(), max_new_tokens=3)  # bucket 208
    eng.submit(_tokens(45, 43).tolist(), max_new_tokens=4)  # warmed: 48
    eng.run_until_idle()
    ev = tr.events()
    steps = [e for e in ev if e["name"] == "decode_step"]
    row = eng.state_row_bytes
    L, Hkv, D = 2, 2, 16
    assert row == L * Hkv * (D + 1) * kvstate.phi_dim(D) * 4
    assert steps and all(
        e["args"]["state_bytes_moved"]
        == 2 * e["args"]["state_rows_live"] * row for e in steps)
    assert max(e["args"]["state_rows_live"] for e in steps) == 2
    assert "live_pages" not in steps[0]["args"]
    pre = {e["args"]["prompt_tokens"]: e["args"]["state_chunks"]
           for e in ev if e["name"] == "prefill"}
    assert pre == {40: 1, 200: 2, 45: 1}  # buckets 48, 208, 48
    # the last admission found every program built
    disp = [e for e in ev if e["name"] == "prefill.dispatch"]
    assert disp[-1]["args"]["retrace_s"] == 0.0
    assert sum(eng.retraces.values()) > before  # bucket 208 was new
    text = Metrics(eng).render()
    assert "bigdl_tpu_state_rows_live 0" in text
    assert f"bigdl_tpu_state_pool_bytes {2 * row}" in text
    assert metric_drift(text, eng) == ([], [])
