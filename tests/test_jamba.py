"""Jamba (`jamba`): Mamba-1 layers (a selective scan whose decay differs by
channel and state index, the state `[N, E]` with the channels on lanes) and a
multi-query NoPE attention layer among them, a state row beside KV pages in
one engine slot (bigdl_tpu/kvhybrid.py `mix1`, models/jamba.py,
ops/pallas/selective_scan.py).

The yardstick is bench/reference/jamba.py: the float32 loop over tokens,
independent of the kernels and of every cache. float32 against float32 holds
to 2e-4 on logits of size 1; the packed model in bf16 through the engine is
held at the LOGPROB level to 0.08 nats, as granite's tests hold theirs."""

import dataclasses
import functools
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import cells  # noqa: E402
from bigdl_tpu import kvhybrid  # noqa: E402
from bigdl_tpu.api import TpuModel, optimize_model  # noqa: E402
from bigdl_tpu.models import get_family  # noqa: E402
from bigdl_tpu.models.config import PRESETS, ModelConfig  # noqa: E402
from bigdl_tpu.serving.engine import InferenceEngine  # noqa: E402
from engines import shared_engine  # noqa: E402

pytestmark = pytest.mark.core

CFG = PRESETS["tiny-jamba"]
# the preset as a config.json (what the reference reads)
HF = dict(
    model_type="jamba", vocab_size=256, hidden_size=128,
    intermediate_size=256, num_hidden_layers=4, attn_layer_period=4,
    attn_layer_offset=2, expert_layer_period=2, expert_layer_offset=1,
    num_attention_heads=4, num_key_value_heads=1, num_experts=1,
    num_experts_per_tok=1, mamba_expand=2, mamba_d_state=16, mamba_d_conv=4,
    mamba_dt_rank=8, mamba_conv_bias=True, mamba_proj_bias=False,
    tie_word_embeddings=True, rms_norm_eps=1e-6, sliding_window=None,
    num_logits_to_keep=1, use_mamba_kernels=True, hidden_act="silu")
E, N, R = 256, 16, 8


@pytest.fixture(scope="module")
def fam():
    return get_family("jamba")


@pytest.fixture(scope="module")
def ref():
    return cells.load_module(ROOT, "reference", "jamba")


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


@functools.lru_cache(maxsize=None)
def _jitted(ref, state_dtype):
    return jax.jit(functools.partial(ref.logits, state_dtype=state_dtype),
                   static_argnums=(0, 3))


def _ref_logits(ref, p, seq, n_last, state_dtype=jnp.float32):
    from bench.records import Frozen

    return np.asarray(_jitted(ref, state_dtype)(
        Frozen(HF), p, jnp.asarray(seq, jnp.int32), n_last))


def _cache(fam, rows=1, n=64):
    """A cache whose pages are float32 too (the pool's bfloat16 keys alone
    move a logit of size 1 by 4e-3)."""
    c = fam.init_cache(CFG, rows, n)
    return dataclasses.replace(c, k=c.k.astype(jnp.float32),
                               v=c.v.astype(jnp.float32))


@functools.partial(jax.jit, static_argnums=(0, 4))
def _f32(fam, p, toks, cache, mode="prefill"):
    return fam.forward(CFG, p, jnp.asarray(toks, jnp.int32), cache, mode=mode,
                       compute_dtype=jnp.float32)


# ---------------------------------------------------------------------------
# (f) the configuration
# ---------------------------------------------------------------------------

def test_preset_is_the_hf_config(fam):
    assert ModelConfig.from_hf_config(HF) == CFG
    assert fam.layer_runs(CFG) == [
        ("mamba", 0, 2), ("attention", 0, 1), ("mamba", 2, 1)]
    assert fam.dims(CFG) == (E, N, R)


def test_the_catalog_rows_config_gives_the_published_layers():
    hf = cells.load_json(ROOT, "bench", "configs",
                         "jamba2-3b-int4.json")["published"]
    cfg = ModelConfig.from_hf_config(hf)
    kinds = cfg.layer_types
    assert len(kinds) == 28 and [i for i, k in enumerate(kinds)
                                 if k == "attention"] == [7, 21]
    assert get_family("jamba").layer_runs(cfg) == [
        ("mamba", 0, 7), ("attention", 0, 1), ("mamba", 7, 13),
        ("attention", 1, 1), ("mamba", 20, 6)]
    assert get_family("jamba").dims(cfg) == (5120, 16, 160)
    assert (cfg.num_key_value_heads, cfg.head_dim_, cfg.vocab_size) == (
        1, 128, 65536)
    assert cfg.tie_word_embeddings and cfg.rms_norm_eps == 1e-6
    assert ModelConfig.from_hf_config(
        dict(hf, mamba_dt_rank="auto")).mamba_dt_rank == 160


@pytest.mark.parametrize("key,value", [
    ("num_experts", 16), ("mamba_proj_bias", True), ("sliding_window", 4096)])
def test_what_the_translator_refuses_by_name(key, value):
    with pytest.raises(NotImplementedError, match=key):
        ModelConfig.from_hf_config(dict(HF, **{key: value}))


def test_importing_the_package_loads_neither_family_nor_kernel():
    import subprocess

    code = ("import sys, bigdl_tpu, bigdl_tpu.api, bigdl_tpu.serving.engine;"
            "bad = [m for m in sys.modules if m.endswith(('models.jamba',"
            " 'pallas.selective_scan'))]; assert not bad, bad")
    subprocess.run([sys.executable, "-c", code], check=True, cwd=ROOT,
                   env=dict(os.environ, JAX_PLATFORMS="cpu"))


# ---------------------------------------------------------------------------
# (a) forward against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 3, 4, 5, 19, 40])
def test_forward_matches_the_loop_over_tokens(fam, ref, dense, n):
    """Prompt lengths around the convolution's width and beyond."""
    toks = _tokens(n, 3 + n)
    got, _ = _f32(fam, dense, toks[None], _cache(fam))
    want = _ref_logits(ref, dense, toks, n)
    assert np.abs(want).max() > 0.3
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=2e-4)


def test_a_bfloat16_scan_is_not_the_reference(ref, dense):
    """The control: the scan and the state in bfloat16 miss the float32
    loop by far more than the tests' bound."""
    toks = _tokens(40, 43)
    want = _ref_logits(ref, dense, toks, 40)
    low = _ref_logits(ref, dense, toks, 40, jnp.bfloat16)
    assert np.abs(low - want).max() > 10 * 2e-4
    ids = jnp.asarray(toks, jnp.int32)
    _, s32 = ref.hidden(HF, dense, ids)
    _, s16 = ref.hidden(HF, dense, ids, state_dtype=jnp.bfloat16)
    assert np.abs(np.asarray(s16, np.float32) - np.asarray(s32)).max() > 1e-3


def test_prefill_hands_over_to_decode(fam, ref, dense):
    toks = _tokens(21, 5)
    cache = _cache(fam)
    out, cache = _f32(fam, dense, toks[None, :13], cache)
    outs = [out]
    for t in range(13, 21):
        o, cache = _f32(fam, dense, toks[None, t:t + 1], cache, "decode")
        outs.append(o)
    got = np.asarray(jnp.concatenate(outs, axis=1)[0])
    np.testing.assert_allclose(got, _ref_logits(ref, dense, toks, 21),
                               atol=2e-4)
    assert int(cache.pos[0]) == 21
    # the state the cache holds is the reference's after the last token
    _, states = ref.hidden(HF, dense, jnp.asarray(toks, jnp.int32))
    np.testing.assert_allclose(np.asarray(cache.ssm[:, 0]),
                               np.asarray(states), atol=1e-5)


# ---------------------------------------------------------------------------
# (d), (g) seams and padding
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pallas", ["0", "interpret"])
def test_a_prefill_in_two_buckets_is_the_prefill_in_one(fam, dense,
                                                       monkeypatch, pallas):
    """The state and the convolution's tail cross the seam."""
    monkeypatch.setenv("BIGDL_TPU_PALLAS", pallas)
    toks = _tokens(24, 11)
    one, whole = fam.forward(CFG, dense, jnp.asarray(toks[None]),
                             _cache(fam), compute_dtype=jnp.float32)
    a, c = fam.forward(CFG, dense, jnp.asarray(toks[None, :15]),
                       _cache(fam), compute_dtype=jnp.float32)
    b, c = fam.forward(CFG, dense, jnp.asarray(toks[None, 15:]), c,
                       compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([a, b], 1)),
                               np.asarray(one), atol=2e-4)
    np.testing.assert_allclose(np.asarray(c.ssm), np.asarray(whole.ssm),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(c.conv), np.asarray(whole.conv),
                               atol=1e-5)


@pytest.mark.parametrize("pallas", ["0", "interpret"])
def test_a_padded_bucket_stops_the_state_at_the_last_token(fam, dense,
                                                          monkeypatch,
                                                          pallas):
    """`valid_len`: right padding neither decays nor updates the state and
    the convolution's tail is the last REAL tokens'."""
    monkeypatch.setenv("BIGDL_TPU_PALLAS", pallas)
    n, bucket = 11, 16
    toks = _tokens(bucket, 7)
    _, plain = fam.forward(CFG, dense, jnp.asarray(toks[None, :n]),
                           fam.init_cache(CFG, 1, 64),
                           compute_dtype=jnp.float32)
    padded = dataclasses.replace(fam.init_cache(CFG, 1, 64),
                                 valid_len=jnp.asarray([n], jnp.int32))
    _, padded = fam.forward(CFG, dense, jnp.asarray(toks[None]), padded,
                            compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(padded.ssm), np.asarray(plain.ssm),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(padded.conv),
                               np.asarray(plain.conv), atol=1e-6)
    assert int(padded.pos[0]) == n and padded.valid_len is None
    assert np.abs(np.asarray(plain.ssm)).max() > 1e-3


# ---------------------------------------------------------------------------
# (c) the kernels
# ---------------------------------------------------------------------------

def _scan64(x, dt, A, Bm, Cm, h):
    """The scan of one row in float64 numpy: (y [T, E], h [N, E])."""
    x, dt, A, Bm, Cm, h = (np.asarray(a, np.float64)
                           for a in (x, dt, A, Bm, Cm, h))
    ys = []
    for t in range(x.shape[0]):
        h = np.exp(dt[t][None] * A) * h + (dt[t] * x[t])[None] * Bm[t][:, None]
        ys.append((h * Cm[t][:, None]).sum(0))
    return np.stack(ys), h


def _scan_inputs(key, lead, width):
    k = jax.random.split(key, 5)
    x = jax.random.normal(k[0], lead + (width,))
    dt = jax.nn.softplus(jax.random.normal(k[1], lead + (width,)) - 1)
    A = -jnp.exp(jax.random.normal(k[2], (N, width)))
    Bm, Cm = jax.random.normal(k[3], (2,) + lead + (N,))
    return x, dt, A, Bm, Cm


@pytest.mark.parametrize("B,width,live", [
    (4, 256, [True, False, True, True]),
    (3, 1024, [False, True, False]),
    (2, 128, [False, False]),
])
def test_mamba1_decode_against_its_jnp_form(B, width, live):
    from bigdl_tpu.ops.pallas.selective_scan import mamba1_decode

    key = jax.random.PRNGKey(B)
    Rw = B + 1
    ssm = jax.random.normal(key, (2, Rw, N, width), jnp.float32)
    x, dt, A, Bm, Cm = _scan_inputs(jax.random.fold_in(key, 1), (B,), width)
    rows = jnp.asarray([Rw - 1 - i for i in range(B)], jnp.int32)
    live = jnp.asarray(live)
    y, out = mamba1_decode(ssm, jnp.int32(1), rows, live, x, dt, A, Bm, Cm,
                           interpret=True)
    want_y, h = kvhybrid.scan1(x[:, None], dt[:, None], A, Bm[:, None],
                               Cm[:, None], ssm[1, rows])
    want = ssm.at[1, jnp.where(live, rows, Rw)].set(h, mode="drop")
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-6)
    idle = np.asarray(jnp.where(live, Rw, rows))  # rows nobody may touch
    np.testing.assert_array_equal(np.asarray(out[1])[idle[idle < Rw]],
                                  np.asarray(ssm[1])[idle[idle < Rw]])
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(ssm[0]))
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(jnp.where(live[:, None], want_y[:, 0], 0)),
        atol=1e-5)
    for b in np.nonzero(np.asarray(live))[0]:  # and a float64 scan
        y64, h64 = _scan64(x[b][None], dt[b][None], A, Bm[b][None],
                           Cm[b][None], ssm[1, rows[b]])
        np.testing.assert_allclose(np.asarray(out[1, rows[b]]), h64,
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(y[b]), y64[0], atol=1e-4)


@pytest.mark.parametrize("T,n_valid,width,fresh", [
    (16, 11, 256, True), (24, 24, 128, False), (136, 130, 256, False),
    (256, 100, 512, True)])
def test_mamba1_prefill_against_its_jnp_form(T, n_valid, width, fresh):
    """A bucket with `n_valid` inside it, from zero and from the row's own
    state; more tokens than one block; a block wholly past the tokens."""
    from bigdl_tpu.ops.pallas.selective_scan import mamba1_prefill

    key = jax.random.PRNGKey(T)
    ssm = jax.random.normal(key, (2, 3, N, width), jnp.float32)
    x, dt, A, Bm, Cm = _scan_inputs(jax.random.fold_in(key, 1), (T,), width)
    dt = jnp.where(jnp.arange(T)[:, None] < n_valid, dt, 0.0)
    y, out = mamba1_prefill(ssm, jnp.int32(1), jnp.int32(2),
                            jnp.asarray(fresh), jnp.int32(n_valid), x, dt, A,
                            Bm, Cm, interpret=True)
    h0 = jnp.zeros((N, width)) if fresh else ssm[1, 2]
    want_y, h = kvhybrid.scan1(x[None], dt[None], A, Bm[None], Cm[None],
                               h0[None])
    np.testing.assert_allclose(np.asarray(out[1, 2]), np.asarray(h[0]),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(y[:n_valid]),
                               np.asarray(want_y[0, :n_valid]), atol=1e-4)
    untouched = np.asarray(out).copy()
    untouched[1, 2] = np.asarray(ssm[1, 2])
    np.testing.assert_array_equal(untouched, np.asarray(ssm))
    y64, h64 = _scan64(x[:n_valid], dt[:n_valid], A, Bm[:n_valid],
                       Cm[:n_valid], h0)
    np.testing.assert_allclose(np.asarray(out[1, 2]), h64, atol=2e-4)
    np.testing.assert_allclose(np.asarray(y[:n_valid]), y64, atol=2e-3)


# ---------------------------------------------------------------------------
# (b), (e) the engine
# ---------------------------------------------------------------------------

def _check_request(ref, params, req, atol=0.08):
    seq = list(req.prompt) + list(req.out_tokens[:-1])
    n = len(req.out_tokens)
    logits = _ref_logits(ref, params, seq, n).astype(np.float64)
    lse = np.log(np.exp(logits - logits.max(-1, keepdims=True)).sum(-1)) \
        + logits.max(-1)
    want = logits[np.arange(n), req.out_tokens] - lse
    np.testing.assert_allclose(np.asarray(req.out_logprobs), want, atol=atol)


@pytest.mark.parametrize("pallas", ["0", "interpret"])
def test_engine_serves_state_beside_pages(model, ref, params, monkeypatch,
                                          pallas):
    """Two requests in flight and a third that reuses a slot, on the XLA
    route and with the kernels through the interpreter: every logprob
    against the reference, a reused row starts from zero, nothing leaks."""
    monkeypatch.setenv("BIGDL_TPU_PALLAS", pallas)
    eng = shared_engine(model, n_slots=2, max_len=64, paged=True,
                        page_size=8)
    assert eng.kind is kvhybrid.CACHE_KIND
    assert eng.cache.ssm.shape == (3, 2, N, E)
    assert eng.cache.k.shape[0] == 1 and eng.cache.k.shape[3] == 1
    assert eng.cache.conv.shape == (3, 2, 3 * E)
    reqs = [eng.submit(_tokens(n, 10 + n).tolist(), max_new_tokens=m)
            for n, m in ((19, 7), (6, 4), (30, 6))]
    eng.run_until_idle()
    for r in reqs:
        assert r.finish_reason == "length", (r.finish_reason, r.error)
        assert len(r.out_tokens) == r.max_new_tokens
        assert np.all(np.isfinite(r.out_logprobs))
        _check_request(ref, params, r)
    assert len({tuple(r.out_tokens) for r in reqs}) == 3
    assert eng.page_leaks() == 0
    assert eng.pages.radix.n_nodes == 0 and eng.pages.prefix_hits == 0
    again = eng.submit(list(reqs[0].prompt), max_new_tokens=7)
    eng.run_until_idle()
    assert again.out_tokens == reqs[0].out_tokens
    assert eng.pages.prefix_hits == 0


def test_an_idle_slots_row_is_unchanged_and_rows_do_not_mix(
        model, monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    eng = shared_engine(model, n_slots=3, max_len=64, paged=True,
                        page_size=8)
    eng.cache = dataclasses.replace(
        eng.cache, ssm=eng.cache.ssm.at[:, 2].set(7.0),
        conv=eng.cache.conv.at[:, 2].set(7.0))
    a = eng.submit(_tokens(12, 1).tolist(), max_new_tokens=5)
    b = eng.submit(_tokens(9, 2).tolist(), max_new_tokens=5)
    eng.run_until_idle()
    assert np.all(np.asarray(eng.cache.ssm[:, 2]) == 7.0)  # never held
    assert np.all(np.asarray(eng.cache.conv[:, 2]) == 7.0)
    alone = shared_engine(model, n_slots=3, max_len=64, paged=True,
                          page_size=8)
    a2 = alone.submit(list(a.prompt), max_new_tokens=5)
    alone.run_until_idle()
    assert a2.out_tokens == a.out_tokens and a2.out_logprobs == a.out_logprobs
    assert b.finish_reason == "length"


def test_engine_chunked_prefill_continues_from_the_row(model, ref, params,
                                                      monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    eng = shared_engine(model, n_slots=2, max_len=64, paged=True,
                        page_size=8, prefill_chunk_tokens=12)
    r = eng.submit(_tokens(30, 21).tolist(), max_new_tokens=5)
    eng.run_until_idle()
    assert eng.prefill_chunks == 3
    _check_request(ref, params, r)


def test_park_and_resume_carries_pages_and_row(model):
    prompt = _tokens(20, 31).tolist()
    plain = shared_engine(model, n_slots=2, max_len=64, paged=True,
                          page_size=8)
    want = plain.submit(prompt, max_new_tokens=10)
    plain.run_until_idle()
    eng = shared_engine(model, n_slots=2, max_len=64, paged=True,
                        page_size=8)
    other = eng.submit(_tokens(10, 32).tolist(), max_new_tokens=10)
    r = eng.submit(prompt, max_new_tokens=10)
    for _ in range(4):
        eng.step()
    eng.preempt(r)
    eng._reap_preempt_requests()  # the head of the next step: parks it
    assert eng.preemptions == 1 and eng.pages.slot_pages[1] == []
    parked = eng._preempted[0].blob
    assert parked.ssm.shape == (3, N, E) and parked.conv.shape == (3, 3 * E)
    assert parked.nbytes == eng.state_row_bytes + \
        parked.k.nbytes + parked.v.nbytes
    eng.run_until_idle()
    assert eng.preemption_resumes == 1
    assert r.out_tokens == want.out_tokens
    assert r.out_logprobs == want.out_logprobs  # bit-equal, not close
    assert other.finish_reason == "length" and eng.page_leaks() == 0


def test_the_engine_thread_serves_submit(model):
    """Through `_EngineThread`, what `bigdl-tpu serve --paged` runs."""
    from bigdl_tpu.serving.api_server import _EngineThread

    eng = shared_engine(model, n_slots=2, max_len=64, paged=True,
                        page_size=8)
    thread = _EngineThread(eng)
    thread.start()
    try:
        reqs = [eng.submit(_tokens(n, 50 + n).tolist(), max_new_tokens=m)
                for n, m in ((14, 6), (5, 9), (22, 3))]
        deadline = time.monotonic() + 120
        while not all(r.done for r in reqs):
            assert time.monotonic() < deadline
            time.sleep(0.01)
    finally:
        thread.stop_flag.set()
        thread.join(30)
    for r in reqs:
        assert len(r.out_tokens) == r.max_new_tokens
        assert np.all(np.isfinite(r.out_logprobs))
    assert eng.page_leaks() == 0


def test_the_refusals_name_the_kind(model):
    kind = kvhybrid.KIND
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


def test_no_site_of_the_engine_asks_a_model_type():
    """The kind is the family's (`PAGED_CACHE_KIND`); the engine names a
    model type only in the sentences of its refusals."""
    import ast
    import inspect

    from bigdl_tpu.serving import engine

    tree = ast.parse(inspect.getsource(engine))
    asks = [n for n in ast.walk(tree) if isinstance(n, ast.Compare)
            and "model_type" in ast.unparse(n)]
    assert asks == []


def test_spans_counters_and_routes(model, monkeypatch):
    from bigdl_tpu.obs.tracing import TraceRecorder
    from bigdl_tpu.ops.routes import record_routes
    from bigdl_tpu.serving.metrics import Metrics, metric_drift

    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    tr = TraceRecorder(capacity=4096)
    with record_routes() as routes:
        eng = InferenceEngine(model, n_slots=2, max_len=64, paged=True,
                              page_size=8, tracer=tr)
        eng.submit(_tokens(20, 41).tolist(), max_new_tokens=3)
        eng.submit(_tokens(7, 42).tolist(), max_new_tokens=4)
        eng.run_until_idle()
    seen = {(op, route, detail.split()[-1]) for op, route, detail in routes}
    assert ("mamba1", "pallas", "decode") in seen
    assert ("mamba1", "pallas", "prefill") in seen
    assert not any(op == "mamba2" for op, _, _ in seen)
    assert any(op == "attention" and route == "pallas:paged"
               for op, route, _ in seen)
    ev = tr.events()
    steps = [e["args"] for e in ev if e["name"] == "decode_step"]
    row = eng.state_row_bytes
    assert row == 3 * (N * E + 3 * E) * 4  # 3 Mamba layers, float32
    assert steps and all(
        a["state_bytes_moved"] == 2 * a["state_rows_live"] * row
        and "live_pages" in a for a in steps)
    assert max(a["state_rows_live"] for a in steps) == 2
    assert eng.state_bytes_moved == sum(a["state_bytes_moved"] for a in steps)
    pre = {e["args"]["prompt_tokens"]: e["args"] for e in ev
           if e["name"] == "prefill"}
    assert {n: a["scan_tokens"] for n, a in pre.items()} == {20: 20, 7: 7}
    assert not any("state_chunks" in a for a in pre.values())
    text = Metrics(eng).render()
    assert "bigdl_tpu_state_rows_live 0" in text
    assert f"bigdl_tpu_state_pool_bytes {2 * row}" in text
    assert metric_drift(text, eng) == ([], [])


def test_generate_left_pads_a_batch(model, ref, params):
    """`TpuModel.generate` through `init_cache` (B > 1: the `jnp` scan):
    a row's tokens are what it gives alone."""
    prompts = [_tokens(17, 1).tolist(), _tokens(5, 2).tolist()]
    both = np.asarray(model.generate(prompts, max_new_tokens=6))
    for i, p in enumerate(prompts):
        alone = np.asarray(model.generate([p], max_new_tokens=6))[0]
        np.testing.assert_array_equal(both[i], alone)
    seq = prompts[0] + both[0][:-1].tolist()
    logits = _ref_logits(ref, params, seq, 6)
    lp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    assert np.all(lp.max(-1) - lp[np.arange(6), both[0]] < 0.08)


# ---------------------------------------------------------------------------
# checkpoints
# ---------------------------------------------------------------------------

def test_hf_names_map_onto_the_tree(fam, dense):
    """A state dict under HF's names (modeling_jamba) gives the logits of
    the tree it was written from."""
    from bigdl_tpu.convert.hf import params_from_state_dict

    sd = {"model.embed_tokens.weight": dense["embed"],
          "model.final_layernorm.weight": dense["final_norm"]}
    i = 0
    for (kind, _, n), run in zip(fam.layer_runs(CFG),
                                 dense["runs"].values()):
        for j in range(n):
            p, g = f"model.layers.{i}.", {k: v[j] for k, v in run.items()}
            sd[p + "input_layernorm.weight"] = g["attn_norm"]
            sd[p + "pre_ff_layernorm.weight"] = g["mlp_norm"]
            if kind == "mamba":
                m = p + "mamba."
                sd[m + "in_proj.weight"] = g["w_in"]
                sd[m + "out_proj.weight"] = g["w_out"]
                sd[m + "x_proj.weight"] = g["w_x"]
                sd[m + "dt_proj.weight"] = g["w_dt"]
                sd[m + "dt_proj.bias"] = g["dt_bias"]
                sd[m + "conv1d.weight"] = g["conv_w"].T[:, None, :]
                sd[m + "conv1d.bias"] = g["conv_b"]
                sd[m + "D"] = g["D"]
                sd[m + "A_log"] = jnp.log(g["a"].astype(jnp.float32)).T
                for ours, theirs in (("dt_norm", "dt_layernorm"),
                                     ("b_norm", "b_layernorm"),
                                     ("c_norm", "c_layernorm")):
                    sd[m + theirs + ".weight"] = g[ours]
            else:
                for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                                     ("wv", "v_proj"), ("wo", "o_proj")):
                    sd[p + f"self_attn.{theirs}.weight"] = g[ours]
            for ours, theirs in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                                 ("w_down", "down_proj")):
                sd[p + f"feed_forward.{theirs}.weight"] = g[ours]
            i += 1
    sd = {k: np.asarray(v) for k, v in sd.items()}
    tree = params_from_state_dict(CFG, sd.__getitem__, qtype="bf16",
                                  dtype=jnp.float32)
    assert sorted(tree["runs"]) == ["00", "01", "02"]
    assert tree["runs"]["00"]["a"].dtype == jnp.float16
    assert tree["runs"]["00"]["a"].shape == (2, N, E)
    assert tree["runs"]["00"]["conv_w"].dtype == jnp.float32
    toks = _tokens(12, 77)[None]
    got, _ = _f32(fam, tree, toks, _cache(fam))
    want, _ = _f32(fam, dense, toks, _cache(fam))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    packed = params_from_state_dict(CFG, sd.__getitem__, qtype="sym_int4")
    assert packed["lm_head"].qtype == "sym_int4"  # the tied table, packed
    assert packed["runs"]["00"]["w_in"].qtype == "sym_int4"
    assert not hasattr(packed["runs"]["00"]["w_x"], "qtype")
