"""LFM2-MoE (`lfm2_moe`): gated short-convolution layers whose only state is
the convolution's tail (`kvhybrid.tail_conv`, a state row with NO recurrence
state beside KV pages in one engine slot), attention layers on KV heads of 64
kept two to a row of lanes (`ops/attention.lane_pairs`), dense layers first
and then sigmoid-routed experts with a selection bias (models/lfm2_moe.py).

The yardstick is bench/reference/lfm2_moe.py: the float32 forward over a whole
sequence, the convolution as an explicit sum of three shifted products, 4 KV
heads of 64 as the preset publishes them, independent of every cache and of
the pairs. float32 against float32 holds to 2e-4 on logits of size 1; the
packed model in bf16 through the engine is held at the LOGPROB level to 0.12
nats (granite's and jamba's tests hold theirs to 0.08 at a quarter of this
preset's hidden size). The weights are this file's
own (a convolution of size 1 / sqrt(3), projections of 0.08): at the
benchmark's drawn weights the convolution is too small for a logprob to see
(bench/configs/lfm2-24b-a2b-int4.json), so the three faults of
scripts/conv_check_sweep.py are planted HERE and must fail."""

import contextlib
import dataclasses
import functools
import importlib.util
import json
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
from bigdl_tpu.ops.attention import (attention, lane_pairs,  # noqa: E402
                                     pair_queries, unpair_context)
from bigdl_tpu.serving.engine import InferenceEngine  # noqa: E402
from engines import shared_engine  # noqa: E402

pytestmark = pytest.mark.core

CFG = PRESETS["tiny-lfm2-moe"]
# the preset as a config.json (what the reference reads)
HF = dict(
    model_type="lfm2_moe", vocab_size=256, hidden_size=512,
    intermediate_size=128, num_hidden_layers=6, num_attention_heads=8,
    num_key_value_heads=4, conv_L_cache=3, conv_bias=False, norm_eps=1e-5,
    layer_types=["conv", "full_attention", "conv", "conv", "full_attention",
                 "conv"],
    max_position_embeddings=4096, num_dense_layers=1, num_experts=8,
    num_experts_per_tok=2, moe_intermediate_size=64, norm_topk_prob=True,
    routed_scaling_factor=1.0, use_expert_bias=True,
    rope_parameters={"rope_theta": 1000000.0, "rope_type": "default"})
H, K, LC = 512, 3, 4  # hidden, taps, convolution layers


def _load(name, *path):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, *path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def fam():
    return get_family("lfm2_moe")


@pytest.fixture(scope="module")
def ref():
    return cells.load_module(ROOT, "reference", "lfm2_moe")


@pytest.fixture(scope="module")
def sweep():
    return _load("conv_check_sweep", "scripts", "conv_check_sweep.py")


@pytest.fixture(scope="module")
def dense(fam):
    """float32 weights large enough (0.08) that logits have a spread of
    about 1 and greedy tokens differ; a convolution of 1 / sqrt(3); a
    selection bias that moves choices."""
    p = fam.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32,
                        scale=0.08)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 16))
    for run in p["runs"].values():
        if "e_bias" in run:
            run["e_bias"] = 0.1 * jax.random.normal(
                next(keys), run["e_bias"].shape, jnp.float32)
    return p


@pytest.fixture(scope="module")
def params(dense):
    return optimize_model(dense, CFG, "sym_int4")


@pytest.fixture(scope="module")
def model(params):
    return TpuModel(CFG, params, "sym_int4")


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(1, 256, n)


@functools.lru_cache(maxsize=None)
def _jitted(ref):
    return jax.jit(ref.logits, static_argnums=(0, 3))


def _ref_logits(ref, p, seq, n_last):
    from bench.records import Frozen

    hf = dict(HF, layer_types=tuple(HF["layer_types"]),
              rope_parameters=Frozen(HF["rope_parameters"]))
    return np.asarray(_jitted(ref)(
        Frozen(hf), p, jnp.asarray(seq, jnp.int32), n_last))


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
# the configuration
# ---------------------------------------------------------------------------

def test_preset_is_the_hf_config(fam):
    got = ModelConfig.from_hf_config(HF)
    assert dataclasses.replace(got, moe_dispatch="dense") == CFG
    assert fam.layer_runs(CFG) == [
        ("conv", 0, 1, True), ("attention", 0, 1, False),
        ("conv", 1, 2, False), ("attention", 1, 1, False),
        ("conv", 3, 1, False)]
    assert fam.kv_layout(CFG) == (2, 128)  # 4 heads of 64 as 2 lane pairs


def test_the_catalog_rows_config_gives_the_published_layers(fam):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    published = cells.load_json(
        ROOT, "bench", "configs", "lfm2-24b-a2b-int4.json")["published"]
    if os.path.exists(catalog):  # the row's `config`, where it is at hand
        with open(catalog) as f:
            rows = [json.loads(line) for line in f]
        assert published == next(
            r["config"] for r in rows if r["name"] == "LFM2-24B-A2B")
    cfg = ModelConfig.from_hf_config(published)
    assert (cfg.num_hidden_layers, cfg.hidden_size, cfg.head_dim_) \
        == (40, 2048, 64)
    assert [i for i, k in enumerate(cfg.layer_types) if k == "attention"] \
        == [2, 6, 10, 14, 18, 22, 26, 30, 34, 38]
    assert (cfg.num_experts, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, cfg.intermediate_size,
            cfg.first_k_dense_replace) == (64, 4, 1536, 11776, 2)
    assert (cfg.conv_l_cache, cfg.rope_theta, cfg.rms_norm_eps,
            cfg.tie_word_embeddings, cfg.qk_norm) == (3, 1e6, 1e-5, True,
                                                      True)
    runs = fam.layer_runs(cfg)
    assert runs[0] == ("conv", 0, 2, True) and not any(r[3] for r in runs[1:])
    assert fam.kv_layout(cfg) == (4, 128)  # 8 heads of 64 as 4 lane pairs


@pytest.mark.parametrize("key,value,error", [
    ("conv_bias", True, NotImplementedError),
    ("rope_parameters", {"rope_type": "yarn", "factor": 4.0},
     NotImplementedError),
    ("layer_types", ["conv"] * 5, ValueError)])
def test_what_the_translator_refuses_by_name(key, value, error):
    with pytest.raises(error, match="lfm2_moe|layer_types"):
        ModelConfig.from_hf_config({**HF, key: value})


def test_importing_the_package_loads_no_family_module():
    import subprocess

    code = ("import sys, bigdl_tpu, bigdl_tpu.api, bigdl_tpu.models; "
            "print('bigdl_tpu.models.lfm2_moe' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=ROOT,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.stdout.strip() == "False", out.stderr[-400:]


# ---------------------------------------------------------------------------
# the family against the reference, by LOGITS
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 2, 3, 19, 40])
def test_forward_is_the_reference(fam, ref, dense, n):
    toks = _tokens(n, n)
    got, _ = _f32(fam, dense, toks[None], _cache(fam))
    want = _ref_logits(ref, dense, toks, n)
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=2e-4)


def test_prefill_hands_over_to_decode(fam, ref, dense):
    """19 tokens prefilled, 6 decoded one by one through the tails and the
    pages: every position's logits are the full forward's."""
    toks = _tokens(25, 3)
    c = _cache(fam)
    got, c = _f32(fam, dense, toks[None, :19], c)
    rows = [np.asarray(got[0])]
    for t in range(19, 25):
        got, c = _f32(fam, dense, toks[None, t:t + 1], c, "decode")
        rows.append(np.asarray(got[0]))
    want = _ref_logits(ref, dense, toks, 25)
    np.testing.assert_allclose(np.concatenate(rows), want, atol=2e-4)
    assert c.ssm is None and c.conv.shape == (LC, 1, (K - 1) * H)


def test_a_prefill_in_two_chunks_is_the_prefill_in_one(fam, ref, dense):
    """The tail crosses a chunk seam: 11 tokens, then 9 from `pos` 11."""
    toks = _tokens(20, 5)
    c = _cache(fam)
    a, c = _f32(fam, dense, toks[None, :11], c)
    b, c = _f32(fam, dense, toks[None, 11:], c)
    want = _ref_logits(ref, dense, toks, 20)
    np.testing.assert_allclose(
        np.concatenate([np.asarray(a[0]), np.asarray(b[0])]), want,
        atol=2e-4)


def test_a_padded_bucket_stops_the_tail_at_the_last_token(fam, ref, dense):
    """13 tokens right-padded to a bucket of 16 (`valid_len`), then decode:
    the padding leaves no trace in the tail."""
    toks = _tokens(18, 7)
    padded = np.concatenate([toks[:13], np.zeros(3, toks.dtype)])
    c = dataclasses.replace(_cache(fam), valid_len=jnp.asarray([13]))
    got, c = _f32(fam, dense, padded[None], c)
    assert int(c.pos[0]) == 13 and c.valid_len is None
    rows = [np.asarray(got[0, :13])]
    for t in range(13, 18):
        got, c = _f32(fam, dense, toks[None, t:t + 1], c, "decode")
        rows.append(np.asarray(got[0]))
    want = _ref_logits(ref, dense, toks, 18)
    np.testing.assert_allclose(np.concatenate(rows), want, atol=2e-4)


def test_two_rows_of_different_lengths_left_padded(fam, ref, dense):
    """Row 0 holds 12 tokens behind 5 of left padding, row 1 all 17: a
    position before `start` is no token for the convolution or the keys."""
    a, b = _tokens(12, 11), _tokens(17, 12)
    toks = np.stack([np.concatenate([np.zeros(5, a.dtype), a]), b])
    c = dataclasses.replace(_cache(fam, rows=2),
                            start=jnp.asarray([5, 0], jnp.int32))
    got, c = _f32(fam, dense, toks, c)
    np.testing.assert_allclose(np.asarray(got[0, 5:]),
                               _ref_logits(ref, dense, a, 12), atol=2e-4)
    np.testing.assert_allclose(np.asarray(got[1]),
                               _ref_logits(ref, dense, b, 17), atol=2e-4)
    nxt = np.asarray([[7], [9]])
    got, _ = _f32(fam, dense, nxt, c, "decode")
    for i, seq in enumerate((a, b)):
        want = _ref_logits(ref, dense, np.append(seq, nxt[i]), 1)
        np.testing.assert_allclose(np.asarray(got[i]), want, atol=2e-4)


# ---------------------------------------------------------------------------
# the convolution's tail against the explicit sum
# ---------------------------------------------------------------------------

def _explicit(g, w):
    """c_t = w[0] g_{t-2} + w[1] g_{t-1} + w[2] g_t, zeros before t = 0."""
    T = g.shape[0]
    back = np.concatenate([np.zeros((2,) + g.shape[1:], g.dtype), g])
    return sum(w[k] * back[k:k + T] for k in range(3))


def _tails(rows=3, C=128, layers=2):
    return kvhybrid.init_hybrid(1, layers, 9, 8, 2, 128, rows, 4, C, 3, None,
                                conv_rows=1)


@pytest.mark.parametrize("cuts", [(7,), (4, 7), (1, 2, 3), (9,)])
def test_tail_conv_across_hand_over_and_seams(cuts):
    """A sequence of 9 through layer 1 of row 1: prefill chunks that end at
    `cuts`, then decode steps, against the explicit sum over all 9."""
    rng = np.random.default_rng(0)
    g = rng.standard_normal((9, 128)).astype(np.float32)
    w = rng.standard_normal((3, 128)).astype(np.float32)
    c = dataclasses.replace(
        _tails(), block_tables=jnp.asarray([[0] * 4, [1, 2, 0, 0], [0] * 4]))
    c = dataclasses.replace(c, conv=c.conv + 5.0)  # a last holder's rubbish
    one = dataclasses.replace(c, block_tables=c.block_tables[1:2],
                              pos=c.pos[1:2], start=c.start[1:2],
                              rows=jnp.asarray([1]))
    out, at = [], 0
    for end in cuts:
        y, one = kvhybrid.tail_conv(one, 1, jnp.asarray(g[None, at:end]),
                                    jnp.asarray(w), decode=False)
        one = kvhybrid.advance(one, end - at)
        out.append(np.asarray(y[0]))
        at = end
    c = dataclasses.replace(c, conv=one.conv, pos=c.pos.at[1].set(at))
    for t in range(at, 9):  # decode: batch row b is state row b
        step = jnp.zeros((3, 1, 128)).at[1, 0].set(g[t])
        y, c = kvhybrid.tail_conv(c, 1, step, jnp.asarray(w), decode=True)
        c = kvhybrid.advance(c, 1)
        out.append(np.asarray(y[1]))
    np.testing.assert_allclose(np.concatenate(out), _explicit(g, w),
                               atol=1e-5)
    tail = np.asarray(c.conv[1, 1]).reshape(2, 128)
    np.testing.assert_array_equal(tail, g[7:9])  # oldest first
    # idle rows and the other layer keep what they held
    assert np.all(np.asarray(c.conv[0]) == 5.0)
    assert np.all(np.asarray(c.conv[1, [0, 2]]) == 5.0)


# ---------------------------------------------------------------------------
# lane pairs against plain attention on the published heads
# ---------------------------------------------------------------------------

def test_lane_pairs_are_for_heads_of_64_in_whole_tiles():
    assert lane_pairs(8, 64) and lane_pairs(4, 64) and lane_pairs(16, 64)
    assert not lane_pairs(8, 128) and not lane_pairs(6, 64)
    assert not lane_pairs(2, 64)  # one wide head is another arm's
    assert not lane_pairs(8, 64, itemsize=4) or lane_pairs(16, 64, 4)


@pytest.mark.parametrize("half", [0, 1])
def test_pairs_give_every_query_head_its_own_kv_head(half, monkeypatch):
    """The paged decode kernel over a pool of lane pairs against plain
    attention on `[.., 8, 64]`, with keys and values that are zero but in
    ONE half of every pair: a query head of the other half reads nothing,
    and one of this half reads exactly its own head."""
    from bigdl_tpu import kvpaged
    from bigdl_tpu.ops.pallas import paged_decode_attention

    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    B, Hq, Hkv, D, page, mp = 2, 32, 8, 64, 8, 4
    rng = np.random.default_rng(half)
    S = page * mp
    k = rng.standard_normal((B, S, Hkv, D)).astype(np.float32)
    v = rng.standard_normal((B, S, Hkv, D)).astype(np.float32)
    q = rng.standard_normal((B, Hq, D)).astype(np.float32)
    pos = jnp.asarray([S - 3, 11], jnp.int32)
    both = []
    for only in (None, half):
        kk, vv = k.copy(), v.copy()
        if only is not None:  # the other half of every pair holds nothing
            kk[:, :, 1 - only::2] = 0
            vv[:, :, 1 - only::2] = 0
        cache = kvpaged.init_paged(1, B * mp + 1, page, Hkv // 2, 2 * D, B,
                                   mp)
        cache = dataclasses.replace(
            cache, block_tables=1 + jnp.arange(B * mp).reshape(B, mp))
        cache = kvpaged.update_layer(
            cache, 0, jnp.asarray(kk, jnp.bfloat16).reshape(B, S, 4, 128),
            jnp.asarray(vv, jnp.bfloat16).reshape(B, S, 4, 128))
        got = unpair_context(paged_decode_attention(
            pair_queries(jnp.asarray(q, jnp.bfloat16), Hkv), cache.k,
            cache.v, cache.block_tables, jnp.asarray(0), pos, cache.start,
            scale=D ** -0.5), Hkv)
        sj = jnp.arange(S)[None, None, None, None, :]
        mask = sj <= pos[:, None, None, None, None]
        want = attention(jnp.asarray(q, jnp.bfloat16)[:, None],
                         jnp.asarray(kk, jnp.bfloat16),
                         jnp.asarray(vv, jnp.bfloat16), mask=mask)[:, 0]
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), atol=2e-2)
        both.append(np.asarray(got, np.float32).reshape(B, Hkv, 4, D))
    whole, halved = both
    # the heads of this half are untouched by the other half's absence, and
    # the other half's heads see zero values
    np.testing.assert_array_equal(halved[:, half::2], whole[:, half::2])
    assert not halved[:, 1 - half::2].any()


def test_pair_queries_and_unpair_are_inverse_on_their_halves():
    q = jnp.arange(2 * 8 * 64, dtype=jnp.float32).reshape(2, 8, 64) + 1
    p = pair_queries(q, 4)  # G = 2: heads 0,1 -> half 0; 2,3 -> half 1 ...
    assert p.shape == (2, 8, 128)
    np.testing.assert_array_equal(np.asarray(unpair_context(p, 4)),
                                  np.asarray(q))
    halves = np.asarray(p).reshape(2, 8, 2, 64)
    for h in range(8):
        own = (h // 2) % 2
        assert halves[:, h, own].all() and not halves[:, h, 1 - own].any()


# ---------------------------------------------------------------------------
# the router
# ---------------------------------------------------------------------------

def test_the_bias_chooses_and_never_weighs(fam):
    from bigdl_tpu.models import deepseek

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((5, H)), jnp.float32)
    p = {"router": jnp.asarray(0.05 * rng.standard_normal((8, H)),
                               jnp.float32),
         "e_bias": jnp.zeros((8,), jnp.float32)}
    v0, i0 = deepseek._router(CFG, x, p, norm_eps=fam.ROUTER_EPS)
    s = jax.nn.sigmoid(x @ p["router"].T)
    top = jnp.take_along_axis(s, i0, -1)
    np.testing.assert_allclose(
        np.asarray(v0), np.asarray(top / (top.sum(-1, keepdims=True) + 1e-6)),
        rtol=1e-6)
    # 1e-6, not DeepSeek's 1e-20: visible where the chosen scores are small
    tiny = {"router": p["router"] * 0 - 1.0 / H * 40, "e_bias": p["e_bias"]}
    xs = jnp.ones((1, H), jnp.float32)
    v, i = deepseek._router(CFG, xs, tiny, norm_eps=fam.ROUTER_EPS)
    sc = float(jax.nn.sigmoid(-40.0))
    np.testing.assert_allclose(np.asarray(v)[0], sc / (2 * sc + 1e-6),
                               rtol=1e-4)
    assert float(v.sum()) < 0.9 * float(deepseek._router(CFG, xs, tiny)[0]
                                        .sum())
    # a bias that lifts the worst expert into the choice: the ids change,
    # the weights are the unbiased scores of the new ids
    worst = int(jnp.argmin(s[0]))
    p1 = dict(p, e_bias=p["e_bias"].at[worst].set(10.0))
    v1, i1 = deepseek._router(CFG, x, p1, norm_eps=fam.ROUTER_EPS)
    assert worst in np.asarray(i1[0]) and worst not in np.asarray(i0[0])
    top1 = jnp.take_along_axis(s, i1, -1)
    np.testing.assert_allclose(
        np.asarray(v1),
        np.asarray(top1 / (top1.sum(-1, keepdims=True) + 1e-6)), rtol=1e-6)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

def _gap(ref, params, req):
    seq = list(req.prompt) + list(req.out_tokens[:-1])
    n = len(req.out_tokens)
    logits = _ref_logits(ref, params, seq, n).astype(np.float64)
    lse = np.log(np.exp(logits - logits.max(-1, keepdims=True)).sum(-1)) \
        + logits.max(-1)
    want = logits[np.arange(n), req.out_tokens] - lse
    return np.abs(np.asarray(req.out_logprobs) - want)


def _check_request(ref, params, req, atol=0.12):
    assert _gap(ref, params, req).max() <= atol


@pytest.mark.parametrize("pallas", ["0", "interpret"])
def test_engine_serves_tails_beside_pages(model, ref, params, monkeypatch,
                                          pallas):
    """Two requests in flight and a third that reuses a slot, on the XLA
    route and with the kernels through the interpreter (the pairs): every
    logprob against the reference, a reused row starts from zero."""
    monkeypatch.setenv("BIGDL_TPU_PALLAS", pallas)
    eng = shared_engine(model, n_slots=2, max_len=64, paged=True,
                        page_size=8)
    assert eng.kind is kvhybrid.CACHE_KIND
    assert eng.cache.ssm is None
    assert eng.cache.conv.shape == (LC, 2, (K - 1) * H)
    assert eng.cache.k.shape == (2, 2 * 8 + 1, 8, 2, 128)  # lane pairs
    reqs = [eng.submit(_tokens(n, 10 + n).tolist(), max_new_tokens=m)
            for n, m in ((19, 7), (6, 4), (30, 6))]
    eng.run_until_idle()
    for r in reqs:
        assert r.finish_reason == "length", (r.finish_reason, r.error)
        assert len(r.out_tokens) == r.max_new_tokens
        _check_request(ref, params, r)
    assert len({tuple(r.out_tokens) for r in reqs}) == 3
    assert eng.page_leaks() == 0
    assert eng.pages.radix.n_nodes == 0 and eng.pages.prefix_hits == 0
    again = eng.submit(list(reqs[0].prompt), max_new_tokens=7)
    eng.run_until_idle()
    assert again.out_tokens == reqs[0].out_tokens


def test_engine_chunked_prefill_hands_the_tail_across_chunks(
        model, ref, params, monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    eng = shared_engine(model, n_slots=2, max_len=64, paged=True,
                        page_size=8, prefill_chunk_tokens=12)
    r = eng.submit(_tokens(30, 21).tolist(), max_new_tokens=5)
    eng.run_until_idle()
    assert eng.prefill_chunks == 3
    _check_request(ref, params, r)


def test_park_and_resume_carries_pages_and_tails(model):
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
    assert parked.ssm is None and parked.conv.shape == (LC, (K - 1) * H)
    assert eng.state_row_bytes == LC * (K - 1) * H * 4 == parked.conv.nbytes
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


def test_no_cache_module_asks_a_model_type_or_a_models_key():
    """`kvhybrid`, `kvpaged` and `serving/` read what is there: the state's
    shapes and the prefill's count are the family's to hand over."""
    import ast
    import inspect

    from bigdl_tpu import kvpaged
    from bigdl_tpu.serving import engine

    for mod in (kvhybrid, kvpaged, engine):
        tree = ast.parse(inspect.getsource(mod))
        asks = [ast.unparse(n) for n in ast.walk(tree)
                if isinstance(n, (ast.Compare, ast.If, ast.IfExp))
                and ("model_type ==" in ast.unparse(n)
                     or "mamba_dt_rank" in ast.unparse(n)
                     or "conv_l_cache" in ast.unparse(n))]
        assert asks == [], (mod.__name__, asks)


def test_spans_counters_and_routes(model, monkeypatch):
    from bigdl_tpu.obs.tracing import TraceRecorder
    from bigdl_tpu.ops.routes import record_routes
    from bigdl_tpu.serving.metrics import Metrics, metric_drift

    from bigdl_tpu.ops.pallas import paged_attention

    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    # the kernel's wrapper is a jit of its own and notes its arm while it is
    # TRACED: an earlier test of this process has traced these shapes
    paged_attention.paged_decode_attention.clear_cache()
    tr = TraceRecorder(capacity=4096)
    with record_routes() as routes:
        eng = InferenceEngine(model, n_slots=2, max_len=64, paged=True,
                              page_size=8, tracer=tr)
        eng.submit(_tokens(20, 41).tolist(), max_new_tokens=3)
        eng.submit(_tokens(7, 42).tolist(), max_new_tokens=4)
        eng.run_until_idle()
    seen = {(op, route) for op, route, _ in routes}
    assert ("paged", "rows") in seen and ("paged", "piped") not in seen
    assert ("attention", "pallas:paged") in seen
    assert ("attention", "pallas:flash") in seen
    assert any(op == "attention" and "lane pairs" in d for op, _, d in routes)
    assert not any(op in ("mamba1", "mamba2") for op, _ in seen)
    ev = tr.events()
    steps = [e["args"] for e in ev if e["name"] == "decode_step"]
    row = eng.state_row_bytes
    assert row == LC * (K - 1) * H * 4  # the tails alone, float32
    assert steps and all(
        a["state_bytes_moved"] == 2 * a["state_rows_live"] * row
        and "live_pages" in a and a["moe_experts"] == 5 * 8 for a in steps)
    assert max(a["state_rows_live"] for a in steps) == 2
    assert eng.state_bytes_moved == sum(a["state_bytes_moved"] for a in steps)
    pre = {e["args"]["prompt_tokens"]: e["args"] for e in ev
           if e["name"] == "prefill"}
    assert sorted(pre) == [7, 20]  # a tail is no prefill form: no count
    assert not any("state_chunks" in a or "scan_tokens" in a
                   for a in pre.values())
    text = Metrics(eng).render()
    assert f"bigdl_tpu_state_pool_bytes {2 * row}" in text
    assert metric_drift(text, eng) == ([], [])


def test_generate_left_pads_a_batch(model, ref, params):
    """`TpuModel.generate` through `init_cache` (B > 1): a row's tokens are
    what it gives alone."""
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
# the planted faults of scripts/conv_check_sweep.py must FAIL here
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fault", [
    None, "tail dropped at the hand-over", "B and C exchanged",
    "a pair's halves exchanged"])
def test_a_planted_fault_fails_the_logprob(model, ref, params, sweep,
                                           monkeypatch, fault):
    """The engine traced with the fault in its path (the kernels through
    the interpreter, so that the pairs are on it) against the reference:
    whole, it passes at 0.12 nats (it reads 0.04 to 0.08); with a fault it
    is off by more than twice that (a dropped tail and exchanged gates read
    2 to 7 nats, one exchanged pair of two 0.34 to 0.57)."""
    assert set(sweep.FAULTS) >= {fault} - {None}
    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    with sweep.planted(fault) if fault else contextlib.nullcontext():
        eng = InferenceEngine(model, n_slots=2, max_len=64, paged=True,
                              page_size=8)
        r = eng.submit(_tokens(21, 77).tolist(), max_new_tokens=6)
        eng.run_until_idle()
    worst = _gap(ref, params, r).max()
    assert (worst <= 0.12) if fault is None else (worst > 0.25), worst


# ---------------------------------------------------------------------------
# checkpoints
# ---------------------------------------------------------------------------

def test_hf_names_map_onto_the_tree(fam, dense):
    """A state dict under HF's names (modeling_lfm2_moe) gives the logits of
    the tree it was written from."""
    from bigdl_tpu.convert.hf import params_from_state_dict

    sd = {"model.embed_tokens.weight": dense["embed"],
          "model.embedding_norm.weight": dense["final_norm"]}
    i = 0
    for (kind, _, n, is_dense), run in zip(fam.layer_runs(CFG),
                                           dense["runs"].values()):
        for j in range(n):
            p, g = f"model.layers.{i}.", {k: v[j] for k, v in run.items()}
            sd[p + "operator_norm.weight"] = g["attn_norm"]
            sd[p + "ffn_norm.weight"] = g["mlp_norm"]
            if kind == "conv":
                sd[p + "conv.in_proj.weight"] = g["w_in"]
                sd[p + "conv.out_proj.weight"] = g["w_out"]
                sd[p + "conv.conv.weight"] = g["conv_w"].T[:, None, :]
            else:
                for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                                     ("wv", "v_proj"), ("wo", "out_proj"),
                                     ("q_norm", "q_layernorm"),
                                     ("k_norm", "k_layernorm")):
                    sd[p + f"self_attn.{theirs}.weight"] = g[ours]
            f = p + "feed_forward."
            if is_dense:
                for ours, theirs in (("w_gate", "w1"), ("w_up", "w3"),
                                     ("w_down", "w2")):
                    sd[f + theirs + ".weight"] = g[ours]
            else:
                sd[f + "gate.weight"] = g["router"]
                sd[f + "expert_bias"] = g["e_bias"]
                for e in range(CFG.num_experts):
                    for ours, theirs in (("w_gate_e", "w1"), ("w_up_e", "w3"),
                                         ("w_down_e", "w2")):
                        sd[f"{f}experts.{e}.{theirs}.weight"] = g[ours][e]
            i += 1
    sd = {k: np.asarray(v) for k, v in sd.items()}
    tree = params_from_state_dict(CFG, sd.__getitem__, qtype="bf16",
                                  dtype=jnp.float32)
    assert sorted(tree["runs"]) == ["00", "01", "02", "03", "04"]
    assert tree["runs"]["00"]["conv_w"].dtype == jnp.float32
    assert tree["runs"]["02"]["conv_w"].shape == (2, K, H)
    assert tree["runs"]["01"]["e_bias"].dtype == jnp.float32
    toks = _tokens(12, 77)[None]
    got, _ = _f32(fam, tree, toks, _cache(fam))
    want, _ = _f32(fam, dense, toks, _cache(fam))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    packed = params_from_state_dict(CFG, sd.__getitem__, qtype="sym_int4")
    assert packed["lm_head"].qtype == "sym_int4"  # the tied table, packed
    assert packed["runs"]["00"]["w_in"].qtype == "sym_int4"
    assert packed["runs"]["01"]["w_up_e"].qtype == "sym_int4"
    assert not hasattr(packed["runs"]["01"]["router"], "qtype")
    # a checkpoint without the bias chooses by zeros
    del sd["model.layers.1.feed_forward.expert_bias"]
    tree = params_from_state_dict(CFG, sd.__getitem__, qtype="bf16",
                                  dtype=jnp.float32)
    assert not np.asarray(tree["runs"]["01"]["e_bias"]).any()
