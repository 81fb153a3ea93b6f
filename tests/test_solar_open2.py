"""Solar-Open2 (`solar_open2`): Kimi delta attention layers (a gated delta
rule whose decay is a vector over a head's key channels and whose state is
READ before it is written: `kvhybrid.kda_mix`, the fourth state beside KV
pages), gated NoPE GQA layers, and sigmoid-routed experts of which a program
may hold ONE RANK'S SHARE (models/solar_open2.py, `llama._held_share`).

The yardstick is bench/reference/solar_open2.py: the float32 forward over a
whole sequence, the delta rule token by token, independent of every cache,
chunk and kernel. float32 against float32 holds to 2e-4 on logits of size 1;
the packed model in bf16 through the engine is held at the LOGPROB level to
0.12 nats, as LFM2's tests hold theirs. The weights are this file's own
(`A_log` and `dt_bias` as Kimi Linear draws them, so that a state outlives a
chunk; taps of 1 / 2; projections of 0.08): at the benchmark's drawn weights a
state forgets within ten tokens (bench/configs/solar-open2-250b-int4.json),
so the five faults of scripts/delta_check_sweep.py are planted HERE and must
fail."""

import contextlib
import dataclasses
import functools
import importlib.util
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
from bigdl_tpu import kvhybrid  # noqa: E402
from bigdl_tpu.api import TpuModel, optimize_model  # noqa: E402
from bigdl_tpu.models import get_family, llama  # noqa: E402
from bigdl_tpu.models.config import PRESETS, ModelConfig  # noqa: E402
from bigdl_tpu.serving.engine import InferenceEngine  # noqa: E402
from engines import shared_engine  # noqa: E402

pytestmark = pytest.mark.core

CFG = PRESETS["tiny-solar-open2"]  # every expert held
# ... and rank 1 of 2 of the same model: experts 4..7 of a router of 8
SHARE = dataclasses.replace(CFG, num_experts=4, router_experts=8,
                            first_expert=4)
# the preset as a config.json (what the reference reads)
HF = dict(
    model_type="solar_open2", vocab_size=256, hidden_size=256,
    intermediate_size=512, num_hidden_layers=4, num_attention_heads=4,
    num_key_value_heads=2, head_dim=128, rms_norm_eps=1e-5,
    max_position_embeddings=4096, tie_word_embeddings=False, use_rope=False,
    gqa_layers=[0, 3], use_gqa_gate=True, kda_use_full_proj=False,
    kda_allow_neg_eigval=True, first_k_dense_replace=0, n_routed_experts=8,
    n_shared_experts=1, num_experts_per_tok=2, moe_intermediate_size=64,
    norm_topk_prob=True, routed_scaling_factor=1.0,
    linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 128,
                        "num_heads": 2, "num_kv_heads": None})
HF_SHARE = dict(HF, n_routed_experts=4, expert_parallel_share={
    "router_experts": 8, "first_expert": 4})
H, D, K, LK = 2, 128, 4, 2  # KDA heads, head size, taps, KDA layers


def _load(name, *path):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, *path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def fam():
    return get_family("solar_open2")


@pytest.fixture(scope="module")
def ref():
    return cells.load_module(ROOT, "reference", "solar_open2")


@pytest.fixture(scope="module")
def sweep():
    return _load("delta_check_sweep", "scripts", "delta_check_sweep.py")


@pytest.fixture(scope="module")
def dense(fam):
    """float32 weights large enough (0.08) that logits have a spread of
    about 1; decays slow enough that a state outlives a chunk (`init_params`
    draws `A_log` and `dt_bias` as Kimi Linear does); taps of 1 / 2; a
    selection bias that moves choices."""
    p = fam.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32,
                        scale=0.08)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 16))
    for run in p["runs"].values():
        run["e_bias"] = 0.1 * jax.random.normal(
            next(keys), run["e_bias"].shape, jnp.float32)
    return p


def _held(dense):
    """The tree rank 1 of 2 holds: experts 4..7 of every layer's stacks."""
    return dict(dense, runs={r: {
        n: (w[:, 4:8] if n in llama._EXPERT_STACKS else w)
        for n, w in run.items()} for r, run in dense["runs"].items()})


@pytest.fixture(scope="module")
def params(dense):
    return optimize_model(_held(dense), SHARE, "sym_int4")


@pytest.fixture(scope="module")
def model(params):
    return TpuModel(SHARE, params, "sym_int4")


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(1, 256, n)


@functools.lru_cache(maxsize=None)
def _jitted(ref):
    return jax.jit(ref.logits, static_argnums=(0, 3))


def _ref_logits(ref, p, seq, n_last, hf=HF):
    from bench.records import Frozen

    hf = {k: Frozen(v) if isinstance(v, dict) else
          tuple(v) if isinstance(v, list) else v for k, v in hf.items()}
    return np.asarray(_jitted(ref)(
        Frozen(hf), p, jnp.asarray(seq, jnp.int32), n_last))


def _cache(fam, rows=1, n=256):
    """A cache whose pages are float32 too (the pool's bfloat16 keys alone
    move a logit of size 1 by 4e-3)."""
    c = fam.init_cache(CFG, rows, n)
    return dataclasses.replace(c, k=c.k.astype(jnp.float32),
                               v=c.v.astype(jnp.float32))


@functools.partial(jax.jit, static_argnums=(0, 4, 5))
def _f32(fam, p, toks, cache, mode="prefill", cfg=CFG):
    return fam.forward(cfg, p, jnp.asarray(toks, jnp.int32), cache, mode=mode,
                       compute_dtype=jnp.float32)


# ---------------------------------------------------------------------------
# the configuration
# ---------------------------------------------------------------------------

def test_preset_is_the_hf_config(fam):
    got = ModelConfig.from_hf_config(HF)
    assert dataclasses.replace(got, moe_dispatch="dense") == CFG
    assert got.expert_share is None and got.router_width == 8
    share = ModelConfig.from_hf_config(HF_SHARE)
    assert dataclasses.replace(share, moe_dispatch="dense") == SHARE
    assert share.expert_share == (4, 4, 8)
    assert fam.layer_runs(CFG) == [("attention", 0, 1), ("kda", 0, 2),
                                   ("attention", 1, 1)]
    with pytest.raises(ValueError, match="does not lie in a router"):
        dataclasses.replace(SHARE, first_expert=5)


def test_the_catalog_rows_config_gives_the_published_layers(fam):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    file = cells.load_json(ROOT, "bench", "configs",
                           "solar-open2-250b-int4.json")
    published = dict(file["published"])
    assert published.pop("expert_parallel_share") == {
        "router_experts": 320, "first_expert": 0}
    if os.path.exists(catalog):  # the row's `config`, where it is at hand
        with open(catalog) as f:
            rows = [json.loads(line) for line in f]
        assert published == next(
            r["config"] for r in rows if r["name"] == "Solar-Open2-250B")
    cfg = ModelConfig.from_hf_config(file["published"])
    assert (cfg.num_hidden_layers, cfg.hidden_size, cfg.head_dim_,
            cfg.vocab_size) == (48, 4096, 128, 196608)
    assert [i for i, k in enumerate(cfg.layer_types) if k == "attention"] \
        == list(range(0, 48, 4))
    assert (cfg.num_experts, cfg.router_width, cfg.expert_share,
            cfg.num_experts_per_tok, cfg.moe_intermediate_size,
            cfg.n_shared_experts) == (320, 320, None, 8, 1280, 1)
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.conv_l_cache,
            cfg.rms_norm_eps, cfg.tie_word_embeddings) == (64, 128, 4, 1e-5,
                                                           False)
    run = ModelConfig.from_hf_config(cells.as_run(file))
    assert run.expert_share == (0, 40, 320) and run.num_hidden_layers == 12
    assert fam.layer_runs(run) == [
        ("attention", 0, 1), ("kda", 0, 3), ("attention", 1, 1),
        ("kda", 3, 3), ("attention", 2, 1), ("kda", 6, 3)]


@pytest.mark.parametrize("key,value,error", [
    ("use_rope", True, NotImplementedError),
    ("first_k_dense_replace", 1, NotImplementedError),
    ("kda_use_full_proj", True, NotImplementedError),
    ("use_gqa_gate", False, NotImplementedError),
    ("kda_allow_neg_eigval", False, NotImplementedError),
    ("gqa_layers", [0, 9], ValueError)])
def test_what_the_translator_refuses_by_name(key, value, error):
    with pytest.raises(error, match="solar_open2|gqa_layers"):
        ModelConfig.from_hf_config({**HF, key: value})


def test_importing_the_package_loads_no_family_module():
    import subprocess

    code = ("import sys, bigdl_tpu, bigdl_tpu.api, bigdl_tpu.models; "
            "print('bigdl_tpu.models.solar_open2' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=ROOT,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.stdout.strip() == "False", out.stderr[-400:]


# ---------------------------------------------------------------------------
# the family against the reference, by LOGITS
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 63, 64, 65, 200])
def test_forward_is_the_reference_across_chunk_seams(fam, ref, dense, n):
    """Prompts on both sides of the chunk of 64 and over three of them."""
    toks = _tokens(n, n)
    got, _ = _f32(fam, dense, toks[None], _cache(fam))
    want = _ref_logits(ref, dense, toks, n)
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=2e-4)


def test_prefill_hands_over_to_decode(fam, ref, dense):
    """70 tokens prefilled (a chunk and a piece), 6 decoded one by one
    through the tails, the state and the pages: every position's logits are
    the full forward's."""
    toks = _tokens(76, 3)
    c = _cache(fam)
    got, c = _f32(fam, dense, toks[None, :70], c)
    rows = [np.asarray(got[0])]
    for t in range(70, 76):
        got, c = _f32(fam, dense, toks[None, t:t + 1], c, "decode")
        rows.append(np.asarray(got[0]))
    want = _ref_logits(ref, dense, toks, 76)
    np.testing.assert_allclose(np.concatenate(rows), want, atol=2e-4)
    assert c.ssm.shape == (LK, 1, H * D, D)
    assert c.conv.shape == (LK, 1, (K - 1) * 3 * H * D)


def test_a_prefill_in_two_chunks_is_the_prefill_in_one(fam, ref, dense):
    """The state and the tails cross a seam of `prefill_chunk_tokens`: 37
    tokens, then 43 from `pos` 37 (a chunk of the form from a state that is
    not zero)."""
    toks = _tokens(80, 5)
    c = _cache(fam)
    a, c = _f32(fam, dense, toks[None, :37], c)
    b, c = _f32(fam, dense, toks[None, 37:], c)
    want = _ref_logits(ref, dense, toks, 80)
    np.testing.assert_allclose(
        np.concatenate([np.asarray(a[0]), np.asarray(b[0])]), want,
        atol=2e-4)


def test_a_padded_bucket_stops_the_state_at_the_last_token(fam, ref, dense):
    """13 tokens right-padded to a bucket of 16 (`valid_len`), then decode:
    the padding neither decays nor updates, and leaves no trace in a tail."""
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


def test_a_share_is_the_reference_given_the_same_share(fam, ref, dense):
    """Rank 1 of 2 (experts 4..7) through the forward's XLA dispatch against
    the reference handed the same share: the absent experts' part is left
    out alike, and the partial result goes on through four layers."""
    toks = _tokens(40, 9)
    held = _held(dense)
    got, _ = _f32(fam, held, toks[None], _cache(fam), "prefill", SHARE)
    want = _ref_logits(ref, held, toks, 40, HF_SHARE)
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=2e-4)
    whole = _ref_logits(ref, dense, toks, 40)
    assert np.abs(whole - want).max() > 0.05  # the share is no small thing


# ---------------------------------------------------------------------------
# the delta rule: the chunked form, the step, the kernel
# ---------------------------------------------------------------------------

def _drawn(T, decay, beta_at, seed=0, heads=2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa
    q = unit(jax.random.normal(ks[0], (T, heads, D))) * D ** -0.5
    k = unit(jax.random.normal(ks[1], (T, heads, D)))
    v = jax.random.normal(ks[2], (T, heads, D))
    g = np.log(decay) * jax.random.uniform(ks[3], (T, heads, D), minval=0.5,
                                           maxval=1.5)
    beta = jnp.clip(beta_at + 0.3 * jax.random.normal(ks[4], (T, heads)),
                    0.05, 1.95)
    S = 0.1 * jax.random.normal(ks[5], (heads, D, D))
    return q, k, v, g, beta, S


def _recurrence(q, k, v, g, beta, S):
    def one(S, t):
        o, S = kvhybrid.kda_step(*(a[None] for a in t), S[None])
        return S[0], o[0]

    S, o = jax.lax.scan(one, S, (q, k, v, g, beta))
    return o, S


@pytest.mark.parametrize("decay", [0.999, 0.9, 0.3, 1e-6])
@pytest.mark.parametrize("beta_at", [0.5, 1.5])
def test_the_chunked_form_is_the_recurrence(decay, beta_at):
    """From a state that is not zero, over three chunks and a piece, at
    decays from a state that outlives the sequence to one that dies inside a
    sub-block (1e-6 a token: exp(G) underflows, nothing overflows), with
    beta on both sides of 1."""
    with jax.default_matmul_precision("highest"):
        args = _drawn(200, decay, beta_at)
        o, S = _recurrence(*args)
        oc, Sc = jax.jit(kvhybrid.kda_chunked)(*args)
    assert np.all(np.isfinite(np.asarray(oc)))
    np.testing.assert_allclose(np.asarray(oc), np.asarray(o), atol=2e-6)
    np.testing.assert_allclose(np.asarray(Sc), np.asarray(S), atol=2e-5)


def test_the_kernel_is_the_step_and_leaves_idle_rows_alone():
    """`kda_decode` through the interpreter against `kda_step`: row 1 of 3
    is idle (its state and every other layer's untouched BIT FOR BIT, its
    output zeros); then with every row idle nothing moves at all."""
    from bigdl_tpu.ops.pallas.mamba2 import kda_decode

    heads, B = 4, 3
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    state = jax.random.normal(ks[0], (2, 4, heads * D, D))
    q, k, v, g, beta, _ = _drawn(B, 0.5, 1.0, seed=3, heads=heads)
    rows, live = jnp.asarray([2, 0, 3]), jnp.asarray([True, False, True])
    y, out = kda_decode(state, jnp.int32(1), rows, live, q, k, v, g, beta,
                        interpret=True)
    with jax.default_matmul_precision("highest"):
        o, S = kvhybrid.kda_step(q, k, v, g, beta,
                                 state[1, rows].reshape(B, heads, D, D))
    on = np.asarray([0, 2])
    np.testing.assert_allclose(np.asarray(y)[on], np.asarray(o)[on],
                               atol=2e-6)
    np.testing.assert_allclose(
        np.asarray(out[1, rows[on]]),
        np.asarray(S)[on].reshape(2, heads * D, D), atol=2e-6)
    assert not np.asarray(y[1]).any()
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(state[0]))
    np.testing.assert_array_equal(np.asarray(out[1, :2]),
                                  np.asarray(state[1, :2]))
    y, out = kda_decode(state, jnp.int32(1), rows, jnp.zeros(3, bool), q, k,
                        v, g, beta, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(state))
    assert not np.asarray(y).any()


# ---------------------------------------------------------------------------
# one rank's share of an expert-parallel layer
# ---------------------------------------------------------------------------

def _layer(seed=0, E=8, hid=256, I=64):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    w = lambda k, s: 0.08 * jax.random.normal(k, s, jnp.float32)  # noqa
    p = {"router": w(ks[0], (E, hid)),
         "e_bias": 0.1 * jax.random.normal(ks[1], (E,), jnp.float32),
         "w_gate_e": w(ks[2], (E, I, hid)), "w_up_e": w(ks[3], (E, I, hid)),
         "w_down_e": w(ks[4], (E, hid, I))}
    return p, jax.random.normal(ks[5], (2, 24, hid), jnp.float32)


def _share_of(p, first, n):
    return {k: (v[first:first + n] if k in llama._EXPERT_STACKS else v)
            for k, v in p.items()}


def _dispatch(cfg, p, x, dtype=jnp.float32):
    from bigdl_tpu.models import deepseek

    B, T, hid = x.shape
    topv, topi = deepseek._router(cfg, x.reshape(B * T, hid), p)
    return llama._moe_dispatch(cfg, x.astype(dtype), p, dtype,
                               topv.reshape(B, T, -1), topi.reshape(B, T, -1))


@pytest.mark.parametrize("form", ["dense", "ragged", "grouped"])
def test_the_shares_add_up_to_the_uncut_layer(form, monkeypatch):
    """FOUR ranks of 2 experts each of a layer of 8: the routed parts of all
    shares add up to what the uncut layer gives (the shared expert, which
    every rank computes alike, is no part of the dispatch and so is counted
    once by construction). Every XLA formulation and, on packed stacks
    through the interpreter, the grouped kernel: both of its layouts, 48
    rows in one tile and 320 rows sorted."""
    p, x = _layer()
    dtype = jnp.float32
    cfg = dataclasses.replace(CFG, moe_dispatch="ragged" if form == "ragged"
                              else "dense", moe_capacity_factor=8.0)
    if form == "grouped":
        monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
        dtype = jnp.bfloat16
        x = jnp.concatenate([x, jnp.tile(x, (1, 6, 1))[:, :136]], axis=1)
    shapes = [x] if form != "grouped" else [x[:, :24], x]

    def pack(q):
        if form != "grouped":
            return q
        from bigdl_tpu.quant import quantize_or_dense

        return {k: (quantize_or_dense(v, "sym_int4", k)
                    if k in llama._EXPERT_STACKS else v)
                for k, v in q.items()}

    for xs in shapes:
        whole = _dispatch(cfg, pack(p), xs, dtype)
        parts = [_dispatch(dataclasses.replace(
            cfg, num_experts=2, router_experts=8, first_expert=f),
            pack(_share_of(p, f, 2)), xs, dtype) for f in (0, 2, 4, 6)]
        total = sum(np.asarray(a, np.float32) for a in parts)
        np.testing.assert_allclose(
            total, np.asarray(whole, np.float32),
            atol=2e-5 if form != "grouped" else 2e-2)
        assert all(np.abs(np.asarray(a, np.float32)).max() > 0.01
                   for a in parts)  # each rank gives a part of it


@pytest.mark.parametrize("rows", [3, 300])
def test_a_call_with_nothing_held_keeps_one_tile_and_gives_zeros(
        rows, monkeypatch):
    """One live row whose choices all fall on other ranks' experts (on the
    chip the kernel's row block of a call with NO live tile lay before the
    buffer and the device halted): a tile stays live, nobody reads it, the
    routed part is zero. Both layouts."""
    from bigdl_tpu.ops.pallas import moe_qmatmul as mq
    from bigdl_tpu.quant import quantize_or_dense

    topi = jnp.zeros((rows, 2), jnp.int32)
    none = jnp.zeros((rows, 2), bool)
    assert int(mq.moe_layout_shared(topi[:3], 4, 8, none[:3])[2]) == 1
    assert int(mq.moe_layout_shared(topi[:3], 4, 8)[2]) == 1
    dest, _, _, n_used = mq.moe_layout(topi, 4, 8, 80, none)
    assert int(n_used) == 1 and int(dest.min()) == 80 * 8
    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    p, x = _layer()
    x = jnp.tile(x.reshape(1, -1, 256), (1, 7, 1))[:, :rows]
    p = {k: (quantize_or_dense(v[:2], "sym_int4", k)
             if k in llama._EXPERT_STACKS else v) for k, v in p.items()}
    cfg = dataclasses.replace(CFG, num_experts=2, router_experts=8,
                              first_expert=0)
    topv = jnp.full((1, rows, 2), 0.5, jnp.float32)
    topi = jnp.full((1, rows, 2), 5, jnp.int32)  # held by another rank
    out = llama._moe_dispatch(cfg, x.astype(jnp.bfloat16), p, jnp.bfloat16,
                              topv, topi)
    assert not np.asarray(out, np.float32).any()


def test_an_uncut_configuration_never_meets_the_share(monkeypatch):
    """Every expert held: `_held_share` is not called, the layouts get no
    `held`, and the route note is the parent's."""
    from bigdl_tpu.ops.routes import record_routes

    def boom(*a):
        raise AssertionError("an uncut layer asked for a share")

    monkeypatch.setattr(llama, "_held_share", boom)
    p, x = _layer()
    with record_routes() as routes:
        _dispatch(CFG, p, x)
    (note,) = [d for op, _, d in routes if op == "moe"]
    assert "held" not in note
    with record_routes() as routes, pytest.raises(AssertionError):
        _dispatch(SHARE, _share_of(p, 4, 4), x)


def test_the_engine_counts_the_experts_held_and_hit():
    from bigdl_tpu.serving.engine import _moe_load

    chosen = np.asarray([[[0, 5], [4, 7], [5, 6]],
                         [[1, 2], [3, 0], [4, 4]]])  # [L, n, k] of 8
    assert _moe_load(chosen, 8) == {
        "moe_assignments": 12, "moe_experts_hit": 10,
        "moe_max_expert_load": 2, "moe_experts": 16}
    assert _moe_load(chosen, 4, first=4) == {  # 5, 4, 7, 5, 6 | 4, 4
        "moe_assignments": 7, "moe_experts_hit": 5,
        "moe_max_expert_load": 2, "moe_experts": 8}


# ---------------------------------------------------------------------------
# the engine (rank 1 of 2: the share goes through it)
# ---------------------------------------------------------------------------

def _gap(ref, params, req):
    seq = list(req.prompt) + list(req.out_tokens[:-1])
    n = len(req.out_tokens)
    logits = _ref_logits(ref, params, seq, n, HF_SHARE).astype(np.float64)
    lse = np.log(np.exp(logits - logits.max(-1, keepdims=True)).sum(-1)) \
        + logits.max(-1)
    want = logits[np.arange(n), req.out_tokens] - lse
    return np.abs(np.asarray(req.out_logprobs) - want)


@pytest.mark.parametrize("pallas", ["0", "interpret"])
def test_engine_serves_a_delta_state_beside_pages(model, ref, params,
                                                  monkeypatch, pallas):
    """Two requests in flight and a third that reuses a slot, on the XLA
    route and with the kernels through the interpreter (`kda_decode`, the
    grouped experts with a share): every logprob against the reference, a
    reused row starts from zero."""
    monkeypatch.setenv("BIGDL_TPU_PALLAS", pallas)
    eng = shared_engine(model, n_slots=2, max_len=128, paged=True,
                        page_size=16)
    assert eng.kind is kvhybrid.CACHE_KIND
    assert eng.cache.ssm.shape == (LK, 2, H * D, D)
    assert eng.cache.conv.shape == (LK, 2, (K - 1) * 3 * H * D)
    assert eng.cache.k.shape == (2, 2 * 8 + 1, 16, 2, 128)
    reqs = [eng.submit(_tokens(n, 10 + n).tolist(), max_new_tokens=m)
            for n, m in ((70, 7), (6, 4), (30, 6))]
    eng.run_until_idle()
    for r in reqs:
        assert r.finish_reason == "length", (r.finish_reason, r.error)
        assert len(r.out_tokens) == r.max_new_tokens
        assert _gap(ref, params, r).max() <= 0.12
    assert eng.page_leaks() == 0
    assert eng.pages.radix.n_nodes == 0 and eng.pages.prefix_hits == 0
    again = eng.submit(list(reqs[0].prompt), max_new_tokens=7)
    eng.run_until_idle()
    assert again.out_tokens == reqs[0].out_tokens


def test_engine_chunked_prefill_hands_the_state_across_chunks(
        model, ref, params, monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    eng = shared_engine(model, n_slots=2, max_len=128, paged=True,
                        page_size=16, prefill_chunk_tokens=32)
    r = eng.submit(_tokens(75, 21).tolist(), max_new_tokens=5)
    eng.run_until_idle()
    assert eng.prefill_chunks == 3
    assert _gap(ref, params, r).max() <= 0.12


def test_park_and_resume_carries_pages_tails_and_state(model):
    prompt = _tokens(20, 31).tolist()
    plain = shared_engine(model, n_slots=2, max_len=128, paged=True,
                          page_size=16)
    want = plain.submit(prompt, max_new_tokens=10)
    plain.run_until_idle()
    eng = shared_engine(model, n_slots=2, max_len=128, paged=True,
                        page_size=16)
    other = eng.submit(_tokens(10, 32).tolist(), max_new_tokens=10)
    r = eng.submit(prompt, max_new_tokens=10)
    for _ in range(4):
        eng.step()
    eng.preempt(r)
    eng._reap_preempt_requests()  # the head of the next step: parks it
    assert eng.preemptions == 1 and eng.pages.slot_pages[1] == []
    parked = eng._preempted[0].blob
    assert parked.ssm.shape == (LK, H * D, D)
    assert parked.conv.shape == (LK, (K - 1) * 3 * H * D)
    row = LK * (H * D * D + (K - 1) * 3 * H * D) * 4
    assert eng.state_row_bytes == row == parked.ssm.nbytes + parked.conv.nbytes
    eng.run_until_idle()
    assert eng.preemption_resumes == 1
    assert r.out_tokens == want.out_tokens
    assert r.out_logprobs == want.out_logprobs  # bit-equal, not close
    assert other.finish_reason == "length" and eng.page_leaks() == 0


def test_spans_counters_and_routes(model, monkeypatch):
    from bigdl_tpu.obs.tracing import TraceRecorder
    from bigdl_tpu.ops.routes import record_routes
    from bigdl_tpu.serving.metrics import Metrics, metric_drift

    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    tr = TraceRecorder(capacity=4096)
    with record_routes() as routes:
        eng = InferenceEngine(model, n_slots=2, max_len=128, paged=True,
                              page_size=16, tracer=tr)
        eng.submit(_tokens(70, 41).tolist(), max_new_tokens=3)
        eng.submit(_tokens(7, 42).tolist(), max_new_tokens=4)
        eng.run_until_idle()
    seen = {(op, route) for op, route, _ in routes}
    assert ("kda", "pallas:kda_decode") in seen and ("kda", "xla") in seen
    assert any(op == "kda" and "chunked prefill C64" in d
               for op, _, d in routes)
    assert ("attention", "pallas:paged") in seen
    assert ("attention", "pallas:flash") in seen
    assert any(op == "moe" and "held 4/8 first 4" in d for op, _, d in routes)
    assert not any(op in ("mamba1", "mamba2", "lightning") for op, _ in seen)
    ev = tr.events()
    steps = [e["args"] for e in ev if e["name"] == "decode_step"]
    row = eng.state_row_bytes
    assert steps and all(
        a["state_bytes_moved"] == 2 * a["state_rows_live"] * row
        and "live_pages" in a and a["moe_experts"] == 4 * 4  # HELD here
        and a["moe_assignments"] <= 4 * 2 * a["state_rows_live"]
        and a["moe_experts_hit"] <= a["moe_assignments"] for a in steps)
    assert 0 < sum(a["moe_assignments"] for a in steps) < sum(
        4 * 2 * a["state_rows_live"] for a in steps)  # some fell elsewhere
    assert max(a["state_rows_live"] for a in steps) == 2
    assert eng.state_bytes_moved == sum(a["state_bytes_moved"] for a in steps)
    pre = {e["args"]["prompt_tokens"]: e["args"] for e in ev
           if e["name"] == "prefill"}
    assert pre[70]["state_chunks"] == 2 and pre[7]["state_chunks"] == 1
    text = Metrics(eng).render()
    assert f"bigdl_tpu_state_pool_bytes {2 * row}" in text
    assert "bigdl_tpu_state_rows_live" in text
    assert metric_drift(text, eng) == ([], [])


def test_generate_left_pads_a_batch(ref, dense):
    """`TpuModel.generate` through `init_cache` (B > 1), every expert held:
    a row's tokens are what it gives alone."""
    whole = TpuModel(CFG, optimize_model(dense, CFG, "sym_int4"), "sym_int4")
    prompts = [_tokens(17, 1).tolist(), _tokens(5, 2).tolist()]
    both = np.asarray(whole.generate(prompts, max_new_tokens=6))
    for i, p in enumerate(prompts):
        alone = np.asarray(whole.generate([p], max_new_tokens=6))[0]
        np.testing.assert_array_equal(both[i], alone)
    seq = prompts[0] + both[0][:-1].tolist()
    logits = _ref_logits(ref, whole.params, seq, 6)
    lp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    assert np.all(lp.max(-1) - lp[np.arange(6), both[0]] < 0.08)


def test_a_mesh_refuses_a_share_by_name(model):
    with pytest.raises(NotImplementedError, match="share.*shard_map"):
        model.to_mesh(tp=2)


# ---------------------------------------------------------------------------
# the planted faults of scripts/delta_check_sweep.py must FAIL here
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fast(dense):
    """The same model with every decay some twelve times faster (`A_log` +
    2.5: a half a token and more, the benchmark's drawn decay): where WHEN
    the decay is applied shows."""
    p = _held(dense)
    p = dict(p, runs={r: {n: (w + 2.5 if n == "A_log" else w)
                          for n, w in run.items()}
                      for r, run in p["runs"].items()})
    return optimize_model(p, SHARE, "sym_int4")


@pytest.mark.parametrize("fault,quick", [
    (None, False), (None, True), ("state dropped at the hand-over", False),
    ("q and k exchanged", False), ("decay after the update", True),
    ("beta not doubled", False), ("a share's first id off by one", False)])
def test_a_planted_fault_fails_the_logprob(model, ref, params, fast, sweep,
                                           monkeypatch, fault, quick):
    """The engine traced with the fault in its path (the kernels through
    the interpreter) against the reference: whole, it passes at 0.12 nats;
    with a fault it is off by more than twice that. `quick`: at decays of a
    half a token, where a decay applied on the wrong side of the update
    shows (at this file's slow decays it reads 0.14)."""
    assert set(sweep.FAULTS) >= {fault} - {None}
    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    weights = fast if quick else params
    with sweep.planted(fault) if fault else contextlib.nullcontext():
        eng = InferenceEngine(TpuModel(SHARE, weights, "sym_int4"),
                              n_slots=2, max_len=128, paged=True,
                              page_size=16)
        r = eng.submit(_tokens(70, 77).tolist(), max_new_tokens=6)
        eng.run_until_idle()
    worst = _gap(ref, weights, r).max()
    assert (worst <= 0.12) if fault is None else (worst > 0.25), worst


# ---------------------------------------------------------------------------
# checkpoints
# ---------------------------------------------------------------------------

def test_hf_names_map_onto_the_tree(fam, dense):
    """A state dict under the names `convert/hf._solar_open2_tree` reads
    gives the logits of the tree it was written from; a share's tree reads
    its own experts alone."""
    from bigdl_tpu.convert.hf import params_from_state_dict

    sd = {"model.embed_tokens.weight": dense["embed"],
          "model.norm.weight": dense["final_norm"],
          "lm_head.weight": dense["lm_head"]}
    i = 0
    for (kind, _, n), run in zip(fam.layer_runs(CFG),
                                 dense["runs"].values()):
        for j in range(n):
            p, g = f"model.layers.{i}.", {k: v[j] for k, v in run.items()}
            a, m = p + "self_attn.", p + "mlp."
            sd[p + "input_layernorm.weight"] = g["attn_norm"]
            sd[p + "post_attention_layernorm.weight"] = g["mlp_norm"]
            names = [("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"),
                     ("wo", "o_proj")]
            if kind == "attention":
                names.append(("wg", "g_proj"))
            else:
                names += [("f_a", "f_a_proj"), ("f_b", "f_b_proj"),
                          ("g_a", "g_a_proj"), ("g_b", "g_b_proj"),
                          ("w_beta", "b_proj"), ("o_norm", "o_norm")]
                for at, c in enumerate("qkv"):
                    w = g["conv_w"][:, at * H * D:(at + 1) * H * D]
                    sd[f"{a}{c}_conv1d.weight"] = w.T[:, None, :]
                sd[a + "A_log"], sd[a + "dt_bias"] = g["A_log"], g["dt_bias"]
                sd[a + "g_b_proj.bias"] = g["g_bias"]
            for ours, theirs in names:
                sd[f"{a}{theirs}.weight"] = g[ours]
            sd[m + "gate.weight"] = g["router"]
            sd[m + "gate.e_score_correction_bias"] = g["e_bias"]
            for ours, theirs in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                                 ("w_down", "down_proj")):
                sd[f"{m}shared_experts.{theirs}.weight"] = g[ours + "_s"]
                for e in range(8):
                    sd[f"{m}experts.{e}.{theirs}.weight"] = g[ours + "_e"][e]
            i += 1
    sd = {k: np.asarray(v) for k, v in sd.items()}
    tree = params_from_state_dict(CFG, sd.__getitem__, qtype="bf16",
                                  dtype=jnp.float32)
    assert sorted(tree["runs"]) == ["00", "01", "02"]
    assert tree["runs"]["01"]["conv_w"].shape == (2, K, 3 * H * D)
    assert tree["runs"]["01"]["A_log"].dtype == jnp.float32
    toks = _tokens(12, 77)[None]
    got, _ = _f32(fam, tree, toks, _cache(fam))
    want, _ = _f32(fam, dense, toks, _cache(fam))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    packed = params_from_state_dict(SHARE, sd.__getitem__, qtype="sym_int4")
    run = packed["runs"]["01"]
    assert packed["lm_head"].qtype == run["wq"].qtype == "sym_int4"
    assert packed["runs"]["00"]["wg"].qtype == "sym_int4"
    assert run["w_up_e"].data.shape[:2] == (2, 4)  # the four held here
    assert run["w_up_s"].qtype == "sym_int4"
    assert run["router"].shape == (2, 8, 256)  # the router's whole width
    assert not any(hasattr(run[n], "qtype") for n in (
        "router", "e_bias", "f_a", "f_b", "g_a", "g_b", "w_beta", "conv_w"))
    held = params_from_state_dict(SHARE, sd.__getitem__, qtype="bf16",
                                  dtype=jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(held["runs"]["01"]["w_up_e"]),
        np.asarray(dense["runs"]["01"]["w_up_e"][:, 4:8]))
