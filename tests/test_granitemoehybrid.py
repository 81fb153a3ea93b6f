"""Granite 4.0-H (`granitemoehybrid`): Mamba-2 and NoPE attention layers
mixed by index, a state row beside KV pages in one engine slot
(bigdl_tpu/kvhybrid.py, models/granitemoehybrid.py, ops/pallas/mamba2.py).

The yardstick is bench/reference/granitemoehybrid.py: the float32
token-by-token recurrence, independent of the chunked form and of every
cache. Tolerances: float32 against float32 holds to 2e-4 on logits of size
1 (sums in another order); the packed model in bf16 through the engine is
held at the LOGPROB level, as the benchmark's check holds it, to 0.08 nats
(bf16 activations through 5 layers on logits of spread ~1; Brumby's tests
hold the same statistic to the same bound)."""

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
from bigdl_tpu import kvhybrid  # noqa: E402
from bigdl_tpu.api import TpuModel, optimize_model  # noqa: E402
from bigdl_tpu.models import get_family  # noqa: E402
from bigdl_tpu.models.config import PRESETS, ModelConfig  # noqa: E402
from bigdl_tpu.serving.engine import InferenceEngine  # noqa: E402
from engines import shared_engine  # noqa: E402

pytestmark = pytest.mark.core

CFG = PRESETS["tiny-granite-hybrid"]
# the preset as a config.json (what the reference reads)
HF = dict(
    model_type="granitemoehybrid", vocab_size=256, hidden_size=64,
    intermediate_size=32, shared_intermediate_size=64, num_hidden_layers=5,
    layer_types=["mamba", "mamba", "attention", "mamba", "mamba"],
    num_attention_heads=4, num_key_value_heads=2, num_local_experts=8,
    num_experts_per_tok=3, mamba_n_heads=4, mamba_d_head=32,
    mamba_d_state=16, mamba_d_conv=4, mamba_expand=2, mamba_n_groups=1,
    mamba_chunk_size=8, embedding_multiplier=12, residual_multiplier=0.22,
    attention_multiplier=0.0625, logits_scaling=4,
    position_embedding_type="nope", tie_word_embeddings=True,
    rms_norm_eps=1e-5)
CHUNK = HF["mamba_chunk_size"]


@pytest.fixture(scope="module")
def fam():
    return get_family("granitemoehybrid")


@pytest.fixture(scope="module")
def ref():
    return cells.load_module(ROOT, "reference", "granitemoehybrid")


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


def _ref_logits(ref, p, seq, n_last):
    return np.asarray(ref.logits(HF, p, jnp.asarray(seq, jnp.int32), n_last))


@functools.partial(jax.jit, static_argnums=(0, 4))
def _f32(fam, p, toks, cache=None, mode="prefill"):
    return fam.forward(CFG, p, jnp.asarray(toks, jnp.int32), cache, mode=mode,
                       compute_dtype=jnp.float32)


def test_preset_is_the_hf_config():
    assert ModelConfig.from_hf_config(HF) == CFG
    assert CFG.layer_types.count("attention") == 1
    assert get_family("granitemoehybrid").layer_runs(CFG) == [
        ("mamba", 0, 2), ("attention", 0, 1), ("mamba", 2, 2)]
    for key, value in (("mamba_n_groups", 2), ("mamba_proj_bias", True),
                       ("position_embedding_type", "rope")):
        with pytest.raises(NotImplementedError, match=key):
            ModelConfig.from_hf_config(dict(HF, **{key: value}))


def test_importing_the_package_loads_neither_family_nor_kernel():
    import subprocess

    code = ("import sys, bigdl_tpu, bigdl_tpu.api, bigdl_tpu.serving.engine;"
            "bad = [m for m in sys.modules if m.endswith(('granitemoehybrid',"
            " 'pallas.mamba2'))]; assert not bad, bad")
    subprocess.run([sys.executable, "-c", code], check=True, cwd=ROOT,
                   env=dict(os.environ, JAX_PLATFORMS="cpu"))


# ---------------------------------------------------------------------------
# forward against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, CHUNK - 1, CHUNK, CHUNK + 1, 19])
def test_forward_matches_the_recurrence_across_chunk_seams(fam, ref, dense,
                                                          n):
    """Prompt lengths of 1, one under, at and one over a chunk, and two
    chunks and a part (the issue's 1, 255, 256, 257, 600 at chunk 256)."""
    toks = _tokens(n, 3 + n)
    got, _ = _f32(fam, dense, toks[None])
    want = _ref_logits(ref, dense, toks, n)
    assert np.abs(want).max() > 0.3
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=2e-4)


def test_prefill_hands_over_to_decode(fam, ref, dense):
    toks = _tokens(21, 5)
    cache = fam.init_cache(CFG, 1, 64)
    out, cache = _f32(fam, dense, toks[None, :13], cache)
    outs = [out]
    for t in range(13, 21):
        o, cache = _f32(fam, dense, toks[None, t:t + 1], cache, "decode")
        outs.append(o)
    got = np.asarray(jnp.concatenate(outs, axis=1)[0])
    np.testing.assert_allclose(got, _ref_logits(ref, dense, toks, 21),
                               atol=2e-4)
    assert int(cache.pos[0]) == 21


def test_a_padded_bucket_stops_the_state_at_the_last_token(fam, dense):
    """`valid_len`: right padding neither decays nor updates the state and
    the convolution's tail is the last REAL tokens'."""
    n, bucket = 11, 16
    toks = _tokens(bucket, 7)
    _, plain = _f32(fam, dense, toks[None, :n], fam.init_cache(CFG, 1, 64))
    padded = dataclasses.replace(fam.init_cache(CFG, 1, 64),
                                 valid_len=jnp.asarray([n], jnp.int32))
    out, padded = _f32(fam, dense, toks[None], padded)
    np.testing.assert_allclose(np.asarray(padded.ssm), np.asarray(plain.ssm),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(padded.conv),
                               np.asarray(plain.conv), atol=1e-6)
    assert int(padded.pos[0]) == n and padded.valid_len is None
    assert np.abs(np.asarray(plain.ssm)).max() > 1e-3


def test_generate_left_pads_a_batch(model, ref, params):
    """`TpuModel.generate` through `init_cache`: rows of different lengths
    are left-padded, and a row's tokens are what it gives alone."""
    prompts = [_tokens(17, 1).tolist(), _tokens(5, 2).tolist()]
    both = np.asarray(model.generate(prompts, max_new_tokens=6))
    for i, p in enumerate(prompts):
        alone = np.asarray(model.generate([p], max_new_tokens=6))[0]
        np.testing.assert_array_equal(both[i], alone)
    seq = prompts[0] + both[0][:-1].tolist()
    logits = _ref_logits(ref, params, seq, 6)
    lp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    # greedy: the chosen token is within the bound of the reference's best
    assert np.all(lp.max(-1) - lp[np.arange(6), both[0]] < 0.08)


def test_long_memory_with_a_slow_decay(fam, ref, dense):
    """Random weights on the chip forget fast or slowly as they are drawn;
    here `a` is rigged to 1e-3, so the first token still weighs at the
    last, and a change of the FIRST token moves the last logits."""
    runs = {k: dict(r, a=jnp.full_like(r["a"], 1e-3)) if "a" in r else r
            for k, r in dense["runs"].items()}
    slow = dict(dense, runs=runs)
    toks = _tokens(40, 9)
    got, _ = _f32(fam, slow, toks[None])
    np.testing.assert_allclose(np.asarray(got[0]),
                               _ref_logits(ref, slow, toks, 40), atol=3e-4)
    other = toks.copy()
    other[0] = (other[0] + 1) % 255 + 1
    moved, _ = _f32(fam, slow, other[None])
    assert float(jnp.abs(moved[0, -1] - got[0, -1]).max()) > 1e-3


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,P,N,live", [
    (4, 4, 32, 16, [True, False, True, True]),
    (3, 8, 64, 128, [False, True, False]),
    (2, 4, 32, 16, [False, False]),
])
def test_mamba2_decode_against_its_jnp_form(B, H, P, N, live):
    from bigdl_tpu.ops.pallas.mamba2 import mamba2_decode

    k = jax.random.split(jax.random.PRNGKey(B), 6)
    R = B + 1
    ssm = jax.random.normal(k[0], (2, R, H * P, N), jnp.float32)
    x = jax.random.normal(k[1], (B, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[2], (B, H)) - 2)
    A = -jnp.exp(jax.random.normal(k[3], (H,)))
    Bm, Cm = jax.random.normal(k[4], (2, B, N))
    rows = jnp.asarray([R - 1 - i for i in range(B)], jnp.int32)
    live = jnp.asarray(live)
    y, out = mamba2_decode(ssm, jnp.int32(1), rows, live, x, dt, A, Bm, Cm,
                           interpret=True)
    want_y, h = kvhybrid.ssm_step(x, dt, A, Bm, Cm,
                                  ssm[1, rows].reshape(B, H, P, N))
    want = ssm.at[1, jnp.where(live, rows, R)].set(
        h.reshape(B, H * P, N), mode="drop")
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-6)
    idle = np.asarray(jnp.where(live, R, rows))  # rows nobody may touch
    np.testing.assert_array_equal(np.asarray(out[1])[idle[idle < R]],
                                  np.asarray(ssm[1])[idle[idle < R]])
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(ssm[0]))
    # the readout goes through bfloat16 halves of h and C: 2**-16 a product
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(jnp.where(live[:, None, None], want_y, 0)),
        atol=1e-3)


# ---------------------------------------------------------------------------
# the engine
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
    assert eng.kind is kvhybrid.CACHE_KIND and eng.cache.ssm.shape[1] == 2
    assert eng.cache.k.shape[0] == 1 and eng.cache.conv.shape[0] == 4
    reqs = [eng.submit(_tokens(n, 10 + n).tolist(), max_new_tokens=m)
            for n, m in ((19, 7), (6, 4), (30, 6))]
    eng.run_until_idle()
    for r in reqs:
        assert r.finish_reason == "length", (r.finish_reason, r.error)
        _check_request(ref, params, r)
    assert len({tuple(r.out_tokens) for r in reqs}) == 3
    assert eng.page_leaks() == 0
    assert eng.pages.radix.n_nodes == 0 and eng.pages.prefix_hits == 0
    again = eng.submit(list(reqs[0].prompt), max_new_tokens=7)
    eng.run_until_idle()
    assert again.out_tokens == reqs[0].out_tokens
    assert eng.pages.prefix_hits == 0


def test_an_idle_slots_row_is_unchanged_and_rows_do_not_mix(model):
    eng = shared_engine(model, n_slots=3, max_len=64, paged=True,
                        page_size=8)
    eng.cache = dataclasses.replace(
        eng.cache, ssm=eng.cache.ssm.at[:, 2].set(7.0),
        conv=eng.cache.conv.at[:, :, 2].set(7.0))
    a = eng.submit(_tokens(12, 1).tolist(), max_new_tokens=5)
    b = eng.submit(_tokens(9, 2).tolist(), max_new_tokens=5)
    eng.run_until_idle()
    assert np.all(np.asarray(eng.cache.ssm[:, 2]) == 7.0)  # never held
    assert np.all(np.asarray(eng.cache.conv[:, :, 2]) == 7.0)
    alone = shared_engine(model, n_slots=3, max_len=64, paged=True,
                          page_size=8)
    a2 = alone.submit(list(a.prompt), max_new_tokens=5)
    alone.run_until_idle()
    assert a2.out_tokens == a.out_tokens and a2.out_logprobs == a.out_logprobs
    assert b.finish_reason == "length"


def test_engine_chunked_prefill_continues_from_the_row(model, ref, params):
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
    assert parked.ssm.shape == eng.cache.ssm.shape[:1] + \
        eng.cache.ssm.shape[2:]
    assert parked.nbytes == eng.state_row_bytes + \
        parked.k.nbytes + parked.v.nbytes
    eng.run_until_idle()
    assert eng.preemption_resumes == 1
    assert r.out_tokens == want.out_tokens
    assert r.out_logprobs == want.out_logprobs  # bit-equal, not close
    assert other.finish_reason == "length" and eng.page_leaks() == 0


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
    seen = {(op, route) for op, route, _ in routes}
    assert ("mamba2", "pallas") in seen and ("mamba2", "xla") in seen
    assert ("attention", "pallas:paged") in seen
    ev = tr.events()
    steps = [e["args"] for e in ev if e["name"] == "decode_step"]
    row = eng.state_row_bytes
    H, P, N, inner, C = get_family("granitemoehybrid").dims(CFG)
    assert row == 4 * (inner * N + 3 * C) * 4  # 4 Mamba layers, float32
    assert steps and all(
        a["state_bytes_moved"] == 2 * a["state_rows_live"] * row
        and "live_pages" in a and "moe_experts_hit" in a for a in steps)
    assert max(a["state_rows_live"] for a in steps) == 2
    assert eng.state_bytes_moved == sum(a["state_bytes_moved"] for a in steps)
    pre = {e["args"]["prompt_tokens"]: e["args"]["state_chunks"]
           for e in ev if e["name"] == "prefill"}
    assert pre == {20: 4, 7: 2}  # buckets 32 and 16 at chunk 8
    text = Metrics(eng).render()
    assert "bigdl_tpu_state_rows_live 0" in text
    assert f"bigdl_tpu_state_pool_bytes {2 * row}" in text
    assert f"bigdl_tpu_state_bytes_moved_total {eng.state_bytes_moved}" in text
    assert metric_drift(text, eng) == ([], [])


# ---------------------------------------------------------------------------
# checkpoints
# ---------------------------------------------------------------------------

def test_save_low_bit_round_trips_the_runs(model, tmp_path):
    from bigdl_tpu.api import AutoModelForCausalLM

    model.save_low_bit(str(tmp_path))
    back = AutoModelForCausalLM.load_low_bit(str(tmp_path))
    assert back.config == CFG and sorted(back.params["runs"]) == [
        "00", "01", "02"]
    assert back.params["runs"]["00"]["a"].dtype == jnp.float16
    prompt = [_tokens(9, 3).tolist()]
    np.testing.assert_array_equal(
        np.asarray(back.generate(prompt, max_new_tokens=4)),
        np.asarray(model.generate(prompt, max_new_tokens=4)))


def test_hf_names_map_onto_the_tree(fam, dense):
    """A state dict under HF's names (modeling_granitemoehybrid) gives the
    logits of the tree it was written from."""
    from bigdl_tpu.convert.hf import params_from_state_dict

    sd = {"model.embed_tokens.weight": dense["embed"],
          "model.norm.weight": dense["final_norm"]}
    i = 0
    for (kind, _, n), run in zip(fam.layer_runs(CFG),
                                 dense["runs"].values()):
        for j in range(n):
            p, g = f"model.layers.{i}.", {k: v[j] for k, v in run.items()}
            sd[p + "input_layernorm.weight"] = g["attn_norm"]
            sd[p + "post_attention_layernorm.weight"] = g["mlp_norm"]
            if kind == "mamba":
                sd[p + "mamba.in_proj.weight"] = g["w_in"]
                sd[p + "mamba.out_proj.weight"] = g["w_out"]
                sd[p + "mamba.norm.weight"] = g["mixer_norm"]
                sd[p + "mamba.conv1d.weight"] = g["conv_w"].T[:, None, :]
                sd[p + "mamba.conv1d.bias"] = g["conv_b"]
                sd[p + "mamba.dt_bias"] = g["dt_bias"]
                sd[p + "mamba.D"] = g["D"]
                sd[p + "mamba.A_log"] = jnp.log(g["a"].astype(jnp.float32))
            else:
                for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                                     ("wv", "v_proj"), ("wo", "o_proj")):
                    sd[p + f"self_attn.{theirs}.weight"] = g[ours]
            sd[p + "block_sparse_moe.router.layer.weight"] = g["router"]
            sd[p + "block_sparse_moe.input_linear.weight"] = jnp.concatenate(
                [g["w_gate_e"], g["w_up_e"]], axis=1)
            sd[p + "block_sparse_moe.output_linear.weight"] = g["w_down_e"]
            sd[p + "shared_mlp.input_linear.weight"] = jnp.concatenate(
                [g["w_gate_s"], g["w_up_s"]], axis=0)
            sd[p + "shared_mlp.output_linear.weight"] = g["w_down_s"]
            i += 1
    sd = {k: np.asarray(v) for k, v in sd.items()}
    tree = params_from_state_dict(CFG, sd.__getitem__, qtype="bf16",
                                  dtype=jnp.float32)
    assert sorted(tree["runs"]) == ["00", "01", "02"]
    assert tree["runs"]["00"]["a"].dtype == jnp.float16
    assert tree["runs"]["00"]["conv_w"].dtype == jnp.float32
    toks = _tokens(12, 77)[None]
    got, _ = _f32(fam, tree, toks)
    want, _ = _f32(fam, dense, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    packed = params_from_state_dict(CFG, sd.__getitem__, qtype="sym_int4")
    assert packed["lm_head"].qtype == "sym_int4"  # the tied table, packed
    assert packed["runs"]["00"]["w_in"].qtype == "sym_int4"
    assert packed["runs"]["01"]["w_gate_e"].data.shape[:2] == (1, 8)
