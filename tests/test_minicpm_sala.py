"""MiniCPM-SALA (models/minicpm_sala.py, kvsparse.py): block-sparse attention
layers that select pages from pooled keys between lightning (decayed linear
attention) layers, served by `InferenceEngine(paged=True)`, against the plain
float32 reference `bench/reference/minicpm_sala.py`.

Small sizes on the CPU, seeded random weights (standard deviation 0.15, so
that the logits are not flat), a tiny `sparse_config`: windows of 8 keys
every 4, blocks of 16 = the page, 6 of a row's blocks, 1 initial block, a
local window of 32, dense under 64. A prompt of 150 tokens has 10 blocks, 4
of them forced: the top-k BINDS (2 free picks among 6). ONE engine serves
every case side by side (a dense row and a sparse row in one decode step);
the cases below read what it left and compile nothing of their own but the
reference and the controls. The module ASKS the kind for the chosen blocks
(`kvsparse.CACHE_KIND.report_ids`: a served engine reports five counts a row
and no ids), and the controls are faults PLANTED in `bigdl_tpu.kvsparse`
from here and from scripts/sparse_check_sweep.py (`planted`): the served
forward has no switch that breaks it.

Tolerances, and why. The engine computes in bfloat16 from sym_int4 weights,
the reference in float32 from the same weights. `LOGPROB_ATOL` 0.05 nats
holds the chosen token's log-probability of every emitted token (the worst
of all the cases reads 0.02). `WHOLE_ATOL` 0.06 holds the whole log-softmax
of a 150-token sequence's last 24 positions (0.027 at the worst of 12,000
entries), and the five controls (a rope on the sparse layers, a residual
scale without `scale_depth`, a selection without the local window, without
the initial block, pooled keys off by one window) read 0.23 to 0.63 there:
each has to read over 0.12. A bfloat16 state does not show in the logits at
this size (0.034): the state itself is held to a float64 scan instead.
"""

import contextlib
import dataclasses
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from engines import shared_engine

from bigdl_tpu import kvsparse
from bigdl_tpu.api import TpuModel, optimize_model
from bigdl_tpu.generate import GenerationConfig
from bigdl_tpu.models import get_family
from bigdl_tpu.models.config import ModelConfig
from bigdl_tpu.serving.engine import InferenceEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPARSE = dict(kernel_size=8, kernel_stride=4, block_size=16, topk=6,
              init_blocks=1, window_size=32, dense_len=64)
HF = dict(model_type="minicpm_sala", hidden_size=128, intermediate_size=256,
          num_hidden_layers=4,
          mixer_types=["minicpm4", "lightning-attn", "lightning-attn",
                       "minicpm4"],
          num_attention_heads=4, num_key_value_heads=2, head_dim=32,
          lightning_nh=4, lightning_nkv=4, lightning_head_dim=32,
          vocab_size=500, rms_norm_eps=1e-6, rope_theta=10000, scale_emb=12,
          scale_depth=1.4, dim_model_base=32, tie_word_embeddings=False,
          sparse_config=SPARSE)
LOGPROB_ATOL = 0.05
WHOLE_ATOL = 0.06
# (prompt tokens, new tokens): sparse with a binding top-k, and long enough
# to end a window that straddles two pages (tokens 156 .. 163) in decode;
# dense throughout; sparse, another prompt of the same bucket (both are
# right-padded to 160); dense that turns sparse while decoding (59 .. 66 pass
# dense_len 64). Two prefill programs in all.
CASES = [(150, 16), (50, 6), (145, 6), (59, 8)]
IDS = [f"P{p}-n{n}" for p, n in CASES]
ENGINE = dict(n_slots=4, max_len=256, paged=True, page_size=16, n_pages=65)


def _load(name, *path):
    import sys

    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, *path))
    mod = importlib.util.module_from_spec(spec)
    sys.path.insert(0, ROOT)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("bench_reference_minicpm_sala",
                 "bench", "reference", "minicpm_sala.py")


@pytest.fixture(scope="module")
def sweep():
    return _load("sparse_check_sweep", "scripts", "sparse_check_sweep.py")


@pytest.fixture(scope="module", autouse=True)
def _the_chosen_blocks_are_asked_for():
    """Every engine and cache of this module reports the ids too."""
    assert not kvsparse.CACHE_KIND.report_ids  # a served engine's default
    kvsparse.CACHE_KIND.report_ids = True
    yield
    kvsparse.CACHE_KIND.report_ids = False


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig.from_hf_config(HF)
    fam = get_family(cfg.model_type)
    params = fam.init_params(cfg, jax.random.PRNGKey(0), scale=0.15)
    return TpuModel(cfg, optimize_model(params, cfg, "sym_int4"), "sym_int4")


@pytest.fixture(scope="module")
def served(model):
    """The engine after the cases ran side by side: (engine, requests, what
    the pool held of the first request's row one step before its end)."""
    eng = shared_engine(model, gen=GenerationConfig(eos_token_id=None),
                        **ENGINE)
    rng = np.random.default_rng(0)
    reqs = [eng.submit(rng.integers(1, 500, p).tolist(), max_new_tokens=n)
            for p, n in CASES]
    held = None
    for _ in range(400):
        if all(r.done for r in reqs):
            break
        eng.step()
        eng._drain()  # what was dispatched has landed: the pool is whole
        long = reqs[0]
        if held is None and len(long.out_tokens) >= CASES[0][1] - 1:
            slot = next(i for i, s in enumerate(eng._slots) if s.req is long)
            row = np.asarray(eng.cache.block_tables[slot])
            held = {"pos": int(eng.cache.pos[slot]),
                    "k": np.asarray(eng.cache.k[:, row], np.float32),
                    "kp": np.asarray(eng.cache.kp[:, row], np.float32),
                    "state": np.asarray(eng.cache.state[:, slot])}
    assert all(r.done for r in reqs)
    return eng, reqs, held


def _logprobs(ref, model, r, take=True):
    n = len(r.out_tokens)
    seq = jnp.asarray(r.prompt + r.out_tokens[:-1], jnp.int32)
    f = jax.jit(ref.logits, static_argnums=(0, 3, 4, 5))
    from bench.records import Frozen

    lg = np.asarray(f(Frozen(HF), model.params, seq, n, ref._same, take),
                    np.float64)
    lse = np.log(np.exp(lg - lg.max(-1, keepdims=True)).sum(-1)) + lg.max(-1)
    return lg[np.arange(n), r.out_tokens] - lse


@pytest.mark.parametrize("case", range(len(CASES)), ids=IDS)
def test_the_engine_agrees_with_the_reference(ref, model, served, case):
    import weakref

    import bigdl_tpu.serving.engine as E

    _, reqs, _ = served
    r = reqs[case]
    assert len(r.out_tokens) == CASES[case][1] and r.finish_reason == "length"
    assert np.all(np.isfinite(r.out_logprobs))
    E._last_routed = weakref.ref(r)  # the request whose selection is taken
    want = _logprobs(ref, model, r)
    worst = np.abs(want - np.asarray(r.out_logprobs)).max()
    assert worst <= LOGPROB_ATOL, worst
    if case == 0:  # the reference's OWN selection is the same network here
        own = _logprobs(ref, model, r, take=False)
        assert np.abs(own - want).max() <= LOGPROB_ATOL


def test_the_topk_binds_and_a_dense_row_shares_the_step(served):
    eng, reqs, _ = served
    sz = kvsparse.Sizes.of(eng.config)
    long, short = reqs[0], reqs[1]
    sel = long.prompt_selection.reshape(2, 2, sz.topk)
    n_blocks = (len(long.prompt) - 1) // sz.block + 1
    assert n_blocks - sz.forced > sz.topk - sz.forced > 0  # free picks bind
    assert (sel >= 0).all() and sel.max() < n_blocks
    for layer_head in sel.reshape(-1, sz.topk):
        assert len(set(layer_head.tolist())) == sz.topk
        assert {0, n_blocks - 1, n_blocks - 2, n_blocks - 3} <= set(
            layer_head.tolist())
    # the short prompt's rows read all their pages, in the same steps
    assert short.prompt_selection.max() == -1
    assert all(s.max() == -1 for s in short.out_selection)
    assert len(long.out_selection) == len(long.out_tokens) - 1
    t = eng.report_totals
    assert t["sparse_rows_dense"] > 0 and t["sparse_pages_selected"] > 0
    assert 0 < t["sparse_pages_read"] <= t["sparse_pages_selected"]
    # a row that turns sparse while decoding: -1 at first, then its blocks
    # (5 of them, fewer than topk: all, and -1 in the sixth place)
    turn = reqs[3].out_selection[-1].reshape(2, 2, sz.topk)
    assert reqs[3].out_selection[0].max() == -1
    assert np.all(np.sort(turn, -1) == np.asarray([-1, 0, 1, 2, 3, 4]))
    assert eng.page_leaks() == 0


def test_pooled_keys_are_the_means_of_the_cached_keys(served):
    """Every complete window of the row, the prompt's and those that decode
    steps ended (one of them across two pages), is the mean of the keys as
    cached; no other slot of the row's pooled pages was written."""
    _, reqs, held = served
    pos, k, kp = held["pos"], held["k"], held["kp"]
    assert pos == CASES[0][0] + CASES[0][1] - 2
    Ls = k.shape[0]
    keys = k.reshape(Ls, -1, *k.shape[3:])  # [Ls, slots, Hkv, D]
    windows = kp.reshape(Ls, -1, *kp.shape[3:])
    n_done = (pos - 8) // 4 + 1  # tokens 0 .. pos - 1 are cached
    assert n_done > (CASES[0][0] - 8) // 4 + 1  # some were ended in decode
    assert any(j % 4 == 3 and 4 * j + 7 >= CASES[0][0]
               for j in range(n_done))  # ... one across two pages
    for j in range(n_done):
        want = keys[:, 4 * j:4 * j + 8].mean(axis=1)
        np.testing.assert_allclose(windows[:, j], want, atol=0.02)
    # (the table's entries past the row's pages name page 0, the sink)
    assert np.all(windows[:, n_done:(pos // 16 + 1) * 4] == 0)


def test_a_padded_bucket_leaves_state_and_windows_alone(model):
    """A prompt of 97 tokens in its bucket of 112: the state after it is the
    state after 97 tokens, and no window past the prompt's is written."""
    fam = get_family("minicpm_sala")
    cfg = model.config
    rng = np.random.default_rng(1)
    toks = rng.integers(1, 500, 112)

    @functools.partial(jax.jit, static_argnums=0)
    def forward(T, params, valid):
        cache = dataclasses.replace(fam.init_cache(cfg, 1, 128),
                                    valid_len=valid)
        return fam.forward(cfg, params, jnp.asarray(toks[None, :T]), cache,
                           last_logits_only=True)[1]

    def run(T, valid):
        return forward(T, model.params, jnp.asarray([valid], jnp.int32))

    padded, exact = run(112, 97), run(97, 97)
    np.testing.assert_allclose(np.asarray(padded.state),
                               np.asarray(exact.state), atol=1e-5)
    assert int(padded.pos[0]) == 97
    np.testing.assert_array_equal(np.asarray(padded.kp, np.float32),
                                  np.asarray(exact.kp, np.float32))


def test_the_chunked_lightning_form_is_the_plain_scan():
    """Across chunk seams, with the slowest decay (2^-8 a token) and padding
    in the middle of a chunk."""
    H, D, T = 32, 8, 45
    rng = np.random.default_rng(2)
    q, k, v = (jnp.asarray(rng.normal(size=(1, T, H, D)), jnp.float32)
               for _ in range(3))
    valid = jnp.asarray((np.arange(T) < 41)[None])
    s = jnp.asarray(kvsparse.slopes(H))
    assert float(s.min()) == 2.0 ** -8
    h0 = jnp.asarray(rng.normal(size=(1, H, D, D)), jnp.float32)
    y, h = kvsparse.lightning_chunked(q, k, v, valid, s, h0, 16)
    hs, ys = h0, []
    for t in range(T):
        yt, new = kvsparse.lightning_step(q[:, t], k[:, t], v[:, t],
                                          jnp.exp(-s), hs)
        hs = jnp.where(valid[0, t], new, hs)
        ys.append(yt)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hs), atol=2e-4)
    np.testing.assert_allclose(np.asarray(y[0, :41]),
                               np.asarray(jnp.stack(ys, 1)[0, :41]),
                               atol=2e-4)


@pytest.mark.parametrize("fault,holds", [(None, True),
                                         ("bfloat16 state", False)],
                         ids=["float32", "bfloat16-fails"])
def test_the_state_is_kept_in_float32(sweep, fault, holds):
    """The state after 120 tokens against a float64 scan: the program's
    float32 state agrees to 1e-4 of its scale, one rounded to bfloat16
    between tokens (the planted control) does not."""
    H, D, T = 4, 32, 120
    rng = np.random.default_rng(6)
    q, k, v = (rng.normal(size=(1, T, H, D)) for _ in range(3))
    cache = kvsparse.init_sparse(1, 1, 9, 16, 2, 32, 1, 8, H * D, D, 4, 6)
    cache = dataclasses.replace(
        cache, block_tables=1 + jnp.arange(8, dtype=jnp.int32)[None])
    with sweep.planted(fault) if fault else contextlib.nullcontext():
        _, c = kvsparse.lightning_mix(
            cache, jnp.asarray(0), *(jnp.asarray(a, jnp.float32)
                                     for a in (q, k, v)),
            chunk=32, decode=False)
    lam = np.exp(-kvsparse.slopes(H).astype(np.float64))[:, None, None]
    S = np.zeros((H, D, D))
    for t in range(T):  # S[h, p, n]: the value index, then the key's
        S = lam * S + v[0, t][:, :, None] * k[0, t][:, None, :]
    err = np.abs(np.asarray(c.state[0, 0]).reshape(H, D, D) - S).max()
    assert (err <= 1e-4 * np.abs(S).max()) == holds, err


def test_the_sparse_decode_kernel_against_float32():
    from bigdl_tpu.ops.pallas.paged_attention import (
        paged_sparse_decode_attention,
    )

    rng = np.random.default_rng(3)
    B, Hq, Hkv, D, page, NP, U = 3, 4, 2, 128, 16, 40, 24
    q = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.bfloat16)
    kp = jnp.asarray(rng.normal(size=(2, NP, page, Hkv, D)), jnp.bfloat16)
    vp = jnp.asarray(rng.normal(size=(2, NP, page, Hkv, D)), jnp.bfloat16)
    plist = jnp.asarray(rng.permutation(NP - 1)[:B * U].reshape(B, U) + 1
                        if B * U < NP else rng.integers(1, NP, (B, U)),
                        jnp.int32)
    n = jnp.asarray([U, 7, 0], jnp.int32)
    reads = jnp.asarray(rng.random((B, Hkv, U)) < 0.6)
    reads = reads.at[jnp.arange(B), :, jnp.maximum(n - 1, 0)].set(True)
    fill = jnp.asarray([5, 16, 1], jnp.int32)
    out = paged_sparse_decode_attention(
        q, kp, vp, plist, n, reads, jnp.asarray(1), fill, scale=D ** -0.5,
        interpret=True)
    k = np.asarray(kp[1], np.float32)[np.asarray(plist)]  # [B, U, page, ..]
    v = np.asarray(vp[1], np.float32)[np.asarray(plist)]
    for b in range(B):
        nb = int(n[b])
        if nb == 0:
            assert np.all(np.asarray(out[b], np.float32) == 0)
            continue
        for hq in range(Hq):
            h = hq // (Hq // Hkv)
            ok = np.zeros((U, page), bool)
            ok[:nb] = np.asarray(reads[b, h, :nb])[:, None]
            ok[nb - 1, int(fill[b]):] = False
            s = np.einsum("d,upd->up", np.asarray(q[b, hq], np.float32),
                          k[b, :, :, h]) * D ** -0.5
            p = np.where(ok, np.exp(s - s[ok].max()), 0.0)
            want = np.einsum("up,upd->d", p / p.sum(), v[b, :, :, h])
            np.testing.assert_allclose(np.asarray(out[b, hq], np.float32),
                                       want, atol=0.02)


def test_flash_attention_under_a_selections_mask():
    """The prefill's kernel with the int8 mask beside its causal bound
    (interpreted) against masked softmax attention in `jnp`."""
    from bigdl_tpu.ops.attention import attention
    from bigdl_tpu.ops.pallas import flash_attention

    rng = np.random.default_rng(7)
    T, Hq, Hkv, D = 192, 4, 2, 128
    q = jnp.asarray(rng.normal(size=(1, T, Hq, D)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, T, Hkv, D)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, T, Hkv, D)), jnp.bfloat16)
    blocks = rng.random((1, Hkv, T, T // 16)) < 0.5
    mask = jnp.asarray(np.repeat(blocks, 16, axis=-1)
                       | np.eye(T, dtype=bool), jnp.int8)  # its own key
    got = flash_attention(q, k, v, scale=D ** -0.5, mask=mask,
                          block_q=64, block_k=128, interpret=True)
    allowed = (mask != 0) & jnp.tril(jnp.ones((T, T), bool))
    want = attention(q, k, v, mask=allowed[:, :, None], scale=D ** -0.5)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=0.03)


@pytest.mark.parametrize("budget,admitted", [(99, (1, 2, 3)),
                                             (100, (2, 3, 3)),
                                             (16384, (3, 3, 3))])
def test_a_step_admits_a_budget_of_prompt_tokens(model, monkeypatch, budget,
                                                 admitted):
    """Long prompts prefill for seconds: a `step()` admits a further prompt
    only while the prompts of this call, with it, stay within
    `ADMIT_TOKENS_PER_STEP` (the first always is; three of 50 tokens here),
    and the rows already admitted decode a step before the next (every
    kind's rule: serving/engine.py)."""
    import bigdl_tpu.serving.engine as E

    monkeypatch.setattr(E, "ADMIT_TOKENS_PER_STEP", budget)
    eng = shared_engine(model, gen=GenerationConfig(eos_token_id=None),
                        **ENGINE)
    rng = np.random.default_rng(8)
    reqs = [eng.submit(rng.integers(1, 500, 50).tolist(), max_new_tokens=12)
            for _ in range(3)]
    for n in admitted:
        eng.step()
        assert int(eng.active.sum()) == n
    while not all(r.done for r in reqs):
        eng.step()
    assert [len(r.out_tokens) for r in reqs] == [12, 12, 12]
    assert eng.page_leaks() == 0


def test_the_state_kernel_against_float32():
    from bigdl_tpu.ops.pallas.mamba2 import lightning_decode

    rng = np.random.default_rng(4)
    B, H, D, R = 3, 4, 128, 5
    state = jnp.asarray(rng.normal(size=(2, R, H * D, D)), jnp.float32)
    q, k, v = (jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
               for _ in range(3))
    decay = jnp.exp(-jnp.asarray(kvsparse.slopes(H)))
    rows = jnp.asarray([3, 0, 4], jnp.int32)
    live = jnp.asarray([True, False, True])
    y, new = lightning_decode(state, jnp.asarray(1), rows, live, v, decay, k,
                              q, interpret=True)
    h0 = state[1][rows].reshape(B, H, D, D)
    want_y, want_h = kvsparse.lightning_step(q, k, v, decay, h0)
    for b in (0, 2):
        np.testing.assert_allclose(np.asarray(y[b]), np.asarray(want_y[b]),
                                   rtol=1e-4, atol=1e-3)
        np.testing.assert_allclose(
            np.asarray(new[1, int(rows[b])]).reshape(H, D, D),
            np.asarray(want_h[b]), rtol=1e-5, atol=1e-5)
    assert np.all(np.asarray(y[1]) == 0)
    keep = np.ones(R, bool)
    keep[[3, 4]] = False
    np.testing.assert_array_equal(np.asarray(new[1])[keep],
                                  np.asarray(state[1])[keep])
    np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(state[0]))


@contextlib.contextmanager
def _rope_on_the_sparse_layers(monkeypatch):
    """The sparse prefill layer with HF's half-split rope on its q and k (a
    whole sequence from an empty row: a position is its index)."""
    from bigdl_tpu.ops.rope import (
        apply_rotary_emb, default_inv_freq, rope_cos_sin,
    )

    whole = kvsparse.sparse_prefill_layer

    def roped(cache, layer, q, k, *rest):
        cos, sin = rope_cos_sin(jnp.arange(q.shape[1])[None],
                                default_inv_freq(q.shape[-1], 10000.0))
        return whole(cache, layer, *apply_rotary_emb(q, k, cos, sin), *rest)

    with monkeypatch.context() as m:
        m.setattr(kvsparse, "sparse_prefill_layer", roped)
        yield


# a broken forward: a fault planted in kvsparse (scripts/sparse_check_sweep
# .planted, by its name there), or a config without the family's scale
CONTROLS = {
    "rope_on_the_sparse_layers": _rope_on_the_sparse_layers,
    "no_scale_depth": {"residual_scale": 1.0 / 2.0},
    "no_local_window": "no local window",
    "no_initial_block": "no initial block",
    "windows_off_by_one": "windows off by one",
}


@pytest.fixture(scope="module")
def whole(ref, model):
    """A sequence of 150 tokens: (the reference's log-softmax at its last 24
    positions, the program's under the config `over` writes over)."""
    from bench.records import Frozen

    fam, cfg = get_family("minicpm_sala"), model.config
    toks = jnp.asarray(np.random.default_rng(5).integers(1, 500, 150),
                       jnp.int32)
    want = jax.nn.log_softmax(jax.jit(
        ref.logits, static_argnums=(0, 3, 4, 5))(
            Frozen(HF), model.params, toks, 24, ref._same, False))

    def program(**over):  # a new jit a call: traced with what is planted
        lg, _ = jax.jit(functools.partial(
            fam.forward, dataclasses.replace(cfg, **over)))(
                model.params, toks[None], None)
        return jax.nn.log_softmax(lg[0, -24:])

    return want, program


def test_the_whole_sequence_forward_agrees(whole):
    want, program = whole
    assert float(jnp.abs(program() - want).max()) <= WHOLE_ATOL


@pytest.mark.parametrize("name", sorted(CONTROLS))
def test_a_broken_forward_fails(whole, sweep, monkeypatch, name):
    want, program = whole
    fault = CONTROLS[name]
    if isinstance(fault, dict):
        got = program(**fault)
    else:
        with (sweep.planted(fault) if isinstance(fault, str)
              else fault(monkeypatch)):
            got = program()
    worst = float(jnp.abs(got - want).max())
    assert worst > 2 * WHOLE_ATOL, worst


def test_what_cannot_be_served_is_refused_by_name(model):
    bad = [({"lightning_nkv": 2}, "lightning_nkv"),
           ({"attn_use_rope": True}, "attn_use_rope"),
           ({"use_output_gate": False}, "use_output_gate"),
           ({"sparse_config": {**SPARSE, "kernel_size": 12}}, "sparse_config"),
           ({"sparse_config": {"nope": 1}}, "nope")]
    for over, word in bad:
        with pytest.raises((NotImplementedError, ValueError), match=word):
            ModelConfig.from_hf_config({**HF, **over})
    with pytest.raises(NotImplementedError, match="page"):
        InferenceEngine(model, **{**ENGINE, "page_size": 32})
    for opts, word in (({"quantize_kv": True}, "quantize_kv"),
                       ({"prefill_chunk_tokens": 64}, "prefill_chunk_tokens"),
                       ({"paged": False}, "paged=True")):
        with pytest.raises(NotImplementedError, match=word):
            InferenceEngine(model, **{**ENGINE, **opts})
    with pytest.raises(NotImplementedError, match="left padding"):
        model.generate([[1, 2, 3]], max_new_tokens=2)
    assert not kvsparse.CACHE_KIND.share_prefixes
