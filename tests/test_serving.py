"""Continuous-batching engine + OpenAI-compatible server tests.

Oracle: a request served through the slot engine (joining a batch with
other in-flight requests, staggered admission) must produce exactly the
greedy tokens the plain `model.generate` path yields for the same
prompt — continuous batching is a scheduling optimization, never a
quality change (the reference's PPModelWorker makes the same implicit
promise, pipeline_parallel.py:482-929). One sanctioned divergence: with
eos_token_id set, the engine finishes the request WITHOUT emitting the
EOS id itself, while model.generate includes it (then pads).
"""

import json
import queue
import urllib.request

import jax
import numpy as np
import pytest

from bigdl_tpu import optimize_model
from bigdl_tpu.api import TpuModel
from bigdl_tpu.generate import GenerationConfig
from bigdl_tpu.models import llama
from bigdl_tpu.models.config import PRESETS
from bigdl_tpu.serving.engine import InferenceEngine
from engines import shared_engine

CFG = PRESETS["tiny-llama"]


@pytest.fixture(scope="module")
def model():
    params = optimize_model(
        llama.init_params(CFG, jax.random.PRNGKey(7)), CFG, "sym_int4"
    )
    return TpuModel(CFG, params, "sym_int4")


PROMPTS = [
    [3, 1, 4, 1, 5, 9, 2, 6],
    [2, 7, 1, 8],
    [9, 9, 8, 2, 4],
]


@pytest.mark.core
def test_engine_matches_generate(model):
    want = {
        tuple(p): model.generate([p], max_new_tokens=10)[0].tolist()
        for p in PROMPTS
    }
    eng = shared_engine(model, n_slots=2, max_len=128)
    # staggered admission: 2 slots, 3 requests — the third joins only when
    # a slot frees, mid-flight of the others
    reqs = [eng.submit(p, max_new_tokens=10) for p in PROMPTS]
    eng.run_until_idle(max_steps=200)
    for p, r in zip(PROMPTS, reqs):
        assert r.done
        assert r.out_tokens == want[tuple(p)], (p, r.out_tokens, want[tuple(p)])


def test_engine_streaming_queue(model):
    eng = shared_engine(model, n_slots=2, max_len=128)
    q: queue.SimpleQueue = queue.SimpleQueue()
    req = eng.submit(PROMPTS[0], max_new_tokens=6, stream=q)
    eng.run_until_idle(max_steps=100)
    got = []
    while True:
        t = q.get_nowait()
        if t is None:
            break
        got.append(t)
    assert got == req.out_tokens and len(got) == 6


def test_engine_eos_frees_slot(model):
    # force an early EOS: run one request, take its 3rd token as eos id.
    # The oracle is the FIRST occurrence of that id — this seed's tiny
    # model greedily repeats one token, so ref[2] can already appear at
    # index 0 and the engine correctly stops there (the old hardcoded
    # ref[:2] oracle assumed the first occurrence was at index 2; these
    # were the two pre-existing seed failures noted in PR 10)
    ref = model.generate([PROMPTS[0]], max_new_tokens=8)[0].tolist()
    eos = ref[2]
    eng = shared_engine(
        model, n_slots=1, max_len=128,
        gen=GenerationConfig(eos_token_id=eos),
    )
    r1 = eng.submit(PROMPTS[0], max_new_tokens=8)
    r2 = eng.submit(PROMPTS[1], max_new_tokens=4)
    eng.run_until_idle(max_steps=100)
    # the EOS id itself is not emitted as text (finish_reason records it)
    assert r1.done and r1.finish_reason == "stop"
    assert r1.out_tokens == ref[: ref.index(eos)]
    assert r2.done and len(r2.out_tokens) == 4


def test_oversized_max_tokens_clamped(model):
    """max_new_tokens >= max_len must not crash the engine (regression:
    bucket went to zero and the worker thread died)."""
    eng = shared_engine(model, n_slots=1, max_len=128)
    r = eng.submit(PROMPTS[0], max_new_tokens=5000)
    eng.run_until_idle(max_steps=300)
    assert r.done and r.error is None
    assert len(r.out_tokens) == 128 - 16  # clamped budget
    assert r.finish_reason == "length"


@pytest.mark.core
def test_finish_reason_stop_vs_length(model):
    ref = model.generate([PROMPTS[0]], max_new_tokens=8)[0].tolist()
    eng = shared_engine(
        model, n_slots=1, max_len=128,
        gen=GenerationConfig(eos_token_id=ref[2]),
    )
    stopped = eng.submit(PROMPTS[0], max_new_tokens=8)
    eng.run_until_idle(max_steps=100)
    assert stopped.finish_reason == "stop"
    eng2 = shared_engine(model, n_slots=1, max_len=128)
    capped = eng2.submit(PROMPTS[0], max_new_tokens=4)
    eng2.run_until_idle(max_steps=100)
    assert capped.finish_reason == "length"


def test_api_server_endpoints(model):
    from bigdl_tpu.serving.api_server import ApiServer

    server = ApiServer(model, host="127.0.0.1", port=0, n_slots=2, max_len=128)
    server.start()
    base = f"http://127.0.0.1:{server.port}"
    try:
        with urllib.request.urlopen(base + "/health", timeout=10) as r:
            assert json.load(r)["status"] == "ok"

        body = json.dumps({"prompt": PROMPTS[0], "max_new_tokens": 6}).encode()
        req = urllib.request.Request(
            base + "/generate", data=body,
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=60) as r:
            out = json.load(r)
        want = model.generate([PROMPTS[0]], max_new_tokens=6)[0].tolist()
        assert out["tokens"] == want

        body = json.dumps(
            {"messages": [{"role": "user", "content": PROMPTS[1]}],
             "max_tokens": 4}
        ).encode()
        req = urllib.request.Request(
            base + "/v1/chat/completions", data=body,
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=60) as r:
            out = json.load(r)
        assert out["object"] == "chat.completion"
        assert out["choices"][0]["finish_reason"] in ("stop", "length")

        # streaming SSE
        body = json.dumps({"prompt": PROMPTS[2], "max_new_tokens": 4}).encode()
        req = urllib.request.Request(
            base + "/generate_stream", data=body,
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=60) as r:
            events = [
                ln for ln in r.read().decode().splitlines()
                if ln.startswith("data: ")
            ]
        assert events[-1] == "data: [DONE]"
        toks = [json.loads(e[6:])["token"] for e in events[:-1]]
        want = model.generate([PROMPTS[2]], max_new_tokens=4)[0].tolist()
        assert toks == want
    finally:
        server.shutdown()


def test_per_request_sampling_independent_streams(model):
    """VERDICT round-1 #10: two concurrent requests with different
    sampling params produce correct independent streams in ONE compiled
    decode program.

    Oracles: (a) a greedy request must still match model.generate while a
    hot-temperature sampled request shares the batch; (b) a sampled
    request with top_k=1 IS argmax, so it must also match the greedy
    reference despite going through the sampling branch."""
    ref0 = model.generate([PROMPTS[0]], max_new_tokens=10)[0].tolist()
    ref1 = model.generate([PROMPTS[1]], max_new_tokens=10)[0].tolist()

    eng = shared_engine(model, n_slots=3, max_len=128)
    greedy = eng.submit(PROMPTS[0], max_new_tokens=10)
    hot = eng.submit(PROMPTS[2], max_new_tokens=10,
                     do_sample=True, temperature=5.0)
    topk1 = eng.submit(PROMPTS[1], max_new_tokens=10,
                       do_sample=True, temperature=3.0, top_k=1)
    eng.run_until_idle(max_steps=100)
    assert greedy.done and hot.done and topk1.done
    assert greedy.out_tokens == ref0
    assert topk1.out_tokens == ref1
    assert len(hot.out_tokens) == 10


def test_per_request_eos(model):
    ref = model.generate([PROMPTS[0]], max_new_tokens=8)[0].tolist()
    eng = shared_engine(model, n_slots=2, max_len=128)
    # same prompt, two different per-request EOS ids. The stop oracle is
    # everything BEFORE the eos id's first occurrence (this seed's model
    # repeats its greedy token, so ref[2] can occur at index 0 — the old
    # ref[:2] oracle was the second pre-existing seed failure, PR 10)
    r_stop = eng.submit(PROMPTS[0], max_new_tokens=8, eos_token_id=ref[2])
    r_full = eng.submit(PROMPTS[0], max_new_tokens=8, eos_token_id=-1)
    eng.run_until_idle(max_steps=100)
    assert r_stop.finish_reason == "stop"
    assert r_stop.out_tokens == ref[: ref.index(ref[2])]
    assert r_full.out_tokens == ref and r_full.finish_reason == "length"


def test_server_sampling_passthrough(model):
    from bigdl_tpu.serving.api_server import ApiServer

    ref = model.generate([PROMPTS[0]], max_new_tokens=6)[0].tolist()
    srv = ApiServer(model, host="127.0.0.1", port=0, n_slots=2, max_len=128)
    srv.start()
    try:
        # temperature=0 → greedy per the OpenAI convention
        body = json.dumps({"prompt": PROMPTS[0], "max_new_tokens": 6,
                           "temperature": 0}).encode()
        r = urllib.request.urlopen(
            urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/generate", data=body,
                headers={"Content-Type": "application/json"},
            ), timeout=60,
        )
        out = json.loads(r.read())
        assert out["tokens"] == ref
        # sampled with top_k=1 ≡ greedy, exercised through the HTTP layer
        body = json.dumps({"prompt": PROMPTS[0], "max_new_tokens": 6,
                           "temperature": 2.5, "top_k": 1}).encode()
        r = urllib.request.urlopen(
            urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/generate", data=body,
                headers={"Content-Type": "application/json"},
            ), timeout=60,
        )
        assert json.loads(r.read())["tokens"] == ref
    finally:
        srv.shutdown()


def test_engine_repetition_penalty_matches_generate(model):
    """A greedy request with repetition_penalty must emit exactly what
    TpuModel.generate(repetition_penalty=) emits (same per-step seen
    semantics), and concurrent no-penalty requests stay unaffected."""
    prompt = [5, 6, 7, 8, 5, 6]
    ref = model.generate([prompt], max_new_tokens=8, repetition_penalty=1.5)

    eng = shared_engine(model, n_slots=4, max_len=128)
    r_pen = eng.submit(prompt, max_new_tokens=8, repetition_penalty=1.5)
    r_plain = eng.submit(prompt, max_new_tokens=8)
    eng.run_until_idle()
    assert r_pen.out_tokens == ref[0].tolist()
    assert r_plain.out_tokens == model.generate(
        [prompt], max_new_tokens=8
    )[0].tolist()


def test_engine_serves_mla_family():
    """DeepSeek (MLA latent cache) through the continuous-batching
    engine: concurrent greedy requests must match TpuModel.generate
    per prompt, and admission works mid-flight."""
    from bigdl_tpu.models import deepseek
    from bigdl_tpu.models.config import ModelConfig

    cfg = ModelConfig.from_hf_config(dict(
        model_type="deepseek_v2", vocab_size=96, hidden_size=64,
        intermediate_size=128, num_hidden_layers=2, num_attention_heads=4,
        q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=0,
        first_k_dense_replace=2,
    ))
    params = deepseek.quantize_params(
        deepseek.init_params(cfg, jax.random.PRNGKey(0)), "sym_int4"
    )
    m = TpuModel(cfg, params, "sym_int4")

    prompts = [[3, 1, 4, 1, 5], [9, 2, 6], [5, 3, 5, 8]]
    refs = [m.generate([p], max_new_tokens=6)[0].tolist() for p in prompts]

    eng = shared_engine(m, n_slots=2, max_len=128)  # < len(prompts): requeue
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run_until_idle()
    for r, ref in zip(reqs, refs):
        assert r.out_tokens == ref, (r.out_tokens, ref)

    # paged, the family serves from latent pages (PR 34) and says the same
    paged = shared_engine(m, n_slots=2, max_len=64, paged=True,
                          page_size=16, n_pages=9)
    reqs = [paged.submit(p, max_new_tokens=6) for p in prompts]
    paged.run_until_idle()
    for r, ref in zip(reqs, refs):
        assert r.out_tokens == ref, (r.out_tokens, ref)
    assert paged.page_leaks() == 0


def test_paged_refuses_a_family_cache_that_is_not_a_pool():
    """Paged mode is a page-pool concept: a family whose cache is neither
    KV pages, a state row nor latent pages refuses clearly."""
    from bigdl_tpu.models import rwkv
    from bigdl_tpu.models.config import ModelConfig

    cfg = ModelConfig.from_hf_config(dict(
        model_type="rwkv", vocab_size=96, hidden_size=64,
        num_hidden_layers=2, attention_hidden_size=64,
        intermediate_size=128))
    m = TpuModel(cfg, rwkv.init_params(cfg, jax.random.PRNGKey(0)), "bf16")
    with pytest.raises(NotImplementedError, match="paged"):
        shared_engine(m, n_slots=2, max_len=64, paged=True)


def test_engine_rejects_unsupported_family_caches():
    """Every in-tree family now serves (SERVABLE_CACHE or the
    engine_pool/engine_insert adapter pair); the gates still protect
    against future families with neither, and against HALF an adapter —
    which would silently mix the custom and generic cache paths."""
    import types

    from bigdl_tpu.models.config import ModelConfig

    cfg = ModelConfig(
        vocab_size=64, hidden_size=64, num_hidden_layers=1,
        num_attention_heads=1, num_key_value_heads=1, intermediate_size=128,
    )
    fake_family = types.SimpleNamespace(
        init_cache=lambda *a, **k: None, forward=lambda *a, **k: None,
    )
    fake_model = types.SimpleNamespace(
        config=cfg, family=fake_family, params={}, qtype="bf16",
    )
    with pytest.raises(NotImplementedError, match="cache layout"):
        shared_engine(fake_model, n_slots=2, max_len=64)
    fake_family.engine_pool = lambda *a, **k: None  # half an adapter
    with pytest.raises(TypeError, match="must be defined together"):
        shared_engine(fake_model, n_slots=2, max_len=64)


def test_engine_speculative_matches_generate(model):
    """Speculative serving is byte-identical to plain greedy serving per
    request, and genuinely emits >1 token per verify round (here the
    draft IS the target, so acceptance is ~always draft_k-1)."""
    want = {
        tuple(p): model.generate([p], max_new_tokens=12)[0].tolist()
        for p in PROMPTS
    }
    eng = shared_engine(
        model, n_slots=2, max_len=128, speculative=True,
        draft_params=model.params, draft_k=4,
    )
    reqs = [eng.submit(p, max_new_tokens=12) for p in PROMPTS]
    eng.run_until_idle(max_steps=300)
    for p, r in zip(PROMPTS, reqs):
        assert r.done
        assert r.out_tokens == want[tuple(p)], (p, r.out_tokens)
    # the speedup claim: tokens per verify round must exceed 1
    assert eng.spec_rounds > 0
    assert eng.spec_emitted / eng.spec_rounds > 1.0, (
        eng.spec_emitted, eng.spec_rounds
    )


def test_engine_speculative_sampled_rides_along(model):
    """A do_sample request in a speculative batch accepts 0 drafts but
    still completes with the requested token budget."""
    eng = shared_engine(
        model, n_slots=2, max_len=128, speculative=True,
        draft_params=model.params, draft_k=4,
        gen=GenerationConfig(do_sample=False),
    )
    r1 = eng.submit(PROMPTS[0], max_new_tokens=8)
    r2 = eng.submit(PROMPTS[1], max_new_tokens=8, do_sample=True,
                    temperature=0.9)
    eng.run_until_idle(max_steps=300)
    assert r1.done and r2.done
    assert len(r1.out_tokens) == 8 and len(r2.out_tokens) == 8
    # greedy request still byte-identical in the mixed batch
    want = model.generate([PROMPTS[0]], max_new_tokens=8)[0].tolist()
    assert r1.out_tokens == want


@pytest.mark.parametrize("model_type", ["rwkv5", "yuan", "mllama"])
def test_engine_custom_cache_families(model_type):
    """rwkv/yuan/mllama serve through the engine via their
    engine_pool/engine_insert adapters (VERDICT r03 weak #4: the
    SERVABLE_CACHE gate refused them); engine output == generate()."""
    from bigdl_tpu.models.config import ModelConfig
    from bigdl_tpu.models import get_family

    if model_type == "rwkv5":
        cfg = ModelConfig(
            model_type="rwkv5", vocab_size=64, hidden_size=32,
            attention_hidden_size=32, rwkv_head_size=8,
            rwkv_group_norm_eps=64e-5, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=4,
            intermediate_size=64, norm_type="layernorm",
        )
    elif model_type == "yuan":
        cfg = ModelConfig(
            model_type="yuan", vocab_size=96, hidden_size=32,
            intermediate_size=64, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=4,
            max_position_embeddings=256,
        )
    else:
        cfg = ModelConfig(
            model_type="mllama", vocab_size=96, hidden_size=64,
            intermediate_size=128, num_hidden_layers=3,
            num_attention_heads=4, num_key_value_heads=2,
            cross_attention_layers=(1,), max_position_embeddings=256,
        )
    fam = get_family(model_type)
    m = TpuModel(cfg, fam.init_params(cfg, jax.random.PRNGKey(3)), "bf16")
    prompts = [[3, 1, 4, 1, 5], [2, 7], [9, 9, 8, 2]]
    want = {
        tuple(p): m.generate([p], max_new_tokens=8)[0].tolist()
        for p in prompts
    }
    eng = shared_engine(m, n_slots=2, max_len=128)
    reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
    eng.run_until_idle(max_steps=300)
    for p, r in zip(prompts, reqs):
        assert r.done
        assert r.out_tokens == want[tuple(p)], (model_type, p, r.out_tokens,
                                                want[tuple(p)])


def test_rejection_accept_exact_distribution():
    """Speculative sampling must leave the output law unchanged: over
    many keys, the first emitted token's empirical distribution matches
    the target distribution p_0 exactly (TV < 3%), for an arbitrary
    draft proposal — the Leviathan et al. guarantee that lets the engine
    serve sampling requests speculatively."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.decode.speculative import rejection_accept

    V, K = 6, 4
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(1, K, V)) * 1.5, jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    drafts = jnp.asarray([[2, 4, 1, 3]], jnp.int32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    row_g = jnp.asarray([False])
    row_s = jnp.asarray([True])

    def first_token(key):
        n_acc, extra = rejection_accept(key, probs, drafts, greedy,
                                        row_g, row_s)
        # emitted position 0: draft 0 if accepted, else the resample
        return jnp.where(n_acc > 0, drafts[0, 0], extra[0])

    n = 20000
    toks = jax.vmap(first_token)(
        jax.random.split(jax.random.PRNGKey(1), n)
    )
    emp = np.bincount(np.asarray(toks).ravel(), minlength=V) / n
    tv = 0.5 * np.abs(emp - np.asarray(probs[0, 0])).sum()
    assert tv < 0.03, (tv, emp, np.asarray(probs[0, 0]))

    # greedy rows stay deterministic argmax-match
    n_acc, extra = rejection_accept(
        jax.random.PRNGKey(2), probs, drafts, greedy,
        jnp.asarray([True]), jnp.asarray([False]),
    )
    want = 0
    for i in range(K - 1):
        if int(drafts[0, i]) != int(greedy[0, i]):
            break
        want += 1
    assert int(n_acc[0]) == want
    assert int(extra[0]) == int(greedy[0, want])


def test_engine_speculative_sampling_accepts_drafts(model):
    """With draft == target, sampling rows now accept drafts with
    probability p(argmax) > 0 — rounds emit more than 1 token on
    average, and requests still complete with their full budget."""
    eng = shared_engine(
        model, n_slots=2, max_len=128, speculative=True,
        draft_params=model.params, draft_k=4,
    )
    reqs = [eng.submit(p, max_new_tokens=16, do_sample=True,
                       temperature=0.7) for p in PROMPTS]
    eng.run_until_idle(max_steps=400)
    for r in reqs:
        assert r.done and len(r.out_tokens) == 16
    assert eng.spec_rounds > 0
    # acceptance is stochastic, but with the draft == the target the
    # argmax carries most of the mass at temperature 0.7 — across two
    # 16-token requests at least SOME draft must be accepted
    assert eng.spec_emitted / eng.spec_rounds > 1.0, (
        eng.spec_emitted, eng.spec_rounds
    )


def test_engine_speculative_mla_family():
    """Speculative decoding over the MLA latent cache (SERVABLE_CACHE
    families): the latent dataclass carries real per-row pos, so the
    vector rollback applies unchanged — greedy output byte-identical to
    plain MLA serving; engine_pool adapter families still refuse."""
    from bigdl_tpu.models import deepseek
    from bigdl_tpu.models.config import ModelConfig

    cfg = ModelConfig.from_hf_config(dict(
        model_type="deepseek_v2", vocab_size=96, hidden_size=64,
        intermediate_size=128, num_hidden_layers=2, num_attention_heads=4,
        q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=0,
        first_k_dense_replace=2,
    ))
    params = deepseek.quantize_params(
        deepseek.init_params(cfg, jax.random.PRNGKey(0)), "sym_int4"
    )
    m = TpuModel(cfg, params, "sym_int4")

    prompts = [[3, 1, 4, 1, 5], [9, 2, 6]]
    ref_eng = shared_engine(m, n_slots=2, max_len=128)
    refs = [ref_eng.submit(p, max_new_tokens=8) for p in prompts]
    ref_eng.run_until_idle()

    eng = shared_engine(m, n_slots=2, max_len=128, speculative=True,
                        draft_params=m.params, draft_k=3)
    reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
    eng.run_until_idle(max_steps=300)
    for r, ref in zip(reqs, refs):
        assert r.done and r.out_tokens == ref.out_tokens, (
            r.out_tokens, ref.out_tokens
        )
    assert eng.spec_rounds > 0
    assert eng.spec_emitted / eng.spec_rounds > 1.0


# ---------------------------------------------------------------------------
# crash-recovery request journal (serving/journal.py)
# ---------------------------------------------------------------------------

@pytest.mark.core
def test_journal_recovery_replays_unfinished(model, tmp_path):
    """Serving-restart story: a journaled engine dies mid-flight; a
    replacement engine pointed at the same journal replays exactly the
    unfinished requests and produces the same greedy tokens the plain
    generate path yields. Completed requests are tombstoned and must
    NOT replay."""
    jpath = str(tmp_path / "requests.jsonl")
    want = {
        tuple(p): model.generate([p], max_new_tokens=8)[0].tolist()
        for p in PROMPTS
    }

    eng1 = shared_engine(model, n_slots=2, max_len=128, journal=jpath)
    r_done = eng1.submit(PROMPTS[0], max_new_tokens=8)
    eng1.run_until_idle(max_steps=200)  # completes + tombstones request 0
    assert r_done.done
    # two more accepted, then the process "dies" before serving them
    eng1.submit(PROMPTS[1], max_new_tokens=8)
    eng1.submit(PROMPTS[2], max_new_tokens=8, temperature=None)
    # torn trailing line (crash mid-append) must not break recovery
    with open(jpath, "a") as f:
        f.write('{"op": "sub')

    eng2 = shared_engine(model, n_slots=2, max_len=128, journal=jpath)
    replayed = eng2.recovered_requests  # auto-replayed at attach
    assert [r.prompt for r in replayed] == [PROMPTS[1], PROMPTS[2]]
    # rid counter seeded past every journaled rid: a fresh submit must
    # not collide with (and tombstone) an old journal entry
    old_rids = {r.rid for r in [r_done]} | {1, 2}
    assert all(r.rid not in old_rids for r in replayed)
    eng2.run_until_idle(max_steps=200)
    for p, r in zip(PROMPTS[1:], replayed):
        assert r.done and r.finish_reason != "error"
        assert r.out_tokens == want[tuple(p)]

    # the replayed generation re-journaled and tombstoned: a third
    # engine finds nothing to replay
    eng3 = shared_engine(model, n_slots=2, max_len=128, journal=jpath)
    assert eng3.recovered_requests == []


def test_engine_adaptive_draft_identical_and_ladder(model):
    """adaptive_draft=True must not change output (speculative decoding
    is exact at any K — the ladder only moves draft compute); here the
    draft IS the target so acceptance is ~always full and K climbs or
    stays at the top of the ladder."""
    want = {
        tuple(p): model.generate([p], max_new_tokens=12)[0].tolist()
        for p in PROMPTS
    }
    eng = shared_engine(
        model, n_slots=2, max_len=128, speculative=True,
        draft_params=model.params, draft_k=4, adaptive_draft=True,
    )
    assert eng._k_ladder == [2, 4]
    reqs = [eng.submit(p, max_new_tokens=12) for p in PROMPTS]
    eng.run_until_idle(max_steps=300)
    for p, r in zip(PROMPTS, reqs):
        assert r.done
        assert r.out_tokens == want[tuple(p)], (p, r.out_tokens)
    assert eng._cur_k == 4  # full acceptance never downshifts

    # ladder steering unit check: sustained low acceptance downshifts,
    # then sustained full acceptance climbs back
    import numpy as np

    eng._cur_k, eng._accept_ema = 4, None
    for _ in range(8):
        eng._adapt_draft_k(np.zeros(2, np.int32))
    assert eng._cur_k == 2
    for _ in range(8):
        eng._adapt_draft_k(np.full(2, eng._cur_k - 1, np.int32))
    assert eng._cur_k == 4


def test_adaptive_draft_requires_speculative(model):
    with pytest.raises(ValueError, match="adaptive_draft"):
        shared_engine(model, n_slots=2, max_len=64, adaptive_draft=True)


def test_logprobs_plain_and_speculative_agree(model):
    """Every emitted token carries its model logprob; the speculative
    engine reports the SAME logprobs as plain serving (the verify pass
    scores with the target model — exactness extends to logprobs)."""
    prompt = [3, 1, 4, 1, 5, 9]
    eng = shared_engine(model, n_slots=2, max_len=128)
    r = eng.submit(prompt, max_new_tokens=10)
    eng.run_until_idle()
    assert len(r.out_logprobs) == len(r.out_tokens) == 10
    assert all(lp <= 0.0 for lp in r.out_logprobs)

    spec = shared_engine(model, n_slots=2, max_len=128, speculative=True,
                         draft_params=model.params, draft_k=4)
    rs = spec.submit(prompt, max_new_tokens=10)
    spec.run_until_idle()
    assert rs.out_tokens == r.out_tokens
    np.testing.assert_allclose(rs.out_logprobs, r.out_logprobs,
                               rtol=1e-3, atol=1e-3)


def test_completions_endpoint_logprobs(model):
    import json
    import urllib.request

    from bigdl_tpu.serving.api_server import ApiServer

    srv = ApiServer(model, port=0, n_slots=2, max_len=128)
    srv.start()
    try:
        port = srv.httpd.server_address[1]
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/completions",
            data=json.dumps({"prompt": [3, 1, 4], "max_tokens": 5,
                             "logprobs": 1}).encode(),
            headers={"Content-Type": "application/json"},
        )
        out = json.loads(urllib.request.urlopen(req, timeout=300).read())
        lp = out["choices"][0]["logprobs"]
        assert len(lp["token_logprobs"]) == 5
        assert all(x <= 0 for x in lp["token_logprobs"])
        assert len(lp["tokens"]) == 5
    finally:
        srv.shutdown()


def test_logprobs_penalty_rows_match_across_modes(model):
    """With repetition_penalty != 1, the emitted token is drawn from the
    penalty-adjusted distribution — both engine modes must report THAT
    logprob (review finding, round 5)."""
    prompt = [3, 1, 4, 1, 5, 9]
    plain = shared_engine(model, n_slots=2, max_len=128)
    rp = plain.submit(prompt, max_new_tokens=8, repetition_penalty=1.3)
    plain.run_until_idle()
    spec = shared_engine(model, n_slots=2, max_len=128, speculative=True,
                         draft_params=model.params, draft_k=4)
    rs = spec.submit(prompt, max_new_tokens=8, repetition_penalty=1.3)
    spec.run_until_idle()
    assert rs.out_tokens == rp.out_tokens
    np.testing.assert_allclose(rs.out_logprobs, rp.out_logprobs,
                               rtol=1e-3, atol=1e-3)


def test_top_logprobs_opt_in(model):
    """logprobs_top_k=N returns the N most likely alternatives per token,
    consistent with the chosen-token logprob; engines without the option
    pay nothing and return none."""
    eng = shared_engine(model, n_slots=2, max_len=64, logprobs_top_k=3)
    r = eng.submit([3, 1, 4], max_new_tokens=5)
    eng.run_until_idle()
    assert len(r.out_top_logprobs) == 5
    for tok, lp, alt in zip(r.out_tokens, r.out_logprobs, r.out_top_logprobs):
        assert len(alt) == 3
        assert all(v <= 0 for v in alt.values())
        # greedy: the chosen token IS the argmax, so it leads the top-k
        best = max(alt, key=alt.get)
        assert best == tok
        assert abs(alt[tok] - lp) < 1e-3

    plain = shared_engine(model, n_slots=2, max_len=64)
    rp = plain.submit([3, 1, 4], max_new_tokens=5)
    plain.run_until_idle()
    assert rp.out_top_logprobs == []
    assert rp.out_tokens == r.out_tokens  # option does not change output

    with pytest.raises(NotImplementedError, match="logprobs_top_k"):
        shared_engine(model, n_slots=2, max_len=64, logprobs_top_k=3,
                      speculative=True, draft_params=model.params)


def test_completions_top_logprobs_honors_requested_count(model):
    import json
    import urllib.request

    from bigdl_tpu.serving.api_server import ApiServer

    srv = ApiServer(model, port=0, n_slots=2, max_len=64, logprobs_top_k=4)
    srv.start()
    try:
        port = srv.httpd.server_address[1]

        def post(lp):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/completions",
                data=json.dumps({"prompt": [3, 1, 4], "max_tokens": 3,
                                 "logprobs": lp}).encode(),
                headers={"Content-Type": "application/json"},
            )
            return json.loads(urllib.request.urlopen(req, timeout=300).read())

        out = post(2)  # clamp to the requested 2 of the engine's 4
        tops = out["choices"][0]["logprobs"]["top_logprobs"]
        assert len(tops) == 3 and all(len(d) <= 2 for d in tops)
        out0 = post(0)  # chosen-token only: no top_logprobs key
        assert "top_logprobs" not in out0["choices"][0]["logprobs"]
        assert len(out0["choices"][0]["logprobs"]["token_logprobs"]) == 3
    finally:
        srv.shutdown()


# ---- an admission compiles nothing (the engine's first-token program) ----

ADMISSION_PATHS = {
    "dense": {},
    "paged": {"paged": True, "page_size": 16},
    "chunked": {"paged": True, "page_size": 16, "prefill_chunk_tokens": 16},
}
# per request kind: the engine's options and those of request i
REQUEST_KINDS = {
    "greedy": ({}, lambda i: {}),
    "sampled": ({}, lambda i: {"do_sample": True,
                               "temperature": 0.6 + 0.1 * i,
                               "top_k": (0, 5, 40)[i % 3],
                               "top_p": (1.0, 0.9, 0.5)[i % 3]}),
    "penalty": ({}, lambda i: {"repetition_penalty": 1.3}),
    "top-logprobs": ({"logprobs_top_k": 3}, lambda i: {}),
}
PROMPT_LENGTHS = (5, 23, 70)  # three lengths, three dense prefill buckets


@pytest.mark.parametrize("kind", list(REQUEST_KINDS))
@pytest.mark.parametrize("path", list(ADMISSION_PATHS))
def test_admission_compiles_nothing_after_warmup(model, path, kind,
                                                 monkeypatch):
    """After one request of each prompt length, six further admissions
    trace, lower and compile nothing between the end of
    `prefill.dispatch` and the emit (all of `_activate`), whatever the
    request asks for, and each reads the device once."""
    from jax._src import array as jax_array

    from bigdl_tpu.obs import retrace

    eng_kw, req_kw = REQUEST_KINDS[kind]
    eng = InferenceEngine(model, n_slots=2, max_len=128,
                          **ADMISSION_PATHS[path], **eng_kw)
    acc = retrace.thread_accumulator()
    paid, fetches = [], [0]
    # a device array reaches the host through np.asarray (the buffer
    # protocol on the CPU) or through int() / float() / .item(), which
    # read ArrayImpl._value
    value, asarray = jax_array.ArrayImpl._value, np.asarray

    def counting_value(self):
        fetches[0] += 1
        return value.fget(self)

    def counting_asarray(a, *args, **kw):
        fetches[0] += isinstance(a, jax.Array)
        return asarray(a, *args, **kw)

    activate = eng._activate

    def counted(*a, **k):
        n0, s0, f0 = acc.programs, acc.seconds, fetches[0]
        activate(*a, **k)
        paid.append((acc.programs - n0, acc.seconds - s0, fetches[0] - f0))

    eng._activate = counted
    monkeypatch.setattr(jax_array.ArrayImpl, "_value",
                        property(counting_value))
    monkeypatch.setattr(np, "asarray", counting_asarray)

    def serve(i):
        n = PROMPT_LENGTHS[i % 3]
        r = eng.submit([(7 * i + j) % CFG.vocab_size for j in range(n)],
                       max_new_tokens=3, **req_kw(i))
        eng.run_until_idle(max_steps=100)
        assert r.done and len(r.out_tokens) == 3

    for i in range(3):  # warm-up: one of each length
        serve(i)
    assert eng.retraces["first_token.sample"] == 1  # built once
    del paid[:]
    counts = dict(eng.retraces)
    for i in range(3, 9):
        serve(i)
    eng.close()
    assert paid == [(0, 0.0, 1)] * 6
    for phase in ("first_token.sample", "first_token.arm"):
        assert eng.retraces[phase] == counts[phase]


def _eager_first_token(logits, rng, temp, topk, topp, dosample, penalty,
                       row, slot, cur, seen, n_top):
    """What `_activate` ran op by op before the engine owned a program:
    the formulation `_first_token_impl` has to reproduce."""
    import jax.numpy as jnp

    from bigdl_tpu.generate import (apply_repetition_penalty,
                                    sample_token_per_row)

    rng, k = jax.random.split(rng)
    if penalty != 1.0:
        logits = apply_repetition_penalty(
            logits, row[None], jnp.asarray(penalty, jnp.float32))
    first = int(sample_token_per_row(
        logits, k,
        jnp.asarray([temp], jnp.float32), jnp.asarray([topk], jnp.int32),
        jnp.asarray([topp], jnp.float32), jnp.asarray([dosample], jnp.bool_),
    )[0])
    cur = cur.at[slot].set(first)
    seen = seen.at[slot].set(row).at[slot, first].set(True)
    row_lp = jax.nn.log_softmax(jnp.asarray(logits, jnp.float32).reshape(-1))
    tv, ti = jax.lax.top_k(row_lp, n_top)
    top = {int(t): float(l) for t, l in zip(np.asarray(ti), np.asarray(tv))}
    return (first, float(row_lp[first]), top, np.asarray(cur),
            np.asarray(seen), np.asarray(rng))


@pytest.mark.parametrize("penalty", [1.0, 1.3], ids=["plain", "penalised"])
@pytest.mark.parametrize("dosample", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("vocab", [32000, 1003])
def test_first_token_program_agrees_with_eager(model, vocab, dosample,
                                               penalty):
    """The engine's first-token program against the eager formulation it
    replaced: token, logprob, top-k logprobs, `cur` and the slot's `seen`
    row bit for bit on greedy rows, with and without a penalty; the same
    token for the same key when sampling, and the same key left behind.
    The program's shapes are its inputs', so any vocabulary runs through
    a tiny engine's."""
    import jax.numpy as jnp

    from bigdl_tpu.serving.engine import _read_first_token

    n_top, slots, slot = 4, 3, 1
    eng = shared_engine(model, n_slots=2, max_len=64, logprobs_top_k=n_top)
    rs = np.random.default_rng(vocab + 2 * dosample)
    for trial in range(3):
        logits = jnp.asarray(
            rs.normal(0.0, 3.0, (1, vocab)).astype(np.float32))
        row = np.zeros((vocab,), bool)
        if penalty != 1.0:
            row[rs.integers(0, vocab, 50)] = True
            row[int(np.argmax(logits))] = True  # the penalty moves the best
        rng = jax.random.PRNGKey(11 + trial)
        cur = jnp.asarray(rs.integers(0, vocab, slots), jnp.int32)
        seen = jnp.asarray(rs.random((slots, vocab)) < 0.01)
        temp, topk, topp = 0.8, (0, 50, 7)[trial], (1.0, 0.9, 0.6)[trial]
        want = _eager_first_token(
            logits, rng, temp, topk, topp, dosample, penalty,
            jnp.asarray(row), slot, cur, seen, n_top)
        cur2, seen2, rng2, out = eng._first_token(
            logits, rng, np.float32(temp), np.int32(topk), np.float32(topp),
            np.bool_(dosample), np.float32(penalty), row, np.int32(slot),
            cur=cur, seen=seen)
        assert out.dtype == np.int32 and out.shape == (2 + 2 * n_top,)
        first, first_lp, top = _read_first_token(out, n_top)
        assert first == want[0]
        np.testing.assert_array_equal(np.asarray(cur2), want[3])
        np.testing.assert_array_equal(np.asarray(seen2), want[4])
        np.testing.assert_array_equal(np.asarray(rng2), want[5])
        if dosample:  # the filter's sort is not held to the bit
            assert first_lp == pytest.approx(want[1], rel=1e-6)
        else:  # floats compare exactly: bit for bit (no NaN here)
            assert (first_lp, top) == (want[1], want[2])
    eng.close()
