"""HTTP serving concurrency stress: many client threads hammering the
server (mixed streaming/non-streaming, mid-stream disconnects) must
neither deadlock nor corrupt engine state. The handler threads and the
single engine thread share the queue/cancel/journal surfaces — this is
where cross-thread races would live."""

import json
import threading
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from bigdl_tpu.api import TpuModel, optimize_model
from bigdl_tpu.models import llama
from bigdl_tpu.models.config import PRESETS
from engines import shared_engine

CFG = PRESETS["tiny-llama"]


@pytest.fixture(scope="module")
def server():
    from bigdl_tpu.serving.api_server import ApiServer

    model = TpuModel(CFG, optimize_model(
        llama.init_params(CFG, jax.random.PRNGKey(0)), CFG
    ), "sym_int4")
    srv = ApiServer(model, port=0, n_slots=2, max_len=128, paged=True,
                    page_size=16)
    srv.start()
    yield srv
    srv.shutdown()


def _post(port, path, payload, timeout=600):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    return urllib.request.urlopen(req, timeout=timeout)


def test_concurrent_mixed_clients_all_complete(server):
    port = server.httpd.server_address[1]
    n_clients = 12
    results = [None] * n_clients
    errors = []

    def client(i):
        try:
            rng = np.random.default_rng(i)
            prompt = [int(t) for t in rng.integers(2, 200, 4 + i % 5)]
            if i % 3 == 0:  # streaming, read fully
                resp = _post(port, "/generate_stream",
                             {"prompt": prompt, "max_new_tokens": 6})
                body = resp.read().decode()
                results[i] = body.count("data:")
            elif i % 3 == 1:  # streaming, disconnect after first event
                resp = _post(port, "/generate_stream",
                             {"prompt": prompt, "max_new_tokens": 30})
                resp.fp.read(20)
                resp.close()  # mid-stream disconnect
                results[i] = "disconnected"
            else:  # plain completion
                resp = _post(port, "/generate",
                             {"prompt": prompt, "max_new_tokens": 6})
                out = json.loads(resp.read())
                results[i] = len(out.get("tokens", out.get(
                    "generated_text", "")))
        except Exception as e:  # noqa: BLE001 — collected for the assert
            errors.append((i, repr(e)))

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    assert not any(t.is_alive() for t in threads), "client thread hung"
    assert not errors, errors
    # full-read streaming clients got all their events
    for i in range(0, n_clients, 3):
        assert results[i] and results[i] >= 6, (i, results[i])

    # the engine is still healthy: a fresh request completes normally
    resp = _post(port, "/generate", {"prompt": [3, 1, 4],
                                     "max_new_tokens": 4})
    out = json.loads(resp.read())
    assert out


def test_server_survives_malformed_and_oversized(server):
    port = server.httpd.server_address[1]
    # malformed JSON
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate", data=b"{not json",
        headers={"Content-Type": "application/json"},
    )
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(req, timeout=60)
    assert e.value.code == 400
    # prompt longer than max_len fails cleanly, not fatally
    with pytest.raises(urllib.error.HTTPError):
        _post(port, "/generate",
              {"prompt": list(range(2, 300)), "max_new_tokens": 4},
              timeout=120)
    # and the server still serves
    resp = _post(port, "/generate", {"prompt": [5, 6], "max_new_tokens": 3})
    assert json.loads(resp.read())


def test_overlong_prompt_rejected_not_truncated(server):
    """Round-5 stress finding: admission used to tail-truncate silently
    and generate from a different context than the caller sent. The
    default is now vLLM-style rejection (HTTP 400); truncation is an
    explicit engine opt-in."""
    import jax

    port = server.httpd.server_address[1]
    long_prompt = [(i % 250) + 2 for i in range(298)]  # in-vocab, 298 toks
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(port, "/generate",
              {"prompt": long_prompt, "max_new_tokens": 4}, timeout=120)
    assert e.value.code == 400
    assert b"truncate_prompts" in e.value.read()

    # engine-level: rejected request is done+invalid without queueing
    model = server.engine.model
    eng = shared_engine(model, n_slots=1, max_len=64)
    r = eng.submit(list(range(2, 200)), max_new_tokens=4)
    assert r.done and r.finish_reason == "invalid" and "exceeds" in r.error

    # opt-in truncation restores the old behavior: generates from the
    # kept tail, byte-identical to generate() on that tail
    eng_t = shared_engine(model, n_slots=1, max_len=64,
                          truncate_prompts=True)
    long_p = list(range(2, 200))
    r = eng_t.submit(long_p, max_new_tokens=4)
    eng_t.run_until_idle()
    assert r.done and not r.error
    kept = long_p[-(64 - 4):]
    want = model.generate([kept], max_new_tokens=4)[0].tolist()
    assert r.out_tokens == want


def test_generate_input_validation(server):
    """Bad inputs fail with actionable ValueErrors, not jax internals
    (round-5 fuzz findings: max_new_tokens<1 crashed with IndexError,
    top_k=0 with a broadcast TypeError, out-of-vocab ids silently
    generated garbage)."""
    model = server.engine.model
    V = model.config.vocab_size
    with pytest.raises(ValueError, match="max_new_tokens"):
        model.generate([[3, 1]], max_new_tokens=0)
    # top_k <= 0 disables the filter (stack-wide convention), not error
    out = model.generate([[3, 1]], max_new_tokens=2, do_sample=True, top_k=0)
    assert out.shape == (1, 2)
    with pytest.raises(ValueError, match="empty prompt"):
        model.generate([[]], max_new_tokens=2)
    with pytest.raises(ValueError, match="token ids"):
        model.generate([[V + 7]], max_new_tokens=2)
    with pytest.raises(ValueError, match="token ids"):
        model.generate([[-1]], max_new_tokens=2)
    # top_k larger than vocab clamps (HF semantics) instead of raising
    out = model.generate([[3, 1]], max_new_tokens=2, do_sample=True,
                         top_k=10 * V)
    assert out.shape == (1, 2)

    # engine submit: out-of-vocab / empty prompts fail as "invalid"
    eng = shared_engine(model, n_slots=1, max_len=64)
    req = eng.submit([V + 7], max_new_tokens=2)
    assert req.done and req.finish_reason == "invalid"
    req = eng.submit([], max_new_tokens=2)
    assert req.done and req.finish_reason == "invalid"
    # top_k=0 is explicit-disable through the engine too
    req = eng.submit([3, 1], max_new_tokens=2, do_sample=True, top_k=0)
    eng.run_until_idle()
    assert req.done and not req.error


def test_full_feature_composition_torture(server, tmp_path):
    """Every serving feature at once — paged + fp8 pages + speculative +
    adaptive draft + journal + mixed sampling + a mid-flight cancel —
    must complete all requests, leak no pages, and tombstone the journal
    so a successor engine replays nothing."""
    model = server.engine.model
    jpath = str(tmp_path / "journal.jsonl")
    eng = shared_engine(
        model, n_slots=2, max_len=96, paged=True, page_size=8,
        speculative=True, draft_params=model.params, draft_k=4,
        adaptive_draft=True, quantize_kv=True, journal=jpath,
    )
    free0 = eng.pages.pool.n_free
    reqs = [eng.submit([2 + i, 7, 9, 11], max_new_tokens=12,
                       do_sample=(i % 2 == 0), temperature=0.8)
            for i in range(5)]
    for _ in range(2):
        eng.step()
    eng.cancel(reqs[0])
    eng.run_until_idle()
    assert all(r.done for r in reqs)
    assert not [r.error for r in reqs if r.error]
    assert eng.pages.pool.n_free + eng.pages.radix.n_nodes == free0
    assert eng.page_leaks() == 0
    eng2 = shared_engine(model, n_slots=2, max_len=96, paged=True,
                         page_size=8, journal=jpath)
    assert len(eng2.recovered_requests) == 0  # all tombstoned
