"""Multi-tenant LoRA adapter serving (serving/adapters.py; ISSUE 15).

Correctness oracle: a request decoding with adapter X through the
engine's BATCHED epilogue (one forward over a heterogeneous adapter
batch, ops/linear.lora_epilogue) must produce the same greedy tokens as
the same prompt through a model whose weights were merged OFFLINE via
`train/qlora.merge_lora` — per adapter, including under preemption,
chunked prefill, and journal replay. The base is kept DENSE (bf16) in
the parity tests so merge_lora is exact (a quantized base would
requantize the merge and blur the oracle with quantization noise —
exactly why serving applies the adapter as an epilogue, arxiv
2301.12017).
"""

import json
import os
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import optimize_model
from bigdl_tpu.api import TpuModel
from bigdl_tpu.models import llama
from bigdl_tpu.models.config import PRESETS
from bigdl_tpu.serving.adapters import (
    AdapterError, AdapterRegistry, load_adapter, rank_bucket, save_adapter,
)
from engines import shared_engine
from bigdl_tpu.serving.faults import FaultInjector
from bigdl_tpu.train.qlora import init_lora, merge_lora

CFG = PRESETS["tiny-llama"]

PROMPTS = [
    [3, 1, 4, 1, 5, 9, 2, 6],
    [2, 7, 1, 8, 2, 8],
    [9, 9, 8, 2, 4, 9, 1],
    [5, 3, 5, 8, 9, 7],
]


@pytest.fixture(scope="module")
def model():
    params = optimize_model(
        llama.init_params(CFG, jax.random.PRNGKey(7)), CFG, "bf16"
    )
    return TpuModel(CFG, params, "bf16")


def _mk_lora(seed: int, rank: int, targets=("wq", "wv", "w_up")):
    """A rank-r adapter with NONZERO B (init_lora's B=0 is the identity
    adapter — parity with it would not prove the epilogue runs)."""
    lora = init_lora(CFG, jax.random.PRNGKey(seed), rank=rank,
                     alpha=2.0 * rank, targets=targets)
    for i, t in enumerate(targets):
        b = lora["layers"][t]["b"]
        lora["layers"][t]["b"] = (
            jax.random.normal(jax.random.PRNGKey(seed * 31 + i), b.shape,
                              jnp.float32) * 0.05
        ).astype(b.dtype)
    return lora


@pytest.fixture(scope="module")
def adapter_dir(tmp_path_factory):
    """Three tenants at DIFFERENT ranks (bucketing must pad them into
    one batch) plus their source trees for the merge oracle."""
    d = tmp_path_factory.mktemp("adapters")
    loras = {}
    for name, seed, rank in (("t-r2", 11, 2), ("t-r3", 12, 3),
                             ("t-r5", 13, 5)):
        lora = _mk_lora(seed, rank)
        save_adapter(str(d / f"{name}.npz"), lora)
        loras[name] = lora
    return str(d), loras


def _run_engine(model, jobs, registry=None, n_new=8, **eng_kw):
    """jobs: list of (prompt, adapter_name|None) -> out_tokens list."""
    eng = shared_engine(model, n_slots=4, max_len=128, paged=True,
                        page_size=16, adapters=registry, **eng_kw)
    reqs = [eng.submit(p, max_new_tokens=n_new, adapter=a)
            for p, a in jobs]
    eng.run_until_idle(max_steps=2000)
    assert eng.page_leaks() == 0
    return eng, reqs


# ---------------------------------------------------------------------------
# artifact I/O
# ---------------------------------------------------------------------------

@pytest.mark.core
def test_artifact_roundtrip(tmp_path):
    lora = _mk_lora(1, 3)
    path = str(tmp_path / "a.npz")
    save_adapter(path, lora)
    got, meta = load_adapter(path, verify="full")
    assert meta["rank"] == 3 and meta["targets"] == ["w_up", "wq", "wv"]
    for t, pair in lora["layers"].items():
        for leaf in ("a", "b"):
            np.testing.assert_array_equal(
                np.asarray(pair[leaf], np.float32),
                np.asarray(got["layers"][t][leaf], np.float32),
            )
    assert float(got["scale"]) == pytest.approx(2.0)


@pytest.mark.core
def test_corrupt_artifact_structured(tmp_path):
    from bigdl_tpu.utils.durability import IntegrityError

    path = str(tmp_path / "a.npz")
    save_adapter(path, _mk_lora(2, 2))
    with open(path, "r+b") as f:  # interior bit rot
        raw = bytearray(f.read())
        raw[len(raw) // 2] ^= 0xFF
        f.seek(0)
        f.write(bytes(raw))
    with pytest.raises(IntegrityError):
        load_adapter(path, verify="fast")
    reg = AdapterRegistry(dir=str(tmp_path))
    with pytest.raises(AdapterError) as ei:
        reg.load("a")
    assert ei.value.kind == "corrupt"
    assert reg.stats()["load_failures"] == 1


@pytest.mark.core
def test_rank_bucket_ladder():
    assert [rank_bucket(r) for r in (1, 2, 4, 5, 8, 9, 33)] == \
        [4, 4, 4, 8, 8, 16, 64]


# ---------------------------------------------------------------------------
# registry: LRU, budget, refcounts, pin
# ---------------------------------------------------------------------------

@pytest.mark.core
def test_eviction_under_refcount(tmp_path):
    d = str(tmp_path)
    sizes = {}
    for name in ("a", "b", "c"):
        lora = _mk_lora(ord(name), 2)
        save_adapter(os.path.join(d, f"{name}.npz"), lora)
        sizes[name] = sum(
            int(np.asarray(pair[leaf]).nbytes)
            for pair in lora["layers"].values() for leaf in ("a", "b")
        )
    one = max(sizes.values())
    reg = AdapterRegistry(dir=d, budget_bytes=one)  # exactly 1 resident
    ea = reg.acquire("a")
    # budget full AND the only resident is referenced: loading b must
    # fail structurally, never evict a decoding tenant's weights
    with pytest.raises(AdapterError) as ei:
        reg.get("b")
    assert ei.value.kind == "budget"
    reg.release(ea)
    reg.get("b")  # now evicts a (refcount 0)
    st = reg.stats()
    assert st["evictions"] == 1 and st["resident"] == 1
    # a's path is remembered: next get() reloads it (counted)
    reg.get("a")
    assert reg.stats()["loads"] == 3
    # pinned survives pressure: a pinned sole resident blocks c's load
    reg.load("b", pin=True)
    with pytest.raises(AdapterError):
        reg.get("c")
    # double-release is a programming error, caught at the site
    eb = reg.acquire("b")
    reg.release(eb)
    with pytest.raises(AssertionError):
        reg.release(eb)


@pytest.mark.core
def test_unload_busy_and_missing(tmp_path):
    d = str(tmp_path)
    save_adapter(os.path.join(d, "a.npz"), _mk_lora(3, 2))
    reg = AdapterRegistry(dir=d)
    e = reg.acquire("a")
    with pytest.raises(AdapterError) as ei:
        reg.unload("a")
    assert ei.value.kind == "busy"
    reg.release(e)
    reg.unload("a")
    with pytest.raises(AdapterError) as ei:
        reg.unload("a")
    assert ei.value.kind == "missing"
    with pytest.raises(AdapterError) as ei:
        reg.get("nope")
    assert ei.value.kind == "missing"


def test_failed_reload_keeps_healthy_entry(tmp_path):
    """An operator reload with a bad path (or corrupt artifact) must
    not cost the resident entry: the old adapter stays loaded, pinned,
    and serving — only the failed attempt is counted."""
    d = str(tmp_path)
    save_adapter(os.path.join(d, "a.npz"), _mk_lora(3, 2))
    reg = AdapterRegistry(dir=d)
    reg.load("a", pin=True)
    with pytest.raises(AdapterError) as ei:
        reg.load("a", path=os.path.join(d, "typo.npz"))
    assert ei.value.kind == "missing"
    resident = reg.resident()
    assert [e["name"] for e in resident] == ["a"]
    assert resident[0]["pinned"], "pin must survive the failed reload"
    assert reg.stats()["load_failures"] == 1
    # the restored entry still serves without a counted reload
    loads_before = reg.stats()["loads"]
    assert reg.get("a").rank == 2
    assert reg.stats()["loads"] == loads_before


# ---------------------------------------------------------------------------
# the batched epilogue itself (forward-level, logits)
# ---------------------------------------------------------------------------

@pytest.mark.core
def test_batched_epilogue_matches_per_request(model):
    """[B] rows each with ITS adapter (one base-only) through ONE
    forward must equal B separate forwards with plain per-request lora
    trees — zero-padding to the rank bucket is exact."""
    loras = [_mk_lora(21, 2), _mk_lora(22, 5), None]
    B = len(loras)
    rb = rank_bucket(5)
    L = CFG.num_hidden_layers
    targets = ("wq", "wv", "w_up")
    layers = {}
    for t in targets:
        sample = loras[0]["layers"][t]
        in_d = sample["a"].shape[-1]
        out_d = sample["b"].shape[-2]
        a = np.zeros((L, B, rb, in_d), np.float32)
        b = np.zeros((L, B, out_d, rb), np.float32)
        for i, lo in enumerate(loras):
            if lo is None:
                continue
            r = lo["layers"][t]["a"].shape[1]
            a[:, i, :r, :] = np.asarray(lo["layers"][t]["a"], np.float32)
            b[:, i, :, :r] = np.asarray(lo["layers"][t]["b"], np.float32)
        layers[t] = {"a": jnp.asarray(a, jnp.bfloat16),
                     "b": jnp.asarray(b, jnp.bfloat16)}
    scale = jnp.asarray(
        [float(lo["scale"]) if lo else 0.0 for lo in loras], jnp.float32
    )
    blora = {"layers": layers, "scale": scale}
    toks = jnp.asarray([[3, 1, 4, 1], [2, 7, 1, 8], [9, 9, 8, 2]],
                       jnp.int32)
    batched, _ = llama.forward(CFG, model.params, toks, None, lora=blora)
    for i, lo in enumerate(loras):
        single, _ = llama.forward(
            CFG, model.params, toks[i:i + 1], None, lora=lo
        )
        np.testing.assert_allclose(
            np.asarray(batched[i], np.float32),
            np.asarray(single[0], np.float32), atol=2e-2, rtol=0,
        )


# ---------------------------------------------------------------------------
# end-to-end parity vs offline merge_lora (the acceptance oracle)
# ---------------------------------------------------------------------------

def _merged_tokens(model, lora, prompt, n_new=8):
    merged = TpuModel(CFG, merge_lora(model.params, lora), "bf16")
    eng = shared_engine(merged, n_slots=4, max_len=128, paged=True,
                        page_size=16)
    req = eng.submit(prompt, max_new_tokens=n_new)
    eng.run_until_idle(max_steps=500)
    return req.out_tokens


@pytest.fixture(scope="module")
def merged_oracle(model, adapter_dir):
    """Greedy tokens per (tenant, prompt) from offline-merged weights —
    computed once, shared by the mixed-batch / preemption / chunked /
    replay parity tests below."""
    _, loras = adapter_dir
    names = [None, "t-r2", "t-r3", "t-r5"]
    out = {}
    for name, prompt in zip(names, PROMPTS):
        if name is None:
            out[(name, tuple(prompt))] = _merged_tokens(
                model, init_lora(CFG, jax.random.PRNGKey(0), rank=2),
                prompt)  # B=0 identity adapter == pure base
        else:
            out[(name, tuple(prompt))] = _merged_tokens(
                model, loras[name], prompt)
    return out


@pytest.mark.core
def test_mixed_batch_parity_vs_merged(model, adapter_dir, merged_oracle):
    """3 adapters of different ranks + 1 base-only slot in ONE decode
    batch: each request's tokens equal its offline-merged oracle."""
    d, _ = adapter_dir
    reg = AdapterRegistry(dir=d)
    jobs = list(zip(PROMPTS, [None, "t-r2", "t-r3", "t-r5"]))
    eng, reqs = _run_engine(model, jobs, reg)
    for (prompt, name), req in zip(jobs, reqs):
        assert req.finish_reason in ("stop", "length"), req.error
        assert req.out_tokens == merged_oracle[(name, tuple(prompt))], \
            (name, prompt)
    st = reg.stats()
    assert st["loads"] == 3 and st["load_failures"] == 0
    # refcounts drained at finish: everything is evictable again
    assert all(e["refcount"] == 0 for e in reg.resident())


@pytest.mark.chaos
def test_parity_under_preemption(model, adapter_dir, merged_oracle):
    """Pool pressure preempts an adapter-carrying request to host RAM;
    after resume its tokens still match the merged oracle (the parked
    request kept its adapter reference — eviction could not drop it)."""
    d, _ = adapter_dir
    reg = AdapterRegistry(dir=d)
    jobs = list(zip(PROMPTS, [None, "t-r2", "t-r3", "t-r5"]))
    # injected pool exhaustion mid-decode (the chaos-suite pattern)
    # forces a victim to host RAM; decode runs long enough that every
    # row crosses a page boundary and needs the allocation. The first
    # 10 alloc_page fires are admission (4) + adapter page-ins (6,
    # ISSUE 18's unified paging — a fault there is absorbed as a host
    # epilogue fallback, never a preemption), so skip 12 to land both
    # faults on decode page growth.
    inj = FaultInjector(seed=0).arm("alloc_page", times=2, after=12)
    eng, reqs = _run_engine(model, jobs, reg, n_new=16, faults=inj)
    assert eng.preemptions > 0, "scenario must actually preempt"
    for (prompt, name), req in zip(jobs, reqs):
        assert req.finish_reason in ("stop", "length"), req.error
        # greedy decode is prefix-stable: the 8-token oracle must be a
        # prefix of this 16-token (preempted-and-resumed) run
        oracle = merged_oracle[(name, tuple(prompt))]
        assert req.out_tokens[: len(oracle)] == oracle, \
            (name, prompt, req.preemptions)
        assert len(req.out_tokens) == 16


@pytest.mark.core
def test_shared_prefix_never_leaks_across_tenants(model, adapter_dir):
    """Adapter-prefilled KV pages carry that adapter's shifted K/V from
    the first adapted layer up, so the radix cache namespaces them per
    tenant (radix.root_for): a multi-page prompt served FIRST through
    tenant A must not be prefix-reused by the base or another tenant —
    each run still matches its own merged oracle."""
    d, loras = adapter_dir
    prompt = list(range(1, 36))  # 2 full pages + tail at page_size 16
    refs = {
        None: _merged_tokens(
            model, init_lora(CFG, jax.random.PRNGKey(0), rank=2), prompt),
        "t-r2": _merged_tokens(model, loras["t-r2"], prompt),
        "t-r3": _merged_tokens(model, loras["t-r3"], prompt),
    }
    reg = AdapterRegistry(dir=d)
    eng = shared_engine(model, n_slots=4, max_len=128, paged=True,
                        page_size=16, adapters=reg)
    # tenant A primes the cache with its adapter-shifted pages
    first = eng.submit(prompt, max_new_tokens=8, adapter="t-r2")
    eng.run_until_idle(max_steps=500)
    assert first.out_tokens == refs["t-r2"]
    assert eng.pages.radix.n_nodes == 2, "scenario must register shared pages"
    # same tokens through the base and a second tenant: A's pages are
    # unreachable from their namespaces, so both re-prefill correctly
    # (and a repeat of A itself HITS its own namespace, staying parity)
    for name in (None, "t-r3", "t-r2"):
        req = eng.submit(prompt, max_new_tokens=8, adapter=name)
        eng.run_until_idle(max_steps=500)
        assert req.out_tokens == refs[name], name
    assert eng.pages.prefix_hits > 0, "tenant A's repeat must hit its own ns"
    assert eng.page_leaks() == 0


@pytest.mark.core
def test_parity_chunked_prefill(model, adapter_dir, merged_oracle):
    """Every chunk of a chunked prefill carries the adapter: tokens
    match the merged oracle bit-for-bit (chunk size straddles pages)."""
    d, _ = adapter_dir
    reg = AdapterRegistry(dir=d)
    jobs = list(zip(PROMPTS, [None, "t-r2", "t-r3", "t-r5"]))
    eng, reqs = _run_engine(model, jobs, reg, prefill_chunk_tokens=3)
    for (prompt, name), req in zip(jobs, reqs):
        assert req.out_tokens == merged_oracle[(name, tuple(prompt))], \
            (name, prompt)


@pytest.mark.chaos
def test_parity_cancel_mid_decode(model, adapter_dir):
    """Cancelling an adapter request mid-decode releases its reference
    (the registry can evict it again) and never disturbs neighbours."""
    d, _ = adapter_dir
    reg = AdapterRegistry(dir=d)
    eng = shared_engine(model, n_slots=2, max_len=128, paged=True,
                        page_size=16, adapters=reg)
    r1 = eng.submit(PROMPTS[0], max_new_tokens=30, adapter="t-r2")
    r2 = eng.submit(PROMPTS[1], max_new_tokens=6, adapter="t-r3")
    for _ in range(3):
        eng.step()
    eng.cancel(r1)
    eng.run_until_idle(max_steps=500)
    assert r1.done and r2.done and r2.finish_reason in ("stop", "length")
    assert all(e["refcount"] == 0 for e in reg.resident())
    assert eng.page_leaks() == 0


@pytest.mark.chaos
def test_corrupt_adapter_is_one_request_error(model, adapter_dir):
    """An injected corrupt adapter load fails THAT request with the
    structured message ("error", not fail_all): the rest of the batch
    completes normally."""
    d, _ = adapter_dir
    inj = FaultInjector(seed=0).arm("adapter_load_corrupt", times=1)
    reg = AdapterRegistry(dir=d, faults=inj)
    jobs = [(PROMPTS[0], "t-r2"), (PROMPTS[1], "t-r3"), (PROMPTS[2], None)]
    eng, reqs = _run_engine(model, jobs, reg)
    bad, good, base = reqs
    assert bad.done and bad.finish_reason == "error"
    assert "corrupt" in bad.error and "t-r2" in bad.error
    assert good.finish_reason in ("stop", "length")
    assert base.finish_reason in ("stop", "length")
    assert reg.stats()["load_failures"] == 1
    # fixed-reason metrics contract intact, adapter families rendered
    from bigdl_tpu.serving.metrics import Metrics, metric_drift

    rendered = Metrics(eng).render()
    missing, unregistered = metric_drift(rendered, eng)
    assert not missing and not unregistered, (missing, unregistered)
    assert "bigdl_tpu_adapter_load_failures_total 1" in rendered
    assert 'bigdl_tpu_requests_finished_total{reason="error"} 1' in rendered


@pytest.mark.core
def test_unknown_and_mismatched_adapter(model, adapter_dir, tmp_path):
    d, _ = adapter_dir
    reg = AdapterRegistry(dir=d)
    # unknown name -> that request errors at admission
    eng, (r1, r2) = _run_engine(
        model, [(PROMPTS[0], "never-saved"), (PROMPTS[1], "t-r2")], reg
    )
    assert r1.finish_reason == "error" and "missing" in r1.error
    assert r2.finish_reason in ("stop", "length")
    # adapter trained against a different base -> structured
    # rank_mismatch at admission, not an XLA shape error mid-decode
    wrong = init_lora(CFG, jax.random.PRNGKey(5), rank=2, targets=("wq",))
    wrong["layers"]["wq"]["a"] = wrong["layers"]["wq"]["a"][:, :, :-8]
    save_adapter(str(tmp_path / "wrong.npz"), wrong)
    reg2 = AdapterRegistry(dir=str(tmp_path))
    eng2, (r3,) = _run_engine(model, [(PROMPTS[0], "wrong")], reg2)
    assert r3.finish_reason == "error" and "rank_mismatch" in r3.error
    # adapter named but no registry configured -> invalid at submit
    eng3 = shared_engine(model, n_slots=2, max_len=128)
    r4 = eng3.submit(PROMPTS[0], max_new_tokens=4, adapter="t-r2")
    assert r4.done and r4.finish_reason == "invalid"


@pytest.mark.chaos
def test_replay_after_crash_with_adapter(model, adapter_dir, tmp_path,
                                         merged_oracle):
    """A journaled adapter request whose process dies before the
    tombstone is REPLAYED by the successor engine — with its adapter
    (the name rides the journal), and its tokens match the oracle."""
    d, _ = adapter_dir
    jpath = str(tmp_path / "journal.jsonl")
    inj = FaultInjector(seed=0).arm("crash_before_done", times=1)
    reg = AdapterRegistry(dir=d)
    eng = shared_engine(model, n_slots=2, max_len=128, paged=True,
                        page_size=16, adapters=reg, journal=jpath,
                        faults=inj)
    req = eng.submit(PROMPTS[1], max_new_tokens=8, adapter="t-r2")
    with pytest.raises(Exception):
        eng.run_until_idle(max_steps=500)  # injected crash in _finish
    assert req.done  # completed, but its tombstone never landed
    # successor process: replay must resubmit WITH the adapter
    reg2 = AdapterRegistry(dir=d)
    eng2 = shared_engine(model, n_slots=2, max_len=128, paged=True,
                         page_size=16, adapters=reg2, journal=jpath)
    assert len(eng2.recovered_requests) == 1
    rec = eng2.recovered_requests[0]
    assert rec.adapter == "t-r2"
    eng2.run_until_idle(max_steps=500)
    assert rec.done and rec.finish_reason in ("stop", "length")
    assert rec.out_tokens == merged_oracle[("t-r2", tuple(PROMPTS[1]))]
    eng2.close()


@pytest.mark.core
def test_quantized_base_all_targets(tmp_path):
    """The production shape: QUANTIZED base + an adapter on all 7
    targets (incl. the wo/w_down OUTPUT projections, whose delta rides
    the residual). Regression: a non-weak f32 scale leaf used to
    promote the residual to f32 and break the layer scan's carry —
    the epilogue must stay in the compute dtype."""
    params = optimize_model(
        llama.init_params(CFG, jax.random.PRNGKey(7)), CFG, "sym_int4"
    )
    qmodel = TpuModel(CFG, params, "sym_int4")
    lora = _mk_lora(41, 2, targets=("wq", "wk", "wv", "wo", "w_gate",
                                    "w_up", "w_down"))
    save_adapter(str(tmp_path / "q.npz"), lora)
    reg = AdapterRegistry(dir=str(tmp_path))
    eng = shared_engine(qmodel, n_slots=2, max_len=128, paged=True,
                        page_size=16, adapters=reg)
    ra = eng.submit(PROMPTS[0], max_new_tokens=8, adapter="q")
    rb = eng.submit(PROMPTS[0], max_new_tokens=8)
    eng.run_until_idle(max_steps=300)
    assert ra.finish_reason in ("stop", "length"), ra.error
    assert rb.finish_reason in ("stop", "length")
    # the adapter genuinely changed generation vs the shared base
    assert ra.out_tokens != rb.out_tokens
    assert eng.page_leaks() == 0
    assert all(e["refcount"] == 0 for e in reg.resident())


# ---------------------------------------------------------------------------
# HTTP lifecycle surface
# ---------------------------------------------------------------------------

@pytest.mark.core
def test_http_adapter_lifecycle(model, adapter_dir):
    from bigdl_tpu.serving.api_server import ApiServer

    d, _ = adapter_dir
    reg = AdapterRegistry(dir=d)
    srv = ApiServer(model, port=0, n_slots=2, max_len=128, paged=True,
                    page_size=16, adapters=reg)
    srv.start()
    base = f"http://127.0.0.1:{srv.port}"

    def post(path, payload):
        req = urllib.request.Request(
            base + path, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            return json.load(r)

    try:
        out = post("/adapters/load", {"name": "t-r3", "pin": True})
        assert out["adapter"]["rank"] == 3 and out["adapter"]["pinned"]
        with urllib.request.urlopen(base + "/adapters", timeout=10) as r:
            listing = json.load(r)
        assert [a["name"] for a in listing["adapters"]] == ["t-r3"]
        # generate WITH an adapter through the JSON surface
        out = post("/generate", {"prompt": PROMPTS[0],
                                 "max_new_tokens": 4,
                                 "adapter": "t-r2"})
        assert len(out["tokens"]) == 4
        # missing adapter -> 404 on the lifecycle op
        try:
            post("/adapters/unload", {"name": "ghost"})
            raise AssertionError("expected 404")
        except urllib.error.HTTPError as e:
            assert e.code == 404
            assert json.loads(e.read())["kind"] == "missing"
        post("/adapters/unload", {"name": "t-r3"})
        # bad adapter field type -> 400 before submit
        try:
            post("/generate", {"prompt": PROMPTS[0], "adapter": 7})
            raise AssertionError("expected 400")
        except urllib.error.HTTPError as e:
            assert e.code == 400
        # /metrics exposes the adapter families
        with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
            body = r.read().decode()
        assert "bigdl_tpu_adapter_loads_total" in body
        assert "bigdl_tpu_adapters_resident" in body
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# sim trace plumbing (cheap pieces; the full scenario runs in ci --core)
# ---------------------------------------------------------------------------

@pytest.mark.core
def test_zipf_trace_roundtrip(tmp_path):
    from bigdl_tpu.sim.traces import Trace, named_trace

    tr = named_trace("adapter-zipf", seed=0)
    names = {a.adapter for a in tr.arrivals}
    assert names and all(n and n.startswith("tenant-") for n in names)
    assert len(names) <= 4 and tr.params["n_adapters"] == 4
    # hot-tenant skew: the most popular tenant dominates (Zipf)
    from collections import Counter

    counts = Counter(a.adapter for a in tr.arrivals)
    top = counts.most_common()[0][1]
    assert top >= len(tr.arrivals) / 3
    p = str(tmp_path / "t.jsonl")
    tr.save(p)
    tr2 = Trace.load(p)
    assert [a.adapter for a in tr2.arrivals] == \
        [a.adapter for a in tr.arrivals]
    # determinism
    tr3 = named_trace("adapter-zipf", seed=0)
    assert [a.adapter for a in tr3.arrivals] == \
        [a.adapter for a in tr.arrivals]


@pytest.mark.core
def test_cost_model_prices_epilogue():
    from bigdl_tpu.sim.engine_driver import default_cost_model

    cm = default_cost_model()
    base = cm.decode_step_s([64, 64], 64)
    with_lora = cm.decode_step_s([64, 64], 64, adapter_ranks=[8, 8])
    assert with_lora > base
    # rank-monotone
    r16 = cm.decode_step_s([64, 64], 64, adapter_ranks=[16, 16])
    assert r16 > with_lora
    assert cm.prefill_s(64, adapter_rank=8) > cm.prefill_s(64)


# ---------------------------------------------------------------------------
# ISSUE 18: unified HBM paging + adapter-aware speculative decode
# ---------------------------------------------------------------------------

@pytest.mark.core
def test_unified_paging_shares_kv_pool(model, adapter_dir, merged_oracle):
    """Adapter weights live in pages of the SAME PagePool as KV: the
    pager pages in at admission, residency survives the request (warm
    reuse), page_leaks() reconciles adapter holds, and KV pressure
    pages holder-free adapters back out (host copy survives)."""
    d, _ = adapter_dir
    reg = AdapterRegistry(dir=d)
    jobs = list(zip(PROMPTS, [None, "t-r2", "t-r3", "t-r5"]))
    eng, reqs = _run_engine(model, jobs, reg)
    pager = eng._pager
    assert pager is not None and pager.page_ins >= 3
    assert pager.pages_resident > 0  # warm after drain, holder-free
    for (prompt, name), req in zip(jobs, reqs):
        assert req.out_tokens == merged_oracle[(name, tuple(prompt))], \
            (name, prompt)
    # every resident page carries a real pool reference (one each)
    for pg in pager.held_pages():
        assert eng.pages.pool.ref[pg] >= 1
    # holder-free residency is evictable: drain the pool and the
    # allocator's escalation (radix -> adapter page-out) frees them
    grabbed = []
    pg = eng.pages.alloc()
    while pg is not None:
        grabbed.append(pg)
        pg = eng.pages.alloc()
    assert pager.pages_resident == 0 and pager.page_outs >= 3
    for pg in grabbed:
        eng.pages.pool.decref(pg)
    assert eng.page_leaks() == 0
    # next admission pages back in from the surviving host copy
    r = eng.submit(PROMPTS[1], max_new_tokens=4, adapter="t-r2")
    eng.run_until_idle(max_steps=300)
    assert r.out_tokens == merged_oracle[("t-r2", tuple(PROMPTS[1]))][:4]
    assert pager.pages_resident > 0
    # the new families render and the drift gate stays clean
    from bigdl_tpu.serving.metrics import Metrics, metric_drift

    rendered = Metrics(eng).render()
    missing, unregistered = metric_drift(rendered, eng)
    assert not missing and not unregistered, (missing, unregistered)
    assert "bigdl_tpu_adapter_page_ins_total" in rendered
    assert "bigdl_tpu_adapter_page_outs_total" in rendered
    assert "bigdl_tpu_adapter_pages_resident" in rendered


@pytest.mark.chaos
def test_adapter_page_in_stall_quarantines_one_request(model, adapter_dir):
    """An injected device page-in stall fails exactly the request that
    triggered it ("error", structured kind) — neighbours, including
    another tenant, finish normally; nothing leaks; refcounts drain."""
    d, _ = adapter_dir
    inj = FaultInjector(seed=0).arm("adapter_page_in_stall", times=1)
    reg = AdapterRegistry(dir=d)
    jobs = [(PROMPTS[0], "t-r2"), (PROMPTS[1], "t-r3"), (PROMPTS[2], None)]
    eng, reqs = _run_engine(model, jobs, reg, faults=inj)
    bad, good, base = reqs
    assert bad.done and bad.finish_reason == "error"
    assert "page_in_stall" in bad.error and "t-r2" in bad.error
    assert good.finish_reason in ("stop", "length"), good.error
    assert base.finish_reason in ("stop", "length")
    assert inj.fired["adapter_page_in_stall"] == 1
    # the failed page-in left no partial residency, and the stalled
    # tenant's registry reference was handed back (evictable again)
    assert all(e["refcount"] == 0 for e in reg.resident())
    assert eng.page_leaks() == 0
    # the stalled tenant works on retry (fault exhausted)
    r = eng.submit(PROMPTS[0], max_new_tokens=4, adapter="t-r2")
    eng.run_until_idle(max_steps=300)
    assert r.finish_reason in ("stop", "length"), r.error


@pytest.mark.core
def test_speculative_adapter_parity_vs_merged(model, adapter_dir,
                                              merged_oracle):
    """The S-LoRA completion oracle: a mixed batch (3 ranks + base)
    decoded through SPECULATIVE rounds — base-model draft, adapter
    applied in the verify forward — emits the same greedy tokens as
    non-speculative adapter decode, i.e. the offline merge_lora oracle
    (which test_mixed_batch_parity_vs_merged pins to the plain path)."""
    d, _ = adapter_dir
    reg = AdapterRegistry(dir=d)
    jobs = list(zip(PROMPTS, [None, "t-r2", "t-r3", "t-r5"]))
    eng, reqs = _run_engine(model, jobs, reg, speculative=True, draft_k=2)
    assert eng.spec_rounds > 0 and eng.spec_emitted > 0
    for (prompt, name), req in zip(jobs, reqs):
        assert req.finish_reason in ("stop", "length"), req.error
        assert req.out_tokens == merged_oracle[(name, tuple(prompt))], \
            (name, prompt)
    assert eng._pager is not None and eng._pager.page_ins > 0
    assert all(e["refcount"] == 0 for e in reg.resident())


@pytest.mark.chaos
def test_speculative_adapter_parity_under_preemption(model, adapter_dir,
                                                     merged_oracle):
    """Injected pool exhaustion preempts an adapter-carrying slot out of
    a SPECULATIVE batch; after resume the emitted tokens still extend
    the merged oracle (greedy prefix-stability), and the shared pool
    reconciles at drain."""
    d, _ = adapter_dir
    reg = AdapterRegistry(dir=d)
    jobs = list(zip(PROMPTS, [None, "t-r2", "t-r3", "t-r5"]))
    # skip admission (4) + adapter page-in (6) allocs so both faults
    # land on decode page growth (see test_parity_under_preemption)
    inj = FaultInjector(seed=0).arm("alloc_page", times=2, after=12)
    eng, reqs = _run_engine(model, jobs, reg, n_new=12, faults=inj,
                            speculative=True, draft_k=2)
    assert eng.preemptions > 0, "scenario must actually preempt"
    for (prompt, name), req in zip(jobs, reqs):
        assert req.finish_reason in ("stop", "length"), req.error
        oracle = merged_oracle[(name, tuple(prompt))]
        assert req.out_tokens[: len(oracle)] == oracle, (name, prompt)
        assert len(req.out_tokens) == 12


@pytest.mark.chaos
@pytest.mark.slow
def test_speculative_adapter_replay_after_crash(model, adapter_dir,
                                                tmp_path, merged_oracle):
    """crash_before_done on a speculative adapter engine: the successor
    (also speculative) replays the journaled request WITH its adapter
    and matches the merged oracle — the journal path is agnostic to how
    tokens were emitted."""
    d, _ = adapter_dir
    jpath = str(tmp_path / "journal.jsonl")
    inj = FaultInjector(seed=0).arm("crash_before_done", times=1)
    reg = AdapterRegistry(dir=d)
    eng = shared_engine(model, n_slots=4, max_len=128, paged=True,
                        page_size=16, adapters=reg, journal=jpath,
                        faults=inj, speculative=True, draft_k=2)
    req = eng.submit(PROMPTS[1], max_new_tokens=8, adapter="t-r2")
    with pytest.raises(Exception):
        eng.run_until_idle(max_steps=500)
    assert req.done
    reg2 = AdapterRegistry(dir=d)
    eng2 = shared_engine(model, n_slots=4, max_len=128, paged=True,
                         page_size=16, adapters=reg2, journal=jpath,
                         speculative=True, draft_k=2)
    assert len(eng2.recovered_requests) == 1
    rec = eng2.recovered_requests[0]
    assert rec.adapter == "t-r2"
    eng2.run_until_idle(max_steps=500)
    assert rec.done and rec.finish_reason in ("stop", "length")
    assert rec.out_tokens == merged_oracle[("t-r2", tuple(PROMPTS[1]))]
    eng2.close()
