"""Plain decode keeps ONE step in flight (serving/engine.py `_step`): step
N+1 is dispatched before step N is read. Held here, over the four cache
kinds a slot can hold (a dense row, KV pages, a recurrent state row, latent
pages), against the same engine made to read every step before it
dispatches the next (`_book_ahead` answering "not now": a monkeypatch of the
test, not an option of the engine)."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.api import TpuModel, optimize_model
from bigdl_tpu.generate import GenerationConfig
from bigdl_tpu.models import deepseek, get_family, llama
from bigdl_tpu.models.config import PRESETS, ModelConfig
from bigdl_tpu.obs.tracing import DECODE_TID, TraceRecorder, validate_nesting
from bigdl_tpu.serving.faults import FaultInjector
from bigdl_tpu.serving.metrics import Metrics
from engines import shared_engine

pytestmark = pytest.mark.core

BRUMBY = dict(model_type="brumby", hidden_size=64, intermediate_size=128,
              num_hidden_layers=2, num_attention_heads=10,
              num_key_value_heads=2, head_dim=16, vocab_size=256,
              rms_norm_eps=1e-6, rope_theta=1e6, tie_word_embeddings=False,
              max_position_embeddings=4096)
GLM = dict(
    model_type="glm4_moe_lite", hidden_size=128, intermediate_size=256,
    moe_intermediate_size=64, num_attention_heads=4, num_key_value_heads=4,
    num_hidden_layers=3, n_routed_experts=8, n_shared_experts=1,
    num_experts_per_tok=2, first_k_dense_replace=1, q_lora_rank=64,
    kv_lora_rank=128, qk_nope_head_dim=32, qk_rope_head_dim=16,
    v_head_dim=48, vocab_size=512, rms_norm_eps=1e-5, rope_theta=1e6,
    rope_scaling=None, topk_method="noaux_tc", norm_topk_prob=True,
    n_group=1, topk_group=1, routed_scaling_factor=1.8,
    tie_word_embeddings=False, max_position_embeddings=4096)

#: cache kind -> (model key, engine options)
KINDS = {
    "dense": ("llama", {}),
    "pages": ("llama", {"paged": True, "page_size": 8}),
    "state": ("brumby", {"paged": True}),
    "latent": ("glm", {"paged": True, "page_size": 8}),
}


@pytest.fixture(scope="module")
def models():
    cfg = PRESETS["tiny-llama"]
    out = {"llama": TpuModel(cfg, optimize_model(
        llama.init_params(cfg, jax.random.PRNGKey(7)), cfg), "sym_int4")}
    cfg = ModelConfig.from_hf_config(BRUMBY)
    out["brumby"] = TpuModel(cfg, optimize_model(
        get_family("brumby").init_params(cfg, jax.random.PRNGKey(1),
                                         dtype=jnp.float32),
        cfg, "sym_int4"), "sym_int4")
    cfg = ModelConfig.from_hf_config(GLM)
    out["glm"] = TpuModel(cfg, optimize_model(
        deepseek.init_params(cfg, jax.random.PRNGKey(0)), cfg, "bf16"),
        "bf16")
    return out


def _engine(models, kind, ahead=True, **kw):
    """An engine of `kind`; `ahead=False`: one that never dispatches a step
    before it has read the one before."""
    key, opts = KINDS[kind]
    args = dict(n_slots=2, max_len=64, gen=GenerationConfig(
        eos_token_id=None), **opts)
    args.update(kw)
    eng = shared_engine(models[key], **args)
    if not ahead:
        eng._book_ahead = lambda unread: None
    return eng


def _prompt(i, n=6):
    return [(7 * i + 3 * j) % 200 + 1 for j in range(n)]


def _outs(reqs):
    return [(r.out_tokens, r.out_logprobs, r.finish_reason) for r in reqs]


def _solo(models, kind, prompt, n, **submit):
    """What one request yields alone on an engine that never runs ahead."""
    eng = _engine(models, kind, ahead=False, n_slots=1)
    r = eng.submit(prompt, max_new_tokens=n, **submit)
    eng.run_until_idle()
    return r


# ---- (a) the same tokens and logprobs --------------------------------------

@pytest.mark.parametrize("kind", list(KINDS))
def test_greedy_tokens_and_logprobs_equal_the_drained_engines(models, kind):
    """Requests queued for a slot, and requests that land in a free slot
    while a step is unread (an admission donates `cur`: the host must not
    need it), yield exactly what the engine yields that reads every step
    before the next dispatch."""
    got = {}
    for ahead in (True, False):
        eng = _engine(models, kind, ahead=ahead, n_slots=3)
        reqs = [eng.submit(_prompt(i), max_new_tokens=5 + 2 * i)
                for i in range(2)]
        for call in range(200):
            if call == 2:  # a free slot, a step in flight
                assert (eng._flight is not None) == ahead
                reqs.append(eng.submit(_prompt(7, 11), max_new_tokens=6))
            if call == 3:  # two more: one waits for a slot
                reqs += [eng.submit(_prompt(8 + i), max_new_tokens=4)
                         for i in range(2)]
            if not eng.step() and call > 3:
                break
        assert all(r.done for r in reqs) and eng._flight is None
        assert eng.page_leaks() == 0 and eng.decode_rows_discarded == 0
        assert (eng.decode_steps[1] > 0) == ahead
        got[ahead] = _outs(reqs)
    assert got[True] == got[False]
    assert all(len(t) == n for (t, _, _), n in zip(
        got[True], (5, 7, 6, 4, 4)))


@pytest.mark.parametrize("kind", list(KINDS))
def test_seeded_sampling_is_the_same_stream(models, kind):
    """The key is split once an admission and once a DISPATCHED step, in
    dispatch order: for the same order of admissions and steps the sampled
    tokens are the same. An admission made in call c joins the step
    dispatched in call c, which is read one call later than on the engine
    that never runs ahead: there the same order needs the request a call
    later."""
    got = {}
    for ahead in (True, False):
        eng = _engine(models, kind, ahead=ahead, n_slots=3, seed=11)
        kw = dict(do_sample=True, temperature=1.3, top_k=40)
        reqs = [eng.submit(_prompt(i), max_new_tokens=9, **kw)
                for i in range(2)]
        for call in range(100):
            if call == (3 if ahead else 4):
                reqs.append(eng.submit(_prompt(5), max_new_tokens=5,
                                       repetition_penalty=1.3, **kw))
            if not eng.step() and call > 4:
                break
        assert all(r.done for r in reqs)
        got[ahead] = _outs(reqs)
    assert got[True] == got[False]
    assert got[True][0][0] != _solo(models, kind, _prompt(0), 9).out_tokens


# ---- (b) a finish seen one step late ---------------------------------------

class _Clock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        self.t += 1e-4
        return self.t


def _finish_late(eng, how, req, clock):
    """Make `req` finish in a way the host could not foresee when it
    dispatched the step in flight."""
    if how == "cancel":
        eng.cancel(req)
    elif how == "deadline":
        clock.t += 100.0
    elif how == "nan":
        eng._faults.arm("nan_logits", times=1, slots=[0])


@pytest.mark.parametrize("how", ["eos", "cancel", "deadline", "nan"])
@pytest.mark.parametrize("kind", list(KINDS))
def test_a_finish_seen_late_drops_exactly_one_row(models, kind, how):
    """EOS (a stop sequence reaches the engine as a cancel), a cancel, a
    deadline and a quarantined row end a request after the next step was
    dispatched with a row for it: that one row is computed and dropped,
    nothing more is emitted, the neighbour's tokens are untouched and no
    page leaks."""
    solo = [_solo(models, kind, _prompt(i), 12) for i in range(2)]
    clock = _Clock()
    eng = _engine(models, kind, clock=clock, faults=FaultInjector(seed=0))
    eos = solo[0].out_tokens[5] if how == "eos" else None
    first = eng.submit(_prompt(0), max_new_tokens=12, eos_token_id=eos,
                       deadline_s=50.0 if how == "deadline" else None)
    other = eng.submit(_prompt(1), max_new_tokens=12)
    for _ in range(3):
        eng.step()
    assert eng._flight is not None and eng._flight.reqs[0] is first
    n_before = len(first.out_tokens)
    _finish_late(eng, how, first, clock)
    eng.run_until_idle()
    assert first.done and other.done and eng._flight is None
    assert eng.decode_rows_discarded == 1
    want = solo[0].out_tokens
    if how == "eos":
        assert first.finish_reason == "stop"
        assert first.out_tokens == want[:want.index(eos)]
    else:
        assert first.finish_reason == {"cancel": "stop", "nan": "error",
                                       "deadline": "timeout"}[how]
        # seen at the top of the next call, or in the step then read:
        # nothing is emitted after it
        assert first.out_tokens == want[:n_before]
    assert (other.out_tokens, other.finish_reason) == (
        solo[1].out_tokens, "length")
    np.testing.assert_allclose(other.out_logprobs, solo[1].out_logprobs,
                               atol=1e-5)
    assert eng.page_leaks() == 0
    # the freed slot serves the next request as if nothing had been dropped
    again = eng.submit(_prompt(1), max_new_tokens=12)
    eng.run_until_idle()
    assert again.out_tokens == solo[1].out_tokens


# ---- (c) the last step by count ---------------------------------------------

@pytest.mark.parametrize("kind", list(KINDS))
def test_a_last_step_in_flight_takes_no_page_and_no_length(models, kind):
    """A slot whose last step by its count of tokens is in flight gets no
    row in the step after it: no page a token further (here the next
    token would open a new page, and a state row is `row_full` from the
    start), no `"length"` before its last token, and where no slot
    outlives the step in flight nothing is dispatched at all."""
    page = 8
    prompt, n = _prompt(3, 5), 2 * page + 1 - 5  # ends on a page's last slot
    eng = _engine(models, kind, n_slots=1)
    r = eng.submit(prompt, max_new_tokens=n)
    held, steps = 0, 0
    while eng.step():
        steps += 1
        if eng.paged:
            held = max(held, len(eng.pages.slot_pages[0]))
    assert r.finish_reason == "length" and len(r.out_tokens) == n
    assert r.out_tokens == _solo(models, kind, prompt, n).out_tokens
    assert sum(eng.decode_steps) == n - 1  # one a token after the first
    assert eng.decode_rows_discarded == 0 and eng.page_leaks() == 0
    if kind in ("pages", "latent"):
        assert held == 2  # positions 0 .. 2 * page - 1, and not one more
    if kind == "state":
        assert held == 1 and eng.max_pages_per_row == 1


# ---- (d) what needs the pool at rest ---------------------------------------

@pytest.mark.parametrize("kind", list(KINDS))
def test_preempt_and_resume_with_a_step_in_flight(models, kind):
    """A preemption reads the step in flight before it copies the slot to
    host RAM (exact positions, a pool at rest); the resume lands behind
    the step then in flight. Tokens are the uninterrupted run's."""
    solo = [_solo(models, kind, _prompt(i), 14) for i in range(2)]
    eng = _engine(models, kind)
    reqs = [eng.submit(_prompt(i), max_new_tokens=14) for i in range(2)]
    for _ in range(4):
        eng.step()
    assert eng._flight is not None
    eng.preempt(reqs[0])
    eng.step()  # drains, parks the slot; dispatches nothing more
    assert reqs[0].preemptions == 1 and eng._flight is None
    assert len(reqs[0].out_tokens) == 6  # the step in flight was applied
    eng.run_until_idle()
    assert eng.preemption_resumes == 1 and eng.page_leaks() == 0
    assert [r.out_tokens for r in reqs] == [s.out_tokens for s in solo]
    assert eng.decode_rows_discarded == 0


@pytest.mark.parametrize("kind", list(KINDS))
def test_drain_close_and_fail_all_with_a_step_in_flight(models, kind):
    solo = _solo(models, kind, _prompt(0), 10)
    # drain(): steps to the end and leaves nothing in flight
    eng = _engine(models, kind)
    r = eng.submit(_prompt(0), max_new_tokens=10)
    eng.step()
    assert eng._flight is not None
    assert eng.drain() and r.out_tokens == solo.out_tokens
    assert eng._flight is None and eng.page_leaks() == 0
    # close(): the step in flight is read and applied, not lost
    eng = _engine(models, kind)
    r = eng.submit(_prompt(0), max_new_tokens=10)
    eng.step()
    n = len(r.out_tokens)
    eng.close()
    assert eng._flight is None and len(r.out_tokens) == n + 1
    assert r.out_tokens == solo.out_tokens[:n + 1]
    # fail_all(): the step in flight is dropped, its rows counted, and the
    # engine serves on
    eng = _engine(models, kind)
    reqs = [eng.submit(_prompt(i), max_new_tokens=10) for i in range(2)]
    eng.step()
    n = len(reqs[0].out_tokens)
    eng.fail_all("boom")
    assert eng._flight is None and eng.decode_rows_discarded == 2
    assert all(q.finish_reason == "error" and len(q.out_tokens) == n
               for q in reqs)
    assert eng.page_leaks() == 0
    again = eng.submit(_prompt(0), max_new_tokens=10)
    eng.run_until_idle()
    assert again.out_tokens == solo.out_tokens


@pytest.mark.parametrize("kind", ["dense", "pages"])
def test_a_speculative_round_reads_the_step_in_flight_first(models, kind):
    """A round's accepted counts decide the next positions, so a round
    never starts with a plain step unread. (An engine is speculative or
    not for life; the test flips it to put a plain step in flight.)"""
    solo = _solo(models, kind, _prompt(0), 12)
    eng = _engine(models, kind, n_slots=1, speculative=True, draft_k=3,
                  draft_params=models["llama"].params)
    r = eng.submit(_prompt(0), max_new_tokens=12)
    eng.speculative = False
    eng.step()
    assert eng._flight is not None
    n = len(r.out_tokens)
    eng.speculative = True
    eng.step()
    assert eng._flight is None and len(r.out_tokens) > n + 1
    eng.run_until_idle()
    assert r.out_tokens == solo.out_tokens and eng.page_leaks() == 0


def test_a_device_failure_surfaces_at_the_fetch(models):
    """The fetch of step N fails with N+1 queued: every request fails,
    both steps are gone with the pool, and the engine serves on."""
    eng = _engine(models, "pages")
    reqs = [eng.submit(_prompt(i), max_new_tokens=10) for i in range(2)]
    eng.step()
    assert eng._flight is not None

    class Broken:
        def block_until_ready(self):
            raise RuntimeError("device said no")

    eng._flight.out = Broken()
    with pytest.raises(RuntimeError, match="device said no"):
        eng.step()
    assert eng._flight is None and eng.page_leaks() == 0
    assert all(r.finish_reason == "error" for r in reqs)
    again = eng.submit(_prompt(0), max_new_tokens=6)
    eng.run_until_idle()
    assert again.out_tokens == _solo(models, "pages", _prompt(0),
                                     6).out_tokens


# ---- (e) step()'s contract --------------------------------------------------

@pytest.mark.parametrize("kind", list(KINDS))
def test_a_call_emits_one_token_a_slot_that_was_active(models, kind):
    """Every call of `step()` that decodes emits exactly one token for
    every slot that was active when it was entered, the first call after
    an idle stretch included (it dispatches two steps and reads one)."""
    eng = _engine(models, kind)
    reqs = [eng.submit(_prompt(i), max_new_tokens=4 + 3 * i)
            for i in range(3)]
    first = True
    while True:
        active = [s.req for s, a in zip(eng._slots, eng.active) if a]
        before = {r.rid: len(r.out_tokens) for r in reqs}
        more = eng.step()
        for r in active:
            assert len(r.out_tokens) == before[r.rid] + 1
        for r in reqs:  # an admission: its first token and, after an idle
            # stretch, the first step's too
            if r not in active and r.out_tokens and not before[r.rid]:
                assert len(r.out_tokens) == 1 + first
        first = False
        if not more:
            break
    assert [len(r.out_tokens) for r in reqs] == [4, 7, 10]
    assert eng.decode_steps[0] == 1  # the step after the idle start; the
    # second request keeps a step in flight across both admissions


# ---- (f) spans and counters -------------------------------------------------

def test_spans_annotations_and_counters_follow_the_steps(models,
                                                         monkeypatch):
    built = []

    class Annotation(contextlib.nullcontext):
        def __init__(self, name, **ids):
            super().__init__()
            built.append((name, ids))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    tr = TraceRecorder(enabled=True)
    eng = _engine(models, "pages", tracer=tr)
    eos = _solo(models, "pages", _prompt(0), 12).out_tokens[4]
    eng.submit(_prompt(0), max_new_tokens=12, eos_token_id=eos)
    eng.submit(_prompt(1), max_new_tokens=9)
    eng.run_until_idle()
    events = tr.events()
    assert validate_nesting(events) == []
    steps = sorted((e for e in events if e["name"] == "decode_step"),
                   key=lambda e: e["ts"])
    assert all(e["tid"] == DECODE_TID for e in steps)
    for a, b in zip(steps, steps[1:]):
        assert a["ts"] + a["dur"] <= b["ts"]
    # a dropped row is not in its step's occupancy
    assert [e["args"]["occupancy"] for e in steps] == [2] * 4 + [1] * 4
    n_ahead = sum(e["args"]["ahead"] for e in steps)
    assert eng.decode_steps == [len(steps) - n_ahead, n_ahead] == [1, 7]
    assert eng.decode_rows_discarded == 1
    text = Metrics(eng).render()
    assert 'bigdl_tpu_decode_steps_total{ahead="0"} 1' in text
    assert 'bigdl_tpu_decode_steps_total{ahead="1"} 7' in text
    assert "bigdl_tpu_decode_rows_discarded_total 1" in text
    # `seq` pairs a step's five phases, and the uploads of step N+1 come
    # before the read of step N
    phases = ["decode.args", "decode.call", "decode.wait", "decode.read",
              "step.emit"]
    for e in steps:
        seq = e["args"]["seq"]
        assert [n for n, ids in built if ids.get("seq") == seq] == phases
    order = [(n, ids["seq"]) for n, ids in built if "seq" in ids]
    assert order.index(("decode.args", 3)) < order.index(("decode.read", 2))
    # the histogram follows the spans
    assert sum(eng.decode_step_seconds.counts) == len(steps)
    assert abs(eng.decode_step_seconds.sum
               - sum(e["dur"] for e in steps) / 1e6) < 1e-4 * len(steps)
