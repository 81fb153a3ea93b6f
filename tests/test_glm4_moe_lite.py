"""GLM-4.7-Flash (`glm4_moe_lite`) and the latent pages it forced: the config
builder, `deepseek.forward` against the plain reference
(bench/reference/glm4_moe_lite.py), prefill then decode through
`kvpaged.PagedLatentCache` in the paged engine, the absorbed decode kernel
interpreted against `jnp`, what the page table does with a latent page, and
the other MLA model types through the same code. CPU, tiny sizes."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import kvpaged
from bigdl_tpu.api import TpuModel, optimize_model
from bigdl_tpu.generate import GenerationConfig
from bigdl_tpu.models import deepseek, get_family
from bigdl_tpu.models.config import ModelConfig
from bigdl_tpu.ops.pallas.paged_attention import latent_group_pages
from bigdl_tpu.serving.engine import InferenceEngine
from engines import shared_engine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = dict(
    model_type="glm4_moe_lite", hidden_size=128, intermediate_size=256,
    moe_intermediate_size=64, num_attention_heads=4, num_key_value_heads=4,
    num_hidden_layers=3, n_routed_experts=8, n_shared_experts=1,
    num_experts_per_tok=2, first_k_dense_replace=1, q_lora_rank=64,
    kv_lora_rank=128, qk_nope_head_dim=32, qk_rope_head_dim=16,
    v_head_dim=48, vocab_size=512, rms_norm_eps=1e-5, rope_theta=1e6,
    rope_scaling=None, topk_method="noaux_tc", norm_topk_prob=True,
    n_group=1, topk_group=1, routed_scaling_factor=1.8,
    tie_word_embeddings=False, max_position_embeddings=4096)
# widths the kernels' shape guards take: the grouped expert kernel and the
# stacked qmatmul run (interpreted) instead of the XLA formulations
TINY_KERNELS = dict(TINY, moe_intermediate_size=128, qk_rope_head_dim=32,
                    qk_nope_head_dim=32, v_head_dim=64, kv_lora_rank=96)


def _reference():
    from bench import cells

    return cells.load_module(ROOT, "reference", "glm4_moe_lite")


_MADE = {}  # a tiny model's (config, tree), once a (config, qtype, seed)
_JITTED = {}  # the reference, compiled once a (config, n_last)


def _params(hf, qtype, seed=0):
    """The same objects to every test that asks alike (none changes a
    tree): `shared_engine` knows a model by them."""
    key = (json.dumps(hf, sort_keys=True), qtype, seed)
    if key not in _MADE:
        cfg = ModelConfig.from_hf_config(hf)
        params = deepseek.init_params(cfg, jax.random.PRNGKey(seed))
        if "moe_layers" in params and "e_bias" in params["moe_layers"]:
            params["moe_layers"]["e_bias"] = 0.01 * jax.random.normal(
                jax.random.PRNGKey(seed + 5),
                params["moe_layers"]["e_bias"].shape)
        _MADE[key] = cfg, optimize_model(params, cfg, qtype)
    return _MADE[key]


def _ref_logits(ref, hf, params, seq, n_last):
    """The reference under `jax.jit`: run eagerly it compiles every scan of
    its own again at every call."""
    key = (json.dumps(hf, sort_keys=True), n_last)
    if key not in _JITTED:
        _JITTED[key] = jax.jit(lambda p, t: ref.logits(hf, p, t, n_last))
    return _JITTED[key](params, jnp.asarray(seq, jnp.int32))


def _tokens(n, seed, vocab=512):
    return np.random.default_rng(seed).integers(1, vocab, n)


def _engine(cfg, params, qtype="bf16", fresh=False, **kw):
    """`fresh`: with programs of its own, for a test of what it traces."""
    args = dict(n_slots=3, max_len=256, paged=True, page_size=16, n_pages=60,
                gen=GenerationConfig(eos_token_id=None))
    args.update(kw)
    make = InferenceEngine if fresh else shared_engine
    return make(TpuModel(cfg, params, qtype), **args)


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")


# ---- the config --------------------------------------------------------------

def test_from_hf_config_on_the_published_config():
    with open(os.path.join(ROOT, "bench", "configs",
                           "glm-4.7-flash-int4.json")) as f:
        hf = json.load(f)["published"]
    cfg = ModelConfig.from_hf_config(hf)
    assert get_family(cfg.model_type) is deepseek
    assert (cfg.num_hidden_layers, cfg.hidden_size, cfg.num_attention_heads,
            cfg.vocab_size) == (47, 2048, 20, 154880)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim) == (768, 512, 192, 64, 256)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.n_shared_experts,
            cfg.moe_intermediate_size, cfg.first_k_dense_replace) == (
                64, 4, 1, 1536, 1)
    # what the source's config.json has no key for, set for the model type
    assert cfg.scoring_func == "sigmoid" and cfg.rope_interleaved
    assert cfg.topk_method == "noaux_tc" and cfg.norm_topk_prob
    assert (cfg.n_group, cfg.topk_group) == (1, 1)
    assert cfg.routed_scaling_factor == 1.8 and not cfg.tie_word_embeddings
    assert cfg.rope_theta == 1e6 and cfg.rope_scaling_dict is None
    assert deepseek.num_dense_layers(cfg) == 1
    assert deepseek.latent_token_nbytes(cfg) == 47 * 576 * 2


@pytest.mark.parametrize("rope_scaling,want", [
    (None, (192 + 64) ** -0.5),
    ({"type": "yarn", "factor": 40, "mscale_all_dim": 1.0,
      "original_max_position_embeddings": 4096},
     (192 + 64) ** -0.5 * (0.1 * np.log(40) + 1.0) ** 2)])
def test_mla_softmax_scale_with_and_without_rope_scaling(rope_scaling, want):
    cfg = ModelConfig.from_hf_config(dict(
        TINY, qk_nope_head_dim=192, qk_rope_head_dim=64,
        rope_scaling=rope_scaling))
    assert deepseek.mla_softmax_scale(cfg) == pytest.approx(want, rel=1e-6)


# ---- forward against the plain reference -------------------------------------

@pytest.mark.parametrize("qtype", ["bf16", "sym_int4"])
def test_forward_matches_the_reference_whole_sequence(qtype):
    """float32 compute: the same function to rounding of float32 sums; the
    routing it reports is the reference's own top-k wherever the k-th and
    the next score lie further apart than that rounding."""
    ref = _reference()
    cfg, params = _params(TINY, qtype)
    toks = _tokens(40, 1)
    want = _ref_logits(ref, TINY, params, toks, 40)
    got, _, routing = deepseek.forward(
        cfg, params, jnp.asarray(toks[None], jnp.int32), None,
        compute_dtype=jnp.float32, moe_routing=True)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=0, atol=2e-5)
    assert routing.shape == (2, 1, 40, 2)  # the expert layers only
    got16, _ = deepseek.forward(cfg, params,
                                jnp.asarray(toks[None], jnp.int32), None)
    np.testing.assert_allclose(np.asarray(got16[0], np.float32),
                               np.asarray(want), rtol=0, atol=0.03)


def test_moe_routing_ids_are_the_reference_routers_within_its_margin():
    """Layer by layer on the reference's own hidden state: the experts the
    program reports differ from the reference's own top-k only where the
    chosen one lies within `ROUTER_TIE` of the reference's k-th best."""
    ref = _reference()
    cfg, params = _params(TINY, "bf16")
    toks = jnp.asarray(_tokens(64, 2), jnp.int32)
    _, _, routing = deepseek.forward(cfg, params, toks[None], None,
                                     moe_routing=True)
    chosen = np.asarray(routing[:, 0])  # [L_moe, T, k]
    # the reference's first expert layer sees its own layer-0 output
    with jax.default_matmul_precision("highest"):
        eps = TINY["rms_norm_eps"]
        h = params["embed"][toks].astype(jnp.float32)
        p0 = jax.tree.map(lambda a: a[0], params["layers"])
        h = h + ref._attention(TINY, ref._rms(
            h, ref.dense(p0["attn_norm"]), eps), p0, ref._same)
        x = ref._rms(h, ref.dense(p0["mlp_norm"]), eps)
        h = h + ref._swiglu(x, ref.dense(p0["w_gate"]), ref.dense(p0["w_up"]),
                            ref.dense(p0["w_down"]), ref._same)
        p1 = jax.tree.map(lambda a: a[0], params["moe_layers"])
        h = h + ref._attention(TINY, ref._rms(
            h, ref.dense(p1["attn_norm"]), eps), p1, ref._same)
        x = ref._rms(h, ref.dense(p1["mlp_norm"]), eps)
        score = jax.nn.sigmoid(x @ ref.dense(p1["router"]).T)
        biased = np.asarray(score + p1["e_bias"][None])
    kth = np.sort(biased, -1)[:, -2]
    mine = np.take_along_axis(biased, chosen[0], -1)
    assert np.all(mine >= kth[:, None] - ref.ROUTER_TIE)
    own = np.argsort(-biased, -1)[:, :2]
    same = np.all(np.sort(own, -1) == np.sort(chosen[0], -1), -1)
    assert same.mean() > 0.9


# ---- prefill then decode through latent pages --------------------------------

def _check(ref, hf, params, r, n_new, atol):
    want = jax.nn.log_softmax(
        _ref_logits(ref, hf, params, r.prompt + r.out_tokens[:-1], n_new))
    want = np.asarray(want)[np.arange(n_new), r.out_tokens]
    np.testing.assert_allclose(np.asarray(r.out_logprobs), want, rtol=0,
                               atol=atol)


@pytest.mark.parametrize("qtype", ["bf16", "sym_int4"])
def test_engine_through_latent_pages_matches_the_reference(qtype):
    """Prompts that end inside a page (37), cross several (70) and fill one
    exactly (16): the first token from the expanded prefill, the rest from
    absorbed decode steps through the pages, against the reference that
    never absorbs and has no cache. The XLA routes (no interpreter)."""
    ref = _reference()
    cfg, params = _params(TINY, qtype)
    eng = _engine(cfg, params, qtype)
    assert isinstance(eng.cache, kvpaged.PagedLatentCache)
    assert eng.cache.lat.shape == (3, 60, 16, 256)  # 128 + 16 -> 256 lanes
    reqs = [eng.submit(_tokens(n, n).tolist(), max_new_tokens=6)
            for n in (37, 70, 16)]
    eng.run_until_idle()
    for r in reqs:
        assert r.finish_reason == "length"
        _check(ref, TINY, params, r, 6, 0.02)
        assert r.expert_ids(len(r.prompt) + 5).shape == (
            2, len(r.prompt) + 5, 2)
    assert eng.page_leaks() == 0
    load = eng.moe_load()
    assert load["moe_experts"] == 16 and load["moe_assignments"] > 0
    eng.close()


def test_engine_with_the_kernels_interpreted_matches_the_reference(interpret):
    """The same through the absorbed decode kernel, the flash kernel on
    expanded K and V, the stacked qmatmul and the grouped expert kernel,
    all interpreted; and the reference evaluated AT the program's choice."""
    from bigdl_tpu.ops.routes import record_routes

    ref = _reference()
    cfg, params = _params(TINY_KERNELS, "sym_int4")
    with record_routes() as routes:
        eng = _engine(cfg, params, "sym_int4", fresh=True, n_slots=2,
                      n_pages=30)
        r = eng.submit(_tokens(37, 3).tolist(), max_new_tokens=4)
        eng.run_until_idle()
    _check(ref, TINY_KERNELS, params, r, 4, 0.02)
    kinds = {k[:2] for k in routes}
    assert ("attention", "pallas:paged_latent") in kinds
    (form,) = {k[2].split(" T1 ")[1] for k in routes
               if k[1] == "pallas:paged_latent"}
    assert form == "grid of 2 rows, groups of %d pages" % (
        latent_group_pages(eng.cache.lat, cfg.num_attention_heads,
                           eng.max_pages_per_row))
    assert ("attention", "pallas:flash") in kinds
    assert ("moe", "pallas:grouped") in kinds
    assert eng.page_leaks() == 0
    eng.close()


def test_absorbed_decode_agrees_with_expanded_prefill():
    """The same tokens two ways through a `PagedLatentCache`: n tokens
    expanded and then one absorbed decode step, against n + 1 expanded."""
    cfg, params = _params(TINY, "bf16")
    toks = jnp.asarray(_tokens(41, 4)[None], jnp.int32)

    def fresh():
        c = deepseek.init_paged_cache(cfg, 9, 16, 1, 4)
        return kvpaged.PagedLatentCache(
            lat=c.lat, block_tables=jnp.asarray([[3, 1, 7, 5]], jnp.int32),
            pos=c.pos, start=c.start)

    whole, _ = deepseek.forward(cfg, params, toks, fresh())
    _, c = deepseek.forward(cfg, params, toks[:, :40], fresh())
    assert int(c.pos[0]) == 40
    step, c = deepseek.forward(cfg, params, toks[:, 40:], c, mode="decode")
    assert int(c.pos[0]) == 41
    np.testing.assert_allclose(np.asarray(step[0, 0], np.float32),
                               np.asarray(whole[0, 40], np.float32),
                               rtol=0, atol=0.02)
    # and against no cache at all (absorbed throughout), in float32 so that
    # no expert choice flips: what is left is the pages' bf16 latents
    whole32, _ = deepseek.forward(cfg, params, toks, fresh(),
                                  compute_dtype=jnp.float32)
    dense32, _ = deepseek.forward(cfg, params, toks, None,
                                  compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(whole32[0]), np.asarray(dense32[0]),
                               rtol=0, atol=0.01)


def test_a_prefix_hit_gives_the_logits_of_a_cold_run():
    cfg, params = _params(TINY, "bf16")
    shared = _tokens(40, 5).tolist()  # two and a half pages
    prompt = shared + _tokens(9, 6).tolist()
    cold = _engine(cfg, params)
    want = cold.submit(prompt, max_new_tokens=5)
    cold.run_until_idle()
    eng = _engine(cfg, params)
    eng.submit(shared + _tokens(16, 7).tolist(), max_new_tokens=2)
    eng.run_until_idle()
    r = eng.submit(prompt, max_new_tokens=5)
    eng.run_until_idle()
    # two whole pages shared, and the half page after them copied
    assert eng.pages.prefix_hits == 1 and eng.pages.prefix_partial_hits == 1
    assert r.out_tokens == want.out_tokens
    np.testing.assert_allclose(r.out_logprobs, want.out_logprobs, rtol=0,
                               atol=0.02)
    assert r.prompt_experts is None  # part of the prompt was not computed
    assert eng.page_leaks() == 0 and cold.page_leaks() == 0


def test_park_and_resume_is_bit_equal_and_leaks_nothing():
    cfg, params = _params(TINY, "bf16")
    prompt = _tokens(50, 31).tolist()
    plain = _engine(cfg, params, n_slots=2)
    want = plain.submit(prompt, max_new_tokens=14)
    plain.run_until_idle()
    eng = _engine(cfg, params, n_slots=2)
    other = eng.submit(_tokens(20, 32).tolist(), max_new_tokens=14)
    r = eng.submit(prompt, max_new_tokens=14)
    for _ in range(4):
        eng.step()
    held = list(eng.pages.slot_pages[1])
    before = np.asarray(eng.cache.lat[:, jnp.asarray(held)]).copy()
    eng.preempt(r)
    eng._reap_preempt_requests()  # the head of the next step: parks it
    assert eng.preemptions == 1 and eng.pages.slot_pages[1] == []
    parked = eng._preempted[0].blob
    np.testing.assert_array_equal(parked.lat, before)
    assert parked.nbytes == len(held) * kvpaged.kv_page_nbytes(eng.cache)
    eng.run_until_idle()
    assert eng.preemption_resumes == 1
    assert r.out_tokens == want.out_tokens
    assert r.out_logprobs == want.out_logprobs  # bit-equal, not close
    assert other.finish_reason == "length" and eng.page_leaks() == 0


def test_the_page_table_needs_a_latent_pages_byte_count_and_nothing_else():
    """`PageTable` is built from counts alone (no array of the pool reaches
    it), and one latent page is `page_nbytes` = layers x page x padded
    width x 2 B."""
    import inspect

    from bigdl_tpu.serving import pages

    assert "jax" not in inspect.getsource(pages).split('"""', 2)[2].split(
        "class PageTable")[0].replace("no jax", "")
    cfg, params = _params(TINY, "bf16")
    eng = _engine(cfg, params)
    assert kvpaged.kv_page_nbytes(eng.cache) == 3 * 16 * 256 * 2
    assert eng.latent_token_bytes == 3 * (128 + 16) * 2
    assert eng.pages.share_prefixes and eng.preemption


def test_the_refusals_name_latent_pages():
    cfg, params = _params(TINY, "bf16")
    model = TpuModel(cfg, params, "bf16")
    for kw, what in ((dict(quantize_kv=True), "quantize_kv"),
                     (dict(speculative=True), "speculative"),
                     (dict(adapters=object()), "adapter")):
        with pytest.raises(NotImplementedError,
                           match=f"{what}.*not wired for latent pages"):
            shared_engine(model, n_slots=1, max_len=64, paged=True, **kw)
    # the dense pool of latents is still served, unpaged
    eng = shared_engine(model, n_slots=1, max_len=64,
                        gen=GenerationConfig(eos_token_id=None))
    r = eng.submit(_tokens(20, 8).tolist(), max_new_tokens=3)
    eng.run_until_idle()
    assert r.finish_reason == "length" and len(r.out_tokens) == 3


def test_spans_and_gauges_of_a_latent_engine():
    from bigdl_tpu.obs.tracing import TraceRecorder
    from bigdl_tpu.serving.metrics import Metrics, metric_drift

    cfg, params = _params(TINY, "bf16")
    tr = TraceRecorder(capacity=4096)
    eng = _engine(cfg, params, tracer=tr)
    eng.submit(_tokens(40, 41).tolist(), max_new_tokens=3)
    eng.submit(_tokens(20, 42).tolist(), max_new_tokens=3)
    eng.run_until_idle()
    ev = tr.events()
    steps = [e["args"] for e in ev if e["name"] == "decode_step"]
    per_token = 3 * (128 + 16) * 2
    assert steps and all(
        a["latent_bytes_read"] == a["latent_live_tokens"] * per_token
        and a["live_pages"] <= a["grid_pages"] for a in steps)
    # both rows live in the second of the two decode steps: slots 0 .. pos
    # of each, the token the step itself wrote included
    assert max(a["latent_live_tokens"] for a in steps) == 42 + 22
    # the decode kernel's form, a call: a grid step a slot, and the trips of
    # its loop, one a group of pages up to a live row's pos
    group = latent_group_pages(eng.cache.lat, cfg.num_attention_heads,
                               eng.max_pages_per_row)
    assert all(a["attn_grid_steps"] == eng.n_slots and a["occupancy"]
               <= a["attn_live_groups"] <= a["live_pages"] for a in steps)
    assert max(a["attn_live_groups"] for a in steps) == (
        41 // eng.page_size // group + 1 + 21 // eng.page_size // group + 1)
    assert all(a["moe_experts"] == 16 and a["moe_assignments"] > 0
               for a in steps)
    pre = {e["args"]["prompt_tokens"]: e["args"] for e in ev
           if e["name"] == "prefill"}
    assert pre[40]["latent_tokens_upprojected"] == 256  # the row's capacity
    assert pre[40]["moe_assignments"] == 40 * 2 * 2
    text = Metrics(eng).render()
    assert metric_drift(text, eng) == ([], [])
    assert "\nbigdl_tpu_latent_pages_in_use " in text
    assert f"\nbigdl_tpu_latent_token_bytes {per_token}\n" in text
    assert "\nbigdl_tpu_moe_experts_hit_share " in text


# ---- the decode kernel --------------------------------------------------------

def _jnp_absorbed(q_eff, q_pe, lat, bt, layer, pos, start, scale, live):
    r, w = q_eff.shape[-1], q_eff.shape[-1] + q_pe.shape[-1]
    rows = lat[layer][bt]
    B, mp, page, _ = rows.shape
    rows = jnp.nan_to_num(rows.reshape(B, mp * page, -1).astype(jnp.float32))
    q = jnp.concatenate([q_eff, q_pe], -1).astype(jnp.float32)
    s = jnp.einsum("bhw,bsw->bhs", q, rows[..., :w]) * scale
    sj = jnp.arange(mp * page)
    ok = ((sj[None] >= start[:, None]) & (sj[None] <= pos[:, None])
          & live[:, None])
    p = jax.nn.softmax(jnp.where(ok[:, None], s, -1e30), -1)
    p = jnp.where(ok[:, None], p, 0.0)
    return jnp.einsum("bhs,bsr->bhr", p, rows[..., :r])


@pytest.mark.parametrize("group", [1, 4, 8])
def test_latent_kernel_interpreted_equals_jnp(group):
    """Live ranges that start past slot 0 and end inside a page, an idle
    row, and NaN in every page no live row maps (the scratch page 0
    included): the kernel neither loads nor uses a dead page."""
    from bigdl_tpu.ops.pallas.paged_attention import (
        paged_latent_decode_attention)

    rng = np.random.default_rng(0)
    B, H, r, dr, page, mp, L, NP = 3, 5, 128, 64, 16, 11, 2, 40
    lat = jnp.asarray(rng.normal(size=(L, NP, page, 256)), jnp.bfloat16)
    lat = lat.at[..., r + dr:].set(0)
    pos, start = np.array([37, 150, 0]), np.array([0, 3, 0])
    live = np.array([True, True, False])
    bt, perm, k = np.zeros((B, mp), np.int32), rng.permutation(
        np.arange(1, NP)), 0
    for b in range(2):
        n = pos[b] // page + 1
        bt[b, :n] = perm[k:k + n]
        k += n
    dead = np.setdiff1d(np.arange(NP), bt.ravel()[bt.ravel() > 0])
    lat = lat.at[:, jnp.asarray(dead)].set(jnp.nan)
    q_eff = jnp.asarray(rng.normal(size=(B, H, r)), jnp.bfloat16)
    q_pe = jnp.asarray(rng.normal(size=(B, H, dr)), jnp.bfloat16)
    args = (jnp.asarray(bt), jnp.asarray(1), jnp.asarray(pos),
            jnp.asarray(start))
    got = paged_latent_decode_attention(
        q_eff, q_pe, lat, *args, scale=0.1, live=jnp.asarray(live),
        interpret=True, pages_per_group=group)
    want = _jnp_absorbed(q_eff, q_pe, lat, args[0], 1, args[2], args[3], 0.1,
                         jnp.asarray(live))
    assert got.shape == (B, H, r) and got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=0, atol=0.01)
    assert not np.any(np.asarray(got[2], np.float32))  # the idle row: zeros


def _latent_case(lengths, page=16, mp=12, r=128, dr=64, H=5, seed=0,
                 shuffle=True, poison=True):
    """(q_eff, q_pe, lat, (bt, layer, pos, start), live) for rows holding
    `lengths` tokens (0: an idle row, mapped to no page): a pool whose
    pages are dealt out of order (or in order), NaN in every page no live
    row maps."""
    rng = np.random.default_rng(seed)
    B, L = len(lengths), 2
    n_pages = 1 + sum(-(-n // page) for n in lengths) + 3
    lat = jnp.asarray(rng.normal(size=(L, n_pages, page, 256)), jnp.bfloat16)
    lat = lat.at[..., r + dr:].set(0)
    ids = np.arange(1, n_pages)
    ids, k = (rng.permutation(ids) if shuffle else ids), 0
    bt = np.zeros((B, mp), np.int32)
    for b, n in enumerate(lengths):
        held = -(-n // page)
        bt[b, :held] = ids[k:k + held]
        k += held
    if poison:
        dead = np.setdiff1d(np.arange(n_pages), bt.ravel()[bt.ravel() > 0])
        lat = lat.at[:, jnp.asarray(dead)].set(jnp.nan)
    lengths = np.asarray(lengths)
    q_eff = jnp.asarray(rng.normal(size=(B, H, r)), jnp.bfloat16)
    q_pe = jnp.asarray(rng.normal(size=(B, H, dr)), jnp.bfloat16)
    args = (jnp.asarray(bt), jnp.asarray(1),
            jnp.asarray(np.maximum(lengths - 1, 0)), jnp.zeros(B, jnp.int32))
    return q_eff, q_pe, lat, args, jnp.asarray(lengths > 0)


def _holds_to_jnp(case, group):
    from bigdl_tpu.ops.pallas.paged_attention import (
        paged_latent_decode_attention)

    q_eff, q_pe, lat, args, live = case
    got = paged_latent_decode_attention(
        q_eff, q_pe, lat, *args, scale=0.1, live=live, interpret=True,
        pages_per_group=group)
    want = _jnp_absorbed(q_eff, q_pe, lat, args[0], 1, args[2], args[3], 0.1,
                         live)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=0, atol=0.01)
    idle = ~np.asarray(live)
    assert not np.any(np.asarray(got, np.float32)[idle])  # zeros, not NaN
    return got


# pages of 16 tokens, 12 a row: the rule gives such rows groups of 8 pages
# (128 tokens, the largest power of two a row holds); the other is 4 (64)
_LENGTHS = {
    "idle": 0, "one_token": 1, "a_token_short_of_a_page": 15,
    "a_page": 16, "a_token_into_the_next_page": 17,
    "a_group_of_4": 64, "a_last_group_of_4_with_dead_pages": 64 + 17,
    "a_group_of_8": 128, "a_last_group_of_8_with_dead_pages": 128 + 17,
    "the_whole_row": 12 * 16,
}


@pytest.mark.parametrize("group", [None, 4], ids=["ruled", "of_4"])
@pytest.mark.parametrize("case", list(_LENGTHS))
def test_latent_kernel_at_a_rows_length(case, group):
    """One row of the length under test between an idle row and a full
    one: every page it does not own holds NaN, the dead pages of its last
    group among them, and none reaches the output."""
    _holds_to_jnp(_latent_case([0, _LENGTHS[case], 12 * 16]), group)


@pytest.mark.parametrize("group", [None, 4], ids=["ruled", "of_4"])
def test_latent_kernel_over_32_rows_of_mixed_lengths(group):
    lengths = np.random.default_rng(7).integers(0, 12 * 16 + 1, 32)
    lengths[[3, 11]] = 0  # idle rows among them
    _holds_to_jnp(_latent_case(list(lengths), H=3, seed=3), group)


@pytest.mark.parametrize("group", [None, 4], ids=["ruled", "of_4"])
def test_latent_kernel_reads_through_the_block_table(group):
    """The same rows from a pool dealt in order and from one dealt out of
    order: the same pages' contents under other page numbers give the same
    bits."""
    lengths, outs = [70, 33, 0, 150], []
    for shuffle in (False, True):
        q_eff, q_pe, lat, args, live = _latent_case(
            lengths, shuffle=shuffle, poison=False)
        if shuffle:  # carry the in-order pool's pages to their new numbers
            lat = jnp.zeros_like(lat).at[:, args[0].ravel()].set(
                straight[2][:, straight[3][0].ravel()])
            case = (straight[0], straight[1], lat, args, live)
        else:
            case = straight = (q_eff, q_pe, lat, args, live)
        outs.append(np.asarray(_holds_to_jnp(case, group), np.float32))
    np.testing.assert_array_equal(*outs)


def _script():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "latent_kernel_bench",
        os.path.join(ROOT, "scripts", "latent_kernel_bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("group", [1, 4, 8])
def test_latent_kernel_equals_the_old_grid_bit_for_bit(group):
    """PR 51 changed how pages REACH the dots, not what the dots are fed:
    at the same pages a group the row loop gives the bits of the (row,
    group) grid it replaced, which `scripts/latent_kernel_bench.py` keeps."""
    from bigdl_tpu.ops.pallas.paged_attention import (
        paged_latent_decode_attention)

    q_eff, q_pe, lat, args, live = _latent_case([5, 0, 100, 192, 64],
                                                poison=False)
    got = paged_latent_decode_attention(
        q_eff, q_pe, lat, *args, scale=0.1, live=live, interpret=True,
        pages_per_group=group)
    old = _script().grid_form(q_eff, q_pe, lat, *args, 0.1, live, group,
                              interpret=True)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(old, np.float32))


def _pallas_calls(jaxpr):
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            yield e
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _pallas_calls(sub)


@pytest.mark.parametrize("rows,pages_a_row", [(3, 12), (32, 80)])
def test_latent_kernels_grid_is_a_step_a_row(rows, pages_a_row):
    """Grid (B,), whatever a row may hold, and the pool handed over where
    it lies (no block of it: the body fetches what is live)."""
    from bigdl_tpu.ops.pallas.paged_attention import (
        paged_latent_decode_attention)

    B, H, r, dr, page, L, NP = rows, 5, 128, 64, 16, 2, 9
    z = jnp.zeros
    jaxpr = jax.make_jaxpr(lambda *a: paged_latent_decode_attention(
        *a, scale=0.1, interpret=True))(
        z((B, H, r), jnp.bfloat16), z((B, H, dr), jnp.bfloat16),
        z((L, NP, page, 256), jnp.bfloat16), z((B, pages_a_row), jnp.int32),
        z((), jnp.int32), z((B,), jnp.int32), z((B,), jnp.int32))
    (call,) = _pallas_calls(jaxpr.jaxpr)
    gm = call.params["grid_mapping"]
    assert gm.grid == (B,)
    q_block, pool, out_block = (str(bm.block_aval)
                                for bm in gm.block_mappings)
    assert "any" in pool and "any" not in q_block + out_block


def test_latent_group_comes_from_the_rows_bytes():
    """`tiling.latent_group_pages`: from the page's bytes and the heads'
    rows against `LATENT_GROUP_BYTES`: a power of two, within the budget
    and the row, the next one beyond one of them."""
    from bigdl_tpu.ops.pallas import tiling

    cell = tiling.latent_group_pages(64, 640, 2, 20, 80)  # GLM-4.7-Flash's
    assert cell == _CELL_GROUP
    assert tiling.latent_group_pages(16, 256, 2, 5, 12) == 8  # these tests'
    for page, width, itemsize, heads, mp in (
            (64, 640, 2, 20, 80), (16, 256, 2, 5, 12), (128, 640, 2, 128, 64),
            (64, 640, 4, 32, 80), (4096, 640, 2, 20, 4), (64, 640, 2, 20, 3)):
        p = tiling.latent_group_pages(page, width, itemsize, heads, mp)
        rows = -(-heads // 16) * 16
        assert 1 <= p <= mp and p & (p - 1) == 0
        assert p == 1 or tiling.latent_group_bytes(
            p, page, width, itemsize, rows) <= tiling.LATENT_GROUP_BYTES
        assert 2 * p > mp or tiling.latent_group_bytes(
            2 * p, page, width, itemsize, rows) > tiling.LATENT_GROUP_BYTES
    # wider rows, more heads or bigger pages: never more pages a group
    assert tiling.latent_group_pages(64, 1280, 2, 20, 80) <= cell
    assert tiling.latent_group_pages(64, 640, 2, 128, 80) <= cell
    assert tiling.latent_group_pages(128, 640, 2, 20, 80) <= cell


_CELL_GROUP = 16


# ---- weights stay out of the scan's slices ------------------------------------

def test_packed_codes_stay_out_of_both_scans_slices(interpret):
    """No uint8 stack of packed codes among either scan's sliced inputs when
    the kernels run: neither the expert stacks [M, E, O, C] nor a dense
    weight's [L, O, C]; on the XLA route they are sliced as before."""
    cfg, params = _params(TINY_KERNELS, "sym_int4")
    toks = jnp.zeros((2, 1), jnp.int32)
    cache = deepseek.init_paged_cache(cfg, 9, 16, 2, 4)

    def sliced_codes():
        jaxpr = jax.make_jaxpr(lambda p, c: deepseek.forward(
            cfg, p, toks, c, mode="decode"))(params, cache).jaxpr
        scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
        assert [e.params["length"] for e in scans] == [1, 2]
        out = []
        for e in scans:
            skip = e.params["num_consts"] + e.params["num_carry"]
            out += [v.aval.shape for v in e.invars[skip:]
                    if v.aval.dtype == jnp.uint8]
        return out

    left = sliced_codes()
    # w_dkv (O = 128: no lane tile... kept by the shape guard) may stay; no
    # expert stack and none of the big projections does
    assert not [s for s in left if len(s) == 4], left
    assert len(left) <= 2, left


def test_packed_codes_stay_sliced_on_the_xla_route():
    cfg, params = _params(TINY_KERNELS, "sym_int4")
    toks = jnp.zeros((2, 1), jnp.int32)
    cache = deepseek.init_paged_cache(cfg, 9, 16, 2, 4)
    jaxpr = jax.make_jaxpr(lambda p, c: deepseek.forward(
        cfg, p, toks, c, mode="decode"))(params, cache).jaxpr
    moe = [e for e in jaxpr.eqns if e.primitive.name == "scan"][1]
    skip = moe.params["num_consts"] + moe.params["num_carry"]
    assert len([v for v in moe.invars[skip:]
                if v.aval.dtype == jnp.uint8 and v.aval.ndim == 4]) == 3


# ---- the other MLA model types, by the same code -------------------------------

_V3 = dict(TINY, model_type="deepseek_v3", n_group=2, topk_group=1,
           routed_scaling_factor=2.5)
_V2 = dict(TINY, model_type="deepseek_v2", topk_method="group_limited_greedy",
           n_group=2, topk_group=1, norm_topk_prob=False,
           routed_scaling_factor=1.0, q_lora_rank=None)
_MINICPM3 = dict(
    model_type="minicpm3", hidden_size=128, intermediate_size=256,
    num_attention_heads=4, num_key_value_heads=4, num_hidden_layers=2,
    q_lora_rank=64, kv_lora_rank=96, qk_nope_head_dim=32,
    qk_rope_head_dim=16, v_head_dim=32, vocab_size=512, rms_norm_eps=1e-5,
    scale_emb=12, scale_depth=1.4, dim_model_base=64,
    max_position_embeddings=4096)


@pytest.mark.parametrize("hf", [_V3, _V2, _MINICPM3],
                         ids=["deepseek_v3", "deepseek_v2", "minicpm3"])
def test_the_other_mla_model_types_serve_paged(hf):
    """Paged serving against the family's own whole-sequence forward
    (absorbed, no cache), which tests/test_deepseek.py holds to HF."""
    cfg, params = _params(hf, "bf16")
    eng = _engine(cfg, params)
    assert isinstance(eng.cache, kvpaged.PagedLatentCache)
    reqs = [eng.submit(_tokens(n, n).tolist(), max_new_tokens=5)
            for n in (37, 70)]
    eng.run_until_idle()
    for r in reqs:
        seq = jnp.asarray([r.prompt + r.out_tokens[:-1]], jnp.int32)
        logits, _ = deepseek.forward(cfg, params, seq, None)
        want = jax.nn.log_softmax(logits[0, -5:].astype(jnp.float32))
        want = np.asarray(want)[np.arange(5), r.out_tokens]
        np.testing.assert_allclose(np.asarray(r.out_logprobs), want, rtol=0,
                                   atol=0.03)
    assert eng.page_leaks() == 0


def test_chunked_prefill_through_latent_pages_matches_the_reference():
    """`prefill_chunk_tokens`: every chunk expands the row's earlier
    latents beside its own; three chunks give what one prefill gives."""
    ref = _reference()
    cfg, params = _params(TINY, "bf16")
    eng = _engine(cfg, params, prefill_chunk_tokens=32)
    r = eng.submit(_tokens(70, 70).tolist(), max_new_tokens=5)
    eng.run_until_idle()
    assert eng.prefill_chunks == 3 and eng.page_leaks() == 0
    _check(ref, TINY, params, r, 5, 0.02)
