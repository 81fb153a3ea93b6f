"""LLaMA-family model tests.

The core pattern mirrors the reference's GPU layer-equivalence tests
(test_transformers_api_attention.py:44-110 in /root/reference): run the
same checkpoint through HF transformers (torch CPU) and through our JAX
implementation, and require logits to agree within tolerance — dense
first (exact-ish), then quantized (looser).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import kvcache
from bigdl_tpu.generate import (
    GenerationConfig,
    generate_tokens,
    pad_prompts,
    sample_token,
)
from bigdl_tpu.models import llama
from bigdl_tpu.models.config import PRESETS, ModelConfig


# fast gate subset: pytest -m core (scripts/ci.sh --core)
pytestmark = pytest.mark.core

CFG = PRESETS["tiny-llama"]


def make_params(qtype="bf16"):
    params = llama.init_params(CFG, jax.random.PRNGKey(0))
    if qtype != "bf16":
        params = llama.quantize_params(params, qtype)
    return params


def run_full(params, tokens, start=None):
    B, T = tokens.shape
    cache = kvcache.init_cache(
        CFG.num_hidden_layers, B, T + 8, CFG.num_key_value_heads, CFG.head_dim_
    )
    if start is not None:
        cache = dataclasses.replace(cache, start=jnp.asarray(start, jnp.int32))
    return llama.forward(CFG, params, tokens, cache, mode="prefill")


def test_forward_shapes():
    params = make_params()
    tokens = jnp.arange(12, dtype=jnp.int32).reshape(2, 6) % CFG.vocab_size
    logits, cache = run_full(params, tokens)
    assert logits.shape == (2, 6, CFG.vocab_size)
    assert logits.dtype == jnp.float32
    assert int(cache.pos) == 6


def test_prefill_then_decode_matches_full_prefill():
    """Decoding token-by-token must reproduce full-sequence prefill logits."""
    params = make_params()
    full = jnp.asarray([[5, 9, 2, 7, 3, 11]], jnp.int32)
    logits_full, _ = run_full(params, full)

    B, T = 1, 4
    cache = kvcache.init_cache(
        CFG.num_hidden_layers, B, 16, CFG.num_key_value_heads, CFG.head_dim_
    )
    logits_p, cache = llama.forward(CFG, params, full[:, :T], cache, mode="prefill")
    np.testing.assert_allclose(
        np.asarray(logits_p), np.asarray(logits_full[:, :T]), rtol=2e-2, atol=2e-2
    )
    for t in range(T, 6):
        logits_d, cache = llama.forward(
            CFG, params, full[:, t : t + 1], cache, mode="decode"
        )
        np.testing.assert_allclose(
            np.asarray(logits_d[:, 0]),
            np.asarray(logits_full[:, t]),
            rtol=2e-2,
            atol=2e-2,
        )


def test_left_padding_matches_unpadded():
    """A left-padded row must produce the same last-token logits as the
    unpadded prompt (padding masked out of attention and rope)."""
    params = make_params()
    prompt = [5, 9, 2, 7]
    tokens_np, start = pad_prompts([prompt], pad_id=0, bucket=8)
    logits_pad, _ = run_full(params, jnp.asarray(tokens_np), start)
    logits_ref, _ = run_full(params, jnp.asarray([prompt], jnp.int32))
    np.testing.assert_allclose(
        np.asarray(logits_pad[:, -1]),
        np.asarray(logits_ref[:, -1]),
        rtol=2e-2,
        atol=2e-2,
    )


def test_left_padded_decode_matches_unpadded():
    """Rope positions must CONTINUE across the prefill→decode boundary for
    left-padded rows (regression: a per-row base clamp shifted prompt key
    positions by the pad length, which cancels inside prefill by rope
    translation-invariance but breaks the first decode step)."""
    params = make_params()
    prompt = [5, 9, 2, 7]
    # padded path
    tokens_np, start = pad_prompts([prompt], pad_id=0, bucket=16)
    cache = kvcache.init_cache(
        CFG.num_hidden_layers, 1, 32, CFG.num_key_value_heads, CFG.head_dim_
    )
    cache = dataclasses.replace(cache, start=jnp.asarray(start, jnp.int32))
    logits, cache = llama.forward(
        CFG, params, jnp.asarray(tokens_np), cache, mode="prefill"
    )
    nxt = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    d_pad, _ = llama.forward(CFG, params, nxt, cache, mode="decode")

    # unpadded reference
    cache2 = kvcache.init_cache(
        CFG.num_hidden_layers, 1, 32, CFG.num_key_value_heads, CFG.head_dim_
    )
    logits2, cache2 = llama.forward(
        CFG, params, jnp.asarray([prompt], jnp.int32), cache2, mode="prefill"
    )
    d_ref, _ = llama.forward(CFG, params, nxt, cache2, mode="decode")
    np.testing.assert_allclose(
        np.asarray(d_pad), np.asarray(d_ref), rtol=2e-2, atol=2e-2
    )


def test_quantized_forward_close_to_dense():
    params = make_params()
    qparams = llama.quantize_params(params, "sym_int8")
    tokens = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    dense, _ = run_full(params, tokens)
    quant, _ = run_full(qparams, tokens)
    # int8 weight quantization: logits stay close
    err = np.abs(np.asarray(dense) - np.asarray(quant)).mean()
    scale = np.abs(np.asarray(dense)).mean() + 1e-6
    assert err / scale < 0.12, err / scale


def test_fp8_kv_cache_decode_close():
    params = make_params()
    full = jnp.asarray([[5, 9, 2, 7, 3, 11, 4, 8]], jnp.int32)
    logits_ref, _ = run_full(params, full)
    cache = kvcache.init_cache(
        CFG.num_hidden_layers, 1, 16, CFG.num_key_value_heads, CFG.head_dim_,
        quantize_kv=True,
    )
    logits_p, cache = llama.forward(CFG, params, full[:, :7], cache, mode="prefill")
    logits_d, _ = llama.forward(CFG, params, full[:, 7:8], cache, mode="decode")
    np.testing.assert_allclose(
        np.asarray(logits_d[:, 0]), np.asarray(logits_ref[:, 7]), rtol=0.15, atol=0.15
    )


def test_generate_greedy_deterministic():
    params = make_params()
    tokens_np, start = pad_prompts([[3, 1, 4, 1, 5], [9, 2, 6]], pad_id=0)
    gen = GenerationConfig(max_new_tokens=8)
    out = generate_tokens(
        CFG, params, jnp.asarray(tokens_np), jnp.asarray(start),
        jax.random.PRNGKey(0), gen, llama.forward,
        cache_len=tokens_np.shape[1] + 8,
    )
    out2 = generate_tokens(
        CFG, params, jnp.asarray(tokens_np), jnp.asarray(start),
        jax.random.PRNGKey(1), gen, llama.forward,
        cache_len=tokens_np.shape[1] + 8,
    )
    assert out.shape == (2, 8)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
    assert np.all(np.asarray(out) >= 0) and np.all(np.asarray(out) < CFG.vocab_size)


def test_generate_matches_stepwise_argmax():
    """generate() greedy must equal manual prefill+decode argmax chain."""
    params = make_params()
    prompt = [3, 1, 4, 1, 5]
    tokens_np, start = pad_prompts([prompt], pad_id=0, bucket=8)
    gen = GenerationConfig(max_new_tokens=4)
    out = generate_tokens(
        CFG, params, jnp.asarray(tokens_np), jnp.asarray(start),
        jax.random.PRNGKey(0), gen, llama.forward, cache_len=16,
    )
    cache = kvcache.init_cache(
        CFG.num_hidden_layers, 1, 16, CFG.num_key_value_heads, CFG.head_dim_
    )
    cache = dataclasses.replace(cache, start=jnp.asarray(start, jnp.int32))
    logits, cache = llama.forward(
        CFG, params, jnp.asarray(tokens_np), cache, mode="prefill"
    )
    expected = []
    cur = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    expected.append(int(cur[0]))
    for _ in range(3):
        logits, cache = llama.forward(CFG, params, cur[:, None], cache, mode="decode")
        cur = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        expected.append(int(cur[0]))
    np.testing.assert_array_equal(np.asarray(out)[0], expected)


def test_chunked_prefill_matches_full():
    """Two sequential prefill chunks must see each other through the cache."""
    params = make_params()
    full = jnp.asarray([[5, 9, 2, 7, 3, 11, 4, 8]], jnp.int32)
    logits_full, _ = run_full(params, full)
    cache = kvcache.init_cache(
        CFG.num_hidden_layers, 1, 16, CFG.num_key_value_heads, CFG.head_dim_
    )
    _, cache = llama.forward(CFG, params, full[:, :5], cache, mode="prefill")
    logits2, _ = llama.forward(CFG, params, full[:, 5:], cache, mode="prefill")
    np.testing.assert_allclose(
        np.asarray(logits2), np.asarray(logits_full[:, 5:]), rtol=2e-2, atol=2e-2
    )


def test_rope_scaled_config_is_jittable():
    """rope_scaling arrives as a dict from HF config.json; ModelConfig must
    stay hashable (it is a static jit argument) and llama3 scaling must run."""
    cfg = dataclasses.replace(
        CFG,
        rope_theta=500000.0,
        rope_scaling={
            "rope_type": "llama3", "factor": 8.0,
            "low_freq_factor": 1.0, "high_freq_factor": 4.0,
            "original_max_position_embeddings": 64,
        },
    )
    hash(cfg)  # static-arg requirement
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tokens_np, start = pad_prompts([[3, 1, 4]], pad_id=0, bucket=8)
    out = generate_tokens(
        cfg, params, jnp.asarray(tokens_np), jnp.asarray(start),
        jax.random.PRNGKey(0), GenerationConfig(max_new_tokens=4),
        llama.forward, cache_len=16,
    )
    assert out.shape == (1, 4)
    # json round-trip (save_low_bit path) keeps it hashable too
    import json as _json

    rs = _json.loads(_json.dumps(dataclasses.asdict(cfg)))["rope_scaling"]
    hash(dataclasses.replace(cfg, rope_scaling=rs))


def test_sampling_topk_topp_valid():
    logits = jnp.asarray(np.random.default_rng(0).standard_normal((4, 64)), jnp.float32)
    for gen in [
        GenerationConfig(do_sample=True, temperature=0.7),
        GenerationConfig(do_sample=True, top_k=5),
        GenerationConfig(do_sample=True, top_p=0.9),
        GenerationConfig(do_sample=True, top_k=8, top_p=0.8, temperature=1.3),
    ]:
        tok = sample_token(logits, jax.random.PRNGKey(1), gen)
        assert tok.shape == (4,)
        assert np.all(np.asarray(tok) >= 0) and np.all(np.asarray(tok) < 64)
    # top_k=1 is argmax
    gen = GenerationConfig(do_sample=True, top_k=1)
    tok = sample_token(logits, jax.random.PRNGKey(2), gen)
    np.testing.assert_array_equal(np.asarray(tok), np.asarray(jnp.argmax(logits, -1)))


@pytest.mark.parametrize("qtype", ["sym_int4", "nf4"])
def test_hf_equivalence(qtype):
    """Dense JAX forward vs HF torch forward on identical tiny weights;
    quantized forward within the quantization error band."""
    torch = pytest.importorskip("torch")
    from transformers import LlamaConfig, LlamaForCausalLM

    hf_cfg = LlamaConfig(
        vocab_size=CFG.vocab_size,
        hidden_size=CFG.hidden_size,
        intermediate_size=CFG.intermediate_size,
        num_hidden_layers=CFG.num_hidden_layers,
        num_attention_heads=CFG.num_attention_heads,
        num_key_value_heads=CFG.num_key_value_heads,
        max_position_embeddings=CFG.max_position_embeddings,
        rms_norm_eps=CFG.rms_norm_eps,
        rope_theta=CFG.rope_theta,
        tie_word_embeddings=False,
    )
    torch.manual_seed(0)
    hf_model = LlamaForCausalLM(hf_cfg).eval().to(torch.float32)
    sd = {k: v.detach().numpy() for k, v in hf_model.state_dict().items()}

    from bigdl_tpu.convert import params_from_state_dict

    tokens = np.asarray([[3, 1, 4, 1, 5, 9, 2, 6]], np.int32)
    with torch.no_grad():
        hf_logits = hf_model(torch.from_numpy(tokens).long()).logits.numpy()

    # dense equivalence (fp32 compute)
    params = params_from_state_dict(CFG, sd.__getitem__, qtype="bf16", dtype=jnp.float32)
    cache = kvcache.init_cache(
        CFG.num_hidden_layers, 1, 16, CFG.num_key_value_heads, CFG.head_dim_,
        dtype=jnp.float32,
    )
    logits, _ = llama.forward(
        CFG, params, jnp.asarray(tokens), cache, mode="prefill",
        compute_dtype=jnp.float32,
    )
    np.testing.assert_allclose(np.asarray(logits), hf_logits, rtol=2e-3, atol=2e-3)

    # quantized: compare against HF-with-quantized-weights would need HF
    # surgery; instead bound the drift from our own dense logits.
    qparams = params_from_state_dict(CFG, sd.__getitem__, qtype=qtype, dtype=jnp.float32)
    qlogits, _ = llama.forward(
        CFG, qparams, jnp.asarray(tokens),
        kvcache.init_cache(
            CFG.num_hidden_layers, 1, 16, CFG.num_key_value_heads, CFG.head_dim_,
            dtype=jnp.float32,
        ),
        mode="prefill", compute_dtype=jnp.float32,
    )
    err = np.abs(np.asarray(qlogits) - hf_logits).mean()
    scale = np.abs(hf_logits).mean() + 1e-6
    assert err / scale < 0.35, err / scale


# ---------------------------------------------------------------------------
# the layer scan hands the kernels whole stacks of packed codes and its
# index (PR 30), where a per-layer slice given to a Mosaic call is copied
# ---------------------------------------------------------------------------

@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")


STACK_CFG = ModelConfig(
    vocab_size=256, hidden_size=256, intermediate_size=512,
    num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
    max_position_embeddings=128)


def _stack_params(qtype="sym_int4"):
    from bigdl_tpu.api import optimize_model

    return optimize_model(
        llama.init_params(STACK_CFG, jax.random.PRNGKey(5)), STACK_CFG, qtype)


@pytest.mark.parametrize("qtype", ["sym_int4", "q4_k"])
def test_forward_over_stacked_codes_equals_chained_one_layer_forwards(
        interpret, qtype):
    """Three DIFFERENT layers through one scan, against three one-layer
    `forward` calls chained by the pipeline's hooks, each on its own slice
    of the parameters and its own one-layer cache: bit-equal in prefill and
    in decode, so a layer index that is wrong or stale cannot pass."""
    cfg, params = STACK_CFG, _stack_params(qtype)
    L, B, T = cfg.num_hidden_layers, 2, 40
    toks = jax.random.randint(jax.random.PRNGKey(6), (B, T), 0, 256)

    def cache(n):
        return kvcache.init_cache(n, B, 64, cfg.num_key_value_heads,
                                  cfg.head_dim_)

    def whole(p, t, c, mode):
        return llama.forward(cfg, p, t, c, mode=mode)

    def chained(p, t, cs, mode):
        h, out = t, []
        for l in range(L):
            one = {**p, "layers": jax.tree.map(lambda a: a[l:l + 1],
                                               p["layers"])}
            h, c = llama.forward(
                cfg, one, h, cs[l], mode=mode, input_is_hidden=l > 0,
                return_hidden=l < L - 1, layer_offset=l)
            out.append(c)
        return h, out

    run = jax.jit(whole, static_argnames="mode")
    run_chain = jax.jit(chained, static_argnames="mode")
    lg, c = run(params, toks, cache(L), mode="prefill")
    lg1, cs = run_chain(params, toks, [cache(1) for _ in range(L)],
                        mode="prefill")
    np.testing.assert_array_equal(np.asarray(lg), np.asarray(lg1))
    nxt = jnp.argmax(lg[:, -1], -1)[:, None].astype(jnp.int32)
    ld, c = run(params, nxt, c, mode="decode")
    ld1, cs = run_chain(params, nxt, cs, mode="decode")
    np.testing.assert_array_equal(np.asarray(ld), np.asarray(ld1))
    assert np.isfinite(np.asarray(ld)).all()
    # the layers differ: the same token through layer 0 three times is
    # another network
    same = {**params, "layers": jax.tree.map(
        lambda a: jnp.repeat(a[:1], L, 0), params["layers"])}
    assert not np.array_equal(
        np.asarray(run(same, toks, cache(L), mode="prefill")[0]),
        np.asarray(lg))


def _scan_xs(jaxpr):
    """The avals of the layer scan's per-iteration inputs."""
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"
             and e.params["length"] == STACK_CFG.num_hidden_layers]
    assert len(scans) == 1, [e.primitive.name for e in jaxpr.eqns]
    e = scans[0]
    skip = e.params["num_consts"] + e.params["num_carry"]
    return [v.aval for v in e.invars[skip:]]


def test_packed_codes_stay_out_of_the_scan_slices_unless_adapters(interpret):
    """No rank-3 uint8 leaf (a stack of packed codes [L, O, C]) among the
    scan's sliced inputs when the kernels run and nothing is
    differentiated; all four of them back under an adapter tree, and on
    the XLA route, which fuses its own slice."""
    from bigdl_tpu.train.qlora import init_lora

    params = _stack_params()
    toks = jnp.zeros((1, 8), jnp.int32)
    lora = init_lora(STACK_CFG, jax.random.PRNGKey(0), rank=4)

    def codes(lora_tree):
        jaxpr = jax.make_jaxpr(lambda p, lo: llama.forward(
            STACK_CFG, p, toks, None, lora=lo))(params, lora_tree).jaxpr
        return [a for a in _scan_xs(jaxpr)
                if a.dtype == jnp.uint8 and a.ndim == 3]

    assert codes(None) == []
    assert len(codes(lora)) == 4  # wqkv, wo, w_gateup, w_down


def test_packed_codes_stay_sliced_on_the_xla_route():
    params = _stack_params()
    toks = jnp.zeros((1, 8), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p: llama.forward(
        STACK_CFG, p, toks, None))(params).jaxpr
    assert len([a for a in _scan_xs(jaxpr)
                if a.dtype == jnp.uint8 and a.ndim == 3]) == 4


@pytest.mark.parametrize("name,stacked", [
    ("mistral-7b-int4", 4), ("qwen2-7b-int4", 4), ("mixtral-8x7b-int4", 2)])
def test_bench_configurations_read_every_projection_from_the_stack(
        interpret, name, stacked):
    """The benchmark's three configurations at their published widths and
    two layers, traced with abstract weights: a decode step and a prefill
    note `stack` for all four projections of a layer (Mixtral: the two of
    its attention; its experts go through the grouped kernel by the same
    index), `slice` for the LM head alone, and `stack` for none under an
    adapter tree. The scales follow (ISSUE 48): `scales:slice` on every call
    of the tree `optimize_model` makes, `scales:stack` on every layer call
    AND the head once the tree is prepared for serving, and `scales:slice`
    again for whatever an adapter keeps sliced."""
    import os

    from bench import cells, weights
    from bigdl_tpu.ops.routes import record_routes
    from bigdl_tpu.train.qlora import init_lora

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    config = cells.load_json(root, "bench", "configs", name + ".json")
    hf = cells.as_run(config)
    hf["num_hidden_layers"] = 2
    cfg = ModelConfig.from_hf_config(hf)
    qtype, slots = config["bench"]["qtype"], config["bench"]["engine"]["n_slots"]
    stored = weights.param_shapes(cfg, qtype)
    prepared = jax.eval_shape(
        lambda p: llama.prepare_kernel_scales(cfg, p), stored)
    assert jax.tree.structure(stored) == jax.tree.structure(
        weights.param_shapes(cfg, qtype))  # preparing touches no tree

    def notes(B, T, mode, lora=None, params=stored):
        cache = jax.eval_shape(lambda: kvcache.init_cache(
            2, B, 2048, cfg.num_key_value_heads, cfg.head_dim_))
        with record_routes() as routes:
            jax.eval_shape(
                lambda p, c, lo: llama.forward(
                    cfg, p, jnp.zeros((B, T), jnp.int32), c, mode=mode,
                    lora=lo),
                params, cache, lora)
        lin = {k: n for k, n in routes.items() if k[0] == "linear"}
        assert all(k[1].startswith("pallas:") for k in lin), lin
        # a kernel call's detail: qtype M K O, the codes, ..., the scales
        scales = {k[2].split()[-1] for k in routes
                  if k[0] == "linear" or k[:2] == ("moe", "pallas:grouped")}
        return (sum(n for k, n in lin.items() if k[2].split()[4] == "stack"),
                sum(n for k, n in lin.items() if k[2].split()[4] == "slice"),
                scales, routes)

    for B, T, mode in ((slots, 1, "decode"), (1, 1024, "prefill")):
        for params, want in ((stored, "scales:slice"),
                             (prepared, "scales:stack")):
            n_stack, n_slice, scales, routes = notes(B, T, mode,
                                                     params=params)
            assert (n_stack, n_slice) == (stacked, 1), routes
            assert scales == {want}, routes
            # ISSUE 49: every packed call decodes in place; since ISSUE 55
            # a head with no 512-row tile too (Mistral's and Mixtral's 32000
            # rows: 63 word tiles, the last ragged), and its note says so
            for (op, _, detail), _n in routes.items():
                if op == "linear":
                    O = int(detail.split()[3][1:])
                    assert detail.split()[5] == (
                        "words:inplace" if O % 512 == 0
                        else f"words:inplace:ragged:{-(-O // 512)}"), detail
                    assert O % 512 == 0 or "slice" in detail.split()
                elif op == "moe":
                    assert detail.count("words:inplace") == 2, detail
            if cfg.is_moe:
                assert any(k[:2] == ("moe", "pallas:grouped")
                           for k in routes)
    lora = jax.eval_shape(lambda: init_lora(cfg, jax.random.PRNGKey(0), 4))
    # under an adapter only the head, which no adapter touches, reads bits
    for params, want in ((stored, {"scales:slice"}),
                         (prepared, {"scales:slice", "scales:stack"})):
        n_stack, n_slice, scales, routes = notes(1, 64, "prefill", lora,
                                                 params)
        assert n_stack == 0 and n_slice == stacked + 1, routes
        assert scales == want, routes
