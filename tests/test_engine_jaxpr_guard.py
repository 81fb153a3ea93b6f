"""A model PR keeps its code off the other models' trace path.

PR 33 was refused over warm `setup_s` alone (`qwen2-7b.chat-closed` 36.28 ->
40.72 s): a warm set-up is mostly TRACING the shared forward and the engine's
programs, and a new mechanism on that path is paid by every cell. So the
programs the benchmark's other configurations trace are counted here, at toy
sizes: the equations of `jax.make_jaxpr` of `engine_decode` and
`engine_paged_prefill` (nested jaxprs counted in) for a tiny Mistral, Qwen2,
Mixtral, Brumby and GLM-4.7-Flash, pinned to what the tree BEFORE PR 38
counted (this file run as a script from that tree's root prints them). A
count, not a time: a change that moves one has to say why and measure warm
`setup_s` on both sides before it re-pins. PR 39 did, for the three models that
prefill from KV pages: their `engine_paged_prefill` gathers the row's pages,
prefills a dense row and writes pages back in a loop (314 -> 357 equations for
the tiny Mistral, on the CPU's mask route), and warm `setup_s` read 35.3 / 36.2
s against the parent's 37.3 / 37.1 in `qwen2-7b.chat-closed`, 35.1 against 36.5
in `mistral-7b.chat-steady` (PERF.md section 6, PR 39). The same write-back
UNROLLED, a `dynamic_update_slice` a page, counted 395 here and cost 5.7 s of
warm set-up in chat-steady: the count saw it before the chip did.
PR 41 adds a tiny SmallThinker with a pin of its own: a family of its own
(models/smallthinker.py, loaded on first use), so the five above count what
they counted; its own count is a scan over the periods of its layouts with
the period's four layers as the body, so it grows with the PERIOD and not
with the depth (two periods here count what six would).
PR 47 adds a tiny Laguna the same way (models/laguna.py, loaded on first
use): its first period is run by itself, because layer 0's feed-forward is
dense, and the other periods are the scan's body, so it counts two periods'
layers whatever the depth; the six above count what they counted.
PR 50 adds a tiny SDAR-MoE (models/sdar.py, loaded on first use: Qwen3-MoE's
block through `llama.forward`, generated by diffusion over blocks): its
`engine_decode` is a PASS over a block of 4 positions a row with the reveal
after it (serving/blocks.py), its prefill the llama prefill under a mask
causal by block. What it added to `llama.forward` and `flash_attention` is
behind `config.block_length`, 0 for everyone else: the seven above count
what they counted.
PR 56 adds a tiny Jamba (models/jamba.py, loaded on first use: Mamba-1 layers
on `state_beside_pages`' second state layout, `kvhybrid.mix1`): a family and
a mixer of its own beside granite's, whose programs, and everyone else's,
`scripts/engine_parity.py` found the parent's to the byte.
PR 61 adds a tiny LFM2-MoE (models/lfm2_moe.py, loaded on first use: gated
short-convolution layers on `state_beside_pages` with NO recurrence state,
`kvhybrid.tail_conv`; KV heads of 64 as lane pairs): a third family on the
kind. Granite's, jamba's, qwen2's and GLM's programs lowered for a described
v5e are the parent's to the byte, the kernels' own bodies aside (they carry
source locations; `scripts/engine_parity.py --v5e`): PERF.md section 6, PR 61.

    JAX_PLATFORMS=cpu python tests/test_engine_jaxpr_guard.py
"""

import functools
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_DENSE = dict(hidden_size=128, intermediate_size=256, num_hidden_layers=2,
              num_attention_heads=4, num_key_value_heads=2, vocab_size=512,
              rms_norm_eps=1e-5, rope_theta=1e4, max_position_embeddings=2048,
              tie_word_embeddings=False)
MODELS = {
    "mistral": dict(_DENSE, model_type="mistral"),
    "qwen2": dict(_DENSE, model_type="qwen2"),
    "mixtral": dict(_DENSE, model_type="mixtral", num_local_experts=4,
                    num_experts_per_tok=2),
    "brumby": dict(_DENSE, model_type="brumby", head_dim=32),
    "glm4_moe_lite": dict(
        _DENSE, model_type="glm4_moe_lite", num_hidden_layers=3,
        num_key_value_heads=4, moe_intermediate_size=128, n_routed_experts=8,
        num_experts_per_tok=2, n_shared_experts=1, first_k_dense_replace=1,
        n_group=1, topk_group=1, topk_method="noaux_tc", norm_topk_prob=True,
        routed_scaling_factor=1.8, q_lora_rank=128, kv_lora_rank=96,
        qk_nope_head_dim=32, qk_rope_head_dim=32, v_head_dim=64,
        rope_scaling=None),
    # no `model_type`, as the source's config.json keys have none
    "smallthinker": dict(
        hidden_size=128, head_dim=32, num_attention_heads=4,
        num_key_value_heads=2, num_hidden_layers=8, vocab_size=512,
        rms_norm_eps=1e-6, rope_theta=1.5e6, max_position_embeddings=2048,
        tie_word_embeddings=False, moe_ffn_hidden_size=128,
        moe_num_primary_experts=8, moe_num_active_primary_experts=3,
        sliding_window_size=32, sliding_window_layout=[0, 1, 1, 1] * 2,
        rope_layout=[0, 1, 1, 1] * 2),
    "laguna": dict(
        model_type="laguna", hidden_size=128, intermediate_size=256,
        head_dim=32, num_attention_heads=6, num_key_value_heads=2,
        num_hidden_layers=8, vocab_size=512, rms_norm_eps=1e-6,
        max_position_embeddings=2048, tie_word_embeddings=False,
        num_experts=16, num_experts_per_tok=4, moe_intermediate_size=128,
        shared_expert_intermediate_size=128, gating=True, sliding_window=32,
        moe_routed_scaling_factor=2.5, partial_rotary_factor=0.5,
        layer_types=(["full_attention"] + ["sliding_attention"] * 3) * 2,
        mlp_layer_types=["dense"] + ["sparse"] * 7,
        num_attention_heads_per_layer=[6, 8, 8, 8] * 2,
        rope_parameters={
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 16,
                "original_max_position_embeddings": 64, "beta_slow": 1,
                "beta_fast": 8, "attention_factor": 1.2772588722239782,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                                  "partial_rotary_factor": 1}}),
    "sdar_moe": dict(
        _DENSE, model_type="sdar_moe", head_dim=32, rms_norm_eps=1e-6,
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=128,
        norm_topk_prob=True, mask_token_id=511),
    "minicpm_sala": dict(
        _DENSE, model_type="minicpm_sala", num_hidden_layers=4,
        mixer_types=["minicpm4", "lightning-attn", "lightning-attn",
                     "minicpm4"],
        head_dim=32, lightning_nh=4, lightning_nkv=4, lightning_head_dim=32,
        rms_norm_eps=1e-6, scale_emb=12, scale_depth=1.4, dim_model_base=32,
        sparse_config=dict(kernel_size=8, kernel_stride=4, block_size=16,
                           topk=6, init_blocks=1, window_size=32,
                           dense_len=64)),
    "jamba": dict(
        _DENSE, model_type="jamba", num_hidden_layers=4,
        num_key_value_heads=1, attn_layer_period=4, attn_layer_offset=2,
        num_experts=1, mamba_expand=2, mamba_d_state=16, mamba_d_conv=4,
        mamba_dt_rank=8, rms_norm_eps=1e-6, tie_word_embeddings=True),
    "lfm2_moe": dict(
        model_type="lfm2_moe", vocab_size=512, hidden_size=512,
        intermediate_size=128, num_hidden_layers=6, num_attention_heads=8,
        num_key_value_heads=4, conv_L_cache=3, conv_bias=False,
        norm_eps=1e-5, num_dense_layers=1, num_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=128,
        norm_topk_prob=True, routed_scaling_factor=1.0,
        use_expert_bias=True, max_position_embeddings=2048,
        layer_types=["conv", "full_attention", "conv", "conv",
                     "full_attention", "conv"],
        rope_parameters={"rope_theta": 1000000.0, "rope_type": "default"}),
    # rank 1 of 2 of a tiny Solar-Open2 (PR 65): a GQA layer, a two-layer
    # scan of Kimi delta attention layers, a GQA layer; 4 of a router's 8
    # experts held
    "solar_open2": dict(
        model_type="solar_open2", vocab_size=512, hidden_size=256,
        intermediate_size=512, num_hidden_layers=4, num_attention_heads=4,
        num_key_value_heads=2, head_dim=128, rms_norm_eps=1e-5,
        max_position_embeddings=2048, use_rope=False, gqa_layers=[0, 3],
        use_gqa_gate=True, n_routed_experts=4, n_shared_experts=1,
        num_experts_per_tok=2, moe_intermediate_size=128,
        norm_topk_prob=True, routed_scaling_factor=1.0,
        linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 128,
                            "num_heads": 2, "num_kv_heads": None},
        expert_parallel_share={"router_experts": 8, "first_expert": 4}),
}

# (engine_decode, engine_paged_prefill) on the parent of PR 38; the paged
# prefill of mistral, mixtral and qwen2 as PR 39 left it (314, 356, 316 before)
PINNED = {
    "brumby": (577, 488),
    "glm4_moe_lite": (921, 803),
    "mistral": (460, 357),
    "mixtral": (505, 399),
    "qwen2": (462, 359),
    "smallthinker": (1404, 1244),  # PR 41's own: a four-layer scan body
    # PR 47's own: the first period's four layers, then a four-layer body
    "laguna": (3316, 2904),
    # PR 50's own: a pass over blocks of 4 and its reveal; the llama prefill
    "sdar_moe": (598, 438),
    # PR 54's own: two sparse layers one by one around a two-layer scan of
    # lightning layers; the selection, the page list and the report
    "minicpm_sala": (1924, 1476),
    # PR 56's own: a two-layer scan of Mamba-1 layers, the attention layer,
    # one more Mamba-1 layer; the `jnp` scan on this route
    "jamba": (1135, 1031),
    # PR 61's own: a dense convolution layer, an attention layer on lane
    # pairs, a two-layer scan of sparse convolution layers, one more of each
    "lfm2_moe": (1621, 1534),
    # PR 65's own: the share's mask and local ids in every layer, the
    # chunked delta rule (its `lax.map` and its walk) in the prefill
    "solar_open2": (1476, 1547),
}


def n_eqns(jaxpr) -> int:
    n = 0
    for e in jaxpr.eqns:
        n += 1
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)  # a closed one's own
                if hasattr(sub, "eqns"):
                    n += n_eqns(sub)
    return n


def decode_args(eng, params) -> tuple:
    """`engine_decode`'s arguments for a four-slot engine."""
    import jax
    import jax.numpy as jnp

    B, z = 4, jnp.zeros
    sampling = (z((B,)), z((B,), jnp.int32), z((B,)), z((B,), bool))
    if eng.blocks is not None:  # a pass over every row's block
        return (params, eng.blocks.state, eng.cache, jax.random.PRNGKey(0),
                *sampling)
    return (params, z((B,), jnp.int32), eng.cache, jax.random.PRNGKey(0),
            *sampling, eng.seen, z((B,)))


def counts(name: str) -> tuple:
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.api import TpuModel, optimize_model
    from bigdl_tpu.models import get_family
    from bigdl_tpu.models.config import ModelConfig
    from bigdl_tpu.serving.engine import InferenceEngine

    cfg = ModelConfig.from_hf_config(MODELS[name])
    params = optimize_model(
        get_family(cfg.model_type).init_params(cfg, jax.random.PRNGKey(0)),
        cfg, "sym_int4")
    eng = InferenceEngine(TpuModel(cfg, params, "sym_int4"), n_slots=4,
                          max_len=256, paged=True, page_size=16, n_pages=65)
    c, z = eng.cache, jnp.zeros
    table = z((1, eng.max_pages_per_row), jnp.int32)
    programs = (
        (eng._decode, decode_args(eng, params)),
        (eng._paged_prefill, (
            params, eng.kind.leaves(c), (table, table), z((1,), jnp.int32),
            z((1, 64), jnp.int32), z((), jnp.int32), z((1,), jnp.int32))))
    return tuple(
        n_eqns(jax.make_jaxpr(getattr(fn, "__wrapped__", fn))(*args).jaxpr)
        for fn, args in programs)


@pytest.mark.core
@pytest.mark.parametrize("name", sorted(MODELS))
def test_the_other_models_programs_count_what_they_counted(name):
    assert counts(name) == PINNED[name]


# ---- the scales' per-layer work cannot come back unseen (ISSUE 48) --------
#
# The pins above are of the XLA route (a CPU without the interpreter runs no
# kernel, so nothing there views a scale), and PR 48 left all seven where they
# were. With the kernels on, the decode step of a tree prepared for serving
# (`llama.prepare_kernel_scales`, which `TpuModel` runs) must neither view a
# float16 scale as uint16 nor slice a scales-shaped operand inside its layer
# scan: on the chip each of those was a copy of the WHOLE stack, padded to 128
# lanes, every layer of every step (12.7 ms of Laguna's 28 ms step, PR 47).

_WIDE = dict(hidden_size=256, intermediate_size=512)  # a 512-row word tile
GUARDED = {
    "mistral": dict(MODELS["mistral"], **_WIDE),
    "mixtral": dict(MODELS["mixtral"], **_WIDE),
}
# what `kernel_route_jaxpr` traces: the tiny models above, the two guarded
# ones at their wider sizes, and a granite-4.0-h wide enough for the grouped
# kernel (the preset's 64 is not)
KERNEL_MODELS = dict(
    MODELS, **GUARDED,
    granitemoehybrid=dict(
        model_type="granitemoehybrid", vocab_size=256, hidden_size=128,
        intermediate_size=128, shared_intermediate_size=128,
        num_hidden_layers=5,
        layer_types=["mamba", "mamba", "attention", "mamba", "mamba"],
        num_attention_heads=4, num_key_value_heads=2, num_local_experts=8,
        num_experts_per_tok=3, mamba_n_heads=4, mamba_d_head=32,
        mamba_d_state=16, mamba_d_conv=4, mamba_expand=1, mamba_n_groups=1,
        mamba_chunk_size=8, embedding_multiplier=12,
        residual_multiplier=0.22, attention_multiplier=0.0625,
        logits_scaling=4, position_embedding_type="nope",
        tie_word_embeddings=True, rms_norm_eps=1e-5))
# equations of `engine_decode` with the kernels on (the interpreter's route):
# (the tree as `optimize_model` makes it, the tree prepared). Mixtral's were
# (531, 523) until ISSUE 53 took the sort out of a decode step's dispatch,
# and (487, 479) until ISSUE 60's k-major combine (`MOE_KERNEL_ROUTE` below).
# Re-pinned by ISSUE 64, on purpose, from (431, 427) and (490, 482): the word
# path copies its code tiles in as words by the kernel's own DMA, and through
# the INTERPRETER (this route) the packed stack passes an
# `optimization_barrier` in front of every such call (XLA:CPU folds the
# interpreter's word view of a constant stack to wrong words): one equation a
# word-path call, three in the tiny Mistral's program and four in the tiny
# Mixtral's, and NONE in a program traced for the chip (`interpret` unset),
# whose equations outside the kernels' bodies are the parent's. Warm
# `setup_s`, as this file asks, parent -> change, alternating pairs
# (my chip runs, PR 64): `mistral-7b.chat-steady` 32.07 / 31.04 / 32.47 /
# 31.84 -> 32.40 / 32.50 / 31.54 / 32.40 s, `mistral-7b.generate-1024-128`
# 23.76 -> 24.13 over six pairs, `sdar-30b-a3b.blockgen-closed` 52.64 ->
# 53.27 over four: the kernels' own traces, not these equations (PERF.md
# section 6, PR 64).
KERNEL_ROUTE = {
    "mistral": (434, 430),
    "mixtral": (494, 486),
}


def _eqns(jaxpr, inside_scan=False):
    """(equation, is it inside a scan's body) over `jaxpr` and what it nests;
    a kernel's own body (`pallas_call`) is the kernel's business."""
    for e in jaxpr.eqns:
        yield e, inside_scan
        if e.primitive.name == "pallas_call":
            continue
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(
                        sub, inside_scan or e.primitive.name == "scan")


@functools.lru_cache(maxsize=None)
def kernel_route_jaxpr(name: str, prepare: bool = True):
    """`engine_decode` of a four-slot paged engine on `KERNEL_MODELS[name]`,
    traced with whatever route the environment gives the kernels (every
    caller sets the interpreter's): the tree prepared for serving, or as
    `optimize_model` makes it. Traced once a process."""
    import jax

    from bigdl_tpu.api import TpuModel, optimize_model
    from bigdl_tpu.models import get_family
    from bigdl_tpu.models.config import ModelConfig
    from bigdl_tpu.quant.qtensor import without_scale_bits
    from bigdl_tpu.serving.engine import InferenceEngine

    cfg = ModelConfig.from_hf_config(KERNEL_MODELS[name])
    model = TpuModel(cfg, optimize_model(
        get_family(cfg.model_type).init_params(cfg, jax.random.PRNGKey(0)),
        cfg, "sym_int4"), "sym_int4")
    params = model.params if prepare else without_scale_bits(model.params)
    eng = InferenceEngine(model, n_slots=4, max_len=256, paged=True,
                          page_size=16, n_pages=65)
    return jax.make_jaxpr(getattr(eng._decode, "__wrapped__", eng._decode))(
        *decode_args(eng, params)).jaxpr


def scale_work(name: str, prepare: bool) -> tuple:
    """(equations of `engine_decode` outside the kernels' bodies, the
    equations inside its layer scan that view 16-bit floats as integers or
    slice a 16-bit operand, the layer stacks the scan is handed whole)."""
    import jax.numpy as jnp

    jaxpr = kernel_route_jaxpr(name, prepare)
    sixteen = (jnp.float16, jnp.uint16)
    work, n, whole = [], 0, 0
    for e, in_scan in _eqns(jaxpr):
        n += 1
        prim = e.primitive.name
        if prim == "scan":  # its constants: what the body indexes by layer
            whole += sum(v.aval.dtype == jnp.uint16 and v.aval.ndim >= 3
                         for v in e.invars[:e.params["num_consts"]])
        if in_scan and e.invars and hasattr(e.invars[0], "aval") and (
                prim == "bitcast_convert_type"
                and e.invars[0].aval.dtype == jnp.float16
                or prim in ("dynamic_slice", "slice", "gather")
                and e.invars[0].aval.dtype in sixteen
                and e.invars[0].aval.ndim >= 2):
            work.append(f"{prim} {e.invars[0].aval.str_short()}")
    return n, work, whole


@pytest.mark.core
@pytest.mark.parametrize("name", sorted(GUARDED))
def test_a_prepared_trees_decode_scan_neither_views_nor_slices_a_scale(
        monkeypatch, name):
    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    n_stored, stored, whole_stored = scale_work(name, prepare=False)
    n_prepared, prepared, whole = scale_work(name, prepare=True)
    # the guard can see what it guards against: a view a projection (and an
    # expert stack) a layer on the tree nobody prepared
    assert whole_stored == 0 and len(stored) >= 4, stored
    assert all(w.startswith("bitcast_convert_type") for w in stored), stored
    assert prepared == [], prepared
    assert whole == len(stored), (whole, stored)  # each view became a stack
    assert (n_stored, n_prepared) == KERNEL_ROUTE[name]


# ---- the head's route note names the loop it ran (ISSUE 55) ----------------
#
# A vocabulary of whole lanes that is no multiple of 512 (32000, 151936,
# 154880, 73472: seven of the ten configurations) ran its head on the
# stored-layout loop until PR 55; it runs the word path over a ragged last
# tile, and the `linear` route line every bench run prints says which.

@pytest.mark.core
@pytest.mark.parametrize("vocab,note", [
    (1024, "words:inplace"), (1024 + 128, "words:inplace:ragged:3")])
def test_the_heads_route_note_names_its_word_form(monkeypatch, vocab, note):
    import jax

    from bigdl_tpu.api import TpuModel, optimize_model
    from bigdl_tpu.models import get_family
    from bigdl_tpu.models.config import ModelConfig
    from bigdl_tpu.ops.routes import record_routes
    from bigdl_tpu.serving.engine import InferenceEngine

    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    cfg = ModelConfig.from_hf_config(
        dict(GUARDED["mistral"], vocab_size=vocab))
    model = TpuModel(cfg, optimize_model(
        get_family(cfg.model_type).init_params(cfg, jax.random.PRNGKey(0)),
        cfg, "sym_int4"), "sym_int4")
    eng = InferenceEngine(model, n_slots=4, max_len=256, paged=True,
                          page_size=16, n_pages=65)
    with record_routes() as routes:
        jax.make_jaxpr(getattr(eng._decode, "__wrapped__", eng._decode))(
            *decode_args(eng, model.params))
    # qtype M K O, codes sliced (outside the scan), the loop, the scales
    heads = [d.split() for op, route, d in routes
             if op == "linear" and {f"O{vocab}", "slice"} <= set(d.split())]
    assert heads and all(
        route.startswith("pallas:") for op, route, d in routes
        if op == "linear")
    assert all(d[4:] == ["slice", note, "scales:stack"] for d in heads), heads
    # the projections in the scan are whole tiles: their notes did not move
    assert all("ragged" not in d for op, _, d in routes
               if op == "linear" and "stack" in d.split())


# GLM's decode step with the kernels on, outside the kernels' bodies: 928 on
# PR 50's tree, and 930 since PR 51 gave the latent decode kernel its
# sibling's scalar operand (`paged_attention._scalars`: the window's entry, one
# equation a call, two calls a step). The XLA-route pin above did not move.
# 886 since ISSUE 53: `MOE_KERNEL_ROUTE` below has every MoE family's.


def glm_kernel_route() -> tuple:
    """(equations of GLM's `engine_decode` outside the kernels' bodies, the
    grid of each `paged_latent_decode_attention` call in it)."""
    jaxpr = kernel_route_jaxpr("glm4_moe_lite")
    eqns = [e for e, _ in _eqns(jaxpr)]
    return len(eqns), [
        e.params["grid_mapping"].grid for e in eqns
        if e.primitive.name == "pallas_call" and "paged_latent_decode"
        in e.params["name"]]


@pytest.mark.core
def test_glms_decode_step_calls_the_latent_kernel_a_grid_step_a_row(
        monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    n, grids = glm_kernel_route()
    assert grids == [(4,), (4,)]  # the dense layer's scan, the experts'
    assert n == MOE_KERNEL_ROUTE["glm4_moe_lite"]


# ---- a decode step's experts read the step's rows where they lie (ISSUE 53)
#
# Until PR 53 every MoE layer of every decode step gathered the step's N rows
# into the grouped kernel's sorted layout, `[n_tiles * block_m, H]` for N * k
# assignments (SDAR: 8192 rows from 64, 33.5 MB written a layer; `moe.dispatch`
# 2.77 ms of a 24 ms pass, PERF.md section 5). One row tile holds a whole
# decode step, so each hit expert's tile is the step's rows as they stand and
# nothing is sorted or copied. The guard: with the kernels on, no gather of
# the decode program has a result of more than N * k rows of width H (the
# combine's `y[dest.T]` has exactly that many), and the programs are SHORTER
# than the parent's (equations outside the kernels' bodies; the parent's in
# the comment), none longer. ISSUE 60's k-major combine adds three equations
# a traced layer body (`dest` and the weights transposed, N * k words each,
# so that no `[N, k, H]` float32 is re-laid: tests/test_moe.py (f)); the
# counts before it are the comment's second number.
MOE_KERNEL_ROUTE = {
    "mixtral": 486,  # 523, 479; 482 until ISSUE 64 (`KERNEL_ROUTE` above)
    "glm4_moe_lite": 889,  # 930, 886
    "smallthinker": 1385,  # 1549, 1373
    "laguna": 2937,  # 3224, 2916
    "sdar_moe": 549,  # 593, 546
    "granitemoehybrid": 1257,  # 1380, 1248
    "lfm2_moe": 1635,  # PR 61's own 1624, and ISSUE 64's barriers
    "solar_open2": 1367,  # PR 65's own
}


def wide_row_gathers(jaxpr, H: int, most: int) -> list:
    """The gathers of `jaxpr` (kernels' bodies apart) whose result has more
    than `most` rows of width `H`."""
    import math

    return [e.outvars[0].aval.str_short() for e, _ in _eqns(jaxpr)
            if e.primitive.name == "gather"
            and e.outvars[0].aval.shape[-1:] == (H,)
            and math.prod(e.outvars[0].aval.shape[:-1]) > most]


@pytest.mark.core
@pytest.mark.parametrize("name", sorted(MOE_KERNEL_ROUTE))
def test_a_moe_decode_step_gathers_no_padded_copy_of_its_rows(monkeypatch,
                                                              name):
    from bigdl_tpu.models.config import ModelConfig

    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    cfg = ModelConfig.from_hf_config(KERNEL_MODELS[name])
    jaxpr = kernel_route_jaxpr(name)
    N = 4 * max(1, cfg.block_length)  # the slots, or SDAR's blocks of 4
    assert wide_row_gathers(
        jaxpr, cfg.hidden_size, N * cfg.num_experts_per_tok) == []
    assert sum(1 for _ in _eqns(jaxpr)) == MOE_KERNEL_ROUTE[name]


@pytest.mark.core
def test_the_gather_guard_sees_the_sorted_forms_copy(monkeypatch):
    """What the guard guards against, in the one place that keeps it: a
    call of more rows than a tile holds (a prefill above 256 tokens) sorts
    its rows into `[n_tiles * block_m, H]`."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.models import llama
    from bigdl_tpu.models.config import ModelConfig
    from bigdl_tpu.quant.qtensor import QTensor

    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    cfg = ModelConfig.from_hf_config(KERNEL_MODELS["mixtral"])
    E, k, H, I = cfg.num_experts, cfg.num_experts_per_tok, 256, 512

    def stack(O, K):
        return QTensor(qtype="sym_int4", data=jnp.zeros((E, O, K // 2),
                                                        jnp.uint8),
                       scales=jnp.zeros((E, O, K // 32), jnp.float16))

    p = {"w_gate_e": stack(I, H), "w_up_e": stack(I, H),
         "w_down_e": stack(H, I)}

    def gathers(N):
        jaxpr = jax.make_jaxpr(lambda x, v, i: llama._moe_dispatch_grouped(
            cfg, x, p, jnp.bfloat16, v, i))(
            jnp.zeros((1, N, H), jnp.bfloat16), jnp.zeros((1, N, k)),
            jnp.zeros((1, N, k), jnp.int32)).jaxpr
        return wide_row_gathers(jaxpr, H, N * k)

    assert gathers(256) == []
    assert gathers(264) == ["bfloat16[1536,256]"]  # 6 tiles of 256 rows


if __name__ == "__main__":
    sys.path.insert(0, os.getcwd())
    for name in sorted(MODELS):
        print(f"    {name!r}: {counts(name)},", flush=True)
    os.environ["BIGDL_TPU_PALLAS"] = "interpret"
    for name in sorted(GUARDED):
        print(f"    {name!r}: ({scale_work(name, False)[0]}, "
              f"{scale_work(name, True)[0]}),  # KERNEL_ROUTE", flush=True)
    for name in sorted(MOE_KERNEL_ROUTE):
        n = sum(1 for _ in _eqns(kernel_route_jaxpr(name)))
        print(f"    {name!r}: {n},  # MOE_KERNEL_ROUTE", flush=True)
