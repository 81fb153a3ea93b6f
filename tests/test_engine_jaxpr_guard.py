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

    JAX_PLATFORMS=cpu python tests/test_engine_jaxpr_guard.py
"""

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
    B, c, z = 4, eng.cache, jnp.zeros
    table = z((1, eng.max_pages_per_row), jnp.int32)
    programs = (
        (eng._decode, (
            params, z((B,), jnp.int32), c, jax.random.PRNGKey(0), z((B,)),
            z((B,), jnp.int32), z((B,)), z((B,), bool), eng.seen, z((B,)))),
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
# equations of `engine_decode` with the kernels on (the interpreter's route):
# (the tree as `optimize_model` makes it, the tree prepared)
KERNEL_ROUTE = {
    "mistral": (431, 427),
    "mixtral": (531, 523),
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


def scale_work(name: str, prepare: bool) -> tuple:
    """(equations of `engine_decode` outside the kernels' bodies, the
    equations inside its layer scan that view 16-bit floats as integers or
    slice a 16-bit operand, the layer stacks the scan is handed whole)."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.api import TpuModel, optimize_model
    from bigdl_tpu.models import get_family
    from bigdl_tpu.models.config import ModelConfig
    from bigdl_tpu.quant.qtensor import without_scale_bits
    from bigdl_tpu.serving.engine import InferenceEngine

    cfg = ModelConfig.from_hf_config(GUARDED[name])
    model = TpuModel(cfg, optimize_model(
        get_family(cfg.model_type).init_params(cfg, jax.random.PRNGKey(0)),
        cfg, "sym_int4"), "sym_int4")
    params = model.params if prepare else without_scale_bits(model.params)
    eng = InferenceEngine(model, n_slots=4, max_len=256, paged=True,
                          page_size=16, n_pages=65)
    B, z = 4, jnp.zeros
    jaxpr = jax.make_jaxpr(eng._decode.__wrapped__)(
        params, z((B,), jnp.int32), eng.cache, jax.random.PRNGKey(0),
        z((B,)), z((B,), jnp.int32), z((B,)), z((B,), bool), eng.seen,
        z((B,))).jaxpr
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


if __name__ == "__main__":
    sys.path.insert(0, os.getcwd())
    for name in sorted(MODELS):
        print(f"    {name!r}: {counts(name)},", flush=True)
    os.environ["BIGDL_TPU_PALLAS"] = "interpret"
    for name in sorted(GUARDED):
        print(f"    {name!r}: ({scale_work(name, False)[0]}, "
              f"{scale_work(name, True)[0]}),  # KERNEL_ROUTE", flush=True)
