"""Capacity-based ragged MoE dispatch tests (VERDICT r2 item 5).

The reference runs MoE through fused index kernels
(`xe_linear.get_moe_indexes`, models/qwen2_moe.py + mixtral.py in
/root/reference); our two formulations are dense combine (E<=8) and
GShard-style capacity dispatch (E>8), which must agree whenever capacity
is not exceeded.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.models import llama
from bigdl_tpu.models.config import ModelConfig


def moe_config(E=16, k=2, **kw):
    return ModelConfig(
        model_type="mixtral", vocab_size=128, hidden_size=64,
        intermediate_size=128, moe_intermediate_size=32,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, num_experts=E, num_experts_per_tok=k,
        norm_topk_prob=True, **kw,
    )


def _forward_logits(config, params, tokens):
    logits, _ = llama.forward(
        config, params, tokens, None, mode="prefill",
        compute_dtype=jnp.float32,
    )
    return np.asarray(logits)


def test_ragged_matches_dense_when_capacity_suffices():
    """With capacity >= all assignments, ragged dispatch computes exactly
    the dense combine (same experts, same weights, different data path)."""
    cfg_dense = moe_config(E=16, k=2, moe_dispatch="dense")
    # capacity factor E/k guarantees C >= N (no expert can overflow)
    cfg_ragged = dataclasses.replace(
        cfg_dense, moe_dispatch="ragged", moe_capacity_factor=8.0
    )
    params = llama.init_params(cfg_dense, jax.random.PRNGKey(0))
    tokens = jnp.asarray([[3, 1, 4, 1, 5, 9, 2, 6], [2, 7, 1, 8, 2, 8, 1, 8]],
                         jnp.int32)
    dense = _forward_logits(cfg_dense, params, tokens)
    ragged = _forward_logits(cfg_ragged, params, tokens)
    np.testing.assert_allclose(ragged, dense, rtol=2e-4, atol=2e-4)


def test_auto_dispatch_by_expert_count():
    assert llama.resolve_moe_dispatch(moe_config(E=8)) == "dense"
    assert llama.resolve_moe_dispatch(moe_config(E=60, k=4)) == "ragged"
    assert llama.resolve_moe_dispatch(
        moe_config(E=60, k=4, moe_dispatch="dense")) == "dense"
    with pytest.raises(ValueError):
        moe_config(E=8, moe_dispatch="Ragged")  # typo must not silently
        # fall through to the dense path (a ~15x FLOP blowup at E=60)


def test_qwen2_moe_scale_flops_scale_with_k_over_E():
    """E=60, k=4 (the qwen2-moe shape): ragged forward FLOPs must be a
    small fraction of the dense formulation's — cost ∝ k/E, the point of
    the dispatch (VERDICT: dense would be a ~15x active-FLOP blowup)."""
    E, k = 60, 4
    cfg_r = moe_config(E=E, k=k, moe_dispatch="ragged")
    cfg_d = moe_config(E=E, k=k, moe_dispatch="dense")
    params = llama.init_params(cfg_r, jax.random.PRNGKey(0))
    tokens = jnp.ones((2, 32), jnp.int32)

    def flops(cfg):
        fn = lambda p, t: llama.forward(cfg, p, t, None, mode="prefill")[0]
        comp = jax.jit(fn).lower(params, tokens).compile()
        ca = comp.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        return ca.get("flops") if ca else None

    fr, fd = flops(cfg_r), flops(cfg_d)
    if not fr or not fd:
        pytest.skip("cost_analysis unavailable on this backend")
    # expert-FFN flops dominate: dense computes E/(k*cf) times more of
    # them; whole-model ratio is diluted by attention/lm_head, so just
    # require a decisive factor
    assert fr < fd / 3, (fr, fd)


def test_ragged_overflow_drops_are_finite_and_bounded():
    """Tiny capacity: overflowing tokens lose their expert contribution
    (GShard semantics) but the output stays finite and the shared/dense
    residual path is unaffected."""
    cfg = moe_config(E=4, k=2, moe_dispatch="ragged", moe_capacity_factor=0.25)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.asarray([[3, 1, 4, 1, 5, 9, 2, 6]], jnp.int32)
    out = _forward_logits(cfg, params, tokens)
    assert np.all(np.isfinite(out))


def test_ragged_under_expert_parallel_mesh():
    """Ragged dispatch jitted over a tp mesh with experts sharded (the
    dryrun EP case, now with the economical path)."""
    from bigdl_tpu.parallel import make_mesh, shard_params
    from bigdl_tpu.parallel.sharding import param_specs

    if len(jax.devices()) < 2:
        pytest.skip("needs >=2 devices")
    mesh = make_mesh((1, 1, 2), devices=jax.devices()[:2])
    cfg = moe_config(E=16, k=2, moe_dispatch="ragged")
    params = llama.quantize_params(
        llama.init_params(cfg, jax.random.PRNGKey(0)), "sym_int4"
    )
    sharded = shard_params(params, param_specs(cfg), mesh)
    tokens = jnp.ones((2, 8), jnp.int32)

    with jax.set_mesh(mesh):
        logits = jax.jit(
            lambda p, t: llama.forward(cfg, p, t, None, mode="prefill")[0]
        )(sharded, tokens)
        assert bool(jnp.all(jnp.isfinite(logits)))
    # and the sharded result matches the unsharded one
    ref = jax.jit(
        lambda p, t: llama.forward(cfg, p, t, None, mode="prefill")[0]
    )(params, tokens)
    np.testing.assert_allclose(
        np.asarray(logits, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2,
    )
