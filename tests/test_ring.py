"""Ring attention vs dense attention on the 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.ops import attention
from bigdl_tpu.ops.attention import causal_mask
from bigdl_tpu.parallel import make_mesh
from bigdl_tpu.parallel.ring import make_ring_attention, ring_attention


@pytest.fixture(scope="module")
def sp_mesh():
    return make_mesh((1, 8, 1))


def _qkv(rng, B=2, T=64, Hq=4, Hkv=2, D=16):
    q = jnp.asarray(rng.standard_normal((B, T, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, T, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, T, Hkv, D)), jnp.float32)
    return q, k, v


def test_ring_matches_dense_causal(rng, sp_mesh):
    q, k, v = _qkv(rng)
    T = q.shape[1]
    mask = causal_mask(T, T)[None, None, None]
    dense = attention(q, k, v, mask)
    ring = make_ring_attention(sp_mesh)(q, k, v)
    np.testing.assert_allclose(
        np.asarray(ring), np.asarray(dense), rtol=2e-5, atol=2e-5
    )


def test_ring_matches_dense_full(rng, sp_mesh):
    q, k, v = _qkv(rng, T=32)
    dense = attention(q, k, v, None)
    ring = make_ring_attention(sp_mesh, causal=False)(q, k, v)
    np.testing.assert_allclose(
        np.asarray(ring), np.asarray(dense), rtol=2e-5, atol=2e-5
    )


def test_ring_gqa_grouping(rng, sp_mesh):
    """Hq=8, Hkv=2: group mapping must match the dense einsum path."""
    q, k, v = _qkv(rng, T=16, Hq=8, Hkv=2)
    T = q.shape[1]
    mask = causal_mask(T, T)[None, None, None]
    dense = attention(q, k, v, mask)
    ring = make_ring_attention(sp_mesh)(q, k, v)
    np.testing.assert_allclose(
        np.asarray(ring), np.asarray(dense), rtol=2e-5, atol=2e-5
    )


def test_train_step_with_ring_matches_dense(rng):
    """QLoRA loss with ring attention == loss with plain attention on the
    same (dp, sp, tp) mesh."""
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from bigdl_tpu.models import llama
    from bigdl_tpu.models.config import ModelConfig
    from bigdl_tpu.parallel import shard_params
    from bigdl_tpu.parallel.sharding import param_specs
    from bigdl_tpu.train import init_lora, make_train_step

    mesh = make_mesh((2, 2, 2))
    config = ModelConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=128,
    )
    params = llama.quantize_params(
        llama.init_params(config, jax.random.PRNGKey(0)), "sym_int4"
    )
    lora = init_lora(config, jax.random.PRNGKey(1), rank=4)
    params = shard_params(params, param_specs(config), mesh)
    optimizer = optax.sgd(1e-3)
    opt_state = optimizer.init(lora["layers"])

    B, T = 4, 33  # model sees 32 tokens → 16 per sp shard
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 128, (B, T)), jnp.int32
    )
    tokens = jax.device_put(tokens, NamedSharding(mesh, P("dp", None)))
    mask = jnp.ones((B, T), jnp.float32)


    with jax.set_mesh(mesh):
        plain = make_train_step(config, llama.forward, optimizer, P("dp", "sp"))
        ringd = make_train_step(
            config, llama.forward, optimizer, P("dp", "sp"), ring_mesh=mesh
        )
        _, _, loss_plain = jax.jit(plain)(params, lora, opt_state, tokens, mask)
        _, _, loss_ring = jax.jit(ringd)(params, lora, opt_state, tokens, mask)
    np.testing.assert_allclose(
        float(loss_ring), float(loss_plain), rtol=2e-4, atol=2e-4
    )


def test_ring_with_left_padding(rng, sp_mesh):
    """start[b] masks pad slots globally across ring hops."""
    from functools import partial

    from jax.sharding import PartitionSpec as P

    q, k, v = _qkv(rng, B=2, T=32)
    start = jnp.asarray([8, 0], jnp.int32)
    T = q.shape[1]
    tj = jnp.arange(T)
    mask = (tj[None, :] <= tj[:, None])[None] & (
        tj[None, None, :] >= start[:, None, None]
    )
    dense = attention(q, k, v, mask[:, None, None])

    seq = P(None, "sp", None, None)
    ring_fn = partial(
        ring_attention, axis_name="sp", axis_size=8, causal=True, start=start
    )

    sharded = jax.shard_map(
        lambda a, b, c: ring_fn(a, b, c),
        mesh=sp_mesh, in_specs=(seq, seq, seq), out_specs=seq,
        check_vma=False,
    )
    ring = sharded(q, k, v)
    # fully-masked (pad) query rows: dense softmaxes uniform garbage, ring
    # zeroes — compare only valid rows
    np.testing.assert_allclose(
        np.asarray(ring)[0, 8:], np.asarray(dense)[0, 8:], rtol=2e-5, atol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(ring)[1], np.asarray(dense)[1], rtol=2e-5, atol=2e-5
    )


def test_host_aware_mesh_layout():
    """tp stays within a simulated host's device block; oversubscription
    raises with the DCN warning."""
    import pytest

    from bigdl_tpu.parallel.multihost import host_aware_mesh

    devs = jax.devices()[:8]
    # simulate 2 hosts x 4 local devices
    mesh = host_aware_mesh(tp=4, dp=2, devices=devs, local_devices=4)
    assert mesh.axis_names == ("dp", "pp", "sp", "tp")
    assert mesh.devices.shape == (2, 1, 1, 4)
    # each tp row must be one host's contiguous block
    row0 = mesh.devices[0, 0, 0, :].tolist()
    assert row0 == devs[:4]

    with pytest.raises(ValueError, match="DCN"):
        host_aware_mesh(tp=8, devices=devs, local_devices=4)

    # generate on a host-aware mesh stays bit-identical
    from bigdl_tpu.api import TpuModel, optimize_model
    from bigdl_tpu.models import llama
    from bigdl_tpu.models.config import PRESETS

    cfg = PRESETS["tiny-llama"]
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    m = TpuModel(cfg, optimize_model(params, cfg), "sym_int4")
    single = m.generate([[1, 2, 3, 4]], max_new_tokens=6)
    sharded = m.to_mesh(mesh=host_aware_mesh(tp=2, dp=4, devices=devs,
                                             local_devices=4))
    np.testing.assert_array_equal(single, sharded.generate([[1, 2, 3, 4]],
                                                           max_new_tokens=6))


def test_init_multihost_guards(monkeypatch):
    import pytest

    from bigdl_tpu.parallel.multihost import init_multihost

    # partial explicit config fails loudly
    with pytest.raises(ValueError, match="together"):
        init_multihost(process_id=3)
    # no markers, no explicit config: clean no-op on a single host
    for m in ("COORDINATOR_ADDRESS", "TPU_WORKER_HOSTNAMES",
              "MEGASCALE_COORDINATOR_ADDRESS", "CLOUD_TPU_TASK_ID"):
        monkeypatch.delenv(m, raising=False)
    init_multihost()
