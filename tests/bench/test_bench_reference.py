"""bench/reference/mistral.py against the program on tiny shapes, and its
nibble unpack against QTensor.dequantize."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import weights  # noqa: E402
from bench.reference import mistral as ref  # noqa: E402
from bigdl_tpu.models import llama  # noqa: E402
from bigdl_tpu.models.config import ModelConfig  # noqa: E402
from bigdl_tpu.quant import quantize  # noqa: E402

MISTRAL = dict(model_type="mistral", vocab_size=512, hidden_size=256,
               intermediate_size=512, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2,
               rms_norm_eps=1e-5, rope_theta=10000.0, sliding_window=16)
MIXTRAL = dict(MISTRAL, model_type="mixtral", rope_theta=1e6,
               sliding_window=None, num_local_experts=8,
               num_experts_per_tok=2)
QWEN2 = dict(MISTRAL, model_type="qwen2", rope_theta=1e6, rms_norm_eps=1e-6,
             sliding_window=None, num_attention_heads=8,
             num_key_value_heads=2)  # a q/k/v bias, 4 query heads to a KV head


def test_unpack_is_qtensor_dequantize():
    x = jax.random.normal(jax.random.PRNGKey(0), (24, 128), jnp.float32)
    q = quantize(x, "sym_int4")
    ours = ref.unpack_sym_int4(q.data, q.scales)
    np.testing.assert_array_equal(np.asarray(ours),
                                  np.asarray(q.dequantize(jnp.float32)))
    assert float(jnp.max(jnp.abs(ours - x))) < 0.5  # and it is x, roughly


@pytest.mark.parametrize("hf", [MISTRAL, MIXTRAL, QWEN2],
                         ids=["mistral-window", "mixtral-top2", "qwen2-bias"])
def test_reference_agrees_with_the_programs_float32_forward(hf):
    cfg = ModelConfig.from_hf_config(hf)
    params = weights.make_params(cfg, 3)
    assert ("bqkv" in params["layers"]) == (hf["model_type"] == "qwen2")
    toks = jnp.asarray(np.random.default_rng(0).integers(1, 512, 40),
                       jnp.int32)
    want = ref.logits(hf, params, toks, 6)
    with jax.default_matmul_precision("highest"):
        got, _ = llama.forward(cfg, params, toks[None], None,
                               compute_dtype=jnp.float32)
    err = float(jnp.linalg.norm(got[0, -6:] - want) / jnp.linalg.norm(want))
    assert want.shape == (6, 512) and err < 1e-5, err


def test_the_window_binds_in_the_reference():
    toks = jnp.asarray(np.random.default_rng(1).integers(1, 512, 40),
                       jnp.int32)
    cfg = ModelConfig.from_hf_config(MISTRAL)
    params = weights.make_params(cfg, 4)
    a = ref.logits(MISTRAL, params, toks, 1)
    b = ref.logits(dict(MISTRAL, sliding_window=None), params, toks, 1)
    assert float(jnp.max(jnp.abs(a - b))) > 1e-4
