"""bench/weights: synth_params' tree, made on the device in one call."""

import os
import sys

import jax
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import weights  # noqa: E402
from bigdl_tpu.models.config import ModelConfig  # noqa: E402
from bigdl_tpu.quant.synth import synth_params  # noqa: E402

TINY = dict(model_type="mistral", vocab_size=512, hidden_size=256,
            intermediate_size=512, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, sliding_window=64)


def _cfg(**kw):
    return ModelConfig.from_hf_config(dict(TINY, **kw))


def test_tree_is_synth_params_tree():
    cfg = _cfg()
    ours = weights.make_params(cfg, 5)
    theirs = synth_params(cfg, seed=5)
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert a.shape == b.shape and a.dtype == b.dtype


def test_moe_tree_too():
    cfg = _cfg(model_type="mixtral", num_local_experts=8,
               num_experts_per_tok=2, sliding_window=None)
    ours = weights.make_params(cfg, 1)
    assert jax.tree.structure(ours) == jax.tree.structure(synth_params(cfg))
    assert ours["layers"]["w_up_e"].data.dtype == np.uint8


def test_codes_are_zero_mean_and_scales_in_range():
    p = weights.make_params(_cfg(), 2**31 + 3)
    w = p["layers"]["w_gateup"]
    data = np.asarray(w.data)
    lo, hi = data & 0x0F, data >> 4
    assert not np.any(lo == 0) and not np.any(hi == 0)  # code 0 moved to 8
    codes = np.concatenate([lo, hi], -1).astype(np.float64) - 8.0
    assert abs(codes.mean()) < 0.05
    assert abs(codes.std() - weights.CODE_STD) < 0.1
    s = np.asarray(w.scales, np.float64) * weights.CODE_STD / weights.WEIGHT_STD
    assert 0.49 < s.min() < 0.55 and 1.45 < s.max() < 1.51
    assert np.all(np.asarray(p["layers"]["attn_norm"], np.float32) == 1.0)
    assert np.all(np.asarray(p["final_norm"], np.float32) == 1.0)
    e = np.asarray(p["embed"], np.float32)
    assert abs(e.std() - 0.02) < 0.002 and abs(e.mean()) < 0.002


def test_same_seed_same_weights_other_seed_other_weights():
    cfg = _cfg()
    a, b, c = (weights.make_params(cfg, s) for s in (9, 9, 10))
    la, lb, lc = (jax.tree.leaves(t) for t in (a, b, c))
    assert all(np.array_equal(x, y) for x, y in zip(la, lb))
    assert not np.array_equal(la[0], lc[0])
