"""What an engine program packs for the host: one int32 row a slot, fetched
in one transfer. Shared by the engine's own programs (serving/engine.py) and
the pass over blocks (serving/blocks.py)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def bits(x):
    """A float32 array's bits as int32: how a logprob rides in the one
    int32 vector a program packs for the host."""
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def expert_id_dtype(n_experts: int):
    """The small integer type a step's expert ids are kept in."""
    return np.int8 if n_experts <= 127 else np.int16
