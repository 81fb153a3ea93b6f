"""Scaled dot-product attention with GQA.

Equivalent of the reference's `scaled_dot_product_attention` dispatch
(models/common.py:222-270) over the `xe_addons.sdp / sdp_causal /
sdp_fp8*` fused kernels. Here one jnp implementation covers all mask
shapes (XLA fuses it well on TPU); a Pallas flash-attention kernel is
planned as the long-sequence prefill fast path.

Softmax is computed in float32 (the reference kernels likewise accumulate
at higher precision).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    softcap: Optional[float] = None,
) -> jax.Array:
    """q [B,T,Hq,D]; k,v [B,S,Hkv,D]; mask broadcastable to [B,Hkv,G,T,S]
    (bool: True = attend). Returns [B,T,Hq,D] in q.dtype.

    Hq must be a multiple of Hkv (grouped-query attention); kv heads are
    never materialized repeated — the grouping happens in the einsum.
    """
    b, t, hq, d = q.shape
    _, s, hkv, _ = k.shape
    assert hq % hkv == 0, f"GQA needs Hq % Hkv == 0, got {hq} % {hkv}"
    g = hq // hkv
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    qg = q.reshape(b, t, hkv, g, d)
    scores = jnp.einsum(
        "bthgd,bshd->bhgts", qg, k, preferred_element_type=jnp.float32
    )
    scores = scores.astype(jnp.float32) * scale
    if softcap is not None:
        scores = jnp.tanh(scores / softcap) * softcap
    if mask is not None:
        if mask.dtype == jnp.bool_:
            scores = jnp.where(mask, scores, _NEG_INF)
        else:
            scores = scores + mask.astype(jnp.float32)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bhgts,bshd->bthgd", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, t, hq, d).astype(q.dtype)


def causal_mask(t: int, s: int, offset: int = 0) -> jax.Array:
    """[T, S] bool mask: query i attends kv j iff j <= i + offset."""
    qi = jnp.arange(t)[:, None] + offset
    kj = jnp.arange(s)[None, :]
    return kj <= qi


# ---------------------------------------------------------------------------
# lane pairs: KV heads of half a lane tile, two to a row of 128 lanes
# ---------------------------------------------------------------------------

def lane_pairs(n_kv: int, head_dim: int, itemsize: int = 2) -> bool:
    """Whether a pool of `n_kv` KV heads of `head_dim` is kept as LANE
    PAIRS, `[.., n_kv // 2, 2 * head_dim]`: a head of 64 fills half a tile
    of 128 lanes, so XLA stores `[.., n_kv, 64]` with every head padded to
    128 (the pool doubles, and no DMA of the paged kernel can slice a page
    out of it). Row p of a slot holds heads 2p and 2p + 1 side by side, head
    2p on lanes 0..63: a reshape of `[.., n_kv, 64]` in row-major order, in
    whole tiles wherever `n_kv // 2` wide heads are
    (`paged_attention.pool_tiles_whole`)."""
    from bigdl_tpu.ops.pallas.paged_attention import pool_tiles_whole

    return (head_dim == 64 and n_kv % 2 == 0
            and pool_tiles_whole(n_kv // 2, 2 * head_dim, itemsize))


def _half_of(n_q: int, n_kv: int) -> jax.Array:
    """[Hq, 1]: which half of its pair's lanes query head h's KV head is."""
    return ((jnp.arange(n_q) // (n_q // n_kv)) % 2)[:, None]


def pair_queries(q: jax.Array, n_kv: int) -> jax.Array:
    """q [..., Hq, D] -> [..., Hq, 2 D] for attention over lane pairs:
    query head h (KV head j = h // G) keeps its D values on the lanes of
    its own half, D (j % 2) .., and zeros on the other's, so that a score
    against the pair's row is `q_h . k_j` exactly. Grouped over `n_kv // 2`
    wide heads the rows fall right as they stand: the 2 G query heads of a
    pair are consecutive."""
    zeros = jnp.zeros_like(q)
    return jnp.where(_half_of(q.shape[-2], n_kv) == 0,
                     jnp.concatenate([q, zeros], axis=-1),
                     jnp.concatenate([zeros, q], axis=-1))


def unpair_context(out: jax.Array, n_kv: int) -> jax.Array:
    """[..., Hq, 2 D] -> [..., Hq, D]: the half of each context row that is
    its own KV head's values; the other half (the pair's other head under
    the same weights) is thrown away."""
    D = out.shape[-1] // 2
    return jnp.where(_half_of(out.shape[-2], n_kv) == 0,
                     out[..., :D], out[..., D:])
