"""Ring attention — sequence/context parallelism over the `sp` mesh axis.

The reference has **no** sequence parallelism (SURVEY.md §2.3: its
long-context levers are single-device KV compression/quantization); this
is the TPU-native upgrade that makes long context a first-class scaling
axis: shard the sequence over `sp`, keep every device's attention
working set at 1/n of the sequence, and rotate KV shards around the ring
with `ppermute` so each hop overlaps compute with neighbor ICI traffic
(blockwise/ring attention; PAPERS.md "Ring Attention with Blockwise
Transformers").

`ring_attention` is the device-local function — call it INSIDE
`shard_map` with q/k/v already sharded along the sequence axis. Online
softmax (m, l, acc) accumulates across ring steps exactly like the
Pallas flash kernel accumulates across K blocks, so the result is
bit-comparable to dense attention up to fp32 reduction order.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_NEG_INF = -1e30


def ring_attention(
    q: jax.Array,  # [B, Tl, Hq, D] local query chunk
    k: jax.Array,  # [B, Sl, Hkv, D] local key chunk
    v: jax.Array,  # [B, Sl, Hkv, D]
    axis_name: str = "sp",
    axis_size: Optional[int] = None,  # ring length (static); None = axis size
    causal: bool = True,
    scale: Optional[float] = None,
    start: Optional[jax.Array] = None,  # [B] global left-pad offsets
    comm_qtype: str = "none",  # quantize the rotating k/v payloads
    comm_block_size: int = 256,
) -> jax.Array:
    """Device-local ring attention step (use inside shard_map).

    Chunk layout: device i holds global positions [i*Tl, (i+1)*Tl) of q
    and [i*Sl, (i+1)*Sl) of k/v. Returns the local output chunk
    [B, Tl, Hq, D] in q.dtype.

    `comm_qtype` ("int8"|"fp8_e4m3"; parallel/qcollectives.py) encodes
    each k/v chunk ONCE at entry and rotates the block-quantized
    payload (codes + f16 scales) around the ring instead of the raw
    fp32/bf16 chunks — n-1 hops of ~quarter traffic, one quantization
    event total (no per-hop requantization, so no error feedback is
    needed on this path). Every device decodes the same bytes, so all
    shards attend over identical dequantized k/v.
    """
    B, Tl, Hq, D = q.shape
    _, Sl, Hkv, _ = k.shape
    G = Hq // Hkv
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    if axis_size is None:
        axis_size = jax.lax.psum(1, axis_name)  # concrete under shard_map
    n = int(axis_size)
    me = jax.lax.axis_index(axis_name)

    qf = q.astype(jnp.float32).reshape(B, Tl, Hkv, G, D)
    qf = jnp.moveaxis(qf, 1, 3)  # [B, Hkv, G, Tl, D]
    qpos = me * Tl + jnp.arange(Tl)  # [Tl] global q positions

    m0 = jnp.full((B, Hkv, G, Tl, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Tl, 1), jnp.float32)
    acc0 = jnp.zeros((B, Hkv, G, Tl, D), jnp.float32)
    perm = [(j, (j + 1) % n) for j in range(n)]

    from bigdl_tpu.parallel import qcollectives as qc

    if qc.resolve_comm_qtype(comm_qtype) != "none":
        payload0 = (qc.encode_array(k, comm_qtype, comm_block_size)
                    + qc.encode_array(v, comm_qtype, comm_block_size))

        def materialize(pl):
            kd, ks, vd, vs = pl
            return (
                qc.decode_array(kd, ks, k.shape, jnp.float32,
                                comm_block_size),
                qc.decode_array(vd, vs, v.shape, jnp.float32,
                                comm_block_size),
            )
    else:
        payload0 = (k, v)

        def materialize(pl):
            return pl

    def rotate(pl):
        return tuple(jax.lax.ppermute(a, axis_name, perm) for a in pl)

    def step(carry, i):
        m, l, acc, pl = carry
        # rotate at the TOP of every step after the first — the final
        # step's kv then stays put, saving one k+v ICI hop per call
        pl = jax.lax.cond(i > 0, rotate, lambda p: p, pl)
        kc, vc = materialize(pl)
        src = (me - i) % n  # origin shard of the kv chunk we hold now
        kpos = src * Sl + jnp.arange(Sl)  # [Sl] global k positions

        s = jnp.einsum(
            "bhgtd,bshd->bhgts", qf, kc.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        ) * scale
        valid = jnp.ones((B, 1, 1, Tl, Sl), jnp.bool_)
        if causal:
            valid = valid & (kpos[None, :] <= qpos[:, None])[None, None, None]
        if start is not None:
            valid = valid & (kpos[None, None, None, None, :] >= start[:, None, None, None, None])
        s = jnp.where(valid, s, _NEG_INF)

        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        alpha = jnp.exp(m - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum(
            "bhgts,bshd->bhgtd", p, vc.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        acc_new = acc * alpha + pv
        return (m_new, l_new, acc_new, pl), None

    (m, l, acc, _), _ = jax.lax.scan(
        step, (m0, l0, acc0, payload0), jnp.arange(n)
    )
    out = acc / jnp.where(l == 0.0, 1.0, l)  # [B, Hkv, G, Tl, D]
    out = jnp.moveaxis(out, 3, 1).reshape(B, Tl, Hq, D)
    return out.astype(q.dtype)


def make_ring_attention(mesh: Mesh, axis_name: str = "sp", causal: bool = True,
                        comm_qtype: str = "none"):
    """Whole-array convenience wrapper: shard q/k/v over `axis_name`
    (sequence dim), run ring attention, return the full output. Other mesh
    axes are ignored (inputs replicated over them). `comm_qtype` rotates
    block-quantized k/v payloads (see `ring_attention`)."""
    n = mesh.shape[axis_name]
    seq_spec = P(None, axis_name, None, None)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(seq_spec, seq_spec, seq_spec),
        out_specs=seq_spec,
        check_vma=False,
    )
    def sharded(q, k, v):
        return ring_attention(
            q, k, v, axis_name=axis_name, axis_size=n, causal=causal,
            comm_qtype=comm_qtype,
        )

    def fn(q, k, v):
        sh = NamedSharding(mesh, seq_spec)
        return sharded(
            jax.device_put(q, sh), jax.device_put(k, sh), jax.device_put(v, sh)
        )

    return fn
