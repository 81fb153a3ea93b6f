"""Pallas kernel `power_retention_decode`: one token's update of a
power-retention layer's state, IN PLACE, one pass over HBM.

`kvstate.py` has the mathematics and the layout. Per live batch row b and
KV head h the kernel reads that row's `S [D, P]` and `z [1, P]` of one
layer once, in chunks of the phi axis, and for each chunk

    S <- e^g S + v phi_k(k)^T       (a column of v across, a row of phi_k(k) down)
    z <- e^g z + phi_k(k)
    num += phi_q(q) S^T            ([G, chunk] x [D, chunk]^T on the MXU)
    den += phi_q(q) . z

and writes the chunk back where it came from (`input_output_aliases`: the
whole `[L, R, Hkv, D, P]` pool goes in and comes out as the same buffer,
found by the scalar-prefetched layer and row, so neither a layer's slice
nor a row is ever copied). The group's queries (5 at Brumby's sizes, padded
to 8 sublanes) ride in the same pass. The pair products phi_q(q) and
phi_k(k) of a chunk are made HERE, one lane rotation and one multiply per
diagonal of the chunk (kvstate: lane (d, i) is x_i x_{(i + d) mod D}), so
nothing of size P ever crosses HBM but the state itself; q, k, v and the
gate's e^g arrive as they are (a few KB a row), and the quotient num /
(den + eps) is taken outside on `[B, Hq, D]` numbers.

float32 state through a bfloat16 MXU, in two passes. phi_q(q) is the bare
pair products of a bfloat16 q: exact in 16 bits, so its bfloat16 head and
remainder hold it whole, and the two ride STACKED as 16 rows of one operand.
S is split into a head and a remainder too (what is left of it is below
2**-17 of S), and each half takes one matmul against the stacked queries:
all four cross products in two passes, float32 sums. A single bfloat16 pass
would not do: the weight (q . k)**2 is a sum of pair products that cancel,
and at 8 bits the error is a quarter of a typical weight.

An idle row costs nothing: its grid steps name the block a NEIGHBOURING
live step names (the one before it, else the first live row's first), the
pipeline holds that block already and issues no DMA, and the body is
skipped; its outputs are zeros. Every grid axis is sequential
("arbitrary"): a block that several consecutive steps name must be written
back once, after the live step among them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_GROUP_PAD = 8  # query heads of a group, padded to whole sublanes
_BLOCK_BYTES = 1 << 20  # at most this much of S in one block


def phi_chunk(head_dim: int, n_phi: int) -> int:
    """Lanes of one block of the phi axis: whole diagonals (`head_dim`
    lanes each), a divisor of their count, as large as `_BLOCK_BYTES`
    allows."""
    n_diag = n_phi // head_dim
    fits = [d for d in range(1, n_diag + 1) if n_diag % d == 0
            and d * head_dim * head_dim * 4 <= _BLOCK_BYTES]
    return max(fits, default=1) * head_dim


def _split(x):
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _pairs(x, first_diag, n_diag: int, weighted: bool):
    """[rows, D] -> [rows, n_diag * D]: the pair products of diagonals
    `first_diag` .. of each row, with `kvstate.phi_k`'s weights or
    `phi_q`'s none; lanes past D / 2 of diagonal D / 2 are zero."""
    D = x.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    parts = []
    for j in range(n_diag):
        d = first_diag + j
        # lane i of roll(x, D - d) is x[(i + d) mod D]
        prod = x * pltpu.roll(x, jax.lax.rem(D - d, D), 1)
        if weighted:
            prod = prod * jnp.where(d == 0, 1.0 / D, 2.0 / D)
        parts.append(jnp.where((d < D // 2) | (lane < D // 2), prod, 0.0))
    return jnp.concatenate(parts, axis=1)


def _kernel(meta_ref, q_ref, k_ref, v_ref, dec_ref, s_ref, z_ref,
            s_out, z_out, num_ref, den_ref, *, n_diag: int):
    b, h, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    live = meta_ref[2 + b] == 1

    @pl.when(c == 0)
    def _zero():
        num_ref[...] = jnp.zeros_like(num_ref)
        den_ref[...] = jnp.zeros_like(den_ref)

    @pl.when(live)
    def _update():
        dec = dec_ref[...]  # [D, 1], the same number in every row
        pk = _pairs(k_ref[...], c * n_diag, n_diag, True)[:1]  # [1, chunk]
        S = dec * s_ref[...] + v_ref[...] * pk  # [D, chunk]
        s_out[...] = S
        z = dec[:1] * z_ref[...] + pk  # [1, chunk]
        z_out[...] = z
        pq = _pairs(q_ref[...], c * n_diag, n_diag, False)  # [G, chunk]
        den_ref[...] += jnp.sum(pq * z, axis=1, keepdims=True)
        s_hi, s_lo = _split(S)
        q_both = jnp.concatenate(_split(pq), axis=0)  # [2 G, chunk]

        def dot(a):  # [2 G, chunk] x [D, chunk]^T -> [2 G, D]
            return jax.lax.dot_general(
                q_both, a, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)

        both = dot(s_hi) + dot(s_lo)
        half = both.shape[0] // 2
        num_ref[...] += both[:half] + both[half:]

    # with no live row at all every step names block (0, 0, 0) and none
    # fills it: hand back what came in
    @pl.when((meta_ref[1] == 0) & (b == 0) & (h == 0) & (c == 0))
    def _untouched():
        s_out[...] = s_ref[...]
        z_out[...] = z_ref[...]


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def power_retention_decode(
    S: jax.Array,  # [L, R, Hkv, D, P] float32, the whole pool
    z: jax.Array,  # [L, R, Hkv, 1, P] float32
    layer: jax.Array,  # scalar int32
    rows: jax.Array,  # [B] int32 state row of each batch row
    live: jax.Array,  # [B] bool: rows that hold one
    q: jax.Array,  # [B, Hkv, G, D] rotated queries of the token
    k: jax.Array,  # [B, Hkv, D]
    v: jax.Array,  # [B, Hkv, D]
    g: jax.Array,  # [B, Hkv] float32 log-gates
    eps: float = 1e-6,
    interpret: bool | None = None,
):
    """Returns (y [B, Hkv, G, D] float32, S, z): the token's output and the
    pool with the live rows of layer `layer` updated in place. An idle
    row's y is zeros and its state is not touched."""
    from bigdl_tpu.ops.pallas import interpret_mode

    if interpret is None:
        interpret = interpret_mode()
    B, Hkv, G, D = q.shape
    P = S.shape[-1]
    chunk = phi_chunk(D, P)
    n_chunks = P // chunk
    Gp = -(-G // _GROUP_PAD) * _GROUP_PAD

    qf = jnp.pad(q.astype(jnp.float32),
                 ((0, 0), (0, 0), (0, Gp - G), (0, 0)))  # [B, Hkv, Gp, D]
    kf = jnp.broadcast_to(k.astype(jnp.float32)[:, :, None],
                          (B, Hkv, _GROUP_PAD, D))  # whole sublanes
    vcol = v.astype(jnp.float32)[..., None]  # [B, Hkv, D, 1]
    dec = jnp.broadcast_to(jnp.exp(g)[..., None, None], vcol.shape)

    # where an idle row's steps point: the live row before it (its LAST
    # block), else the first live row (its FIRST block)
    idx = jnp.arange(B, dtype=jnp.int32)
    prev = jax.lax.cummax(jnp.where(live, idx, -1), axis=0)
    nxt = jax.lax.cummin(jnp.where(live, idx, B), axis=0, reverse=True)
    first = prev < 0  # no live row before this one
    at = jnp.where(first, jnp.where(nxt < B, nxt, 0), prev)
    meta = jnp.concatenate([
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        jnp.sum(live, dtype=jnp.int32)[None],
        live.astype(jnp.int32),
        jnp.maximum(rows[at], 0).astype(jnp.int32),
        first.astype(jnp.int32),
    ])

    def state_block(b, h, c, m):
        idle_h = jnp.where(m[2 + 2 * B + b] == 1, 0, Hkv - 1)
        idle_c = jnp.where(m[2 + 2 * B + b] == 1, 0, n_chunks - 1)
        on = m[2 + b] == 1
        return (m[0], m[2 + B + b], jnp.where(on, h, idle_h), 0,
                jnp.where(on, c, idle_c))

    def per_row(b, h, c, m):  # an idle row asks for one block, once
        return (b, jnp.where(m[2 + b] == 1, h, 0), 0, 0)

    s_spec = pl.BlockSpec((None, None, None, D, chunk), state_block)
    z_spec = pl.BlockSpec((None, None, None, 1, chunk), state_block)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, n_chunks),
        in_specs=[
            pl.BlockSpec((None, None, Gp, D), per_row),
            pl.BlockSpec((None, None, _GROUP_PAD, D), per_row),
            pl.BlockSpec((None, None, D, 1), per_row),
            pl.BlockSpec((None, None, D, 1), per_row),
            s_spec, z_spec,
        ],
        out_specs=[
            s_spec, z_spec,
            pl.BlockSpec((None, None, Gp, D), lambda b, h, c, m: (b, h, 0, 0)),
            pl.BlockSpec((None, None, Gp, 1), lambda b, h, c, m: (b, h, 0, 0)),
        ],
    )
    S, z, num, den = pl.pallas_call(
        functools.partial(_kernel, n_diag=chunk // D),
        name="power_retention_decode",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(S.shape, S.dtype),
            jax.ShapeDtypeStruct(z.shape, z.dtype),
            jax.ShapeDtypeStruct((B, Hkv, Gp, D), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, Gp, 1), jnp.float32),
        ],
        # operands count from the scalar-prefetched one: S is 5, z is 6
        input_output_aliases={5: 0, 6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(meta, qf, kf, vcol, dec, S, z)
    y = num[:, :, :G] / (den[:, :, :G] + eps)
    return y, S, z
