"""Pallas kernel `mamba2_decode`: one token's update of a Mamba-2 layer's
state, IN PLACE, one pass over HBM.

`kvhybrid.py` has the recurrence and the layout. The pool of one model is
`ssm [Lm, R, heads * head size, d_state]` float32: a (head, channel) pair on
sublanes, `d_state` = 128 on lanes, whole tiles. Per LIVE batch row the
kernel reads that row's state of one layer once, in blocks of `BLOCK_ROWS`
sublanes (1 MB), and for each 128 rows of a block

    h <- exp(dt A) h + (dt x) (x) B     (a column down the rows, B across)
    y  = h . C                          ([C] x [128, N]^T on the MXU)

and writes the block back where it came from (`input_output_aliases`: the
whole pool goes in and comes out as the same buffer, found by the
scalar-prefetched layer and row, so neither a layer's slice nor a row is
ever copied). The per-row factors arrive as COLUMNS `[128, blocks of 128]`
made outside from a few KB (a `[rows, 1]` array would be stored as whole
tiles, as large as the state itself); B and C arrive as they are. The D term
and everything before and after the recurrence (the convolution, the gate,
the norm) stay outside: they touch no state.

float32 state through a bfloat16 MXU: h and C are each split into a bfloat16
head and remainder and all four cross products are summed in float32 (two
dots against the stacked halves of C), as `power_retention_decode` does; the
update itself is float32 on the VPU.

Idle rows cost nothing: the grid walks the batch rows LIVE ROWS FIRST (the
order is scalar-prefetched); every step after the last live one names the
block that step left, so the pipeline moves nothing, the body is skipped and
the row's y is zeros. Both grid axes are sequential ("arbitrary"): a block
that several consecutive steps name is written back once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 128  # rows of the state handled at a time: one column of factors
BLOCK_ROWS = 2048  # at most this many rows in one block (1 MB at N = 128)
_PAD = 8  # a vector as a matrix: whole sublanes


def block_rows(inner: int) -> int:
    """Rows of one block: whole chunks, a divisor of `inner`, as large as
    `BLOCK_ROWS` allows."""
    if inner % CHUNK:
        raise ValueError(f"inner width {inner} is not whole chunks of {CHUNK}")
    n = inner // CHUNK
    return CHUNK * max(d for d in range(1, BLOCK_ROWS // CHUNK + 1)
                       if n % d == 0)


def _split(x):
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _kernel(meta_ref, d_ref, u_ref, b_ref, c_ref, s_ref, s_out, y_ref, *,
            n_chunks: int, per_chunk: bool = False):
    i, j = pl.program_id(0), pl.program_id(1)
    live = i < meta_ref[1]

    @pl.when(live)
    def _update():
        brow = b_ref[:1, :]  # [1, N]
        # per_chunk (`lightning_decode`): B and C are a chunk's own, row c of
        # [chunks padded to 8, N]; y's row c is then row c of its own dot
        c_both = jnp.concatenate(_split(c_ref[...]), axis=0)  # [2 * 8, N]
        pad = c_ref.shape[0]
        for c in range(n_chunks):
            at = pl.ds(c * CHUNK, CHUNK)
            if per_chunk:
                brow = b_ref[c:c + 1, :]
            h = (d_ref[:, c:c + 1] * s_ref[at, :]
                 + u_ref[:, c:c + 1] * brow)  # [128, N]
            s_out[at, :] = h

            def dot(a):  # [16, N] x [128, N]^T -> [16, 128]
                return jax.lax.dot_general(
                    c_both, a, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)

            both = sum(map(dot, _split(h)))
            r = c if per_chunk else 0
            y_ref[c:c + 1, :] = both[r:r + 1] + both[pad + r:pad + r + 1]

    @pl.when(jnp.logical_not(live))
    def _idle():
        y_ref[...] = jnp.zeros_like(y_ref)

    # with no live row at all every step names one block and none fills
    # it: hand back what came in
    @pl.when((meta_ref[1] == 0) & (i == 0) & (j == 0))
    def _untouched():
        s_out[...] = s_ref[...]


def _live_rows_first(layer, rows, live, n_blocks: int):
    """The grid's walk over the batch rows, LIVE ROWS FIRST: (the
    scalar-prefetched `meta` = [layer, live rows, the batch rows in that
    order, the state row of each step], the state's index map, a per-row
    operand's). A step past the last live one stays on that one's state
    row and asks for one block of a per-row operand, once."""
    B = rows.shape[0]
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    n_live = jnp.sum(live, dtype=jnp.int32)
    step = jnp.minimum(jnp.arange(B, dtype=jnp.int32),
                       jnp.maximum(n_live - 1, 0))
    meta = jnp.concatenate([
        jnp.reshape(layer, (1,)).astype(jnp.int32), n_live[None], order,
        jnp.maximum(rows.astype(jnp.int32)[order[step]], 0)])

    def state_block(i, j, m):
        return (m[0], m[2 + B + i], jnp.where(i < m[1], j, n_blocks - 1), 0)

    def per_row(i, j, m):
        return (m[2 + i], jnp.where(i < m[1], j, 0), 0, 0)

    return meta, state_block, per_row


@functools.partial(jax.jit, static_argnames=("interpret",))
def mamba2_decode(
    ssm: jax.Array,  # [Lm, R, heads * head size, N] float32, the whole pool
    layer: jax.Array,  # scalar int32
    rows: jax.Array,  # [B] int32 state row of each batch row
    live: jax.Array,  # [B] bool: rows that hold one
    x: jax.Array,  # [B, H, P] float32: the token after conv and silu
    dt: jax.Array,  # [B, H] float32 step sizes (after the softplus)
    A: jax.Array,  # [H] float32, negative
    Bm: jax.Array,  # [B, N] float32
    Cm: jax.Array,  # [B, N] float32
    interpret: bool | None = None,
):
    """Returns (y [B, H, P] float32 without the D term, ssm): the token's
    output and the pool with the live rows of layer `layer` updated in
    place. An idle row's y is zeros and its state is not touched."""
    from bigdl_tpu.ops.pallas import interpret_mode

    if interpret is None:
        interpret = interpret_mode()
    B, H, P = x.shape
    inner, N = ssm.shape[-2:]
    blk = block_rows(inner)
    n_blocks, n_chunks = inner // blk, blk // CHUNK

    def columns(a):  # [B, H] a head -> [B, blocks, 128, chunks] a row
        a = jnp.repeat(a.astype(jnp.float32), P, axis=1)
        return jnp.swapaxes(a.reshape(B, n_blocks, n_chunks, CHUNK), 2, 3)

    dec = columns(jnp.exp(dt * A))
    u = jnp.swapaxes((dt[..., None] * x).astype(jnp.float32).reshape(
        B, n_blocks, n_chunks, CHUNK), 2, 3)

    def padded(a):  # [B, N] -> [B, 8, N], the vector in every row
        return jnp.broadcast_to(a.astype(jnp.float32)[:, None], (B, _PAD, N))

    meta, state_block, per_row = _live_rows_first(layer, rows, live,
                                                  n_blocks)

    def vector(i, j, m):
        return (m[2 + i], 0, 0)

    s_spec = pl.BlockSpec((None, None, blk, N), state_block)
    col = pl.BlockSpec((None, None, CHUNK, n_chunks), per_row)
    vec = pl.BlockSpec((None, _PAD, N), vector)
    ssm, y = pl.pallas_call(
        functools.partial(_kernel, n_chunks=n_chunks),
        name="mamba2_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, n_blocks),
            in_specs=[col, col, vec, vec, s_spec],
            out_specs=[
                s_spec,
                pl.BlockSpec((None, None, n_chunks, CHUNK),
                             lambda i, j, m: (m[2 + i], j, 0, 0)),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct(ssm.shape, ssm.dtype),
            jax.ShapeDtypeStruct((B, n_blocks, n_chunks, CHUNK), jnp.float32),
        ],
        # operands count from the scalar-prefetched one: ssm is 5
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(meta, dec, u, padded(Bm), padded(Cm), ssm)
    return y.reshape(B, H, P), ssm


@functools.partial(jax.jit, static_argnames=("interpret",))
def lightning_decode(
    state: jax.Array,  # [Ll, R, heads * head size, N] float32, the whole pool
    layer: jax.Array,  # scalar int32
    rows: jax.Array,  # [B] int32 state row of each batch row
    live: jax.Array,  # [B] bool: rows that hold one
    v: jax.Array,  # [B, H, P] float32: the token's values
    decay: jax.Array,  # [H] float32: a head's decay a token, in (0, 1)
    k: jax.Array,  # [B, H, N] float32: the token's keys, a head's own
    q: jax.Array,  # [B, H, N] float32: its queries (scaled), a head's own
    interpret: bool | None = None,
):
    """`mamba2_decode`'s sibling for lightning attention (kvsparse.py): the
    same pass over the live rows' state in place, with `dt` = 1, the decay
    a constant of the head, and B and C (k and q) A HEAD: a head is one
    chunk of `CHUNK` = P rows, so chunk c of a block reads row c of the
    block's keys and queries. Returns (y [B, H, P] float32, state)."""
    from bigdl_tpu.ops.pallas import interpret_mode

    if interpret is None:
        interpret = interpret_mode()
    B, H, P = v.shape
    inner, N = state.shape[-2:]
    if P != CHUNK:
        raise ValueError(f"a head of {P} values is not a chunk of {CHUNK}")
    blk = block_rows(inner)
    n_blocks, n_chunks = inner // blk, blk // CHUNK
    pad = -(-n_chunks // _PAD) * _PAD

    def columns(a):  # [B, H * P] a row -> [B, blocks, 128, chunks]
        return jnp.swapaxes(a.astype(jnp.float32).reshape(
            B, n_blocks, n_chunks, CHUNK), 2, 3)

    dec = columns(jnp.broadcast_to(jnp.repeat(decay, P)[None], (B, inner)))
    u = columns(v.reshape(B, inner))

    def by_block(a):  # [B, H, N] -> [B, blocks, chunks padded, N]
        a = a.astype(jnp.float32).reshape(B, n_blocks, n_chunks, N)
        return jnp.pad(a, ((0, 0), (0, 0), (0, pad - n_chunks), (0, 0)))

    meta, state_block, per_row = _live_rows_first(layer, rows, live,
                                                  n_blocks)
    s_spec = pl.BlockSpec((None, None, blk, N), state_block)
    col = pl.BlockSpec((None, None, CHUNK, n_chunks), per_row)
    vec = pl.BlockSpec((None, None, pad, N), per_row)
    state, y = pl.pallas_call(
        functools.partial(_kernel, n_chunks=n_chunks, per_chunk=True),
        name="lightning_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, n_blocks),
            in_specs=[col, col, vec, vec, s_spec],
            out_specs=[
                s_spec,
                pl.BlockSpec((None, None, n_chunks, CHUNK),
                             lambda i, j, m: (m[2 + i], j, 0, 0)),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct(state.shape, state.dtype),
            jax.ShapeDtypeStruct((B, n_blocks, n_chunks, CHUNK), jnp.float32),
        ],
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(meta, dec, u, by_block(k), by_block(q), state)
    return y.reshape(B, H, P), state


def _kda_kernel(meta_ref, e_ref, k_ref, q_ref, bv_ref, b_ref, s_ref, s_out,
                y_ref, *, n_chunks: int):
    """One block of heads of one live row: a head is one chunk of `CHUNK`
    value rows by N key lanes. Decay by the head's row of factors, read at
    k (a lane reduction: a column down the value rows), add the corrected
    rank-one update (that column times k's row), read at q. float32 on the
    VPU throughout; the block is read once and written once."""
    i, j = pl.program_id(0), pl.program_id(1)
    live = i < meta_ref[1]

    @pl.when(live)
    def _update():
        for c in range(n_chunks):
            at = pl.ds(c * CHUNK, CHUNK)
            krow = k_ref[c:c + 1, :]  # [1, N]
            s1 = s_ref[at, :] * e_ref[c:c + 1, :]  # [128, N]
            r = jnp.sum(s1 * krow, axis=1, keepdims=True)  # S'^T k: [128, 1]
            h = s1 + (bv_ref[:, c:c + 1] - b_ref[:, c:c + 1] * r) * krow
            s_out[at, :] = h
            y_ref[:, c:c + 1] = jnp.sum(h * q_ref[c:c + 1, :], axis=1,
                                        keepdims=True)

    @pl.when(jnp.logical_not(live))
    def _idle():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when((meta_ref[1] == 0) & (i == 0) & (j == 0))
    def _untouched():
        s_out[...] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_decode(
    state: jax.Array,  # [Lk, R, heads * head size, N] float32, the whole pool
    layer: jax.Array,  # scalar int32
    rows: jax.Array,  # [B] int32 state row of each batch row
    live: jax.Array,  # [B] bool: rows that hold one
    q: jax.Array,  # [B, H, N] float32: the token's queries (normed, scaled)
    k: jax.Array,  # [B, H, N] float32: its keys (normed)
    v: jax.Array,  # [B, H, P] float32: its values
    g: jax.Array,  # [B, H, N] float32 <= 0: the log-decay a key channel
    beta: jax.Array,  # [B, H] float32: the update's strength
    interpret: bool | None = None,
):
    """`lightning_decode`'s sibling for Kimi delta attention (kvhybrid.py):
    the same pass over the live rows' state in place, with the decay a
    VECTOR over a head's key lanes and the state READ (S'^T k) before the
    rank-one update it corrects is written: S' = S * exp(g) row by row,
    S = S' + beta (v - S'^T k) k^T, y = S^T q. Returns (y [B, H, P]
    float32, state). An idle row's y is zeros and its state is not
    touched."""
    from bigdl_tpu.ops.pallas import interpret_mode

    if interpret is None:
        interpret = interpret_mode()
    B, H, P = v.shape
    inner, N = state.shape[-2:]
    if P != CHUNK:
        raise ValueError(f"a head of {P} values is not a chunk of {CHUNK}")
    blk = block_rows(inner)
    n_blocks, n_chunks = inner // blk, blk // CHUNK
    pad = -(-n_chunks // _PAD) * _PAD

    def columns(a):  # [B, H, P] -> [B, blocks, 128, chunks]
        return jnp.swapaxes(a.astype(jnp.float32).reshape(
            B, n_blocks, n_chunks, CHUNK), 2, 3)

    def by_block(a):  # [B, H, N] -> [B, blocks, chunks padded, N]
        a = a.astype(jnp.float32).reshape(B, n_blocks, n_chunks, N)
        return jnp.pad(a, ((0, 0), (0, 0), (0, pad - n_chunks), (0, 0)))

    meta, state_block, per_row = _live_rows_first(layer, rows, live,
                                                  n_blocks)
    s_spec = pl.BlockSpec((None, None, blk, N), state_block)
    col = pl.BlockSpec((None, None, CHUNK, n_chunks), per_row)
    vec = pl.BlockSpec((None, None, pad, N), per_row)
    b_col = jnp.broadcast_to(beta[..., None], v.shape)
    state, y = pl.pallas_call(
        functools.partial(_kda_kernel, n_chunks=n_chunks),
        name="kda_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, n_blocks),
            in_specs=[vec, vec, vec, col, col, s_spec],
            out_specs=[
                s_spec,
                pl.BlockSpec((None, None, CHUNK, n_chunks),
                             lambda i, j, m: (m[2 + i], j, 0, 0)),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct(state.shape, state.dtype),
            jax.ShapeDtypeStruct((B, n_blocks, CHUNK, n_chunks), jnp.float32),
        ],
        # operands count from the scalar-prefetched one: the state is 6
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(meta, by_block(jnp.exp(g)), by_block(k), by_block(q),
      columns(b_col * v), columns(b_col), state)
    return jnp.swapaxes(y, 2, 3).reshape(B, H, P), state
