"""Pallas kernels `mamba1_decode` and `mamba1_prefill`: the selective scan of
a Mamba-1 layer over a state that lies in the pool, IN PLACE.

`kvhybrid.py` has the recurrence. The decay differs by channel d AND state
index n, so there is no head and no matrix product in it:

    h[n, d] <- exp(dt[d] * A[n, d]) * h[n, d] + (dt[d] * x[d]) * B[n]
    y[d]     = sum_n C[n] * h[n, d]

The pool of one model is `ssm [Lm, R, N, E]` float32: the E channels on
lanes, the N state indices on sublanes (N = 16 and E = 5120: two sublane
tiles by forty lane tiles a row and layer, whole tiles). One body (`_token`)
serves both call shapes, on `[N, LANES]` pieces of the state: `dt * A`, the
`exp` and the multiply-adds are the VPU's and the EUP's, the readout a sum
down the sublanes; nothing goes to the MXU. `exp(dt * A)` is formed here
from `dt [1, E]` and `A [N, E]`: no `[B, N, E]` or `[T, N, E]` array exists
outside the pool.

* `mamba1_decode`: T = 1 for every LIVE batch row. The grid walks the batch
  rows live rows first (the order is scalar-prefetched, as `mamba2_decode`'s);
  a row and layer's `[N, E]` is read once and written once where it lay
  (`input_output_aliases`: the whole pool goes in and comes out as the same
  buffer); every step past the last live one names the block that step left,
  so an idle row moves nothing and its y is zeros.
* `mamba1_prefill`: ONE row, T tokens. The grid walks blocks of `TOKENS`
  tokens; the row's state stays in VMEM across them (its block index does not
  change), from zero where `fresh`, else from the row's own; x, dt, B, C
  stream in a block at a time and y streams out. A token past `n_valid`
  carries dt = 0 (its caller's rule): no decay, no update; a block wholly
  past it is skipped.

B and C, `[N]` a token, arrive as ONE array `[.., N, 128]` with B in lane 0
and C in lane 1: a column down the sublanes, which the kernel spreads over
the lanes (an `[.., N, 1]` array would be stored as whole tiles all the
same; the two share one).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 512  # channels of one piece of the state: [16, 512] is 8 registers
TOKENS = 128  # tokens of one block of a prefill
_GROUP = 8  # tokens read and written together: whole sublanes
_COLS = 128  # lanes of the array that carries B and C


def lanes_of(inner: int) -> int:
    """Channels of one piece: whole lane tiles, a divisor of `inner`."""
    for w in (LANES, 256, 128):
        if inner % w == 0:
            return w
    raise ValueError(f"inner width {inner} is not whole lane tiles of 128")


def _token(h, a, x, dt, b, c):
    """One token of one piece. h, a [N, W]; x, dt [1, W]; b, c [N, 1].
    Returns (h, y [1, W] without the D term)."""
    h = jnp.exp(dt * a) * h + (dt * x) * b
    return h, jnp.sum(h * c, axis=0, keepdims=True)


def _columns(bm, cm):
    """B, C [.., N] -> [.., N, 128] float32: B in lane 0, C in lane 1."""
    bc = jnp.stack([bm, cm], axis=-1).astype(jnp.float32)
    return jnp.pad(bc, ((0, 0),) * (bc.ndim - 1) + ((0, _COLS - 2),))


def _decode_kernel(meta_ref, x_ref, dt_ref, bc_ref, a_ref, s_ref, s_out,
                   y_ref, *, width: int):
    i = pl.program_id(0)
    live = i < meta_ref[1]

    @pl.when(live)
    def _update():
        b, c = bc_ref[:, 0:1], bc_ref[:, 1:2]
        for lo in range(0, s_ref.shape[-1], width):
            at = pl.ds(lo, width)
            h, y = _token(s_ref[:, at], a_ref[:, at], x_ref[:, at],
                          dt_ref[:, at], b, c)
            s_out[:, at] = h
            y_ref[:, at] = y

    @pl.when(jnp.logical_not(live))
    def _idle():
        y_ref[...] = jnp.zeros_like(y_ref)

    # with no live row at all every step names one block and none fills
    # it: hand back what came in
    @pl.when((meta_ref[1] == 0) & (i == 0))
    def _untouched():
        s_out[...] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def mamba1_decode(
    ssm: jax.Array,  # [Lm, R, N, E] float32, the whole pool
    layer: jax.Array,  # scalar int32
    rows: jax.Array,  # [B] int32 state row of each batch row
    live: jax.Array,  # [B] bool: rows that hold one
    x: jax.Array,  # [B, E] float32: the token after conv and silu
    dt: jax.Array,  # [B, E] float32 step sizes (after the softplus)
    A: jax.Array,  # [N, E] float32, negative
    Bm: jax.Array,  # [B, N] float32
    Cm: jax.Array,  # [B, N] float32
    interpret: bool | None = None,
):
    """Returns (y [B, E] float32 without the D term, ssm): the token's
    output and the pool with the live rows of layer `layer` updated in
    place. An idle row's y is zeros and its state is not touched."""
    from bigdl_tpu.ops.pallas import interpret_mode

    if interpret is None:
        interpret = interpret_mode()
    B, E = x.shape
    N = ssm.shape[-2]
    width = lanes_of(E)

    # batch rows, live ones first; a step past the last live one stays on
    # that one's state row
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    n_live = jnp.sum(live, dtype=jnp.int32)
    step = jnp.minimum(jnp.arange(B, dtype=jnp.int32),
                       jnp.maximum(n_live - 1, 0))
    meta = jnp.concatenate([
        jnp.reshape(layer, (1,)).astype(jnp.int32), n_live[None], order,
        jnp.maximum(rows.astype(jnp.int32)[order[step]], 0)])

    def state(i, m):
        return (m[0], m[2 + B + i], 0, 0)

    def per_row(i, m):
        return (m[2 + i], 0, 0)

    s_spec = pl.BlockSpec((None, None, N, E), state)
    row = pl.BlockSpec((None, 1, E), per_row)
    ssm, y = pl.pallas_call(
        functools.partial(_decode_kernel, width=width),
        name="mamba1_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B,),
            in_specs=[row, row, pl.BlockSpec((None, N, _COLS), per_row),
                      pl.BlockSpec((N, E), lambda i, m: (0, 0)), s_spec],
            out_specs=[s_spec, row]),
        out_shape=[jax.ShapeDtypeStruct(ssm.shape, ssm.dtype),
                   jax.ShapeDtypeStruct((B, 1, E), jnp.float32)],
        # operands count from the scalar-prefetched one: ssm is 5
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(meta, x.astype(jnp.float32)[:, None], dt.astype(jnp.float32)[:, None],
      _columns(Bm, Cm), A.astype(jnp.float32), ssm)
    return y[:, 0], ssm


def _prefill_kernel(meta_ref, x_ref, dt_ref, bc_ref, a_ref, s_ref, s_out,
                    y_ref, *, width: int):
    j = pl.program_id(0)
    tokens = x_ref.shape[0]

    @pl.when(j == 0)
    def _start():  # from nothing, or from what the row holds
        @pl.when(meta_ref[2] != 0)
        def _():
            s_out[...] = jnp.zeros_like(s_out)

        @pl.when(meta_ref[2] == 0)
        def _():
            s_out[...] = s_ref[...]

    some = j * tokens < meta_ref[3]

    @pl.when(some)
    def _scan():
        for lo in range(0, s_out.shape[-1], width):
            at = pl.ds(lo, width)
            a = a_ref[:, at]

            def group(g, h):
                t0 = pl.multiple_of(g * _GROUP, _GROUP)
                rows = pl.ds(t0, _GROUP)
                xs, dts = x_ref[rows, at], dt_ref[rows, at]
                ys = []
                for k in range(_GROUP):
                    bc = bc_ref[t0 + k]
                    h, y = _token(h, a, xs[k:k + 1], dts[k:k + 1],
                                  bc[:, 0:1], bc[:, 1:2])
                    ys.append(y)
                y_ref[rows, at] = jnp.concatenate(ys, axis=0)
                return h

            s_out[:, at] = jax.lax.fori_loop(0, tokens // _GROUP, group,
                                             s_out[:, at])

    @pl.when(jnp.logical_not(some))
    def _past():
        y_ref[...] = jnp.zeros_like(y_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def mamba1_prefill(
    ssm: jax.Array,  # [Lm, R, N, E] float32, the whole pool
    layer: jax.Array,  # scalar int32
    row: jax.Array,  # scalar int32: the state row
    fresh: jax.Array,  # scalar bool: start from zero, not from the row
    n_valid: jax.Array,  # scalar int32: the first `n_valid` of T are tokens
    x: jax.Array,  # [T, E] float32: the tokens after conv and silu
    dt: jax.Array,  # [T, E] float32 step sizes, 0 past `n_valid`
    A: jax.Array,  # [N, E] float32, negative
    Bm: jax.Array,  # [T, N] float32
    Cm: jax.Array,  # [T, N] float32
    interpret: bool | None = None,
):
    """Returns (y [T, E] float32 without the D term, ssm): the tokens'
    outputs and the pool with row `row` of layer `layer` after them."""
    from bigdl_tpu.ops.pallas import interpret_mode

    if interpret is None:
        interpret = interpret_mode()
    T, E = x.shape
    N = ssm.shape[-2]
    tokens = min(TOKENS, -(-T // _GROUP) * _GROUP)
    pad = -T % tokens
    if pad:  # dt = 0: a padded position neither decays nor updates
        x, dt, Bm, Cm = (jnp.pad(a, ((0, pad), (0, 0)))
                         for a in (x, dt, Bm, Cm))
    meta = jnp.stack([layer, row, fresh, n_valid]).astype(jnp.int32)

    def state(j, m):
        return (m[0], m[1], 0, 0)

    s_spec = pl.BlockSpec((None, None, N, E), state)
    tok = pl.BlockSpec((tokens, E), lambda j, m: (j, 0))
    ssm, y = pl.pallas_call(
        functools.partial(_prefill_kernel, width=lanes_of(E)),
        name="mamba1_prefill",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=((T + pad) // tokens,),
            in_specs=[tok, tok,
                      pl.BlockSpec((tokens, N, _COLS),
                                   lambda j, m: (j, 0, 0)),
                      pl.BlockSpec((N, E), lambda j, m: (0, 0)), s_spec],
            out_specs=[s_spec, tok]),
        out_shape=[jax.ShapeDtypeStruct(ssm.shape, ssm.dtype),
                   jax.ShapeDtypeStruct((T + pad, E), jnp.float32)],
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(meta, x.astype(jnp.float32), dt.astype(jnp.float32), _columns(Bm, Cm),
      A.astype(jnp.float32), ssm)
    return y[:T], ssm
