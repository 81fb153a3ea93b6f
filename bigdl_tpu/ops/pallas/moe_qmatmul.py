"""Pallas fused dequant matmul over a STACK of experts, rows grouped by
expert: the sparse-expert counterpart of `ops/pallas/qmatmul.py`.

    y[r] = x[r] @ dequant(W[expert_of(r)])^T        for every row r

`x` holds the token assignments of one MoE layer sorted by expert
(`moe_layout`): expert e's rows are contiguous and start at a multiple of
`block_m`, so every `block_m`-row tile belongs to exactly one expert and
the kernel is `qmatmul`'s body with one more index: the grid is
(row tiles, O tiles), and the weight block of tile m comes from expert
`tile_expert[m]` of the packed stack `[E, O, K*bits/8]`, read through a
scalar-prefetched table. Nothing here depends on how many rows an expert
got: group sizes live on the device, 0 is allowed, and no assignment is
ever dropped (the layout has room for all of them).

Where ONE tile holds the whole call (`N <= block_m`: every decode step,
`block_m` being the step's rows rounded up to 8) nothing is sorted: a
tile's `block_m` rows go through the MXU whatever they hold, so every hit
expert's tile is the call's rows as they stand, in token order
(`moe_layout_shared`: assignment (n, j) is row n of the tile of expert
`topi[n, j]`), and `x` is `[block_m, K]`, ONE block whose index is (0, 0)
at every grid step, fetched once a call. The sorted form would first write
`[n_tiles * block_m, K]` (8192 rows from a pass's 64) and read it back a
live tile at a time for the same products, bit for bit. `moe_qmatmul`
takes the form from `x`'s shape; the output is in tile layout in both.

What a step pays for:

* the tiles in use come first (`n_used` of the static `n_tiles`); a tile
  past them computes nothing and its block indices name the blocks the
  last live step already holds, so it costs no DMA either (the rule
  `paged_attention.py` uses for dead pages). On the word forms the codes
  are no blocks at all: the stacks stay in HBM and every LIVE step copies
  its expert's tiles in as words, one live step ahead
  (`qdecode.copy_tiles_ahead`; docs/kernels.md#word-path), so a dead tile
  starts and waits for nothing;
* an expert with no rows has no tile, so its weights are never read; an
  expert whose rows fit one tile (every decode batch: `block_m` covers
  the whole batch) is read once, packed;
* the same per-chunk decode as `qmatmul` (`qdecode.decode_chunk`): bf16
  weight chunks in VMEM, float32 accumulation, no bf16 or float32 copy
  of an expert in HBM.

With two stacks (`w_gate`, `w_up`) the tile computes both products from
the one x tile and writes `act(gate) * up`, in float32, before the
cast: the gated FFN's first half in one call.

What one grid step holds is `tiling.grouped_tile`'s, from O, the row bytes
and the number of stacks (`call_plan` says it; the models put it in their
route note):

* `words`: a 512-row word tile of each stack (docs/kernels.md#word-path),
  wherever O is a multiple of 512: every down projection, Mixtral's and
  GLM's gate / up;
* `words:paired`: a gated call whose O is a multiple of 256 and not of
  512 (granite's and SmallThinker's 768-wide experts) holds 256 rows of
  `w_gate` and 256 of `w_up` a step and decodes them as ONE 512-row word
  tile: the two blocks' words stacked on sublanes and turned together,
  ONE product `[block_m, 512]` where `words` runs two, columns 0..255
  gate and 256..511 up once `natural_columns` has put them back. Same
  index maps, same dead-tile rule;
* on either word form a step holds SEVERAL word tiles where they are
  small (`tiling.GROUPED_STEP_BYTES` of codes: a whole 768-wide expert,
  GLM's too; Mixtral's 2 and 3.5 MiB tiles stay one a step): a grid step
  costs 0.8 to 1.1 us beside the bytes it brings (chip, PR 44), so the
  body walks the tiles it holds, one staging, one product and one store
  each, over two `jit`s of the kernel's refs (`_stage_tile`,
  `_tile_product`) that are traced ONCE and lowered a tile: straight-line
  code lets one tile's stores overlap the next one's staging (a rolled
  loop gains nothing over a tile a step), and a body TRACED a tile in
  Python cost granite's cell 45 s of warm set-up. (Until PR 63 a
  `fori_loop` unrolled at lowering, traced once a kernel instance; the
  `jit`s are traced once a PROCESS for blocks of one shape.)
* `loop`: the stored-layout loop at 256- or 128-row tiles. Left to an
  UNGATED call at such a width (phixtral's `fc1`, or the two plain calls
  of a gated FFN whose activation is not in `FUSED_ACTS`) and to rows of
  codes that are not whole 128-byte lanes: no benchmark cell runs one,
  so there is no third form for them.

The packed codes may keep their leading layer axis (`[L, E, O, C]` with
a traced `layer`): the kernel then reads its blocks straight out of the
scanned-over array, where a `[E, O, C]` slice handed to a Mosaic call
would first be copied whole, every expert of it, hit or not. So may the
scales, once they are prepared (`ops/linear.prepare_scale_bits`: uint16 bits,
`[L, E, O / rows, nb, rows]` with a word tile's rows on lanes in the order
its decode leaves them): a grid step's side block is then the
`[held, nb, rows]` of the tiles it holds and `qdecode.stage_words` a load.
Float16 scales (a tree nobody prepared) are viewed as uint16 before the
call, a copy XLA materializes over every expert of the layer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops.pallas import qdecode
from bigdl_tpu.ops.pallas.qdecode import DecodeSpec
from bigdl_tpu.ops.pallas.qmatmul import (
    _validate, prepared_bits, side_operands,
)
from bigdl_tpu.ops.pallas.tiling import (
    VMEM_LIMIT_BYTES, WORD_BLOCK_O, WORD_ROWS, finest_split, forward_chunk,
    grouped_tile, pick_block_m,
)

#: activations the gated call applies in-kernel (float32, before the
#: cast); any other gated activation runs as two plain calls + XLA
FUSED_ACTS = {"silu": jax.nn.silu,
              "relu": lambda g: jnp.maximum(g, 0.0)}


def moe_block_m(n_tokens: int, k_max: int) -> int:
    """Row tile of one MoE layer: `qmatmul`'s policy at the LARGER of
    the FFN's two contraction dims, since gate/up and down share one row
    layout. Up to 256 tokens it covers the whole batch, so no expert
    needs a second tile and each hit expert is read once."""
    return pick_block_m(n_tokens, k_max)


def moe_n_tiles(n_tokens: int, k: int, n_experts: int, block_m: int) -> int:
    """Static bound on the row tiles the sorted layout can need: an expert
    pads its group to a multiple of `block_m` and gets at most `n_tokens`
    rows (top-k picks distinct experts); a tile holds an assignment."""
    per_expert = -(-n_tokens // block_m)
    padded = (n_tokens * k + n_experts * (block_m - 1)) // block_m
    return max(1, min(n_experts * per_expert, padded, n_tokens * k))


def moe_layout(topi: jax.Array, n_experts: int, block_m: int, n_tiles: int,
               held=None):
    """Where each assignment goes. `topi [N, k]` int32 expert ids ->

    * `dest [N, k]`: row of the sorted buffer that holds assignment
      (n, j); rows of one expert are contiguous, in token order, from a
      multiple of `block_m`;
    * `src [n_tiles * block_m]`: the token each row reads (padding rows
      read token 0; nothing reads their output);
    * `tile_expert [n_tiles]`: the expert whose weights tile m uses (a
      tile past `n_used` repeats the last live tile's);
    * `n_used`: scalar, tiles that hold at least one assignment.

    `held [N, k]` bool (one rank's share of the experts, `topi` its LOCAL
    ids): an assignment that is not held gets no row, counts toward no
    tile, and its `dest` lies past the buffer.
    """
    N, k = topi.shape
    e_flat = topi.reshape(N * k).astype(jnp.int32)
    onehot = e_flat[:, None] == jnp.arange(n_experts, dtype=jnp.int32)[None]
    if held is not None:
        onehot = onehot & held.reshape(N * k, 1)
    onehot = onehot.astype(jnp.int32)  # [N*k, E]
    seen = jnp.cumsum(onehot, axis=0)
    rank = jnp.sum((seen - onehot) * onehot, axis=-1)  # earlier same-expert
    counts = seen[-1]
    first, tile_expert, n_used = _tiles((counts + block_m - 1) // block_m,
                                        n_tiles)
    dest = (first[e_flat] * block_m + rank).astype(jnp.int32)
    tok = jnp.arange(N * k, dtype=jnp.int32) // k
    if held is not None:  # dropped: a row past the buffer, written nowhere
        dest = jnp.where(held.reshape(N * k), dest, n_tiles * block_m)
        n_used = _one_tile_at_least(n_used)
    src = jnp.zeros((n_tiles * block_m,), jnp.int32).at[dest].set(
        tok, unique_indices=True, mode="drop")
    return dest.reshape(N, k), src, tile_expert, n_used


def moe_layout_shared(topi: jax.Array, n_experts: int, block_m: int,
                      held=None):
    """`moe_layout` where ONE tile holds the whole call (`N <= block_m`)
    and every hit expert's tile is the call's rows as they stand, in token
    order: `dest[n, j]` is row n of the tile of expert `topi[n, j]`, the
    tiles numbered over the hit experts in expert order. No rows move, so
    there is no `src` and nothing to rank: -> (`dest`, `tile_expert`,
    `n_used`), the last two as `moe_layout` gives them. `held`: as
    `moe_layout`'s; an assignment that is not held hits no expert."""
    N, k = topi.shape
    assert N <= block_m, (N, block_m)
    hit = topi.reshape(N * k, 1).astype(jnp.int32) == jnp.arange(
        n_experts, dtype=jnp.int32)[None]
    if held is not None:
        hit = hit & held.reshape(N * k, 1)
    hit = jnp.any(hit, axis=0).astype(jnp.int32)
    first, tile_expert, n_used = _tiles(
        hit, moe_n_tiles(N, k, n_experts, block_m))
    dest = first[topi] * block_m + jnp.arange(N, dtype=jnp.int32)[:, None]
    if held is not None:
        n_used = _one_tile_at_least(n_used)
    return dest, tile_expert, n_used


def _one_tile_at_least(n_used):
    """A share's call in which NO assignment is held (one live row whose
    eight choices all fall on other ranks' experts) keeps tile 0 live, on
    expert 0 and rows nobody reads: with no live tile at all the row block
    the kernel asks for, `min(m, n_used - 1)`, lies before the buffer (on
    the chip the device halts on that DMA; the interpreter clamps it). A
    layer that holds every expert always has a live tile."""
    return jnp.maximum(n_used, 1)


def _tiles(tiles: jax.Array, n_tiles: int):
    """From the tiles each expert needs `[E]`: the tile each expert's first
    is, `tile_expert [n_tiles]` and `n_used` (`moe_layout`)."""
    tile_end = jnp.cumsum(tiles)
    n_used = tile_end[-1]
    m = jnp.minimum(jnp.arange(n_tiles, dtype=jnp.int32), n_used - 1)
    tile_expert = jnp.sum(
        (tile_end[None, :] <= m[:, None]).astype(jnp.int32), axis=-1)
    return tile_end - tiles, tile_expert, n_used


# The kernel's two halves, `jit`s of its refs as `qdecode.stage_tile` and
# `qdecode.product_of_tile` are: traced once for each set of block shapes,
# whatever instance calls them (a cell's prefill buckets, its layers' two
# calls at other O), and lowered in line at every call.

@functools.partial(jax.jit, static_argnames=("spec", "prepared", "rows",
                                             "paired"))
def _stage_tile(bufs, slot, sides, scratch, j, *, spec: DecodeSpec,
                prepared: bool, rows: int, paired: bool):
    """Word tile `j` of the tiles a step holds (word rows `j * rows / 4 ..`
    of buffer `slot` of each stack's `bufs`, where
    `qdecode.copy_tiles_ahead` left the step's words; rows `j * rows ..` of
    its side blocks, or block `j` of its prepared bits) into the scratch:
    one staging a stack, or the paired tile's one of both."""
    rows_in = rows // WORD_ROWS
    if bufs[0].shape[1] == rows_in:  # the step holds one tile
        blk, sd = [b.at[slot] for b in bufs], (
            [[r[0] for r in side] for side in sides] if prepared else sides)
    else:
        at = pl.ds(pl.multiple_of(j * rows, rows), rows)
        blk = [b.at[slot, pl.ds(pl.multiple_of(j * rows_in, rows_in),
                                rows_in), :] for b in bufs]
        # (loaded here: a ref view narrower than 128 lanes does not lower)
        sd = [[r[j] if prepared else r[at, :] for r in side]
              for side in sides]
    if paired:
        qdecode.stage_words(spec, blk, sd, scratch, prepared=prepared)
        return
    for i in range(len(bufs)):
        qdecode.stage_words(spec, (blk[i],), (sd[i],),
                            scratch[3 * i:3 * i + 3], prepared=prepared)


@functools.partial(jax.jit, static_argnames=("spec", "K", "ck", "act", "rows",
                                             "paired", "dtype"))
def _tile_product(x_ref, scratch, *, spec: DecodeSpec, K: int, ck: int,
                  act, rows: int, paired: bool, dtype):
    """[block_m, rows] of `dtype`: the product of the staged tile (of each
    stack's, then `act(gate) * up` in float32), its columns put back."""
    if paired:
        y = qdecode.natural_columns(qdecode.staged_product(
            spec, K, ck, x_ref, scratch))
        y = FUSED_ACTS[act](y[:, :rows]) * y[:, rows:]
    else:
        accs = [qdecode.staged_product(spec, K, ck, x_ref,
                                       scratch[3 * i:3 * i + 3])
                for i in range(len(scratch) // 3)]
        y = qdecode.natural_columns(
            accs[0] if act is None else FUSED_ACTS[act](accs[0]) * accs[1])
    return y.astype(dtype)


def _kernel(te_ref, meta_ref, x_ref, *refs, K: int, ck: int,
            spec: DecodeSpec, n_w: int, act, form: str, rows: int,
            prepared: bool = False, layered: bool = False, n_o: int = 1):
    """One [block_m, block_o] tile of one expert: `qmatmul._kernel`'s
    chunk loop over each of the `n_w` weight stacks, skipped whole when
    the tile holds no assignment. On the word path three scratch refs per
    word tile follow the output; the paired form (`tiling.grouped_tile`)
    has one tile for both stacks. Then come each stack's two word buffers
    and their semaphores: the word forms' code refs are the whole stacks in
    HBM (``layered``: read at layer `meta[1]`, else at 0) and a live step's
    tiles of expert `te[m]` are copied in by `qdecode.copy_tiles_ahead`,
    the next LIVE step's asked for first, `meta[0] * n_o` steps in the
    chain (``n_o``: grid steps a row tile). A step that holds several tiles of
    `rows` rows walks them, one staging, one product and one store each,
    through the same scratch, written out here over `_stage_tile` and
    `_tile_product`: each is traced once and lowered a tile, so Mosaic
    sees straight-line code (see the module docstring). With ``prepared``
    a side ref holds the `qdecode.pack_major_bits` blocks of the step's
    tiles, `[held, nb, rows]`."""
    per = 1 + spec.n_side
    o_ref = refs[n_w * per]
    scratch = refs[n_w * per + 1:]
    held = o_ref.shape[1] // rows  # tiles this step holds
    blocks = [refs[i * per] for i in range(n_w)]
    sides = [refs[i * per + 1:(i + 1) * per] for i in range(n_w)]
    # (read out here: the interpreter resolves no grid index in a branch)
    m, o = pl.program_id(0), pl.program_id(1)

    @pl.when(m < meta_ref[0])
    def _live_tile():
        if form == "loop":
            accs = [qdecode.tile_product(spec, K, ck, x_ref, blocks[i],
                                         sides[i]) for i in range(n_w)]
            o_ref[:] = (accs[0] if n_w == 1 else FUSED_ACTS[act](accs[0])
                        * accs[1]).astype(o_ref.dtype)
            return
        paired = form == "words:paired"
        stage, bufs, sem = (scratch[:-n_w - 1], scratch[-n_w - 1:-1],
                            scratch[-1])
        layer = meta_ref[1] if layered else 0
        # the row tile of the step after this one (the chain's last step
        # asks for nothing: any tile of the table does)
        m_next = jax.lax.min(
            m + 1 if n_o == 1 else m + jax.lax.div(o + 1, n_o),
            te_ref.shape[0] - 1)
        slot = qdecode.copy_tiles_ahead(
            blocks, bufs, sem, m, o, meta_ref[0], (layer, te_ref[m]),
            (layer, te_ref[m_next]), n_o=n_o, last_rows=bufs[0].shape[1])
        for j in range(held):
            _stage_tile(bufs, slot, sides, stage, j, spec=spec,
                        prepared=prepared, rows=rows, paired=paired)
            o_ref[:, j * rows:(j + 1) * rows] = _tile_product(
                x_ref, stage, spec=spec, K=K, ck=ck, act=act, rows=rows,
                paired=paired, dtype=o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("spec", "out_dtype", "block_m", "block_o",
                              "ck", "n_w", "act", "form", "rows",
                              "layered", "prepared", "interpret"))
def _moe_qmm(spec, out_dtype, block_m: int, block_o: int, ck: int, n_w: int,
             act, form: str, rows: int, layered: tuple, prepared: bool,
             interpret: bool, tile_expert, meta, x, *arrays):
    """``prepared``: the side arrays are `qdecode.pack_major_bits` of the
    call's word tiles, `[L, E, O / rows, nb, rows]`, and a grid step's
    block is the `[held, nb, rows]` of the tiles it holds."""
    K = x.shape[-1]
    Mp = tile_expert.shape[0] * block_m
    O = arrays[0].shape[-2]
    n_o = O // block_o
    per = 1 + spec.n_side

    def x_map(m, o, te, meta):  # shared rows: the one block, fetched once
        return (jnp.minimum(m, meta[0] - 1) if x.shape[0] == Mp else 0, 0)

    def w_map(has_layer):  # a dead tile names the block already held
        return lambda m, o, te, meta: (
            meta[1] if has_layer else 0, te[m],
            jnp.where(m < meta[0], o, n_o - 1), 0)

    def bits_map(has_layer):  # the same block of tiles, one axis up
        w = w_map(has_layer)
        return lambda *a: (*w(*a), 0)

    words = form != "loop"
    in_specs = [pl.BlockSpec((block_m, K), x_map)] + [
        pl.BlockSpec((None, None, block_o // rows, *a.shape[-2:]),
                     bits_map(has_layer)) if prepared and i % per
        # the word forms' codes stay in HBM: the kernel brings its tiles
        else pl.BlockSpec(memory_space=pl.ANY) if words and not i % per
        else pl.BlockSpec((None, None, block_o, a.shape[-1]),
                          w_map(has_layer))
        for i, (a, has_layer) in enumerate(zip(arrays, layered))
    ]
    scratch = []
    if words:  # a word tile of each stack, or the pair's one; then two
        # buffers of a step's words a stack and their semaphores
        scratch = qdecode.word_scratch(
            spec, WORD_BLOCK_O, arrays[0].shape[-1],
            K // spec.block if prepared else arrays[per - 1].shape[-1]
        ) * (1 if form == "words:paired" else n_w) + qdecode.word_buffers(
            n_w, block_o, arrays[0].shape[-1])
        if interpret:  # (`qmatmul._qmm`: a constant stack's word view)
            arrays = [jax.lax.optimization_barrier(a) if not i % per else a
                      for i, a in enumerate(arrays)]
    return pl.pallas_call(
        functools.partial(_kernel, K=K, ck=ck, spec=spec, n_w=n_w, act=act,
                          form=form, rows=rows, prepared=prepared,
                          layered=layered[0], n_o=n_o),
        name="moe_qmatmul",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(Mp // block_m, n_o),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((block_m, block_o),
                                   lambda m, o, te, meta: (m, o)),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((Mp, O), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(tile_expert, meta, x, *arrays)


def moe_qmatmul(
    x: jax.Array,  # [n_tiles * block_m, K] rows sorted by expert, or
    # [block_m, K]: the call's rows in token order, every tile's alike
    ws,  # one QTensor stack [E, O, K], or the (gate, up) pair of a gated
    # FFN; with `layer`, any field may be rank 4 and is indexed by it
    tile_expert: jax.Array,  # [n_tiles] int32 (moe_layout)
    n_used: jax.Array,  # scalar int32
    block_m: int,
    act: str | None = None,  # with a pair: y = act(x @ Wg^T) * (x @ Wu^T)
    layer=None,  # traced index into the stacks' leading layer axis
    out_dtype=jnp.bfloat16,
    interpret: bool | None = None,
) -> jax.Array:
    """[n_tiles * block_m, O]; rows of tiles past `n_used` are not
    written and hold no meaning."""
    from bigdl_tpu.ops.pallas import interpret_mode

    if interpret is None:
        interpret = interpret_mode()
    ws = tuple(ws) if isinstance(ws, (tuple, list)) else (ws,)
    assert len(ws) in (1, 2) and (len(ws) == 2) == (act is not None), (
        len(ws), act)
    w0 = ws[0]
    assert all(w.qtype == w0.qtype and w.data.shape == w0.data.shape
               for w in ws)
    spec = qdecode.spec_for(w0.spec)
    K = x.shape[-1]
    assert x.shape[0] in (block_m, tile_expert.shape[0] * block_m), (
        x.shape, block_m)

    n_w = len(ws)
    form, rows, held, persist_row = _plan(ws)
    bits = bits_layout(ws)
    bits = bits if all(prepared_bits(w, bits) for w in ws) else None
    arrays, layered = [], []
    for w in ws:
        data = w.data
        if w.spec.storage.startswith("fp8"):
            data = jax.lax.bitcast_convert_type(data, jnp.uint8)
        _validate(spec, K, data)
        for i, a in enumerate((data, *side_operands(spec, w, bits))):
            # (a block of `pack_major_bits` has one axis more)
            rank = 4 if i and bits and bits != "stored" else 3
            layered.append(a.ndim == rank + 1)
            arrays.append(a if layered[-1] else a[None])
    block_o = rows * held
    persist = (n_w * block_o * persist_row + block_m * K * 2
               + n_w * block_m * block_o * 4)
    ck = forward_chunk(form != "loop", block_o * n_w, persist,
                       finest_split(K, spec.planes), spec.block, spec.mins)
    meta = jnp.stack([jnp.asarray(n_used, jnp.int32),
                      jnp.asarray(0 if layer is None else layer, jnp.int32)])
    return _moe_qmm(spec, jnp.dtype(out_dtype), block_m, block_o, ck, n_w,
                    act, form, rows, tuple(layered),
                    bits is not None and bits != "stored", bool(interpret),
                    tile_expert.astype(jnp.int32),
                    meta, x.astype(jnp.bfloat16), *arrays)


def bits_layout(ws):
    """How a call on these stacks reads prepared scale bits
    (`qmatmul.bits_layout` for the grouped kernel): its plan's word form
    (``"words"``: `qdecode.pack_major_bits` of 512-row tiles,
    ``"words:paired"``: of each stack's 256-row blocks), ``"stored"`` for
    the loop, None for the two-level formats."""
    ws = tuple(ws) if isinstance(ws, (tuple, list)) else (ws,)
    if ws[0].spec.superblock:
        return None
    form = _plan(ws)[0]
    return "stored" if form == "loop" else form


def _plan(ws) -> tuple:
    """`tiling.grouped_tile` of one call's stacks and the bytes a row of
    one stack holds, from their fields' static shapes (the side arrays
    cross with the bytes they are stored in)."""
    w0 = ws[0]
    fields = [f for f in (w0.data, w0.scales, w0.mins, w0.sub_scales,
                          w0.sub_mins) if f is not None]
    persist_row = sum(f.shape[-1] * f.dtype.itemsize for f in fields)
    row_bytes = w0.data.shape[-1] * w0.data.dtype.itemsize
    return (*grouped_tile(w0.data.shape[-2], persist_row, row_bytes,
                          len(ws)), persist_row)


def call_plan(ws) -> str:
    """What `moe_qmatmul` will run for these stacks, for a route note: the
    loop, the grid steps an expert and the word tiles a step where it
    holds several, `words:inplace:paired x1 of 3 tiles`; `:inplace` where
    the word path cuts the codes out signed where they lie
    (`qdecode.signed_field`)."""
    ws = tuple(ws) if isinstance(ws, (tuple, list)) else (ws,)
    form, rows, held, _ = _plan(ws)
    if form != "loop" and qdecode.signed_field(qdecode.spec_for(ws[0].spec)):
        form = form.replace("words", "words:inplace")
    note = f"{form} x{ws[0].data.shape[-2] // (rows * held)}"
    return note if held == 1 else f"{note} of {held} tiles"
