"""Pallas paged-attention decode kernel: attention reads KV pages IN
PLACE through the block table.

Parity target: the reference's vLLM paged attention
(/root/reference/python/llm/src/ipex_llm/vllm/xpu/model_convert.py:65-127,
backed by its SYCL paged kernels). The XLA fallback (kvpaged.read_layer)
gathers every allocated page back into a dense [B, S] view per decode
step — the bytes paging saves are spent on the gather, tripling HBM
traffic (page read + dense write + attention read). Here the pool stays
in HBM (`memory_space=ANY`: no dense copy, no per-layer slice of it) and
the kernel's unit of work is a GROUP of a row's LIVE pages:

- grid (B,): one step a row. Row b's valid slots are
  max(start_b, pos_b - window + 1) .. pos_b, so its live pages are one
  range first_b .. last_b (`live_page_range`), and a row the caller marks
  idle has none. The block table, per-row pos / start / first / last, the
  layer index and the traced per-layer window ride as SCALAR-PREFETCH
  operands;
- inside the step a loop over the groups of `group_pages` pages that the
  range reaches into, its trip count the row's own (an idle row runs
  none and writes zeros): one DMA a LIVE page and array, straight from
  the pool through the block table into one of two VMEM buffers, the
  next group's in flight while this one is computed. A dead page, inside
  a live group or not, is never loaded, whatever it holds;
- the group's pages are read as ONE operand [columns, D], column c being
  slot c // Hkv of KV head c % Hkv: the pool's own order, so joining
  pages and heads is no relayout. One score dot and one context dot
  serve every query head of every KV head (GQA: all of a row's heads
  from one DMA of a page); a query row meets the columns of the other
  KV heads masked, like dead slots. The MXU is paid by the operand it
  loads, not by the products it throws away;
- operands as they are stored: a bf16 pool and q go to the MXU as bf16
  (exact products) with float32 accumulation, `scale` multiplies the
  float32 scores, the softmax state (m / l / acc, the flash recurrence
  with groups as the K blocks) stays float32 in VMEM scratch, and the
  weights enter the context dot in the operands' type. fp8 pages are
  decoded to the bf16 values they are (`qdecode.decode_kv`), and their
  per-vector scales multiply in float32, K's on the scores and V's on
  the weights.

What the context dot meets in place of a page that was not loaded is
zeros, stored into V's buffer where the DMA would have landed, at a
weight of exactly 0: a row's result depends on its own live pages alone,
so the rows are independent ("parallel"). A row with no live page writes
zeros.

`paged_latent_decode_attention` (latent pages: one array, no heads)
runs the same row loop around its own body: `_row_of_live_groups` is the
skeleton of both, the flash recurrence (`_softmax_*`) and the scalar
operand (`_scalars`) are shared, and what a group IS comes from the cache
kind's static shapes.

The kernel's own DMA can take a page out of the pool only where the
page lies in HBM in whole, unpadded tiles. XLA leaves the page's
[Hkv, D] tiles so at every cell's shape with 2, 4 or 8 KV heads of 128
(`pool_tiles_whole`). A pool of ONE KV head it keeps with a page's SLOTS
on the sublanes, where the head axis stood (`{4,2,3,1,0}`): read as
[L, n_pages, page, D] (a bitcast, `one_head_view`) its [page, D] face is
whole tiles, and the same row loop and body take it with `n_kv = 1`.
Elsewhere (3 or 6 KV heads, a head of 64 or 96) Mosaic refuses the
slice, and the pages reach the SAME body through Pallas's pipeline, one
page a grid step of a (B, max_pages) grid, a dead step naming the block
already held (`clamped_page`) and skipping the body: the price of such a
shape is a grid step a dead page again, and a copy of the pool wherever
XLA's layout of it is not the one Mosaic asks. Which of the two a pool
took is noted while tracing (`routes.note("paged", "rows" | "piped")`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops import routes
from bigdl_tpu.ops.pallas import qdecode, tiling

_NEG_INF = -1e30
_NO_WINDOW = 2 ** 30
#: slots of one group of pages. The dots and the softmax between them run
#: over a whole group whatever it holds, so a row's last group wastes half
#: a group on average, and a group's fixed cost is small beside its DMA:
#: 4 pages of 64 beat 2, 8 and 16 on every cell's mix of rows (PERF.md
#: section 6, PR 35, the kernel alone on a v5e)
_GROUP_TOKENS = 256
#: most columns (slots x KV heads) of a group at 2-byte operands: VMEM
_GROUP_COLUMNS = 4096


def live_page_range(pos, start, window, page: int, max_pages: int,
                    live=None):
    """(first, last) logical pages of each row that hold a valid slot,
    both inside 0 .. max_pages - 1. The valid slots are
    max(start, pos - window + 1) .. pos; `first > last` says the row has
    none (`start > pos`, or `live` false: then (1, 0), which
    `clamped_page` still turns into the in-range page 0)."""
    lo = jnp.maximum(start, pos - window + 1)
    first = jnp.clip(lo // page, 0, max_pages - 1)
    last = jnp.clip(pos // page, 0, max_pages - 1)
    if live is not None:
        first = jnp.where(live, first, 1)
        last = jnp.where(live, last, 0)
    return first, last


def clamped_page(p, first, last):
    """The logical page grid step `p` points its DMA at: `p` inside
    first .. last, the nearer end outside it, so that consecutive dead
    steps name the block already held."""
    return jnp.minimum(jnp.maximum(p, first), last)


def pool_tiles_whole(n_kv: int, head_dim: int, itemsize: int) -> bool:
    """Whether a page's [Hkv, D] tiles lie in HBM unpadded, so that a DMA
    of ours can take one page out of the pool as it is stored: D in whole
    lane tiles, and the KV heads a sublane tile of their own (2 or 4 heads
    of 2-byte values, 8 of any) or whole tiles of 8. Where XLA pads them
    (6 heads to 8, a head of 64 to 128 lanes) Mosaic refuses the slice as
    not aligned to the tiling."""
    return head_dim % 128 == 0 and (
        n_kv % 8 == 0 or (n_kv in (2, 4) and itemsize == 2))


def one_head_view(page: int, n_kv: int, head_dim: int, itemsize: int) -> bool:
    """Whether a pool of ONE KV head read as [L, n_pages, page, D] has its
    [page, D] face in whole unpadded tiles: XLA keeps such a pool with the
    page's slots on the sublanes (`{4,2,3,1,0}`: there is no head axis to
    tile), so the view is a bitcast of what lies in HBM, and a page of
    whole sublane tiles (16 rows of bf16, 32 of fp8 codes) at D in whole
    lane tiles is a slice Mosaic takes. Compiled for a described v5e at
    both widths (tests/test_tpu_lowering.py); a 4-byte pool is no cell's
    and stays where it was."""
    return (n_kv == 1 and head_dim % 128 == 0 and itemsize in (1, 2)
            and page % (32 // itemsize) == 0)


def pages_by_dma(page: int, n_kv: int, head_dim: int, itemsize: int) -> bool:
    """Whether `paged_decode_attention` fetches a row's live pages itself
    (the row loop, grid (B,)): out of the pool as it is stored
    (`pool_tiles_whole`) or through its `one_head_view`. Elsewhere the
    pages come through Pallas's own pipeline, one a grid step (`_kernel`,
    `piped`): the one arm a pool laid out in whole tiles would delete
    (PERF.md section 7)."""
    return (pool_tiles_whole(n_kv, head_dim, itemsize)
            or one_head_view(page, n_kv, head_dim, itemsize))


def group_pages(page: int, n_kv: int, head_dim: int, itemsize: int,
                max_pages: int) -> int:
    """Logical pages of one unit of the GQA kernel's work (one score dot
    and one context dot), from the static shapes alone: `_GROUP_TOKENS`
    slots' worth. Fewer where that many would not fit: a group's columns
    (slots x KV heads) stay within `_GROUP_COLUMNS` of 2-byte operands
    (half that for 4-byte ones and for a head of 256), which keeps K and
    V, double-buffered, and the [Hq, columns] float32 scores in a few MiB
    of VMEM. Never more than a row has, never fewer than one: a page too
    large to join is a group of its own, and so is every page of a pool
    whose pages no DMA of ours can take (`pages_by_dma`), in the same
    kernel."""
    if not pages_by_dma(page, n_kv, head_dim, itemsize):
        return 1
    columns = _GROUP_COLUMNS * 2 // max(2, itemsize) * 128 // max(128, head_dim)
    tokens = min(_GROUP_TOKENS, columns // n_kv)
    return max(1, min(max_pages, tokens // page))


def _rem(x, n: int):
    return x & (n - 1) if n & (n - 1) == 0 else jax.lax.rem(x, n)


def _div(x, n: int):
    if n & (n - 1) == 0:
        return x >> (n.bit_length() - 1)
    return jax.lax.div(x, n)


def _row_range(meta_ref, b, n_batch: int):
    """(first, last) live pages of row `b` out of `_scalars`' operand."""
    return meta_ref[2 + 2 * n_batch + b], meta_ref[2 + 3 * n_batch + b]


def _row_meta(meta_ref, b, n_batch: int):
    """(layer, window, pos, start, first, last) of row `b` out of it."""
    return (meta_ref[0], meta_ref[1], meta_ref[2 + b],
            meta_ref[2 + n_batch + b], *_row_range(meta_ref, b, n_batch))


def _softmax_init(acc_ref, m_ref, l_ref):
    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)


def _softmax_update(s, valid, v, acc_ref, m_ref, l_ref, v_scale=None):
    """The flash recurrence over one more block of columns: `s` the masked
    float32 scores [rows, columns], `v` [columns, D] the context dot's
    operand; `v_scale()` the columns' float32 scales (fp8 pages)."""
    m_prev = m_ref[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    # exp-weights of masked columns are exactly 0 (a fully-masked
    # group must contribute nothing, even while m is still -inf)
    pexp = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[:] = l_ref[:] * alpha + jnp.sum(pexp, axis=1, keepdims=True)
    if v_scale is not None:  # a dead page's scale is anything: 0 * NaN
        pexp = jnp.where(valid, pexp * v_scale(), 0.0)
    acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
        pexp.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[:] = m_new


def _softmax_finish(o_ref, acc_ref, l_ref):  # no valid slot: zeros
    l = l_ref[:]
    o_ref[0] = (acc_ref[:] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def _row_of_live_groups(bt_ref, b, layer, first_b, last_b, pages: int,
                        arrays, sem, zeroed, o_ref, state, row_body,
                        next_row=None):
    """One grid step of a decode kernel whose pool stays in HBM: the loop
    over the groups of `pages` logical pages that row b's live range
    first_b .. last_b reaches into, BOTH kernels' skeleton.

    `arrays` is [(the pool [L, n_pages, ...] in HBM, its VMEM buffer
    [2, pages, ...])]: one DMA a live page and array, straight through the
    block table, the next group's in flight while this one is computed.
    A page the row does not own is never loaded: zeros stand in its place
    in `zeroed` (the buffer the context dot reads: weight exactly 0,
    whatever earlier groups and rows left there), and its scores are
    masked by slot. `row_body()` is traced once a live row, after its
    first group's copies have started, and returns `attend(g, slot)`:
    group g's dots and softmax update out of buffer `slot`. `state` is the
    (acc, m, l) scratch. A row with no live page runs nothing and writes
    zeros.

    `next_row` is (row -> its (first, last), the number of rows, an SMEM
    scratch of two int32): the row's LAST trip then starts the first group
    of the row after it, if that one is live, into the buffer the trip
    leaves free, and says so in the scratch (was it started, into which
    buffer), so that no row but the first after an idle one waits for a
    DMA with nothing to compute. The grid must then run its rows in order
    ("arbitrary": on a chip of two TensorCores they all run on one).
    TEMPORARY: `paged_decode_attention` gives none while its Mosaic
    modules are held to PR 50's (`tests/test_tpu_lowering._PAGED_BODIES`),
    and a row of its then opens with its own first group; the change that
    makes it pass one and re-pins deletes that arm, the ONE `if next_row
    is None` below, and the default (PERF.md 'Left by PR 51' (2))."""
    max_pages = bt_ref.shape[1]

    def groups_of(first, last):
        g_first = first // pages
        return g_first, jnp.where(first <= last,
                                  last // pages - g_first + 1, 0)

    g_first, n_groups = groups_of(first_b, last_b)

    def transfers(row, first, last, g, slot):
        """[(is the page live, its copies)] of a row's group g into
        `slot`."""
        out = []
        for j in range(pages):
            pg = g * pages + j
            phys = bt_ref[row, jnp.minimum(pg, max_pages - 1)]
            out.append(((pg >= first) & (pg <= last), [
                pltpu.make_async_copy(src.at[layer, phys], dst.at[slot, j],
                                      sem.at[i, slot])
                for i, (src, dst) in enumerate(arrays)]))
        return out

    def start(g, slot, row=b, first=first_b, last=last_b):
        for j, (is_live, copies) in enumerate(
                transfers(row, first, last, g, slot)):
            @pl.when(is_live)
            def _():
                for c in copies:
                    c.start()

            @pl.when(jnp.logical_not(is_live))
            def _():
                zeroed[slot, j] = jnp.zeros(zeroed.shape[2:], zeroed.dtype)

    def wait(g, slot):
        for is_live, copies in transfers(b, first_b, last_b, g, slot):
            @pl.when(is_live)
            def _():
                for c in copies:
                    c.wait()

    if next_row is None:  # TEMPORARY, see above: never ahead of its row
        def open_row():
            start(g_first, 0)
            return (lambda i: i & 1), lambda i, slot: pl.when(
                i + 1 < n_groups)(functools.partial(
                    start, g_first + i + 1, 1 - slot))
    else:
        range_of, n_rows, ahead_ref = next_row

        @pl.when(b == 0)
        def _nothing_in_flight():
            ahead_ref[0] = 0

        def open_row():
            started = ahead_ref[0] == 1  # by the row before, in its slot
            slot0 = jnp.where(started, ahead_ref[1], 0)
            pl.when(jnp.logical_not(started))(
                functools.partial(start, g_first, 0))
            ahead_ref[0] = 0
            after = jnp.minimum(b + 1, n_rows - 1)
            first_a, last_a = range_of(after)
            g_first_a, n_groups_a = groups_of(first_a, last_a)
            after_is_live = (b + 1 < n_rows) & (n_groups_a > 0)

            def start_next(i, slot):  # this row's group, or the next row's
                mine = i + 1 < n_groups

                @pl.when(mine | after_is_live)
                def _start_what_comes_next():
                    start(jnp.where(mine, g_first + i + 1, g_first_a),
                          1 - slot, jnp.where(mine, b, after),
                          jnp.where(mine, first_b, first_a),
                          jnp.where(mine, last_b, last_a))

                @pl.when(jnp.logical_not(mine) & after_is_live)
                def _tell_the_row_after():
                    ahead_ref[0] = 1
                    ahead_ref[1] = 1 - slot

            return (lambda i: (slot0 + i) & 1), start_next

    @pl.when(n_groups == 0)
    def _idle_row():  # the grid step and this store are all it costs
        o_ref[0] = jnp.zeros_like(o_ref[0])

    @pl.when(n_groups > 0)
    def _live_row():
        _softmax_init(*state)
        # the row's first group is in flight; trip i -> its buffer, and
        # what trip i starts for the trip after it
        slot_of, start_next = open_row()
        attend = row_body()  # once a row, not once a group

        def one_group(i, _):
            slot = slot_of(i)
            start_next(i, slot)
            wait(g_first + i, slot)
            attend(g_first + i, slot)

        jax.lax.fori_loop(0, n_groups, one_group, None)
        _softmax_finish(o_ref, state[0], state[2])


def _kernel(bt_ref, meta_ref, q_ref, k_in, v_in, *refs,
            n_kv: int, group: int, page: int, pages: int, n_batch: int,
            scale: float, softcap: float | None, quantized: bool,
            piped: bool, listed: bool = False):
    ks_ref = vs_ref = mask_ref = None
    if quantized:  # fp8 pages: the row's per-vector f32 scales, by column
        ks_ref, vs_ref, *refs = refs
    if listed:  # a page LIST (`paged_sparse_decode_attention`): which
        # columns of each group a KV head reads, 1.0 or 0.0
        mask_ref, *refs = refs
    o_ref, *buffers, acc_ref, m_ref, l_ref = refs
    state = (acc_ref, m_ref, l_ref)
    b = pl.program_id(0)
    n_q, head_dim = q_ref.shape[1:]
    columns = pages * page * n_kv

    # win: the traced per-layer sliding window (2**30 = none)
    layer, win, pos_b, start_b, first_b, last_b = _row_meta(
        meta_ref, b, n_batch)
    lo_b = jnp.maximum(start_b, pos_b - win + 1)

    def columns_of_row():
        """(the columns of a query row's own KV head, a column's slot in
        its group), both [Hq, columns]. Column c of a group is slot
        c // Hkv of KV head c % Hkv (the pool's own order, so joining
        pages and heads is no relayout); query row r belongs to KV head
        r // G. One dot serves every head, and the columns of the other
        heads are masked like dead slots."""
        col = jax.lax.broadcasted_iota(jnp.int32, (n_q, columns), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (n_q, columns), 0)
        head0 = _rem(col, n_kv) * group
        return (row >= head0) & (row < head0 + group), _div(col, n_kv)

    def attend(g, k, v, own_head, slot_in_group):
        """Group g of the row, its pages [pages, page, Hkv, D] joined:
        one score dot, the masked softmax update, one context dot."""
        # validity for row b: start <= slot <= pos (pos is the slot the
        # current token was just written to), inside the window
        t = g * (pages * page) + slot_in_group
        valid = own_head & (t >= lo_b) & (t <= pos_b)
        if listed:
            valid = valid & (mask_ref[0, g] > 0.0)

        q = q_ref[0]
        if quantized:
            # shared KV decode body (qdecode.decode_kv): pages stay TYPED
            # fp8 here (bitcasting the [L, n_pages, ...] pool per decode
            # step would copy it in HBM) and every fp8 value is a bf16
            # value; the scales multiply in float32, K's on the scores and
            # V's on the weights
            k = qdecode.decode_kv(k).astype(q.dtype)
            v = qdecode.decode_kv(v).astype(q.dtype)
            at = 0 if piped else g  # the group's row of scales
        # operands as they are stored: exact products, float32 sums
        k = k.reshape(columns, head_dim)
        v = v.reshape(columns, head_dim)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [Hq, columns]
        if quantized:
            s = s * ks_ref[0, at]
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(valid, s, _NEG_INF)
        _softmax_update(s, valid, v, *state,
                        v_scale=(lambda: vs_ref[0, at]) if quantized else None)

    if piped:
        # a pool whose tiles XLA pads (`pages_by_dma` false): grid
        # (B, max_pages), a page a step through Pallas's pipeline. On a
        # step outside first_b .. last_b the index maps name the block
        # already held (`clamped_page`: no DMA) and the body is skipped.
        p = pl.program_id(1)
        pl.when(p == 0)(functools.partial(_softmax_init, *state))

        @pl.when((p >= first_b) & (p <= last_b))
        def _live_page():
            attend(p, k_in[0], v_in[0], *columns_of_row())

        pl.when(p == pl.num_programs(1) - 1)(
            functools.partial(_softmax_finish, o_ref, acc_ref, l_ref))
        return

    k_buf, v_buf, sem = buffers

    def row_body():
        row_columns = columns_of_row()
        return lambda g, slot: attend(g, k_buf[slot], v_buf[slot],
                                      *row_columns)

    _row_of_live_groups(bt_ref, b, layer, first_b, last_b, pages,
                        [(k_in, k_buf), (v_in, v_buf)], sem, v_buf, o_ref,
                        state, row_body)


def _scalars(layer, window, pos, start, page: int, max_pages: int, live):
    """The kernels' second scalar-prefetch operand (the block table is
    the first): [layer, window, pos x B, start x B, first x B, last x B],
    first .. last each row's `live_page_range`."""
    win = jnp.asarray(_NO_WINDOW if window is None else window, jnp.int32)
    pos = pos.astype(jnp.int32)
    start = start.astype(jnp.int32)
    first, last = live_page_range(pos, start, win, page, max_pages, live)
    return jnp.concatenate([
        jnp.reshape(layer, (1,)).astype(jnp.int32), win[None],
        pos, start, first, last,
    ])


@functools.partial(
    jax.jit,
    static_argnames=("scale", "softcap", "interpret"),
)
def paged_decode_attention(
    q: jax.Array,  # [B, Hq, D] current-token queries
    k_pages: jax.Array,  # [L, n_pages, page, Hkv, D] the FULL pool
    v_pages: jax.Array,
    block_tables: jax.Array,  # [B, max_pages] int32
    layer: jax.Array,  # scalar int32
    pos: jax.Array,  # [B] slot holding the current token
    start: jax.Array,  # [B]
    k_scale: jax.Array | None = None,  # [L, n_pages, page, Hkv] f32 (fp8)
    v_scale: jax.Array | None = None,
    scale: float | None = None,
    softcap: float | None = None,
    window=None,  # traced per-layer sliding window; None = unbounded
    live: jax.Array | None = None,  # [B] bool; None = every row is live
    interpret: bool | None = None,
) -> jax.Array:
    """Returns [B, Hq, D] attention over each row's pages, in place.
    A row that `live` marks idle costs neither DMA nor compute and
    comes back as zeros."""
    from bigdl_tpu.ops.pallas import interpret_mode

    if interpret is None:
        interpret = interpret_mode()
    B, Hq, D = q.shape
    L, NP, page, Hkv, _ = k_pages.shape
    G = Hq // Hkv
    mp = block_tables.shape[1]
    quantized = k_scale is not None
    # what the dots are fed: the pool as it is stored (and q with it), fp8
    # pages as the bf16 values they are
    operand = jnp.bfloat16 if quantized else k_pages.dtype
    P = group_pages(page, Hkv, D, k_pages.dtype.itemsize, mp)

    meta = _scalars(layer, window, pos, start, page, mp, live)

    piped = not pages_by_dma(page, Hkv, D, k_pages.dtype.itemsize)
    routes.note("paged", "piped" if piped else "rows",
                f"{P} pages a group of {page} x {Hkv} at D={D}")
    face = (page, Hkv, D)  # one page as the kernel's DMA and buffers see it
    if one_head_view(page, Hkv, D, k_pages.dtype.itemsize):
        # the pool as XLA keeps it: a bitcast, no copy in front of the call
        face = (page, D)
        k_pages = k_pages.reshape(L, NP, *face)
        v_pages = v_pages.reshape(L, NP, *face)
    if piped:  # a page a grid step, through the block table's clamped map
        grid = (B, mp)

        def page_block(b, p, bt, meta):
            pg = clamped_page(p, meta[2 + 2 * B + b], meta[2 + 3 * B + b])
            return meta[0], bt[b, pg], 0, 0, 0

        pool = pl.BlockSpec((None, 1, page, Hkv, D), page_block)
        scratch = []
    else:  # the pool stays in HBM; the kernel fetches groups of live pages
        grid = (B,)
        pool = pl.BlockSpec(memory_space=pl.ANY)
        scratch = [pltpu.VMEM((2, P, *face), k_pages.dtype),
                   pltpu.VMEM((2, P, *face), v_pages.dtype),
                   pltpu.SemaphoreType.DMA((2, 2))]
    row = pl.BlockSpec((1, Hq, D), lambda b, *_: (b, 0, 0))
    in_specs = [row, pool, pool]
    args = [block_tables, meta, q.astype(operand), k_pages, v_pages]
    if quantized:
        # the scales of a row's pages by group and column. [page, Hkv]
        # float32 lies in HBM with its Hkv lanes padded to 128, which no
        # DMA of one page can slice (Mosaic: not aligned to the tiling),
        # so XLA re-lays the layer's scales densely and gathers the rows'
        # (dead pages' too: masked)
        n_groups = -(-mp // P)

        def by_column(scales):
            rows = scales[layer].reshape(NP, page * Hkv)[block_tables]
            rows = jnp.pad(rows, ((0, 0), (0, n_groups * P - mp), (0, 0)))
            return rows.reshape(B, n_groups, 1, P * page * Hkv)

        # a group's row a step (piped), or the row's groups whole
        in_specs += [pl.BlockSpec((1, 1, 1, page * Hkv),
                                  lambda b, p, *_: (b, p, 0, 0)) if piped
                     else pl.BlockSpec((1, n_groups, 1, P * page * Hkv),
                                       lambda b, *_: (b, 0, 0, 0))] * 2
        args += [by_column(k_scale), by_column(v_scale)]
    scratch += [
        pltpu.VMEM((Hq, D), jnp.float32),
        pltpu.VMEM((Hq, 1), jnp.float32),
        pltpu.VMEM((Hq, 1), jnp.float32),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=row,
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        functools.partial(
            _kernel, n_kv=Hkv, group=G, page=page, pages=P, n_batch=B,
            scale=scale if scale is not None else D ** -0.5,
            softcap=softcap, quantized=quantized, piped=piped,
        ),
        name="paged_decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), jnp.bfloat16),
        compiler_params=pltpu.CompilerParams(
            # rows share nothing; a row's pages (piped) accumulate in order
            dimension_semantics=("parallel", "arbitrary")[:len(grid)],
            # both buffers of K and V, and a dozen [Hq, columns] float32
            # temporaries of the softmax between the dots
            vmem_limit_bytes=int(
                4 * P * page * Hkv * D * k_pages.dtype.itemsize
                + 12 * max(Hq, 8) * P * page * Hkv * 4 + 2 ** 23),
        ),
        interpret=interpret,
    )(*args)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_sparse_decode_attention(
    q: jax.Array,  # [B, Hq, D] current-token queries
    k_pages: jax.Array,  # [L, n_pages, page, Hkv, D] the FULL pool, bf16
    v_pages: jax.Array,
    page_list: jax.Array,  # [B, U] int32 PHYSICAL pages, a row's in order
    n_listed: jax.Array,  # [B] int32 how many of them the row reads
    reads: jax.Array,  # [B, Hkv, U] bool: KV head h reads listed page u
    layer: jax.Array,  # scalar int32
    last_fill: jax.Array,  # [B] int32 slots of the row's LAST listed page
    scale: float | None = None,
    live: jax.Array | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """`paged_decode_attention` over a LIST of pages a row in place of a
    range (block-sparse attention, kvsparse.py): row b attends to the pages
    `page_list[b, :n_listed[b]]`, each whole but the last, which holds
    `last_fill[b]` valid slots (the page of the current token: a selection
    always takes it). The list is the UNION of what the row's KV heads
    chose, a page's DMA bringing every head's half of it, and `reads` says
    which head's columns count: the others are masked like dead slots. The
    body, the row loop and the DMA a listed page are the range kernel's
    (`_kernel`, `_row_of_live_groups`): the list stands where the block
    table stood, at positions of its own (no rope: where a key lay is not
    read). Returns [B, Hq, D]; an idle or empty row comes back as zeros."""
    from bigdl_tpu.ops.pallas import interpret_mode

    if interpret is None:
        interpret = interpret_mode()
    B, Hq, D = q.shape
    _, _, page, Hkv, _ = k_pages.shape
    U = page_list.shape[1]
    if not (interpret
            or pool_tiles_whole(Hkv, D, k_pages.dtype.itemsize)):
        raise NotImplementedError(
            f"a pool of {Hkv} KV heads of {D} is not laid out in whole "
            "tiles: the sparse decode kernel takes its pages by its own DMA")
    P = group_pages(page, Hkv, D, k_pages.dtype.itemsize, U)
    if not pool_tiles_whole(Hkv, D, k_pages.dtype.itemsize):
        # (the interpreter alone: `group_pages` says 1 for such a pool)
        P = max(1, min(U, _GROUP_TOKENS // page))
    n_groups = -(-U // P)
    n = n_listed.astype(jnp.int32)
    has = n > 0 if live is None else (n > 0) & live
    pos = jnp.maximum(n - 1, 0) * page + last_fill.astype(jnp.int32) - 1
    meta = _scalars(layer, None, pos, jnp.zeros_like(pos), page, U, has)
    # which columns of each group a head reads: column c of a group is
    # slot c // Hkv of KV head c % Hkv of page c // (page * Hkv)
    cols = jnp.pad(reads, ((0, 0), (0, 0), (0, n_groups * P - U)))
    cols = jnp.moveaxis(cols.reshape(B, Hkv, n_groups, P), 1, 3)
    cols = jnp.broadcast_to(cols[:, :, :, None, :],
                            (B, n_groups, P, page, Hkv))
    cols = cols.reshape(B, n_groups, 1, P * page * Hkv).astype(jnp.float32)
    row = pl.BlockSpec((1, Hq, D), lambda b, *_: (b, 0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(
            _kernel, n_kv=Hkv, group=Hq // Hkv, page=page, pages=P,
            n_batch=B, scale=scale if scale is not None else D ** -0.5,
            softcap=None, quantized=False, piped=False, listed=True),
        name="paged_sparse_decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B,),
            in_specs=[row, pool, pool,
                      pl.BlockSpec((1, n_groups, 1, P * page * Hkv),
                                   lambda b, *_: (b, 0, 0, 0))],
            out_specs=row,
            scratch_shapes=[
                pltpu.VMEM((2, P, page, Hkv, D), k_pages.dtype),
                pltpu.VMEM((2, P, page, Hkv, D), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((Hq, D), jnp.float32),
                pltpu.VMEM((Hq, 1), jnp.float32),
                pltpu.VMEM((Hq, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), jnp.bfloat16),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=int(
                4 * P * page * Hkv * D * k_pages.dtype.itemsize
                + 12 * max(Hq, 8) * P * page * Hkv * 4 + 2 ** 23)),
        interpret=interpret,
    )(page_list.astype(jnp.int32), meta, q.astype(k_pages.dtype), k_pages,
      v_pages, cols)


def paged_block_attention(
    q: jax.Array,  # [B, b, Hq, D]: the queries of each row's block
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    layer: jax.Array,
    pos: jax.Array,  # [B] the block's FIRST slot
    start: jax.Array,
    **kw,  # paged_decode_attention's (scales, scale, softcap, live, ...)
) -> jax.Array:
    """A block's `b` query positions a row in one call of
    `paged_decode_attention` (models/sdar.py: a pass of the diffusion over
    blocks). Every query of a block sees slots `start .. pos + b - 1`, both
    directions inside the block, so the block's queries are `b x G` rows of
    each KV head's dot at ONE position, the block's last slot: the kernel's
    body as it stands, with `b` times the rows a KV head (`group` is read
    off q's shape) and the same one DMA a live page. Returns
    [B, b, Hq, D]."""
    B, b, Hq, D = q.shape
    Hkv = k_pages.shape[3]
    G = Hq // Hkv
    # row (h, t, g) of the kernel's q: KV head h owns rows h*b*G .. +b*G
    rows = jnp.transpose(q.reshape(B, b, Hkv, G, D), (0, 2, 1, 3, 4))
    out = paged_decode_attention(
        rows.reshape(B, Hkv * b * G, D), k_pages, v_pages, block_tables,
        layer, pos + (b - 1), start, **kw)
    out = jnp.transpose(out.reshape(B, Hkv, b, G, D), (0, 2, 1, 3, 4))
    return out.reshape(B, b, Hq, D)


# ---------------------------------------------------------------------------
# Latent pages (MLA, models/deepseek.py): the absorbed decode form
# ---------------------------------------------------------------------------

def _latent_kernel(bt_ref, meta_ref, q_ref, lat_in, o_ref, lat_buf, sem,
                   ahead_ref, acc_ref, m_ref, l_ref, *, rank: int, page: int,
                   pages: int, n_batch: int, scale: float):
    """The row loop's other body: a group is `pages` pages of ONE array,
    rows [slots, width] that are key (all of a row) and value (its first
    `rank` lanes) at once, and every head is a row of the two dots."""
    b = pl.program_id(0)
    layer, _, pos_b, start_b, first_b, last_b = _row_meta(meta_ref, b, n_batch)
    slots = pages * page

    def row_body():
        slot_in_group = jax.lax.broadcasted_iota(
            jnp.int32, (q_ref.shape[1], slots), 1)

        def attend(g, slot):
            lat = lat_buf[slot].reshape(slots, lat_buf.shape[-1])
            # one read of the group serves every head
            s = jax.lax.dot_general(
                q_ref[0], lat, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [Hp, slots]
            t = g * slots + slot_in_group
            valid = (t >= start_b) & (t <= pos_b)
            s = jnp.where(valid, s, _NEG_INF)
            _softmax_update(s, valid, lat[:, :rank], acc_ref, m_ref, l_ref)

        return attend

    _row_of_live_groups(
        bt_ref, b, layer, first_b, last_b, pages, [(lat_in, lat_buf)], sem,
        lat_buf, o_ref, (acc_ref, m_ref, l_ref), row_body,
        next_row=(functools.partial(_row_range, meta_ref, n_batch=n_batch),
                  n_batch, ahead_ref))


def latent_group_pages(lat_pages, heads: int, max_pages: int) -> int:
    """Logical pages one trip of `paged_latent_decode_attention`'s row loop
    joins over the pool `lat_pages` [L, n_pages, page, width] (its shape
    and dtype are all that is read) for `heads` heads and rows of
    `max_pages` pages: `tiling.latent_group_pages` on the pool as it is.
    The kernel's own call, and what a record that names its group (the
    route line, the `decode_step` span's `attn_live_groups`) calls on the
    pool it holds."""
    _, _, page, width = lat_pages.shape
    return tiling.latent_group_pages(
        page, width, jnp.dtype(lat_pages.dtype).itemsize, heads, max_pages)


@functools.partial(jax.jit, static_argnames=("scale", "interpret",
                                             "pages_per_group"))
def paged_latent_decode_attention(
    q_eff: jax.Array,  # [B, H, r]: W_uk^T q_nope, the absorbed query
    q_pe: jax.Array,  # [B, H, dr] rotated
    lat_pages: jax.Array,  # [L, n_pages, page, width >= r + dr] the FULL
    # pool, rows zero-padded to whole lane tiles (kvpaged.PagedLatentCache)
    block_tables: jax.Array,  # [B, max_pages] int32
    layer: jax.Array,  # scalar int32
    pos: jax.Array,  # [B] slot holding the current token
    start: jax.Array,  # [B]
    scale: float,
    live: jax.Array | None = None,  # [B] bool; None = every row is live
    interpret: bool | None = None,
    pages_per_group: int | None = None,  # tests and the kernel bench
) -> jax.Array:
    """Absorbed MLA decode over latent pages, in place: returns the
    context [B, H, r] (softmax-weighted sum of the compressed kv), to be
    up-projected by W_uv outside. `paged_decode_attention`'s form (grid
    (B,), the pool in HBM, a loop over the row's live groups, a DMA a live
    page) around its own body. bf16 dots accumulated in float32, float32
    softmax state; pages outside `live_page_range` cost neither DMA nor
    compute, and a row `live` marks idle comes back as zeros."""
    from bigdl_tpu.ops.pallas import interpret_mode

    if interpret is None:
        interpret = interpret_mode()
    B, H, r = q_eff.shape
    L, NP, page, width = lat_pages.shape
    mp = block_tables.shape[1]
    Hp = -(-H // 16) * 16  # whole bf16 sublane tiles for the dots' rows
    P = min(mp, pages_per_group or latent_group_pages(lat_pages, H, mp))

    q = jnp.concatenate([q_eff, q_pe], axis=-1).astype(lat_pages.dtype)
    q = jnp.pad(q, ((0, 0), (0, Hp - H), (0, width - q.shape[-1])))
    meta = _scalars(layer, None, pos, start, page, mp, live)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, Hp, width), lambda b, *_: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, Hp, r), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, P, page, width), lat_pages.dtype),
            pltpu.SemaphoreType.DMA((1, 2)),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((Hp, r), jnp.float32),
            pltpu.VMEM((Hp, 1), jnp.float32),
            pltpu.VMEM((Hp, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_latent_kernel, rank=r, page=page, pages=P,
                          n_batch=B, scale=scale),
        name="paged_latent_decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hp, r), jnp.bfloat16),
        compiler_params=pltpu.CompilerParams(
            # a row starts the next one's first copies, so rows run in
            # order: "arbitrary" where the old grid's rows were "parallel".
            # A v5e has one TensorCore and loses nothing; on a chip of two
            # the rows would all run on one of them: such a target splits
            # the rows by core first and reads ahead within a core's share
            # (docs/kernels.md#paged-latent)
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=tiling.latent_group_bytes(
                P, page, width, lat_pages.dtype.itemsize, Hp) + 2 ** 23,
        ),
        interpret=interpret,
    )(block_tables, meta, q, lat_pages)
    return out[:, :H]
