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

The kernel's own DMA can take a page out of the pool only where XLA
leaves the page's [Hkv, D] tiles unpadded in HBM (`pool_tiles_whole`:
every cell's shape; not 1, 3 or 6 KV heads, not a head of 64 or 96).
Elsewhere Mosaic refuses the slice, and the pages reach the SAME body
through Pallas's pipeline, one page a grid step of a (B, max_pages)
grid, a dead step naming the block already held (`clamped_page`) and
skipping the body: the price of such a shape is a grid step a dead page
again.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops.pallas import qdecode

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
    of ours can take one page out of the pool: D in whole lane tiles, and
    the KV heads a sublane tile of their own (2 or 4 heads of 2-byte
    values, 8 of any) or whole tiles of 8. Where XLA pads them (6 heads to
    8, 1 to 2, a head of 64 to 128 lanes) Mosaic refuses the slice as not
    aligned to the tiling, and the pages come through Pallas's own
    pipeline instead, one a grid step (`_kernel`, `piped`): the one arm
    a pool laid out in whole tiles would delete (PERF.md section 7)."""
    return head_dim % 128 == 0 and (
        n_kv % 8 == 0 or (n_kv in (2, 4) and itemsize == 2))


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
    whose tiles are not whole (`pool_tiles_whole`), in the same kernel."""
    if not pool_tiles_whole(n_kv, head_dim, itemsize):
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


def _kernel(bt_ref, meta_ref, q_ref, k_in, v_in, *refs,
            n_kv: int, group: int, page: int, pages: int, n_batch: int,
            scale: float, softcap: float | None, quantized: bool,
            piped: bool):
    ks_ref = vs_ref = None
    if quantized:  # fp8 pages: the row's per-vector f32 scales, by column
        ks_ref, vs_ref, *refs = refs
    o_ref, *buffers, acc_ref, m_ref, l_ref = refs
    b = pl.program_id(0)
    n_q, head_dim = q_ref.shape[1:]
    columns = pages * page * n_kv

    layer = meta_ref[0]
    win = meta_ref[1]  # traced per-layer sliding window (2**30 = none)
    pos_b = meta_ref[2 + b]
    start_b = meta_ref[2 + n_batch + b]
    first_b = meta_ref[2 + 2 * n_batch + b]
    last_b = meta_ref[2 + 3 * n_batch + b]
    lo_b = jnp.maximum(start_b, pos_b - win + 1)

    def init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def finish():  # a row with no valid slot writes zeros
        l = l_ref[:]
        o_ref[0] = (acc_ref[:] / jnp.where(l == 0.0, 1.0, l)
                    ).astype(o_ref.dtype)

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

        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # exp-weights of masked columns are exactly 0 (a fully-masked
        # group must contribute nothing, even while m is still -inf)
        pexp = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(pexp, axis=1, keepdims=True)
        if quantized:  # a dead page's scale is anything: 0 * NaN
            pexp = jnp.where(valid, pexp * vs_ref[0, at], 0.0)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            pexp.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    if piped:
        # a pool whose tiles XLA pads (`pool_tiles_whole`): grid
        # (B, max_pages), a page a step through Pallas's pipeline. On a
        # step outside first_b .. last_b the index maps name the block
        # already held (`clamped_page`: no DMA) and the body is skipped.
        p = pl.program_id(1)
        pl.when(p == 0)(init)

        @pl.when((p >= first_b) & (p <= last_b))
        def _live_page():
            attend(p, k_in[0], v_in[0], *columns_of_row())

        pl.when(p == pl.num_programs(1) - 1)(finish)
        return

    k_buf, v_buf, sem = buffers
    max_pages = bt_ref.shape[1]

    g_first = first_b // pages
    n_groups = jnp.where(first_b <= last_b, last_b // pages - g_first + 1, 0)

    def transfers(g, slot):
        """[(is the page live, its copies)] of group g into buffer `slot`:
        one DMA a live page and array, straight through the block table."""
        out = []
        for j in range(pages):
            pg = g * pages + j
            phys = bt_ref[b, jnp.minimum(pg, max_pages - 1)]
            out.append(((pg >= first_b) & (pg <= last_b), [
                pltpu.make_async_copy(src.at[layer, phys], dst.at[slot, j],
                                      sem.at[i, slot])
                for i, (src, dst) in enumerate([(k_in, k_buf),
                                                (v_in, v_buf)])]))
        return out

    def start(g, slot):
        for j, (is_live, copies) in enumerate(transfers(g, slot)):
            @pl.when(is_live)
            def _():
                for c in copies:
                    c.start()

            # a page the row does not own is never loaded: zeros stand in
            # its place in V (weight exactly 0), whatever earlier groups
            # and rows left there; K's scores are masked by slot
            @pl.when(jnp.logical_not(is_live))
            def _():
                v_buf[slot, j] = jnp.zeros(v_buf.shape[2:], v_buf.dtype)

    def wait(g, slot):
        for is_live, copies in transfers(g, slot):
            @pl.when(is_live)
            def _():
                for c in copies:
                    c.wait()

    @pl.when(n_groups == 0)
    def _idle_row():  # the grid step and this store are all it costs
        o_ref[0] = jnp.zeros_like(o_ref[0])

    @pl.when(n_groups > 0)
    def _live_row():
        init()
        start(g_first, 0)
        row_columns = columns_of_row()  # once a row, not once a group

        def one_group(i, _):
            slot = i & 1
            pl.when(i + 1 < n_groups)(
                functools.partial(start, g_first + i + 1, 1 - slot))
            wait(g_first + i, slot)
            attend(g_first + i, k_buf[slot], v_buf[slot], *row_columns)

        jax.lax.fori_loop(0, n_groups, one_group, None)
        finish()


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

    win = jnp.asarray(_NO_WINDOW if window is None else window, jnp.int32)
    pos = pos.astype(jnp.int32)
    start = start.astype(jnp.int32)
    first, last = live_page_range(pos, start, win, page, mp, live)
    meta = jnp.concatenate([
        jnp.reshape(layer, (1,)).astype(jnp.int32), win[None],
        pos, start, first, last,
    ])

    piped = not pool_tiles_whole(Hkv, D, k_pages.dtype.itemsize)
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
        scratch = [pltpu.VMEM((2, P, page, Hkv, D), k_pages.dtype),
                   pltpu.VMEM((2, P, page, Hkv, D), v_pages.dtype),
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

#: logical pages one grid step of the latent kernel reads. Each is its own
#: block (its own DMA through the block table), and the step joins them in
#: VMEM and makes ONE score dot and ONE context dot over all of them: a
#: 64-token page alone is a dot of 64 columns, and the grid step's fixed
#: cost would be paid per page.
LATENT_PAGES_PER_STEP = 8


def _latent_kernel(bt_ref, meta_ref, q_ref, *refs, rank: int, page: int,
                   group: int, n_batch: int, scale: float):
    lat_refs, (o_ref, acc_ref, m_ref, l_ref) = refs[:group], refs[group:]
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    first_b = meta_ref[1 + 2 * n_batch + b]
    last_b = meta_ref[1 + 3 * n_batch + b]

    # a step with a live page among its `group`: the others' blocks are
    # clamped onto live pages of the same row (finite, and masked by slot)
    @pl.when((first_b <= last_b) & (p * group <= last_b)
             & (p * group + group - 1 >= first_b))
    def _live_step():
        pos_b = meta_ref[1 + b]
        start_b = meta_ref[1 + n_batch + b]
        lat = jnp.concatenate([r[0, 0] for r in lat_refs], axis=0) \
            if group > 1 else lat_refs[0][0, 0]  # [group * page, r + dr]
        # every head is a row of the dot: one read of the page serves all
        s = jax.lax.dot_general(
            q_ref[0], lat, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [Hp, G * page]
        slot = p * (group * page) + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        valid = (slot >= start_b) & (slot <= pos_b)
        s = jnp.where(valid, s, _NEG_INF)

        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        pexp = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(pexp, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            pexp.astype(lat.dtype), lat[:, :rank], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    @pl.when(p == pl.num_programs(1) - 1)
    def _finish():
        l = l_ref[:]
        o_ref[0] = (acc_ref[:] / jnp.where(l == 0.0, 1.0, l)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret",
                                             "pages_per_step"))
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
    pages_per_step: int = LATENT_PAGES_PER_STEP,
) -> jax.Array:
    """Absorbed MLA decode over latent pages, in place: returns the
    context [B, H, r] (softmax-weighted sum of the compressed kv), to be
    up-projected by W_uv outside. bf16 dots accumulated in float32,
    float32 softmax state; pages outside `live_page_range` cost neither
    DMA nor compute, and a row `live` marks idle comes back as zeros."""
    from bigdl_tpu.ops.pallas import interpret_mode

    if interpret is None:
        interpret = interpret_mode()
    B, H, r = q_eff.shape
    L, NP, page, width = lat_pages.shape
    mp = block_tables.shape[1]
    G = min(pages_per_step, mp)
    steps = -(-mp // G)
    Hp = -(-H // 16) * 16  # whole bf16 sublane tiles for the dots' rows

    q = jnp.concatenate([q_eff, q_pe], axis=-1).astype(lat_pages.dtype)
    q = jnp.pad(q, ((0, 0), (0, Hp - H), (0, width - q.shape[-1])))
    pos = pos.astype(jnp.int32)
    start = start.astype(jnp.int32)
    first, last = live_page_range(pos, start, _NO_WINDOW, page, mp, live)
    meta = jnp.concatenate([
        jnp.reshape(layer, (1,)).astype(jnp.int32), pos, start, first, last])

    def lat_spec(j):
        def index(b, p, bt, meta):
            pg = clamped_page(p * G + j, meta[1 + 2 * B + b],
                              meta[1 + 3 * B + b])
            return meta[0], bt[b, pg], 0, 0
        return pl.BlockSpec((1, 1, page, width), index)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, steps),
        in_specs=[pl.BlockSpec((1, Hp, width), lambda b, p, bt, meta: (b, 0, 0))]
        + [lat_spec(j) for j in range(G)],
        out_specs=pl.BlockSpec((1, Hp, r), lambda b, p, bt, meta: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hp, r), jnp.float32),
            pltpu.VMEM((Hp, 1), jnp.float32),
            pltpu.VMEM((Hp, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_latent_kernel, rank=r, page=page, group=G,
                          n_batch=B, scale=scale),
        name="paged_latent_decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hp, r), jnp.bfloat16),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(block_tables, meta, q, *([lat_pages] * G))
    return out[:, :H]
