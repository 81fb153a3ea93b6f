"""Pallas paged-attention decode kernel: attention reads KV pages IN
PLACE through the block table.

Parity target: the reference's vLLM paged attention
(/root/reference/python/llm/src/ipex_llm/vllm/xpu/model_convert.py:65-127,
backed by its SYCL paged kernels). The XLA fallback (kvpaged.read_layer)
gathers every allocated page back into a dense [B, S] view per decode
step — the bytes paging saves are spent on the gather, tripling HBM
traffic (page read + dense write + attention read). Here the kernel DMAs
each row's pages straight from the pool:

- grid (B, max_pages); the block table, per-row pos/start and the layer
  index ride as SCALAR-PREFETCH operands so the KV BlockSpec index maps
  can pick the physical page (and layer) per step — no dense copy, no
  per-layer slice of the pool;
- online softmax accumulates across the page axis in VMEM scratch
  (m/l/acc), exactly the flash-attention recurrence with pages as the
  K blocks;
- GQA: q reshapes to [Hkv, G, D] and both dots batch over the kv head
  axis, so all query heads of a row are served by one page DMA.

Only LIVE pages cost anything. Row b's valid slots are
max(start_b, pos_b - window + 1) .. pos_b, so its live pages are one range
first_b .. last_b (`live_page_range`), and a row the caller marks idle
has none. On a grid step outside the range the body is skipped (m / l /
acc stand) and the K / V / scale index maps clamp p into the range
(`clamped_page`), so the step asks for the block the pipeline already
holds and no DMA is issued: a dead page is neither loaded nor used,
whatever it holds. A row with no live page writes zeros (l == 0).
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


def _kernel(bt_ref, meta_ref, q_ref, k_ref, v_ref, *refs,
            n_kv: int, group: int, page: int,
            n_batch: int, softcap: float | None, quantized: bool):
    if quantized:  # fp8 pages: per-vector f32 scales ride alongside
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = refs
    else:
        o_ref, acc_ref, m_ref, l_ref = refs
    b = pl.program_id(0)
    p = pl.program_id(1)
    mp = pl.num_programs(1)

    @pl.when(p == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    first_b = meta_ref[2 + 2 * n_batch + b]
    last_b = meta_ref[2 + 3 * n_batch + b]

    @pl.when((p >= first_b) & (p <= last_b))
    def _live_page():
        pos_b = meta_ref[2 + b]
        start_b = meta_ref[2 + n_batch + b]
        win = meta_ref[1]  # traced per-layer sliding window (2**30 = none)
        q = q_ref[0].reshape(n_kv, group, -1).astype(jnp.float32)
        # shared KV decode body (qdecode.decode_kv): pages stay TYPED fp8
        # here — bitcasting the [L, n_pages, ...] pool per decode step would
        # copy it in HBM — so decode_kv takes its typed-fp8 arm, exact and
        # bit-identical to the uint8 bit-decode arm the flash wrapper uses
        k = qdecode.decode_kv(
            k_ref[0, 0], ks_ref[0, 0][..., None] if quantized else None
        )  # [page, Hkv, D]
        v = qdecode.decode_kv(
            v_ref[0, 0], vs_ref[0, 0][..., None] if quantized else None
        )

        # scores [Hkv, G, page], both dots batched over the kv-head axis
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (1,))),
            preferred_element_type=jnp.float32,
        )
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap

        # validity of this page's slots for row b: start <= slot <= pos
        # (pos is the slot the current token was just written to)
        slot = p * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        valid = (slot >= start_b) & (slot <= pos_b) & (slot > pos_b - win)
        s = jnp.where(valid, s, _NEG_INF)

        m_prev = m_ref[:]  # [Hkv, G, 1-padded lanes]
        m_cur = jnp.max(s, axis=2, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # exp-weights of masked slots are exactly 0 (a fully-masked page
        # must contribute nothing, even while m is still -inf)
        pexp = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)

        l_ref[:] = l_ref[:] * alpha + jnp.sum(pexp, axis=2, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            pexp, v, (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = m_new

    @pl.when(p == mp - 1)
    def _finish():
        l = l_ref[:]
        out = acc_ref[:] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = out.reshape(n_kv * group, -1).astype(o_ref.dtype)


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

    sc = scale if scale is not None else D ** -0.5
    q = q.astype(jnp.float32) * sc  # q block is tiny; keep full precision

    win = jnp.asarray(_NO_WINDOW if window is None else window, jnp.int32)
    pos = pos.astype(jnp.int32)
    start = start.astype(jnp.int32)
    first, last = live_page_range(pos, start, win, page, mp, live)
    meta = jnp.concatenate([
        jnp.reshape(layer, (1,)).astype(jnp.int32), win[None],
        pos, start, first, last,
    ])

    def phys(b, p, bt, meta):  # the physical page step (b, p) holds
        return bt[b, clamped_page(p, meta[2 + 2 * B + b],
                                  meta[2 + 3 * B + b])]

    quantized = k_scale is not None
    kv_spec = pl.BlockSpec(
        (1, 1, page, Hkv, D),
        lambda b, p, bt, meta: (meta[0], phys(b, p, bt, meta), 0, 0, 0),
    )
    in_specs = [
        pl.BlockSpec((1, Hq, D), lambda b, p, bt, meta: (b, 0, 0)),
        kv_spec, kv_spec,
    ]
    args = [block_tables, meta, q, k_pages, v_pages]
    if quantized:
        sc_spec = pl.BlockSpec(
            (1, 1, page, Hkv),
            lambda b, p, bt, meta: (meta[0], phys(b, p, bt, meta), 0, 0),
        )
        in_specs += [sc_spec, sc_spec]
        args += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, mp),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hq, D), lambda b, p, bt, meta: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, D), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
        ],
    )
    out_dtype = jnp.bfloat16
    return pl.pallas_call(
        functools.partial(
            _kernel, n_kv=Hkv, group=G, page=page, n_batch=B,
            softcap=softcap, quantized=quantized,
        ),
        name="paged_decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*args)


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
