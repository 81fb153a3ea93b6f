"""What a packed matmul costs a process to TRACE (ISSUE 63).

Every process traces and lowers every program anew (the persistent cache
holds compiled programs only), so the size of what `qmatmul._qmm` and
`moe_qmatmul._moe_qmm` trace is paid in every cell's warm `setup_s`: a
chat-steady set-up traces 45 instances of `_qmm`. PR 62 was refused over
+3.46 s of set-up for a fifth more to trace and lower an instance. Held
here, on the CPU, so that the next kernel PR sees a doubled body in tier-1
and not in the driver's check:

* tracing one instance calls `qdecode.stage_words` once a weight stack and
  `qdecode.staged_product` once a product, whatever the number of word
  tiles a call or a grid step has;
* a SECOND instance whose blocks have the same shapes (another O, another
  M on the same row tile, a prefill bucket) traces neither again: the
  halves are module-level `jit`s of the kernel's refs
  (`qdecode.stage_tile`, `qdecode.product_of_tile`, the grouped kernel's
  `_stage_tile` and `_tile_product`);
* the copy chain is traced once for instances of one block shape (ISSUE
  64: the word path's code tiles are copied in as words by the kernel's own
  DMA, `qdecode.copy_tiles_ahead`, a module-level `jit` too; its HBM ref is
  the whole stack, so "one shape" is one weight stack's, whatever the rows,
  the bucket or the kernel instance): an instance's own body holds the
  `pjit` equation and a handful of index equations, not three `cond`s with
  a DMA each;
* the whole call's jaxpr, nested jaxprs included and each traced jaxpr
  counted once, stays under a stated count of equations.
"""

import jax
import jax.numpy as jnp
import pytest

from bigdl_tpu.ops.pallas import moe_qmatmul as mq
from bigdl_tpu.ops.pallas import qdecode
from bigdl_tpu.ops.pallas.qmatmul import qmatmul
from bigdl_tpu.quant.qtensor import QTensor

pytestmark = pytest.mark.core


@pytest.fixture(autouse=True)
def _fresh_traces(monkeypatch):
    """The kernels' wrappers are `jit`s: an instance another test of this
    worker traced would not be traced again, and nothing would be counted."""
    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    jax.clear_caches()


@pytest.fixture
def calls(monkeypatch):
    seen = {"stage_words": 0, "staged_product": 0, "copy_tile": 0}
    for name in seen:
        real = getattr(qdecode, name)
        monkeypatch.setattr(
            qdecode, name, lambda *a, _r=real, _n=name, **k: (
                seen.__setitem__(_n, seen[_n] + 1), _r(*a, **k))[1])
    return seen


def _sds(*shape, dtype=jnp.uint8):
    return jax.ShapeDtypeStruct(shape, dtype)


def _weight(*lead, O, K, prepared, stacks=None):
    from bigdl_tpu.ops.linear import prepare_scale_bits

    w = QTensor(qtype="sym_int4", data=_sds(*lead, O, K // 2),
                scales=_sds(*lead, O, K // 32, dtype=jnp.float16))
    return jax.eval_shape(lambda w: prepare_scale_bits(w, stacks), w) \
        if prepared else w


def _equations(jaxpr, seen=None) -> int:
    """Equations of a jaxpr and of every jaxpr its equations carry, each
    traced jaxpr once however often it is called (a `jit` called a tile)."""
    seen = set() if seen is None else seen
    if id(jaxpr) in seen:
        return 0
    seen.add(id(jaxpr))
    n = 0
    for e in jaxpr.eqns:
        n += 1
        for v in e.params.values():
            for j in v if isinstance(v, (tuple, list)) else (v,):
                inner = getattr(j, "jaxpr", j)
                if hasattr(inner, "eqns"):
                    n += _equations(inner, seen)
    return n


def _dense(K, O, M, prepared):
    return jax.make_jaxpr(lambda x, w: qmatmul(x, w))(
        _sds(M, K, dtype=jnp.bfloat16), _weight(O=O, K=K, prepared=prepared))


# (K, O, M): a wqkv (12 tiles), a w_down (8 tiles of 28 chunks), Mistral's
# head (62 tiles and a ragged one), a prefill's row tiles, and one tile
@pytest.mark.parametrize("prepared", (False, True),
                         ids=("staged", "prepared"))
@pytest.mark.parametrize("K,O,M", [(4096, 6144, 32), (14336, 4096, 32),
                                   (4096, 32000, 32), (14336, 4096, 1024),
                                   (4096, 512, 32)])
def test_qmm_traces_each_half_once(calls, K, O, M, prepared):
    _dense(K, O, M, prepared)
    # (the chain starts this step's copy, starts the next one's and waits)
    assert calls == {"stage_words": 1, "staged_product": 1,
                     "copy_tile": 3}, calls


def test_qmm_instances_of_one_block_shape_share_their_traces(calls):
    """Chat-steady's set-up in small: the five `linear`s of a layer at a
    decode step's rows and at three prefill buckets that share a row tile.
    The four K = 4096 projections differ in O alone, which no block shows,
    and the buckets in the number of row tiles: 20 instances trace the
    staging twice (one a K) and the chunk loop four times (a K and a row
    tile). The copy chain is traced once for instances of one block shape:
    its HBM ref is the whole weight, so five times here, once a weight,
    whatever the rows (a decode step and the three buckets share it)."""
    for M in (32, 512, 768, 1024):
        for K, O in ((4096, 6144), (4096, 4096), (4096, 28672),
                     (14336, 4096), (4096, 32000)):
            _dense(K, O, M, True)
    assert calls == {"stage_words": 2, "staged_product": 4,
                     "copy_tile": 5 * 3}, calls


# the whole `qmatmul` call's jaxpr through the interpreter, each traced
# jaxpr once. The parent of ISSUE 63 counted 161 and 220 on scales nobody
# prepared, 148 and 163 on prepared bits; `f16_bits_to_f32` is six
# equations shorter and the three `jit`s are three `pjit` equations more:
# 158 / 217 and 145 / 160. Re-pinned by ISSUE 64, on purpose: the word path
# copies its code tiles in itself (`qdecode.copy_tiles_ahead`), which is 25
# equations once for weights of one shape (the step's arithmetic and three
# `cond`s with a DMA each), and in every instance's own body FOUR (two
# `program_id`s, the layer read, the `pjit`; `natural_columns` is called
# inside `product_of_tile` now, so an instance still calls three `jit`s),
# and through the interpreter the barrier in front of the call: 187 / 243
# and 174 / 186, and four to spare. A second copy of the staging or of the
# chunk loop's body is 25 to 70 more.
@pytest.mark.parametrize("K,O,M,prepared,most", [
    (4096, 6144, 32, False, 191), (14336, 4096, 32, False, 247),
    (4096, 6144, 32, True, 178), (14336, 4096, 32, True, 190)])
def test_qmm_jaxpr_stays_under_its_count(K, O, M, prepared, most):
    n = _equations(_dense(K, O, M, prepared).jaxpr)
    assert most - 12 <= n <= most, (n, most)


# name: (E, K, O, gated, rows of the call): each form of the grouped kernel
_GROUPED = {
    # a whole expert a step, its tiles walked inside the step
    "paired-3-tiles": (72, 4096, 768, True, 32),
    "down-8-tiles": (72, 768, 4096, False, 32),
    "gated-3-tiles": (64, 2048, 1536, True, 32),
    # a tile a step (Mixtral's)
    "gated-28-steps": (8, 4096, 14336, True, 16),
    "down-8-steps": (8, 14336, 4096, False, 16),
    # an expert of ONE word tile (Laguna's gate / up)
    "gated-1-tile": (256, 2048, 512, True, 16),
    # a prefill's sorted rows take the same kernel
    "paired-prefill": (72, 4096, 768, True, 2048),
}
# equations of the whole call on prepared bits, each traced jaxpr counted
# once: the tree's 200 / 153 / 247 / 239 / 165 / 239 / 200 and four to
# spare (the parent of ISSUE 63: 216 / 168 / 263 / 248 / 168 / 248 / 216; a
# step's walk was a `fori_loop` of guarded stores and is two `jit`s called
# a tile). Re-pinned by ISSUE 64, on purpose: the copy chain (once for
# stacks of one shape: three `cond`s with a DMA a stack), a live step's own
# index arithmetic (the step, the next step's row tile clamped, two experts
# read) and the interpreter's barrier a stack: 242 / 187 / 289 / 279 / 196 /
# 277 / 242, and four to spare. The chain takes the step's two experts as
# scalars, not the table they come from: a prefill bucket's table has its
# own length, and the chain would be traced again for each. A second copy
# of the staging or of a chunk loop's body is 25 to 70 more.
_GROUPED_MOST = {"paired-3-tiles": 246, "down-8-tiles": 191,
                 "gated-3-tiles": 293, "gated-28-steps": 283,
                 "down-8-steps": 200, "gated-1-tile": 281,
                 "paired-prefill": 246}


def _grouped(name, prepared=True, N=None):
    E, K, O, gated, rows = _GROUPED[name]
    N = N or rows
    n_w = 2 if gated else 1
    ws = [_weight(2, E, O=O, K=K, prepared=prepared, stacks=n_w)
          for _ in range(n_w)]
    bm = mq.moe_block_m(N, max(K, O, 2048))
    n_tiles = mq.moe_n_tiles(N, 8, E, bm)

    def f(x, te, n_used, layer, *ws):
        return mq.moe_qmatmul(x, list(ws) if gated else ws[0], te, n_used,
                              bm, act="silu" if gated else None, layer=layer)

    return ws, jax.make_jaxpr(f)(
        _sds(n_tiles * bm, K, dtype=jnp.bfloat16),
        _sds(n_tiles, dtype=jnp.int32), _sds(dtype=jnp.int32),
        _sds(dtype=jnp.int32), *ws)


@pytest.mark.parametrize("name", list(_GROUPED))
def test_moe_qmm_traces_each_half_once(calls, name):
    """`stage_words` once a weight stack (the paired tile is one staging
    of both), `staged_product` once a product: the gated word form has two
    of each, every other form one, however many tiles a step walks. A
    second instance on the same row tile (another prefill bucket) traces
    none."""
    ws, jaxpr = _grouped(name)
    plan = mq.call_plan(ws if len(ws) == 2 else ws[0])
    n = 2 if len(ws) == 2 and ":paired" not in plan else 1
    want = {"stage_words": n, "staged_product": n, "copy_tile": 3}
    assert calls == want, (plan, calls)
    count = _equations(jaxpr.jaxpr)
    assert _GROUPED_MOST[name] - 12 <= count <= _GROUPED_MOST[name], (
        name, count)
    if _GROUPED[name][4] >= 256:  # twice the rows: the same 256-row tile
        _grouped(name, N=2 * _GROUPED[name][4])
        assert calls == want, calls
