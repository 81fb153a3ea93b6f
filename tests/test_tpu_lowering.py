"""The packed matmuls compile for a v5e at the benchmark cells' own widths.

No chip is attached: the TPU compiler that is installed here compiles for a
DESCRIBED v5e (`jax.experimental.topologies`), which raises what the chip's
compiler would raise: a slice Mosaic cannot tile, a transpose or strided read
it does not lower, more scoped VMEM than the kernel may use. The Pallas
interpreter accepts all of these in silence. Nothing runs, so this says
nothing of results or times.

One file, and the topology is described inside a fixture: only one process
at a time may load the TPU library, so only the xdist worker that is given
this file does, and every worker collects the same tests.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

pytestmark = pytest.mark.core


@pytest.fixture(autouse=True, scope="module")
def _compile_as_the_chip_does():
    """`conftest.py` compiles with most optimizations off, an option every
    backend is handed: here the TPU's compiler works as it does on the chip
    (what it refuses, what it copies, what it keeps in memory)."""
    was = jax.config.read("jax_disable_most_optimizations")
    jax.config.update("jax_disable_most_optimizations", False)
    yield
    jax.config.update("jax_disable_most_optimizations", was)


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or it logs under /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# (K, O) of the cells' projections (bench/costs.decode_linears of the four
# configurations) with the rows the cell's decode step has, and a prefill's
# row tile at each configuration's widest contraction
def _lower_on_a_stack(chip, K, O, M, L=2):
    """`qmatmul` lowered on a sym_int4 weight nobody prepared, as a layer
    scan hands it over: the codes of a stack of L layers, the scales of
    the layer's own."""
    from bigdl_tpu.ops.pallas.qmatmul import qmatmul
    from bigdl_tpu.quant import QTensor

    w = QTensor(data=_sds((L, O, K // 2), jnp.uint8, chip),
                scales=_sds((O, K // 32), jnp.float16, chip),
                qtype="sym_int4")
    return jax.jit(
        lambda x, w, layer: qmatmul(x, w, interpret=False, layer=layer)
    ).lower(_sds((M, K), jnp.bfloat16, chip), w, _sds((), jnp.int32, chip))


_DENSE = [
    # Mistral-7B: wqkv, wo, w_gateup, w_down, head (32000: 62 word tiles
    # and a ragged one of 256 rows since ISSUE 55, as Brumby's 151936 below)
    (4096, 6144, 1), (4096, 6144, 32), (4096, 4096, 32), (4096, 28672, 32),
    (14336, 4096, 32), (4096, 32000, 32), (14336, 4096, 256),
    # Qwen2-7B
    (3584, 4608, 16), (3584, 3584, 16), (3584, 37888, 16), (18944, 3584, 16),
    (3584, 152064, 16), (18944, 3584, 256),
    # Brumby-14B
    (5120, 7168, 8), (5120, 5120, 8), (5120, 34816, 8), (17408, 5120, 8),
    (5120, 151936, 8), (17408, 5120, 256),
    # ISSUE 55: the other ragged heads (SmallThinker, SDAR's 64 rows a pass,
    # GLM, MiniCPM-SALA), a prefill's head over longprompt's rows, granite's
    # Mamba `in_proj` and GLM's O = 768; MiniCPM-SALA's O = 256 keeps the loop
    (2560, 151936, 16), (2048, 151936, 64), (2048, 154880, 32),
    (4096, 73472, 16), (4096, 32000, 1792), (4096, 16768, 32),
    (2048, 768, 32), (4096, 256, 16),
]


@pytest.mark.parametrize("K,O,M", _DENSE)
def test_qmatmul_compiles_at_the_cells_shapes(one_chip, K, O, M):
    _lower_on_a_stack(one_chip, K, O, M).compile()


def _format_names():
    from bigdl_tpu.ops.linear import _QGEMV_QTYPES
    return sorted(_QGEMV_QTYPES)


@pytest.mark.parametrize("O", (1024, 768, 384),
                         ids=("words", "ragged", "rows"))
@pytest.mark.parametrize("qtype", _format_names())
def test_every_format_compiles_on_both_loops(one_chip, qtype, O):
    """GEMV rows and a GEMM row tile, K = 2048, on the word path (two
    512-row tiles; since ISSUE 55 a whole tile and a ragged one of 256
    rows too, every operand block of the last grid step partial: codes,
    each format's one to four side arrays, the output) and on the
    stored-layout loop (O = 384 has no whole tile): each format's planes,
    value decode and scale levels through Mosaic. (The k-quants encode on
    the host, so the fields' shapes come from a real, small quantization
    and not from `eval_shape`.)"""
    from bigdl_tpu.ops.pallas.qmatmul import qmatmul
    from bigdl_tpu.ops.pallas.tiling import WORD_BLOCK_O, pick_block_o
    from bigdl_tpu.quant import quantize

    K = 2048
    qt = quantize(jnp.zeros((O, K), jnp.float32), qtype)
    rb = qt.data.shape[1] * qt.data.dtype.itemsize
    assert (pick_block_o(O, 2 * rb, row_bytes=rb) == WORD_BLOCK_O) \
        == (O != 384)
    assert qt.qtype == qtype
    qt = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), qt)
    for M in (8, 256):
        jax.jit(lambda x, w: qmatmul(x, w, interpret=False)).lower(
            _sds((M, K), jnp.bfloat16, one_chip), qt).compile()


# sha256 of the grouped kernel's Mosaic module at Mixtral's two calls (a
# word tile a grid step, 28 and 8 steps an expert), as `_DENSE_BODIES` pins
# the dense kernel's. Pinned by ISSUE 64 with the change it makes on
# purpose: the word forms' code stacks stay in HBM and a live step copies
# its expert's tiles in as int32 words, the next live step's first
# (`qdecode.copy_tiles_ahead`).
_GROUPED_BODIES = {
    (4096, 14336):
        "dd12356e801de81eb015ee6fd8f16c3561522b5f9240169268ba6c49715a3248",
    (14336, 4096):
        "5da83e92ff56b87e0121c5c6a57448c9b1a069de9a26f9089ec601bdb699add6",
}


@pytest.mark.parametrize("K,O,gated", [(4096, 14336, True),
                                       (14336, 4096, False)])
def test_moe_qmatmul_compiles_at_mixtrals_shapes(one_chip, K, O, gated):
    import hashlib

    from bigdl_tpu.ops.pallas import moe_qmatmul as mq
    from bigdl_tpu.quant.qtensor import QTensor

    L, E, N, k = 2, 8, 16, 2
    bm = mq.moe_block_m(N, 14336)
    n_tiles = mq.moe_n_tiles(N, k, E, bm)

    def f(x, te, n_used, layer, *fields):
        ws = [QTensor(qtype="sym_int4", data=fields[2 * i],
                      scales=fields[2 * i + 1]) for i in range(len(fields) // 2)]
        return mq.moe_qmatmul(x, ws if gated else ws[0], te, n_used, bm,
                              act="silu" if gated else None, layer=layer,
                              interpret=False)

    fields = []
    for _ in range(2 if gated else 1):
        fields += [_sds((L, E, O, K // 2), jnp.uint8, one_chip),
                   _sds((E, O, K // 32), jnp.float16, one_chip)]
    lowered = jax.jit(f).lower(
        _sds((n_tiles * bm, K), jnp.bfloat16, one_chip),
        _sds((n_tiles,), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip), _sds((), jnp.int32, one_chip),
        *fields,
    )
    lowered.compile()
    (body,) = _mosaic_bodies(lowered.as_text())
    assert hashlib.sha256(body.encode()).hexdigest() == _GROUPED_BODIES[K, O]


# ---- 768-wide experts on the paired word tile (ISSUE 44) -------------------

# (E, k, K, O, act): granite-4.0-h-small's and SmallThinker-21BA3B's expert
# calls, the gated first half (768 = 3 x 256 rows a stack) and the down
# projection; each at a decode step's rows and at a prefill's 256-row tiles
_EXPERTS_768 = {
    "granite-gate_up": (72, 10, 4096, 768, "silu"),
    "granite-down": (72, 10, 768, 4096, None),
    "smallthinker-gate_up": (64, 6, 2560, 768, "relu"),
    "smallthinker-down": (64, 6, 768, 2560, None),
}


@pytest.mark.parametrize("rows", ("decode", "prefill"))
@pytest.mark.parametrize("name", list(_EXPERTS_768))
def test_moe_qmatmul_compiles_at_granites_and_smallthinkers_shapes(
        one_chip, monkeypatch, name, rows):
    """Mosaic takes the paired tile (two 256-row blocks' words stacked on
    sublanes and turned as one, one product, the activation on the two
    halves of its columns) at both cells' widths and row tiles, and NO call
    there takes the stored-layout loop."""
    from bigdl_tpu.ops.pallas import moe_qmatmul as mq
    from bigdl_tpu.ops.pallas import qdecode
    from bigdl_tpu.quant.qtensor import QTensor

    E, k, K, O, act = _EXPERTS_768[name]
    N = {"granite": 32, "smallthinker": 16}[name.split("-")[0]] \
        if rows == "decode" else 2048
    bm = mq.moe_block_m(N, max(K, O))
    assert bm == (N if rows == "decode" else 256)
    n_tiles = mq.moe_n_tiles(N, k, E, bm)
    calls = {"loop": 0, "staged": 0}
    for fn, key in (("decode_chunk", "loop"), ("stage_words", "staged")):
        real = getattr(qdecode, fn)
        monkeypatch.setattr(
            qdecode, fn, lambda *a, _r=real, _k=key, **kw: (
                calls.__setitem__(_k, calls[_k] + 1), _r(*a, **kw))[1])

    def f(x, te, n_used, layer, *fields):
        ws = [QTensor(qtype="sym_int4", data=fields[2 * i],
                      scales=fields[2 * i + 1]) for i in range(len(fields) // 2)]
        assert mq.call_plan(ws) == (
            "words:inplace:paired x1 of 3 tiles" if act
            else f"words:inplace x1 of {O // 512} tiles")
        # (as `_moe_dispatch_grouped` calls it: `down` leaves in float32)
        return mq.moe_qmatmul(x, ws if act else ws[0], te, n_used, bm,
                              act=act, layer=layer, interpret=False,
                              out_dtype=jnp.bfloat16 if act else jnp.float32)

    fields = []
    for _ in range(2 if act else 1):
        fields += [_sds((2, E, O, K // 2), jnp.uint8, one_chip),
                   _sds((E, O, K // 32), jnp.float16, one_chip)]
    c = jax.jit(f).lower(
        _sds((n_tiles * bm, K), jnp.bfloat16, one_chip),
        _sds((n_tiles,), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip), _sds((), jnp.int32, one_chip),
        *fields,
    ).compile()
    assert "moe_qmatmul" in c.as_text()  # the name the roofline readers find
    # (a whole expert a grid step: the walk over its word tiles calls a
    # `jit` of the kernel's refs a tile, traced ONCE a process for blocks of
    # one shape: not again by a later case of this test on the same blocks)
    assert calls["loop"] == 0 and calls["staged"] <= 1, calls


@pytest.mark.parametrize("rows", ("decode", "prefill"))
@pytest.mark.parametrize("name", ("gate_up", "down"))
def test_moe_qmatmul_compiles_at_256_experts_of_width_512(one_chip, name,
                                                          rows):
    """Laguna-XS.2's expert calls (PR 47): E = 256, four times the largest
    stack the kernel had taken, top-8, width 512. A decode step's 128
    assignments leave most of the 256 tiles of the plan empty; both calls
    take the word path."""
    from bigdl_tpu.ops.pallas import moe_qmatmul as mq
    from bigdl_tpu.quant.qtensor import QTensor

    E, k = 256, 8
    K, O, act = (2048, 512, "silu") if name == "gate_up" else (512, 2048,
                                                               None)
    N = 16 if rows == "decode" else 8192
    bm = mq.moe_block_m(N, 2048)
    n_tiles = mq.moe_n_tiles(N, k, E, bm)
    assert n_tiles == (128 if rows == "decode" else 256 + 255)

    def f(x, te, n_used, layer, *fields):
        ws = [QTensor(qtype="sym_int4", data=fields[2 * i],
                      scales=fields[2 * i + 1]) for i in range(len(fields) // 2)]
        assert mq.call_plan(ws) == ("words:inplace x1" if act
                                    else "words:inplace x1 of 4 tiles")
        return mq.moe_qmatmul(x, ws if act else ws[0], te, n_used, bm,
                              act=act, layer=layer, interpret=False,
                              out_dtype=jnp.bfloat16 if act else jnp.float32)

    fields = []
    for _ in range(2 if act else 1):
        fields += [_sds((3, E, O, K // 2), jnp.uint8, one_chip),
                   _sds((E, O, K // 32), jnp.float16, one_chip)]
    c = jax.jit(f).lower(
        _sds((n_tiles * bm, K), jnp.bfloat16, one_chip),
        _sds((n_tiles,), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip), _sds((), jnp.int32, one_chip),
        *fields,
    ).compile()
    assert "moe_qmatmul" in c.as_text()


def _mosaic_bodies(lowered_text):
    """The Mosaic modules of a lowered program's kernels, printed without
    source locations (the serialized bodies carry file lines)."""
    import base64
    import re

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    out = []
    for body in re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22',
                           lowered_text):
        ctx = mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            out.append(ir.Module.parse(base64.b64decode(body)).operation
                       .get_asm(enable_debug_info=False))
    return out


# sha256 of the dense `qmatmul`'s Mosaic module (wqkv at 32 rows, w_down at
# a prefill's 256, Mistral's head, Qwen2's wqkv whose chunks are Python's
# loop, MiniCPM-SALA's O = 256 on the stored-layout loop). The four on the
# word path re-pinned by ISSUE 64, on purpose: the code tile is no pipelined
# uint8 block any more, the stack stays in HBM (`memory_space<any>`) and the
# kernel copies each tile in as int32 words one grid step ahead
# (`qdecode.copy_tiles_ahead`: two buffers and their DMA semaphores among
# the scratch operands, both grid axes "arbitrary"), so that no byte block
# is re-laid on the VALU in front of the transpose; the decode, the chunk
# loop, the scales and the tiling are what they were, and the stored-layout
# loop's module (O = 256) kept its hash. Before that all five were
# re-pinned by ISSUE 63, on purpose: `qdecode.f16_bits_to_f32`, which every
# packed kernel stages its scales with, is 14 operations where it was 20
# (the same float32 for every finite pattern), and nothing else of the
# module moved (the halves became `jit`s of the kernel's refs, which Mosaic
# is handed in line). Before: PR 49's chunk loop (a sym_int4 nibble cut out
# signed) and PR 55's ragged head (the word path over 63 tiles, its grid
# and block shapes alone differing from wqkv's module).
_DENSE_BODIES = {
    (4096, 6144, 32):
        "3cdcc33fdb0e8e6808489e889ef0236539e3a54fffb11c0f1d052377065c815a",
    (14336, 4096, 256):
        "3213a55e20876c50a6670f4aaf34fa3812affe7b06f808033fdb6e222639703c",
    (4096, 32000, 32):
        "91771e61a029744a2962f006be03eee9b95a5bb379ad3528f8f7396972ae9413",
    (3584, 4608, 16):
        "1cc7ae295092df3e4c7114354cb1efc892885e968f46b91d58acbee831993195",
    (4096, 256, 16):
        "e92c10ef3bbded5c23c42b4d7dc98c9ba15bc7578ebcc2c550a684aff7fba0ef",
}


@pytest.mark.parametrize("K,O,M", list(_DENSE_BODIES))
def test_dense_qmatmul_lowers_to_the_parents_program(one_chip, K, O, M):
    """A pin against changes that were not meant: ISSUE 44 gave
    `qdecode.stage_words` a second block for the grouped kernel's paired
    tile and ISSUE 48 another way in for the scales, and the dense kernels'
    own programs stayed what they were, to the byte (ISSUE 49 changed the
    word path's chunk loop and re-pinned it; the stored-layout loop, which
    it left alone, kept its hash), and the grouped kernel's plan at Mixtral's shapes
    is one 512-row word tile a grid step as it was (GLM's smaller tiles
    are now a whole expert a step)."""
    import hashlib

    from bigdl_tpu.ops.pallas.tiling import grouped_tile

    (body,) = _mosaic_bodies(_lower_on_a_stack(one_chip, K, O, M).as_text())
    assert hashlib.sha256(body.encode()).hexdigest() == _DENSE_BODIES[K, O, M]
    for k, o, stacks, held in ((4096, 14336, 2, 1), (14336, 4096, 1, 1),
                               (2048, 1536, 2, 3), (1536, 2048, 1, 4)):
        assert grouped_tile(o, k // 2 + k // 16, k // 2, stacks) \
            == ("words", 512, held)


# ---- prepared scale bits, read in place (ISSUE 48) --------------------------

def _no_scale_is_moved(compiled):
    """The optimized program neither copies nor views a 16-bit array: the
    prepared bits reach the Mosaic call as the argument they are."""
    text = compiled.as_text()
    moved = [line.strip()[:160] for line in text.splitlines()
             if (" copy(" in line or "bitcast-convert" in line)
             and (" u16[" in line or " f16[" in line)]
    assert not moved, moved
    return text


def _prepared(w, stacks, chip):
    from bigdl_tpu.ops.linear import prepare_scale_bits

    w = jax.eval_shape(lambda w: prepare_scale_bits(w, stacks), w)
    return jax.tree.map(lambda a: _sds(a.shape, a.dtype, chip), w)


# (K, O, M, qtype, in a layer stack?): a wqkv, a prefill's w_down (nb = 448),
# Qwen2's w_down (nb = 592) and its head outside the scan (297 tiles of
# nb = 112), a format with mins; since ISSUE 55 the ragged last word tile on
# bits padded to whole tiles (Mistral's head, Brumby's, granite's `in_proj`
# and GLM's O = 768 in their stacks, mins), and the stored-layout loop at
# what it keeps (MiniCPM-SALA's O = 256)
_PREPARED_DENSE = {
    "wqkv": (4096, 6144, 32, "sym_int4", True),
    "w_down-prefill": (14336, 4096, 256, "sym_int4", True),
    "qwen2-w_down": (18944, 3584, 16, "sym_int4", True),
    "qwen2-head": (3584, 152064, 16, "sym_int4", False),
    "mistral-head-ragged": (4096, 32000, 32, "sym_int4", False),
    "ragged-in-a-stack": (4096, 768, 32, "sym_int4", True),
    "mins": (2048, 1024, 8, "asym_int4", True),
    "brumby-head-ragged": (5120, 151936, 8, "sym_int4", False),
    "granite-in_proj-ragged": (4096, 16768, 32, "sym_int4", True),
    "mins-ragged": (2048, 1280, 8, "asym_int4", True),
    "stored-in-a-stack": (4096, 256, 16, "sym_int4", True),
}


@pytest.mark.parametrize("name", list(_PREPARED_DENSE))
def test_qmatmul_compiles_on_prepared_scale_bits(one_chip, monkeypatch, name):
    """Mosaic takes the operand blocks of a call that reads its scales in
    place: `[nb, 512]` uint16 of `[L, ceil(O / 512), nb, 512]` by layer and
    tile (whole (16, 128) tiles at every cell's nb; a ragged last tile's
    block is whole too, beside its partial code and output blocks), the
    stored `[O, nb]` on the loop; and XLA hands the stack over as it is."""
    from bigdl_tpu.ops.pallas.qmatmul import qmatmul
    from bigdl_tpu.quant.qtensor import QTensor

    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")  # the guards' switch
    K, O, M, qtype, stacked = _PREPARED_DENSE[name]
    lead = (2,) if stacked else ()
    side = _sds((*lead, O, K // 32), jnp.float16, one_chip)
    w = _prepared(QTensor(
        qtype=qtype, data=_sds((*lead, O, K // 2), jnp.uint8, one_chip),
        scales=side, mins=side if qtype == "asym_int4" else None),
        None, one_chip)
    assert w.bits_layout == ("stored" if "stored" in name else "words")
    assert ("ragged" in name) == (w.bits_layout == "words" and O % 512 > 0)
    if w.bits_layout == "words":
        assert w.scale_bits.shape == (*lead, -(-O // 512), K // 32, 512)
    x = _sds((M, K), jnp.bfloat16, one_chip)
    if stacked:
        c = jax.jit(lambda x, w, l: qmatmul(x, w, interpret=False, layer=l)
                    ).lower(x, w, _sds((), jnp.int32, one_chip)).compile()
    else:
        c = jax.jit(lambda x, w: qmatmul(x, w, interpret=False)
                    ).lower(x, w).compile()
    assert "qmatmul" in _no_scale_is_moved(c)


# (E, k, K, O, act, rows of a step): the paired tile's two `[nb, 256]` blocks
# (a lane roll and a select a pack), several word tiles a step (nb = 24: a
# tile and a half of sublanes), Laguna's and Mixtral's
_PREPARED_EXPERTS = {
    "granite-gate_up": (72, 10, 4096, 768, "silu", 32),
    "granite-down": (72, 10, 768, 4096, None, 32),
    "smallthinker-gate_up-prefill": (64, 6, 2560, 768, "relu", 2048),
    "smallthinker-down": (64, 6, 768, 2560, None, 16),
    "laguna-gate_up": (256, 8, 2048, 512, "silu", 16),
    "laguna-down": (256, 8, 512, 2048, None, 16),
    "laguna-down-prefill": (256, 8, 512, 2048, None, 8192),
    "mixtral-gate_up": (8, 2, 4096, 14336, "silu", 16),
    "glm-gate_up": (64, 4, 2048, 1536, "silu", 32),
    # SDAR's pass: 64 rows, 512 assignments over 128 experts (ISSUE 53)
    "sdar-gate_up": (128, 8, 2048, 768, "silu", 64),
    "sdar-down": (128, 8, 768, 2048, None, 64),
}
# the gate / up call of a step one row tile holds: since ISSUE 53 it is handed
# the step's rows as they stand, `[block_m, K]`, beside the sorted form the
# prefill (and whoever calls the kernel with sorted rows) keeps
_SHARED_ROWS = [name for name, (*_, act, N) in _PREPARED_EXPERTS.items()
                if act and N <= 256]


def _x_index_map(mosaic_body: str) -> str:
    """The function Mosaic is handed for the `x` operand's block index."""
    funcs = mosaic_body.split('"stable_mosaic.func.func"')
    (fn,) = [f for f in funcs if 'sym_name = "transform_0"' in f]
    return fn


@pytest.mark.parametrize("name,rows", [
    *((name, "sorted") for name in _PREPARED_EXPERTS),
    *((name, "shared") for name in _SHARED_ROWS)])
def test_moe_qmatmul_compiles_on_prepared_scale_bits(one_chip, monkeypatch,
                                                     name, rows):
    """Mosaic takes the grouped call on prepared bits in either form of
    `x`: rows sorted by expert, a tile each, its block index read from the
    live-tile count; or the call's rows as they stand, ONE block whose index
    is a constant, so the pipeline fetches it once a call and not once a
    live tile."""
    from bigdl_tpu.ops.pallas import moe_qmatmul as mq
    from bigdl_tpu.quant.qtensor import QTensor

    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")
    E, k, K, O, act, N = _PREPARED_EXPERTS[name]
    n_w = 2 if act else 1
    ws = [_prepared(QTensor(
        qtype="sym_int4", data=_sds((2, E, O, K // 2), jnp.uint8, one_chip),
        scales=_sds((2, E, O, K // 32), jnp.float16, one_chip)), n_w,
        one_chip) for _ in range(n_w)]
    form = mq._plan(ws)[0]
    assert form != "loop" and all(w.bits_layout == form for w in ws)
    bm = mq.moe_block_m(N, max(K, O, 2048))
    n_tiles = mq.moe_n_tiles(N, k, E, bm)

    def f(x, te, n_used, layer, *ws):
        return mq.moe_qmatmul(x, list(ws) if act else ws[0], te, n_used, bm,
                              act=act, layer=layer, interpret=False,
                              out_dtype=jnp.bfloat16 if act else jnp.float32)

    assert len(_SHARED_ROWS) == 5 and (rows == "sorted" or N == bm)
    lowered = jax.jit(f).lower(
        _sds(((n_tiles if rows == "sorted" else 1) * bm, K), jnp.bfloat16,
             one_chip),
        _sds((n_tiles,), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip), _sds((), jnp.int32, one_chip),
        *ws)
    (body,) = _mosaic_bodies(lowered.as_text())
    assert ("memref.load" in _x_index_map(body)) == (rows == "sorted")
    c = lowered.compile()
    assert "moe_qmatmul" in _no_scale_is_moved(c)
    assert c.out_info.shape == (n_tiles * bm, O)


# ---- a sym_int4 nibble cut out of its word signed (ISSUE 49) ----------------

@pytest.mark.parametrize("prepared", (False, True),
                         ids=("staged", "prepared"))
@pytest.mark.parametrize("kind", ("dense", "grouped", "paired", "mins"))
def test_word_path_is_lowered_with_signed_nibbles(one_chip, monkeypatch, kind,
                                                  prepared):
    """What Mosaic is handed for a cell's packed calls (a wqkv, Mixtral's
    down projection, granite's paired gate / up; scales staged and
    prepared) flips the nibbles' top bits once a tile (`arith.xori`: the
    kernels have no other) and masks nothing in the chunk loop; a format
    with a minimum keeps the unsigned field. (That all of these COMPILE is
    the tests' above.)"""
    from bigdl_tpu.ops.pallas import moe_qmatmul as mq
    from bigdl_tpu.ops.pallas.qmatmul import qmatmul
    from bigdl_tpu.quant.qtensor import QTensor

    monkeypatch.setenv("BIGDL_TPU_PALLAS", "interpret")  # the guards' switch
    qtype = "asym_int4" if kind == "mins" else "sym_int4"
    E, K, O, act = {"dense": (None, 4096, 6144, None),
                    "mins": (None, 2048, 1024, None),
                    "grouped": (8, 14336, 4096, None),
                    "paired": (72, 4096, 768, "silu")}[kind]
    lead = (2,) if E is None else (2, E)

    def weight():
        # (a dense layer's stored scales are its own slice of the scan's)
        side = _sds((*(() if E is None and not prepared else lead), O,
                     K // 32), jnp.float16, one_chip)
        w = QTensor(qtype=qtype, data=_sds((*lead, O, K // 2), jnp.uint8,
                                           one_chip),
                    scales=side, mins=side if kind == "mins" else None)
        return _prepared(w, 2 if act else None if E is None else 1,
                         one_chip) if prepared else w

    layer = _sds((), jnp.int32, one_chip)
    if E is None:
        lowered = jax.jit(
            lambda x, w, l: qmatmul(x, w, interpret=False, layer=l)
        ).lower(_sds((32, K), jnp.bfloat16, one_chip), weight(), layer)
    else:
        ws = [weight() for _ in range(2 if act else 1)]
        assert ("words:inplace" in mq.call_plan(ws)) and (
            ":paired" in mq.call_plan(ws)) == (kind == "paired")
        bm = mq.moe_block_m(32, max(K, O))
        n_tiles = mq.moe_n_tiles(32, 2, E, bm)
        lowered = jax.jit(lambda x, te, n, l, *ws: mq.moe_qmatmul(
            x, list(ws) if act else ws[0], te, n, bm, act=act, layer=l,
            interpret=False)).lower(
            _sds((n_tiles * bm, K), jnp.bfloat16, one_chip),
            _sds((n_tiles,), jnp.int32, one_chip), layer, layer, *ws)
    (body,) = _mosaic_bodies(lowered.as_text())
    assert ("arith.xori" in body) == (kind != "mins")
    assert "arith.shrsi" in body and "arith.shli" in body


# ---- paged decode attention over groups of live pages (ISSUE 35) -----------

# (slots, KV heads, query heads a KV head, head size, layers, pages in the
# pool[, pages a row: 32]): every configuration that serves from KV pages
# (pages of 64 tokens) at its cell's pool, Mistral's with an fp8 pool too,
# a pool of ONE KV head, which the row loop takes through a view of what XLA
# keeps (`one_head_view`, ISSUE 57), and shapes whose [Hkv, D] tiles XLA pads
# in HBM, which no DMA of the kernel's own can slice: those pages come
# through Pallas's pipeline, one a grid step, to the same body (`piped`). The
# two kinds with two groups of pages call
# the kernel once a group; SDAR's block of 4 positions is 4 x 8 query rows
# a KV head through `paged_block_attention`
_PAGED = {
    "mistral-7b": (32, 8, 4, 128, 32, 1025),
    "qwen2-7b": (16, 4, 7, 128, 28, 1025),
    "mixtral-8x7b": (16, 8, 4, 128, 10, 1025),
    "mistral-7b-fp8": (32, 8, 4, 128, 32, 1025),
    "piped-head-64": (8, 8, 4, 64, 16, 257),  # Llama-3.2-1B's attention
    "rows-one-kv-head": (8, 1, 8, 256, 18, 257),  # Gemma-2B's (MQA)
    "piped-six-kv-heads": (8, 6, 4, 128, 2, 257),
    "piped-head-64-fp8": (8, 2, 7, 64, 24, 257),  # Qwen2-0.5B's, fp8 pool
    "granite-4.0-h-small": (32, 8, 4, 128, 2, 1537, 48),
    "smallthinker-21ba3b-global": (16, 4, 7, 128, 6, 2305, 144),
    "smallthinker-21ba3b-window": (16, 4, 7, 128, 18, 1057, 144),
    "laguna-xs.2-full": (16, 8, 6, 128, 4, 2305, 144),
    "laguna-xs.2-window": (16, 8, 8, 128, 12, 161, 144),
    "sdar-30b-a3b-block": (16, 4, 8, 128, 24, 513),
}


def _lowered_paged(name, one_chip):
    from bigdl_tpu.ops.pallas import paged_attention as pa

    B, Hkv, G, D, L, NP, mp = (*_PAGED[name], 32)[:7]
    page = 64
    fp8 = name.endswith("fp8")
    assert pa.pages_by_dma(page, Hkv, D, 1 if fp8 else 2) \
        != name.startswith("piped")
    assert pa.one_head_view(page, Hkv, D, 2) == (name == "rows-one-kv-head")
    kv = _sds((L, NP, page, Hkv, D),
              jnp.float8_e5m2 if fp8 else jnp.bfloat16, one_chip)
    scales = [_sds((L, NP, page, Hkv), jnp.float32, one_chip)] * 2 if fp8 \
        else []
    q = _sds((B, Hkv * G, D), jnp.bfloat16, one_chip)
    attention = pa.paged_decode_attention
    if name.endswith("block"):  # b = 4 positions a row
        q = _sds((B, 4, Hkv * G, D), jnp.bfloat16, one_chip)
        attention = pa.paged_block_attention

    def f(q, k, v, bt, layer, pos, start, win, live, *scales):
        return attention(
            q, k, v, bt, layer, pos, start, *scales, window=win, live=live,
            softcap=30.0 if fp8 else None, interpret=False)

    return jax.jit(f).lower(
        q, kv, kv,
        _sds((B, mp), jnp.int32, one_chip), _sds((), jnp.int32, one_chip),
        _sds((B,), jnp.int32, one_chip), _sds((B,), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip), _sds((B,), jnp.bool_, one_chip),
        *scales)


@pytest.mark.parametrize("name", list(_PAGED))
def test_paged_decode_kernel_compiles_at_the_cells_shapes(one_chip, name):
    """Mosaic takes the in-kernel loop over a row's live groups: a DMA per
    live page out of the pool in HBM, the pages joined into one operand
    without a relayout ([pages, page, Hkv, D] read as [columns, D]), the two
    dots over all KV heads at once and the masked softmax between them; and
    XLA hands the pool over as it lies (no copy in front of the call), a
    pool of one KV head through the view that is a bitcast of it. The fp8
    pool brings its scales by group and column. The `piped` shapes compile
    the other way pages reach the same body."""
    c = _lowered_paged(name, one_chip).compile()
    assert "paged_decode_attention" in c.as_text()
    if not name.startswith("piped"):  # a pool of padded tiles is re-laid in
        # front of a call that stands alone, on the parent's kernel as here;
        # a block's q and out are re-ordered by KV head (1 MiB), never a pool
        assert c.memory_analysis().temp_size_in_bytes < 2 ** (
            21 if name.endswith("block") else 20)


# sha256 of `paged_decode_attention`'s Mosaic module at those shapes, on PR
# 50's tree (`git archive 16740e8`, this file's `_lowered_paged` run there):
# PR 51 made the row loop (`_row_of_live_groups`), the softmax
# recurrence and the scalar operand the latent kernel's too, and this
# kernel's own programs stayed what they were, to the byte
_PAGED_BODIES = {
    "mistral-7b":
        "08a55bc782e096b59537fa82f635f89597caa4202a6cd128b1638f38b6998d3a",
    "qwen2-7b":
        "bb7e78d1dba394d2e7a7b63b0ea73d5e102e3b8a9b28e5910c51c5515c37b4b4",
    "mixtral-8x7b":
        "e6666e973d406c4c29387029863c3d2df8fec099f0c54485fdb923650ce05dd1",
    "mistral-7b-fp8":
        "e015a7c4557c427c65d16efe4b212c0d19d386c60855e60eb2cbc4686c424c28",
    "piped-head-64":
        "9730dd96c20962a607dbcb4f43af716a61e75672d590cca8e256bf82b6f7af44",
    "rows-one-kv-head":  # since PR 57: grid (8,), groups of 4 pages [64, 256]
        "071cac7a3c2312fdb9d4e8cef0b634a2b9ed97a44339c9eb2a23f5b86dad9165",
    "piped-six-kv-heads":
        "2ca14ff71de83b8304db9b4fe6753640575998c432a08b732d6ad4c75dddcbf3",
    "piped-head-64-fp8":
        "68d46919bff1b3fca0a61b6422a73dfb1b64ea5f89aa37cb7a8cc9e92953b7e5",
    "granite-4.0-h-small":
        "0b672d2402078d9f2ff0225aff818cb0bd9cc7a242cccd4e54c7e6391528508d",
    "smallthinker-21ba3b-global":
        "4aee7701aa0330e80fdac1d486f2f0d7301739d85f6e7745ca0e5064f2896c2d",
    "smallthinker-21ba3b-window":
        "d9a5a8c857388b3630b8439bddb274bbbc5299d407eab025c2edd8a3d5d41391",
    "laguna-xs.2-full":
        "d64c6b040b5bbeb784534b7b47f3aec309fb79bd4c17ecfbbeff87ee9a16d53a",
    "laguna-xs.2-window":
        "90bb412f9cdc2cb1557a4cec3898c7a89059f48b78ab7486cc6d4dffc66d24d0",
    "sdar-30b-a3b-block":
        "e701829cdaece8e699875dff399e96cf9ef10c830b9f262f5b78f20c17774886",
}


@pytest.mark.parametrize("name", list(_PAGED))
def test_paged_decode_kernel_lowers_to_the_parents_program(one_chip, name):
    import hashlib

    (body,) = _mosaic_bodies(_lowered_paged(name, one_chip).as_text())
    assert hashlib.sha256(body.encode()).hexdigest() == _PAGED_BODIES[name]


# ---- GLM-4.7-Flash: latent pages, 64 experts (ISSUE 34) ---------------------

# sha256 of `paged_latent_decode_attention`'s Mosaic module at the cell's
# shapes since PR 51: grid (32,), the pool in HBM, a loop over a row's live
# groups of 16 pages (`tiling.latent_group_pages`), a DMA a live page
_LATENT_BODY = (
    "7f16238c86047e796bfc74fdeee991b3d221860acfe7aaef177bec67a5b2033a")


def test_latent_decode_kernel_compiles_at_the_cells_shapes(one_chip):
    """32 slots x 80 pages of 64 tokens, 20 heads on rows of 640 lanes, a
    pool of 2561 pages over 20 layers: Mosaic takes the loop over a row's
    live groups, the DMA a live page [64, 640] out of the pool in HBM and
    the two dots over a group's pages joined [slots, 640] without a
    relayout, and XLA hands the pool over as it lies (no copy of 4.2 GB in
    front of the call)."""
    import hashlib

    from bigdl_tpu.ops.pallas import tiling
    from bigdl_tpu.ops.pallas.paged_attention import (
        paged_latent_decode_attention)

    B, H, r, dr, page, mp, L, NP = 32, 20, 512, 64, 64, 80, 20, 2561
    assert tiling.latent_group_pages(page, 640, 2, H, mp) == 16

    def f(qe, qp, lat, bt, layer, pos, start, live):
        return paged_latent_decode_attention(
            qe, qp, lat, bt, layer, pos, start, scale=0.0625, live=live,
            interpret=False)

    lowered = jax.jit(f).lower(
        _sds((B, H, r), jnp.bfloat16, one_chip),
        _sds((B, H, dr), jnp.bfloat16, one_chip),
        _sds((L, NP, page, 640), jnp.bfloat16, one_chip),
        _sds((B, mp), jnp.int32, one_chip), _sds((), jnp.int32, one_chip),
        _sds((B,), jnp.int32, one_chip), _sds((B,), jnp.int32, one_chip),
        _sds((B,), jnp.bool_, one_chip))
    c = lowered.compile()
    assert "paged_latent_decode_attention" in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < 2 ** 20
    (body,) = _mosaic_bodies(lowered.as_text())
    assert hashlib.sha256(body.encode()).hexdigest() == _LATENT_BODY


@pytest.mark.parametrize("K,O,gated", [(2048, 1536, True),
                                       (1536, 2048, False)])
def test_moe_qmatmul_compiles_at_glms_shapes(one_chip, K, O, gated):
    """64 experts of width 1536 under a 32-slot step's 128 assignments."""
    from bigdl_tpu.ops.pallas import moe_qmatmul as mq
    from bigdl_tpu.quant.qtensor import QTensor

    L, E, N, k = 2, 64, 32, 4
    bm = mq.moe_block_m(N, 2048)
    n_tiles = mq.moe_n_tiles(N, k, E, bm)

    def f(x, te, n_used, layer, *fields):
        ws = [QTensor(qtype="sym_int4", data=fields[2 * i],
                      scales=fields[2 * i + 1]) for i in range(len(fields) // 2)]
        return mq.moe_qmatmul(x, ws if gated else ws[0], te, n_used, bm,
                              act="silu" if gated else None, layer=layer,
                              interpret=False)

    fields = []
    for _ in range(2 if gated else 1):
        fields += [_sds((L, E, O, K // 2), jnp.uint8, one_chip),
                   _sds((E, O, K // 32), jnp.float16, one_chip)]
    jax.jit(f).lower(
        _sds((n_tiles * bm, K), jnp.bfloat16, one_chip),
        _sds((n_tiles,), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip), _sds((), jnp.int32, one_chip),
        *fields,
    ).compile()


def test_glms_decode_step_slices_no_packed_stack(one_chip, monkeypatch):
    """`deepseek.forward`'s decode step at published widths (one dense and
    two expert layers, 32 slots) compiled for the chip: no expert stack
    [64, O, C] and no packed projection of a layer is sliced out of its
    stack in the optimized HLO: the kernels read them by layer index."""
    import json
    import os
    import re

    from bench import weights
    from bigdl_tpu.models import deepseek
    from bigdl_tpu.models.config import ModelConfig

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "bench", "configs",
                           "glm-4.7-flash-int4.json")) as f:
        hf = dict(json.load(f)["published"], num_hidden_layers=3)
    cfg = ModelConfig.from_hf_config(hf)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the target

    def on_chip(tree):
        return jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), tree)

    params = on_chip(weights.param_shapes(cfg, "sym_int4"))
    cache = on_chip(jax.eval_shape(
        lambda: deepseek.init_paged_cache(cfg, 161, 64, 32, 80)))

    def step(p, toks, c):
        return deepseek.forward(cfg, p, toks, c, mode="decode",
                                moe_routing=True)

    text = jax.jit(step, donate_argnums=(2,)).lower(
        params, _sds((32, 1), jnp.int32, one_chip), cache).compile().as_text()
    assert "paged_latent_decode_attention" in text and "moe_qmatmul" in text
    # uint8 results of a dynamic-slice: packed codes cut out of a stack.
    # One is left, by `ops/linear`'s shape guard: w_dkv's 576 rows are not
    # whole lane tiles, so it takes the XLA dequant, which fuses its slice
    sliced = set(re.findall(r"= (u8\[[\d,]+\])\S* dynamic-slice\(", text))
    big = {s for s in sliced
           if sum(int(d) >= 256 for d in s[3:-1].split(",")) >= 2}
    assert big <= {"u8[1,576,1024]"}, big


# ---- an admission's prefill on its row's own pages (ISSUE 39) ---------------

@pytest.mark.parametrize("name,bucket", [("qwen2-7b-int4", 256),
                                         ("mistral-7b-int4", 1024)])
def test_paged_prefill_handles_no_whole_pool(one_chip, monkeypatch, name,
                                             bucket):
    """`engine_paged_prefill` at a cell's own pool (1025 pages of 64 tokens;
    Qwen2's four KV heads do not fill a tile, Mistral's eight do), two
    layers, compiled for the chip. In the optimized HLO no `copy` is as
    large as one layer of the pool (XLA re-laid the whole four-head pool,
    there and back, around a scatter into it), no `dynamic-slice` cuts a
    layer's whole pool out for a page gather, the pool comes back aliased,
    and the attention is the flash kernel."""
    import math
    import os
    import re

    from bench import cells, weights
    from bigdl_tpu import kvpaged
    from bigdl_tpu.api import TpuModel
    from bigdl_tpu.models.config import ModelConfig
    from bigdl_tpu.serving.engine import InferenceEngine

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    config = cells.load_json(root, "bench", "configs", name + ".json")
    cfg = ModelConfig.from_hf_config(
        dict(cells.as_run(config), num_hidden_layers=2))
    e, qtype = config["bench"]["engine"], config["bench"]["qtype"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the target

    def on_chip(tree):
        return jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), tree)

    # the engine lends its program; its own pool is tiny
    eng = InferenceEngine(TpuModel(cfg, None, qtype), n_slots=e["n_slots"],
                          max_len=e["max_len"], paged=True,
                          page_size=e["page_size"], n_pages=e["n_slots"] + 1)
    pool = on_chip(jax.eval_shape(lambda: kvpaged.init_paged(
        2, e["n_pages"], e["page_size"], cfg.num_key_value_heads,
        cfg.head_dim_, 1, eng.max_pages_per_row)))
    c = eng._paged_prefill.lower(
        on_chip(weights.param_shapes(cfg, qtype)), eng.kind.leaves(pool),
        (_sds((1, eng.max_pages_per_row), jnp.int32, one_chip), None),
        _sds((1,), jnp.int32, one_chip), _sds((1, bucket), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip), _sds((1,), jnp.int32, one_chip),
        lora=None).compile()
    text = c.as_text()
    assert "flash_attention" in text

    layer = pool.k.shape[1:]  # [n_pages, page, Hkv, D]
    results = re.findall(r"= \w+\[([\d,]+)\]\S* (copy|dynamic-slice)\(", text)
    assert results
    for dims, op in results:
        dims = [int(d) for d in dims.split(",")]
        if op == "copy":
            assert math.prod(dims) < math.prod(layer), dims
        else:
            assert tuple(d for d in dims if d != 1) != layer, dims
    m = c.memory_analysis()
    assert m.alias_size_in_bytes >= 2 * pool.k.size * pool.k.dtype.itemsize
    assert m.temp_size_in_bytes < math.prod(layer) * pool.k.dtype.itemsize


# The prefill's attention at the cells' shapes (PR 45): name: (T, S, Hq, Hkv,
# D, window, softcap, fp8 cache). The q block stacks the heads of a group on
# sublanes (a reshape Mosaic takes only at whole sublane tiles), the index
# maps read the scalar-prefetched offset, and a step's scores are float32
# [group * block_q, block_k] in VMEM
_FLASH = {
    "mistral-7b longprompt": (1792, 2048, 32, 8, 128, 4096, None, False),
    "mistral-7b generate": (1024, 1152, 32, 8, 128, 4096, None, False),
    "mistral-7b chat, 16 tokens": (16, 2048, 32, 8, 128, 4096, None, False),
    "qwen2-7b, group of 7": (768, 2048, 28, 4, 128, None, None, False),
    "glm-4.7-flash expanded, D 256": (4096, 5120, 20, 20, 256, None, None,
                                      False),
    "smallthinker window layer": (8192, 9216, 28, 4, 128, 4096, None, False),
    # PR 47: two head counts over the same 8 KV heads in one program, a
    # group of 6 unbounded and a group of 8 under a window of one K block
    "laguna full layer, group of 6": (8192, 9216, 48, 8, 128, None, None,
                                      False),
    "laguna window layer, group of 8": (8192, 9216, 64, 8, 128, 512, None,
                                        False),
    "gemma2 softcap, D 256": (1024, 1024, 16, 8, 256, None, 50.0, False),
    "head of 64": (512, 1024, 8, 8, 64, None, None, False),
    "fp8 cache": (1024, 2048, 32, 8, 128, None, None, True),
}


@pytest.mark.parametrize("name", list(_FLASH))
def test_flash_attention_compiles_at_the_cells_shapes(one_chip, name):
    from bigdl_tpu.ops.pallas.flash_attention import flash_attention

    T, S, Hq, Hkv, D, window, softcap, fp8 = _FLASH[name]
    kv = jnp.float8_e5m2 if fp8 else jnp.bfloat16
    args = [_sds((1, T, Hq, D), jnp.bfloat16, one_chip),
            _sds((1, S, Hkv, D), kv, one_chip),
            _sds((1, S, Hkv, D), kv, one_chip),
            _sds((1,), jnp.int32, one_chip), _sds((), jnp.int32, one_chip)]
    if fp8:
        args += [_sds((1, S, Hkv), jnp.float16, one_chip)] * 2

    def call(q, k, v, start, q_offset, k_scale=None, v_scale=None):
        return flash_attention(q, k, v, start=start, q_offset=q_offset,
                               window=window, softcap=softcap,
                               k_scale=k_scale, v_scale=v_scale,
                               interpret=False)

    text = jax.jit(call).lower(*args).compile().as_text()
    assert "flash_attention" in text


# ---- SDAR-30B-A3B: generation by diffusion over blocks (ISSUE 50) ----------

# Hkv 4, D 128, 8 query heads a KV head, pages of 64, b 4, 24 layers, 16 slots


def test_a_blocks_queries_compile_as_rows_of_the_paged_kernel(one_chip):
    """A block's 4 query positions are 4 x 8 = 32 rows of each KV head's dot
    at the block's last slot: `paged_decode_attention` itself, 128 query rows
    a slot where a one-token step has 32, the pool handed over as it lies."""
    from bigdl_tpu.ops.pallas import paged_attention as pa

    B, b, Hq, Hkv, D, L, NP, page, mp = 16, 4, 32, 4, 128, 24, 513, 64, 32
    kv = _sds((L, NP, page, Hkv, D), jnp.bfloat16, one_chip)

    def f(q, k, v, bt, layer, pos, start, live):
        return pa.paged_block_attention(q, k, v, bt, layer, pos, start,
                                        live=live, interpret=False)

    c = jax.jit(f).lower(
        _sds((B, b, Hq, D), jnp.bfloat16, one_chip), kv, kv,
        _sds((B, mp), jnp.int32, one_chip), _sds((), jnp.int32, one_chip),
        _sds((B,), jnp.int32, one_chip), _sds((B,), jnp.int32, one_chip),
        _sds((B,), jnp.bool_, one_chip)).compile()
    assert "paged_decode_attention" in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < 2 ** 21  # q and out
    # re-ordered by KV head: 2 x 16 x 128 x 128 bf16, never the pool


@pytest.mark.parametrize("T", [64, 1024])
def test_block_causal_flash_attention_compiles(one_chip, T):
    """The prefill's mask causal by blocks of 4: T 1024 over 2048 slots (the
    cell's longest prompt) and its shortest."""
    from bigdl_tpu.ops.pallas.flash_attention import flash_attention

    S, Hq, Hkv, D = 2048, 32, 4, 128

    def call(q, k, v, start, q_offset):
        return flash_attention(q, k, v, start=start, q_offset=q_offset,
                               block_causal=4, interpret=False)

    text = jax.jit(call).lower(
        _sds((1, T, Hq, D), jnp.bfloat16, one_chip),
        _sds((1, S, Hkv, D), jnp.bfloat16, one_chip),
        _sds((1, S, Hkv, D), jnp.bfloat16, one_chip),
        _sds((1,), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip)).compile().as_text()
    assert "flash_attention" in text


def test_sparse_decode_kernel_compiles_at_the_cells_shapes(one_chip):
    """MiniCPM-SALA's sparse layers (PR 54): 16 slots, 2 KV heads of 128
    with 16 query heads each, pages of 64, 272 pages a row of which a list of
    at most 128 is read (64 chosen a KV head, their union; a row below
    `dense_len` its 128), a pool of 4353 pages over 8 layers. The range
    kernel's body and row loop with the list where the block table stood and
    a mask a head and column: Mosaic takes it, and XLA hands the pool over as
    it lies (the column mask is the one temporary, 1 MiB)."""
    from bigdl_tpu import kvsparse
    from bigdl_tpu.ops.pallas import paged_attention as pa

    B, Hkv, G, D, page, L, NP, mp = 16, 2, 16, 128, 64, 8, 4353, 272
    sz = kvsparse.Sizes(16, 64, 64, 1, 32, 8192)
    U = kvsparse.list_width(sz, Hkv, mp)
    assert U == 128 and pa.pool_tiles_whole(Hkv, D, 2)
    kv = _sds((L, NP, page, Hkv, D), jnp.bfloat16, one_chip)

    def f(q, k, v, plist, n, reads, layer, fill, live):
        return pa.paged_sparse_decode_attention(
            q, k, v, plist, n, reads, layer, fill, scale=D ** -0.5,
            live=live, interpret=False)

    c = jax.jit(f).lower(
        _sds((B, Hkv * G, D), jnp.bfloat16, one_chip), kv, kv,
        _sds((B, U), jnp.int32, one_chip), _sds((B,), jnp.int32, one_chip),
        _sds((B, Hkv, U), jnp.bool_, one_chip),
        _sds((), jnp.int32, one_chip), _sds((B,), jnp.int32, one_chip),
        _sds((B,), jnp.bool_, one_chip)).compile()
    assert "paged_sparse_decode_attention" in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < 2 ** 22


def test_lightning_decode_kernel_compiles_at_the_cells_shapes(one_chip):
    """MiniCPM-SALA's lightning layers (PR 54): 16 slots' state rows `[32
    heads x 128, 128]` float32 over 24 layers, B and C (k and q) A HEAD: a
    chunk of a block its own row of keys and queries. The pool goes in and
    comes out as one buffer."""
    from bigdl_tpu.ops.pallas.mamba2 import lightning_decode

    B, H, D, L, R = 16, 32, 128, 24, 16
    vec = _sds((B, H, D), jnp.float32, one_chip)

    def f(state, layer, rows, live, v, decay, k, q):
        return lightning_decode(state, layer, rows, live, v, decay, k, q,
                                interpret=False)

    c = jax.jit(f, donate_argnums=0).lower(
        _sds((L, R, H * D, D), jnp.float32, one_chip),
        _sds((), jnp.int32, one_chip), _sds((B,), jnp.int32, one_chip),
        _sds((B,), jnp.bool_, one_chip), vec,
        _sds((H,), jnp.float32, one_chip), vec, vec).compile()
    assert "lightning_decode" in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < 2 ** 22


def test_kda_decode_kernel_compiles_at_the_cells_shapes(one_chip):
    """Solar-Open2's Kimi delta attention layers (PR 65): 32 slots' state
    rows `[64 heads x 128, 128]` float32 over 9 layers, the decay a VECTOR a
    head, the state read (a lane reduction) before the rank-one update, the
    output stored as columns. The pool goes in and comes out as one
    buffer."""
    from bigdl_tpu.ops.pallas.mamba2 import kda_decode

    B, H, D, L, R = 32, 64, 128, 9, 32
    vec = _sds((B, H, D), jnp.float32, one_chip)

    def f(state, layer, rows, live, q, k, v, g, beta):
        return kda_decode(state, layer, rows, live, q, k, v, g, beta,
                          interpret=False)

    c = jax.jit(f, donate_argnums=0).lower(
        _sds((L, R, H * D, D), jnp.float32, one_chip),
        _sds((), jnp.int32, one_chip), _sds((B,), jnp.int32, one_chip),
        _sds((B,), jnp.bool_, one_chip), vec, vec, vec, vec,
        _sds((B, H), jnp.float32, one_chip)).compile()
    assert "kda_decode" in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < 2 ** 22


def test_flash_attention_compiles_under_a_selections_mask(one_chip):
    """MiniCPM-SALA's prefill (PR 54): 16384 positions of 16 query heads a
    KV head, 2 KV heads of 128, and beside the causal bound an int8 mask a
    query, KV head and key (what the selection lets a query read), a tile a
    grid step through the kernel's own clamped index map."""
    from bigdl_tpu.ops.pallas import flash_attention

    T, Hkv, G, D = 16384, 2, 16, 128
    kv = _sds((1, T, Hkv, D), jnp.bfloat16, one_chip)

    def f(q, k, v, mask):
        return flash_attention(q, k, v, scale=D ** -0.5, mask=mask,
                               interpret=False)

    c = jax.jit(f).lower(
        _sds((1, T, Hkv * G, D), jnp.bfloat16, one_chip), kv, kv,
        _sds((1, Hkv, T, T), jnp.int8, one_chip)).compile()
    assert "flash_attention" in c.as_text()


# ---- Jamba2-3B: Mamba-1 layers, one KV head (ISSUE 56) ----------------------

# 26 Mamba layers, 256 state rows `[16, 5120]` float32 (channels on lanes), a
# decode step's 256 tokens and a prefill's 64 / 1024 of one row


def _scan_pool(one_chip, L=26, R=256, N=16, E=5120):
    return _sds((L, R, N, E), jnp.float32, one_chip)


def test_mamba1_decode_kernel_compiles_at_the_cells_shapes(one_chip):
    """The selective scan of one token a live row, the pool in and out as one
    buffer: the only temporaries are the small operands as the kernel takes
    them (x, dt and y a row a block, B and C a column), never a `[B, 16,
    5120]` array."""
    from bigdl_tpu.ops.pallas.selective_scan import mamba1_decode

    B, N, E = 256, 16, 5120
    row = _sds((B, E), jnp.float32, one_chip)
    vec = _sds((B, N), jnp.float32, one_chip)

    def f(ssm, layer, rows, live, x, dt, A, Bm, Cm):
        return mamba1_decode(ssm, layer, rows, live, x, dt, A, Bm, Cm,
                             interpret=False)

    c = jax.jit(f, donate_argnums=0).lower(
        _scan_pool(one_chip), _sds((), jnp.int32, one_chip),
        _sds((B,), jnp.int32, one_chip), _sds((B,), jnp.bool_, one_chip),
        row, row, _sds((N, E), jnp.float32, one_chip), vec, vec).compile()
    assert "mamba1_decode" in c.as_text()
    m = c.memory_analysis()
    assert m.temp_size_in_bytes < B * N * E * 4 // 2
    assert m.alias_size_in_bytes >= 26 * B * N * E * 4


@pytest.mark.parametrize("T", [64, 1024])
def test_mamba1_prefill_kernel_compiles_at_the_cells_shapes(one_chip, T):
    """One row's T tokens with the state in VMEM across them: x, dt, B, C in
    blocks of tokens, y out, no `[T, 16, 5120]` array."""
    from bigdl_tpu.ops.pallas.selective_scan import mamba1_prefill

    N, E = 16, 5120
    tok = _sds((T, E), jnp.float32, one_chip)
    vec = _sds((T, N), jnp.float32, one_chip)
    scalar = _sds((), jnp.int32, one_chip)

    def f(ssm, layer, row, fresh, n_valid, x, dt, A, Bm, Cm):
        return mamba1_prefill(ssm, layer, row, fresh, n_valid, x, dt, A, Bm,
                              Cm, interpret=False)

    c = jax.jit(f, donate_argnums=0).lower(
        _scan_pool(one_chip), scalar, scalar,
        _sds((), jnp.bool_, one_chip), scalar, tok, tok,
        _sds((N, E), jnp.float32, one_chip), vec, vec).compile()
    assert "mamba1_prefill" in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < T * N * E * 4 // 2


def _made_by_copy(text, *shapes):
    """The `copy` results of optimized HLO `text` that have one of
    `shapes`, whatever their type and layout."""
    import re

    dims = "|".join(",".join(map(str, s)) for s in shapes)
    return re.findall(r"= \(?\w+\[(?:" + dims + r")\]\S* copy\(", text)


@pytest.mark.parametrize("program", ["engine_decode", "engine_paged_prefill"])
def test_jambas_engine_programs_keep_the_scan_in_the_pool(one_chip,
                                                        monkeypatch, program):
    """`engine_decode` at 256 rows and `engine_paged_prefill` at T = 1024 of
    the cell's own pool, the first two runs of layers (7 Mamba, 1 attention),
    compiled for the chip: both call the scan kernel; no float32 array has a
    `[.., 16, 5120]` piece a token or a row outside the pool (the decay
    `exp(dt A)` is formed in the kernel); the state pool is aliased in and
    out with no other result of its shape; no whole `conv` array is
    re-laid (3 inputs side by side a row, not an axis of 3); and no K or V
    pool is (ONE KV head, which the paged kernel reads as XLA keeps it)."""
    import math
    import os
    import re

    from bench import cells, weights
    from bigdl_tpu.api import TpuModel
    from bigdl_tpu.models.config import ModelConfig
    from bigdl_tpu.models.llama import prepare_kernel_scales
    from bigdl_tpu.serving.engine import InferenceEngine

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    config = cells.load_json(root, "bench", "configs", "jamba2-3b-int4.json")
    cfg = ModelConfig.from_hf_config(
        dict(cells.as_run(config), num_hidden_layers=8))
    e, qtype = config["bench"]["engine"], config["bench"]["qtype"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the target
    B, T = e["n_slots"], 1024

    def on_chip(tree):
        return jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda p: prepare_kernel_scales(cfg, p),
        weights.param_shapes(cfg, qtype)))
    eng = InferenceEngine(TpuModel(cfg, None, qtype), n_slots=1,
                          max_len=e["max_len"], paged=True,
                          page_size=e["page_size"], n_pages=2)
    eng.n_slots, eng.n_pages = B, e["n_pages"]
    pool = on_chip(jax.eval_shape(eng._make_pool))
    assert pool.ssm.shape == (7, B, 16, 5120)
    if program == "engine_decode":
        c = eng._decode.lower(
            params, _sds((B,), jnp.int32, one_chip), pool,
            _sds((2,), jnp.uint32, one_chip),
            _sds((B,), jnp.float32, one_chip), _sds((B,), jnp.int32, one_chip),
            _sds((B,), jnp.float32, one_chip), _sds((B,), jnp.bool_, one_chip),
            _sds((B, cfg.vocab_size), jnp.bool_, one_chip),
            _sds((B,), jnp.float32, one_chip), lora=None).compile()
        kernel, lead = "mamba1_decode", B
    else:
        table = _sds((1, eng.max_pages_per_row), jnp.int32, one_chip)
        c = eng._paged_prefill.lower(
            params, eng.kind.leaves(pool), (table, table),
            _sds((1,), jnp.int32, one_chip), _sds((1, T), jnp.int32, one_chip),
            _sds((), jnp.int32, one_chip), _sds((1,), jnp.int32, one_chip),
            lora=None).compile()
        kernel, lead = "mamba1_prefill", T
    text = c.as_text()
    assert f'kernel_name = "{kernel}"' in text or kernel in text
    shapes = {tuple(int(d) for d in dims.split(",") if d)
              for dims in re.findall(r"f32\[([\d,]+)\]", text)}
    wide = {s for s in shapes if s[-2:] == (16, 5120)
            and math.prod(s[:-2]) > 1}  # (one layer's A is `[16, 5120]`)
    assert wide == {pool.ssm.shape}, wide
    assert not {s for s in shapes - {pool.ssm.shape}
                if lead in s[:-1] and 5120 in s and 16 in s}
    # the pool itself: a parameter, the kernel's aliased result, the loop's
    # and the output's plumbing, and no copy
    state = ",".join(map(str, pool.ssm.shape))
    made = set(re.findall(
        r"= \(?f32\[" + state + r"\]\S* ([\w\-]+)\(", text))
    assert made <= {"parameter", "get-tuple-element", "custom-call", "while",
                    "tuple", "bitcast", "conditional"}, made
    assert not _made_by_copy(text, pool.conv.shape)
    # nor is a K or V pool of ONE KV head: the paged kernel reads it as XLA
    # keeps it (`paged_attention.one_head_view`; four copies a step till PR 57)
    assert not _made_by_copy(text, pool.k.shape,
                             pool.k.shape[:3] + pool.k.shape[4:])
    m = c.memory_analysis()
    assert m.alias_size_in_bytes >= pool.ssm.size * 4 + pool.conv.size * 4


@pytest.mark.parametrize("fp8", [False, True], ids=["bf16", "fp8"])
def test_paged_decode_kernel_compiles_at_jambas_one_kv_head(one_chip, fp8):
    """256 slots x 10 pages of 256 tokens, 20 query heads on ONE KV head of
    128, a pool of 2561 pages over 2 layers: `pool_tiles_whole` is false (XLA
    would pad `[1, 128]` tiles, so it keeps the page's 256 slots on the
    sublanes instead, `{4,2,3,1,0}`), and the kernel reads the pool as
    `[L, pages, 256, 128]`, a bitcast of that (`one_head_view`): the row
    loop's own DMA takes a live page, a group is one page, grid (256,); a
    group of 20 query rows is no multiple of 8 and Mosaic takes it. The pool
    is handed over as it lies: no copy of 0.31 GiB in front of the call
    (PERF.md section 6, PR 57; the four a step that ROADMAP R10 counted).
    fp8 codes by the same rule at their 32 rows a tile."""
    from bigdl_tpu.ops.pallas import paged_attention as pa

    B, Hkv, G, D, L, NP, page, mp = 256, 1, 20, 128, 2, 2561, 256, 10
    itemsize = 1 if fp8 else 2
    assert not pa.pool_tiles_whole(Hkv, D, itemsize)
    assert pa.one_head_view(page, Hkv, D, itemsize)
    assert pa.group_pages(page, Hkv, D, itemsize, mp) == 1
    kv = _sds((L, NP, page, Hkv, D),
              jnp.float8_e5m2 if fp8 else jnp.bfloat16, one_chip)
    scales = [_sds((L, NP, page, Hkv), jnp.float32, one_chip)] * 2 * fp8

    def f(q, k, v, bt, layer, pos, start, live, *scales):
        return pa.paged_decode_attention(q, k, v, bt, layer, pos, start,
                                         *scales, scale=D ** -0.5, live=live,
                                         interpret=False)

    c = jax.jit(f).lower(
        _sds((B, Hkv * G, D), jnp.bfloat16, one_chip), kv, kv,
        _sds((B, mp), jnp.int32, one_chip), _sds((), jnp.int32, one_chip),
        _sds((B,), jnp.int32, one_chip), _sds((B,), jnp.int32, one_chip),
        _sds((B,), jnp.bool_, one_chip), *scales).compile()
    text = c.as_text()
    assert "paged_decode_attention" in text
    assert not _made_by_copy(text, (L, NP, page, Hkv, D), (L, NP, page, D))
    if not fp8:  # (the scales of a layer are re-laid and gathered: 10 MiB)
        assert c.memory_analysis().temp_size_in_bytes < 2 ** 20


@pytest.mark.parametrize("T", [64, 1024])
def test_flash_attention_compiles_at_jambas_group_of_20(one_chip, T):
    from bigdl_tpu.ops.pallas.flash_attention import flash_attention

    S, Hq, D = 2560, 20, 128

    def call(q, k, v, start, q_offset):
        return flash_attention(q, k, v, start=start, q_offset=q_offset,
                               scale=D ** -0.5, interpret=False)

    text = jax.jit(call).lower(
        _sds((1, T, Hq, D), jnp.bfloat16, one_chip),
        _sds((1, S, 1, D), jnp.bfloat16, one_chip),
        _sds((1, S, 1, D), jnp.bfloat16, one_chip),
        _sds((1,), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip)).compile().as_text()
    assert "flash_attention" in text


# ---------------------------------------------------------------------------
# LFM2-24B-A2B (ISSUE 61): KV heads of 64 as lane pairs, a tail-only state
# ---------------------------------------------------------------------------

def test_paged_decode_kernel_compiles_at_lfm2s_lane_pairs(one_chip):
    """64 slots x 80 pages of 64 tokens, 32 query heads on 8 KV heads of 64
    kept two to a row of 128 lanes, a pool of 5121 pages over 5 layers. As
    published, `[.., 8, 64]`, the tiles are padded and the kernel takes its
    `piped` arm; as pairs, `[.., 4, 128]`, they are whole (Qwen2's shape),
    the row loop's own DMA takes a live page, grid (64,), and the queries
    are padded instead of the pool: no copy of a 1.56 GiB pool, and next to
    nothing kept beside the arguments."""
    from bigdl_tpu.ops import routes
    from bigdl_tpu.ops.attention import (lane_pairs, pair_queries,
                                         unpair_context)
    from bigdl_tpu.ops.pallas import paged_attention as pa

    B, Hq, Hkv, D, L, NP, page, mp = 64, 32, 8, 64, 5, 5121, 64, 80
    assert not pa.pool_tiles_whole(Hkv, D, 2) and lane_pairs(Hkv, D)
    assert pa.pool_tiles_whole(Hkv // 2, 2 * D, 2)
    assert pa.group_pages(page, Hkv // 2, 2 * D, 2, mp) == 4
    kv = _sds((L, NP, page, Hkv // 2, 2 * D), jnp.bfloat16, one_chip)

    def f(q, k, v, bt, layer, pos, start, live):
        out = pa.paged_decode_attention(
            pair_queries(q, Hkv), k, v, bt, layer, pos, start,
            scale=D ** -0.5, live=live, interpret=False)
        return unpair_context(out, Hkv)

    with routes.record_routes() as seen:
        c = jax.jit(f).lower(
            _sds((B, Hq, D), jnp.bfloat16, one_chip), kv, kv,
            _sds((B, mp), jnp.int32, one_chip), _sds((), jnp.int32, one_chip),
            _sds((B,), jnp.int32, one_chip), _sds((B,), jnp.int32, one_chip),
            _sds((B,), jnp.bool_, one_chip)).compile()
    assert {(op, route) for op, route, _ in seen} == {("paged", "rows")}
    text = c.as_text()
    assert "paged_decode_attention" in text
    assert not _made_by_copy(text, kv.shape)
    assert c.memory_analysis().temp_size_in_bytes < 2 ** 21


@pytest.mark.parametrize("T", [1024, 4096])
def test_flash_attention_on_lane_pairs_reads_k_and_v_unpadded(
        one_chip, T):
    """A prefill's attention from a row's gathered pages `[1, S, 4, 128]`
    (the pool's layout): with the queries padded to the pairs (4 KV heads of
    128, a group of 8) the kernel reads K and V as they lie; as 8 heads of 64
    it pads every head to 128 lanes first (`flash_attention` pads D to whole
    lane tiles), a re-laid copy of K and of V at twice their bytes. Both
    compile; the pairs make no padded copy, and are what
    `models/lfm2_moe.py` runs."""
    from bigdl_tpu.ops.attention import pair_queries, unpair_context
    from bigdl_tpu.ops.pallas.flash_attention import flash_attention

    S, Hq, Hkv, D = 5120, 32, 8, 64

    def as_pairs(q, k, v, start, q_offset):
        return unpair_context(flash_attention(
            pair_queries(q, Hkv), k, v, start=start, q_offset=q_offset,
            scale=D ** -0.5, interpret=False), Hkv)

    def as_heads(q, k, v, start, q_offset):
        return flash_attention(
            q, k.reshape(1, S, Hkv, D), v.reshape(1, S, Hkv, D), start=start,
            q_offset=q_offset, scale=D ** -0.5, interpret=False)

    args = (_sds((1, T, Hq, D), jnp.bfloat16, one_chip),
            _sds((1, S, Hkv // 2, 2 * D), jnp.bfloat16, one_chip),
            _sds((1, S, Hkv // 2, 2 * D), jnp.bfloat16, one_chip),
            _sds((1,), jnp.int32, one_chip), _sds((), jnp.int32, one_chip))
    made = {}
    for form in (as_pairs, as_heads):
        text = jax.jit(form).lower(*args).compile().as_text()
        assert "flash_attention" in text
        made[form.__name__] = text
    # what the kernel is handed as K and V: the row's pages transposed,
    # `[1, 4, S, 128]`, or every head of 64 padded to a tile, twice that
    assert "bf16[1,4,5120,128]" in made["as_pairs"]
    assert "bf16[1,8,5120,128]" not in made["as_pairs"]
    assert "bf16[1,8,5120,128]" in made["as_heads"]


@pytest.mark.parametrize("program,T", [
    ("engine_decode", 1), ("engine_paged_prefill", 1024),
    ("engine_paged_prefill", 4096)])
def test_lfm2s_engine_programs_keep_the_pool_whole_and_unpadded(
        one_chip, monkeypatch, program, T):
    """`engine_decode` at 64 rows and `engine_paged_prefill` at T = 1024 and
    4096 of the cell's own pool, the first three runs of layers (2 dense
    convolution layers, 1 attention, 3 sparse convolution layers), compiled
    for the chip. The three holds of ISSUE 61: the paged route is `rows`; no
    copy of a whole K or V pool (nor of the tails); and the pool's bytes as
    compiled are its shape's (a pool of `[.., 8, 64]` would be padded to
    twice that: the arguments would outgrow their shapes by a pool)."""
    import os

    from bench import cells, weights
    from bigdl_tpu.api import TpuModel
    from bigdl_tpu.models.config import ModelConfig
    from bigdl_tpu.models.llama import prepare_kernel_scales
    from bigdl_tpu.ops import routes
    from bigdl_tpu.serving.engine import InferenceEngine

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    config = cells.load_json(root, "bench", "configs",
                             "lfm2-24b-a2b-int4.json")
    hf = cells.as_run(config)
    cfg = ModelConfig.from_hf_config(dict(
        hf, num_hidden_layers=6, layer_types=hf["layer_types"][:6]))
    e, qtype = config["bench"]["engine"], config["bench"]["qtype"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the target
    B = e["n_slots"]

    def on_chip(tree):
        return jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda p: prepare_kernel_scales(cfg, p),
        weights.param_shapes(cfg, qtype)))
    eng = InferenceEngine(TpuModel(cfg, None, qtype), n_slots=1,
                          max_len=e["max_len"], paged=True,
                          page_size=e["page_size"], n_pages=2)
    eng.n_slots, eng.n_pages = B, e["n_pages"]
    pool = on_chip(jax.eval_shape(eng._make_pool))
    assert pool.ssm is None and pool.conv.shape == (5, B, 2 * 2048)
    assert pool.k.shape == (1, e["n_pages"], 64, 4, 128)  # lane pairs
    with routes.record_routes() as seen:
        if program == "engine_decode":
            args = (params, _sds((B,), jnp.int32, one_chip), pool,
                    _sds((2,), jnp.uint32, one_chip),
                    _sds((B,), jnp.float32, one_chip),
                    _sds((B,), jnp.int32, one_chip),
                    _sds((B,), jnp.float32, one_chip),
                    _sds((B,), jnp.bool_, one_chip),
                    _sds((B, cfg.vocab_size), jnp.bool_, one_chip),
                    _sds((B,), jnp.float32, one_chip))
            c = eng._decode.lower(*args, lora=None).compile()
        else:
            table = _sds((1, eng.max_pages_per_row), jnp.int32, one_chip)
            args = (params, eng.kind.leaves(pool), (table, table),
                    _sds((1,), jnp.int32, one_chip),
                    _sds((1, T), jnp.int32, one_chip),
                    _sds((), jnp.int32, one_chip),
                    _sds((1,), jnp.int32, one_chip))
            c = eng._paged_prefill.lower(*args, lora=None).compile()
    took = {(op, route) for op, route, _ in seen}
    if program == "engine_decode":
        assert ("paged", "rows") in took and ("paged", "piped") not in took
        assert ("attention", "pallas:paged") in took
    else:
        assert ("attention", "pallas:flash") in took
    assert any(op == "moe" and route == "pallas:grouped"
               for op, route in took)
    text = c.as_text()
    assert ("paged_decode_attention" if program == "engine_decode"
            else "flash_attention") in text
    assert not _made_by_copy(text, pool.k.shape, pool.conv.shape)
    m = c.memory_analysis()
    pool_bytes = 2 * pool.k.size * 2 + pool.conv.size * 4
    assert m.alias_size_in_bytes >= pool_bytes
    shaped = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(args))
    # (arguments nobody reads, the float16 scales beside their prepared
    # bits, are pruned: the compiled arguments can be fewer, never a pool
    # more)
    assert m.argument_size_in_bytes < shaped + pool_bytes // 8
