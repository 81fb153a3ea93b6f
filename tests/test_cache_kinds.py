"""The paged cache kinds, each clause of `kvpaged.CacheKind` at toy sizes.

What `serving/engine.InferenceEngine` asks of a paged cache it asks one
object (`engine.kind`, docs/serving.md "Cache kinds"): KV pages and latent
pages (kvpaged.py), a state row a slot (kvstate.py), a state row beside
pages (kvhybrid.py), two groups of pages (kvwindow.py). Every test here is
one clause of that protocol over the five kinds, on pools of a few hundred
KB, with no engine built and no engine program compiled. The names of span
arguments and of `/metrics` families are written out HERE: the benchmark's
readers (bench/reduce, bench/metrics) take them by name, and a kind that
renames one must fail a test that does not import the name from it.
"""

import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import kvhybrid, kvpaged, kvsparse, kvstate, kvwindow
from bigdl_tpu.models import get_family
from bigdl_tpu.models.config import PRESETS, ModelConfig
from bigdl_tpu.serving.engine import _cache_kind, _PrefillState
from bigdl_tpu.serving.pages import PageTable

pytestmark = pytest.mark.core

_DENSE = dict(hidden_size=128, intermediate_size=256, num_hidden_layers=2,
              num_attention_heads=4, num_key_value_heads=2, vocab_size=512,
              rms_norm_eps=1e-5, rope_theta=1e4, max_position_embeddings=2048,
              tie_word_embeddings=False)
CONFIGS = {
    "kv_pages": ModelConfig.from_hf_config(dict(_DENSE, model_type="mistral")),
    "latent_pages": ModelConfig.from_hf_config(dict(
        _DENSE, model_type="glm4_moe_lite", num_hidden_layers=3,
        num_key_value_heads=4, moe_intermediate_size=128, n_routed_experts=8,
        num_experts_per_tok=2, n_shared_experts=1, first_k_dense_replace=1,
        n_group=1, topk_group=1, topk_method="noaux_tc", norm_topk_prob=True,
        routed_scaling_factor=1.8, q_lora_rank=128, kv_lora_rank=96,
        qk_nope_head_dim=32, qk_rope_head_dim=32, v_head_dim=64,
        rope_scaling=None)),
    "power_retention": ModelConfig.from_hf_config(
        dict(_DENSE, model_type="brumby", head_dim=32)),
    "state_beside_pages": PRESETS["tiny-granite-hybrid"],
    "window_pages_beside_pages": PRESETS["tiny-smallthinker"],
    "selected_pages_beside_state": ModelConfig.from_hf_config(dict(
        _DENSE, model_type="minicpm_sala", num_hidden_layers=3,
        mixer_types=["minicpm4", "lightning-attn", "minicpm4"], head_dim=32,
        lightning_nh=4, lightning_nkv=4, lightning_head_dim=32,
        rms_norm_eps=1e-6, scale_emb=12, scale_depth=1.4, dim_model_base=32,
        sparse_config=dict(kernel_size=8, kernel_stride=4, block_size=8,
                           topk=4, init_blocks=1, window_size=8,
                           dense_len=16))),
}
KINDS = {
    "kv_pages": kvpaged.KV_PAGES,
    "latent_pages": kvpaged.LATENT_PAGES,
    "power_retention": kvstate.CACHE_KIND,
    "state_beside_pages": kvhybrid.CACHE_KIND,
    "window_pages_beside_pages": kvwindow.CACHE_KIND,
    "selected_pages_beside_state": kvsparse.CACHE_KIND,
}
NAMES = sorted(KINDS)
N_SLOTS, MAX_LEN, PAGE = 4, 64, 8


def _geometry(kind) -> kvpaged.Geometry:
    page, n_pages = kind.page_geometry(N_SLOTS, MAX_LEN, PAGE, 17)
    return kvpaged.Geometry(N_SLOTS, MAX_LEN, page, n_pages,
                            -(-MAX_LEN // page))


def _pool(name, fill: bool = True):
    """The kind's pool; `fill`: every array random, so that a copy that
    misses a layer or a field shows."""
    kind = KINDS[name]
    pool = kind.make_pool(CONFIGS[name], _geometry(kind))
    if not fill:
        return pool
    rng = np.random.default_rng(0)
    return kind.with_leaves(pool, tuple(
        None if a is None else
        jnp.asarray(rng.standard_normal(a.shape), a.dtype)
        for a in kind.leaves(pool)))


def _table(name) -> PageTable:
    kind, geo = KINDS[name], _geometry(KINDS[name])
    return PageTable(N_SLOTS, geo.n_pages, geo.page_size,
                     geo.max_pages_per_row, MAX_LEN,
                     share_prefixes=kind.share_prefixes,
                     window=kind.window(CONFIGS[name]))


@pytest.mark.parametrize("name", NAMES)
def test_the_kind_is_chosen_from_the_model_alone(name):
    cfg = CONFIGS[name]
    model = types.SimpleNamespace(config=cfg,
                                  family=get_family(cfg.model_type))
    kind = _cache_kind(model)
    assert kind is KINDS[name] and kind.name == name
    # what it tells the page table
    assert kind.share_prefixes == (name in ("kv_pages", "latent_pages"))
    assert kind.window(cfg) == (
        cfg.sliding_window if name == "window_pages_beside_pages" else None)
    page, n_pages = kind.page_geometry(N_SLOTS, MAX_LEN, PAGE, 17)
    assert (page, n_pages) == ((MAX_LEN, N_SLOTS + 1)
                               if name == "power_retention" else (PAGE, 17))


@pytest.mark.parametrize("name", NAMES)
def test_leaves_round_trip_to_the_same_pytree(name):
    kind, pool = KINDS[name], _pool(name, fill=False)
    leaves = kind.leaves(pool)
    assert len(leaves) == len(kind.arrays)
    # the pool's arrays and nothing else: no table, no position
    big = {id(a) for a in jax.tree.leaves(pool) if a.ndim >= 4}
    assert big == {id(a) for a in jax.tree.leaves(leaves)}
    again = kind.with_leaves(pool, leaves)
    assert jax.tree.structure(again) == jax.tree.structure(pool)
    assert all(a is b for a, b in zip(jax.tree.leaves(again),
                                      jax.tree.leaves(pool)))
    swapped = kind.with_leaves(pool, jax.tree.map(lambda a: a + 1, leaves))
    assert all(np.all(np.asarray(a, np.float32) == 1)
               for a in jax.tree.leaves(kind.leaves(swapped)))
    assert swapped.block_tables is pool.block_tables


@pytest.mark.parametrize("name", NAMES)
def test_swap_out_then_in_restores_every_array_bit_for_bit(name):
    kind, pool = KINDS[name], _pool(name)
    # a page is a row for a state (page p is row p - 1): stay inside 1..4
    src, dst = ([1, 2], 0, [3]), ([4, 3], 2, [1])
    blob = kind.swap_out(pool, *src)
    parked = kind.leaves(blob)
    assert all(a is None or (isinstance(a, np.ndarray) and a.any())
               for a in parked)
    assert blob.nbytes == sum(a.nbytes for a in parked if a is not None)
    empty = kind.with_leaves(pool, jax.tree.map(jnp.zeros_like,
                                                kind.leaves(pool)))
    into = (jnp.asarray(dst[0], jnp.int32), jnp.asarray(dst[1]),
            jnp.asarray(dst[2], jnp.int32))
    back = kind.leaves(kind.swap_out(kind.swap_in(empty, parked, into), *dst))
    for a, b in zip(parked, back):
        assert (a is None) == (b is None)
        if a is not None:
            assert a.dtype == b.dtype and a.tobytes() == b.tobytes()


@pytest.mark.parametrize("name", NAMES)
def test_copy_page_copies_every_layer_and_nothing_else(name):
    kind, pool = KINDS[name], _pool(name)
    got = kind.copy_page(pool, jnp.asarray(1), jnp.asarray(3))
    row = name == "power_retention"  # page p is state row p - 1
    fields = kind.arrays if row else kind.page_arrays
    assert fields
    for f in fields:
        a, b = getattr(pool, f), getattr(got, f)
        if a is None:
            assert b is None
            continue
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        s, d = (0, 2) if row else (1, 3)
        assert np.array_equal(b[:, d], a[:, s]) and a[:, s].any()
        rest = [i for i in range(a.shape[1]) if i != d]
        assert np.array_equal(b[:, rest], a[:, rest])
    for f in set(kind.arrays) - set(fields):  # a state row, a second group
        assert getattr(got, f) is getattr(pool, f)


_NOT_WIRED = "{what} is not wired for {kind} \\({model}\\) yet \\(ROADMAP {r}\\)"
_WHAT = {"quantize_kv": "quantize_kv", "speculative": "speculative serving",
         "adapters": "adapter serving",
         "prefill_chunk_tokens": "prefill_chunk_tokens"}
# kind -> (its name in a refusal, model type, ROADMAP item, what it refuses)
_REFUSES = {
    "kv_pages": ("kv_pages", "mistral", None, ()),
    "latent_pages": ("latent pages", "glm4_moe_lite", "R1",
                     ("quantize_kv", "speculative", "adapters")),
    "power_retention": ("power_retention", "brumby", None,
                        ("quantize_kv", "speculative")),
    "state_beside_pages": ("state_beside_pages", "granitemoehybrid", "R4",
                           ("quantize_kv", "speculative", "adapters")),
    "window_pages_beside_pages": (
        "window_pages_beside_pages", "smallthinker", "R3",
        ("quantize_kv", "speculative", "adapters", "prefill_chunk_tokens")),
    "selected_pages_beside_state": (
        "selected_pages_beside_state", "minicpm_sala", "R11",
        ("quantize_kv", "speculative", "adapters", "prefill_chunk_tokens")),
}
_OWN = {  # today's sentences where they are not "not wired"
    ("power_retention", "paged"):
        r"power_retention \(brumby\) is served with paged=True: a slot's "
        "recurrent state is a row the page table owns, and there is no "
        "dense pool of keys to fall back on",
    ("power_retention", "quantize_kv"):
        r"quantize_kv is not available for power_retention \(brumby\): the "
        "cache is a float32 recurrent state, not keys and values",
    ("power_retention", "speculative"):
        r"speculative serving is not available for power_retention "
        r"\(brumby\): a rejected draft cannot be taken back out of a "
        "recurrent state by moving `pos`",
    ("state_beside_pages", "paged"):
        r"state_beside_pages \(granitemoehybrid\) is served with paged=True: "
        "a slot holds KV pages for the attention layers and a state row for "
        "the others",
    ("selected_pages_beside_state", "paged"):
        r"selected_pages_beside_state \(minicpm_sala\) is served with "
        "paged=True: a slot holds KV pages and pooled keys for the sparse "
        "layers and a state row for the others",
    ("selected_pages_beside_state", "prefill_chunk_tokens"):
        r"prefill_chunk_tokens is not wired for selected_pages_beside_state "
        r"\(minicpm_sala\) yet \(ROADMAP R11\): a prefill runs a whole "
        "prompt from an empty row",
    ("window_pages_beside_pages", "paged"):
        r"window_pages_beside_pages \(smallthinker\) is served with "
        "paged=True: a slot holds KV pages for the attention layers in two "
        "groups, and frees the window group's behind the window",
}


@pytest.mark.parametrize("feature", ["paged", *_WHAT])
@pytest.mark.parametrize("name", NAMES)
def test_refusals_are_todays_sentences(name, feature):
    kind = KINDS[name]
    label, model, where, refused = _REFUSES[name]
    assert CONFIGS[name].model_type == model
    if feature == "paged":
        ask, want = dict(paged=False), _OWN.get((name, "paged"))
    else:
        ask = dict(paged=True, **{feature: True})
        want = _OWN.get((name, feature)) if feature in refused else None
        if feature in refused and want is None:
            want = _NOT_WIRED.format(what=_WHAT[feature], kind=label,
                                     model=model, r=where)
    if want is None:
        kind.check(model, **ask)
        return
    with pytest.raises(NotImplementedError) as e:
        kind.check(model, **ask)
    assert re.fullmatch(want, str(e.value)), str(e.value)
    # asked for nothing, it serves
    kind.check(model, paged=True, quantize_kv=False, speculative=False,
               adapters=False, prefill_chunk_tokens=False)


# the names bench/reduce and bench/metrics read, by kind: the `prefill`
# span's arguments, the `decode_step` span's, the `/metrics` families
_SPANS = {
    "kv_pages": (["pages_written", "row_pages"],
                 ["grid_pages", "live_pages"], []),
    "latent_pages": (["latent_tokens_upprojected"],
                     ["attn_grid_steps", "attn_live_groups", "grid_pages",
                      "latent_bytes_read", "latent_live_tokens",
                      "live_pages"],
                     ["bigdl_tpu_latent_pages_in_use",
                      "bigdl_tpu_latent_token_bytes"]),
    "power_retention": (["state_chunks"],
                        ["state_bytes_moved", "state_rows_live"],
                        ["bigdl_tpu_state_rows_live",
                         "bigdl_tpu_state_pool_bytes",
                         "bigdl_tpu_state_bytes_moved_total"]),
    "state_beside_pages": (["state_chunks"],
                           ["grid_pages", "live_pages", "state_bytes_moved",
                            "state_rows_live"],
                           ["bigdl_tpu_state_rows_live",
                            "bigdl_tpu_state_pool_bytes",
                            "bigdl_tpu_state_bytes_moved_total"]),
    "window_pages_beside_pages": (
        ["pages_written_global", "pages_written_window", "row_pages"],
        ["grid_pages_global", "grid_pages_window", "live_pages_global",
         "live_pages_window", "window_pages_freed", "window_pages_held",
         "window_pages_unfreed"],
        ["bigdl_tpu_global_pages_in_use", "bigdl_tpu_window_pages_in_use",
         "bigdl_tpu_window_pages_freed_total"]),
    # (the report's counts, `sparse_pages_selected` and the rest, join the
    # spans where the engine has fetched them: tests/test_minicpm_sala.py)
    "selected_pages_beside_state": (
        ["state_chunks"],
        ["grid_pages", "live_pages", "state_bytes_moved",
         "state_rows_live"],
        ["bigdl_tpu_state_rows_live", "bigdl_tpu_state_pool_bytes",
         "bigdl_tpu_state_bytes_moved_total",
         "bigdl_tpu_sparse_pages_selected_total",
         "bigdl_tpu_sparse_pages_read_total",
         "bigdl_tpu_pooled_keys_written_total",
         "bigdl_tpu_sparse_selected_page_share"]),
}


def _chunk(name, written: int, bucket: int, n: int) -> _PrefillState:
    st = _PrefillState(req=None, slot=0, row=None, written=written, path=[],
                       chunk=n)
    kind = KINDS[name]
    kind.note_chunk(st, CONFIGS[name], _geometry(kind), bucket, n,
                    _pool(name, fill=False))
    return st


@pytest.mark.parametrize("name", NAMES)
def test_span_arguments_keep_the_names_the_benchmark_reads(name):
    kind, cfg = KINDS[name], CONFIGS[name]
    prefill, decode, _ = _SPANS[name]
    st = _chunk(name, 0, 32, 30)
    args = kind.prefill_args(st)
    assert sorted(args) == prefill
    assert all(isinstance(v, int) and v > 0 for v in args.values()), args
    table = _table(name)
    table.reserve(0, list(range(1, 20)))
    live = np.array([True, False, False, False])
    args = kind.decode_args(cfg, table, live, 2 * 4096,
                            _pool(name, fill=False))
    assert sorted(args) == decode
    assert all(isinstance(v, int) for v in args.values()), args
    if "state_bytes_moved" in args:
        assert (args["state_rows_live"], args["state_bytes_moved"]) \
            == (1, 8192)


@pytest.mark.parametrize("name", NAMES)
def test_a_chunk_adds_what_the_kind_counts(name):
    """Two chunks, 0..31 and 32..63 of a 64-token row of eight pages."""
    kind, geo = KINDS[name], _geometry(KINDS[name])
    st = _chunk(name, 0, 32, 32)
    st.written = 32
    kind.note_chunk(st, CONFIGS[name], geo, 32, 20, _pool(name, fill=False))
    got = (st.state_chunks, st.upprojected, st.row_pages, st.pages_written,
           st.window_pages_written)
    cfg = CONFIGS[name]
    assert got == {
        "kv_pages": lambda: (0, 0, 16, 8, 0),
        "latent_pages": lambda: (0, 128, 0, 0, 0),
        "power_retention": lambda: (
            2 * kvstate.prefill_chunks(32), 0, 0, 0, 0),
        "state_beside_pages": lambda: (
            2 * kvhybrid.prefill_chunks(32, cfg.mamba_chunk_size), 0, 0, 0, 0),
        "selected_pages_beside_state": lambda: (
            2 * kvsparse.prefill_chunks(32, cfg.mamba_chunk_size), 0, 0, 0, 0),
        "window_pages_beside_pages": lambda: (0, 0, 32, 8, sum(
            kvwindow.window_pages_spanned(w, 32, n, cfg.sliding_window,
                                          PAGE, 8)
            for w, n in ((0, 32), (32, 20)))),
    }[name]()


@pytest.mark.parametrize("name", NAMES)
def test_metrics_families_keep_their_names(name):
    kind, pool = KINDS[name], _pool(name, fill=False)
    engine = types.SimpleNamespace(
        kind=kind, config=CONFIGS[name], pages=_table(name), n_slots=N_SLOTS,
        active=np.array([True, True, False, False]), state_bytes_moved=12,
        state_row_bytes=kind.state_row_nbytes(pool),
        report_totals={"sparse_pages_read": 3, "sparse_pages_live": 4})
    got = kind.metrics(engine)
    assert [m[0] for m in got] == _SPANS[name][2]
    for family, typ, text, value in got:
        assert typ == ("counter" if family.endswith("_total") else "gauge")
        assert text and "\n" not in text and isinstance(
            value, float if family.endswith("_share") else int)
    from bigdl_tpu.serving.metrics import expected_families

    assert set(_SPANS[name][2]) <= set(expected_families(engine))
    assert bool(kind.state_row_nbytes(pool)) == ("state" in "".join(
        _SPANS[name][0]))
    assert bool(kind.token_nbytes(CONFIGS[name])) == (name == "latent_pages")


@pytest.mark.parametrize("name", NAMES)
def test_a_prefill_gives_back_the_pool_it_was_lent(name):
    """`row_view` then `write_back`, traced and not run: the leaves come
    back in their shapes (what `donate_argnames=("pool",)` aliases), and
    only the gathering kinds prefill a row that is not the pool."""
    kind, cfg, geo = KINDS[name], CONFIGS[name], _geometry(KINDS[name])
    pool = _pool(name, fill=False)
    table = jnp.zeros((1, geo.max_pages_per_row), jnp.int32)
    seen = {}

    def program(leaves, last_idx):
        one, row = kind.row_view(leaves, (table, table), jnp.zeros(
            (1,), jnp.int32), last_idx, jnp.zeros((1,), jnp.int32), cfg, geo)
        seen["same"] = row is one
        return kind.write_back(one, row, 16, last_idx, cfg)

    leaves = kind.leaves(pool)
    out = jax.eval_shape(program, leaves, jnp.asarray(11))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), out) \
        == jax.tree.map(lambda a: (a.shape, a.dtype), leaves)
    assert seen["same"] == (name not in ("kv_pages",
                                         "window_pages_beside_pages"))
    assert kind.forward_kw(3) == (
        {"logits_at": 3} if name in ("window_pages_beside_pages",
                                     "selected_pages_beside_state") else {})


# ---------------------------------------------------------------------------
# `state_beside_pages` holds THREE state layouts (ISSUE 56, 61): Mamba-2's
# (granite: `ssm [Lm, R, inner, d_state]`, `conv [Lm, K - 1, R, C]`),
# Mamba-1's (jamba: `ssm [Lm, R, d_state, E]`, `conv [Lm, R, (K - 1) * E]`)
# and a short convolution's (lfm2_moe: NO `ssm`, `conv [Lc, R, (K - 1) * H]`:
# the tail is all the state), with nothing in the kind that asks which
# ---------------------------------------------------------------------------

_LAYOUTS = {
    # preset: (ssm after the row axis or None, conv's shape with R its
    # rows, the axis of conv that is the row, the prefill span's argument,
    # the name of the state layers in `layer_types`, the K - 1 inputs a tail
    # holds)
    "tiny-granite-hybrid": (
        lambda c: (c.mamba_n_heads * c.mamba_d_head, c.mamba_d_state),
        lambda c, R: (3, R, c.mamba_n_heads * c.mamba_d_head
                      + 2 * c.mamba_d_state), 2, "state_chunks", "mamba", 3),
    "tiny-jamba": (
        lambda c: (c.mamba_d_state, 2 * c.hidden_size),
        lambda c, R: (R, 3 * 2 * c.hidden_size), 1, "scan_tokens", "mamba",
        3),
    "tiny-lfm2-moe": (
        lambda c: None,
        lambda c, R: (R, 2 * c.hidden_size), 1, None, "conv", 2),
    # a delta rule's (solar_open2, ISSUE 65): lightning's state layout
    # behind the tails of three convolutions in one piece
    "tiny-solar-open2": (
        lambda c: (c.kda_heads * c.kda_head_dim, c.kda_head_dim),
        lambda c, R: (R, 3 * 3 * c.kda_heads * c.kda_head_dim), 1,
        "state_chunks", "kda", 3),
}


def _layout_pool(preset):
    cfg, kind = PRESETS[preset], kvhybrid.CACHE_KIND
    pool = kind.make_pool(cfg, _geometry(kind))
    rng = np.random.default_rng(1)
    return cfg, kind.with_leaves(pool, tuple(
        None if a is None else
        jnp.asarray(rng.standard_normal(a.shape), a.dtype)
        for a in kind.leaves(pool)))


@pytest.mark.parametrize("preset", sorted(_LAYOUTS))
def test_state_beside_pages_is_both_families_kind(preset):
    cfg = PRESETS[preset]
    model = types.SimpleNamespace(config=cfg,
                                  family=get_family(cfg.model_type))
    assert _cache_kind(model) is kvhybrid.CACHE_KIND
    state, conv, rows_axis, _, layers, taps = _LAYOUTS[preset]
    cfg, pool = _layout_pool(preset)
    Lm = sum(k == layers for k in cfg.layer_types)
    if state(cfg) is None:  # the tail is all the state
        assert pool.ssm is None
    else:
        assert pool.ssm.shape == (Lm, N_SLOTS) + state(cfg)
    assert pool.conv.shape == (Lm,) + conv(cfg, N_SLOTS)
    assert pool.n_rows == N_SLOTS and pool.conv_rows == rows_axis
    assert pool.k.shape[0] == len(cfg.layer_types) - Lm
    # a row's bytes: the state (where there is one) and the K - 1 inputs of
    # the convolution over its channels, float32, every state layer
    kind = kvhybrid.CACHE_KIND
    channels = int(np.prod(conv(cfg, 1))) // taps
    assert kind.state_row_nbytes(pool) == kvhybrid.row_nbytes(pool) == (
        Lm * (int(np.prod(state(cfg) or (0,))) + taps * channels) * 4)
    assert kind.axes_of(pool) == (1, 1, rows_axis, 1)
    assert kind._spots("pages", "slot", None) == (
        "pages", "pages", "slot", "slot")


@pytest.mark.parametrize("preset", sorted(_LAYOUTS))
def test_both_layouts_park_and_restore_a_row_bit_for_bit(preset):
    kind = kvhybrid.CACHE_KIND
    cfg, pool = _layout_pool(preset)
    Lm = pool.conv.shape[0]
    blob = kind.swap_out(pool, [1, 2], 1, [])
    assert blob.conv.size == pool.conv.size // N_SLOTS
    assert blob.conv.tobytes() == np.asarray(
        pool.conv[:, 1] if pool.conv_rows == 1
        else pool.conv[:, :, 1]).tobytes()
    if pool.ssm is None:  # nothing is parked where nothing is kept
        assert blob.ssm is None
        assert blob.conv.nbytes == kind.state_row_nbytes(pool)
    else:
        assert blob.ssm.shape == (Lm,) + pool.ssm.shape[2:]
        assert blob.ssm.tobytes() == np.asarray(pool.ssm[:, 1]).tobytes()
        assert blob.ssm.nbytes + blob.conv.nbytes \
            == kind.state_row_nbytes(pool)
    empty = kind.with_leaves(pool, jax.tree.map(jnp.zeros_like,
                                                kind.leaves(pool)))
    into = (jnp.asarray([4, 3], jnp.int32), jnp.asarray(2),
            jnp.asarray([], jnp.int32))
    there = kind.swap_in(empty, kind.leaves(blob), into)
    assert there.conv_rows == pool.conv_rows
    back = kind.leaves(kind.swap_out(there, [4, 3], 2, []))
    for a, b in zip(kind.leaves(blob), back):
        assert (a is None and b is None) or (
            a.dtype == b.dtype and a.tobytes() == b.tobytes())
    # the other rows of the pool it was written into stay zeros
    rows = np.moveaxis(np.asarray(there.conv), there.conv_rows, 1)
    assert not rows[:, [0, 1, 3]].any() and rows[:, 2].any()
    if there.ssm is not None:
        assert not np.asarray(there.ssm[:, [0, 1, 3]]).any()


@pytest.mark.parametrize("preset", sorted(_LAYOUTS))
def test_both_layouts_count_a_prefill_their_own_way(preset):
    kind = kvhybrid.CACHE_KIND
    cfg, want = PRESETS[preset], _LAYOUTS[preset][3]
    st = _PrefillState(req=None, slot=0, row=None, written=0, path=[],
                       chunk=30)
    pool = kind.make_pool(cfg, _geometry(kind))  # the family's `counts`
    kind.note_chunk(st, cfg, _geometry(kind), 32, 30, pool)
    st.written = 30
    kind.note_chunk(st, cfg, _geometry(kind), 16, 9, pool)
    args = kind.prefill_args(st)
    if want is None:  # a tail is no prefill form: nothing to count
        assert args == {}
        return
    assert list(args) == [want]
    assert args[want] == (39 if want.endswith("_tokens") else sum(
        kvhybrid.prefill_chunks(b, cfg.mamba_chunk_size) for b in (32, 16)))


@pytest.mark.parametrize("preset", sorted(_LAYOUTS))
def test_both_layouts_prefill_on_the_pool_itself(preset):
    """`row_view`: the row IS the pool behind a one-row table, with the
    family's own tail layout, and comes back in the pool's shapes."""
    kind = kvhybrid.CACHE_KIND
    cfg, pool = _layout_pool(preset)
    geo = _geometry(kind)
    table = jnp.zeros((1, geo.max_pages_per_row), jnp.int32)
    seen = {}

    def program(leaves, last_idx):
        one, row = kind.row_view(leaves, (table, table), jnp.zeros(
            (1,), jnp.int32), last_idx, jnp.asarray([2], jnp.int32), cfg, geo)
        seen.update(same=row is one, conv_rows=row.conv_rows)
        return kind.write_back(one, row, 16, last_idx, cfg)

    leaves = kind.leaves(pool)
    out = jax.eval_shape(program, leaves, jnp.asarray(11))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), out) \
        == jax.tree.map(lambda a: (a.shape, a.dtype), leaves)
    assert seen == dict(same=True, conv_rows=pool.conv_rows)
