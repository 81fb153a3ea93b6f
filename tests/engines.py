"""Engines for the tests: a NEW `InferenceEngine` every call, the compiled
programs of the first one of its shape.

An engine's programs are `jax.jit` objects over closures of that engine
(`engine_decode`, `engine_first_token`, `engine_paged_prefill`, ...), so two
engines built alike in two tests trace, lower and compile the same programs
twice: most of what the engine's test files cost (ISSUE 46: 2,472 of tier-1's
7,018 test-seconds in eight files). `shared_engine(model, **options)` builds
the engine the constructor builds, host state and pool and all, and then
hands it the program objects of the first engine this MODULE built from the
same weights, config and mesh with the same program-shaping options under
the same `BIGDL_TPU_*` switches: what those closures read of their own engine
and of the environment at trace time (`config`, `kind`, the geometry,
`logprobs_top_k`, `quantize_kv`, the kernels' route) is then equal by
construction, and a call with the same shapes finds the donor's compiled
code. Everything else of an engine is its own, so a test may still
monkeypatch an attribute of its instance (`eng._book_ahead`,
`eng._paged_prefill = spy`).

A test that is ABOUT what an engine traces calls the constructor itself:
retrace counters, `ops.routes.record_routes` (routes are noted while
tracing), `.lower`. Programs compiled ahead in `__init__` (`adaptive_draft`)
stay the engine's own. `tests/conftest.py` forgets the donors after each
module, where it drops JAX's caches.
"""

import inspect
import os

from bigdl_tpu.serving.engine import InferenceEngine

#: the constructor options a traced program depends on, beside the model
_SHAPES = ("n_slots", "max_len", "paged", "page_size", "n_pages",
           "speculative", "draft_k", "logprobs_top_k", "quantize_kv")
#: the attributes that hold an engine's programs
_PROGRAMS = ("_decode", "_first_token", "_arm_block", "_prefill",
             "_insert", "_paged_prefill", "_copy_page", "_swap_in",
             "_dense_swap_in", "_spec_decode")
_SIGNATURE = inspect.signature(InferenceEngine.__init__)
_donors: dict = {}


def shared_engine(model, *args, **kwargs) -> InferenceEngine:
    eng = InferenceEngine(model, *args, **kwargs)
    bound = _SIGNATURE.bind(None, model, *args, **kwargs)
    bound.apply_defaults()
    opts = bound.arguments
    if opts["adaptive_draft"]:  # its programs were compiled in __init__
        return eng
    # a model is its weights, its config and its mesh, whatever `TpuModel`
    # wraps them; the kernels' switches are read while tracing
    whose = (model.params, model.config, getattr(model, "mesh", None),
             opts["draft_params"])
    switches = tuple(sorted(
        kv for kv in os.environ.items() if kv[0].startswith("BIGDL_TPU_")))
    key = (*map(id, whose), getattr(model, "qtype", None), switches,
           *(opts[name] for name in _SHAPES))
    # the entry keeps `whose` alive: their ids are not given out again
    _, programs = _donors.setdefault(key, (whose, {
        name: getattr(eng, name) for name in _PROGRAMS
        if hasattr(eng, name)}))
    for name, program in programs.items():
        setattr(eng, name, program)
    return eng


def forget() -> None:
    """Drop the donors (and with them the engines their closures hold)."""
    _donors.clear()
