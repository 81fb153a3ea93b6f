"""Every operation of a step program lies under a scope of ONE vocabulary
(bigdl_tpu/obs/scopes.py).

For the rehearsal-size configuration of each of the benchmark's configs the
step programs are traced to a jaxpr with the kernels on (the interpreter's
route: what the chip runs, `pallas_call` bodies included) and every equation
is walked, the bodies of `scan`, `cond`, `while`, `jit` and `pallas_call`
with it: the name stack of the equation, under the stacks of the equations it
is nested in, holds a name of the vocabulary. `bench/reduce/scopes.py` reads
the same stack off a profile (`tf_op`), so an equation this test lets through
without a name is device time the reducer calls `unscoped`.

Nothing runs: `jax.make_jaxpr` only.
"""

import pathlib
import re
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from bench import cells  # noqa: E402
from bench.run import merge  # noqa: E402

CONFIGS = sorted(p.stem for p in (ROOT / "bench" / "configs").glob("*.json"))
PROGRAMS = ("engine_decode", "engine_paged_prefill", "engine_first_token",
            "generate_tokens")

# An equation that holds others is judged by them: the device time of a
# `scan` or a `cond` is its body's, and what is left of the `while` it lowers
# to (the loop's counter and the carried tuple) is the row `while` of the
# reducer's table. Every other equation needs a name of its own.
CONTAINERS = ("scan", "while", "cond", "jit", "pjit", "closed_call",
              "core_call", "custom_jvp_call", "custom_vjp_call", "remat",
              "pallas_call")


def _bodies(eqn):
    for v in eqn.params.values():
        for sub in (v if isinstance(v, (list, tuple)) else [v]):
            sub = getattr(sub, "jaxpr", sub)  # a closed one's own
            if hasattr(sub, "eqns"):
                yield sub


def unscoped(jaxpr, names: frozenset, outer: tuple = ()) -> tuple:
    """(equations walked, [(primitive, name stack)] of those under no name
    of `names`). An equation's name stack is relative to the jaxpr it stands
    in, so the walk carries the enclosing equations' stacks along."""
    n, bad = 0, []
    for e in jaxpr.eqns:
        stack = outer + tuple(str(e.source_info.name_stack).split("/"))
        bodies = list(_bodies(e))
        assert bool(bodies) <= (e.primitive.name in CONTAINERS), (
            f"{e.primitive.name} holds equations: walk it, or list it")
        for body in bodies:
            m, b = unscoped(body, names, stack)
            n, bad = n + m, bad + b
        if not bodies:
            n += 1
            if not names.intersection(stack):
                bad.append((e.primitive.name, "/".join(stack)))
    return n, bad


def programs(config: str) -> dict:
    """name -> (the program as the engine jits it, its arguments) of a paged
    engine on the rehearsal-size `config`, and `generate_tokens` of the same
    model with its static arguments bound."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.api import TpuModel, optimize_model
    from bigdl_tpu.generate import GenerationConfig, generate_tokens
    from bigdl_tpu.models import get_family
    from bigdl_tpu.models.config import ModelConfig
    from bigdl_tpu.serving.engine import InferenceEngine

    conf = cells.load_json(str(ROOT), "bench", "configs", f"{config}.json")
    conf = merge(conf, conf["bench"].get("rehearsal"))  # as `--rehearse`
    cfg = ModelConfig.from_hf_config(cells.as_run(conf))
    qtype, e = conf["bench"]["qtype"], conf["bench"]["engine"]
    family = get_family(cfg.model_type)
    model = TpuModel(cfg, optimize_model(
        family.init_params(cfg, jax.random.PRNGKey(0)), cfg, qtype), qtype)
    eng = InferenceEngine(
        model, n_slots=e["n_slots"], max_len=e["max_len"], paged=True,
        page_size=e["page_size"], n_pages=e["n_pages"],
        gen=GenerationConfig(eos_token_id=None))
    B, V, z = e["n_slots"], cfg.vocab_size, jnp.zeros
    params, key = model.params, jax.random.PRNGKey(0)
    sampling = (z((B,)), z((B,), jnp.int32), z((B,)), z((B,), bool))
    table = z((1, eng.max_pages_per_row), jnp.int32)
    out = {"engine_paged_prefill": (eng._paged_prefill, (
        params, eng.kind.leaves(eng.cache), (table, table),
        z((1,), jnp.int32), z((1, 64), jnp.int32), z((), jnp.int32),
        z((1,), jnp.int32)))}
    if eng.blocks is not None:  # a pass over every row's block; a slot is
        # opened by `arm`, and no first token is sampled
        b = cfg.block_length
        out["engine_decode"] = (eng._decode, (
            params, eng.blocks.state, eng.cache, key, *sampling))
        out["engine_first_token"] = (eng._arm_block, (
            eng.blocks.state, z((), jnp.int32), z((b,), jnp.int32),
            z((b,), bool)))
    else:
        out["engine_decode"] = (eng._decode, (
            params, z((B,), jnp.int32), eng.cache, key, *sampling, eng.seen,
            z((B,))))
        out["engine_first_token"] = (eng._first_token, (
            z((V,)), key, z(()), z((), jnp.int32), z(()), z((), bool),
            z(()), z((V,), bool), z((), jnp.int32), z((B,), jnp.int32),
            eng.seen))
    gen = GenerationConfig(max_new_tokens=4, eos_token_id=None)

    def generate(params, tokens, start, key):
        return generate_tokens.__wrapped__(
            cfg, params, tokens, start, key, gen, model.forward_fn, 32,
            cache_init=getattr(family, "init_cache", None))

    out["generate_tokens"] = (generate, (
        params, z((1, 16), jnp.int32), z((1,), jnp.int32), key))
    return out


@pytest.fixture(scope="module")
def traced():
    """config -> its programs, built once a config for the four cases; the
    kernels on while this module's tests run (`TpuModel` prepares the
    kernels' operands where they are in use)."""
    built = {}

    def get(config):
        if config not in built:
            built[config] = programs(config)
        return built[config]

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("BIGDL_TPU_PALLAS", "interpret")
        yield get


@pytest.mark.core
@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("config", CONFIGS)
def test_every_equation_of_a_step_program_lies_under_a_scope(
        traced, config, program):
    import jax

    from bigdl_tpu.obs.scopes import VOCABULARY

    fn, args = traced(config)[program]
    jaxpr = jax.make_jaxpr(getattr(fn, "__wrapped__", fn))(*args)
    n, bad = unscoped(jaxpr.jaxpr, frozenset(VOCABULARY))
    assert n > 10
    assert not bad, f"{len(bad)} of {n} equations under no scope: {bad[:8]}"


def test_the_vocabulary_is_short_and_checked_where_a_program_is_traced():
    from bigdl_tpu.obs.scopes import VOCABULARY, scope

    assert len(VOCABULARY) == len(set(VOCABULARY)) <= 20
    assert "norm_rope" not in VOCABULARY
    assert all(re.fullmatch(r"[a-z0-9_]+(\.[a-z0-9_]+)*", n)
               for n in VOCABULARY)
    with scope("attn.proj"):
        pass
    with pytest.raises(ValueError, match="nope"):
        scope("nope")


def test_no_bare_named_scope_is_left_in_the_program():
    bare = [str(p.relative_to(ROOT))
            for p in sorted((ROOT / "bigdl_tpu").rglob("*.py"))
            if "jax.named_scope(" in p.read_text()
            and p.relative_to(ROOT).as_posix() != "bigdl_tpu/obs/scopes.py"]
    assert bare == []
