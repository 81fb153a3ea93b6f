"""Test harness: run everything on a virtual 8-device CPU mesh.

The reference tests only on real self-hosted accelerators (SURVEY.md §4);
XLA lets us do better — distributed paths compile and execute against
`--xla_force_host_platform_device_count=8` fake CPU devices, so TP/PP/DP
shardings are exercised in CI without hardware.

Must set the flags before jax initializes a backend, hence module-level.
"""

import os

# Tests run on fake CPU devices and never claim a chip: JAX_PLATFORMS=cpu
# is sufficient.
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# The persistent compilation cache stays OFF for the test suite, whatever
# the environment says: XLA:CPU's AOT loader has rejected its own cache
# entries ("Target machine feature +prefer-no-gather is not supported on
# the host machine") and intermittently SEGFAULTED inside
# compilation_cache.get_executable_and_time on deserialize (observed
# 2026-07-30 with a fresh cache dir, so not stale-entry poisoning;
# docs/ci.md). Fresh compiles cost ~1 extra minute per full run; a
# segfaulted suite costs everything.
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
# Sixteen test files import `transformers`, which imports TensorFlow unless
# told not to: 46 s for the first test of a worker to do so, 12 s for every
# later worker, 7 s with this (ISSUE 58). No test uses a TensorFlow model.
os.environ.setdefault("USE_TF", "0")

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)
jax.config.update("jax_compilation_cache_dir", None)
# XLA compiles with most of its optimization passes off: the suite's CPU
# time is mostly compiling programs that then run once, on tiny shapes
# (ISSUE 58: about 600 of tier-1's 6,100 test-seconds between two runs on
# one machine; files that mostly INTERPRET kernels run a little slower). What it trades is the
# quality of the CPU's code, which no test measures. The pinned Mosaic
# modules are hashes of LOWERED text and do not see it; the compiles for a
# described TPU would (every backend is handed the option), so
# `test_tpu_lowering.py` turns it off for its module.
jax.config.update("jax_disable_most_optimizations", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_per_module():
    """Bound native-state growth across the 300+ test suite: one long
    process accumulating every compiled executable has produced
    intermittent XLA:CPU compiler segfaults near the end of the run
    (2026-07-30, crash inside backend_compile_and_load with 120 GB
    free — not OOM). Dropping compiled-computation caches between
    modules keeps the process young at a modest recompile cost."""
    yield
    import engines  # tests/engines.py: the engines' programs, shared a module

    engines.forget()
    jax.clear_caches()
