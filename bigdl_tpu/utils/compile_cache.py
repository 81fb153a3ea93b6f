"""Where the persistent XLA compilation cache lives.

One rule, applied by the two entry points that start a process which
compiles for an accelerator (`cli.main` and `chip_smoke.py`); nothing
else in the tree names a cache directory:

* `JAX_COMPILATION_CACHE_DIR` set: JAX reads it itself and no code sets
  another directory;
* unset: `<checkout>/.jax_cache`, a fixed path (the path is part of the
  cache key, so a directory built from a temporary name, a pid or the
  time never hits), and every program is kept, however quickly it
  compiled, so that a second start compiles nothing;
* a process held to the CPU (`JAX_PLATFORMS=cpu`) keeps the cache off:
  XLA:CPU has crashed deserializing its own entries (docs/ci.md), and a
  CPU run must not fill the directory that is later copied to the chip.
"""

from __future__ import annotations

import os
from typing import Optional

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _held_to_cpu() -> bool:
    import jax

    return jax.config.jax_platforms == "cpu"


def enable_compile_cache() -> Optional[str]:
    """Apply the rule above; returns the directory in use (None = off).
    Call before the first compilation."""
    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    if _held_to_cpu():
        return None
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
