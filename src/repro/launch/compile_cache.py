"""Where JAX keeps its persistent compilation cache for this repo's runs.

    from repro.launch.compile_cache import use_compile_cache
    cache_dir, from_env = use_compile_cache()

Called first thing by `chip_smoke.py` and the `repro.launch` entry points,
never on import of `repro`.  A `JAX_COMPILATION_CACHE_DIR` set in the
environment wins, and then nothing is set in code: JAX reads the variable
itself.  Otherwise the cache lives at the fixed `<repo>/.jax_cache`, so a
later process on the same checkout finds what an earlier one compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def use_compile_cache() -> tuple[str, bool]:
    """Returns (cache directory, whether it came from the environment)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env, True
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR), False
