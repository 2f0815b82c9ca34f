"""JAX's persistent compilation cache for this repo's entry points.

`enable_compile_cache()` is called by `chip_smoke.py`, `launch/serve.py`,
`launch/train.py` and `benchmarks/run.py` before their first compile, and
never on library import: tests and library users keep JAX's own settings.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# A fixed directory inside the checkout (listed in .gitignore). The cache's
# path is part of its key, so a temp-, pid- or time-named directory would
# never be hit again.
DEFAULT_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    With $JAX_COMPILATION_CACHE_DIR set, JAX already reads it, and nothing
    else is set here. Otherwise the cache goes to `DEFAULT_DIR`."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
