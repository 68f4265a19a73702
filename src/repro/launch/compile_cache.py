"""Where the entry points keep JAX's persistent compilation cache.

A cold process pays for every compile; a later process of the same
checkout loads what an earlier one wrote. ``JAX_COMPILATION_CACHE_DIR``,
when set, wins: JAX reads the variable itself and nothing here touches
the config. Otherwise the cache goes to ``<checkout>/.jax_cache``, a
fixed path (gitignored), so every run of this checkout looks in the same
place.

Called from ``launch.serve.main``, ``launch.train.main`` and
``chip_smoke.py`` — never at import, so tests and library users write no
cache they did not ask for.
"""

from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's compilation cache at its directory; returns it."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
