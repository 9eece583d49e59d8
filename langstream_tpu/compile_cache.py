"""Where JAX's persistent compilation cache lives — decided in one place.

Every process that compiles serving programs (the pod entry point, the CLI,
``chip_smoke.py``, ``bench.py`` children, the ``tools/`` scripts, the test
suite) calls :func:`configure_compile_cache` first. The rule:

- ``JAX_COMPILATION_CACHE_DIR`` set from outside: nothing is touched. The
  operator (or the chip tool) placed the cache; no code sets another path.
- unset: the one fixed directory ``<checkout>/.jax_cache`` (gitignored).
  Fixed because the path is part of what makes a cache findable again — a
  directory named after a pid, a temporary name or the time never hits.

An entry's key covers the program's metadata too (``jax.named_scope`` names,
source locations), which JAX leaves out by default: the scope names the
serving programs carry (docs/OBSERVABILITY.md, "Device profiling") are
metadata only, so without this a cache written before a scope was added or
renamed would keep serving executables whose profile shows the old names.

The variables are exported, so child processes share the parent's cache.
Imports nothing heavy: callers run it before their first ``import jax``.
When JAX is already imported (a test process, an embedding program) the
same directory is handed to ``jax.config`` instead — the cache is only
opened at the first compile, so that is still in time.
"""

from __future__ import annotations

import os
import sys

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
KEY_ENV_VAR = "JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def configure_compile_cache() -> str:
    """Resolve the cache directory (see module docstring); returns it."""
    os.environ[KEY_ENV_VAR] = "1"
    if "jax" in sys.modules:
        sys.modules["jax"].config.update(
            "jax_compilation_cache_include_metadata_in_key", True
        )
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    os.environ[ENV_VAR] = DEFAULT_DIR
    if "jax" in sys.modules:
        sys.modules["jax"].config.update(
            "jax_compilation_cache_dir", DEFAULT_DIR
        )
    return DEFAULT_DIR
