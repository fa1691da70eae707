"""Where compiled programs persist between processes.

JAX's persistent compilation cache keys each entry on the cache directory's
path among other things, so a directory that moves (a temp name, a pid, a
timestamp) never hits. The rule here: the operator's choice wins — JAX fills
``jax_compilation_cache_dir`` from ``JAX_COMPILATION_CACHE_DIR`` at import —
and otherwise the cache sits at one fixed, git-ignored place beside the
package. Entry scripts (``chip_smoke.py``, ``bench.py``, ``accuracy_gate.py``)
call :func:`ensure_compile_cache` before their first compile; importing the
package never does.
"""

from __future__ import annotations

import os

import jax

#: ``<checkout>/.jax_cache`` — derived from this file's own location, so two
#: processes started from different working directories share it.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def ensure_compile_cache() -> str:
    """Make sure a persistent compile cache is configured; return its path.
    A directory that is already set (``JAX_COMPILATION_CACHE_DIR``, or an
    earlier ``jax.config.update``) is left alone."""
    path = jax.config.jax_compilation_cache_dir
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
