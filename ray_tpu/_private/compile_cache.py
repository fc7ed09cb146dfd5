"""Where JAX's persistent compilation cache lives — decided here and
nowhere else.

Where ``JAX_COMPILATION_CACHE_DIR`` is set from outside, that directory is
used. Where it is not, the cache is one fixed, git-ignored directory at the
root of the checkout: the path is part of what a cache lookup depends on,
so it never comes from ``tempfile``, a pid or the time. Either way the
answer is exported into ``os.environ``, which is how every process agrees:
JAX reads the variable when it is imported, and workers inherit the
raylet's environment (``Raylet._worker_env``).
"""

from __future__ import annotations

import os

_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def export_compile_cache_dir() -> str:
    """Export the cache directory into this process's environment (a value
    given from outside wins) and return it. Call before importing jax in a
    process that compiles; a raylet calls it before it snapshots the
    environment its workers get."""
    return os.environ.setdefault(_ENV_VAR, os.path.join(_CHECKOUT,
                                                       ".jax_cache"))
