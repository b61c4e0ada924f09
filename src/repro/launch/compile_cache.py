"""Where JAX keeps its persistent compilation cache for this checkout.

Entry points (``chip_smoke.py``, ``launch/serve.py``,
``examples/serve_lm.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` before their first compile; importing the
package never does.  A directory named by ``JAX_COMPILATION_CACHE_DIR``
wins, and JAX reads that variable itself, so nothing else is set then.
Otherwise the cache lives at the fixed ``<checkout>/.jax_cache``: the
path is part of what the cache is keyed on, so a directory that moved
between runs would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: the checkout root: src/repro/launch/compile_cache.py -> three levels up
CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def cache_dir() -> str:
    """The directory the persistent compilation cache uses."""
    return os.environ.get(ENV_VAR) or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`cache_dir`;
    returns the directory."""
    path = cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
