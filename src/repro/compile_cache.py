"""The persistent XLA compilation cache every entry point turns on.

A cold process compiles every program it runs; on a TPU that is a large
part of a short fit. JAX can keep compiled programs on disk and find them
again in the next process, but only if the directory stays put: a path that
names a temporary directory, a process id or a time never hits. So the
cache lives at one fixed place per checkout.

Call :func:`enable_compile_cache` once, before the first compile, from
``__main__`` code (``chip_smoke.py``, ``repro.launch.serve``,
``examples/*.py``). Importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: The checkout this package is imported from (``<checkout>/src/repro``).
CHECKOUT = Path(__file__).resolve().parents[2]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache goes to ``<checkout>/.jax_cache``
    (listed in ``.gitignore``).
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
