"""PRNG keys from ``--seed`` (any whole number, 64-bit and beyond)."""
from __future__ import annotations

import numpy as np


def key(seed: int, *path: int):
    """A raw uint32[2] jax PRNG key for stream ``path`` of ``seed``.

    ``jax.random.PRNGKey`` takes at most 64 bits and folds them in a
    platform-dependent way; a ``SeedSequence`` takes any non-negative
    integer and spreads it over both key words."""
    import jax.numpy as jnp
    words = np.random.SeedSequence([int(seed), *map(int, path)]).generate_state(2)
    return jnp.asarray(words, dtype=jnp.uint32)


def rng(seed: int, *path: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *map(int, path)])
