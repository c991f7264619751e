"""Synthetic kernel-regression data at a deployment's shapes, from a seed.

A copy of ``make_kernel_dataset`` (``repro.data.synthetic``) kept with the
benchmark so that no change to the program can move the inputs: X ~ N(0, I_d)
and a target from a 64-feature random Fourier mixture at the kernel's own
sigma, so the Gaussian kernel is well specified. Binary tasks take the sign
of the mixture with a ``noise`` share of labels flipped; regression adds
``noise``-scaled Gaussian noise and ``y_offset``. Made in one jitted call on
the device.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

N_FEAT = 64


def _rows(key, n, d, sigma, task, noise, y_offset):
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    X = jax.random.normal(k1, (n, d))
    W = jax.random.normal(k2, (d, N_FEAT)) / sigma
    b = jax.random.uniform(k3, (N_FEAT,), maxval=2 * np.pi)
    phi = jnp.cos(jnp.matmul(X, W, precision="highest") + b) * np.sqrt(2.0 / N_FEAT)
    w = jax.random.normal(k4, (N_FEAT,))
    f = jnp.matmul(phi, w, precision="highest")
    if task == "binary":
        flip = jax.random.uniform(k5, (n,)) < noise
        y = jnp.where(jnp.logical_xor(f > 0, flip), 1.0, -1.0)
    elif task == "regression":
        y = f + noise * jax.random.normal(k5, (n,)) + y_offset
    else:
        raise ValueError(f"unknown task {task!r}")
    return X, y.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("n", "m", "d", "sigma", "task",
                                             "noise", "y_offset"))
def _split(key, *, n, m, d, sigma, task, noise, y_offset):
    X, y = _rows(key, n + m, d, sigma, task, noise, y_offset)
    return X[:n], y[:n], X[n:], y[n:]


def make_split(key, config: dict):
    """Train and held-out rows of one deployment: (X, y, X_test, y_test)."""
    return _split(key, n=int(config["n_train"]),
                  m=int(config["n_test"]), d=int(config["d"]),
                  sigma=float(config["sigma"]), task=config["task"],
                  noise=float(config["noise"]),
                  y_offset=float(config.get("y_offset", 0.0)))
