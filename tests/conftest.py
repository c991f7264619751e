import gc
import os
import sys

# Tests run on the single real CPU device (the 512-device override belongs to
# the dry-run ONLY — see src/repro/launch/dryrun.py). Distributed tests spawn
# subprocesses with their own XLA_FLAGS.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))  # benchmarks

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

# The CI precision matrix runs the tier-1 suite once per axis with
# REPRO_TEST_PRECISION in {fp32, bf16}. Cheap precision-policy unit tests
# always parametrize over both policies; the expensive cases (the M=32768
# acceptance sweep, CG-parity fits, streaming fits in tests/test_precision.py)
# follow this value so each CI axis exercises its own policy end-to-end.
TEST_PRECISION = os.environ.get("REPRO_TEST_PRECISION", "fp32")
assert TEST_PRECISION in ("fp32", "bf16"), TEST_PRECISION


@pytest.fixture(autouse=True, scope="module")
def _bound_compiled_executables():
    """Free XLA executables after every test module.

    Each compiled executable mmaps its own code pages and the CPU client
    never unmaps them while cached; over the full suite the accumulated
    compiles can exhaust the kernel's vm.max_map_count (default 65530),
    and the failed mmap surfaces as a segfault inside backend_compile on
    whichever unlucky test compiles next. Clearing per module bounds the
    peak map count at one module's worth of executables; the price is
    cross-module recompiles, which the suite can afford.
    """
    yield
    gc.collect()
    jax.clear_caches()


@pytest.fixture(scope="session")
def test_precision() -> str:
    """The precision axis this test process runs under (env-selected)."""
    return TEST_PRECISION


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


def synthetic_regression(key, n, d=5, noise=0.05, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    X = jax.random.normal(k1, (n, d), dtype)
    w = jax.random.normal(k2, (d,), dtype)
    y = jnp.sin(X @ w) + noise * jax.random.normal(k3, (n,), dtype)
    return X, y


#: fp32 unit roundoff (round to nearest).
U32 = 2.0 ** -24


def gamma(k: int) -> float:
    """gamma_k = k u / (1 - k u): worst-case relative error of a k-term fp32
    sum or dot product against the sum of magnitudes (Higham, Thm 3.1)."""
    return k * U32 / (1.0 - k * U32)


def sweep_fp32_error_bound(kern, X, C, u, v, *, fwd_terms=None, bwd_terms=None):
    """Elementwise first-order bound on the fp32 rounding error of
    ``w = K(X, C)^T (K(X, C) u + v)`` computed from these (exactly
    representable) inputs, in float64. Returns (w_exact, bound, |K|^T |t|),
    each (M, p); the last is what rounding the spilled t scales with.

    Kernel entries: the precursors a2 = |x|^2, b2 = |c|^2 and ab = x.c are
    d-term fp32 sums, each off by at most gamma_d times its sum of
    magnitudes; the registered formula carries that through its partial
    derivatives (forward-mode derivatives of ``tile_transform``), and its
    own few fp32 operations add at most 8 u |K|. Accumulation: the forward
    product sums M terms plus v, the transposed one n terms. A kernel that
    sums in tiles of b terms into a running fp32 accumulator over L tiles
    is bounded by gamma_{b + L} instead (Higham, Sect. 4.2): pass those
    lengths as ``fwd_terms`` / ``bwd_terms`` (defaults M + 1 and n). So

        |dw| <= |dK|^T |t| + |K|^T |dK| |u|
                + gamma_fwd |K|^T (|K| |u| + |v|) + gamma_bwd |K|^T |t|.
    """
    from repro.compat import enable_x64
    from repro.core.kernels import spec_of, tile_transform

    spec = spec_of(kern)
    with enable_x64(True):
        X, C, u, v = (jnp.asarray(np.asarray(a), jnp.float64) for a in (X, C, u, v))
        if u.ndim == 1:
            u, v = u[:, None], v[:, None]
        n, d = X.shape
        a2 = jnp.sum(X * X, axis=1, keepdims=True) * jnp.ones((1, C.shape[0]))
        b2 = jnp.sum(C * C, axis=1, keepdims=True).T * jnp.ones((n, 1))
        ab = X @ C.T
        f = lambda ab_, a2_, b2_: tile_transform(ab_, a2_, b2_, spec)
        zero = jnp.zeros_like(ab)
        g = gamma(d)
        dab = g * (jnp.abs(X) @ jnp.abs(C).T)
        K = f(ab, a2, b2)
        dK = 8 * U32 * jnp.abs(K)
        for tangent in ((dab, zero, zero), (zero, g * a2, zero), (zero, zero, g * b2)):
            dK = dK + jnp.abs(jax.jvp(f, (ab, a2, b2), tangent)[1])
        t = K @ u + v
        aK, at = jnp.abs(K), jnp.abs(t)
        bound = (dK.T @ at + aK.T @ (dK @ jnp.abs(u))
                 + gamma(fwd_terms or C.shape[0] + 1)
                 * aK.T @ (aK @ jnp.abs(u) + jnp.abs(v))
                 + gamma(bwd_terms or n) * aK.T @ at)
        return np.asarray(K.T @ t), np.asarray(bound), np.asarray(aK.T @ at)
