"""Nystrom center selection (paper Appendix A).

Two sampling schemes:

* **uniform**: a uniformly random subset of M training points (Alg. 1 setting);
  D = I.
* **approximate leverage scores** (Def. 1): sample M indices with replacement
  with p_i proportional to approximate ridge leverage scores ``lhat_lambda(i)``,
  and build the Def. 2 reweighting diagonal
  ``D_jj = 1 / sqrt(n * p_{i_j} * count_j)``
  (the ``count`` factor matches Alg. 2's ``discrete_prob_sample``, which
  collapses duplicate draws into one center with multiplicity).

Leverage-score estimation: exact scores are
``l_lambda(i) = [K_nn (K_nn + lambda n I)^{-1}]_ii`` — O(n^3), test-only. The
scalable estimator uses a uniform pilot subset S of size M0 and the Nystrom/
Woodbury identity

    lhat_lambda(i) = k_{iS}^T (lambda n K_SS + K_Sn K_nS)^{-1} k_{iS}

which is the q-approximate estimator family of [Rudi et al. 2015; Alaoui &
Mahoney 2015] computable in O(n M0^2 + M0^3) time and O(M0^2) memory (blocked
over rows of K_nS).

The estimator factors into a lambda-INDEPENDENT pilot stage and a cheap
per-lambda stage, mirroring the preconditioner split:

* ``build_leverage_pilot``      — draw S, build K_SS and accumulate
                                  K_Sn K_nS over row blocks (the O(n M0^2)
                                  data pass; lambda never appears).
* ``leverage_scores_from_pilot`` — form G = lam n K_SS + K_Sn K_nS, factor
                                  it (O(M0^3)) and score the rows.

A lambda grid therefore pays for the pilot-Gram build once
(``approximate_leverage_scores_path``); ``approximate_leverage_scores`` is
the single-lambda composition of the two stages.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .kernels import FP32, KernelFn

Array = jax.Array


class NystromCenters(NamedTuple):
    centers: Array       # (M, d)
    indices: Array       # (M,) indices into X
    D: Array | None      # (M,) Def. 2 diagonal; None for uniform sampling


def uniform_centers(key: Array, X: Array, M: int) -> NystromCenters:
    n = X.shape[0]
    idx = jax.random.choice(key, n, shape=(M,), replace=False)
    return NystromCenters(centers=X[idx], indices=idx, D=None)


def exact_leverage_scores(X: Array, kernel: KernelFn, lam: float) -> Array:
    """Exact ridge leverage scores (O(n^3); for tests / tiny n only)."""
    n = X.shape[0]
    Knn = kernel(X, X)
    S = jnp.linalg.solve(Knn + lam * n * jnp.eye(n, dtype=Knn.dtype), Knn)
    return jnp.diagonal(S)


class LeveragePilot(NamedTuple):
    """The lambda-independent half of the leverage-score estimator."""
    S: Array          # (M0, d) pilot subset
    KSS: Array        # (M0, M0) pilot Gram
    KSnKnS: Array     # (M0, M0) accumulated K_Sn K_nS (the O(n M0^2) pass)
    indices: Array    # (M0,) pilot row indices into X
    n: int            # rows the pilot was built over


def _blocked_rows(X: Array, block_size: int) -> tuple[Array, Array]:
    """(nb, block, d) row blocks of X plus the (nb, block) validity mask."""
    n = X.shape[0]
    nb = -(-n // block_size)
    pad = nb * block_size - n
    Xp = jnp.pad(X, ((0, pad), (0, 0)))
    mask = jnp.pad(jnp.ones((n,), X.dtype), (0, pad)).reshape(nb, block_size)
    return Xp.reshape(nb, block_size, -1), mask


def build_leverage_pilot(
    key: Array,
    X: Array,
    kernel: KernelFn,
    *,
    pilot_size: int = 256,
    block_size: int = 4096,
) -> LeveragePilot:
    """Stage 1 — the pilot-Gram build: everything lambda never touches.

    One O(n M0^2) pass over the data accumulates K_Sn K_nS; a lambda grid
    reuses the result for every ridge value (see
    ``leverage_scores_from_pilot``).
    """
    n, _ = X.shape
    M0 = min(pilot_size, n)
    pilot_idx = jax.random.choice(key, n, shape=(M0,), replace=False)
    S = X[pilot_idx]
    KSS = kernel(S, S)

    # Accumulate K_Sn K_nS = sum over row-blocks of K_bS^T K_bS.
    Xb, mask = _blocked_rows(X, block_size)

    def acc(carry, inp):
        xb, mb = inp
        Kb = kernel(xb, S) * mb[:, None]
        return carry + jnp.matmul(Kb.T, Kb, precision=FP32), None

    KSnKnS, _ = jax.lax.scan(acc, jnp.zeros((M0, M0), X.dtype), (Xb, mask))
    return LeveragePilot(S=S, KSS=KSS, KSnKnS=KSnKnS, indices=pilot_idx, n=n)


def leverage_scores_from_pilot(
    pilot: LeveragePilot,
    X: Array,
    kernel: KernelFn,
    lam: float,
    *,
    block_size: int = 4096,
) -> Array:
    """Stage 2 — score the rows at one ridge value from a built pilot.

    Cost per lambda: one O(M0^3) Cholesky of G = lam n K_SS + K_Sn K_nS
    plus the blocked scoring pass — the pilot-Gram accumulation is NOT
    repeated.
    """
    M0 = pilot.S.shape[0]
    n = X.shape[0]
    G = lam * pilot.n * pilot.KSS + pilot.KSnKnS
    G = G + 1e-6 * jnp.trace(G) / M0 * jnp.eye(M0, dtype=G.dtype)
    cho = jax.scipy.linalg.cho_factor(G)
    S = pilot.S
    Xb, _ = _blocked_rows(X, block_size)

    def score_block(xb):
        KbS = kernel(xb, S)                       # (b, M0)
        sol = jax.scipy.linalg.cho_solve(cho, KbS.T)  # (M0, b)
        return jnp.sum(KbS.T * sol, axis=0)       # (b,)

    scores = jax.lax.map(score_block, Xb).reshape(-1)[:n]
    return jnp.maximum(scores, 1e-12)


def approximate_leverage_scores(
    key: Array,
    X: Array,
    kernel: KernelFn,
    lam: float,
    *,
    pilot_size: int = 256,
    block_size: int = 4096,
) -> Array:
    """Nystrom/Woodbury approximate ridge leverage scores, O(n M0^2).

    The single-lambda composition of ``build_leverage_pilot`` and
    ``leverage_scores_from_pilot``.
    """
    pilot = build_leverage_pilot(
        key, X, kernel, pilot_size=pilot_size, block_size=block_size
    )
    return leverage_scores_from_pilot(pilot, X, kernel, lam, block_size=block_size)


def approximate_leverage_scores_path(
    key: Array,
    X: Array,
    kernel: KernelFn,
    lams,
    *,
    pilot_size: int = 256,
    block_size: int = 4096,
) -> Array:
    """(L, n) leverage scores over a lambda grid from ONE pilot-Gram build.

    The O(n M0^2) accumulation runs once; each grid point pays only its
    G-Cholesky and scoring pass — the sampling-diagnostics twin of the
    shared-sweep path solve.
    """
    pilot = build_leverage_pilot(
        key, X, kernel, pilot_size=pilot_size, block_size=block_size
    )
    return jnp.stack([
        leverage_scores_from_pilot(pilot, X, kernel, float(lam),
                                   block_size=block_size)
        for lam in lams
    ])


def leverage_score_centers(
    key: Array,
    X: Array,
    M: int,
    scores: Array,
) -> NystromCenters:
    """Sample M centers ~ p_i = scores_i / sum(scores); build Def. 2 D.

    Follows Alg. 2's ``discrete_prob_sample``: duplicates are kept as repeated
    rows (static shape) and D_jj = 1/sqrt(n * p_{i_j}) with each draw counted
    once — for draws of the same index this is equivalent to the collapsed
    (count-weighted) form up to a unitary rotation of the coefficient space,
    and keeps everything shape-static for jit.
    """
    n = X.shape[0]
    p = scores / jnp.sum(scores)
    idx = jax.random.choice(key, n, shape=(M,), replace=True, p=p)
    # Def. 2 / Def. 6: G_M = (1/M) sum_j D_jj^2 K_xj (x) K_xj with
    # D_jj^2 = 1/(n p_j) — the 1/M lives in G_M, so D itself is 1/sqrt(n p).
    D = 1.0 / jnp.sqrt(n * p[idx])
    return NystromCenters(centers=X[idx], indices=idx, D=D.astype(X.dtype))


def select_centers(
    key: Array,
    X: Array,
    M: int,
    *,
    kernel: KernelFn | None = None,
    lam: float | None = None,
    scheme: str = "uniform",
    pilot_size: int = 256,
) -> NystromCenters:
    if scheme == "uniform":
        return uniform_centers(key, X, M)
    if scheme == "leverage":
        assert kernel is not None and lam is not None
        k1, k2 = jax.random.split(key)
        scores = approximate_leverage_scores(k1, X, kernel, lam, pilot_size=pilot_size)
        return leverage_score_centers(k2, X, M, scores)
    raise ValueError(f"unknown center-selection scheme {scheme!r}")
