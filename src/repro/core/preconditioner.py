"""FALKON preconditioner (paper Sect. 3 Eq. 13 and Appendix A Def. 3).

Full-rank path (Alg. 1):
    T = chol(K_MM + eps*M*I)        (upper triangular, K_MM = T^T T)
    A = chol(T T^T / M + lam * I)   (upper triangular)
    B = (1/sqrt(n)) T^{-1} A^{-1}

General path (Alg. 2 / Def. 3) adds the sampling-weight diagonal D (Def. 2, for
approximate-leverage-score sampling) and a rank-revealing step for singular
K_MM. We implement the eigendecomposition variant of Example 2 (simpler than
pivoted QR and jittable):
    D K_MM D = Q diag(s) Q^T,  T = diag(sqrt(s)) restricted to s > tol,
with Q (M, q) a partial isometry. T diagonal is a valid special case of
"triangular"; all Def. 3 needs is invertibility and Q T^T T Q^T = D K_MM D.

B is never materialized: we expose the linear maps FALKON needs (the B^T H B
composition happens in falkon.py), exactly like Alg. 1's nested triangular
solves.

The factorization is split into two stages because only the second depends on
the regularization:

* **shared stage** (``_shared_factor``) — the O(M^3) work: one Cholesky (or
  eigendecomposition) of D K_MM D producing T/Q, plus the Gram of the factor
  ``T T^T`` that every lam-ridge reads. lam never appears.
* **lam stage** (``_lam_factor``) — ``A = chol(T T^T / M + lam I)``, a single
  cheap Cholesky per lam.

``make_preconditioner`` composes them for one lam;
``make_preconditioner_path`` runs the shared stage ONCE and vmaps the lam
stage over a grid of L lams, returning a :class:`PreconditionerPath` whose
``A`` is a batched (L, q, q) stack and whose maps act on (q, L*p) blocks —
L independent systems stacked along the column axis, sharing every
O(nM)-cost data sweep upstream (see falkon.py's path solver).

Factor-path routing (in-core vs blocked)
----------------------------------------
Every factor here is UPPER triangular by convention: ``T = chol(...).T``
with ``K = T^T T`` (jnp's Cholesky is lower; the transpose is taken at the
factorization, never at the solves). Both builders route each O(M^3)
Cholesky through ``repro.ops.plan_factor`` — the ``plan_sweep`` sibling for
the preconditioner stack:

* **incore** (dense factor fits the factor budget) — one Cholesky on the
  device-resident matrix: ``jnp.linalg.cholesky`` up to order
  ``_DIRECT_MAX``, a panel loop above it (:func:`cholesky_upper`; the
  solves likewise, :func:`tri_solve`), so compile time stays flat in M.
  The budget is ``REPRO_FACTOR_BUDGET_MB`` when set; otherwise the least
  free memory (``memory_stats()``: ``bytes_limit - bytes_in_use``) of the
  devices K_MM lives on, over the dense factors this route holds at its
  peak (``_INCORE_SHARED`` + ``_INCORE_PER_LAM`` per lam factor +
  ``_INCORE_MARGIN``), and never below the 512 MB default, which also
  stands where a device gives no reading (the CPU backend).
* **blocked** (dense factor exceeds the budget) — the tiled right-looking
  out-of-core path (``repro.kernels.blocked_cholesky``): the matrix is
  factored from HOST memory in (b, b) tiles with only O(b * M) panel bytes
  device-resident, lifting the M ceiling from "dense (M, M) fits HBM" to
  "dense (M, M) fits host RAM". A :class:`repro.ops.FactorPlanWarning`
  (carrying the full ``FactorPlan``) announces the fallback, mirroring
  ``SweepPlanWarning``. The finished factors still live on device for
  solve time — the remaining O(M^2) ceiling, documented in
  docs/architecture.md.

Routing honors the ``PrecisionPolicy`` ``cholesky`` override: tiles compute
in float32 at minimum regardless of the storage policy (bf16 factors
destabilize preconditioned CG — measured, see repro.ops.base), float64 when
the caller runs x64. The blocked path requires a CONCRETE K_MM (it round-
trips host memory): under a jit trace the plan silently falls back to
in-core, and the eig-based ``rank_deficient`` factorization refuses the
blocked route loudly (a dense (M, M) eigendecomposition cannot be tiled by
this scheme — see ``_shared_factor``).
"""
from __future__ import annotations

import dataclasses
import functools
import warnings

import numpy as np

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

from .kernels import FP32

Array = jax.Array


def _bcast(d: Array, v: Array) -> Array:
    return d[(...,) + (None,) * (v.ndim - 1)]


# ---------------------------------------------------------------------------
# Dense factor and triangular solves that compile in O(1)
# ---------------------------------------------------------------------------
#: Above this order the Cholesky and its triangular solves run as a loop
#: over PANEL-wide panels. XLA's TPU expanders for both unroll over the
#: whole matrix, so their compile time grows superlinearly: for a v5e,
#: Cholesky compiles in 1.1 s at 1024, 19 s at 4096 and 63 s at 10^4, a
#: vector triangular solve in 12 s at 10^4, and a fit compiles a dozen
#: solves. A fori_loop over panels compiles once, at PANEL size.
_DIRECT_MAX = 1024
PANEL = 256


def _pad_eye(A: Array, qp: int) -> Array:
    """Pad a square matrix to (qp, qp) with an identity tail block."""
    q = A.shape[-1]
    if qp == q:
        return A
    P = jnp.pad(A, ((0, qp - q), (0, qp - q)))
    tail = jnp.arange(qp) >= q
    return P + jnp.diag(tail.astype(A.dtype))


def cholesky_upper(A: Array) -> Array:
    """Upper-triangular T with A = T^T T (the repo's ``chol(...).T``).

    Past ``_DIRECT_MAX`` this is a right-looking blocked Cholesky: per
    panel, a (PANEL, PANEL) Cholesky, a panel triangular solve for the rows
    below, and an fp32 rank-PANEL update of the trailing matrix. A non-SPD
    input yields NaNs, as ``jnp.linalg.cholesky`` does."""
    q = A.shape[-1]
    if q <= _DIRECT_MAX:
        return jnp.linalg.cholesky(A).T
    b = PANEL
    nb = -(-q // b)
    qp = nb * b
    rows = jax.lax.broadcasted_iota(jnp.int32, (qp, 1), 0)

    def step(k, S):
        r0 = k * b
        Lkk = jnp.linalg.cholesky(jax.lax.dynamic_slice(S, (r0, r0), (b, b)))
        col = jax.lax.dynamic_slice(S, (0, r0), (qp, b))
        P = solve_triangular(Lkk, col.T, lower=True).T      # col Lkk^{-T}
        P = jnp.where(rows >= r0 + b, P, 0.0)                # rows below only
        S = jax.lax.dynamic_update_slice(
            S, jax.lax.dynamic_update_slice(P, Lkk, (r0, 0)), (0, r0))
        return S - jnp.matmul(P, P.T, precision=FP32)

    S = jax.lax.fori_loop(0, nb, step, _pad_eye(A, qp))
    return jnp.tril(S)[:q, :q].T


def tri_solve(U: Array, v: Array, trans: bool = False) -> Array:
    """U^{-1} v (or U^{-T} v) for upper-triangular U and v of shape (q,)
    or (q, p). Past ``_DIRECT_MAX`` this is block substitution: per panel,
    the solved part's contribution (one fp32 matmul) and a (PANEL, PANEL)
    triangular solve."""
    q = U.shape[-1]
    if q <= _DIRECT_MAX:
        return solve_triangular(U, v, lower=False, trans=1 if trans else 0)
    b = PANEL
    nb = -(-q // b)
    qp = nb * b
    V = v[:, None] if v.ndim == 1 else v
    Up = _pad_eye(U, qp)
    Vp = jnp.pad(V, ((0, qp - q), (0, 0)))

    def step(i, X):
        r0 = (i if trans else nb - 1 - i) * b
        if trans:      # forward: (U^T)[panel rows] = U[:, panel cols]^T
            rows = jax.lax.dynamic_slice(Up, (0, r0), (qp, b)).T
        else:          # backward
            rows = jax.lax.dynamic_slice(Up, (r0, 0), (b, qp))
        rhs = jax.lax.dynamic_slice(Vp, (r0, 0), (b, V.shape[1]))
        rhs = rhs - jnp.matmul(rows, X, precision=FP32)
        Ukk = jax.lax.dynamic_slice(Up, (r0, r0), (b, b))
        xk = solve_triangular(Ukk, rhs, lower=False, trans=1 if trans else 0)
        return jax.lax.dynamic_update_slice(X, xk, (r0, 0))

    X = jax.lax.fori_loop(0, nb, step, jnp.zeros_like(Vp))[:q]
    return X[:, 0] if v.ndim == 1 else X


def _solve_T(T: Array, diag_T: bool, v: Array, trans: bool = False) -> Array:
    """T^{-1} v (or T^{-T} v) — diagonal fast path for the eig factorization.

    Shared by the single-lam and path preconditioners: T is lam-independent,
    so the path applies it to the whole stacked column block in one solve.
    """
    if diag_T:
        return v / _bcast(jnp.diagonal(T), v)
    return tri_solve(T, v, trans)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Preconditioner:
    T: Array            # (q, q) upper triangular (diagonal in the eig path)
    A: Array            # (q, q) upper triangular
    Q: Array | None     # (M, q) partial isometry; None => identity (full rank)
    D: Array | None     # (M,) sampling-weight diagonal; None => ones
    n: Array            # number of training points (scalar)
    diag_T: bool = dataclasses.field(metadata=dict(static=True), default=False)

    @property
    def q(self) -> int:
        return self.T.shape[0]

    def _solve_T(self, v: Array, trans: bool = False) -> Array:
        return _solve_T(self.T, self.diag_T, v, trans)

    # --- the three maps -------------------------------------------------
    def right(self, u: Array) -> Array:
        """gamma = D Q T^{-1} A^{-1} u : (q,...) -> (M,...).

        This is sqrt(n) * B u; the 1/sqrt(n) is folded into the matvec's 1/n
        exactly as Alg. 1 does.
        """
        v = tri_solve(self.A, u)
        v = self._solve_T(v)
        if self.Q is not None:
            v = self.Q @ v
        if self.D is not None:
            v = v * _bcast(self.D, v)
        return v

    def left(self, w: Array) -> Array:
        """A^{-T} T^{-T} Q^T D w : (M,...) -> (q,...)."""
        if self.D is not None:
            w = w * _bcast(self.D, w)
        if self.Q is not None:
            w = self.Q.T @ w
        w = self._solve_T(w, trans=True)
        return tri_solve(self.A, w, trans=True)

    def coeffs(self, beta: Array) -> Array:
        """alpha = D Q T^{-1} A^{-1} beta (Alg. 1's ``alpha = T\\(A\\beta)``)."""
        return self.right(beta)

    def beta_of_coeffs(self, alpha: Array) -> Array:
        """Inverse of ``coeffs``: beta = A T Q^T D^{-1} alpha, (M,...) -> (q,...).

        The warm-start map for ``partial_fit``: a deployed estimator stores
        alpha (the kernel-space coefficients), but the mini-batch iteration
        lives in the preconditioned space, so resuming from a served model
        means pulling alpha back through the factors. Triangular/diagonal
        MULTIPLIES, not solves — exact for the full-rank path
        (``coeffs(beta_of_coeffs(a)) == a``); in the rank-deficient eig path
        ``Q^T`` is the least-squares pullback onto the kept eigenspace, which
        is the only part of alpha the solver ever produced.
        """
        v = alpha
        if self.D is not None:
            v = v / _bcast(self.D, v)
        if self.Q is not None:
            v = self.Q.T @ v
        if self.diag_T:
            v = _bcast(jnp.diagonal(self.T), v) * v
        else:
            v = self.T @ v
        return self.A @ v

    def ridge(self, u: Array, lam) -> Array:
        """lam * A^{-T} A^{-1} u — the regularization term of W = B^T H B.

        Uses the T^{-T} Q^T D K_MM D Q T^{-1} = I identity (Lemma 2 /
        Eq. 19), exactly as the MATLAB code does.
        """
        return lam * tri_solve(self.A, tri_solve(self.A, u), trans=True)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PreconditionerPath:
    """L preconditioners sharing T/Q/D, differing only in the lam-ridge A.

    The maps act on **stacked column blocks**: a (q, L*p) array whose column
    group ``[l*p:(l+1)*p]`` belongs to system l (lam = ``lams[l]``). The
    lam-independent part (T, Q, D — the expensive factors) applies to the
    whole block in one solve; only the cheap per-system A triangular solves
    are vmapped over the (L, q, q) stack. This is the seam that lets ONE
    O(nM) data sweep serve all L regularization values in the path solver.
    """

    T: Array            # (q, q) shared factor (diagonal in the eig path)
    A: Array            # (L, q, q) per-lam upper-triangular stack
    Q: Array | None     # (M, q) shared partial isometry
    D: Array | None     # (M,) shared sampling-weight diagonal
    lams: Array         # (L,) regularization grid, A[l] = chol(TT^T/M + lams[l] I)
    n: Array            # number of training points (scalar)
    diag_T: bool = dataclasses.field(metadata=dict(static=True), default=False)

    @property
    def q(self) -> int:
        return self.T.shape[0]

    @property
    def L(self) -> int:
        return self.A.shape[0]

    # --- stacked-block plumbing ----------------------------------------
    def _group(self, U: Array) -> Array:
        """(q, L*p) -> (L, q, p): split the column axis into systems."""
        q, cols = U.shape
        return U.reshape(q, self.L, cols // self.L).transpose(1, 0, 2)

    @staticmethod
    def _ungroup(G: Array) -> Array:
        """(L, q, p) -> (q, L*p): inverse of ``_group``."""
        L, q, p = G.shape
        return G.transpose(1, 0, 2).reshape(q, L * p)

    def solve_A(self, U: Array, trans: bool = False) -> Array:
        """Per-system A^{-1} (or A^{-T}) over the column groups of U."""
        solve = functools.partial(tri_solve, trans=trans)
        return self._ungroup(jax.vmap(solve)(self.A, self._group(U)))

    def col_lams(self, U: Array) -> Array:
        """lams broadcast to U's columns: lam_l repeated p times."""
        return jnp.repeat(self.lams, U.shape[1] // self.L)

    # --- the three maps, system-batched ---------------------------------
    def right(self, U: Array) -> Array:
        """gamma_l = D Q T^{-1} A_l^{-1} u_l, stacked: (q, L*p) -> (M, L*p)."""
        v = self.solve_A(U)
        v = _solve_T(self.T, self.diag_T, v)
        if self.Q is not None:
            v = self.Q @ v
        if self.D is not None:
            v = v * _bcast(self.D, v)
        return v

    def left(self, W: Array) -> Array:
        """A_l^{-T} T^{-T} Q^T D w_l, stacked: (M, L*p) -> (q, L*p)."""
        if self.D is not None:
            W = W * _bcast(self.D, W)
        if self.Q is not None:
            W = self.Q.T @ W
        W = _solve_T(self.T, self.diag_T, W, trans=True)
        return self.solve_A(W, trans=True)

    def coeffs(self, beta: Array) -> Array:
        """alpha_l = D Q T^{-1} A_l^{-1} beta_l, stacked over columns."""
        return self.right(beta)

    def ridge(self, U: Array, lams=None) -> Array:
        """lam_l * A_l^{-T} A_l^{-1} u_l per column group of U."""
        del lams  # the grid is part of the factorization; kept for the
        # _falkon_operator calling convention shared with Preconditioner
        v = self.solve_A(self.solve_A(U), trans=True)
        return v * self.col_lams(U)[None,:]

    def expand_rhs(self, w: Array) -> Array:
        """The lam-independent RHS ``w = K_nM^T y / n`` (M, p) expanded to
        the stacked (q, L*p) CG right-hand side.

        The shared D/Q/T^{-T} half is applied ONCE; only the per-system
        A_l^{-T} differs — the b-side twin of the shared data sweep.
        """
        if w.ndim == 1:
            w = w[:, None]
        if self.D is not None:
            w = w * _bcast(self.D, w)
        if self.Q is not None:
            w = self.Q.T @ w
        shared = _solve_T(self.T, self.diag_T, w, trans=True)      # (q, p)
        per = jax.vmap(lambda A: tri_solve(A, shared, True))(self.A)  # (L, q, p)
        return self._ungroup(per)

    def split(self, stacked: Array) -> Array:
        """(rows, L*p) -> (L, rows, p): per-system views of a stacked block."""
        rows, cols = stacked.shape
        return stacked.reshape(rows, self.L, cols // self.L).transpose(1, 0, 2)

    def system(self, index: int) -> Preconditioner:
        """The single-lam :class:`Preconditioner` for system ``index``."""
        return Preconditioner(
            T=self.T, A=self.A[index], Q=self.Q, D=self.D, n=self.n, diag_T=self.diag_T
        )


# ---------------------------------------------------------------------------
# Factorization stages
# ---------------------------------------------------------------------------
#: The in-core route's peak, in dense (M, M) factors over the bytes in use
#: once K_MM is computed. ``_shared_factor`` and ``_lam_factor`` run eagerly
#: and dispatch runs ahead of the device, so each temporary (the identity
#: and the jittered copy, each panel loop's carry and its three
#: temporaries, ``tril``, slices, transposes, the ridge matrix) stays
#: allocated until the ops that read it have run. Measured on a TPU v5e:
#: 13.3 factors at M = 8192 and 16.3 at M = 12288 and 14080 for one lam;
#: 28.3 for a grid of 4 at M = 8192, i.e. 5 more per further lam. The count
#: covers them with room: 12 shared, 6 per lam factor built at once, 2 for
#: XLA's own temporaries.
_INCORE_SHARED = 12
_INCORE_PER_LAM = 6
_INCORE_MARGIN = 2


def device_free_bytes(x) -> int | None:
    """Least free memory (``bytes_limit - bytes_in_use``) over the devices
    ``x`` lives on; None where a device gives no reading (the CPU backend).
    Where there is a reading, a ``jax.Array`` is waited for first, so that
    the temporaries of the ops computing it are freed, not counted in use."""
    devices = getattr(getattr(x, "sharding", None), "device_set", ())
    if not devices or not all("bytes_limit" in (d.memory_stats() or {}) for d in devices):
        return None
    if isinstance(x, jax.Array):
        jax.block_until_ready(x)
    return min(s["bytes_limit"] - s.get("bytes_in_use", 0)
               for s in (d.memory_stats() for d in devices))


def resolve_factor_plan(KMM: Array, factor_plan, rank_deficient: bool, systems: int = 1):
    """Resolve the caller's ``factor_plan`` argument to a ``FactorPlan``.

    ``None`` auto-plans from the factor budget: ``REPRO_FACTOR_BUDGET_MB``
    when set, else the least free memory of the devices K_MM lives on over
    the in-core route's peak for ``systems`` lam factors built at once
    (``repro.ops.base.device_factor_budget``: never below the 512 MB
    default, which also stands where there is no reading); the plan's
    ``free_bytes`` keeps the reading. A path name ("incore"/"blocked")
    forces that route; a ``FactorPlan`` is taken as-is. A traced K_MM
    always lands in-core (the blocked path round-trips host memory, which a
    trace cannot do); a blocked plan with ``rank_deficient=True`` raises
    (see ``_shared_factor``); a blocked plan made here from ``None`` or a
    path name emits ``FactorPlanWarning`` (a caller that passes a
    ``FactorPlan`` resolved it, and was warned, once).
    """
    # Lazy import: repro.ops.__init__ constructs backends that reach into
    # repro.core, so a module-level import here would be a cycle.
    from repro.ops.base import (
        FACTOR_PATHS, FactorPlan, FactorPlanWarning, device_factor_budget, plan_factor)

    M = KMM.shape[0]
    itemsize = max(jnp.dtype(KMM.dtype).itemsize, 4)
    if isinstance(factor_plan, FactorPlan):
        plan = factor_plan
    elif factor_plan is None:
        free = None if isinstance(KMM, jax.core.Tracer) else device_free_bytes(KMM)
        peak = _INCORE_SHARED + _INCORE_PER_LAM * systems + _INCORE_MARGIN
        plan = plan_factor(M, itemsize=itemsize, factor_budget=device_factor_budget(free, peak))
        plan = dataclasses.replace(plan, free_bytes=-1 if free is None else free)
    elif factor_plan in FACTOR_PATHS:
        # Force the named path by planning against a budget the dense
        # factor trivially fits (incore) or trivially exceeds (blocked).
        dense = M * M * itemsize
        plan = plan_factor(
            M,
            itemsize=itemsize,
            factor_budget=dense if factor_plan == "incore" else dense - 1,
        )
    else:
        raise ValueError(
            f"factor_plan must be None, a FactorPlan, or one of "
            f"{FACTOR_PATHS}; got {factor_plan!r}")

    if plan.path == "blocked":
        if isinstance(KMM, jax.core.Tracer):
            # Can't leave the device under a trace — quietly keep the
            # traced program on the historical in-core path.
            return plan_factor(M, itemsize=itemsize, factor_budget=M * M * itemsize)
        if rank_deficient:
            raise ValueError(
                "rank_deficient=True is not supported on the blocked factor "
                "path: the eig fallback needs a dense (M, M) "
                "eigendecomposition that this tiling cannot express. Use "
                "the in-core path (raise REPRO_FACTOR_BUDGET_MB or pass "
                "factor_plan='incore'), or drop rank_deficient.")
        if not isinstance(factor_plan, FactorPlan):
            warnings.warn(FactorPlanWarning(plan), stacklevel=3)
    return plan


# ---------------------------------------------------------------------------
# The blocked route's host copies and host loops, each one profiler span
# ---------------------------------------------------------------------------
def _download(x: Array) -> np.ndarray:
    """A whole device factor copied to a host working array (span
    ``falkon.factor.download``). The wait for ``x`` to be computed stays
    outside the span, so it times the copy alone."""
    jax.block_until_ready(x)
    with jax.profiler.TraceAnnotation("falkon.factor.download", bytes=x.nbytes):
        return np.array(x)


def _upload(h: np.ndarray, dt) -> Array:
    """A whole host factor copied to the device (span ``falkon.factor.upload``).
    The copy runs on after the call returns: the span holds the host's part."""
    with jax.profiler.TraceAnnotation("falkon.factor.upload", bytes=h.nbytes):
        return jnp.asarray(h, dt)


def _blocked(name: str, loop, H: np.ndarray, block: int) -> np.ndarray:
    """Run one host-blocked loop (``blocked_cholesky``/``blocked_syrk_tt``)
    under span ``name``, its ``FactorStats`` set as the span's counts."""
    from repro.kernels.blocked_cholesky import FactorStats
    stats = FactorStats()
    with jax.profiler.TraceAnnotation(name) as span:
        out = loop(H, block, stats=stats)
        span.set_metadata(
            panels=stats.panels, tiles_updated=stats.tiles_updated,
            bytes_transferred=stats.bytes_transferred,
            peak_device_bytes=stats.peak_device_bytes)
    return out


def _shared_factor(
    KMM: Array,
    D: Array | None,
    jitter: float | None,
    rank_deficient: bool,
    rank_tol: float,
    plan=None,
) -> tuple[Array, Array | None, Array, bool]:
    """Stage 1 — everything lam never touches: (T, Q, TTt, diag_T).

    ``TTt`` is the (q, q) Gram of the factor (``T T^T`` for the Cholesky
    path, ``diag(kept s)`` for the eig path) that every lam-ridge Cholesky
    reads; computing it here means an L-point path pays for it once.

    ``plan`` (a resolved ``FactorPlan`` or None) selects the Cholesky
    route. On the blocked path the D-scaling, the jitter and both O(M^3)
    products (``chol`` and ``T T^T``) run against HOST-resident numpy via
    ``repro.kernels.blocked_cholesky`` — the device never holds more than
    O(plan.block * M) factor bytes; the in-core path is untouched (and the
    eig-based ``rank_deficient`` branch is in-core only — the resolver
    refuses blocked plans for it loudly).
    """
    M = KMM.shape[0]
    dt = KMM.dtype

    if plan is not None and plan.path == "blocked" and not rank_deficient:
        from repro.kernels.blocked_cholesky import blocked_cholesky, blocked_syrk_tt
        Kh = _download(KMM)                    # host working copy
        if D is not None:
            Dh = np.array(D, dtype=Kh.dtype)
            Kh *= Dh[:, None]
            Kh *= Dh[None,:]
        eps = jitter if jitter is not None else float(jnp.finfo(dt).eps) * M
        Kh.flat[:: M + 1] += np.asarray(eps, Kh.dtype)
        Th = _blocked("falkon.factor.cholesky", blocked_cholesky, Kh, plan.block)
        TTth = _blocked("falkon.factor.syrk", blocked_syrk_tt, Th, plan.block)
        return _upload(Th, dt), None, _upload(TTth, dt), False

    if D is not None:
        KMM = KMM * D[:, None] * D[None,:]

    if rank_deficient:
        # Appendix A Example 2 (eigendecomposition). Static shapes: rank-q
        # truncation is expressed by zeroing the dropped columns of Q and
        # guarding the inverses, so q == M structurally.
        s, U = jnp.linalg.eigh(KMM)                       # ascending
        s = s[::-1]
        U = U[:,::-1]
        keep = s > (rank_tol * jnp.maximum(s[0], 1e-30))
        s_safe = jnp.where(keep, s, 1.0)
        T = jnp.diag(jnp.sqrt(s_safe))
        Q = U * keep[None,:].astype(dt)
        TTt = jnp.diag(jnp.where(keep, s_safe, 0.0))
        return T, Q, TTt, True

    eps = jitter if jitter is not None else float(jnp.finfo(dt).eps) * M
    T = cholesky_upper(KMM + eps * jnp.eye(M, dtype=dt))
    return T, None, jnp.matmul(T, T.T, precision=FP32), False


def _lam_factor(TTt: Array, lam, M: int, plan=None) -> Array:
    """Stage 2 — ``A = chol(T T^T / M + lam I)`` (upper): one cheap Cholesky
    per regularization value; vmapped over the grid by the path builder.

    "Cheap" is relative to the data sweeps, not to device memory: at the
    same (q, q) size as T it hits the same dense-factor wall, so a blocked
    ``plan`` routes it through the same out-of-core tiling (requires a
    concrete TTt and lam; traced inputs stay in-core).
    """
    if (plan is not None and plan.path == "blocked"
            and not isinstance(TTt, jax.core.Tracer)
            and not isinstance(lam, jax.core.Tracer)):
        from repro.kernels.blocked_cholesky import blocked_cholesky
        Bh = _download(TTt)
        Bh /= M
        Bh.flat[:: Bh.shape[0] + 1] += np.asarray(float(lam), Bh.dtype)
        return _upload(
            _blocked("falkon.factor.cholesky", blocked_cholesky, Bh, plan.block), TTt.dtype)
    eye = jnp.eye(TTt.shape[0], dtype=TTt.dtype)
    return cholesky_upper(TTt / M + lam * eye)


def make_preconditioner(
    KMM: Array,
    lam: float,
    n: int,
    *,
    D: Array | None = None,
    jitter: float | None = None,
    rank_deficient: bool = False,
    rank_tol: float = 1e-7,
    factor_plan=None,
) -> Preconditioner:
    """Build the FALKON preconditioner from K_MM.

    Cost: 2 Cholesky factorizations + one triangular product = 4/3 M^3 flops
    (paper Sect. 3 "Computations"). ``D`` is the Def. 2 diagonal for
    leverage-score sampling (None for uniform sampling).

    ``factor_plan`` routes the two Cholesky factorizations: ``None``
    auto-plans in-core vs blocked from the dense-factor budget (derived
    from the devices' free memory, see :func:`resolve_factor_plan`),
    ``"incore"``/``"blocked"`` force a path,
    and a ``repro.ops.FactorPlan`` is used as-is. See the module docstring
    ("Factor-path routing") for the contract; results are path-independent
    to ~1e-5 relative (tested), not bit-identical.
    """
    M = KMM.shape[0]
    dt = KMM.dtype
    plan = resolve_factor_plan(KMM, factor_plan, rank_deficient)
    T, Q, TTt, diag_T = _shared_factor(
        KMM, D, jitter, rank_deficient, rank_tol, plan=plan
    )
    A = _lam_factor(TTt, lam, M, plan=plan)
    return Preconditioner(T=T, A=A, Q=Q, D=D, n=jnp.asarray(n, dt), diag_T=diag_T)


def make_preconditioner_path(
    KMM: Array,
    lams,
    n: int,
    *,
    D: Array | None = None,
    jitter: float | None = None,
    rank_deficient: bool = False,
    rank_tol: float = 1e-7,
    factor_plan=None,
) -> PreconditionerPath:
    """One shared factorization, L cheap lam-ridge Cholesky's.

    ``lams`` is the regularization grid ((L,) array-like, each > 0). The
    O(M^3) shared stage runs once; the (L, q, q) ``A`` stack costs L * M^3/3
    on an M x M triangular Gram that is already resident — against L full
    ``make_preconditioner`` calls this saves L-1 Cholesky factorizations of
    K_MM itself, and against L full *fits* it is the enabler for sharing
    every O(nM) data sweep (see ``falkon_solve_path``).

    ``factor_plan`` routes every factorization exactly as in
    ``make_preconditioner``. One sizing note: a blocked path builds the L
    lam-ridge factors SEQUENTIALLY (a host-blocked loop cannot be vmapped),
    and the (L, q, q) stack itself is L dense factors on device — the stack,
    not the factorization, becomes the memory bound for large L * M^2.
    """
    M = KMM.shape[0]
    dt = KMM.dtype
    lams = jnp.asarray(lams, dt)
    if lams.ndim != 1 or lams.shape[0] < 1:
        raise ValueError(
            f"lams must be a non-empty 1-D grid, got shape " f"{lams.shape}"
        )
    if not isinstance(lams, jax.core.Tracer) and bool(jnp.any(lams <= 0.0)):
        # a non-positive ridge makes TT^T/M + lam I indefinite and the
        # batched Cholesky returns silent NaNs, not an error — fail here
        # (concrete grids only; traced grids keep the builder jittable)
        raise ValueError(
            f"every lam in the path must be > 0, got {tuple(map(float, lams))}"
        )
    plan = resolve_factor_plan(KMM, factor_plan, rank_deficient, systems=lams.shape[0])
    T, Q, TTt, diag_T = _shared_factor(
        KMM, D, jitter, rank_deficient, rank_tol, plan=plan
    )
    if plan.path == "blocked" and not isinstance(lams, jax.core.Tracer):
        # The host-blocked factorization cannot run under vmap; build the
        # (L, q, q) stack one out-of-core Cholesky at a time.
        A = jnp.stack([_lam_factor(TTt, lam, M, plan=plan) for lam in np.asarray(lams)])
    else:
        A = jax.vmap(lambda lam: _lam_factor(TTt, lam, M))(lams)
    return PreconditionerPath(
        T=T, A=A, Q=Q, D=D, lams=lams, n=jnp.asarray(n, dt), diag_T=diag_T
    )
