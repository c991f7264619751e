"""FALKON solver (paper Alg. 1 / Alg. 2) — composable JAX module.

Single-device path mirrors Alg. 1 line by line; the distributed path shards
the data sweep over the mesh data axes — the preconditioner and the
(q,)-sized CG state are replicated (they are O(M^2)/O(M), the paper's memory
budget). Distribution is a *backend*, not solver logic:
``FalkonConfig(mesh=..., data_axes=...)`` makes ``make_ops`` wrap the named
backend in :class:`repro.ops.DistributedOps` (shard-local sweeps, one (M, p)
psum per iteration), and every fit variant below — in-core, lam-path,
streaming — inherits the sharding with no mesh-specific code of its own.

All kernel work flows through a pluggable ``KernelOps`` backend
(``repro.ops``): ``FalkonConfig.ops_impl`` selects it ("jnp" reference or
"pallas" fused single-pass sweep) and ``FalkonConfig.precision`` names the
``PrecisionPolicy`` — "fp32", or "bf16" for END-TO-END bfloat16 storage
(X/C/v, the CG iterates, the streamed chunks) with compensated fp32
accumulation; the Gram block and preconditioner Cholesky stay fp32 by
per-buffer override. ``matvec_impl`` is kept as a deprecated alias of
``ops_impl`` (using it warns).

The fit is an explicit five-stage pipeline — select -> gram -> precondition
-> solve -> wrap — with each stage a named function, so variants compose
from the same parts instead of re-inlining them: ``falkon_fit`` (in-core),
``falkon_fit_streaming`` (host-streamed X) and ``falkon_fit_path`` (the
lam-path solver) differ only in which solve stage they run.

**The lam path.** FALKON's entire per-iteration cost is the O(nM) data sweep
``K_nM^T (K_nM gamma)``, which never reads lam — only the preconditioner's
cheap A factor and the lam-ridge term do. ``falkon_fit_path`` exploits this:
L regularization systems are stacked along the CG column axis ((q, L*p)
iterates), the shared sweep runs ONCE per iteration at width L*p, and the
per-system A-solves/ridge are vmapped over a batched (L, q, q) A stack
(``make_preconditioner_path``). Model selection over L lams therefore costs
~1 fit of data passes instead of L — the workflow the Falkon library paper
(Meanti et al. 2020) identifies as dominating practice.

The solve is fully jittable: ``falkon_solve`` is a pure function of
(X, y, centers, preconditioner) so it can be lowered/compiled for the dry-run
like any train_step.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.ops import (
    CachePlanWarning, DistributedOps, KernelCache, KernelOps, available_ops,
    data_shards, get_ops, plan_cache, resolve_precision
)

from .cg import conjugate_gradient, conjugate_gradient_host
from .kernels import KernelFn, make_kernel
from .minibatch import (
    MinibatchConfig, MinibatchResult, minibatch_solve, minibatch_solve_stream
)
from .nystrom import NystromCenters, select_centers
from .preconditioner import (
    Preconditioner, PreconditionerPath, make_preconditioner, make_preconditioner_path,
    resolve_factor_plan
)

Array = jax.Array

CENTER_SELECTIONS = ("uniform", "leverage")

# knm_cache modes: "off" recomputes K_nM every sweep (the seed behavior,
# bit-identical); "auto" lets plan_cache route by the memory budgets;
# "device"/"host" force a residency tier (refusing, not spilling, when the
# forced tier is unavailable — e.g. host under a mesh).
KNM_CACHE_MODES = ("off", "auto", "device", "host")

# Steps of each of the two power iterations behind ``estimate_cond``; each
# takes one sweep per step plus one for its Rayleigh quotient.
POWER_ITERS = 12

_MATVEC_IMPL_DEPRECATION = (
    "matvec_impl is a deprecated alias of ops_impl (renamed in the KernelOps "
    "refactor); pass ops_impl instead"
)


@dataclasses.dataclass(frozen=True)
class FalkonConfig:
    kernel: str = "gaussian"
    kernel_params: tuple = (("sigma", 1.0),)
    lam: float = 1e-6
    num_centers: int = 1024
    iterations: int = 20
    center_selection: str = "uniform"      # "uniform" | "leverage"
    pilot_size: int = 256                  # leverage-score pilot subset
    block_size: int = 2048
    jitter: float | None = None
    rank_deficient: bool = False
    ops_impl: str = "jnp"                  # KernelOps backend: "jnp" | "pallas"
    precision: str = "fp32"                # PrecisionPolicy name: "fp32" |
                                           # "bf16" (end-to-end bf16 storage,
                                           # compensated fp32 accumulation)
    matvec_impl: str | None = None         # deprecated alias of ops_impl
    tol: float = 0.0
    dtype: str = "float32"
    estimate_cond: bool = True             # power-iteration cond(W) diagnostic
    knm_cache: str = "off"                 # materialized-K_nM cache: "off" |
                                           # "auto" | "device" | "host" (see
                                           # repro.ops.KernelCache)
    mesh: Mesh | None = None               # data-parallel mesh (None = single
                                           # device); make_ops wraps the
                                           # backend in DistributedOps
    data_axes: tuple[str, ...] = ("data",)  # mesh axes the rows shard over

    def __post_init__(self):
        """Fail on an unknown backend/policy/scheme at CONFIG time, naming
        the options — not deep inside ``get_ops`` at solve time."""
        if self.matvec_impl is not None:
            warnings.warn(_MATVEC_IMPL_DEPRECATION, DeprecationWarning, stacklevel=3)
        if self.impl not in available_ops():
            raise ValueError(
                f"unknown ops_impl {self.impl!r}; registered KernelOps "
                f"backends: {available_ops()}")
        resolve_precision(self.precision)  # raises naming the known policies
        if self.knm_cache not in KNM_CACHE_MODES:
            raise ValueError(
                f"unknown knm_cache {self.knm_cache!r}; "
                f"supported: {KNM_CACHE_MODES}")
        if self.center_selection not in CENTER_SELECTIONS:
            raise ValueError(
                f"unknown center_selection {self.center_selection!r}; "
                f"supported: {CENTER_SELECTIONS}")
        if self.mesh is not None:
            missing = [a for a in self.data_axes if a not in self.mesh.shape]
            if missing:
                raise ValueError(
                    f"data_axes {missing} not in mesh axes " f"{tuple(self.mesh.shape)}"
                )

    @property
    def impl(self) -> str:
        """Resolved backend name (honors the deprecated ``matvec_impl``)."""
        return self.matvec_impl if self.matvec_impl is not None else self.ops_impl

    def make_kernel(self) -> KernelFn:
        return make_kernel(self.kernel, **dict(self.kernel_params))

    def make_ops(self, kernel: KernelFn | None = None) -> KernelOps:
        """The backend every stage of a fit runs on — wrapped in
        :class:`DistributedOps` when a ``mesh`` is configured, so sharding
        is decided here once and inherited by every fit/predict path."""
        ops = get_ops(
            self.impl,
            kernel if kernel is not None else self.make_kernel(),
            block_size=self.block_size,
            precision=self.precision,
        )
        if self.mesh is not None:
            ops = DistributedOps(ops, self.mesh, self.data_axes)
        return ops


class FalkonState(NamedTuple):
    """Everything needed to run / resume the iterative solve."""
    centers: Array
    precond: Preconditioner
    beta: Array
    alpha: Array
    residual_norms: Array
    cond_estimate: Array


class FalkonPathState(NamedTuple):
    """The lam-path twin of :class:`FalkonState`: one CG run, L systems."""
    centers: Array
    precond: PreconditionerPath
    beta: Array            # (q, L*p) stacked CG solution
    alphas: Array          # (L, M) or (L, M, p): per-lam coefficients
    residual_norms: Array  # (t+1, L*p) per-column residual history
    lams: Array            # (L,) the regularization grid


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FalkonEstimator:
    centers: Array
    alpha: Array
    kernel: KernelFn
    block_size: int = dataclasses.field(metadata=dict(static=True), default=2048)
    ops_impl: str = dataclasses.field(metadata=dict(static=True), default="jnp")
    precision: str = dataclasses.field(metadata=dict(static=True), default="fp32")
    # Fit-time state the incremental path needs: the factored preconditioner
    # and its lam. None on estimators built before PR 8 / by hand — predict
    # works regardless; partial_fit refuses with guidance.
    precond: Preconditioner | None = None
    lam: float | None = dataclasses.field(metadata=dict(static=True), default=None)
    # The fit's data mesh: a mesh-fit estimator scores through
    # DistributedOps too (row-sharded apply). Its centers/alpha live on the
    # mesh, and a Pallas kernel fed mesh-placed arrays outside a shard_map
    # cannot be partitioned by the TPU compiler.
    mesh: Mesh | None = dataclasses.field(metadata=dict(static=True), default=None)
    data_axes: tuple[str, ...] = dataclasses.field(
        metadata=dict(static=True), default=("data",))

    @functools.cached_property
    def _ops(self) -> KernelOps:
        # cached on the instance (cached_property writes __dict__ directly,
        # so the frozen dataclass is fine — same trick as _jitted_ops): the
        # backend + resolved precision policy are built ONCE, not rebuilt
        # via get_ops on every predict() call. Both predict paths and the
        # serving layer route through this one object.
        ops = get_ops(
            self.ops_impl,
            self.kernel,
            block_size=self.block_size,
            precision=self.precision,
        )
        if self.mesh is not None:
            ops = DistributedOps(ops, self.mesh, self.data_axes)
        return ops

    def build_knm_cache(self, X: Array, *, tier: str | None = None) -> KernelCache:
        """Materialize K(X, centers) once for REPEATED scoring of the same X.

        The serving twin of the fit-time cache: re-scoring a fixed
        evaluation set (a val fold every partial_fit, a dashboard panel, a
        lam-path model-selection grid) pays the kernel once, and every
        later ``predict(X, cache=...)`` is one GEMM. The cache is also kept
        on the estimator (``__dict__``, same trick as ``_ops`` — the frozen
        dataclass is fine), so plain ``predict(X)`` with the SAME X object
        hits it automatically; any other X falls back to recompute. ``tier``
        forces residency; None auto-routes via ``plan_cache``. Raises if
        the plan routes "off" — a scoring set too big for both budgets
        should stream (``predict_stream``), not cache.
        """
        X = jnp.asarray(X, self.centers.dtype)
        plan = plan_cache(
            int(X.shape[0]), int(self.centers.shape[0]),
            policy=self._ops.policy, tier=tier,
        )
        cache = KernelCache(self._ops, X, self.centers, plan=plan)
        self.__dict__["_knm_cache"] = cache
        return cache

    def predict(self, X: Array, *, cache: KernelCache | None = None) -> Array:
        """Score X — from the cache's stored tiles when one covers exactly
        this (X, centers) pair, else by a fresh kernel apply.

        An EXPLICIT ``cache`` must serve: a stale (``invalidate()``-d),
        foreign-centers or wrong-X cache raises rather than silently
        recomputing — the refusal ``swap_model`` relies on. The implicitly
        stored one (``build_knm_cache``) is only a fast path and is skipped
        when it doesn't match.
        """
        if cache is None:
            held = self.__dict__.get("_knm_cache")
            if (held is not None and held.matches(self.centers)
                    and X is held.X):
                cache = held
            else:
                return self._ops.apply(X, self.centers, self.alpha)
        cache.check_serves(self.centers, int(X.shape[0]), X=X)
        return cache.apply(self.alpha)

    @functools.cached_property
    def _jitted_ops(self):
        # jit wrappers over the cached ops: repeat predict_stream calls
        # reuse the same XLA compile cache per chunk shape.
        from repro.data.streaming import JittedOps
        return JittedOps(self._ops)

    def predict_stream(self, loader, *, cache: KernelCache | None = None) -> Array:
        """Predict over a ``StreamingLoader``/iterable of (X_chunk, _) pairs
        — X need never be device-resident at once (see repro.data.streaming).

        With a ``cache`` (built over the loader's rows, in order), the
        stream is not read at all: the stored tiles already ARE the kernel
        entries, so the whole prediction is the cache's GEMM apply. The
        cache must serve this model (stale/foreign raises) and cover the
        loader's exact row count.
        """
        from repro.data.streaming import streaming_apply
        if cache is not None:
            cache.check_serves(self.centers, getattr(loader, "n_rows", None))
            return cache.apply(self.alpha)
        return streaming_apply(self._jitted_ops, loader, self.centers, self.alpha)

    def partial_fit(
        self,
        X_tail: Array,
        y_tail: Array,
        minibatch: "MinibatchConfig | None" = None,
        *,
        key: Array | None = None,
    ) -> "FalkonEstimator":
        """Refresh the model from a data tail WITHOUT a full refit.

        The production scenario the exact solver can't touch: a serving
        model absorbing a live-traffic tail. Everything O(M^3)/O(nM) that a
        refit would redo is REUSED — the Nystrom centers, the factored
        preconditioner (its ``FactorPlan`` routing was decided at fit time)
        and the deployed alpha, pulled back to the preconditioned space via
        ``Preconditioner.beta_of_coeffs`` as the warm start. The tail then
        trains with the delayed-projection mini-batch rule at chunk-sweep
        cost per step.

        Returns a NEW estimator (this class is a frozen pytree): same
        centers object, same alpha shape/dtype — so a serving tier that
        swaps it behind compiled applies sees ZERO retraces by construction
        (asserted via the serve trace counter in tests/test_minibatch.py).
        """
        if self.precond is None or self.lam is None:
            raise ValueError(
                "partial_fit needs the fit-time preconditioner, but this "
                "estimator does not carry one (it was built by hand or by a "
                "pre-partial_fit fit). Refit with falkon_fit / "
                "falkon_fit_minibatch / falkon_fit_streaming, which attach "
                "precond and lam to the estimator."
            )
        mb = minibatch if minibatch is not None else MinibatchConfig()
        if key is None:
            key = jax.random.PRNGKey(0)
        dt = self.centers.dtype
        X_tail = jnp.asarray(X_tail, dt)
        y_tail = jnp.asarray(y_tail, dt)
        want = (self.precond.q,) + y_tail.shape[1:]
        beta0 = self.precond.beta_of_coeffs(self.alpha)
        if beta0.shape != want:
            raise ValueError(
                f"y_tail implies a {want} iterate but the deployed alpha "
                f"warm-starts a {beta0.shape} one — the tail's output width "
                f"must match the fitted model's"
            )
        result = minibatch_solve(
            X_tail,
            y_tail,
            self.centers,
            self.precond,
            self.lam,
            mb,
            ops=self._ops,
            key=key,
            beta0=beta0.astype(dt),
        )
        alpha = result.alpha.astype(self.alpha.dtype)
        return dataclasses.replace(self, alpha=alpha)

    def __call__(self, X: Array) -> Array:
        return self.predict(X)


class FalkonPathResult(NamedTuple):
    """Per-lam estimators + the shared-solve state + validation selection."""
    estimators: tuple[FalkonEstimator, ...]
    state: FalkonPathState
    lams: tuple[float, ...]
    val_scores: Array | None   # (L,) validation MSE per lam (None: no val set)
    best_index: int | None     # argmin of val_scores (None: no val set)

    @property
    def best(self) -> FalkonEstimator | None:
        """The validation-selected estimator (None without a val set)."""
        return None if self.best_index is None else self.estimators[self.best_index]


# ----------------------------------------------------------------------------
# Pure solve (jittable)
# ----------------------------------------------------------------------------
def _cg_storage(ops: KernelOps | None):
    """The CG iterate storage dtype the backend's precision policy implies.

    Under the bf16 end-to-end policy the CG vectors x/r/p — the (q, p)
    buffers every sweep reads — are stored bfloat16 with all scalars fp32
    (see repro.core.cg); the fp32 policy returns None, i.e. the unchanged
    full-precision recurrence.
    """
    pol = getattr(ops, "policy", None)
    if pol is None or pol.storage == "float32":
        return None
    return pol.storage


def _falkon_operator(
    matvec: Callable,
    precond: "Preconditioner | PreconditionerPath",
    lam,
    n: int,
) -> Callable[[Array], Array]:
    """W(u) = B^T H B u via Alg. 1's nested-solve composition.

    W u = left( KnM^T(KnM gamma)/n ) + lam-ridge(u), gamma = right(u), with
    the lam-term delegated to the preconditioner's ``ridge`` (the
    T^{-T} Q^T D K_MM D Q T^{-1} = I identity, Lemma 2 / Eq. 19, exactly as
    the MATLAB code does). With a :class:`PreconditionerPath` the SAME
    composition runs on the stacked (q, L*p) block: ``right``/``left`` apply
    the per-system A-solves to each column group while the matvec — the
    one O(nM) cost — is a single lam-independent sweep of width L*p.
    """
    def W(u: Array) -> Array:
        gamma = precond.right(u)
        w = matvec(gamma) / n                     # K_nM^T K_nM gamma / n
        return precond.left(w) + precond.ridge(u, lam)

    return W


def falkon_solve(
    X: Array,
    y: Array,
    centers: Array,
    precond: Preconditioner,
    kernel: KernelFn,
    lam: float,
    t: int,
    *,
    block_size: int = 2048,
    ops_impl: str = "jnp",
    precision: str = "fp32",
    matvec_impl: str | None = None,
    tol: float = 0.0,
    estimate_cond: bool = True,
    ops: KernelOps | None = None,
    cache: KernelCache | None = None,
) -> FalkonState:
    """Run t preconditioned-CG iterations; return coefficients + diagnostics.

    The per-iteration sweep runs on ``ops`` if given, else on the KernelOps
    backend named by ``ops_impl`` (``matvec_impl`` is a deprecated alias —
    using it warns). Distribution is an ``ops`` concern: pass a
    :class:`repro.ops.DistributedOps` (or fit via
    ``FalkonConfig(mesh=...)``) and every sweep below shards over the mesh
    with one (M, p) psum per call — this replaced the retired
    ``dist_matvec``/``make_distributed_matvec`` wrapper.

    With a ``cache`` (a :class:`repro.ops.KernelCache` over exactly this
    (X, centers) pair — ``falkon_fit`` builds one when
    ``config.knm_cache != "off"``), the RHS sweep, every CG matvec AND the
    ``estimate_cond`` power-iteration sweeps consume the stored entries as
    GEMMs: zero kernel evaluations after the one materialization pass. A
    host-tier cache streams tiles through a Python loop, so the CG
    recurrence drops to the host driver (same contract as the streaming
    fits) — device tier keeps the fully-scanned in-core driver.
    """
    n = X.shape[0]
    if ops is None:
        if matvec_impl is not None:
            warnings.warn(_MATVEC_IMPL_DEPRECATION, DeprecationWarning, stacklevel=2)
        impl = matvec_impl if matvec_impl is not None else ops_impl
        ops = get_ops(impl, kernel, block_size=block_size, precision=precision)

    if cache is not None:
        cache.check_serves(centers, n)

        def matvec(g):
            return cache.sweep(g)

        def rhs_sweep():
            zeros = jnp.zeros((centers.shape[0],) + y.shape[1:], X.dtype)
            return cache.sweep(zeros, y)
    else:
        def matvec(g):
            return ops.sweep(X, centers, g, None)

        def rhs_sweep():
            zeros = jnp.zeros((centers.shape[0],) + y.shape[1:], X.dtype)
            return ops.sweep(X, centers, zeros, y)

    W = _falkon_operator(matvec, precond, lam, n)
    # Profiler spans here and below record when this function runs eagerly
    # (``falkon_fit``); under ``jit`` they record once, at trace time.
    with jax.profiler.TraceAnnotation("falkon.rhs", sweeps=1):
        b = precond.left(rhs_sweep() / n)         # r = B^T z / n (Alg. 1)

    host = cache is not None and cache.tier == "host"
    driver = conjugate_gradient_host if host else conjugate_gradient
    cg = driver(W, b, t, tol=tol, storage_dtype=_cg_storage(ops))
    alpha = precond.coeffs(cg.x)

    if not estimate_cond:
        return FalkonState(
            centers=centers,
            precond=precond,
            beta=cg.x,
            alpha=alpha,
            residual_norms=cg.residual_norms,
            cond_estimate=jnp.zeros((), X.dtype),
        )

    # Power-iteration estimate of cond(W) — cheap diagnostic for Thm 2.
    # Its 2 * (POWER_ITERS + 1) width-1 sweeps go through the SAME matvec
    # closure as CG, so a cache serves them as GEMMs too (a host-tier cache
    # cannot trace its tile loop under lax.scan — unroll the recurrence at
    # the host level).
    def power(mv, q, iters=POWER_ITERS):
        v = jnp.ones((q,), b.dtype) / jnp.sqrt(q)
        if host:
            for _ in range(iters):
                w = mv(v)
                v = w / jnp.maximum(jnp.linalg.norm(w), 1e-30)
        else:
            def step(v, _):
                w = mv(v)
                return w / jnp.maximum(jnp.linalg.norm(w), 1e-30), None
            v, _ = jax.lax.scan(step, v, None, length=iters)
        return jnp.vdot(v, mv(v))

    q = precond.q
    with jax.profiler.TraceAnnotation("falkon.cond", sweeps=2 * (POWER_ITERS + 1)):
        lam_max = power(lambda v: W(v.reshape((q,) + (1,) * (b.ndim - 1))).reshape(q), q)
        lam_min = lam_max - power(
            lambda v: lam_max * v - W(v.reshape((q,) + (1,) * (b.ndim - 1))).reshape(q), q
        )
        cond = jnp.abs(lam_max) / jnp.maximum(jnp.abs(lam_min), 1e-30)

    return FalkonState(
        centers=centers,
        precond=precond,
        beta=cg.x,
        alpha=alpha,
        residual_norms=cg.residual_norms,
        cond_estimate=cond,
    )


def _solve_path_core(
    matvec: Callable,
    rhs_sweep: Callable,
    precond: PreconditionerPath,
    n: int,
    t: int,
    *,
    tol: float,
    storage,
    host: bool,
):
    """The shared lam-path solve: ONE RHS sweep + t stacked-matvec CG
    iterations serve all L systems; returns (CGResult, (M, L*p) alphas)."""
    with jax.profiler.TraceAnnotation("falkon.rhs", sweeps=1):
        w0 = rhs_sweep() / n              # K_nM^T y / n — lam-independent
        b = precond.expand_rhs(w0)        # (q, L*p): per-system A^{-T} only
    W = _falkon_operator(matvec, precond, None, n)
    driver = conjugate_gradient_host if host else conjugate_gradient
    cg = driver(W, b, t, tol=tol, storage_dtype=storage)
    return cg, precond.coeffs(cg.x)


def falkon_solve_path(
    X: Array,
    y: Array,
    centers: Array,
    precond: PreconditionerPath,
    t: int,
    *,
    ops: KernelOps,
    tol: float = 0.0,
    cache: KernelCache | None = None,
) -> FalkonPathState:
    """Solve the FALKON system for every lam in ``precond.lams`` at the data
    cost of ONE solve.

    Per CG iteration: a single ``ops.sweep`` of column width L*p (the
    planner routes the widened block — see ``KernelOps.plan(systems=)``)
    instead of L sweeps of width p; the per-system work is O(q^2 L p)
    triangular solves, invisible next to the O(n M) sweep. Per-column
    convergence masking in the CG core doubles as per-SYSTEM masking: a
    small-lam system that needs all t iterations does not force extra
    arithmetic on an already-converged large-lam one.

    A ``cache`` compounds with the path's sharing: the L systems already
    share each sweep, and with stored entries that ONE stacked sweep per
    iteration is a GEMM — a single kernel pass covers the entire lam grid.
    """
    n = X.shape[0]
    M = centers.shape[0]

    if cache is not None:
        cache.check_serves(centers, n)

        def matvec(G):
            return cache.sweep(G)

        def rhs_sweep():
            zeros = jnp.zeros((M,) + y.shape[1:], X.dtype)
            return cache.sweep(zeros, y)
    else:
        def matvec(G):
            return ops.sweep(X, centers, G, None)

        def rhs_sweep():
            zeros = jnp.zeros((M,) + y.shape[1:], X.dtype)
            return ops.sweep(X, centers, zeros, y)

    host = cache is not None and cache.tier == "host"
    cg, alpha_flat = _solve_path_core(
        matvec, rhs_sweep, precond, n, t, tol=tol, storage=_cg_storage(ops), host=host
    )
    alphas = precond.split(alpha_flat)            # (L, M, p)
    if y.ndim == 1:
        alphas = alphas[..., 0]
    return FalkonPathState(
        centers=centers,
        precond=precond,
        beta=cg.x,
        alphas=alphas,
        residual_norms=cg.residual_norms,
        lams=precond.lams,
    )


# ----------------------------------------------------------------------------
# The fit pipeline: select -> gram -> precondition -> solve -> wrap
# ----------------------------------------------------------------------------
def _stage_select(
    key: Array,
    X: Array,
    config: FalkonConfig,
    kernel: KernelFn,
    *,
    lam: float | None = None,
) -> NystromCenters:
    """Stage 1 — Nystrom center selection. ``lam`` overrides ``config.lam``
    for leverage scoring (the path fit scores at a grid-reference lam)."""
    M = min(config.num_centers, X.shape[0])
    with jax.profiler.TraceAnnotation("falkon.select"):
        return select_centers(
            key,
            X,
            M,
            kernel=kernel,
            lam=config.lam if lam is None else lam,
            scheme=config.center_selection,
            pilot_size=config.pilot_size,
        )


def _stage_gram(ops: KernelOps, centers: Array) -> Array:
    """Stage 2 — the M x M Gram block (the paper's memory budget)."""
    with jax.profiler.TraceAnnotation("falkon.gram"):
        return ops.gram(centers, centers)


def _stage_cache(
    ops: KernelOps,
    X: Array,
    centers: Array,
    config: FalkonConfig,
) -> KernelCache | None:
    """Stage 2.5 — the optional materialized-K_nM cache.

    ``knm_cache="auto"`` routes by :func:`repro.ops.plan_cache` (per-shard
    device/host budgets, ``REPRO_KNM_BUDGET_MB`` / ``REPRO_KNM_HOST_BUDGET_MB``)
    and warns with a structured :class:`CachePlanWarning` whenever the
    routing falls off the device tier — silently switching a fit between
    GEMM-served and streamed/recompute sweeps is exactly the surprise the
    sweep/factor planners refuse elsewhere. ``"device"``/``"host"`` force a
    tier (a forced host tier under a mesh raises in ``KernelCache``); an
    ``"off"`` route returns None and the fit takes the recompute path,
    bit-identical to the seed.
    """
    if config.knm_cache == "off":
        return None
    shards = data_shards(ops)
    tier = None if config.knm_cache == "auto" else config.knm_cache
    plan = plan_cache(
        int(X.shape[0]),
        int(centers.shape[0]),
        policy=getattr(ops, "policy", None),
        shards=shards,
        tier=tier,
    )
    if tier is None and plan.tier == "host" and shards > 1:
        # each shard's row block either fits HBM or the fit recomputes;
        # there is no per-shard host-streaming story (see KernelCache)
        plan = dataclasses.replace(
            plan, tier="off",
            reason=f"host tier unsupported under {shards}-way row sharding",
        )
    if tier is None and plan.tier != "device":
        warnings.warn(CachePlanWarning(plan), stacklevel=3)
    if plan.tier == "off":
        return None
    with jax.profiler.TraceAnnotation("falkon.cache", tier=plan.tier):
        return KernelCache(ops, X, centers, plan=plan)


def _stage_precondition(
    KMM: Array,
    lam,
    n: int,
    config: FalkonConfig,
    *,
    D: Array | None = None,
) -> "Preconditioner | PreconditionerPath":
    """Stage 3 — factorization. A scalar ``lam`` builds the single
    :class:`Preconditioner`; a grid builds the batched
    :class:`PreconditionerPath` (shared T/Q/D, (L, q, q) A stack). The
    span's ``path`` is the factor route (``"incore"`` or ``"blocked"``),
    chosen against ``factor_budget_bytes``, derived from the devices'
    ``free_bytes`` (-1: no reading)."""
    build = make_preconditioner if jnp.ndim(lam) == 0 else make_preconditioner_path
    with jax.profiler.TraceAnnotation("falkon.precondition") as span:
        plan = resolve_factor_plan(KMM, None, config.rank_deficient, systems=jnp.size(lam))
        span.set_metadata(path=plan.path, factor_budget_bytes=plan.factor_budget_bytes,
                          free_bytes=plan.free_bytes)
        return build(
            KMM, lam, n, D=D, jitter=config.jitter,
            rank_deficient=config.rank_deficient, factor_plan=plan,
        )


def _resolve_ops(
    config: FalkonConfig,
    kernel: KernelFn,
    ops: KernelOps | None,
) -> KernelOps:
    """The one place every fit variant resolves its backend.

    ``ops=None`` builds from the config (mesh-wrapped when configured). An
    explicit ``ops`` — the instrumentation seam, e.g. ``CountingOps`` — is
    wrapped in :class:`DistributedOps` when the config names a mesh and the
    caller has not already distributed it, so counting facades compose with
    sharding on either side. "Already distributed" is decided by walking the
    whole facade chain (``.inner`` / ``.ops`` delegation attributes), not
    just the outermost wrapper: ``CountingOps(DistributedOps(...))`` must
    not get a second ``shard_map`` over the same mesh axes.
    """
    if ops is None:
        return config.make_ops(kernel)
    if config.mesh is not None and not _wraps_distributed(ops):
        return DistributedOps(ops, config.mesh, config.data_axes)
    return ops


def _wraps_distributed(ops: KernelOps) -> bool:
    """True if ``ops`` is, or anywhere down its facade chain wraps, a
    :class:`DistributedOps`."""
    seen: set[int] = set()
    o: object | None = ops
    while o is not None and id(o) not in seen:
        if isinstance(o, DistributedOps):
            return True
        seen.add(id(o))
        o = getattr(o, "inner", None) or getattr(o, "ops", None)
    return False


def _stage_wrap(
    centers: Array,
    alpha: Array,
    kernel: KernelFn,
    config: FalkonConfig,
    *,
    precond: Preconditioner | None = None,
    lam: float | None = None,
) -> FalkonEstimator:
    """Stage 5 — bind coefficients + backend knobs into the estimator.

    ``precond``/``lam`` attach the fit-time factorization so the estimator
    can ``partial_fit`` later; every fit variant passes them (the path fit
    passes each system's single-lam view). Omitting them still yields a
    fully serving-capable estimator."""
    with jax.profiler.TraceAnnotation("falkon.wrap"):
        return FalkonEstimator(
            centers=centers,
            alpha=alpha,
            kernel=kernel,
            block_size=config.block_size,
            ops_impl=config.impl,
            precision=config.precision,
            precond=precond,
            lam=None if lam is None else float(lam),
            mesh=config.mesh,
            data_axes=tuple(config.data_axes),
        )


def falkon_fit(
    key: Array,
    X: Array,
    y: Array,
    config: FalkonConfig,
    *,
    mesh: Mesh | None = None,
    data_axes: tuple[str, ...] = ("data",),
    ops: KernelOps | None = None,
) -> tuple[FalkonEstimator, FalkonState]:
    """Select centers, build the preconditioner, run the solve.

    With a mesh (``config.mesh``, or the ``mesh=``/``data_axes=`` kwargs,
    which override the config), every sweep runs shard-locally over the data
    axes and is reduced with one (M, p) psum per CG iteration — the backend
    is wrapped in :class:`repro.ops.DistributedOps`, so the fused/two-pass/
    j-sharded planner and the precision policy apply per shard unchanged.
    The K_MM Gram block, every CG sweep and the returned estimator's predict
    path all run on the backend named by ``config.ops_impl`` — or on ``ops``
    when given (the instrumentation seam: e.g. ``repro.ops.CountingOps``).

    The fit is one ``falkon.fit`` profiler span holding one span per stage
    (see docs/architecture.md, "Tracing").
    """
    if mesh is not None:
        config = dataclasses.replace(config, mesh=mesh, data_axes=tuple(data_axes))
    n, d = X.shape
    with jax.profiler.TraceAnnotation(
            "falkon.fit", n=n, M=min(config.num_centers, n), d=d, t=config.iterations):
        kernel = config.make_kernel()
        ops = _resolve_ops(config, kernel, ops)
        dt = jnp.dtype(config.dtype)
        X = X.astype(dt)
        y = y.astype(dt)

        sel = _stage_select(key, X, config, kernel)
        cache = _stage_cache(ops, X, sel.centers, config)
        KMM = _stage_gram(ops, sel.centers)
        precond = _stage_precondition(KMM, config.lam, n, config, D=sel.D)

        state = falkon_solve(
            X,
            y,
            sel.centers,
            precond,
            kernel,
            config.lam,
            config.iterations,
            block_size=config.block_size,
            tol=config.tol,
            estimate_cond=config.estimate_cond,
            ops=ops,
            cache=cache,
        )
        est = _stage_wrap(
            sel.centers, state.alpha, kernel, config, precond=precond, lam=config.lam
        )
        return est, state


def _score_path(
    ops: KernelOps,
    centers: Array,
    alphas: Array,
    X_val: Array,
    y_val: Array,
) -> tuple[Array, int]:
    """Validation MSE per lam with ONE stacked apply over the val set.

    ``alphas`` is the (L, M[, p]) stack; the predictions for every lam come
    from a single ``ops.apply`` of column width L*p — the same
    one-data-pass-serves-all-lams trick as the training sweep.
    """
    L = alphas.shape[0]
    M = alphas.shape[1]
    p = alphas.shape[2] if alphas.ndim > 2 else 1
    flat = alphas.reshape(L, M, p).transpose(1, 0, 2).reshape(M, L * p)
    preds = ops.apply(X_val, centers, flat)            # (n_val, L*p)
    preds = preds.reshape(X_val.shape[0], L, p)
    yv = y_val.reshape(y_val.shape[0], 1, p).astype(preds.dtype)
    scores = jnp.mean((preds - yv) ** 2, axis=(0, 2))  # (L,)
    return scores, int(jnp.argmin(scores))


def _check_lams(lams) -> tuple[float, ...]:
    vals = tuple(float(l) for l in lams)
    if not vals:
        raise ValueError("lams must be a non-empty grid of regularizers")
    if any(l <= 0.0 for l in vals):
        raise ValueError(f"every lam in the path must be > 0, got {vals}")
    return vals


def falkon_fit_path(
    key: Array,
    X: Array,
    y: Array,
    config: FalkonConfig,
    lams,
    *,
    X_val: Array | None = None,
    y_val: Array | None = None,
    ops: KernelOps | None = None,
) -> FalkonPathResult:
    """Fit the FULL regularization path in ~one fit's worth of data sweeps.

    Runs the same select -> gram -> precondition -> solve -> wrap pipeline
    as ``falkon_fit``, but stage 3 builds the batched
    :class:`PreconditionerPath` (one chol(K_MM), L cheap A-Cholesky's) and
    stage 4 runs ``falkon_solve_path``: every O(nM) data sweep carries all L
    systems stacked along the column axis, so the whole path costs
    ``iterations + 1`` sweeps — the same count as ONE ``falkon_fit`` —
    instead of ``L * (iterations + 1)``. ``config.lam`` is ignored; the
    grid ``lams`` replaces it.

    Centers (and, under ``center_selection="leverage"``, the sampling
    diagonal D) are SHARED across the path — a requirement, not a
    shortcut: a common K_nM is what makes the sweep lam-independent.
    Leverage scores are taken at the grid's geometric-mean lam; any fixed
    sampling distribution yields a valid Nystrom model for every lam (the
    lam enters only the ridge).

    With ``X_val``/``y_val`` given, every estimator is scored (one stacked
    apply over the val set) and ``result.best`` is the argmin-MSE model.
    """
    lam_vals = _check_lams(lams)
    kernel = config.make_kernel()
    ops = _resolve_ops(config, kernel, ops)
    dt = jnp.dtype(config.dtype)
    X = X.astype(dt)
    y = y.astype(dt)
    n = X.shape[0]

    # geometric-mean reference lam for (leverage) center selection
    log_mean = sum(jnp.log(jnp.asarray(l)) for l in lam_vals) / len(lam_vals)
    lam_ref = float(jnp.exp(log_mean))
    sel = _stage_select(key, X, config, kernel, lam=lam_ref)
    cache = _stage_cache(ops, X, sel.centers, config)
    KMM = _stage_gram(ops, sel.centers)
    precond = _stage_precondition(KMM, jnp.asarray(lam_vals, dt), n, config, D=sel.D)

    state = falkon_solve_path(
        X, y, sel.centers, precond, config.iterations, ops=ops, tol=config.tol,
        cache=cache,
    )
    ests = tuple(_stage_wrap(sel.centers, state.alphas[i], kernel, config,
                             precond=precond.system(i), lam=lam_vals[i])
                 for i in range(len(lam_vals)))

    val_scores = best = None
    if (X_val is None) != (y_val is None):
        raise ValueError("X_val and y_val must be given together")
    if X_val is not None:
        val_scores, best = _score_path(
            ops, sel.centers, state.alphas, X_val.astype(dt), y_val.astype(dt)
        )
    return FalkonPathResult(
        estimators=ests,
        state=state,
        lams=lam_vals,
        val_scores=val_scores,
        best_index=best,
    )


# ----------------------------------------------------------------------------
# Out-of-core fit: X streamed from the host, never device-resident at once
# ----------------------------------------------------------------------------
def falkon_solve_streaming(
    loader,
    centers: Array,
    precond: Preconditioner,
    lam: float,
    t: int,
    *,
    ops: KernelOps,
    out_dim: tuple = (),
    tol: float = 0.0,
) -> FalkonState:
    """``falkon_solve`` with every data sweep streamed through ``loader``.

    ``loader`` is a re-iterable of (X_chunk, y_chunk) device pairs (see
    ``repro.data.StreamingLoader``); one CG iteration = one full pass over
    the stream, chunk sweeps accumulated on the device — O(chunk + M^2)
    device memory for any n. The CG recurrence runs at the Python level
    (``conjugate_gradient_host``): a host loop cannot live inside lax.scan,
    which also means per-chunk sweeps still jit/cache by chunk shape while
    the solve itself is not one fused XLA program. ``out_dim`` is y's
    trailing shape: () for single-output, (p,) for multi-rhs.
    """
    from repro.data.streaming import JittedOps, streaming_sweep

    n = loader.n_rows
    M = centers.shape[0]
    jops = JittedOps(ops)  # chunks of one shape compile once, not per call

    def matvec(g):
        return streaming_sweep(jops, loader, centers, g, use_targets=False)

    def rhs_sweep():
        zeros = jnp.zeros((M,) + tuple(out_dim), centers.dtype)
        return streaming_sweep(jops, loader, centers, zeros, use_targets=True)

    W = _falkon_operator(matvec, precond, lam, n)
    with jax.profiler.TraceAnnotation("falkon.rhs", sweeps=1):
        b = precond.left(rhs_sweep() / n)
    cg = conjugate_gradient_host(W, b, t, tol=tol, storage_dtype=_cg_storage(ops))
    alpha = precond.coeffs(cg.x)
    return FalkonState(
        centers=centers,
        precond=precond,
        beta=cg.x,
        alpha=alpha,
        residual_norms=cg.residual_norms,
        cond_estimate=jnp.zeros((), b.dtype),
    )


def falkon_solve_path_streaming(
    loader,
    centers: Array,
    precond: PreconditionerPath,
    t: int,
    *,
    ops: KernelOps,
    out_dim: tuple = (),
    tol: float = 0.0,
) -> FalkonPathState:
    """``falkon_solve_path`` with every stacked sweep streamed from the host.

    One full pass over the stream per CG iteration serves all L systems —
    out-of-core n and the lam path compose: the per-chunk sweep just
    carries an (M, L*p) coefficient block instead of (M, p). The host CG
    driver's early stop applies when EVERY system/column has converged (each
    skipped iteration saves a whole pass over the data).
    """
    from repro.data.streaming import JittedOps, streaming_sweep

    n = loader.n_rows
    M = centers.shape[0]
    jops = JittedOps(ops)

    def matvec(G):
        return streaming_sweep(jops, loader, centers, G, use_targets=False)

    def rhs_sweep():
        zeros = jnp.zeros((M,) + tuple(out_dim), centers.dtype)
        return streaming_sweep(jops, loader, centers, zeros, use_targets=True)

    cg, alpha_flat = _solve_path_core(
        matvec, rhs_sweep, precond, n, t, tol=tol, storage=_cg_storage(ops), host=True
    )
    alphas = precond.split(alpha_flat)
    if not tuple(out_dim):
        alphas = alphas[..., 0]
    return FalkonPathState(
        centers=centers,
        precond=precond,
        beta=cg.x,
        alphas=alphas,
        residual_norms=cg.residual_norms,
        lams=precond.lams,
    )


def _streaming_setup(
    key: Array,
    source,
    config: FalkonConfig,
    *,
    prefetch: int | None,
    centers: Array | None,
    ops: KernelOps | None = None,
):
    """Shared front half of the streaming fits: centers, loader, out_dim.

    Centers are sampled uniformly in one host-side pass (exact, not
    reservoir-approximate — n_rows is known). Only
    ``center_selection="uniform"`` is supported out-of-core: leverage-score
    sampling needs a pilot Gram pass that is not chunk-additive.
    """
    from repro.data.streaming import (
        StreamingLoader, default_prefetch, streaming_uniform_centers
    )

    if prefetch is None:
        prefetch = default_prefetch()

    if config.knm_cache != "off":
        raise ValueError(
            "streaming fits do not support knm_cache (got "
            f"{config.knm_cache!r}): the point of streaming X is that "
            "O(n*M) state never materializes — cache the kernel with an "
            "in-core fit, or set knm_cache='off'")
    if config.center_selection != "uniform" and centers is None:
        raise ValueError(
            "streaming fit supports center_selection='uniform' only "
            f"(got {config.center_selection!r})")

    kernel = config.make_kernel()
    ops = _resolve_ops(config, kernel, ops)
    dt = jnp.dtype(config.dtype)
    n = source.n_rows
    M = min(config.num_centers, n)

    if centers is None:
        centers, _ = streaming_uniform_centers(key, source, M)
    centers = jnp.asarray(centers, dt)

    # Under the bf16 policy the host->device chunk transfer itself runs at
    # storage width — half the PCIe/DMA traffic of an fp32 stream; the
    # backend would only re-quantize an fp32 chunk on arrival anyway.
    pol = getattr(ops, "policy", None)
    loader_dt = (
        jnp.dtype(pol.storage) if pol is not None and pol.storage != "float32" else dt
    )
    loader = StreamingLoader(source, prefetch=prefetch, dtype=loader_dt)
    # y's trailing shape from one peeked chunk (hosts only, no transfer)
    out_dim: tuple = ()
    for _, yc in source.chunks():
        if yc is None:
            raise ValueError("streaming fit needs targets in the source")
        out_dim = tuple(yc.shape[1:])
        break
    return kernel, ops, centers, loader, out_dim, n


def falkon_fit_streaming(
    key: Array,
    source,
    config: FalkonConfig,
    *,
    prefetch: int | None = None,
    centers: Array | None = None,
    ops: KernelOps | None = None,
) -> tuple[FalkonEstimator, FalkonState]:
    """Fit FALKON from a ``ChunkSource`` without materializing X on device.

    Same pipeline as ``falkon_fit`` with the select and solve stages swapped
    for their streaming variants: uniform centers from one host-side pass,
    the M x M preconditioner built in-core (the paper's memory budget), then
    every CG sweep streams the chunks through a double-buffered host->device
    loader. ``centers`` overrides sampling (used by parity tests); ``ops``
    overrides the backend (the instrumentation seam — a ``CountingOps``
    under the jitted streaming facade counts XLA compiles, which is how
    tests pin the one-compile-per-fit contract for ragged tail chunks).
    ``prefetch`` defaults to 2 chunks in flight on real accelerators and to
    synchronous transfers on CPU, where an overlap thread only contends with
    compute.
    """
    kernel, ops, centers, loader, out_dim, n = _streaming_setup(
        key, source, config, prefetch=prefetch, centers=centers, ops=ops
    )
    KMM = _stage_gram(ops, centers)
    precond = _stage_precondition(KMM, config.lam, n, config)

    state = falkon_solve_streaming(
        loader,
        centers,
        precond,
        config.lam,
        config.iterations,
        ops=ops,
        out_dim=out_dim,
        tol=config.tol,
    )
    est = _stage_wrap(
        centers, state.alpha, kernel, config, precond=precond, lam=config.lam
    )
    return est, state


def falkon_fit_path_streaming(
    key: Array,
    source,
    config: FalkonConfig,
    lams,
    *,
    prefetch: int | None = None,
    centers: Array | None = None,
    ops: KernelOps | None = None,
) -> FalkonPathResult:
    """``falkon_fit_path`` for a host-streamed ``ChunkSource``.

    The whole L-lam path costs the stream passes of ONE fit: per CG
    iteration one pass over the chunks, each chunk sweep carrying the
    stacked (M, L*p) block. Validation scoring is not built in (the val set
    would need its own stream); score the returned estimators with
    ``FalkonEstimator.predict_stream``.
    """
    lam_vals = _check_lams(lams)
    kernel, ops, centers, loader, out_dim, n = _streaming_setup(
        key, source, config, prefetch=prefetch, centers=centers, ops=ops
    )
    dt = jnp.dtype(config.dtype)
    KMM = _stage_gram(ops, centers)
    precond = _stage_precondition(KMM, jnp.asarray(lam_vals, dt), n, config)

    state = falkon_solve_path_streaming(
        loader,
        centers,
        precond,
        config.iterations,
        ops=ops,
        out_dim=out_dim,
        tol=config.tol,
    )
    ests = tuple(_stage_wrap(centers, state.alphas[i], kernel, config,
                             precond=precond.system(i), lam=lam_vals[i])
                 for i in range(len(lam_vals)))
    return FalkonPathResult(
        estimators=ests, state=state, lams=lam_vals, val_scores=None, best_index=None
    )


# ----------------------------------------------------------------------------
# Mini-batch fit: delayed-projection stochastic solve (see core/minibatch.py)
# ----------------------------------------------------------------------------
def falkon_fit_minibatch(
    key: Array,
    X: Array,
    y: Array,
    config: FalkonConfig,
    minibatch: MinibatchConfig | None = None,
    *,
    centers: Array | None = None,
    ops: KernelOps | None = None,
    beta0: Array | None = None,
) -> tuple[FalkonEstimator, MinibatchResult]:
    """Fit by stochastic preconditioned sweeps with delayed projections.

    Same select -> gram -> precondition pipeline as ``falkon_fit`` — the
    preconditioner is factored ONCE (through the same ``FactorPlan``
    in-core/blocked routing) and reused by every projection — but the solve
    stage is the mini-batch driver: per step one chunk-sized sweep (not a
    full O(nM) pass), a projection every ``minibatch.project_every`` steps,
    epoch reshuffling, tail averaging. ``config.iterations``/``config.tol``
    are CG knobs and are ignored here; the budget lives in ``minibatch``
    (``epochs`` x ``chunk_rows`` x ``project_every``). ``centers`` overrides
    selection (parity tests / shared-center comparisons), ``ops`` is the
    instrumentation seam, ``beta0`` warm-starts (what ``partial_fit``
    passes). Prefer this over full CG when epochs-to-target-MSE x n is
    smaller than (iterations + 1) x n — see README's step-cost model.
    """
    mb = minibatch if minibatch is not None else MinibatchConfig()
    if config.knm_cache != "off":
        raise ValueError(
            "the mini-batch solver does not support knm_cache (got "
            f"{config.knm_cache!r}): each step sweeps a fresh shuffled "
            "chunk, so there is no fixed tile set to materialize — use "
            "falkon_fit for cached sweeps, or set knm_cache='off'")
    kernel = config.make_kernel()
    ops = _resolve_ops(config, kernel, ops)
    dt = jnp.dtype(config.dtype)
    X = X.astype(dt)
    y = y.astype(dt)
    n = X.shape[0]

    key_sel, key_shuffle = jax.random.split(key)
    if centers is None:
        sel = _stage_select(key_sel, X, config, kernel)
        centers_arr, D = sel.centers, sel.D
    else:
        centers_arr, D = jnp.asarray(centers, dt), None
    KMM = _stage_gram(ops, centers_arr)
    precond = _stage_precondition(KMM, config.lam, n, config, D=D)

    result = minibatch_solve(
        X,
        y,
        centers_arr,
        precond,
        config.lam,
        mb,
        ops=ops,
        key=key_shuffle,
        beta0=beta0,
    )
    est = _stage_wrap(
        centers_arr, result.alpha, kernel, config, precond=precond, lam=config.lam
    )
    return est, result


def falkon_fit_minibatch_streaming(
    key: Array,
    source,
    config: FalkonConfig,
    minibatch: MinibatchConfig | None = None,
    *,
    prefetch: int | None = None,
    centers: Array | None = None,
    ops: KernelOps | None = None,
    beta0: Array | None = None,
) -> tuple[FalkonEstimator, MinibatchResult]:
    """``falkon_fit_minibatch`` for a host-streamed ``ChunkSource``.

    The out-of-core twin: the same front half as ``falkon_fit_streaming``
    (uniform centers in one host pass, in-core M x M preconditioner), then
    the host-driven mini-batch loop. With ``minibatch.shuffle`` the source
    is wrapped in :class:`repro.data.ShuffledChunkSource`, whose every pass
    (= every epoch) draws a fresh windowed shuffle of the chunk order plus
    in-chunk row shuffles — epoch reshuffling without materializing n rows.
    Unlike full streaming CG (one full pass per iteration), each update here
    costs ``project_every`` chunk transfers + sweeps.
    """
    mb = minibatch if minibatch is not None else MinibatchConfig()
    if mb.shuffle:
        from repro.data.streaming import ShuffledChunkSource

        seed = int(jax.random.randint(jax.random.fold_in(key, 7), (), 0, 2**31 - 1))
        source = ShuffledChunkSource(source, seed=seed)
    kernel, ops, centers, loader, out_dim, n = _streaming_setup(
        key, source, config, prefetch=prefetch, centers=centers, ops=ops
    )
    KMM = _stage_gram(ops, centers)
    precond = _stage_precondition(KMM, config.lam, n, config)

    result = minibatch_solve_stream(
        loader,
        centers,
        precond,
        config.lam,
        mb,
        ops=ops,
        out_dim=out_dim,
        beta0=beta0,
    )
    est = _stage_wrap(
        centers, result.alpha, kernel, config, precond=precond, lam=config.lam
    )
    return est, result
