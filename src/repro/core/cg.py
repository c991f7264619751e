"""Conjugate gradient for the FALKON preconditioned system.

Matches the paper's Alg. 2 ``conjgrad``: plain CG (the system W = B^T H B is
symmetric positive definite by construction, Lemma 5), fixed iteration count so
the whole solve jits into one XLA program, with an optional residual tolerance
implemented as a masked no-op (keeps the program shape static, which is what we
need for pjit/shard_map and for the dry-run).

Supports multiple right-hand sides (b of shape (q,) or (q, p)) — multiclass
problems (TIMIT / IMAGENET in the paper) solve all one-vs-all systems in one CG
run; the per-column scalars are kept separate. The lam-path solver stacks L
INDEPENDENT regularization systems along the same column axis (see
``falkon_solve_path``): because every scalar of the recurrence is per-column,
a (q, L*p) block is exactly L*p independent CG runs that share each matvec —
per-system convergence masking falls out of the per-column masking for free.

Both drivers — the in-core ``lax.scan`` one (``conjugate_gradient``) and the
host-loop one for streaming matvecs (``conjugate_gradient_host``) — are thin
shells over one shared core (``_cg_solve``): same initialization, same masked
update (``_masked_cg_update``), same residual bookkeeping, so the in-core and
out-of-core solves cannot numerically diverge and any capability added to the
update (multi-rhs, lam-path stacking, reduced-storage iterates) reaches both
for free. They differ ONLY in the loop: the scanned driver keeps the program
shape static (converged columns become masked no-ops), the host driver may
``break`` early once every column has converged — each skipped iteration is
a full data pass saved — which truncates ``residual_norms`` to
``iterations + 1`` entries (a pinned contract, see tests/test_cg_drivers.py).

``storage_dtype`` (the bf16 end-to-end policy's knob, threaded from
``PrecisionPolicy.storage`` by ``falkon_solve``) stores the CG iterates
x/r/p at reduced width — they are the (q, p) vectors every sweep reads and
writes — while ALL scalars (alpha, beta, rs, residual norms) and the update
arithmetic stay float32: the recurrence is computed full-precision and only
the iterates are rounded back to storage. ``storage_dtype=None`` (default)
is byte-for-byte the pre-policy fp32 path.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

Array = jax.Array


class CGResult(NamedTuple):
    x: Array
    residual_norms: Array  # (t+1,) or (t+1, p): ||r||_2 after each iteration
    iterations: Array      # scalar int: iterations actually applied (tol-aware)


def col_dot(u, v):
    """Per-column inner products: (q,) -> scalar, (q, p) -> (p,).

    The reduction every per-column scalar of the CG recurrence is built
    from; the mini-batch solver reuses it for its per-projection gradient
    norms so the two solvers share one definition of "column magnitude".
    """
    return jnp.sum(u * v, axis=0)


_col_dot = col_dot  # internal alias kept for call-site symmetry below


def active_columns(rs, tol_sq):
    """The per-column "still iterating" mask both solvers share.

    A column whose squared residual/gradient norm ``rs`` has dropped to
    ``tol_sq`` (floored at 1e-30 so a tol of 0 still masks exact zeros,
    whose rs/denom ratios would otherwise overflow) is DONE: CG turns its
    update into a masked no-op (``_masked_cg_update``), and the mini-batch
    projection freezes its beta/velocity the same way — converged columns
    of a multi-rhs block must not keep taking noisy stochastic steps.
    """
    return rs > jnp.maximum(tol_sq, 1e-30)


def _masked_cg_update(x, r, p, rs, Ap, tol_sq, storage=None):
    """One CG update with PER-COLUMN convergence masking.

    Once a column's residual hits fp32 noise, rs/denom can overflow and
    poison every later iterate of that column (observed on one-vs-all
    systems with rare classes) — converged columns become masked no-ops.
    Shared by the scanned (``conjugate_gradient``) and host-loop
    (``conjugate_gradient_host``) drivers so the in-core and streaming
    solves cannot numerically diverge. Returns the updated
    (x, r, p, rs, active) with ``active`` the pre-update mask.

    With ``storage`` set the incoming iterates are promoted to float32, the
    whole update (alpha/beta/norm scalars included) is computed in float32,
    and only the outgoing x/r/p are rounded back to ``storage``.
    """
    if storage is not None:
        f32 = jnp.float32
        x, r, p, Ap = (a.astype(f32) for a in (x, r, p, Ap))
        rs = rs.astype(f32)
    active = active_columns(rs, tol_sq)
    denom = _col_dot(p, Ap)
    a = jnp.where(active & (denom > 1e-38), rs / jnp.maximum(denom, 1e-38), 0.0)
    x_new = x + a * p
    r_new = r - a * Ap
    rs_new = _col_dot(r_new, r_new)
    beta = jnp.where(active, rs_new / jnp.maximum(rs, 1e-38), 0.0)
    p_new = r_new + beta * p
    sel = lambda new, old: jnp.where(active, new, old)
    x, r, p, rs = (sel(x_new, x), sel(r_new, r), sel(p_new, p), sel(rs_new, rs))
    if storage is not None:
        x, r, p = (a.astype(storage) for a in (x, r, p))
    return x, r, p, rs, active


def _cg_init(matvec, b, x0, storage):
    """Shared iterate/residual initialization for both drivers."""
    if x0 is None:
        x = jnp.zeros_like(b)
        r = b
    else:
        x = x0
        r = b - matvec(x0)
    p = r
    if storage is not None:
        x, r, p = (a.astype(storage) for a in (x, r, p))
        rs = _col_dot(r.astype(b.dtype), r.astype(b.dtype))
    else:
        rs = _col_dot(r, r)
    return x, r, p, rs


def _scan_driver(matvec, state, t, tol_sq, storage, res0, span, swept):
    """Fixed-length ``lax.scan`` loop — one static XLA program; converged
    columns become masked no-ops (the dry-run wants the full-t shape), so
    all ``t`` matvecs run whatever the tolerance."""
    span.set_metadata(sweeps=swept + t, iterations=t)
    def step(carry, _):
        x, r, p, rs, it = carry
        Ap = matvec(p)
        x, r, p, rs, active = _masked_cg_update(
            x, r, p, rs, Ap, tol_sq, storage=storage
        )
        carry = (x, r, p, rs, it + jnp.any(active).astype(jnp.int32))
        return carry, jnp.sqrt(jnp.maximum(rs, 0.0))

    (x, r, p, rs, it), res_hist = jax.lax.scan(
        step, state + (jnp.asarray(0, jnp.int32),), None, length=t
    )
    return CGResult(
        x=x, residual_norms=jnp.concatenate([res0, res_hist], axis=0), iterations=it
    )


def _host_driver(matvec, state, t, tol_sq, storage, res0, span, swept):
    """Python-level loop for host-streaming matvecs; stops early once every
    column has converged (each skipped iteration is a full data pass), so
    ``residual_norms`` is truncated to ``iterations + 1`` entries. Each
    executed iteration is a ``falkon.cg.step`` span of one sweep."""
    x, r, p, rs = state
    residuals = [res0]
    it = 0
    for _ in range(t):
        if not bool(jnp.any(active_columns(rs, tol_sq))):
            break  # every column converged — skip the remaining data passes
        with jax.profiler.TraceAnnotation("falkon.cg.step", sweeps=1):
            Ap = matvec(p)
            x, r, p, rs, _ = _masked_cg_update(x, r, p, rs, Ap, tol_sq, storage=storage)
            residuals.append(jnp.sqrt(jnp.maximum(rs, 0.0))[None])
        it += 1
    span.set_metadata(sweeps=swept, iterations=it)
    return CGResult(
        x=x,
        residual_norms=jnp.concatenate(residuals, axis=0),
        iterations=jnp.asarray(it, jnp.int32),
    )


def _cg_solve(matvec, b, t, tol, x0, storage_dtype, driver):
    """The one CG core both public drivers share: initialization, tolerance
    scaling and the ||b|| history head are computed identically, then the
    ``driver`` runs the shared masked update in its loop style.

    The solve is one ``falkon.cg`` profiler span whose ``sweeps`` count the
    matvecs issued (one data sweep each in FALKON's W, the x0 residual's
    included) and ``iterations`` the CG steps run. Under ``jit`` the span
    records when the solve is traced, not when it runs."""
    with jax.profiler.TraceAnnotation("falkon.cg") as span:
        storage = None if storage_dtype is None else jnp.dtype(storage_dtype)
        state = _cg_init(matvec, b, x0, storage)
        b_norm_sq = jnp.maximum(_col_dot(b, b), 1e-38)
        tol_sq = (tol * tol) * b_norm_sq
        # ||b|| leads the history; [None] gives the (1,)/(1, p) leading entry
        # for single- and multi-rhs alike.
        res0 = jnp.sqrt(jnp.maximum(_col_dot(b, b), 0.0))[None]
        swept = int(x0 is not None)
        return driver(matvec, state, t, tol_sq, storage, res0, span, swept)


def conjugate_gradient(
    matvec: Callable[[Array], Array],
    b: Array,
    t: int,
    *,
    tol: float = 0.0,
    x0: Array | None = None,
    storage_dtype=None,
) -> CGResult:
    """Run ``t`` CG iterations on ``matvec(x) = b``.

    When ``tol > 0`` iterations whose residual norm has already dropped below
    ``tol * ||b||`` become masked no-ops (identical output, static shape).
    ``storage_dtype`` stores the iterates x/r/p at reduced width (bf16
    policy) while scalars and update arithmetic stay float32; None is the
    unchanged full-precision path.
    """
    return _cg_solve(matvec, b, t, tol, x0, storage_dtype, _scan_driver)


def conjugate_gradient_host(
    matvec: Callable[[Array], Array],
    b: Array,
    t: int,
    *,
    tol: float = 0.0,
    x0: Array | None = None,
    storage_dtype=None,
) -> CGResult:
    """Python-loop twin of ``conjugate_gradient`` for host-streaming matvecs.

    The streaming sweep is a host loop over data chunks (one full pass per
    CG iteration), which cannot be traced inside ``lax.scan`` — so the CG
    recurrence itself runs at the Python level via the same shared core and
    masking math (and the same ``storage_dtype`` contract) as the scanned
    version. Unlike the scanned version it may stop early once every column
    has converged (there is no static-shape program to preserve
    out-of-core); ``residual_norms`` then has ``iterations + 1`` entries
    instead of ``t + 1``.
    """
    return _cg_solve(matvec, b, t, tol, x0, storage_dtype, _host_driver)
