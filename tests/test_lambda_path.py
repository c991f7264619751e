"""The lam-path solver: one data sweep serves every hyperparameter.

Acceptance, keyed to the batched-path refactor:

* ``falkon_fit_path`` over an L=8 lam grid matches L independent
  ``falkon_fit`` runs on each alpha — on the fused, two_pass, j_sharded AND
  streaming sweep paths, under the fp32 and bf16 policies. The parity
  tolerance is policy-scaled: 1e-4 relative for fp32; for bf16 the floor is
  the policy's own storage quantization (the CG iterates round through
  eps_bf16 ~ 3.9e-3 in BOTH runs, so any eps_fp32-level reordering between
  the stacked and per-system pipelines surfaces at bf16 ulps) — we pin the
  documented 1e-2 policy ceiling there, matching tests/test_precision.py.
* The path fit issues ~1/L the data sweeps — asserted exactly via the
  ``CountingOps`` facade.
* The planner charges the widened p = L*p column block (``systems=``), so
  fat paths route off the fused path like fat multi-rhs blocks do.
* The leverage-score pilot-Gram build is shared across a lam grid.
* A validation split selects the same lam the L sequential fits select.
"""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import TEST_PRECISION, synthetic_regression
from repro.compat import enable_x64
from repro.core import (
    FalkonConfig,
    approximate_leverage_scores,
    approximate_leverage_scores_path,
    build_leverage_pilot,
    falkon_fit,
    falkon_fit_path,
    falkon_fit_path_streaming,
    falkon_fit_streaming,
    leverage_scores_from_pilot,
    make_kernel,
    make_preconditioner,
    make_preconditioner_path,
)
from repro.ops import CountingOps, SweepPlanWarning, get_ops, plan_sweep

LAMS = tuple(float(10.0**e) for e in np.linspace(-4.0, -1.0, 8))
#: fp32: the acceptance bound. bf16: the policy's documented error ceiling —
#: both runs quantize the CG iterates at eps_bf16, which is the parity floor.
REL_TOL = {"fp32": 1e-4, "bf16": 1e-2}


def _problem(n=400, d=5, seed=0):
    return synthetic_regression(jax.random.PRNGKey(seed), n, d=d)


def _cfg(**kw):
    defaults = dict(
        kernel_params=(("sigma", 1.0),),
        num_centers=64,
        iterations=30,
        block_size=128,
        jitter=1e-5,
        estimate_cond=False,
    )
    defaults.update(kw)
    return FalkonConfig(**defaults)


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-30))


def _assert_path_matches_sequential(X, y, cfg, lams, tol):
    """Shared acceptance core: same key, L sequential fits vs one path fit."""
    key = jax.random.PRNGKey(1)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", SweepPlanWarning)
        res = falkon_fit_path(key, X, y, cfg, lams)
        for i, lam in enumerate(lams):
            est, _ = falkon_fit(key, X, y, dataclasses.replace(cfg, lam=lam))
            rel = _rel(res.estimators[i].alpha, est.alpha)
            assert rel <= tol, f"lam={lam:.2e}: rel alpha gap {rel:.2e} > {tol}"
    return res


# ---------------------------------------------------------------------------
# Parity: jnp reference + every planner-routed Pallas path + streaming
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("precision", ["fp32", "bf16"])
def test_path_matches_sequential_jnp(precision):
    X, y = _problem()
    cfg = _cfg(ops_impl="jnp", precision=precision)
    res = _assert_path_matches_sequential(X, y, cfg, LAMS, REL_TOL[precision])
    assert len(res.estimators) == len(LAMS)
    assert res.state.alphas.shape == (len(LAMS), 64)


def test_path_matches_sequential_pallas_fused():
    """Fused single-pass Pallas sweep (interpret mode on CPU), the CI axis's
    precision policy."""
    X, y = _problem(n=192)
    cfg = _cfg(ops_impl="pallas", precision=TEST_PRECISION, iterations=8)
    ops = cfg.make_ops()
    assert ops.plan(192, 64, 5, 1, systems=len(LAMS)).path == "fused"
    _assert_path_matches_sequential(X, y, cfg, LAMS, REL_TOL[TEST_PRECISION])


@pytest.mark.parametrize("route,n,M,t,budget_mb,sigma,jitter,lam_lo", [
    ("two_pass", 192, 64, 6, 0.05, 1.0, 1e-5, -4.0),
    # j_sharded needs M > the 512-lane shard floor; M=640 of n=768 points
    # makes K_MM near-singular, so this point runs better-conditioned
    # (smaller sigma, bigger jitter, lam >= 1e-3) to keep the fp-noise
    # amplification below the parity tolerance.
    ("j_sharded", 768, 640, 4, 0.1, 0.5, 1e-4, -3.0),
])
def test_path_matches_sequential_pallas_out_of_core(
    monkeypatch, route, n, M, t, budget_mb, sigma, jitter, lam_lo
):
    """The out-of-core sweep schedules under a shrunken VMEM budget: the
    path solve and the sequential fits both route onto ``route`` and still
    agree per alpha."""
    monkeypatch.setenv("REPRO_VMEM_BUDGET_MB", str(budget_mb))
    X, y = _problem(n=n)
    lams = tuple(float(10.0**e) for e in np.linspace(lam_lo, -1.0, 8))
    cfg = _cfg(
        ops_impl="pallas",
        precision=TEST_PRECISION,
        iterations=t,
        num_centers=M,
        kernel_params=(("sigma", sigma),),
        jitter=jitter,
    )
    plan = cfg.make_ops().plan(n, M, 5, 1, systems=len(lams))
    assert plan.path == route, plan
    _assert_path_matches_sequential(X, y, cfg, lams, REL_TOL[TEST_PRECISION])


def test_path_matches_sequential_streaming():
    """Host-streamed chunks: one pass over the stream per CG iteration
    serves all L systems (ragged chunking, same sampled centers by key)."""
    from repro.data.streaming import ArrayChunkSource

    X, y = _problem()
    src = ArrayChunkSource(np.asarray(X), np.asarray(y), chunk_rows=96)
    # better-conditioned than the in-core points: the host CG's per-chunk
    # accumulation order differs between the stacked and thin blocks, and
    # under bf16 iterate storage that reordering costs extra bf16 ulps
    cfg = _cfg(ops_impl="jnp", precision=TEST_PRECISION, jitter=1e-4)
    lams = tuple(float(10.0**e) for e in np.linspace(-3.0, -1.0, 8))
    key = jax.random.PRNGKey(1)
    res = falkon_fit_path_streaming(key, src, cfg, lams)
    tol = REL_TOL[TEST_PRECISION]
    for i, lam in enumerate(lams):
        est, _ = falkon_fit_streaming(key, src, dataclasses.replace(cfg, lam=lam))
        rel = _rel(res.estimators[i].alpha, est.alpha)
        assert rel <= tol, f"lam={lam:.2e}: rel alpha gap {rel:.2e} > {tol}"


# ---------------------------------------------------------------------------
# The claim itself: ~1/L the data sweeps, counted at the ops facade
# ---------------------------------------------------------------------------
def test_path_issues_one_fit_of_sweeps():
    """The path fit's program contains ONE sweep per CG step (RHS + in-scan
    matvec) regardless of L; L sequential fits contain L of each. The
    scanned CG traces its matvec once and executes it t times, so the
    counted call-site ratio equals the executed data-pass ratio: exactly L.
    """
    X, y = _problem()
    cfg = _cfg(ops_impl="jnp")
    kern = cfg.make_kernel()
    key = jax.random.PRNGKey(1)

    path_ops = CountingOps(get_ops("jnp", kern, block_size=cfg.block_size))
    falkon_fit_path(key, X, y, cfg, LAMS, ops=path_ops)

    seq_ops = CountingOps(get_ops("jnp", kern, block_size=cfg.block_size))
    for lam in LAMS:
        falkon_fit(key, X, y, dataclasses.replace(cfg, lam=lam), ops=seq_ops)

    L = len(LAMS)
    assert path_ops.sweeps == 2                  # RHS pass + the scanned matvec
    assert seq_ops.sweeps == L * path_ops.sweeps  # the 1/L sweep claim
    assert path_ops.grams == 1 and seq_ops.grams == L  # one chol(K_MM) total


def test_path_validation_scoring_is_one_apply():
    """Scoring L lams over the val set is ONE stacked apply, not L."""
    X, y = _problem()
    cfg = _cfg(ops_impl="jnp")
    ops = CountingOps(get_ops("jnp", cfg.make_kernel(), block_size=cfg.block_size))
    res = falkon_fit_path(
        jax.random.PRNGKey(1), X, y, cfg, LAMS, X_val=X[:100], y_val=y[:100], ops=ops
    )
    assert ops.applies == 1
    assert res.val_scores.shape == (len(LAMS),)
    assert res.best is res.estimators[res.best_index]


def test_path_validation_selects_sequential_argmin():
    X, y = _problem(seed=3)
    Xv, yv = _problem(seed=9)
    cfg = _cfg(ops_impl="jnp")
    key = jax.random.PRNGKey(1)
    res = falkon_fit_path(key, X, y, cfg, LAMS, X_val=Xv, y_val=yv)
    seq_mse = []
    for lam in LAMS:
        est, _ = falkon_fit(key, X, y, dataclasses.replace(cfg, lam=lam))
        seq_mse.append(float(jnp.mean((est.predict(Xv) - yv) ** 2)))
    assert res.best_index == int(np.argmin(seq_mse))
    np.testing.assert_allclose(
        np.asarray(res.val_scores), seq_mse, rtol=1e-3, atol=1e-5
    )


def test_path_multirhs():
    """Multiclass targets: the stacked block is (q, L*p), split back to
    (L, M, p) coefficient stacks."""
    X, _ = _problem()
    labels = jnp.argmax(jax.random.normal(jax.random.PRNGKey(5), (400, 3)), -1)
    Y = jax.nn.one_hot(labels, 3)
    cfg = _cfg(ops_impl="jnp", iterations=30)
    lams = LAMS[2:6]
    key = jax.random.PRNGKey(1)
    res = falkon_fit_path(key, X, Y, cfg, lams)
    assert res.state.alphas.shape == (4, 64, 3)
    for i, lam in enumerate(lams):
        est, _ = falkon_fit(key, X, Y, dataclasses.replace(cfg, lam=lam))
        assert _rel(res.estimators[i].alpha, est.alpha) <= 1e-4
        assert res.estimators[i].predict(X[:7]).shape == (7, 3)


# ---------------------------------------------------------------------------
# Planner: the widened p = L*p column block routes fat paths off fused
# ---------------------------------------------------------------------------
def test_planner_charges_widened_path_block():
    kern = make_kernel("gaussian", sigma=2.0)
    pops = get_ops("pallas", kern, block_size=2048)
    thin = pops.plan(2048, 2048, 32, 1)
    assert thin.path == "fused" and thin.systems == 1
    fat = pops.plan(2048, 2048, 32, 1, systems=512)
    assert fat.p == 512 and fat.systems == 512
    assert fat.path != "fused", "a 512-system path block must not fit fused"
    # jnp backend reports the same widening through the uniform SweepPlan
    jplan = get_ops("jnp", kern).plan(2048, 2048, 32, 2, systems=8)
    assert jplan.p == 16 and jplan.systems == 8


def test_plan_sweep_systems_equivalent_to_prewidened_p():
    kw = dict(bm=256, bn=512, vmem_budget=4 * 2**20)
    a = plan_sweep(8192, 4096, 32, 2, systems=8, **kw)
    b = plan_sweep(8192, 4096, 32, 16, **kw)
    assert a.path == b.path and a.p == b.p == 16
    assert a.scratch_bytes == b.scratch_bytes and a.io_bytes == b.io_bytes
    assert a.systems == 8 and b.systems == 1


# ---------------------------------------------------------------------------
# Preconditioner path: shared stage + batched A stack
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("rank_deficient", [False, True])
def test_preconditioner_path_matches_singles(rank_deficient):
    kern = make_kernel("gaussian", sigma=1.5)
    C = jax.random.normal(jax.random.PRNGKey(2), (48, 4))
    KMM = kern(C, C)
    lams = LAMS[:5]
    pp = make_preconditioner_path(KMM, lams, 1000, rank_deficient=rank_deficient)
    U = jax.random.normal(jax.random.PRNGKey(3), (pp.q, len(lams) * 2))
    right = pp.right(U)
    left = pp.left(
        jax.random.normal(jax.random.PRNGKey(4), (KMM.shape[0], len(lams) * 2))
    )
    for i, lam in enumerate(lams):
        single = make_preconditioner(KMM, lam, 1000, rank_deficient=rank_deficient)
        np.testing.assert_array_equal(np.asarray(pp.A[i]), np.asarray(single.A))
        # per-system column groups of the stacked maps == the single maps
        # (loose: T^{-1}A^{-1} amplifies batched-vs-plain trsm rounding)
        cols = slice(i * 2, (i + 1) * 2)
        np.testing.assert_allclose(
            np.asarray(right[:, cols]),
            np.asarray(single.right(U[:, cols])),
            rtol=2e-4,
            atol=2e-4,
        )
        sysp = pp.system(i)
        np.testing.assert_array_equal(np.asarray(sysp.A), np.asarray(single.A))
    assert left.shape == (pp.q, len(lams) * 2)


def test_preconditioner_path_expand_rhs_matches_left():
    kern = make_kernel("gaussian", sigma=1.5)
    C = jax.random.normal(jax.random.PRNGKey(2), (32, 4))
    KMM = kern(C, C)
    lams = LAMS[:3]
    pp = make_preconditioner_path(KMM, lams, 500)
    w = jax.random.normal(jax.random.PRNGKey(7), (32,))
    b = pp.expand_rhs(w)                       # (q, L)
    for i, lam in enumerate(lams):
        single = make_preconditioner(KMM, lam, 500)
        np.testing.assert_allclose(
            np.asarray(b[:, i]), np.asarray(single.left(w)), rtol=1e-4, atol=1e-5
        )


def test_preconditioner_path_rejects_empty_grid():
    KMM = jnp.eye(8)
    with pytest.raises(ValueError, match="non-empty"):
        make_preconditioner_path(KMM, [], 100)


def test_preconditioner_path_rejects_nonpositive_lams():
    """Direct builder callers get an error, not the batched Cholesky's
    silent NaNs (the fit wrappers validate separately)."""
    KMM = jnp.eye(8)
    with pytest.raises(ValueError, match="> 0"):
        make_preconditioner_path(KMM, [1e-3, -1e-3], 100)
    with pytest.raises(ValueError, match="> 0"):
        make_preconditioner_path(KMM, [0.0], 100)


# ---------------------------------------------------------------------------
# Leverage scores: pilot-Gram build shared across the lam grid
# ---------------------------------------------------------------------------
def test_leverage_pilot_reuse_matches_single_shot():
    X, _ = _problem(n=300)
    kern = make_kernel("gaussian", sigma=2.0)
    key = jax.random.PRNGKey(11)
    pilot = build_leverage_pilot(key, X, kern, pilot_size=64, block_size=128)
    for lam in (1e-4, 1e-2):
        composed = leverage_scores_from_pilot(pilot, X, kern, lam, block_size=128)
        one_shot = approximate_leverage_scores(
            key, X, kern, lam, pilot_size=64, block_size=128
        )
        np.testing.assert_allclose(
            np.asarray(composed), np.asarray(one_shot), rtol=1e-6
        )
    grid = approximate_leverage_scores_path(
        key, X, kern, (1e-4, 1e-2), pilot_size=64, block_size=128
    )
    assert grid.shape == (2, 300)
    np.testing.assert_allclose(
        np.asarray(grid[1]),
        np.asarray(approximate_leverage_scores(key, X, kern, 1e-2,
                                               pilot_size=64,
                                               block_size=128)),
        rtol=1e-6)


def test_path_fit_leverage_selection_shares_centers():
    X, y = _problem()
    cfg = _cfg(center_selection="leverage", pilot_size=96, iterations=15)
    res = falkon_fit_path(jax.random.PRNGKey(1), X, y, cfg, LAMS[:4])
    assert all(est.centers is res.estimators[0].centers for est in res.estimators)
    for est in res.estimators:
        assert bool(jnp.all(jnp.isfinite(est.alpha)))
    est = res.estimators[0]
    mse = float(jnp.mean((est.predict(X) - y) ** 2))
    J_t, bound = _cg_objective_bound(est, X, y, LAMS[0], cfg.iterations)
    assert mse <= J_t <= bound, (mse, J_t, bound)


def _cg_objective_bound(est, X, y, lam, t):
    """(J(alpha_t), the bound CG guarantees for it), in float64.

    The solver minimizes J(a) = |K_nM a - y|^2 / n + lam a^T R a with
    R = D^-1 T^T T D^-1 (its own factor: K_MM plus the jitter), as
    preconditioned CG on W = B^T H B. After t iterations from zero, CG's
    error satisfies |e_t|_W <= 2 r^t |e_0|_W with r = (sqrt(k) - 1) /
    (sqrt(k) + 1), k = cond(W); since J - J* = |e|_W^2 this gives
    J(a_t) <= J* + 4 r^(2t) (J(0) - J*).
    """
    with enable_x64(True):
        f64 = lambda a: None if a is None else jnp.asarray(np.asarray(a), jnp.float64)
        P = jax.tree.map(f64, est.precond)
        X64, y64, C64 = f64(X), f64(y), f64(est.centers)
        n = X64.shape[0]
        Knm = est.kernel(X64, C64)
        Dinv = jnp.diag(1.0 / (P.D if P.D is not None else jnp.ones(C64.shape[0])))
        R = Dinv @ P.T.T @ P.T @ Dinv
        J = lambda a: float(jnp.mean((Knm @ a - y64) ** 2) + lam * a @ R @ a)
        eye = jnp.eye(P.q)
        W = P.left(Knm.T @ (Knm @ P.right(eye))) / n + P.ridge(eye, lam)
        ev = jnp.linalg.eigvalsh((W + W.T) / 2)
        r = (np.sqrt(ev[-1] / ev[0]) - 1) / (np.sqrt(ev[-1] / ev[0]) + 1)
        J_star = J(jnp.linalg.solve(Knm.T @ Knm / n + lam * R, Knm.T @ y64 / n))
        J_0 = J(jnp.zeros(C64.shape[0]))
        return J(f64(est.alpha)), J_star + 4 * float(r) ** (2 * t) * (J_0 - J_star)


# ---------------------------------------------------------------------------
# API guards
# ---------------------------------------------------------------------------
def test_path_fit_rejects_bad_grids():
    X, y = _problem(n=64)
    cfg = _cfg(num_centers=16, iterations=2)
    with pytest.raises(ValueError, match="non-empty"):
        falkon_fit_path(jax.random.PRNGKey(0), X, y, cfg, [])
    with pytest.raises(ValueError, match="> 0"):
        falkon_fit_path(jax.random.PRNGKey(0), X, y, cfg, [1e-3, 0.0])
    with pytest.raises(ValueError, match="y_val"):
        falkon_fit_path(jax.random.PRNGKey(0), X, y, cfg, [1e-3], X_val=X)
    with pytest.raises(ValueError, match="together"):
        falkon_fit_path(jax.random.PRNGKey(0), X, y, cfg, [1e-3], y_val=y)
