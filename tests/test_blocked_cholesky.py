"""The blocked out-of-core preconditioner path (ISSUE 7 tentpole).

* ``plan_factor`` routing: budget model, block sizing, env override, the
  structured ``FactorPlanWarning``; the budget ``resolve_factor_plan``
  derives from the devices' ``memory_stats()`` (readings monkeypatched).
* Blocked-vs-in-core factor parity (<= 1e-5 rel) on every registered
  kernel's K_MM, with and without the leverage-score diagonal D, for both
  ``make_preconditioner`` and ``make_preconditioner_path``.
* The Pallas tile engine (interpret mode on CPU) against the jnp tile
  engine and a float64 numpy reference.
* A forced-blocked full ``falkon_fit`` whose alpha matches the in-core fit.
* The O(b * M) device-residency proof: measured peak device bytes (ground
  truth via ``jax.live_arrays()``) stay under ``FactorPlan``'s ceiling,
  under the dense footprint, and scale LINEARLY in M at fixed block.
* The rank-deficient eig path refuses the blocked route loudly.

The M = 32768 acceptance point runs under ``REPRO_XL_TESTS=1`` (about half
an hour of O(M^3) on one CPU core); ``benchmarks/precond_blocked.py`` +
the ``precond_blocked`` gate carry the same invariant in CI at smaller M.
"""
import os
import warnings
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import FalkonConfig, falkon_fit, make_kernel
from repro.core import preconditioner as pc
from repro.core.preconditioner import (make_preconditioner, make_preconditioner_path)
from repro.kernels.blocked_cholesky import (
    FactorStats, blocked_cholesky, blocked_syrk_tt, resolve_tile_impl
)
from repro.ops import (
    FACTOR_PATHS, FactorPlan, FactorPlanWarning, get_ops, plan_factor
)
from repro.ops.base import DEFAULT_FACTOR_BUDGET

KERNELS = [
    ("gaussian", dict(sigma=1.3)),
    ("laplacian", dict(sigma=1.1)),
    ("matern32", dict(sigma=1.7)),
    ("linear", dict(scale=1.5)),
    ("polynomial", dict(degree=2, c=0.5, scale=2.0)),
]


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _spd(M, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    A = rng.standard_normal((M, M)).astype(dtype)
    return A @ A.T / M + np.eye(M, dtype=dtype)


def _kernel_gram(name, params, M=333, d=7, seed=0):
    kern = make_kernel(name, **params)
    C = jax.random.normal(jax.random.PRNGKey(seed), (M, d))
    return get_ops("jnp", kern).gram(C, C)


# ---------------------------------------------------------------------------
# plan_factor
# ---------------------------------------------------------------------------
def test_plan_factor_routing_and_block_sizing():
    small = plan_factor(1024)
    assert small.path == "incore" and small.block is None
    big = plan_factor(32768)           # 4 GB dense fp32 >> 512 MB default
    assert big.path == "blocked"
    assert big.block is not None and big.block % 256 == 0
    assert big.panel_bytes == 2 * big.block * big.M * big.itemsize
    assert big.device_ceiling_bytes == 3 * big.panel_bytes
    assert big.device_ceiling_bytes < big.dense_bytes
    assert big.path in FACTOR_PATHS and "blocked" in big.reason


def test_plan_factor_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_FACTOR_BUDGET_MB", "1")
    assert plan_factor(1024).path == "blocked"
    monkeypatch.setenv("REPRO_FACTOR_BUDGET_MB", "100000")
    assert plan_factor(65536).path == "incore"


def test_plan_factor_x64_itemsize():
    p4 = plan_factor(8192, itemsize=4)
    p8 = plan_factor(8192, itemsize=8)
    assert p8.dense_bytes == 2 * p4.dense_bytes


# ---------------------------------------------------------------------------
# The factor budget derived from the devices' free memory
# ---------------------------------------------------------------------------
MS = 12288                          # MillionSongs' centers: 604 MB dense fp32
GIB = 2**30
PEAK = pc._INCORE_SHARED + pc._INCORE_PER_LAM + pc._INCORE_MARGIN


def _reading(limit, in_use):
    return dict(bytes_limit=limit, bytes_in_use=in_use, peak_bytes_in_use=in_use)


def _kmm_on_device(monkeypatch, M, stats):
    """An (M, M) fp32 K_MM on this process's device, whose ``memory_stats``
    returns ``stats``. Shapes only: planning reads no entries."""
    device = jax.devices()[0]
    monkeypatch.setattr(type(device), "memory_stats", lambda self: stats)
    return jax.ShapeDtypeStruct((M, M), jnp.float32, sharding=SingleDeviceSharding(device))


class _Device:
    def __init__(self, stats):
        self.stats = stats

    def memory_stats(self):
        return self.stats


def test_free_memory_routes_millionsongs_factor_incore(monkeypatch):
    """A v5e-like reading (16 GiB, 4.5 GB in use) holds M = 12288's in-core
    peak: that factor, blocked under the 512 MB default, stays in-core."""
    assert plan_factor(MS).path == "blocked"
    KMM = _kmm_on_device(monkeypatch, MS, _reading(16 * GIB, 4_500_000_000))
    with warnings.catch_warnings():
        warnings.simplefilter("error", FactorPlanWarning)
        plan = pc.resolve_factor_plan(KMM, None, False)
    free = 16 * GIB - 4_500_000_000
    assert plan.path == "incore" and plan.free_bytes == free
    assert plan.factor_budget_bytes == free // PEAK > plan.dense_bytes


def test_free_memory_too_small_routes_blocked(monkeypatch):
    """A reading one byte short of the in-core peak at M = 12288 (its budget
    still above the floor) routes the factor blocked."""
    dense = MS * MS * 4
    KMM = _kmm_on_device(monkeypatch, MS, _reading(8 * GIB, 8 * GIB - PEAK * dense + 1))
    with pytest.warns(FactorPlanWarning):
        plan = pc.resolve_factor_plan(KMM, None, False)
    assert plan.path == "blocked"
    assert DEFAULT_FACTOR_BUDGET < plan.factor_budget_bytes == dense - 1


@pytest.mark.parametrize("M", [300, 10000, MS])
def test_no_reading_plans_as_before(monkeypatch, M):
    """``memory_stats()`` giving None (the CPU backend) or no
    ``bytes_limit`` leaves every plan exactly what the 512 MB default
    gives."""
    for stats in (None, dict(bytes_in_use=1)):
        KMM = _kmm_on_device(monkeypatch, M, stats)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", FactorPlanWarning)
            plan = pc.resolve_factor_plan(KMM, None, False)
        assert plan == plan_factor(M) and plan.free_bytes == -1
    monkeypatch.undo()
    real = _kernel_gram("gaussian", dict(sigma=1.3), M=64)
    assert jax.devices()[0].memory_stats() is None          # this CPU backend
    assert pc.resolve_factor_plan(real, None, False) == plan_factor(64)


def test_env_budget_overrides_the_reading(monkeypatch):
    KMM = _kmm_on_device(monkeypatch, MS, _reading(16 * GIB, GIB))
    monkeypatch.setenv("REPRO_FACTOR_BUDGET_MB", "512")
    with pytest.warns(FactorPlanWarning):
        plan = pc.resolve_factor_plan(KMM, None, False)
    assert plan.path == "blocked" and plan.factor_budget_bytes == 512 * 2**20
    assert plan.free_bytes == 15 * GIB                      # still recorded
    tiny = _kmm_on_device(monkeypatch, MS, _reading(GIB, GIB))
    monkeypatch.setenv("REPRO_FACTOR_BUDGET_MB", "100000")
    assert pc.resolve_factor_plan(tiny, None, False).path == "incore"


def test_budget_floor_keeps_default_incore_factors(monkeypatch):
    """Under a tiny reading the budget never drops below the default, so a
    400 MB factor (SUSY's M = 1e4) stays in-core."""
    KMM = _kmm_on_device(monkeypatch, 10000, _reading(GIB, GIB - 2**20))
    plan = pc.resolve_factor_plan(KMM, None, False)
    assert plan.path == "incore" and plan.factor_budget_bytes == DEFAULT_FACTOR_BUDGET
    assert plan.free_bytes == 2**20


def test_least_free_device_decides():
    """K_MM replicated over two devices: the one with less free memory
    sets the budget."""
    roomy, tight = _reading(16 * GIB, GIB), _reading(16 * GIB, 15 * GIB)
    KMM = SimpleNamespace(shape=(MS, MS), dtype=jnp.float32, sharding=SimpleNamespace(
        device_set=[_Device(roomy), _Device(tight)]))
    assert pc.device_free_bytes(KMM) == GIB
    with pytest.warns(FactorPlanWarning):
        plan = pc.resolve_factor_plan(KMM, None, False)
    assert plan.path == "blocked" and plan.free_bytes == GIB
    KMM.sharding.device_set[1] = _Device(roomy)
    assert pc.resolve_factor_plan(KMM, None, False).path == "incore"


def test_reading_waits_for_kmm(monkeypatch):
    """With a reading, K_MM is waited for before the reading is taken, so
    the temporaries of the ops computing it are not counted in use; without
    one, nothing waits."""
    waited = []
    monkeypatch.setattr(jax, "block_until_ready", lambda x: waited.append(x) or x)
    KMM = jnp.eye(8)
    device = jax.devices()[0]
    monkeypatch.setattr(type(device), "memory_stats", lambda self: None)
    assert pc.device_free_bytes(KMM) is None and waited == []
    monkeypatch.setattr(type(device), "memory_stats", lambda self: _reading(GIB, 2**20))
    assert pc.device_free_bytes(KMM) == GIB - 2**20 and waited == [KMM]


def test_lam_grid_raises_the_incore_peak(monkeypatch):
    """A path build holds every lam factor's ridge, carry and result at
    once: the reading that takes one lam factor in-core sends eight
    blocked."""
    KMM = _kmm_on_device(monkeypatch, MS, _reading(16 * GIB, 4_500_000_000))
    assert pc.resolve_factor_plan(KMM, None, False).path == "incore"
    with pytest.warns(FactorPlanWarning):
        plan = pc.resolve_factor_plan(KMM, None, False, systems=8)
    peak = pc._INCORE_SHARED + 8 * pc._INCORE_PER_LAM + pc._INCORE_MARGIN
    assert plan.path == "blocked"
    assert plan.factor_budget_bytes == max(DEFAULT_FACTOR_BUDGET, plan.free_bytes // peak)


# ---------------------------------------------------------------------------
# The blocked factorization itself
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("M,block", [(97, 32), (256, 64), (500, 128)])
def test_blocked_cholesky_matches_reference(M, block):
    K = _spd(M, seed=M)
    ref = np.linalg.cholesky(K.astype(np.float64)).T
    T = blocked_cholesky(K, block)
    assert T.shape == (M, M)
    assert np.allclose(np.tril(T, -1), 0.0), "factor must be upper"
    assert _rel(T, ref) < 1e-5
    TT = blocked_syrk_tt(T, block)
    assert _rel(TT, T @ T.T) < 1e-6


def test_blocked_cholesky_pallas_tile_engine_parity():
    """The Pallas POTRF/TRSM/update kernels (interpret mode off-TPU) agree
    with the BLAS-backed jnp tile engine on ragged multi-tile problems."""
    K = _spd(200, seed=3)
    Tj = blocked_cholesky(K, 64, tile_impl="jnp")
    Tp = blocked_cholesky(K, 64, tile_impl="pallas")
    assert _rel(Tp, Tj) < 1e-5
    assert _rel(Tp, np.linalg.cholesky(K.astype(np.float64)).T) < 1e-5


@pytest.mark.parametrize("M,block", [(300, 256), (500, 192), (260, 256)])
def test_blocked_cholesky_pallas_wide_block_ragged_parity(M, block):
    """Regression: with block > LANE(=128) and M % block != 0, the trailing
    update's factor panels are WIDER than the ragged output tile. The update
    kernel must pad/tile the contraction dimension to the panel width, not
    the output width — getting it wrong silently truncates the contraction
    and corrupts the factor only on the default TPU (pallas) path."""
    K = _spd(M, seed=M + block)
    Tp = blocked_cholesky(K, block, tile_impl="pallas")
    Tj = blocked_cholesky(K, block, tile_impl="jnp")
    assert _rel(Tp, Tj) < 1e-5
    assert _rel(Tp, np.linalg.cholesky(K.astype(np.float64)).T) < 1e-5


def test_blocked_cholesky_pallas_indefinite_yields_nan():
    """An indefinite (under-jittered) input must fail OBSERVABLY on the
    pallas engine — NaNs in the factor, same as the in-core/jnp path —
    not clamp the bad pivot and emit a finite garbage factor."""
    M = 96
    K = _spd(M, seed=11)
    K[M // 2, M // 2] = -100.0  # force a negative pivot mid-factorization
    Tp = blocked_cholesky(K, 32, tile_impl="pallas")
    assert np.isnan(Tp).any(), "indefinite input produced a finite factor"
    Tj = blocked_cholesky(K, 32, tile_impl="jnp")
    assert np.isnan(Tj).any(), "jnp engine should also surface NaNs"


@pytest.mark.parametrize("M", [1280, 1100])
def test_panel_cholesky_and_solves_match_direct(M):
    """Past ``_DIRECT_MAX`` the in-core factor and its solves run as panel
    loops (whole and ragged last panel here); they agree with XLA's direct
    calls to fp32 rounding, for vector and matrix right-hand sides in both
    orientations, under vmap, and surface an indefinite input as NaNs."""
    from jax.scipy.linalg import solve_triangular
    from repro.core.preconditioner import _DIRECT_MAX, cholesky_upper, tri_solve
    assert M > _DIRECT_MAX
    A = jnp.asarray(_spd(M, seed=5))
    T = jnp.linalg.cholesky(A).T
    assert _rel(cholesky_upper(A), T) < 1e-5
    rng = np.random.default_rng(6)
    for rhs in (rng.standard_normal(M), rng.standard_normal((M, 3))):
        rhs = jnp.asarray(rhs, jnp.float32)
        for trans in (False, True):
            want = solve_triangular(T, rhs, lower=False, trans=int(trans))
            assert _rel(tri_solve(T, rhs, trans), want) < 1e-5
    batched = jax.vmap(cholesky_upper)(jnp.stack([A, 2 * A]))
    assert _rel(batched[1], np.sqrt(2.0) * np.asarray(T)) < 1e-5
    bad = A.at[M // 2, M // 2].set(-100.0)
    assert np.isnan(np.asarray(cholesky_upper(bad))).any()


def test_resolve_tile_impl():
    assert resolve_tile_impl("jnp") == "jnp"
    assert resolve_tile_impl("pallas") == "pallas"
    expected = "pallas" if jax.default_backend() == "tpu" else "jnp"
    assert resolve_tile_impl("auto") == expected
    with pytest.raises(ValueError, match="tile_impl"):
        resolve_tile_impl("cuda")


def test_blocked_cholesky_float64_input():
    """float64 hosts factor without error; device math matches whatever
    precision the in-core path would run at (x64 on or off)."""
    K = _spd(150, seed=9).astype(np.float64)
    T = blocked_cholesky(K, 64)
    ref = np.asarray(jnp.linalg.cholesky(jnp.asarray(K)).T)
    assert _rel(T, ref) < 1e-5


def test_blocked_cholesky_rejects_bad_inputs():
    with pytest.raises(ValueError, match="square"):
        blocked_cholesky(np.ones((4, 5), np.float32), 2)
    with pytest.raises(ValueError, match="block"):
        blocked_cholesky(np.eye(4, dtype=np.float32), 0)


# ---------------------------------------------------------------------------
# Preconditioner routing + parity
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kernel_name,params", KERNELS)
@pytest.mark.filterwarnings("ignore::repro.ops.FactorPlanWarning")
def test_blocked_preconditioner_parity_all_kernels(kernel_name, params):
    """Blocked vs in-core T/A parity on every registered kernel's Gram,
    ragged M=333 over 256-wide tiles.

    The jitter keeps the comparison about the FACTORIZATION, not the
    conditioning: linear/polynomial grams in d=7 are rank-deficient
    (cond ~1e7), where ANY two fp32 Cholesky orderings diverge to ~1e-4 in
    the near-null directions — the regime the rank_deficient eig path (or a
    real jitter) exists for."""
    KMM = _kernel_gram(kernel_name, params)
    pin = make_preconditioner(KMM, 1e-3, 1000, factor_plan="incore", jitter=0.1)
    pbl = make_preconditioner(KMM, 1e-3, 1000, factor_plan="blocked", jitter=0.1)
    assert _rel(pbl.T, pin.T) < 1e-5
    assert _rel(pbl.A, pin.A) < 1e-5


@pytest.mark.filterwarnings("ignore::repro.ops.FactorPlanWarning")
def test_blocked_preconditioner_with_leverage_diagonal():
    KMM = _kernel_gram("gaussian", dict(sigma=1.3), M=300)
    D = jnp.asarray(np.random.default_rng(5).uniform(0.5, 1.5, 300).astype(np.float32))
    pin = make_preconditioner(KMM, 1e-3, 1000, D=D, factor_plan="incore")
    pbl = make_preconditioner(KMM, 1e-3, 1000, D=D, factor_plan="blocked")
    assert _rel(pbl.T, pin.T) < 1e-5
    assert _rel(pbl.A, pin.A) < 1e-5


@pytest.mark.filterwarnings("ignore::repro.ops.FactorPlanWarning")
def test_blocked_path_builder_parity():
    KMM = _kernel_gram("gaussian", dict(sigma=1.3), M=300)
    lams = [1e-2, 1e-3, 1e-4]
    pin = make_preconditioner_path(KMM, lams, 1000, factor_plan="incore")
    pbl = make_preconditioner_path(KMM, lams, 1000, factor_plan="blocked")
    assert pbl.A.shape == pin.A.shape == (3, 300, 300)
    assert _rel(pbl.T, pin.T) < 1e-5
    assert _rel(pbl.A, pin.A) < 1e-5


def test_blocked_route_warns_with_plan():
    KMM = _kernel_gram("gaussian", dict(sigma=1.3), M=300)
    with pytest.warns(FactorPlanWarning) as rec:
        make_preconditioner(KMM, 1e-3, 1000, factor_plan="blocked")
    plans = [w.message.plan for w in rec if isinstance(w.message, FactorPlanWarning)]
    assert plans and plans[0].path == "blocked"
    assert isinstance(plans[0], FactorPlan)


def test_auto_plan_routes_blocked_under_tiny_budget(monkeypatch):
    monkeypatch.setenv("REPRO_FACTOR_BUDGET_MB", "0.05")
    KMM = _kernel_gram("gaussian", dict(sigma=1.3), M=300)
    with pytest.warns(FactorPlanWarning):
        pbl = make_preconditioner(KMM, 1e-3, 1000)
    monkeypatch.delenv("REPRO_FACTOR_BUDGET_MB")
    pin = make_preconditioner(KMM, 1e-3, 1000)
    assert _rel(pbl.A, pin.A) < 1e-5


def test_traced_build_falls_back_incore(monkeypatch):
    """Under jit the blocked path cannot leave the device; the plan must
    quietly land in-core and produce the historical result."""
    monkeypatch.setenv("REPRO_FACTOR_BUDGET_MB", "0.01")
    KMM = _kernel_gram("gaussian", dict(sigma=1.3), M=200)
    jitted = jax.jit(lambda K: make_preconditioner(K, 1e-3, 1000).A)
    with warnings.catch_warnings():
        warnings.simplefilter("error", FactorPlanWarning)  # must NOT warn
        A = jitted(KMM)
    monkeypatch.delenv("REPRO_FACTOR_BUDGET_MB")
    ref = make_preconditioner(KMM, 1e-3, 1000).A
    assert _rel(A, ref) < 1e-6


def test_invalid_factor_plan_rejected():
    KMM = _kernel_gram("gaussian", dict(sigma=1.3), M=64)
    with pytest.raises(ValueError, match="factor_plan"):
        make_preconditioner(KMM, 1e-3, 1000, factor_plan="banana")


def test_rank_deficient_refuses_blocked_route():
    """Satellite: the eig fallback must be loudly refused by the blocked
    route (a dense eigendecomposition cannot be tiled by this scheme)."""
    KMM = _kernel_gram("gaussian", dict(sigma=1.3), M=200)
    with pytest.raises(ValueError, match="rank_deficient"):
        make_preconditioner(KMM, 1e-3, 1000, rank_deficient=True, factor_plan="blocked")
    with pytest.raises(ValueError, match="REPRO_FACTOR_BUDGET_MB"):
        make_preconditioner_path(
            KMM, [1e-3], 1000, rank_deficient=True, factor_plan="blocked"
        )
    # in-core eig fallback is untouched
    p = make_preconditioner(KMM, 1e-3, 1000, rank_deficient=True, factor_plan="incore")
    assert p.diag_T


# ---------------------------------------------------------------------------
# Forced-blocked end-to-end fit
# ---------------------------------------------------------------------------
@pytest.mark.filterwarnings("ignore::repro.ops.FactorPlanWarning")
def test_forced_blocked_falkon_fit_alpha_parity(monkeypatch):
    """A full falkon_fit with the preconditioner forced onto the blocked
    path matches the in-core fit's alpha to <= 1e-4 rel (fp32).

    The problem is kept well-conditioned (sigma=1, explicit jitter): with a
    near-singular K_MM the converged FUNCTION is identical (predictions
    agree to ~1e-4 regardless — also asserted) but alpha itself is only
    determined up to near-null directions of K_MM, which is a property of
    Nystrom ridge regression, not of the factor path."""
    n, d, M = 1500, 6, 320
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    X = jax.random.normal(keys[0], (n, d))
    w = jax.random.normal(keys[1], (d,))
    y = X @ w + 0.05 * jax.random.normal(keys[2], (n,))
    config = FalkonConfig(
        kernel="gaussian",
        kernel_params=(("sigma", 1.0),),
        num_centers=M,
        lam=1e-3,
        iterations=30,
        jitter=1e-3,
    )
    est_in, _ = falkon_fit(keys[0], X, y, config)
    monkeypatch.setenv("REPRO_FACTOR_BUDGET_MB", "0.2")   # M=320 -> blocked
    est_bl, _ = falkon_fit(keys[0], X, y, config)
    monkeypatch.delenv("REPRO_FACTOR_BUDGET_MB")
    assert _rel(est_bl.alpha, est_in.alpha) < 1e-4
    preds_in = est_in.predict(X[:100])
    preds_bl = est_bl.predict(X[:100])
    assert _rel(preds_bl, preds_in) < 1e-4


# ---------------------------------------------------------------------------
# The O(b * M) device-residency proof
# ---------------------------------------------------------------------------
def _measure_peak(M, block, seed=0):
    """Factor a HOST matrix and return (measured peak device bytes via
    jax.live_arrays — the ground truth — , self-accounted stats peak)."""
    K = _spd(M, seed=seed)
    baseline = sum(a.nbytes for a in jax.live_arrays())
    peak = {"live": 0}

    def on_step(stage, st):
        live = sum(a.nbytes for a in jax.live_arrays()) - baseline
        peak["live"] = max(peak["live"], live)

    stats = FactorStats()
    T = blocked_cholesky(K, block, stats=stats, on_step=on_step)
    assert _rel(T, np.linalg.cholesky(K.astype(np.float64)).T) < 1e-5
    assert stats.current_device_bytes == 0, "device buffers leaked"
    return peak["live"], stats.peak_device_bytes


def test_device_peak_is_o_block_m_not_m_squared():
    """The acceptance-seam memory claim, measured: peak device-resident
    bytes stay under the plan's O(b * M) ceiling and UNDER the dense M^2
    footprint, and grow linearly (not quadratically) in M at fixed block."""
    block = 128
    peaks = {}
    for M in (1024, 2048):
        plan = plan_factor(M, block=block, factor_budget=1)  # force blocked
        assert plan.path == "blocked" and plan.block == block
        live, accounted = _measure_peak(M, block, seed=M)
        assert live <= plan.device_ceiling_bytes, (
            f"M={M}: measured {live}B above the O(b*M) ceiling "
            f"{plan.device_ceiling_bytes}B")
        assert live < plan.dense_bytes, (
            f"M={M}: measured {live}B not below dense {plan.dense_bytes}B"
        )
        assert accounted <= plan.device_ceiling_bytes
        peaks[M] = live
    # doubling M at fixed block must not 4x the peak: linear-with-slack
    assert peaks[2048] <= 3.0 * peaks[1024], (f"peak grew superlinearly: {peaks}")


@pytest.mark.skipif(not os.environ.get("REPRO_XL_TESTS"),
                    reason="M=32768 acceptance point: ~30 min of O(M^3) on "
                           "one CPU core; set REPRO_XL_TESTS=1 to run")
def test_blocked_parity_m32768_xl():
    M = 32768
    plan = plan_factor(M)
    assert plan.path == "blocked"
    rng = np.random.default_rng(0)
    A = rng.standard_normal((M, 64)).astype(np.float32)
    K = (A @ A.T) / 64 + np.eye(M, dtype=np.float32)
    stats = FactorStats()
    T = blocked_cholesky(K, plan.block, stats=stats)
    Tref = np.asarray(jnp.linalg.cholesky(jnp.asarray(K)).T)
    assert _rel(T, Tref) < 1e-5
    assert stats.peak_device_bytes <= plan.device_ceiling_bytes
