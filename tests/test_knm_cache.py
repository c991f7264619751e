"""Materialized K_nM cache (``repro.ops.KernelCache``) tests.

The contract under test (see ``repro.ops.gemm`` / ``repro.ops.knm_cache``):

* **Parity** — fp32 device-tier cached sweeps/applies on the jnp backend are
  BIT-IDENTICAL to the recompute path (the GEMM sweep replays the exact
  blocked scan over stored entries); pallas/host tiers agree to <= 1e-4 per
  sweep; bf16 storage agrees to the policy's quantization tolerance.
* **One kernel evaluation per tile** — ``CountingOps.gram_tile_evals`` after
  a cached fit equals ``cache.num_tiles + ceil(M/bs)`` (one materialization
  pass + the K_MM gram), with ``sweeps == 0``: every CG iteration, the RHS
  sweep and the ``estimate_cond`` power-iteration diagnostics consumed
  stored entries.
* **Routing** — ``plan_cache`` tiers by per-shard bytes against the
  ``REPRO_KNM_BUDGET_MB`` / ``REPRO_KNM_HOST_BUDGET_MB`` budgets; forced
  tiers are respected; ``knm_cache="off"`` fits are bit-identical to the
  seed recompute path.
* **Staleness** — a cache pins its exact (X, centers) arrays by identity;
  ``invalidate()``/``swap_model`` make it refuse to serve.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    FalkonConfig,
    GaussianKernel,
    cached_knm_apply,
    cached_knm_matvec,
    falkon_fit,
    falkon_fit_minibatch,
    falkon_fit_path,
    falkon_fit_streaming,
    make_knm_cache,
)
from repro.ops import (
    CachePlan,
    CachePlanWarning,
    CountingOps,
    KernelCache,
    data_shards,
    get_ops,
    plan_cache,
)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _problem(n=1000, d=6, M=128, key=0):
    kx, kf = jax.random.split(jax.random.PRNGKey(key))
    X = jax.random.normal(kx, (n, d))
    y = jnp.sin(X[:, 0]) + 0.1 * jax.random.normal(kf, (n,))
    return X, y, kf


# ---------------------------------------------------------------------------
# plan_cache routing
# ---------------------------------------------------------------------------
def test_plan_cache_tiers_by_budget():
    # 1000 * 128 * 4 bytes = 512000 B = ~0.49 MiB
    p = plan_cache(1000, 128, budget=2**20)
    assert p.tier == "device" and p.cache_bytes == 1000 * 128 * 4
    p = plan_cache(1000, 128, budget=2**18, host_budget=2**20)
    assert p.tier == "host"
    p = plan_cache(1000, 128, budget=2**18, host_budget=2**18)
    assert p.tier == "off"


def test_plan_cache_env_budgets(monkeypatch):
    monkeypatch.setenv("REPRO_KNM_BUDGET_MB", "0.25")     # 256 KiB
    monkeypatch.setenv("REPRO_KNM_HOST_BUDGET_MB", "1")   # 1 MiB
    assert plan_cache(1000, 128).tier == "host"
    monkeypatch.setenv("REPRO_KNM_HOST_BUDGET_MB", "0.25")
    assert plan_cache(1000, 128).tier == "off"
    monkeypatch.setenv("REPRO_KNM_BUDGET_MB", "1")
    assert plan_cache(1000, 128).tier == "device"


def test_plan_cache_charges_per_shard():
    # the same problem that busts a single device fits once row-sharded
    whole = plan_cache(1000, 128, budget=2**18)
    assert whole.tier != "device"
    sharded = plan_cache(1000, 128, budget=2**18, shards=4)
    assert sharded.tier == "device"
    assert sharded.shard_bytes == -(-1000 * 128 * 4 // 4)


def test_plan_cache_forced_tier_and_policy_itemsize():
    p = plan_cache(1000, 128, tier="host", budget=2**30)
    assert p.tier == "host" and "forced" in p.reason
    from repro.ops import resolve_precision
    bf16 = plan_cache(1000, 128, policy=resolve_precision("bf16"))
    fp32 = plan_cache(1000, 128, policy=resolve_precision("fp32"))
    assert bf16.cache_bytes * 2 == fp32.cache_bytes
    assert bf16.storage_dtype == "bfloat16"
    with pytest.raises(ValueError):
        plan_cache(1000, 128, tier="hbm")


def test_cache_refuses_off_plan():
    kern = GaussianKernel(sigma=1.5)
    ops = get_ops("jnp", kern, block_size=256)
    X, _, _ = _problem()
    plan = plan_cache(1000, 128, budget=0, host_budget=0)
    assert plan.tier == "off"
    with pytest.raises(ValueError, match="off"):
        KernelCache(ops, X, X[:128], plan=plan)


# ---------------------------------------------------------------------------
# Parity: cached primitives vs recompute
# ---------------------------------------------------------------------------
def _forced(ops, n, M, tier):
    return plan_cache(n, M, policy=ops.policy, tier=tier)


def test_device_tier_bit_identical_jnp():
    """fp32 jnp device tier: the GEMM sweep replays the recompute scan over
    stored entries — cached == recompute BIT-identically (ragged n)."""
    X, _, _ = _problem(n=1000)
    C = X[:128]
    kern = GaussianKernel(sigma=1.5)
    ops = get_ops("jnp", kern, block_size=256)
    u = jax.random.normal(jax.random.PRNGKey(3), (128,))
    v = jax.random.normal(jax.random.PRNGKey(4), (1000,))
    cache = KernelCache(ops, X, C, plan=_forced(ops, 1000, 128, "device"))
    np.testing.assert_array_equal(
        np.asarray(cache.sweep(u, v)), np.asarray(ops.sweep(X, C, u, v)))
    np.testing.assert_array_equal(
        np.asarray(cache.sweep(u)), np.asarray(ops.sweep(X, C, u)))
    np.testing.assert_array_equal(
        np.asarray(cache.apply(u)), np.asarray(ops.apply(X, C, u)))


@pytest.mark.parametrize("impl,tier", [("pallas", "device"), ("jnp", "host"),
                                       ("pallas", "host")])
def test_cached_sweep_close_other_tiers(impl, tier):
    """Pallas entries / host-tier jitted GEMMs fuse differently than the
    in-core scan: agreement to <= 1e-4 relative, per sweep."""
    X, _, _ = _problem(n=1000)
    C = X[:128]
    kern = GaussianKernel(sigma=1.5)
    ops = get_ops(impl, kern, block_size=256)
    u = jax.random.normal(jax.random.PRNGKey(3), (128,))
    v = jax.random.normal(jax.random.PRNGKey(4), (1000,))
    cache = KernelCache(ops, X, C, plan=_forced(ops, 1000, 128, tier))
    assert cache.tier == tier
    ref = np.asarray(ops.sweep(X, C, u, v))
    got = np.asarray(cache.sweep(u, v))
    rel = np.max(np.abs(got - ref)) / np.max(np.abs(ref))
    assert rel <= 1e-4, rel
    pa = np.asarray(cache.apply(u))
    pr = np.asarray(ops.apply(X, C, u))
    assert np.max(np.abs(pa - pr)) / np.max(np.abs(pr)) <= 1e-4


def test_bf16_storage_halves_footprint_and_stays_close():
    """bf16 policy: tiles are STORED at bfloat16 (half bytes — the cache
    composes with the precision work); sweeps agree to quantization level."""
    X, _, _ = _problem(n=768)
    C = X[:128]
    kern = GaussianKernel(sigma=1.5)
    ops = get_ops("jnp", kern, block_size=256, precision="bf16")
    cache = KernelCache(ops, X, C, plan=_forced(ops, 768, 128, "device"))
    assert cache.K.dtype == jnp.bfloat16
    u = jax.random.normal(jax.random.PRNGKey(3), (128,))
    v = jax.random.normal(jax.random.PRNGKey(4), (768,))
    ref = np.asarray(ops.sweep(X, C, u, v), np.float32)
    got = np.asarray(cache.sweep(u, v), np.float32)
    rel = np.max(np.abs(got - ref)) / np.max(np.abs(ref))
    assert rel <= 5e-3, rel


def test_row_mask_zero_contribution():
    """Masked rows contribute EXACTLY zero — same contract as the recompute
    sweep's internal padding (fixed-shape padded chunks sweep correctly)."""
    X, _, _ = _problem(n=700)
    C = X[:96]
    kern = GaussianKernel(sigma=1.2)
    ops = get_ops("jnp", kern, block_size=256)
    u = jax.random.normal(jax.random.PRNGKey(5), (96,))
    v = jax.random.normal(jax.random.PRNGKey(6), (700,))
    mask = (jnp.arange(700) < 600).astype(jnp.float32)
    cache = KernelCache(ops, X, C, plan=_forced(ops, 700, 96, "device"))
    np.testing.assert_array_equal(
        np.asarray(cache.sweep(u, v, row_mask=mask)),
        np.asarray(ops.sweep(X[:600], C, u, v[:600])))


def test_functional_veneer():
    X, _, _ = _problem(n=512)
    C = X[:64]
    kern = GaussianKernel(sigma=1.5)
    ops = get_ops("jnp", kern, block_size=256)
    cache = make_knm_cache(X, C, kern, block_size=256, tier="device")
    u = jax.random.normal(jax.random.PRNGKey(3), (64,))
    v = jax.random.normal(jax.random.PRNGKey(4), (512,))
    np.testing.assert_array_equal(
        np.asarray(cached_knm_matvec(cache, u, v)),
        np.asarray(ops.sweep(X, C, u, v)))
    np.testing.assert_array_equal(
        np.asarray(cached_knm_apply(cache, u)),
        np.asarray(ops.apply(X, C, u)))


# ---------------------------------------------------------------------------
# Fit-level: bit-identity, counting, lam-path sharing
# ---------------------------------------------------------------------------
def test_cached_fit_bit_identical_fp32():
    X, y, kf = _problem()
    base = dict(num_centers=128, iterations=8, block_size=256, lam=1e-4)
    _, st0 = falkon_fit(kf, X, y, FalkonConfig(**base, knm_cache="off"))
    _, st1 = falkon_fit(kf, X, y, FalkonConfig(**base, knm_cache="device"))
    np.testing.assert_array_equal(np.asarray(st0.alpha), np.asarray(st1.alpha))
    np.testing.assert_array_equal(
        np.asarray(st0.cond_estimate), np.asarray(st1.cond_estimate))


def test_cached_fit_one_eval_per_tile():
    """THE acceptance invariant: a cached fit evaluates each K_nM row tile
    exactly once (plus ceil(M/bs) tiles for the K_MM gram), runs ZERO
    recompute sweeps, and serves CG + RHS as GEMMs."""
    X, y, kf = _problem()
    n, M, bs = 1000, 128, 256
    cfg = FalkonConfig(num_centers=M, iterations=8, block_size=bs, lam=1e-4,
                       knm_cache="device", estimate_cond=False)
    ops = CountingOps(get_ops("jnp", cfg.make_kernel(), block_size=bs))
    falkon_fit(kf, X, y, cfg, ops=ops)
    nb, mt = -(-n // bs), -(-M // bs)
    assert ops.sweeps == 0
    assert ops.materializes == 1
    assert ops.gram_tile_evals == nb + mt, (ops.gram_tile_evals, nb, mt)
    # program points: 1 eager RHS + 1 scanned CG matvec trace
    assert ops.gemm_sweeps == 2


def test_cond_estimate_sweeps_are_cached_too():
    """The ~26 width-1 power-iteration diagnostic sweeps route through the
    same cache: tile evals unchanged, 4 extra gemm_sweep program points
    (2 power() calls x (1 scanned trace + 1 eager mv))."""
    X, y, kf = _problem()
    n, M, bs = 1000, 128, 256
    cfg = FalkonConfig(num_centers=M, iterations=8, block_size=bs, lam=1e-4,
                       knm_cache="device", estimate_cond=True)
    ops = CountingOps(get_ops("jnp", cfg.make_kernel(), block_size=bs))
    falkon_fit(kf, X, y, cfg, ops=ops)
    assert ops.sweeps == 0
    assert ops.gram_tile_evals == -(-n // bs) + -(-M // bs)
    assert ops.gemm_sweeps == 6


def test_recompute_fit_unaffected_when_off():
    """knm_cache='off' charges zero cache counters — the seed path."""
    X, y, kf = _problem()
    cfg = FalkonConfig(num_centers=128, iterations=4, block_size=256,
                       lam=1e-4, knm_cache="off", estimate_cond=False)
    ops = CountingOps(get_ops("jnp", cfg.make_kernel(), block_size=256))
    falkon_fit(kf, X, y, cfg, ops=ops)
    assert ops.materializes == 0 and ops.gemm_sweeps == 0
    assert ops.sweeps == 2     # eager RHS + scanned CG matvec trace


def test_lambda_path_shares_one_cache_build():
    """L lam systems ride ONE materialization — and match the uncached
    path fit bit-identically in fp32."""
    X, y, kf = _problem()
    n, M, bs = 1000, 128, 256
    lams = (1e-3, 1e-4, 1e-5)
    base = dict(num_centers=M, iterations=6, block_size=bs, lam=1e-4)
    r0 = falkon_fit_path(kf, X, y, FalkonConfig(**base, knm_cache="off"), lams)
    cfg = FalkonConfig(**base, knm_cache="device")
    ops = CountingOps(get_ops("jnp", cfg.make_kernel(), block_size=bs))
    r1 = falkon_fit_path(kf, X, y, cfg, lams, ops=ops)
    np.testing.assert_array_equal(
        np.asarray(r0.state.alphas), np.asarray(r1.state.alphas))
    assert ops.materializes == 1
    assert ops.sweeps == 0
    assert ops.gram_tile_evals == -(-n // bs) + -(-M // bs)


def test_host_tier_fit_close():
    X, y, kf = _problem(n=900, M=96)
    base = dict(num_centers=96, iterations=6, block_size=256, lam=1e-4)
    est0, _ = falkon_fit(kf, X, y, FalkonConfig(**base, knm_cache="off"))
    esth, _ = falkon_fit(kf, X, y, FalkonConfig(**base, knm_cache="host"))
    p0, ph = np.asarray(est0.predict(X)), np.asarray(esth.predict(X))
    assert np.max(np.abs(ph - p0)) / np.max(np.abs(p0)) <= 1e-3


def test_auto_route_off_warns_and_matches_seed(monkeypatch):
    monkeypatch.setenv("REPRO_KNM_BUDGET_MB", "0.001")
    monkeypatch.setenv("REPRO_KNM_HOST_BUDGET_MB", "0.001")
    X, y, kf = _problem()
    base = dict(num_centers=128, iterations=4, block_size=256, lam=1e-4)
    _, st0 = falkon_fit(kf, X, y, FalkonConfig(**base, knm_cache="off"))
    with pytest.warns(CachePlanWarning) as rec:
        _, sta = falkon_fit(kf, X, y, FalkonConfig(**base, knm_cache="auto"))
    assert rec[0].message.plan.tier == "off"
    np.testing.assert_array_equal(np.asarray(st0.alpha), np.asarray(sta.alpha))


# ---------------------------------------------------------------------------
# Config validation + unsupported-variant refusals
# ---------------------------------------------------------------------------
def test_config_rejects_unknown_mode():
    with pytest.raises(ValueError, match="knm_cache"):
        FalkonConfig(knm_cache="hbm")


def test_streaming_and_minibatch_refuse_cache():
    X, y, kf = _problem(n=512, M=64)
    cfg = FalkonConfig(num_centers=64, iterations=2, block_size=256,
                       lam=1e-4, knm_cache="device")
    with pytest.raises(ValueError, match="mini-batch"):
        falkon_fit_minibatch(kf, X, y, cfg)
    from repro.data.streaming import ArrayChunkSource
    src = ArrayChunkSource(np.asarray(X), np.asarray(y), chunk_rows=256)
    with pytest.raises(ValueError, match="streaming"):
        falkon_fit_streaming(kf, src, cfg)


# ---------------------------------------------------------------------------
# Staleness: estimator + serving tier
# ---------------------------------------------------------------------------
def test_estimator_scoring_cache_and_staleness():
    X, y, kf = _problem()
    cfg = FalkonConfig(num_centers=128, iterations=6, block_size=256, lam=1e-4)
    est, _ = falkon_fit(kf, X, y, cfg)
    Xe = jax.random.normal(jax.random.PRNGKey(9), (300, X.shape[1]))
    cache = est.build_knm_cache(Xe, tier="device")
    direct = np.asarray(est._ops.apply(Xe.astype(est.centers.dtype),
                                       est.centers, est.alpha))
    # explicit cache, implicit (held) cache: both serve bit-identically
    np.testing.assert_array_equal(np.asarray(est.predict(Xe, cache=cache)), direct)
    # held cache only fast-paths the SAME X object it was built over
    held_x = cache.X
    np.testing.assert_array_equal(np.asarray(est.predict(held_x)), direct)
    # a foreign X with an explicit cache is refused, not silently recomputed
    X2 = jax.random.normal(jax.random.PRNGKey(10), (300, X.shape[1]))
    with pytest.raises(ValueError, match="different X"):
        est.predict(X2, cache=cache)
    # invalidation: explicit use refuses; implicit use falls back
    cache.invalidate()
    with pytest.raises(ValueError, match="stale"):
        est.predict(Xe, cache=cache)
    np.testing.assert_array_equal(np.asarray(est.predict(held_x)), direct)


def test_server_swap_model_invalidates_scoring_cache():
    """A cache of K(X_eval, old_centers) MUST NOT score a swapped model:
    swap_model invalidates + detaches it, and the caller's handle refuses."""
    from repro.serve import CoalescingPredictServer

    X, y, kf = _problem()
    cfg = FalkonConfig(num_centers=128, iterations=6, block_size=256, lam=1e-4)
    est, _ = falkon_fit(kf, X, y, cfg)
    Xe = jax.random.normal(jax.random.PRNGKey(9), (200, X.shape[1]))
    srv = CoalescingPredictServer(est, max_batch=128)
    srv.warmup()
    cache = est.build_knm_cache(Xe, tier="device")
    srv.attach_scoring_cache(cache)
    s0 = srv.predict_scoring_set()
    np.testing.assert_array_equal(
        s0, np.asarray(est.predict(Xe.astype(est.centers.dtype))))
    swapped = est.partial_fit(X[:512], y[:512])
    srv.swap_model(swapped)
    with pytest.raises(RuntimeError, match="no scoring cache"):
        srv.predict_scoring_set()
    with pytest.raises(ValueError, match="stale"):
        cache.check_serves(est.centers)
    # a fresh cache over the swapped model re-attaches cleanly
    cache2 = swapped.build_knm_cache(Xe)
    srv.attach_scoring_cache(cache2)
    np.testing.assert_array_equal(
        srv.predict_scoring_set(),
        np.asarray(swapped.predict(Xe.astype(swapped.centers.dtype))))


def test_attach_refuses_foreign_cache():
    from repro.serve import CoalescingPredictServer

    X, y, kf = _problem()
    cfg = FalkonConfig(num_centers=64, iterations=4, block_size=256, lam=1e-4)
    est, _ = falkon_fit(kf, X, y, cfg)
    other, _ = falkon_fit(jax.random.PRNGKey(42), X, y, cfg)
    Xe = jax.random.normal(jax.random.PRNGKey(9), (100, X.shape[1]))
    cache = other.build_knm_cache(Xe)
    srv = CoalescingPredictServer(est, max_batch=64)
    with pytest.raises(ValueError, match="different centers"):
        srv.attach_scoring_cache(cache)


# ---------------------------------------------------------------------------
# Distributed: shard-local caches, one psum per cached sweep
# ---------------------------------------------------------------------------
def _run(body: str) -> str:
    code = textwrap.dedent(body)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"   # a child never competes for the chip
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_distributed_cached_fit_parity():
    """Cached fit under a (4,2) mesh: shard-local row-block caches, one
    (M, p) psum per cached sweep, predictions matching the single-device
    cached fit; the host tier is refused under sharding."""
    _run("""
        import warnings
        import jax, jax.numpy as jnp, numpy as np
        import pytest
        from repro.core import FalkonConfig, falkon_fit
        from repro.ops import (
            CountingOps, DistributedOps, KernelCache, get_ops, plan_cache
        )
        assert len(jax.devices()) == 8
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        kx, kf = jax.random.split(jax.random.PRNGKey(0))
        X = jax.random.normal(kx, (1000, 6))
        y = jnp.sin(X[:, 0]) + 0.1 * jax.random.normal(kf, (1000,))
        base = dict(num_centers=128, iterations=6, block_size=64, lam=1e-4,
                    knm_cache="device", estimate_cond=False)
        est1, st1 = falkon_fit(kf, X, y, FalkonConfig(**base))
        cfg = FalkonConfig(**base, mesh=mesh)
        ops = CountingOps(DistributedOps(
            get_ops("jnp", cfg.make_kernel(), block_size=64),
            mesh, ("data",)))
        estd, std = falkon_fit(kf, X, y, cfg, ops=ops)
        rel = float(jnp.max(jnp.abs(std.alpha - st1.alpha))
                    / jnp.max(jnp.abs(st1.alpha)))
        assert rel < 2e-3, rel
        # shard-local tiles: no recompute sweeps, one materialization,
        # one psum per cached sweep program point (RHS + CG trace)
        assert ops.sweeps == 0 and ops.materializes == 1
        dist = ops.ops
        assert dist.psums == 2, dist.psums
        # host tier refuses under sharding
        plan = plan_cache(1000, 128, tier="host")
        try:
            KernelCache(ops, X, est1.centers, plan=plan)
            raise AssertionError("host tier should refuse under sharding")
        except ValueError as e:
            assert "DistributedOps" in str(e)
        print("DIST CACHED FIT OK", rel)
    """)
