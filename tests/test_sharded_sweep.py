"""Out-of-core (j-sharded) sweep: parity vs the jnp reference + the planner.

* ``sharded_sweep_pallas`` parity across all five registered kernels, ragged
  M not divisible by the shard size, multi-rhs u, and v=None — <= 1e-4 fp32
  against the jnp reference backend.
* The M >= 32k acceptance point: the pallas backend's ``sweep`` routed by
  the planner onto the j-sharded path (CPU-interpreted Pallas) stays within
  the fp32 rounding bound of a float64 oracle while the fused path's VMEM
  model says "no".
* ``plan_sweep`` / ``KernelOps.plan()``: fused-to-two-pass-to-j-sharded
  transitions driven by the VMEM budget model, shard sizing, budget
  overrides, and the structured ``SweepPlanWarning`` on fallback.
"""
import jax
import numpy as np
import pytest

from conftest import sweep_fp32_error_bound
from repro.core import make_kernel, spec_of
from repro.kernels.kernel_matvec import sharded_sweep_pallas, sweep_block_dims
from repro.ops import SweepPlanWarning, get_ops, plan_sweep

KERNELS = [
    ("gaussian", dict(sigma=1.3)),
    ("laplacian", dict(sigma=1.1)),
    ("matern32", dict(sigma=1.7)),
    ("linear", dict(scale=1.5)),
    ("polynomial", dict(degree=2, c=0.5, scale=2.0)),
]

TOL = dict(rtol=1e-4, atol=1e-4)


def _data(n, M, d, p=None, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    ush = (M,) if p is None else (M, p)
    vsh = (n,) if p is None else (n, p)
    return (
        jax.random.normal(ks[0], (n, d)),
        jax.random.normal(ks[1], (M, d)),
        jax.random.normal(ks[2], ush),
        jax.random.normal(ks[3], vsh),
    )


@pytest.mark.parametrize("kernel_name,params", KERNELS)
def test_sharded_parity_all_kernels_ragged_shards(kernel_name, params):
    """M=333 with shard_m=128: shards of 128/128/77 — ragged in both the
    shard count and the final shard's row count."""
    n, M, d = 200, 333, 13
    kern = make_kernel(kernel_name, **params)
    seed = [k for k, _ in KERNELS].index(kernel_name)
    X, C, u, v = _data(n, M, d, seed=seed)
    ref = get_ops("jnp", kern, block_size=64).sweep(X, C, u, v)
    got = sharded_sweep_pallas(X, C, u, v, spec=spec_of(kern), shard_m=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **TOL)


@pytest.mark.parametrize("p", [None, 3])
@pytest.mark.parametrize("shard_m", [100, 512])
def test_sharded_parity_multirhs_and_vnone(p, shard_m):
    n, M, d = 150, 257, 9
    kern = make_kernel("gaussian", sigma=1.5)
    X, C, u, v = _data(n, M, d, p=p, seed=7)
    jops = get_ops("jnp", kern, block_size=64)
    for vv in (v, None):
        got = sharded_sweep_pallas(X, C, u, vv, spec=spec_of(kern), shard_m=shard_m)
        ref = jops.sweep(X, C, u, vv)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **TOL)


def test_big_m_backend_routes_j_sharded_and_matches_reference():
    """The acceptance point: M = 32768 >= 32k on CPU-interpreted Pallas.

    The planner must refuse the fused path (its strip+accumulator is ~50MB
    against a 12MB budget), warn structurally, take the j-sharded path in
    more than one shard, and still match the float64 oracle within the
    worst-case fp32 rounding bound of its accumulation lengths (every
    element, see ``conftest.sweep_fp32_error_bound``).
    """
    n, M, d, p = 256, 32768, 7, 2
    kern = make_kernel("gaussian", sigma=1.5)
    pops = get_ops("pallas", kern, block_size=128)

    plan = pops.plan(n, M, d, p)
    assert plan.path == "j_sharded"
    assert plan.shard_m is not None and plan.shard_m < M
    assert plan.total_bytes > plan.vmem_budget_bytes

    X, C, u, v = _data(n, M, d, p=p, seed=11)
    with pytest.warns(SweepPlanWarning) as rec:
        got = pops.sweep(X, C, u, v)
    assert rec[0].message.plan.path == "j_sharded"
    # forward pass: M in tiles of 512 into an fp32 accumulator (64 tiles);
    # transposed pass: the n = 256 rows in one tile
    oracle, bound, _ = sweep_fp32_error_bound(
        kern, X, C, u, v, fwd_terms=512 + M // 512 + 1, bwd_terms=n)
    err = np.abs(np.asarray(got, np.float64) - oracle)
    assert np.all(err <= bound), float(np.max(err / bound))


def test_planner_transitions_with_budget():
    """fused -> two_pass -> j_sharded as the budget shrinks, M fixed."""
    bm, bn = sweep_block_dims(4096, 2048, 256, 512)
    big = plan_sweep(4096, 2048, 32, 1, bm=bm, bn=bn, vmem_budget=64 * 2**20)
    assert big.path == "fused" and big.shard_m is None
    mid = plan_sweep(4096, 2048, 32, 1, bm=bm, bn=bn, vmem_budget=4 * 2**20)
    assert mid.path in ("two_pass", "j_sharded")
    tiny = plan_sweep(4096, 2048, 32, 1, bm=bm, bn=bn, vmem_budget=2**19)
    assert tiny.path == "j_sharded"
    assert tiny.shard_m is not None
    assert tiny.shard_m % bn == 0, "shards must stay tile-aligned"
    # the reason string carries the budget numbers (the structured part of
    # the fallback warning)
    assert str(tiny.vmem_budget_bytes) in tiny.reason


def test_planner_env_budget_override(monkeypatch):
    kern = make_kernel("gaussian", sigma=2.0)
    pops = get_ops("pallas", kern, block_size=2048)
    assert pops.plan(2048, 2048, 32, 1).path == "fused"
    monkeypatch.setenv("REPRO_VMEM_BUDGET_MB", "1")
    assert pops.plan(2048, 2048, 32, 1).path != "fused"


def test_jnp_backend_reports_plan_too():
    jops = get_ops("jnp", make_kernel("gaussian", sigma=2.0), block_size=512)
    plan = jops.plan(10_000, 4096, 32)
    assert plan.path == "jnp"
    assert "lax.scan" in plan.reason


def test_sweep_with_stats_rejects_out_of_core_shapes():
    """The tile counter only exists on the fused kernel; shapes the planner
    routes out-of-core must be rejected, not silently measured elsewhere."""
    kern = make_kernel("gaussian", sigma=1.5)
    pops = get_ops("pallas", kern, block_size=128)
    X, C, u, v = _data(64, 32768, 5, seed=3)
    with pytest.raises(ValueError, match="VMEM budget"):
        pops.sweep_with_stats(X, C, u, v)


def test_small_shapes_still_take_the_fused_path():
    """Regression guard: the planner must not push in-core shapes (the
    entire pre-existing test matrix) off the single-evaluation fused path."""
    kern = make_kernel("gaussian", sigma=1.5)
    pops = get_ops("pallas", kern, block_size=128)
    for n, M in [(300, 97), (513, 129), (2048, 1024)]:
        assert pops.plan(n, M, 16, 1).path == "fused", (n, M)
