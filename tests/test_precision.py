"""Precision-policy suite: bf16 end-to-end vs an fp64 oracle.

* error model, measured: every registered kernel on the fused, two-pass,
  j-sharded and streaming sweep paths stays within the documented relative
  error bound of an fp64 dense oracle — <= 1e-4 for the fp32 policy, <= 1e-2
  for end-to-end bf16 storage with compensated fp32 accumulation (storage
  quantization at eps_bf16 ~ 3.9e-3 dominates; the Kahan tile loops keep the
  summation term at O(eps_fp32)).
* fp32 stays bit-identical: the policy machinery must be a no-op on the
  default path — same arrays out of the backend as out of the raw kernels.
* CG storage contract: bf16 iterates / fp32 scalars converge, and track the
  fp32 solve on the M=32768 acceptance shape (axis-selected via
  REPRO_TEST_PRECISION — the CI precision matrix runs this file once per
  policy).
* planner: the budget model charges u/v/t at their storage dtype and the
  chosen dtypes are visible on ``SweepPlan`` (and its repr / the structured
  fallback warning).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import TEST_PRECISION, sweep_fp32_error_bound
from repro.compat import enable_x64
from repro.core import make_kernel, spec_of
from repro.core.cg import conjugate_gradient, conjugate_gradient_host
from repro.core.falkon import FalkonConfig, falkon_fit, falkon_fit_streaming
from repro.data import ArrayChunkSource, StreamingLoader, streaming_sweep
from repro.kernels.kernel_matvec import (
    fused_sweep_pallas, kernel_matmul_pallas, sharded_sweep_pallas
)
from repro.ops import (
    POLICIES, PrecisionPolicy, SweepPlanWarning, get_ops, resolve_precision
)

KERNELS = [
    ("gaussian", dict(sigma=1.3)),
    ("laplacian", dict(sigma=1.1)),
    ("matern32", dict(sigma=1.7)),
    ("linear", dict(scale=1.5)),
    ("polynomial", dict(degree=2, c=0.5, scale=2.0)),
]

#: Documented end-to-end relative error ceilings vs the fp64 oracle
#: (mirrored in README / benchmarks/precision_sweep.py).
ERROR_BOUND = {"fp32": 1e-4, "bf16": 1e-2}


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


def _oracle_sweep(kern, X, C, u, v):
    """K^T (K u + v) in float64 — the ground truth every policy is judged
    against (kernel math from the same registered formula, via __call__)."""
    with enable_x64(True):
        X64 = jnp.asarray(np.asarray(X), jnp.float64)
        C64 = jnp.asarray(np.asarray(C), jnp.float64)
        u64 = jnp.asarray(np.asarray(u), jnp.float64)
        K = kern(X64, C64)
        t = K @ u64
        if v is not None:
            t = t + jnp.asarray(np.asarray(v), jnp.float64)
        return np.asarray(K.T @ t, dtype=np.float64)


#: bf16 unit roundoff (8-bit significand, round to nearest).
U_BF16 = 2.0 ** -8

def _rel_err(got, oracle):
    got = np.asarray(got, dtype=np.float64)
    return float(np.linalg.norm(got - oracle) / np.linalg.norm(oracle))


# ---------------------------------------------------------------------------
# policy registry
# ---------------------------------------------------------------------------
def test_policy_registry_and_overrides():
    bf16 = resolve_precision("bf16")
    assert bf16 is POLICIES["bf16"]
    assert bf16.storage == "bfloat16" and bf16.accumulate == "float32"
    assert bf16.compensated
    assert bf16.buffer_dtype("gram") == "float32"        # per-buffer override
    assert bf16.buffer_dtype("cholesky") == "float32"
    assert bf16.buffer_dtype("u") == "bfloat16"          # default: storage
    assert bf16.storage_itemsize == 2 and bf16.accumulate_itemsize == 4

    fp32 = resolve_precision("fp32")
    assert fp32.storage == "float32" and not fp32.compensated

    with pytest.raises(ValueError, match="unknown precision"):
        resolve_precision("fp8")

    # a full PrecisionPolicy is accepted wherever a name is; per-buffer
    # overrides are honored (default: coeffs float32 -> w comes back fp32;
    # an empty override set makes even the coefficients ride bf16)
    custom = PrecisionPolicy(name="bf16-raw", storage="bfloat16", compensated=False)
    ops = get_ops("jnp", make_kernel("gaussian", sigma=1.5), precision=custom)
    assert ops.policy is custom
    X, C, u, v = _data(64, 32, 5, seed=0)
    assert ops.sweep(X, C, u, v).dtype == jnp.float32
    raw = PrecisionPolicy(
        name="bf16-all", storage="bfloat16", compensated=False, overrides=()
    )
    assert raw.buffer_dtype("coeffs") == "bfloat16"
    ops_raw = get_ops("jnp", make_kernel("gaussian", sigma=1.5), precision=raw)
    assert ops_raw.sweep(X, C, u, v).dtype == jnp.bfloat16


def test_custom_reduced_policy_widens_coeffs():
    """The coeffs=float32 override must hold for ANY reduced storage dtype
    (not just bfloat16): a float16 policy's sweep still takes/returns fp32
    coefficients, and the plan reports the true dtype names."""
    f16 = PrecisionPolicy(name="f16", storage="float16", compensated=True)
    X, C, u, v = _data(96, 48, 7, seed=2)
    for impl in ("jnp", "pallas"):
        ops = get_ops(
            impl, make_kernel("gaussian", sigma=1.5), block_size=64, precision=f16
        )
        w = ops.sweep(X, C, u.astype(jnp.float16), v)
        assert w.dtype == jnp.float32, impl   # coeffs override wins
    plan = ops.plan(96, 48, 7, 1)
    assert plan.input_dtype == "float16"      # not mislabeled as bfloat16
    assert plan.vector_dtype == "float16"
    assert plan.coeffs_dtype == "float32"


# ---------------------------------------------------------------------------
# error vs the fp64 oracle — all kernels, all sweep paths
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kernel_name,params", KERNELS)
@pytest.mark.parametrize("path", ["fused", "two_pass", "j_sharded"])
def test_bf16_sweep_error_within_bound(kernel_name, params, path):
    n, M, d = 160, 96, 11
    kern = make_kernel(kernel_name, **params)
    seed = [k for k, _ in KERNELS].index(kernel_name) * 7 + 1
    X, C, u, v = _data(n, M, d, seed=seed)
    oracle = _oracle_sweep(kern, X, C, u, v)

    bf = jnp.bfloat16
    Xb, Cb, ub, vb = (a.astype(bf) for a in (X, C, u, v))
    kw = dict(spec=spec_of(kern), block_m=64, compensated=True, interpret=True)
    if path == "fused":
        got = fused_sweep_pallas(Xb, Cb, ub, vb, block_n=64, **kw)
    elif path == "two_pass":
        got = sharded_sweep_pallas(Xb, Cb, ub, vb, shard_m=M, **kw)
    else:
        got = sharded_sweep_pallas(Xb, Cb, ub, vb, shard_m=64, **kw)
    assert got.dtype == bf                   # t spill / output at half width

    # The error splits into what the storage policy defines and what the
    # kernel adds. Storage: the sweep of the bf16-ROUNDED inputs, in fp64,
    # differs from the oracle by exactly |oracle_q - oracle|. Kernel: from
    # those inputs it rounds its output to bf16 (<= U_BF16 |w|), the
    # two-pass paths also round the spilled t (<= U_BF16 |K|^T |t|), and its
    # fp32 arithmetic stays within sweep_fp32_error_bound.
    oracle_q, fp32_bound, spill_mag = sweep_fp32_error_bound(
        kern, *(a.astype(jnp.float32) for a in (Xb, Cb, ub, vb)))
    oracle_q = oracle_q.reshape(oracle.shape)
    spill = 0.0 if path == "fused" else U_BF16 * np.linalg.norm(spill_mag)
    kernel_bound = (U_BF16 * np.linalg.norm(oracle_q) + spill
                    + np.linalg.norm(fp32_bound))
    got64 = np.asarray(got, np.float64)
    assert np.linalg.norm(got64 - oracle_q) <= kernel_bound
    bound = (np.linalg.norm(oracle_q - oracle) + kernel_bound) / np.linalg.norm(oracle)
    assert _rel_err(got, oracle) <= bound


@pytest.mark.parametrize("kernel_name,params", KERNELS)
def test_backend_sweep_error_both_policies(kernel_name, params):
    """The user-facing path: get_ops(...).sweep under each named policy stays
    within that policy's documented bound, for every registered kernel."""
    n, M, d = 200, 97, 9
    kern = make_kernel(kernel_name, **params)
    seed = [k for k, _ in KERNELS].index(kernel_name) * 3 + 2
    X, C, u, v = _data(n, M, d, seed=seed)
    oracle = _oracle_sweep(kern, X, C, u, v)
    for impl in ("jnp", "pallas"):
        for prec in ("fp32", "bf16"):
            got = get_ops(impl, kern, block_size=64, precision=prec).sweep(X, C, u, v)
            err = _rel_err(got, oracle)
            assert err <= ERROR_BOUND[prec], (impl, prec, err)


def test_streaming_bf16_chunk_dtype_and_error():
    """bf16 chunks cross the host->device boundary at half width and the
    chunk-accumulated sweep stays within the bf16 bound."""
    n, M, d = 300, 64, 8
    kern = make_kernel("gaussian", sigma=1.5)
    X, C, u, v = _data(n, M, d, seed=4)
    oracle = _oracle_sweep(kern, X, C, u, v)

    source = ArrayChunkSource(np.asarray(X), np.asarray(v), chunk_rows=77)
    loader = StreamingLoader(source, prefetch=0, dtype=jnp.bfloat16)
    for xc, yc in loader:
        assert xc.dtype == jnp.bfloat16 and yc.dtype == jnp.bfloat16
    ops = get_ops("jnp", kern, block_size=64, precision="bf16")
    got = streaming_sweep(ops, loader, C, u, use_targets=True)
    assert got.dtype == jnp.float32          # w at coeffs width
    assert _rel_err(got, oracle) <= ERROR_BOUND["bf16"]

    # fp32 loader + fp32 policy: chunked == in-core stays bit-exact with the
    # same block geometry (single chunk == single scan stream)
    src32 = ArrayChunkSource(np.asarray(X), np.asarray(v), chunk_rows=n)
    ld32 = StreamingLoader(src32, prefetch=0, dtype=jnp.float32)
    ops32 = get_ops("jnp", kern, block_size=64)
    np.testing.assert_array_equal(
        np.asarray(streaming_sweep(ops32, ld32, C, u, use_targets=True)),
        np.asarray(ops32.sweep(X, C, u, v)),
    )


# ---------------------------------------------------------------------------
# fp32 must stay bit-identical to the pre-policy code path
# ---------------------------------------------------------------------------
def test_fp32_path_bit_identical_to_raw_kernels():
    n, M, d = 300, 97, 13
    kern = make_kernel("gaussian", sigma=1.5)
    X, C, u, v = _data(n, M, d, seed=6)

    pops = get_ops("pallas", kern, block_size=128)
    raw = fused_sweep_pallas(
        X, C, u, v, spec=spec_of(kern), block_m=128, compensated=False, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(pops.sweep(X, C, u, v)), np.asarray(raw))

    # string name and explicit policy object resolve to the same arrays
    pol = PrecisionPolicy(name="fp32")
    np.testing.assert_array_equal(
        np.asarray(get_ops("jnp", kern, block_size=64).sweep(X, C, u, v)),
        np.asarray(get_ops("jnp", kern, block_size=64,
                           precision=pol).sweep(X, C, u, v)))


def test_compensated_accumulation_not_worse_than_plain():
    """Kahan two-sum must never lose to plain fp32 accumulation (and both
    sit under the fp32 bound) — many j tiles so the reduction is long."""
    m, n, d, p = 64, 4096, 7, 2
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    A = jax.random.normal(ks[0], (m, d))
    B = jax.random.normal(ks[1], (n, d))
    V = jax.random.normal(ks[2], (n, p))
    kern = make_kernel("gaussian", sigma=1.5)
    with enable_x64(True):
        K64 = kern(
            jnp.asarray(np.asarray(A), jnp.float64),
            jnp.asarray(np.asarray(B), jnp.float64),
        )
        oracle = np.asarray(K64 @ jnp.asarray(np.asarray(V), jnp.float64))

    kw = dict(spec=spec_of(kern), block_m=64, block_n=128, interpret=True)
    plain = kernel_matmul_pallas(A, B, V, compensated=False, **kw)
    comp = kernel_matmul_pallas(A, B, V, compensated=True, **kw)
    e_plain, e_comp = _rel_err(plain, oracle), _rel_err(comp, oracle)
    assert e_comp <= ERROR_BOUND["fp32"]
    assert e_comp <= e_plain * 1.5 + 1e-12, (e_comp, e_plain)


# ---------------------------------------------------------------------------
# CG storage contract
# ---------------------------------------------------------------------------
def _spd_system(q=96, p=2, seed=9):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    Q = jax.random.normal(ks[0], (q, q)) / np.sqrt(q)
    A = Q @ Q.T + 0.5 * jnp.eye(q)
    b = jax.random.normal(ks[1], (q, p))
    return A, b


@pytest.mark.parametrize("driver", [conjugate_gradient, conjugate_gradient_host])
def test_cg_bf16_storage_converges_with_fp32_scalars(driver):
    A, b = _spd_system()
    mv = lambda x: A @ x.astype(jnp.float32)
    res32 = driver(mv, b, 40, storage_dtype=None)
    resbf = driver(mv, b, 40, storage_dtype=jnp.bfloat16)
    assert resbf.x.dtype == jnp.bfloat16          # iterates at storage width
    assert resbf.residual_norms.dtype == jnp.float32   # scalars stay fp32
    r32 = np.linalg.norm(np.asarray(A @ res32.x.astype(jnp.float32) - b))
    rbf = np.linalg.norm(np.asarray(A @ resbf.x.astype(jnp.float32) - b))
    bn = np.linalg.norm(np.asarray(b))
    assert r32 / bn < 1e-5
    # bf16 iterate-rounding floor: ~ O(sqrt(cond) * eps_bf16) relative
    assert rbf / bn < 3e-2
    # storage_dtype float32 is the same arithmetic as None (no-op casts)
    res32b = driver(mv, b, 40, storage_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(res32.x), np.asarray(res32b.x))


def test_cg_convergence_parity_on_acceptance_shape():
    """CG on the normal-equation operator at the M=32768 acceptance point:
    the axis policy (REPRO_TEST_PRECISION) must track the fp32 solve."""
    n, M, d = 256, 32768, 7
    kern = make_kernel("gaussian", sigma=1.5)
    X, C, u0, y = _data(n, M, d, seed=11)
    # strongly regularized so 10 plain-CG iterations converge in fp32 — the
    # point here is the precision PARITY of the trajectory, not CG speed on
    # an ill-conditioned normal operator (falkon's preconditioner covers
    # that; this test runs the raw sweep at the acceptance shape).
    lam = 8.0

    def solve(prec):
        ops = get_ops("jnp", kern, block_size=4096, precision=prec)
        mv = lambda g: (ops.sweep(X, C, g, None).astype(jnp.float32) / n
                        + lam * g.astype(jnp.float32))
        b = ops.sweep(X, C, jnp.zeros_like(u0), y).astype(jnp.float32) / n
        storage = jnp.bfloat16 if prec == "bf16" else None
        return conjugate_gradient(mv, b, 10, storage_dtype=storage)

    ref = solve("fp32")
    got = solve(TEST_PRECISION)
    r_ref = float(ref.residual_norms[-1] / ref.residual_norms[0])
    r_got = float(got.residual_norms[-1] / got.residual_norms[0])
    assert r_ref < 1e-3                       # fp32 CG converges on this case
    if TEST_PRECISION == "fp32":
        np.testing.assert_array_equal(np.asarray(got.x), np.asarray(ref.x))
    else:
        assert r_got < 3e-2, r_got            # bf16 iterate rounding floor
        rel = _rel_err(got.x.astype(jnp.float32), np.asarray(ref.x, dtype=np.float64))
        assert rel < 5e-2, rel


# ---------------------------------------------------------------------------
# end-to-end fits under the axis policy
# ---------------------------------------------------------------------------
def test_falkon_fit_parity_under_axis_policy(rng):
    from conftest import synthetic_regression
    X, y = synthetic_regression(rng, 384)
    base = dict(
        kernel="gaussian",
        kernel_params=(("sigma", 2.0),),
        lam=1e-4,
        num_centers=64,
        iterations=25,
        block_size=128,
    )
    est_ref, _ = falkon_fit(
        jax.random.PRNGKey(1), X, y, FalkonConfig(**base, ops_impl="jnp")
    )
    est, _ = falkon_fit(
        jax.random.PRNGKey(1),
        X,
        y,
        FalkonConfig(**base, ops_impl="pallas", precision=TEST_PRECISION),
    )
    p_ref, p = est_ref.predict(X), est.predict(X)
    rel = float(jnp.linalg.norm(p.astype(jnp.float32) - p_ref) / jnp.linalg.norm(p_ref))
    assert rel < (5e-2 if TEST_PRECISION == "bf16" else 2e-3), rel


def test_falkon_fit_streaming_parity_under_axis_policy(rng):
    from conftest import synthetic_regression
    X, y = synthetic_regression(rng, 400)
    cfg = FalkonConfig(
        kernel="gaussian",
        kernel_params=(("sigma", 2.0),),
        lam=1e-4,
        num_centers=48,
        iterations=20,
        block_size=128,
        precision=TEST_PRECISION,
    )
    centers = np.asarray(X[:48])
    est_in, _ = falkon_fit(
        jax.random.PRNGKey(2),
        X,
        y,
        dataclasses.replace(cfg, center_selection="uniform"),
    )
    source = ArrayChunkSource(np.asarray(X), np.asarray(y), chunk_rows=97)
    est_st, _ = falkon_fit_streaming(
        jax.random.PRNGKey(2), source, cfg, centers=jnp.asarray(centers)
    )
    p_in = est_in.predict(X)
    p_st = est_st.predict(X)
    # different centers -> only sanity-level agreement is meaningful; the
    # strong check is that the streamed fit converged under the policy
    assert np.isfinite(np.asarray(p_st, dtype=np.float64)).all()
    rel = float(jnp.linalg.norm(p_st.astype(jnp.float32) - y) / jnp.linalg.norm(y))
    rel_in = float(jnp.linalg.norm(p_in.astype(jnp.float32) - y) / jnp.linalg.norm(y))
    assert rel < max(2 * rel_in, 0.5), (rel, rel_in)


# ---------------------------------------------------------------------------
# planner: storage-dtype budget model + dtypes on the plan
# ---------------------------------------------------------------------------
def test_plan_carries_dtypes_and_charges_storage():
    kern = make_kernel("gaussian", sigma=2.0)
    p32 = get_ops("pallas", kern, block_size=128).plan(4096, 2048, 32, 1)
    pbf = get_ops("pallas", kern, block_size=128, precision="bf16").plan(
        4096, 2048, 32, 1
    )
    assert p32.vector_dtype == "float32" and not p32.compensated
    assert pbf.input_dtype == "bfloat16"
    assert pbf.vector_dtype == "bfloat16"           # data-space v/t storage
    assert pbf.coeffs_dtype == "float32"            # u/w stay wide
    assert pbf.accum_dtype == "float32" and pbf.compensated
    assert "bfloat16" in repr(pbf)                  # dtypes visible in repr
    # X/C and v io tiles charged at storage width: bf16 io strictly smaller
    assert pbf.io_bytes < p32.io_bytes
    # compensation carry buffers charged in scratch
    assert pbf.scratch_bytes > p32.scratch_bytes
    # the HBM working set approaches the full 2x as n-sized terms dominate
    big32 = get_ops("pallas", kern, block_size=128).plan(262144, 2048, 32, 1)
    bigbf = get_ops("pallas", kern, block_size=128, precision="bf16").plan(
        262144, 2048, 32, 1
    )
    assert big32.hbm_bytes / bigbf.hbm_bytes >= 1.8


def test_sweep_plan_warning_carries_policy_dtypes():
    kern = make_kernel("gaussian", sigma=1.5)
    pops = get_ops("pallas", kern, block_size=128, precision="bf16")
    X, C, u, v = _data(64, 32768, 5, seed=3)
    with pytest.warns(SweepPlanWarning) as rec:
        got = pops.sweep(X, C, u, v)
    plan = rec[0].message.plan
    assert plan.vector_dtype == "bfloat16" and plan.compensated
    assert plan.coeffs_dtype == "float32"
    assert got.dtype == jnp.float32          # w at coeffs width
