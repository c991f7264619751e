"""Profiler spans and counts inside the FALKON fit.

Each fit runs under ``jax.profiler.trace``; the ``falkon.*`` host events are
read back from the trace with ``jax.profiler.ProfileData``. Checked: the
spans nest as docs/architecture.md ("Tracing") lists them, their ``sweeps``
counts add up to the sweeps a fit executes, the blocked factor's spans
carry its ``FactorStats``, the host CG driver records one step span per
executed iteration, and a traced fit computes the same bits as an untraced
one.
"""
import glob
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    FalkonConfig, MinibatchConfig, conjugate_gradient, conjugate_gradient_host,
    falkon_fit, falkon_fit_minibatch, falkon_fit_path, falkon_fit_streaming,
)
from repro.core.falkon import POWER_ITERS
from repro.data import ArrayChunkSource
from repro.kernels.blocked_cholesky import FactorStats, blocked_cholesky, blocked_syrk_tt
from repro.ops import plan_factor

N, D, M, T = 600, 5, 64, 6
FACTOR_COUNTS = ("panels", "tiles_updated", "bytes_transferred", "peak_device_bytes")


def _problem(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    X = jax.random.normal(ks[0], (N, D))
    y = jnp.sin(X @ jax.random.normal(ks[1], (D,))) + 0.05 * jax.random.normal(ks[2], (N,))
    return X, y


def _config(**kw):
    base = dict(kernel_params=(("sigma", 1.5),), lam=1e-4, num_centers=M,
                iterations=T, jitter=1e-3)
    return FalkonConfig(**dict(base, **kw))


def _spans(log_dir):
    """The ``falkon.*`` host events of the one trace under ``log_dir``, in
    start order, as dicts with name, start, end, stats and parent (the
    innermost span that holds it on the same thread, or None)."""
    path, = glob.glob(os.path.join(str(log_dir), "plugins", "profile", "*", "*.xplane.pb"))
    spans = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("falkon."):
                        spans.append(dict(name=e.name, line=line.name, start=e.start_ns,
                                          end=e.start_ns + e.duration_ns,
                                          stats=dict(e.stats)))
    spans.sort(key=lambda s: (s["start"], -s["end"]))
    for i, s in enumerate(spans):
        holders = [p for p in spans[:i] if p["line"] == s["line"]
                   and p["start"] <= s["start"] and s["end"] <= p["end"]]
        s["parent"] = holders[-1]["name"] if holders else None
    return spans


def _traced(tmp_path, fn):
    with jax.profiler.trace(str(tmp_path)):
        out = fn()
        jax.block_until_ready(out)
    return out, _spans(tmp_path)


def _sweeps(spans):
    return sum(s["stats"].get("sweeps", 0) for s in spans)


@pytest.mark.parametrize("estimate_cond", [True, False])
def test_fit_spans_nest_and_count_sweeps(tmp_path, estimate_cond):
    X, y = _problem()
    _, spans = _traced(tmp_path, lambda: falkon_fit(
        jax.random.PRNGKey(1), X, y, _config(estimate_cond=estimate_cond)))
    stages = ["falkon.select", "falkon.gram", "falkon.precondition", "falkon.rhs",
              "falkon.cg"] + (["falkon.cond"] if estimate_cond else []) + ["falkon.wrap"]
    root, *rest = spans
    assert root["name"] == "falkon.fit" and root["parent"] is None
    assert root["stats"] == dict(n=N, M=M, d=D, t=T)
    assert [s["name"] for s in rest] == stages           # in order, nothing else
    assert all(s["parent"] == "falkon.fit" for s in rest)
    by = {s["name"]: s["stats"] for s in rest}
    assert by["falkon.precondition"] == dict(path="incore")
    assert by["falkon.rhs"] == dict(sweeps=1)
    assert by["falkon.cg"] == dict(sweeps=T, iterations=T)
    if estimate_cond:
        assert by["falkon.cond"] == dict(sweeps=2 * (POWER_ITERS + 1))
    assert _sweeps(spans) == 1 + T + (26 if estimate_cond else 0)


@pytest.mark.filterwarnings("ignore::repro.ops.FactorPlanWarning")
def test_blocked_factor_spans_carry_factor_stats(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_FACTOR_BUDGET_MB", "0.05")      # M = 300 -> blocked
    m = 300
    block = plan_factor(m).block
    X, y = _problem()
    _, spans = _traced(tmp_path, lambda: falkon_fit(
        jax.random.PRNGKey(1), X, y, _config(num_centers=m)))
    by = {}
    for s in spans:
        by.setdefault(s["name"], []).append(s)
    pre, = by["falkon.precondition"]
    assert pre["stats"] == dict(path="blocked") and pre["parent"] == "falkon.fit"
    factor = [s for s in spans if s["name"].startswith("falkon.factor.")]
    assert all(s["parent"] == "falkon.precondition" for s in factor)
    # K_MM and T T^T go down; T, T T^T and A come back up, in this order
    assert [s["name"].rsplit(".", 1)[1] for s in factor] == [
        "download", "cholesky", "syrk", "upload", "upload", "download", "cholesky", "upload"]
    assert all(s["stats"] == dict(bytes=m * m * 4) for s in factor
               if s["name"].endswith(("download", "upload")))
    # each loop's counts are what a direct run of it at the same shape counts
    K = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (m, m)))
    K = K @ K.T / m + np.eye(m, dtype=np.float32)
    chol, syrk = FactorStats(), FactorStats()
    blocked_syrk_tt(blocked_cholesky(K, block, stats=chol), block, stats=syrk)
    want = lambda st: {k: getattr(st, k) for k in FACTOR_COUNTS}
    assert [s["stats"] for s in by["falkon.factor.cholesky"]] == [want(chol)] * 2
    assert [s["stats"] for s in by["falkon.factor.syrk"]] == [want(syrk)]
    assert chol.panels == -(-m // block) > 1 and chol.tiles_updated > 0


def _spd_system(q=40, seed=0):
    A = np.asarray(jax.random.normal(jax.random.PRNGKey(seed), (q, q)))
    A = A @ A.T / q + 0.5 * np.eye(q, dtype=np.float32)
    return (lambda v: jnp.asarray(A) @ v), jnp.ones((q,), jnp.float32)


@pytest.mark.parametrize("tol", [1e-3, 1e-5])
def test_host_cg_records_one_step_per_executed_iteration(tmp_path, tol):
    matvec, b = _spd_system()
    t = 40
    res, spans = _traced(tmp_path, lambda: conjugate_gradient_host(matvec, b, t, tol=tol))
    cg, = [s for s in spans if s["name"] == "falkon.cg"]
    steps = [s for s in spans if s["name"] == "falkon.cg.step"]
    ran = int(res.iterations)
    assert 0 < ran < t                          # stopped early at the tolerance
    assert len(steps) == ran and all(s["parent"] == "falkon.cg" for s in steps)
    assert all(s["stats"] == dict(sweeps=1) for s in steps)
    assert cg["stats"] == dict(sweeps=0, iterations=ran)
    assert _sweeps(spans) == ran


@pytest.mark.parametrize("x0", [False, True])
def test_scan_cg_counts_every_matvec(tmp_path, x0):
    """The scanned driver runs all t matvecs at any tolerance; a start
    point adds its residual's matvec."""
    matvec, b = _spd_system()
    start = 0.1 * b if x0 else None
    _, spans = _traced(tmp_path, lambda: conjugate_gradient(matvec, b, 9, tol=1e-2, x0=start))
    cg, = spans
    assert cg["stats"] == dict(sweeps=9 + x0, iterations=9)


def _streaming_fit(key, X, y, cfg):
    return falkon_fit_streaming(key, ArrayChunkSource(np.asarray(X), np.asarray(y),
                                                      chunk_rows=256), cfg)


def _minibatch_fit(key, X, y, cfg):
    return falkon_fit_minibatch(key, X, y, cfg, MinibatchConfig(chunk_rows=128, epochs=1))


def _path_fit(key, X, y, cfg):
    return falkon_fit_path(key, X, y, cfg, [1e-3, 1e-4])


@pytest.mark.parametrize("fit, spans_expected", [
    (_path_fit, ["falkon.select", "falkon.gram", "falkon.precondition", "falkon.rhs",
                 "falkon.cg", "falkon.wrap", "falkon.wrap"]),
    (_streaming_fit, ["falkon.gram", "falkon.precondition", "falkon.rhs", "falkon.cg"]
     + ["falkon.cg.step"] * T + ["falkon.wrap"]),
    (_minibatch_fit, ["falkon.select", "falkon.gram", "falkon.precondition", "falkon.wrap"]),
], ids=["path", "streaming", "minibatch"])
def test_every_fit_variant_records_its_stages(tmp_path, fit, spans_expected):
    X, y = _problem()
    _, spans = _traced(tmp_path, lambda: fit(jax.random.PRNGKey(1), X, y, _config()))
    assert [s["name"] for s in spans] == spans_expected


@pytest.mark.filterwarnings("ignore::repro.ops.FactorPlanWarning")
@pytest.mark.parametrize("budget_mb", [None, "0.005"], ids=["incore", "blocked"])
def test_traced_fit_is_bit_identical(tmp_path, monkeypatch, budget_mb):
    if budget_mb is not None:
        monkeypatch.setenv("REPRO_FACTOR_BUDGET_MB", budget_mb)
    X, y = _problem()
    fit = lambda: falkon_fit(jax.random.PRNGKey(1), X, y, _config())
    plain, _ = fit()
    (traced, state), spans = _traced(tmp_path, fit)
    assert spans and spans[0]["name"] == "falkon.fit"
    np.testing.assert_array_equal(np.asarray(traced.alpha), np.asarray(plain.alpha))
    assert np.isfinite(np.asarray(state.cond_estimate))
