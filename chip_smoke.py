#!/usr/bin/env python3
"""Bring-up check of FALKON's main path on a TPU: the fit and coalesced scoring.

    python chip_smoke.py                # one chip: phases (a)-(d)
    python chip_smoke.py --four-chips   # the data-parallel fit on 4 chips vs 1

The problem is SUSY-shaped: ``PAPER_TASKS["susy"]`` of
``repro.data.synthetic`` (the paper's Sect. 5 binary task: d = 18, Gaussian
sigma = 4, lam = 1e-6), made from a seed. The paper trains SUSY on 5e6 rows;
this check cuts that to 1e6 training rows (plus 1e5 held-out rows) so that
two full fits and the scoring phase stay well inside one twenty-minute run.
M = 1e4 uniform Nystrom centers, 20 CG iterations, the fp32 policy. Device
bytes: X 72 MB, K_MM 400 MB, the T and A factors 400 MB each, the two-pass
sweep's (n, 128) fp32 ``t`` spill 512 MB.

Phases (one process, library entry points only):

(a) Stop unless ``jax.devices()[0].platform == "tpu"``.
(b) ``falkon_fit`` with ``ops_impl="pallas"``: the sweep and factor paths
    the planners take, setup+compile seconds apart from solve seconds, the
    CG residuals.
(c) The same fit with the ``jnp`` backend under
    ``jax.default_matmul_precision("highest")``, the float32 reference:
    held-out predictions and MSE of the Pallas fit against it, within the
    bounds derived in :func:`agreement_bound` from the fp32 floor of this
    fit (two reference fits that differ only in their summation grouping).
(d) ``CoalescingPredictServer(max_batch=1024)``: 64 seeded ragged requests,
    each answer checked against ``est.predict`` on the same rows, and zero
    retraces after warmup.

``--four-chips`` runs only the phase-(b) fit over
``Mesh(jax.devices()[:4], ("data",))`` and the same fit on one chip, which
must agree within the same bound (psum reassociation is one more regrouping
of the row sums).

Any failed check exits 1. On success the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
every other line comes before it. With no TPU the script exits 2 and prints
no result line.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

#: fp32 unit roundoff (round to nearest).
U32 = 2.0 ** -24


@dataclasses.dataclass(frozen=True)
class Sizes:
    n_train: int = 1_000_000     # SUSY: 5e6 in the paper, cut 5x (see above)
    n_test: int = 100_000
    centers: int = 10_000
    iterations: int = 20
    requests: int = 64
    max_batch: int = 1024
    seed: int = 0


class CheckFailed(RuntimeError):
    """A smoke check did not hold; the message says which and by how much."""


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    log(("PASS " if ok else "FAIL ") + what)
    if not ok:
        raise CheckFailed(what)


# ---------------------------------------------------------------------------
# bounds
# ---------------------------------------------------------------------------
#: How far apart two fp32 evaluations of the fit may land, in units of the
#: measured regrouping floor (see agreement_bound).
FLOOR_FACTOR = 4.0


def agreement_bound(floor: float) -> float:
    """Bound on ||f_a - f_b|| / ||f_b|| for two fp32 evaluations of the
    same fit (same data, centers, lam, iterations), given ``floor``: the
    relative difference between two jnp reference fits that differ only in
    the grouping of their row sums (block_size 2048 vs 1024).

    The fit amplifies rounding: a relative error in a sweep passes through
    the preconditioner's triangular solves, scaled by up to cond(T) cond(A)
    (about 5e5 at n = 1e5, M = 3e3), so a worst-case bound is vacuous. To
    first order, though, a fit's error is e = G xi: one linear map G of its
    rounding errors xi, independent with variance s^2 per operation. Two
    evaluations then differ by G (xi_a - xi_b), of expected square norm
    |G|^2 (s_a^2 + s_b^2). The floor is that for two evaluations with the
    reference's statistics, ~ sqrt(2) |G| s_r; an evaluation whose rounding
    is c times the reference's lands within sqrt((1 + c^2) / 2) floors of
    it. FLOOR_FACTOR = 4 admits c up to 5.6. A matmul that silently runs as
    one bf16 pass (2.3e-3 relative, against ~sqrt(n) 2^-24 = 6e-5 for an
    fp32 sum of n = 1e6 rows) has c ~ 40 and fails it.
    """
    return FLOOR_FACTOR * floor


def rel_diff(a: np.ndarray, b: np.ndarray) -> float:
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def mse_band(eps: float, f_ref: np.ndarray, y: np.ndarray) -> float:
    """|MSE(f) - MSE(f_ref)| allowed when ||f - f_ref|| <= eps ||f_ref||.

    With d = f - f_ref, MSE(f) - MSE(f_ref) = (2 d.(f_ref - y) + d.d) / n,
    and Cauchy-Schwarz gives the band 2 eps rms(f_ref) sqrt(MSE(f_ref)) +
    eps^2 ms(f_ref).
    """
    ms_f = float(np.mean(f_ref.astype(np.float64) ** 2))
    mse = float(np.mean((f_ref.astype(np.float64) - y) ** 2))
    return 2.0 * eps * math.sqrt(ms_f * mse) + eps * eps * ms_f


def dot_bound(terms: int) -> float:
    """gamma_k = k u / (1 - k u): the worst-case relative error of a k-term
    fp32 dot product against its |x|.|y| (Higham, Thm 3.1)."""
    return terms * U32 / (1.0 - terms * U32)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class CompileClock:
    """Seconds JAX spends in backend compiles (persistent-cache reads
    included) and persistent-cache hits, read from ``jax.monitoring``."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def make_data(s: Sizes):
    """SUSY-shaped train/held-out split made on the device from the seed."""
    import jax
    from repro.data.synthetic import PAPER_TASKS, make_kernel_dataset
    task = PAPER_TASKS["susy"]
    X, y = make_kernel_dataset(jax.random.PRNGKey(s.seed), task,
                               n=s.n_train + s.n_test)
    X, y = jax.block_until_ready((X, y))
    return (X[:s.n_train], y[:s.n_train], X[s.n_train:], y[s.n_train:], task)


def fit_config(s: Sizes, task, ops_impl: str, mesh=None):
    from repro.core import FalkonConfig
    return FalkonConfig(
        kernel="gaussian", kernel_params=(("sigma", task.sigma),),
        lam=task.lam, num_centers=s.centers, iterations=s.iterations,
        ops_impl=ops_impl, precision="fp32", mesh=mesh)


def timed_fit(s: Sizes, data, cfg, clock: CompileClock, *, ops=None,
              time_solve: bool = True) -> dict:
    """Fit through ``falkon_fit``, then (``time_solve``) time its solve
    stage alone: compiled ahead as one program, then run once. Solve
    seconds are that run; setup+compile seconds are the rest of the cold
    fit."""
    import jax
    from repro.core import falkon_fit, falkon_solve
    X, y = data[0], data[1]
    key = jax.random.PRNGKey(s.seed + 1)
    c0, h0, t0 = clock.seconds, clock.cache_hits, time.perf_counter()
    est, state = falkon_fit(key, X, y, cfg, ops=ops)
    jax.block_until_ready(est.alpha)
    fit_s = time.perf_counter() - t0
    psums = getattr(ops, "psums", None)   # traced by the fit alone
    compile_s, hits = clock.seconds - c0, clock.cache_hits - h0

    solve_s = float("nan")
    if time_solve:
        solve = jax.jit(functools.partial(
            falkon_solve, kernel=cfg.make_kernel(), lam=cfg.lam,
            t=cfg.iterations, estimate_cond=cfg.estimate_cond,
            ops=ops if ops is not None else cfg.make_ops()))
        solve = solve.lower(X, y, state.centers, state.precond).compile()
        t0 = time.perf_counter()
        jax.block_until_ready(solve(X, y, state.centers, state.precond).alpha)
        solve_s = time.perf_counter() - t0
    res = np.asarray(state.residual_norms, np.float64).reshape(-1)
    return dict(est=est, state=state, fit_s=fit_s, compile_s=compile_s,
                cache_hits=hits, solve_s=solve_s, psums=psums,
                setup_compile_s=fit_s - solve_s, residuals=res,
                rho=float(res[-1] / res[0]),
                kappa=float(state.cond_estimate))


def report_fit(tag: str, r: dict) -> None:
    split = ("" if math.isnan(r["solve_s"]) else
             f" = setup+compile {r['setup_compile_s']:.3f}s + solve "
             f"{r['solve_s']:.3f}s")
    log(f"[{tag}] fit {r['fit_s']:.3f}s{split} (backend compile "
        f"{r['compile_s']:.3f}s, {r['cache_hits']} persistent-cache hits)")
    log(f"[{tag}] CG residuals ||r_k||: "
        + " ".join(f"{v:.3e}" for v in r["residuals"]))
    log(f"[{tag}] relative residual {r['rho']:.3e}, cond(W) estimate "
        f"{r['kappa']:.4f}")


def phase_fit(s: Sizes, data, clock: CompileClock) -> dict:
    """(b) the Pallas fit, with the paths its planners take."""
    from repro.ops import plan_factor
    X, task = data[0], data[4]
    cfg = fit_config(s, task, "pallas")
    ops = cfg.make_ops()
    sweep = ops.plan(int(X.shape[0]), s.centers, int(X.shape[1]), 1)
    factor = plan_factor(s.centers, policy=ops.policy)
    log(f"[b] sweep path {sweep.path}: {sweep.reason}")
    log(f"[b] factor path {factor.path}: {factor.reason}")
    r = timed_fit(s, data, cfg, clock)
    report_fit("b", r)
    check(bool(np.all(np.isfinite(r["residuals"]))), "pallas CG residuals are finite")
    check(r["rho"] < 1.0, f"pallas CG reduced the residual (rho {r['rho']:.3e} < 1)")
    return r


def reference_fits(s: Sizes, data, clock: CompileClock | None = None):
    """The jnp float32 reference: held-out predictions of the fit at the
    default block size (timed when ``clock`` is given) and of the same fit
    with its row sums grouped in 1024-row blocks; returns (timing dict or
    None, predictions, regrouping floor)."""
    import jax
    from repro.core import falkon_fit
    X, y, Xte, task = data[0], data[1], data[2], data[4]
    cfg = fit_config(s, task, "jnp")
    with jax.default_matmul_precision("highest"):
        if clock is not None:
            ref = timed_fit(s, data, cfg, clock)
            est = ref["est"]
        else:
            ref = None
            est, _ = falkon_fit(jax.random.PRNGKey(s.seed + 1), X, y, cfg)
        f_ref = np.asarray(est.predict(Xte), np.float64)
        regrouped = dataclasses.replace(cfg, block_size=1024)
        est2, _ = falkon_fit(jax.random.PRNGKey(s.seed + 1), X, y, regrouped)
        f_ref2 = np.asarray(est2.predict(Xte), np.float64)
    floor = rel_diff(f_ref2, f_ref)
    log(f"[ref] fp32 regrouping floor (jnp block 1024 vs 2048): {floor:.3e}")
    return ref, f_ref, floor


def phase_reference(s: Sizes, data, fit: dict, clock: CompileClock) -> None:
    """(c) the jnp float32 reference and the agreement checks."""
    Xte, yte = data[2], data[3]
    ref, f_ref, floor = reference_fits(s, data, clock)
    report_fit("c", ref)
    f_pal = np.asarray(fit["est"].predict(Xte), np.float64)
    yt = np.asarray(yte, np.float64)
    check(bool(np.all(np.isfinite(f_pal))), "pallas held-out predictions are finite")
    check(f_pal.shape == (s.n_test,), f"prediction shape {f_pal.shape} == ({s.n_test},)")

    bound = agreement_bound(floor)
    rel = rel_diff(f_pal, f_ref)
    check(rel <= bound, f"held-out prediction rel diff {rel:.3e} <= bound "
          f"{bound:.3e} ({FLOOR_FACTOR:g} x the regrouping floor)")

    mse_pal = float(np.mean((f_pal - yt) ** 2))
    mse_ref = float(np.mean((f_ref - yt) ** 2))
    band = mse_band(bound, f_ref, yt)
    check(abs(mse_pal - mse_ref) <= band,
          f"held-out MSE pallas {mse_pal:.6f} vs reference {mse_ref:.6f}: "
          f"|diff| {abs(mse_pal - mse_ref):.3e} <= band {band:.3e}")
    var = float(np.var(yt))
    check(mse_ref < var, f"reference MSE {mse_ref:.6f} < label variance {var:.6f}")


def phase_serve(s: Sizes, data, est) -> None:
    """(d) coalesced scoring against est.predict, zero retraces."""
    import jax.numpy as jnp
    from repro.serve import CoalescingPredictServer
    Xte = np.asarray(data[2])
    rng = np.random.default_rng(s.seed)
    sizes = rng.integers(1, s.max_batch + 1, size=s.requests)
    starts = rng.integers(0, Xte.shape[0] - s.max_batch, size=s.requests)
    batches = [Xte[a:a + k] for a, k in zip(starts, sizes)]

    server = CoalescingPredictServer(est, max_batch=s.max_batch)
    t0 = time.perf_counter()
    warm = server.warmup()
    log(f"[d] warmup {time.perf_counter() - t0:.3f}s over ladder {server.ladder}")
    t0 = time.perf_counter()
    outs = server.predict_many(batches)
    serve_s = time.perf_counter() - t0
    log(f"[d] served {s.requests} requests, {int(sizes.sum())} rows in "
        f"{serve_s:.3f}s ({server.stats.dispatches} dispatches, pad fraction "
        f"{server.stats.pad_fraction:.4f}); warmup per rung "
        + " ".join(f"{k}:{v:.3f}s" for k, v in warm.items()))

    # est.predict is row-local, so one call over all requested rows answers
    # for each request. Both sides evaluate the same M-term dot product per
    # row; any regrouping moves a row by at most 2 gamma_M (K |alpha|)_i
    # (K >= 0 for the Gaussian).
    rows = jnp.asarray(np.concatenate(batches))
    ref = np.split(np.asarray(est.predict(rows), np.float64), np.cumsum(sizes)[:-1])
    abs_est = dataclasses.replace(est, alpha=jnp.abs(est.alpha))
    mag = np.split(np.asarray(abs_est.predict(rows), np.float64), np.cumsum(sizes)[:-1])
    gamma = dot_bound(int(est.centers.shape[0]) + 2 * int(est.centers.shape[1]))
    worst, exact = 0.0, 0
    for out, r, m in zip(outs, ref, mag):
        got = np.asarray(out, np.float64)
        check(got.shape == r.shape, f"answer shape {got.shape} == {r.shape}")
        worst = max(worst, float(np.max(np.abs(got - r) / (2.0 * gamma * m + 1e-30))))
        exact += int(np.array_equal(got, r))
    log(f"[d] {exact}/{s.requests} answers bit-identical to est.predict")
    check(worst <= 1.0, f"every served row within 2 gamma_M (K|alpha|) of "
          f"est.predict (worst {worst:.3e} of the bound)")
    check(server.retraces_since_warmup() == 0,
          f"retraces_since_warmup() == {server.retraces_since_warmup()} == 0")


def phase_four_chips(s: Sizes, data, clock: CompileClock) -> None:
    """The phase-(b) fit over a 4-device data mesh vs the same fit on one."""
    import jax
    from jax.sharding import Mesh
    from repro.ops import DistributedOps
    X, y, Xte, yte, task = data
    # one compile per device layout is all this phase pays for: no solve
    # re-timing (the one-chip run reports it)
    one = timed_fit(s, data, fit_config(s, task, "pallas"), clock, time_solve=False)
    report_fit("1 chip", one)
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("data",))
    cfg = fit_config(s, task, "pallas", mesh=mesh)
    ops = DistributedOps(fit_config(s, task, "pallas").make_ops(), mesh, ("data",))
    plan = ops.plan(int(X.shape[0]), s.centers, int(X.shape[1]))
    log(f"[4 chips] sweep path per shard {plan.path}: {plan.reason}")
    four = timed_fit(s, data, cfg, clock, ops=ops, time_solve=False)
    report_fit("4 chips", four)
    log(f"[4 chips] psums traced by the fit: {four['psums']}; "
        f"{ops.num_shards} shards")
    f1 = np.asarray(one["est"].predict(Xte), np.float64)
    f4 = np.asarray(four["est"].predict(Xte), np.float64)
    a1 = np.asarray(one["est"].alpha, np.float64)
    a4 = np.asarray(four["est"].alpha, np.float64)
    log(f"[4 chips] alpha rel diff {rel_diff(a4, a1):.3e}")
    _, _, floor = reference_fits(s, data)
    bound = agreement_bound(floor)
    rel = rel_diff(f4, f1)
    check(bool(np.all(np.isfinite(f4))), "4-chip held-out predictions are finite")
    check(rel <= bound, f"4-chip vs 1-chip held-out prediction rel diff "
          f"{rel:.3e} <= bound {bound:.3e} ({FLOOR_FACTOR:g} x the regrouping floor)")
    check(four["psums"] >= 1, f"the 4-chip fit issued psums ({four['psums']})")


def run(s: Sizes, four_chips: bool) -> None:
    import jax
    clock = CompileClock()
    data = make_data(s)
    log(f"[data] X {tuple(data[0].shape)} {data[0].dtype}, held-out "
        f"{tuple(data[2].shape)}, task {data[4].name}")
    if four_chips:
        if len(jax.devices()) < 4:
            raise CheckFailed(f"--four-chips needs 4 devices, found {len(jax.devices())}")
        phase_four_chips(s, data, clock)
        return
    fit = phase_fit(s, data, clock)
    phase_reference(s, data, fit, clock)
    phase_serve(s, data, fit["est"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip data-parallel fit and its "
                         "1-chip comparison")
    args = ap.parse_args(argv)

    import jax
    info = device_info()
    if info["platform"] != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {info['platform']!r}, "
              f"{info['count']} device(s)); nothing was run", file=sys.stderr)
        return 2
    log(f"[a] device {info['kind']} x{info['count']} (jax {jax.__version__})")

    from repro.compile_cache import enable_compile_cache
    log(f"[a] compile cache {enable_compile_cache()}")
    t0 = time.perf_counter()
    try:
        run(Sizes(), args.four_chips)
    except CheckFailed as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr)
        return 1
    log(f"[done] {time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
