"""Traffic ``fit_loop``: whole FALKON fits of one deployment, back to back.

What a user of ``falkon_fit`` gets today: the configuration file gives only
the deployment's hyperparameters, so the program runs its own default path
(backend, cond(W) diagnostic, the planners' factor route). The data is made
on the device from the seed; each fit draws its centers with a key of its
own from the seed.

Set-up: data, then one warm-up fit, which compiles (first run in a checkout)
or loads from the compile cache every program of the cell's shapes.
Window (``--trace 0``): fits start while fewer than ``seconds`` have passed,
so the window is the least whole number of fits that covers it; ``fit_s``
is their total time over their count. Traced run (``--trace 1``): one fit
(span ``bench.fit``), then the public ``falkon_solve`` on that fit's state,
compiled ahead in set-up and run once (span ``bench.solve``).

After the window one fit is drawn from the seed (the traced one with
``--trace 1``) and the program's sweep is probed on one row of it (see
``sweep_row``); then the memory peak is read, the program's state is freed,
and that fit and probe are compared with the plain reference fit from the
same key (``reference.falkon_ref``).
"""
from __future__ import annotations

import functools
import sys
import time

import numpy as np

from harness import cell as cells, compile_cache, data, keys, output, profile, work


def _program_config(c: dict):
    from repro.core import FalkonConfig
    return FalkonConfig(kernel=c["kernel"], kernel_params=(("sigma", float(c["sigma"])),),
                        lam=float(c["lam"]), num_centers=int(c["num_centers"]),
                        iterations=int(c["iterations"]), precision=c["precision"])


def _fit(key, X, y, cfg) -> dict:
    """One timed fit; returns its output on the host-side checks need."""
    import jax
    from repro.core import falkon_fit
    t0 = time.perf_counter()
    est, state = falkon_fit(key, X, y, cfg)
    jax.block_until_ready(est.alpha)
    seconds = time.perf_counter() - t0
    return dict(seconds=seconds, centers=est.centers, alpha=est.alpha,
                T_row0=est.precond.T[0], residuals=state.residual_norms, state=state)


CHECKED = ("centers", "alpha", "T_row0", "residuals", "sweep_row")


def probe_row(seed: int, n: int) -> int:
    """The data row whose kernel entries the sweep probe reads."""
    return int(keys.rng(seed, 3).integers(n))


def unit(n: int, i: int, dtype):
    import jax.numpy as jnp
    return jnp.zeros((n,), dtype).at[i].set(1)


def sweep_row(X, C, i: int, cfg):
    """Row i of K_nM, elementwise, from the program's own sweep at the timed
    size: the fit's RHS sweep K_nM^T (K_nM 0 + v), with its shapes and
    dtypes, called with v = e_i in place of y. Every other row meets a zero,
    so the answer is the kernel entries k(x_i, c_j) as the sweep computes
    them, products included; no sum over n averages their rounding away."""
    import jax.numpy as jnp
    zeros = jnp.zeros((C.shape[0],), X.dtype)
    return cfg.make_ops().sweep(X, C, zeros, unit(X.shape[0], i, X.dtype))


def check(c: dict, limits: dict, key, X, y, Xte, fit: dict, row: int,
          detail=None) -> list:
    """Compare one fit, and the sweep probe on data row ``row``, with the
    plain reference fit from the same key: each number that ``limits``
    names, beside its limit. ``detail`` (a dict), when given, receives both
    residual histories."""
    import jax.numpy as jnp
    from reference import falkon_ref as ref
    r = ref.fit(key, X, y, sigma=float(c["sigma"]), lam=float(c["lam"]),
                M=int(c["num_centers"]), t=int(c["iterations"]))
    mismatch = int(jnp.sum(jnp.any(fit["centers"] != r["centers"], axis=1)))
    gap = float(jnp.max(jnp.abs(fit["T_row0"] - r["T_row0"]) / jnp.abs(r["T_row0"])))
    k = ref.gaussian(X[row:row + 1], r["centers"], float(c["sigma"]), "highest")[0]
    entry = float(jnp.max(jnp.abs(fit["sweep_row"] - k) / k))
    f_prog, _ = ref.predict(Xte, fit["centers"], fit["alpha"], sigma=float(c["sigma"]))
    f_ref, _ = ref.predict(Xte, r["centers"], r["alpha"], sigma=float(c["sigma"]))
    rel = float(jnp.linalg.norm(f_prog - f_ref) / jnp.linalg.norm(f_ref))
    r_prog, r_ref = (np.asarray(v, np.float64).reshape(-1)
                     for v in (fit["residuals"], r["residuals"]))
    if detail is not None:
        detail.update(residuals_program=r_prog.tolist(), residuals_reference=r_ref.tolist())
    values = dict(centers_mismatch=mismatch, gram_row_gap=gap, sweep_entry_gap=entry,
                  rhs_gap=float(abs(r_prog[0] - r_ref[0]) / r_ref[0]), heldout_rel=rel)
    return [output.Check(name, values[name], limit) for name, limit in limits.items()]


def run(cell: cells.Cell, seed: int, seconds: float, trace: bool, t0: float,
        limits: dict) -> output.Result:
    import jax
    from repro.core import falkon_solve
    c = cell.config
    compiles = compile_cache.CompileCounter()
    X, y, Xte, _ = jax.block_until_ready(data.make_split(keys.key(seed, 0), c))
    cfg = _program_config(c)
    fit_key = lambda i: keys.key(seed, 1, i)

    warm = _fit(fit_key(0), X, y, cfg)
    if trace:
        solve = jax.jit(functools.partial(
            falkon_solve, kernel=cfg.make_kernel(), lam=cfg.lam, t=cfg.iterations,
            estimate_cond=cfg.estimate_cond, ops=cfg.make_ops()))
        st = warm["state"]
        solve = solve.lower(X, y, st.centers, st.precond).compile()
    del warm
    setup_s = time.perf_counter() - t0
    print(f"[fit_loop] set-up {setup_s:.3f}s", file=sys.stderr, flush=True)

    fits, failed, obs = [], 0, {}
    busy_s = window_s = breakdown = None
    before = compiles.snapshot()
    if not trace:
        start = time.perf_counter()
        i = 1
        while time.perf_counter() - start < seconds:
            try:
                f = _fit(fit_key(i), X, y, cfg)
            except Exception as e:       # a failed fit counts, the run goes on
                print(f"[fit_loop] fit {i} failed: {e!r}", file=sys.stderr)
                failed += 1
                i += 1
                continue
            del f["state"]
            fits.append((i, f))
            i += 1
        print("[fit_loop] fits " + " ".join(f"{f['seconds']:.4f}" for _, f in fits),
              file=sys.stderr, flush=True)
    else:
        with profile.traced() as tr:
            with jax.profiler.TraceAnnotation("bench.fit"):
                f = _fit(fit_key(1), X, y, cfg)
            st = f.pop("state")
            with jax.profiler.TraceAnnotation("bench.solve"):
                t_s = time.perf_counter()
                jax.block_until_ready(solve(X, y, st.centers, st.precond).alpha)
                solve_s = time.perf_counter() - t_s
            del st
        fits.append((1, f))
        n, d = map(int, X.shape)
        M, t = int(c["num_centers"]), int(c["iterations"])
        trace_ = tr["trace"]
        windows = {name: trace_.spans(f"bench.{name}") for name in ("fit", "solve")}
        busy_s, window_s, breakdown = profile.device_summary(
            trace_, windows["fit"] + windows["solve"])
        obs = dict(fit_s=f["seconds"], solve_s=solve_s, iterations=t,
                   fit_flops=work.fit_flops(n, M, d, t), work_sweep=work.sweep(n, M, d),
                   peaks=_peaks(), trace=trace_, windows=windows)

    compiles.report("fit_loop", before)
    attempted = len(fits) + failed
    fit_s = (sum(f["seconds"] for _, f in fits) / len(fits)) if fits else float("nan")
    checked = None
    if fits:
        i, f = fits[int(keys.rng(seed, 2).integers(len(fits)))]
        row = probe_row(seed, X.shape[0])
        f["sweep_row"] = sweep_row(X, f["centers"], row, cfg)
        checked = (i, {k: f[k] for k in CHECKED})
        fits.clear()
    peak = output.memory_peak_bytes(jax.devices())
    checks = []
    if checked:
        i, f = checked
        checks = check(c, limits, fit_key(i), X, y, Xte, f, row)
    return output.Result(
        attempted=attempted, failed=failed,
        end_to_end=dict(setup_s=setup_s, fit_s=fit_s), checks=checks,
        memory_peak_bytes=peak, observations=obs, busy_s=busy_s,
        window_s=window_s, breakdown=breakdown)


def _peaks() -> dict:
    import jax
    from harness.peaks import peaks_for
    return peaks_for(jax.devices()[0].device_kind)
