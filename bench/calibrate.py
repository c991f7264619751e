#!/usr/bin/env python3
"""Readings that the correctness limits are set from, at a cell's own size.

    python bench/calibrate.py --workload susy_fit --seeds 1,2,3 \
        --variants program,control,unchanged,half,altered

For each seed and variant it prints one JSON line with the numbers the
cell compares (``harness.output.Check`` values; the limits in
``bench/limits/<workload>.json`` are ignored here). Variants:

* ``program``: the timed path as it is (the lower readings);
* ``control``: the plain reference put in the program's place, computed
  one precision step below the configuration's (``high``, three bf16
  passes, for fp32 at ``highest``) (the upper readings);
* ``sweep_high``: the program with its data sweep alone replaced by the
  reference's sweep at ``high``, the precision cut a change to the sweep
  layer would most likely make;
* faults planted in the program: ``unchanged`` (each CG step returns its
  state unchanged), ``half`` (every data sweep over half the rows, the
  mean taken over them), ``altered`` (the fit's first coefficient negated,
  an answer altered where it is produced).

Not run by the benchmark's own runs. Exits 2 without a TPU unless
``--anywhere``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness import cell as cells, compile_cache, data, keys  # noqa: E402

NO_LIMITS = {"centers_mismatch": math.inf, "gram_row_gap": math.inf,
             "sweep_entry_gap": math.inf, "rhs_gap": math.inf, "heldout_rel": math.inf}


@contextlib.contextmanager
def planted(variant: str, config: dict):
    """Plant one fault, or the sweep at ``high``, in the program for the
    body (``program`` and ``control`` plant nothing)."""
    import jax.numpy as jnp
    from repro.core import cg as cg_mod
    from repro.core.preconditioner import Preconditioner
    from repro.ops.jnp_backend import JnpKernelOps
    from reference import falkon_ref as ref
    saved = []

    def patch(owner, name, new):
        saved.append((owner, name, getattr(owner, name)))
        setattr(owner, name, new)

    if variant == "unchanged":
        def same(x, r, p, rs, Ap, tol_sq, storage=None):
            return x, r, p, rs, jnp.ones_like(rs, bool)
        patch(cg_mod, "_masked_cg_update", same)
    elif variant == "half":
        sweep = JnpKernelOps.sweep

        def half_sweep(self, X, C, u, v=None, row_mask=None):
            h = X.shape[0] // 2
            return 2.0 * sweep(self, X[:h], C, u, None if v is None else v[:h])
        patch(JnpKernelOps, "sweep", half_sweep)
    elif variant == "altered":
        coeffs = Preconditioner.coeffs

        def altered_coeffs(self, beta):
            a = coeffs(self, beta)
            return a.at[0].set(-a[0])
        patch(Preconditioner, "coeffs", altered_coeffs)
    elif variant == "sweep_high":
        def high_sweep(self, X, C, u, v=None, row_mask=None):
            return ref.knm_t(X, C, float(config["sigma"]), "high", u=u, v=v)
        patch(JnpKernelOps, "sweep", high_sweep)
    try:
        yield
    finally:
        for owner, name, old in reversed(saved):
            setattr(owner, name, old)


def fit_readings(cell, seed: int, variant: str) -> dict:
    """The fit cell's compared numbers for one fit of ``seed``."""
    import jax
    from reference import falkon_ref as ref
    fit_loop = cells.driver(cell)
    c = cell.config
    X, y, Xte, _ = jax.block_until_ready(data.make_split(keys.key(seed, 0), c))
    key = keys.key(seed, 1, 1)
    row = fit_loop.probe_row(seed, X.shape[0])
    if variant == "control":
        r = ref.fit(key, X, y, sigma=float(c["sigma"]), lam=float(c["lam"]),
                    M=int(c["num_centers"]), t=int(c["iterations"]), precision="high")
        r["sweep_row"] = ref.knm_t(X, r["centers"], float(c["sigma"]), "high",
                                   v=fit_loop.unit(X.shape[0], row, X.dtype))
        f = {k: r[k] for k in fit_loop.CHECKED}
    else:
        cfg = fit_loop._program_config(c)
        with planted(variant, c):
            f = fit_loop._fit(key, X, y, cfg)
            f["sweep_row"] = fit_loop.sweep_row(X, f["centers"], row, cfg)
        f.pop("state")
    detail = {}
    checks = fit_loop.check(c, NO_LIMITS, key, X, y, Xte, f, row, detail)
    return {ch.name: ch.value for ch in checks} | detail


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--variants", default="program,control")
    ap.add_argument("--anywhere", action="store_true",
                    help="run without a TPU (rehearsal at the configured size)")
    args = ap.parse_args(argv)
    cell = cells.load_cell(args.workload)
    import jax
    if not args.anywhere and jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU found; nothing was run", file=sys.stderr)
        return 2
    compile_cache.enable()
    cells.program_path()
    for seed in (int(s) for s in args.seeds.split(",")):
        for variant in args.variants.split(","):
            t0 = time.perf_counter()
            try:
                r = fit_readings(cell, seed, variant)
            except Exception as e:   # a control or fault that crashes has failed
                r = {"error": repr(e)[:300]}
            r.update(workload=cell.name, seed=seed, variant=variant,
                     seconds=time.perf_counter() - t0)
            print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
