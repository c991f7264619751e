"""Quickstart: fit FALKON on a synthetic regression problem in ~30 lines.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.core import FalkonConfig, falkon_fit, krr_direct


def main():
    enable_compile_cache()
    # data: y = sin(<w, x>) + noise
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    n, d = 8_000, 10
    X = jax.random.normal(k1, (n, d))
    w = jax.random.normal(k2, (d,))
    y = jnp.sin(X @ w) + 0.1 * jax.random.normal(k3, (n,))
    Xtr, ytr, Xte, yte = X[:6000], y[:6000], X[6000:], y[6000:]

    # paper hyperparameters: lam = 1/sqrt(n), M = O(sqrt(n)), t = O(log n)
    cfg = FalkonConfig(
        kernel="gaussian",
        kernel_params=(("sigma", 3.0),),
        lam=float(1 / jnp.sqrt(len(Xtr))),
        num_centers=300,
        iterations=15,
    )
    est, state = falkon_fit(jax.random.PRNGKey(1), Xtr, ytr, cfg)

    mse = float(jnp.mean((est.predict(Xte) - yte) ** 2))
    print(f"FALKON   test MSE: {mse:.4f}   cond(W)={float(state.cond_estimate):.1f}"
          f"   CG residual={float(state.residual_norms[-1]):.2e}")

    # exact KRR reference on a subsample (O(n^3) — keep it small)
    kr = krr_direct(Xtr[:2000], ytr[:2000], cfg.make_kernel(), cfg.lam)
    mse_kr = float(jnp.mean((kr.predict(Xte) - yte) ** 2))
    print(f"exact KRR (n=2000) test MSE: {mse_kr:.4f}")


if __name__ == "__main__":
    main()
