"""The plain reference agrees with the program at a tiny size on the CPU."""
import jax.numpy as jnp
import numpy as np

from harness import data, keys
from reference import falkon_ref as ref

CFG = dict(n_train=4000, n_test=800, d=18, sigma=4.0, task="binary", noise=0.1)
M, T, LAM, SIGMA = 300, 12, 1e-6, 4.0


def _program_fit(key, X, y):
    from repro.core import FalkonConfig, falkon_fit
    cfg = FalkonConfig(kernel="gaussian", kernel_params=(("sigma", SIGMA),), lam=LAM,
                       num_centers=M, iterations=T)
    return falkon_fit(key, X, y, cfg)


def test_reference_fit_agrees_with_falkon_fit():
    X, y, Xte, _ = data.make_split(keys.key(3, 0), CFG)
    key = keys.key(3, 1, 1)
    est, state = _program_fit(key, X, y)
    r = ref.fit(key, X, y, sigma=SIGMA, lam=LAM, M=M, t=T)
    assert bool(jnp.all(r["centers"] == est.centers))
    # one Gram row over a scalar: fp32 rounding, no amplification
    gap = jnp.max(jnp.abs(est.precond.T[0] - r["T_row0"]) / jnp.abs(r["T_row0"]))
    assert float(gap) < 1e-6
    # the same CG: the first residuals agree to a few digits; later ones
    # drift apart as lam = 1e-6 amplifies each side's rounding
    np.testing.assert_allclose(np.asarray(state.residual_norms)[:6],
                               np.asarray(r["residuals"])[:6], rtol=1e-3)
    f_prog, _ = ref.predict(Xte, est.centers, est.alpha, sigma=SIGMA)
    f_ref, _ = ref.predict(Xte, r["centers"], r["alpha"], sigma=SIGMA)
    # lam = 1e-6 amplifies fp32 rounding; at this size two fp32 fits land
    # ~1e-3 apart (measured 3.4e-3 at n = 2e4); a wrong fit lands near 1
    assert float(jnp.linalg.norm(f_prog - f_ref) / jnp.linalg.norm(f_ref)) < 0.05


def test_reference_predict_agrees_with_est_predict():
    X, y, Xte, _ = data.make_split(keys.key(4, 0), CFG)
    est, _ = _program_fit(keys.key(4, 1, 1), X, y)
    f, mag = ref.predict(Xte, est.centers, est.alpha, sigma=SIGMA)
    got = np.asarray(est.predict(Xte), np.float64)
    # two fp32 M-term dot products: within 2 gamma_M of K|alpha| per row
    gamma = 2 * (M + 2 * 18) * 2.0 ** -24
    assert np.all(np.abs(got - np.asarray(f)) <= gamma * np.asarray(mag))


def test_high_is_emulated_off_the_chip():
    a = jnp.asarray(np.random.default_rng(0).standard_normal((64, 64)), jnp.float32)
    exact = np.asarray(a, np.float64) @ np.asarray(a, np.float64)
    err = lambda p: np.max(np.abs(np.asarray(ref.mm(a, a, p), np.float64) - exact))
    assert err("highest") < 1e-4 < err("high") < 1e-2
