"""Plain FALKON (Rudi, Carratino, Rosasco, NeurIPS 2017, Alg. 1) in
``jax.numpy`` float32, and a plain kernel predict.

It imports nothing from the program under test and takes nothing it made:
the centers come from the same selection key by the same rule (M rows drawn
uniformly without replacement), every kernel entry, factor and CG step is
computed here. ``precision`` is the matmul precision of every contraction:
``highest`` is the reference (full fp32 on a TPU, whose default runs one
bf16 pass); ``high`` (three bf16 passes) is the control one step below it.

    T = chol(K_MM + eps M I),  A = chol(T T^T / M + lam I)   (upper)
    W u = A^-T T^-T K_nM^T K_nM T^-1 A^-1 u / n + lam A^-T A^-1 u
    b = A^-T T^-T K_nM^T y / n,  beta = CG_t(W, b),  alpha = T^-1 A^-1 beta

The Cholesky and triangular solves run over 512-wide panels so that they
compile in seconds at M = 2e4 (XLA's TPU expanders unroll over the matrix).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

PANEL = 512
ROWS = 2048


def _bf16_parts(a):
    hi = a.astype(jnp.bfloat16)
    return hi, (a - hi.astype(a.dtype)).astype(jnp.bfloat16)


def mm(a, b, precision):
    """a @ b at ``precision``. On a TPU that is the chip's own pass count;
    elsewhere ``high`` is emulated as the TPU computes it (three bf16
    products, hi*hi + hi*lo + lo*hi, accumulated in fp32), since the CPU
    ignores the setting and would run it at full fp32."""
    if precision != "high" or jax.default_backend() == "tpu":
        return jnp.matmul(a, b, precision=precision)
    (ah, al), (bh, bl) = _bf16_parts(a), _bf16_parts(b)
    f = lambda x, y: jnp.matmul(x, y, preferred_element_type=jnp.float32)
    return f(ah, bh) + (f(ah, bl) + f(al, bh))


def gaussian(A, B, sigma: float, precision):
    a2 = jnp.sum(A * A, axis=1)[:, None]
    b2 = jnp.sum(B * B, axis=1)[None, :]
    d2 = jnp.maximum(a2 + b2 - 2.0 * mm(A, B.T, precision), 0.0)
    return jnp.exp(d2 * (-0.5 / (sigma * sigma)))


def _row_blocks(X, rows: int = ROWS):
    n = X.shape[0]
    nb = -(-n // rows)
    Xb = jnp.pad(X, ((0, nb * rows - n), (0, 0))).reshape(nb, rows, X.shape[1])
    valid = (jnp.arange(nb * rows) < n).reshape(nb, rows)
    return Xb, valid, nb


def knm_t(X, C, sigma, precision, u=None, v=None):
    """K_nM^T (K_nM u + v); u (M,) or None, v (n,) or None."""
    Xb, valid, nb = _row_blocks(X)
    vb = None if v is None else jnp.pad(v, (0, nb * ROWS - X.shape[0])).reshape(nb, ROWS)

    def body(acc, i):
        K = gaussian(Xb[i], C, sigma, precision) * valid[i][:, None]
        t = jnp.zeros((ROWS,), X.dtype)
        if u is not None:
            t = t + mm(K, u, precision)
        if vb is not None:
            t = t + vb[i]
        return acc + mm(t, K, precision), None

    out, _ = jax.lax.scan(body, jnp.zeros((C.shape[0],), X.dtype), jnp.arange(nb))
    return out


@functools.partial(jax.jit, static_argnames=("sigma", "precision"))
def predict(Xq, C, alpha, *, sigma: float, precision="highest"):
    """(K(Xq, C) alpha, K(Xq, C) |alpha|) by row blocks."""
    Xb, _, nb = _row_blocks(Xq)
    coef = jnp.stack([alpha, jnp.abs(alpha)], axis=1)

    def body(xb):
        return mm(gaussian(xb, C, sigma, precision), coef, precision)

    out = jax.lax.map(body, Xb).reshape(nb * ROWS, 2)[: Xq.shape[0]]
    return out[:, 0], out[:, 1]


def _padded(M: int) -> int:
    b = min(PANEL, M)
    return -(-M // b) * b


def _pad_eye(K, Mp: int):
    """K (M, M) as (Mp, Mp) with an identity tail block."""
    M = K.shape[0]
    Kp = jnp.pad(K, ((0, Mp - M), (0, Mp - M)))
    return Kp + jnp.diag((jnp.arange(Mp) >= M).astype(K.dtype))


def chol_upper(Kp, precision):
    """Upper U with Kp = U^T U: left-looking Cholesky over PANEL columns of
    a padded (Mp, Mp) matrix (its identity tail stays identity)."""
    Mp = Kp.shape[0]
    b = min(PANEL, Mp)
    rows = jnp.arange(Mp)[:, None]

    def step(k, L):
        r0 = k * b
        Lk = jax.lax.dynamic_slice(L, (r0, 0), (b, Mp))        # done columns only
        S = jax.lax.dynamic_slice(Kp, (0, r0), (Mp, b)) - mm(L, Lk.T, precision)
        Lkk = jnp.linalg.cholesky(jax.lax.dynamic_slice(S, (r0, 0), (b, b)))
        P = solve_triangular(Lkk, S.T, lower=True).T
        P = jnp.where(rows >= r0 + b, P, 0.0)
        P = jax.lax.dynamic_update_slice(P, Lkk, (r0, 0))
        return jax.lax.dynamic_update_slice(L, P, (0, r0))

    return jax.lax.fori_loop(0, Mp // b, step, jnp.zeros_like(Kp)).T


def tri(Up, v, trans: bool, precision):
    """Up^-1 v, or Up^-T v with ``trans``, for a padded upper (Mp, Mp) Up
    and v (Mp,), by block substitution over PANEL rows."""
    Mp = Up.shape[0]
    b = min(PANEL, Mp)
    nb = Mp // b

    def step(i, x):
        r0 = (i if trans else nb - 1 - i) * b
        if trans:
            R = jax.lax.dynamic_slice(Up, (0, r0), (Mp, b)).T     # rows of U^T
        else:
            R = jax.lax.dynamic_slice(Up, (r0, 0), (b, Mp))
        rhs = jax.lax.dynamic_slice(v, (r0,), (b,)) - mm(R, x, precision)
        Ukk = jax.lax.dynamic_slice(Up, (r0, r0), (b, b))
        xk = solve_triangular(Ukk, rhs, lower=False, trans=1 if trans else 0)
        return jax.lax.dynamic_update_slice(x, xk, (r0,))

    return jax.lax.fori_loop(0, nb, step, jnp.zeros_like(v))


def centers(key, X, M: int):
    """M rows of X drawn uniformly without replacement by ``key``."""
    return X[jax.random.choice(key, X.shape[0], shape=(M,), replace=False)]


@functools.partial(jax.jit, static_argnames=("precision",))
def _factors(C, lam, sigma, *, precision):
    """Padded upper factors T and A (Alg. 1: eps = float32 eps times M)."""
    M = C.shape[0]
    Mp = _padded(M)
    eps = jnp.finfo(jnp.float32).eps * M
    KMM = gaussian(C, C, sigma, precision) + eps * jnp.eye(M, dtype=C.dtype)
    T = chol_upper(_pad_eye(KMM, Mp), precision)
    TT = mm(T, T.T, precision)[:M, :M]
    A = chol_upper(_pad_eye(TT / M + lam * jnp.eye(M, dtype=TT.dtype), Mp), precision)
    return T, A


@functools.partial(jax.jit, static_argnames=("t", "precision"))
def _solve(X, y, C, T, A, lam, sigma, *, t: int, precision):
    n, M = X.shape[0], C.shape[0]
    pad = lambda w: jnp.pad(w, (0, T.shape[0] - M))
    right = lambda u: tri(T, tri(A, u, False, precision), False, precision)
    left = lambda w: tri(A, tri(T, pad(w), True, precision), True, precision)

    def W(u):
        w = knm_t(X, C, sigma, precision, u=right(u)[:M]) / n
        return left(w) + lam * tri(A, tri(A, u, False, precision), True, precision)

    b = left(knm_t(X, C, sigma, precision, v=y) / n)

    def cg(i, s):
        x, r, p, rs, hist = s
        Ap = W(p)
        a = rs / jnp.vdot(p, Ap)
        x, r = x + a * p, r - a * Ap
        rs_new = jnp.vdot(r, r)
        return x, r, r + rs_new / rs * p, rs_new, hist.at[i + 1].set(jnp.sqrt(rs_new))

    rs0 = jnp.vdot(b, b)
    hist = jnp.zeros((t + 1,), b.dtype).at[0].set(jnp.sqrt(rs0))
    x, _, _, _, hist = jax.lax.fori_loop(0, t, cg, (jnp.zeros_like(b), b, b, rs0, hist))
    return right(x)[:M], hist


def fit(key, X, y, *, sigma: float, lam: float, M: int, t: int,
        precision="highest") -> dict:
    """A t-iteration FALKON fit: ``centers`` (M, d), ``alpha`` (M,),
    ``T_row0`` (the first row of T, i.e. K_MM's first row over
    sqrt(K_11 + eps M)) and ``residuals`` (||r_k||, k = 0..t)."""
    C = centers(key, X, M)
    T, A = _factors(C, jnp.float32(lam), jnp.float32(sigma), precision=precision)
    alpha, hist = _solve(X, y, C, T, A, jnp.float32(lam), jnp.float32(sigma),
                         t=t, precision=precision)
    return dict(centers=C, alpha=alpha, T_row0=T[0, :M], residuals=hist)
