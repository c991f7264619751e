"""The work a FALKON fit requires, counted from its shapes.

These are the algorithm's counts (paper Sect. 3, "Computations"), not what an
implementation happens to execute: padding, the cond(W) diagnostic's sweeps
and recomputation are not counted, so a change that removes them moves the
time and not the yardstick.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def least_seconds(self, peaks: dict) -> tuple[float, str]:
        """(least time on the chip, the bound that sets it)."""
        compute = self.flops / peaks["flops_per_s"]
        memory = self.bytes / peaks["hbm_bytes_per_s"]
        return (compute, "mxu") if compute >= memory else (memory, "hbm")


def sweep(n: int, M: int, d: int, p: int = 1, itemsize: int = 4) -> Work:
    """One data sweep K_nM^T (K_nM u + v).

    FLOPs: the distance products 2 n M d, then K u and K^T t, 2 n M p each.
    Bytes: X and C read once, u (M, p) and v (n, p) read, w (M, p) written;
    the kernel entries are recomputed on chip and never need to leave it.
    """
    flops = 2.0 * n * M * d + 4.0 * n * M * p
    byts = itemsize * (n * d + M * d + n * p + 2.0 * M * p)
    return Work(flops, byts)


def fit_flops(n: int, M: int, d: int, t: int, p: int = 1) -> float:
    """A whole fit: t + 1 sweeps (RHS and t CG matvecs), the M x M Gram,
    chol(K_MM) and chol(T T^T / M + lam I) at M^3 / 3 each and T T^T at
    2 M^3 / 3 (paper Sect. 3: 4/3 M^3 in all), and the triangular solves:
    four per CG iteration for B^T (.) B, two for the ridge, two for the RHS
    and two for alpha, M^2 p each."""
    gram = 2.0 * M * M * d
    factor = 4.0 * M ** 3 / 3.0
    solves = (6 * t + 4) * float(M) * M * p
    return (t + 1) * sweep(n, M, d, p).flops + gram + factor + solves
