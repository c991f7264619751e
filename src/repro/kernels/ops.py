"""jit'd wrappers around the Pallas kernels, with interpret-mode fallback.

This module is the thin waist between the ``repro.ops`` backend layer (see
``repro/ops/pallas_backend.py``) and the raw ``pl.pallas_call`` kernels in
``kernel_matvec.py``. Kernels are identified by their declarative
``KernelSpec`` (``repro.core.kernels.spec_of``) — there is no class-name
sniffing and no per-backend list of supported kernels: anything registered in
``core/kernels.py`` runs here.

``fused_knm_matvec`` is the single-pass FALKON sweep
``w = K_nM^T (K_nM u + v)``: each Gram tile is computed once in VMEM and used
for both the forward product and the transposed accumulation
(``fused_sweep_pallas``). ``two_pass_knm_matvec`` keeps the legacy
two-kernel-matmul composition (every Gram tile evaluated twice) for A/B
benchmarking — see ``benchmarks/sweep_fusion.py``. On non-TPU backends the
kernels run in interpret mode (Python emulation — correctness only); on TPU
they compile to Mosaic.
"""
from __future__ import annotations

import jax

from repro.core.kernels import spec_of
from .kernel_matvec import (
    fused_sweep_pallas,
    interpret_mode,
    kernel_matmul_pallas,
    pairwise_kernel_pallas,
    sharded_sweep_pallas,
)

Array = jax.Array


def fused_knm_matvec(
    X: Array,
    C: Array,
    u: Array,
    v: Array | None,
    kernel,
    *,
    block_size: int = 2048,
) -> Array:
    """w = K(X,C)^T (K(X,C) u + v), single pass, Gram tiles VMEM-resident
    only and evaluated exactly once each."""
    return fused_sweep_pallas(
        X,
        C,
        u,
        v,
        spec=spec_of(kernel),
        block_m=min(block_size, 256),
        interpret=interpret_mode(),
    )


def sharded_knm_matvec(
    X: Array,
    C: Array,
    u: Array,
    v: Array | None,
    kernel,
    *,
    shard_m: int = 8192,
    block_size: int = 2048,
) -> Array:
    """Out-of-core sweep for M past the fused kernel's VMEM reach: forward
    product spilled to HBM, then per-C-shard transposed passes (2 Gram
    evaluations per tile, O(tile) VMEM — see ``sharded_sweep_pallas``)."""
    return sharded_sweep_pallas(
        X,
        C,
        u,
        v,
        spec=spec_of(kernel),
        shard_m=shard_m,
        block_m=min(block_size, 256),
        interpret=interpret_mode(),
    )


def two_pass_knm_matvec(
    X: Array,
    C: Array,
    u: Array,
    v: Array | None,
    kernel,
    *,
    block_size: int = 2048,
) -> Array:
    """Legacy sweep as two kernel matmuls (K(X,C) @ u then K(C,X) @ t, using
    K^T(X,C) = K(C,X)). Evaluates every Gram tile twice — kept only as the
    baseline the fused kernel is benchmarked against."""
    spec = spec_of(kernel)
    squeeze = u.ndim == 1
    u2 = u[:, None] if squeeze else u
    t = kernel_matmul_pallas(
        X, C, u2, spec=spec, block_m=min(block_size, 256), interpret=interpret_mode()
    )
    if v is not None:
        t = t + (v[:, None] if squeeze else v)
    w = kernel_matmul_pallas(
        C, X, t, spec=spec, block_m=min(block_size, 256), interpret=interpret_mode()
    )
    return w[:, 0] if squeeze else w


def kernel_matmul(
    A: Array, B: Array, V: Array, kernel, *, block_m: int = 256, block_n: int = 512
) -> Array:
    """out = K(A, B) @ V (the prediction path's primitive)."""
    squeeze = V.ndim == 1
    V2 = V[:, None] if squeeze else V
    out = kernel_matmul_pallas(
        A,
        B,
        V2,
        spec=spec_of(kernel),
        block_m=block_m,
        block_n=block_n,
        interpret=interpret_mode(),
    )
    return out[:, 0] if squeeze else out


def pairwise_kernel(
    A: Array, B: Array, kernel, *, block_m: int = 256, block_n: int = 256
) -> Array:
    """K(A, B) materialized (preconditioner's K_MM builder)."""
    return pairwise_kernel_pallas(
        A,
        B,
        spec=spec_of(kernel),
        block_m=block_m,
        block_n=block_n,
        interpret=interpret_mode(),
    )
