"""Fused Pallas ``KernelOps`` backend (TPU target; interpret mode elsewhere).

* ``sweep`` — routed by the VMEM planner (``repro.ops.base.plan_sweep``):

  - ``fused``     — ONE Pallas pass per CG iteration. Each (block_m x
    block_n) Gram tile is computed once in VMEM, used for the forward
    product ``t = K u (+ v)`` and re-read from the VMEM row strip for the
    transposed accumulation ``w += K^T t`` into an fp32 scratch — half the
    kernel-tile evaluations and HBM round-trips of the two-matmul
    composition. Requires the (bm, Mpad) strip + (Mpad, p) accumulator to
    fit the VMEM budget, which caps M near ~8k at default tiles.
  - ``two_pass`` / ``j_sharded`` — the out-of-core schedule
    (``sharded_sweep_pallas``): forward pass spills ``t = K u + v`` to HBM,
    then per-C-shard transposed passes accumulate ``w_j`` with O(tile) VMEM,
    scaling M to 10^5+ at the cost of 2 Gram evaluations per tile. Falling
    off the fused path emits a structured ``SweepPlanWarning`` naming the
    chosen path and the budget numbers; ``plan()`` exposes the decision.

* ``apply`` / ``gram`` — thin wrappers over the kernel-matmul and pairwise
  Pallas kernels.

With ``precision="bf16"`` (or any custom :class:`PrecisionPolicy`) the policy
is END-TO-END over the data-space buffers: X, C and the v term are cast to
the storage dtype before entering the bandwidth-bound kernels, and the
j-sharded path's HBM-spilled ``t`` moves at storage width — the full 2x
HBM-footprint/bandwidth win (the sweep's traffic is dominated by these
n-sized objects). The distance/contraction matmuls feed the MXU
storage-dtype inputs with ``preferred_element_type=float32`` and, when the
policy says ``compensated``, every tile-loop reduction runs through
Kahan/two-sum carry buffers (see ``repro.kernels.kernel_matvec``).
Per-buffer overrides keep the M-sized coefficient vectors at the sweep
boundary (u in, w out) and the one-shot ``gram`` feeding the Cholesky in
float32 — see ``PrecisionPolicy`` for why quantizing those is not safe.
"""
from __future__ import annotations

import dataclasses
import warnings

import jax
import jax.numpy as jnp

from .base import OpsBase, SweepPlan, SweepPlanWarning, plan_sweep, register_ops
from .gemm import GemmCacheMixin

Array = jax.Array


@register_ops("pallas")
@dataclasses.dataclass(frozen=True)
class PallasKernelOps(GemmCacheMixin, OpsBase):
    """KernelOps over the fused Pallas kernels, keyed by the kernel's spec.

    The K_nM-cache primitives (materialize / gemm_sweep / gemm_apply) come
    from the shared ``GemmCacheMixin``: after materialization (one
    ``pairwise_kernel_pallas`` evaluation per row tile) there is no kernel
    math left, only GEMMs, and XLA's native matmuls are the right tool —
    a fused Pallas GEMM would re-solve a solved problem.
    """

    @property
    def _spec(self):
        from repro.core.kernels import spec_of
        return spec_of(self.kernel)

    @property
    def _block_m(self) -> int:
        return min(self.block_size, 256)

    def _inputs(self, X: Array, C: Array) -> tuple[Array, Array]:
        # storage == float32 means "full precision": leave inputs untouched
        # (x64 callers keep their float64), exactly the pre-policy behavior.
        if self.policy.storage == "float32":
            return X, C
        st = jnp.dtype(self.policy.storage)
        return X.astype(st), C.astype(st)

    def _vectors(self, u: Array, v: Array | None) -> tuple[Array, Array | None]:
        """u at the policy's coefficient dtype (float32 by override — see
        PrecisionPolicy: quantized coefficients destabilize preconditioned
        CG), v at data-space storage width (n-sized, the HBM win)."""
        pol = self.policy
        if pol.storage != "float32" and v is not None:
            v = v.astype(jnp.dtype(pol.storage))
        co_name = pol.buffer_dtype("coeffs")
        co = jnp.dtype(co_name)
        if u.dtype != co and (
            co_name != "float32" or jnp.dtype(u.dtype).itemsize < co.itemsize
        ):
            # the override WIDENS any reduced-storage u (bf16/fp16/fp8 CG
            # iterates crossing back into the sweep) — never narrows an
            # fp64 u under the default float32 coeffs (x64 callers)
            u = u.astype(co)
        return u, v

    def plan(self, n: int, M: int, d: int, p: int = 1, systems: int = 1) -> SweepPlan:
        """The routing decision ``sweep`` will take for these shapes.

        The same VMEM budget model applies in interpret mode: Python
        emulation has no hard VMEM ceiling, but letting the fused kernel
        allocate a (bm, Mpad) strip at M ~ 10^5 is exactly the
        out-of-memory blowup the j-sharded path exists to avoid, and CPU
        tests should exercise the routing real TPUs will use. ``systems``
        charges the lam-path stacking (effective width ``p * systems``) so
        a fat path routes off the fused path exactly like a fat multi-rhs.
        """
        from repro.kernels.kernel_matvec import sweep_block_dims
        bm, bn = sweep_block_dims(n, M, self._block_m, 512)
        return plan_sweep(n, M, d, p, systems=systems, bm=bm, bn=bn, policy=self.policy)

    def sweep(
        self,
        X: Array,
        C: Array,
        u: Array,
        v: Array | None = None,
        row_mask: Array | None = None,
    ) -> Array:
        """``row_mask`` (n,), 0/1: masked rows contribute EXACTLY zero (the
        fused kernel zeroes their t_i in VMEM; the sharded path zeroes the
        spilled t rows) — fixed-shape padded chunks sweep correctly."""
        from repro.kernels.kernel_matvec import (
            fused_sweep_pallas, interpret_mode, sharded_sweep_pallas
        )
        pol = self.policy
        X, C = self._inputs(X, C)
        u, v = self._vectors(u, v)
        p = u.shape[1] if u.ndim > 1 else 1
        plan = self.plan(X.shape[0], C.shape[0], X.shape[1], p)
        if plan.path == "fused":
            return fused_sweep_pallas(
                X,
                C,
                u,
                v,
                spec=self._spec,
                row_mask=row_mask,
                block_m=self._block_m,
                compensated=pol.compensated,
                interpret=interpret_mode(),
            )
        warnings.warn(SweepPlanWarning(plan), stacklevel=2)
        # reduced-storage policies pin the HBM t spill to storage width and
        # the final M-sized w to the coefficient dtype; the fp32 policy
        # keeps the legacy promotion (None) so x64 callers stay fp64
        t_dt = out_dt = None
        if pol.storage != "float32":
            t_dt = jnp.dtype(pol.storage)
            out_dt = jnp.dtype(pol.buffer_dtype("coeffs"))
        return sharded_sweep_pallas(
            X,
            C,
            u,
            v,
            spec=self._spec,
            row_mask=row_mask,
            shard_m=plan.shard_m if plan.shard_m is not None else plan.M,
            block_m=self._block_m,
            compensated=pol.compensated,
            t_dtype=t_dt,
            out_dtype=out_dt,
            interpret=interpret_mode(),
        )

    def sweep_with_stats(
        self, X: Array, C: Array, u: Array, v: Array | None = None
    ) -> tuple[Array, Array]:
        """sweep() plus the kernel's Gram-tile evaluation counter (int32).

        The counter is the fusion proof: it equals
        ceil(n/block_m) * ceil(M/block_n) — one evaluation per tile per call.
        Diagnostic path: it is always the fused kernel, so shapes the planner
        would route to an out-of-core path are rejected here rather than
        silently measuring a different implementation.
        """
        from repro.kernels.kernel_matvec import fused_sweep_pallas, interpret_mode
        pol = self.policy
        X, C = self._inputs(X, C)
        u, v = self._vectors(u, v)
        p = u.shape[1] if u.ndim > 1 else 1
        plan = self.plan(X.shape[0], C.shape[0], X.shape[1], p)
        if plan.path != "fused":
            raise ValueError(
                f"fused sweep scratch for n={X.shape[0]}, M={C.shape[0]}, "
                f"d={X.shape[1]}, p={p} exceeds the VMEM budget on this "
                f"backend ({plan.reason}); sweep() would take the "
                f"{plan.path!r} path, which has no tile counter")
        return fused_sweep_pallas(
            X,
            C,
            u,
            v,
            spec=self._spec,
            block_m=self._block_m,
            compensated=pol.compensated,
            interpret=interpret_mode(),
            return_tile_count=True,
        )

    def apply(self, X: Array, C: Array, u: Array) -> Array:
        from repro.kernels.kernel_matvec import interpret_mode, kernel_matmul_pallas
        pol = self.policy
        X, C = self._inputs(X, C)
        u, _ = self._vectors(u, None)
        squeeze = u.ndim == 1
        u2 = u[:, None] if squeeze else u
        out = kernel_matmul_pallas(
            X,
            C,
            u2,
            spec=self._spec,
            block_m=self._block_m,
            compensated=pol.compensated,
            interpret=interpret_mode(),
        )
        return out[:, 0] if squeeze else out

    def gram(self, A: Array, B: Array) -> Array:
        # Per-buffer override (default float32 regardless of the bf16
        # policy): gram feeds the preconditioner's Cholesky (one-shot O(M^2)
        # work with no bandwidth win to harvest), and bf16 quantization can
        # push a borderline-PSD K_MM indefinite.
        from repro.kernels.kernel_matvec import interpret_mode, pairwise_kernel_pallas
        gt = jnp.dtype(self.policy.buffer_dtype("gram"))
        if jnp.dtype(A.dtype).itemsize < gt.itemsize:   # never downcast fp64
            A = A.astype(gt)
        if jnp.dtype(B.dtype).itemsize < gt.itemsize:
            B = B.astype(gt)
        return pairwise_kernel_pallas(A, B, spec=self._spec, interpret=interpret_mode())
