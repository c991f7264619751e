"""Reference ``KernelOps`` backend: pure jnp, blocked, runs anywhere.

The sweep is the paper's Alg. 1 ``KnM_times_vector``: a ``lax.scan`` over row
blocks of X, each step materializing one (block, M) Gram strip, using it for
both the forward product and the transposed accumulation, then discarding it —
O(M * block) memory, never the full K_nM. This is the numerical ground truth
the Pallas backend is tested against (same math via the shared
``tile_transform`` registry), and the fp64-capable path for the theory tests.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .base import OpsBase, SweepPlan, register_ops
from .gemm import FP32, GemmCacheMixin, quantize_coeffs, quantize_storage

Array = jax.Array


def _pad_blocks(
    X: Array, v: Array | None, block_size: int, row_mask: Array | None = None
):
    """Pad rows of X (and v) to a multiple of block_size; return mask.

    ``row_mask`` (n,), 0/1 — a caller-supplied validity mask folded into the
    block-padding mask, so masked rows drop out of the sweep exactly like
    the block padding does (their Gram rows are zeroed)."""
    n = X.shape[0]
    nb = -(-n // block_size)
    pad = nb * block_size - n
    Xp = jnp.pad(X, ((0, pad), (0, 0)))
    valid = (jnp.ones((n,), X.dtype) if row_mask is None else row_mask.astype(X.dtype))
    mask = jnp.pad(valid, (0, pad))
    vp = None
    if v is not None:
        widths = ((0, pad),) + ((0, 0),) * (v.ndim - 1)
        vp = jnp.pad(v, widths)
    return Xp.reshape(nb, block_size, X.shape[1]), mask.reshape(nb, block_size), vp, nb


@register_ops("jnp")
@dataclasses.dataclass(frozen=True)
class JnpKernelOps(GemmCacheMixin, OpsBase):
    """Blocked lax.scan reference implementation of the three primitives
    (plus the shared materialize/gemm cache primitives — see
    ``repro.ops.gemm``, whose blocked GEMM arithmetic mirrors this sweep's
    scan exactly, the cached == recompute bit-identity contract)."""

    def _quant(self, a: Array | None) -> Array | None:
        """Storage-dtype quantization, fp32 compute — mirrors the Pallas
        backend's storage-in/fp32-accumulate policy bit-for-policy (not
        bit-for-bit: MXU bf16 matmuls round differently). float32 storage
        means full precision: pass through untouched (x64 callers keep
        their float64). Shared with the GEMM cache path (one definition of
        "quantize" keeps the parity contract honest)."""
        return quantize_storage(self.policy, a)

    def _quant_coeffs(self, u: Array) -> Array:
        """u at the coefficient dtype — see ``gemm.quantize_coeffs``."""
        return quantize_coeffs(self.policy, u)

    def _inputs(self, X: Array, C: Array) -> tuple[Array, Array]:
        return self._quant(X), self._quant(C)

    def sweep(
        self,
        X: Array,
        C: Array,
        u: Array,
        v: Array | None = None,
        row_mask: Array | None = None,
    ) -> Array:
        """K_nM^T (K_nM u + v) with blocked O(M * block) memory.

        ``u``: (M,) or (M, p); ``v``: (n,) or (n, p) or None (treated as 0).
        ``row_mask`` (n,), 0/1: rows with mask 0 contribute EXACTLY zero —
        the contract that lets streamed tail chunks be padded to a fixed
        shape (one XLA compile per fit) without changing the result.
        Under a non-fp32 policy the data-space v is quantized through the
        storage dtype, u through the policy's coefficient dtype (float32 by
        override — quantized coefficients destabilize preconditioned CG),
        and the block reduction is Kahan-compensated when the policy says
        so — mirroring the Pallas backend's end-to-end contract, w included
        (returned at the coefficient dtype).
        """
        pol = self.policy
        X, C = self._inputs(X, C)
        u, v = self._quant_coeffs(u), self._quant(v)
        block_size = self.block_size
        kernel = self.kernel
        Xb, mask, vp, nb = _pad_blocks(X, v, block_size, row_mask)
        out_shape = (C.shape[0],) + u.shape[1:]
        if vp is not None:
            vb = vp.reshape((nb, block_size) + v.shape[1:])

        def delta(inp):
            if v is None:
                xb, mb = inp
                Kb = kernel(xb, C) * mb[:, None]          # mask padded rows
                t = jnp.matmul(Kb, u, precision=FP32)
            else:
                xb, mb, vblk = inp
                Kb = kernel(xb, C) * mb[:, None]
                # Kb's zeroed rows already null padded contributions in
                # Kb.T @ t; masking v too keeps t finite for arbitrary pads.
                t = jnp.matmul(Kb, u, precision=FP32) + vblk * (
                    mb[:, None] if vblk.ndim > 1 else mb)
            return jnp.matmul(Kb.T, t, precision=FP32)

        xs = (Xb, mask) if v is None else (Xb, mask, vb)
        if pol.compensated:
            # Kahan/two-sum across row blocks — literally the same _two_sum
            # the Pallas tile loops run (lazy import: kernels -> core is the
            # allowed direction, ops must not import kernels at module load)
            from repro.kernels.kernel_matvec import _two_sum

            def body(carry, inp):
                acc, comp = carry
                return _two_sum(acc, comp, delta(inp)), None

            init = (jnp.zeros(out_shape, X.dtype), jnp.zeros(out_shape, X.dtype))
            (w, _), _ = jax.lax.scan(body, init, xs)
        else:
            def body(carry, inp):
                return carry + delta(inp), None

            w, _ = jax.lax.scan(body, jnp.zeros(out_shape, X.dtype), xs)
        co = pol.buffer_dtype("coeffs")
        return w.astype(jnp.dtype(co)) if co != "float32" else w

    def apply(self, X: Array, C: Array, u: Array) -> Array:
        """K_nM u (prediction path), blocked over rows of X."""
        X, C = self._inputs(X, C)
        u = self._quant_coeffs(u)
        n = X.shape[0]
        Xb, mask, _, nb = _pad_blocks(X, None, self.block_size)
        kernel = self.kernel

        def body(xb):
            return jnp.matmul(kernel(xb, C), u, precision=FP32)

        out = jax.lax.map(body, Xb)
        out = out.reshape((nb * Xb.shape[1],) + u.shape[1:])
        return out[:n]

    def gram(self, A: Array, B: Array) -> Array:
        """K(A, B) dense (M x M for the preconditioner — paper's memory
        budget, no blocking needed). Full precision by per-buffer override
        (policy ``gram`` buffer, float32 by default): the Cholesky
        downstream is the numerically fragile step, and the bf16 policy's
        bandwidth win does not apply to this one-shot block."""
        gt = jnp.dtype(self.policy.buffer_dtype("gram"))
        if jnp.dtype(A.dtype).itemsize < gt.itemsize:   # never downcast fp64
            A = A.astype(gt)
        if jnp.dtype(B.dtype).itemsize < gt.itemsize:
            B = B.astype(gt)
        return self.kernel(A, B)

    def plan(self, n: int, M: int, d: int, p: int = 1, systems: int = 1) -> SweepPlan:
        """Reference backend has one path: the lax.scan row sweep. Reported
        through the same ``SweepPlan`` shape so callers can introspect any
        backend uniformly (``systems`` widens p exactly as the Pallas
        planner charges a stacked lam-path block)."""
        systems = max(systems, 1)
        p = max(p, 1) * systems
        pol = self.policy
        return SweepPlan(
            path="jnp", n=n, M=M, d=d, p=p, systems=systems,
            block_m=self.block_size, block_n=M, shard_m=None,
            scratch_bytes=4 * self.block_size * M, io_bytes=0,
            vmem_budget_bytes=0,
            input_dtype=pol.storage, vector_dtype=pol.storage,
            accum_dtype=pol.accumulate, compensated=pol.compensated,
            reason=(f"jnp reference: lax.scan over {self.block_size}-row "
                    f"blocks, O(block * M) live memory"))
