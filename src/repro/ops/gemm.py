"""Materialized-K_nM GEMM primitives shared by every backend.

The recompute sweep pays one full kernel evaluation of K_nM per CG
iteration. The :class:`~repro.ops.knm_cache.KernelCache` path instead calls
``materialize`` ONCE — each (block, M) row tile evaluated a single time via
the backend's ``gram`` — and serves every later sweep/apply as pure matmuls
over the stored entries:

    materialize(X, C) -> K        (n_pad, M) at the policy's STORAGE dtype
    gemm_sweep(K, u, v, mask)  =  (K*mask)^T ((K*mask) u + v*mask)
    gemm_apply(K, u)           =  K u        (caller slices [:n])

These are deliberately implemented ONCE here (``GemmCacheMixin``) and
inherited by both the jnp and Pallas backends: after materialization there
is no kernel math left — only GEMMs — so there is nothing backend-specific
to fuse, and XLA's native matmuls are the right tool on every platform.

Numerical contract (the cache's parity guarantees hang off this):

* ``gemm_sweep`` replays the jnp reference sweep's EXACT blocked
  ``lax.scan`` arithmetic — same (block_size, M) strips, same mask
  multiply, same accumulation order, same Kahan compensation under a
  ``compensated`` policy — over stored entries instead of freshly
  evaluated ones. Under the fp32 policy the stored entries ARE the
  entries the recompute sweep computes (``materialize`` quantizes X/C
  through the same storage round-trip before ``gram``), so cached and
  recompute sweeps are bit-identical on the jnp backend.
* Under a reduced-storage policy (bf16) the tiles are stored at storage
  width — the halved-footprint point of composing with the precision
  work — which adds ONE extra rounding of the kernel entries; every
  contraction still accumulates in float32 (widened inside the scan), so
  parity vs recompute stays within the policy tolerance.

Row-padding contract: ``materialize`` zero-pads X to a multiple of
``block_size`` (row i of K is row i of the padded X), and the GEMM calls
take operands already padded to ``K.shape[0]`` rows — the cache owner
(``KernelCache``) folds the pad mask into ``row_mask`` so pad rows
contribute exactly zero, the same contract the recompute sweep's internal
padding satisfies.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: Full-fp32 contraction for fp32 operands (this package cannot import
#: ``repro.core``; same value and reason as ``repro.core.kernels.FP32``).
FP32 = jax.lax.Precision.HIGHEST

Array = jax.Array


def quantize_storage(policy, a: Array | None) -> Array | None:
    """Data-space storage quantization, fp32 compute — the jnp reference
    sweep's ``_quant``: round through the storage dtype, widen back for the
    contraction. float32 storage means full precision: pass through
    untouched (x64 callers keep their float64)."""
    if a is None or policy.storage == "float32":
        return a
    return a.astype(jnp.dtype(policy.storage)).astype(jnp.float32)


def quantize_coeffs(policy, u: Array) -> Array:
    """u at the policy's coefficient dtype (float32 by override; any
    reduced-storage u — bf16/fp16/fp8 CG iterates — is widened for compute;
    an fp64 u under float32 coeffs is never narrowed)."""
    co_name = policy.buffer_dtype("coeffs")
    co = jnp.dtype(co_name)
    if co_name != "float32":
        return u.astype(co).astype(jnp.float32)
    if jnp.dtype(u.dtype).itemsize < co.itemsize:
        return u.astype(jnp.float32)
    return u


def _compute_dtype(K: Array):
    """fp32 floor for the GEMM contraction; stored fp64 stays fp64."""
    dt = jnp.dtype(K.dtype)
    return dt if dt.itemsize >= 4 else jnp.dtype(jnp.float32)


class GemmCacheMixin:
    """The three cache primitives, shared by every concrete backend.

    Mixes into a frozen ``OpsBase`` dataclass: uses only ``self.kernel``,
    ``self.block_size``, ``self.policy`` and ``self.gram`` — no state.
    """

    def materialize(self, X: Array, C: Array) -> Array:
        """Evaluate K(X, C) once, blocked, at the policy's storage dtype.

        Returns (n_pad, M) with n_pad = ceil(n / block_size) * block_size;
        row i is row i of the zero-padded X (pad rows carry K(0, C) values
        — finite, and masked/sliced away by every consumer). Each row tile
        goes through ONE ``gram`` evaluation — the single kernel pass a
        cached fit performs, and what ``CountingOps.gram_tile_evals``
        charges.
        """
        pol = self.policy
        Xq = quantize_storage(pol, X)
        Cq = quantize_storage(pol, C)
        bs = self.block_size
        n = Xq.shape[0]
        nb = -(-n // bs)
        Xp = jnp.pad(Xq, ((0, nb * bs - n), (0, 0)))
        st = jnp.dtype(pol.storage)
        tiles = []
        for i in range(nb):
            Kt = self.gram(Xp[i * bs:(i + 1) * bs], Cq)
            # store at storage width (bf16 => half footprint); float32
            # storage keeps gram's full-precision output untouched
            tiles.append(Kt if pol.storage == "float32" else Kt.astype(st))
        return tiles[0] if nb == 1 else jnp.concatenate(tiles, axis=0)

    def gemm_sweep(
        self,
        K: Array,
        u: Array,
        v: Array | None = None,
        row_mask: Array | None = None,
    ) -> Array:
        """K^T (K u + v) over STORED entries — the cached CG iteration.

        ``K``: (rows, M) from ``materialize`` (rows % block_size == 0);
        ``v``/``row_mask`` must already be padded to ``rows`` (the cache
        folds its pad mask in). Replays the jnp reference sweep's blocked
        scan arithmetic exactly — fp32-stored entries give bit-identical
        results to the recompute sweep.
        """
        pol = self.policy
        bs = self.block_size
        rows, M = K.shape
        if rows % bs != 0:
            raise ValueError(
                f"cached K has {rows} rows, not a multiple of "
                f"block_size={bs} — materialize() pads; hand-built caches "
                f"must too")
        if v is not None and v.shape[0] != rows:
            raise ValueError(
                f"v has {v.shape[0]} rows but cached K has {rows}; pad v "
                f"(and mask the pad rows) to the cache's row count")
        u = quantize_coeffs(pol, u)
        v = quantize_storage(pol, v)
        cd = _compute_dtype(K)
        nb = rows // bs
        Kb = K.reshape(nb, bs, M)
        # No-mask fast path: a fully-aligned cache (no pad rows, no caller
        # mask) skips the mask multiply — a whole read+write pass over the
        # n x M entries, the dominant memory traffic of a served sweep.
        # Bit-identity survives because x * 1.0 is EXACT in IEEE: the
        # reference sweep's all-ones multiply returns bitwise-unchanged
        # entries, so dropping it feeds the same bits to the same matmuls.
        mb = None if row_mask is None else row_mask.astype(cd).reshape(nb, bs)
        out_shape = (M,) + u.shape[1:]
        if v is not None:
            vb = v.reshape((nb, bs) + v.shape[1:])

        def delta(inp):
            if v is None:
                if mb is None:
                    (kb,) = inp
                    Kf = kb.astype(cd)
                else:
                    kb, m = inp
                    Kf = kb.astype(cd) * m[:, None]
                t = jnp.matmul(Kf, u, precision=FP32)
            elif mb is None:
                kb, vblk = inp
                Kf = kb.astype(cd)
                t = jnp.matmul(Kf, u, precision=FP32) + vblk
            else:
                kb, m, vblk = inp
                Kf = kb.astype(cd) * m[:, None]
                t = jnp.matmul(Kf, u, precision=FP32) + vblk * (
                    m[:, None] if vblk.ndim > 1 else m)
            return jnp.matmul(Kf.T, t, precision=FP32)

        if mb is None:
            xs = (Kb,) if v is None else (Kb, vb)
        else:
            xs = (Kb, mb) if v is None else (Kb, mb, vb)
        if pol.compensated:
            # identical cross-block Kahan to the recompute sweep (lazy
            # import: ops must not import kernels at module load)
            from repro.kernels.kernel_matvec import _two_sum

            def body(carry, inp):
                acc, comp = carry
                return _two_sum(acc, comp, delta(inp)), None

            init = (jnp.zeros(out_shape, cd), jnp.zeros(out_shape, cd))
            (w, _), _ = jax.lax.scan(body, init, xs)
        else:
            def body(carry, inp):
                return carry + delta(inp), None

            w, _ = jax.lax.scan(body, jnp.zeros(out_shape, cd), xs)
        co = pol.buffer_dtype("coeffs")
        return w.astype(jnp.dtype(co)) if co != "float32" else w

    def gemm_apply(self, K: Array, u: Array) -> Array:
        """K u over stored entries — the cached prediction path.

        Returns ALL ``K.shape[0]`` rows (pad rows included); the cache
        slices back to the valid n, mirroring the recompute ``apply``.
        """
        u = quantize_coeffs(self.policy, u)
        cd = _compute_dtype(K)
        bs = self.block_size
        rows, M = K.shape
        if rows % bs != 0:
            raise ValueError(
                f"cached K has {rows} rows, not a multiple of "
                f"block_size={bs}")
        Kb = K.reshape(rows // bs, bs, M)

        def body(kb):
            return jnp.matmul(kb.astype(cd), u, precision=FP32)

        out = jax.lax.map(body, Kb)
        return out.reshape((rows,) + u.shape[1:])
