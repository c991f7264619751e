"""Tiled right-looking blocked Cholesky — the out-of-core factor path.

``make_preconditioner`` historically did ONE in-core ``jnp.linalg.cholesky``
on the dense (M, M) regularized Gram. FALKON's statistical optimality wants
M ~ sqrt(n) Nystrom centers, so the dense factor is the first wall the
preconditioner hits as n grows: 1 GB fp32 at M = 16384, 40 GB at M = 10^5.
This module factors the matrix while keeping it HOST-resident, moving only
O(block * M) panel bytes onto the device at any moment.

Algorithm (right-looking, by column panels of width b = ``block``):

    for panel k over the (b, b) tile grid:
        POTRF   L_kk          = chol(A_kk)            — one (b, b) tile
        TRSM    L_panel       = A[below, k] L_kk^{-T} — (rows, b) panel
        SYRK    A[j:, j]     -= L[j:, k] L[j, k]^T    — trailing update,
                                                        per column block j > k

The factor accumulates in a host numpy working buffer; each step uploads one
panel, runs the tile math on device, copies the result back and ``delete()``s
the device buffers, so the device working set is two (M, b) panels plus the
update's output tile — the O(b * M) bound ``FactorPlan.device_ceiling_bytes``
models and ``tests/test_blocked_cholesky.py`` measures via
``jax.live_arrays()``.

Two interchangeable TILE ENGINES supply the three per-tile primitives:

* ``"jnp"``    — BLAS-backed ``jnp.linalg.cholesky`` / ``solve_triangular`` /
                 matmul per tile. Default off-TPU; the numerical ground truth.
* ``"pallas"`` — Pallas kernels (in-VMEM right-looking POTRF/TRSM on the
                 VPU, gridded fp32 SYRK update on the MXU), sized so every
                 block ``plan_factor`` picks fits VMEM. Default on TPU;
                 interpret-mode on CPU for parity tests (``tile_impl="auto"``
                 picks per backend).

Tiles compute in float32 at minimum — the ``PrecisionPolicy`` ``cholesky``
override's fp32 floor (quantized factors destabilize preconditioned CG; the
PR 3 measured constraint) — and in float64 when the input is float64 and x64
is enabled. Conventions match the preconditioner stack: ``blocked_cholesky``
returns the UPPER factor T with A = T^T T (the repo-wide ``chol(...).T``
convention), as host numpy; callers move it to device for solve time, which
is the remaining O(M^2) device-residency ceiling (documented in
``docs/architecture.md``).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.kernels import FP32
from repro.ops.base import FACTOR_CHUNK as CHUNK
from repro.ops.base import _vmem_budget, factor_tile_vmem_bytes

from .kernel_matvec import interpret_mode

LANE = 128   # MXU/VREG lane width — last-dim tile alignment

TILE_IMPLS = ("auto", "jnp", "pallas")


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def resolve_tile_impl(tile_impl: str) -> str:
    """Resolve ``"auto"`` to the per-backend default engine."""
    if tile_impl not in TILE_IMPLS:
        raise ValueError(f"unknown tile_impl {tile_impl!r}; supported: {TILE_IMPLS}")
    if tile_impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    return tile_impl


# ---------------------------------------------------------------------------
# Device-residency accounting
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class FactorStats:
    """Self-accounted device residency of one blocked factorization.

    Every device buffer the driver creates is charged on upload and credited
    when it is copied back and ``delete()``d, so ``peak_device_bytes`` is the
    algorithmic working set (panels + tiles), comparable against
    ``FactorPlan.device_ceiling_bytes``. Tests cross-check it against the
    ground truth (``jax.live_arrays()`` deltas sampled from ``on_step``).
    """

    peak_device_bytes: int = 0
    current_device_bytes: int = 0
    bytes_transferred: int = 0   # host<->device traffic, both directions
    panels: int = 0              # column panels factored
    tiles_updated: int = 0       # trailing (rows, b) update tiles

    def alloc(self, nbytes: int) -> None:
        self.current_device_bytes += nbytes
        self.bytes_transferred += nbytes
        self.peak_device_bytes = max(self.peak_device_bytes, self.current_device_bytes)

    def free(self, nbytes: int) -> None:
        self.current_device_bytes -= nbytes
        self.bytes_transferred += nbytes


def _put(stats: FactorStats, host_block: np.ndarray, dt) -> jax.Array:
    dev = jax.device_put(np.ascontiguousarray(np.asarray(host_block, dt)))
    dev.block_until_ready()
    stats.alloc(dev.nbytes)
    return dev


def _take(stats: FactorStats, dev: jax.Array) -> np.ndarray:
    """Copy a device buffer back to host and release it."""
    host = np.array(dev)  # forced copy — safe to delete the backing buffer
    stats.free(dev.nbytes)
    dev.delete()
    return host


def _drop(stats: FactorStats, dev: jax.Array) -> None:
    stats.free(dev.nbytes)
    dev.delete()


# ---------------------------------------------------------------------------
# Pallas tile kernels
# ---------------------------------------------------------------------------
# POTRF and TRSM are one right-looking elimination over the columns of a
# VMEM tile. Step k scales column k by a pivot and subtracts its outer
# product with a pivot row from the later columns, in fp32 on the VPU (no
# MXU pass, so no matmul precision question). Dynamic indexing is along
# sublanes only (``pl.ds`` rows); column k is read with a 2-D iota mask.
# The tile is swept in CHUNK-row slabs so the temporaries stay (CHUNK, b)
# whatever the tile height: the VMEM a tile needs is its operand buffers
# plus a few slabs, which ``repro.ops.base.factor_tile_vmem_bytes``
# charges and ``plan_factor`` caps the block with.

def _eliminate(o_ref, k, pivot, rest):
    """Column k of ``o_ref`` becomes x = o[:, k] / pivot; every column j
    loses x * rest[j] (``rest`` is a (1, b) row, zero at j <= k)."""
    rows, b = o_ref.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, b), 1)

    def slab(c, carry):
        r0 = pl.multiple_of(c * CHUNK, CHUNK)
        blk = o_ref[pl.ds(r0, CHUNK), :]
        x = jnp.sum(jnp.where(cols == k, blk, 0.0), axis=1, keepdims=True) / pivot
        o_ref[pl.ds(r0, CHUNK), :] = jnp.where(cols == k, x, blk - x * rest)
        return carry

    jax.lax.fori_loop(0, rows // CHUNK, slab, 0)


def _potrf_kernel(a_ref, o_ref):
    """Cholesky of one (b, b) SPD tile, A = L L^T; ``o_ref`` ends with L in
    its lower triangle (the strict upper triangle is left stale).

    Step k: the pivot row k of the trailing matrix S gives d = sqrt(S_kk)
    and, by symmetry, column k of L as the row S[k, k+1:] / d; eliminating
    with pivot d writes L[:, k] and applies S -= l_k l_k^T."""
    b = o_ref.shape[1]
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, b), 1)
    o_ref[...] = a_ref[...]

    def step(k, carry):
        row = o_ref[pl.ds(k, 1), :]
        d = jnp.sum(jnp.where(cols == k, row, 0.0))
        # A non-positive pivot means the tile is not SPD (insufficient
        # jitter); propagate NaN so the failure is as observable as the
        # in-core jnp.linalg.cholesky path's, rather than clamping to a
        # finite garbage factor.
        d = jnp.sqrt(jnp.where(d > 0, d, jnp.nan))
        _eliminate(o_ref, k, d, jnp.where(cols > k, row / d, 0.0))
        return carry

    jax.lax.fori_loop(0, b, step, 0)


def _trsm_kernel(u_ref, a_ref, o_ref):
    """One (bt, b) tile of X = A L^{-T}, given U = L^T: forward
    substitution over the columns of X U = A, pivot U_jj, pivot row U[j]."""
    b = o_ref.shape[1]
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, b), 1)
    o_ref[...] = a_ref[...]

    def step(j, carry):
        urow = u_ref[pl.ds(j, 1), :]
        pivot = jnp.sum(jnp.where(cols == j, urow, 0.0))
        _eliminate(o_ref, j, pivot, jnp.where(cols > j, urow, 0.0))
        return carry

    jax.lax.fori_loop(0, b, step, 0)


def _update_kernel(c_ref, p_ref, q_ref, o_ref, acc_ref):
    """One (UT, UN) tile of the trailing update  C - P Q^T  (SYRK/GEMM),
    accumulated over UK-wide slices of the contraction in full fp32 on the
    MXU."""
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = c_ref[...]

    acc_ref[...] -= jax.lax.dot_general(
        p_ref[...],
        q_ref[...],
        (((1,), (1,)), ((), ())),
        precision=FP32,
        preferred_element_type=acc_ref.dtype,
    )

    @pl.when(kk == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


#: Update-kernel tiles (rows, output columns, contraction slice): fixed, so
#: its VMEM stays a few MB at any panel width.
UT, UN, UK = 512, 256, 512


def _row_tile(r: int, bp: int) -> int:
    """Largest CHUNK-multiple TRSM row tile (<= 1024, <= the padded rows)
    whose VMEM fits the budget; at least one CHUNK."""
    bt = min(_round_up(r, CHUNK), 4 * CHUNK)
    while bt > CHUNK and factor_tile_vmem_bytes("trsm", bp, bt) > _vmem_budget():
        bt -= CHUNK
    return bt


def _pad_identity(A: jax.Array, bp: int) -> jax.Array:
    """Pad a (b, b) SPD tile to (bp, bp) with an identity tail block, so its
    Cholesky factor is the original factor plus an identity tail."""
    b = A.shape[0]
    if bp == b:
        return A
    P = jnp.pad(A, ((0, bp - b), (0, bp - b)))
    r = jax.lax.broadcasted_iota(jnp.int32, (bp, bp), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (bp, bp), 1)
    return jnp.where((r == c) & (r >= b), jnp.ones((), P.dtype), P)


def _tile_width(b: int) -> int:
    """Padded panel width: lane-aligned and a whole number of slabs."""
    return _round_up(b, max(LANE, CHUNK))


@partial(jax.jit, static_argnames=("interpret",))
def _pallas_potrf(A, *, interpret: bool):
    b = A.shape[0]
    bp = _tile_width(b)
    L = pl.pallas_call(
        _potrf_kernel,
        out_shape=jax.ShapeDtypeStruct((bp, bp), A.dtype),
        interpret=interpret,
    )(_pad_identity(A, bp))
    return jnp.tril(L[:b,:b])


@partial(jax.jit, static_argnames=("interpret",))
def _pallas_trsm(L, A, *, interpret: bool):
    b = L.shape[0]
    r = A.shape[0]
    bp = _tile_width(b)
    bt = _row_tile(r, bp)
    rp = _round_up(r, bt)
    Up = _pad_identity(jnp.tril(L), bp).T
    Ap = jnp.pad(A, ((0, rp - r), (0, bp - b)))
    X = pl.pallas_call(
        _trsm_kernel,
        grid=(rp // bt,),
        in_specs=[pl.BlockSpec((bp, bp), lambda i: (0, 0),
                               pipeline_mode=pl.Buffered(1)),
                  pl.BlockSpec((bt, bp), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bt, bp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, bp), A.dtype),
        interpret=interpret,
    )(Up, Ap)
    return X[:r,:b]


@partial(jax.jit, static_argnames=("interpret",))
def _pallas_update(C, P, Q, *, interpret: bool):
    # The output width b (C's columns — ragged on the last block) and the
    # contraction width k (P/Q's columns — the FACTOR panel width) are
    # independent: in the trailing update of a ragged final block, k can
    # exceed b. Pad each to its own lane-aligned size or the contraction
    # silently truncates to the first bp columns.
    r, b = C.shape
    k = P.shape[1]
    bn = min(_round_up(b, LANE), UN)
    bk = min(_round_up(k, LANE), UK)
    bt = min(_round_up(r, 8), UT)
    bp, kp, rp = _round_up(b, bn), _round_up(k, bk), _round_up(r, bt)
    Cp = jnp.pad(C, ((0, rp - r), (0, bp - b)))
    Pp = jnp.pad(P, ((0, rp - r), (0, kp - k)))
    Qp = jnp.pad(Q, ((0, bp - Q.shape[0]), (0, kp - k)))
    O = pl.pallas_call(
        _update_kernel,
        grid=(rp // bt, bp // bn, kp // bk),
        in_specs=[pl.BlockSpec((bt, bn), lambda i, j, kk: (i, j)),
                  pl.BlockSpec((bt, bk), lambda i, j, kk: (i, kk)),
                  pl.BlockSpec((bn, bk), lambda i, j, kk: (j, kk))],
        out_specs=pl.BlockSpec((bt, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rp, bp), C.dtype),
        scratch_shapes=[pltpu.VMEM((bt, bn), C.dtype)],
        interpret=interpret,
    )(Cp, Pp, Qp)
    return O[:r,:b]


# ---------------------------------------------------------------------------
# jnp tile engine (BLAS-backed; default off-TPU)
# ---------------------------------------------------------------------------
@jax.jit
def _jnp_potrf(A):
    return jnp.linalg.cholesky(A)


@jax.jit
def _jnp_trsm(L, A):
    return jax.scipy.linalg.solve_triangular(L, A.T, lower=True).T


@jax.jit
def _jnp_update(C, P, Q):
    return C - jax.lax.dot_general(
        P, Q, (((1,), (1,)), ((), ())), precision=FP32,
        preferred_element_type=C.dtype,
    )


def _engine(tile_impl: str):
    impl = resolve_tile_impl(tile_impl)
    if impl == "jnp":
        return _jnp_potrf, _jnp_trsm, _jnp_update
    interp = interpret_mode()
    return (
        partial(_pallas_potrf, interpret=interp),
        partial(_pallas_trsm, interpret=interp),
        partial(_pallas_update, interpret=interp),
    )


def _host_compute_dtypes(K) -> tuple[np.dtype, jnp.dtype]:
    """(host working dtype, device tile dtype) for an input matrix.

    float32 floor always (the policy ``cholesky`` override); float64 tiles
    only when the input is float64 AND x64 is enabled — otherwise device
    math runs fp32 exactly like the in-core ``jnp.linalg.cholesky`` would,
    keeping blocked-vs-in-core parity an apples-to-apples comparison."""
    host_dt = np.float64 if np.dtype(K.dtype) == np.float64 else np.float32
    if host_dt == np.float64 and jax.config.jax_enable_x64:
        return host_dt, jnp.float64
    return host_dt, jnp.float32


# ---------------------------------------------------------------------------
# The host-blocked driver
# ---------------------------------------------------------------------------
def blocked_cholesky(
    K,
    block: int = 1024,
    *,
    tile_impl: str = "auto",
    stats: FactorStats | None = None,
    on_step=None,
) -> np.ndarray:
    """Factor a host-resident SPD matrix, returning the UPPER factor T
    (A = T^T T — the repo's ``chol(...).T`` convention) as host numpy.

    ``K`` is any (M, M) SPD array-like (numpy or jax; a jax input is copied
    to host once up front — callers who want true out-of-core behavior pass
    host numpy, as ``_shared_factor`` does). Jitter is the CALLER's job:
    this routine factors exactly what it is given.

    Device residency: at most one (rows, b) factor panel + one (rows, b)
    trailing tile (+ the update's output) live at once; every buffer is
    copied back and deleted before the next panel. ``stats`` (a
    :class:`FactorStats`) receives the self-accounted peak; ``on_step`` (a
    ``callable(stage: str, stats)``) fires at the residency high-water
    points so tests can sample ``jax.live_arrays()`` for the ground truth.
    """
    if block < 1:
        raise ValueError(f"block must be >= 1, got {block}")
    stats = stats if stats is not None else FactorStats()
    step = on_step if on_step is not None else (lambda stage, s: None)
    potrf, trsm, update = _engine(tile_impl)
    host_dt, dev_dt = _host_compute_dtypes(K)

    W = np.array(K, dtype=host_dt, copy=True)
    if W.ndim != 2 or W.shape[0] != W.shape[1]:
        raise ValueError(f"expected a square matrix, got shape {W.shape}")
    M = W.shape[0]
    nb = -(-M // block)

    for k in range(nb):
        i0, i1 = k * block, min((k + 1) * block, M)
        stats.panels += 1

        # POTRF the (b, b) diagonal tile, TRSM the rows below it, and land
        # both back in W's lower triangle before touching the trailing
        # matrix — no factor bytes stay device-resident between phases.
        Akk = _put(stats, W[i0:i1, i0:i1], dev_dt)
        Lkk = potrf(Akk)
        Lkk.block_until_ready()
        stats.alloc(Lkk.nbytes)
        _drop(stats, Akk)
        if i1 < M:
            Ak = _put(stats, W[i1:, i0:i1], dev_dt)
            Lpanel = trsm(Lkk, Ak)
            Lpanel.block_until_ready()
            stats.alloc(Lpanel.nbytes)
            _drop(stats, Ak)
            step("panel", stats)
            W[i1:, i0:i1] = _take(stats, Lpanel)
        W[i0:i1, i0:i1] = _take(stats, Lkk)

        # Trailing update, one column block at a time: each step holds one
        # (rows, b) slice of the fresh factor panel, its (b, b) top, and
        # one (rows, b) trailing tile — the O(b * M) working set.
        for j in range(k + 1, nb):
            j0, j1 = j * block, min((j + 1) * block, M)
            P = _put(stats, W[j0:, i0:i1], dev_dt)
            Q = _put(stats, W[j0:j1, i0:i1], dev_dt)
            Cj = _put(stats, W[j0:, j0:j1], dev_dt)
            Cn = update(Cj, P, Q)
            Cn.block_until_ready()
            stats.alloc(Cn.nbytes)
            stats.tiles_updated += 1
            step("update", stats)
            _drop(stats, Cj)
            _drop(stats, P)
            _drop(stats, Q)
            W[j0:, j0:j1] = _take(stats, Cn)

    # W's lower triangle now holds L (A = L L^T); strict upper still holds
    # stale input. Emit the upper-convention factor T = L^T.
    return np.ascontiguousarray(np.tril(W).T)


def blocked_syrk_tt(
    T: np.ndarray, block: int = 1024, *, stats: FactorStats | None = None
) -> np.ndarray:
    """Host-blocked  T T^T  for an UPPER-triangular host factor T.

    The lambda-independent half of the preconditioner's second stage
    (``A = chol(T T^T / M + lam I).T``) needs the full (M, M) product; this
    computes it panel-by-panel under the same O(b * M) device-residency
    contract. Upper-triangularity is exploited: rows i of T are supported on
    columns k >= i, so the (i, j) block pair (i >= j) only contracts over
    k >= i0 — the contraction shrinks as the row panel descends.
    """
    stats = stats if stats is not None else FactorStats()
    dev_dt = _host_compute_dtypes(T)[1]
    M = T.shape[0]
    nb = -(-M // block)
    out = np.empty((M, M), dtype=T.dtype)

    for i in range(nb):
        i0, i1 = i * block, min((i + 1) * block, M)
        R = _put(stats, T[i0:i1, i0:], dev_dt)       # (b, M - i0) row panel
        for j in range(i + 1):
            j0, j1 = j * block, min((j + 1) * block, M)
            S = _put(stats, T[j0:j1, i0:], dev_dt)
            D = jax.lax.dot_general(
                R, S, (((1,), (1,)), ((), ())),
                precision=FP32, preferred_element_type=dev_dt,
            )
            D.block_until_ready()
            stats.alloc(D.nbytes)
            _drop(stats, S)
            Dh = _take(stats, D)
            out[i0:i1, j0:j1] = Dh
            if i != j:
                out[j0:j1, i0:i1] = Dh.T
        _drop(stats, R)
    return out
