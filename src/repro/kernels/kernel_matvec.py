"""Pallas TPU kernels for FALKON's O(nMt) hot loop.

Two primitives:

* ``kernel_matmul_pallas`` — ``out = K(A, B) @ V`` with the Gram tile
  ``K(A_i, B_j)`` computed on the fly in VMEM (pairwise precursors via one MXU
  matmul ``A_i B_j^T`` plus row/col norms on the VPU, then the registered
  kernel's elementwise map) and immediately contracted against ``V_j`` on the
  MXU. The (bm x bn) Gram tile never touches HBM.

* ``fused_sweep_pallas`` — the whole FALKON CG sweep
  ``w = K(X,C)^T (K(X,C) u + v)`` in ONE pass over the data: for each (i, j)
  grid tile the Gram tile ``K(X_i, C_j)`` is computed exactly once, staged in
  a VMEM row-strip scratch, used for the forward product ``t_i += K_ij u_j``,
  and — once the row strip is complete — re-read from VMEM for the transposed
  accumulation ``w_j += K_ij^T t_i`` into a persistent fp32 VMEM accumulator.
  Versus composing two ``kernel_matmul_pallas`` calls this halves kernel-tile
  evaluations and HBM round-trips per CG iteration: every Gram entry is
  evaluated once and never re-materialized.

Kernel math is NOT duplicated here: both kernels evaluate tiles through
``repro.core.kernels.tile_transform`` keyed by a declarative ``KernelSpec``,
so every kernel registered in ``core/kernels.py`` (gaussian, laplacian,
matern32, linear, polynomial, ...) runs on the Pallas path with no
per-backend kernel lists.

Grid conventions: (i over A/X row tiles, j over B/C tiles), j minor.
Accumulators are fp32 VMEM scratch initialised on the first visit and flushed
on the last — the standard Pallas reduction pattern. Operands may be bf16
(``precision='bf16'`` upstream — under the end-to-end policy X, C, u, v AND
the outputs/HBM spills are all bfloat16): the distance/dot matmuls feed the
MXU in the input dtype with ``preferred_element_type=float32``, i.e.
bf16-in/fp32-accumulate. With ``compensated=True`` each accumulator carries a
same-shape Kahan/two-sum compensation buffer (``_two_sum``), so the tile-loop
reduction error stays O(eps_fp32) independent of the grid size — the
guarantee that makes bf16 storage safe at large n/M. Tile sizes default to
multiples of the 128-wide MXU systolic dimensions; wrappers pad every operand
to tile multiples and mask padded rows with in-kernel iota masks (no mask
operands in HBM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.kernels import FP32, KernelSpec, tile_transform

Array = jax.Array

LANE = 128   # MXU/VREG lane width — last-dim tile alignment
SUBLANE = 8  # fp32 sublane granularity


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def interpret_mode() -> bool:
    """Whether Pallas kernels run in interpret mode: compiled by Mosaic on a
    TPU, emulated in Python on every other backend (CPU tests)."""
    return jax.default_backend() != "tpu"


def _as_spec(kind: str, scale: float, spec: KernelSpec | None) -> KernelSpec:
    """Back-compat shim: legacy (kind, scale) callers -> KernelSpec.

    Only the sigma-kernels are expressible through the legacy signature;
    kernels with more params (polynomial's degree/c) must come in as a spec —
    defaulting them silently would compute the wrong Gram values.
    """
    if spec is not None:
        return spec
    if kind in ("gaussian", "laplacian", "matern32"):
        return KernelSpec(kind, (("sigma", scale),))
    raise ValueError(
        f"legacy (kind, scale) interface supports only the sigma kernels; "
        f"pass spec=KernelSpec(...) for {kind!r}")


def sweep_block_dims(n: int, M: int, block_m: int, block_n: int) -> tuple[int, int]:
    """(bm, bn) the fused sweep actually tiles with — the single source of
    the rounding policy, used by ``fused_sweep_pallas`` itself and by the
    grid/count derivations below."""
    bm = min(_round_up(block_m, SUBLANE), _round_up(n, SUBLANE))
    bn = min(_round_up(block_n, LANE), _round_up(M, LANE))
    return bm, bn


def sweep_tile_grid(n: int, M: int, block_m: int, block_n: int) -> tuple[int, int]:
    """(nbi, nbj) tile grid the fused sweep runs over for these shapes —
    benchmarks and tests derive expected Gram-tile evaluation counts from
    this: one per tile."""
    bm, bn = sweep_block_dims(n, M, block_m, block_n)
    return -(-n // bm), -(-M // bn)


def _two_sum(acc: Array, comp: Array, delta: Array) -> tuple[Array, Array]:
    """Kahan/two-sum compensated ``acc += delta``; returns (acc', comp').

    ``comp`` carries the low-order bits lost by each fp32 add; folding it
    into the next delta bounds the whole reduction's error at O(eps_fp32)
    instead of O(steps * eps_fp32). Pure arithmetic — safe inside Pallas
    bodies and lax.scan carries alike.
    """
    y = delta - comp
    t = acc + y
    return t, (t - acc) - y


def _tile(a, b, spec: KernelSpec) -> Array:
    """K(a, b) tile: one MXU matmul + VPU elementwise, fp32 accumulate."""
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    a2 = jnp.sum(af * af, axis=-1, keepdims=True)              # (bm, 1) VPU
    b2 = jnp.sum(bf * bf, axis=-1, keepdims=True).T            # (1, bn) VPU
    # Mosaic contracts narrow (bf16) operands natively and refuses an fp32
    # contract precision for them, wide ones need it (see FP32). DEFAULT is
    # explicit so that jax.default_matmul_precision cannot override it.
    wide = jnp.dtype(a.dtype).itemsize >= 4
    ab = jax.lax.dot_general(                                   # (bm, bn) MXU
        a, b, (((1,), (1,)), ((), ())),
        precision=FP32 if wide else jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32)
    return tile_transform(ab, a2, b2, spec)


# ---------------------------------------------------------------------------
# kernel matmul: out = K(A, B) @ V
# ---------------------------------------------------------------------------
def _kernel_matmul_kernel(
    a_ref,
    b_ref,
    v_ref,
    *rest,
    spec: KernelSpec,
    n_valid: int,
    bn: int,
    nbj: int,
    has_add: bool,
    compensated: bool,
):
    """One (i, j) grid step: acc_i += K(A_i, B_j) @ V_j (+ add_i at init).

    With ``compensated`` the j-loop reduction runs through a Kahan carry
    buffer (``_two_sum``) so bf16-policy sweeps keep O(eps_fp32) summation
    error regardless of the tile count.
    """
    if compensated:
        *rest, comp_ref = rest
    if has_add:
        add_ref, o_ref, acc_ref = rest
    else:
        o_ref, acc_ref = rest
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        if has_add:
            acc_ref[...] = add_ref[...].astype(jnp.float32)
        else:
            acc_ref[...] = jnp.zeros_like(acc_ref)
        if compensated:
            comp_ref[...] = jnp.zeros_like(comp_ref)

    # mask padded B rows: global column index >= n_valid has no data
    col = j * bn + jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
    bmask = (col < n_valid).astype(jnp.float32)
    k = _tile(a_ref[...], b_ref[...], spec) * bmask
    v = v_ref[...].astype(jnp.float32)
    delta = jax.lax.dot_general(                               # (bm, p) MXU
        k, v, (((1,), (0,)), ((), ())), precision=FP32,
        preferred_element_type=jnp.float32)
    if compensated:
        acc_ref[...], comp_ref[...] = _two_sum(acc_ref[...], comp_ref[...], delta)
    else:
        acc_ref[...] += delta

    @pl.when(j == nbj - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def kernel_matmul_pallas(
    A: Array,
    B: Array,
    V: Array,
    *,
    kind: str = "gaussian",
    scale: float = 1.0,
    spec: KernelSpec | None = None,
    add: Array | None = None,
    block_m: int = 256,
    block_n: int = 512,
    compensated: bool = False,
    out_dtype=None,
    interpret: bool = True,
) -> Array:
    """out = K(A, B) @ V (+ add) with on-the-fly Gram tiles.

    A: (m, d), B: (n, d), V: (n, p) -> (m, p). All shapes may be ragged; the
    wrapper pads to tile multiples and masks padded B rows. ``add`` is an
    optional (m, p) additive term folded into the accumulator at init — the
    j-sharded sweep uses it to fuse ``t = K u + v`` into one pass instead of
    spilling ``K u`` and re-reading it for the add. ``compensated`` switches
    the j-loop reduction to Kahan/two-sum fp32 (the bf16 policy's
    accumulation contract). ``out_dtype`` overrides the output dtype (the
    flush cast out of the fp32 accumulator); by default it follows the
    operands' promotion — the j-sharded sweep passes the policy's storage
    dtype so ``t`` spills to HBM at half width, and the coefficient dtype
    for the final w. The accumulator itself is always fp32 VMEM scratch.
    Pass either a ``spec`` (preferred) or legacy ``kind``/``scale``.
    ``interpret=True`` runs the kernel body in Python (CPU validation); on
    TPU pass False.
    """
    spec = _as_spec(kind, scale, spec)
    m, d = A.shape
    n, _ = B.shape
    p = V.shape[1]
    if out_dtype is None:
        out_dtype = jnp.promote_types(A.dtype, V.dtype)

    bm = min(_round_up(block_m, SUBLANE), _round_up(m, SUBLANE))
    bn = min(_round_up(block_n, LANE), _round_up(n, LANE))
    mp = _round_up(m, bm)
    np_ = _round_up(n, bn)
    dp = _round_up(d, LANE)
    pp = _round_up(p, LANE)

    Ap = jnp.pad(A, ((0, mp - m), (0, dp - d)))
    Bp = jnp.pad(B, ((0, np_ - n), (0, dp - d)))
    Vp = jnp.pad(V, ((0, np_ - n), (0, pp - p)))

    nbi, nbj = mp // bm, np_ // bn

    has_add = add is not None
    in_specs = [
        pl.BlockSpec((bm, dp), lambda i, j: (i, 0)),          # A_i
        pl.BlockSpec((bn, dp), lambda i, j: (j, 0)),          # B_j
        pl.BlockSpec((bn, pp), lambda i, j: (j, 0)),          # V_j
    ]
    operands = [Ap, Bp, Vp]
    if has_add:
        in_specs.append(pl.BlockSpec((bm, pp), lambda i, j: (i, 0)))  # add_i
        operands.append(jnp.pad(add, ((0, mp - m), (0, pp - p))))

    scratch = [pltpu.VMEM((bm, pp), jnp.float32)]             # fp32 accum
    if compensated:
        scratch.append(pltpu.VMEM((bm, pp), jnp.float32))     # Kahan carry
    out = pl.pallas_call(
        functools.partial(_kernel_matmul_kernel, spec=spec, n_valid=n,
                          bn=bn, nbj=nbj, has_add=has_add,
                          compensated=compensated),
        grid=(nbi, nbj),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, pp), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, pp), out_dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(*operands)
    return out[:m,:p]


# ---------------------------------------------------------------------------
# fused sweep: w = K(X, C)^T (K(X, C) u + v) in ONE pass over X
# ---------------------------------------------------------------------------
def _fused_sweep_kernel(
    x_ref,
    c_ref,
    u_ref,
    *rest,
    spec: KernelSpec,
    has_v: bool,
    has_mask: bool,
    compensated: bool,
    n_valid: int,
    m_valid: int,
    bm: int,
    bn: int,
    nbi: int,
    nbj: int,
):
    """One (i, j) grid step of the single-pass sweep.

    Per step: the Gram tile K_ij is computed ONCE, staged into the row-strip
    scratch ``strip[j]``, and folded into ``t_i += K_ij u_j``. When the strip
    for row block i is complete (j == nbj-1), ``t_i`` gains ``v_i``, padded X
    rows are masked (both the wrapper's shape padding via the in-kernel iota
    and, with ``has_mask``, the caller's explicit row mask — streamed tail
    chunks padded to a fixed shape), and the strip is swept a second time
    FROM VMEM for ``w_j += K_ij^T t_i`` — no kernel re-evaluation, no HBM
    round-trip.

    With ``compensated`` both reductions (t over the j tiles, w over the i
    row blocks) run through Kahan carry buffers, keeping the summation error
    at O(eps_fp32) independent of the grid — the bf16 policy's accumulation
    contract.
    """
    if compensated:
        *rest, tc_ref, wc_ref = rest
    mask_ref = None
    if has_mask:
        if has_v:
            v_ref, mask_ref, *rest = rest
        else:
            mask_ref, *rest = rest
        o_ref, cnt_ref, strip_ref, t_ref, w_ref = rest
    elif has_v:
        v_ref, o_ref, cnt_ref, strip_ref, t_ref, w_ref = rest
    else:
        o_ref, cnt_ref, strip_ref, t_ref, w_ref = rest
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _init_w():
        w_ref[...] = jnp.zeros_like(w_ref)
        cnt_ref[0, 0] = 0
        if compensated:
            wc_ref[...] = jnp.zeros_like(wc_ref)

    @pl.when(j == 0)
    def _init_t():
        t_ref[...] = jnp.zeros_like(t_ref)
        if compensated:
            tc_ref[...] = jnp.zeros_like(tc_ref)

    # K_ij evaluated exactly once per (i, j): count it.
    col = j * bn + jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
    cmask = (col < m_valid).astype(jnp.float32)                # pad cols of C
    k = _tile(x_ref[...], c_ref[...], spec) * cmask            # (bm, bn)
    strip_ref[j] = k
    u = u_ref[...].astype(jnp.float32)                         # (bn, p)
    t_delta = jax.lax.dot_general(                             # (bm, p) MXU
        k, u, (((1,), (0,)), ((), ())), precision=FP32,
        preferred_element_type=jnp.float32)
    if compensated:
        t_ref[...], tc_ref[...] = _two_sum(t_ref[...], tc_ref[...], t_delta)
    else:
        t_ref[...] += t_delta
    cnt_ref[0, 0] += 1

    @pl.when(j == nbj - 1)
    def _accumulate():
        t = t_ref[...]
        if has_v:
            t = t + v_ref[...].astype(jnp.float32)
        row = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
        t = t * (row < n_valid).astype(jnp.float32)            # pad rows of X
        if has_mask:
            # caller-supplied row mask (lane-padded; column 0 is the mask):
            # zeroing t_i zeroes the masked rows' K^T t contribution EXACTLY
            t = t * mask_ref[...][:,:1]

        def body(jj, _):
            delta = jax.lax.dot_general(                       # (bn, p) MXU
                strip_ref[jj], t, (((0,), (0,)), ((), ())), precision=FP32,
                preferred_element_type=jnp.float32)
            if compensated:
                w_ref[jj], wc_ref[jj] = _two_sum(w_ref[jj], wc_ref[jj], delta)
            else:
                w_ref[jj] += delta
            return 0

        jax.lax.fori_loop(0, nbj, body, 0)

    @pl.when((i == nbi - 1) & (j == nbj - 1))
    def _flush():
        o_ref[...] = w_ref[...].astype(o_ref.dtype)


def fused_sweep_pallas(
    X: Array,
    C: Array,
    u: Array,
    v: Array | None,
    *,
    spec: KernelSpec,
    row_mask: Array | None = None,
    block_m: int = 256,
    block_n: int = 512,
    compensated: bool = False,
    interpret: bool = True,
    return_tile_count: bool = False,
) -> Array | tuple[Array, Array]:
    """w = K(X,C)^T (K(X,C) u + v) — one fused pass, each Gram tile once.

    X: (n, d), C: (M, d), u: (M, p), v: (n, p) or None -> (M, p).
    ``row_mask`` (n,), 0/1: rows with mask 0 contribute EXACTLY zero to w
    (their t_i is zeroed before the transposed product) — how callers sweep
    a fixed-shape chunk whose tail rows are padding (see
    ``repro.data.streaming``) without a shape-changing slice.

    VMEM residency per step: one (bm, d) X tile, one (bn, d) C tile, the
    row-strip scratch (nbj, bm, bn) and the fp32 accumulator (nbj, bn, p) —
    i.e. O(bm * M + M * p) scratch, the paper's O(M) working-set budget times
    the block height. ``compensated`` adds Kahan carry buffers beside the t/w
    accumulators (two-sum fp32 — the bf16 policy's accumulation contract; the
    planner's budget model counts them). Output dtype follows the operands
    (bf16 in -> bf16 out under the end-to-end policy). With
    ``return_tile_count=True`` also returns the number of Gram-tile
    evaluations the kernel performed (an int32 scalar; equals
    ceil(n/bm) * ceil(M/bn) — exactly one evaluation per tile, which is the
    fusion claim and is asserted by tests/test_kernel_ops.py).
    """
    n, d = X.shape
    M, _ = C.shape
    squeeze = u.ndim == 1
    u2 = u[:, None] if squeeze else u
    v2 = None if v is None else (v[:, None] if squeeze else v)
    p = u2.shape[1]
    out_dtype = jnp.promote_types(X.dtype, u.dtype)

    bm, bn = sweep_block_dims(n, M, block_m, block_n)
    npad = _round_up(n, bm)
    Mpad = _round_up(M, bn)
    dp = _round_up(d, LANE)
    pp = _round_up(p, LANE)
    nbi, nbj = npad // bm, Mpad // bn

    Xp = jnp.pad(X, ((0, npad - n), (0, dp - d)))
    Cp = jnp.pad(C, ((0, Mpad - M), (0, dp - d)))
    up = jnp.pad(u2, ((0, Mpad - M), (0, pp - p)))

    has_v = v2 is not None
    has_mask = row_mask is not None
    in_specs = [
        pl.BlockSpec((bm, dp), lambda i, j: (i, 0)),          # X_i
        pl.BlockSpec((bn, dp), lambda i, j: (j, 0)),          # C_j
        pl.BlockSpec((bn, pp), lambda i, j: (j, 0)),          # u_j
    ]
    operands = [Xp, Cp, up]
    if has_v:
        vp = jnp.pad(v2, ((0, npad - n), (0, pp - p)))
        in_specs.append(pl.BlockSpec((bm, pp), lambda i, j: (i, 0)))  # v_i
        operands.append(vp)
    if has_mask:
        # (n,) -> (npad, LANE) with the mask in column 0 (lane-aligned
        # operand; the kernel reads [:, :1])
        mk = row_mask.astype(jnp.float32).reshape(n, 1)
        operands.append(jnp.pad(mk, ((0, npad - n), (0, LANE - 1))))
        in_specs.append(pl.BlockSpec((bm, LANE), lambda i, j: (i, 0)))

    scratch = [
        pltpu.VMEM((nbj, bm, bn), jnp.float32),   # Gram row strip
        pltpu.VMEM((bm, pp), jnp.float32),        # t_i = K_i u + v_i
        pltpu.VMEM((nbj, bn, pp), jnp.float32),   # fp32 w accumulator
    ]
    if compensated:
        scratch += [
            pltpu.VMEM((bm, pp), jnp.float32),        # t Kahan carry
            pltpu.VMEM((nbj, bn, pp), jnp.float32),   # w Kahan carry
        ]
    out, cnt = pl.pallas_call(
        functools.partial(
            _fused_sweep_kernel, spec=spec, has_v=has_v, has_mask=has_mask,
            compensated=compensated,
            n_valid=n, m_valid=M, bm=bm, bn=bn, nbi=nbi, nbj=nbj),
        grid=(nbi, nbj),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((nbj, bn, pp), lambda i, j: (0, 0, 0)),   # w
            pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                         memory_space=pltpu.SMEM),                 # tile count
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nbj, bn, pp), out_dtype),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
    )(*operands)

    w = out.reshape(Mpad, pp)[:M,:p]
    if squeeze:
        w = w[:, 0]
    if return_tile_count:
        return w, cnt[0, 0]
    return w


# ---------------------------------------------------------------------------
# j-sharded sweep: out-of-core M — Gram never resident, t spilled to HBM
# ---------------------------------------------------------------------------
def sharded_sweep_pallas(
    X: Array,
    C: Array,
    u: Array,
    v: Array | None,
    *,
    spec: KernelSpec,
    row_mask: Array | None = None,
    shard_m: int = 8192,
    block_m: int = 256,
    block_n: int = 512,
    compensated: bool = False,
    t_dtype=None,
    out_dtype=None,
    interpret: bool = True,
) -> Array:
    """w = K(X,C)^T (K(X,C) u + v) for M far beyond the fused kernel's reach.

    The fused single-pass sweep holds a (bm, Mpad) Gram row strip plus the
    (Mpad, p) accumulator in VMEM, which caps M near ~8k at default tiles.
    Past that a tile cannot wait in VMEM for the final ``t_i`` it needs for
    the transposed product, so each Gram entry must be evaluated twice — the
    out-of-core schedule of Meanti et al. (2020). This variant does exactly
    that, in two Pallas phases with only O(tile) VMEM state:

    1. **forward** — ``t = K(X, C) u + v`` in one pass streaming C through
       (bn, d) tiles, the v-add fused into the accumulator init (no extra
       HBM round-trip for ``K u``); ``t`` (n, p) spills to HBM.
    2. **transpose, j-major** — the center axis is partitioned into
       ``shard_m``-row shards; each shard runs its own Pallas pass computing
       ``w_j = K(C_j, X) t`` with partial ``w_j`` accumulated per (bm, p)
       C-tile in VMEM and flushed to HBM when the tile's row sweep ends.
       The final reduction is the concatenation of the shard outputs.

    Per-phase VMEM is O(bm*d + bn*d + bm*p + bn*p) — independent of M and n —
    so M scales to 10^5+; ``shard_m`` only bounds the per-``pallas_call`` HBM
    workspace (each shard pads its C rows to lane multiples) and is picked by
    the planner in ``repro.ops.base``. Cost: 2 Gram evaluations per tile vs
    the fused kernel's 1 — the price of not holding the strip. Under the bf16
    policy ``t_dtype`` (the policy's storage dtype) makes the HBM-spilled
    ``t`` — the dominant O(n*p) HBM round-trip of this path — move at half
    width, while ``out_dtype`` (the policy's coefficient dtype) keeps the
    final M-sized w full precision.
    """
    M = C.shape[0]
    squeeze = u.ndim == 1
    u2 = u[:, None] if squeeze else u
    v2 = None if v is None else (v[:, None] if squeeze else v)

    t = kernel_matmul_pallas(
        X,
        C,
        u2,
        spec=spec,
        add=v2,
        block_m=block_m,
        block_n=block_n,
        compensated=compensated,
        out_dtype=t_dtype,
        interpret=interpret,
    )
    if row_mask is not None:
        # zeroing masked rows of the HBM-spilled t zeroes their K^T t
        # contribution EXACTLY (the transpose phase only ever reads t)
        t = t * row_mask.astype(t.dtype)[:, None]

    shard = max(int(shard_m), 1)
    ws = [
        kernel_matmul_pallas(C[j0:min(j0 + shard, M)], X, t, spec=spec,
                             block_m=block_m, block_n=block_n,
                             compensated=compensated, out_dtype=out_dtype,
                             interpret=interpret)
        for j0 in range(0, M, shard)
    ]
    w = ws[0] if len(ws) == 1 else jnp.concatenate(ws, axis=0)
    return w[:, 0] if squeeze else w


# ---------------------------------------------------------------------------
# pairwise kernel: K(A, B) materialized (preconditioner's K_MM builder)
# ---------------------------------------------------------------------------
def _pairwise_kernel(a_ref, b_ref, o_ref, *, spec: KernelSpec):
    o_ref[...] = _tile(a_ref[...], b_ref[...], spec).astype(o_ref.dtype)


def pairwise_kernel_pallas(
    A: Array,
    B: Array,
    *,
    kind: str = "gaussian",
    scale: float = 1.0,
    spec: KernelSpec | None = None,
    block_m: int = 256,
    block_n: int = 256,
    interpret: bool = True,
) -> Array:
    """Materialize K(A, B) tile-by-tile (used to build K_MM for the
    preconditioner). Grid (i, j) with one output tile per step."""
    spec = _as_spec(kind, scale, spec)
    m, d = A.shape
    n, _ = B.shape
    bm = min(_round_up(block_m, SUBLANE), _round_up(m, SUBLANE))
    bn = min(_round_up(block_n, LANE), _round_up(n, LANE))
    mp = _round_up(m, bm)
    np_ = _round_up(n, bn)
    dp = _round_up(d, LANE)
    Ap = jnp.pad(A, ((0, mp - m), (0, dp - d)))
    Bp = jnp.pad(B, ((0, np_ - n), (0, dp - d)))

    out = pl.pallas_call(
        functools.partial(_pairwise_kernel, spec=spec),
        grid=(mp // bm, np_ // bn),
        in_specs=[
            pl.BlockSpec((bm, dp), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, dp), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), A.dtype),
        interpret=interpret,
    )(Ap, Bp)
    return out[:m,:n]
