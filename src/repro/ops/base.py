"""The ``KernelOps`` backend protocol and registry.

FALKON's entire O(n sqrt(n)) time budget reduces to three primitives over an
(n, d) dataset ``X``, (M, d) Nystrom centers ``C`` and coefficient vectors:

    sweep(X, C, u, v)  =  K(X,C)^T (K(X,C) u + v)    — one CG iteration
    apply(X, C, u)     =  K(X,C) u                    — the prediction path
    gram(A, B)         =  K(A, B)                     — the preconditioner path

A ``KernelOps`` backend implements exactly these three, parameterized by a
kernel object carrying a declarative ``KernelSpec`` (see
``repro.core.kernels``). Backends are selected by name from a registry:

    ops = get_ops("pallas", kernel, block_size=2048, precision="bf16")
    w = ops.sweep(X, C, u, v)

Registered implementations:

* ``"jnp"``    — pure-jnp blocked reference (lax.scan over row blocks); runs
                 anywhere, fp32/fp64, the numerical ground truth.
* ``"pallas"`` — fused TPU path: the sweep is ONE Pallas pass that computes
                 each Gram tile once (see ``repro.kernels.kernel_matvec``).

Everything above this layer (core/matvec.py, core/falkon.py, the distributed
shard_map wrapper, serving, benchmarks) talks to a KernelOps and never to a
concrete kernel implementation. This module deliberately has no imports from
``repro.core`` or ``repro.kernels`` so it can never participate in an import
cycle; backends duck-type the kernel via its ``spec`` attribute / call.

``precision`` is the storage/accumulate policy of the hot loop, resolved to a
:class:`PrecisionPolicy` (a name is just a registry key):

* ``"fp32"`` (default) — every buffer float32 (or float64 under x64), plain
  accumulation. Numerically identical to the pre-policy code path.
* ``"bf16"`` — END-TO-END bfloat16 storage for every DATA-SPACE (n-sized)
  buffer: X, C, the v term, the forward buffer ``t`` (including its HBM
  spill in the j-sharded sweep), the CG iterates, and the streamed
  host->device chunks — the full 2x HBM-footprint/bandwidth win, since the
  sweep's traffic is dominated by n-sized objects — while every contraction
  accumulates in float32 with Kahan/two-sum COMPENSATION inside the tile
  loops, so the reduction error stays O(eps_fp32) instead of growing with
  the tile count. Per-buffer overrides keep three things float32: ``gram``
  (the preconditioner's Cholesky input), ``cholesky`` (the factors), and
  ``coeffs`` — the M-sized coefficient vectors crossing the sweep boundary
  (u in, w out). The last one is measured, not taste: quantizing u/w makes
  the PRECONDITIONED operator nonlinear at the quantization scale, the
  triangular solves amplify that noise, and CG stalls near 1e-1 relative
  residual (vs 5e-4 with fp32 coeffs); u/w are O(M*p) so keeping them wide
  costs no meaningful bandwidth. The bf16 CG iterates are safe precisely
  because the operator stays exact-at-the-point (see repro.core.cg).

Error model (tested against an fp64 oracle in tests/test_precision.py and
measured by benchmarks/precision_sweep.py): with bf16 storage the dominant
term is input/vector quantization, |w - w_fp64| / |w_fp64| <= c * eps_bf16
with eps_bf16 = 2^-8 ~= 3.9e-3; compensated fp32 accumulation keeps the
summation term at O(eps_fp32) independent of n/M, so the documented
end-to-end ceiling is 1e-2 relative across all registered kernels.

This module also hosts the two memory planners — pure static-shape
arithmetic (no jax, safe at trace time), each emitting a structured warning
carrying the full plan when it routes off the default path:

* :func:`plan_sweep` -> :class:`SweepPlan` (+ ``SweepPlanWarning``): routes
  a sweep fused -> two_pass -> j_sharded against the VMEM budget
  (``REPRO_VMEM_BUDGET_MB``).
* :func:`plan_factor` -> :class:`FactorPlan` (+ ``FactorPlanWarning``):
  routes the preconditioner's O(M^2) Cholesky factors incore -> blocked
  against a device-memory budget (``REPRO_FACTOR_BUDGET_MB``; else derived
  from the devices' free memory by :func:`device_factor_budget`, 512 MB at
  least and where there is no reading). The blocked path
  (``repro.kernels.blocked_cholesky``, consumed by
  ``repro.core.preconditioner``) keeps the factor host-resident and
  bounds peak device bytes at ``FactorPlan.device_ceiling_bytes`` =
  3 * 2 * block * M * itemsize — O(b*M), not O(M^2). ``tile_dtype`` honors
  the PrecisionPolicy ``cholesky`` override (float32 floor; see above).
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Protocol, runtime_checkable

PRECISIONS = ("fp32", "bf16")

#: dtype-name -> bytes, kept local so this module stays jax-import-free.
_ITEMSIZE = {
    "float64": 8,
    "float32": 4,
    "bfloat16": 2,
    "float16": 2,
    "float8_e4m3fn": 1,
    "float8_e5m2": 1,
}


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """The full precision contract of the FALKON hot loop.

    ``storage`` is the dtype the DATA-SPACE (n-sized) buffers live in (HBM
    footprint and host->device transfer width): X, C, v, the forward buffer
    ``t`` and its j-sharded HBM spill, the streamed chunks, and the CG
    iterates. ``accumulate`` is the dtype every contraction reduces in (the
    MXU runs storage-in/accumulate-out via ``preferred_element_type``).
    With ``compensated=True`` the Pallas tile loops (and the jnp reference
    scan) carry a Kahan/two-sum compensation buffer next to each
    accumulator, so the summation error is O(eps_accumulate), independent
    of the number of tiles reduced. ``overrides`` pins individual buffers
    to a different storage dtype — by default three stay float32:

    * ``gram`` / ``cholesky`` — the preconditioner's K_MM is one-shot
      O(M^2) work with no bandwidth win to harvest, and quantizing it can
      push a borderline-PSD matrix indefinite.
    * ``coeffs`` — the M-sized coefficient vectors at the sweep boundary
      (u in, w out). Quantizing them makes the preconditioned CG operator
      nonlinear at eps_storage scale, which the triangular solves amplify
      into a ~1e-1 residual stall (measured in tests/test_precision.py);
      they are O(M*p), so float32 costs nothing against the n-sized
      buffers the policy shrinks.

    CG scalars (alpha, beta, residual norms) are ALWAYS computed in
    ``accumulate`` precision regardless of ``storage`` — see repro.core.cg.
    """

    name: str
    storage: str = "float32"
    accumulate: str = "float32"
    compensated: bool = False
    overrides: tuple[tuple[str, str], ...] = (
        ("gram", "float32"), ("cholesky", "float32"), ("coeffs", "float32")
    )

    def buffer_dtype(self, buffer: str) -> str:
        """Storage dtype for a named buffer, honoring per-buffer overrides."""
        return dict(self.overrides).get(buffer, self.storage)

    @property
    def storage_itemsize(self) -> int:
        return _ITEMSIZE[self.storage]

    @property
    def accumulate_itemsize(self) -> int:
        return _ITEMSIZE[self.accumulate]

    @property
    def coeffs_itemsize(self) -> int:
        return _ITEMSIZE[self.buffer_dtype("coeffs")]


#: Named policies ``get_ops(precision=...)`` accepts as strings.
POLICIES: dict[str, PrecisionPolicy] = {
    "fp32": PrecisionPolicy(name="fp32"),
    "bf16": PrecisionPolicy(name="bf16", storage="bfloat16",
                            accumulate="float32", compensated=True),
}


def resolve_precision(precision) -> PrecisionPolicy:
    """Resolve a policy name (or pass through a ``PrecisionPolicy``)."""
    if isinstance(precision, PrecisionPolicy):
        return precision
    if precision in POLICIES:
        return POLICIES[precision]
    raise ValueError(
        f"unknown precision {precision!r}; supported: {PRECISIONS} "
        f"(or a PrecisionPolicy instance)")

SWEEP_PATHS = ("fused", "two_pass", "j_sharded", "jnp")

#: Default VMEM budget for the fused sweep's scratch + pipelined IO tiles.
#: Real TPUs fail to compile somewhere past ~16MB of requested VMEM; 12MB
#: leaves headroom for the compiler's own allocations. Override per-process
#: with ``REPRO_VMEM_BUDGET_MB`` or per-call via ``plan_sweep(vmem_budget=)``.
DEFAULT_VMEM_BUDGET = 12 * 2**20

_LANE = 128  # MXU lane width — mirrors repro.kernels.kernel_matvec.LANE


def _vmem_budget() -> int:
    mb = os.environ.get("REPRO_VMEM_BUDGET_MB")
    return int(float(mb) * 2**20) if mb else DEFAULT_VMEM_BUDGET


@dataclasses.dataclass(frozen=True)
class SweepPlan:
    """The sweep-path decision for one (n, M, d, p) problem, with the budget
    numbers that produced it — exposed via ``KernelOps.plan()`` so tests and
    benchmarks can assert on routing instead of reverse-engineering it."""

    path: str                  # one of SWEEP_PATHS
    n: int
    M: int
    d: int
    p: int                     # TOTAL column width charged (= systems * p_rhs)
    block_m: int               # (bm, bn) tile dims the sweep runs with
    block_n: int
    shard_m: int | None        # C-shard rows for the j_sharded path
    scratch_bytes: int         # fused-path VMEM scratch estimate
    io_bytes: int              # double-buffered operand/output tiles
    vmem_budget_bytes: int
    reason: str
    input_dtype: str = "float32"    # X/C storage dtype
    vector_dtype: str = "float32"   # v/t data-space storage dtype
    accum_dtype: str = "float32"    # contraction accumulate dtype
    coeffs_dtype: str = "float32"   # u-in / w-out coefficient dtype
    compensated: bool = False       # Kahan carry buffers counted in scratch
    systems: int = 1                # stacked lam-path systems sharing the sweep

    @property
    def total_bytes(self) -> int:
        return self.scratch_bytes + self.io_bytes

    @property
    def hbm_bytes(self) -> int:
        """Storage-dtype HBM working set of one sweep: X, C, v and the
        forward buffer t (spilled on the out-of-core paths) at storage
        width, plus the M-sized u/w at coefficient width. This is the
        footprint the bf16 policy halves (the n-sized terms dominate) —
        the planner-model number the precision benchmark reports as
        headroom."""
        in_item = _ITEMSIZE[self.input_dtype]
        vec_item = _ITEMSIZE[self.vector_dtype]
        co_item = _ITEMSIZE[self.coeffs_dtype]
        return (in_item * (self.n + self.M) * self.d
                + vec_item * 2 * self.n * self.p
                + co_item * 2 * self.M * self.p)


def plan_sweep(
    n: int,
    M: int,
    d: int,
    p: int = 1,
    *,
    bm: int,
    bn: int,
    systems: int = 1,
    itemsize: int = 4,
    vec_itemsize: int | None = None,
    coeffs_itemsize: int | None = None,
    acc_itemsize: int = 4,
    compensated: bool = False,
    policy: "PrecisionPolicy | None" = None,
    vmem_budget: int | None = None,
    shard_m: int | None = None,
) -> SweepPlan:
    """Pick fused / two-pass / j-sharded from a VMEM budget model.

    The fused single-pass sweep needs, in VMEM: the (bm, Mpad) accumulate-
    dtype Gram row strip; the (Mpad, pp) w accumulator and (bm, pp) forward
    block in the accumulate dtype (doubled when ``compensated`` — each
    accumulator carries a same-shape Kahan compensation buffer); the
    (Mpad, pp) w OUTPUT buffer at ``coeffs_itemsize``; plus double-buffered
    input/output tiles — ``itemsize`` bytes for the X/C tiles,
    ``vec_itemsize`` for the data-space v tile and ``coeffs_itemsize`` for
    the u tile (the pre-policy model wrongly charged every vector at 4
    bytes regardless of its storage dtype). When the total exceeds the
    budget the sweep must evaluate each Gram tile twice, and the only
    question left is the C-shard granularity: ``shard_m`` is sized so one
    shard's padded storage-dtype copy stays within the budget-scaled HBM
    workspace. A single shard covering all of M degenerates to the classic
    two-pass composition.

    ``systems`` is the lam-path stacking factor: the path solver stacks L
    independent regularization systems along the column axis so one data
    sweep serves all of them, which means every p-sized term above is
    charged at the WIDENED width ``p * systems`` — a fat path that no
    longer fits the fused budget must route to two_pass/j_sharded exactly
    as a fat multi-rhs would (the plan records the effective ``p`` and the
    ``systems`` factor separately). Passing the stacked width directly as
    ``p`` is equivalent; ``systems`` exists so callers planning a path can
    ask about it without pre-multiplying.

    ``policy`` (a :class:`PrecisionPolicy`) is the preferred way to set the
    dtype knobs; explicit ``itemsize``/``vec_itemsize``/``compensated``
    remain for direct calls. Pure arithmetic on static shapes — safe to call
    at trace time, no jax imports (this module must stay import-cycle-free).
    """
    _names = {8: "float64", 4: "float32", 2: "bfloat16"}
    if policy is not None:
        itemsize = policy.storage_itemsize
        vec_itemsize = policy.storage_itemsize
        coeffs_itemsize = policy.coeffs_itemsize
        acc_itemsize = policy.accumulate_itemsize
        compensated = policy.compensated
        # dtype NAMES come straight from the policy (the itemsize map below
        # cannot tell float16 from bfloat16)
        names = dict(
            input_dtype=policy.storage,
            vector_dtype=policy.storage,
            accum_dtype=policy.accumulate,
            coeffs_dtype=policy.buffer_dtype("coeffs"),
        )
    else:
        names = None
    if vec_itemsize is None:
        vec_itemsize = itemsize if itemsize >= 4 else 4
    if coeffs_itemsize is None:
        coeffs_itemsize = vec_itemsize
    if names is None:
        names = dict(
            input_dtype=_names.get(itemsize, "float32"),
            vector_dtype=_names.get(vec_itemsize, "float32"),
            accum_dtype=_names.get(acc_itemsize, "float32"),
            coeffs_dtype=_names.get(coeffs_itemsize, "float32"),
        )
    if vmem_budget is None:
        vmem_budget = _vmem_budget()
    systems = max(systems, 1)
    p = max(p, 1) * systems
    Mpad = -(-M // _LANE) * _LANE
    dp = -(-d // _LANE) * _LANE
    pp = -(-p // _LANE) * _LANE
    acc = acc_itemsize * (Mpad * pp + bm * pp)      # w + t accumulators
    if compensated:
        acc *= 2                                    # Kahan carry buffers
    scratch = (acc_itemsize * bm * Mpad             # Gram row strip
               + acc
               + coeffs_itemsize * Mpad * pp)       # w output buffer
    io = 2 * (itemsize * (bm + bn) * dp            # X_i / C_j tiles
              + coeffs_itemsize * bn * pp          # u_j tile
              + vec_itemsize * bm * pp)            # v_i tile
    base = dict(
        n=n,
        M=M,
        d=d,
        p=p,
        block_m=bm,
        block_n=bn,
        scratch_bytes=scratch,
        io_bytes=io,
        vmem_budget_bytes=vmem_budget,
        compensated=compensated,
        systems=systems,
        **names,
    )

    if scratch + io <= vmem_budget:
        return SweepPlan(
            path="fused", shard_m=None,
            reason=(f"fused scratch {scratch}B + io {io}B fits the "
                    f"{vmem_budget}B VMEM budget"),
            **base)

    if shard_m is None:
        # one shard's padded storage-dtype C copy ~ one budget of HBM
        # workspace
        shard_m = max(bn, vmem_budget // (itemsize * dp))
    shard_m = max(bn, (int(shard_m) // bn) * bn)
    over = (f"fused scratch {scratch}B + io {io}B exceeds the "
            f"{vmem_budget}B VMEM budget")
    if shard_m >= M:
        return SweepPlan(
            path="two_pass",
            shard_m=None,
            reason=f"{over}; single C-shard covers M={M} — two-pass sweep",
            **base,
        )
    return SweepPlan(
        path="j_sharded", shard_m=shard_m,
        reason=(f"{over}; j-sharded sweep over "
                f"{-(-M // shard_m)} C-shards of {shard_m} rows"),
        **base)


class SweepPlanWarning(UserWarning):
    """Structured fallback notice: the fused single-pass sweep did not fit
    the VMEM budget and a 2-evaluations-per-tile path was chosen. Carries the
    full ``SweepPlan`` as ``.plan`` for programmatic inspection."""

    def __init__(self, plan: SweepPlan):
        self.plan = plan
        super().__init__(
            f"falkon sweep (n={plan.n}, M={plan.M}, d={plan.d}, p={plan.p}): "
            f"taking the {plan.path!r} path — {plan.reason}")


# ---------------------------------------------------------------------------
# Factorization planning: in-core vs blocked (out-of-core) Cholesky
# ---------------------------------------------------------------------------
FACTOR_PATHS = ("incore", "blocked")

#: Budget for a DENSE in-core Cholesky factor where no device reading is
#: given. FALKON's statistical optimality wants M ~ sqrt(n) Nystrom centers,
#: and the preconditioner's O(M^2) factors are the first thing that stops
#: fitting as M grows: a dense fp32 factor is 1 GB at M = 16384 and 40 GB at
#: M = 10^5. Past the budget ``plan_factor`` routes to the blocked
#: right-looking Cholesky (``repro.kernels.blocked_cholesky``), which keeps
#: the matrix host-resident in (b, b) tiles and holds only O(b * M) panel
#: bytes device-resident at any moment. ``plan_factor`` itself plans against
#: this number; the preconditioner (``resolve_factor_plan``) derives the
#: budget from the free memory of the devices K_MM lives on, never below
#: this floor, and keeps it where a device gives no reading (the CPU
#: backend). ``REPRO_FACTOR_BUDGET_MB`` overrides both per process (the
#: forcing knob tests use, mirroring ``REPRO_VMEM_BUDGET_MB``).
DEFAULT_FACTOR_BUDGET = 512 * 2**20

#: Blocked-path tile bounds: lane-aligned (multiples of _LANE*2 = 256) so the
#: Pallas tile kernels need no ragged-edge handling inside the hot loop.
_FACTOR_BLOCK_MIN = 256
_FACTOR_BLOCK_MAX = 2048


#: Rows per slab of the Pallas factor kernels' column elimination
#: (``repro.kernels.blocked_cholesky``): their temporaries are (slab, b).
FACTOR_CHUNK = 256


def factor_tile_vmem_bytes(kind: str, b: int, rows: int = 0) -> int:
    """VMEM a Pallas factor tile kernel holds at (padded) panel width ``b``
    (fp32 tiles: the ``cholesky`` override's floor).

    ``"potrf"``: the (b, b) input and output tiles (no grid). ``"trsm"``:
    the single-buffered (b, b) U and the double-buffered (rows, b) A and X
    tiles. Both add four (FACTOR_CHUNK, b) slabs of elimination
    temporaries. (The trailing-update GEMM runs on fixed small tiles and
    needs no model.)"""
    slabs = 4 * FACTOR_CHUNK * b * 4
    if kind == "potrf":
        return 2 * b * b * 4 + slabs
    if kind == "trsm":
        return b * b * 4 + 4 * rows * b * 4 + slabs
    raise ValueError(f"unknown factor tile kernel {kind!r}")


def factor_block_cap() -> int:
    """The widest lane-aligned panel whose Pallas POTRF tile (the one tile
    held whole in VMEM) and a one-slab TRSM tile fit the VMEM budget."""
    budget = _vmem_budget()
    b = _FACTOR_BLOCK_MAX
    while b > _FACTOR_BLOCK_MIN and (
        factor_tile_vmem_bytes("potrf", b) > budget
        or factor_tile_vmem_bytes("trsm", b, FACTOR_CHUNK) > budget
    ):
        b -= _FACTOR_BLOCK_MIN
    return b


def _factor_budget() -> int:
    mb = os.environ.get("REPRO_FACTOR_BUDGET_MB")
    return int(float(mb) * 2**20) if mb else DEFAULT_FACTOR_BUDGET


def device_factor_budget(free_bytes: int | None, peak_factors: int) -> int:
    """The dense-factor budget for a factorization on a device with
    ``free_bytes`` free (None: no reading), whose in-core route holds
    ``peak_factors`` dense factors at its peak: ``free_bytes //
    peak_factors``, never below ``DEFAULT_FACTOR_BUDGET``, so a factor
    routed in-core by the default stays in-core on any device. Without a
    reading, or with ``REPRO_FACTOR_BUDGET_MB`` set, what
    :func:`plan_factor` plans against by default."""
    if free_bytes is None or os.environ.get("REPRO_FACTOR_BUDGET_MB"):
        return _factor_budget()
    return max(DEFAULT_FACTOR_BUDGET, free_bytes // peak_factors)


@dataclasses.dataclass(frozen=True)
class FactorPlan:
    """The Cholesky-path decision for one (M, M) factorization — the
    ``SweepPlan`` sibling for the preconditioner stack, exposed so tests and
    benchmarks can assert on routing and on the device-residency model
    instead of reverse-engineering them.

    ``dense_bytes`` is what the in-core path keeps device-resident (the
    factor itself, before LAPACK workspace); ``panel_bytes`` is the blocked
    path's algorithmic working set — the current factor panel plus one
    trailing column panel, 2 * block * M * itemsize — the O(b * M) bound the
    acceptance tests measure against (with slack for XLA temporaries; see
    ``device_ceiling_bytes``).
    """

    path: str                  # one of FACTOR_PATHS
    M: int
    block: int | None          # (b, b) tile side for the blocked path
    itemsize: int              # bytes per element of the factor dtype
    dense_bytes: int           # M * M * itemsize — in-core factor residency
    panel_bytes: int           # 2 * block * M * itemsize — blocked working set
    factor_budget_bytes: int
    reason: str
    tile_dtype: str = "float32"   # in-tile compute dtype (policy `cholesky`
    #                               override: fp32 floor even under bf16
    #                               storage — the PR 3 measured constraint)
    free_bytes: int = -1          # least free bytes over K_MM's devices when
    #                               the plan was resolved; -1: no reading

    @property
    def device_ceiling_bytes(self) -> int:
        """The bound the blocked path's measured peak device residency must
        stay under: 3x the two-panel model, covering the update's output
        buffer and transient XLA copies. Still O(b * M) — the point is that
        it does not scale with M^2."""
        return 3 * self.panel_bytes


def plan_factor(
    M: int,
    *,
    itemsize: int = 4,
    policy: "PrecisionPolicy | None" = None,
    block: int | None = None,
    factor_budget: int | None = None,
) -> FactorPlan:
    """Pick in-core vs blocked Cholesky from a dense-factor budget model.

    In-core ``jnp.linalg.cholesky`` keeps the full (M, M) factor (plus the
    jittered input and LAPACK workspace) device-resident: ``M^2 * itemsize``
    bytes. When that exceeds the budget the factorization routes to the
    tiled right-looking blocked path, whose device working set is two
    (M, block) panels. ``block`` is sized so those panels fit the budget
    (lane-aligned, at least {_FACTOR_BLOCK_MIN}) and capped by
    :func:`factor_block_cap`, so the Pallas tile kernels fit VMEM at any
    block the planner picks.

    ``policy`` pins the in-tile compute dtype through the ``cholesky``
    per-buffer override — float32 by default even under the bf16 storage
    policy (quantized factors destabilize the preconditioned CG operator;
    the PR 3 measured constraint). ``itemsize`` is the factor storage width
    (4 for fp32, 8 for x64 callers). Pure arithmetic on static shapes — safe
    at trace time, no jax imports (this module stays import-cycle-free).
    """
    if factor_budget is None:
        factor_budget = _factor_budget()
    tile_dtype = "float32"
    if policy is not None:
        tile_dtype = policy.buffer_dtype("cholesky")
        itemsize = max(_ITEMSIZE[tile_dtype], 4)  # fp32 floor
    dense = M * M * itemsize

    if block is None:
        # two (M, block) panels ~ one budget of device workspace, and no
        # wider than the Pallas tile kernels can hold in VMEM
        block = factor_budget // max(2 * M * itemsize, 1)
        block = (block // _FACTOR_BLOCK_MIN) * _FACTOR_BLOCK_MIN
        block = max(_FACTOR_BLOCK_MIN, min(factor_block_cap(), block))
    panel = 2 * block * M * itemsize
    base = dict(
        M=M,
        itemsize=itemsize,
        dense_bytes=dense,
        panel_bytes=panel,
        factor_budget_bytes=factor_budget,
        tile_dtype=tile_dtype,
    )

    if dense <= factor_budget:
        return FactorPlan(
            path="incore", block=None, panel_bytes=0,
            reason=(f"dense factor {dense}B fits the {factor_budget}B "
                    f"factor budget — in-core cholesky"),
            **{k: v for k, v in base.items() if k != "panel_bytes"})
    return FactorPlan(
        path="blocked", block=block,
        reason=(f"dense factor {dense}B exceeds the {factor_budget}B factor "
                f"budget — blocked right-looking cholesky over "
                f"{-(-M // block)} panels of {block} columns "
                f"(device working set ~{panel}B)"),
        **base)


class FactorPlanWarning(UserWarning):
    """Structured notice that a preconditioner factorization left the
    in-core path: the dense (M, M) factor exceeded the factor budget and the
    blocked out-of-core Cholesky was chosen (host-resident tiles, O(b * M)
    device-resident panels). Carries the full ``FactorPlan`` as ``.plan``."""

    def __init__(self, plan: FactorPlan):
        self.plan = plan
        super().__init__(
            f"falkon preconditioner (M={plan.M}): taking the {plan.path!r} "
            f"factor path — {plan.reason}")


# ---------------------------------------------------------------------------
# K_nM cache planning: device-resident vs host-streamed vs recompute
# ---------------------------------------------------------------------------
CACHE_TIERS = ("device", "host", "off")

#: Default device-memory budget for a materialized K_nM. The cached sweep
#: turns every CG iteration's kernel re-evaluation (the paper's one-full-
#: kernel-pass-per-sweep cost model) into two GEMMs over stored entries, so
#: the only question is where n*M*itemsize bytes live. Up to this budget the
#: cache is device-resident ("device" tier); past it the tiles are pinned
#: host-side and streamed ("host" tier, double-buffered via
#: ``repro.data.streaming.StreamingLoader``); past ``REPRO_KNM_HOST_BUDGET_MB``
#: the cache is refused outright ("off" — today's recompute path, bit-
#: identical). Override per-process with ``REPRO_KNM_BUDGET_MB`` (the
#: forcing knob tests use, mirroring ``REPRO_VMEM_BUDGET_MB``).
DEFAULT_KNM_BUDGET = 1024 * 2**20
DEFAULT_KNM_HOST_BUDGET = 8192 * 2**20


def _knm_budget() -> int:
    mb = os.environ.get("REPRO_KNM_BUDGET_MB")
    return int(float(mb) * 2**20) if mb is not None else DEFAULT_KNM_BUDGET


def _knm_host_budget() -> int:
    mb = os.environ.get("REPRO_KNM_HOST_BUDGET_MB")
    return int(float(mb) * 2**20) if mb is not None else DEFAULT_KNM_HOST_BUDGET


@dataclasses.dataclass(frozen=True)
class CachePlan:
    """The K_nM-residency decision for one (n, M) problem — the
    ``SweepPlan``/``FactorPlan`` sibling for the materialized-sweep cache,
    exposed so tests and benchmarks can assert on tier routing and on the
    bytes model instead of reverse-engineering them.

    ``cache_bytes`` is the full materialized K_nM at the policy's STORAGE
    width (the bf16 policy halves it — the cache composes with the
    precision work); ``shard_bytes`` is what one data shard actually holds
    (``DistributedOps`` caches only its local row block, so the budget is
    charged per shard — zero extra communication, the psum invariants are
    unchanged).
    """

    tier: str                  # one of CACHE_TIERS
    n: int
    M: int
    shards: int                # data shards splitting the rows (1 = local)
    itemsize: int              # bytes per stored kernel entry
    cache_bytes: int           # n * M * itemsize — the full cache
    shard_bytes: int           # per-shard residency the budgets are charged on
    budget_bytes: int          # device (HBM) budget
    host_budget_bytes: int     # pinned-host budget for the streamed tier
    reason: str
    storage_dtype: str = "float32"  # dtype the tiles are stored at


def plan_cache(
    n: int,
    M: int,
    *,
    itemsize: int = 4,
    policy: "PrecisionPolicy | None" = None,
    shards: int = 1,
    tier: str | None = None,
    budget: int | None = None,
    host_budget: int | None = None,
) -> CachePlan:
    """Pick the K_nM cache tier (device / host / off) from a bytes model.

    A cached fit evaluates each of the ceil(n/block) row tiles of K_nM
    exactly ONCE (via ``KernelOps.materialize``) and serves every later
    sweep/apply as GEMMs over the stored entries, so the decision is purely
    residency: ``n * M * itemsize`` bytes at the policy's storage width
    (``overrides`` do NOT apply — the cache deliberately stores at the
    data-space storage dtype to harvest the bf16 footprint halving;
    accumulation back to float32 happens in the GEMM consumers). Charged
    per data shard: a ``DistributedOps`` wrapper splits the rows over
    ``shards`` devices and each holds only its block.

    ``tier`` forces a specific tier (tests and the benchmark's routing
    table use it); ``None`` routes device -> host -> off against the
    budgets (``REPRO_KNM_BUDGET_MB`` / ``REPRO_KNM_HOST_BUDGET_MB``).
    Pure arithmetic on static shapes — safe at trace time, no jax imports
    (this module stays import-cycle-free).
    """
    if policy is not None:
        itemsize = policy.storage_itemsize
        storage_dtype = policy.storage
    else:
        storage_dtype = {8: "float64", 4: "float32", 2: "bfloat16"}.get(
            itemsize, "float32")
    if budget is None:
        budget = _knm_budget()
    if host_budget is None:
        host_budget = _knm_host_budget()
    shards = max(int(shards), 1)
    total = n * M * itemsize
    shard_bytes = -(-total // shards)
    base = dict(
        n=n,
        M=M,
        shards=shards,
        itemsize=itemsize,
        cache_bytes=total,
        shard_bytes=shard_bytes,
        budget_bytes=budget,
        host_budget_bytes=host_budget,
        storage_dtype=storage_dtype,
    )
    if tier is not None:
        if tier not in CACHE_TIERS:
            raise ValueError(
                f"unknown cache tier {tier!r}; supported: {CACHE_TIERS}")
        return CachePlan(tier=tier, reason=f"tier {tier!r} forced by caller",
                         **base)
    if shard_bytes <= budget:
        return CachePlan(
            tier="device",
            reason=(f"K_nM shard {shard_bytes}B fits the {budget}B device "
                    f"budget — device-resident cache"),
            **base)
    if shard_bytes <= host_budget:
        return CachePlan(
            tier="host",
            reason=(f"K_nM shard {shard_bytes}B exceeds the {budget}B device "
                    f"budget but fits the {host_budget}B host budget — "
                    f"host-pinned tiles, streamed sweeps"),
            **base)
    return CachePlan(
        tier="off",
        reason=(f"K_nM shard {shard_bytes}B exceeds the {host_budget}B host "
                f"budget — recompute path (no cache)"),
        **base)


class CachePlanWarning(UserWarning):
    """Structured notice that a requested K_nM cache routed off the
    device-resident default (host-streamed tiles, or refused entirely and
    fell back to the recompute path). Carries the full ``CachePlan`` as
    ``.plan`` for programmatic inspection."""

    def __init__(self, plan: CachePlan):
        self.plan = plan
        super().__init__(
            f"falkon K_nM cache (n={plan.n}, M={plan.M}, "
            f"shards={plan.shards}): taking the {plan.tier!r} tier — "
            f"{plan.reason}")


@runtime_checkable
class KernelOps(Protocol):
    """The three primitives the whole codebase needs — and nothing else
    (plus ``plan``, the introspectable routing decision behind ``sweep``)."""

    kernel: Any
    block_size: int
    precision: "str | PrecisionPolicy"

    def sweep(self, X, C, u, v=None, row_mask=None):
        """K(X,C)^T (K(X,C) u + v); ``v=None`` means v == 0.

        ``row_mask`` (n,), 0/1 (or None = all valid): rows with mask 0
        contribute EXACTLY zero to the result. The sweep is additive over
        rows, so this lets callers pad a ragged row chunk to a fixed shape
        (one XLA compile per fit instead of one per distinct chunk shape —
        see ``repro.data.streaming``) without changing the math.
        """
        ...

    def apply(self, X, C, u):
        """K(X,C) u — the prediction path."""
        ...

    def gram(self, A, B):
        """K(A, B) materialized — the preconditioner path."""
        ...

    def plan(self, n: int, M: int, d: int, p: int = 1, systems: int = 1) -> SweepPlan:
        """The sweep path this backend would take for these shapes.

        ``systems`` charges the lam-path stacking: the planner models the
        widened ``p * systems`` column block the path solve actually sweeps.
        """
        ...


_REGISTRY: dict[str, type] = {}


def register_ops(name: str):
    """Class decorator registering a KernelOps implementation under ``name``."""
    def deco(cls):
        cls.impl_name = name
        _REGISTRY[name] = cls
        return cls
    return deco


def available_ops() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get_ops(
    impl: str,
    kernel,
    *,
    block_size: int = 2048,
    precision: "str | PrecisionPolicy" = "fp32",
) -> KernelOps:
    """Construct the named backend for ``kernel``.

    ``kernel`` must carry a ``KernelSpec`` (anything built by
    ``repro.core.kernels.make_kernel`` / ``@register_kernel`` does).
    ``precision`` is a policy name ("fp32"/"bf16") or a full
    :class:`PrecisionPolicy`.
    """
    if impl not in _REGISTRY:
        raise ValueError(
            f"unknown KernelOps impl {impl!r}; registered: {available_ops()}"
        )
    resolve_precision(precision)  # validate early; backends resolve lazily
    return _REGISTRY[impl](kernel=kernel, block_size=block_size, precision=precision)


@dataclasses.dataclass(frozen=True)
class OpsBase:
    """Shared constructor shape for backends (kernel + static knobs)."""

    kernel: Any
    block_size: int = 2048
    precision: "str | PrecisionPolicy" = "fp32"

    @property
    def policy(self) -> PrecisionPolicy:
        """The resolved :class:`PrecisionPolicy` this backend runs under."""
        return resolve_precision(self.precision)


class CountingOps:
    """Invocation-counting facade over any :class:`KernelOps`.

    The instrumentation seam behind the lam-path acceptance claim: a path
    fit over L regularizers must issue ~1/L the ``sweep`` calls of L
    sequential fits, and "number of sweeps" is exactly what this wrapper
    counts. Pure delegation (same primitives, same results, same plan) plus
    the counters — ``sweeps``, ``applies``, ``grams``, and the K_nM-cache
    seam's quartet:

    * ``gram_tile_evals`` — kernel-entry evaluation work, in units of
      ceil(rows / block_size) row tiles, charged by every primitive that
      EVALUATES kernel entries (``sweep``, ``apply``, ``gram``,
      ``materialize``). This is the cache acceptance seam: a cached fit
      materializes each K_nM row tile exactly once, so its K_nM share of
      ``gram_tile_evals`` equals the tile count — where the recompute path
      charges it once per sweep/apply program point.
    * ``materializes`` / ``gemm_sweeps`` / ``gemm_applies`` — the cache-path
      primitives. The GEMM calls consume STORED entries and charge no
      ``gram_tile_evals``; that asymmetry is what makes the one-eval-per-
      tile claim provable by counting.

    The counters are PROGRAM-POINT counts, not executed-data-pass counts:
    a primitive called under a trace (``jax.jit``, or the matvec inside the
    scanned CG driver's ``lax.scan`` body) increments once at trace time no
    matter how many times the compiled program replays it. That is still
    the right invariant for the sharing claim — a solve whose scan body
    contains ONE sweep serving L systems counts 1 where L sequential solves
    count L, and both execute their traced sweep t times — but it means a
    fixed count does NOT scale with the iteration count t, and jitted
    facades (e.g. the streaming ``JittedOps``) count compilations, not
    calls.
    """

    def __init__(self, ops):
        self.ops = ops
        self.sweeps = 0
        self.applies = 0
        self.grams = 0
        self.gram_tile_evals = 0
        self.materializes = 0
        self.gemm_sweeps = 0
        self.gemm_applies = 0

    @property
    def kernel(self):
        return self.ops.kernel

    @property
    def block_size(self):
        return self.ops.block_size

    @property
    def precision(self):
        return self.ops.precision

    @property
    def policy(self):
        return self.ops.policy

    def _tiles(self, rows) -> int:
        bs = self.ops.block_size
        return -(-int(rows) // bs)

    def sweep(self, X, C, u, v=None, row_mask=None):
        self.sweeps += 1
        self.gram_tile_evals += self._tiles(X.shape[0])
        return self.ops.sweep(X, C, u, v, row_mask)

    def apply(self, X, C, u):
        self.applies += 1
        self.gram_tile_evals += self._tiles(X.shape[0])
        return self.ops.apply(X, C, u)

    def gram(self, A, B):
        self.grams += 1
        self.gram_tile_evals += self._tiles(A.shape[0])
        return self.ops.gram(A, B)

    def materialize(self, X, C):
        # ONE kernel evaluation per row tile — the only K_nM entry
        # evaluation a cached fit performs.
        self.materializes += 1
        self.gram_tile_evals += self._tiles(X.shape[0])
        return self.ops.materialize(X, C)

    def gemm_sweep(self, K, u, v=None, row_mask=None):
        # consumes STORED entries: no gram_tile_evals charge
        self.gemm_sweeps += 1
        return self.ops.gemm_sweep(K, u, v, row_mask)

    def gemm_apply(self, K, u):
        self.gemm_applies += 1
        return self.ops.gemm_apply(K, u)

    def plan(self, n: int, M: int, d: int, p: int = 1, systems: int = 1) -> SweepPlan:
        return self.ops.plan(n, M, d, p, systems)

    def reset(self) -> None:
        self.sweeps = self.applies = self.grams = 0
        self.gram_tile_evals = 0
        self.materializes = self.gemm_sweeps = self.gemm_applies = 0
