"""Kernel functions for FALKON, plus the declarative kernel-spec registry.

Each kernel is a small dataclass with ``__call__(X, Y) -> (n, m)`` returning the
Gram block K(X, Y). All kernels are positive definite, bounded (kappa^2 = K(x,x)
finite) per the paper's standing assumption, and written so the pairwise block is
a single MXU-friendly matmul plus cheap elementwise work.

Every kernel registered here carries a declarative :class:`KernelSpec`
(``kind`` string + static params tuple). The spec — not the Python class — is
what crosses the backend boundary: the ``repro.ops`` backends (jnp reference,
Pallas fused) and the Pallas kernel bodies all evaluate kernels through
:func:`tile_transform`, a pure function of the matmul precursors

    ab = A @ B^T,   a2 = ||a_i||^2,   b2 = ||b_j||^2

keyed by ``spec.kind``. This makes ``core/kernels.py`` the single source of
truth for kernel math: adding a kernel here (``@register_kernel``) makes it
available to every backend with no name-sniffing anywhere.
"""
from __future__ import annotations

import dataclasses
from typing import Protocol

import jax
import jax.numpy as jnp

Array = jax.Array

#: Matmul precision of the fit and scoring paths. XLA on a TPU runs an fp32
#: matmul at DEFAULT precision as one bf16 pass (about 3 significant
#: digits), which moves a Gram entry by ~1e-3 and breaks the fp32 policy's
#: contract; HIGHEST contracts fp32 operands in full fp32 and leaves bf16
#: operands (exact products) as they are.
FP32 = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """Declarative, hashable description of a kernel: (kind, static params).

    This is what backends receive instead of a Python object whose class name
    would have to be sniffed; ``params`` is a sorted tuple of (name, value)
    pairs so specs are hashable (usable as static jit/pallas arguments).
    """

    kind: str
    params: tuple[tuple[str, float], ...] = ()

    def as_dict(self) -> dict:
        return dict(self.params)


def _sqdist_of(ab: Array, a2: Array, b2: Array) -> Array:
    """||a||^2 + ||b||^2 - 2 a.b, clamped at 0 for numerical safety."""
    return jnp.maximum(a2 + b2 - 2.0 * ab, 0.0)


def tile_transform(ab: Array, a2: Array, b2: Array, spec: KernelSpec) -> Array:
    """Map matmul precursors to a Gram tile for any registered kernel kind.

    ``ab`` is (m, n) = A @ B^T; ``a2`` is (m, 1); ``b2`` is (1, n). Shared by
    the jnp reference path, the oracle in ``repro.kernels.ref``, and the Pallas
    kernel bodies — one formula per kernel, everywhere.
    """
    p = spec.as_dict()
    kind = spec.kind
    if kind == "gaussian":
        sigma = p.get("sigma", 1.0)
        return jnp.exp(-0.5 / (sigma * sigma) * _sqdist_of(ab, a2, b2))
    if kind == "laplacian":
        sigma = p.get("sigma", 1.0)
        d = jnp.sqrt(_sqdist_of(ab, a2, b2) + 1e-12)
        return jnp.exp(-d / sigma)
    if kind == "matern32":
        sigma = p.get("sigma", 1.0)
        r = jnp.sqrt(_sqdist_of(ab, a2, b2) + 1e-12)
        a = jnp.sqrt(3.0) * r / sigma
        return (1.0 + a) * jnp.exp(-a)
    if kind == "linear":
        scale = p.get("scale", 1.0)
        return ab / (scale * scale)
    if kind == "polynomial":
        scale = p.get("scale", 1.0)
        return (ab / (scale * scale) + p.get("c", 1.0)) ** int(p.get("degree", 2))
    raise ValueError(f"unknown kernel kind {spec.kind!r}; have {sorted(_REGISTRY)}")


def tile_eval(spec: KernelSpec, X: Array, Y: Array) -> Array:
    """K(X, Y) from a spec — the dense jnp evaluation every kernel's
    ``__call__`` reduces to (one matmul + VPU elementwise)."""
    a2 = jnp.sum(X * X, axis=-1, keepdims=True)            # (n, 1)
    b2 = jnp.sum(Y * Y, axis=-1, keepdims=True).T          # (1, m)
    ab = jnp.matmul(X, Y.T, precision=FP32)                # (n, m)  MXU
    return tile_transform(ab, a2, b2, spec)


def _sqdist(X: Array, Y: Array) -> Array:
    """Pairwise squared euclidean distances, (n, d) x (m, d) -> (n, m)."""
    xx = jnp.sum(X * X, axis=-1, keepdims=True)
    yy = jnp.sum(Y * Y, axis=-1, keepdims=True).T
    return jnp.maximum(xx + yy - 2.0 * jnp.matmul(X, Y.T, precision=FP32), 0.0)


class KernelFn(Protocol):
    def __call__(self, X: Array, Y: Array) -> Array: ...

    @property
    def kappa_sq(self) -> float: ...

    @property
    def spec(self) -> KernelSpec: ...


_REGISTRY: dict[str, type] = {}


def _make_spec(self) -> KernelSpec:
    return KernelSpec(
        kind=type(self).kind,
        params=tuple(sorted((f.name, getattr(self, f.name))
                            for f in dataclasses.fields(self))),
    )


def register_kernel(kind: str):
    """Register a kernel dataclass under ``kind`` and attach its ``spec``."""
    def deco(cls):
        cls.kind = kind
        cls.spec = property(_make_spec)
        _REGISTRY[kind] = cls
        return cls
    return deco


def spec_of(kernel) -> KernelSpec:
    """The KernelSpec of a kernel object (the only sanctioned way for a
    backend to learn what kernel it is running)."""
    spec = getattr(kernel, "spec", None)
    if isinstance(spec, KernelSpec):
        return spec
    if isinstance(kernel, KernelSpec):
        return kernel
    raise TypeError(
        f"{type(kernel).__name__} carries no KernelSpec; register it with "
        "@register_kernel in repro.core.kernels")


@register_kernel("gaussian")
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GaussianKernel:
    """K(x, y) = exp(-||x - y||^2 / (2 sigma^2)).  kappa^2 = 1."""

    sigma: float = dataclasses.field(metadata=dict(static=True), default=1.0)

    def __call__(self, X: Array, Y: Array) -> Array:
        return tile_eval(self.spec, X, Y)

    @property
    def kappa_sq(self) -> float:
        return 1.0


@register_kernel("laplacian")
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class LaplacianKernel:
    """K(x, y) = exp(-||x - y|| / sigma).  kappa^2 = 1."""

    sigma: float = dataclasses.field(metadata=dict(static=True), default=1.0)

    def __call__(self, X: Array, Y: Array) -> Array:
        return tile_eval(self.spec, X, Y)

    @property
    def kappa_sq(self) -> float:
        return 1.0


@register_kernel("matern32")
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Matern32Kernel:
    """Matern nu=3/2: (1 + sqrt(3) r / sigma) exp(-sqrt(3) r / sigma)."""

    sigma: float = dataclasses.field(metadata=dict(static=True), default=1.0)

    def __call__(self, X: Array, Y: Array) -> Array:
        return tile_eval(self.spec, X, Y)

    @property
    def kappa_sq(self) -> float:
        return 1.0


@register_kernel("linear")
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class LinearKernel:
    """K(x, y) = x.y / scale^2 (used for the YELP sparse-3gram experiment)."""

    scale: float = dataclasses.field(metadata=dict(static=True), default=1.0)

    def __call__(self, X: Array, Y: Array) -> Array:
        return tile_eval(self.spec, X, Y)

    @property
    def kappa_sq(self) -> float:  # bounded only on bounded domains; nominal
        return 1.0


@register_kernel("polynomial")
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PolynomialKernel:
    """K(x, y) = (x.y / scale^2 + c)^degree."""

    degree: int = dataclasses.field(metadata=dict(static=True), default=2)
    c: float = dataclasses.field(metadata=dict(static=True), default=1.0)
    scale: float = dataclasses.field(metadata=dict(static=True), default=1.0)

    def __call__(self, X: Array, Y: Array) -> Array:
        return tile_eval(self.spec, X, Y)

    @property
    def kappa_sq(self) -> float:
        return 1.0


def make_kernel(name: str, **kwargs) -> KernelFn:
    if name not in _REGISTRY:
        raise ValueError(f"unknown kernel {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name](**kwargs)


def available_kernels() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))
