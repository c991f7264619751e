"""FALKON at 'large' scale with the Pallas hot loop and a device mesh.

    PYTHONPATH=src python examples/falkon_large_scale.py [--n 100000]
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python examples/falkon_large_scale.py --mesh 4x2

Demonstrates the paper's headline setting (n in the 10^5-10^6 range, M ~ sqrt
n) end to end: uniform Nystrom centers, Cholesky preconditioner, blocked CG
sweeps — optionally routed through the fused Pallas kernel (interpret mode
on CPU). Data-parallelism is one config field: ``FalkonConfig(mesh=...)``
wraps whichever backend is selected in ``repro.ops.DistributedOps``, which
shard_maps every sweep row-wise over the mesh data axes (one (M, p) psum
per CG iteration — see docs/architecture.md for the comm model and the
rest of the subsystem map).
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.core import FalkonConfig, falkon_fit
from repro.data.synthetic import KernelTask, make_kernel_dataset


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=100_000)
    ap.add_argument("--d", type=int, default=20)
    ap.add_argument("--centers", type=int, default=0, help="0 = 3*sqrt(n)")
    ap.add_argument("--iters", type=int, default=15)
    ap.add_argument("--mesh", default=None, help="e.g. 8 or 4x2")
    ap.add_argument(
        "--pallas",
        action="store_true",
        help="use the fused single-pass Pallas sweep backend",
    )
    ap.add_argument(
        "--precision",
        default="fp32",
        choices=("fp32", "bf16"),
        help="bf16 = bf16 inputs / fp32 accumulation",
    )
    args = ap.parse_args()
    enable_compile_cache()

    n = args.n
    M = args.centers or int(3 * n**0.5)
    task = KernelTask(
        "big", n=n, d=args.d, task="regression", sigma=4.0, lam=0.0, num_centers=0
    )
    X, y = make_kernel_dataset(jax.random.PRNGKey(0), task)
    Xte, yte = make_kernel_dataset(jax.random.PRNGKey(1), task, n=5000)

    mesh = None
    data_axes = ("data",)
    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split("x"))
        axes = ("data", "model")[:len(dims)]
        mesh = jax.make_mesh(dims, axes)
        data_axes = axes[:1]
        print(f"mesh: {dict(zip(axes, dims))} over {len(jax.devices())} devices")

    cfg = FalkonConfig(
        kernel="gaussian",
        kernel_params=(("sigma", 4.0),),
        lam=float(1 / n**0.5),
        num_centers=M,
        iterations=args.iters,
        block_size=4096,
        ops_impl="pallas" if args.pallas else "jnp",
        precision=args.precision,
        mesh=mesh,
        data_axes=data_axes,
    )
    print(f"n={n} d={args.d} M={M} t={args.iters} lam={cfg.lam:.2e} "
          f"impl={cfg.impl} precision={cfg.precision}")
    t0 = time.perf_counter()
    est, state = falkon_fit(jax.random.PRNGKey(2), X, y, cfg)
    jax.block_until_ready(est.alpha)
    dt = time.perf_counter() - t0
    mse = float(jnp.mean((est.predict(Xte) - yte) ** 2))
    print(f"fit in {dt:.1f}s; test MSE {mse:.4f}; "
          f"cond(W)={float(state.cond_estimate):.1f}; "
          f"final CG residual {float(state.residual_norms[-1]):.2e}")


if __name__ == "__main__":
    main()
