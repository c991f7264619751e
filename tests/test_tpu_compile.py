"""Ahead-of-time compiles of the chip path for a described TPU v5e.

Interpret mode hides what Mosaic refuses (unaligned slices, VMEM overruns),
so these tests compile the kernels of the main path with the TPU compiler,
for a ``v5e:2x2`` topology that is described, not attached:

* the sweep kernels at ``chip_smoke.py``'s widths (n = 1e6, d = 18): the
  two-pass sweep at M = 1e4 and the fused sweep at the widest M the
  planner keeps fused, each in fp32 and bf16;
* the blocked-Cholesky tile kernels at the blocks ``plan_factor`` picks
  for M = 1.2e4 and 2e4, including the ragged last panel;
* the ``DistributedOps`` sweep over a 4-device mesh, whose program must
  hold the one psum (an all-reduce) and the Pallas kernel.

Nothing runs: a compile that passes says nothing about results or times.
The topology is described inside a module fixture (never at import), so
only the worker that is given this file loads the TPU compiler.
"""
import os
import warnings
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import make_kernel
from repro.kernels import blocked_cholesky as bc
from repro.kernels import kernel_matvec
from repro.ops import DistributedOps, SweepPlanWarning, get_ops, plan_factor

N, D = 1_000_000, 18          # chip_smoke.py's SUSY-shaped fit
M_TWO_PASS = 10_000


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The backends ask ``interpret_mode()``, which sees this CPU process;
    steer them to the Mosaic path the chip takes, and keep these compiles
    out of any persistent cache (they cannot be read back without a chip)."""
    monkeypatch.setattr(kernel_matvec, "interpret_mode", lambda: False)
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", SweepPlanWarning)
        yield
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _shape(sharding, *shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fused_m_limit(ops) -> int:
    """The widest lane multiple of M the planner keeps on the fused path."""
    m = 128
    while ops.plan(N, m + 128, D, 1).path == "fused":
        m += 128
    return m


@pytest.mark.parametrize("precision", ["fp32", "bf16"])
@pytest.mark.parametrize("path", ["fused", "two_pass"])
def test_sweep_compiles_for_v5e(one_chip, compiled_kernels, path, precision):
    ops = get_ops("pallas", make_kernel("gaussian", sigma=4.0), precision=precision)
    M = M_TWO_PASS if path == "two_pass" else _fused_m_limit(ops)
    assert ops.plan(N, M, D, 1).path == path
    dt = jnp.bfloat16 if precision == "bf16" else jnp.float32
    args = (_shape(one_chip, N, D, dtype=dt), _shape(one_chip, M, D, dtype=dt),
            _shape(one_chip, M, 1), _shape(one_chip, N, 1, dtype=dt))
    compiled = jax.jit(ops.sweep).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_predict_and_gram_compile_for_v5e(one_chip, compiled_kernels):
    ops = get_ops("pallas", make_kernel("gaussian", sigma=4.0))
    C, alpha = _shape(one_chip, M_TWO_PASS, D), _shape(one_chip, M_TWO_PASS)
    for rows in (8, 1024, 100_000):      # serving rungs and the held-out set
        jax.jit(ops.apply).lower(_shape(one_chip, rows, D), C, alpha).compile()
    jax.jit(ops.gram).lower(C, C).compile()


def _factor_tiles(M: int, block: int):
    """(kernel, argument shapes) of every distinct tile the blocked driver
    runs for an M x M factor: the first panel (POTRF, the tallest TRSM and
    update) and the ragged last one."""
    rag = M % block
    tiles = [(bc._pallas_potrf, [(block, block)]),
             (bc._pallas_trsm, [(block, block), (M - block, block)]),
             (bc._pallas_update, [(M - block, block)] * 2 + [(block, block)])]
    if rag:
        tiles += [(bc._pallas_potrf, [(rag, rag)]),
                  (bc._pallas_update, [(rag, rag), (rag, block), (rag, block)])]
    return tiles


@pytest.mark.parametrize("M", [12_000, 20_000])
def test_blocked_cholesky_tiles_compile_at_planner_blocks(one_chip, compiled_kernels, M):
    plan = plan_factor(M)
    assert plan.path == "blocked" and plan.block >= 1024
    for kernel, shapes in _factor_tiles(M, plan.block):
        args = [_shape(one_chip, *s) for s in shapes]
        compiled = jax.jit(partial(kernel, interpret=False)).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()


def test_distributed_sweep_compiles_on_four_chips(topo, compiled_kernels):
    mesh = Mesh(np.asarray(topo.devices[:4]), ("data",))
    rows = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    ops = DistributedOps(get_ops("pallas", make_kernel("gaussian", sigma=4.0)), mesh)
    args = (_shape(rows, N, D), _shape(rep, M_TWO_PASS, D), _shape(rep, M_TWO_PASS, 1),
            _shape(rows, N, 1))
    compiled = jax.jit(ops.sweep).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert text.count("all-reduce(") + text.count("all-reduce-start(") == 1
    assert ops.psums == 1


@pytest.mark.parametrize("mesh_fit", [True, False])
def test_mesh_placed_scoring_compiles_on_four_chips(topo, compiled_kernels, mesh_fit):
    """A mesh fit leaves centers/alpha on the mesh; its estimator scores
    through DistributedOps (a shard_map) — a Pallas kernel fed mesh-placed
    arrays outside one cannot be partitioned by the TPU compiler."""
    from repro.core import FalkonEstimator
    mesh = Mesh(np.asarray(topo.devices[:4]), ("data",))
    rep = NamedSharding(mesh, P())
    kern = make_kernel("gaussian", sigma=4.0)

    def predict(X, C, alpha):
        est = FalkonEstimator(C, alpha, kern, ops_impl="pallas",
                              mesh=mesh if mesh_fit else None)
        return est.predict(X)

    args = (_shape(rep, 100_000, D), _shape(rep, M_TWO_PASS, D), _shape(rep, M_TWO_PASS))
    lowered = jax.jit(predict)
    if mesh_fit:
        assert "tpu_custom_call" in lowered.lower(*args).compile().as_text()
    else:
        with pytest.raises(NotImplementedError, match="cannot be automatically partitioned"):
            lowered.lower(*args)
