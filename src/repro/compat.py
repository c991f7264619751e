"""The JAX APIs this repo calls through one import (repo pin: jax==0.9.0).

* ``enable_x64``  — ``jax.enable_x64``: ``with enable_x64(True): ...``
* ``use_mesh``    — ``jax.set_mesh``: ``with use_mesh(mesh): ...``
* ``shard_map``   — ``jax.shard_map`` with ``check_vma=False``: the
                    per-shard functions this repo maps end in ``psum``
                    reductions whose replication the static checker cannot
                    always prove. This is the one entry point the
                    distributed KernelOps backend uses.
* ``cost_analysis_dict`` — ``compiled.cost_analysis()`` as a dict.
"""
from __future__ import annotations

import jax

enable_x64 = jax.enable_x64
use_mesh = jax.set_mesh


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication check off (see above)."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def cost_analysis_dict(compiled) -> dict:
    """``compiled.cost_analysis()``, or ``{}`` for a trivial program."""
    return dict(compiled.cost_analysis() or {})
