"""Distributed-path tests.

These need >1 device, so they run in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the main test process
keeps the single real device, per the dry-run isolation rule).
"""
import os
import subprocess
import sys
import textwrap


SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(body: str) -> str:
    code = textwrap.dedent(body)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"   # a child never competes for the chip
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        env=env,
        timeout=600,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_distributed_sweep_matches_single_device():
    """DistributedOps.sweep over a (4,2) mesh data axis == the wrapped
    backend's sweep, for both jnp and pallas inner backends, with exactly
    one (M, p) psum of comm per call."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import GaussianKernel
        from repro.ops import DistributedOps, get_ops
        assert len(jax.devices()) == 8
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        kern = GaussianKernel(sigma=1.5)
        X = jax.random.normal(jax.random.PRNGKey(0), (512, 6))
        C = X[:64]
        u = jax.random.normal(jax.random.PRNGKey(1), (64,))
        v = jax.random.normal(jax.random.PRNGKey(2), (512,))
        for impl in ("jnp", "pallas"):
            inner = get_ops(impl, kern, block_size=64)
            ref = inner.sweep(X, C, u, v)
            dist = DistributedOps(inner, mesh, ("data",))
            got = dist.sweep(X, C, u, v)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=2e-4, atol=2e-3)
            # apply is row-local: no psum, bit-identical to the inner backend
            np.testing.assert_array_equal(
                np.asarray(dist.apply(X, C, u)),
                np.asarray(inner.apply(X, C, u)))
            assert dist.psums == 1, (impl, dist.psums)
            assert dist.psum_floats == 64, (impl, dist.psum_floats)
            print(impl, "OK")
    """)


def test_distributed_fit_matches_single_device():
    _run("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.core import FalkonConfig, falkon_fit
        mesh = jax.make_mesh((8,), ("data",))
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
        X = jax.random.normal(k1, (1024, 5))
        w = jax.random.normal(k2, (5,))
        y = jnp.sin(X @ w) + 0.05 * jax.random.normal(k3, (1024,))
        cfg = FalkonConfig(kernel="gaussian", kernel_params=(("sigma", 2.0),),
                           lam=1e-4, num_centers=128, iterations=20,
                           block_size=128)
        est_1, _ = falkon_fit(jax.random.PRNGKey(1), X, y, cfg)
        cfg_8 = dataclasses.replace(cfg, mesh=mesh)
        est_8, _ = falkon_fit(jax.random.PRNGKey(1), X, y, cfg_8)
        # alpha itself is ill-conditioned in fp32; predictions are the
        # well-posed quantity (same reason Thm 1 bounds excess risk, not alpha)
        p1, p8 = est_1.predict(X), est_8.predict(X)
        rel = float(jnp.linalg.norm(p8 - p1) / jnp.linalg.norm(p1))
        assert rel < 2e-3, rel
        # legacy mesh=/data_axes= kwargs are the same route as config.mesh
        est_kw, _ = falkon_fit(jax.random.PRNGKey(1), X, y, cfg, mesh=mesh,
                               data_axes=("data",))
        assert bool(jnp.all(est_kw.alpha == est_8.alpha))
        print("OK")
    """)


def test_distributed_fit_multipod_axes():
    """The FALKON sweep shards over BOTH ('pod','data') axes — the multi-pod
    configuration of DESIGN.md §6 in miniature."""
    _run("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.core import FalkonConfig, falkon_fit
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
        X = jax.random.normal(k1, (512, 5))
        w = jax.random.normal(k2, (5,))
        y = jnp.sin(X @ w) + 0.05 * jax.random.normal(k3, (512,))
        cfg = FalkonConfig(kernel="gaussian", kernel_params=(("sigma", 2.0),),
                           lam=1e-4, num_centers=64, iterations=15,
                           block_size=64, mesh=mesh, data_axes=("pod", "data"))
        est_1, _ = falkon_fit(jax.random.PRNGKey(1), X, y,
                              dataclasses.replace(cfg, mesh=None))
        est_d, _ = falkon_fit(jax.random.PRNGKey(1), X, y, cfg)
        p1, pd = est_1.predict(X), est_d.predict(X)
        rel = float(jnp.linalg.norm(pd - p1) / jnp.linalg.norm(p1))
        assert rel < 2e-3, rel
        print("OK")
    """)


def test_counting_ops_under_shard_map():
    """A CountingOps wrapped by DistributedOps proves the distributed fit
    traces the SAME number of sweeps and gram builds as a single-device
    fit — no hidden per-shard re-sweeps — and that every sweep costs
    exactly one (M, p) psum."""
    _run("""
        import dataclasses, jax, jax.numpy as jnp
        from repro.core import FalkonConfig, falkon_fit
        from repro.core.falkon import _resolve_ops
        from repro.ops import CountingOps, DistributedOps, get_ops
        mesh = jax.make_mesh((8,), ("data",))
        k1, k2 = jax.random.split(jax.random.PRNGKey(0))
        X = jax.random.normal(k1, (512, 5))
        y = jnp.sin(X @ jax.random.normal(k2, (5,)))
        cfg = FalkonConfig(kernel="gaussian", kernel_params=(("sigma", 2.0),),
                           lam=1e-4, num_centers=64, iterations=10,
                           block_size=64)
        count_1 = CountingOps(get_ops("jnp", cfg.make_kernel(), block_size=64))
        falkon_fit(jax.random.PRNGKey(1), X, y, cfg, ops=count_1)
        count_8 = CountingOps(get_ops("jnp", cfg.make_kernel(), block_size=64))
        cfg_8 = dataclasses.replace(cfg, mesh=mesh)
        # _resolve_ops wraps the CountingOps in DistributedOps, so the
        # counter records the trace-time program points the shards replay
        dist = _resolve_ops(cfg_8, cfg.make_kernel(), count_8)
        assert isinstance(dist, DistributedOps)
        falkon_fit(jax.random.PRNGKey(1), X, y, cfg_8, ops=dist)
        assert count_8.sweeps == count_1.sweeps, (count_8.sweeps, count_1.sweeps)
        assert count_8.grams == count_1.grams, (count_8.grams, count_1.grams)
        assert count_8.applies == count_1.applies
        # one (M, p) psum per sweep and nothing else on the wire
        assert dist.psums == count_8.sweeps, (dist.psums, count_8.sweeps)
        assert dist.psum_floats == count_8.sweeps * 64
        print("OK sweeps", count_8.sweeps, "grams", count_8.grams)
    """)


def test_counting_outside_distributed_not_double_wrapped():
    """The other composition order the _resolve_ops docstring promises:
    CountingOps(DistributedOps(inner)) with config.mesh set must pass
    through unwrapped — a second DistributedOps would nest shard_map over
    the same mesh axes (trace failure / double reduction)."""
    _run("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.core import FalkonConfig, falkon_fit
        from repro.core.falkon import _resolve_ops
        from repro.ops import CountingOps, DistributedOps, get_ops
        mesh = jax.make_mesh((8,), ("data",))
        k1, k2 = jax.random.split(jax.random.PRNGKey(0))
        X = jax.random.normal(k1, (512, 5))
        y = jnp.sin(X @ jax.random.normal(k2, (5,)))
        cfg = FalkonConfig(kernel="gaussian", kernel_params=(("sigma", 2.0),),
                           lam=1e-4, num_centers=64, iterations=10,
                           block_size=64, mesh=mesh)
        inner = get_ops("jnp", cfg.make_kernel(), block_size=64)
        counted = CountingOps(DistributedOps(inner, mesh, ("data",)))
        resolved = _resolve_ops(cfg, cfg.make_kernel(), counted)
        assert resolved is counted, type(resolved)  # no second wrap
        est_c, _ = falkon_fit(jax.random.PRNGKey(1), X, y, cfg, ops=counted)
        assert counted.sweeps > 0
        est_p, _ = falkon_fit(jax.random.PRNGKey(1), X, y, cfg)
        assert bool(jnp.all(est_c.alpha == est_p.alpha))
        print("OK sweeps", counted.sweeps)
    """)


def test_ragged_shard_mask_pad_parity():
    """n not divisible by the data axis: the padded final shard contributes
    exactly zero. At the same padded length, junk rows + row_mask is
    bit-identical to internal zero-padding (fp32) across jnp and pallas
    inner backends and the VMEM-starved fallback route; bf16 holds to its
    compensated-accumulation tolerance."""
    _run("""
        import os, jax, jax.numpy as jnp, numpy as np
        from repro.core import GaussianKernel
        from repro.ops import DistributedOps, get_ops
        mesh = jax.make_mesh((8,), ("data",))
        kern = GaussianKernel(sigma=1.5)
        n, n_pad = 397, 400            # 397 % 8 != 0; ceil(397/8)*8 = 400
        X = jax.random.normal(jax.random.PRNGKey(0), (n, 6))
        C = X[:48]
        u = jax.random.normal(jax.random.PRNGKey(1), (48,))
        v = jax.random.normal(jax.random.PRNGKey(2), (n,))
        junk = 1e3 * jax.random.normal(jax.random.PRNGKey(3), (n_pad - n, 6))
        X_junk = jnp.concatenate([X, junk])
        v_junk = jnp.concatenate([v, jnp.full((n_pad - n,), 1e6)])
        mask = (jnp.arange(n_pad) < n)

        def check(impl, **kw):
            inner = get_ops(impl, kern, block_size=64, **kw)
            dist = DistributedOps(inner, mesh, ("data",))
            ref = inner.sweep(X, C, u, v)                 # single device
            got = dist.sweep(X, C, u, v)                  # internal zero-pad
            masked = dist.sweep(X_junk, C, u, v_junk, row_mask=mask)
            tol = dict(rtol=2e-4, atol=2e-3)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **tol)
            if inner.policy.storage == "float32":
                # masked junk rows are EXACTLY invisible: bit-identical
                np.testing.assert_array_equal(np.asarray(masked),
                                              np.asarray(got))
            else:
                np.testing.assert_allclose(np.asarray(masked),
                                           np.asarray(got), **tol)

        check("jnp")
        check("pallas")
        check("jnp", precision="bf16")
        check("pallas", precision="bf16")
        # starve the planner so the pallas sweep leaves the fused path
        os.environ["REPRO_VMEM_BUDGET_MB"] = "0.05"
        inner = get_ops("pallas", kern, block_size=64)
        assert inner.plan(400, 48, 6).path != "fused", inner.plan(400, 48, 6)
        check("pallas")
        del os.environ["REPRO_VMEM_BUDGET_MB"]
        print("OK")
    """)


def test_int8_psum_compression_parity():
    """Opt-in int8 wire compression: quantize/dequantize round-trip before
    the psum bounds the comm payload's precision; results stay within the
    symmetric-int8 quantization tolerance of the uncompressed sweep."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import GaussianKernel
        from repro.ops import DistributedOps, get_ops
        mesh = jax.make_mesh((8,), ("data",))
        kern = GaussianKernel(sigma=1.5)
        X = jax.random.normal(jax.random.PRNGKey(0), (512, 6))
        C = X[:64]
        u = jax.random.normal(jax.random.PRNGKey(1), (64,))
        v = jax.random.normal(jax.random.PRNGKey(2), (512,))
        inner = get_ops("jnp", kern, block_size=64)
        ref = DistributedOps(inner, mesh, ("data",)).sweep(X, C, u, v)
        comp = DistributedOps(inner, mesh, ("data",), compress="int8")
        got = comp.sweep(X, C, u, v)
        rel = float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref))
        assert 0.0 < rel < 2e-2, rel   # int8 wire: ~1/127 per-shard rounding
        print("OK rel", rel)
    """)


def test_sharded_chunk_sources_cover_the_stream():
    """shard_chunk_sources splits a ChunkSource into per-shard row ranges
    that partition the stream: the shards reassemble the exact rows, and
    per-shard sweeps SUM to the full-stream sweep even when shard
    boundaries cut across chunk boundaries."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import GaussianKernel
        from repro.data import (ArrayChunkSource, StreamingLoader,
                                shard_chunk_sources, streaming_sweep)
        from repro.ops import get_ops
        kern = GaussianKernel(sigma=1.5)
        n = 397                      # ragged vs both chunk size and shards
        X = np.random.RandomState(0).randn(n, 6).astype(np.float32)
        y = np.random.RandomState(1).randn(n).astype(np.float32)
        src = ArrayChunkSource(X, y, chunk_rows=96)
        shards = shard_chunk_sources(src, 8)
        assert len(shards) == 8
        assert sum(s.n_rows for s in shards) == n
        np.testing.assert_array_equal(
            np.concatenate([np.concatenate([c[0] for c in s.chunks()])
                            for s in shards if s.n_rows]), X)
        ops = get_ops("jnp", kern, block_size=64)
        C = jnp.asarray(X[:48])
        u = jax.random.normal(jax.random.PRNGKey(2), (48,))
        full = streaming_sweep(ops, StreamingLoader(src), C, u,
                               use_targets=True)
        parts = [streaming_sweep(ops, StreamingLoader(s), C, u,
                                 use_targets=True)
                 for s in shards if s.n_rows]
        np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(full),
                                   rtol=2e-5, atol=2e-5)
        print("OK")
    """)


def test_distributed_fit_path_and_streaming():
    """The lambda-path fit stacks L systems into ONE psum'd (M, L*p) block
    per sweep, and the streaming fit inherits the mesh from config — both
    match their single-device counterparts."""
    _run("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.core import (FalkonConfig, falkon_fit_path,
                                falkon_fit_streaming)
        from repro.core.falkon import _resolve_ops
        from repro.data import ArrayChunkSource
        from repro.ops import CountingOps, get_ops
        mesh = jax.make_mesh((8,), ("data",))
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
        X = jax.random.normal(k1, (640, 5))
        y = jnp.sin(X @ jax.random.normal(k2, (5,)))
        y = y + 0.05 * jax.random.normal(k3, (640,))
        cfg = FalkonConfig(kernel="gaussian", kernel_params=(("sigma", 2.0),),
                           lam=1e-4, num_centers=64, iterations=15,
                           block_size=64)
        cfg_8 = dataclasses.replace(cfg, mesh=mesh)
        lams = (1e-2, 1e-3, 1e-4)
        res_1 = falkon_fit_path(jax.random.PRNGKey(1), X, y, cfg, lams,
                                X_val=X[:96], y_val=y[:96])
        count = CountingOps(get_ops("jnp", cfg.make_kernel(), block_size=64))
        dist = _resolve_ops(cfg_8, cfg.make_kernel(), count)
        res_8 = falkon_fit_path(jax.random.PRNGKey(1), X, y, cfg_8, lams,
                                X_val=X[:96], y_val=y[:96], ops=dist)
        # the val curves match pointwise (best_index itself can flip on a
        # near-tie under fp32 psum reassociation, so compare the curve)
        np.testing.assert_allclose(np.asarray(res_8.val_scores),
                                   np.asarray(res_1.val_scores),
                                   rtol=5e-2, atol=5e-4)
        for e1, e8 in zip(res_1.estimators, res_8.estimators):
            p1, p8 = e1.predict(X), e8.predict(X)
            rel = float(jnp.linalg.norm(p8 - p1) / jnp.linalg.norm(p1))
            assert rel < 5e-2, rel
        # one psum per batched sweep: the L systems share the wire. The path
        # fit traces exactly two sweeps — the p=1 RHS build and the CG body
        # carrying all L systems as one (M, L) block — so the wire carries
        # M*1 + M*L floats, NOT L independent psums per iteration.
        assert dist.psums == count.sweeps == 2, (dist.psums, count.sweeps)
        assert dist.psum_floats == 64 * (1 + len(lams)), dist.psum_floats

        src = ArrayChunkSource(np.asarray(X), np.asarray(y), chunk_rows=128)
        # converge CG properly: an under-converged solve amplifies the psum
        # reassociation noise through the ill-conditioned operator
        cfg_s = dataclasses.replace(cfg, iterations=25)
        cfg_s8 = dataclasses.replace(cfg_8, iterations=25)
        est_s1, _ = falkon_fit_streaming(jax.random.PRNGKey(1), src, cfg_s)
        est_s8, _ = falkon_fit_streaming(jax.random.PRNGKey(1), src, cfg_s8)
        p1, p8 = est_s1.predict(X), est_s8.predict(X)
        rel = float(jnp.linalg.norm(p8 - p1) / jnp.linalg.norm(p1))
        assert rel < 2e-3, rel
        print("OK")
    """)


def test_mini_dryrun_train_and_decode():
    """End-to-end dry-run machinery on an 8-device mesh: pspec resolution,
    lower + compile, memory/cost analysis, HLO collective parse — the same
    code path the 512-device production dry-run uses."""
    _run("""
        import jax, jax.numpy as jnp, dataclasses
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import reduced_config
        from repro.configs.base import input_specs
        from repro.distributed.mesh import AxisRules, use_rules
        from repro.models import cache_pspecs, cache_specs, model_param_structs
        from repro.models.model import model_param_pspecs
        from repro.roofline.analysis import derive_roofline, memory_report
        from repro.launch.mesh import make_mesh
        from repro.train.steps import (TrainConfig, batch_pspecs,
                                       make_serve_step, make_train_step,
                                       train_state_pspecs, train_state_structs)
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        named = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                       is_leaf=lambda x: isinstance(x, P))
        for arch in ("jamba-1.5-large-398b", "granite-moe-3b-a800m"):
            cfg = dataclasses.replace(reduced_config(arch), remat="full",
                                      fsdp=True)
            rules = AxisRules(mesh=mesh, fsdp=True)
            with mesh, use_rules(rules):
                # train cell
                tcfg = TrainConfig(microbatch=2)
                step = make_train_step(cfg, tcfg)
                ss = train_state_structs(cfg, tcfg)
                sp = train_state_pspecs(cfg, tcfg, rules)
                bstructs = {
                    "tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
                    "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
                bp = batch_pspecs(cfg, bstructs, rules)
                comp = jax.jit(step, in_shardings=(named(sp), named(bp)),
                               donate_argnums=(0,)).lower(ss, bstructs).compile()
                roof = derive_roofline(comp, chips=8, model_flops=1.0)
                assert roof.flops_per_device > 0
                assert memory_report(comp)["total_per_device"] > 0
                # decode cell
                serve = make_serve_step(cfg)
                ps = model_param_structs(cfg)
                pp = model_param_pspecs(cfg, rules)
                cs = cache_specs(cfg, 8, 64)
                cp = cache_pspecs(cfg, 8, 64, rules)
                bs = {"token": jax.ShapeDtypeStruct((8,), jnp.int32)}
                comp2 = jax.jit(serve, in_shardings=(
                    named(pp), named(cp), named(bp := batch_pspecs(cfg, bs, rules))),
                    donate_argnums=(1,)).lower(ps, cs, bs).compile()
                assert memory_report(comp2)["total_per_device"] > 0
            print(arch, "OK")
    """)


def test_shardmap_moe_matches_local():
    """Expert-parallel (all_to_all) MoE == local-dispatch MoE numerically."""
    _run("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.configs import reduced_config
        from repro.distributed.mesh import AxisRules, use_rules
        from repro.models import layers as L
        from repro.models.params import init_params
        cfg = dataclasses.replace(reduced_config("granite-moe-3b-a800m"),
                                  n_experts=4, expert_pad_multiple=2, top_k=2,
                                  capacity_factor=4.0)
        p = init_params(jax.random.PRNGKey(0), L.moe_pd(cfg), jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model)) * .5
        ref = L._moe_local(p, x, cfg)
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        rules = AxisRules(mesh=mesh)
        with mesh, use_rules(rules):
            got = jax.jit(lambda p, x: L.moe_apply(p, x, cfg))(p, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
        print("OK")
    """)


def test_elastic_restore_across_meshes():
    """Fault tolerance: train on a (2,2,2) pod mesh, checkpoint, restore the
    same state onto a (4,2) single-pod mesh (elastic rescale), resume, and
    get bit-identical metrics to an uninterrupted run."""
    _run("""
        import os, tempfile, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import load_checkpoint, save_checkpoint, step_dir
        from repro.configs import reduced_config
        from repro.distributed.mesh import AxisRules, use_rules
        from repro.launch.mesh import make_mesh
        from repro.train import TrainConfig, init_train_state, make_train_step
        from repro.train.steps import train_state_pspecs

        cfg = reduced_config("qwen2-72b")
        tcfg = TrainConfig(learning_rate=1e-3, warmup_steps=2, total_steps=10)
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32),
                                              0, cfg.vocab),
                 "labels": jax.random.randint(jax.random.PRNGKey(2), (8, 32),
                                              0, cfg.vocab)}
        named = lambda mesh, t: jax.tree.map(
            lambda s: NamedSharding(mesh, s), t,
            is_leaf=lambda x: isinstance(x, P))

        mesh_a = make_mesh((2, 2, 2), ("pod", "data", "model"))
        rules_a = AxisRules(mesh=mesh_a)
        state = init_train_state(jax.random.PRNGKey(0), cfg, tcfg)
        with mesh_a, use_rules(rules_a):
            step = jax.jit(make_train_step(cfg, tcfg))
            state, m1 = step(state, batch)
        with tempfile.TemporaryDirectory() as d:
            save_checkpoint(step_dir(d, 1), state, 1, blocking=True)

            # restore onto a DIFFERENT mesh with its own shardings
            mesh_b = make_mesh((4, 2), ("data", "model"))
            rules_b = AxisRules(mesh=mesh_b)
            shardings = named(mesh_b, train_state_pspecs(cfg, tcfg, rules_b))
            restored, stp = load_checkpoint(step_dir(d, 1), state,
                                            shardings=shardings)
            assert stp == 1
            with mesh_b, use_rules(rules_b):
                step_b = jax.jit(make_train_step(cfg, tcfg))
                _, m2 = step_b(restored, batch)

            # uninterrupted reference on mesh_a
            with mesh_a, use_rules(rules_a):
                _, m_ref = step(state, batch)
        np.testing.assert_allclose(float(m2["loss"]), float(m_ref["loss"]),
                                   rtol=2e-4)
        print("OK elastic restore", float(m2["loss"]))
    """)
