"""Serving launcher: batched LM prefill+decode loop, or a FALKON predictor.

LM mode (default):

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --reduced \
        --batch 4 --gen 32

FALKON mode — fit a kernel estimator and serve a ragged request trace
through the batch-coalescing predict server (``repro.serve``): requests are
packed into a power-of-two bucket ladder compiled once at warmup, so
steady-state serving never retraces and one device call serves many
requests. The per-request single-stream loop survives behind
``--per-request`` as the baseline the benchmark gates against:

    PYTHONPATH=src python -m repro.launch.serve --falkon --ops-impl pallas \
        --batch 256 --requests 200

With ``--stream-chunk N`` the fit streams X through the out-of-core path
(``falkon_fit_streaming``): host chunks of N rows double-buffered onto the
device, so n is bounded by host memory, not HBM.

Scaling limits — which (n, M) regime maps to which sweep path:

* ``fused`` (one Gram evaluation per tile): needs the (bm, M) Gram row strip
  and the (M, p) accumulator in VMEM — M up to ~8k at default tiles. n bound
  only by device HBM holding X.
* ``two_pass`` / ``j_sharded`` (two Gram evaluations per tile, chosen
  automatically by the VMEM planner — see ``KernelOps.plan()`` and the
  ``SweepPlanWarning`` it emits on fallback): O(tile) VMEM, M to 10^5+;
  ``t = K u + v`` spills to HBM and the center axis is swept in
  planner-sized C-shards.
* ``--stream-chunk`` (host streaming): n beyond HBM — each CG iteration
  streams X in chunks with O(chunk_rows * d + M * p) device state. Composes
  with either M regime above; the CG loop moves to the host, so the solve is
  no longer one fused XLA program.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache


def serve_lm(args) -> None:
    from repro.configs import ARCH_IDS, get_config, reduced_config
    from repro.models import decode_step, model_params, prefill

    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    if cfg.frontend == "embeds":
        cfg = dataclasses.replace(cfg, frontend="tokens")
    params = model_params(jax.random.PRNGKey(0), cfg)

    B, P, G = args.batch, args.prompt_len, args.gen
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (B, P), 0, cfg.vocab)}
    if cfg.frontend == "tokens+vision":
        batch["vision_embeds"] = jax.random.normal(
            jax.random.PRNGKey(2), (B, cfg.n_image_tokens, cfg.d_vision)
        ) * .05

    t0 = time.perf_counter()
    logits, cache = prefill(params, cfg, batch, S_max=P + G)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0

    step = jax.jit(lambda c, t: decode_step(params, cfg, c, {"token": t}))
    tok = jnp.argmax(logits, -1)
    out = [tok]
    logits, cache = step(cache, tok)        # compile
    t0 = time.perf_counter()
    for _ in range(G - 2):
        tok = jnp.argmax(logits, -1)
        out.append(tok)
        logits, cache = step(cache, tok)
    jax.block_until_ready(logits)
    t_decode = (time.perf_counter() - t0) / max(G - 2, 1)
    print(f"{cfg.name}: prefill {B}x{P} in {t_prefill*1e3:.0f}ms; "
          f"decode {t_decode*1e3:.1f}ms/token/batch")
    print("sample:", jnp.stack(out, 1)[0,:12].tolist())


def make_request_trace(
    key, n_requests: int, max_batch: int, d: int, seed: int = 0
) -> list:
    """Pre-generated ragged request batches (host arrays, sizes 1..max_batch).

    Generated BEFORE any serving timer starts: the old loop built each batch
    inside the timed region, so "ms/request" charged host-side RNG + array
    construction to the serving path and the numbers measured the generator,
    not the device work.
    """
    import numpy as np

    rng = np.random.default_rng(seed)
    sizes = rng.integers(1, max_batch + 1, size=n_requests)
    keys = jax.random.split(key, n_requests)
    return [jax.device_get(jax.random.normal(keys[i], (int(s), d)))
            for i, s in enumerate(sizes)]


def serve_falkon(args) -> None:
    """Fit once, then serve a ragged request trace — coalesced by default,
    the single-stream per-request loop behind ``--per-request``."""
    from repro.core import FalkonConfig, falkon_fit, falkon_fit_streaming
    from repro.data import ArrayChunkSource

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    n, d = args.n, args.d
    X = jax.random.normal(k1, (n, d))
    w = jax.random.normal(k2, (d,))
    y = jnp.sin(X @ w) + 0.05 * jax.random.normal(k3, (n,))

    cfg = FalkonConfig(
        kernel="gaussian",
        kernel_params=(("sigma", 2.0),),
        lam=1e-5,
        num_centers=args.centers,
        iterations=15,
        block_size=max(args.batch, 128),
        ops_impl=args.ops_impl,
        precision=args.precision,
    )
    plan = cfg.make_ops().plan(n, min(args.centers, n), d)
    print(f"sweep plan: {plan.path} ({plan.reason})")
    t0 = time.perf_counter()
    if args.stream_chunk > 0:
        # out-of-core: X/y live on the host, chunks stream through a
        # double-buffered transfer (see repro.data.streaming)
        src = ArrayChunkSource(
            jax.device_get(X), jax.device_get(y), chunk_rows=args.stream_chunk
        )
        est, state = falkon_fit_streaming(jax.random.PRNGKey(1), src, cfg)
    else:
        est, state = falkon_fit(jax.random.PRNGKey(1), X, y, cfg)
    jax.block_until_ready(est.alpha)
    t_fit = time.perf_counter() - t0

    # the streaming solve skips the power-iteration cond estimate (each
    # probe would cost a full data pass) — don't print a fabricated 0.0
    cond = ("n/a" if args.stream_chunk > 0 else f"{float(state.cond_estimate):.1f}")
    print(f"falkon[{cfg.impl}/{cfg.precision}]: fit n={n} "
          f"M={est.centers.shape[0]} in {t_fit:.2f}s; cond(W)={cond}")

    # The serving step is KernelOps.apply on the backend baked into the
    # estimator — per request one (batch, M) kernel matmul. The trace is
    # pre-generated so the timer below measures serving, not host RNG.
    trace = make_request_trace(jax.random.PRNGKey(2), args.requests, args.batch, d)
    rows = sum(b.shape[0] for b in trace)
    if args.per_request:
        # single-stream baseline: one dispatch per request, one XLA trace
        # per DISTINCT batch shape — the cost profile the coalescing server
        # exists to remove
        step = jax.jit(est.predict)
        jax.block_until_ready(step(jnp.zeros((args.batch, d))))  # compile one
        t0 = time.perf_counter()
        for xb in trace:
            jax.block_until_ready(step(jnp.asarray(xb)))
        dt = time.perf_counter() - t0
        print(f"per-request: {len(trace)} requests ({rows} rows) in "
              f"{dt:.3f}s — {rows / dt:.0f} rows/s, "
              f"{dt / len(trace) * 1e3:.2f} ms/request")
    else:
        from repro.serve import CoalescingPredictServer

        server = CoalescingPredictServer(est, max_batch=args.batch)
        compile_s = server.warmup()
        print(f"coalescing server: ladder {server.ladder}, warmup "
              f"{sum(compile_s.values()):.2f}s "
              f"({len(compile_s)} bucket compiles)")
        t0 = time.perf_counter()
        server.predict_many(trace)
        dt = time.perf_counter() - t0
        s = server.stats
        print(f"coalesced: {len(trace)} requests ({rows} rows) in {dt:.3f}s "
              f"— {rows / dt:.0f} rows/s, {s.dispatches} dispatches, "
              f"pad fraction {s.pad_fraction:.1%}, retraces after warmup: "
              f"{server.retraces_since_warmup()}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--falkon",
        action="store_true",
        help="serve a FALKON predictor instead of an LM",
    )
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    # FALKON-mode knobs
    ap.add_argument(
        "--ops-impl",
        default="jnp",
        choices=("jnp", "pallas"),
        help="KernelOps backend for fit + serving",
    )
    ap.add_argument("--precision", default="fp32", choices=("fp32", "bf16"))
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--d", type=int, default=16)
    ap.add_argument("--centers", type=int, default=256)
    ap.add_argument("--requests", type=int, default=50)
    ap.add_argument("--per-request", action="store_true",
                    help="serve the trace one request per dispatch (the "
                         "single-stream baseline) instead of coalescing")
    ap.add_argument("--stream-chunk", type=int, default=0,
                    help="fit via the host-streaming loader with this many "
                         "rows per chunk (0 = in-core fit)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.falkon:
        serve_falkon(args)
    else:
        from repro.configs import ARCH_IDS
        if args.arch not in ARCH_IDS:
            raise SystemExit(f"unknown arch {args.arch}; have {ARCH_IDS}")
        serve_lm(args)


if __name__ == "__main__":
    main()
