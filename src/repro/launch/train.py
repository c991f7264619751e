"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch gemma3-1b --steps 100 \
        [--reduced] [--mesh dxm] [--ckpt-dir DIR]

On real hardware this runs the full config on the production mesh; on CPU use
--reduced (the smoke-scale config). The Trainer provides checkpoint/restart,
straggler detection and preemption-safe saves (SIGTERM handler installed).
"""
from __future__ import annotations

import argparse
import signal

from repro.configs import ARCH_IDS, get_config, reduced_config
from repro.data import ShardedLoader, TokenStreamConfig, token_stream
from repro.distributed.mesh import AxisRules
from repro.launch.mesh import make_mesh
from repro.train import TrainConfig, Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b", choices=ARCH_IDS)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument(
        "--mesh", default=None, help="e.g. 16x16 or 2x16x16 (None = single device)"
    )
    args = ap.parse_args()

    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    mesh = rules = None
    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split("x"))
        axes = ("pod", "data", "model")[-len(dims):]
        mesh = make_mesh(dims, axes)
        rules = AxisRules(mesh=mesh, fsdp=cfg.fsdp)

    tcfg = TrainConfig(
        learning_rate=args.lr,
        warmup_steps=args.steps // 10,
        total_steps=args.steps,
        microbatch=args.microbatch,
        grad_compression=args.grad_compression,
    )
    rcfg = TrainerConfig(ckpt_dir=args.ckpt_dir, ckpt_every=max(10, args.steps // 5))
    trainer = Trainer(
        cfg,
        tcfg,
        rcfg,
        mesh=mesh,
        rules=rules,
        straggler_cb=lambda i,
        dt,
        z: print(f"[straggler] step {i}: {dt*1e3:.0f}ms (z={z:.1f})"),
    )
    signal.signal(signal.SIGTERM, lambda *_: trainer.request_preemption())

    stream = token_stream(TokenStreamConfig(
        vocab=min(cfg.vocab, 4096), seq_len=args.seq, batch=args.batch))
    loader = ShardedLoader(stream, mesh=mesh) if mesh else stream
    hist = trainer.fit(loader, steps=args.steps)
    print(f"{len(hist)} steps; loss {hist[0]['loss']:.4f} -> "
          f"{hist[-1]['loss']:.4f}; stragglers={len(trainer.straggler_events)}")


if __name__ == "__main__":
    main()
