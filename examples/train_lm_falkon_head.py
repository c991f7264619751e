"""End-to-end driver: train a ~100M-param LM for a few hundred steps, then fit
a FALKON head on its features (the paper's IMAGENET recipe: kernel method on
frozen deep features).

    PYTHONPATH=src python examples/train_lm_falkon_head.py [--steps 300]

Uses the full production substrate: Trainer (checkpoint/restart, straggler
monitor), the synthetic token pipeline, and the FALKON core as the adaptation
head. CPU-sized by default (a ~10M reduced config); pass --d-model 768
--layers 12 for the true ~100M run if you have the patience.
"""
import argparse
import tempfile

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs.base import ModelConfig
from repro.core import FalkonConfig, falkon_fit
from repro.data import TokenStreamConfig, token_stream
from repro.models.model import _backbone
from repro.train import TrainConfig, Trainer, TrainerConfig


def make_lm(d_model: int, layers: int, vocab: int) -> ModelConfig:
    return ModelConfig(
        name=f"lm-{d_model}x{layers}",
        family="dense",
        n_layers=layers,
        d_model=d_model,
        n_heads=max(4, d_model // 64),
        n_kv_heads=max(2, d_model // 128),
        d_head=64,
        d_ff=4 * d_model,
        vocab=vocab,
        vocab_pad_multiple=64,
        dtype="float32",
        remat="none",
        dense_attn_max_seq=4096,
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    args = ap.parse_args()
    enable_compile_cache()

    vocab = 512
    cfg = make_lm(args.d_model, args.layers, vocab)
    n_params = cfg.param_count()
    print(f"model: {cfg.name}  ({n_params/1e6:.1f}M params)")

    tcfg = TrainConfig(learning_rate=3e-4, warmup_steps=20, total_steps=args.steps)
    with tempfile.TemporaryDirectory() as ckpt_dir:
        rcfg = TrainerConfig(ckpt_dir=ckpt_dir, ckpt_every=100)
        trainer = Trainer(cfg, tcfg, rcfg)
        stream = token_stream(
            TokenStreamConfig(vocab=vocab, seq_len=args.seq, batch=args.batch)
        )
        hist = trainer.fit(stream, steps=args.steps)
        first, last = hist[0]["loss"], hist[-1]["loss"]
        print(f"train loss: {first:.3f} -> {last:.3f} over {len(hist)} steps "
              f"({len(trainer.straggler_events)} straggler events)")
        assert last < first, "LM did not learn"
        params = trainer.state.params

    # ---- FALKON head on frozen features (paper Sect. 5, IMAGENET setup) ----
    # task: predict next-token top-class family from the hidden state.
    stream = token_stream(
        TokenStreamConfig(vocab=vocab, seq_len=args.seq, batch=args.batch), seed=7
    )
    feats, targets = [], []
    for _ in range(8):
        b = next(stream)
        h = _backbone(params, cfg, {"tokens": b["tokens"]})  # (B,S,D)
        feats.append(h.reshape(-1, cfg.d_model))
        targets.append((b["tokens"] % 8).reshape(-1))        # 8-way task
    X = jnp.concatenate(feats)
    ylab = jnp.concatenate(targets)
    Y = jax.nn.one_hot(ylab, 8)
    ntr = int(0.8 * X.shape[0])

    fcfg = FalkonConfig(
        kernel="gaussian",
        kernel_params=(("sigma", 4.0),),
        lam=1e-6,
        num_centers=512,
        iterations=15,
    )
    est, state = falkon_fit(jax.random.PRNGKey(0), X[:ntr], Y[:ntr], fcfg)
    pred = jnp.argmax(est.predict(X[ntr:]), -1)
    acc = float(jnp.mean(pred == ylab[ntr:]))
    print(f"FALKON head: {acc*100:.1f}% acc on 8-way feature task "
          f"(chance 12.5%), cond(W)={float(state.cond_estimate):.1f}")
    assert acc > 0.2


if __name__ == "__main__":
    main()
