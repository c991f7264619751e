"""Serve a small model with batched requests: prefill + decode loop.

    PYTHONPATH=src python examples/serve_batched.py [--arch gemma3-1b]

Uses the reduced config of any assigned architecture (prefill builds the KV /
SSM caches, decode_step generates token-by-token for the whole batch). Shows
hybrid/SSM caches working identically to attention caches through one API.
"""
import argparse

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import ARCH_IDS, reduced_config
from repro.models import decode_step, model_params, prefill


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b", choices=ARCH_IDS)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=24)
    args = ap.parse_args()
    enable_compile_cache()

    import dataclasses
    cfg = reduced_config(args.arch)
    if cfg.frontend == "embeds":
        cfg = dataclasses.replace(cfg, frontend="tokens")
    params = model_params(jax.random.PRNGKey(0), cfg)

    B, P, G = args.batch, args.prompt_len, args.gen
    prompts = jax.random.randint(jax.random.PRNGKey(1), (B, P), 0, cfg.vocab)
    batch = {"tokens": prompts}
    if cfg.frontend == "tokens+vision":
        batch["vision_embeds"] = jax.random.normal(
            jax.random.PRNGKey(2), (B, cfg.n_image_tokens, cfg.d_vision)
        ) * 0.05

    logits, cache = prefill(params, cfg, batch, S_max=P + G)
    print(f"{args.arch}: prefill of {B}x{P} tokens done "
          f"(cache pos={int(cache['pos'])})")

    step = jax.jit(lambda c, t: decode_step(params, cfg, c, {"token": t}))
    tok = jnp.argmax(logits, -1)
    generated = [tok]
    for _ in range(G - 1):
        logits, cache = step(cache, tok)
        tok = jnp.argmax(logits, -1)
        generated.append(tok)
    out = jnp.stack(generated, 1)
    assert out.shape == (B, G) and bool(jnp.all(out >= 0))
    print(f"generated {G} tokens per request; first row: " f"{out[0, :12].tolist()}...")


if __name__ == "__main__":
    main()
