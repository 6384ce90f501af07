"""Batched serving example: prefill a batch of prompts, then decode tokens
step by step against the KV cache (``lm.prefill``/``lm.decode_step``).

    PYTHONPATH=src python examples/serve_lm.py [--arch mamba2-2.7b]
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCHS
from repro.models import decode_step, init_params, prefill


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--new-tokens", type=int, default=16)
    args = ap.parse_args()

    cfg = ARCHS[args.arch].reduced()
    params = init_params(jax.random.key(0), cfg)
    prompts = jax.random.randint(jax.random.key(1),
                                 (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    batch = {"tokens": prompts}
    if cfg.frontend == "audio":
        batch["frames"] = jax.random.normal(
            jax.random.key(2), (args.batch, cfg.frontend_len, cfg.d_model))
    if cfg.frontend == "patch":
        batch["patch_embed"] = jax.random.normal(
            jax.random.key(2),
            (args.batch, cfg.frontend_len, cfg.resolved_frontend_dim))
    off = cfg.frontend_len if cfg.frontend == "patch" else 0
    max_len = off + args.prompt_len + args.new_tokens

    logits, cache = jax.jit(
        lambda p, b: prefill(p, b, cfg, max_len=max_len))(params, batch)
    step = jax.jit(lambda p, c, t, pos: decode_step(p, c, t, pos, cfg))

    toks = jnp.argmax(logits, -1)[:, None]
    out = [toks]
    t0 = time.perf_counter()
    for i in range(args.new_tokens - 1):
        pos = jnp.int32(off + args.prompt_len + i)
        logits, cache = step(params, cache, toks, pos)
        toks = jnp.argmax(logits, -1)[:, None]
        out.append(toks)
    dt = time.perf_counter() - t0
    gen = jnp.concatenate(out, axis=1)
    print(f"arch={cfg.name} batch={args.batch} "
          f"generated {gen.shape[1]} tokens/seq in {dt:.2f}s "
          f"({args.batch * gen.shape[1] / dt:.1f} tok/s on CPU)")
    print("first sequence:", gen[0].tolist())


if __name__ == "__main__":
    main()
