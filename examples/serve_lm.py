"""Batched serving demo: prefill a batch of prompts, decode greedily.

Exercises the production serve path (KV caches, ring buffers for SWA,
SSM states for the attention-free archs) on any assigned arch's smoke
config.

    PYTHONPATH=src python examples/serve_lm.py --arch mixtral-8x7b --tokens 32
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
import repro.configs as cfgs
from repro.models import EncDecConfig, build


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-14b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--tokens", type=int, default=48)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = cfgs.get_smoke(args.arch)
    model = build(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    B, L = args.batch, args.prompt_len
    prompts = jax.random.randint(key, (B, L), 0, cfg.vocab)
    max_len = L + args.tokens + 1

    if isinstance(cfg, EncDecConfig):
        frames = jax.random.normal(key, (B, cfg.enc_len, cfg.d_model))
        enc_out = model.encode(params, frames)
        cache = model.init_cache(params, enc_out, B, max_len)
    else:
        cache = model.init_cache(B, max_len)

    prefill = jax.jit(model.prefill)
    decode = jax.jit(model.decode_step)

    t0 = time.perf_counter()
    logits, cache = prefill(params, prompts, cache)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    jax.block_until_ready(tok)
    t_prefill = time.perf_counter() - t0

    out = [tok]
    t0 = time.perf_counter()
    for _ in range(args.tokens - 1):
        logits, cache = decode(params, tok, cache)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.perf_counter() - t0

    gen = np.stack([np.asarray(t) for t in out], 1)  # (B, tokens)
    print(f"arch={args.arch} ({cfg.name})")
    print(f"prefill: {B}x{L} tokens in {t_prefill*1e3:.1f} ms "
          f"({B*L/t_prefill:.0f} tok/s)")
    print(f"decode:  {args.tokens-1} steps x {B} seqs in {t_decode*1e3:.1f} ms "
          f"({B*(args.tokens-1)/max(t_decode,1e-9):.0f} tok/s)")
    print("first generated rows:", gen[:2, :12].tolist())


if __name__ == "__main__":
    main()
