"""Batched serving: decode tokens from a small model with a KV cache,
mirroring the decode_32k dry-run cell at laptop scale.

    PYTHONPATH=src python examples/serve_lm.py [--tokens 64] [--batch 4]
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.launch import compile_cache
from repro.configs import get_config
from repro.models import Model
from repro.train import make_serve_step


def main():
    compile_cache.setup()
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=256)
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--temperature", type=float, default=1.0)
    args = ap.parse_args()

    cfg = dataclasses.replace(
        get_config(args.arch), name="serve-small",
        n_layers=4, d_model=256, n_heads=4, n_kv_heads=2, head_dim=64,
        d_ff=768, vocab=4096)
    model = Model(cfg, remat="none")
    params = model.init(jax.random.PRNGKey(0))
    serve_step = jax.jit(make_serve_step(model), donate_argnums=(1,))

    b = args.batch
    cache = model.init_cache(b, args.cache_len)
    tokens = jnp.zeros((b,), jnp.int32)
    key = jax.random.PRNGKey(42)
    out = []
    t0 = time.time()
    for pos in range(args.tokens):
        logits, cache = serve_step(params, cache,
                                   tokens, jnp.full((b,), pos, jnp.int32))
        key, sub = jax.random.split(key)
        tokens = jax.random.categorical(sub, logits / args.temperature, axis=-1)
        out.append(tokens)
    jax.block_until_ready(tokens)
    dt = time.time() - t0
    seqs = jnp.stack(out, axis=1)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    print(f"decoded {args.tokens} tokens x {b} sequences in {dt:.1f}s "
          f"({b*args.tokens/dt:.1f} tok/s)")
    print("sample token ids:", seqs[0, :16].tolist())
    print("OK: batched KV-cache serving works")


if __name__ == "__main__":
    main()
