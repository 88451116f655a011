"""Serve a small LM with batched requests through the same substrate the
dry-run lowers at pod scale: prefill a batch of prompts, then decode tokens
autoregressively (KV cache threaded through jit'd steps).

  PYTHONPATH=src python examples/lm_serve.py [--arch llama3-8b] [--tokens 16]

(The arch's SMOKE config is served — full configs are dry-run-only on CPU.)
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.registry import ARCH_IDS, get_smoke_config
from repro.models.registry import build


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b", choices=ARCH_IDS)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=16)
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch)
    bundle = build(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0), jnp.float32)
    rng = np.random.default_rng(0)

    S_max = args.prompt_len + args.tokens
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)),
        jnp.int32)

    prefill = jax.jit(bundle.prefill_fn)
    decode = jax.jit(bundle.decode_fn)

    t0 = time.time()
    logits, cache = prefill(params, {"tokens": prompts})
    # grow KV capacity to S_max (recurrent archs have O(1) state)
    cache = jax.tree.map(
        lambda c: (jnp.pad(c, [(0, 0)] * 2 + [(0, args.tokens)]
                           + [(0, 0)] * (c.ndim - 3))
                   if c.ndim >= 4 and c.shape[2] == args.prompt_len else c),
        cache)
    t_prefill = time.time() - t0

    tokens = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    generated = [tokens]
    t0 = time.time()
    for i in range(args.tokens - 1):
        pos = jnp.asarray(args.prompt_len + i, jnp.int32)
        logits, cache = decode(params, cache, {"tokens": tokens, "pos": pos})
        tokens = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        generated.append(tokens)
    jax.block_until_ready(tokens)
    dt = time.time() - t0
    out = np.concatenate(generated, axis=1)
    print(f"arch={args.arch} (smoke config: {cfg.n_layers}L d={cfg.d_model})")
    print(f"prefill: {args.batch}x{args.prompt_len} tokens in "
          f"{t_prefill*1e3:.1f}ms")
    print(f"decode : {args.tokens-1} steps x {args.batch} seqs = "
          f"{(args.tokens-1)*args.batch/dt:.0f} tok/s")
    print(f"sample token ids: {out[0][:12].tolist()}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
