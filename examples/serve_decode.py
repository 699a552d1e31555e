"""Batched-request LM serving: prefill a batch of prompts, then decode with
the per-arch KV/recurrent caches (the serve_step the decode_* dry-run shapes
lower). Runs a reduced config of any assigned architecture on CPU.

    PYTHONPATH=src python examples/serve_decode.py --arch rwkv6-7b --tokens 24
    PYTHONPATH=src python examples/serve_decode.py --arch deepseek-v2-lite-16b
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.configs import ARCH_NAMES, reduced_config
from repro.models.lm import LM


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b", choices=ARCH_NAMES)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=128)
    args = ap.parse_args()

    cfg = reduced_config(args.arch)
    lm = LM(cfg)
    params = lm.init_params(jax.random.key(0))
    mem = None
    if cfg.family in ("vlm", "encdec"):
        t = cfg.frontend_tokens or 16
        mem = (jax.random.normal(jax.random.key(1),
                                 (args.batch, t, cfg.d_model)) * 0.05
               ).astype(jnp.bfloat16)

    prompts = jax.random.randint(jax.random.key(2),
                                 (args.batch, args.prompt_len), 0, cfg.vocab)
    prefill = jax.jit(lambda p, t: lm.prefill(p, t, args.max_seq, mem))
    decode = jax.jit(lambda p, c, t, n: lm.decode_step(p, c, t, n, mem))

    t0 = time.time()
    logits, caches = prefill(params, prompts)
    jax.block_until_ready(logits)
    print(f"prefill {args.batch}x{args.prompt_len}: {time.time()-t0:.2f}s")

    toks = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    out = [toks]
    t0 = time.time()
    for i in range(args.tokens - 1):
        logits, caches = decode(params, caches, toks,
                                jnp.int32(args.prompt_len + i))
        toks = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        out.append(toks)
    jax.block_until_ready(toks)
    dt = time.time() - t0
    gen = np.concatenate([np.asarray(t) for t in out], axis=1)
    print(f"decoded {args.tokens} tokens/seq in {dt:.2f}s "
          f"({args.tokens*args.batch/max(dt,1e-9):.1f} tok/s total)")
    for b in range(args.batch):
        print(f"  seq{b}: {gen[b].tolist()}")


if __name__ == "__main__":
    main()
