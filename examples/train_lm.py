"""Train a (reduced) assigned LM architecture on the synthetic token stream —
the LM-side end-to-end driver: data pipeline -> train step (microbatching,
clipping, optimizer) -> loss curve.

    PYTHONPATH=src python examples/train_lm.py --arch llama3.2-1b --steps 60
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.configs import ARCH_NAMES, reduced_config
from repro.data import TokenStream
from repro.launch.steps import make_lm_train_step
from repro.models.lm import LM
from repro.optim import make_optimizer


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b", choices=ARCH_NAMES)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    args = ap.parse_args()

    cfg = reduced_config(args.arch)
    lm = LM(cfg)
    params = lm.init_params(jax.random.key(0))
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"{args.arch} (reduced): {n_params/1e6:.1f}M params, "
          f"optimizer={cfg.optimizer}")

    opt = make_optimizer(cfg.optimizer, lr=3e-3)
    opt_state = opt.init(params, lm.params_spec())
    step = jax.jit(make_lm_train_step(lm, opt))
    stream = TokenStream(cfg.vocab, args.batch, args.seq, seed=0)

    mem = None
    if cfg.family in ("vlm", "encdec"):
        t = cfg.frontend_tokens or 16
        mem = (jax.random.normal(jax.random.key(1),
                                 (args.batch, t, cfg.d_model)) * 0.05
               ).astype(jnp.bfloat16)

    t0 = time.time()
    for i in range(args.steps):
        batch = next(stream)
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        if mem is not None:
            batch["memory"] = mem
        params, opt_state, m = step(params, opt_state, batch)
        if i % 10 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {float(m['loss']):.4f}  "
                  f"gnorm {float(m['grad_norm']):.3f}  "
                  f"({time.time()-t0:.1f}s)")
    print("loss should decrease from ~ln(vocab) as the model memorizes the "
          "Zipf/markov stream")


if __name__ == "__main__":
    main()
