"""End-to-end driver: train a ~100M-parameter qwen3-family model for a few
hundred steps on the synthetic LM stream (deliverable b).

The full assigned configs are exercised via the dry-run; this driver proves
the training stack end to end at a size the CPU container can actually run.
Defaults: 12 layers x d_model 512 x 8 heads with the qwen3 feature set
(qk-norm, GQA, SwiGLU) and tied embeddings over a 32k vocab ≈ 55M params; use
--big for the ~110M variant.

Run:  PYTHONPATH=src python examples/train_llm.py [--steps 300] [--big]
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save
from repro.configs import get_config
from repro.data import DataConfig, SyntheticLM
from repro.launch.steps import make_optimizer, make_train_step
from repro.models import get_model
from repro.launch.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--big", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    cfg = get_config("qwen3_8b").with_(
        n_layers=16 if args.big else 12,
        d_model=768 if args.big else 512,
        n_heads=12 if args.big else 8,
        n_kv_heads=4,
        d_ff=2048 if args.big else 1408,
        vocab=32768,
    )
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"model: {cfg.n_layers}L d{cfg.d_model} -> {n_params/1e6:.1f}M params")

    opt_init, opt_update = make_optimizer(lr=6e-4, warmup=50, steps=args.steps)
    opt = opt_init(params)
    step = jax.jit(make_train_step(api, opt_update), donate_argnums=(0, 1))
    stream = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                    global_batch=args.batch, seed=0)).batches()

    t0 = time.time()
    first = None
    for i in range(args.steps):
        b = next(stream)
        params, opt, m = step(params, opt,
                              {k: jnp.asarray(v) for k, v in b.items()})
        loss = float(m["loss"])
        first = first or loss
        if i % 20 == 0 or i == args.steps - 1:
            tok_s = args.batch * args.seq * (i + 1) / (time.time() - t0)
            print(f"step {i:4d}  loss {loss:.4f}  {tok_s:,.0f} tok/s")

    print(f"\nloss {first:.3f} -> {loss:.3f} over {args.steps} steps")
    if args.ckpt_dir:
        path = save(args.ckpt_dir, args.steps, {"params": params})
        print("checkpoint:", path)


if __name__ == "__main__":
    enable_compile_cache()
    main()
