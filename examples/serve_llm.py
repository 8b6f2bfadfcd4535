"""Serving example: batched requests against a reduced assigned architecture,
with the paper's optimizations as switches (deliverable b).

  --engine continuous   per-slot continuous batching (serving/continuous.py)
  --quant SINT          int8 weights through the qmatmul path (§6.1)
  --kv-quant            int8 KV cache (§6.1 applied to serving state)
  --cyclic N            multipart decode, N layer-segments per cycle (§6.3);
                        with --engine continuous, segments compose with slots

Run:  PYTHONPATH=src python examples/serve_llm.py --arch qwen3_8b --engine continuous
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.models import get_model
from repro.serving import ContinuousEngine, CyclicDecoder, Engine, Request
from repro.launch.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen3_8b")
    ap.add_argument("--engine", choices=("wave", "continuous"), default="wave")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--quant", choices=("SINT", "INT", "DINT"))
    ap.add_argument("--kv-quant", action="store_true")
    ap.add_argument("--cyclic", type=int, default=0)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    if args.quant:
        cfg = cfg.with_(quant=args.quant)
    if args.kv_quant:
        cfg = cfg.with_(kv_quant=True)
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    print(f"serving {cfg.name} (reduced) quant={cfg.quant} kv_quant={cfg.kv_quant}")

    extras = {}
    if cfg.family == "vlm":
        extras["image_emb"] = jnp.zeros((4, cfg.num_image_tokens, 1152), cfg.dtype)
    elif cfg.family == "audio":
        extras["frames"] = jnp.zeros((4, cfg.encoder_frames, cfg.d_model), cfg.dtype)

    rng = np.random.default_rng(0)
    if args.cyclic and args.engine == "wave":
        batch = {"tokens": jnp.asarray(
            rng.integers(0, cfg.vocab, 8).astype(np.int32)[None]),
            **{k: v[:1] for k, v in extras.items()}}
        cache, logits = api.prefill(params, batch, 128)
        first = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
        cd = CyclicDecoder(cfg, params, n_segments=args.cyclic, batch=1,
                           cache_len=128)
        toks, _, stats = cd.decode_tokens(cache, first, 8, args.max_new,
                                          control_task=lambda: None)
        ct = np.asarray(stats.cycle_times_s) * 1e3
        print(f"multipart decode: {args.cyclic} cycles/token; "
              f"cycle p50={np.percentile(ct, 50):.1f}ms p99={np.percentile(ct, 99):.1f}ms")
        print("tokens:", toks)
        return

    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab, 8).astype(np.int32),
                    max_new_tokens=args.max_new, temperature=args.temperature)
            for i in range(args.requests)]
    if args.engine == "continuous":
        engine = ContinuousEngine(api, params, batch_slots=4, cache_len=128,
                                  cyclic_segments=args.cyclic)
        for c in engine.serve(reqs):
            print(f"req {c.uid}: prefill {c.prefill_s * 1e3:.0f}ms "
                  f"finished {c.finished_s * 1e3:.0f}ms "
                  f"tokens={c.tokens[:10].tolist()}...")
        st = engine.last_stats
        print(f"continuous{f' x {args.cyclic}-part' if args.cyclic else ''}: "
              f"{st.steps} steps, {st.admitted} requests, "
              f"{st.wall_s:.2f}s wall")
        return

    engine = Engine(api, params, batch_slots=4, cache_len=128, extras=extras)
    for c in engine.serve(reqs):
        print(f"req {c.uid}: prefill {c.prefill_s * 1e3:.0f}ms "
              f"{c.tokens_per_s:.1f} tok/s  tokens={c.tokens[:10].tolist()}...")


if __name__ == "__main__":
    enable_compile_cache()
    main()
