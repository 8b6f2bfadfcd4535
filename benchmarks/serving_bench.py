"""Wave vs continuous batching under a skewed request-length workload.

The workload mixes many short completions with a few long ones (the shape
that breaks wave batching: every wave stalls on its longest request, so
short requests pay the long tail's latency and the slots idle).  Both
engines serve the same requests from the same params; we report aggregate
decode throughput (generated tokens / wall time) and p50/p99 per-request
latency (submit-to-retire, all requests submitted at t0).

Run:  PYTHONPATH=src python benchmarks/serving_bench.py [--arch qwen3_8b]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.configs import get_config
from repro.models import get_model
from repro.serving import ContinuousEngine, Engine, Request
from repro.launch.compile_cache import enable_compile_cache


def skewed_requests(n: int, *, prompt_len: int, short_new: int, long_new: int,
                    long_every: int, vocab: int, seed: int = 0):
    """1-in-`long_every` requests decode `long_new` tokens, the rest
    `short_new` — interleaved so every wave catches a straggler."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        max_new = long_new if i % long_every == 0 else short_new
        reqs.append(Request(
            uid=i,
            prompt=rng.integers(0, vocab, prompt_len).astype(np.int32),
            max_new_tokens=max_new))
    return reqs


def summarize(name: str, done, wall_s: float):
    lat = np.asarray([c.finished_s for c in done])
    toks = sum(len(c.tokens) for c in done)
    tps = toks / wall_s
    print(f"{name}: {toks} tokens in {wall_s:.2f}s -> {tps:.1f} tok/s | "
          f"latency p50={np.percentile(lat, 50) * 1e3:.0f}ms "
          f"p99={np.percentile(lat, 99) * 1e3:.0f}ms")
    return tps, lat


def main(quick: bool = False, arch: str = "qwen3_8b", requests: int = 0,
         slots: int = 4, cache_len: int = 128, prompt_len: int = 8,
         short_new: int = 0, long_new: int = 0, long_every: int = 5):
    requests = requests or (12 if quick else 24)
    short_new = short_new or (6 if quick else 8)
    long_new = long_new or (32 if quick else 64)

    cfg = get_config(arch).reduced()
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    reqs = skewed_requests(requests, prompt_len=prompt_len,
                           short_new=short_new, long_new=long_new,
                           long_every=long_every, vocab=cfg.vocab)
    total_new = sum(r.max_new_tokens for r in reqs)
    print(f"{cfg.name} (reduced): {requests} requests, "
          f"{total_new} decode tokens, slots={slots}, "
          f"lengths {short_new}/{long_new} "
          f"(1 in {long_every} long)")

    # warmup both engines (compile decode/prefill outside the timed region)
    warm = [Request(uid=-1, prompt=reqs[0].prompt, max_new_tokens=2)]
    wave = Engine(api, params, batch_slots=slots, cache_len=cache_len)
    wave.serve(warm * slots)
    cont = ContinuousEngine(api, params, batch_slots=slots,
                            cache_len=cache_len)
    cont.serve(warm)

    t0 = time.perf_counter()
    done_w = wave.serve(reqs)
    wall_w = time.perf_counter() - t0
    tps_w, lat_w = summarize("wave      ", done_w, wall_w)

    t0 = time.perf_counter()
    done_c = cont.serve(reqs)
    wall_c = time.perf_counter() - t0
    tps_c, lat_c = summarize("continuous", done_c, wall_c)

    speedup = tps_c / tps_w
    print(f"continuous/wave throughput: {speedup:.2f}x "
          f"({cont.last_stats.steps} continuous steps)")
    rows = [
        {"name": "serving_wave",
         "us_per_call": wall_w / total_new * 1e6,
         "derived": f"tok_s={tps_w:.1f};"
                    f"p99_s={np.percentile(lat_w, 99):.2f}"},
        {"name": "serving_continuous",
         "us_per_call": wall_c / total_new * 1e6,
         "derived": f"tok_s={tps_c:.1f};"
                    f"p99_s={np.percentile(lat_c, 99):.2f};"
                    f"speedup={speedup:.2f}x"},
    ]
    # harness contract: name,us_per_call,derived
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.3f},{r['derived']}")
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--arch", default="qwen3_8b")
    ap.add_argument("--requests", type=int, default=0)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--short-new", type=int, default=0)
    ap.add_argument("--long-new", type=int, default=0)
    ap.add_argument("--long-every", type=int, default=5)
    a = ap.parse_args()
    main(quick=a.quick, arch=a.arch, requests=a.requests, slots=a.slots,
         cache_len=a.cache_len, prompt_len=a.prompt_len,
         short_new=a.short_new, long_new=a.long_new,
         long_every=a.long_every)
