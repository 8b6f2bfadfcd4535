"""Quickstart: the ICSML core in five minutes.

Builds a small model the ICSML way (array of layers + static memory plan),
runs planned (arena) inference, quantizes it (§6.1), prunes it (§6.2), and
executes it multipart across simulated scan cycles (§6.3).

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import MultipartInference, layers as L, prune, quantize, sequential
from repro.launch.compile_cache import enable_compile_cache


def main():
    # 1. declare the model — an array of layers, sizes static (ICSML style)
    model = sequential(
        [L.Input(),
         L.Dense(units=128, activation="relu"),
         L.Dense(units=64, activation="relu"),
         L.Dense(units=10, activation="softmax")],
        input_shape=(32,))
    params = model.init_params(jax.random.PRNGKey(0))
    print(model.summary(), "\n")

    # 2. static memory plan (the dataMem table) + planned inference
    plan = model.memory_plan()
    print(f"activation arena: {plan.arena_bytes} B "
          f"(naive layout would be {model.memory_plan(reuse=False).arena_bytes} B)")
    x = jax.random.normal(jax.random.PRNGKey(1), (32,))
    y_ref = model.apply(params, x)
    y_arena = model.apply_planned(params, x)
    assert np.array_equal(np.asarray(y_ref), np.asarray(y_arena))
    print("planned (arena) inference == reference inference ✓\n")

    # 3. integer quantization (§6.1)
    qparams = quantize.quantize_params(model, params, "SINT", calibration=[x])
    y_q = model.apply(qparams, x)
    print(f"SINT output max|err| = {float(jnp.abs(y_q - y_ref).max()):.4g}")
    print("512x512 layer memory (Table 2):",
          {s: quantize.memory_report(512, 512, s)["total"]
           for s in ("SINT", "INT", "DINT", "REAL")}, "\n")

    # 4. pruning (§6.2)
    pparams = prune.prune_model(model, params, 0.5)
    print(f"pruned sparsity of layer 1: "
          f"{prune.sparsity_of(pparams[1]['w']):.2f}\n")

    # 5. multipart inference (§6.3): one segment per scan cycle
    mi = MultipartInference(model, params, n_segments=3)
    state = mi.start(x)
    for cycle in range(mi.n_segments):
        state = mi.step(state)      # this cycle's inference budget
        print(f"scan cycle {cycle}: segment done "
              f"({mi.segment_flops()[cycle]} FLOPs)")
    np.testing.assert_allclose(np.asarray(mi.output(state)),
                               np.asarray(y_arena), rtol=1e-6, atol=1e-7)
    print("multipart output identical to single-shot ✓")


if __name__ == "__main__":
    enable_compile_cache()
    main()
