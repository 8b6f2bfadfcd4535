"""Seeded weights for a configuration, made on the device in one jitted call.

Per Dense layer: Glorot-uniform float32 weights and uniform biases from the
seed, then the paper's §6.1 SINT quantization, as the repository's
porting step does it: symmetric per-output-channel int8 weights with
float32 scales, and one float32 activation scale per layer from the
largest activation the calibration windows reach at that layer's input
(the float forward pass).  The program and the reference are both handed
these arrays; neither makes its own.

The seed enters as an operand (two 32-bit words), so one compiled program
serves every seed.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

SINT_QMAX = 127
BIAS_SCALE = 0.1     # biases are uniform in [-BIAS_SCALE, BIAS_SCALE]


def seed_words(seed: int, stream: str) -> np.ndarray:
    """Two uint32 words for one named use of ``seed`` (any size)."""
    tag = [ord(c) for c in stream]
    ss = np.random.SeedSequence([int(seed), *tag])
    return ss.generate_state(2, np.uint32)


def _act(name: str, y):
    if name == "relu":
        return jnp.maximum(y, 0.0)
    if name == "linear":
        return y
    raise ValueError(f"activation {name!r} is not in the benchmark's set")


@functools.lru_cache(maxsize=None)
def _maker(shapes: tuple):
    """``shapes``: per group, a tuple of ``(k, n, activation)`` per layer."""

    def make(key_data, calibs):
        # One draw of uniform numbers for every weight and bias, sliced per
        # layer: one random-number program, however many layers.
        sizes = [k * n + n for layers in shapes for k, n, _ in layers]
        flat = jax.random.uniform(jax.random.wrap_key_data(key_data),
                                  (sum(sizes),), jnp.float32, -1.0, 1.0)
        groups, at = [], 0
        for gi, layers in enumerate(shapes):
            x = calibs[gi]
            out = []
            for k, n, act in layers:
                limit = math.sqrt(6.0 / (k + n))
                w = flat[at:at + k * n].reshape(k, n) * limit
                b = flat[at + k * n:at + k * n + n] * BIAS_SCALE
                at += k * n + n
                x_absmax = jnp.max(jnp.abs(x))
                w_absmax = jnp.max(jnp.abs(w), axis=0)
                w_scale = jnp.maximum(w_absmax, 1e-12) / SINT_QMAX
                qw = jnp.clip(jnp.round(w / w_scale), -SINT_QMAX,
                              SINT_QMAX).astype(jnp.int8)
                x_scale = jnp.maximum(x_absmax, 1e-12) / SINT_QMAX
                out.append({"w": w, "b": b, "qw": qw, "w_scale": w_scale,
                            "x_scale": x_scale, "x_absmax": x_absmax})
                x = _act(act, jnp.dot(x, w,
                                      precision=jax.lax.Precision.HIGHEST) + b)
            groups.append(out)
        return groups

    return jax.jit(make)


def layer_shapes(group: dict) -> tuple:
    widths, acts = group["widths"], group["activations"]
    if len(acts) != len(widths) - 1:
        raise ValueError(f"group {group['name']!r}: {len(widths) - 1} layers "
                         f"but {len(acts)} activations")
    return tuple((int(widths[i]), int(widths[i + 1]), acts[i])
                 for i in range(len(acts)))


def make(config: dict, seed: int, calibs) -> list:
    """Per group, a list of per-layer dicts of device arrays: ``w``, ``b``
    (float32), ``qw`` (int8), ``w_scale``, ``x_scale`` and ``x_absmax``
    (float32).  ``calibs`` is one ``(k, widths[0])`` float32 array per
    group: normalized calibration windows as that group's model sees them."""
    shapes = tuple(layer_shapes(g) for g in config["groups"])
    return _maker(shapes)(jnp.asarray(seed_words(seed, "weights")),
                 tuple(jnp.asarray(c, jnp.float32) for c in calibs))


def to_host(groups) -> list:
    """The same arrays as numpy, for the reference."""
    return [[{k: np.asarray(v) for k, v in layer.items()} for layer in g]
            for g in jax.device_get(groups)]
