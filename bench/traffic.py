"""The one traffic generator: a traffic mix is a JSON file of parameters
under ``bench/traffic/``, and this module turns it and a seed into the pool
of raw scan-cycle readings a run replays, closed loop: cycles are handed in
back to back.

Every plant sits at the configuration's nominal operating point
(``norm_mean``) with its own fixed offset, and its sensors wander around it
as a first-order autoregressive process, all in units of the
configuration's ``norm_std``.  The pool is ``(POOL_CYCLES, plants,
n_features)`` float32, made in one vectorised pass: every seed gives the
same sizes, only the values differ.

Keys of a traffic file:

``plants``         fleet size (streams the engine serves).
"""

from __future__ import annotations

import numpy as np

POOL_CYCLES = 2000    # scan cycles in the pool; a run replays them cyclically
OFFSET_STD = 0.5      # spread of the per-plant operating-point offsets
NOISE_STD = 0.5       # stationary spread of the autoregressive sensor wander
AR_PHI = 0.95         # autoregressive coefficient per scan cycle


def validate(mix: dict, name: str = "") -> dict:
    if int(mix.get("plants", 0)) < 1:
        raise ValueError(f"traffic {name!r}: plants must be a positive count")
    return mix


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """A numpy generator for one named use of ``seed`` (any non-negative
    integer, however large)."""
    tag = [ord(c) for c in stream]
    return np.random.default_rng(np.random.SeedSequence([int(seed), *tag]))


def pool(mix: dict, config: dict, seed: int) -> np.ndarray:
    """Raw readings ``(POOL_CYCLES, plants, n_features)`` float32."""
    n, p = int(mix["plants"]), POOL_CYCLES
    f = int(config["n_features"])
    mean = np.asarray(config["norm_mean"], np.float32)
    std = np.asarray(config["norm_std"], np.float32)
    rng = rng_for(seed, "traffic")
    phi = np.float32(AR_PHI)
    # Innovations scaled so that the process's stationary spread is
    # NOISE_STD; the first cycle starts in the stationary distribution.
    innov = rng.standard_normal((p, n, f), dtype=np.float32)
    innov *= np.float32(NOISE_STD) * np.sqrt(np.float32(1) - phi * phi)
    innov[0] *= np.float32(1) / np.sqrt(np.float32(1) - phi * phi)
    for c in range(1, p):
        innov[c] += phi * innov[c - 1]
    offset = rng.standard_normal((n, f), dtype=np.float32)
    offset *= np.float32(OFFSET_STD)
    innov += offset
    innov *= std
    innov += mean
    return innov
