"""Seeded point sets at a configuration's (n, d).

A copy of the repository's Gaussian-mixture generator
(`benchmarks/datasets.make_dataset`), kept here so that no later change to
the program can move the benchmark's data.  The mixture has power-law
cluster sizes and anisotropic per-cluster scales: the regime in which D^2
seeding matters.  The number of components is part of the configuration.
"""

from __future__ import annotations

import numpy as np

__all__ = ["mixture", "point_set"]


def mixture(n: int, d: int, components: int, seed) -> np.ndarray:
    """(n, d) float64 points drawn from `seed` (any non-negative int or
    a sequence of them, as `numpy.random.default_rng` takes)."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(components, d)) * 12.0
    weights = 1.0 / np.arange(1, components + 1) ** 1.3
    weights /= weights.sum()
    assign = rng.choice(components, size=n, p=weights)
    scales = rng.uniform(0.3, 3.0, size=(components, d))
    pts = centers[assign] + rng.normal(size=(n, d)) * scales[assign]
    return pts.astype(np.float64)


def point_set(config: dict, seed, *stream) -> np.ndarray:
    """The configuration's point set for `seed` (and an optional stream
    of further ints, such as a request index)."""
    return mixture(config["n"], config["d"], config["mixture_components"],
                   [int(seed), *map(int, stream)])
