"""The plain reference: k-means++ D^2 sampling and the seeding cost.

Written in plain `jax.numpy` and NumPy and importing nothing of the
program under test: no Pallas, no tree embedding, no LSH, nothing the
program prepared.  Distances are taken in the difference form
``sum((x - c)^2)`` on the VPU, and the one matrix product (the argmin over
centers) runs at ``Precision.HIGHEST``.

`seeding_cost` is the k-means cost of a set of centers, summed in float64
on the host from per-point float32 squared distances to the nearest
center (difference form, relative error ~1e-7 per point); the nearest
center is the argmin of the expanded form at HIGHEST precision, so a
near-tie can pick a center whose distance differs from the true minimum
by the rounding of that expansion and no more.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["kmeanspp", "seeding_cost", "ROW_BLOCK"]

ROW_BLOCK = 32768           # rows per block of the cost's argmin
HI = jax.lax.Precision.HIGHEST


def _d2_to(points, c):
    return jnp.sum((points - c[None, :]) ** 2, axis=1)


@functools.partial(jax.jit, static_argnames=("k",))
def _kmeanspp(x, key, *, k):
    """Indices of k centers by exact D^2 sampling (Arthur & Vassilvitskii)."""
    n = x.shape[0]
    key, k0 = jax.random.split(key)
    first = jax.random.randint(k0, (), 0, n)
    d2 = _d2_to(x, x[first])
    chosen = jnp.zeros((k,), jnp.int32).at[0].set(first)

    def body(i, state):
        d2, chosen, key = state
        key, ki = jax.random.split(key)
        logits = jnp.where(d2 > 0, jnp.log(jnp.maximum(d2, 1e-30)), -jnp.inf)
        nxt = jax.random.categorical(ki, logits).astype(jnp.int32)
        d2 = jnp.minimum(d2, _d2_to(x, x[nxt]))
        return d2, chosen.at[i].set(nxt), key

    _, chosen, _ = jax.lax.fori_loop(1, k, body, (d2, chosen, key))
    return chosen


def kmeanspp(points_dev, k: int, seed: int):
    """k-means++ center indices on the float32 device points."""
    key = jax.random.key(int(seed) % (2 ** 32))
    return _kmeanspp(points_dev, key, k=k)


@jax.jit
def _nearest_d2(block, centers):
    cross = jnp.matmul(block, centers.T, precision=HI)
    d2x = (jnp.sum(block * block, axis=1, keepdims=True) - 2.0 * cross
           + jnp.sum(centers * centers, axis=1)[None, :])
    near = jnp.argmin(d2x, axis=1)
    return jnp.sum((block - centers[near]) ** 2, axis=1)


def seeding_cost(points_dev, indices) -> float:
    """k-means cost of the centers `points_dev[indices]`, float64 sum."""
    idx = jnp.asarray(np.asarray(indices, np.int64), jnp.int32)
    centers = jnp.take(points_dev, idx, axis=0)
    n = points_dev.shape[0]
    total = 0.0
    for lo in range(0, n, ROW_BLOCK):
        block = points_dev[lo:lo + ROW_BLOCK]
        total += float(np.asarray(_nearest_d2(block, centers),
                                  np.float64).sum())
    return total
