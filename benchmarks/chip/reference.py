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

The rows lie row-wise over the cell's chips (`place`), padded with zero
rows to a multiple of their count; the same jitted code runs on one chip
or, partitioned by GSPMD, on several.  The pad rows are never drawn and
add nothing to the cost; on one chip nothing is padded.  A block of the
cost takes `ROW_BLOCK` rows of each chip's own shard, so every chip runs
the one-chip block program.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = ["Rows", "place", "kmeanspp", "seeding_cost", "ROW_BLOCK"]

ROW_BLOCK = 32768           # rows per chip per block of the cost's argmin
HI = jax.lax.Precision.HIGHEST


class Rows(NamedTuple):
    """A point set's float32 rows on the chips: `x` holds them row-wise
    over its devices, padded to a multiple of their count; `n` are real."""

    x: jax.Array
    n: int


def place(points: np.ndarray, devices) -> Rows:
    """`points` as float32, row-wise over `devices`, each chip's shard
    copied to it from the host alone."""
    points = np.asarray(points, np.float32)
    n, d = points.shape
    chips = len(devices)
    per_chip = -(-n // chips)
    sharding = NamedSharding(Mesh(np.asarray(devices), ("rows",)),
                             PartitionSpec("rows", None))

    def shard(index):
        lo, hi, _ = index[0].indices(per_chip * chips)
        block = points[lo:min(hi, n)]
        if len(block) < hi - lo:
            block = np.concatenate(
                [block, np.zeros((hi - lo - len(block), d), np.float32)])
        return block

    return Rows(jax.make_array_from_callback((per_chip * chips, d), sharding,
                                             shard), n)


def _d2_to(points, c):
    return jnp.sum((points - c[None, :]) ** 2, axis=1)


def _row(x, i):
    """Row i of x, taken by a gather: on rows over several chips each
    takes what it holds and the row is summed across them, where a
    dynamic slice would gather every row onto every chip."""
    return jnp.take(x, i[None], axis=0, mode="clip")[0]


@functools.partial(jax.jit, static_argnames=("k", "n"))
def _kmeanspp(x, key, *, k, n):
    """Indices of k centers by exact D^2 sampling (Arthur & Vassilvitskii)
    over the first n rows of x."""
    key, k0 = jax.random.split(key)
    first = jax.random.randint(k0, (), 0, n)
    d2 = _d2_to(x, _row(x, first))
    chosen = jnp.zeros((k,), jnp.int32).at[0].set(first)

    def body(i, state):
        d2, chosen, key = state
        key, ki = jax.random.split(key)
        logits = jnp.where(d2 > 0, jnp.log(jnp.maximum(d2, 1e-30)), -jnp.inf)
        if x.shape[0] > n:
            logits = jnp.where(jnp.arange(x.shape[0]) < n, logits, -jnp.inf)
        nxt = jax.random.categorical(ki, logits).astype(jnp.int32)
        d2 = jnp.minimum(d2, _d2_to(x, _row(x, nxt)))
        return d2, chosen.at[i].set(nxt), key

    _, chosen, _ = jax.lax.fori_loop(1, k, body, (d2, chosen, key))
    return chosen


def kmeanspp(rows: Rows, k: int, seed: int):
    """k-means++ center indices on the placed rows."""
    key = jax.random.key(int(seed) % (2 ** 32))
    return _kmeanspp(rows.x, key, k=k, n=rows.n)


@jax.jit
def _nearest_d2(block, centers):
    cross = jnp.matmul(block, centers.T, precision=HI)
    d2x = (jnp.sum(block * block, axis=1, keepdims=True) - 2.0 * cross
           + jnp.sum(centers * centers, axis=1)[None, :])
    near = jnp.argmin(d2x, axis=1)
    return jnp.sum((block - centers[near]) ** 2, axis=1)


@functools.partial(jax.jit, static_argnames=("size", "chips"))
def _block(x, lo, *, size, chips):
    """Rows [lo, lo + size) of each chip's shard, chip after chip."""
    d = x.shape[1]
    rows = jax.lax.dynamic_slice_in_dim(x.reshape(chips, -1, d), lo, size, 1)
    return rows.reshape(chips * size, d)


def seeding_cost(rows: Rows, indices) -> float:
    """k-means cost of the centers `rows.x[indices]`, float64 sum of the
    real rows' distances, block by block of `ROW_BLOCK` rows."""
    x, n = rows
    idx = jnp.asarray(np.asarray(indices, np.int64), jnp.int32)
    centers = jnp.take(x, idx, axis=0)
    chips = len(x.sharding.device_set)
    per_chip = x.shape[0] // chips
    blocks = [(lo, min(ROW_BLOCK, per_chip - lo))
              for lo in range(0, per_chip, ROW_BLOCK)]
    outs = [_nearest_d2(_block(x, lo, size=size, chips=chips), centers)
            for lo, size in blocks]
    d2 = np.empty((chips, per_chip), np.float32)
    for (lo, size), out in zip(blocks, outs):
        d2[:, lo:lo + size] = np.asarray(out).reshape(chips, size)
    d2 = d2.reshape(-1)[:n].astype(np.float64)
    total = 0.0
    for lo in range(0, n, ROW_BLOCK):
        total += float(d2[lo:lo + ROW_BLOCK].sum())
    return total
