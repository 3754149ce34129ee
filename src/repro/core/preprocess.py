"""Aspect-ratio control (paper Appendix F).

The running time carries a log(Delta) factor (Delta = max/min pairwise
distance).  Appendix F bounds it by quantising coordinates to an integer grid
whose resolution is a small fraction of a cheaply-estimated optimum cost:

  1. sample 20 random points as a rough solution and compute its cost;
  2. scaling = cost / (n * d * 200)  (per-coordinate error budget; the factor
     200 keeps the total quantisation error within ~0.5% of that cost);
  3. floor-divide every coordinate by `scaling`.

After this, log Delta = O(log(n d)) and the quantisation scale is the natural
`resolution` for the tree embedding and the LSH collision width.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
from threadpoolctl import threadpool_limits

from repro.core.lloyd import assign

__all__ = ["quantize", "QuantizedData", "host_block_map",
           "QUANTIZE_BLOCK_ROWS"]

#: Rows per block of `quantize`: `assign`'s chunk, so that a block's rows
#: are chunked as the whole set's are.
QUANTIZE_BLOCK_ROWS = 65536


@contextlib.contextmanager
def host_block_map(blocks: int):
    """`map` over a thread pool for `blocks` blocks of host work (NumPy
    releases the GIL in its loops); the plain `map`, on this thread, for
    one block.

    While the pool runs, BLAS runs one thread a call: a block's matrix
    products are small, and BLAS's own threads, one set a call from every
    pool thread, leave the pool no faster than one thread.  BLAS never
    splits the inner dimension, so every element is summed as before."""
    if blocks <= 1:
        yield map
        return
    with threadpool_limits(1, user_api="blas"), \
            ThreadPoolExecutor(min(blocks, os.cpu_count() or 1)) as pool:
        yield pool.map


@dataclasses.dataclass
class QuantizedData:
    points: np.ndarray      # quantised coordinates (float64, integer-valued)
    scaling: float          # one grid unit in original coordinates
    estimate: float         # the rough 20-center solution cost used


def quantize(
    points: np.ndarray, rng: np.random.Generator, *, sample_centers: int = 20
) -> QuantizedData:
    """Quantises `points` (steps 1-3 above), block by block of
    `QUANTIZE_BLOCK_ROWS` rows, side by side for a set of several blocks:
    every value is the one the whole set in one piece would give."""
    pts = np.asarray(points, dtype=np.float64)
    n, d = pts.shape
    idx = rng.choice(n, size=min(sample_centers, n), replace=False)
    sample = pts[idx]
    starts = range(0, n, QUANTIZE_BLOCK_ROWS)
    d2 = np.empty(n, np.float64)

    def nearest(lo):
        hi = lo + QUANTIZE_BLOCK_ROWS
        d2[lo:hi] = assign(pts[lo:hi], sample)[1]

    def scaled(lo):
        block = q[lo:lo + QUANTIZE_BLOCK_ROWS]
        np.divide(pts[lo:lo + QUANTIZE_BLOCK_ROWS], scaling, out=block)
        np.floor(block, out=block)

    with host_block_map(len(starts)) as map_fn:
        list(map_fn(nearest, starts))
        est = float(d2.sum())
        if est <= 0:  # all points identical: nothing to scale
            return QuantizedData(points=pts.copy(), scaling=1.0,
                                 estimate=0.0)
        scaling = np.sqrt(est / (n * d)) / 200.0
        q = np.empty_like(pts)
        list(map_fn(scaled, starts))
    return QuantizedData(points=q, scaling=scaling, estimate=est)
