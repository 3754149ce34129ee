"""Pallas TPU kernel: fused D^2 weight maintenance for one new center.

``w <- min(w, ||x - center||^2)`` over all n points — the inner loop of
exact k-means++ seeding (one call per opened center) and of the device-side
rejection seeder's bookkeeping.  Fusing the distance computation with the
min-update halves HBM traffic vs materialising the distance vector
(read x + w, write w; no intermediate).

The weight vector travels lane-dense as a ``(1, n)`` array in ``(1,
block_n)`` blocks: Mosaic refuses rank-1 blocks smaller than the array
(XLA tiles a 1-D f32 array by 1024, the block by `block_n`).  Per-tile
weight sums for the `TiledSampleTree` heap are reduced outside the kernel
(`ops.d2_update_tiles`).

Grid: 1-D over point tiles; the center row is broadcast to every tile
(a (1, d) block with a constant index map).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["d2_update_pallas"]


def _kernel(x_ref, c_ref, w_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)       # (BN, D)
    c = c_ref[...].astype(jnp.float32)       # (1, D)
    diff = x - c
    d2 = jnp.sum(diff * diff, axis=1)        # (BN,)
    out_ref[...] = jnp.minimum(w_ref[...].astype(jnp.float32),
                               d2.reshape(1, -1))


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def d2_update_pallas(
    x: jax.Array,
    center: jax.Array,
    w: jax.Array,            # (1, n) f32
    *,
    block_n: int = 512,  # autotune: VMEM-sized row tile; retune on hw
    interpret: bool = False,
):
    """Pre-padded inputs (n % block_n == 0); returns (1, n).  See
    `ops.d2_update`."""
    n, d = x.shape
    assert n % block_n == 0, (n, block_n)
    return pl.pallas_call(
        _kernel,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=interpret,
        name="d2_update_pallas",
    )(x, center.reshape(1, -1), w)
