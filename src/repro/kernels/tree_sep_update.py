"""Pallas TPU kernel: one tree's MULTITREEOPEN weight sweep.

TPU-native form of the paper's Algorithm 1 inner loop (DESIGN.md §3): when a
center x opens, every point's tree distance to the center set can only
improve through x, and the improvement is a closed form of the *separation
level* — the number of grid heights at which the point shares x's cell.

The kernel fuses, per point tile:
  sep   = 1 + sum_h [codes(y, h) == codes(x, h)]     (VPU compare+reduce)
  dist  = scale * (2^(1-sep) - 2^(1-H))
  w'    = min(w, dist^2)

Cell codes are 64-bit hashes stored as two int32 planes (TPU has no 64-bit
integers); equality requires both planes to agree.  The (H, BN) code tiles
put points in the lane dimension; H (~20-32, padded to a multiple of 8) sits
in sublanes, and the weight vector is a lane-dense ``(1, n)`` array in
``(1, block_n)`` blocks (Mosaic refuses rank-1 blocks smaller than the
array).

Grid: 1-D over point tiles; the opened center's code column is broadcast.
The per-tile weight sums that the coarse `TiledSampleTree` heap consumes
are reduced outside the kernel (`ops.tree_sep_update_tiles`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["tree_sep_update_pallas"]


def _kernel(lo_ref, hi_ref, clo_ref, chi_ref, w_ref, out_ref, *,
            scale: float, num_levels: int):
    lo = lo_ref[...]                       # (H, BN) int32
    hi = hi_ref[...]
    clo = clo_ref[...]                     # (H, 1) int32
    chi = chi_ref[...]
    eq = (lo == clo) & (hi == chi)         # (H, BN)
    sep = 1 + jnp.sum(eq.astype(jnp.int32), axis=0, keepdims=True)  # (1, BN)
    dist = scale * (
        jnp.exp2(1.0 - sep.astype(jnp.float32)) - 2.0 ** (1.0 - num_levels)
    )
    dist = jnp.maximum(dist, 0.0)
    out_ref[...] = jnp.minimum(w_ref[...].astype(jnp.float32), dist * dist)


@functools.partial(
    jax.jit, static_argnames=("block_n", "scale", "num_levels", "interpret")
)
def tree_sep_update_pallas(
    codes_lo: jax.Array,    # (H, n) int32
    codes_hi: jax.Array,    # (H, n) int32
    center_lo: jax.Array,   # (H,) int32
    center_hi: jax.Array,   # (H,) int32
    w: jax.Array,           # (1, n) f32
    *,
    scale: float,
    num_levels: int,
    block_n: int = 1024,  # autotune: VMEM-sized row tile; retune on hw
    interpret: bool = False,
):
    """Pre-padded inputs (n % block_n == 0); returns (1, n).  See
    `ops.tree_sep_update`."""
    h, n = codes_lo.shape
    assert n % block_n == 0, (n, block_n)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, num_levels=num_levels),
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((h, block_n), lambda i: (0, i)),
            pl.BlockSpec((h, block_n), lambda i: (0, i)),
            pl.BlockSpec((h, 1), lambda i: (0, 0)),
            pl.BlockSpec((h, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=interpret,
        name="tree_sep_update_pallas",
    )(codes_lo, codes_hi, center_lo.reshape(-1, 1), center_hi.reshape(-1, 1), w)
