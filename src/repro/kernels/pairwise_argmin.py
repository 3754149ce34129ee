"""Pallas TPU kernel: tiled nearest-center search (argmin_c ||x - c||^2).

The compute hot spot of Lloyd's assignment step, exact k-means++ D^2
maintenance and the rejection sampler's acceptance test.  The squared
distance decomposes as ``x^2 + c^2 - 2 x.c`` so the inner loop is an MXU
matmul of an (BN, D) point tile against a (BK, D) center tile held in VMEM,
plus a running min/argmin accumulator carried across center tiles.

Grid: ``(n // BN, k // BK)`` with the center dimension minor, so the output
block (indexed only by the point tile) stays resident in VMEM while the
kernel sweeps center tiles (the standard Pallas accumulation pattern).

Block shapes default to (128, d) x (128, d): MXU-aligned on the matmul
dims; d stays un-tiled because clustering dimensionality (<= a few hundred)
fits VMEM comfortably: 2 * 128 * d * 4B ~ 0.1-0.4 MB << 16 MB.  The
per-point outputs are lane-dense ``(1, n)`` arrays in ``(1, block_n)``
blocks (Mosaic refuses rank-1 blocks smaller than the array).

The matmul runs at `MATMUL_PRECISION`, f32 on the MXU.  The expansion
cancels for nearby points, so a bf16 pass would misorder argmins at real
coordinate scales.  On a v5e, Mosaic's default for f32 operands measured
the same (max error 2.9e-7 of |x|^2 + |c|^2 against float64 at KDD-Cup
width, `chip_smoke.py`); HIGHEST states the requirement instead of relying
on that default.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["pairwise_argmin_pallas", "MATMUL_PRECISION"]

MATMUL_PRECISION = jax.lax.Precision.HIGHEST


def _kernel(x_ref, c_ref, min_ref, arg_ref, *, block_k: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        min_ref[...] = jnp.full_like(min_ref, jnp.inf)
        arg_ref[...] = jnp.zeros_like(arg_ref)

    x = x_ref[...].astype(jnp.float32)           # (BN, D)
    c = c_ref[...].astype(jnp.float32)           # (BK, D)
    dots = jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())), precision=MATMUL_PRECISION,
        preferred_element_type=jnp.float32,
    )                                            # (BN, BK) on the MXU
    x_sq = jnp.sum(x * x, axis=1, keepdims=True)          # (BN, 1)
    c_sq = jnp.sum(c * c, axis=1, keepdims=True).T        # (1, BK)
    d2 = jnp.maximum(x_sq - 2.0 * dots + c_sq, 0.0)

    local_min = jnp.min(d2, axis=1).reshape(1, -1)               # (1, BN)
    local_arg = (jnp.argmin(d2, axis=1).astype(jnp.int32)
                 + j * block_k).reshape(1, -1)

    better = local_min < min_ref[...]
    min_ref[...] = jnp.where(better, local_min, min_ref[...])
    arg_ref[...] = jnp.where(better, local_arg, arg_ref[...])


@functools.partial(jax.jit, static_argnames=("block_n", "block_k", "interpret"))
def pairwise_argmin_pallas(
    x: jax.Array,
    c: jax.Array,
    *,
    block_n: int = 128,  # autotune: lane-width tile; retune on hw
    block_k: int = 128,  # autotune: lane-width tile; retune on hw
    interpret: bool = False,
):
    """(min_d2 f32 (1, n), argmin int32 (1, n)).  Requires pre-padded inputs:
    n % block_n == 0, k % block_k == 0 (use `ops.pairwise_argmin` for the
    padding/unpadding wrapper)."""
    n, d = x.shape
    k = c.shape[0]
    assert n % block_n == 0 and k % block_k == 0, (n, k, block_n, block_k)
    grid = (n // block_n, k // block_k)
    return pl.pallas_call(
        functools.partial(_kernel, block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_k, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_n), lambda i, j: (0, i)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.int32),
        ],
        interpret=interpret,
        name="pairwise_argmin_pallas",
    )(x, c)
