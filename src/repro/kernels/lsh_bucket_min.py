"""Pallas TPU kernel: monotone-LSH nearest-bucket query, fused.

The acceptance test of the paper's Algorithm 4 needs, per candidate x,
``dist(x, Query(x))^2`` — the squared distance to the nearest *opened center
that shares an LSH bucket with x* in at least one of the L hash tables
(`repro.core.lsh.MonotoneLSH` semantics: minimum-distance colliding entry,
+infinity on a complete miss, which the sampler treats as "accept").

Bucket keys are 64-bit hashes precomputed host-side for every point (like the
multi-tree cell codes) and stored as two int32 planes in a (L, n) layout —
tables in sublanes, points in lanes, exactly the `tree_sep_update` idiom.
The kernel fuses, per (candidate tile, center tile):

  collide[b, c] = OR_l (qk(b, l) == ck(c, l))        (VPU compare+reduce)
  d2[b, c]      = |q_b|^2 - 2 q_b . c_c + |c_c|^2    (MXU matmul)
  out[b]        = min(out[b], min_c where(collide, d2, MISS))

Grid: ``(B // BB, K // BK)`` with the center dimension minor so the output
tile stays resident in VMEM while center tiles sweep (the `pairwise_argmin`
accumulation pattern).  A miss leaves the lane at ``MISS`` (3e38, finite so
downstream f32 arithmetic stays NaN-free); callers compare against
``MISS / 2`` to detect it.  The per-candidate vectors (``mtd2``, the
outputs) are lane-dense ``(1, B)`` arrays in ``(1, block_b)`` blocks: Mosaic
refuses rank-1 blocks smaller than the array.  The matmul runs at
`MATMUL_PRECISION`, for the reason `pairwise_argmin` gives.

The `_accept` variant fuses the rejection sampler's acceptance epilogue: at
the final center tile (the accumulated min is then complete) it also emits
``p = d2_min / (c^2 * mtd2)`` per candidate — the Algorithm 4 acceptance
probability — so the seeder's inner loop reads one fused kernel result
instead of post-processing the distance vector.  A complete LSH miss makes
``p`` astronomically large (always accepts), matching the CPU structure's
+inf convention; ``mtd2 == 0`` (already-covered point) yields ``p = 0``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.pairwise_argmin import MATMUL_PRECISION

__all__ = ["lsh_bucket_min_pallas", "lsh_bucket_accept_pallas", "LSH_MISS"]

LSH_MISS = 3.0e38  # "no colliding center" sentinel (finite in f32)


def _kernel(qk_lo_ref, qk_hi_ref, q_ref, ck_lo_ref, ck_hi_ref, c_ref,
            pen_ref, out_ref, *, num_tables: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, LSH_MISS)

    qk_lo = qk_lo_ref[...]                 # (L, BB) int32
    qk_hi = qk_hi_ref[...]
    ck_lo = ck_lo_ref[...]                 # (L, BK) int32
    ck_hi = ck_hi_ref[...]
    bb = qk_lo.shape[1]
    bk = ck_lo.shape[1]
    # Bucket collision in any table: unrolled OR over the (static, small) L.
    collide = jnp.zeros((bb, bk), dtype=jnp.bool_)
    for l in range(num_tables):
        collide |= (qk_lo[l, :][:, None] == ck_lo[l, :][None, :]) & (
            qk_hi[l, :][:, None] == ck_hi[l, :][None, :]
        )

    q = q_ref[...].astype(jnp.float32)     # (BB, D)
    c = c_ref[...].astype(jnp.float32)     # (BK, D)
    dots = jax.lax.dot_general(
        q, c, (((1,), (1,)), ((), ())), precision=MATMUL_PRECISION,
        preferred_element_type=jnp.float32,
    )                                      # (BB, BK) on the MXU
    q_sq = jnp.sum(q * q, axis=1, keepdims=True)       # (BB, 1)
    c_sq = jnp.sum(c * c, axis=1, keepdims=True).T     # (1, BK)
    d2 = jnp.maximum(q_sq - 2.0 * dots + c_sq, 0.0)

    # penalty row: 0 for live centers, LSH_MISS for padded / not-yet-opened
    # slots — the max() turns any accidental collision with them into a miss.
    masked = jnp.maximum(jnp.where(collide, d2, LSH_MISS), pen_ref[...])
    out_ref[...] = jnp.minimum(out_ref[...],
                               jnp.min(masked, axis=1).reshape(1, -1))


def _kernel_accept(qk_lo_ref, qk_hi_ref, q_ref, ck_lo_ref, ck_hi_ref, c_ref,
                   pen_ref, mtd2_ref, out_ref, p_ref, *, num_tables: int,
                   c2: float):
    _kernel(qk_lo_ref, qk_hi_ref, q_ref, ck_lo_ref, ck_hi_ref, c_ref,
            pen_ref, out_ref, num_tables=num_tables)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _epilogue():
        mtd2 = mtd2_ref[...].astype(jnp.float32)
        p_ref[...] = jnp.where(
            mtd2 > 0.0, out_ref[...] / jnp.maximum(c2 * mtd2, 1e-30), 0.0
        )


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_k", "interpret")
)
def lsh_bucket_min_pallas(
    q_keys_lo: jax.Array,    # (L, B) int32 — candidate bucket keys, low plane
    q_keys_hi: jax.Array,    # (L, B) int32
    q: jax.Array,            # (B, D) f32  — candidate coordinates
    c_keys_lo: jax.Array,    # (L, K) int32 — opened-center bucket keys
    c_keys_hi: jax.Array,    # (L, K) int32
    c: jax.Array,            # (K, D) f32  — opened-center coordinates
    penalty: jax.Array,      # (1, K) f32  — 0 live, LSH_MISS masked-out
    *,
    block_b: int = 128,  # autotune: lane-width tile; retune on hw
    block_k: int = 128,  # autotune: lane-width tile; retune on hw
    interpret: bool = False,
):
    """Pre-padded inputs (B % block_b == 0, K % block_k == 0, L % 8 == 0);
    returns (1, B).  See `ops.lsh_bucket_min` for the padding wrapper."""
    l, b = q_keys_lo.shape
    k = c_keys_lo.shape[1]
    assert b % block_b == 0 and k % block_k == 0, (b, k, block_b, block_k)
    d = q.shape[1]
    grid = (b // block_b, k // block_k)
    return pl.pallas_call(
        functools.partial(_kernel, num_tables=l),
        grid=grid,
        in_specs=[
            pl.BlockSpec((l, block_b), lambda i, j: (0, i)),
            pl.BlockSpec((l, block_b), lambda i, j: (0, i)),
            pl.BlockSpec((block_b, d), lambda i, j: (i, 0)),
            pl.BlockSpec((l, block_k), lambda i, j: (0, j)),
            pl.BlockSpec((l, block_k), lambda i, j: (0, j)),
            pl.BlockSpec((block_k, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, block_k), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_b), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, b), jnp.float32),
        interpret=interpret,
        name="lsh_bucket_min_pallas",
    )(q_keys_lo, q_keys_hi, q, c_keys_lo, c_keys_hi, c, penalty)


@functools.partial(
    jax.jit, static_argnames=("c2", "block_b", "block_k", "interpret")
)
def lsh_bucket_accept_pallas(
    q_keys_lo: jax.Array,    # (L, B) int32
    q_keys_hi: jax.Array,
    q: jax.Array,            # (B, D) f32
    c_keys_lo: jax.Array,    # (L, K) int32
    c_keys_hi: jax.Array,
    c: jax.Array,            # (K, D) f32
    penalty: jax.Array,      # (1, K) f32
    mtd2: jax.Array,         # (1, B) f32 — current multi-tree D^2 weights
    *,
    c2: float,
    block_b: int = 128,  # autotune: lane-width tile; retune on hw
    block_k: int = 128,  # autotune: lane-width tile; retune on hw
    interpret: bool = False,
):
    """`lsh_bucket_min_pallas` + the fused acceptance-probability epilogue.

    Returns ``(d2_min (1, B), p_accept (1, B))``; pre-padded inputs as in
    `lsh_bucket_min_pallas`, ``mtd2`` padded to the candidate block multiple.
    """
    l, b = q_keys_lo.shape
    k = c_keys_lo.shape[1]
    assert b % block_b == 0 and k % block_k == 0, (b, k, block_b, block_k)
    d = q.shape[1]
    grid = (b // block_b, k // block_k)
    return pl.pallas_call(
        functools.partial(_kernel_accept, num_tables=l, c2=c2),
        grid=grid,
        in_specs=[
            pl.BlockSpec((l, block_b), lambda i, j: (0, i)),
            pl.BlockSpec((l, block_b), lambda i, j: (0, i)),
            pl.BlockSpec((block_b, d), lambda i, j: (i, 0)),
            pl.BlockSpec((l, block_k), lambda i, j: (0, j)),
            pl.BlockSpec((l, block_k), lambda i, j: (0, j)),
            pl.BlockSpec((block_k, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, block_k), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_b), lambda i, j: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_b), lambda i, j: (0, i)),
            pl.BlockSpec((1, block_b), lambda i, j: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, b), jnp.float32),
            jax.ShapeDtypeStruct((1, b), jnp.float32),
        ],
        interpret=interpret,
        name="lsh_bucket_accept_pallas",
    )(q_keys_lo, q_keys_hi, q, c_keys_lo, c_keys_hi, c, penalty, mtd2)
