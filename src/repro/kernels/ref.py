"""Pure-jnp oracles for every Pallas kernel (the correctness references).

Each function mirrors the semantics of its kernel twin exactly (same
accumulation dtype, same tie-breaking) so tests can `assert_allclose`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "pairwise_argmin_ref",
    "d2_update_ref",
    "d2_update_tiles_ref",
    "tree_sep_update_ref",
    "tree_sep_update_tiles_ref",
    "lsh_bucket_min_ref",
    "lsh_bucket_accept_ref",
]


def _tile_sums_ref(w: jax.Array, block_n: int) -> jax.Array:
    """Per-tile weight sums — the `_tiles` wrappers' oracle."""
    return w.reshape(-1, block_n).sum(axis=1)


def pairwise_argmin_ref(x: jax.Array, c: jax.Array):
    """argmin_c ||x - c||^2 per row of x.

    Returns (min_d2 f32 (n,), argmin int32 (n,)).  f32 accumulation; ties
    break to the smallest center index (jnp.argmin semantics).
    """
    x = x.astype(jnp.float32)
    c = c.astype(jnp.float32)
    x_sq = (x * x).sum(axis=1)
    c_sq = (c * c).sum(axis=1)
    d2 = x_sq[:, None] - 2.0 * (x @ c.T) + c_sq[None, :]
    d2 = jnp.maximum(d2, 0.0)
    idx = jnp.argmin(d2, axis=1).astype(jnp.int32)
    return jnp.min(d2, axis=1), idx


def d2_update_ref(x: jax.Array, center: jax.Array, w: jax.Array):
    """w <- min(w, ||x - center||^2): the D^2 maintenance step of k-means++."""
    x = x.astype(jnp.float32)
    center = center.astype(jnp.float32)
    diff = x - center[None, :]
    d2 = (diff * diff).sum(axis=1)
    return jnp.minimum(w.astype(jnp.float32), d2)


def d2_update_tiles_ref(x: jax.Array, center: jax.Array, w: jax.Array, *,
                        block_n: int = 512):  # autotune: matches pallas default
    """(w', per-tile sums of w') — `ops.d2_update_tiles` oracle."""
    out = d2_update_ref(x, center, w)
    return out, _tile_sums_ref(out, block_n)


def tree_sep_update_ref(
    codes_lo: jax.Array,     # (H, n) int32 — low 32 bits of cell codes
    codes_hi: jax.Array,     # (H, n) int32 — high 32 bits
    center_lo: jax.Array,    # (H,) int32
    center_hi: jax.Array,    # (H,) int32
    w: jax.Array,            # (n,) f32 — current MultiTreeDist(x, S)^2
    *,
    scale: float,            # 2 * sqrt(d) * max_dist
    num_levels: int,         # H (heights incl. root)
):
    """One tree's MULTITREEOPEN weight sweep (DESIGN.md §3).

    sep(y, x) = 1 (root) + #{h >= 1 : codes agree}; the closed-form tree
    distance is scale * (2^(1-sep) - 2^(1-H)); w' = min(w, dist^2).
    The code arrays carry heights 1..H-1 (the root is implicit).
    """
    eq = (codes_lo == center_lo[:, None]) & (codes_hi == center_hi[:, None])
    sep = 1 + eq.sum(axis=0).astype(jnp.int32)
    dist = scale * (jnp.exp2(1.0 - sep.astype(jnp.float32)) - 2.0 ** (1.0 - num_levels))
    dist = jnp.maximum(dist, 0.0)
    return jnp.minimum(w.astype(jnp.float32), dist * dist)


def tree_sep_update_tiles_ref(
    codes_lo: jax.Array,
    codes_hi: jax.Array,
    center_lo: jax.Array,
    center_hi: jax.Array,
    w: jax.Array,
    *,
    scale: float,
    num_levels: int,
    block_n: int = 512,  # autotune: matches pallas default
):
    """(w', per-tile sums of w') — `ops.tree_sep_update_tiles` oracle."""
    out = tree_sep_update_ref(codes_lo, codes_hi, center_lo, center_hi, w,
                              scale=scale, num_levels=num_levels)
    return out, _tile_sums_ref(out, block_n)


def lsh_bucket_min_ref(
    q_keys_lo: jax.Array,    # (L, B) int32 — candidate bucket keys, low plane
    q_keys_hi: jax.Array,    # (L, B) int32
    q: jax.Array,            # (B, D) — candidate coordinates
    c_keys_lo: jax.Array,    # (L, K) int32 — opened-center bucket keys
    c_keys_hi: jax.Array,    # (L, K) int32
    c: jax.Array,            # (K, D) — opened-center coordinates
    count=None,              # scalar — only the first `count` centers live
):
    """Monotone-LSH nearest-bucket query: min over centers sharing a bucket.

    Returns (B,) f32 — squared distance to the nearest colliding center, or
    `LSH_MISS` when no center shares any of the L buckets (the rejection
    sampler then accepts, mirroring `MonotoneLSH.query_batch`'s +inf miss).
    """
    from repro.kernels.lsh_bucket_min import LSH_MISS

    collide = (
        (q_keys_lo[:, :, None] == c_keys_lo[:, None, :])
        & (q_keys_hi[:, :, None] == c_keys_hi[:, None, :])
    ).any(axis=0)                                       # (B, K)
    if count is not None:
        collide &= (jnp.arange(c.shape[0]) < count)[None, :]
    qf = q.astype(jnp.float32)
    cf = c.astype(jnp.float32)
    q_sq = (qf * qf).sum(axis=1)
    c_sq = (cf * cf).sum(axis=1)
    d2 = jnp.maximum(q_sq[:, None] - 2.0 * (qf @ cf.T) + c_sq[None, :], 0.0)
    return jnp.where(collide, d2, LSH_MISS).min(axis=1)


def lsh_bucket_accept_ref(
    q_keys_lo: jax.Array,
    q_keys_hi: jax.Array,
    q: jax.Array,
    c_keys_lo: jax.Array,
    c_keys_hi: jax.Array,
    c: jax.Array,
    mtd2: jax.Array,         # (B,) — current multi-tree D^2 weights
    count=None,
    *,
    c2: float,
):
    """(d2_min, acceptance probability) — `lsh_bucket_accept_pallas` oracle.

    ``p = d2_min / (c^2 * mtd2)`` with ``p = 0`` where ``mtd2 == 0``; a miss
    (``d2_min == LSH_MISS``) gives p >> 1, i.e. always accepts.
    """
    d2_min = lsh_bucket_min_ref(q_keys_lo, q_keys_hi, q,
                                c_keys_lo, c_keys_hi, c, count)
    mtd2 = mtd2.astype(jnp.float32)
    p = jnp.where(mtd2 > 0.0, d2_min / jnp.maximum(c2 * mtd2, 1e-30), 0.0)
    return d2_min, p


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        scale: float, causal: bool = True):
    """Exact attention oracle for the flash kernel.  (BH, S, D) layout."""
    qf = q.astype(jnp.float32) * scale
    s = jnp.einsum("bqd,bkd->bqk", qf, k.astype(jnp.float32))
    if causal:
        n = q.shape[1]
        mask = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
        s = jnp.where(mask[None], s, -1.0e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32))
