"""Jit'd public wrappers for the Pallas kernels.

Handle padding/unpadding to kernel block multiples, the kernels' lane-dense
``(1, n)`` per-point layout, and the execution mode: compiled Pallas on TPU,
`interpret=True` elsewhere (the kernel body then runs as reference
Python/XLA ops on CPU — bit-identical semantics, used by tests).  Every
wrapper has a pure-jnp oracle in `ref.py`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.d2_update import d2_update_pallas
from repro.kernels.lsh_bucket_min import (
    LSH_MISS,
    lsh_bucket_accept_pallas,
    lsh_bucket_min_pallas,
)
from repro.kernels.pairwise_argmin import pairwise_argmin_pallas
from repro.kernels.tree_sep_update import tree_sep_update_pallas

__all__ = [
    "pairwise_argmin",
    "d2_update",
    "d2_update_tiles",
    "tree_sep_update",
    "tree_sep_update_tiles",
    "pad_tree_codes",
    "lsh_bucket_min",
    "lsh_bucket_accept",
    "LSH_MISS",
    "default_interpret",
]

_PAD_DIST = 3.0e38  # padded centers sit "at infinity"
_PAD_FAR = 1.0e17   # per-coordinate "far away" (distance^2 stays f32-finite)


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(a: jax.Array, axis: int, multiple: int, value) -> jax.Array:
    size = a.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths, constant_values=value)


def _row(v: jax.Array) -> jax.Array:
    """(n,) -> the kernels' lane-dense (1, n) layout."""
    return v.reshape(1, -1)


def _tile_sums(w_pad: jax.Array, block_n: int) -> jax.Array:
    """Per-tile sums of a padded weight vector (the `TiledSampleTree`
    leaf update), reduced by XLA next to the kernel."""
    return w_pad.reshape(-1, block_n).sum(axis=1)


def pairwise_argmin(
    x: jax.Array,
    c: jax.Array,
    *,
    block_n: int = 128,  # autotune: lane-width tile; retune on hw
    block_k: int = 128,  # autotune: lane-width tile; retune on hw
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """(min squared distance, argmin center index) per point.

    Accepts any (n, d) x (k, d); pads internally.  f32 accumulation.
    """
    if interpret is None:
        interpret = default_interpret()
    n, k = x.shape[0], c.shape[0]
    xp = _pad_to(x, 0, block_n, 0)
    # Padded centers must never win the argmin: place them at "infinity"
    # on a single coordinate (keeps x^2 + c^2 - 2xc finite in f32).
    cp = _pad_to(c, 0, block_k, 0)
    if cp.shape[0] != k:
        mask = (jnp.arange(cp.shape[0]) >= k)[:, None]
        cp = jnp.where(mask, jnp.full_like(cp, 1.0e17), cp)
    d2, idx = pairwise_argmin_pallas(
        xp, cp, block_n=block_n, block_k=block_k, interpret=interpret
    )
    return d2[0, :n], idx[0, :n]


def _d2_update_padded(x, center, w, block_n, interpret):
    """The d2 sweep on block-padded inputs; returns the padded (n_pad,) w'
    (padding lanes carry w=0, so they add nothing to tile sums)."""
    if interpret is None:
        interpret = default_interpret()
    xp = _pad_to(x, 0, block_n, 0)
    wp = _pad_to(w, 0, block_n, 0.0)
    return d2_update_pallas(xp, center, _row(wp), block_n=block_n,
                            interpret=interpret)[0]


def d2_update(
    x: jax.Array,
    center: jax.Array,
    w: jax.Array,
    *,
    block_n: int = 512,  # autotune: VMEM-sized row tile; retune on hw
    interpret: bool | None = None,
) -> jax.Array:
    """w <- min(w, ||x - center||^2); any n, pads internally."""
    return _d2_update_padded(x, center, w, block_n, interpret)[:x.shape[0]]


def d2_update_tiles(
    x: jax.Array,
    center: jax.Array,
    w: jax.Array,
    *,
    block_n: int = 512,  # autotune: VMEM-sized row tile; retune on hw
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """(w', per-tile sums); any n, pads internally.  Returns the *padded*
    weight vector alongside the (ceil(n/block_n),) sums — callers running
    the incremental `TiledSampleTree` path keep the padded layout as loop
    state, so no per-call unpad slicing."""
    out = _d2_update_padded(x, center, w, block_n, interpret)
    return out, _tile_sums(out, block_n)


def _pad_tree_plane(codes, block_n):
    """(H, n) codes -> (H8, n_pad): points padded with 0, heights with the
    point sentinel -1."""
    return _pad_to(_pad_to(codes, 1, block_n, 0), 0, 8, -1)


def _tree_sep_padded(codes_lo, codes_hi, center_lo, center_hi, w, *, scale,
                     num_levels, block_n, interpret):
    """The tree sweep on block-padded inputs; returns the padded w'.

    Height padding (to a sublane multiple of 8) uses codes that can never
    match (-1 vs -2), so padded heights contribute nothing to `sep`.  Every
    pad here is a no-op on planes from `pad_tree_codes`: the seeders pad
    those once per seeding, outside their per-center loop, and hand in the
    center's column at its real height, so only that column (here, with
    -2) is padded per sweep.
    """
    if interpret is None:
        interpret = default_interpret()
    lo = _pad_tree_plane(codes_lo, block_n)
    hi = _pad_tree_plane(codes_hi, block_n)
    clo = _pad_to(center_lo, 0, 8, -2)
    chi = _pad_to(center_hi, 0, 8, -2)
    wp = _pad_to(w, 0, block_n, 0.0)
    return tree_sep_update_pallas(
        lo, hi, clo, chi, _row(wp),
        scale=scale, num_levels=num_levels, block_n=block_n,
        interpret=interpret,
    )[0]


def tree_sep_update(
    codes_lo: jax.Array,
    codes_hi: jax.Array,
    center_lo: jax.Array,
    center_hi: jax.Array,
    w: jax.Array,
    *,
    scale: float,
    num_levels: int,
    block_n: int = 1024,  # autotune: VMEM-sized row tile; retune on hw
    interpret: bool | None = None,
) -> jax.Array:
    """One tree's open-center weight sweep; any n, pads internally."""
    out = _tree_sep_padded(codes_lo, codes_hi, center_lo, center_hi, w,
                           scale=scale, num_levels=num_levels,
                           block_n=block_n, interpret=interpret)
    return out[:codes_lo.shape[1]]


def tree_sep_update_tiles(
    codes_lo: jax.Array,
    codes_hi: jax.Array,
    center_lo: jax.Array,
    center_hi: jax.Array,
    w: jax.Array,
    *,
    scale: float,
    num_levels: int,
    block_n: int = 512,  # autotune: VMEM-sized row tile; retune on hw
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """One tree's open-center sweep + per-tile sums; any n, pads internally.

    Returns the *padded* (w', tile_sums) pair (see `d2_update_tiles`): the
    device seeders carry the padded weight vector across centers and feed
    the sums straight into `TiledSampleTree.refresh`.
    """
    out = _tree_sep_padded(codes_lo, codes_hi, center_lo, center_hi, w,
                           scale=scale, num_levels=num_levels,
                           block_n=block_n, interpret=interpret)
    return out, _tile_sums(out, block_n)


def pad_tree_codes(codes: jax.Array, *,
                   block_n: int) -> tuple[jax.Array, ...]:
    """(T, H-1, n) code plane -> T per-tree (H8, n_pad) planes, padded as
    `tree_sep_update` pads them: points to a multiple of `block_n` with 0,
    heights to a multiple of 8 with the point sentinel -1.

    Codes never change during a seeding, so the device seeders call this
    once, before their per-center loop, and sweep the result: the loop
    then slices and pads no code plane.  Each plane stays its own array
    (one kernel operand per tree, not a stacked operand), and a center's
    column must be taken from the first H-1 rows, so the wrapper pads it
    with the center sentinel -2.
    """
    return tuple(_pad_tree_plane(codes[ti], block_n)
                 for ti in range(codes.shape[0]))


def lsh_bucket_min(
    q_keys_lo: jax.Array,
    q_keys_hi: jax.Array,
    q: jax.Array,
    c_keys_lo: jax.Array,
    c_keys_hi: jax.Array,
    c: jax.Array,
    count: jax.Array | int | None = None,
    *,
    block_b: int = 128,  # autotune: lane-width tile; retune on hw
    block_k: int = 128,  # autotune: lane-width tile; retune on hw
    interpret: bool | None = None,
) -> jax.Array:
    """Nearest colliding-bucket center per candidate; any B/K/L, pads inside.

    Keys are (L, B) / (L, K) int32 planes of the uint64 bucket keys (tables
    in sublanes, points in lanes — the `tree_sep_update` layout).  `count`
    (static or traced scalar) marks only the first `count` center slots
    live — the device seeder grows its center set inside a fixed (k, ...)
    buffer.  Padding: tables (L -> multiple of 8) use query codes -1 vs
    center codes -2 (never collide); centers and candidates pad to block
    multiples, masked via the penalty row / sliced off respectively.
    """
    if interpret is None:
        interpret = default_interpret()
    b = q.shape[0]
    k = c.shape[0]
    qlo = _pad_to(_pad_to(q_keys_lo, 1, block_b, 0), 0, 8, -1)
    qhi = _pad_to(_pad_to(q_keys_hi, 1, block_b, 0), 0, 8, -1)
    qp = _pad_to(q, 0, block_b, 0.0)
    clo = _pad_to(_pad_to(c_keys_lo, 1, block_k, -2), 0, 8, -2)
    chi = _pad_to(_pad_to(c_keys_hi, 1, block_k, -2), 0, 8, -2)
    cp = _pad_to(c, 0, block_k, _PAD_FAR)
    live = jnp.arange(cp.shape[0]) < (k if count is None else count)
    penalty = jnp.where(live, 0.0, LSH_MISS).astype(jnp.float32)[None, :]
    out = lsh_bucket_min_pallas(
        qlo, qhi, qp, clo, chi, cp, penalty,
        block_b=block_b, block_k=block_k, interpret=interpret,
    )
    return out[0, :b]


def lsh_bucket_accept(
    q_keys_lo: jax.Array,
    q_keys_hi: jax.Array,
    q: jax.Array,
    c_keys_lo: jax.Array,
    c_keys_hi: jax.Array,
    c: jax.Array,
    mtd2: jax.Array,
    count: jax.Array | int | None = None,
    *,
    c2: float,
    block_b: int = 128,  # autotune: lane-width tile; retune on hw
    block_k: int = 128,  # autotune: lane-width tile; retune on hw
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """`lsh_bucket_min` + the fused Algorithm-4 acceptance epilogue.

    Returns ``(d2_min (B,), p_accept (B,))`` with
    ``p = d2_min / (c^2 * mtd2)`` (0 where ``mtd2 == 0``); padding as in
    `lsh_bucket_min`, ``mtd2`` padded with zeros (padded lanes get p = 0).
    """
    if interpret is None:
        interpret = default_interpret()
    b = q.shape[0]
    k = c.shape[0]
    qlo = _pad_to(_pad_to(q_keys_lo, 1, block_b, 0), 0, 8, -1)
    qhi = _pad_to(_pad_to(q_keys_hi, 1, block_b, 0), 0, 8, -1)
    qp = _pad_to(q, 0, block_b, 0.0)
    clo = _pad_to(_pad_to(c_keys_lo, 1, block_k, -2), 0, 8, -2)
    chi = _pad_to(_pad_to(c_keys_hi, 1, block_k, -2), 0, 8, -2)
    cp = _pad_to(c, 0, block_k, _PAD_FAR)
    mp = _pad_to(mtd2, 0, block_b, 0.0)
    live = jnp.arange(cp.shape[0]) < (k if count is None else count)
    penalty = jnp.where(live, 0.0, LSH_MISS).astype(jnp.float32)[None, :]
    d2_min, p = lsh_bucket_accept_pallas(
        qlo, qhi, qp, clo, chi, cp, penalty, _row(mp),
        c2=c2, block_b=block_b, block_k=block_k, interpret=interpret,
    )
    return d2_min[0, :b], p[0, :b]


def split_codes_u64(codes: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """uint64 cell codes -> two int32 planes (TPU-friendly)."""
    lo = (codes & np.uint64(0xFFFFFFFF)).astype(np.int64).astype(np.int32)
    hi = (codes >> np.uint64(32)).astype(np.int64).astype(np.int32)
    return lo, hi
