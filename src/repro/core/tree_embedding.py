"""Random-shift grid (quadtree) embeddings — the paper's §2/§3 construct.

A tree embedding is represented *implicitly* by per-level integer cell codes:
``code_h(x) = hash(floor((x - origin + shift) * 2**h / (2 * max_dist)))`` for
heights ``h = 0 .. H-1`` (height 0 is the root: one cell containing every
point).  Because the grids nest (side halves each level, lines are a superset
of the parent's), code equality is prefix-closed along the root-to-leaf path,
so the LCA height of two points is simply the number of levels at which their
codes agree.  The tree distance then has the closed form

    TreeDist(p, q) = 2 * sqrt(d) * max_dist * (2**(1 - sep) - 2**(1 - H))

where ``sep`` is the number of agreeing levels (``sep == H`` => same leaf =>
distance 0).  This is the TPU-native adaptation documented in DESIGN.md §3:
pointer trees become dense ``(H, n)`` integer arrays and LCA queries become
vectorised compare+reduce.

The d-dimensional cell coordinate vector is hashed to a single uint64 with a
random linear hash (odd multipliers, wrap-around arithmetic); the collision
probability per compared pair per level is ~2**-64 and is documented as
negligible (a collision could only *lower* a tree distance estimate for one
pair in one tree).

Both a NumPy path (used by the faithful CPU benchmarks) and a jnp path (used
inside jit) are provided and produce identical codes for identical inputs.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

__all__ = [
    "TreeEmbedding",
    "MultiTreeEmbedding",
    "GridTrees",
    "build_multitree",
    "compute_max_dist",
    "grid_trees",
    "hash_level_planes",
    "set_bounds",
    "sep_levels",
    "tree_dist_from_sep",
    "NUM_TREES",
]

NUM_TREES = 3  # the paper uses exactly three shifted trees ("multi-tree").


BOUNDS_ROWS = 1 << 16   # rows per block of `set_bounds`


def set_bounds(points: np.ndarray, *, map_fn=map) -> tuple[float, np.ndarray]:
    """`compute_max_dist(points)` and the per-coordinate minimum, in one
    pass over blocks of `BOUNDS_ROWS` rows.

    Each block's temporaries are the block's size, never the set's, and
    both results are exact whatever the blocks (a maximum of per-row
    distances, a minimum of coordinates).  `map_fn` maps the per-block
    work over the blocks' starts (a thread pool's `map` runs them side by
    side).
    """
    x0 = points[0]

    def block(lo):
        rows = points[lo:lo + BOUNDS_ROWS]
        far = np.sqrt(np.maximum(((rows - x0) ** 2).sum(axis=1), 0.0)).max()
        return far, rows.min(axis=0)

    parts = list(map_fn(block, range(0, len(points), BOUNDS_ROWS)))
    d = max(far for far, _ in parts)
    origin = np.min(np.stack([low for _, low in parts]), axis=0)
    return (float(2.0 * d) if d > 0 else 1.0), origin


def compute_max_dist(points: np.ndarray) -> float:
    """Upper bound on the diameter within a factor of 2 (paper §2, fn. 6).

    Picks the first point and doubles its maximum distance to any other point.
    O(nd).
    """
    return set_bounds(np.asarray(points))[0]


def _num_levels(max_dist: float, resolution: float) -> int:
    """Number of grid heights H such that the leaf cell side < resolution."""
    # Root cell side = 2 * max_dist; level h side = 2 * max_dist / 2**h.
    # Stop when side <= resolution  =>  h >= log2(2 * max_dist / resolution).
    h = int(np.ceil(np.log2(max(2.0 * max_dist / max(resolution, 1e-300), 2.0))))
    return max(2, min(h + 1, 60))


@dataclasses.dataclass(frozen=True)
class TreeEmbedding:
    """One random-shift grid embedding: per-level hashed cell codes."""

    codes: np.ndarray          # (H, n) uint64 — hashed cell ids per height.
    max_dist: float            # root cell side / 2.
    num_levels: int            # H.
    dim: int                   # ambient dimension d (for sqrt(d) edge weights).
    shift: np.ndarray          # (d,) the random shift used (for point queries).
    origin: np.ndarray         # (d,) per-coordinate min, subtracted first.
    hash_mults: np.ndarray     # (d,) odd uint64 multipliers.

    def point_codes(self, x: np.ndarray) -> np.ndarray:
        """Codes for arbitrary query points x of shape (..., d)."""
        return _grid_codes(
            np.asarray(x, dtype=np.float64),
            self.origin,
            self.shift,
            self.max_dist,
            self.num_levels,
            self.hash_mults,
        )


@dataclasses.dataclass(frozen=True)
class MultiTreeEmbedding:
    """Three independently shifted tree embeddings (paper §3)."""

    trees: tuple[TreeEmbedding, ...]
    max_dist: float
    num_levels: int
    dim: int
    num_points: int

    @property
    def dist_upper_bound_sq(self) -> float:
        """M = 16 d MaxDist^2, the paper's upper bound on MultiTreeDist^2."""
        return 16.0 * self.dim * self.max_dist ** 2

    def codes_array(self) -> np.ndarray:
        """All codes stacked: (num_trees, H, n) uint64."""
        return np.stack([t.codes for t in self.trees])


_MIX = np.uint64(0x9E3779B97F4A7C15)


def _deep_cells(pts, origin, shift, max_dist, num_levels) -> np.ndarray:
    """Cell coordinates at the deepest height, (..., d) float64 integers:
    the only float work of the codes (a single floor-divide)."""
    y = pts - origin
    y += shift                  # all coords in [0, 2*max_dist)
    y /= 2.0 * max_dist / (1 << (num_levels - 1))
    return np.floor(y, out=y)


def _hash_levels(cell_deep: np.ndarray, hash_mults: np.ndarray,
                 num_levels: int) -> np.ndarray:
    """(H, ...) uint64 codes of uint64 deepest-level cells (..., d).

    Because level sides halve exactly, the level-h cell coordinate is the
    deepest level's coordinate right-shifted by (H-1-h) bits, so every
    height above is integer shifts + the per-level linear hash.
    """
    out = np.empty((num_levels,) + cell_deep.shape[:-1], dtype=np.uint64)
    # Height 0 is the root: a single cell.
    out[0] = 0
    cell = np.empty_like(cell_deep)     # one buffer, reused at every height
    with np.errstate(over="ignore"):
        for h in range(1, num_levels):
            np.right_shift(cell_deep, np.uint64(num_levels - 1 - h), out=cell)
            cell *= hash_mults
            code = cell.sum(axis=-1, dtype=np.uint64, out=out[h, ...])
            # Mix in the height so identical cells at different heights differ.
            code *= _MIX
            code += np.uint64(h)
    return out


def _grid_codes(
    pts: np.ndarray,
    origin: np.ndarray,
    shift: np.ndarray,
    max_dist: float,
    num_levels: int,
    hash_mults: np.ndarray,
) -> np.ndarray:
    """Hashed cell codes for every height; returns (H, ...) uint64."""
    cells = _deep_cells(pts, origin, shift, max_dist, num_levels)
    return _hash_levels(cells.astype(np.uint64), hash_mults, num_levels)


def hash_level_planes(cells, hash_mults, rows, num_levels: int):
    """The jnp twin of `_hash_levels`, traceable (call it with x64 on):
    cells (..., b, d) of unsigned integers -> the codes of heights
    1..H-1 (the root dropped) as (..., H-1, b) int32 lo/hi planes, bit
    for bit `ops.split_codes_u64` of the NumPy codes, for the first
    `rows` cells and zero after them.  The hash runs in uint64 (emulated
    on a TPU)."""
    c = cells.astype(jnp.uint64)
    live = jnp.arange(cells.shape[-2]) < rows
    lo, hi = [], []
    for h in range(1, num_levels):
        code = jnp.sum((c >> np.uint64(num_levels - 1 - h)) * hash_mults,
                       axis=-1, dtype=jnp.uint64)
        code = jnp.where(live, code * _MIX + np.uint64(h), np.uint64(0))
        lo.append(jax.lax.bitcast_convert_type(
            (code & np.uint64(0xFFFFFFFF)).astype(jnp.uint32), jnp.int32))
        hi.append(jax.lax.bitcast_convert_type(
            (code >> np.uint64(32)).astype(jnp.uint32), jnp.int32))
    return jnp.stack(lo, axis=-2), jnp.stack(hi, axis=-2)


@dataclasses.dataclass(frozen=True)
class GridTrees:
    """The random draws of `num_trees` grid embeddings over one point
    set's bounds: codes for any block of rows, each block on its own and
    bit for bit as the whole set's codes."""

    max_dist: float
    num_levels: int
    origin: np.ndarray           # (d,) per-coordinate min of the set
    shifts: tuple                # per tree, (d,) float64
    mults: tuple                 # per tree, (d,) odd uint64

    def tree_codes(self, rows: np.ndarray, tree: int) -> np.ndarray:
        """(H, b) uint64 codes of `rows` (float64) in tree `tree`."""
        return _grid_codes(rows, self.origin, self.shifts[tree],
                           self.max_dist, self.num_levels, self.mults[tree])

    @property
    def cell_dtype(self) -> np.dtype:
        """The narrowest unsigned dtype that holds a deepest-level cell
        coordinate (they lie in [0, 2^(H-1)))."""
        return np.min_scalar_type((1 << (self.num_levels - 1)) - 1)

    def cells(self, rows: np.ndarray, tree: int) -> np.ndarray:
        """(b, d) deepest-level cells of `rows` in tree `tree`, as
        `cell_dtype`: what `hash_level_planes` hashes into codes."""
        return _deep_cells(rows, self.origin, self.shifts[tree],
                           self.max_dist, self.num_levels).astype(
                               self.cell_dtype)


def grid_trees(max_dist: float, origin: np.ndarray, *, seed: int = 0,
               resolution: Optional[float] = None,
               num_trees: int = NUM_TREES) -> GridTrees:
    """The shifts and hash multipliers of MULTITREEINIT, drawn from `seed`
    (the draws `build_multitree` makes, in its order)."""
    rng = np.random.default_rng(seed)
    d = len(origin)
    if resolution is None:
        resolution = max_dist * 1e-6
    shifts, mults = [], []
    for _ in range(num_trees):
        shifts.append(rng.uniform(0.0, max_dist, size=d))
        mults.append(rng.integers(1, 2 ** 63, size=d, dtype=np.uint64)
                     * np.uint64(2) + np.uint64(1))
    return GridTrees(max_dist=max_dist,
                     num_levels=_num_levels(max_dist, resolution),
                     origin=origin, shifts=tuple(shifts), mults=tuple(mults))


def build_multitree(
    points: np.ndarray,
    *,
    seed: int = 0,
    resolution: Optional[float] = None,
    num_trees: int = NUM_TREES,
    max_dist: Optional[float] = None,
) -> MultiTreeEmbedding:
    """MULTITREEINIT(): three random-shift grid embeddings over `points`.

    `resolution` bounds the leaf cell side (aspect-ratio control, paper App. F
    — callers may pass the quantisation scale).  Defaults to a 1e-6 fraction
    of max_dist, giving H = O(log Delta) ~ 21 levels.
    O(n d H) time, O(n H) memory per tree.

    `max_dist` overrides the computed diameter bound.  The stacked
    multi-dataset path pre-scales every dataset into the unit ball (an exact
    power-of-two rescale) and forces ``max_dist=1.0`` so the embedding's
    static metadata — `scale`, `num_levels`, `dist_upper_bound_sq` — is
    bit-identical across datasets and one jit program serves them all.  The
    caller guarantees the override really upper-bounds `compute_max_dist`.
    """
    pts = np.asarray(points, dtype=np.float64)
    n, d = pts.shape
    if max_dist is None:
        max_dist, origin = set_bounds(pts)
    else:
        max_dist, origin = float(max_dist), pts.min(axis=0)
    grid = grid_trees(max_dist, origin, seed=seed, resolution=resolution,
                      num_trees=num_trees)
    trees = tuple(
        TreeEmbedding(
            codes=grid.tree_codes(pts, t),
            max_dist=max_dist,
            num_levels=grid.num_levels,
            dim=d,
            shift=grid.shifts[t],
            origin=origin,
            hash_mults=grid.mults[t],
        )
        for t in range(num_trees))
    return MultiTreeEmbedding(
        trees=trees,
        max_dist=max_dist,
        num_levels=grid.num_levels,
        dim=d,
        num_points=n,
    )


# --------------------------------------------------------------------------
# Separation levels and tree distances (NumPy + jnp twins).
# --------------------------------------------------------------------------

def sep_levels(codes_a: np.ndarray, codes_b: np.ndarray) -> np.ndarray:
    """Number of agreeing heights between code columns.

    codes_a: (H, ...) vs codes_b: (H, ...) broadcastable; returns int32 (...).
    Because grids nest, equality is prefix-closed, so the count equals the
    index of the first disagreement.
    """
    eq = codes_a == codes_b
    return eq.sum(axis=0).astype(np.int32)


def tree_dist_from_sep(
    sep: np.ndarray, max_dist: float, num_levels: int, dim: int
) -> np.ndarray:
    """Closed-form TreeDist given separation level (App. A geometry)."""
    sep = np.asarray(sep)
    scale = 2.0 * np.sqrt(dim) * max_dist
    return scale * (np.exp2(1.0 - sep) - np.exp2(1.0 - num_levels))


def tree_dist_from_sep_jnp(
    sep: jax.Array, max_dist: float, num_levels: int, dim: int
) -> jax.Array:
    scale = 2.0 * jnp.sqrt(float(dim)) * max_dist
    return scale * (jnp.exp2(1.0 - sep.astype(jnp.float32)) - 2.0 ** (1.0 - num_levels))


def multitree_dist_sq_points(
    emb: MultiTreeEmbedding, i: np.ndarray, j: np.ndarray
) -> np.ndarray:
    """MULTITREEDIST(p_i, p_j)^2 for index arrays i, j (broadcastable)."""
    best = None
    for t in emb.trees:
        sep = sep_levels(t.codes[:, i], t.codes[:, j])
        dist = tree_dist_from_sep(sep, emb.max_dist, emb.num_levels, emb.dim)
        best = dist if best is None else np.minimum(best, dist)
    return best ** 2
