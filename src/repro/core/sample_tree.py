"""The sample-tree: a balanced binary tree over points with subtree weights.

Paper §4: a leaf per point holds ``w_x = MultiTreeDist(x, S)^2``; internal
nodes hold subtree sums; MULTITREESAMPLE descends root->leaf choosing children
proportionally to their weights (O(log n)); weight updates propagate to the
root (O(log n)).

TPU-native adaptation (DESIGN.md §3, docs/sample_tree.md): the tree is a
*flat array heap* of size 2*cap (1-indexed, leaves at [cap, cap+n)).  Batch
updates touch each of the log2(cap) ancestor levels with one vectorised
scatter-add, so a batch of U updated leaves costs O(U log n) elementwise work
in O(log n) NumPy calls — no per-point Python.  A jnp twin (`SampleTreeJax`)
provides a jit-able fixed-shape version used inside device code; its
`scatter_update` is the incremental-update contract the device seeders rely
on (never a from-scratch `init` inside a seeding loop).

`TiledSampleTree` is the device seeders' two-level variant: leaves are
*kernel tiles* rather than points — a coarse flat heap holds per-tile weight
sums (refreshed from the sweeps' per-tile sums via one
`scatter_update`, O(T log T) for T = n/tile tiles), and sampling descends the
coarse heap to a tile then resolves the point with one vectorised intra-tile
cumsum.  This is also the shard-local sub-heap of the sharded seeding path.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["SampleTree", "SampleTreeJax", "TiledSampleTree"]


class SampleTree:
    """NumPy flat-heap weighted sampler (exact, float64)."""

    def __init__(self, weights: np.ndarray):
        w = np.asarray(weights, dtype=np.float64)
        n = w.shape[0]
        cap = 1 << max(1, int(np.ceil(np.log2(max(n, 2)))))
        self.n = n
        self.cap = cap
        self.levels = int(np.log2(cap))
        heap = np.zeros(2 * cap, dtype=np.float64)
        heap[cap : cap + n] = w
        # Build internal sums bottom-up, one vectorised halving per level.
        idx = cap
        while idx > 1:
            half = idx // 2
            heap[half:idx] = heap[idx : 2 * idx : 2] + heap[idx + 1 : 2 * idx : 2]
            idx = half
        self.heap = heap

    @property
    def total(self) -> float:
        return float(self.heap[1])

    def leaf_weights(self) -> np.ndarray:
        return self.heap[self.cap : self.cap + self.n]

    def update(self, indices: np.ndarray, new_weights: np.ndarray) -> None:
        """Set w[indices] = new_weights and fix all ancestor sums.

        Vectorised: one scatter-add per tree level.  Duplicate indices are not
        allowed (callers pass unique point ids).
        """
        idx = np.asarray(indices, dtype=np.int64)
        if idx.size == 0:
            return
        new = np.asarray(new_weights, dtype=np.float64)
        leaf = idx + self.cap
        delta = new - self.heap[leaf]
        self.heap[leaf] = new
        anc = leaf >> 1
        for _ in range(self.levels):
            np.add.at(self.heap, anc, delta)
            # Guard against accumulated negative dust at *every* internal
            # level: a stale negative partial sum deep in the tree would
            # otherwise steer descents into zero-weight subtrees.
            np.maximum.at(self.heap, anc, 0.0)
            anc = anc >> 1

    def sample(self, rng: np.random.Generator) -> int:
        """Draw one leaf index with probability w_x / total.  O(log n)."""
        u = rng.uniform(0.0, self.heap[1])
        v = 1
        while v < self.cap:
            left = 2 * v
            wl = self.heap[left]
            if u < wl:
                v = left
            else:
                u -= wl
                v = left + 1
        return int(v - self.cap)

    def sample_batch(self, rng: np.random.Generator, size: int) -> np.ndarray:
        """Draw `size` i.i.d. leaves; vectorised descent (log n NumPy steps)."""
        u = rng.uniform(0.0, self.heap[1], size=size)
        v = np.ones(size, dtype=np.int64)
        for _ in range(self.levels):
            left = 2 * v
            wl = self.heap[left]
            go_left = u < wl
            u = np.where(go_left, u, u - wl)
            v = np.where(go_left, left, left + 1)
        return v - self.cap


class SampleTreeJax:
    """Functional jnp flat-heap sampler (fixed shapes, jit/scan friendly).

    State is a single (2*cap,) array; all methods are pure functions suitable
    for `lax.scan` carries.  Used by the device-side (vectorised) seeder.
    """

    def __init__(self, n: int):
        self.n = n
        self.cap = 1 << max(1, int(np.ceil(np.log2(max(n, 2)))))
        self.levels = int(np.log2(self.cap))

    def init(self, weights: jax.Array) -> jax.Array:
        heap = jnp.zeros(2 * self.cap, dtype=jnp.float32)
        heap = heap.at[self.cap : self.cap + self.n].set(weights.astype(jnp.float32))
        idx = self.cap
        while idx > 1:
            half = idx // 2
            heap = heap.at[half:idx].set(
                heap[idx : 2 * idx : 2] + heap[idx + 1 : 2 * idx : 2]
            )
            idx = half
        return heap

    def scatter_update(self, heap: jax.Array, indices: jax.Array,
                       new_weights: jax.Array,
                       valid: jax.Array | None = None) -> jax.Array:
        """Set w[indices] = new_weights and fix ONLY the touched ancestors.

        The incremental-update contract (docs/sample_tree.md): a batch of U
        unique leaves costs O(U log n) scatter work — one `.at[].add` per
        level — never an O(n) rebuild, so it is safe inside per-center
        seeding loop bodies.  `valid` masks out padding lanes.  Every
        internal level is clamped to >= 0 after its scatter-add so f32
        delta accumulation can never leave negative dust that would steer
        descents into empty subtrees.
        """
        leaf = indices + self.cap
        new = new_weights.astype(jnp.float32)
        delta = new - heap[leaf]
        if valid is not None:
            delta = jnp.where(valid, delta, 0.0)
            heap = heap.at[leaf].add(delta)
        else:
            heap = heap.at[leaf].set(new)
        anc = leaf >> 1
        for _ in range(self.levels):
            heap = heap.at[anc].add(delta)
            heap = heap.at[anc].max(0.0)
            anc = anc >> 1
        return heap

    # Backwards-compatible name; `scatter_update` is the canonical contract.
    update = scatter_update

    def sample(self, heap: jax.Array, key: jax.Array, size: int) -> jax.Array:
        """Draw `size` i.i.d. leaf indices proportional to leaf weights."""
        u = jax.random.uniform(key, (size,), dtype=jnp.float32) * heap[1]
        v = jnp.ones((size,), dtype=jnp.int32)

        def step(carry, _):
            u, v = carry
            left = 2 * v
            wl = heap[left]
            go_left = u < wl
            u = jnp.where(go_left, u, u - wl)
            v = jnp.where(go_left, left, left + 1)
            return (u, v), None

        (_, v), _ = jax.lax.scan(step, (u, v), None, length=self.levels)
        return jnp.clip(v - self.cap, 0, self.n - 1)


class TiledSampleTree:
    """Two-level device sampler: coarse flat heap over *tile* sums + dense w.

    The leaf level is the dense weight array itself (padded to a multiple of
    `tile`); the heap only spans the T = n_pad/tile per-tile sums.  The fused
    sweeps return those sums with the weights, so the per-center
    sample-structure update is one `scatter_update` on a T-leaf heap —
    O(T log T) with T = n/tile, instead of the O(n) full rebuild the device
    seeders used to pay (`SampleTreeJax.init` per opened center).

    Sampling descends the coarse heap to a tile (O(log T)) and resolves the
    point inside the tile with one vectorised cumsum + count (O(tile) VPU
    work, no sequential depth).  Zero-weight leaves — including the padding
    tail — are never selected: their cumsum step is empty.
    """

    def __init__(self, n: int, tile: int = 512):
        self.n = n
        self.tile = tile
        self.num_tiles = -(-n // tile)
        self.n_pad = self.num_tiles * tile
        self.coarse = SampleTreeJax(self.num_tiles)

    def tile_sums(self, w_pad: jax.Array) -> jax.Array:
        """(n_pad,) weights -> (T,) per-tile sums (the `_tiles` wrappers'
        oracle; used at init time and by tests)."""
        return w_pad.reshape(self.num_tiles, self.tile).sum(axis=1)

    def init(self, w_pad: jax.Array) -> jax.Array:
        """Build the coarse heap from scratch — O(T); loop *preambles* only."""
        return self.coarse.init(self.tile_sums(w_pad))

    def refresh(self, heap: jax.Array, tile_sums: jax.Array) -> jax.Array:
        """Incremental per-center update from the sweeps' tile sums."""
        ids = jnp.arange(self.num_tiles, dtype=jnp.int32)
        return self.coarse.scatter_update(heap, ids, tile_sums)

    def total(self, heap: jax.Array) -> jax.Array:
        return heap[1]

    def sample(self, heap: jax.Array, w_pad: jax.Array, key: jax.Array,
               size: int) -> jax.Array:
        """Draw `size` i.i.d. point indices proportional to w_pad."""
        k1, k2 = jax.random.split(key)
        tiles = self.coarse.sample(heap, k1, size)                  # (B,)
        wt = w_pad.reshape(self.num_tiles, self.tile)[tiles]        # (B, tile)
        csum = jnp.cumsum(wt, axis=1)
        # Fresh intra-tile uniform over the tile's *exact* mass, so the
        # conditional leaf distribution is exact even when the coarse sums
        # carry f32 scatter drift.  Smallest j with csum[j] > u, i.e. a
        # zero-weight leaf (empty cumsum step) is never chosen.
        u = jax.random.uniform(k2, (size,), dtype=jnp.float32) * csum[:, -1]
        off = jnp.sum(csum <= u[:, None], axis=1).astype(jnp.int32)
        off = jnp.minimum(off, self.tile - 1)
        idx = tiles.astype(jnp.int32) * self.tile + off
        return jnp.clip(idx, 0, self.n - 1)
