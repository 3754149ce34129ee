"""TPU-native seeders: the paper's Algorithms 3 and 4 as jit-able device loops.

The pointer-machine data structures become arrays (DESIGN.md §3):
  - the multi-tree embedding is a (trees, H, n) int32x2 code tensor built
    host-side once (O(nd log Δ), embarrassingly vectorisable);
  - MULTITREEOPEN is the fused `tree_sep_update` Pallas kernel per tree
    (compare+reduce+min over all points: O(nH) VPU work, no pointers); the
    *last* tree's sweep uses the `_tiles` wrapper, which also returns the
    per-tile weight sums.  The kernel wants heights padded to a multiple
    of 8 and points to the tile; the codes never change during a seeding,
    so they are padded and split into per-tree planes once, before the
    k-center loop (`_make_sweep`), and no sweep re-pads them;
  - MULTITREESAMPLE is the two-level `TiledSampleTree` descent: a coarse
    flat heap over the T = n/tile tile sums plus one vectorised intra-tile
    cumsum.  After each opened center the coarse heap is fixed *in place*
    with one `scatter_update` from those tile sums —
    O(T log T) — never rebuilt from scratch (the old per-center
    `SampleTreeJax.init` cost O(n) per open, O(nk) total, and dominated
    large-n seeding);
  - the monotone LSH of Algorithm 4 becomes a (L, n) int32x2 bucket-key
    tensor (hashed host-side with the *same* hash family as
    `repro.core.lsh.MonotoneLSH`) plus the fused `lsh_bucket_accept` Pallas
    kernel: nearest *colliding-bucket* opened center per candidate, with the
    acceptance probability computed in the kernel epilogue;
  - the whole k-center loop is one `lax.fori_loop` — a single device
    program, no host round-trips.

The multi-chip twin of this module lives in `repro.core.sharded_seeding`
(`backend="sharded"`): shard-then-descend sampling over per-device sub-heaps
with the same incremental tile-sum updates.

`device_rejection_sampling` (Algorithm 4, REJECTIONSAMPLING) runs batched
speculative rejection inside a `lax.while_loop` per center: draw a block of
candidates + uniforms from the *current* multi-tree D^2 distribution,
evaluate every acceptance test ``d2_lsh / (c^2 * mtd2)`` vectorised, and
open the first accept, discarding the rest of the block.  Because the block
is i.i.d. from the current distribution this matches the sequential
distribution exactly — the same argument as the CPU
`seeding.rejection_sampling` docstring.

Asymptotics differ from the amortised CPU form (O(k n H) vs O(n H log n)
total update work) but every step is a dense fused sweep at full VPU
utilisation — the standard trade on SIMD hardware.  Cross-checked against
the faithful implementations in tests/test_device_seeding.py and
tests/test_device_rejection.py.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.batch_schedule import BatchSchedule, shape_bucket
from repro.core.lsh import MonotoneLSH
from repro.core.sample_tree import TiledSampleTree
from repro.core.tracing import count_trace, span
from repro.core.tree_embedding import (
    GridTrees,
    build_multitree,
    compute_max_dist,
    grid_trees,
    set_bounds,
)
from repro.kernels.ops import (
    lsh_bucket_accept,
    pad_tree_codes,
    pairwise_argmin,
    split_codes_u64,
    tree_sep_update,
    tree_sep_update_tiles,
)

__all__ = [
    "device_fast_kmeanspp",
    "device_rejection_sampling",
    "device_kmeans_parallel_rounds",
    "prepare_embedding",
    "prepare_rejection",
    "rejection_encoder",
    "DeviceSeedingData",
    "RejectionEncoder",
    "StackedLane",
    "stacked_rejection_sampling",
    "stacked_fast_kmeanspp",
    "canonical_pow2_scale",
    "device_fast_kmeanspp_seeder",
    "device_rejection_seeder",
    "device_kmeans_parallel_seeder",
    "DEVICE_SEEDERS",
]

_FAR = 1.0e17  # "no center yet" coordinate sentinel (distance^2 f32-finite)


def prepare_embedding(points: np.ndarray, *, seed: int = 0,
                      resolution: Optional[float] = None,
                      max_dist: Optional[float] = None):
    """Host-side MULTITREEINIT -> device tensors (codes as int32 planes).

    `max_dist` forwards the diameter-bound override of `build_multitree`
    (the stacked multi-dataset path forces 1.0 after its exact power-of-two
    rescale so `meta` is bit-identical across datasets).
    """
    emb = build_multitree(points, seed=seed, resolution=resolution,
                          max_dist=max_dist)
    # drop the trivial root level (height 0)
    codes = emb.codes_array()[:, 1:, :]            # (T, H-1, n)
    lo, hi = split_codes_u64(codes)
    meta = {
        "scale": 2.0 * np.sqrt(emb.dim) * emb.max_dist,
        "num_levels": emb.num_levels,
        "m_init": emb.dist_upper_bound_sq,
    }
    return jnp.asarray(lo), jnp.asarray(hi), meta


def _pad_axis(a: jax.Array, axis: int, n_pad: int) -> jax.Array:
    """Zero-pad one axis to `n_pad` (trace-time static shapes)."""
    pad = n_pad - a.shape[axis]
    if pad <= 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


def _make_sweep(codes_lo, codes_hi, *, scale, num_levels, tile, interpret):
    """MULTITREEOPEN over all trees: ``sweep(weights, col_lo, col_hi)``
    for an opened center's code columns, and ``columns(x)``, point x's
    columns.  The last tree's kernel emits the per-tile weight sums the
    coarse heap update consumes (one pass over the weight vector, not
    over the points).

    Call it outside the per-center loop: it pads and splits the (T, H-1,
    n) codes here, once (`ops.pad_tree_codes`), and both functions close
    over the T per-tree (H8, n_pad) planes, so the loop body slices and
    pads no code plane (and reads no column of the unpadded codes, which
    on a TPU can make XLA hold them in a second layout).  A column is
    read from the first H-1 rows only: the wrapper pads it with the
    center sentinel -2, where the planes carry the point sentinel -1.
    Read from all H8 rows it would carry -1 there, match every point on
    the pad rows and raise each point's `sep` by the pad height.  The
    sharded programs call this on each shard's codes.
    """
    h = codes_lo.shape[1]
    planes = list(zip(pad_tree_codes(codes_lo, block_n=tile),
                      pad_tree_codes(codes_hi, block_n=tile)))

    def columns(x):
        def column(plane):
            return jax.lax.dynamic_slice(plane, (0, x), (h, 1))[:, 0]

        return ([column(lo) for lo, _ in planes],
                [column(hi) for _, hi in planes])

    def sweep(weights, col_lo, col_hi):
        for ti, (lo, hi) in enumerate(planes[:-1]):
            weights = tree_sep_update(
                lo, hi, col_lo[ti], col_hi[ti], weights,
                scale=scale, num_levels=num_levels, block_n=tile,
                interpret=interpret,
            )
        lo, hi = planes[-1]
        return tree_sep_update_tiles(
            lo, hi, col_lo[-1], col_hi[-1], weights,
            scale=scale, num_levels=num_levels, block_n=tile,
            interpret=interpret,
        )

    return sweep, columns


def _make_open_center(codes_lo, codes_hi, *, scale, num_levels, tile,
                      interpret):
    """`_make_sweep` for a center given by its row index."""
    sweep, columns = _make_sweep(codes_lo, codes_hi, scale=scale,
                                 num_levels=num_levels, tile=tile,
                                 interpret=interpret)

    def open_center(weights, x):
        return sweep(weights, *columns(x))

    return open_center


@functools.partial(
    jax.jit,
    static_argnames=("k", "scale", "num_levels", "m_init", "tile",
                     "interpret"),
)
def device_fast_kmeanspp(
    codes_lo: jax.Array,     # (T, H-1, n) int32
    codes_hi: jax.Array,
    k: int,
    key: jax.Array,
    *,
    scale: float,
    num_levels: int,
    m_init: float,
    tile: int = 512,
    interpret: bool | None = None,
    n_real: jax.Array | None = None,
    w0: jax.Array | None = None,
    base0: jax.Array | None = None,
) -> jax.Array:
    """Algorithm 3.  Returns (k,) int32 chosen indices.  One jit program,
    cached by (shapes, static args) — repeated fits never re-trace
    (`tracing.TRACE_COUNTS["fastkmeans++/device"]` counts real traces).

    Per opened center the sample structure is fixed *incrementally*: the last
    tree sweep's tile sums feed one `TiledSampleTree.refresh`
    (O(T log T), T = n/tile) — there is no `SampleTreeJax.init` (O(n) heap
    rebuild) anywhere in the loop body.

    `n_real` (a *traced* int32 scalar) marks only the first `n_real` rows
    live: rows beyond it start at weight 0 (never sampled) and the uniform
    first draw is bounded by it.  The stacked multi-dataset path pads every
    lane to a common shape bucket and passes each lane's true row count
    here; `None` (the solo path) means all `n` rows are live.

    `w0` (traced, `(n_pad,)` f32, streaming path) replaces the
    arange-masked base weights: live rows carry `m_init`, retired/padded
    rows 0 — they are never sampled and never perturb the loop, so the
    program draws the exact law over the live set.  With `w0` the uniform
    first-center draw becomes an equal-weight `TiledSampleTree.sample`
    over `w0` (exactly uniform on live rows; rows at weight 0 have zero
    mass in the exact intra-tile cumsum).  `base0` optionally supplies
    the matching coarse heap (the streaming state's incrementally patched
    `base_heap`); `None` rebuilds it from `w0` at O(T) trace cost.
    """
    count_trace("fastkmeans++/device")        # trace-time only
    t, h, n = codes_lo.shape
    live = n if n_real is None else n_real
    ts = TiledSampleTree(n, tile=tile)
    open_center = _make_open_center(codes_lo, codes_hi, scale=scale,
                                    num_levels=num_levels, tile=tile,
                                    interpret=interpret)

    # Padded tail lanes start (and stay) at weight 0: never sampled.
    if w0 is None:
        weights0 = jnp.where(jnp.arange(ts.n_pad) < live, m_init,
                             0.0).astype(jnp.float32)
        coarse0 = ts.init(weights0)
    else:
        weights0 = _pad_axis(w0.astype(jnp.float32), 0, ts.n_pad)
        coarse0 = ts.init(weights0) if base0 is None else base0

    def body(i, state):
        weights, coarse, chosen, key = state
        key, k_unif, k_samp = jax.random.split(key, 3)
        if w0 is None:
            first = jax.random.randint(k_unif, (), 0, live)
        else:
            first = ts.sample(coarse0, weights0, k_unif, 1)[0]
        x = jnp.where(
            i == 0,
            first,
            ts.sample(coarse, weights, k_samp, 1)[0],
        ).astype(jnp.int32)
        weights, tsums = open_center(weights, x)
        coarse = ts.refresh(coarse, tsums)
        chosen = chosen.at[i].set(x)
        return weights, coarse, chosen, key

    chosen0 = jnp.zeros((k,), jnp.int32)
    _, _, chosen, _ = jax.lax.fori_loop(
        0, k, body, (weights0, coarse0, chosen0, key)
    )
    return chosen


# ---------------------------------------------------------------------------
# Algorithm 4: REJECTIONSAMPLING as one device program.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DeviceSeedingData:
    """Device tensors + static scalars for `device_rejection_sampling`."""

    codes_lo: jax.Array      # (T, H-1, n) int32 — multi-tree cell codes
    codes_hi: jax.Array
    points: jax.Array        # (n, d) f32 — coordinates (acceptance distances)
    keys_lo: jax.Array       # (L, n) int32 — LSH bucket keys, low plane
    keys_hi: jax.Array
    scale: float             # 2 sqrt(d) MaxDist — tree-distance closed form
    num_levels: int          # H
    m_init: float            # M = 16 d MaxDist^2


@dataclasses.dataclass(frozen=True)
class RejectionEncoder:
    """What Algorithm 4's host prepare fixes from the whole point set: the
    trees' draws over its bounds and the LSH family.  `encode` turns any
    block of rows into its code and key planes on its own, bit for bit
    the columns the whole set's encoding would give those rows, so a set
    can be encoded block by block, on several threads, and never needs a
    temporary the size of the set."""

    trees: GridTrees
    lsh: MonotoneLSH

    @property
    def meta(self) -> dict:
        """The program's statics: the tree-distance scale, H, M."""
        d, md = len(self.trees.origin), self.trees.max_dist
        return {"scale": 2.0 * np.sqrt(d) * md,
                "num_levels": self.trees.num_levels,
                "m_init": 16.0 * d * md ** 2}

    def codes(self, rows: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """(T, H-1, b) int32 lo/hi code planes of `rows` (float64), the
        trivial root level dropped."""
        t = len(self.trees.shifts)
        lo = np.empty((t, self.trees.num_levels - 1, len(rows)), np.int32)
        hi = np.empty_like(lo)
        for ti in range(t):
            lo[ti], hi[ti] = split_codes_u64(
                self.trees.tree_codes(rows, ti)[1:])
        return lo, hi

    def keys(self, rows: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """(L, b) int32 lo/hi LSH bucket-key planes of `rows`."""
        klo, khi = split_codes_u64(self.lsh.hash_keys(rows))
        return klo.T, khi.T


def rejection_encoder(
    points: np.ndarray,
    *,
    seed: int = 0,
    resolution: Optional[float] = None,
    lsh_r: Optional[float] = None,
    num_tables: int = 15,
    hashes_per_table: int = 1,
    max_dist: Optional[float] = None,
    map_fn=map,
) -> RejectionEncoder:
    """The whole-set half of `prepare_rejection`: the diameter bound and
    origin in one pass over row blocks (`set_bounds`, whose blocks
    `map_fn` maps), the LSH width, and every random draw, in the order
    `prepare_rejection` has always made them."""
    pts = np.asarray(points, dtype=np.float64)
    rng = np.random.default_rng(seed)
    tree_seed = int(rng.integers(2 ** 31))
    if max_dist is None:
        max_dist, origin = set_bounds(pts, map_fn=map_fn)
    else:
        max_dist, origin = float(max_dist), pts.min(axis=0)
    trees = grid_trees(max_dist, origin, seed=tree_seed,
                       resolution=resolution)
    if lsh_r is None:
        from repro.core.seeding import _estimate_scale

        lsh_r = 10.0 * (resolution or _estimate_scale(pts, rng))
    lsh = MonotoneLSH(
        pts.shape[1],
        r=lsh_r,
        num_tables=num_tables,
        hashes_per_table=hashes_per_table,
        seed=int(rng.integers(2 ** 31)),
        capacity=16,
    )
    return RejectionEncoder(trees=trees, lsh=lsh)


def prepare_rejection(
    points: np.ndarray,
    *,
    seed: int = 0,
    resolution: Optional[float] = None,
    lsh_r: Optional[float] = None,
    num_tables: int = 15,
    hashes_per_table: int = 1,
    max_dist: Optional[float] = None,
) -> DeviceSeedingData:
    """Host-side init of Algorithm 4's two structures as device tensors.

    The multi-tree part mirrors `prepare_embedding`; the LSH part hashes
    every point with the same p-stable family as `MonotoneLSH` (App. D.3
    defaults), so the device bucket-collision test is bit-identical to the
    CPU structure's.  The paper's LSH stores only *opened centers*; since
    every center is an input point, precomputing all n keys host-side lets
    the device program insert a center by copying one precomputed column.
    The set is encoded as one block, on this thread (`RejectionEncoder`;
    the sharded backend encodes its shards block by block).
    """
    pts = np.asarray(points, dtype=np.float64)
    with span("repro.prepare.embed"):
        enc = rejection_encoder(
            pts, seed=seed, resolution=resolution, lsh_r=lsh_r,
            num_tables=num_tables, hashes_per_table=hashes_per_table,
            max_dist=max_dist)
        lo, hi = enc.codes(pts)
        lo, hi = jnp.asarray(lo), jnp.asarray(hi)
    with span("repro.prepare.lsh"):
        klo, khi = enc.keys(pts)
        klo, khi = jnp.asarray(klo), jnp.asarray(khi)
    meta = enc.meta
    return DeviceSeedingData(
        codes_lo=lo,
        codes_hi=hi,
        points=jnp.asarray(pts, jnp.float32),
        keys_lo=klo,                                # (L, n)
        keys_hi=khi,
        scale=meta["scale"],
        num_levels=meta["num_levels"],
        m_init=meta["m_init"],
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "scale", "num_levels", "m_init", "c", "schedule", "max_rounds",
        "tile", "interpret",
    ),
)
def device_rejection_sampling(
    codes_lo: jax.Array,     # (T, H-1, n) int32
    codes_hi: jax.Array,
    points: jax.Array,       # (n, d) f32
    keys_lo: jax.Array,      # (L, n) int32
    keys_hi: jax.Array,
    k: int,
    key: jax.Array,
    *,
    scale: float,
    num_levels: int,
    m_init: float,
    c: float = 1.2,
    schedule: BatchSchedule | None = None,
    max_rounds: int = 32,
    tile: int = 512,
    interpret: bool | None = None,
    n_real: jax.Array | None = None,
    w0: jax.Array | None = None,
    base0: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Algorithm 4 as one device program (jit-able end to end).

    Per center, a `lax.while_loop` runs batched speculative rejection: draw
    a block of i.i.d. candidates from the current multi-tree D^2
    distribution (two-level `TiledSampleTree` descent) plus uniforms,
    compute every candidate's LSH nearest-bucket distance *and* acceptance
    probability ``d2_lsh / (c^2 * mtd2)`` with one fused `lsh_bucket_accept`
    kernel sweep over the opened centers, and open the *first* accept (the
    rest of the block is discarded, preserving the sequential distribution
    exactly).  A complete LSH miss (kernel sentinel `LSH_MISS`) makes the
    ratio > 1, i.e. always accepts — the CPU structure's +inf convention.

    The block size follows the adaptive `schedule` (`BatchSchedule`): block
    shapes must be trace-time constants inside the `while_loop`, so each
    round `lax.switch`-es between one branch per power-of-two bucket of the
    schedule's ladder, and only the bucket index plus the acceptance-rate
    EMA travel as loop state (carried across rounds AND across centers, so
    each center starts from the measured rate so far).  Because every
    candidate in a block is i.i.d. from the *current* distribution and the
    block size depends only on past rounds, adaptivity does not perturb the
    sampled distribution.  `BatchSchedule.fixed(b)` pins one bucket and
    reproduces the legacy fixed-batch program (identical RNG stream).

    Opening a center never rebuilds the sample structure: the last tree
    sweep's tile sums feed one incremental
    `TiledSampleTree.refresh` (O(T log T), T = n/tile) instead of the old
    O(n) `SampleTreeJax.init` per center.

    `max_rounds` bounds the per-center loop (expected trials are
    O(c^2 d^2), Lemma 5.3); on exhaustion the first candidate of the last
    block — an exact multi-tree D^2 draw — is opened, mirroring the CPU
    safety net.  The degenerate all-weights-zero case (total coarse-heap
    weight 0) skips the loop and opens a uniform draw.

    Returns ``(chosen (k,) int32, trials (k,) int32)`` — trials per center
    for the Lemma 5.3 statistics.

    `n_real` (a *traced* int32 scalar, `None` on the solo path) bounds the
    live rows for the stacked multi-dataset lanes — see
    `device_fast_kmeanspp`.

    `w0` / `base0` (traced, streaming path) replace the arange base
    weights with the stream's patched leaf-weight vector and its coarse
    heap — semantics as in `device_fast_kmeanspp`: rows at weight 0
    (retired or padding) are never proposed and the uniform fallback draw
    is exactly uniform on the live rows.
    """
    count_trace("rejection/device")           # trace-time only
    t, h, n = codes_lo.shape
    live = n if n_real is None else n_real
    l = keys_lo.shape[0]
    d = points.shape[1]
    ts = TiledSampleTree(n, tile=tile)
    c2 = float(c) ** 2
    schedule = schedule if schedule is not None else BatchSchedule()
    buckets = schedule.buckets()
    b_idx0 = schedule.index_of(schedule.initial(n, k, ts.num_tiles))

    pts_pad = _pad_axis(points, 0, ts.n_pad)
    klo_pad = _pad_axis(keys_lo, 1, ts.n_pad)
    khi_pad = _pad_axis(keys_hi, 1, ts.n_pad)
    open_center = _make_open_center(codes_lo, codes_hi, scale=scale,
                                    num_levels=num_levels, tile=tile,
                                    interpret=interpret)

    if w0 is None:
        weights0 = jnp.where(jnp.arange(ts.n_pad) < live, m_init,
                             0.0).astype(jnp.float32)
        coarse0 = ts.init(weights0)
    else:
        weights0 = _pad_axis(w0.astype(jnp.float32), 0, ts.n_pad)
        coarse0 = ts.init(weights0) if base0 is None else base0

    def body(i, state):
        (weights, coarse, chosen, ctr_pts, ck_lo, ck_hi, trials, b_idx,
         acc_ema, key) = state
        key, k_unif = jax.random.split(key)
        if w0 is None:
            x_unif = jax.random.randint(k_unif, (), 0, live).astype(
                jnp.int32)
        else:
            x_unif = ts.sample(coarse0, weights0, k_unif, 1)[0].astype(
                jnp.int32)

        def round_cond(carry):
            key, x_sel, done, t_i, rounds, b_idx, acc_ema = carry
            return (~done) & (rounds < max_rounds) & (i > 0) & (coarse[1] > 0)

        def round_body(carry):
            key, x_sel, done, t_i, rounds, b_idx, acc_ema = carry
            key, k_cand, k_u = jax.random.split(key, 3)

            def make_branch(bj):
                # One bucket of the schedule's ladder: block shapes are
                # trace-time constants, so each bucket is its own branch.
                def branch(_):
                    cand = ts.sample(coarse, weights, k_cand, bj)  # i.i.d. D^2
                    us = jax.random.uniform(k_u, (bj,), dtype=jnp.float32)
                    mtd2 = weights[cand]                  # current weights
                    _, p_acc = lsh_bucket_accept(
                        jnp.take(klo_pad, cand, axis=1),
                        jnp.take(khi_pad, cand, axis=1),
                        jnp.take(pts_pad, cand, axis=0),
                        ck_lo.T, ck_hi.T, ctr_pts, mtd2, i,
                        c2=c2, interpret=interpret,
                    )
                    acc = us < p_acc
                    any_acc = jnp.any(acc)
                    hit = jnp.argmax(acc)                 # first accept
                    # On exhaustion, cand[0] (exact D^2 draw) is the fallback.
                    x_b = jnp.where(any_acc, cand[hit], cand[0]).astype(
                        jnp.int32
                    )
                    used = jnp.where(any_acc, hit + 1, bj).astype(jnp.int32)
                    rate = (jnp.sum(acc) / bj).astype(jnp.float32)
                    return x_b, any_acc, used, rate
                return branch

            branches = [make_branch(bj) for bj in buckets]
            if len(branches) == 1:                        # fixed schedule
                x_sel, any_acc, used, rate = branches[0](None)
            else:
                x_sel, any_acc, used, rate = jax.lax.switch(
                    b_idx, branches, None
                )
            t_i = t_i + used
            acc_ema = schedule.update_rate(acc_ema, rate)
            b_idx = schedule.next_index(b_idx, acc_ema)
            return key, x_sel, any_acc, t_i, rounds + 1, b_idx, acc_ema

        key, x_sel, _, t_i, _, b_idx, acc_ema = jax.lax.while_loop(
            round_cond, round_body,
            (key, x_unif, jnp.bool_(False), jnp.int32(0), jnp.int32(0),
             b_idx, acc_ema),
        )
        x = x_sel
        t_i = jnp.maximum(t_i, 1)             # the uniform/fallback draw

        weights, tsums = open_center(weights, x)
        coarse = ts.refresh(coarse, tsums)
        chosen = chosen.at[i].set(x)
        ctr_pts = ctr_pts.at[i].set(pts_pad[x])
        ck_lo = ck_lo.at[i].set(klo_pad[:, x])
        ck_hi = ck_hi.at[i].set(khi_pad[:, x])
        trials = trials.at[i].set(t_i)
        return (weights, coarse, chosen, ctr_pts, ck_lo, ck_hi, trials,
                b_idx, acc_ema, key)

    chosen0 = jnp.zeros((k,), jnp.int32)
    ctr_pts0 = jnp.full((k, d), _FAR, jnp.float32)
    # The opened centers' bucket keys, one row per center (the kernel takes
    # them transposed).  Rows, not columns: a column write can make XLA hold
    # the (L, n) key planes in a second layout on the TPU and copy both into
    # it for every opened center, where a row write reads the center's keys
    # in the layout the candidate gather already wants.
    ck_lo0 = jnp.zeros((k, l), jnp.int32)
    ck_hi0 = jnp.zeros((k, l), jnp.int32)
    trials0 = jnp.zeros((k,), jnp.int32)
    out = jax.lax.fori_loop(
        0, k, body,
        (weights0, coarse0, chosen0, ctr_pts0, ck_lo0, ck_hi0, trials0,
         jnp.int32(b_idx0), jnp.float32(schedule.prior_accept), key),
    )
    return out[2], out[6]


# ---------------------------------------------------------------------------
# Stacked multi-dataset lanes: ONE vmapped jit program solving B *different*
# datasets (`ClusterPlan.fit_batch(datasets=...)`, ISSUE 5).
#
# The blocker for stacking is that `scale` / `num_levels` / `m_init` are
# trace-time statics derived from each dataset's diameter — naive stacking
# would compile one program per dataset.  The canonical prepare removes the
# data dependence: every dataset is rescaled into the unit ball by an EXACT
# power-of-two factor (mantissas untouched, so distance *ratios* — all that
# D^2 sampling and the scale-free acceptance test d2_lsh/(c^2 mtd2) consume
# — are preserved bit-for-bit), and the embedding is built with the forced
# diameter bound max_dist=1.0 and a fixed canonical resolution.  The statics
# then depend only on (d, resolution): every same-d dataset shares them.
#
# Shapes are bucketed on `batch_schedule.shape_bucket`'s power-of-two
# ladder: each lane's row count pads up to the next rung, so B datasets in
# one bucket run as one `jax.vmap` over `device_rejection_sampling` /
# `device_fast_kmeanspp` with a traced per-lane `n_real` masking the padded
# tail (padded rows carry weight 0 — never sampled).  `TRACE_COUNTS`
# (keys "<seeder>/device/stacked") proves one trace per bucket.
#
# Donation: the `_donated` jit variants donate the stacked code/point/key
# block, letting XLA alias its pages for the programs' weight/loop buffers
# instead of holding both alive — the ROADMAP's "donate the per-fit weight
# buffers".  Only meaningful off-CPU (the plan gates on the backend).
# ---------------------------------------------------------------------------

_STACK_RESOLUTION = 2.0 ** -10   # canonical leaf side => H = 12 fixed levels


def canonical_pow2_scale(points: np.ndarray) -> float:
    """Exact power-of-two factor mapping `points` into the unit ball.

    ``s = 2^-ceil(log2(compute_max_dist(points)))`` guarantees
    ``compute_max_dist(points * s) <= 1.0``; because s is a power of two the
    rescale only shifts exponents (no mantissa rounding), so every pairwise
    distance ratio — and therefore the D^2 sampling distribution and the
    Algorithm-4 acceptance ratio — is preserved exactly.
    """
    md = compute_max_dist(np.asarray(points, dtype=np.float64))
    return 2.0 ** -math.ceil(math.log2(md)) if md > 0 else 1.0


@dataclasses.dataclass(frozen=True)
class StackedLane:
    """One dataset's canonically-rescaled, bucket-padded lane artifacts.

    `arrays` are the per-lane device tensors (row axis padded to a
    `shape_bucket` rung); `statics` the jit static kwargs, bit-identical
    across every lane of a shape bucket; `n_real` the live row count the
    traced mask sees.  Lanes stack (via `jnp.stack`) iff their `shape_key`s
    are equal — the plan groups by it, one vmapped program per group.
    """

    arrays: tuple
    n_real: int
    statics: tuple

    @property
    def shape_key(self) -> tuple:
        return (tuple(a.shape for a in self.arrays), self.statics)


def _canonical_rejection_lane(points, rng, *, options, execution):
    """`BackendImpl.prepare_stacked` for the rejection seeder."""
    pts = np.asarray(points, dtype=np.float64)
    n = pts.shape[0]
    s = canonical_pow2_scale(pts)
    resolution = float(options.get("stack_resolution", _STACK_RESOLUTION))
    # A user lsh_r is expressed in ORIGINAL data units: rescale it with the
    # points, or the canonical lane's collision radius is off by 1/s.
    lsh_r = options.get("lsh_r")
    data = prepare_rejection(
        pts * s,
        seed=int(rng.integers(2 ** 31)), resolution=resolution,
        max_dist=1.0, lsh_r=None if lsh_r is None else float(lsh_r) * s,
        num_tables=options.get("num_tables", 15),
        hashes_per_table=options.get("hashes_per_table", 1),
    )
    bucket = shape_bucket(n, min_bucket=max(1024, execution.tile))
    return StackedLane(
        arrays=(
            _pad_axis(data.codes_lo, 2, bucket),
            _pad_axis(data.codes_hi, 2, bucket),
            _pad_axis(data.points, 0, bucket),
            _pad_axis(data.keys_lo, 1, bucket),
            _pad_axis(data.keys_hi, 1, bucket),
        ),
        n_real=n,
        statics=(data.scale, data.num_levels, data.m_init),
    )


def _canonical_fastkmeanspp_lane(points, rng, *, options, execution):
    """`BackendImpl.prepare_stacked` for the fastkmeans++ seeder."""
    pts = np.asarray(points, dtype=np.float64)
    n = pts.shape[0]
    resolution = float(options.get("stack_resolution", _STACK_RESOLUTION))
    lo, hi, meta = prepare_embedding(
        pts * canonical_pow2_scale(pts),
        seed=int(rng.integers(2 ** 31)), resolution=resolution,
        max_dist=1.0,
    )
    bucket = shape_bucket(n, min_bucket=max(1024, execution.tile))
    return StackedLane(
        arrays=(_pad_axis(lo, 2, bucket), _pad_axis(hi, 2, bucket)),
        n_real=n,
        statics=(meta["scale"], meta["num_levels"], meta["m_init"]),
    )


def _stacked_rejection_body(codes_lo, codes_hi, points, keys_lo, keys_hi,
                            n_real, key_bits, *, k, scale, num_levels,
                            m_init, c, schedule, max_rounds, tile,
                            interpret):
    count_trace("rejection/device/stacked")   # trace-time only

    def lane(cl, ch, p, klo, khi, nr, bits):
        return device_rejection_sampling(
            cl, ch, p, klo, khi, k, jax.random.wrap_key_data(bits),
            scale=scale, num_levels=num_levels, m_init=m_init, c=c,
            schedule=schedule, max_rounds=max_rounds, tile=tile,
            interpret=interpret, n_real=nr,
        )

    return jax.vmap(lane)(codes_lo, codes_hi, points, keys_lo, keys_hi,
                          n_real, key_bits)


def _stacked_fastkmeanspp_body(codes_lo, codes_hi, n_real, key_bits, *, k,
                               scale, num_levels, m_init, tile, interpret):
    count_trace("fastkmeans++/device/stacked")  # trace-time only

    def lane(cl, ch, nr, bits):
        return device_fast_kmeanspp(
            cl, ch, k, jax.random.wrap_key_data(bits),
            scale=scale, num_levels=num_levels, m_init=m_init, tile=tile,
            interpret=interpret, n_real=nr,
        )

    return jax.vmap(lane)(codes_lo, codes_hi, n_real, key_bits)


_STACKED_REJ_STATICS = ("k", "scale", "num_levels", "m_init", "c",
                        "schedule", "max_rounds", "tile", "interpret")
_STACKED_FKM_STATICS = ("k", "scale", "num_levels", "m_init", "tile",
                        "interpret")

stacked_rejection_sampling = jax.jit(
    _stacked_rejection_body, static_argnames=_STACKED_REJ_STATICS)
stacked_rejection_sampling_donated = jax.jit(
    _stacked_rejection_body, static_argnames=_STACKED_REJ_STATICS,
    donate_argnums=(0, 1, 2, 3, 4))
stacked_fast_kmeanspp = jax.jit(
    _stacked_fastkmeanspp_body, static_argnames=_STACKED_FKM_STATICS)
stacked_fast_kmeanspp_donated = jax.jit(
    _stacked_fastkmeanspp_body, static_argnames=_STACKED_FKM_STATICS,
    donate_argnums=(0, 1))


def use_donation(execution) -> bool:
    """Donation policy: only when asked for AND the backend honours it
    (XLA:CPU ignores donations with a warning, so `donate=True` stays
    advisory there — the documented ExecutionSpec semantics)."""
    return bool(execution.donate) and jax.default_backend() != "cpu"


def _solve_stacked_rejection(lanes, k, key_bits, *, c, schedule, options,
                             execution):
    """`BackendImpl.solve_stacked`: one vmapped program per shape bucket."""
    arrs = [jnp.stack([lane.arrays[j] for lane in lanes])
            for j in range(len(lanes[0].arrays))]
    n_real = jnp.asarray([lane.n_real for lane in lanes], jnp.int32)
    scale, num_levels, m_init = lanes[0].statics
    sched = resolve_schedule(schedule, options.get("batch"))
    donate = use_donation(execution)
    fn = stacked_rejection_sampling_donated if donate \
        else stacked_rejection_sampling
    idx, trials = fn(
        *arrs, n_real, key_bits, k=k, scale=scale, num_levels=num_levels,
        m_init=m_init, c=c, schedule=sched,
        max_rounds=options.get("max_rounds", 32), tile=execution.tile,
        interpret=execution.interpret,
    )
    return idx, {"trials": trials, "batch_buckets": sched.buckets(),
                 "donated": donate}


def _solve_stacked_fastkmeanspp(lanes, k, key_bits, *, c, schedule, options,
                                execution):
    arrs = [jnp.stack([lane.arrays[j] for lane in lanes])
            for j in range(len(lanes[0].arrays))]
    n_real = jnp.asarray([lane.n_real for lane in lanes], jnp.int32)
    scale, num_levels, m_init = lanes[0].statics
    donate = use_donation(execution)
    fn = stacked_fast_kmeanspp_donated if donate else stacked_fast_kmeanspp
    idx = fn(*arrs, n_real, key_bits, k=k, scale=scale,
             num_levels=num_levels, m_init=m_init, tile=execution.tile,
             interpret=execution.interpret)
    return idx, {"donated": donate}


# ---------------------------------------------------------------------------
# Host-facing wrappers with the common `seed_fn(points, k, rng, **kw)`
# signature, registered in `seeding.SEEDERS` under "<name>/device".
# ---------------------------------------------------------------------------

def device_fast_kmeanspp_seeder(points, k, rng, *, resolution=None,
                                tile=512, interpret=None, **_):
    """Algorithm 3 on device; `SeedingResult` facade over the jit program."""
    from repro.core.seeding import SeedingResult

    t0 = time.perf_counter()
    pts = np.asarray(points, dtype=np.float64)
    lo, hi, meta = prepare_embedding(pts, seed=int(rng.integers(2 ** 31)),
                                     resolution=resolution)
    t_prep = time.perf_counter() - t0
    key = jax.random.key(int(rng.integers(2 ** 31)))
    # NOTE: every static is passed explicitly — jax.jit keys its cache on
    # the bound call, so an omitted default and an explicit equal value
    # land in different cache entries; this call must bind exactly like
    # the plan adapter's to share one compiled program.
    chosen = device_fast_kmeanspp(
        lo, hi, k, key,
        scale=meta["scale"], num_levels=meta["num_levels"],
        m_init=meta["m_init"], tile=tile, interpret=interpret,
    )
    idx = np.asarray(jax.block_until_ready(chosen), dtype=np.int64)
    seconds = time.perf_counter() - t0
    return SeedingResult(
        centers=pts[idx].copy(),
        indices=idx,
        seconds=seconds,
        num_candidates=k,
        prepare_seconds=t_prep,
        solve_seconds=seconds - t_prep,
        extras={"backend": "device"},
    )


def resolve_schedule(schedule, batch) -> BatchSchedule:
    """The seeders' schedule policy: an explicit `BatchSchedule` wins, a
    legacy ``batch=<int>`` pins a one-bucket fixed schedule, and the default
    is the adaptive schedule."""
    if schedule is not None:
        return schedule
    if batch is not None:
        return BatchSchedule.fixed(int(batch))
    return BatchSchedule()


def device_rejection_seeder(points, k, rng, *, c=1.2, lsh_r=None,
                            num_tables=15, hashes_per_table=1,
                            resolution=None, schedule=None, batch=None,
                            max_rounds=32, tile=512, interpret=None, **_):
    """Algorithm 4 on device; `SeedingResult` facade over the jit program."""
    from repro.core.seeding import SeedingResult

    t0 = time.perf_counter()
    pts = np.asarray(points, dtype=np.float64)
    sched = resolve_schedule(schedule, batch)
    data = prepare_rejection(
        pts, seed=int(rng.integers(2 ** 31)), resolution=resolution,
        lsh_r=lsh_r, num_tables=num_tables,
        hashes_per_table=hashes_per_table,
    )
    t_prep = time.perf_counter() - t0
    key = jax.random.key(int(rng.integers(2 ** 31)))
    chosen, trials = device_rejection_sampling(
        data.codes_lo, data.codes_hi, data.points,
        data.keys_lo, data.keys_hi, k, key,
        scale=data.scale, num_levels=data.num_levels, m_init=data.m_init,
        c=c, schedule=sched, max_rounds=max_rounds, tile=tile,
        interpret=interpret,
    )
    idx = np.asarray(jax.block_until_ready(chosen), dtype=np.int64)
    trials = np.asarray(trials, dtype=np.int64)
    total = int(trials.sum())
    seconds = time.perf_counter() - t0
    return SeedingResult(
        centers=pts[idx].copy(),
        indices=idx,
        seconds=seconds,
        num_candidates=total,
        prepare_seconds=t_prep,
        solve_seconds=seconds - t_prep,
        extras={
            "backend": "device",
            "trials_per_center": total / k,
            "per_center_trials": trials,
            "batch_buckets": sched.buckets(),
        },
    )


# ---------------------------------------------------------------------------
# k-means|| baseline (Bahmani et al. 2012; bias analysis Makarychev et al.,
# arXiv:2010.14487): the oversampling rounds as one jit device program.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("rounds", "cap", "interpret"))
def device_kmeans_parallel_rounds(
    points: jax.Array,       # (n, d) f32
    key: jax.Array,
    ell: jax.Array,          # oversampling factor per round (scalar f32)
    *,
    rounds: int,
    cap: int,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """k-means|| oversampling: `rounds` passes, each picking every point
    independently with probability ``min(1, ell * d2(x) / phi)`` and then
    refreshing d2 against the round's picks with one `pairwise_argmin`
    kernel sweep.  Returns ``(selected (n,) bool, d2 (n,))``.

    `cap` bounds a single round's pick count (static shapes for the gather);
    picks beyond it are dropped *consistently* — they are neither marked
    selected nor allowed to lower d2 — so the candidate pool stays exactly
    the set the distance field saw.  The weighted recluster down to k runs
    host-side on the O(ell * rounds) pool (`seeding.kmeans_parallel` doc).
    """
    count_trace("kmeans||/device")            # trace-time only
    n, d = points.shape
    key, k0 = jax.random.split(key)
    x0 = jax.random.randint(k0, (), 0, n)
    d2_0 = jnp.sum((points - points[x0]) ** 2, axis=1)
    sel0 = jnp.zeros((n,), jnp.bool_).at[x0].set(True)

    def round_body(r, carry):
        key, sel, d2 = carry
        key, kr = jax.random.split(key)
        phi = jnp.sum(d2)
        p = jnp.minimum(1.0, ell * d2 / jnp.maximum(phi, 1e-30))
        u = jax.random.uniform(kr, (n,), dtype=jnp.float32)
        want = (u < p) & (phi > 0)
        idx = jnp.nonzero(want, size=cap, fill_value=0)[0]
        valid = jnp.arange(cap) < jnp.sum(want)
        picked = jnp.zeros((n,), jnp.int32).at[idx].max(
            valid.astype(jnp.int32)
        ).astype(jnp.bool_) & want
        ctrs = jnp.where(valid[:, None], points[idx], _FAR)
        dmin, _ = pairwise_argmin(points, ctrs, interpret=interpret)
        return key, sel | picked, jnp.minimum(d2, dmin)

    _, sel, d2 = jax.lax.fori_loop(0, rounds, round_body, (key, sel0, d2_0))
    return sel, d2


def device_kmeans_parallel_seeder(points, k, rng, *, rounds=5,
                                  oversample=None, interpret=None, **_):
    """k-means|| with the oversampling rounds on device; the O(ell * rounds)
    candidate pool is reclustered host-side by weighted k-means++ (shared
    with the CPU baseline)."""
    from repro.core.seeding import (
        SeedingResult,
        _candidate_pool_to_centers,
    )

    t0 = time.perf_counter()
    pts = np.asarray(points, dtype=np.float64)
    n = len(pts)
    ell = float(oversample) if oversample is not None else 2.0 * k
    cap = int(min(n, max(8, 4 * ell)))
    key = jax.random.key(int(rng.integers(2 ** 31)))
    sel, _ = device_kmeans_parallel_rounds(
        jnp.asarray(pts, jnp.float32), key, jnp.float32(ell),
        rounds=rounds, cap=cap, interpret=interpret,
    )
    cand = np.flatnonzero(np.asarray(jax.block_until_ready(sel)))
    idx, pool = _candidate_pool_to_centers(pts, cand, k, rng)
    return SeedingResult(
        centers=pts[idx].copy(),
        indices=idx,
        seconds=time.perf_counter() - t0,
        num_candidates=pool,
        extras={"backend": "device", "pool_size": pool, "rounds": rounds,
                "oversample": ell},
    )


DEVICE_SEEDERS = {
    "fastkmeans++": device_fast_kmeanspp_seeder,
    "rejection": device_rejection_seeder,
    "kmeans||": device_kmeans_parallel_seeder,
}


# ---------------------------------------------------------------------------
# Cached prepare/solve split for `core.plan.ClusterPlan` (typed registry).
#
# Contract: `prepare` consumes from `rng` exactly the draws the composed
# legacy seeder would before its jit program key, and `solve` draws the key
# (plus any post-program host draws) — so prepare-then-solve reproduces the
# legacy `seed_fn` bit-for-bit while letting the plan cache `prepare`'s
# artifacts across fits.
# ---------------------------------------------------------------------------

def _prep_fastkmeanspp(pts, rng, *, resolution, options, execution):
    return prepare_embedding(pts, seed=int(rng.integers(2 ** 31)),
                             resolution=resolution)


def _solve_fastkmeanspp(artifacts, pts, k, rng, *, c, schedule, options,
                        execution):
    lo, hi, meta = artifacts
    key = jax.random.key(int(rng.integers(2 ** 31)))
    chosen = device_fast_kmeanspp(
        lo, hi, k, key,
        scale=meta["scale"], num_levels=meta["num_levels"],
        m_init=meta["m_init"], tile=execution.tile,
        interpret=execution.interpret,
    )
    return chosen, {"num_candidates": k}


def _prep_rejection(pts, rng, *, resolution, options, execution):
    return prepare_rejection(
        pts, seed=int(rng.integers(2 ** 31)), resolution=resolution,
        lsh_r=options.get("lsh_r"),
        num_tables=options.get("num_tables", 15),
        hashes_per_table=options.get("hashes_per_table", 1),
    )


def _solve_rejection(data, pts, k, rng, *, c, schedule, options, execution):
    sched = resolve_schedule(schedule, options.get("batch"))
    key = jax.random.key(int(rng.integers(2 ** 31)))
    chosen, trials = device_rejection_sampling(
        data.codes_lo, data.codes_hi, data.points,
        data.keys_lo, data.keys_hi, k, key,
        scale=data.scale, num_levels=data.num_levels, m_init=data.m_init,
        c=c, schedule=sched,
        max_rounds=options.get("max_rounds", 32), tile=execution.tile,
        interpret=execution.interpret,
    )
    return chosen, {"trials": trials, "batch_buckets": sched.buckets()}


def _prep_kmeans_parallel(pts, rng, *, resolution, options, execution):
    # The only reusable artifact is the device upload itself (f32 copy).
    return jnp.asarray(pts, jnp.float32)


def _solve_kmeans_parallel(pts_dev, pts, k, rng, *, c, schedule, options,
                           execution):
    from repro.core.seeding import _candidate_pool_to_centers

    n = pts_dev.shape[0]
    oversample = options.get("oversample")
    ell = float(oversample) if oversample is not None else 2.0 * k
    cap = int(min(n, max(8, 4 * ell)))
    key = jax.random.key(int(rng.integers(2 ** 31)))
    sel, _ = device_kmeans_parallel_rounds(
        pts_dev, key, jnp.float32(ell),
        rounds=options.get("rounds", 5), cap=cap,
        interpret=execution.interpret,
    )
    cand = np.flatnonzero(np.asarray(jax.block_until_ready(sel)))
    idx, pool = _candidate_pool_to_centers(pts, cand, k, rng)
    return idx, {"pool_size": pool, "num_candidates": pool}


def _register():
    from repro.core import registry, seeding

    impls = {
        "fastkmeans++": registry.BackendImpl(
            run=device_fast_kmeanspp_seeder, device_native=True,
            prepare=_prep_fastkmeanspp, solve=_solve_fastkmeanspp,
            prepare_stacked=_canonical_fastkmeanspp_lane,
            solve_stacked=_solve_stacked_fastkmeanspp),
        "rejection": registry.BackendImpl(
            run=device_rejection_seeder, device_native=True,
            prepare=_prep_rejection, solve=_solve_rejection,
            prepare_stacked=_canonical_rejection_lane,
            solve_stacked=_solve_stacked_rejection),
        # kmeans|| is NOT device_native: the oversampling rounds are one jit
        # program but the weighted recluster runs host-side per fit.
        "kmeans||": registry.BackendImpl(
            run=device_kmeans_parallel_seeder, device_native=False,
            prepare=_prep_kmeans_parallel, solve=_solve_kmeans_parallel),
    }
    for name, impl in impls.items():
        registry.register_backend(name, "device", impl,
                                  legacy_registry=seeding.SEEDERS)


_register()
