"""Multi-chip seeders: the device programs of `device_seeding` sharded over
a 1-D "data" mesh with `shard_map` — the codebase's first multi-chip seeding
path (ROADMAP: "shard the tree-sep/LSH sweeps across chips").

Layout (docs/sample_tree.md): every per-point tensor — multi-tree codes
(T, H, n), coordinates (n, d), LSH bucket keys (L, n), and the D^2 weight
vector — is split into D contiguous leaf ranges, one per device.  Each shard
owns a *local sub-heap* (`TiledSampleTree` over its own tiles, refreshed
incrementally from the sweeps' per-tile sums) and the only
replicated sampling state is the tiny top-tree: the (D,) vector of shard
totals, produced by one `all_gather` per draw.

MULTITREESAMPLE therefore runs shard-then-descend: a replicated uniform
picks a shard from the top-tree cumsum, the owning shard descends its local
coarse heap + intra-tile cumsum, and the winning global index (plus, for the
rejection sampler, the candidate's coordinates / bucket keys / current
weight) is broadcast with one masked `psum`.  Opening a center broadcasts
the owner shard's code column the same way; the O(nH) tree-sep and LSH
sweeps then run fully parallel, each device touching only its n/D points —
the cross-chip sharding of the distance/LSH sweeps.

Everything (the k-center `fori_loop`, the per-center rejection
`while_loop`, the Pallas kernels — interpret mode off-TPU) runs inside one
`shard_map`-wrapped jit program; control flow stays in lockstep because
every predicate is computed from replicated (psum/all_gather) values.

**Program cache.**  Serving-style callers `fit` repeatedly with identical
static configuration; re-wrapping `shard_map` + `jax.jit` per call would
re-trace every time.  The jitted programs are therefore built once per
``(mesh, array shapes, static args)`` key by `functools.lru_cache`-d
builders and reused — `TRACE_COUNTS` (incremented inside the program bodies,
i.e. at trace time only) plus `program_cache_info()` expose the behaviour to
tests and profiling.

The per-center rejection block size follows the same adaptive
`BatchSchedule` as the single-device program: one `lax.switch` branch per
power-of-two bucket, bucket index + acceptance EMA carried as loop state.
Every value feeding the switch predicate is replicated (psum outputs), so
all shards take the same branch and the collectives inside the branches stay
in lockstep.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.batch_schedule import BatchSchedule
from repro.core.device_seeding import (
    _FAR,
    DeviceSeedingData,
    _make_sweep,
    _pad_axis,
    prepare_embedding,
    rejection_encoder,
    resolve_schedule,
)
from repro.core.plan import _pairwise_d2
from repro.core.preprocess import host_block_map
from repro.core.sample_tree import TiledSampleTree
from repro.core.tracing import TRACE_COUNTS, span
from repro.core.tree_embedding import hash_level_planes
from repro.distributed.sharding import _mesh_size, points_axis
from repro.kernels.ops import lsh_bucket_accept, pairwise_argmin
from repro.launch.mesh import make_seeding_mesh

__all__ = [
    "sharded_fast_kmeanspp",
    "sharded_rejection_sampling",
    "sharded_kmeans_parallel_rounds",
    "sharded_fast_kmeanspp_seeder",
    "sharded_rejection_seeder",
    "sharded_kmeans_parallel_seeder",
    "SHARDED_SEEDERS",
    "TRACE_COUNTS",
    "PlacedRows",
    "place_rows",
    "program_cache_info",
]

# TRACE_COUNTS (re-exported from `repro.core.tracing`, shared with the
# single-device programs): incremented inside the shard_map program bodies,
# which only execute while jax traces them — so each key counts *traces*,
# not calls.  Tests use it to assert that repeated fits with identical
# static args reuse the cached compiled program instead of re-tracing.


def program_cache_info():
    """lru_cache statistics of the jit-program builders (hits = reuses)."""
    return {
        "fastkmeans++": _fastkmeanspp_program.cache_info(),
        "rejection": _rejection_program.cache_info(),
        "kmeans||": _kmeans_parallel_program.cache_info(),
    }


def _shard_sampler(ts_loc, axis):
    """Shard-then-descend MULTITREESAMPLE over local sub-heaps.

    Returns a function drawing `size` i.i.d. global indices: the (D,)
    top-tree of shard totals is gathered once, a replicated uniform picks
    each draw's shard, every shard descends locally for all lanes, and one
    masked psum publishes the winners.  Exact per-point distribution:
    P(shard) * P(point | shard).
    """

    def sample(coarse, w_loc, key, size):
        sid = jax.lax.axis_index(axis)
        n_loc = w_loc.shape[0]
        k1, k2 = jax.random.split(key)
        totals = jax.lax.all_gather(coarse[1], axis)          # (D,) top-tree
        csum = jnp.cumsum(totals)
        u = jax.random.uniform(k1, (size,), dtype=jnp.float32) * csum[-1]
        s = jnp.sum(csum[None, :] <= u[:, None], axis=1).astype(jnp.int32)
        s = jnp.minimum(s, totals.shape[0] - 1)               # (size,) shards
        loc = ts_loc.sample(coarse, w_loc, k2, size)          # local descent
        mine = s == sid
        return jax.lax.psum(
            jnp.where(mine, loc + sid * n_loc, 0), axis
        ).astype(jnp.int32), mine, loc

    return sample


def _broadcast_from_owner(x_glob, n_loc, axis, *columns):
    """Publish per-point data of a *global* index from its owner shard.

    Each entry of `columns` is a fn(local_index) -> array; the owner's value
    is psum-broadcast (other shards contribute zeros).  Returns the local
    index alongside the broadcast values.
    """
    sid = jax.lax.axis_index(axis)
    owner = x_glob // n_loc
    x_loc = x_glob % n_loc
    out = []
    for fn in columns:
        val = fn(x_loc)
        out.append(jax.lax.psum(jnp.where(sid == owner, val, 0), axis))
    return out


def _plane_rows(planes, idx, mode=None):
    """Rows `idx` of a point set held as coordinate planes (d8, n): the
    (len(idx), d8) coordinates.  Taken through the (d8 / 8, 8, n) view of
    the planes, a gather XLA runs in the planes' own layout; from the 2-D
    view it first copies every plane into another (on a TPU, a second
    copy of the set, larger than the first)."""
    g, n = planes.shape[0] // 8, planes.shape[1]
    got = jnp.take(planes.reshape(g, 8, n), idx, axis=2, mode=mode)
    return got.reshape(g * 8, -1).T


def _init_weights(n_loc, n_real, m_init, axis):
    """Local slice of the initial weight vector; the global padding tail
    (and only it) starts — and therefore stays — at weight 0."""
    sid = jax.lax.axis_index(axis)
    gids = sid * n_loc + jnp.arange(n_loc)
    return jnp.where(gids < n_real, m_init, 0.0).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Cached jit-program builders.  Key = (mesh, shapes, static args): the Mesh
# object hashes by device assignment + axis names, so one program per
# serving configuration, reused across every subsequent `fit`.
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _fastkmeanspp_program(mesh, t, h, n_pad, k, scale, num_levels, m_init,
                          n_real, tile, interpret):
    axis = points_axis(mesh, n_pad)
    d_ax = _mesh_size(mesh, axis)
    n_loc = n_pad // d_ax
    ts_loc = TiledSampleTree(n_loc, tile=tile)

    def program(clo, chi, bits):
        TRACE_COUNTS["fastkmeans++"] += 1     # trace-time only
        key = jax.random.wrap_key_data(bits)
        # Sharded MULTITREEOPEN: each device sweeps only its own points,
        # for the opened center's code columns (read on its owner shard
        # and broadcast); the shard's code planes are padded here, once
        # per seeding.
        sweep, columns = _make_sweep(clo, chi, scale=scale,
                                     num_levels=num_levels, tile=tile,
                                     interpret=interpret)
        sample = _shard_sampler(ts_loc, axis)

        def body(i, state):
            w, coarse, chosen, key = state
            key, k_unif, k_samp = jax.random.split(key, 3)
            x_samp, _, _ = sample(coarse, w, k_samp, 1)
            x = jnp.where(
                i == 0, jax.random.randint(k_unif, (), 0, n_real), x_samp[0]
            ).astype(jnp.int32)
            col_lo, col_hi = _broadcast_from_owner(
                x, n_loc, axis,
                lambda xl: jnp.stack(columns(xl)[0]),
                lambda xl: jnp.stack(columns(xl)[1]),
            )
            w, tsums = sweep(w, col_lo, col_hi)
            coarse = ts_loc.refresh(coarse, tsums)
            chosen = chosen.at[i].set(x)
            return w, coarse, chosen, key

        w0 = _init_weights(n_loc, n_real, m_init, axis)
        coarse0 = ts_loc.init(w0)
        chosen0 = jnp.zeros((k,), jnp.int32)
        _, _, chosen, _ = jax.lax.fori_loop(
            0, k, body, (w0, coarse0, chosen0, key)
        )
        return chosen

    fn = jax.shard_map(
        program, mesh=mesh,
        in_specs=(P(None, None, axis), P(None, None, axis), P()),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(fn)


def sharded_fast_kmeanspp(
    codes_lo: jax.Array,     # (T, H-1, n_pad) int32, n_pad % (D * tile) == 0
    codes_hi: jax.Array,
    k: int,
    seed_bits: jax.Array,    # raw PRNG key data (replicated)
    *,
    mesh,
    scale: float,
    num_levels: int,
    m_init: float,
    n_real: int,
    tile: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Algorithm 3 sharded over the mesh's "data" axis.  (k,) int32 indices."""
    t, h, n_pad = codes_lo.shape
    fn = _fastkmeanspp_program(mesh, t, h, n_pad, k, scale, num_levels,
                               m_init, n_real, tile, interpret)
    return fn(codes_lo, codes_hi, seed_bits)


@functools.lru_cache(maxsize=None)
def _rejection_program(mesh, t, h, n_pad, l, d, k, scale, num_levels, m_init,
                       n_real, c, schedule, max_rounds, tile, interpret):
    axis = points_axis(mesh, n_pad)
    d_ax = _mesh_size(mesh, axis)
    n_loc = n_pad // d_ax
    ts_loc = TiledSampleTree(n_loc, tile=tile)
    c2 = float(c) ** 2
    buckets = schedule.buckets()
    b_idx0 = schedule.index_of(schedule.initial(n_real, k, ts_loc.num_tiles))

    def program(clo, chi, pts_loc, klo, khi, bits):
        TRACE_COUNTS["rejection"] += 1        # trace-time only
        key = jax.random.wrap_key_data(bits)
        # Sharded MULTITREEOPEN: each device sweeps only its own points,
        # for the opened center's code columns (read on its owner shard
        # and broadcast); the shard's code planes are padded here, once
        # per seeding.
        sweep, columns = _make_sweep(clo, chi, scale=scale,
                                     num_levels=num_levels, tile=tile,
                                     interpret=interpret)
        sample = _shard_sampler(ts_loc, axis)

        def body(i, state):
            (w, coarse, chosen, ctr_pts, ck_lo, ck_hi, trials, b_idx,
             acc_ema, key) = state
            key, k_unif = jax.random.split(key)
            x_unif = jax.random.randint(k_unif, (), 0, n_real).astype(
                jnp.int32
            )
            total = jax.lax.psum(coarse[1], axis)

            def round_cond(carry):
                key, x_sel, done, t_i, rounds, b_idx, acc_ema = carry
                return (~done) & (rounds < max_rounds) & (i > 0) & (total > 0)

            def round_body(carry):
                key, x_sel, done, t_i, rounds, b_idx, acc_ema = carry
                key, k_cand, k_u = jax.random.split(key, 3)

                def make_branch(bj):
                    # One bucket of the schedule's ladder; every shard takes
                    # the same branch (b_idx derives from replicated values)
                    # so the psums inside stay in lockstep.
                    def branch(_):
                        cand, mine, loc = sample(coarse, w, k_cand, bj)
                        us = jax.random.uniform(k_u, (bj,),
                                                dtype=jnp.float32)
                        # Two masked psums ship the winning candidates' data
                        # to every shard: coordinates + current weight share
                        # one f32 (B, d+1) payload, both bucket-key planes
                        # one int32 (2L, B) payload — the round's collective
                        # latency floor.
                        fpay = jnp.concatenate(
                            [_plane_rows(pts_loc, loc), w[loc][:, None]],
                            axis=1
                        )
                        fpay = jax.lax.psum(
                            jnp.where(mine[:, None], fpay, 0.0), axis
                        )
                        q, mtd2 = fpay[:, :d], fpay[:, d]
                        kpay = jnp.concatenate(
                            [klo[:, loc], khi[:, loc]], axis=0
                        )
                        kpay = jax.lax.psum(
                            jnp.where(mine[None, :], kpay, 0), axis
                        )
                        qk_lo, qk_hi = kpay[:l], kpay[l:]
                        _, p_acc = lsh_bucket_accept(
                            qk_lo, qk_hi, q, ck_lo, ck_hi, ctr_pts, mtd2, i,
                            c2=c2, interpret=interpret,
                        )
                        acc = us < p_acc
                        any_acc = jnp.any(acc)
                        hit = jnp.argmax(acc)
                        x_b = jnp.where(any_acc, cand[hit], cand[0]).astype(
                            jnp.int32
                        )
                        used = jnp.where(any_acc, hit + 1, bj).astype(
                            jnp.int32
                        )
                        rate = (jnp.sum(acc) / bj).astype(jnp.float32)
                        return x_b, any_acc, used, rate
                    return branch

                branches = [make_branch(bj) for bj in buckets]
                if len(branches) == 1:        # fixed schedule
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
            t_i = jnp.maximum(t_i, 1)

            col_lo, col_hi, x_pt, xk_lo, xk_hi = _broadcast_from_owner(
                x, n_loc, axis,
                lambda xl: jnp.stack(columns(xl)[0]),
                lambda xl: jnp.stack(columns(xl)[1]),
                lambda xl: _plane_rows(pts_loc, xl[None])[0],
                lambda xl: klo[:, xl],
                lambda xl: khi[:, xl],
            )
            w, tsums = sweep(w, col_lo, col_hi)
            coarse = ts_loc.refresh(coarse, tsums)
            chosen = chosen.at[i].set(x)
            ctr_pts = ctr_pts.at[i].set(x_pt)
            ck_lo = ck_lo.at[:, i].set(xk_lo)
            ck_hi = ck_hi.at[:, i].set(xk_hi)
            trials = trials.at[i].set(t_i)
            return (w, coarse, chosen, ctr_pts, ck_lo, ck_hi, trials,
                    b_idx, acc_ema, key)

        w0 = _init_weights(n_loc, n_real, m_init, axis)
        coarse0 = ts_loc.init(w0)
        state0 = (
            w0, coarse0,
            jnp.zeros((k,), jnp.int32),
            jnp.full((k, d), _FAR, jnp.float32),
            jnp.zeros((l, k), jnp.int32),
            jnp.zeros((l, k), jnp.int32),
            jnp.zeros((k,), jnp.int32),
            jnp.int32(b_idx0),
            jnp.float32(schedule.prior_accept),
            key,
        )
        out = jax.lax.fori_loop(0, k, body, state0)
        return out[2], out[6]

    fn = jax.shard_map(
        program, mesh=mesh,
        in_specs=(
            P(None, None, axis), P(None, None, axis),
            P(None, axis), P(None, axis), P(None, axis), P(),
        ),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(fn)


def sharded_rejection_sampling(
    codes_lo: jax.Array,     # (T, H-1, n_pad) int32
    codes_hi: jax.Array,
    points: jax.Array,       # (d8, n_pad) f32 coordinate planes
    keys_lo: jax.Array,      # (L, n_pad) int32
    keys_hi: jax.Array,
    k: int,
    seed_bits: jax.Array,
    *,
    mesh,
    scale: float,
    num_levels: int,
    m_init: float,
    n_real: int,
    c: float = 1.2,
    schedule: BatchSchedule | None = None,
    max_rounds: int = 32,
    tile: int = 512,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Algorithm 4 sharded over the mesh's "data" axis.

    Candidate batches are drawn shard-then-descend; each candidate's
    coordinates, bucket keys and current weight cross chips with one masked
    psum, after which the (small, replicated) opened-center acceptance sweep
    runs everywhere so the rejection `while_loop` stays in lockstep.
    `points` are the coordinates as planes, as `prepare_rejection_sharded`
    lays them out: d8 = d rounded up to a multiple of 8, the rows beyond d
    zero, which add nothing to a distance.  The batch size follows the
    adaptive `schedule` exactly as in `device_rejection_sampling` (see
    that docstring).
    Returns ``(chosen (k,), trials (k,))`` as in the single-device program.
    """
    t, h, n_pad = codes_lo.shape
    l = keys_lo.shape[0]
    d = points.shape[0]
    schedule = schedule if schedule is not None else BatchSchedule()
    fn = _rejection_program(mesh, t, h, n_pad, l, d, k, scale, num_levels,
                            m_init, n_real, c, schedule, max_rounds, tile,
                            interpret)
    return fn(codes_lo, codes_hi, points, keys_lo, keys_hi, seed_bits)


# ---------------------------------------------------------------------------
# k-means|| oversampling rounds, sharded: local d2/pick per shard, one
# all_gather of the round's picks, local pairwise refresh.
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _kmeans_parallel_program(mesh, n_pad, d, rounds, cap_loc, n_real,
                             interpret):
    axis = points_axis(mesh, n_pad)
    d_ax = _mesh_size(mesh, axis)
    n_loc = n_pad // d_ax

    def program(pts_loc, ell, bits):
        TRACE_COUNTS["kmeans||"] += 1         # trace-time only
        key = jax.random.wrap_key_data(bits)
        sid = jax.lax.axis_index(axis)
        gids = sid * n_loc + jnp.arange(n_loc)
        live = gids < n_real
        key, k0 = jax.random.split(key)
        x0 = jax.random.randint(k0, (), 0, n_real)
        (x_pt,) = _broadcast_from_owner(x0, n_loc, axis,
                                        lambda xl: pts_loc[xl])
        d2 = jnp.where(live, jnp.sum((pts_loc - x_pt) ** 2, axis=1), 0.0)
        sel = gids == x0

        def round_body(r, carry):
            key, sel, d2 = carry
            key, kr = jax.random.split(key)
            phi = jax.lax.psum(jnp.sum(d2), axis)
            p = jnp.minimum(1.0, ell * d2 / jnp.maximum(phi, 1e-30))
            # Per-shard independent coins: fold the (replicated) round key
            # with the shard id.
            u = jax.random.uniform(jax.random.fold_in(kr, sid), (n_loc,),
                                   dtype=jnp.float32)
            want = (u < p) & live & (phi > 0)
            idx = jnp.nonzero(want, size=cap_loc, fill_value=0)[0]
            valid = jnp.arange(cap_loc) < jnp.sum(want)
            picked = jnp.zeros((n_loc,), jnp.int32).at[idx].max(
                valid.astype(jnp.int32)
            ).astype(jnp.bool_) & want
            ctrs_loc = jnp.where(valid[:, None], pts_loc[idx], _FAR)
            ctrs = jax.lax.all_gather(ctrs_loc, axis, tiled=True)
            dmin, _ = pairwise_argmin(pts_loc, ctrs, interpret=interpret)
            d2 = jnp.where(live, jnp.minimum(d2, dmin), 0.0)
            return key, sel | picked, d2

        _, sel, _ = jax.lax.fori_loop(0, rounds, round_body, (key, sel, d2))
        return sel

    fn = jax.shard_map(
        program, mesh=mesh,
        in_specs=(P(axis, None), P(), P()),
        out_specs=P(axis),
        check_vma=False,
    )
    return jax.jit(fn)


def sharded_kmeans_parallel_rounds(
    points: jax.Array,       # (n_pad, d) f32
    ell,                     # oversampling factor (scalar f32)
    seed_bits: jax.Array,
    *,
    mesh,
    rounds: int,
    cap_loc: int,
    n_real: int,
    interpret: bool | None = None,
) -> jax.Array:
    """k-means|| oversampling rounds over the mesh; (n_pad,) bool picks.

    Per round each shard draws its own picks (at most `cap_loc`, dropped
    consistently as in `device_kmeans_parallel_rounds`), one `all_gather`
    replicates the round's (D * cap_loc, d) pick block, and the distance
    refresh runs shard-locally.
    """
    n_pad, d = points.shape
    fn = _kmeans_parallel_program(mesh, n_pad, d, rounds, cap_loc, n_real,
                                  interpret)
    return fn(points, jnp.float32(ell), seed_bits)


# ---------------------------------------------------------------------------
# Host prepare, shard by shard: the whole-set statistics in one bounded pass,
# then each shard's codes, keys and coordinates built on the host in row
# blocks and put on its own chips.  No whole-set temporary, no whole copy
# on any one device.
# ---------------------------------------------------------------------------

#: Rows one host task encodes: its float64 temporaries are a few MB, not
#: the set's.  A block starts on a multiple of the tile (shards start on
#: one), so its LSH projections take the BLAS path the whole set's take.
PREPARE_BLOCK_ROWS = 8192


def _shard_ranges(mesh, n_pad: int) -> dict:
    """(first, end) column of each device's shard of the points axis ->
    the devices that hold it."""
    sharding = NamedSharding(mesh, P(points_axis(mesh, n_pad)))
    ranges: dict = {}
    for dev, index in sharding.addressable_devices_indices_map(
            (n_pad,)).items():
        lo, hi, _ = index[0].indices(n_pad)
        ranges.setdefault((lo, hi), []).append(dev)
    return dict(sorted(ranges.items()))


def _assemble(mesh, spec, shape, placed: dict) -> jax.Array:
    """One global array from per-device shards already on their devices."""
    sharding = NamedSharding(mesh, spec)
    devices = sharding.addressable_devices_indices_map(shape)
    return jax.make_array_from_single_device_arrays(
        shape, sharding, [placed[dev] for dev in devices])


def _encode_shard(enc, pts, lo, hi, map_fn) -> dict:
    """The host's part of rows [lo, hi): every tree's deepest-level cells
    (T, hi - lo, d), which the shard's device hashes into codes, and the
    LSH key and coordinate planes; zero past the set's last row."""
    n, d = pts.shape
    width, real = hi - lo, max(0, min(hi, n) - lo)
    trees = len(enc.trees.shifts)
    out = {
        "cells": np.zeros((trees, width, d), enc.trees.cell_dtype),
        "keys_lo": np.zeros((enc.lsh.L, width), np.int32),
        "keys_hi": np.zeros((enc.lsh.L, width), np.int32),
        "points": np.zeros((-(-d // 8) * 8, width), np.float32),
    }

    def block(a):
        rows = pts[lo + a:lo + min(a + PREPARE_BLOCK_ROWS, real)]
        cols = slice(a, a + len(rows))
        with span("repro.prepare.embed"):
            for t in range(trees):
                out["cells"][t, cols] = enc.trees.cells(rows, t)
        with span("repro.prepare.lsh"):
            out["keys_lo"][:, cols], out["keys_hi"][:, cols] = enc.keys(rows)
        out["points"][:d, cols] = rows.T

    list(map_fn(block, range(0, real, PREPARE_BLOCK_ROWS)))
    return out


def prepare_rejection_sharded(pts, *, mesh, tile, seed, resolution=None,
                              lsh_r=None, num_tables=15,
                              hashes_per_table=1) -> DeviceSeedingData:
    """`device_seeding.prepare_rejection` for the mesh, shard by shard.

    The whole-set statistics (diameter bound, origin, LSH width) and every
    random draw come first, as `prepare_rejection` makes them
    (`rejection_encoder`).  Then each shard of the points axis is encoded
    on the host in blocks of `PREPARE_BLOCK_ROWS` rows, over a thread pool
    (one block: this thread), and put on its own devices; the global
    arrays are assembled from the placed shards.  The host does the float
    work (each tree's deepest-level cells, the LSH keys); the trees'
    integer hashes, most of the work, run on the devices, each on its own
    shard's cells, in one program over the mesh
    (`tree_embedding.hash_level_planes`).  Codes and keys are bit for bit
    the whole-set prepare's, padded with zero columns to `n_pad` (a
    multiple of devices x `tile`); the coordinates are float32 planes (d8,
    n_pad), d rounded up to a multiple of 8 with zero rows.

    Spans: ``repro.prepare.shard`` (``shard=i``) around each shard's host
    build and placement, ``repro.prepare.place`` inside it around the
    transfer, ``repro.prepare.embed`` (cells, and the device hash) and
    ``repro.prepare.lsh`` (keys) per block, summed over threads.
    """
    pts = np.asarray(pts, dtype=np.float64)
    n, d = pts.shape
    n_pad = _padded_for_mesh(n, mesh, tile)
    axis = points_axis(mesh, n_pad)
    with host_block_map(-(-n // PREPARE_BLOCK_ROWS)) as map_fn:
        with span("repro.prepare.embed"):
            enc = rejection_encoder(
                pts, seed=seed, resolution=resolution, lsh_r=lsh_r,
                num_tables=num_tables, hashes_per_table=hashes_per_table,
                map_fn=map_fn)
        placed: dict = {}
        for i, ((lo, hi), devices) in enumerate(
                _shard_ranges(mesh, n_pad).items()):
            with span("repro.prepare.shard", shard=i):
                host = _encode_shard(enc, pts, lo, hi, map_fn)
                with span("repro.prepare.place", shard=i):
                    for dev in devices:
                        for name, a in jax.block_until_ready(
                                jax.device_put(host, dev)).items():
                            placed.setdefault(name, {})[dev] = a
                del host
    trees = len(enc.trees.shifts)
    cells = _assemble(mesh, P(None, axis, None), (trees, n_pad, d),
                      placed.pop("cells"))
    with span("repro.prepare.embed"):
        hash_fn = _hash_program(mesh, n_pad, n, enc.trees.num_levels,
                                HASH_CHUNK_ROWS)
        with jax.enable_x64(True):
            codes_lo, codes_hi = jax.block_until_ready(
                hash_fn(cells, np.stack(enc.trees.mults)[:, None, :]))
    del cells
    keys = (enc.lsh.L, n_pad)
    meta = enc.meta
    return DeviceSeedingData(
        codes_lo=codes_lo,
        codes_hi=codes_hi,
        points=_assemble(mesh, P(None, axis), (-(-d // 8) * 8, n_pad),
                         placed["points"]),
        keys_lo=_assemble(mesh, P(None, axis), keys, placed["keys_lo"]),
        keys_hi=_assemble(mesh, P(None, axis), keys, placed["keys_hi"]),
        scale=meta["scale"],
        num_levels=meta["num_levels"],
        m_init=meta["m_init"],
    )


#: Rows of a shard one step of the device hash takes: its uint64
#: temporaries are a chunk's, not the shard's.
HASH_CHUNK_ROWS = 1 << 20


@functools.lru_cache(maxsize=None)
def _hash_program(mesh, n_pad, n_real, num_levels, chunk):
    """The trees' hash over the mesh: each device hashes its own shard of
    the (T, n_pad, d) cells into (T, H-1, n_pad) lo/hi code planes, zero
    past the set's last row, `chunk` rows at a time.  One program for
    every shard, so one compile (call it with x64 on)."""
    axis = points_axis(mesh, n_pad)
    n_loc = n_pad // _mesh_size(mesh, axis)
    chunk = min(chunk, n_loc)

    def program(cells_loc, mults):
        first = jax.lax.axis_index(axis) * n_loc

        def step(i, planes):
            # The last chunk ends where the shard does; it may overlap the
            # one before it, whose codes it writes again unchanged.
            at = jnp.minimum(i * chunk, n_loc - chunk)
            got = hash_level_planes(
                jax.lax.dynamic_slice_in_dim(cells_loc, at, chunk, axis=1),
                mults, n_real - first - at, num_levels)
            return tuple(jax.lax.dynamic_update_slice_in_dim(p, g, at, axis=2)
                         for p, g in zip(planes, got))

        zero = jnp.zeros((cells_loc.shape[0], num_levels - 1, n_loc),
                         jnp.int32)
        return jax.lax.fori_loop(0, -(-n_loc // chunk), step, (zero, zero))

    codes = P(None, None, axis)
    return jax.jit(jax.shard_map(
        program, mesh=mesh, in_specs=(P(None, axis, None), P()),
        out_specs=(codes, codes), check_vma=False))


# ---------------------------------------------------------------------------
# A set's float32 rows over the mesh, for the plan's finish: each seeding's
# centers gathered from them and its cost reduced inside the mesh.
# ---------------------------------------------------------------------------

#: Rows per device per block of the reported cost: a block's (rows, k)
#: distance matrix is its only temporary.
COST_BLOCK_ROWS = 32768


@dataclasses.dataclass(frozen=True)
class PlacedRows:
    """A point set's rows (float32 by default) as coordinate planes (d8,
    chips x per_chip) split by columns over the mesh's points axis: n real
    rows of d coordinates, zero columns after them (never gathered, masked
    out of the cost), zero rows after d."""

    planes: jax.Array
    n: int
    d: int
    block: int
    mesh: Any

    def take(self, idx) -> jax.Array:
        """(len(idx), d) rows at `idx`, bit for bit the placed rows."""
        fn = _take_program(self.mesh, self.planes.shape, self.d)
        return fn(self.planes, idx)

    def cost(self, centers) -> jax.Array:
        """The k-means cost of `centers` over the n real rows: per block
        the distances of `plan._cost_program` (same arithmetic, default
        matrix precision), the real rows' minima summed, one psum."""
        fn = _rows_cost_program(self.mesh, self.planes.shape, self.n,
                                self.d, self.block)
        return fn(self.planes, centers)


def place_rows(points: np.ndarray, mesh, *, dtype="float32") -> PlacedRows:
    """`points` as `dtype` coordinate planes over `mesh`, each device's
    columns cast and built on the host (side by side, for a set of more
    than one prepare block) and copied to it alone."""
    points = np.asarray(points)
    n, d = points.shape
    chips = _mesh_size(mesh, points_axis(mesh))
    per_chip = -(-n // chips)
    block = min(COST_BLOCK_ROWS, -(-per_chip // 128) * 128)
    per_chip = -(-per_chip // block) * block
    shape = (-(-d // 8) * 8, per_chip * chips)
    sharding = NamedSharding(mesh, P(None, points_axis(mesh, shape[1])))

    def columns(lo):
        out = np.zeros((shape[0], per_chip), dtype)
        rows = points[lo:min(lo + per_chip, n)]
        out[:d, :len(rows)] = rows.T
        return out

    starts = range(0, shape[1], per_chip)
    with host_block_map(len(starts) if n > PREPARE_BLOCK_ROWS else 1) as map_fn:
        built = dict(zip(starts, map_fn(columns, starts)))
    planes = jax.make_array_from_callback(
        shape, sharding, lambda index: built[index[1].indices(shape[1])[0]])
    return PlacedRows(planes, n=n, d=d, block=block, mesh=mesh)


@functools.lru_cache(maxsize=None)
def _take_program(mesh, shape, d):
    axis = points_axis(mesh, shape[1])
    n_loc = shape[1] // _mesh_size(mesh, axis)

    def program(planes_loc, idx):
        # Each device takes the rows it holds (mode="clip": in range for
        # every index) and one psum assembles them: no device gathers
        # another's columns.
        loc = idx - jax.lax.axis_index(axis) * n_loc
        mine = (loc >= 0) & (loc < n_loc)
        rows = _plane_rows(planes_loc, loc, mode="clip")[:, :d]
        return jax.lax.psum(jnp.where(mine[:, None], rows, 0), axis)

    return jax.jit(jax.shard_map(
        program, mesh=mesh, in_specs=(P(None, axis), P()), out_specs=P(),
        check_vma=False))


@functools.lru_cache(maxsize=None)
def _rows_cost_program(mesh, shape, n_real, d, block):
    axis = points_axis(mesh, shape[1])
    n_loc = shape[1] // _mesh_size(mesh, axis)

    def program(planes_loc, centers):
        first = jax.lax.axis_index(axis) * n_loc

        def body(b, total):
            rows = jax.lax.dynamic_slice_in_dim(
                planes_loc, b * block, block, axis=1)[:d].T
            near = jnp.min(_pairwise_d2(rows, centers), axis=1)
            live = first + b * block + jnp.arange(block) < n_real
            return total + jnp.sum(jnp.where(live, near, 0.0))

        local = jax.lax.fori_loop(0, n_loc // block, body, jnp.float32(0.0))
        return jax.lax.psum(local, axis)

    return jax.jit(jax.shard_map(
        program, mesh=mesh, in_specs=(P(None, axis), P()), out_specs=P(),
        check_vma=False))


# ---------------------------------------------------------------------------
# Host-facing wrappers, registered under "<name>/sharded".
# ---------------------------------------------------------------------------

def _padded_for_mesh(n: int, mesh, tile: int) -> int:
    d_ax = _mesh_size(mesh, points_axis(mesh))
    unit = d_ax * tile
    return -(-n // unit) * unit


def sharded_fast_kmeanspp_seeder(points, k, rng, *, resolution=None,
                                 tile=512, interpret=None, mesh=None, **_):
    """Algorithm 3 across all local devices; `SeedingResult` facade."""
    from repro.core.seeding import SeedingResult

    t0 = time.perf_counter()
    pts = np.asarray(points, dtype=np.float64)
    n = len(pts)
    mesh = mesh if mesh is not None else make_seeding_mesh()
    lo, hi, meta = prepare_embedding(pts, seed=int(rng.integers(2 ** 31)),
                                     resolution=resolution)
    n_pad = _padded_for_mesh(n, mesh, tile)
    lo = _pad_axis(lo, 2, n_pad)
    hi = _pad_axis(hi, 2, n_pad)
    t_prep = time.perf_counter() - t0
    bits = jax.random.key_data(jax.random.key(int(rng.integers(2 ** 31))))
    chosen = sharded_fast_kmeanspp(
        lo, hi, k, bits, mesh=mesh,
        scale=meta["scale"], num_levels=meta["num_levels"],
        m_init=meta["m_init"], n_real=n, tile=tile, interpret=interpret,
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
        extras={"backend": "sharded", "devices": mesh.devices.size},
    )


def sharded_rejection_seeder(points, k, rng, *, c=1.2, lsh_r=None,
                             num_tables=15, hashes_per_table=1,
                             resolution=None, schedule=None, batch=None,
                             max_rounds=32, tile=512, interpret=None,
                             mesh=None, **_):
    """Algorithm 4 across all local devices; `SeedingResult` facade."""
    from repro.core.seeding import SeedingResult

    t0 = time.perf_counter()
    pts = np.asarray(points, dtype=np.float64)
    n = len(pts)
    mesh = mesh if mesh is not None else make_seeding_mesh()
    sched = resolve_schedule(schedule, batch)
    data = prepare_rejection_sharded(
        pts, mesh=mesh, tile=tile, seed=int(rng.integers(2 ** 31)),
        resolution=resolution, lsh_r=lsh_r, num_tables=num_tables,
        hashes_per_table=hashes_per_table,
    )
    t_prep = time.perf_counter() - t0
    bits = jax.random.key_data(jax.random.key(int(rng.integers(2 ** 31))))
    chosen, trials = sharded_rejection_sampling(
        data.codes_lo, data.codes_hi, data.points, data.keys_lo,
        data.keys_hi, k, bits, mesh=mesh, scale=data.scale,
        num_levels=data.num_levels, m_init=data.m_init, n_real=n, c=c,
        schedule=sched, max_rounds=max_rounds, tile=tile,
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
            "backend": "sharded",
            "devices": mesh.devices.size,
            "trials_per_center": total / k,
            "per_center_trials": trials,
            "batch_buckets": sched.buckets(),
        },
    )


def sharded_kmeans_parallel_seeder(points, k, rng, *, rounds=5,
                                   oversample=None, tile=512, interpret=None,
                                   mesh=None, **_):
    """k-means|| with sharded oversampling rounds; host-side weighted
    recluster (shared with the CPU baseline)."""
    from repro.core.seeding import (
        SeedingResult,
        _candidate_pool_to_centers,
    )

    t0 = time.perf_counter()
    pts = np.asarray(points, dtype=np.float64)
    n = len(pts)
    mesh = mesh if mesh is not None else make_seeding_mesh()
    d_ax = _mesh_size(mesh, points_axis(mesh))
    ell = float(oversample) if oversample is not None else 2.0 * k
    n_pad = _padded_for_mesh(n, mesh, tile)
    n_loc = n_pad // d_ax
    # Per-shard pick cap: points are sharded by index order, so a single
    # shard can own nearly all the D^2 mass and draw ~ell picks in one
    # round.  2*ell covers that worst case (global expected picks per round
    # is <= ell) instead of assuming a uniform ell/D split.
    cap_loc = int(min(n_loc, max(8, 2 * ell)))
    pp = _pad_axis(jnp.asarray(pts, jnp.float32), 0, n_pad)
    bits = jax.random.key_data(jax.random.key(int(rng.integers(2 ** 31))))
    sel = sharded_kmeans_parallel_rounds(
        pp, ell, bits, mesh=mesh, rounds=rounds, cap_loc=cap_loc,
        n_real=n, interpret=interpret,
    )
    cand = np.flatnonzero(np.asarray(jax.block_until_ready(sel))[:n])
    idx, pool = _candidate_pool_to_centers(pts, cand, k, rng)
    return SeedingResult(
        centers=pts[idx].copy(),
        indices=idx,
        seconds=time.perf_counter() - t0,
        num_candidates=pool,
        extras={"backend": "sharded", "devices": mesh.devices.size,
                "pool_size": pool, "rounds": rounds, "oversample": ell},
    )


SHARDED_SEEDERS = {
    "fastkmeans++": sharded_fast_kmeanspp_seeder,
    "rejection": sharded_rejection_seeder,
    "kmeans||": sharded_kmeans_parallel_seeder,
}


# ---------------------------------------------------------------------------
# Cached prepare/solve split for `core.plan.ClusterPlan` (typed registry).
# Same rng-draw contract as the device adapters: prepare consumes exactly
# the draws the composed legacy seeder makes before its program key; solve
# draws the key (and any post-program host draws).  The mesh/tile come from
# the plan's resolved execution context, so the padded artifacts — and the
# lru-cached shard_map programs keyed on them — are reused across fits.
#
# The padded artifacts are placed on the mesh with the exact shardings the
# programs' `in_specs` expect (`_place` below; the rejection seeder builds
# and places them shard by shard, `prepare_rejection_sharded`), so the
# cross-chip scatter happens once at prepare time and every solve starts
# from correctly-placed buffers instead of re-laying them out per fit.
# Donation is intentionally NOT applied to these buffers: they are the
# prepare cache — refit/fit_batch reuse them — and donating a cached
# buffer would poison every later solve.  The one-shot stacked path in
# `device_seeding` (which donates fresh per-call stacked blocks) is the
# donation-friendly surface; see docs/api.md §Donation.
# ---------------------------------------------------------------------------

def _place(x, mesh, spec):
    """Pre-place one prepared artifact with a program-input sharding."""
    from jax.sharding import NamedSharding

    return jax.device_put(x, NamedSharding(mesh, spec))


def _prep_fastkmeanspp_sh(pts, rng, *, resolution, options, execution):
    lo, hi, meta = prepare_embedding(pts, seed=int(rng.integers(2 ** 31)),
                                     resolution=resolution)
    n_pad = _padded_for_mesh(len(pts), execution.mesh, execution.tile)
    axis = points_axis(execution.mesh, n_pad)
    codes_spec = P(None, None, axis)
    return (_place(_pad_axis(lo, 2, n_pad), execution.mesh, codes_spec),
            _place(_pad_axis(hi, 2, n_pad), execution.mesh, codes_spec),
            meta, len(pts))


def _solve_fastkmeanspp_sh(artifacts, pts, k, rng, *, c, schedule, options,
                           execution):
    lo, hi, meta, n = artifacts
    bits = jax.random.key_data(jax.random.key(int(rng.integers(2 ** 31))))
    chosen = sharded_fast_kmeanspp(
        lo, hi, k, bits, mesh=execution.mesh,
        scale=meta["scale"], num_levels=meta["num_levels"],
        m_init=meta["m_init"], n_real=n, tile=execution.tile,
        interpret=execution.interpret,
    )
    return chosen, {"num_candidates": k,
                    "devices": execution.mesh.devices.size}


def _prep_rejection_sh(pts, rng, *, resolution, options, execution):
    data = prepare_rejection_sharded(
        pts, mesh=execution.mesh, tile=execution.tile,
        seed=int(rng.integers(2 ** 31)), resolution=resolution,
        lsh_r=options.get("lsh_r"),
        num_tables=options.get("num_tables", 15),
        hashes_per_table=options.get("hashes_per_table", 1),
    )
    return data, len(pts)


def _solve_rejection_sh(artifacts, pts, k, rng, *, c, schedule, options,
                        execution):
    data, n = artifacts
    sched = resolve_schedule(schedule, options.get("batch"))
    bits = jax.random.key_data(jax.random.key(int(rng.integers(2 ** 31))))
    chosen, trials = sharded_rejection_sampling(
        data.codes_lo, data.codes_hi, data.points,
        data.keys_lo, data.keys_hi, k, bits, mesh=execution.mesh,
        scale=data.scale, num_levels=data.num_levels, m_init=data.m_init,
        n_real=n, c=c, schedule=sched,
        max_rounds=options.get("max_rounds", 32), tile=execution.tile,
        interpret=execution.interpret,
    )
    return chosen, {"trials": trials, "batch_buckets": sched.buckets(),
                    "devices": execution.mesh.devices.size}


def _prep_kmeans_parallel_sh(pts, rng, *, resolution, options, execution):
    n_pad = _padded_for_mesh(len(pts), execution.mesh, execution.tile)
    pp = _place(_pad_axis(jnp.asarray(pts, jnp.float32), 0, n_pad),
                execution.mesh, P(points_axis(execution.mesh, n_pad), None))
    return pp, len(pts)


def _solve_kmeans_parallel_sh(artifacts, pts, k, rng, *, c, schedule,
                              options, execution):
    from repro.core.seeding import _candidate_pool_to_centers

    pp, n = artifacts
    mesh = execution.mesh
    d_ax = _mesh_size(mesh, points_axis(mesh))
    oversample = options.get("oversample")
    ell = float(oversample) if oversample is not None else 2.0 * k
    n_loc = pp.shape[0] // d_ax
    cap_loc = int(min(n_loc, max(8, 2 * ell)))
    bits = jax.random.key_data(jax.random.key(int(rng.integers(2 ** 31))))
    sel = sharded_kmeans_parallel_rounds(
        pp, ell, bits, mesh=mesh, rounds=options.get("rounds", 5),
        cap_loc=cap_loc, n_real=n, interpret=execution.interpret,
    )
    cand = np.flatnonzero(np.asarray(jax.block_until_ready(sel))[:n])
    idx, pool = _candidate_pool_to_centers(pts, cand, k, rng)
    return idx, {"pool_size": pool, "num_candidates": pool,
                 "devices": mesh.devices.size}


def _register():
    from repro.core import registry, seeding

    impls = {
        "fastkmeans++": registry.BackendImpl(
            run=sharded_fast_kmeanspp_seeder, device_native=True,
            prepare=_prep_fastkmeanspp_sh, solve=_solve_fastkmeanspp_sh),
        "rejection": registry.BackendImpl(
            run=sharded_rejection_seeder, device_native=True,
            prepare=_prep_rejection_sh, solve=_solve_rejection_sh),
        # host-side weighted recluster per fit => not device_native
        "kmeans||": registry.BackendImpl(
            run=sharded_kmeans_parallel_seeder, device_native=False,
            prepare=_prep_kmeans_parallel_sh,
            solve=_solve_kmeans_parallel_sh),
    }
    for name, impl in impls.items():
        registry.register_backend(name, "sharded", impl,
                                  legacy_registry=seeding.SEEDERS)


_register()
