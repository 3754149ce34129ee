"""The sharded rejection seeder's prepare and finish on four CPU devices;
prints one JSON line of what it saw (read by tests/test_sharded_prepare.py).

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        PYTHONPATH=src python tests/_sharded_prepare_run.py

The device count is fixed when JAX starts, hence a process of its own.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import jax
import numpy as np

from repro.core import ClusterPlan, ClusterSpec, ExecutionSpec
from repro.core import sharded_seeding as ss
from repro.core.seeding import clustering_cost, kmeanspp

N, D, K, K_TRUE = 5003, 10, 24, 8       # n: neither a multiple of 4 nor of
BLOCK = 512                             # the block; several blocks a shard
CHUNK = 500                             # device hash: 4 chunks a shard, the
                                        # last overlapping the one before
SEEDS = range(8)


def mixture(n, d, k_true, seed):
    rng = np.random.default_rng(seed)
    ctr = rng.normal(size=(k_true, d)) * 30.0
    return ctr[rng.integers(k_true, size=n)] + rng.normal(size=(n, d))


def shard_widths(arr) -> list:
    """Per device: the shape of the shard it holds."""
    return sorted(([str(s.device), list(s.data.shape)]
                   for s in arr.addressable_shards), key=lambda x: x[0])


def main() -> None:
    ss.PREPARE_BLOCK_ROWS = BLOCK
    ss.HASH_CHUNK_ROWS = CHUNK
    pts = mixture(N, D, K_TRUE, 0)
    spec = ClusterSpec(k=K, seeder="rejection", seed=7)
    plan = ClusterPlan(spec, ExecutionSpec(backend="sharded"))
    prep = plan.prepare_data(pts)
    data, n = prep.artifacts
    out = {"devices": len(jax.devices()), "n": n,
           "shards": {name: shard_widths(getattr(data, name))
                      for name in ("codes_lo", "codes_hi", "points",
                                   "keys_lo", "keys_hi")}}

    answers = [plan.fit_prepared(prep, seed=s).to_numpy() for s in SEEDS]
    rows32 = pts.astype(np.float32)
    out["distinct"] = [len(np.unique(a.indices)) for a in answers]
    out["centers_are_rows"] = [bool(np.array_equal(a.centers,
                                                   rows32[a.indices]))
                               for a in answers]
    out["cost"] = [[float(a.cost), clustering_cost(pts, pts[a.indices])]
                   for a in answers]
    out["kmeanspp_cost"] = [
        clustering_cost(pts, kmeanspp(pts, K, np.random.default_rng(s))
                        .centers) for s in SEEDS]

    # A nonzero value planted in every pad column of the placed rows.
    rows = prep.points_dev
    planes = np.asarray(rows.planes).copy()
    planes[:, rows.n:] = 1.0e3
    planted = ss.PlacedRows(jax.device_put(planes, rows.planes.sharding),
                            n=rows.n, d=rows.d, block=rows.block,
                            mesh=rows.mesh)
    centers = answers[0].centers
    out["pad_columns"] = int(planes.shape[1] - rows.n)
    out["cost_planted"] = [float(rows.cost(centers)),
                           float(planted.cost(centers))]
    out["rows_shards"] = shard_widths(rows.planes)
    # Every device array alive now: no shard of any holds the whole set.
    out["largest_shard_dim"] = max(
        max(s.data.shape, default=0)
        for a in jax.live_arrays() for s in a.addressable_shards)

    # The whole-set prepare of the device backend, on the same points and
    # plan seed: the same codes, keys and statics, the same rng after.
    dev = ClusterPlan(spec, ExecutionSpec(backend="device")).prepare_data(
        pts)
    ref = dev.artifacts
    same = {}
    for name in ("codes_lo", "codes_hi", "keys_lo", "keys_hi"):
        got = np.asarray(getattr(data, name))
        same[name] = bool(np.array_equal(got[..., :N],
                                         np.asarray(getattr(ref, name)))
                          and not got[..., N:].any())
    got = np.asarray(data.points)
    same["points"] = bool(np.array_equal(
        got[:D, :N], np.asarray(ref.points).T) and not got[D:].any()
        and not got[:, N:].any())
    same["statics"] = [data.scale, data.num_levels, data.m_init] == [
        ref.scale, ref.num_levels, ref.m_init]
    same["rng_state"] = prep.rng_state == dev.rng_state
    out["same_as_whole_set"] = same
    out["conformance"] = conformance()
    print(json.dumps(out))


def conformance() -> dict:
    """`tests/test_conformance.py`'s first- and second-center law checks
    for the sharded backend, over four devices: the statistics and the
    thresholds its tests compare them with."""
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from tests import test_conformance as tc

    draws = tc._draws("sharded")
    crit = tc._chi2_isf(tc.ALPHA / tc.N_TESTS, tc.BINS - 1)
    out = {"crit": crit, "tv_bound": tc.TV_BOUND}
    for i, law in enumerate(tc._exact_laws(tc._fixture())):
        bins = tc._mass_balanced_bins(law, tc.BINS)
        counts = tc._binned(np.bincount(draws[:, i], minlength=tc.N)
                            .astype(float), bins, tc.BINS)
        expected = tc._binned(law, bins, tc.BINS) * tc.R
        out[f"center{i}"] = {
            "chi2": tc._chi2_stat(counts, expected),
            "tv": 0.5 * float(np.abs(counts - expected).sum()) / tc.R}
    return out


if __name__ == "__main__":
    main()
