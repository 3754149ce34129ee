"""Sharded (shard_map) seeders vs the single-device programs.

Runs on however many local devices exist: 1 in a plain CPU session (the
mesh degenerates to one shard but the full collective code path still
executes), 4 under the CI step that forces
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import KMeansConfig, SEEDERS, clustering_cost, fit, resolve_seeder
from repro.core.sample_tree import TiledSampleTree
from repro.core.sharded_seeding import SHARDED_SEEDERS, _shard_sampler
from repro.launch.mesh import make_seeding_mesh


def _mixture(n=1200, d=5, k_true=12, spread=40.0, seed=0):
    rng = np.random.default_rng(seed)
    ctr = rng.normal(size=(k_true, d)) * spread
    return ctr[rng.integers(k_true, size=n)] + rng.normal(size=(n, d))


def test_registration_and_facade():
    assert resolve_seeder("rejection", "sharded") is SEEDERS["rejection/sharded"]
    assert resolve_seeder("fastkmeans++", "sharded") is SEEDERS["fastkmeans++/sharded"]
    with pytest.raises(KeyError):
        resolve_seeder("kmeans++", "sharded")
    pts = _mixture(n=600, d=4, k_true=8, seed=1)
    km = fit(pts, KMeansConfig(k=10, seeder="rejection", backend="sharded"))
    assert km.centers.shape == (10, 4)
    assert km.seeding.extras["backend"] == "sharded"
    assert km.seeding.extras["devices"] == len(jax.devices())
    assert len(np.unique(km.seeding.indices)) == 10


def test_shard_sampler_distribution():
    """Shard-then-descend MULTITREESAMPLE draws each point with probability
    w_x / total across ALL shards (exactness of the top-tree + local
    descent factorisation)."""
    from jax.sharding import PartitionSpec as P

    mesh = make_seeding_mesh()
    d_ax = mesh.devices.size
    tile = 32
    n = d_ax * tile * 4                      # 4 tiles per shard
    n_loc = n // d_ax
    rng = np.random.default_rng(2)
    w = rng.uniform(0, 2, size=n).astype(np.float32)
    w[rng.choice(n, n // 5, replace=False)] = 0.0
    ts_loc = TiledSampleTree(n_loc, tile=tile)
    m = 120_000

    def prog(w_loc, bits):
        sampler = _shard_sampler(ts_loc, "data")
        coarse = ts_loc.init(w_loc)
        idx, _, _ = sampler(coarse, w_loc, jax.random.wrap_key_data(bits), m)
        return idx

    fn = jax.jit(jax.shard_map(
        prog, mesh=mesh, in_specs=(P("data"), P()), out_specs=P(),
        check_vma=False,
    ))
    bits = jax.random.key_data(jax.random.key(0))
    draws = np.asarray(fn(jnp.asarray(w), bits))
    freq = np.bincount(draws, minlength=n) / m
    p = w / w.sum()
    assert (freq[w == 0.0] == 0.0).all()
    np.testing.assert_allclose(freq, p, atol=0.01)


@pytest.mark.parametrize("algo", ["fastkmeans++", "rejection"])
def test_sharded_matches_single_device_cost(algo):
    """Acceptance: the sharded seeder's clustering cost matches the
    single-device device program within 5% (means over paired seeds, with
    k = 3x the true cluster count so every cluster is covered and the
    per-seed costs concentrate to a few percent)."""
    pts = _mixture(n=2000, d=5, k_true=12, seed=6)
    k = 36
    dev_costs, sh_costs = [], []
    for s in range(8):
        dev = SEEDERS[f"{algo}/device"](pts, k, np.random.default_rng(s))
        sh = SEEDERS[f"{algo}/sharded"](pts, k, np.random.default_rng(s))
        assert len(np.unique(sh.indices)) == k
        dev_costs.append(clustering_cost(pts, pts[dev.indices]))
        sh_costs.append(clustering_cost(pts, pts[sh.indices]))
    dev_mean = np.mean(dev_costs)
    sh_mean = np.mean(sh_costs)
    assert abs(sh_mean / dev_mean - 1.0) < 0.05, (dev_mean, sh_mean)


def test_repeated_fit_hits_program_cache():
    """Serving contract: repeated `fit(..., backend="sharded")` calls with
    identical static args reuse the cached jit program — no re-trace.
    `TRACE_COUNTS` is incremented inside the shard_map program bodies, which
    only run while jax traces them, so it counts traces, not calls."""
    from repro.core import sharded_seeding as ss

    pts = _mixture(n=640, d=4, k_true=8, seed=11)
    cfg = KMeansConfig(k=8, seeder="rejection", backend="sharded")
    fit(pts, cfg)                      # builds + traces (or reuses) once
    traces_before = dict(ss.TRACE_COUNTS)
    hits_before = ss.program_cache_info()["rejection"].hits
    km = fit(pts, cfg)                 # identical static args
    assert dict(ss.TRACE_COUNTS) == traces_before, "sharded fit re-traced"
    assert ss.program_cache_info()["rejection"].hits > hits_before
    assert km.centers.shape == (8, 4)
    # A different static configuration still (re)builds its own program.
    fit(pts, KMeansConfig(k=9, seeder="rejection", backend="sharded"))
    assert ss.TRACE_COUNTS["rejection"] == traces_before["rejection"] + 1


def test_sharded_rejection_trials_contract():
    pts = _mixture(n=900, d=4, k_true=10, seed=9)
    res = SHARDED_SEEDERS["rejection"](pts, 12, np.random.default_rng(3))
    assert res.indices.shape == (12,)
    assert res.num_candidates >= 12
    assert res.extras["per_center_trials"].shape == (12,)
    assert res.extras["trials_per_center"] >= 1.0
