"""The sharded rejection seeder's prepare, shard by shard, and its finish on
the shards.

Prepare computes the whole-set statistics (diameter bound, origin, LSH
width) and every random draw first, then encodes each shard of the points
axis on the host in row blocks and puts it on its own devices; the plan
gathers each seeding's centers from float32 rows placed the same way and
reduces its cost inside the mesh.  The blocked encoding must be bit for
bit the whole-set one.  The four-device cases run in a process of their
own (`_sharded_prepare_run.py`; the device count is fixed when JAX
starts).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import ClusterPlan, ClusterSpec, ExecutionSpec
from repro.core import sharded_seeding as ss
from repro.core import tree_embedding as te
from repro.core.plan import data_fingerprint
from repro.core.preprocess import quantize
from repro.kernels.ops import split_codes_u64

HERE = Path(__file__).resolve().parent


def _mixture(n, d, seed):
    rng = np.random.default_rng(seed)
    ctr = rng.normal(size=(9, d)) * 20.0
    return ctr[rng.integers(9, size=n)] + rng.normal(size=(n, d))


@pytest.mark.parametrize("n, block, chunk", [(3001, 512, 1000),
                                             (5003, 1024, 4096),
                                             (700, 8192, 1 << 20)])
def test_blocked_prepare_is_the_whole_set_prepare(monkeypatch, n, block,
                                                  chunk):
    """One device: the sharded prepare in blocks of `block` rows (several
    on a thread pool; one on this thread), its codes hashed on the device
    `chunk` rows at a time (the last chunk overlapping the one before
    where `chunk` does not divide the padded rows), gives the codes, keys, statics and post-prepare rng state of the
    device backend's whole-set prepare, bit for bit, with zero padding
    after the last row."""
    monkeypatch.setattr(ss, "PREPARE_BLOCK_ROWS", block)
    monkeypatch.setattr(ss, "HASH_CHUNK_ROWS", chunk)
    pts = _mixture(n, 12, seed=n)
    spec = ClusterSpec(k=8, seeder="rejection", seed=3)
    sh = ClusterPlan(spec, ExecutionSpec(backend="sharded")).prepare_data(
        pts)
    dev = ClusterPlan(spec, ExecutionSpec(backend="device")).prepare_data(
        pts)
    data, n_real = sh.artifacts
    ref = dev.artifacts
    assert n_real == n and sh.rng_state == dev.rng_state
    for name in ("codes_lo", "codes_hi", "keys_lo", "keys_hi"):
        got = np.asarray(getattr(data, name))
        np.testing.assert_array_equal(got[..., :n],
                                      np.asarray(getattr(ref, name)))
        assert not got[..., n:].any()
    planes = np.asarray(data.points)
    assert planes.shape[0] == 16
    np.testing.assert_array_equal(planes[:12, :n], np.asarray(ref.points).T)
    assert not planes[12:].any() and not planes[:, n:].any()
    assert (data.scale, data.num_levels, data.m_init) == (
        ref.scale, ref.num_levels, ref.m_init)


@pytest.mark.parametrize("resolution, dtype", [(1e3, np.uint8),
                                               (1.0, np.uint16),
                                               (1e-3, np.uint32)])
def test_device_hash_is_the_numpy_hash(resolution, dtype):
    """The jnp twin of the trees' hash, in emulated uint64 on the device,
    gives the NumPy codes bit for bit from cells of every width."""
    pts = _mixture(777, 9, seed=5) * 50.0
    trees = te.grid_trees(*te.set_bounds(pts), seed=2,
                          resolution=resolution)
    assert trees.cell_dtype == dtype

    def device_hash(cells, mults, rows):
        with jax.enable_x64(True):
            return jax.jit(te.hash_level_planes, static_argnums=3)(
                cells, np.asarray(mults), rows, trees.num_levels)

    for t in range(3):
        lo, hi = split_codes_u64(trees.tree_codes(pts, t)[1:])
        got = device_hash(trees.cells(pts, t), trees.mults[t], len(pts))
        np.testing.assert_array_equal(np.asarray(got[0]), lo)
        np.testing.assert_array_equal(np.asarray(got[1]), hi)
    # Only the first `rows` cells are hashed; the codes after them are 0.
    got = device_hash(trees.cells(pts, 0), trees.mults[0], 500)
    lo, _ = split_codes_u64(trees.tree_codes(pts[:500], 0)[1:])
    np.testing.assert_array_equal(np.asarray(got[0])[:, :500], lo)
    assert not np.asarray(got[0])[:, 500:].any()
    assert not np.asarray(got[1])[:, 500:].any()


@pytest.mark.parametrize("rows", [1, 7, 100])
def test_set_bounds_in_blocks_is_the_whole_set_formula(monkeypatch, rows):
    """The diameter bound and origin from blocks of rows equal the
    whole-set expressions they replace, whatever the block."""
    monkeypatch.setattr(te, "BOUNDS_ROWS", rows)
    pts = _mixture(523, 7, seed=rows)
    far = np.sqrt(np.maximum(((pts - pts[0]) ** 2).sum(axis=1), 0.0)).max()
    max_dist, origin = te.set_bounds(pts)
    assert max_dist == float(2.0 * far)
    np.testing.assert_array_equal(origin, pts.min(axis=0))
    assert te.set_bounds(np.zeros((5, 3)))[0] == 1.0


def test_quantize_floors_in_place_as_before():
    pts = _mixture(2000, 6, seed=1)
    q = quantize(pts, np.random.default_rng(4))
    np.testing.assert_array_equal(q.points, np.floor(pts / q.scaling))


@pytest.mark.parametrize("n", [65536, 2 * 65536 + 17])
def test_quantize_in_blocks_is_the_whole_set_quantize(n):
    """Blocks of `QUANTIZE_BLOCK_ROWS` rows on a thread pool give the
    estimate, scale, grid and rng state of the whole set in one piece."""
    from repro.core.lloyd import assign

    pts = _mixture(n, 5, seed=n)
    rng, ref_rng = np.random.default_rng(9), np.random.default_rng(9)
    q = quantize(pts, rng)
    idx = ref_rng.choice(n, size=20, replace=False)
    est = float(assign(pts, pts[idx])[1].sum())
    scaling = np.sqrt(est / (n * 5)) / 200.0
    assert (q.estimate, q.scaling) == (est, scaling)
    np.testing.assert_array_equal(q.points, np.floor(pts / scaling))
    assert rng.bit_generator.state == ref_rng.bit_generator.state


def test_chunked_fingerprint_keys_the_content(monkeypatch):
    """Above the chunk size a host array hashes in row chunks: equal
    content, equal key; one changed element anywhere, another key."""
    pts = _mixture(4000, 8, seed=2)
    small = data_fingerprint(pts)
    monkeypatch.setattr("repro.core.plan._CHUNK_HASH_BYTES", 1 << 12)
    key = data_fingerprint(pts)
    assert key == data_fingerprint(pts.copy()) and key != small
    for i in (0, 1999, 3999):
        other = pts.copy()
        other[i, 5] += 1e-9
        assert data_fingerprint(other) != key


# -- four devices ------------------------------------------------------------

@pytest.fixture(scope="module")
def four():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(HERE.parent / "src"),
                    os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run(
        [sys.executable, str(HERE / "_sharded_prepare_run.py")],
        cwd=HERE.parent, env=env, capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_four_devices_prepare_is_the_whole_set_prepare(four):
    assert four["devices"] == 4
    assert all(four["same_as_whole_set"].values()), four["same_as_whole_set"]


def test_each_device_holds_only_its_own_shard(four):
    """Every artifact and the placed rows: one shard of a quarter of the
    padded columns on each of the four devices; no device array alive
    after prepare and eight seedings has a shard as long as the set."""
    n = four["n"]
    for name, shards in four["shards"].items():
        assert len({dev for dev, _ in shards}) == 4, name
        widths = {shape[-1] for _, shape in shards}
        assert len(widths) == 1 and 4 * widths.pop() >= n, name
    assert len({dev for dev, _ in four["rows_shards"]}) == 4
    assert four["largest_shard_dim"] < n


def test_refit_answers_are_distinct_float32_rows(four):
    assert set(four["distinct"]) == {24}
    assert all(four["centers_are_rows"])


def test_sharded_cost_is_the_float64_cost_and_skips_pad_rows(four):
    """The cost reduced in the mesh against the float64 cost of the same
    indices.  It is the expanded float32 form |x|^2 - 2 x.c + |c|^2, whose
    rounding is about 2^-24 of |x|^2 + |c|^2 a point; here |x|^2 is some
    hundreds of times a point's distance to its nearest center, so the
    relative error is ~1e-5 (8.7e-6 read), and 1e-4 leaves room.  A
    nonzero value planted in every pad column changes nothing: pad rows
    are masked out before the sum."""
    for reported, exact in four["cost"]:
        assert abs(reported - exact) <= 1e-4 * exact
    assert four["pad_columns"] > 0
    plain, planted = four["cost_planted"]
    assert plain == planted


def test_sharded_seeding_stays_in_the_kmeanspp_band(four):
    """Over four devices the seeding keeps the law and the quality of the
    single-device programs: mean cost over 8 seeds within 1.25x that of
    plain k-means++ (the band `test_kmeans_parallel` holds k-means|| to;
    1.003x read), and the first and second centers pass
    `test_conformance`'s chi-square and total-variation gates."""
    costs = [exact for _, exact in four["cost"]]
    assert np.mean(costs) < 1.25 * np.mean(four["kmeanspp_cost"])
    conf = four["conformance"]
    for i in (0, 1):
        assert conf[f"center{i}"]["chi2"] < conf["crit"], conf
    assert conf["center1"]["tv"] < conf["tv_bound"], conf
