"""Property tests for the adaptive `BatchSchedule` (hypothesis).

The contract the device programs rely on: a proposed batch is never 0,
never exceeds the configured cap, always sits on the bucket ladder, and is
monotone non-increasing in the observed acceptance rate (more accepts =>
smaller speculative blocks).
"""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.batch_schedule import BatchSchedule

SCHEDULES = (
    BatchSchedule(),
    BatchSchedule(min_batch=8, max_batch=2048),
    BatchSchedule(min_batch=1, max_batch=7),      # ragged (non-pow2) cap
    BatchSchedule.fixed(128),
    BatchSchedule.fixed(1),
)


@settings(max_examples=40, deadline=None)
@given(st.integers(1, 4096), st.floats(0.0, 1.0))
def test_propose_never_zero_never_above_cap(prev, acc):
    for s in SCHEDULES:
        b = s.propose(prev, acc)
        assert b >= 1
        assert s.min_batch <= b <= s.max_batch
        assert b in s.buckets()


@settings(max_examples=40, deadline=None)
@given(st.integers(1, 4096), st.floats(0.0, 1.0), st.floats(0.0, 1.0))
def test_propose_monotone_in_acceptance(prev, a1, a2):
    lo, hi = min(a1, a2), max(a1, a2)
    for s in SCHEDULES:
        # Higher observed acceptance can never ask for a *larger* block.
        assert s.propose(prev, lo) >= s.propose(prev, hi)


@settings(max_examples=40, deadline=None)
@given(st.integers(1, 1 << 20), st.integers(1, 4096), st.integers(1, 4096),
       st.floats(0.001, 1.0))
def test_initial_bounds(n, k, tiles, acc):
    for s in SCHEDULES:
        for rate in (None, acc):
            b = s.initial(n, k, tiles, rate)
            assert 1 <= b <= s.max_batch
            assert b >= s.min_batch
            assert b in s.buckets()


@settings(max_examples=30, deadline=None)
@given(st.floats(0.0, 1.0), st.floats(0.0, 1.0))
def test_traced_index_monotone_and_geometric(a1, a2):
    """The jit-side twin: target_index is monotone non-increasing in the
    acceptance rate and next_index moves at most one ladder rung."""
    s = BatchSchedule()
    lo, hi = min(a1, a2), max(a1, a2)
    assert int(s.target_index(lo)) >= int(s.target_index(hi))
    n_b = len(s.buckets())
    for idx in range(n_b):
        nxt = int(s.next_index(np.int32(idx), np.float32(a1)))
        assert 0 <= nxt < n_b
        assert abs(nxt - idx) <= 1


def test_fixed_schedule_is_one_bucket():
    s = BatchSchedule.fixed(128)
    assert s.buckets() == (128,)
    for acc in (0.0, 0.5, 1.0):
        assert s.propose(128, acc) == 128
        assert int(s.next_index(np.int32(0), np.float32(acc))) == 0
    assert s.initial(10_000, 100, 64) == 128


def test_buckets_ladder_shape():
    s = BatchSchedule(min_batch=16, max_batch=100)
    assert s.buckets() == (16, 32, 64, 100)
    assert s.index_of(1) == 0
    assert s.index_of(33) == 2
    assert s.index_of(10_000) == len(s.buckets()) - 1


def test_validation():
    with pytest.raises(ValueError):
        BatchSchedule(min_batch=0)
    with pytest.raises(ValueError):
        BatchSchedule(min_batch=64, max_batch=32)
    with pytest.raises(ValueError):
        BatchSchedule(ema=0.0)
    with pytest.raises(ValueError):
        BatchSchedule(safety=-1.0)


def test_ema_update_blends():
    s = BatchSchedule(ema=0.5)
    assert float(s.update_rate(0.2, 0.6)) == pytest.approx(0.4)
    s1 = BatchSchedule(ema=1.0)
    assert float(s1.update_rate(0.2, 0.6)) == pytest.approx(0.6)


def test_fit_facade_forwards_schedule():
    """`KMeansConfig.schedule` reaches the device/sharded rejection seeders
    (visible via the result extras) and a fixed one-bucket schedule pins the
    legacy block size."""
    from repro.core import KMeansConfig, fit

    rng = np.random.default_rng(0)
    ctr = rng.normal(size=(8, 4)) * 40
    pts = ctr[rng.integers(8, size=600)] + rng.normal(size=(600, 4))
    for backend in ("device", "sharded"):
        km = fit(pts, KMeansConfig(k=8, seeder="rejection", backend=backend,
                                   schedule=BatchSchedule.fixed(64)))
        assert km.seeding.extras["batch_buckets"] == (64,)
        km = fit(pts, KMeansConfig(k=8, seeder="rejection", backend=backend))
        assert km.seeding.extras["batch_buckets"] == BatchSchedule().buckets()
    # The CPU seeder honours the schedule too (its block size is dynamic,
    # so only the run contract is observable).
    km = fit(pts, KMeansConfig(k=8, seeder="rejection", backend="cpu",
                               schedule=BatchSchedule(min_batch=8,
                                                      max_batch=64)))
    assert len(np.unique(km.seeding.indices)) == 8
    assert km.seeding.num_candidates >= 8
