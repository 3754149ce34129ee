"""Property tests for the streaming mutation path (ISSUE 10).

The laws under test, per backend with streaming support:

* **Scratch equivalence** — ``prepare_streaming(A); extend(B)`` is
  bit-identical to ``prepare_streaming(A + B)`` when B's rows duplicate
  rows of A: duplicates leave the data extent unchanged, so both streams
  freeze the same pow2 scale/origin and (from the same spec seed) the
  same trees and LSH tables — identical artifacts, identical seeded
  draws.  (A *general* B only preserves the sampling *law*, not the
  draw stream — the extended stream keeps its frozen geometry while a
  scratch prepare re-derives it; that case is covered statistically by
  the streaming section of ``tests/test_conformance.py`` and documented
  in ``docs/streaming.md``.)
* **Retire round-trip** — extend-then-retire of the same rows restores
  the sample-tree leaf weights ``w0`` and coarse heap ``base_heap``
  bit-exactly (retire patches weights to exactly 0.0; it never rescales
  surviving mass).
* **Release** — `forget()` on an extended stream drops the cache entry
  under its *mutated* key (the generation re-key is what makes this
  work) and clears the plan's active slot.
* **Cache generations** — after a mutation the old fingerprint key is
  gone, the handle lives under exactly one ``#g<generation>`` key, and
  a fresh `prepare_data` of the original points is a new build, never a
  hit on the mutated stream.
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import ClusterPlan, ClusterSpec, ExecutionSpec

D = 3
OPTIONS = {"lsh_r": 1e6, "resolution": 0.05}


def _spec(k: int = 2, seeder: str = "rejection") -> ClusterSpec:
    return ClusterSpec(k=k, seeder=seeder, c=1.2, quantize=False, seed=0,
                       options=OPTIONS)


def _plan(backend: str, **spec_kw) -> ClusterPlan:
    extra = {"tile": 32} if backend == "sharded" else {}
    return ClusterPlan(_spec(**spec_kw), ExecutionSpec(backend=backend,
                                                       **extra))


def _points(seed: int, n: int) -> np.ndarray:
    return np.random.default_rng(seed).normal(size=(n, D)) * 3.0


# -- scratch equivalence -----------------------------------------------------

@settings(max_examples=5, deadline=None)
@given(st.integers(0, 2), st.integers(8, 32), st.integers(1, 12),
       st.integers(0, 10_000))
def test_extend_duplicates_matches_scratch(backend_i, n_a, n_b, seed):
    """prepare_streaming(A); extend(B) == prepare_streaming(A+B) when B
    duplicates rows of A — same frozen geometry, same artifacts, and the
    same seeded draw stream."""
    backend = ["cpu", "device", "sharded"][backend_i]
    pts_a = _points(seed, n_a)
    dup = np.random.default_rng(seed + 1).integers(0, n_a, size=n_b)
    pts_b = pts_a[dup]

    plan = _plan(backend)
    inc = plan.prepare_streaming(pts_a)
    plan.extend(pts_b, prepared=inc)
    scratch = plan.prepare_streaming(np.concatenate([pts_a, pts_b]))

    si, ss = inc.streaming, scratch.streaming
    assert si.scale == ss.scale
    assert si.capacity == ss.capacity
    assert si.n_rows == ss.n_rows == n_a + n_b
    np.testing.assert_array_equal(si.live, ss.live)
    np.testing.assert_array_equal(si.host_scaled, ss.host_scaled)
    if backend == "device":
        assert si.rebuilds == 0        # duplicates never leave the domain
        np.testing.assert_array_equal(np.asarray(si.w0), np.asarray(ss.w0))
        np.testing.assert_array_equal(np.asarray(si.base_heap),
                                      np.asarray(ss.base_heap))
        np.testing.assert_array_equal(np.asarray(si.codes_lo),
                                      np.asarray(ss.codes_lo))
        np.testing.assert_array_equal(np.asarray(si.keys_lo),
                                      np.asarray(ss.keys_lo))
    ri = plan.fit_prepared(inc, seed=seed + 7)
    rs = plan.fit_prepared(scratch, seed=seed + 7)
    if backend == "sharded":
        # Documented fallback: the re-shard after extend rebuilds its
        # artifacts with a generation-keyed rng, so only the *law* (not
        # the draw stream) matches a scratch prepare — covered by the
        # streaming conformance suite.  Here: both draws live, and the
        # mutated stream flagged its re-shard.
        assert ri.extras.get("resharded") is True
        live = si.live_ids()
        assert np.isin(np.asarray(ri.indices), live).all()
        assert np.isin(np.asarray(rs.indices), live).all()
    else:
        np.testing.assert_array_equal(np.asarray(ri.indices),
                                      np.asarray(rs.indices))
        np.testing.assert_allclose(float(ri.cost), float(rs.cost),
                                   rtol=1e-6, atol=0.0)
    plan.forget(inc)
    plan.forget(scratch)


# -- retire round-trip -------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(st.integers(4, 48), st.integers(1, 24), st.integers(0, 10_000))
def test_extend_then_retire_roundtrips_weights(n_a, n_b, seed):
    """Extend-then-retire of the same rows restores `w0`/`base_heap`
    bit-exactly on the device backend (weights patch to exactly 0.0)."""
    plan = _plan("device")
    prep = plan.prepare_streaming(_points(seed, n_a))
    state = prep.streaming
    w0_before = np.asarray(state.w0).copy()
    heap_before = np.asarray(state.base_heap).copy()

    plan.extend(_points(seed + 1, n_b), prepared=prep)
    plan.retire(np.arange(n_a, n_a + n_b), prepared=prep)

    assert state.live_count == n_a
    np.testing.assert_array_equal(np.asarray(state.w0), w0_before)
    np.testing.assert_array_equal(np.asarray(state.base_heap), heap_before)
    plan.forget(prep)


def test_retire_validates_ids():
    plan = _plan("cpu")
    prep = plan.prepare_streaming(_points(0, 16))
    with pytest.raises(IndexError):
        plan.retire([16], prepared=prep)
    plan.retire([3], prepared=prep)
    with pytest.raises(ValueError):
        plan.retire([3], prepared=prep)        # already retired
    plan.forget(prep)


# -- release -----------------------------------------------------------------

@pytest.mark.parametrize("backend", ["cpu", "device"])
def test_forget_releases_extended_stream(backend):
    plan = _plan(backend)
    prep = plan.prepare_streaming(_points(0, 24))
    plan.extend(_points(1, 8), prepared=prep)
    assert prep.fingerprint in plan._prepared
    assert plan.forget(prep) is True
    assert prep.fingerprint not in plan._prepared
    assert not plan._prepared                  # nothing else retained
    assert plan.forget(prep) is False          # idempotent


# -- cache generations (the ISSUE-10 latent-cache fix) -----------------------

def test_mutation_rekeys_cache_entry():
    """After extend/retire the entry moves from its stale content key to
    exactly one ``#g<generation>`` key; the handle's fingerprint tracks."""
    plan = _plan("cpu")
    pts = _points(0, 24)
    prep = plan.prepare_streaming(pts)
    key0 = prep.fingerprint
    assert "#g0" in key0

    plan.extend(_points(1, 8), prepared=prep)
    assert key0 not in plan._prepared
    assert prep.fingerprint.endswith(f"#g{prep.streaming.generation}")
    assert prep.generation == prep.streaming.generation == 1
    hits = [k for k, v in plan._prepared.items() if v is prep]
    assert hits == [prep.fingerprint]

    plan.retire([0], prepared=prep)
    assert prep.fingerprint.endswith("#g2")
    assert len([k for k, v in plan._prepared.items() if v is prep]) == 1
    plan.forget(prep)


def test_prepare_data_never_hits_mutated_stream():
    """A fresh `prepare_data` of the original points must be a new build —
    the mutated stream's entry can never alias a content-fingerprint hit."""
    plan = _plan("cpu")
    pts = _points(0, 24)
    prep = plan.prepare_streaming(pts)
    plan.extend(pts[:4], prepared=prep)

    builds_before = plan.stats["prepare_builds"]
    fresh = plan.prepare_data(pts)
    assert fresh is not prep
    assert fresh.streaming is None
    assert plan.stats["prepare_builds"] == builds_before + 1

    again = plan.prepare_data(pts)             # and *this* one is a hit
    assert again is fresh
    assert plan.stats["prepare_builds"] == builds_before + 1
    plan.forget(prep)
    plan.forget(fresh)


def test_refit_after_extend_draws_from_grown_stream():
    """A refit after extend sees the mutation: extras carry the bumped
    generation and indices stay inside the live set."""
    plan = _plan("device")
    prep = plan.prepare_streaming(_points(0, 24))
    res0 = plan.fit_prepared(prep, seed=3)
    assert res0.extras["generation"] == 0
    plan.extend(_points(1, 8), prepared=prep)
    plan.retire([0, 5], prepared=prep)
    res1 = plan.fit_prepared(prep, seed=3)
    assert res1.extras["streaming"] is True
    assert res1.extras["generation"] == 2
    idx = np.asarray(res1.indices)
    live = prep.streaming.live_ids()
    assert np.isin(idx, live).all()
    plan.forget(prep)


# -- engine / frontend plumbing ----------------------------------------------

def test_engine_submit_extend_refit_only_requires_handle():
    from repro.core import ClusterEngine

    eng = ClusterEngine(_spec(), ExecutionSpec(backend="cpu"))
    try:
        with pytest.raises(ValueError):
            eng.submit_extend(None)
        plan = eng.plan_for()
        prep = plan.prepare_streaming(_points(0, 24))
        t1 = eng.submit_extend(_points(1, 8), prepared=prep)
        r1 = t1.result(timeout=60)
        assert r1.extras["generation"] == 1
        t2 = eng.submit_extend(None, prepared=prep)    # refit-only
        r2 = t2.result(timeout=60)
        assert r2.extras["generation"] == 1            # no mutation
        assert eng.stats()["extends"] == 1             # refit-only not counted
    finally:
        eng.close()


def test_frontend_submit_extend_settles_ledger():
    from repro.serving.frontend import ClusterFrontend

    fe = ClusterFrontend(_spec(), ExecutionSpec(backend="cpu"))
    try:
        plan = fe.engine.plan_for()
        prep = plan.prepare_streaming(_points(0, 24))
        t = fe.submit_extend(_points(1, 8), prepared=prep)
        res = t.result(timeout=60)
        assert res.extras["streaming"] is True
        fe.flush()
        stats = fe.stats()
        assert stats["extends"] == 1
        assert stats["completed"] == 1
        assert stats["inflight"] == 0
    finally:
        fe.close()
