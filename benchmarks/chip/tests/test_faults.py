"""A run with the timed path broken underneath comes out not correct.

Each test drives the rest of a run on the CPU (the look for a chip is
skipped, the kernels run interpreted) at a tiny size, with one fault
planted in the program the window calls:

* an answer altered where it is produced: one index of a seeding moved
  to another row, or the reported cost taken from another seeding;
* half of the batch left out: the second half of a stacked result's rows
  copies of the first half's (a batch of seeds on one set, or a served
  lane of different sets);
* the members of a served lane handed one another's rows;
* an answer that never comes: the served lane fails.

The cells have no exchange between chips and no state a step carries,
so those faults do not apply.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from _tiny import make_copy, run

from repro.core.plan import ClusterPlan


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return make_copy(tmp_path_factory.mktemp("bench"))


def _reorder(res, order):
    """The stacked result's rows taken in `order`."""
    order = jnp.asarray(order)
    res.indices, res.centers, res.cost = (res.indices[order],
                                          res.centers[order], res.cost[order])
    return res


def test_sound_runs_are_correct(copy):
    for workload in ("tiny.reseed", "tiny.tiny_batch", "tiny.tiny_served"):
        res = run(copy, workload)
        assert res["correct"], (workload, res["checks"])
        assert res["failed"] == 0 and res["attempted"] > 0


def test_altered_answer_is_caught(copy, monkeypatch):
    refit = ClusterPlan.refit

    def altered(self, **kw):
        res = refit(self, **kw)
        free = np.setdiff1d(np.arange(2048), np.asarray(res.indices))
        res.indices = res.indices.at[1].set(int(free[0]))
        return res

    monkeypatch.setattr(ClusterPlan, "refit", altered)
    res = run(copy, "tiny.reseed")
    assert not res["correct"]
    assert res["checks"]["bad_answers"]["value"] > 0


def test_cost_of_another_seeding_is_caught(copy, monkeypatch):
    refit = ClusterPlan.refit
    last = []

    def stale(self, **kw):
        res = refit(self, **kw)
        cost = res.cost
        if last:
            res.cost = last[0]
        last[:] = [cost]
        return res

    monkeypatch.setattr(ClusterPlan, "refit", stale)
    res = run(copy, "tiny.reseed")
    assert not res["correct"]
    gap = res["checks"]["cost_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("workload,method", [
    ("tiny.tiny_batch", "fit_batch"),
    ("tiny.tiny_served", "fit_batch_prepared")])
def test_half_the_batch_left_out_is_caught(copy, monkeypatch, workload,
                                           method):
    solve = getattr(ClusterPlan, method)

    def halved(self, *args, **kw):
        res = solve(self, *args, **kw)
        b = res.indices.shape[0]
        return _reorder(res, [i % max(1, b // 2) for i in range(b)])

    monkeypatch.setattr(ClusterPlan, method, halved)
    res = run(copy, workload)
    assert not res["correct"]
    assert res["checks"]["bad_answers"]["value"] > 0


def test_lanes_mixed_up_are_caught(copy, monkeypatch):
    solve = ClusterPlan.fit_batch_prepared

    def mixed(self, prepared, *, seeds=None):
        res = solve(self, prepared, seeds=seeds)
        b = res.indices.shape[0]
        return _reorder(res, [(i + 1) % b for i in range(b)])

    monkeypatch.setattr(ClusterPlan, "fit_batch_prepared", mixed)
    res = run(copy, "tiny.tiny_served")
    assert not res["correct"]
    assert res["checks"]["bad_answers"]["value"] > 0


def test_answer_that_never_comes_is_caught(copy, monkeypatch):
    solve = ClusterPlan.fit_batch_prepared
    calls = {"n": 0}

    def failing(self, prepared, *, seeds=None):
        calls["n"] += 1
        if calls["n"] > 4:                 # set-up's lanes pass, then fail
            raise ValueError("planted fault: lane lost")
        return solve(self, prepared, seeds=seeds)

    monkeypatch.setattr(ClusterPlan, "fit_batch_prepared", failing)
    res = run(copy, "tiny.tiny_served")
    assert not res["correct"]
    assert res["failed"] > 0
