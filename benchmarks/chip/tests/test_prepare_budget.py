"""The `reseed_budget` traffic kind: re-seeding as `reseed` does, with set-up
failing once the prepare has run past the mix's `prepare_budget_s`."""

from __future__ import annotations

import json
import time

import pytest

from _tiny import TINY, make_copy, run

from registry import Registry


def _budget_mod():
    return Registry().module("traffic", "reseed_budget")


def test_budget_raises_once_spent_while_the_work_runs_on():
    mod = _budget_mod()
    t0 = time.perf_counter()
    with pytest.raises(mod.PrepareBudgetExceeded):
        mod.within_budget(lambda: time.sleep(5.0), 0.05)
    assert time.perf_counter() - t0 < 1.0     # not held by the work
    assert mod.within_budget(lambda: 7, 5.0) == 7
    with pytest.raises(ZeroDivisionError):
        mod.within_budget(lambda: 1 / 0, 5.0)


@pytest.mark.parametrize("n", [1, 2 * 32768 + 5])
def test_mixture_is_the_seeds_whatever_the_threads(monkeypatch, n):
    """The same seed gives the same rows on one thread or many, of
    `data.mixture`'s shape and dtype; another seed gives other rows."""
    import numpy as np

    mod = _budget_mod()
    many = mod.mixture(n, 6, 40, 3000001601)
    monkeypatch.setattr(mod.os, "cpu_count", lambda: 1)
    one = mod.mixture(n, 6, 40, 3000001601)
    assert many.shape == (n, 6) and many.dtype == np.float64
    np.testing.assert_array_equal(many, one)
    assert not np.array_equal(mod.mixture(n, 6, 40, 3000001602), one)
    np.testing.assert_array_equal(mod.as_float32(many),
                                  many.astype(np.float32))


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    dst = make_copy(tmp_path_factory.mktemp("bench"))
    root = dst / "benchmarks" / "chip"
    spec = json.loads((dst / "BENCHMARK.json").read_text())
    limits = (root / "cells" / "tiny.reseed.json").read_text()
    mix = json.loads((root / "traffic" / "reseed_budget.json").read_text())
    for name, seconds in (("tiny_budget", 300.0), ("tiny_overrun", 1e-3)):
        (root / "traffic" / f"{name}.json").write_text(json.dumps(
            dict(mix, cost_sample=4, reference_seeds=2,
                 prepare_budget_s=seconds)))
        cell = f"{TINY['name']}.{name}"
        spec["workloads"].append({"name": cell, "config": TINY["name"],
                                  "traffic": name, "chips": 1,
                                  "why": "CPU tests"})
        (root / "cells" / f"{cell}.json").write_text(limits)
    (dst / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return dst


def test_a_prepare_within_its_budget_runs_the_cell(copy):
    res = run(copy, "tiny.tiny_budget")
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


def test_a_prepare_past_its_budget_fails_the_setup(copy):
    # Each run loads the traffic kind afresh: match the error by its base
    # class and message, not by the class object.
    with pytest.raises(TimeoutError, match="past its budget of 0.001 s"):
        run(copy, "tiny.tiny_overrun")
