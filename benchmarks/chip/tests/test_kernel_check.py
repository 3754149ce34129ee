"""The kernel check: today's numbers on a small set, a row sample on a
large one."""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np

from _tiny import TINY

import kernel_check
from data import mixture, point_set

WIDTHS = {"k": 16, "h": 14, "l": 15, "b": 512}
# kernel_errors(ops, point_set(TINY, 7), seed=11, **WIDTHS) as the check
# read before it had a row cap (interpreted kernels on the CPU).
BEFORE_THE_CAP = {
    "pairwise_argmin.min": 2.292566646328945e-07,
    "pairwise_argmin.argmin_gap": 0.0,
    "d2_update": 1.2516540750751834e-07,
    "tree_sep_update": 1.9375e-06,
    "lsh_bucket_accept.d2": 6.820980868838152e-08,
    "lsh_bucket_accept.p": 3.648662320391413e-08,
}


def test_a_set_under_the_cap_reads_as_before():
    from repro.kernels import ops

    assert TINY["n"] <= kernel_check.KERNEL_ROWS
    errs = kernel_check.kernel_errors(ops, point_set(TINY, 7), seed=11,
                                      **WIDTHS)
    assert errs == BEFORE_THE_CAP


def _recording(ops, shapes: list, rows: list):
    """`ops` with every array argument's shape recorded, and the rows the
    distance kernel was given."""

    def wrap(name):
        def call(*args, **kw):
            shapes.extend((name, np.shape(a)) for a in args
                          if hasattr(a, "shape"))
            if name == "pairwise_argmin":
                rows.append(np.asarray(args[0]))
            return getattr(ops, name)(*args, **kw)
        return call

    names = ("pairwise_argmin", "d2_update", "tree_sep_update",
             "lsh_bucket_accept")
    return SimpleNamespace(**{name: wrap(name) for name in names})


def test_a_set_over_the_cap_is_checked_on_a_sorted_sample(monkeypatch):
    from repro.kernels import ops

    cap, n = 1024, 4100
    monkeypatch.setattr(kernel_check, "KERNEL_ROWS", cap)
    x = mixture(n, 16, 50, [3])
    x[:, 0] = np.arange(n)            # each row names itself
    taken = {}
    for seed in (11, 11, 12):
        shapes, rows = [], []
        errs = kernel_check.kernel_errors(_recording(ops, shapes, rows), x,
                                          seed=seed, **WIDTHS)
        assert max(max(s) for _, s in shapes if s) == cap, shapes
        assert {name for name, _ in shapes} == {
            "pairwise_argmin", "d2_update", "tree_sep_update",
            "lsh_bucket_accept"}
        assert max(errs.values()) < 1e-4, errs
        idx = rows[0][:, 0].astype(np.int64)
        assert len(idx) == cap and (np.diff(idx) > 0).all()
        np.testing.assert_array_equal(rows[0], x[idx].astype(np.float32))
        taken.setdefault(seed, []).append(idx)
    np.testing.assert_array_equal(*taken[11])
    assert not np.array_equal(taken[11][0], taken[12][0])
