"""The precision control comes out not correct; the program does not.

The control (`controls.planted`: the program's kernels with bfloat16
operands and results, and its reported cost in bfloat16) must read above
every cell's limit on at least one number, and the program's own below
all of them, at a size a CPU test run can hold.
"""

from __future__ import annotations

import pytest

from _tiny import make_copy, run

import controls
import kernel_check
from data import mixture
from registry import Registry


@pytest.fixture(scope="module")
def points():
    return mixture(4096, 74, 200, [11])


def _over(errs: dict, limits: dict) -> list:
    return [k for k, v in errs.items() if v > limits[f"kernel.{k}"]]


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  Registry().spec["workloads"]])
def test_control_fails_and_program_passes(cell, points):
    from repro.kernels import ops

    limits = Registry().cell(cell)["limits"]
    widths = {"k": 64, "h": 14, "l": 15, "b": 512}
    control = kernel_check.kernel_errors(controls.bfloat16_kernels(), points,
                                         seed=1, **widths)
    program = kernel_check.kernel_errors(ops, points, seed=1, **widths)
    assert _over(control, limits), control
    assert not _over(program, limits), program


@pytest.mark.parametrize("workload", ["tiny.reseed", "tiny.tiny_served"])
def test_control_in_a_run_is_not_correct(tmp_path, workload, monkeypatch):
    traced = set()
    wrap = controls.in_bfloat16

    def counted(kernel):
        control = wrap(kernel)

        def call(*args, **kw):
            traced.add(kernel.__name__)
            return control(*args, **kw)

        return call

    monkeypatch.setattr(controls, "in_bfloat16", counted)
    copy = make_copy(tmp_path)
    with controls.planted():
        res = run(copy, workload)
    # The solve programs the window ran were built from the control.
    assert {"tree_sep_update_tiles", "lsh_bucket_accept"} <= traced
    checks = res["checks"]
    assert not res["correct"]
    assert checks["bad_answers"]["value"] == 0
    assert _over({k[7:]: c["value"] for k, c in checks.items()
                  if k.startswith("kernel.")}, Registry(copy).cell(workload)
                 ["limits"])
    # Once unplanted, the program is sound again; the window ran the
    # control, whose reported cost is bfloat16's (the CPU's own float32
    # cost is exact to rounding).
    sound = run(copy, workload)
    assert sound["correct"]
    assert (checks["cost_gap"]["value"]
            > 10 * sound["checks"]["cost_gap"]["value"])
