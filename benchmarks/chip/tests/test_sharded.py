"""A cell on four chips, run end to end on four CPU devices in a process
of its own (the device count is fixed when JAX starts)."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from _tiny import TINY_SHARDED, make_copy

HERE = Path(__file__).resolve().parent


@pytest.fixture(scope="module")
def seen(tmp_path_factory):
    copy = make_copy(tmp_path_factory.mktemp("bench"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, str(HERE / "_sharded_run.py"),
                           str(copy)], cwd=HERE, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_four_chip_cell_is_correct_with_its_rows_on_four_devices(seen):
    assert seen["devices"] == 4
    res = seen["result"]
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    n = TINY_SHARDED["n"]
    assert n % 4
    assert seen["placed"] and all(
        p == {"devices": 4, "shape": [n + 4 - n % 4, TINY_SHARDED["d"]],
              "n": n} for p in seen["placed"])
    # The widths came from the sharded backend's (data, n) artifacts.
    art = seen["artifacts"][-1]
    assert art["tuple"]
    assert seen["widths"] and all(
        (w["h"], w["l"], w["k"]) == (art["h"], art["l"], TINY_SHARDED["k"])
        for w in seen["widths"])


def test_reference_over_four_devices_reads_as_over_one(seen):
    for p in seen["pairs"]:
        assert p["same"]
        assert abs(p["cost4"] - p["cost1"]) <= 1e-12 * p["cost1"]
    assert seen["padded_shape"] == [4100, 16]
    assert seen["largest_drawn"] < 4097
