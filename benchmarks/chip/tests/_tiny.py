"""A copy of the benchmark with tiny configurations added as new files.

The copy holds `BENCHMARK.json` and `benchmarks/chip` as committed, plus
`configs/tiny.json` with one cell per traffic mix (a stacked batch of 8
and a served mix sized for the CPU among them), and
`configs/tiny-sharded.json` (`rejection/sharded`, n not a multiple of 4)
with a re-seeding cell on four chips, each a new file, and their entries
in the copy's `BENCHMARK.json`.  No file the benchmark already has is
edited.
"""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
REPO = CHIP.parents[1]
for _p in (str(CHIP), str(REPO / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

TINY = {"name": "tiny", "source": "test", "n": 2048, "d": 16, "k": 16,
        "mixture_components": 50, "seeder": "rejection",
        "backend": "device", "dtype": "float32", "reduced": [],
        "assumed": {}}
TINY_SHARDED = dict(TINY, name="tiny-sharded", n=4101, backend="sharded")
TINY_BATCH = {"kind": "reseed", "batch": 8, "warm_calls": 1,
              "cost_sample": 8, "trace_seconds": 1, "reference_seeds": 2}
TINY_SERVED = {"kind": "closed_loop", "clients": 2, "max_batch": 2,
               "max_wait_ms": 50.0, "cost_sample": 4,
               "trace_seconds": 1, "reference_seeds": 2}


def make_copy(dst: Path) -> Path:
    """Copy the benchmark to `dst` and add the tiny cells; returns dst."""
    shutil.copy(REPO / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(CHIP, dst / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    root = dst / "benchmarks" / "chip"
    spec = json.loads((dst / "BENCHMARK.json").read_text())
    for config in (TINY, TINY_SHARDED):
        name = config["name"]
        (root / "configs" / f"{name}.json").write_text(json.dumps(config))
        spec["configs"].append({"name": name, "source": "test",
                                "file": f"benchmarks/chip/configs/{name}.json",
                                "reduced": [], "why": "CPU tests"})
    (root / "traffic" / "tiny_served.json").write_text(
        json.dumps(TINY_SERVED))
    (root / "traffic" / "tiny_batch.json").write_text(json.dumps(TINY_BATCH))
    limits = json.loads((root / "cells" / "kddcup-k500.reseed.json")
                        .read_text())
    for config, mix, chips in (("tiny", "reseed", 1),
                               ("tiny", "tiny_batch", 1),
                               ("tiny", "tiny_served", 1),
                               ("tiny-sharded", "reseed", 4)):
        name = f"{config}.{mix}"
        spec["workloads"].append({"name": name, "config": config,
                                  "traffic": mix, "chips": chips,
                                  "why": "CPU tests"})
        (root / "cells" / f"{name}.json").write_text(json.dumps(limits))
    (dst / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return dst


def run(copy: Path, workload: str, *, seed: int = 5, seconds: float = 1.0):
    """One run of `workload` in the copy, on whatever devices JAX has
    (the harness's look for a chip is skipped)."""
    import argparse

    import jax

    import bench
    from registry import Registry

    args = argparse.Namespace(workload=workload, seed=seed,
                              seconds=seconds, trace=0)
    return bench.run_cell(args, Registry(copy), devices=jax.devices())
