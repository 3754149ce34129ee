#!/usr/bin/env python3
"""The precision control, planted in the program, and the readings of a
cell's compared numbers over many seeds in one process.

The configurations state float32 arithmetic.  The control is that
arithmetic one precision lower, put in the program's place (`planted`):

* each main-path kernel of the program with its floating operands and
  results rounded to bfloat16 (the storage that would halve the bytes of
  every sweep), bound both where the solve programs call them
  (`repro.core.device_seeding`) and in `repro.kernels.ops`;
* the cost the program reports computed by the plain reference's
  expansion in bfloat16.

The window then runs the control, and the checks after it read it.

    python benchmarks/chip/controls.py --workload <cell> --seeds 1 2 3 \\
        [--control-seeds 4 5 6] [--seconds 5] [--traced 3]

runs the cell once per seed in one process (a cell's set-up is long, and
a process keeps its compiled programs), on the chip, and prints one JSON
line per run with every number compared beside its limit, `correct` and
the run's metrics: the program on `--seeds` (the first `--traced` of them
traced), then the control on `--control-seeds`.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
import traceback
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp

HERE = Path(__file__).resolve().parent
for _p in (str(HERE), str(HERE.parents[1] / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

KERNELS = ("pairwise_argmin", "d2_update", "tree_sep_update",
           "tree_sep_update_tiles", "lsh_bucket_accept")


def _bf16(x):
    """A floating array rounded to bfloat16 in its own dtype; anything
    else as it is."""
    if isinstance(x, jax.Array) and jnp.issubdtype(x.dtype, jnp.floating):
        return x.astype(jnp.bfloat16).astype(x.dtype)
    return x


def in_bfloat16(kernel):
    """`kernel` with its floating operands and results in bfloat16."""

    def control(*args, **kw):
        out = kernel(*(_bf16(a) for a in args), **kw)
        return jax.tree_util.tree_map(_bf16, out)

    return control


@jax.jit
def bfloat16_cost(points, centers):
    """The reference's k-means cost, its expansion taken in bfloat16."""
    x, c = points.astype(jnp.bfloat16), centers.astype(jnp.bfloat16)
    d2 = (jnp.sum(x * x, 1, keepdims=True) - 2 * (x @ c.T)
          + jnp.sum(c * c, 1)[None, :])
    return jnp.sum(jnp.maximum(d2, 0).min(1), dtype=jnp.float32)


def bfloat16_kernels():
    """The control's kernels, by the names `repro.kernels.ops` gives."""
    from repro.kernels import ops

    return SimpleNamespace(**{k: in_bfloat16(getattr(ops, k))
                              for k in KERNELS})


@contextlib.contextmanager
def planted():
    """The program runs the control while the block is open."""
    from repro.core import device_seeding, plan
    from repro.kernels import ops

    control = bfloat16_kernels()
    saved = []
    for mod in (device_seeding, ops):
        for name in KERNELS:
            if hasattr(mod, name):
                saved.append((mod, name, getattr(mod, name)))
                setattr(mod, name, getattr(control, name))
    saved.append((plan, "_cost_program", plan._cost_program))
    plan._cost_program = bfloat16_cost
    jax.clear_caches()          # no program traced before may be reused
    try:
        yield
    finally:
        for mod, name, fn in saved:
            setattr(mod, name, fn)
        jax.clear_caches()


def readings(reg, workload: str, runs: list, *, seconds: float, devices):
    """Yields one dict per (seed, traced, control) of `runs`: the run's
    compared numbers and metrics."""
    import bench

    for seed, traced, control in runs:
        args = argparse.Namespace(workload=workload, seed=seed,
                                  seconds=seconds, trace=int(traced))
        line = {"workload": workload, "seed": seed, "control": control,
                "trace": args.trace}
        try:
            with planted() if control else contextlib.nullcontext():
                res = bench.run_cell(args, reg, devices=devices,
                                     t_start=time.perf_counter())
            line.update(res)
        except Exception as e:  # noqa: BLE001 — a crash is a reading too
            traceback.print_exc()
            line["error"] = f"{type(e).__name__}: {e}"
        yield line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--traced", type=int, default=0)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    import bench
    from registry import Registry

    bench.configure_jax()
    reg = Registry()
    devices = bench.require_chip(reg.workload(args.workload)["chips"])
    runs = ([(s, i < args.traced, False) for i, s in enumerate(args.seeds)]
            + [(s, False, True) for s in args.control_seeds])
    for line in readings(reg, args.workload, runs, seconds=args.seconds,
                         devices=devices):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
