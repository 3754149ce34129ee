"""Re-seeding one large prepared point set, with a time budget for the
prepare.

The `reseed` kind (its `Driver` is imported, not copied) with one more
parameter, `prepare_budget_s`: set-up fails, with an error and no result
line, once `ClusterPlan.prepare` has run that many seconds.  A deployment
whose prepare runs past its budget does not hold its chips; a program
that cannot prepare the configuration in time ends the run in set-up
instead of running out of memory or the run's time much later.

The prepare runs on a worker thread while the main thread waits for it at
most the budget, so the run fails when the budget is spent, also while
the prepare is inside one long NumPy call; the worker is a daemon thread,
left behind for the process's exit.

The point set is the configuration's Gaussian mixture (`data.mixture`'s
law: the same centers, power-law weights and per-cluster scales), drawn
in blocks of `BLOCK_ROWS` rows side by side on a thread pool, each block
from its own stream of the run's seed, so that a set of tens of millions
of rows takes seconds, not minutes, of set-up.  The same seed gives the
same set whatever the number of threads.  The checks' float32 copy of the
rows is made block by block on the same pool.
"""

from __future__ import annotations

import importlib.util
import os
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np


def _reseed():
    """The `reseed` kind's module, as `registry.Registry.module` names it
    (loaded by its path, beside this file)."""
    name = "chipbench_traffic_reseed"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, Path(__file__).with_name("reseed.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


reseed = _reseed()

#: Rows of one block of the point set (its temporaries are a few MB).
BLOCK_ROWS = 32768


class PrepareBudgetExceeded(TimeoutError):
    """`ClusterPlan.prepare` ran past the mix's `prepare_budget_s`."""


def within_budget(fn, seconds: float):
    """`fn()`'s value, or its error; `PrepareBudgetExceeded` once it has
    run `seconds` (it runs on a daemon thread, which is left running)."""
    done = threading.Event()
    out: dict = {}

    def work():
        try:
            out["value"] = fn()
        except BaseException as e:      # noqa: BLE001 — handed to the caller
            out["error"] = e
        finally:
            done.set()

    threading.Thread(target=work, name="prepare", daemon=True).start()
    if not done.wait(seconds):
        raise PrepareBudgetExceeded(
            f"prepare ran past its budget of {seconds:g} s")
    if "error" in out:
        raise out["error"]
    return out["value"]


def _blocks(n: int, fn) -> None:
    """`fn(lo, hi)` for each block of `BLOCK_ROWS` rows of [0, n), on a
    thread pool (NumPy releases the GIL in its loops)."""
    starts = range(0, n, BLOCK_ROWS)
    with ThreadPoolExecutor(min(len(starts), os.cpu_count() or 1)) as pool:
        list(pool.map(lambda lo: fn(lo, min(lo + BLOCK_ROWS, n)), starts))


def mixture(n: int, d: int, components: int, seed: int) -> np.ndarray:
    """(n, d) float64 points of `data.mixture`'s law, drawn from `seed`:
    the components from its first stream, each block of rows from a
    stream of its own."""
    streams = np.random.SeedSequence([int(seed)]).spawn(
        1 + -(-n // BLOCK_ROWS))
    rng = np.random.default_rng(streams[0])
    centers = rng.normal(size=(components, d)) * 12.0
    weights = 1.0 / np.arange(1, components + 1) ** 1.3
    weights /= weights.sum()
    scales = rng.uniform(0.3, 3.0, size=(components, d))
    out = np.empty((n, d), np.float64)
    local = threading.local()

    def block(lo, hi):
        g = np.random.default_rng(streams[1 + lo // BLOCK_ROWS])
        assign = g.choice(components, size=hi - lo, p=weights)
        if not hasattr(local, "buf"):
            local.buf = np.empty((BLOCK_ROWS, d), np.float64)
        rows, buf = out[lo:hi], local.buf[:hi - lo]
        g.standard_normal(out=rows)
        np.multiply(rows, np.take(scales, assign, axis=0, out=buf), out=rows)
        np.add(rows, np.take(centers, assign, axis=0, out=buf), out=rows)

    _blocks(n, block)
    return out


def as_float32(points: np.ndarray) -> np.ndarray:
    """`points.astype(np.float32)`, block by block on a thread pool."""
    out = np.empty(points.shape, np.float32)

    def block(lo, hi):
        out[lo:hi] = points[lo:hi]

    _blocks(len(points), block)
    return out


class Driver(reseed.Driver):
    def setup(self) -> None:
        from repro.core import ClusterPlan, ClusterSpec, ExecutionSpec

        cfg = self.config
        t0 = time.perf_counter()
        self.host_points(())
        t1 = time.perf_counter()
        self.plan = ClusterPlan(
            ClusterSpec(k=cfg["k"], seeder=cfg["seeder"]),
            ExecutionSpec(backend=cfg["backend"], dtype=cfg["dtype"]))
        within_budget(lambda: self.plan.prepare(self.points),
                      float(self.traffic["prepare_budget_s"]))
        self.prepared = self.plan.prepare_data(self.points)   # cache hit
        t2 = time.perf_counter()
        for i in range(self.traffic["warm_calls"]):
            self._call(reseed.WARM_STREAM, i)
        self.log(f"setup: data {t1 - t0:.3f}s, prepare {t2 - t1:.3f}s "
                 f"(budget {self.traffic['prepare_budget_s']} s), "
                 f"{self.traffic['warm_calls']} warm calls "
                 f"{time.perf_counter() - t2:.3f}s")

    def host_points(self, set_key) -> np.ndarray:
        if self.points is None:
            cfg = self.config
            self.points = mixture(cfg["n"], cfg["d"],
                                  cfg["mixture_components"], self.seed)
        return self.points

    def rows(self, set_key) -> np.ndarray:
        """The point set as the program got it (float32)."""
        if self._rows is None:
            self._rows = as_float32(self.host_points(set_key))
        return self._rows
