"""Re-seeding one prepared point set, call after call.

Set-up makes the configuration's point set from the run's seed and
prepares it once (`ClusterPlan.prepare`).  The window then calls
`ClusterPlan.refit(seed=s)` (`batch` 1) or `ClusterPlan.fit_batch(seeds)`
(`batch` > 1, one vmapped program for the lanes) with fresh seeds, one
call after another, each to `block_until_ready` of its indices, centers
and cost.  `reseed_s` is the window over the seedings completed in it.

Parameters (the mix's JSON file): `batch`, `warm_calls` (calls in
set-up: the first compiles), `cost_sample` (answers whose cost is
compared, drawn from the seed), `trace_seconds`, `reference_seeds`.
"""

from __future__ import annotations

import time

import jax
import numpy as np

import reference
from checks import Answer, derive_seed
from data import point_set

WINDOW_STREAM, WARM_STREAM, SAMPLE_STREAM = 1, 0, 5


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int, log,
                 devices: list):
        self.config, self.traffic, self.seed, self.log = (
            config, traffic, seed, log)
        self.devices = devices
        self.batch = int(traffic["batch"])
        self.label = f"{config['seeder']}/{config['backend']}"
        self.points = None
        self._dev = self._rows = None

    # -- set-up --------------------------------------------------------------

    def setup(self) -> None:
        from repro.core import ClusterPlan, ClusterSpec, ExecutionSpec

        cfg = self.config
        t0 = time.perf_counter()
        self.host_points(())
        t1 = time.perf_counter()
        self.plan = ClusterPlan(
            ClusterSpec(k=cfg["k"], seeder=cfg["seeder"]),
            ExecutionSpec(backend=cfg["backend"], dtype=cfg["dtype"]))
        self.plan.prepare(self.points)
        self.prepared = self.plan.prepare_data(self.points)   # cache hit
        t2 = time.perf_counter()
        for i in range(self.traffic["warm_calls"]):
            self._call(WARM_STREAM, i)
        self.log(f"setup: data {t1 - t0:.3f}s, prepare {t2 - t1:.3f}s, "
                 f"{self.traffic['warm_calls']} warm calls "
                 f"{time.perf_counter() - t2:.3f}s")

    def _call(self, stream: int, i: int) -> list:
        seeds = [derive_seed(self.seed, stream, i * self.batch + j)
                 for j in range(self.batch)]
        if self.batch == 1:
            with jax.profiler.TraceAnnotation("bench.refit"):
                res = self.plan.refit(seed=seeds[0])
        else:
            with jax.profiler.TraceAnnotation("bench.fit_batch"):
                res = self.plan.fit_batch(seeds)
        with jax.profiler.TraceAnnotation("bench.block_until_ready"):
            jax.block_until_ready((res.indices, res.centers, res.cost))
        # A solo call returns one seeding; a batch stacks them by row.
        return [Answer(seed=s, set_key=(), indices=res.indices,
                       centers=res.centers, cost=res.cost,
                       row=None if self.batch == 1 else j,
                       trials=res.extras["trials"], served_by=self.label)
                for j, s in enumerate(seeds)]

    # -- window --------------------------------------------------------------

    def window(self, seconds: float) -> dict:
        answers = []
        t0 = time.perf_counter()
        calls = 0
        while True:
            answers += self._call(WINDOW_STREAM, calls)
            calls += 1
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        return {"seconds": elapsed, "answers": answers,
                "attempted": len(answers), "calls": calls}

    def metrics(self, win: dict) -> dict:
        return {"reseed_s": win["seconds"] / len(win["answers"])}

    def kernel_widths(self) -> dict:
        """The kernel check's widths: k, the tree heights and LSH tables of
        the prepared arrays the window swept, the largest candidate block.
        The device backend prepares a `DeviceSeedingData`; the sharded one
        a (`DeviceSeedingData`, n) pair."""
        from repro.core import BatchSchedule

        art = self.prepared.artifacts
        if isinstance(art, tuple):
            art = art[0]
        return {"k": self.config["k"], "h": art.codes_lo.shape[1],
                "l": art.keys_lo.shape[0], "b": BatchSchedule().buckets()[-1]}

    def close(self) -> None:
        self.plan = self.prepared = None

    # -- what the checks read -------------------------------------------------

    def first_set_key(self) -> tuple:
        return ()

    def rows(self, set_key) -> np.ndarray:
        """The point set as the program got it (float32)."""
        if self._rows is None:
            self._rows = self.host_points(set_key).astype(np.float32)
        return self._rows

    def host_points(self, set_key) -> np.ndarray:
        if self.points is None:
            self.points = point_set(self.config, self.seed)
        return self.points

    def points_dev(self, set_key) -> reference.Rows:
        """The reference's rows, over the cell's chips, kept until
        `free_points_dev`."""
        if self._dev is None:
            self._dev = reference.place(self.rows(set_key), self.devices)
        return self._dev

    def free_points_dev(self) -> None:
        self._dev = None

    def cost_sample(self, answers: list) -> list:
        came = [a for a in answers if a.indices is not None]
        rng = np.random.default_rng(derive_seed(self.seed, SAMPLE_STREAM))
        pick = rng.choice(len(came), min(len(came),
                                         self.traffic["cost_sample"]),
                          replace=False)
        return [came[i] for i in sorted(pick)]
