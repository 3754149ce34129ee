"""Closed-loop clients through the whole served path.

`clients` threads, each with its own `ClusterClient` connection to one
`ClusterServer` (`ClusterFrontend` -> `ClusterEngine` -> `ClusterPlan`),
send one fit request at a time and the next once its answer is back.
Every request carries a new point set made from (run seed, client,
request index), generated before its send and outside its latency, so
the prepare cache never hits.  Latency is client-seen: from just before
the send until the answer is back.

Set-up warms every lane the frontend can form (each member count up to
`max_batch`; the solve pads a lane to a power-of-two rung, and the
results are stacked at the member count) through a plan of the
same specs, which compiles the programs the server's engine and
frontend then find (the frontend hands each member its row of the
lane's result), and sends one round of requests through the server.

`fits_per_s` counts the answers that came back inside the window over
its length; `latency_p90_s` is over every request sent in the window,
also those answered after its close (the clients finish the request in
hand and send no more).

Parameters: `clients`, `max_batch`, `max_wait_ms`, `cost_sample` (answers whose cost is compared, drawn from the seed),
`trace_seconds`, `reference_seeds`.
"""

from __future__ import annotations

import threading
import time

import jax
import numpy as np

import reference
from checks import Answer, derive_seed
from data import point_set

WINDOW_STREAM, WARM_STREAM, LANE_STREAM, SAMPLE_STREAM = 2, 3, 4, 5
ANSWER_GRACE_S = 60.0        # how long past the close an answer may take


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int, log,
                 devices: list):
        self.config, self.traffic, self.seed, self.log = (
            config, traffic, seed, log)
        self.devices = devices
        self.server = None
        self.conns: list = []
        self._dev = (None, None)

    def request_points(self, stream: int, j: int, r: int) -> np.ndarray:
        """The point set of request r of client j, as sent (float32)."""
        return point_set(self.config, self.seed, stream, j, r).astype(
            np.float32)

    # -- set-up --------------------------------------------------------------

    def setup(self) -> None:
        from repro.core import ClusterPlan, ClusterSpec, ExecutionSpec
        from repro.serving.net import ClusterClient, ClusterServer

        cfg, tr = self.config, self.traffic
        spec = ClusterSpec(k=cfg["k"], seeder=cfg["seeder"])
        execution = ExecutionSpec(backend=cfg["backend"], dtype=cfg["dtype"])
        t0 = time.perf_counter()
        side = ClusterPlan(spec, execution)
        lanes = list(range(1, tr["max_batch"] + 1))
        preps = [side.prepare_stacked(self.request_points(LANE_STREAM, 0, i))
                 for i in range(lanes[-1])]
        for b in lanes:
            res = side.fit_batch_prepared(
                preps[:b],
                seeds=[derive_seed(self.seed, LANE_STREAM, b, i)
                       for i in range(b)])
            # The frontend hands each member its row of the lane's result.
            jax.block_until_ready([(res.indices[i], res.centers[i],
                                    res.cost[i]) for i in range(b)])
        arrays = preps[0].artifacts.arrays
        self._shapes = {"codes": arrays[0].shape, "keys": arrays[3].shape}
        del side, preps
        t1 = time.perf_counter()
        self.server = ClusterServer(spec, execution,
                                    max_batch=tr["max_batch"],
                                    max_wait_ms=tr["max_wait_ms"])
        self.conns = [ClusterClient(*self.server.address)
                      for _ in range(tr["clients"])]
        warm = self._drive(WARM_STREAM, deadline=None)
        self.log(f"setup: lanes {lanes} warmed in {t1 - t0:.3f}s, "
                 f"{len(warm)} warm requests through the server in "
                 f"{time.perf_counter() - t1:.3f}s")

    # -- load --------------------------------------------------------------

    def _client(self, j: int, stream: int, deadline, out: list,
                lock: threading.Lock) -> None:
        conn = self.conns[j]
        r = 0
        while True:
            with jax.profiler.TraceAnnotation("bench.client.generate"):
                pts = self.request_points(stream, j, r)
            seed = derive_seed(self.seed, stream, j, r)
            a = Answer(seed=seed, set_key=(stream, j, r))
            t_send = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation("bench.client.submit"):
                    rid = conn.submit(pts, seed=seed)
                wait = ANSWER_GRACE_S + (0.0 if deadline is None
                                         else max(0.0, deadline - t_send))
                with jax.profiler.TraceAnnotation("bench.client.wait"):
                    res = conn.result(rid, timeout=wait)
                a.indices, a.centers, a.cost = (res.indices, res.centers,
                                                res.cost)
                attempts = res.extras.get("attempts", 1)
                a.served_by = res.extras.get("served_by")
                if attempts != 1:
                    a.served_by = f"{a.served_by} after {attempts} attempts"
                a.trials = res.extras.get("trials")
            except Exception as e:  # noqa: BLE001 — an answer that never came
                a.error = f"{type(e).__name__}: {e}"
            a.latency_s = time.perf_counter() - t_send
            a.t_done = time.perf_counter()
            with lock:
                out.append(a)
            r += 1
            if deadline is None or time.perf_counter() >= deadline:
                return

    def _drive(self, stream: int, deadline) -> list:
        out: list = []
        lock = threading.Lock()
        threads = [threading.Thread(target=self._client,
                                    args=(j, stream, deadline, out, lock),
                                    name=f"bench-client-{j}")
                   for j in range(len(self.conns))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return out

    # -- window --------------------------------------------------------------

    def window(self, seconds: float) -> dict:
        before = self.server.stats()
        t0 = time.perf_counter()
        close = t0 + seconds
        answers = self._drive(WINDOW_STREAM, deadline=close)
        drained = time.perf_counter() - t0
        after = self.server.stats()
        done = [a for a in answers if a.error is None and a.t_done <= close]
        lat = [a.latency_s for a in answers]
        self.log(f"window: {len(answers)} requests, {len(done)} answered "
                 f"inside {seconds:.3f}s; all answered {drained:.3f}s after "
                 f"the start; {len(lat)} latency samples")
        return {"seconds": seconds, "answers": answers,
                "attempted": len(answers), "completed": len(done),
                "latencies": lat, "stats_before": before,
                "stats_after": after}

    def metrics(self, win: dict) -> dict:
        return {"fits_per_s": win["completed"] / win["seconds"],
                "latency_p90_s": float(np.percentile(win["latencies"], 90))}

    def kernel_widths(self) -> dict:
        """The kernel check's widths: k, the tree heights and LSH tables of
        the stacked lanes' arrays, the largest candidate block."""
        from repro.core import BatchSchedule

        return {"k": self.config["k"], "h": self._shapes["codes"][1],
                "l": self._shapes["keys"][0],
                "b": BatchSchedule().buckets()[-1]}

    def close(self) -> None:
        for conn in self.conns:
            conn.close()
        self.conns = []
        if self.server is not None:
            self.server.close()
            self.server = None

    # -- what the checks read -------------------------------------------------

    def first_set_key(self) -> tuple:
        return (WINDOW_STREAM, 0, 0)

    def rows(self, set_key) -> np.ndarray:
        """The point set as it was sent (float32)."""
        return self.request_points(*set_key)

    def host_points(self, set_key) -> np.ndarray:
        return self.request_points(*set_key).astype(np.float64)

    def points_dev(self, set_key) -> reference.Rows:
        """The reference's rows of one set, over the cell's chips; the
        last set asked for is kept until `free_points_dev`."""
        if self._dev[0] != set_key:
            self._dev = (None, None)      # the last set leaves first
            self._dev = (set_key, reference.place(
                self.request_points(*set_key), self.devices))
        return self._dev[1]

    def free_points_dev(self) -> None:
        self._dev = (None, None)

    def cost_sample(self, answers: list) -> list:
        came = sorted((a for a in answers if a.indices is not None),
                      key=lambda a: a.set_key)
        rng = np.random.default_rng(derive_seed(self.seed, SAMPLE_STREAM))
        pick = rng.choice(len(came), min(len(came),
                                         self.traffic["cost_sample"]),
                          replace=False)
        return [came[i] for i in sorted(pick)]
