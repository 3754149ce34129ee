"""Quickstart: the paper's fast k-means++ seeding on a synthetic dataset.

    PYTHONPATH=src python examples/quickstart.py [--n 100000] [--k 500]

Compares FASTK-MEANS++ and REJECTIONSAMPLING (this paper) against exact
k-means++, AFK-MC^2 and uniform seeding — the experiment of paper §6 —
then demonstrates the plan/execute API: one `ClusterPlan` whose prepare
stage (multi-tree embedding, LSH keys, quantisation) is built once and
reused by `fit` / `refit` / `fit_batch`.

`--engine` (implied by `--smoke`) adds the async pipeline demo: a
`ClusterEngine` overlapping the host prepare of dataset i+1 with the
device solve of dataset i, plus the stacked `fit_batch(datasets=...)`
that solves several *different* datasets as one vmapped jit program
(docs/architecture.md has the full tour).

`--smoke` runs a seconds-sized version of everything (CI keeps this
example from rotting by running it on every push).
"""

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=50_000)
    ap.add_argument("--d", type=int, default=32)
    ap.add_argument("--k", type=int, default=500)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: tiny dataset, every API surface")
    ap.add_argument("--backend", choices=("cpu", "device", "sharded"),
                    default="cpu",
                    help="'device' also runs the jit seeders (Pallas "
                         "kernels; interpret mode off-TPU); 'sharded' the "
                         "multi-chip shard_map seeders over all local "
                         "devices")
    ap.add_argument("--engine", action="store_true",
                    help="also run the async ClusterEngine pipeline demo "
                         "(overlap host prepare with device solve) and the "
                         "stacked multi-dataset fit_batch")
    ap.add_argument("--schedule", default="adaptive",
                    help="candidate-batch schedule for the device/sharded "
                         "rejection seeder: 'adaptive' (default), "
                         "'fixed:<B>' (legacy fixed block, e.g. fixed:128) "
                         "or 'adaptive:<min>,<max>' for a custom ladder")
    args = ap.parse_args()
    if args.smoke:
        args.n, args.d, args.k = 4000, 8, 25

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from repro.core import (
        BatchSchedule,
        ClusterPlan,
        ClusterSpec,
        ExecutionSpec,
        SEEDERS,
        clustering_cost,
    )

    try:
        if args.schedule == "adaptive":
            schedule = BatchSchedule()
        elif args.schedule.startswith("fixed:"):
            schedule = BatchSchedule.fixed(
                int(args.schedule.split(":", 1)[1]))
        elif args.schedule.startswith("adaptive:"):
            lo, hi = args.schedule.split(":", 1)[1].split(",")
            schedule = BatchSchedule(min_batch=int(lo), max_batch=int(hi))
        else:
            raise ValueError("unknown schedule kind")
    except ValueError as e:
        raise SystemExit(
            f"bad --schedule {args.schedule!r} ({e}); expected 'adaptive', "
            f"'fixed:<B>' or 'adaptive:<min>,<max>'")

    rng = np.random.default_rng(args.seed)
    centers = rng.normal(size=(args.k * 2, args.d)) * 10
    pts = centers[rng.integers(len(centers), size=args.n)] + rng.normal(
        size=(args.n, args.d)
    )
    print(f"dataset: n={args.n} d={args.d}, seeding k={args.k}\n")
    print(f"{'algorithm':16s} {'seconds':>8s} {'cost':>14s} {'vs km++':>8s}")
    base = None
    for name in ("kmeans++", "fastkmeans++", "rejection", "kmeans||",
                 "afkmc2", "uniform"):
        res = SEEDERS[name](pts, args.k, np.random.default_rng(args.seed))
        cost = clustering_cost(pts, res.centers)
        if name == "kmeans++":
            base = cost
        print(f"{name:16s} {res.seconds:8.2f} {cost:14.1f} {cost/base:8.3f}")

    # -- plan/execute API ---------------------------------------------------
    # ClusterSpec (what) + ExecutionSpec (where) compile into a ClusterPlan:
    # `prepare` builds the host-side artifacts once (cached by data
    # fingerprint); `fit`/`refit`/`fit_batch` only pay the solve stage.
    print("\nplan/execute API (rejection seeder + 5 Lloyd iterations):")
    spec = ClusterSpec(k=args.k, seeder="rejection", lloyd_iters=5,
                       seed=args.seed, schedule=schedule)
    plan = ClusterPlan(spec, ExecutionSpec(backend="cpu"))
    plan.prepare(pts)
    km = plan.fit()
    print(f"  prepare: {km.prepare_seconds:.2f}s   "
          f"fit (solve only): {km.solve_seconds:.2f}s   "
          f"final cost: {float(np.asarray(km.cost)):.1f} "
          f"({km.extras.get('lloyd_iterations', 0)} Lloyd iterations)")
    km2 = plan.refit(seed=args.seed + 1)
    print(f"  refit(seed+1): {km2.solve_seconds:.2f}s "
          f"(cpu caches the quantise step; the device plans below cache "
          f"embedding+LSH too; cost {float(np.asarray(km2.cost)):.1f})")

    if args.backend in ("device", "sharded") or args.smoke:
        # The same two paper algorithms as single jit device programs
        # (Algorithm 3 + Algorithm 4 with the fused Pallas LSH kernel).
        # On a TPU the Pallas kernels compile; elsewhere they run in
        # interpret mode, so expect this to be slower than the CPU path
        # off-accelerator — it demonstrates the API, not the speed.
        #
        # backend='sharded' runs the shard_map twins instead: one
        # contiguous point range + local sub-heap per device.  Try
        # XLA_FLAGS=--xla_force_host_platform_device_count=4 to see the
        # 4-shard program run without TPU hardware.
        import jax

        backend = args.backend if args.backend != "cpu" else "device"
        dev_pts, dev_k = (pts[:1500], 10) if args.smoke else (pts, args.k)
        ndev = len(jax.devices())
        print(f"\n{backend} backend plans ({ndev} device(s), "
              f"schedule={args.schedule}):")
        for name in ("fastkmeans++", "rejection", "kmeans||"):
            plan = ClusterPlan(
                ClusterSpec(k=dev_k, seeder=name, seed=args.seed,
                            schedule=schedule),
                ExecutionSpec(backend=backend),
            )
            plan.prepare(dev_pts)
            km = plan.fit()
            line = (f"  {name + '/' + backend:24s} "
                    f"prepare {km.prepare_seconds:7.2f}s  "
                    f"solve {km.solve_seconds:7.2f}s  "
                    f"cost={float(np.asarray(km.cost)):14.1f}")
            if name == "rejection":
                batch = plan.fit_batch([1, 2, 3, 4])
                costs = np.asarray(batch.cost)
                line += (f"  fit_batch(4 seeds"
                         f"{', vmapped' if batch.extras['vmapped'] else ''})"
                         f" {batch.solve_seconds:.2f}s best={costs.min():.1f}")
            print(line)

    if args.engine or args.smoke:
        # -- async pipelined engine + stacked multi-dataset fit_batch -------
        # ClusterEngine overlaps the host prepare (embedding/LSH build) of
        # request i+1 with the device solve of request i; results are
        # bit-identical to the serial prepare+fit loop.  The stacked
        # fit_batch solves B *different* datasets as one vmapped program
        # per shape bucket (canonical power-of-two rescale + padded lanes).
        import time as _time

        from repro.core import ClusterEngine
        from repro.core.plan import SOLVE_SPAN
        from repro.core.tracing import span_totals

        b = 3 if args.smoke else 6
        n_eng = 1000 if args.smoke else min(args.n, 20_000)
        eng_rng = np.random.default_rng(args.seed + 99)
        eng_datasets = [
            centers[eng_rng.integers(len(centers), size=n_eng)]
            + eng_rng.normal(size=(n_eng, args.d))
            for _ in range(b)
        ]
        spec = ClusterSpec(k=10 if args.smoke else args.k,
                           seeder="rejection", seed=args.seed,
                           schedule=schedule)
        exe = ExecutionSpec(backend="device")
        print(f"\nClusterEngine pipeline ({b} datasets, n={n_eng}):")
        t0 = _time.time()
        dispatch0 = span_totals().get(SOLVE_SPAN, {}).get("seconds", 0.0)
        with ClusterEngine(spec, exe) as engine:
            results = engine.map_fit(eng_datasets)
            for r in results:
                r.block_until_ready()
            st = engine.stats()
        wall = _time.time() - t0
        dispatch = st["spans"][SOLVE_SPAN]["seconds"] - dispatch0
        print(f"  pipelined wall {wall:.2f}s  "
              f"(host prepare {st['prepare_seconds']:.2f}s overlapped with "
              f"solve dispatch {dispatch:.2f}s)  "
              f"costs={[f'{float(np.asarray(r.cost)):.0f}' for r in results]}")
        plan = ClusterPlan(spec, exe)
        t0 = _time.time()
        stacked = plan.fit_batch(datasets=eng_datasets)
        stacked.block_until_ready()
        print(f"  stacked fit_batch({b} datasets): "
              f"{_time.time()-t0:.2f}s in {stacked.extras['shape_buckets']} "
              f"shape bucket(s), one vmapped program each; "
              f"costs={[f'{c:.0f}' for c in np.asarray(stacked.cost)]}")


if __name__ == "__main__":
    main()
