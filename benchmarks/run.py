"""Benchmark harness entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (plus the formatted paper
tables) and writes the regression artifact ``BENCH_seeding.json`` at the
repo root — per-backend seeding wall-clock, clustering-cost ratios vs exact
CPU k-means++, and the per-open sample-structure update microbenchmark
(O(n) heap rebuild vs the incremental tile-sum scatter) — so every PR
leaves a perf trajectory point.  Sections:
  - seeding speed/quality/variance + rejection stats — paper Tables 1-8 on
    (n,d)-matched synthetic datasets (see datasets.py), CI scale by default;
  - per-open heap-update microbenchmark (rebuild vs incremental) at
    n in {2^14, 2^16, 2^18};
  - robustness — engine goodput / latency percentiles under a seeded
    `FaultPlan` (CI gates goodput >= 0.95 with zero stranded tickets);
  - serving — continuous-batching frontend vs one-request-per-solve on a
    seeded open-loop Poisson trace (CI gates >= 2x requests/sec at equal
    p99 plus a coalesce-rate floor);
  - serving.net — the same trace replayed over the loopback wire
    transport (`repro.serving.net`) vs the in-process frontend: wire
    req/s, added p99, per-tenant Jain fairness index (CI gates the
    p99 overhead ratio and a fairness floor; `--only serving
    --transport net` re-runs just this subsection);
  - streaming — incremental `ClusterPlan.extend` + solve-only refit vs
    re-prepare-then-fit at n=2^16, plus drift-reseed quality on a
    distribution shift (CI gates the extend speedup and the
    post-reseed cost via `check_regression.py --extend-beats-reprep`;
    `--only streaming` re-runs just this section);
  - kernel microbenchmarks — Pallas ops (interpret mode on CPU) vs jnp refs;
  - roofline — §Roofline summary from the dry-run artifacts (if present).
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[1]
for _p in (str(_ROOT), str(_ROOT / "src")):   # script mode: `python benchmarks/run.py`
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np

BENCH_JSON = _ROOT / "BENCH_seeding.json"


def _timeit(fn, *args, reps=3, warmup=1, **kw):
    for _ in range(warmup):
        fn(*args, **kw)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
    dt = (time.perf_counter() - t0) / reps
    return dt, out


def bench_kernels():
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    rng = np.random.default_rng(0)
    rows = []
    for n, k, d in [(4096, 256, 64), (16384, 1024, 74)]:
        x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
        c = jnp.asarray(rng.normal(size=(k, d)), jnp.float32)
        dt, _ = _timeit(lambda: jax.block_until_ready(
            ops.pairwise_argmin(x, c)))
        rows.append((f"kernel.pairwise_argmin[{n}x{k}x{d}]", dt * 1e6,
                     f"{2*n*k*d/dt/1e9:.1f}GFLOP/s"))
        dtr, _ = _timeit(lambda: jax.block_until_ready(
            ref.pairwise_argmin_ref(x, c)))
        rows.append((f"ref.pairwise_argmin[{n}x{k}x{d}]", dtr * 1e6,
                     f"kernel_speedup_vs_ref={dtr/dt:.2f}x"))
        w = jnp.asarray(rng.uniform(1, 10, size=n), jnp.float32)
        dt, _ = _timeit(lambda: jax.block_until_ready(
            ops.d2_update(x, c[0], w)))
        rows.append((f"kernel.d2_update[{n}x{d}]", dt * 1e6, ""))
        dt, _ = _timeit(lambda: jax.block_until_ready(
            ops.d2_update_tiles(x, c[0], w)))
        rows.append((f"kernel.d2_update_tiles[{n}x{d}]", dt * 1e6,
                     "tile sums for TiledSampleTree.refresh"))

    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.kernels.ref import flash_attention_ref

    bh, s, d = 4, 512, 64
    q = jnp.asarray(rng.normal(size=(bh, s, d)), jnp.float32)
    kk = jnp.asarray(rng.normal(size=(bh, s, d)), jnp.float32)
    vv = jnp.asarray(rng.normal(size=(bh, s, d)), jnp.float32)
    dt, out = _timeit(lambda: jax.block_until_ready(flash_attention_pallas(
        q, kk, vv, scale=d ** -0.5, causal=True, interpret=True)), reps=1)
    ref = flash_attention_ref(q, kk, vv, scale=d ** -0.5, causal=True)
    err = float(jnp.abs(out - ref).max())
    rows.append((f"kernel.flash_attention[{bh}x{s}x{d}]", dt * 1e6,
                 f"max_err_vs_exact={err:.1e}"))
    return rows


def bench_seeding(smoke: bool = False):
    from benchmarks.seeding import main as seeding_main

    if smoke:
        # CI-sized run: tiny slice of one dataset, CPU + device + sharded
        # backends so every jit seeder (Pallas kernels in interpret mode
        # off-TPU, shard_map over however many local devices exist) gets
        # exercised end-to-end on every push.
        argv = ["--datasets", "kddcup", "--ks", "25", "--scale", "0.01",
                "--trials", "1", "--backends", "cpu", "device", "sharded"]
    else:
        argv = ["--datasets", "kddcup", "--ks", "100", "500",
                "--scale", "0.05", "--trials", "1"]
    results = seeding_main(argv)
    rows = []
    for res in results:
        for algo, data in res["algos"].items():
            for k, secs in data["seconds"].items():
                rows.append((f"seed.{res['dataset']}.{algo}[k={k}]",
                             secs * 1e6,
                             f"cost={data['cost'][k]:.4g}"))
    return rows, results


def bench_adaptive_batch(n=1 << 16, d=16, k=8, reps=3):
    """Adaptive vs fixed-128 candidate batching (ISSUE 3 acceptance row).

    Times the full jit rejection program (Algorithm 4) at n = 2^16 under
    `BatchSchedule.fixed(128)` — the legacy block size — and the adaptive
    default, reporting *per-center* wall-clock.  Off-TPU the Pallas kernels
    run in interpret mode, so absolute numbers are not TPU-representative,
    but the two schedules share every sweep and differ only in the
    speculative-batch work — exactly the quantity the schedule adapts.
    """
    import jax

    from repro.core.batch_schedule import BatchSchedule
    from repro.core.device_seeding import (
        device_rejection_sampling,
        prepare_rejection,
    )

    rng = np.random.default_rng(0)
    ctr = rng.normal(size=(64, d)) * 20
    pts = ctr[rng.integers(64, size=n)] + rng.normal(size=(n, d))
    # Fixed resolution pins num_levels (a jit static) across runs.
    data = prepare_rejection(pts, seed=0, resolution=0.05)
    rows, record = [], {"n": n, "k": k, "d": d, "reps": reps,
                        "schedules": {}}
    for name, sched in (("fixed128", BatchSchedule.fixed(128)),
                        ("adaptive", BatchSchedule())):
        def run(key):
            return jax.block_until_ready(device_rejection_sampling(
                data.codes_lo, data.codes_hi, data.points,
                data.keys_lo, data.keys_hi, k, key,
                scale=data.scale, num_levels=data.num_levels,
                m_init=data.m_init, schedule=sched,
            )[0])
        run(jax.random.key(1))                   # warm-up: trace + compile
        # Min over reps, not mean: the ratio below gates CI, and min is the
        # noise-robust statistic on shared runners.
        dt = min(_timeit(lambda: run(jax.random.key(1)), reps=1, warmup=0)[0]
                 for _ in range(reps))
        record["schedules"][name] = {
            "seconds": dt,
            "per_center_s": dt / k,
            "buckets": list(sched.buckets()),
        }
        rows.append((f"adaptive_batch.{name}[n={n},k={k}]",
                     dt / k * 1e6, "per-center wall-clock"))
    ratio = (record["schedules"]["adaptive"]["per_center_s"]
             / record["schedules"]["fixed128"]["per_center_s"])
    record["adaptive_over_fixed128"] = ratio
    rows.append((f"adaptive_batch.ratio[n={n}]", 0.0,
                 f"adaptive/fixed128={ratio:.3f}"))
    return rows, record


def bench_plan_refit(n=1 << 14, d=16, k=16, refits=4):
    """Prepare-once / refit-many (ISSUE 4 acceptance row).

    Times the plan/execute lifecycle on the device rejection seeder: the
    first `fit` pays prepare (multi-tree embedding + LSH keys, O(nd log Δ)
    host work) plus the solve stage; every `refit(seed=...)` pays the solve
    stage only — zero host-side re-preparation and zero re-traces
    (`TRACE_COUNTS` is asserted by tests, the wall-clock win is recorded
    here so the cached-prepare advantage stays measurable across PRs).
    """
    from repro.core import ClusterPlan, ClusterSpec, ExecutionSpec

    rng = np.random.default_rng(0)
    ctr = rng.normal(size=(64, d)) * 20
    pts = ctr[rng.integers(64, size=n)] + rng.normal(size=(n, d))
    plan = ClusterPlan(
        ClusterSpec(k=k, seeder="rejection", seed=0,
                    options={"resolution": 0.05}, quantize=False),
        ExecutionSpec(backend="device"),
    )
    t0 = time.perf_counter()
    plan.prepare(pts)
    prepare_s = time.perf_counter() - t0
    first = plan.fit().block_until_ready()     # traces + compiles once
    refit_s = []
    for i in range(refits):
        t0 = time.perf_counter()
        plan.refit(seed=i + 1).block_until_ready()
        refit_s.append(time.perf_counter() - t0)
    best_refit = min(refit_s)
    record = {
        "n": n, "k": k, "d": d,
        "prepare_s": prepare_s,
        "first_fit_s": prepare_s + first.solve_seconds,
        "refit_s": best_refit,
        "refits": refits,
        "prepare_amortized_speedup":
            (prepare_s + best_refit) / max(best_refit, 1e-12),
        "cache": plan.cache_info(),
    }
    rows = [
        ("plan_refit.prepare[n=%d]" % n, prepare_s * 1e6,
         "host artifacts, paid once"),
        ("plan_refit.refit[n=%d]" % n, best_refit * 1e6,
         f"solve-only; prepare amortised "
         f"{record['prepare_amortized_speedup']:.1f}x"),
    ]
    return rows, record


def bench_pipeline(n=1 << 16, d=16, k=4, b=4):
    """Overlapped submit/solve vs the serial prepare+solve loop (ISSUE 5).

    `b` distinct n=2^16 datasets through the same ClusterSpec: the serial
    loop pays ``sum(prepare_i + solve_i)``; the `ClusterEngine` pipeline
    pays ``~ prepare_0 + sum(solve_i)`` because every later prepare runs
    on the host pool while the previous solve executes — the overlap
    speedup recorded here ("pipeline" section, CI-asserted > 1).  Results
    are bit-identical either way (the engine's determinism contract,
    tests/test_engine.py).  Also records the stacked multi-dataset
    `fit_batch`: the same b datasets as ONE vmapped program per shape
    bucket (all land in one bucket here).  The stacked row uses the
    fastkmeans++ seeder: a vmapped `lax.switch` (the rejection schedule)
    executes every branch per round, which interpret-mode CI cannot
    afford — the rejection stacked path is trace-count-asserted in
    tests/test_engine.py instead.
    """
    from repro.core import (
        ClusterEngine,
        ClusterPlan,
        ClusterSpec,
        ExecutionSpec,
        TRACE_COUNTS,
    )
    from repro.core.plan import SOLVE_SPAN
    from repro.core.tracing import span_totals

    rng = np.random.default_rng(0)

    def make():
        ctr = rng.normal(size=(64, d)) * 20
        return ctr[rng.integers(64, size=n)] + rng.normal(size=(n, d))

    datasets = [make() for _ in range(b + 1)]
    spec = ClusterSpec(k=k, seeder="rejection", seed=0,
                       options={"resolution": 0.05}, quantize=False)
    exe = ExecutionSpec(backend="device")
    # Warm-up on a throwaway dataset: both paths then run the one cached
    # program (the measured quantity is throughput, not compile).
    warm = ClusterPlan(spec, exe)
    warm.prepare(datasets[0])
    warm.fit().block_until_ready()

    serial_plan = ClusterPlan(spec, exe)
    t0 = time.perf_counter()
    for ds in datasets[1:]:
        serial_plan.prepare(ds)
        serial_plan.fit().block_until_ready()
    serial_s = time.perf_counter() - t0

    dispatch0 = span_totals().get(SOLVE_SPAN, {}).get("seconds", 0.0)
    t0 = time.perf_counter()
    with ClusterEngine(spec, exe, prepare_workers=2) as engine:
        results = engine.map_fit(datasets[1:])
        for r in results:
            r.block_until_ready()
        st = engine.stats()
    pipelined_s = time.perf_counter() - t0
    speedup = serial_s / max(pipelined_s, 1e-9)

    traces0 = dict(TRACE_COUNTS)
    stacked_plan = ClusterPlan(
        ClusterSpec(k=k, seeder="fastkmeans++", seed=0), exe)
    t0 = time.perf_counter()
    stacked = stacked_plan.fit_batch(datasets=datasets[1:])
    stacked.block_until_ready()
    stacked_s = time.perf_counter() - t0
    stacked_traces = sum(
        v - traces0.get(kk, 0) for kk, v in TRACE_COUNTS.items()
        if kk.endswith("/stacked"))

    record = {
        "n": n, "d": d, "k": k, "num_problems": b,
        "serial_s": serial_s,
        "pipelined_s": pipelined_s,
        "overlap_speedup": speedup,
        "prepare_seconds_total": st["prepare_seconds"],
        "solve_seconds_total": (st["spans"][SOLVE_SPAN]["seconds"]
                                - dispatch0),
        "stacked_fit_batch_s": stacked_s,
        "stacked_shape_buckets": stacked.extras["shape_buckets"],
        "stacked_traces": stacked_traces,
    }
    rows = [
        (f"pipeline.serial[b={b},n={n}]", serial_s / b * 1e6,
         "per-problem prepare+solve, serial loop"),
        (f"pipeline.engine[b={b},n={n}]", pipelined_s / b * 1e6,
         f"overlap_speedup={speedup:.2f}x"),
        (f"pipeline.stacked_fit_batch[b={b},n={n}]", stacked_s / b * 1e6,
         f"{stacked.extras['shape_buckets']} bucket(s), "
         f"{stacked_traces} trace(s)"),
    ]
    return rows, record


def bench_robustness(n=1 << 12, d=16, k=4, b=16):
    """Goodput under injected faults (ISSUE 7 acceptance row).

    Drives `b` same-shape datasets through a `ClusterEngine` on the
    device backend while a seeded `FaultPlan` injects transient failures
    into 25% of primary solve attempts (`match` pins the chaos to
    fastkmeans++/device, so the degradation ladder — fastkmeans++/cpu,
    then kmeans++/cpu — stays healthy).  Each request retries up to 3
    attempts before falling back; goodput is the completed fraction and
    `stranded` counts tickets that never reached a terminal state — the
    CI gate (`check_regression.py`) requires goodput >= 0.95 and zero
    stranded.  Latency percentiles are per-request submit-to-done
    wall-clock, so the cost of a retry/fallback detour is visible in the
    p99/p50 spread across PRs.
    """
    import time as _time

    from repro.core import (
        ClusterEngine,
        ClusterSpec,
        ExecutionSpec,
        FaultPlan,
        RetryPolicy,
    )

    rng = np.random.default_rng(0)

    def make():
        ctr = rng.normal(size=(64, d)) * 20
        return ctr[rng.integers(64, size=n)] + rng.normal(size=(n, d))

    datasets = [make() for _ in range(b)]
    spec = ClusterSpec(k=k, seeder="fastkmeans++", seed=0)
    fault_plan = FaultPlan(seed=0, solve_failure_rate=0.25,
                           match="fastkmeans++/device")
    done_at: dict = {}
    t0 = _time.perf_counter()
    with ClusterEngine(spec, ExecutionSpec(backend="device"),
                       fault_plan=fault_plan,
                       retry=RetryPolicy(max_attempts=3)) as engine:
        submitted_at, tickets = [], []
        for ds in datasets:
            submitted_at.append(_time.perf_counter())
            ticket = engine.submit(ds, deadline=600.0)
            ticket.add_done_callback(
                lambda t: done_at.setdefault(t, _time.perf_counter()))
            tickets.append(ticket)
        failures = sum(t.exception() is not None for t in tickets)
        stats = engine.stats()
    wall_s = _time.perf_counter() - t0
    latencies = sorted(done_at[t] - s
                       for t, s in zip(tickets, submitted_at))
    terminal = stats["completed"] + stats["failed"] + stats["cancelled"]
    record = {
        "n": n, "d": d, "k": k, "requests": b,
        "solve_failure_rate": 0.25,
        "injected_faults": fault_plan.stats()["injected"],
        "goodput": stats["completed"] / b,
        "failures": failures,
        "stranded": stats["submitted"] - terminal,
        "retries": stats["retries"],
        "fallback_served": stats["fallback_served"],
        "short_circuited": stats["short_circuited"],
        "deadline_expired": stats["deadline_expired"],
        "latency_p50_s": float(np.percentile(latencies, 50)),
        "latency_p99_s": float(np.percentile(latencies, 99)),
        "wall_s": wall_s,
        "health": stats["health"],
    }
    rows = [
        (f"robustness.goodput[b={b},n={n}]", 0.0,
         f"goodput={record['goodput']:.3f} with "
         f"{record['injected_faults']} injected faults "
         f"({record['retries']} retries, "
         f"{record['fallback_served']} fallback-served)"),
        (f"robustness.latency_p50[b={b},n={n}]",
         record["latency_p50_s"] * 1e6, "submit-to-done"),
        (f"robustness.latency_p99[b={b},n={n}]",
         record["latency_p99_s"] * 1e6,
         "retry/fallback detours live in the p99/p50 spread"),
    ]
    return rows, record


def bench_serving(smoke: bool = False):
    """Continuous batching vs one-request-per-solve (ISSUE 8 acceptance).

    Replays ONE seeded open-loop Poisson arrival trace of mixed-(n, k, d)
    clustering traffic through two serving paths: a plain `ClusterEngine`
    (the PR-7 serving core — one stacked-solve dispatch per request) and
    the `ClusterFrontend` (hold-and-batch coalescing of compatible
    requests into stacked `fit_batch` lanes).  Both paths see identical
    arrival offsets and identical datasets, and every jit program either
    path can hit (solo per class; stacked per lane key at every
    power-of-two lane width up to ``max_batch``) is warmed before the
    timed window, so the measured quantity is steady-state serving
    throughput, not compile.  The fastkmeans++ seeder is used for the
    same reason as `bench_pipeline`: the rejection schedule's vmapped
    `lax.switch` cannot run stacked under interpret-mode CI.

    Records requests/sec, p50/p99 submit-to-done latency, mean lane
    occupancy and coalesce rate into the "serving" section of
    ``BENCH_seeding.json``; the CI gate (`check_regression.py`) requires
    coalescing to sustain >= 2x the one-request-per-solve requests/sec
    at no worse than serving-p99-slack times the baseline p99, with a
    minimum coalesce rate — the ISSUE 8 acceptance row.
    """
    import time as _time

    from repro.core import ClusterEngine, ClusterSpec, ExecutionSpec
    from repro.serving.frontend import ClusterFrontend

    n_requests = 48 if smoke else 96
    rate_hz = 400.0                 # open-loop: saturates the solo path
    max_batch = 8
    # Mixed n/k/d traffic: three lane keys across two shape buckets.  The
    # first two classes share (spec, d, bucket) and so coalesce together.
    classes = [
        dict(n=300, d=8, k=4),      # bucket 1024 - lane key A
        dict(n=900, d=8, k=4),      # bucket 1024 - lane key A (coalesces)
        dict(n=1300, d=8, k=4),     # bucket 2048 - lane key B
        dict(n=500, d=12, k=8),     # bucket 1024 - lane key C (k, d differ)
    ]
    rng = np.random.default_rng(8)
    arrivals = np.cumsum(rng.exponential(1.0 / rate_hz, size=n_requests))
    which = rng.integers(len(classes), size=n_requests)
    exe = ExecutionSpec(backend="device")
    specs = {c["k"]: ClusterSpec(k=c["k"], seeder="fastkmeans++", seed=0)
             for c in classes}

    def make(c):
        ctr = rng.normal(size=(8, c["d"])) * 20
        return (ctr[rng.integers(8, size=c["n"])]
                + rng.normal(size=(c["n"], c["d"])))

    datasets = [make(classes[i]) for i in which]
    warm_ds = [make(c) for c in classes]

    def replay(submit):
        """Drive the seeded trace; per-request latency via done-callbacks."""
        done: dict = {}
        tickets, sub_at = [], []
        t0 = _time.perf_counter()
        for off, ds, ci in zip(arrivals, datasets, which):
            now = _time.perf_counter() - t0
            if off > now:
                _time.sleep(off - now)
            sub_at.append(_time.perf_counter())
            t = submit(ds, classes[ci]["k"])
            t.add_done_callback(
                lambda tk: done.setdefault(tk, _time.perf_counter()))
            tickets.append(t)
        for t in tickets:
            t.result(timeout=600)
        wall = _time.perf_counter() - t0
        lats = sorted(done[t] - s for t, s in zip(tickets, sub_at))
        return wall, lats

    def _section(wall, lats):
        return {
            "wall_s": wall,
            "req_per_s": n_requests / wall,
            "latency_p50_s": float(np.percentile(lats, 50)),
            "latency_p99_s": float(np.percentile(lats, 99)),
        }

    # -- baseline: one solve dispatch per request ---------------------------
    with ClusterEngine(specs[4], exe, retain_prepared=False) as beng:
        for c, ds in zip(classes, warm_ds):     # warm each class's solo jit
            plan = beng.plan_for(specs[c["k"]])
            plan.fit_prepared(plan.prepare_data(ds)).block_until_ready()
        base_wall, base_lat = replay(
            lambda ds, k: beng.submit(ds, cluster=specs[k]))
    baseline = _section(base_wall, base_lat)

    # -- frontend: hold-and-batch coalescing over the same trace ------------
    feng = ClusterEngine(specs[4], exe, validate_inputs=False,
                         retain_prepared=False)
    with feng:
        for ci in (0, 2, 3):                    # one class per lane key
            plan = feng.plan_for(specs[classes[ci]["k"]])
            bp = 1
            while bp <= max_batch:              # every stacked lane width
                plan.fit_batch(
                    datasets=[warm_ds[ci]] * bp).block_until_ready()
                bp *= 2
        with ClusterFrontend(engine=feng, max_batch=max_batch,
                             max_wait_ms=8.0) as fe:
            fe_wall, fe_lat = replay(lambda ds, k: fe.submit(ds, k=k))
            st = fe.stats()
    frontend = _section(fe_wall, fe_lat)
    frontend.update(
        lanes=st["lanes"],
        mean_lane_occupancy=st["mean_lane_occupancy"],
        coalesce_rate=st["coalesce_rate"],
        flush_reasons={k[len("flush_"):]: v for k, v in st.items()
                       if k.startswith("flush_")},
    )
    record = {
        "requests": n_requests, "arrival_rate_hz": rate_hz,
        "max_batch": max_batch, "classes": classes,
        "baseline": baseline, "frontend": frontend,
        "speedup_req_per_s": frontend["req_per_s"] / baseline["req_per_s"],
        "p99_ratio_vs_baseline": (frontend["latency_p99_s"]
                                  / max(baseline["latency_p99_s"], 1e-12)),
    }
    rows = [
        (f"serving.baseline[b={n_requests}]",
         baseline["latency_p99_s"] * 1e6,
         f"one-request-per-solve: {baseline['req_per_s']:.1f} req/s"),
        (f"serving.frontend[b={n_requests}]",
         frontend["latency_p99_s"] * 1e6,
         f"coalesced: {frontend['req_per_s']:.1f} req/s, "
         f"occupancy={frontend['mean_lane_occupancy']:.2f}, "
         f"coalesce_rate={frontend['coalesce_rate']:.2f}"),
        (f"serving.speedup[b={n_requests}]", 0.0,
         f"req_per_s_speedup={record['speedup_req_per_s']:.2f}x "
         f"p99_ratio={record['p99_ratio_vs_baseline']:.2f}"),
    ]
    return rows, record


def bench_serving_net(smoke: bool = False):
    """Wire-transport overhead and tenant fairness (ISSUE 9 acceptance).

    Replays one seeded open-loop Poisson trace of two coalescible
    request classes through the SAME warmed engine twice: once via an
    in-process `ClusterFrontend` (the bench_serving fast path) and once
    over the `repro.serving.net` loopback RPC (`ClusterClient` ->
    `ClusterServer` sharing a second frontend on that engine, with a
    two-tenant `TenantScheduler` installed).  Both replays see identical
    arrival offsets, datasets and stacked-lane programs, so the wire
    numbers isolate what the transport adds: framing, socket hops, and
    result serialisation — not solve time and not compile.

    Records wire req/s, p50/p99 submit-to-done latency, the added p99
    and its ratio vs in-process, the per-tenant Jain fairness index
    (equal-weight tenants alternating on the trace: fair scheduling
    means near-equal median queue waits, J -> 1), and the server's
    queue_wait / solve / network attribution into
    ``BENCH_seeding.json["serving"]["net"]``.  CI gates the p99
    overhead ratio (`check_regression.py --net-max-p99-overhead`) and a
    fairness floor.
    """
    import threading as _threading
    import time as _time

    from repro.core import ClusterEngine, ClusterSpec, ExecutionSpec
    from repro.serving.frontend import ClusterFrontend
    from repro.serving.net import (
        ClusterClient, ClusterServer, TenantPolicy, TenantScheduler)

    n_requests = 32 if smoke else 64
    rate_hz = 400.0
    max_batch = 8
    # Two classes sharing one lane key (bucket 1024) so both paths
    # coalesce identically; tenants alternate with EQUAL weights, so a
    # fair scheduler shows near-equal per-tenant queue waits.
    classes = [dict(n=300, d=8), dict(n=900, d=8)]
    tenants = ("bulk", "batch")
    spec = ClusterSpec(k=4, seeder="fastkmeans++", seed=0)
    rng = np.random.default_rng(9)
    arrivals = np.cumsum(rng.exponential(1.0 / rate_hz, size=n_requests))
    which = rng.integers(len(classes), size=n_requests)

    def make(c):
        ctr = rng.normal(size=(8, c["d"])) * 20
        return (ctr[rng.integers(8, size=c["n"])]
                + rng.normal(size=(c["n"], c["d"])))

    datasets = [make(classes[i]) for i in which]
    exe = ExecutionSpec(backend="device")
    feng = ClusterEngine(spec, exe, validate_inputs=False,
                         retain_prepared=False)
    with feng:
        plan = feng.plan_for(spec)              # warm every lane width
        bp = 1
        while bp <= max_batch:
            plan.fit_batch(datasets=[datasets[0]] * bp).block_until_ready()
            bp *= 2

        def replay(submit):
            """Drive the trace open-loop; done-stamps via waiter threads."""
            done: dict = {}
            handles, sub_at, waiters = [], [], []
            t0 = _time.perf_counter()
            for i, (off, ds) in enumerate(zip(arrivals, datasets)):
                now = _time.perf_counter() - t0
                if off > now:
                    _time.sleep(off - now)
                sub_at.append(_time.perf_counter())
                h, wait = submit(ds, i)
                handles.append(h)

                def _stamp(h=h, wait=wait):
                    wait(h)
                    done[h] = _time.perf_counter()

                w = _threading.Thread(target=_stamp, daemon=True)
                w.start()
                waiters.append(w)
            for w in waiters:
                w.join(timeout=600)
            wall = _time.perf_counter() - t0
            lats = sorted(done[h] - s for h, s in zip(handles, sub_at))
            return {"wall_s": wall, "req_per_s": n_requests / wall,
                    "latency_p50_s": float(np.percentile(lats, 50)),
                    "latency_p99_s": float(np.percentile(lats, 99))}

        # Alternate timed replays of both paths and keep each path's
        # best rep (min-p99, the noise-robust statistic used across this
        # harness): the p99 of one short trace is nearly its max, so a
        # single rep on a shared CI runner measures scheduler jitter,
        # not transport overhead.  One untimed warm replay first pays
        # the residual prepare/compile warmup.
        reps = 3 if smoke else 5
        sched = TenantScheduler({t: TenantPolicy(weight=1.0)
                                 for t in tenants})
        fe2 = ClusterFrontend(engine=feng, max_batch=max_batch,
                              max_wait_ms=8.0, admission=sched)
        with ClusterFrontend(engine=feng, max_batch=max_batch,
                             max_wait_ms=8.0) as fe, \
                fe2, ClusterServer(frontend=fe2, port=0) as srv, \
                ClusterClient(*srv.address, read_timeout=600) as cl:
            replay(lambda ds, i: (                  # untimed warmup
                fe.submit(ds), lambda t: t.result(timeout=600)))
            inproc_reps, wire_reps = [], []
            for _ in range(reps):
                inproc_reps.append(replay(lambda ds, i: (
                    fe.submit(ds), lambda t: t.result(timeout=600))))
                wire_reps.append(replay(lambda ds, i: (
                    cl.submit(ds, tenant=tenants[i % len(tenants)]),
                    lambda rid: cl.result(rid, timeout=600))))
            inproc = min(inproc_reps, key=lambda r: r["latency_p99_s"])
            wire = min(wire_reps, key=lambda r: r["latency_p99_s"])
            st = srv.stats()

    waits = [float(rec["queue_wait"].get("p50") or 0.0)
             for rec in st.get("tenants", {}).values()]
    sq = sum(w * w for w in waits)              # Jain's fairness index
    fairness = ((sum(waits) ** 2 / (len(waits) * sq)) if sq > 0 else 1.0)
    record = {
        "requests": n_requests, "arrival_rate_hz": rate_hz,
        "max_batch": max_batch, "tenants": list(tenants),
        "inproc": inproc, "wire": wire,
        "req_per_s": wire["req_per_s"],
        "added_p99_s": wire["latency_p99_s"] - inproc["latency_p99_s"],
        "p99_overhead_ratio": (wire["latency_p99_s"]
                               / max(inproc["latency_p99_s"], 1e-12)),
        "fairness_index": float(fairness),
        "per_tenant": st.get("tenants", {}),
        "breakdown": st.get("net", {}).get("breakdown", {}),
    }
    rows = [
        (f"serving.net.wire[b={n_requests}]",
         wire["latency_p99_s"] * 1e6,
         f"loopback: {wire['req_per_s']:.1f} req/s, "
         f"p99_overhead={record['p99_overhead_ratio']:.2f}x "
         f"(+{record['added_p99_s'] * 1e3:.1f}ms)"),
        (f"serving.net.fairness[b={n_requests}]", 0.0,
         f"jain={record['fairness_index']:.3f} over "
         f"{len(waits)} equal-weight tenants"),
    ]
    return rows, record


def bench_streaming(smoke: bool = False, n=1 << 16, d=16, k=8,
                    batch_n=2048):
    """Incremental extend-then-refit vs re-prepare-then-fit (ISSUE 10).

    Grows ONE n=2^16 stream by `batch_n`-row batches two ways: the
    streaming path pays `ClusterPlan.extend` (frozen-scale quantise,
    incremental code/key encode, leaf-weight scatter — no re-prepare)
    plus a solve-only refit; the baseline re-prepares the concatenated
    dataset from scratch (full multi-tree embedding + LSH keys) and
    fits.  Both paths run the device rejection seeder on identical data
    and warmed jit programs (an untimed first round pays the streaming
    path's one-time capacity growth and both paths' compiles).

    The gated quantity (`check_regression.py --extend-beats-reprep`) is
    the per-round *incremental work* ratio — `extend` vs `prepare_data`
    — because that is what incrementality replaces; the solve-only
    refit is common to both paths and is recorded separately.  The
    end-to-end round latencies are recorded too, but NOT gated: off-TPU
    the interpret-mode solve dominates wall-clock and the streaming
    path solves at its capacity-padded shape bucket (2x the rows right
    after a growth), so end-to-end a from-scratch prepare can look
    competitive on CI while on hardware — where the solve is fast and
    the O(n d log Delta) host prepare dominates — the incremental path
    wins by the same prepare ratio gated here.

    Also records drift-reseed quality: a `StreamingController` ingests
    distribution-shifted batches until the cost-ratio EMA trips the
    `DriftPolicy` threshold; the gate requires >= 1 reseed to fire and
    the post-reseed cost to stay within a factor of a from-scratch fit
    on the same (drifted) live set.
    """
    from repro.core import (
        ClusterPlan,
        ClusterSpec,
        DriftPolicy,
        ExecutionSpec,
        StreamingController,
        clustering_cost,
    )

    rng = np.random.default_rng(0)
    ctr = rng.normal(size=(64, d)) * 20

    def draw(m, centers=ctr):
        return (centers[rng.integers(len(centers), size=m)]
                + rng.normal(size=(m, d)))

    timed = 2 if smoke else 4
    base = draw(n)
    batches = [draw(batch_n) for _ in range(timed + 1)]
    spec = ClusterSpec(k=k, seeder="rejection", seed=0,
                       options={"resolution": 0.05}, quantize=False)
    exe = ExecutionSpec(backend="device")

    # -- incremental: one stream, extend + solve-only refit per batch -------
    plan = ClusterPlan(spec, exe)
    t0 = time.perf_counter()
    prep = plan.prepare_streaming(base)
    stream_prepare_s = time.perf_counter() - t0
    plan.fit_prepared(prep).block_until_ready()
    # Untimed warm round: pays the one-time capacity growth (the stream
    # crosses its shape bucket here) and the grown solve program's trace.
    plan.extend(batches[0], prepared=prep)
    plan.fit_prepared(prep, seed=1).block_until_ready()
    ext_times, ext_refit_times = [], []
    for i, b in enumerate(batches[1:], start=2):
        t0 = time.perf_counter()
        plan.extend(b, prepared=prep)
        t1 = time.perf_counter()
        plan.fit_prepared(prep, seed=i).block_until_ready()
        ext_times.append(t1 - t0)
        ext_refit_times.append(time.perf_counter() - t1)
    stream_rebuilds = prep.streaming.rebuilds
    plan.forget(prep)

    # -- baseline: re-prepare the concatenated dataset from scratch ---------
    plan2 = ClusterPlan(spec, exe)
    acc = np.concatenate([base, batches[0]])
    pd = plan2.prepare_data(acc)                    # untimed warm round
    plan2.fit_prepared(pd, seed=1).block_until_ready()
    plan2.forget(pd)
    rep_times, rep_fit_times = [], []
    for i, b in enumerate(batches[1:], start=2):
        acc = np.concatenate([acc, b])
        t0 = time.perf_counter()
        pd = plan2.prepare_data(acc)
        t1 = time.perf_counter()
        plan2.fit_prepared(pd, seed=i).block_until_ready()
        rep_times.append(t1 - t0)
        rep_fit_times.append(time.perf_counter() - t1)
        plan2.forget(pd)

    extend_s = min(ext_times)
    reprep_s = min(rep_times)
    speedup = reprep_s / max(extend_s, 1e-12)

    # -- drift-reseed quality on a distribution shift -----------------------
    dn, dd, dk = 2048, 8, 8
    c_old = rng.normal(size=(dk, dd)) * 10
    c_new = -c_old + rng.normal(size=(dk, dd)) * 10
    dbase = c_old[rng.integers(dk, size=dn)] + rng.normal(size=(dn, dd))
    dplan = ClusterPlan(
        ClusterSpec(k=dk, seeder="rejection", seed=0,
                    options={"resolution": 0.05}, quantize=False), exe)
    ctrl = StreamingController(dplan, dbase,
                               drift=DriftPolicy(threshold=1.25, ema=0.5))
    history = []
    for _ in range(8):
        batch = (c_new[rng.integers(dk, size=512)]
                 + rng.normal(size=(512, dd)))
        history.append(ctrl.ingest(batch))
        if ctrl.reseeds:
            break
    live = ctrl.prepared.streaming.live_points()
    fresh_plan = ClusterPlan(dplan.cluster, exe)
    fresh_plan.prepare(live)
    fresh_cost = float(clustering_cost(
        live, np.asarray(fresh_plan.fit().centers, dtype=np.float64)))
    post_cost = ctrl.cost_now()
    quality_ratio = post_cost / max(fresh_cost, 1e-12)
    dplan.forget(ctrl.prepared)

    record = {
        "n": n, "d": d, "k": k, "batch_n": batch_n,
        "timed_batches": timed,
        "stream_prepare_s": stream_prepare_s,
        "extend_s": extend_s,
        "reprepare_s": reprep_s,
        "extend_speedup": speedup,
        "stream_refit_s": min(ext_refit_times),
        "reprepare_refit_s": min(rep_fit_times),
        "round_extend_refit_s": min(
            e + r for e, r in zip(ext_times, ext_refit_times)),
        "round_reprepare_fit_s": min(
            p + f for p, f in zip(rep_times, rep_fit_times)),
        "stream_rebuilds": stream_rebuilds,
        "drift": {
            "ingests": len(history),
            "reseeds": ctrl.reseeds,
            "peak_ratio": max(h["ratio"] for h in history),
            "post_reseed_cost": post_cost,
            "fresh_fit_cost": fresh_cost,
            "post_reseed_cost_ratio_vs_fresh": quality_ratio,
        },
    }
    rows = [
        (f"streaming.extend[n={n},b={batch_n}]", extend_s * 1e6,
         f"incremental mutation ({stream_rebuilds} rebuild(s)); "
         f"solve-only refit {min(ext_refit_times) * 1e3:.0f}ms rides on "
         f"the capacity-padded bucket"),
        (f"streaming.reprepare[n={n},b={batch_n}]", reprep_s * 1e6,
         f"from-scratch prepare of the concatenated rows; "
         f"extend_speedup={speedup:.1f}x"),
        (f"streaming.drift_reseed[n={dn}]", 0.0,
         f"reseeds={ctrl.reseeds} after {len(history)} shifted ingest(s), "
         f"post-reseed cost {quality_ratio:.2f}x a fresh fit"),
    ]
    return rows, record


def bench_heap_update(ns=(1 << 14, 1 << 16, 1 << 18), tile=512, reps=20):
    """Per-open sample-structure update: O(n) rebuild vs incremental.

    Times exactly the work a device seeder pays per opened center to keep
    its sample structure consistent AFTER the weight sweep: the old path
    rebuilt a full flat heap (`SampleTreeJax.init`, O(n)); the new path
    scatters the sweeps' per-tile sums into the coarse heap
    (`TiledSampleTree.refresh`, O(T log T), T = n/tile) — sublinear in n.
    """
    import jax
    import jax.numpy as jnp

    from repro.core.sample_tree import SampleTreeJax, TiledSampleTree

    rng = np.random.default_rng(0)
    rows, record = [], {}
    for n in ns:
        w = jnp.asarray(rng.uniform(0.5, 2.0, n).astype(np.float32))
        st = SampleTreeJax(n)
        rebuild = jax.jit(st.init)
        # Min over reps (same statistic as bench_adaptive_batch): the
        # regression gate compares growth *ratios* across artifacts, and
        # the mean is dominated by scheduler noise at the ~50us small-n
        # end — exactly where a noise spike most distorts the ratio.
        dt_rebuild = min(
            _timeit(lambda: jax.block_until_ready(rebuild(w)),
                    reps=1, warmup=2 if r == 0 else 0)[0]
            for r in range(reps))
        ts = TiledSampleTree(n, tile=tile)
        coarse = ts.init(w)
        tsums = ts.tile_sums(w) * 0.9       # every tile touched (worst case)
        refresh = jax.jit(ts.refresh)
        dt_inc = min(
            _timeit(lambda: jax.block_until_ready(refresh(coarse, tsums)),
                    reps=1, warmup=2 if r == 0 else 0)[0]
            for r in range(reps))
        record[str(n)] = {
            "rebuild_s": dt_rebuild,
            "incremental_s": dt_inc,
            "speedup": dt_rebuild / max(dt_inc, 1e-12),
        }
        rows.append((f"heap_update.rebuild[n={n}]", dt_rebuild * 1e6, ""))
        rows.append((f"heap_update.incremental[n={n}]", dt_inc * 1e6,
                     f"speedup_vs_rebuild={dt_rebuild / max(dt_inc, 1e-12):.1f}x"))
    return rows, {"tile": tile, "per_open": record}


def write_bench_json(seed_results, heap_update, adaptive_batch, plan_refit,
                     pipeline, robustness, serving, streaming, *,
                     smoke: bool):
    """BENCH_seeding.json: the cross-PR perf-trajectory artifact."""
    import jax

    datasets = []
    for res in seed_results:
        base = res["algos"].get("kmeans++", {}).get("cost", {})
        algos = {}
        for algo, data in res["algos"].items():
            algos[algo] = {
                "seconds": {str(k): v for k, v in data["seconds"].items()},
                "prepare_seconds": {
                    str(k): v
                    for k, v in data.get("prepare_seconds", {}).items()
                },
                "solve_seconds": {
                    str(k): v
                    for k, v in data.get("solve_seconds", {}).items()
                },
                "cost": {str(k): v for k, v in data["cost"].items()},
                "cost_ratio_vs_kmeanspp": {
                    str(k): v / base[k]
                    for k, v in data["cost"].items() if base.get(k)
                },
            }
        datasets.append({"dataset": res["dataset"], "n": res["n"],
                         "d": res["d"], "ks": res["ks"], "algos": algos})
    payload = {
        "generated_by": "benchmarks/run.py" + (" --smoke" if smoke else ""),
        "backend": jax.default_backend(),
        "interpret_kernels": jax.default_backend() != "tpu",
        "num_devices": len(jax.devices()),
        "datasets": datasets,
        "heap_update_per_open": heap_update,
        "adaptive_batch": adaptive_batch,
        "plan_refit": plan_refit,
        "pipeline": pipeline,
        "robustness": robustness,
        "serving": serving,
        "streaming": streaming,
    }
    BENCH_JSON.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"wrote {BENCH_JSON}")


def bench_roofline():
    rows = []
    try:
        from benchmarks.roofline import analyze, load_cells

        for rec in load_cells("pod"):
            a = analyze(rec)
            if a is None:
                continue
            dom = max(a["t_compute"], a["t_memory"], a["t_collective"])
            rows.append((
                f"roofline.{a['arch']}.{a['shape']}",
                dom * 1e6,
                f"bound={a['bottleneck']};roofline={a['roofline_fraction']:.2f}",
            ))
    except Exception as e:  # artifacts may not exist yet
        rows.append(("roofline.unavailable", 0.0, repr(e)[:60]))
    return rows


def main(argv=None) -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized seeding run (CPU + device backends), "
                         "skipping the heavier microbenchmarks")
    ap.add_argument("--only", choices=["serving", "streaming"],
                    default=None,
                    help="re-run a single section and merge its record "
                         "into the existing BENCH_seeding.json (CI uses "
                         "`--only serving` and `--only streaming` as "
                         "named gate steps)")
    ap.add_argument("--transport", choices=["inproc", "net"],
                    default="inproc",
                    help="with `--only serving`: `net` re-measures just "
                         "the loopback wire transport (bench_serving_net) "
                         "and merges it as serving.net, leaving the "
                         "in-process record untouched")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    all_rows = []
    if args.only == "streaming":
        payload = json.loads(BENCH_JSON.read_text())
        print("# streaming: incremental extend vs re-prepare, drift reseed",
              flush=True)
        st_rows, streaming = bench_streaming(smoke=args.smoke)
        payload["streaming"] = streaming
        BENCH_JSON.write_text(json.dumps(payload, indent=2) + "\n")
        print(f"merged streaming section into {BENCH_JSON}")
        print("\nname,us_per_call,derived")
        for name, us, derived in st_rows:
            print(f"{name},{us:.1f},{derived}")
        return
    if args.only == "serving":
        payload = json.loads(BENCH_JSON.read_text())
        prior = payload.get("serving", {})
        if args.transport == "net":
            print("# serving.net: loopback wire transport vs in-process",
                  flush=True)
            sv_rows, net = bench_serving_net(smoke=args.smoke)
            prior["net"] = net
            payload["serving"] = prior
        else:
            print("# serving: continuous batching vs one-request-per-solve",
                  flush=True)
            sv_rows, serving = bench_serving(smoke=args.smoke)
            if "net" in prior:        # keep the wire subsection current
                serving["net"] = prior["net"]
            payload["serving"] = serving
        BENCH_JSON.write_text(json.dumps(payload, indent=2) + "\n")
        print(f"merged serving section into {BENCH_JSON}")
        print("\nname,us_per_call,derived")
        for name, us, derived in sv_rows:
            print(f"{name},{us:.1f},{derived}")
        return
    print("# seeding tables (paper tables 1-8, CI scale)", flush=True)
    seed_rows, seed_results = bench_seeding(smoke=args.smoke)
    all_rows += seed_rows
    print("# per-open heap update: rebuild vs incremental", flush=True)
    heap_rows, heap_update = bench_heap_update()
    all_rows += heap_rows
    print("# adaptive vs fixed candidate batching (n=2^16)", flush=True)
    ab_rows, adaptive_batch = bench_adaptive_batch()
    all_rows += ab_rows
    print("# plan/execute: prepare-once / refit-many", flush=True)
    pr_rows, plan_refit = bench_plan_refit()
    all_rows += pr_rows
    print("# pipeline: overlapped engine vs serial prepare+solve (n=2^16)",
          flush=True)
    pl_rows, pipeline = bench_pipeline()
    all_rows += pl_rows
    print("# robustness: goodput under a seeded FaultPlan", flush=True)
    rb_rows, robustness = bench_robustness()
    all_rows += rb_rows
    print("# serving: continuous batching vs one-request-per-solve",
          flush=True)
    sv_rows, serving = bench_serving(smoke=args.smoke)
    all_rows += sv_rows
    print("# serving.net: loopback wire transport vs in-process",
          flush=True)
    net_rows, serving["net"] = bench_serving_net(smoke=args.smoke)
    all_rows += net_rows
    print("# streaming: incremental extend vs re-prepare, drift reseed",
          flush=True)
    st_rows, streaming = bench_streaming(smoke=args.smoke)
    all_rows += st_rows
    if not args.smoke:
        print("# kernel microbenchmarks", flush=True)
        all_rows += bench_kernels()
        all_rows += bench_roofline()
    write_bench_json(seed_results, heap_update, adaptive_batch, plan_refit,
                     pipeline, robustness, serving, streaming,
                     smoke=args.smoke)
    print("\nname,us_per_call,derived")
    for name, us, derived in all_rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
