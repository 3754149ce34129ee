#!/usr/bin/env python3
"""Smoke test of the clustering service on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded path on four chips

With no option it runs, in one process and in this order:

1. ``device``  — fails unless JAX's first device is a TPU; it never falls
   back to the CPU or to interpret mode.
2. ``kernels`` — the Pallas kernels of the main path (`pairwise_argmin`,
   `d2_update`, `tree_sep_update`, `lsh_bucket_accept`), compiled for the
   chip at KDD-Cup width, against float64 NumPy references with the
   tolerances below.
3. ``served``  — the paper's Algorithm 4 (`rejection`, backend `device`)
   through the served path: `ClusterClient` → `ClusterServer` →
   `ClusterFrontend` → `ClusterEngine` → `ClusterPlan`, on two full-size
   KDD-Cup-shaped point sets (311,029 x 74) with two seeds each, so each
   dataset's two requests coalesce into one stacked lane.  Every request
   must come back with k unique indices, served by ``rejection/device``
   with no fallback and no retry, and the mean cost over the seeds must
   be within `COST_BOUND` of the faithful CPU `rejection` seeder's.

``--chips 4`` runs only the four-chip phase: `rejection` on the
``sharded`` backend over a 4-device mesh and on the ``device`` backend on
the first chip, on one full-size Census-shaped point set (2,458,285 x 68)
with the same seeds.  The points must be split four ways, and both mean
costs must be within `COST_BOUND_KMEANSPP` of CPU k-means++ (the faithful
CPU rejection seeder is too slow at this n for the time limit).

The readings printed on the way (compile seconds, warm seconds per
request, peak device memory) are smoke readings, not benchmark numbers.
Any failed phase exits 1 without a result line; on success the last line
of stdout is ``{"ok": true, "device": {"platform": ..., "kind": ...,
"count": ...}}``.
"""

from __future__ import annotations

import argparse
import concurrent.futures as cf
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

K = 25                       # the paper's k for its tables
SEEDS = (0, 1)
KDD_DATA_SEEDS = (0, 1)      # two KDD-Cup-shaped datasets
CENSUS_DATA_SEED = 2

# Served-cost bound: mean over seeds against the faithful CPU seeder (the
# +-20% agreement the repository's verification recipe uses, averaged).
COST_BOUND = 1.2
# Against CPU k-means++ (four-chip phase): rejection sampling trades a
# constant factor of quality for speed (Lemma 5.3), measured at about
# 1.1-1.2x on these datasets; the bound leaves room for seed noise.
COST_BOUND_KMEANSPP = 1.5

# Kernel tolerances.  The distance kernels expand |x|^2 - 2 x.c + |c|^2 in
# f32, whose rounding error scales with |x|^2 + |c|^2, not with the
# distance: TOL_EXPANSION is relative to that scale.  A bf16 MXU pass
# (8 mantissa bits) would err by ~1e-3 of it.  The other kernels are
# checked relative to the reference value.
TOL_EXPANSION = 1e-5
TOL_RELATIVE = 1e-5
KERNEL_K = 512               # centers in the kernel checks
KERNEL_B = 1024              # LSH candidates
KERNEL_H = 24                # tree code planes
KERNEL_L = 15                # LSH tables

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(RuntimeError):
    pass


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Sums JAX's backend-compile durations (cache reads included)."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == BACKEND_COMPILE_EVENT:
                self.seconds += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


# ---------------------------------------------------------------------------
# float64 references
# ---------------------------------------------------------------------------

def _d2_f64(x, c, chunk=32768):
    """Yields (rows, (rows, k) float64 squared distances) chunk by chunk."""
    import numpy as np

    c_sq = (c * c).sum(1)
    for lo in range(0, len(x), chunk):
        xs = x[lo:lo + chunk]
        d2 = (xs * xs).sum(1)[:, None] - 2.0 * xs @ c.T + c_sq[None, :]
        yield slice(lo, lo + len(xs)), np.maximum(d2, 0.0)


def cost_f64(x, centers) -> float:
    return float(sum(d2.min(1).sum() for _, d2 in _d2_f64(x, centers)))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_device(chips: int):
    import jax

    devs = jax.devices()
    dev = devs[0]
    _require(dev.platform == "tpu",
             f"JAX's first device is {dev.platform!r}, not a TPU")
    _require(len(devs) >= chips,
             f"{chips} chips asked for, JAX sees {len(devs)}")
    print(f"device: {dev.device_kind} x{len(devs)} "
          f"(platform {dev.platform})", flush=True)
    return dev, len(devs)


def phase_kernels(x64):
    """Each main-path kernel on the chip at KDD-Cup width vs float64."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops

    _require(not ops.default_interpret(), "kernels would run interpreted")
    rng = np.random.default_rng(0)
    n, d = x64.shape
    x = jnp.asarray(x64, jnp.float32)
    xf = np.asarray(x, np.float64)          # the inputs the chip sees
    x_sq = (xf * xf).sum(1)
    errs = {}

    # pairwise_argmin: min and argmin over KERNEL_K centers.
    ci = rng.choice(n, KERNEL_K, replace=False)
    cf_ = xf[ci]
    scale = x_sq + (cf_ * cf_).sum(1).max()   # |x|^2 + |c|^2 bound per row
    d2, idx = jax.block_until_ready(ops.pairwise_argmin(x, x[ci]))
    d2, idx = np.asarray(d2, np.float64), np.asarray(idx)
    e_min = e_arg = 0.0
    for rows, ref in _d2_f64(xf, cf_):
        s = scale[rows]
        best = ref.min(1)
        e_min = max(e_min, float((np.abs(d2[rows] - best) / s).max()))
        picked = ref[np.arange(len(best)), idx[rows]]
        e_arg = max(e_arg, float(((picked - best) / s).max()))
    errs["pairwise_argmin.min"] = e_min
    errs["pairwise_argmin.argmin_gap"] = e_arg
    _require(e_min <= TOL_EXPANSION and e_arg <= TOL_EXPANSION,
             f"pairwise_argmin off the float64 reference: min {e_min:.3g}, "
             f"argmin gap {e_arg:.3g} (tolerance {TOL_EXPANSION:g} of "
             f"|x|^2 + |c|^2)")

    # d2_update: one center's D^2 sweep (differences, no cancellation).
    w = rng.uniform(0.0, 2.0 * np.median(d2), size=n)
    out = np.asarray(jax.block_until_ready(
        ops.d2_update(x, x[ci[0]], jnp.asarray(w, jnp.float32))), np.float64)
    wf = np.asarray(np.float32(w), np.float64)
    ref = np.minimum(wf, ((xf - xf[ci[0]]) ** 2).sum(1))
    e = float((np.abs(out - ref) / np.maximum(ref, 1e-30)).max())
    errs["d2_update"] = e
    _require(e <= TOL_RELATIVE, f"d2_update off by {e:.3g} (relative)")

    # tree_sep_update: codes agreeing with the center's on a prefix of
    # heights, so every separation level occurs.
    h = KERNEL_H
    c_lo = rng.integers(-2 ** 31, 2 ** 31, size=h, dtype=np.int64)
    c_hi = rng.integers(-2 ** 31, 2 ** 31, size=h, dtype=np.int64)
    agree = rng.integers(0, h + 1, size=n)
    same = np.arange(h)[:, None] < agree[None, :]
    lo = np.where(same, c_lo[:, None], c_lo[:, None] ^ 1).astype(np.int32)
    hi = np.where(same, c_hi[:, None], c_hi[:, None] + 1).astype(np.int32)
    tscale, levels = 2.0 * np.sqrt(d) * 500.0, h + 1
    w = rng.uniform(0.0, tscale ** 2, size=n).astype(np.float32)
    out = np.asarray(jax.block_until_ready(ops.tree_sep_update(
        jnp.asarray(lo), jnp.asarray(hi), jnp.asarray(c_lo.astype(np.int32)),
        jnp.asarray(c_hi.astype(np.int32)), jnp.asarray(w),
        scale=float(tscale), num_levels=levels, block_n=512)), np.float64)
    dist = tscale * (2.0 ** (-agree.astype(np.float64)) - 2.0 ** (1 - levels))
    ref = np.minimum(w.astype(np.float64), dist * dist)
    e = float((np.abs(out - ref) / np.maximum(ref, tscale ** 2 * 1e-12)).max())
    errs["tree_sep_update"] = e
    _require(e <= TOL_RELATIVE, f"tree_sep_update off by {e:.3g}")

    # lsh_bucket_accept: KERNEL_B candidates against KERNEL_K centers, of
    # which the first `live` are open; keys collide now and then.
    b, l, live, c2 = KERNEL_B, KERNEL_L, KERNEL_K - 12, 4.0
    qi = rng.choice(n, b, replace=False)
    qk = rng.integers(0, 64, size=(2, l, b)).astype(np.int32)
    ck = rng.integers(0, 64, size=(2, l, KERNEL_K)).astype(np.int32)
    mtd2 = rng.uniform(0.0, 2.0 * np.median(d2), size=b).astype(np.float32)
    mtd2[::7] = 0.0
    d2_min, p = jax.block_until_ready(ops.lsh_bucket_accept(
        *(jnp.asarray(a) for a in (qk[0], qk[1])), x[qi],
        *(jnp.asarray(a) for a in (ck[0], ck[1])), x[ci],
        jnp.asarray(mtd2), live, c2=c2))
    d2_min, p = np.asarray(d2_min, np.float64), np.asarray(p, np.float64)
    collide = ((qk[0][:, :, None] == ck[0][:, None, :])
               & (qk[1][:, :, None] == ck[1][:, None, :])).any(0)
    collide[:, live:] = False
    full = next(_d2_f64(xf[qi], cf_))[1]
    hit = collide.any(1)
    ref = np.where(collide, full, np.inf).min(1)
    s = scale[qi]
    _require(np.array_equal(d2_min < ops.LSH_MISS / 2, hit),
             "lsh_bucket_accept: bucket misses differ from the reference")
    e = float((np.abs(d2_min[hit] - ref[hit]) / s[hit]).max())
    m = mtd2.astype(np.float64)
    ok = m > 0
    p_ref = np.where(ok, ref / np.maximum(c2 * m, 1e-30), 0.0)
    sel = ok & hit
    ep = float((np.abs(p[sel] - p_ref[sel])
                / (s[sel] * TOL_EXPANSION / (c2 * m[sel])
                   + TOL_RELATIVE * p_ref[sel])).max())
    errs["lsh_bucket_accept.d2"] = e
    errs["lsh_bucket_accept.p"] = ep
    _require(e <= TOL_EXPANSION, f"lsh_bucket_accept d2 off by {e:.3g}")
    _require(ep <= 1.0, f"lsh_bucket_accept p off by {ep:.3g} x tolerance")
    _require(bool((p[~ok] == 0.0).all()) and bool((p[ok & ~hit] > 1.0).all()),
             "lsh_bucket_accept: covered points must never accept and "
             "bucket misses must always accept")
    print("kernels: max errors " + ", ".join(
        f"{k} {v:.3g}" for k, v in errs.items()), flush=True)
    return errs


def _cpu_reference(points, seeder: str, seeds) -> float:
    """Mean float64 cost of a faithful CPU seeder over `seeds`."""
    import numpy as np

    from repro.core import ClusterPlan, ClusterSpec, ExecutionSpec

    plan = ClusterPlan(ClusterSpec(k=K, seeder=seeder),
                       ExecutionSpec(backend="cpu"))
    prep = plan.prepare_data(points)
    return float(np.mean([
        cost_f64(points, np.asarray(plan.fit_prepared(prep, seed=s).centers))
        for s in seeds]))


def phase_served(datasets, clock: CompileClock):
    """Algorithm 4 on `device` through the served path; one lane per
    dataset, its seeds coalesced into that lane."""
    import numpy as np

    from repro.core import ClusterSpec, ExecutionSpec
    from repro.launch.cluster_serve import drive
    from repro.serving.net import ClusterServer

    lanes = []
    with ClusterServer(ClusterSpec(k=K, seeder="rejection"),
                       ExecutionSpec(backend="device"),
                       max_batch=len(SEEDS),
                       max_wait_ms=600_000.0) as srv:   # flush when full
        for name, pts in datasets:
            c0, t0 = clock.seconds, time.perf_counter()
            outcomes, stats = drive(srv, [(pts, s, None) for s in SEEDS],
                                    timeout=900.0)
            lanes.append((name, pts, outcomes, time.perf_counter() - t0,
                          clock.seconds - c0))
    engine = stats["engine"]
    _require(engine["fallback_served"] == 0 and engine["retries"] == 0,
             f"engine fell back or retried: fallback_served="
             f"{engine['fallback_served']} retries={engine['retries']}")
    ratios = {}
    for name, pts, outcomes, wall, compile_s in lanes:
        costs = []
        for seed, res in zip(SEEDS, outcomes):
            _require(not isinstance(res, BaseException),
                     f"{name} seed {seed}: request failed: {res!r}")
            served_by = res.extras.get("served_by")
            _require(served_by == "rejection/device",
                     f"{name} seed {seed}: served by {served_by!r}")
            _require(len(np.unique(res.indices)) == K,
                     f"{name} seed {seed}: {len(np.unique(res.indices))} "
                     f"unique indices, expected {K}")
            _require(res.extras.get("lane_size") == len(SEEDS),
                     f"{name} seed {seed}: lane of "
                     f"{res.extras.get('lane_size')}, expected {len(SEEDS)}")
            costs.append(cost_f64(pts, pts[res.indices]))
        ref = _cpu_reference(pts, "rejection", SEEDS)
        ratios[name] = float(np.mean(costs)) / ref
        print(f"served: {name} lane of {len(SEEDS)}: {wall:.2f}s wall, "
              f"{compile_s:.2f}s compiling; mean cost "
              f"{np.mean(costs):.6g} vs CPU rejection {ref:.6g} "
              f"(ratio {ratios[name]:.4f}, bound {COST_BOUND})", flush=True)
        _require(ratios[name] <= COST_BOUND,
                 f"{name}: mean cost {ratios[name]:.4f}x the CPU seeder's")
    (_, _, _, cold, cold_c), (_, _, _, warm, warm_c) = lanes[0], lanes[-1]
    print(f"served: compile {cold_c:.2f}s in the first lane, {warm_c:.2f}s "
          f"in the last; warm {warm / len(SEEDS):.2f}s per request "
          f"(host prepare included)", flush=True)
    return ratios


def phase_sharded(clock: CompileClock):
    """`rejection` on `sharded` over four chips and on `device` on the
    first, on one Census-shaped point set with the same seeds."""
    import numpy as np

    from benchmarks.datasets import make_dataset
    from repro.core import ClusterPlan, ClusterSpec, ExecutionSpec
    from repro.distributed.sharding import points_axis
    from repro.launch.mesh import make_seeding_mesh

    pts = make_dataset("census", scale=1.0, seed=CENSUS_DATA_SEED)
    mesh = make_seeding_mesh()
    _require(mesh.devices.size == 4,
             f"the seeding mesh has {mesh.devices.size} devices, not 4")
    cluster = ClusterSpec(k=K, seeder="rejection")
    plans = {"sharded": ClusterPlan(cluster, ExecutionSpec(
                 backend="sharded", mesh=mesh)),
             "device": ClusterPlan(cluster, ExecutionSpec(backend="device"))}
    t0 = time.perf_counter()
    with cf.ThreadPoolExecutor(len(plans)) as pool:
        preps = dict(zip(plans, pool.map(lambda p: p.prepare_data(pts),
                                         plans.values())))
    print(f"sharded: census {pts.shape[0]}x{pts.shape[1]} prepared for both "
          f"backends in {time.perf_counter() - t0:.1f}s", flush=True)

    data, _ = preps["sharded"].artifacts
    n_pad = data.points.shape[1]            # coordinate planes (d8, n_pad)
    _require(points_axis(mesh, n_pad) is not None,
             f"points_axis is None for n_pad={n_pad}: points replicated")
    shards = data.points.addressable_shards
    rows = sorted(sh.data.shape[1] for sh in shards)
    _require(len({sh.device for sh in shards}) == 4
             and rows == [n_pad // 4] * 4,
             f"points not split four ways: shard rows {rows}, n_pad {n_pad}")
    print(f"sharded: points split 4 ways, {n_pad // 4} padded rows per chip",
          flush=True)

    ref = _cpu_reference(pts, "kmeans++", SEEDS)
    ratios = {}
    for backend, plan in plans.items():
        costs, times = [], []
        for s in SEEDS:
            c0, t1 = clock.seconds, time.perf_counter()
            res = plan.fit_prepared(preps[backend], seed=s).to_numpy()
            times.append((time.perf_counter() - t1, clock.seconds - c0))
            idx = np.asarray(res.indices)
            _require(len(np.unique(idx)) == K,
                     f"{backend} seed {s}: {len(np.unique(idx))} unique "
                     f"indices, expected {K}")
            costs.append(cost_f64(pts, pts[idx]))
        ratios[backend] = float(np.mean(costs)) / ref
        print(f"sharded: {backend}: mean cost {np.mean(costs):.6g} vs CPU "
              f"k-means++ {ref:.6g} (ratio {ratios[backend]:.4f}, bound "
              f"{COST_BOUND_KMEANSPP}); solve seconds (compile) "
              + ", ".join(f"{t:.2f} ({c:.2f})" for t, c in times),
              flush=True)
        _require(ratios[backend] <= COST_BOUND_KMEANSPP,
                 f"{backend}: mean cost {ratios[backend]:.4f}x k-means++'s")
    return ratios


def run(chips: int) -> dict:
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"compile cache: {cache} ({entries} entries at start)", flush=True)
    clock = CompileClock()
    dev, count = phase_device(chips)
    if chips == 4:
        phase_sharded(clock)
    else:
        from benchmarks.datasets import make_dataset

        datasets = [(f"kddcup/{s}", make_dataset("kddcup", scale=1.0, seed=s))
                    for s in KDD_DATA_SEEDS]
        phase_kernels(datasets[0][1])
        phase_served(datasets, clock)
    stats = dev.memory_stats() or {}
    print(f"device: peak memory {stats.get('peak_bytes_in_use', 0) / 2**30:.3f}"
          f" GiB on the first chip; {clock.seconds:.2f}s compiling, "
          f"{clock.cache_hits} cache hits", flush=True)
    return {"ok": True, "device": {"platform": dev.platform,
                                   "kind": dev.device_kind, "count": count}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded four-chip phase")
    args = ap.parse_args(argv)
    try:
        result = run(args.chips)
    except Exception as e:  # noqa: BLE001 — any failure fails the smoke
        traceback.print_exc()
        print(f"chip_smoke: FAIL: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
