#!/usr/bin/env python3
"""The chip benchmark: one cell of `BENCHMARK.json`, one run, one process.

    python benchmarks/chip/bench.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

A run fails (exit code other than 0, no result line) unless JAX's first
device is a TPU and it sees as many chips as the cell asks for; it never
falls back to the CPU.  It makes its point sets from `--seed`, prepares
and warms every shape the window uses (that is `setup_s`), then measures
for `--seconds` with the profiler off (`--trace 0`, the cell's end-to-end
metrics) or traces a shorter steady window (`--trace 1`, the cell's
per-layer metrics).  The window runs under `repro.core.no_retrace` and
the compiles inside it are counted and printed.  Once the window has
closed and the program's state is freed, the answers are checked against
the plain reference (`checks.py`): each answer's indices and centers, and
on a sample the reported cost against float64; the kernels the window ran
are checked against float64 at the cell's widths (`kernel_check.py`).
Each number compared
is printed beside its limit as the last lines of standard error and under
"checks" as the last key of the result line, the last line of standard
output.

JAX's persistent compilation cache lives at `.jax_cache/` in the
checkout, and the program is handed that directory through
`JAX_COMPILATION_CACHE_DIR`, so only the first run of a cell compiles.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
CACHE_DIR = REPO / ".jax_cache"

for _p in (str(HERE), str(REPO / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from registry import Registry  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
MISS_EVENT = "/jax/compilation_cache/cache_misses"


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


def log(*parts) -> None:
    print(*parts, flush=True)


class CompileCounter:
    """Counts JAX's traces, programs built (a backend compile or a read of
    the persistent cache) and the persistent cache's misses."""

    def __init__(self):
        import jax

        self.builds = self.traces = self.misses = 0
        self.build_s = 0.0

        def on_duration(event, duration, **_):
            if event == COMPILE_EVENT:
                self.builds += 1
                self.build_s += duration
            elif event == TRACE_EVENT:
                self.traces += 1

        def on_event(event, **_):
            if event == MISS_EVENT:
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> tuple:
        return self.builds, self.misses, self.traces


def require_chip(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX's first device is {devices[0].platform!r}, "
                     "not a TPU")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devices)}")
    return devices


def run_cell(args, reg: Registry, *, devices, t_start=T_START) -> dict:
    """Set-up, window, checks and metrics of one run; returns the result
    line as a dict.  `t_start` is when the run began (`setup_s` counts
    from it)."""
    import jax
    import numpy as np

    import checks
    import kernel_check
    import xplane
    from repro.core import no_retrace

    work = reg.workload(args.workload)
    config = reg.config(work["config"])
    cell = reg.cell(args.workload)
    traffic = reg.traffic(work["traffic"])
    used = devices[:work["chips"]]
    counter = CompileCounter()

    driver = reg.module("traffic", traffic["kind"]).Driver(
        config, traffic, args.seed, log, used)
    driver.setup()
    setup_s = time.perf_counter() - t_start
    log(f"setup: {setup_s:.3f}s ({counter.builds} programs built in "
        f"{counter.build_s:.2f}s, {counter.misses} of them compiled: not in "
        f"the persistent cache)")

    seconds = float(args.seconds)
    if args.trace:
        seconds = min(seconds, float(traffic["trace_seconds"]))
    before = counter.snapshot()
    tdir = None
    with no_retrace():
        if args.trace:
            tdir = tempfile.TemporaryDirectory(prefix="chipbench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            with jax.profiler.trace(tdir.name, profiler_options=opts):
                with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
                    win = driver.window(seconds)
        else:
            win = driver.window(seconds)
    builds, misses, traces = (a - b for a, b in
                              zip(counter.snapshot(), before))
    log(f"window: {win['seconds']:.3f}s, {len(win['answers'])} answers "
        f"of {win['attempted']} attempted; inside the window: {builds} "
        f"programs built, {misses} compiled, {traces} traces")

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    widths = driver.kernel_widths()
    driver.close()

    # -- correctness, once the window has closed and the program's state
    # is freed ------------------------------------------------------------
    t_check = time.perf_counter()
    answers = win["answers"]
    checks.to_host(answers)
    faults = checks.answer_faults(
        answers, rows_of=driver.rows, k=config["k"],
        served_by=f"{config['seeder']}/{config['backend']}")
    for a, why in faults[:10]:
        log(f"fault: seed {a.seed} set {a.set_key}: {why}")
    sample = driver.cost_sample(answers)
    ratio, gap = None, 1.0          # nothing to compare reads as off by 1
    if sample:
        ratio, ref_cost, gap = checks.cost_check(
            sample, points_dev_of=driver.points_dev, k=config["k"],
            ref_seeds=list(range(traffic["reference_seeds"])), log=log)
        log(f"cost: ratio {ratio!r} to k-means++ (mean reference cost "
            f"{ref_cost!r}); reported cost off float64 by at most {gap!r}; "
            f"{time.perf_counter() - t_check:.3f}s")
    driver.free_points_dev()
    from repro.kernels import ops

    kerr = kernel_check.kernel_errors(
        ops, driver.host_points(driver.first_set_key()), seed=args.seed,
        **widths)

    log(f"checks: {time.perf_counter() - t_check:.3f}s after the window")
    limits = cell["limits"]
    compared = {"bad_answers": float(len(faults)), "cost_gap": gap}
    compared.update({f"kernel.{k}": v for k, v in kerr.items()})
    check_line = {name: {"value": v, "limit": limits[name]}
                  for name, v in compared.items()}
    correct = all(v <= limits[name] for name, v in compared.items())

    # -- metrics -----------------------------------------------------------
    dev = used[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": int(win["attempted"]),
              "failed": len(faults)}
    if args.trace:
        summary = xplane.reduce_dir(tdir.name, chips=len(used))
        tdir.cleanup()
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        # What the per-layer readers see of the run.
        run = SimpleNamespace(registry=reg, window=win, trace=summary,
                              device_kind=dev.device_kind)
        metrics = {}
        for m in reg.per_layer(args.workload):
            value = reg.module("metrics", m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        result["metrics"] = metrics
        result["breakdown"] = summary.breakdown()
    else:
        values = dict(driver.metrics(win), setup_s=setup_s, cost_ratio=ratio)
        result["metrics"] = {
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in reg.end_to_end(args.workload)
            if values.get(m["name"]) is not None}
    result["device"] = device
    result["checks"] = check_line
    for name, c in check_line.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    return result


def configure_jax() -> None:
    """Points JAX's persistent compilation cache, and the program's, at
    the checkout's `.jax_cache/`."""
    CACHE_DIR.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # The directory is the benchmark's own: no size limit, so no LRU
    # eviction, whose bookkeeping files a size limit set in the
    # environment would otherwise demand of every entry.
    jax.config.update("jax_compilation_cache_max_size", -1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    try:
        reg = Registry(REPO)
        chips = reg.workload(args.workload)["chips"]
        configure_jax()
        devices = require_chip(chips)
        log(f"device: {devices[0].device_kind} x{len(devices)} (platform "
            f"{devices[0].platform}); compile cache {CACHE_DIR}")
        result = run_cell(args, reg, devices=devices)
    except Exception as e:  # noqa: BLE001 — any failure fails the run
        traceback.print_exc()
        print(f"bench: FAIL: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
