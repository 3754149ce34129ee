"""The program's spans as the benchmark reads them: gap names from a
trace, and the served readers on a tiny served run."""

from __future__ import annotations

import gzip
import math
from pathlib import Path
from types import SimpleNamespace as NS

import jax
import pytest

from _tiny import make_copy

import spans
import xplane

RECORDED = Path(__file__).resolve().parent / "data" / "reseed_k8.xplane.pb.gz"

SERVED_READERS = ("prepare_wait_s.served", "solver_stall_s.served",
                  "deliver_s.served", "fetch_s.served",
                  "prepare_embed_s.served", "prepare_lsh_s.served",
                  "dispatch_wait_s.served")


def _ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def _served_profile(solve_name="repro.plan.solve"):
    """A served window: the solve worker dispatched a lane in [0, 100) and
    waits for the next lane's prepare from 300; a prepare worker builds
    that lane's embedding; a client waits throughout.  The device runs
    [0, 300) and [900, 1000)."""
    client = NS(name="python", events=[
        _ev("bench.window", 0, 1000), _ev("bench.client.wait", 0, 1000)])
    prepare = NS(name="python", events=[
        _ev("repro.engine.prepare#lane=4#", 200, 700),
        _ev("repro.prepare.embed", 250, 500)])
    solver = NS(name="python", events=[
        _ev(solve_name + "#lane=3#", 0, 100),
        _ev("repro.engine.await_prepare#lane=4#", 300, 600)])
    host = NS(name="/host:CPU", lines=[client, prepare, solver])
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
        _ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x)", 0, 300),
        _ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x)", 900, 100)])])
    return NS(planes=[host, dev])


def test_gap_is_named_by_the_thread_that_feeds_the_chip():
    gaps = spans.idle_gaps(_served_profile(), chips=1)
    # [300, 900): the solve worker (line of the latest solve span) is in
    # await_prepare at the middle, not the prepare worker's embed span
    # nor the client's wait, which `xplane` alone would name.
    assert gaps == [("repro.engine.await_prepare", pytest.approx(600e-9))]
    assert [g[0] for g in xplane.reduce_profile(
        _served_profile(), chips=1).gaps] == ["bench.client.wait"]


def test_without_a_solve_span_the_bench_rule_holds():
    gaps = spans.idle_gaps(_served_profile("repro.other"), chips=1)
    assert gaps == [("bench.client.wait", pytest.approx(600e-9))]


def test_trace_me_metadata_is_stripped():
    assert spans.base_name("repro.net.fetch#rid=7,lane=3#") == \
        "repro.net.fetch"
    assert spans.base_name("bench.refit") == "bench.refit"
    names = {s[2] for s in spans.host_spans(_served_profile())}
    assert names == {"bench.client.wait", "repro.engine.prepare",
                     "repro.prepare.embed", "repro.plan.solve",
                     "repro.engine.await_prepare"}


def test_recorded_trace_keeps_its_gap_labels():
    from jax.profiler import ProfileData

    profile = ProfileData.from_serialized_xspace(
        gzip.decompress(RECORDED.read_bytes()))
    old = xplane.reduce_profile(profile, chips=1).gaps
    new = spans.idle_gaps(profile, chips=1)
    assert [g[0] for g in new] == [g[0] for g in old]
    assert [g[1] for g in new] == pytest.approx([g[1] for g in old])
    assert {g[0] for g in new} <= {"bench.refit", "bench.block_until_ready",
                                   "no benchmark span"}


def test_readers_find_nothing_in_a_program_without_spans():
    from registry import Registry

    stats = {"lanes": 2, "completed": 2, "engine": {},
             "net": {"results_sent": 2}}
    run = NS(window={"stats_before": stats, "stats_after": dict(
        stats, lanes=4, completed=4, net={"results_sent": 4})})
    reg = Registry()
    for name in SERVED_READERS:
        assert reg.module("metrics", name).read(run) is None, name


def test_served_readers_on_a_tiny_served_run(tmp_path):
    from registry import Registry

    reg = Registry(make_copy(tmp_path))
    work = reg.workload("tiny.tiny_served")
    driver = reg.module("traffic", "closed_loop").Driver(
        reg.config(work["config"]), reg.traffic(work["traffic"]), 5,
        lambda *a: None, jax.devices()[:1])
    driver.setup()
    try:
        win = driver.window(1.0)
    finally:
        driver.close()
    run = NS(registry=reg, window=win)
    for name in SERVED_READERS:
        value = reg.module("metrics", name).read(run)
        assert value is not None and math.isfinite(value) and value >= 0, \
            name


def test_served_metrics_are_listed_for_fresh8_alone():
    from registry import Registry

    reg = Registry()
    served = {m["name"] for m in reg.per_layer("pq-sift-m8-k256.fresh8")}
    assert set(SERVED_READERS) <= served
    for name in SERVED_READERS:
        entry = next(m for m in reg.spec["per_layer"] if m["name"] == name)
        assert entry["workloads"] == ["pq-sift-m8-k256.fresh8"], name
        assert entry["source"] == "program_counter", name
        assert callable(reg.module("metrics", name).read), name
    for cell in ("kddcup-k500.reseed", "pq-sift-m8-k256.reseed"):
        assert not served & {m["name"] for m in reg.per_layer(cell)}, cell
