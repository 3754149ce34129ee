"""Spans and counters of `repro.core.tracing`, alone and on the served path.

The totals are process-wide and always on, so every check reads the
difference of two `span_totals()` snapshots (or uses names no other code
records).
"""

import glob
import os
import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import ClusterSpec, ExecutionSpec
from repro.core.tracing import add, span, span_totals
from repro.serving.net import ClusterClient, ClusterServer

pytestmark = pytest.mark.timeout(600)


def _gained(before: dict, after: dict, name: str) -> tuple:
    b = before.get(name, {"seconds": 0.0, "count": 0})
    a = after.get(name, {"seconds": 0.0, "count": 0})
    return a["seconds"] - b["seconds"], a["count"] - b["count"]


def test_nested_spans_sum_their_own_times():
    before = span_totals()
    with span("test.outer") as outer:
        for _ in range(3):
            with span("test.inner", lane=1) as inner:
                time.sleep(0.01)
            assert inner.seconds >= 0.01
    after = span_totals()
    outer_s, outer_n = _gained(before, after, "test.outer")
    inner_s, inner_n = _gained(before, after, "test.inner")
    assert (outer_n, inner_n) == (1, 3)
    assert outer_s == outer.seconds
    assert 0.03 <= inner_s <= outer_s


def test_span_that_raises_still_closes_and_counts():
    before = span_totals()
    with pytest.raises(KeyError):
        with span("test.raises") as s:
            time.sleep(0.005)
            raise KeyError("body failed")
    seconds, count = _gained(before, span_totals(), "test.raises")
    assert count == 1 and seconds == s.seconds >= 0.005


def test_concurrent_spans_and_adds_sum_exactly():
    threads, per_thread = 8, 500
    before = span_totals()
    start = threading.Barrier(threads)

    def work():
        start.wait()
        for _ in range(per_thread):
            with span("test.threads"):
                pass
            add("test.threads.counter", 0.25)   # exact in binary

    pool = [threading.Thread(target=work) for _ in range(threads)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)     # switch threads as often as possible
    try:
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in pool)
    after = span_totals()
    assert _gained(before, after, "test.threads")[1] == threads * per_thread
    assert _gained(before, after, "test.threads.counter") == (
        0.25 * threads * per_thread, threads * per_thread)


def test_add_records_an_interval_and_a_count():
    before = span_totals()
    add("test.add", 1.5)
    add("test.add", 0.5)
    assert _gained(before, span_totals(), "test.add") == (2.0, 2)
    snap = span_totals()
    snap["test.add"]["count"] = -1              # a snapshot, not the table
    assert span_totals()["test.add"]["count"] >= 2


def test_span_lands_on_the_profilers_host_plane_with_its_ids(tmp_path):
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        with span("test.profiled", lane=3, rid=7):
            jax.numpy.ones(8).block_until_ready()
    path = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    events = [e for plane in ProfileData.from_file(path).planes
              if plane.name == "/host:CPU" for line in plane.lines
              for e in line.events if e.name.startswith("test.profiled")]
    assert len(events) == 1
    # The ids ride as TraceMe metadata: JAX's reader parses them off the
    # name (``test.profiled#lane=3,rid=7#``) into the event's stats.
    assert events[0].name.split("#")[0] == "test.profiled"
    assert dict(events[0].stats) == {"lane": 3, "rid": 7}


def _mixture(n, d=4, k_true=5, seed=0):
    rng = np.random.default_rng(seed)
    ctr = rng.normal(size=(k_true, d)) * 25
    return ctr[rng.integers(k_true, size=n)] + rng.normal(size=(n, d))


def _settled(client, before: dict, timeout: float = 30.0) -> dict:
    """The server's stats over the wire once every delivery has closed.

    A client holds its answer while the solve worker may still be inside
    the delivery that sent it: the span `repro.engine.callbacks` and the
    server's `results_sent` count it only after the frame went out.  So
    the stats are read again until both cover the four answers and every
    lane, or as they stand when `timeout` runs out."""
    deadline = time.monotonic() + timeout
    while True:
        after = client.stats()
        lanes = after["lanes"] - before["lanes"]
        sent = after["net"]["results_sent"] - before["net"]["results_sent"]
        delivered = _gained(before["engine"]["spans"],
                            after["engine"]["spans"],
                            "repro.engine.callbacks")[1]
        if (sent >= 4 and delivered >= lanes) or \
                time.monotonic() > deadline:
            return after
        time.sleep(0.05)


def test_served_path_counts_one_span_per_boundary():
    """Two closed-loop clients through a `ClusterServer` whose frontend
    forms lanes of up to two, on the device program."""
    spec = ClusterSpec(k=4, seeder="rejection", seed=1)
    with ClusterServer(spec, ExecutionSpec(backend="device"), max_batch=2,
                       max_wait_ms=50.0) as srv:
        clients = [ClusterClient(*srv.address) for _ in range(2)]
        before = srv.stats()

        def drive(j):
            for r in range(2):
                rid = clients[j].submit(_mixture(600, seed=10 * j + r),
                                        seed=r)
                clients[j].result(rid, timeout=300)

        pool = [threading.Thread(target=drive, args=(j,)) for j in range(2)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=400)
        assert not any(t.is_alive() for t in pool)
        after = _settled(clients[0], before)    # over the wire
        for c in clients:
            c.close()
    assert "solve_seconds" not in after["engine"]
    b, a = before["engine"]["spans"], after["engine"]["spans"]
    lanes = after["lanes"] - before["lanes"]
    done = after["completed"] - before["completed"]
    sent = after["net"]["results_sent"] - before["net"]["results_sent"]
    assert done == sent == 4 and 2 <= lanes <= 4
    for name in ("repro.engine.callbacks", "repro.engine.await_prepare",
                 "repro.engine.prepare", "repro.plan.solve",
                 "engine.prepare_queue", "engine.dispatch_wait"):
        assert _gained(b, a, name)[1] == lanes, name
    assert _gained(b, a, "repro.net.fetch")[1] == sent
    for name in ("repro.prepare.embed", "repro.prepare.lsh"):
        assert _gained(b, a, name)[1] == done, name
    for name in a:
        seconds, count = _gained(b, a, name)
        assert np.isfinite(seconds) and seconds >= 0.0 and count >= 0
    # `prepare_seconds` takes its value from the prepare span's clock.
    prepare_s = (after["engine"]["prepare_seconds"]
                 - before["engine"]["prepare_seconds"])
    assert prepare_s == pytest.approx(
        _gained(b, a, "repro.engine.prepare")[0], rel=1e-9)
