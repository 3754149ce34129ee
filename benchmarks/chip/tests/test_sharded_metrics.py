"""The four-chip cell's own readers: `collective_share.sharded` from the
trace's ops, `prepare_shard_s.sharded` from the program's span totals."""

from __future__ import annotations

import collections
import time
from types import SimpleNamespace as NS

import pytest

import _tiny  # noqa: F401  (puts the benchmark on sys.path)

import xplane
from registry import Registry


def _reader(name):
    return Registry().module("metrics", name)


def _op(seconds, *texts):
    return xplane.Op(seconds=seconds, calls=len(texts),
                     texts=collections.Counter(texts))


def test_collectives_are_found_by_opcode_and_by_name():
    share = _reader("collective_share.sharded")
    ops = {
        # An all-reduce that JAX named after the `psum` that made it.
        "psum": _op(0.02, "%psum.3 = f32[8]{0:T(128)} all-reduce(f32[8]{0} "
                          "%x), channel_id=1, to_apply=%add"),
        "all-gather": _op(0.01, "%all-gather.1 = s32[4,128]{1,0:T(4,128)} "
                                "all-gather(s32[1,128]{1,0} %y)"),
        "collective-permute-done": _op(0.005, "%collective-permute-done.2 = "
                                              "f32[8]{0} collective-permute-"
                                              "done(f32[8]{0} %z)"),
        "fusion": _op(0.5, "%fusion.2 = f32[8,128]{1,0:T(8,128)} fusion("
                           "f32[8,128]{1,0:T(8,128)} %w), kind=kLoop"),
        "reduce": _op(0.1, "%reduce.7 = f32[8]{0} reduce(f32[8,128]{1,0} %v,"
                           " f32[] %c), dimensions={1}, to_apply=%add"),
    }
    run = NS(trace=NS(ops=ops, window_s=2.0))
    assert share.read(run) == pytest.approx(100.0 * 0.035 / 2.0)
    assert share.opcode(ops["fusion"].texts.most_common(1)[0][0]) == "fusion"


def test_prepare_shard_seconds_are_the_span_total(monkeypatch):
    from repro.core import tracing

    reader = _reader("prepare_shard_s.sharded")
    before = reader.read(NS()) or 0.0
    with tracing.span("repro.prepare.shard", shard=0):
        time.sleep(0.02)
    assert reader.read(NS()) >= before + 0.02
    # A program without the span (one older than the shard-by-shard
    # prepare) gives nothing, and the result line leaves the metric out.
    monkeypatch.setattr(tracing, "span_totals", lambda: {})
    assert reader.read(NS()) is None
