"""Shared trace accounting for every jit seeding program.

`TRACE_COUNTS` is incremented *inside* the program bodies — code that only
executes while jax traces them — so each key counts real traces, never
calls.  Serving-grade invariant (ROADMAP): repeated fits with identical
static configuration must reuse the compiled program, i.e. leave every
counter untouched.  Tests assert exactly that, for the single-device
programs (keys ``"<seeder>/device"``) and the shard_map programs (bare
``"<seeder>"`` keys, kept for backward compatibility with the PR-3 tests).

Spans time the host side of the served path at its layer boundaries.
`span(name, **ids)` opens a `jax.profiler.TraceAnnotation` of the same
name, so a profiled run shows it on the host plane on the device trace's
clock, and on exit adds its duration and one count to a process-wide
running total; `add(name, seconds)` adds an interval measured across
threads, which no thread-scoped span can hold.  `span_totals()` is the
snapshot `ClusterEngine.stats()` reports under ``"spans"``.  The totals
are always on (two clock reads and one lock per span); spans belong in
host code only, never inside a jit body.
"""

from __future__ import annotations

import collections
import contextlib
import threading
import time

from jax.profiler import TraceAnnotation

__all__ = ["TRACE_COUNTS", "count_trace", "no_retrace", "RetraceError",
           "span", "add", "span_totals"]

TRACE_COUNTS: collections.Counter = collections.Counter()


def count_trace(name: str) -> None:
    """Record one trace of program `name` (call from inside the traced body)."""
    TRACE_COUNTS[name] += 1


class RetraceError(AssertionError):
    """A compiled program re-traced inside a `no_retrace()` block.

    Subclasses AssertionError: a retrace under the guard is a violated
    invariant, not an environmental failure, and existing
    ``pytest.raises(AssertionError)`` patterns keep working.
    """

    def __init__(self, deltas: dict):
        self.deltas = dict(deltas)
        detail = ", ".join(f"{k}: +{v}" for k, v in sorted(deltas.items()))
        super().__init__(
            f"unexpected jit trace(s) inside no_retrace() block: {detail}. "
            "Identical static configuration must reuse the compiled "
            "program — check for data-dependent statics, unhashable "
            "statics, or wrappers rebuilt per call."
        )


@contextlib.contextmanager
def no_retrace(*, watch: tuple = (), allow: tuple = ()):
    """Context manager turning unexpected traces into hard `RetraceError`s.

    Snapshots `TRACE_COUNTS` on entry and compares on exit: any counter
    that grew (over the union of before/after keys, so first-ever traces
    of a program count too) raises.  Run one warmup call *before* the
    block so the programs exist, then wrap the steady-state region::

        fit()                      # warmup: traces + compiles
        with no_retrace():
            for _ in range(100):
                fit()              # must all hit the program cache

    `watch` narrows the guard to counter names with any of the given
    prefixes; `allow` exempts names with any of the given prefixes
    (`allow` wins).  The exit check runs only on clean exit — an
    exception inside the block propagates unwrapped.
    """
    before = dict(TRACE_COUNTS)
    yield
    after = dict(TRACE_COUNTS)
    deltas = {}
    for name in set(before) | set(after):
        if watch and not any(name.startswith(p) for p in watch):
            continue
        if allow and any(name.startswith(p) for p in allow):
            continue
        grew = after.get(name, 0) - before.get(name, 0)
        if grew > 0:
            deltas[name] = grew
    if deltas:
        raise RetraceError(deltas)


_SPAN_LOCK = threading.Lock()
_SPAN_TOTALS: dict = {}            # name -> [seconds, count]


def add(name: str, seconds: float) -> None:
    """Add one interval of `seconds` to the running total of `name`."""
    with _SPAN_LOCK:
        rec = _SPAN_TOTALS.get(name)
        if rec is None:
            _SPAN_TOTALS[name] = [seconds, 1]
        else:
            rec[0] += seconds
            rec[1] += 1


class Span:
    """One timed span (see `span`); `seconds` is its duration once closed."""

    __slots__ = ("name", "seconds", "_annotation", "_t0")

    def __init__(self, name: str, ids: dict):
        self.name = name
        self.seconds = 0.0
        self._annotation = TraceAnnotation(name, **ids)

    def __enter__(self) -> "Span":
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        add(self.name, self.seconds)


def span(name: str, **ids) -> Span:
    """Context manager timing its body as span `name` (also when it raises).

    `ids` ride on the profiler event as metadata (``lane=`` an engine
    ticket index, ``rid=`` a wire request id), so the spans of one lane
    or request can be joined in a trace.
    """
    return Span(name, ids)


def span_totals() -> dict:
    """Snapshot of every span's running ``{"seconds", "count"}``."""
    with _SPAN_LOCK:
        return {name: {"seconds": rec[0], "count": rec[1]}
                for name, rec in _SPAN_TOTALS.items()}
