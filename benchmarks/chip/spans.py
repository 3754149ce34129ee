"""The program's spans, as the benchmark reads them.

The program (`repro.core.tracing`) keeps a running total of every span
and counter, which `ClusterEngine.stats()` reports under ``"spans"``
(``{name: {"seconds", "count"}}``); a served window records the server's
stats before and after it, and `delta` takes their difference.  A program
without spans reports none, and `delta` then returns None.

In a profile each span is a host event named by the span, on the line of
the thread that ran it.  `gap_label` names an idle stretch of the device
by the thread that feeds the chip: the line of the latest
``repro.plan.solve`` span that started before the gap, and on it the
innermost ``repro.*`` span open at the gap's middle.  Where there is none
it falls back to the innermost ``bench.*`` span open on any line, the
rule `xplane.reduce_profile` applies.  `idle_gaps` reduces a profile to
its gaps named this way.
"""

from __future__ import annotations

import xplane

__all__ = ["delta", "base_name", "host_spans", "gap_label", "idle_gaps"]

SOLVE_SPAN = "repro.plan.solve"
PROGRAM_PREFIX = "repro."
NO_SPAN = "no benchmark span"


def delta(run, name: str):
    """(seconds, count) that span or counter `name` gained in the traced
    window, or None where the program reports no such total."""
    totals = [run.window[k].get("engine", {}).get("spans")
              for k in ("stats_before", "stats_after")]
    if any(t is None for t in totals) or name not in totals[1]:
        return None
    before = totals[0].get(name, {"seconds": 0.0, "count": 0})
    after = totals[1][name]
    return (after["seconds"] - before["seconds"],
            after["count"] - before["count"])


def base_name(name: str) -> str:
    """A TraceMe name without its metadata: ``a.b#lane=3,rid=7#`` ->
    ``a.b``."""
    return name.split("#", 1)[0]


def host_spans(profile) -> list:
    """``(start_ns, end_ns, name, line)`` of every ``bench.*`` and
    ``repro.*`` event on the host plane but the window span; `line` is the
    index of the event's line (one line per thread)."""
    out = []
    for plane in profile.planes:
        if plane.name != xplane.HOST_PLANE:
            continue
        for line_no, line in enumerate(plane.lines):
            for e in line.events:
                name = base_name(e.name)
                if name == xplane.WINDOW_SPAN or not name.startswith(
                        (xplane.SPAN_PREFIX, PROGRAM_PREFIX)):
                    continue
                out.append((e.start_ns, e.start_ns + e.duration_ns, name,
                            line_no))
    return out


def _innermost(spans: list, at: float):
    open_spans = [s for s in spans if s[0] <= at < s[1]]
    return max(open_spans, key=lambda s: s[0])[2] if open_spans else None


def gap_label(spans: list, lo: float, hi: float) -> str:
    """The name of the idle gap ``[lo, hi)`` (see the module docstring)."""
    mid = 0.5 * (lo + hi)
    solves = [s for s in spans if s[2] == SOLVE_SPAN and s[0] <= lo]
    if solves:
        line = max(solves, key=lambda s: s[0])[3]
        label = _innermost([s for s in spans if s[3] == line
                            and s[2].startswith(PROGRAM_PREFIX)], mid)
        if label is not None:
            return label
    label = _innermost([s for s in spans
                        if s[2].startswith(xplane.SPAN_PREFIX)], mid)
    return NO_SPAN if label is None else label


def idle_gaps(profile, *, chips: int) -> list:
    """``(label, seconds)`` of every stretch of the window span in which
    no chip ran anything, longest first, named by `gap_label`."""
    window, busy = None, []
    for plane in profile.planes:
        if plane.name == xplane.HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if base_name(e.name) == xplane.WINDOW_SPAN:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
        elif plane.name.startswith(xplane.DEVICE_PREFIX):
            if int(plane.name[len(xplane.DEVICE_PREFIX):]) >= chips:
                continue
            busy += [[e.start_ns, e.start_ns + e.duration_ns]
                     for line in plane.lines if line.name == xplane.OPS_LINE
                     for e in line.events]
    if window is None:
        raise ValueError(f"the trace has no {xplane.WINDOW_SPAN!r} span")
    w_lo, w_hi = window
    spans = host_spans(profile)
    gaps, cursor = [], w_lo
    inside = [[max(lo, w_lo), min(hi, w_hi)] for lo, hi in busy
              if hi > w_lo and lo < w_hi]
    for lo, hi in xplane._union(inside) + [[w_hi, w_hi]]:
        if lo > cursor:
            gaps.append((gap_label(spans, cursor, lo), (lo - cursor) * 1e-9))
        cursor = max(cursor, hi)
    gaps.sort(key=lambda g: -g[1])
    return gaps
