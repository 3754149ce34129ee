"""Reduction of a profiler trace (`.xplane.pb`) to device metrics.

What a TPU trace holds, as JAX 0.9's profiler writes it: one plane per
chip named ``/device:TPU:<i>`` whose line ``XLA Ops`` has one event per
HLO instruction executed, named by the instruction's HLO text
(``%tree_sep_update_pallas.33 = f32[1,311296]{...} custom-call(s32[16,
311296]{...}, ...)``) with its start and duration in nanoseconds; events
of control instructions (``while``, ``conditional``) enclose the events
of their bodies.  Host threads are lines of the plane ``/host:CPU``;
the benchmark's own `jax.profiler.TraceAnnotation` spans (``bench.*``)
appear there on the same clock.

* busy: the union of the ``XLA Ops`` intervals of a chip inside the
  window span, averaged over the chips used;
* per-op device time: each event's self time (its duration less that of
  the events it encloses), summed by instruction name without its
  numeric suffix, so a kernel's time is what its custom call took;
* per-call shapes: the dtypes and dims of a kernel call's results and
  operands, parsed from its HLO text, for the byte and operation counts
  of `kernels/<kernel>.py`;
* idle gaps: the stretches of the window in which no chip ran anything,
  each named by the innermost ``bench.*`` span open on the host at its
  middle.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

__all__ = ["WINDOW_SPAN", "TraceSummary", "reduce_dir", "reduce_profile",
           "parse_call", "DTYPE_BYTES"]

WINDOW_SPAN = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."

DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
               "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
               "f64": 8}
_SHAPE = re.compile(r"\b(" + "|".join(DTYPE_BYTES) + r")\[([0-9,]*)\]")
_OP_NAME = re.compile(r"^%?([A-Za-z_][A-Za-z0-9_\-]*?)(?:\.\d+)?(?:\s|=|$)")


def op_name(event_name: str) -> str:
    """``%tree_sep_update_pallas.33 = ...`` -> ``tree_sep_update_pallas``."""
    m = _OP_NAME.match(event_name)
    return m.group(1) if m else event_name


def _shapes(text: str) -> list:
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in _SHAPE.findall(text)]


def parse_call(event_name: str) -> dict:
    """Result and operand shapes of one HLO instruction's text:
    ``{"results": [(dtype, dims), ...], "operands": [...]}``.

    Operands are the shapes inside the opcode's own parentheses; the
    attributes after them (``operand_layout_constraints={...}`` repeats
    every operand shape) are not read."""
    _, _, rhs = event_name.partition(" = ")
    m = re.search(r"\b[a-z][a-z\-]*\(", rhs)
    if m is None:
        return {"results": _shapes(rhs), "operands": []}
    depth, end = 1, m.end()
    while end < len(rhs) and depth:
        depth += {"(": 1, ")": -1}.get(rhs[end], 0)
        end += 1
    return {"results": _shapes(rhs[:m.start()]),
            "operands": _shapes(rhs[m.end():end - 1])}


def nbytes(shapes: list) -> int:
    total = 0
    for dt, dims in shapes:
        size = DTYPE_BYTES[dt]
        for x in dims:
            size *= x
        total += size
    return total


@dataclasses.dataclass
class Op:
    seconds: float = 0.0          # self time, summed over calls
    calls: int = 0
    texts: collections.Counter = dataclasses.field(
        default_factory=collections.Counter)   # HLO text -> calls


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    ops: dict                      # op name -> Op
    gaps: list                     # (label, seconds), longest first
    chips: int

    @property
    def idle_share(self) -> float:
        return max(0.0, 1.0 - self.busy_s / self.window_s)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1].seconds)
        by_label = collections.Counter()
        for label, s in self.gaps:
            by_label[label] += s
        return {"device_ops": [[name, op.seconds] for name, op in ops[:top]],
                "idle_gaps": [[label, s]
                              for label, s in by_label.most_common(top)]}

    def kernel(self, name: str) -> Op:
        """The op whose name is `name` (the kernel's jitted wrapper, e.g.
        ``tree_sep_update_pallas``), or an empty one."""
        return self.ops.get(name, Op())


def _union(intervals: list) -> list:
    out = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return out


def _self_times(events: list) -> list:
    """(name, start, end, self) with enclosed events' time taken off."""
    events = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []
    for name, lo, hi in events:
        while stack and stack[-1][2] <= lo:
            stack.pop()
        if stack:
            stack[-1][3] -= min(hi, stack[-1][2]) - lo
        rec = [name, lo, hi, hi - lo]
        out.append(rec)
        stack.append(rec)
    return out


def reduce_profile(profile, *, chips: int) -> TraceSummary:
    """Reduce a `jax.profiler.ProfileData` (see the module docstring)."""
    spans, window = [], None
    device_events: dict = collections.defaultdict(list)
    for plane in profile.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        lo, hi = e.start_ns, e.start_ns + e.duration_ns
                        if e.name == WINDOW_SPAN:
                            window = (lo, hi)
                        else:
                            spans.append((lo, hi, e.name))
        elif plane.name.startswith(DEVICE_PREFIX):
            chip = int(plane.name[len(DEVICE_PREFIX):])
            if chip >= chips:
                continue
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_events[chip] += [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
    if window is None:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    if not device_events:
        raise ValueError("the trace has no device plane with XLA ops")
    w_lo, w_hi = window
    ops: dict = collections.defaultdict(Op)
    busy_ns, busy_all = 0.0, []
    for chip, events in device_events.items():
        inside = [(n, max(lo, w_lo), min(hi, w_hi)) for n, lo, hi in events
                  if hi > w_lo and lo < w_hi]
        for name, lo, hi, self_ns in _self_times(inside):
            op = ops[op_name(name)]
            op.seconds += self_ns * 1e-9 / len(device_events)
            op.calls += 1
            op.texts[name] += 1
        merged = _union([[lo, hi] for _, lo, hi in inside])
        busy_ns += sum(hi - lo for lo, hi in merged)
        busy_all += merged
    # Idle: no chip busy.
    gaps, cursor = [], w_lo
    for lo, hi in _union(busy_all) + [[w_hi, w_hi]]:
        if lo > cursor:
            mid = 0.5 * (cursor + lo)
            open_spans = [s for s in spans if s[0] <= mid < s[1]]
            label = (max(open_spans, key=lambda s: s[0])[2] if open_spans
                     else "no benchmark span")
            gaps.append((label, (lo - cursor) * 1e-9))
        cursor = max(cursor, hi)
    gaps.sort(key=lambda g: -g[1])
    return TraceSummary(window_s=(w_hi - w_lo) * 1e-9,
                        busy_s=busy_ns * 1e-9 / len(device_events),
                        ops=dict(ops), gaps=gaps, chips=len(device_events))


def reduce_dir(log_dir: str, *, chips: int) -> TraceSummary:
    """Reduce the newest `.xplane.pb` under a `jax.profiler.trace` dir."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return reduce_profile(ProfileData.from_file(paths[-1]), chips=chips)
