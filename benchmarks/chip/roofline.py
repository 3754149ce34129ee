"""A kernel's share of its roofline, from the trace and its shapes.

For each distinct call of the kernel in the traced window (its HLO text
names its result and operand shapes), `kernels/<kernel>.py` gives the
operations and bytes the call needs.  The least time the chip could take
for it is the larger of operations over the peak rate and bytes over the
peak HBM bandwidth (`peaks.json`, keyed by `device_kind`; a device not in
the table is an error).  A kernel whose matrix products run at a float32
precision (its module's `PRECISION`) takes that many bfloat16 passes at
the bfloat16 peak, so its operations count once per pass.  The share is
the sum of those least times over the kernel's device time in the trace.

A share is per chip.  On a trace of several chips the calls of every chip
are counted, and `xplane` keeps each op's time as the mean over the
chips, so the least times are divided by that mean times the number of
chips: the device time of all chips together.  A call that each of four
chips runs in t seconds reads what the same call on one chip reads.
"""

from __future__ import annotations

import json
from pathlib import Path

import xplane

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}")
    return table[device_kind]


def share(run, kernel: str):
    """Percent of the roofline, or None where the window ran no call."""
    mod = run.registry.module("kernels", kernel)
    op = run.trace.kernel(mod.TRACE_NAME)
    if op.calls == 0 or op.seconds <= 0.0:
        return None
    peak = peaks(run.device_kind)
    passes = peak["matmul_passes"][getattr(mod, "PRECISION", "DEFAULT")]
    least = 0.0
    for text, calls in op.texts.items():
        ops, nbytes = mod.cost(xplane.parse_call(text))
        least += calls * max(passes * ops / peak["flops_per_s"],
                             nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / (op.seconds * run.trace.chips)
