"""A four-chip cell on four CPU devices, and the reference over four
devices against one; prints one JSON line of what it saw.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python _sharded_run.py <copy of the benchmark>
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import jax
import numpy as np

from _tiny import run

import kernel_check
import reference
from data import mixture
from repro.core.plan import ClusterPlan


def main(copy: str) -> None:
    placed, widths = [], []
    place = reference.place

    def recorded_place(points, devices):
        rows = place(points, devices)
        placed.append({"devices": len(rows.x.sharding.device_set),
                       "shape": list(rows.x.shape), "n": rows.n})
        return rows

    reference.place = recorded_place
    kernel_errors = kernel_check.kernel_errors

    def recorded_errors(ops, x64, **kw):
        widths.append(kw)
        return kernel_errors(ops, x64, **kw)

    kernel_check.kernel_errors = recorded_errors
    artifacts = []
    prepare_data = ClusterPlan.prepare_data

    def recorded_prepare_data(self, points):
        prep = prepare_data(self, points)
        art = prep.artifacts
        data = art[0] if isinstance(art, tuple) else art
        artifacts.append({"tuple": isinstance(art, tuple),
                          "h": data.codes_lo.shape[1],
                          "l": data.keys_lo.shape[0]})
        return prep

    ClusterPlan.prepare_data = recorded_prepare_data
    res = run(Path(copy), "tiny-sharded.reseed", seed=3000000017)
    reference.place = place

    # The reference over four devices against one, n a multiple of 4.
    x = mixture(4096, 16, 50, [4096]).astype(np.float32)
    one = reference.place(x, jax.devices()[:1])
    four = reference.place(x, jax.devices())
    pairs = []
    for s in (0, 1, 2):
        i1 = np.asarray(reference.kmeanspp(one, 16, s))
        i4 = np.asarray(reference.kmeanspp(four, 16, s))
        pairs.append({"same": bool(np.array_equal(i1, i4)),
                      "cost1": reference.seeding_cost(one, i1),
                      "cost4": reference.seeding_cost(four, i1)})
    # Pad rows are never drawn: n = 4,097 pads three zero rows on four
    # devices, far from every real row, so D^2 sampling would take them.
    far = mixture(4097, 16, 50, [4097]) + 1000.0
    padded = reference.place(far.astype(np.float32), jax.devices())
    drawn = max(int(np.asarray(reference.kmeanspp(padded, 16, s)).max())
                for s in range(8))
    print(json.dumps({"devices": len(jax.devices()), "result": res,
                      "placed": placed, "widths": widths,
                      "artifacts": artifacts, "pairs": pairs,
                      "padded_shape": list(padded.x.shape),
                      "largest_drawn": drawn}))


if __name__ == "__main__":
    main(sys.argv[1])
