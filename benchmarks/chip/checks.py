"""What decides `correct`: the window's answers against the plain reference.

Each answer of the window is checked one by one: k indices, all distinct,
all rows of its point set, centers that are exactly those rows of its own
point set, served by the configuration's seeder and backend, and no two
answers for different seeds on one point set alike (a seeding replayed or
copied into another lane).  On a sample of the answers drawn from the
seed, the cost the program reported is compared with the float64 cost of
the same indices (`reference.seeding_cost`), and the seeding cost with
plain k-means++ (`reference.kmeanspp`) over a fixed set of reference seeds
on the same point sets.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import numpy as np

__all__ = ["Answer", "answer_faults", "cost_check", "derive_seed",
           "to_host"]


def derive_seed(seed: int, *stream: int) -> int:
    """A 32-bit seed for one stream of the run's `--seed` (any
    non-negative whole number, also beyond 32 bits)."""
    ss = np.random.SeedSequence([int(seed), *map(int, stream)])
    return int(ss.generate_state(1, np.uint32)[0])


@dataclasses.dataclass
class Answer:
    """One seeding the window asked for."""

    seed: int
    set_key: tuple                # which point set it seeds
    indices: Any = None           # (k,) row indices, None if it never came
    centers: Any = None           # (k, d) the centers the program returned
    cost: Any = None              # the seeding cost the program reported
    row: Optional[int] = None     # its row of a stacked result, if any
    served_by: Optional[str] = None
    latency_s: Optional[float] = None
    trials: Any = None            # per-center candidate counts
    error: Optional[str] = None
    t_done: Optional[float] = None  # host clock when it came back


def to_host(answers: list) -> None:
    """Brings each answer's arrays to the host, taking its row of a
    stacked result; run once the window has closed."""
    cache: dict = {}

    def host(x, row):
        if x is None:
            return None
        if id(x) not in cache:
            cache[id(x)] = np.asarray(x)
        out = cache[id(x)]
        return out if row is None else out[row]

    for a in answers:
        a.indices = host(a.indices, a.row)
        a.centers = host(a.centers, a.row)
        a.cost = host(a.cost, a.row)
        a.trials = host(a.trials, a.row)
        a.row = None


def answer_faults(answers: list, *, rows_of, k: int,
                  served_by: Optional[str]) -> list:
    """(answer, reason) for every answer that is wrong or never came.

    `rows_of(set_key)` gives a point set's rows as the program got them
    (float32); `served_by`, when set, is the only seeder/backend an answer
    may come from."""
    faults = []
    seen: dict = {}
    for a in answers:
        if a.error is not None or a.indices is None:
            faults.append((a, f"no answer: {a.error}"))
            continue
        idx = np.asarray(a.indices).reshape(-1)
        rows = rows_of(a.set_key)
        n = len(rows)
        if idx.size != k:
            faults.append((a, f"{idx.size} indices, expected {k}"))
        elif len(np.unique(idx)) != k:
            faults.append((a, f"{len(np.unique(idx))} distinct indices "
                              f"of {k}"))
        elif idx.min() < 0 or idx.max() >= n:
            faults.append((a, f"index outside [0, {n})"))
        elif served_by is not None and a.served_by != served_by:
            faults.append((a, f"served by {a.served_by!r}"))
        elif a.centers is None or not np.array_equal(
                np.asarray(a.centers), rows[idx]):
            faults.append((a, "centers are not its set's rows at its "
                              "indices"))
        else:
            key = (a.set_key, np.sort(idx).tobytes())
            other = seen.setdefault(key, a)
            if other is not a and other.seed != a.seed:
                faults.append((a, f"same centers as seed {other.seed}"))
    return faults


def cost_check(answers: list, *, points_dev_of, k: int, ref_seeds: list,
               log=print) -> tuple[float, float, float]:
    """(ratio, reference mean, cost gap) over `answers`.

    The ratio is the answers' mean float64 seeding cost over the mean cost
    of plain k-means++ on the same point sets: each set's k-means++ mean
    is taken over `ref_seeds` and weighted by how many of the answers seed
    that set.  The cost gap is the largest of |reported - float64| /
    float64 over the answers, the cost the program reported against the
    float64 cost of the same indices.
    """
    import reference

    by_set: dict = {}
    for a in answers:
        by_set.setdefault(a.set_key, []).append(a)
    cost_sum = ref_sum = gap = 0.0
    pairs = []
    for key, group in by_set.items():
        pts = points_dev_of(key)
        ref = np.mean([reference.seeding_cost(
            pts, reference.kmeanspp(pts, k, s)) for s in ref_seeds])
        ref_sum += ref * len(group)
        for a in group:
            c64 = reference.seeding_cost(pts, a.indices)
            pairs.append((float(a.cost), c64))
            cost_sum += c64
            gap = max(gap, abs(float(a.cost) - c64) / c64)
    count = len(answers)
    log(f"cost: {count} answers on {len(by_set)} point set(s), "
        f"{len(ref_seeds)} k-means++ seeds each; (reported, float64) "
        f"pairs {pairs!r}")
    return float(cost_sum / ref_sum), float(ref_sum / count), float(gap)
