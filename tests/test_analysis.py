"""repro.analysis: each rule fires on a minimal positive fixture, stays
quiet on the matching negative one, and the whole repo is finding-free
(the committed baseline is empty and must stay that way — fix or pragma,
don't baseline; see docs/analysis.md)."""

from pathlib import Path

import pytest

from repro.analysis import (
    all_rules,
    analyze_paths,
    analyze_sources,
    load_baseline,
)

REPO = Path(__file__).resolve().parents[1]


def _run(src: str, rule: str, path: str = "fixture.py"):
    return analyze_sources({path: src}, rules=[rule])


# ---------------------------------------------------------------------------
# rng-key-reuse
# ---------------------------------------------------------------------------

_RNG_POS = """
import jax

def body(i, state):
    key = state
    key, k1 = jax.random.split(key)
    a = jax.random.randint(k1, (), 0, 10)
    b = jax.random.uniform(k1)
    return key
"""

_RNG_NEG = """
import jax

def body(i, state):
    key = state
    key, k1, k2 = jax.random.split(key, 3)
    a = jax.random.randint(k1, (), 0, 10)
    b = jax.random.uniform(k2)
    return key
"""

_RNG_BRANCH_NEG = """
import jax
from jax import lax

def round_body(key):
    key, k_cand, k_u = jax.random.split(key, 3)

    def use_a():
        return jax.random.uniform(k_cand)

    def use_b():
        return jax.random.uniform(k_u)

    return lax.cond(True, use_a, use_b)
"""


def test_rng_reuse_fires_on_double_consumption():
    findings = _run(_RNG_POS, "rng-key-reuse")
    assert len(findings) == 1
    assert "k1" in findings[0].message


def test_rng_reuse_quiet_after_split():
    assert _run(_RNG_NEG, "rng-key-reuse") == []


def test_rng_reuse_ignores_per_branch_closures():
    """Keys consumed once per lax.cond branch closure are not reuse."""
    assert _run(_RNG_BRANCH_NEG, "rng-key-reuse") == []


# The serving-engine token-sampling shape: the root key is consumed via a
# method-call argument for the first draw and THEN split in a host loop —
# the split children share entropy with that first draw.
_RNG_SPLIT_AFTER_CONSUME_POS = """
import jax

def generate(self, logits, cache, n):
    key = jax.random.key(0)
    cur = self._sample(logits, key)
    out = []
    for i in range(n):
        out.append(cur)
        logits, cache = self._step(cur, cache)
        key, sub = jax.random.split(key)
        cur = self._sample(logits, sub)
    return out
"""

_RNG_SPLIT_BEFORE_USE_NEG = """
import jax

def generate(self, logits, cache, n):
    key = jax.random.key(0)
    key, sub = jax.random.split(key)
    cur = self._sample(logits, sub)
    out = []
    for i in range(n):
        out.append(cur)
        logits, cache = self._step(cur, cache)
        key, sub = jax.random.split(key)
        cur = self._sample(logits, sub)
    return out
"""


def test_rng_reuse_fires_on_split_after_consume():
    findings = _run(_RNG_SPLIT_AFTER_CONSUME_POS, "rng-key-reuse")
    assert len(findings) == 1
    assert "split before first use" in findings[0].message
    assert "key" in findings[0].message


def test_rng_reuse_quiet_on_linear_key_threading():
    assert _run(_RNG_SPLIT_BEFORE_USE_NEG, "rng-key-reuse") == []


# ---------------------------------------------------------------------------
# host-sync-in-jit
# ---------------------------------------------------------------------------

_SYNC_POS = """
import jax

@jax.jit
def f(x):
    y = x + 1
    return float(y)
"""

_SYNC_NEG = """
import jax

@jax.jit
def f(x):
    n = int(x.shape[0])      # shape metadata: host arithmetic, not a sync
    m = len(x)
    return x * (n + m)
"""


def test_host_sync_fires_on_traced_conversion():
    findings = _run(_SYNC_POS, "host-sync-in-jit")
    assert len(findings) == 1
    assert "float()" in findings[0].message


def test_host_sync_exempts_shape_metadata():
    assert _run(_SYNC_NEG, "host-sync-in-jit") == []


# ---------------------------------------------------------------------------
# jit-static-hashability
# ---------------------------------------------------------------------------

_HASH_POS = """
import dataclasses
import functools
import jax

@dataclasses.dataclass
class Mutable:
    x: int = 0

@functools.partial(jax.jit, static_argnames=("cfg",))
def f(points, cfg: Mutable):
    return points
"""

_HASH_NEG = """
import dataclasses
import functools
import jax

@dataclasses.dataclass(frozen=True)
class Frozen:
    x: int = 0

@functools.partial(jax.jit, static_argnames=("cfg",))
def f(points, cfg: Frozen | None):
    return points
"""

_HASH_LRU_POS = """
import functools

@functools.lru_cache(maxsize=None)
def build(shape: tuple, opts: dict):
    return shape
"""


def test_hashability_fires_on_mutable_dataclass_static():
    findings = _run(_HASH_POS, "jit-static-hashability")
    assert len(findings) == 1
    assert "not frozen" in findings[0].message


def test_hashability_resolves_dataclass_across_files():
    """The Project symbol table resolves annotations cross-module."""
    findings = analyze_sources(
        {
            "specs.py": ("import dataclasses\n"
                         "@dataclasses.dataclass\n"
                         "class Spec:\n"
                         "    x: int = 0\n"),
            "prog.py": ("import functools, jax\n"
                        "@functools.partial(jax.jit, "
                        "static_argnames=('spec',))\n"
                        "def f(pts, spec: 'Spec'):\n"
                        "    return pts\n"),
        },
        rules=["jit-static-hashability"],
    )
    assert len(findings) == 1 and findings[0].path == "prog.py"


def test_hashability_quiet_on_frozen_optional():
    assert _run(_HASH_NEG, "jit-static-hashability") == []


def test_hashability_fires_on_lru_cache_dict_param():
    findings = _run(_HASH_LRU_POS, "jit-static-hashability")
    assert len(findings) == 1
    assert "'dict'" in findings[0].message


# ---------------------------------------------------------------------------
# retrace-hazard
# ---------------------------------------------------------------------------

_RETRACE_LOOP_POS = """
import jax

def solve(problems):
    out = []
    for p in problems:
        f = jax.jit(lambda x: x * 2)
        out.append(f(p))
    return out
"""

_RETRACE_LOOP_NEG = """
import jax

_f = jax.jit(lambda x: x * 2)

def solve(problems):
    return [_f(p) for p in problems]
"""

_RETRACE_REBUILD_POS = """
from jax import lax

def seed(ts, weights, k):
    def body(i, state):
        coarse = ts.init(state)
        return coarse
    return lax.fori_loop(0, k, body, weights)
"""

_RETRACE_REBUILD_NEG = """
from jax import lax

def seed(ts, weights, k):
    coarse0 = ts.init(weights)        # O(T) preamble: outside the loop

    def body(i, coarse):
        return ts.refresh(coarse, coarse)
    return lax.fori_loop(0, k, body, coarse0)
"""

_RETRACE_STATIC_POS = """
import functools
import jax

@functools.partial(jax.jit, static_argnames=("cap",))
def solve(x, cap: int):
    return x[:cap]

def run(x, budget):
    return solve(x, cap=int(budget.mean()))
"""

_RETRACE_STATIC_NEG = """
import functools
import jax

@functools.partial(jax.jit, static_argnames=("cap",))
def solve(x, cap: int):
    return x[:cap]

def run(x):
    return solve(x, cap=int(x.shape[0] // 2))
"""


def test_retrace_fires_on_jit_in_loop():
    findings = _run(_RETRACE_LOOP_POS, "retrace-hazard")
    assert len(findings) == 1
    assert "loop body" in findings[0].message


def test_retrace_quiet_on_hoisted_jit():
    assert _run(_RETRACE_LOOP_NEG, "retrace-hazard") == []


def test_retrace_fires_on_init_inside_lax_body():
    findings = _run(_RETRACE_REBUILD_POS, "retrace-hazard")
    assert len(findings) == 1
    assert ".init" in findings[0].message


def test_retrace_quiet_on_preamble_init_and_refresh():
    assert _run(_RETRACE_REBUILD_NEG, "retrace-hazard") == []


def test_retrace_fires_on_data_dependent_static():
    findings = _run(_RETRACE_STATIC_POS, "retrace-hazard")
    assert len(findings) == 1
    assert "static 'cap'" in findings[0].message


def test_retrace_exempts_shape_derived_static():
    assert _run(_RETRACE_STATIC_NEG, "retrace-hazard") == []


# ---------------------------------------------------------------------------
# pallas-tile-shape  (scoped to kernels/)
# ---------------------------------------------------------------------------

_TILE_POS = """
from jax.experimental import pallas as pl

def op(x, block_n: int = 128):
    grid = (x.shape[0] // block_n,)
    return pl.pallas_call(lambda r, o: None, grid=grid,
                          out_shape=None)(x)
"""

_TILE_NEG = """
from jax.experimental import pallas as pl

def op(x, block_n: int = 128):  # autotune: lane width
    assert x.shape[0] % block_n == 0
    grid = (x.shape[0] // block_n,)
    return pl.pallas_call(lambda r, o: None, grid=grid,
                          out_shape=None)(x)
"""


def test_pallas_tiles_fires_in_kernels_dir():
    findings = _run(_TILE_POS, "pallas-tile-shape",
                    path="src/repro/kernels/fix.py")
    rules = sorted({(f.severity, f.rule) for f in findings})
    assert len(findings) == 2          # missing annotation + missing guard
    assert rules == [("error", "pallas-tile-shape"),
                     ("warning", "pallas-tile-shape")]


def test_pallas_tiles_quiet_when_annotated_and_guarded():
    assert _run(_TILE_NEG, "pallas-tile-shape",
                path="src/repro/kernels/fix.py") == []


# The layout Mosaic refused on a v5e: per-point vectors in rank-1 tiles.
_TILE_RANK1_POS = """
from jax.experimental import pallas as pl

def op(w, block_n: int = 512):  # autotune: row tile
    assert w.shape[0] % block_n == 0
    return pl.pallas_call(
        lambda r, o, t: None, grid=(w.shape[0] // block_n,),
        in_specs=[pl.BlockSpec((block_n,), lambda i: (i,))],
        out_specs=[pl.BlockSpec(block_shape=(block_n,),
                                index_map=lambda i: (i,)),
                   pl.BlockSpec((1,), lambda i: (i,))],
        out_shape=None)(w)
"""

_TILE_RANK1_NEG = """
from jax.experimental import pallas as pl

def op(w, block_n: int = 512):  # autotune: row tile
    n = w.shape[1]
    assert n % block_n == 0
    return pl.pallas_call(
        lambda r, o: None, grid=(n // block_n,),
        in_specs=[pl.BlockSpec((1, block_n), lambda i: (0, i)),
                  pl.BlockSpec((n,), lambda i: (0,))],
        out_specs=pl.BlockSpec((1, block_n), lambda i: (0, i)),
        out_shape=None)(w)
"""


@pytest.mark.parametrize("src,expected", [(_TILE_RANK1_POS, 3),
                                          (_TILE_RANK1_NEG, 0)])
def test_pallas_tiles_rank1_blocks(src, expected):
    """Rank-1 tile blocks (Mosaic's layout refusal) fire; (1, block)
    blocks and a whole-array rank-1 block do not."""
    findings = _run(src, "pallas-tile-shape", path="src/repro/kernels/f.py")
    assert len(findings) == expected
    assert all("rank-1" in f.message for f in findings)


def test_pallas_tiles_scoped_to_kernels():
    """The same source outside kernels/ is not this rule's business."""
    assert _run(_TILE_POS, "pallas-tile-shape",
                path="src/repro/core/fix.py") == []


# ---------------------------------------------------------------------------
# lock-discipline
# ---------------------------------------------------------------------------

_LOCK_POS = """
import threading

class Engine:
    def __init__(self):
        self._lock = threading.Lock()
        self._cancel = False

    def close(self):
        with self._lock:
            self._cancel = True

    def worker(self):
        if self._cancel:          # lock-free read of a guarded attr
            return
"""

_LOCK_NEG = """
import threading

class Engine:
    def __init__(self):
        self._lock = threading.Lock()
        self._cancel = False

    def close(self):
        with self._lock:
            self._cancel = True

    def worker(self):
        with self._lock:
            cancelled = self._cancel
        if cancelled:
            return
"""


def test_lock_discipline_fires_on_bare_read():
    findings = _run(_LOCK_POS, "lock-discipline")
    assert len(findings) == 1
    assert "_cancel" in findings[0].message and "worker" in \
        findings[0].message


def test_lock_discipline_quiet_on_snapshot_under_lock():
    assert _run(_LOCK_NEG, "lock-discipline") == []


# ---------------------------------------------------------------------------
# future-discipline
# ---------------------------------------------------------------------------

_FUTURE_POS = """
def worker(ticket, fn):
    ticket._future.set_result(fn())   # an fn() raise strands the waiter
"""

_FUTURE_NARROW = """
def worker(ticket, fn):
    try:
        ticket._future.set_result(fn())
    except Exception as e:            # BaseException escapes still strand
        ticket._future.set_exception(e)
"""

_FUTURE_WRONG_RECEIVER = """
def worker(a, b, fn):
    try:
        a.set_result(fn())
    except BaseException as e:
        b.set_exception(e)            # forwards to a DIFFERENT future
"""

_FUTURE_NEG = """
def worker(ticket, fn):
    try:
        res = fn()
        ticket._future.set_result(res)
    except BaseException as e:
        ticket._future.set_exception(e)
"""

_FUTURE_NEG_BARE = """
def worker(fut, fn):
    try:
        fut.set_result(fn())
    except:                           # bare except covers BaseException
        fut.set_exception(RuntimeError("boom"))
        raise
"""

_FUTURE_HANDLER_NOT_COVERED = """
def worker(fut, fallback):
    try:
        pass
    except BaseException as e:
        fut.set_result(fallback)      # inside the handler: nothing covers it
        fut.set_exception(e)
"""


def test_future_discipline_fires_on_unguarded_set_result():
    findings = _run(_FUTURE_POS, "future-discipline")
    assert len(findings) == 1
    assert "set_result" in findings[0].message
    assert "ticket._future" in findings[0].message


def test_future_discipline_rejects_narrow_except():
    assert len(_run(_FUTURE_NARROW, "future-discipline")) == 1


def test_future_discipline_requires_same_receiver():
    assert len(_run(_FUTURE_WRONG_RECEIVER, "future-discipline")) == 1


def test_future_discipline_handler_body_is_not_covered():
    assert len(_run(_FUTURE_HANDLER_NOT_COVERED, "future-discipline")) == 1


def test_future_discipline_quiet_on_forwarding_try():
    assert _run(_FUTURE_NEG, "future-discipline") == []
    assert _run(_FUTURE_NEG_BARE, "future-discipline") == []


# The wire twin: a connection's send_result is the remote set_result, and
# must be covered by a send_error forward on the same connection.

_WIRE_POS = """
def deliver(conn, rid, ticket):
    conn.send_result(rid, ticket.result(), {})   # a raise strands the peer
"""

_WIRE_NARROW = """
def deliver(conn, rid, ticket):
    try:
        conn.send_result(rid, ticket.result(), {})
    except Exception as e:            # BaseException escapes still strand
        conn.send_error(rid, e)
"""

_WIRE_WRONG_RECEIVER = """
def deliver(a, b, rid, ticket):
    try:
        a.send_result(rid, ticket.result(), {})
    except BaseException as e:
        b.send_error(rid, e)          # a DIFFERENT connection
"""

_WIRE_NEG = """
def deliver(conn, rid, ticket):
    try:
        res = ticket.result()
        conn.send_result(rid, res, {})
    except BaseException as e:
        conn.send_error(rid, e)
"""

_WIRE_ERROR_ONLY_NEG = """
def refuse(conn, rid, exc):
    conn.send_error(rid, exc)         # error-only paths are unconstrained
"""


def test_future_discipline_fires_on_unguarded_send_result():
    findings = _run(_WIRE_POS, "future-discipline")
    assert len(findings) == 1
    assert "send_result" in findings[0].message
    assert "send_error" in findings[0].message


def test_future_discipline_wire_rejects_narrow_except():
    assert len(_run(_WIRE_NARROW, "future-discipline")) == 1


def test_future_discipline_wire_requires_same_receiver():
    assert len(_run(_WIRE_WRONG_RECEIVER, "future-discipline")) == 1


def test_future_discipline_quiet_on_wire_forwarding_try():
    assert _run(_WIRE_NEG, "future-discipline") == []
    assert _run(_WIRE_ERROR_ONLY_NEG, "future-discipline") == []


# ---------------------------------------------------------------------------
# framework behaviour
# ---------------------------------------------------------------------------

def test_pragma_suppresses_single_rule():
    src = _SYNC_POS.replace(
        "return float(y)",
        "return float(y)  # repro: disable=host-sync-in-jit")
    assert _run(src, "host-sync-in-jit") == []


def test_unparseable_source_raises():
    with pytest.raises(SyntaxError):
        analyze_sources({"bad.py": "def f(:\n"})


def test_all_seven_rules_registered():
    assert sorted(all_rules()) == [
        "future-discipline",
        "host-sync-in-jit",
        "jit-static-hashability",
        "lock-discipline",
        "pallas-tile-shape",
        "retrace-hazard",
        "rng-key-reuse",
    ]


def test_repo_is_finding_free_and_baseline_empty():
    """The CI gate's exact contract: zero findings on src/repro against an
    EMPTY committed baseline."""
    assert load_baseline(REPO / "analysis-baseline.txt") == set()
    findings = analyze_paths([REPO / "src" / "repro"], root=REPO)
    assert findings == [], "\n".join(f.render() for f in findings)
