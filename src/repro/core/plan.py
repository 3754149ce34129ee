"""Plan/execute API: compile a clustering problem once, fit it many times.

The serving-grade entry point (ROADMAP north star: many problems fitted
repeatedly on the same data):

    spec = ClusterSpec(k=64, seeder="rejection", seed=0)
    plan = ClusterPlan(spec, ExecutionSpec(backend="device"))
    plan.prepare(points)          # host-side artifacts, cached by fingerprint
    res  = plan.fit()             # bit-for-bit the legacy fit() seeding
    res2 = plan.refit(seed=7)     # NO re-prep, NO re-trace: solve stage only
    batch = plan.fit_batch([0, 1, 2, 3])   # one vmapped program, 4 seeds

Three stages:

  * **plan** — `ClusterSpec` (algorithm parameters) + `ExecutionSpec`
    (backend/mesh/dtype placement) are frozen, hashable dataclasses; a
    `ClusterPlan` binds them to one `BackendImpl` from the typed registry.
  * **prepare** — the O(nd log Δ) host work (Appendix-F quantisation,
    multi-tree embedding codes, LSH bucket keys, device upload/padding) runs
    once per *data fingerprint* and is cached on the plan.  The rng draws it
    consumes are snapshotted so `fit()` replays the legacy stream exactly.
  * **execute** — `fit` / `refit` / `fit_batch` run only the sampling stage:
    the jit programs are cached by (shapes, statics) so repeated executes
    never re-trace (`tracing.TRACE_COUNTS` is the test-visible proof).

Results are device-resident `FitResult` pytrees (jax arrays; `.to_numpy()`
/ `.block_until_ready()` adapters, jitted `.predict`).  The legacy
`fit(points, KMeansConfig(...))` facade in `core.api` remains bit-for-bit
compatible and is implemented against the same registry.

Two multi-problem surfaces sit on top (ISSUE 5):

  * `fit_batch(seeds)` — B seeds on ONE prepared dataset (one vmapped
    program on device-native seeders), and `fit_batch(datasets=[...])` —
    B *different* datasets, canonically rescaled and padded to
    `batch_schedule.shape_bucket` rungs so every bucket compiles exactly
    one stacked program (re-traces bounded at O(log n) buckets, not O(B));
  * `core.engine.ClusterEngine` — the async pipelined executor that
    overlaps host `prepare_data` of request i+1 (thread pool) with the
    device solve of request i, via the thread-safe `prepare_data` /
    `fit_prepared` split below.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import math
import os
import threading
import time
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import registry
from repro.core.batch_schedule import BatchSchedule
from repro.core.lloyd import lloyd
from repro.core.preprocess import quantize
from repro.core.registry import BACKENDS, get_seeder_spec
from repro.core.tracing import span

__all__ = [
    "ClusterSpec",
    "ExecutionSpec",
    "ClusterPlan",
    "FitResult",
    "PreparedData",
    "ensure_host_f64",
    "data_fingerprint",
]


# ---------------------------------------------------------------------------
# Input adaptation (ISSUE 4 satellite): no unconditional float64 copy.
# ---------------------------------------------------------------------------

def ensure_host_f64(points) -> np.ndarray:
    """Float64 C-contiguous host array of `points` without gratuitous copies.

    Already-conforming numpy inputs are returned *as is* (zero copy — the
    pipelines only ever read them); other numpy inputs pay exactly one
    dtype/layout conversion; jax arrays pay exactly one device->host
    transfer (the device-resident original can still be reused on device,
    see `ClusterPlan`).
    """
    if isinstance(points, np.ndarray):
        if points.dtype == np.float64 and points.flags.c_contiguous:
            return points
        return np.ascontiguousarray(points, dtype=np.float64)
    arr = np.asarray(points)  # one transfer for jax arrays
    if arr.dtype == np.float64 and arr.flags.c_contiguous:
        return arr
    return np.ascontiguousarray(arr, dtype=np.float64)


#: Span of every public solve entry (`refit`, `fit_prepared`, `fit_batch`,
#: `fit_batch_prepared`): from entry until the device-resident result is
#: returned, i.e. host dispatch; the device finishes later.
SOLVE_SPAN = "repro.plan.solve"

_FULL_HASH_BYTES = 1 << 22          # full-hash threshold for device arrays
_SAMPLE_ROWS = 4096
_CHUNK_HASH_BYTES = 1 << 26         # host arrays above: 64 MiB chunks


def _chunk_digests(points: np.ndarray) -> list:
    """blake2b digests of consecutive row chunks of about 64 MiB, hashed
    side by side on a thread pool (hashlib releases the GIL)."""
    from concurrent.futures import ThreadPoolExecutor

    rows = max(1, _CHUNK_HASH_BYTES * points.shape[0] // points.nbytes)
    starts = range(0, points.shape[0], rows)

    def digest(lo):
        chunk = np.ascontiguousarray(points[lo:lo + rows])
        return hashlib.blake2b(chunk, digest_size=16).digest()

    with ThreadPoolExecutor(min(len(starts), os.cpu_count() or 1)) as pool:
        return list(pool.map(digest, starts))


def data_fingerprint(points) -> str:
    """Content fingerprint keying the prepare cache.

    Host (numpy) arrays hash their full bytes — blake2b streams at GB/s,
    negligible next to the O(nd log Δ) prepare work the cache avoids;
    above 64 MiB, in row chunks on several threads, their digests hashed
    in order.
    Device (jax) arrays above 4 MiB avoid a full transfer: a strided row
    sample crosses to the host, plus per-column and total sums computed
    on-device — so any row mutation (even off the sample stride) changes
    the fingerprint.
    """
    h = hashlib.blake2b(digest_size=16)
    shape = tuple(int(s) for s in points.shape)
    h.update(repr((shape, str(points.dtype))).encode())
    nbytes = int(np.prod(shape, dtype=np.int64)) * points.dtype.itemsize
    if isinstance(points, np.ndarray) and nbytes > _CHUNK_HASH_BYTES:
        for digest in _chunk_digests(points):
            h.update(digest)
    elif isinstance(points, np.ndarray) or nbytes <= _FULL_HASH_BYTES \
            or not shape:
        h.update(np.ascontiguousarray(points).tobytes())
    else:
        step = max(1, shape[0] // _SAMPLE_ROWS)
        h.update(np.asarray(points[::step]).tobytes())
        h.update(np.asarray(jnp.sum(points, axis=0,
                                    dtype=jnp.float64
                                    if jax.config.jax_enable_x64
                                    else jnp.float32)).tobytes())
        h.update(np.asarray(points[-1]).tobytes())
    return h.hexdigest()


# ---------------------------------------------------------------------------
# Frozen, hashable specs: they key jit-program and prepare caches directly.
# ---------------------------------------------------------------------------

def _freeze_options(options) -> tuple:
    if isinstance(options, dict):
        return tuple(sorted(options.items()))
    return tuple(options)


@dataclasses.dataclass(frozen=True)
class ClusterSpec:
    """Algorithm parameters: *what* to solve.

    Frozen + hashable (the `options` mapping is canonicalised to a sorted
    tuple of pairs) so a spec can key program caches directly.
    """

    k: int
    seeder: str = "rejection"           # a `registry.SEEDER_SPECS` key
    c: float = 2.0                      # LSH approximation factor
    schedule: Optional[BatchSchedule] = None
    lloyd_iters: int = 0                # 0 = seeding only (paper experiments)
    quantize: bool = True               # Appendix-F aspect-ratio control
    seed: int = 0
    options: tuple = ()                 # extra seeder kwargs, (key, value)*

    def __post_init__(self):
        object.__setattr__(self, "options", _freeze_options(self.options))
        if self.k < 1:
            raise ValueError(f"k must be >= 1, got {self.k}")

    def options_dict(self) -> dict:
        return dict(self.options)

    def replace(self, **changes) -> "ClusterSpec":
        return dataclasses.replace(self, **changes)


@dataclasses.dataclass(frozen=True)
class ExecutionSpec:
    """Execution placement: *where/how* to solve.

    Frozen + hashable.  `mesh=None` on the sharded backend resolves to
    `launch.mesh.make_seeding_mesh()` (all local devices) at plan build.
    `dtype` is the device coordinate dtype ("float32" is what the Pallas
    kernels are tuned for).  `donate=True` marks per-fit buffers donatable
    on TPU builds (advisory off-TPU).
    """

    backend: str = "cpu"                # "cpu" | "device" | "sharded"
    mesh: Any = None
    dtype: str = "float32"
    tile: int = 512
    interpret: Optional[bool] = None
    donate: bool = False

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(
                f"unknown backend {self.backend!r}; expected {BACKENDS}"
            )


@dataclasses.dataclass(frozen=True)
class _ExecContext:
    """ExecutionSpec with the mesh resolved — what backend adapters see."""

    backend: str
    mesh: Any
    dtype: str
    tile: int
    interpret: Optional[bool]
    donate: bool


# ---------------------------------------------------------------------------
# Device-resident results.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class FitResult:
    """Device-resident clustering result (a registered jax pytree).

    `indices` / `centers` / `cost` are jax arrays living where the solve ran
    (`fit_batch` stacks a leading batch axis on all three).  Nothing is
    forced to the host: chain into further jit code directly, or use the
    adapters below.  `centers` are in *original* coordinates regardless of
    the quantised seeding space.
    """

    indices: Any                  # (k,) int32 — or (B, k) from fit_batch
    centers: Any                  # (k, d)     — or (B, k, d)
    cost: Any                     # scalar f32 — or (B,)
    k: int = 0
    prepare_seconds: float = 0.0  # 0.0 on a cache hit: nothing re-prepped
    solve_seconds: float = 0.0
    extras: dict = dataclasses.field(default_factory=dict)

    def block_until_ready(self) -> "FitResult":
        """Wait for the device arrays to materialise; returns self."""
        jax.block_until_ready((self.indices, self.centers, self.cost))
        return self

    def to_numpy(self) -> "FitResult":
        """Host copy: same FitResult shape with numpy arrays."""
        return dataclasses.replace(
            self,
            indices=np.asarray(self.indices, dtype=np.int64),
            centers=np.asarray(self.centers),
            cost=float(np.asarray(self.cost))
            if np.ndim(self.cost) == 0 else np.asarray(self.cost),
        )

    def predict(self, points) -> jax.Array:
        """Nearest-center assignment as one jit program (cached by shape).

        Distances use the expanded BLAS form in the centers' dtype
        (float32 by default): on data with large common offsets prefer the
        float64 host path (`repro.core.lloyd.assign`) — cancellation can
        flip near-ties.
        """
        ctr = self.centers
        if np.ndim(ctr) != 2:
            raise ValueError("predict() needs a single-problem FitResult "
                             "(index into a fit_batch result first)")
        pts = jnp.asarray(points, dtype=ctr.dtype)
        return _predict_program(pts, ctr)


# Pytree registration: the arrays are children; aux carries only the
# static, hashable `k` so FitResults work under jit (the jit cache hashes
# the treedef).  Host metadata (timings, extras) intentionally does NOT
# round-trip through tree transforms — a mapped/jitted FitResult carries
# the transformed arrays and fresh empty metadata.
jax.tree_util.register_pytree_node(
    FitResult,
    lambda r: ((r.indices, r.centers, r.cost), (r.k,)),
    lambda aux, ch: FitResult(indices=ch[0], centers=ch[1], cost=ch[2],
                              k=aux[0]),
)


def _pairwise_d2(points: jax.Array, centers: jax.Array) -> jax.Array:
    """(n, k) squared distances, expanded BLAS form (shared by the predict
    and cost programs so any numerical fix lands in both)."""
    d2 = (
        jnp.sum(points ** 2, axis=1, keepdims=True)
        - 2.0 * points @ centers.T
        + jnp.sum(centers ** 2, axis=1)[None, :]
    )
    return jnp.maximum(d2, 0.0)


@jax.jit
def _predict_program(points: jax.Array, centers: jax.Array) -> jax.Array:
    return jnp.argmin(_pairwise_d2(points, centers), axis=1).astype(
        jnp.int32)


@jax.jit
def _cost_program(points: jax.Array, centers: jax.Array) -> jax.Array:
    return jnp.sum(jnp.min(_pairwise_d2(points, centers), axis=1))


@jax.jit
def _masked_cost_program(points: jax.Array, centers: jax.Array,
                         mask: jax.Array) -> jax.Array:
    # Streaming cost: retired rows stay in place (global ids are stable,
    # rows are never compacted on device) and are masked out here.
    return jnp.sum(jnp.min(_pairwise_d2(points, centers), axis=1) * mask)


# ---------------------------------------------------------------------------
# Batched (vmapped) device programs for fit_batch.  Outer jit caches by
# (shapes incl. batch size, statics); the per-lane results are bit-identical
# to solo refit(seed=s) runs (asserted in tests/test_plan.py).
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("k", "scale", "num_levels", "m_init", "c", "schedule",
                     "max_rounds", "tile", "interpret"),
)
def _batched_rejection(codes_lo, codes_hi, points, keys_lo, keys_hi, k,
                       key_bits, *, scale, num_levels, m_init, c, schedule,
                       max_rounds, tile, interpret):
    from repro.core.device_seeding import device_rejection_sampling

    def lane(bits):
        return device_rejection_sampling(
            codes_lo, codes_hi, points, keys_lo, keys_hi, k,
            jax.random.wrap_key_data(bits),
            scale=scale, num_levels=num_levels, m_init=m_init, c=c,
            schedule=schedule, max_rounds=max_rounds, tile=tile,
            interpret=interpret,
        )

    return jax.vmap(lane)(key_bits)


@functools.partial(
    jax.jit,
    static_argnames=("k", "scale", "num_levels", "m_init", "tile",
                     "interpret"),
)
def _batched_fastkmeanspp(codes_lo, codes_hi, k, key_bits, *, scale,
                          num_levels, m_init, tile, interpret):
    from repro.core.device_seeding import device_fast_kmeanspp

    def lane(bits):
        return device_fast_kmeanspp(
            codes_lo, codes_hi, k, jax.random.wrap_key_data(bits),
            scale=scale, num_levels=num_levels, m_init=m_init, tile=tile,
            interpret=interpret,
        )

    return jax.vmap(lane)(key_bits)


# ---------------------------------------------------------------------------
# The plan.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PreparedData:
    """One data fingerprint's cached prepare-stage output.

    Returned by `ClusterPlan.prepare_data` and accepted by
    `ClusterPlan.fit_prepared` — the handle the async `ClusterEngine`
    threads pass between the host prepare pool and the device solve worker
    (the implicit `prepare()`/`fit()` pair routes through the same object
    via the plan's `_active` slot).  Stacked lanes cache here too, under a
    ``<fingerprint>/stacked`` key with a `StackedLane` in `artifacts`.
    """

    fingerprint: str
    pts: np.ndarray                   # original coords, host float64
    seed_pts: np.ndarray              # seeding-space coords (maybe quantised)
    resolution: Optional[float]       # quantisation grid passed to seeders
    artifacts: Any                    # BackendImpl.prepare output (or None)
    rng_state: dict                   # np.Generator state after prep draws
    prepare_seconds: float
    points_dev: Any = None            # lazy device rows for gather/cost
    # Streaming (ISSUE 10): a mutable `repro.core.streaming.StreamState`
    # makes this handle extendable/retirable in place.  Because mutation
    # invalidates the content fingerprint above, the prepare cache re-keys
    # a mutated handle on `generation` (``<fp>#g<generation>``) — see
    # `ClusterPlan.extend` — so a stale content key can never alias a
    # mutated prep.
    streaming: Any = None
    generation: int = 0


def _load_backend(backend: str) -> None:
    """Importing a backend module registers its impls (idempotent)."""
    if backend == "device":
        import repro.core.device_seeding  # noqa: F401
    elif backend == "sharded":
        import repro.core.sharded_seeding  # noqa: F401
    else:
        import repro.core.seeding  # noqa: F401


class ClusterPlan:
    """A compiled clustering problem: prepare once, execute many times.

    Construction validates the (seeder, backend) pair against the typed
    registry and resolves the mesh; `prepare` caches host artifacts by data
    fingerprint; `fit`/`refit`/`fit_batch` run the solve stage against the
    cached artifacts and the backend's cached jit programs.
    """

    def __init__(self, cluster: ClusterSpec,
                 execution: Optional[ExecutionSpec] = None, *,
                 fault_plan=None):
        if not isinstance(cluster, ClusterSpec):
            raise TypeError(
                f"expected ClusterSpec, got {type(cluster).__name__} "
                "(legacy KMeansConfig goes through core.api.fit)"
            )
        execution = execution if execution is not None else ExecutionSpec()
        _load_backend(execution.backend)
        seeder_spec = get_seeder_spec(cluster.seeder)
        self.cluster = cluster
        self.execution = execution
        self.caps = seeder_spec.caps
        self.impl = seeder_spec.impl(execution.backend)
        mesh = execution.mesh
        if execution.backend == "sharded" and mesh is None:
            from repro.launch.mesh import make_seeding_mesh

            mesh = make_seeding_mesh()
        self._ctx = _ExecContext(
            backend=execution.backend, mesh=mesh, dtype=execution.dtype,
            tile=execution.tile, interpret=execution.interpret,
            donate=execution.donate,
        )
        self._prepared: dict[str, PreparedData] = {}
        self._active: Optional[PreparedData] = None
        self._lock = threading.Lock()      # cache dict + stats counters
        self.stats = {"prepare_calls": 0, "prepare_hits": 0,
                      "prepare_builds": 0, "solves": 0, "extends": 0,
                      "retires": 0}
        self._stream_seq = 0           # uniquifies streaming cache keys
        # Chaos hook (resilience.FaultPlan): seeded failure/latency
        # injection at the top of the prepare build and the solve; None
        # (the default) costs nothing on the hot path.
        self.fault_plan = fault_plan

    def _fault_inject(self, stage: str, detail: str) -> None:
        if self.fault_plan is not None:
            self.fault_plan.inject(
                stage,
                f"{self.cluster.seeder}/{self._ctx.backend}/{stage}/{detail}")

    # -- prepare stage ------------------------------------------------------

    def prepare(self, points) -> ClusterPlan:
        """Build (or fetch) the host-side artifacts for `points`.

        Keyed by `data_fingerprint`: re-preparing the same data is a cache
        hit that does zero host work.  Returns the plan for chaining.
        """
        prep = self.prepare_data(points)
        with self._lock:
            self._active = prep
        return self

    def prepare_data(self, points) -> PreparedData:
        """Thread-safe prepare returning an explicit `PreparedData` handle.

        Unlike `prepare()` this does not touch the plan's implicit
        "active" slot, so N threads can prepare N different datasets on one
        plan concurrently — the `ClusterEngine` pipeline runs exactly this
        against its prepare pool while the solve worker drains
        `fit_prepared`.  Distinct datasets build in parallel (the lock only
        guards the cache dict); a lost same-data build race keeps the first
        entry (both builds are deterministic from the spec seed).
        """
        return self._prepare_cached(points, stacked=False)

    def prepare_stacked(self, points) -> PreparedData:
        """Thread-safe *stacked-lane* prepare (canonical rescale + padding).

        The multi-dataset twin of `prepare_data`: builds (or fetches, keyed
        by ``<fingerprint>/stacked``) the dataset's `StackedLane` artifacts
        — the exact power-of-two rescale into the unit ball plus the
        `shape_bucket` row padding — so a later `fit_batch_prepared` call
        can coalesce it with other same-bucket datasets into ONE vmapped
        program.  Lane members prepared here are shared across every lane
        composition that includes the dataset (the continuous-batching
        front-end relies on this: a request re-coalesced into a different
        lane never re-prepares).  Requires an impl with the stacked
        capability (see the capability table).
        """
        if not self.impl.supports_stacked:
            raise ValueError(
                f"{self.cluster.seeder!r} on backend "
                f"{self._ctx.backend!r} has no stacked lanes; use "
                "prepare_data + fit_batch(datasets=...) (solo loop)")
        return self._prepare_cached(points, stacked=True)

    def prepare_streaming(self, points) -> PreparedData:
        """Prepare `points` as a *mutable stream* (extend/retire in place).

        The streaming twin of `prepare_data`: the backend's streaming ops
        (see the capability table) freeze an exact power-of-two
        quantisation scale and build capacity-padded artifacts that
        `extend`/`retire` mutate incrementally — new rows are encoded
        against the frozen trees/LSH and the sample-tree leaf weights are
        patched via scatter updates, never re-fingerprinted.  Every call
        builds a fresh independent stream (cache keys carry a per-plan
        sequence number plus the mutation generation, so a stream can
        never be aliased by a content-fingerprint cache hit); `forget`
        releases it.  Requires an impl with the streaming capability.
        """
        ops = self._streaming_ops()
        self._fault_inject("prepare", "stream")
        t0 = time.perf_counter()
        pts = ensure_host_f64(points)
        rng = np.random.default_rng(self.cluster.seed)
        options = dict(self.cluster.options_dict(),
                       _seeder=self.cluster.seeder)
        state = ops.prepare(pts, rng, resolution=options.get("resolution"),
                            options=options, execution=self._ctx)
        with self._lock:
            seq = self._stream_seq
            self._stream_seq += 1
        fp = f"{data_fingerprint(pts)}/stream{seq}#g{state.generation}"
        prep = PreparedData(
            fingerprint=fp, pts=pts, seed_pts=pts, resolution=None,
            artifacts=None, rng_state=rng.bit_generator.state,
            prepare_seconds=time.perf_counter() - t0,
            streaming=state, generation=state.generation,
        )
        with self._lock:
            self._prepared[fp] = prep
            self.stats["prepare_calls"] += 1
            self.stats["prepare_builds"] += 1
            self._active = prep
        return prep

    def _streaming_ops(self):
        ops = self.impl.streaming
        if ops is None:
            raise ValueError(
                f"{self.cluster.seeder!r} on backend {self._ctx.backend!r} "
                "has no streaming support (see the capability table); "
                "extend/retire need prepare_streaming-capable impls")
        return ops

    def extend(self, points, *, prepared: Optional[PreparedData] = None
               ) -> PreparedData:
        """Append `points` to a prepared stream *in place* (no re-prep).

        Incoming rows are quantised with the stream's frozen pow2 scale,
        encoded against the frozen tree embeddings / LSH tables, and the
        sample-tree leaf weights are patched via `scatter_update` — so the
        next `refit`/`fit_prepared` draws the exact D^2 law over the grown
        live set without re-fingerprinting (rows outside the frozen grid
        domain trigger a logged embedding rebuild; the sharded backend
        re-shards on next solve, also logged).  `prepared` defaults to the
        plan's active handle; a non-streaming handle is converted to a
        stream in place first.  The handle is re-keyed in the prepare
        cache on its bumped mutation generation.  Returns the handle.
        """
        ops = self._streaming_ops()
        prep = self._mutable_prep(prepared)
        ops.extend(prep.streaming, ensure_host_f64(points),
                   execution=self._ctx)
        self._rekey_mutated(prep)
        with self._lock:
            self.stats["extends"] += 1
        return prep

    def retire(self, indices, *, prepared: Optional[PreparedData] = None
               ) -> PreparedData:
        """Retire rows (by global row id) from a prepared stream in place.

        Retired rows keep their ids (rows are never compacted) but their
        leaf weights drop to exactly zero — they have zero mass in the
        tile cumsum, are never proposed, and are masked out of the
        reported cost.  Extend-then-retire of the same rows round-trips
        the leaf weights bit-exactly (tests/test_streaming.py).  Same
        conversion/re-key semantics as `extend`.  Returns the handle.
        """
        ops = self._streaming_ops()
        prep = self._mutable_prep(prepared)
        ops.retire(prep.streaming, np.asarray(indices, dtype=np.int64),
                   execution=self._ctx)
        self._rekey_mutated(prep)
        with self._lock:
            self.stats["retires"] += 1
        return prep

    def _mutable_prep(self, prepared: Optional[PreparedData]
                      ) -> PreparedData:
        if prepared is None:
            with self._lock:
                prepared = self._active
            if prepared is None:
                raise RuntimeError(
                    "no prepared data: call plan.prepare_streaming(points) "
                    "(or prepare/fit) before extend/retire")
        if prepared.streaming is None:
            # In-place conversion of a static prep: stream over its rows
            # with a fresh spec-seeded rng (the original artifacts are
            # superseded; the rng replay snapshot stays untouched so
            # seed=None refits remain deterministic).
            ops = self._streaming_ops()
            rng = np.random.default_rng(self.cluster.seed)
            options = dict(self.cluster.options_dict(),
                           _seeder=self.cluster.seeder)
            prepared.streaming = ops.prepare(
                prepared.pts, rng, resolution=options.get("resolution"),
                options=options, execution=self._ctx)
            prepared.artifacts = None
            prepared.generation = prepared.streaming.generation
        return prepared

    def _rekey_mutated(self, prep: PreparedData) -> None:
        """Re-key a mutated prep on its generation counter (the ISSUE-10
        cache fix): the content fingerprint no longer matches the mutated
        data, so the stale key is dropped and the entry lives under
        ``<base>#g<generation>`` instead — `forget` and engine eviction
        keep working, and a fresh `prepare_data` of the original points
        can never alias the mutated handle."""
        state = prep.streaming
        base = prep.fingerprint.split("#g")[0]
        with self._lock:
            old_key = prep.fingerprint
            prep.generation = state.generation
            new_key = f"{base}#g{state.generation}"
            if self._prepared.pop(old_key, None) is not None:
                self._prepared[new_key] = prep
            prep.fingerprint = new_key
            prep.points_dev = None        # row set changed: stale gather

    def _prepare_cached(self, points, *, stacked: bool) -> PreparedData:
        fp = data_fingerprint(points) + ("/stacked" if stacked else "")
        with self._lock:
            self.stats["prepare_calls"] += 1
            prep = self._prepared.get(fp)
            if prep is not None:
                self.stats["prepare_hits"] += 1
                return prep
        prep = self._build_prepared(fp, points, stacked)
        with self._lock:
            cur = self._prepared.get(fp)
            if cur is not None:            # lost a same-data build race
                self.stats["prepare_hits"] += 1
                return cur
            self._prepared[fp] = prep
            self.stats["prepare_builds"] += 1
        return prep

    def _build_prepared(self, fp: str, points,
                        stacked: bool) -> PreparedData:
        # Injection happens only on a real build: cache hits never
        # re-enter the fault domain (they do no work that could fail).
        self._fault_inject("prepare", fp)
        t0 = time.perf_counter()
        pts = ensure_host_f64(points)
        rng = np.random.default_rng(self.cluster.seed)
        options = self.cluster.options_dict()
        seed_pts, resolution = pts, options.get("resolution")
        if stacked:
            # Canonical lane: the exact power-of-two rescale replaces the
            # Appendix-F quantisation as the aspect-ratio control (fixed
            # canonical resolution => fixed level count).
            artifacts = self.impl.prepare_stacked(
                pts, rng, options=options, execution=self._ctx,
            )
        else:
            if self.caps.needs_quantize and self.cluster.quantize:
                q = quantize(pts, rng)
                seed_pts = q.points
                resolution = options.get("resolution", 1.0)
            artifacts = None
            if self.impl.preparable:
                artifacts = self.impl.prepare(
                    seed_pts, rng, resolution=resolution, options=options,
                    execution=self._ctx,
                )
        prep = PreparedData(
            fingerprint=fp, pts=pts, seed_pts=seed_pts,
            resolution=resolution, artifacts=artifacts,
            rng_state=rng.bit_generator.state,
            prepare_seconds=time.perf_counter() - t0,
        )
        if isinstance(points, jax.Array) and str(points.dtype) == \
                self._ctx.dtype and points.ndim == 2 and \
                self._ctx.backend != "sharded":
            prep.points_dev = points       # reuse: no host round-trip
        return prep

    def cache_info(self) -> dict:
        """Prepare-cache statistics (tests assert hit/build counts)."""
        with self._lock:
            return dict(self.stats, entries=len(self._prepared))

    def forget(self, prepared: PreparedData) -> bool:
        """Evict one `PreparedData` from the prepare cache (thread-safe).

        Long-running pipelines over a stream of *fresh* datasets would
        otherwise retain every request's host copy + device artifacts for
        the plan's lifetime; `ClusterEngine(retain_prepared=False)` calls
        this after each solve.  The handle itself stays valid for callers
        still holding it — only the cache entry (and the plan's implicit
        active slot, if it points here) is dropped.  Returns True when an
        entry was actually removed.
        """
        with self._lock:
            removed = self._prepared.pop(prepared.fingerprint,
                                         None) is not None
            if self._active is prepared:
                self._active = None
        return removed

    def _require(self, points) -> PreparedData:
        if points is not None:
            self.prepare(points)
        with self._lock:
            active = self._active
        if active is None:
            raise RuntimeError(
                "no prepared data: call plan.prepare(points) or "
                "plan.fit(points) first"
            )
        return active

    def _points_device(self, prep: PreparedData):
        """The rows centers are gathered from and costs taken over: one
        device array, or on the sharded backend a `PlacedRows` split over
        the mesh (no device ever holds the whole set)."""
        if prep.points_dev is None:
            if self._ctx.backend == "sharded":
                from repro.core.sharded_seeding import place_rows

                prep.points_dev = place_rows(prep.pts, self._ctx.mesh,
                                             dtype=self._ctx.dtype)
            else:
                prep.points_dev = jnp.asarray(prep.pts,
                                              jnp.dtype(self._ctx.dtype))
        return prep.points_dev

    # -- execute stage ------------------------------------------------------

    def fit(self, points=None, *, seed: Optional[int] = None) -> FitResult:
        """Seed (+ optional Lloyd) on the prepared data.

        With `seed` unset (or equal to the spec's), the prepare-time rng
        snapshot is replayed so the result is bit-for-bit the legacy
        `fit(points, KMeansConfig(...))` seeding.  A different `seed`
        reseeds the *solve stage only* (prepared structures are part of the
        plan — same semantics as `refit`).
        """
        prep = self._require(points)
        return self._execute(prep, self.cluster.k, seed)

    def refit(self, *, k: Optional[int] = None,
              seed: Optional[int] = None) -> FitResult:
        """Re-run the solve stage on the already-prepared data.

        On backends with a cached prepare split (see the capability table:
        device/sharded) this does zero host-side re-preparation, and
        changing only `seed` also re-traces nothing (the jit program is
        cached — changing `k` compiles one new program per distinct value,
        then caches).  CPU algorithms intermix structure build and sampling
        in one pass, so only the quantisation is cached for them and each
        refit rebuilds its tree/LSH structures.
        """
        with span(SOLVE_SPAN):
            with self._lock:
                active = self._active
            if active is None:
                raise RuntimeError(
                    "refit() needs a prior prepare()/fit(points)")
            return self._execute(active, k or self.cluster.k, seed)

    def fit_prepared(self, prepared: PreparedData, *,
                     k: Optional[int] = None,
                     seed: Optional[int] = None) -> FitResult:
        """Solve against an explicit `prepare_data` handle.

        Same semantics as `fit`/`refit` but with no implicit active-dataset
        state, so it is safe to call from a worker thread while other
        threads prepare new data — the `ClusterEngine` solve loop is built
        on exactly this call.  With `seed` unset (or equal to the spec's)
        the prepare-time rng snapshot is replayed, so the result is
        bit-for-bit the serial `prepare(points); fit()` sequence.
        """
        with span(SOLVE_SPAN):
            return self._solve_prepared(prepared, k or self.cluster.k, seed)

    def _solve_prepared(self, prepared: PreparedData, k: int,
                        seed: Optional[int]) -> FitResult:
        # Keyed by fingerprint only (not the solve seed): retries of one
        # request hit the same key, so FaultPlan's per-key failure caps
        # model a transient fault that heals on re-attempt.
        self._fault_inject("solve", prepared.fingerprint)
        return self._execute(prepared, k, seed)

    def _solve_rng(self, prep: PreparedData,
                   seed: Optional[int]) -> np.random.Generator:
        rng = np.random.default_rng(
            self.cluster.seed if seed is None else seed)
        if seed is None or seed == self.cluster.seed:
            # Replay: jump to the post-prepare state of the legacy stream.
            rng.bit_generator.state = prep.rng_state
        return rng

    def _execute(self, prep: PreparedData, k: int,
                 seed: Optional[int]) -> FitResult:
        t0 = time.perf_counter()
        with self._lock:
            self.stats["solves"] += 1
        rng = self._solve_rng(prep, seed)
        options = self.cluster.options_dict()
        options.pop("resolution", None)
        if prep.streaming is not None:
            idx_raw, extras = self.impl.streaming.solve(
                prep.streaming, k, rng,
                c=self.cluster.c, schedule=self.cluster.schedule,
                options=options, execution=self._ctx,
            )
            return self._finish_streaming(prep, k, idx_raw, extras, t0)
        if self.impl.preparable:
            idx_raw, extras = self.impl.solve(
                prep.artifacts, prep.seed_pts, k, rng,
                c=self.cluster.c, schedule=self.cluster.schedule,
                options=options, execution=self._ctx,
            )
        else:
            # No cached split (cpu algorithms): run the legacy seed_fn with
            # capability-driven kwargs — identical to the old fit() facade.
            if prep.resolution is not None:
                options.setdefault("resolution", prep.resolution)
            if self.caps.accepts_c:
                options.setdefault("c", self.cluster.c)
            if self.caps.accepts_schedule and self.cluster.schedule \
                    is not None:
                options.setdefault("schedule", self.cluster.schedule)
            res = self.impl.run(prep.seed_pts, k, rng, **options)
            idx_raw = res.indices
            extras = dict(res.extras)
            extras.setdefault("num_candidates", res.num_candidates)
        return self._finish(prep, k, idx_raw, extras, t0)

    def _finish(self, prep: PreparedData, k: int, idx_raw, extras: dict,
                t0: float) -> FitResult:
        idx = jnp.asarray(idx_raw, jnp.int32)
        pts_dev = self._points_device(prep)
        sharded = self._ctx.backend == "sharded"
        centers = (pts_dev.take(idx) if sharded
                   else jnp.take(pts_dev, idx, axis=0))
        if self.cluster.lloyd_iters > 0:
            refinement = lloyd(prep.pts,
                               prep.pts[np.asarray(idx, dtype=np.int64)],
                               max_iters=self.cluster.lloyd_iters)
            centers = jnp.asarray(refinement.centers,
                                  jnp.dtype(self._ctx.dtype))
            cost = jnp.asarray(refinement.cost, jnp.float32)
            extras = dict(extras, lloyd_iterations=refinement.iterations)
        elif sharded:
            cost = pts_dev.cost(centers)
        else:
            cost = _cost_program(pts_dev, centers)
        return FitResult(
            indices=idx, centers=centers, cost=cost, k=k,
            prepare_seconds=prep.prepare_seconds,
            solve_seconds=time.perf_counter() - t0,
            extras=extras,
        )

    def _finish_streaming(self, prep: PreparedData, k: int, idx_raw,
                          extras: dict, t0: float) -> FitResult:
        """Streaming `_finish`: gather/cost over the stream's current rows.

        Global row ids are stable (device/cpu streams never compact), so
        the gather indexes the full row block and the cost masks retired
        rows to zero weight.
        """
        state = prep.streaming
        idx = jnp.asarray(idx_raw, jnp.int32)
        with state.lock:
            n_rows = state.n_rows
            if prep.points_dev is None or \
                    prep.points_dev.shape[0] != n_rows:
                prep.points_dev = jnp.asarray(
                    state.host_pts[:n_rows], jnp.dtype(self._ctx.dtype))
            pts_dev = prep.points_dev
            mask = state.live_mask_device()
        centers = jnp.take(pts_dev, idx, axis=0)
        if self.cluster.lloyd_iters > 0:
            live_pts = state.live_points()
            refinement = lloyd(
                live_pts, state.host_pts[np.asarray(idx, dtype=np.int64)],
                max_iters=self.cluster.lloyd_iters)
            centers = jnp.asarray(refinement.centers,
                                  jnp.dtype(self._ctx.dtype))
            cost = jnp.asarray(refinement.cost, jnp.float32)
            extras = dict(extras, lloyd_iterations=refinement.iterations)
        else:
            cost = _masked_cost_program(pts_dev, centers, mask)
        return FitResult(
            indices=idx, centers=centers, cost=cost, k=k,
            prepare_seconds=prep.prepare_seconds,
            solve_seconds=time.perf_counter() - t0,
            extras=extras,
        )

    # -- multi-problem execution -------------------------------------------

    def fit_batch(self, seeds: Optional[Sequence[int]] = None, points=None,
                  *, datasets: Optional[Sequence[Any]] = None) -> FitResult:
        """Solve B independent seeding problems as one stacked batch.

        Two modes, both returning a stacked `FitResult` (leading batch axis
        on indices / centers / cost):

        * ``fit_batch(seeds)`` — B seeds on ONE prepared dataset.  Lane i is
          bit-identical to `refit(seed=seeds[i])`.  Device-native seeders
          run all lanes as ONE vmapped jit program (MoE-router-style
          multi-problem seeding); other backends loop over the cached solo
          program — either way nothing is re-prepared and, after the first
          batch shape, nothing re-traces.
        * ``fit_batch(datasets=[...], seeds=None|[...])`` — B *different*
          datasets (one optional seed per dataset, default the spec's).  On
          backends whose impl `supports_stacked` (see the capability
          table), every dataset is canonically rescaled (exact power-of-two
          factor into the unit ball — distance ratios, and therefore the
          D^2 law and the acceptance test, are preserved exactly) and
          padded to a `batch_schedule.shape_bucket` rung; all lanes of a
          bucket solve as ONE vmapped jit program with a traced per-lane
          `n_real` mask, so re-traces are bounded by the O(log n) rung
          count, never O(B).  Lane i is bit-identical to
          ``fit_batch(datasets=[datasets[i]], ...)`` in the same shape
          bucket.  The stacked path covers the seeding stage only: with
          ``lloyd_iters > 0`` (host-side refinement per dataset) the call
          falls back to the solo-fit loop, as it does on impls without
          the capability — either way each dataset is still
          prepare-cached and ``extras["stacked"]`` reports which path
          ran.  All datasets must share the feature dimension d;
          indices/centers/cost are reported per lane in each dataset's
          ORIGINAL coordinates.
        """
        with span(SOLVE_SPAN):
            if datasets is not None:
                if points is not None:
                    raise ValueError(
                        "pass either points= or datasets=, not both")
                return self._fit_batch_datasets(list(datasets), seeds)
            if seeds is None:
                raise ValueError("fit_batch() needs seeds (or datasets=...)")
            prep = self._require(points)
            seeds = [int(s) for s in seeds]
            if not seeds:
                raise ValueError("fit_batch() needs at least one seed")
            if (self.impl.device_native and self._ctx.backend == "device"
                    and self.cluster.lloyd_iters == 0):
                return self._fit_batch_vmapped(prep, seeds)
            return _stack_results(
                [self._execute(prep, self.cluster.k, s) for s in seeds],
                seeds)

    def _fit_batch_vmapped(self, prep: PreparedData,
                           seeds: list[int]) -> FitResult:
        t0 = time.perf_counter()
        with self._lock:
            self.stats["solves"] += len(seeds)
        key_bits = jnp.stack([
            jax.random.key_data(jax.random.key(
                int(self._solve_rng(prep, s).integers(2 ** 31))))
            for s in seeds
        ])
        k = self.cluster.k
        options = self.cluster.options_dict()
        extras: dict = {"seeds": tuple(seeds), "vmapped": True}
        if self.cluster.seeder == "rejection":
            data = prep.artifacts
            sched = _resolve_schedule(self.cluster.schedule,
                                      options.get("batch"))
            idx, trials = _batched_rejection(
                data.codes_lo, data.codes_hi, data.points,
                data.keys_lo, data.keys_hi, k, key_bits,
                scale=data.scale, num_levels=data.num_levels,
                m_init=data.m_init, c=self.cluster.c, schedule=sched,
                max_rounds=options.get("max_rounds", 32),
                tile=self._ctx.tile, interpret=self._ctx.interpret,
            )
            extras["trials"] = trials
        else:  # fastkmeans++
            lo, hi, meta = prep.artifacts
            idx = _batched_fastkmeanspp(
                lo, hi, k, key_bits,
                scale=meta["scale"], num_levels=meta["num_levels"],
                m_init=meta["m_init"], tile=self._ctx.tile,
                interpret=self._ctx.interpret,
            )
        pts_dev = self._points_device(prep)
        centers = jnp.take(pts_dev, idx, axis=0)        # (B, k, d)
        cost = jax.vmap(lambda c: _cost_program(pts_dev, c))(centers)
        return FitResult(
            indices=idx, centers=centers, cost=cost, k=k,
            prepare_seconds=prep.prepare_seconds,
            solve_seconds=time.perf_counter() - t0,
            extras=extras,
        )

    # -- multi-DATASET execution (stacked lanes) ---------------------------

    def _fit_batch_datasets(self, datasets: list,
                            seeds: Optional[Sequence[int]]) -> FitResult:
        if not datasets:
            raise ValueError("fit_batch(datasets=...) needs >= 1 dataset")
        b = len(datasets)
        seeds = ([int(s) for s in seeds] if seeds is not None
                 else [self.cluster.seed] * b)
        if len(seeds) != b:
            raise ValueError(
                f"got {len(seeds)} seeds for {b} datasets"
            )
        if not (self.impl.supports_stacked
                and self.cluster.lloyd_iters == 0):
            # Fallback: pipeline-free solo loop (each dataset still
            # fingerprint-cached; the engine is the pipelined alternative).
            results = []
            for pts_i, s in zip(datasets, seeds):
                results.append(self._solve_prepared(
                    self.prepare_data(pts_i), self.cluster.k, s))
            out = _stack_results(results, seeds)
            out.extras["stacked"] = False
            return out
        return self._fit_batch_stacked(datasets, seeds)

    def _fit_batch_stacked(self, datasets: list,
                           seeds: list[int]) -> FitResult:
        preps = [self._prepare_cached(pts_i, stacked=True)
                 for pts_i in datasets]
        return self._fit_batch_prepared(preps, seeds)

    def fit_batch_prepared(self, prepared: Sequence[PreparedData], *,
                           seeds: Optional[Sequence[int]] = None
                           ) -> FitResult:
        """Solve B stacked-prepared lanes (one vmapped program per bucket).

        The solve stage of ``fit_batch(datasets=...)`` against explicit
        `prepare_stacked` handles: no implicit state, no host re-prep —
        safe to call from a solve worker while other threads prepare new
        lane members (the `ClusterEngine` lane path is built on exactly
        this call).  Lane i of the stacked `FitResult` is bit-identical
        to ``fit_batch_prepared([prepared[i]], seeds=[seeds[i]])`` in the
        same shape bucket — the PR-5 stacked-lane contract the
        continuous-batching front-end's coalescing rests on.  `seeds`
        defaults to the spec seed per lane (the solo `refit` stream).
        """
        with span(SOLVE_SPAN):
            return self._fit_batch_prepared(prepared, seeds)

    def _fit_batch_prepared(self, prepared: Sequence[PreparedData],
                            seeds: Optional[Sequence[int]]) -> FitResult:
        t0 = time.perf_counter()
        preps = list(prepared)
        if not preps:
            raise ValueError("fit_batch_prepared() needs >= 1 lane")
        seeds = ([int(s) for s in seeds] if seeds is not None
                 else [self.cluster.seed] * len(preps))
        if len(seeds) != len(preps):
            raise ValueError(
                f"got {len(seeds)} seeds for {len(preps)} lanes")
        if any(not hasattr(p.artifacts, "shape_key") for p in preps):
            raise ValueError(
                "fit_batch_prepared() needs prepare_stacked handles "
                "(got a solo prepare_data handle)")
        dims = {p.pts.shape[1] for p in preps}
        if len(dims) > 1:
            raise ValueError(
                f"stacked fit_batch needs one feature dimension, got {dims}"
            )
        # One key per lane *composition*: retries of one lane hit the same
        # key, so FaultPlan per-key caps model healing transient faults.
        self._fault_inject(
            "solve", "+".join(p.fingerprint for p in preps))
        with self._lock:
            self.stats["solves"] += len(seeds)
        k = self.cluster.k
        options = self.cluster.options_dict()
        options.pop("resolution", None)
        groups: dict[tuple, list[int]] = {}
        for i, p in enumerate(preps):
            groups.setdefault(p.artifacts.shape_key, []).append(i)
        idx_lanes: list = [None] * len(preps)
        trials_lanes: dict[int, Any] = {}
        donated = False
        for members in groups.values():
            bits = [self._lane_key_bits(preps[i], seeds[i])
                    for i in members]
            # Batch axis rides the same power-of-two ladder as the row
            # padding: pad with copies of lane 0 (results discarded) so B
            # in [2^j + 1, 2^(j+1)] shares one traced program.
            b_pad = 1 << max(0, math.ceil(math.log2(len(members))))
            lanes = [preps[i].artifacts for i in members]
            lanes += [lanes[0]] * (b_pad - len(members))
            bits += [bits[0]] * (b_pad - len(members))
            idx_g, extras_g = self.impl.solve_stacked(
                lanes, k, jnp.stack(bits), c=self.cluster.c,
                schedule=self.cluster.schedule, options=options,
                execution=self._ctx,
            )
            donated = donated or bool(extras_g.get("donated"))
            for j, i in enumerate(members):
                idx_lanes[i] = idx_g[j]
                if "trials" in extras_g:
                    trials_lanes[i] = extras_g["trials"][j]
        centers, costs = [], []
        for i, p in enumerate(preps):
            pts_dev = self._points_device(p)
            ctr = jnp.take(pts_dev, idx_lanes[i], axis=0)
            centers.append(ctr)
            costs.append(_cost_program(pts_dev, ctr))
        extras: dict = {
            "seeds": tuple(seeds), "stacked": True, "vmapped": True,
            "shape_buckets": len(groups), "donated": donated,
            "lane_rows": tuple(p.artifacts.n_real for p in preps),
            "bucket_rows": tuple(p.artifacts.arrays[0].shape[-1]
                                 for p in preps),
        }
        if trials_lanes:
            extras["trials"] = jnp.stack(
                [trials_lanes[i] for i in range(len(preps))])
        return FitResult(
            indices=jnp.stack(idx_lanes),
            centers=jnp.stack(centers),
            cost=jnp.stack(costs),
            k=k,
            prepare_seconds=float(sum(p.prepare_seconds for p in preps)),
            solve_seconds=time.perf_counter() - t0,
            extras=extras,
        )

    def _lane_key_bits(self, prep: PreparedData, seed: int) -> jax.Array:
        rng = self._solve_rng(prep, seed)
        return jax.random.key_data(
            jax.random.key(int(rng.integers(2 ** 31))))


def _resolve_schedule(schedule, batch):
    from repro.core.device_seeding import resolve_schedule

    return resolve_schedule(schedule, batch)


def _stack_results(results: list[FitResult], seeds: list[int]) -> FitResult:
    return FitResult(
        indices=jnp.stack([r.indices for r in results]),
        centers=jnp.stack([r.centers for r in results]),
        cost=jnp.stack([jnp.asarray(r.cost) for r in results]),
        k=results[0].k,
        prepare_seconds=results[0].prepare_seconds,
        solve_seconds=float(sum(r.solve_seconds for r in results)),
        extras={"seeds": tuple(seeds), "vmapped": False},
    )
