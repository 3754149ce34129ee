"""Wire transport suite (repro.serving.net).

ISSUE 9 acceptance coverage: codec round-trip property tests, server/client
loopback bit-identity against direct `ClusterFrontend.submit`,
tenant-quota starvation (the hot tenant throttles typed, the cold tenant
completes), malformed-frame and mid-stream-disconnect handling with a
balanced serving ledger, and deadline expiry surfacing as the typed
`DeadlineExceededError` over the wire.
"""

import socket
import struct
import time

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    ClusterSpec,
    DeadlineExceededError,
    ExecutionSpec,
    exception_from_wire,
    exception_to_wire,
)
from repro.core.resilience import (
    WIRE_DEADLINE_EXCEEDED,
    WIRE_PROTOCOL_ERROR,
    WIRE_QUOTA_EXCEEDED,
)
from repro.serving.frontend import ClusterFrontend
from repro.serving.net import (
    ClusterClient,
    ClusterServer,
    ProtocolError,
    QuotaExceededError,
    TenantPolicy,
    TenantScheduler,
    decode_frame,
    parse_tenants,
)
from repro.serving.net.protocol import (
    ChunkFrame,
    ErrorFrame,
    FrameReader,
    ResultFrame,
    StatsFrame,
    SubmitFrame,
)

pytestmark = pytest.mark.timeout(300)

SPEC = ClusterSpec(k=4, seeder="fastkmeans++", seed=3)
CPU = ExecutionSpec(backend="cpu")


def _mixture(n, d=6, k_true=5, seed=0):
    rng = np.random.default_rng(seed)
    ctr = rng.normal(size=(k_true, d)) * 25
    return ctr[rng.integers(k_true, size=n)] + rng.normal(size=(n, d))


def _reframe(encoded: bytes, chunk: int):
    """Round-trip encoded bytes through a FrameReader in `chunk`-sized
    feeds (exercising partial-frame buffering)."""
    reader = FrameReader()
    out = []
    for off in range(0, len(encoded), chunk):
        out.extend(reader.feed(encoded[off:off + chunk]))
    assert reader.pending_bytes() == 0
    return out


# ---------------------------------------------------------------------------
# codec round-trips (property tests)
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(st.integers(1, 64), st.integers(1, 8), st.booleans(),
       st.integers(0, 2**63 - 1), st.integers(1, 97))
def test_submit_frame_roundtrip_bit_exact(n, d, f32, rid, chunk):
    rng = np.random.default_rng(n * 131 + d)
    pts = rng.normal(size=(n, d)).astype("<f4" if f32 else "<f8")
    frame = SubmitFrame.from_points(
        rid, pts, k=3, seed=7, deadline=1.5, priority=-2, tenant="tn")
    (back,) = _reframe(frame.encode(), chunk)
    assert (back.request_id, back.k, back.seed, back.priority,
            back.tenant) == (rid, 3, 7, -2, "tn")
    assert back.deadline == pytest.approx(1.5)
    got = back.points()
    assert got.dtype == pts.dtype
    np.testing.assert_array_equal(got, pts)      # bit-exact payload


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 16), st.integers(1, 8), st.booleans(),
       st.integers(1, 97))
def test_result_frame_roundtrip_bit_exact(k, d, f32, chunk):
    rng = np.random.default_rng(k * 17 + d)
    centers = rng.normal(size=(k, d)).astype("<f4" if f32 else "<f8")
    indices = rng.integers(0, 1 << 40, size=k).astype("<i8")
    frame = ResultFrame(9, indices=indices, centers=centers,
                        cost=3.25, extras={"queue_wait": 0.5, "t": "x"})
    (back,) = _reframe(frame.encode(), chunk)
    np.testing.assert_array_equal(back.indices, indices)
    np.testing.assert_array_equal(back.centers, centers)
    assert back.centers.dtype == centers.dtype
    assert back.cost == 3.25
    assert back.extras == {"queue_wait": 0.5, "t": "x"}


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 500), st.integers(1, 64))
def test_chunked_stream_reassembles(total, chunk_bytes):
    payload = np.random.default_rng(total).bytes(total)
    frames = [ChunkFrame(5, payload[o:o + chunk_bytes],
                         last=o + chunk_bytes >= total).encode()
              for o in range(0, total, chunk_bytes)]
    got = _reframe(b"".join(frames), 13)
    assert b"".join(f.payload for f in got) == payload
    assert [f.last for f in got][-1] is True
    assert all(not f.last for f in got[:-1])


def test_error_frame_reconstructs_typed_exception():
    code, msg = exception_to_wire(DeadlineExceededError("too slow"))
    assert code == WIRE_DEADLINE_EXCEEDED
    (back,) = _reframe(ErrorFrame(3, code, msg).encode(), 7)
    exc = exception_from_wire(back.code, back.message)
    assert isinstance(exc, DeadlineExceededError)
    assert "too slow" in str(exc)
    quota = exception_from_wire(WIRE_QUOTA_EXCEEDED, "over quota")
    assert isinstance(quota, QuotaExceededError)


def test_stats_frame_directions():
    (req,) = _reframe(StatsFrame(1).encode(), 3)
    assert req.payload is None
    (resp,) = _reframe(StatsFrame(1, payload={"a": [1, 2]}).encode(), 3)
    assert resp.payload == {"a": [1, 2]}


def test_malformed_frames_raise_protocol_error():
    good = StatsFrame(1).encode()
    with pytest.raises(ProtocolError, match="version"):
        decode_frame(b"\x63" + good[5:])         # wrong version byte
    with pytest.raises(ProtocolError, match="frame type"):
        decode_frame(good[4:5] + b"\x2a" + good[6:])
    with pytest.raises(ProtocolError, match="truncated"):
        # cut mid-way through the SUBMIT fixed header
        decode_frame(SubmitFrame.from_points(
            1, np.zeros((4, 2))).encode()[4:30])
    with pytest.raises(ProtocolError, match="promised"):
        # intact header, inline payload shorter than n*d*itemsize
        decode_frame(SubmitFrame.from_points(
            1, np.zeros((4, 2))).encode()[4:-9])
    reader = FrameReader()
    with pytest.raises(ProtocolError, match="MAX_FRAME_BYTES"):
        list(reader.feed(struct.pack("<I", 0xFFFFFFF0)))


# ---------------------------------------------------------------------------
# loopback serving
# ---------------------------------------------------------------------------

def test_loopback_bit_identical_to_direct_frontend_submit():
    """The wire adds delivery, not drift: a fit served through
    server+client sockets equals the same (points, seed) submitted
    directly to the same frontend, bit for bit."""
    datasets = [_mixture(300 + 60 * i, seed=i) for i in range(3)]
    with ClusterFrontend(SPEC, CPU, max_batch=4, max_wait_ms=5.0) as fe:
        direct = []
        for i, ds in enumerate(datasets):
            t = fe.submit(ds, seed=100 + i)
            direct.append(t.result(timeout=120).to_numpy())
        with ClusterServer(frontend=fe) as srv:
            with ClusterClient(*srv.address) as client:
                ids = [client.submit(ds, seed=100 + i)
                       for i, ds in enumerate(datasets)]
                wire = [client.result(rid, timeout=120) for rid in ids]
    for ref, got in zip(direct, wire):
        np.testing.assert_array_equal(np.asarray(ref.indices),
                                      np.asarray(got.indices))
        np.testing.assert_array_equal(np.asarray(ref.centers),
                                      np.asarray(got.centers))
        assert got.centers.dtype == np.asarray(ref.centers).dtype
        assert float(ref.cost) == float(got.cost)
        assert "server" in got.extras


def test_streamed_upload_matches_inline():
    """A chunked streamed upload admits the identical dataset."""
    ds = _mixture(900, seed=7)
    with ClusterServer(SPEC, CPU, max_batch=2, max_wait_ms=2.0) as srv:
        with ClusterClient(*srv.address, stream_threshold_bytes=1024,
                           chunk_bytes=4096) as streamer, \
                ClusterClient(*srv.address) as inline:
            a = streamer.submit(ds, seed=5)
            b = inline.submit(ds, seed=5)
            ra = streamer.result(a, timeout=120)
            rb = inline.result(b, timeout=120)
    np.testing.assert_array_equal(ra.indices, rb.indices)
    np.testing.assert_array_equal(ra.centers, rb.centers)
    assert float(ra.cost) == float(rb.cost)


def test_deadline_expiry_is_typed_over_the_wire():
    ds = _mixture(400, seed=3)
    with ClusterServer(SPEC, CPU, max_batch=8, max_wait_ms=1.0) as srv:
        with ClusterClient(*srv.address) as client:
            rid = client.submit(ds, seed=1, deadline=1e-6)
            with pytest.raises(DeadlineExceededError):
                client.result(rid, timeout=120)
            st = client.stats(timeout=60)
    assert st["deadline_expired"] >= 1
    assert st["net"]["errors_sent"] >= 1


def test_tenant_quota_throttles_hot_without_starving_cold():
    """The hot tenant blows through its token bucket and gets typed
    `QuotaExceededError` refusals; the cold tenant's traffic all
    completes; the per-tenant ledger and scheduler stats record both."""
    scheduler = TenantScheduler({
        "hot": TenantPolicy(rate_hz=0.001, burst=3.0, weight=1.0),
        "cold": TenantPolicy(weight=4.0),
    }, default=None)
    datasets = [_mixture(300, seed=50 + i) for i in range(6)]
    with ClusterServer(SPEC, CPU, max_batch=4, max_wait_ms=5.0,
                       admission=scheduler) as srv:
        with ClusterClient(*srv.address) as client:
            hot = [client.submit(ds, seed=i, tenant="hot")
                   for i, ds in enumerate(datasets)]
            cold = [client.submit(ds, seed=i, tenant="cold")
                    for i, ds in enumerate(datasets)]
            throttled = 0
            for rid in hot:
                try:
                    client.result(rid, timeout=120)
                except QuotaExceededError:
                    throttled += 1
            cold_results = [client.result(rid, timeout=120)
                            for rid in cold]
            # unknown tenants are refused typed: closed roster
            rogue = client.submit(datasets[0], seed=0, tenant="rogue")
            with pytest.raises(QuotaExceededError):
                client.result(rogue, timeout=120)
            st = client.stats(timeout=60)
    assert throttled == 3, "burst=3 should admit exactly 3 hot requests"
    assert len(cold_results) == 6, "cold tenant was starved"
    assert st["tenants"]["cold"]["completed"] == 6
    assert st["tenants"]["hot"]["throttled"] == 3
    assert st["tenancy"]["hot"]["throttled"] == 3
    assert st["tenancy"]["cold"]["dispatched"] == 6
    # weighted-fair accounting: weight 4 advances vtime at 1/4 rate
    assert st["tenancy"]["cold"]["virtual_time"] == pytest.approx(6 / 4.0)


def test_malformed_wire_input_gets_typed_refusal_and_clean_ledger():
    """A peer speaking garbage gets one ERROR frame (protocol code) and a
    closed connection; nothing enters the serving ledger."""
    with ClusterServer(SPEC, CPU, max_batch=2, max_wait_ms=1.0) as srv:
        with socket.create_connection(srv.address, timeout=10) as sock:
            sock.sendall(struct.pack("<I", 0xFFFFFFF0) + b"junk")
            reader = FrameReader()
            frames = []
            while not frames:
                data = sock.recv(1 << 16)
                assert data, "server closed without a typed refusal"
                frames.extend(reader.feed(data))
            assert isinstance(frames[0], ErrorFrame)
            assert frames[0].code == WIRE_PROTOCOL_ERROR
            assert sock.recv(1 << 16) == b"", "connection not closed"
        # a client ResultFrame is also a protocol violation
        with socket.create_connection(srv.address, timeout=10) as sock:
            sock.sendall(ResultFrame(
                1, indices=np.zeros(2, "<i8"),
                centers=np.zeros((2, 2), "<f8"), cost=0.0).encode())
            reader = FrameReader()
            frames = []
            while not frames:
                data = sock.recv(1 << 16)
                assert data, "server closed without a typed refusal"
                frames.extend(reader.feed(data))
            assert frames[0].code == WIRE_PROTOCOL_ERROR
        st = srv.stats()
    assert st["submitted"] == 0
    assert st["net"]["requests_admitted"] == 0


def test_mid_stream_disconnect_balances_ledger():
    """A client that vanishes mid-flight (inline requests awaiting
    results AND a half-finished streamed upload) must not strand or
    unbalance anything: admitted tickets resolve server-side, the
    half-upload is discarded, and the ledger balances exactly."""
    datasets = [_mixture(300 + 40 * i, seed=70 + i) for i in range(3)]
    with ClusterFrontend(SPEC, CPU, max_batch=4, max_wait_ms=20.0) as fe:
        with ClusterServer(frontend=fe) as srv:
            client = ClusterClient(*srv.address, retries=0)
            for i, ds in enumerate(datasets):
                client.submit(ds, seed=i)
            # half a streamed upload: header + one non-final chunk
            big = SubmitFrame.from_points(99, datasets[0], seed=9,
                                          streamed=True)
            with client._wlock:
                client._sock.sendall(big.encode())
                client._sock.sendall(ChunkFrame(99, b"\x00" * 128).encode())
            client.close()               # vanish before any result lands
            t0 = time.monotonic()
            while fe.stats()["completed"] + fe.stats()["failed"] < 3:
                assert time.monotonic() - t0 < 120, \
                    "tickets never resolved after disconnect"
                time.sleep(0.02)
        st = fe.stats()
    assert st["submitted"] == 3
    assert st["completed"] + st["failed"] + st["cancelled"] \
        == st["submitted"], f"ledger does not balance: {st}"
    assert st["held"] == 0 and st["inflight"] == 0


def test_duplicate_request_id_is_idempotent():
    """Replaying a SUBMIT under the same request id (the client's
    reconnect path) must not double-deliver: inflight duplicates are
    dropped, post-delivery replays re-solve bit-identically."""
    ds = _mixture(300, seed=4)
    with ClusterServer(SPEC, CPU, max_batch=2, max_wait_ms=2.0) as srv:
        frame = SubmitFrame.from_points(7, ds, seed=11).encode()
        with socket.create_connection(srv.address, timeout=10) as sock:
            sock.sendall(frame + frame)      # burst: duplicate while inflight
            reader = FrameReader()
            first = []
            while not first:
                first.extend(reader.feed(sock.recv(1 << 16)))
            # Replay after delivery.  The RESULT frame goes out BEFORE
            # the server releases the id (finish runs in the delivery
            # finally), so a replay racing that window is dropped as an
            # inflight duplicate — exactly the contract.  Resend until
            # one is admitted after release.
            second = []
            sock.settimeout(0.5)
            t0 = time.monotonic()
            while not second:
                assert time.monotonic() - t0 < 30
                sock.sendall(frame)
                try:
                    second.extend(reader.feed(sock.recv(1 << 16)))
                except TimeoutError:
                    continue
            sock.settimeout(10)
        # counters bump just after the frame hits the wire: poll briefly
        t0 = time.monotonic()
        while srv.stats()["net"]["results_sent"] < 2:
            assert time.monotonic() - t0 < 30, srv.stats()["net"]
            time.sleep(0.01)
        st = srv.stats()
    assert isinstance(first[0], ResultFrame)
    assert isinstance(second[0], ResultFrame)
    np.testing.assert_array_equal(first[0].indices, second[0].indices)
    np.testing.assert_array_equal(first[0].centers, second[0].centers)
    assert first[0].cost == second[0].cost
    # >= 1: the initial burst duplicate for certain, plus any replays
    # that raced the post-delivery release window above.
    assert st["net"]["duplicates_dropped"] >= 1
    assert st["net"]["results_sent"] == 2


def test_parse_tenants_spec():
    got = parse_tenants("bulk:50:100:1, rt:200:40:4 ,free")
    assert got["bulk"] == TenantPolicy(rate_hz=50, burst=100, weight=1)
    assert got["rt"] == TenantPolicy(rate_hz=200, burst=40, weight=4)
    assert got["free"] == TenantPolicy()
    with pytest.raises(ValueError, match="tenants entry"):
        parse_tenants("a:1:2:3:4")
