"""Wire-transport tier: protocol framing, socket serving, admission, CLI.

Four layers of pinning:

  * **protocol** — the length-prefixed codec round-trips every message
    type through `FrameReader` under arbitrary chunk boundaries
    (hypothesis drives the chunking), and rejects garbage loudly.
  * **bit-identity over the wire** — a fleet of all five golden Table-2
    classifiers served through the asyncio socket server returns labels
    bit-identical to the offline `CircuitProgram.predict` of the very
    bundles in the emit dir (the PR's acceptance criterion).
  * **admission control** — under synthetic overload (engines slowed to a
    crawl, tiny queue limit, full-speed producer) the shed rate is
    nonzero while *accepted* requests keep meeting their SLO: zero
    `n_slo_miss`, every accepted label correct.
  * **CLI contract** — `python -m repro.serve replay` exits nonzero on
    any bit-identity mismatch *without* `--strict` (strict only adds SLO
    + shed gating), pinned against a fabricated mismatch report.
"""
import os
import time

import numpy as np
import pytest

from repro import obs
from repro.compile import CircuitProgram, load_program, lower_classifier
from repro.compile.verilog import write_artifacts
from repro.core import tnn as T
from repro.serve import ClassifierFleet, TenantSpec
from repro.serve import protocol as P
from repro.serve.client import FleetClient, FleetShedError
from repro.serve.server import FleetServer

N_EXAMPLES = int(os.environ.get("REPRO_CONFORMANCE_EXAMPLES", "20"))


# ---------------------------------------------------------------------------
# Protocol: framing + codecs as pure logic
# ---------------------------------------------------------------------------
def test_protocol_round_trips_every_message_type():
    x = np.random.default_rng(0).random(7)
    frames = [
        (P.encode_hello(), P.MSG_HELLO, {}),
        (P.encode_welcome(), P.MSG_WELCOME, {}),
        (P.encode_submit(42, "tnn_cardio", x, 12.5), P.MSG_SUBMIT,
         {"req_id": 42, "tenant": "tnn_cardio", "deadline_ms": 12.5}),
        (P.encode_submit(7, "t", x), P.MSG_SUBMIT,
         {"req_id": 7, "deadline_ms": None}),
        (P.encode_result(9, 3, 1.25), P.MSG_RESULT,
         {"req_id": 9, "label": 3, "latency_ms": 1.25}),
        (P.encode_shed(11, 40.0), P.MSG_SHED,
         {"req_id": 11, "retry_after_ms": 40.0}),
        (P.encode_error(13, "boom"), P.MSG_ERROR,
         {"req_id": 13, "message": "boom"}),
        (P.encode_list(), P.MSG_LIST, {}),
        (P.encode_tenants([{"name": "a"}]), P.MSG_TENANTS,
         {"doc": [{"name": "a"}]}),
        (P.encode_stats(), P.MSG_STATS, {}),
        (P.encode_stats_reply({"n": 1}), P.MSG_STATS_REPLY,
         {"doc": {"n": 1}}),
        (P.encode_reload(), P.MSG_RELOAD, {}),
        (P.encode_reloaded({"added": []}), P.MSG_RELOADED,
         {"doc": {"added": []}}),
    ]
    reader = P.FrameReader()
    payloads = reader.feed(b"".join(f for f, _, _ in frames))
    assert len(payloads) == len(frames)
    assert reader.buffered == 0
    for payload, (_, mtype, want) in zip(payloads, frames):
        msg = P.decode_message(payload)
        assert msg.type == mtype
        for key, val in want.items():
            assert getattr(msg, key) == val
    # the submit body carries the float64 readings bit-exactly
    sub = P.decode_message(payloads[2])
    np.testing.assert_array_equal(sub.readings, x)


def test_protocol_rejects_garbage():
    with pytest.raises(P.ProtocolError):
        P.decode_message(b"")                          # empty payload
    with pytest.raises(P.ProtocolError):
        P.decode_message(bytes([P.MSG_SUBMIT]) + b"\x00")   # truncated
    with pytest.raises(P.ProtocolError):
        P.decode_message(bytes([99]))                  # unknown type
    with pytest.raises(P.ProtocolError):               # wrong magic
        P.decode_message(bytes([P.MSG_HELLO]) + b"NOPE\x01")
    with pytest.raises(P.ProtocolError):               # version skew
        P.decode_message(bytes([P.MSG_HELLO]) + P.PROTOCOL_MAGIC
                         + bytes([P.PROTOCOL_VERSION + 1]))
    reader = P.FrameReader(max_frame=16)
    with pytest.raises(P.ProtocolError):               # hostile length prefix
        reader.feed(b"\xff\xff\xff\xff")


try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:

    @settings(max_examples=N_EXAMPLES, deadline=None)
    @given(st.lists(st.tuples(st.integers(0, 2**64 - 1),
                              st.integers(0, 2**31 - 1),
                              st.floats(0, 1e6, allow_nan=False)),
                    max_size=24),
           st.randoms(use_true_random=False))
    def test_frame_reader_survives_arbitrary_chunking(results, rnd):
        """A stream of RESULT frames split at random byte boundaries
        reassembles to exactly the original messages, in order."""
        stream = b"".join(P.encode_result(rid, lbl, lat)
                          for rid, lbl, lat in results)
        reader = P.FrameReader()
        out = []
        i = 0
        while i < len(stream):
            j = min(len(stream), i + rnd.randint(1, 7))
            out.extend(reader.feed(stream[i:j]))
            i = j
        assert reader.buffered == 0
        got = [P.decode_message(p) for p in out]
        assert [(m.req_id, m.label, m.latency_ms) for m in got] == \
            [(rid, lbl, lat) for rid, lbl, lat in results]


# ---------------------------------------------------------------------------
# Socket serving: all five golden datasets, bit-identical to offline predict
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def golden_emit_dir(tmp_path_factory):
    """All five golden Table-2 classifiers emitted into one fleet dir."""
    from test_golden import GOLDEN_DIR, golden_classifier
    from repro.data.tabular import DATASETS

    out = tmp_path_factory.mktemp("transport_fleet")
    vectors = {}
    for name in sorted(DATASETS):
        cc, _ = golden_classifier(name)
        write_artifacts(cc, out, base=f"tnn_{name}", dataset=name)
        vectors[f"tnn_{name}"] = np.load(GOLDEN_DIR / f"{name}.npz")["x"]
    return out, vectors


@pytest.fixture(scope="module")
def golden_server(golden_emit_dir):
    emit_dir, vectors = golden_emit_dir
    fleet = ClassifierFleet.from_emit_dir(emit_dir, backends="swar",
                                          max_batch=64, deadline_ms=5_000.0)
    server = FleetServer(fleet)
    host, port = server.start_background()
    yield (host, port), emit_dir, vectors
    server.stop()
    fleet.shutdown(drain=True)


def test_socket_labels_bit_identical_on_all_golden_datasets(golden_server):
    """Acceptance: every golden vector of every Table-2 dataset, served
    through HELLO/SUBMIT/RESULT over TCP, gets the exact label the
    offline `CircuitProgram.predict` of the same emitted bundle gives."""
    (host, port), emit_dir, vectors = golden_server
    from repro.compile.artifact import load_manifest

    rows = {r["name"]: r for r in load_manifest(emit_dir)}
    assert len(rows) == 5
    with FleetClient(host, port) as client:
        served = {r["name"] for r in client.tenants()}
        assert served == set(rows)
        for tenant, x in sorted(vectors.items()):
            got = client.classify(tenant, x, timeout=120.0)
            offline = load_program(emit_dir / rows[tenant]["program"])
            want = offline.predict(x).astype(np.int32)
            np.testing.assert_array_equal(
                got, want, err_msg=f"socket transport != offline predict "
                                   f"({tenant})")


def test_socket_pipelines_interleaved_tenants(golden_server):
    """Many in-flight submits across tenants on one connection resolve to
    the right labels by req_id, whatever order completions arrive in."""
    (host, port), emit_dir, vectors = golden_server
    from repro.compile.artifact import load_manifest

    rows = {r["name"]: r for r in load_manifest(emit_dir)}
    refs = {t: load_program(emit_dir / rows[t]["program"]).predict(x)
            for t, x in vectors.items()}
    with FleetClient(host, port) as client:
        pend = []
        for i in range(max(len(x) for x in vectors.values())):
            for t in sorted(vectors):
                if i < len(vectors[t]):
                    pend.append((t, i, client.submit(t, vectors[t][i])))
        for t, i, p in pend:
            assert p.result(timeout=120.0) == int(refs[t][i]), (t, i)


def test_server_reports_stats_and_errors(golden_server):
    (host, port), _, vectors = golden_server
    with FleetClient(host, port) as client:
        tenant = sorted(vectors)[0]
        client.classify(tenant, vectors[tenant][:8], timeout=60.0)
        s = client.stats()
        assert s["fleet"]["n_requests"] >= 8
        assert tenant in s["tenants"]
        from repro.serve.client import FleetClientError
        with pytest.raises(FleetClientError, match="unknown tenant"):
            client.submit("no_such_tenant", vectors[tenant][0]).result(30.0)
        with pytest.raises(FleetClientError, match="features"):
            client.submit(tenant, np.zeros(1)).result(30.0)


# ---------------------------------------------------------------------------
# Admission control under synthetic overload
# ---------------------------------------------------------------------------
def _toy_classifier(F=9, H=5, Cc=4, seed=7):
    rng = np.random.default_rng(seed)
    w1t = rng.integers(-1, 2, size=(F, H)).astype(np.int8)
    w2t = T.balance_zero_counts(rng.normal(size=(H, Cc)), 1 / 3)
    tnn = T.TrainedTNN(w1t=w1t, w2t=w2t, thresholds=np.full(F, 0.5),
                       train_acc=0.0, test_acc=0.0, name=f"toy{seed}")
    return lower_classifier(tnn, *T.exact_netlists(tnn))


class _SlowProgram:
    """Delegating program wrapper that makes every dispatch cost `delay_s`
    — synthetic overload without timing-sensitive producers."""

    def __init__(self, inner, delay_s: float):
        self._inner = inner
        self._delay_s = delay_s

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def predict(self, x):
        time.sleep(self._delay_s)
        return self._inner.predict(x)


def test_overload_sheds_nonzero_and_accepted_requests_meet_slo():
    """Acceptance: with engines slowed so the offered load far exceeds
    capacity, submissions beyond `max_queue` shed with a positive
    `retry_after_ms` — and every *accepted* request still gets the right
    label within its (generous) deadline: zero SLO misses."""
    cc = _toy_classifier()
    prog = CircuitProgram.from_classifier(cc, backend="np")
    ref = CircuitProgram.from_classifier(cc).predict
    deadline_ms = 20_000.0
    spec = TenantSpec(name="slow", program=prog, backend="np", max_batch=8,
                      deadline_ms=deadline_ms, max_queue=16)
    fleet = ClassifierFleet([spec], warmup=False, autostart=False)
    for rep in fleet._tenant("slow").pool.replicas:
        rep.engine.program = _SlowProgram(rep.engine.program, 0.02)
    fleet.start()
    server = FleetServer(fleet)
    host, port = server.start_background()
    x = np.random.default_rng(3).random((400, 9))
    want = ref(x)
    accepted, sheds = [], 0
    try:
        with FleetClient(host, port) as client:
            pend = [client.submit("slow", row, deadline_ms=deadline_ms)
                    for row in x]
            for i, p in enumerate(pend):
                try:
                    label = p.result(timeout=120.0)
                except FleetShedError as exc:
                    sheds += 1
                    assert exc.retry_after_ms >= 1.0
                else:
                    accepted.append((i, label))
            stats = client.stats()
    finally:
        server.stop()
        fleet.shutdown(drain=True)

    assert sheds > 0, "overload never shed — admission control is inert"
    assert len(accepted) + sheds == x.shape[0]
    assert len(accepted) > 0
    for i, label in accepted:            # every accepted label is correct
        assert label == int(want[i]), i
    tstats = stats["tenants"]["slow"]
    assert stats["fleet"]["n_shed"] == tstats["n_shed"] == sheds
    assert tstats["n_slo_miss"] == 0, \
        "accepted requests missed SLO under overload — shedding too late"
    assert stats["fleet"]["n_slo_miss"] == 0


def test_shed_recovers_once_backlog_drains():
    """After an overload burst is served, the same tenant accepts again —
    shedding is a queue-depth signal, not a latched state."""
    cc = _toy_classifier(seed=11)
    prog = CircuitProgram.from_classifier(cc, backend="np")
    spec = TenantSpec(name="t", program=prog, backend="np", max_batch=4,
                      deadline_ms=60_000.0, max_queue=8)
    fleet = ClassifierFleet([spec], warmup=False, autostart=False)
    for rep in fleet._tenant("t").pool.replicas:
        rep.engine.program = _SlowProgram(rep.engine.program, 0.01)
    fleet.start()
    from repro.serve import FleetOverloadError

    x = np.random.default_rng(5).random((64, 9))
    try:
        shed = 0
        for row in x:
            try:
                fleet.submit("t", row)
            except FleetOverloadError:
                shed += 1
        assert shed > 0
        fleet.flush(timeout=60.0)
        # queue drained: accepted again (short budget so it ships promptly)
        req = fleet.submit("t", x[0], deadline_ms=200.0)
        assert req.result(timeout=30.0) is not None
    finally:
        fleet.shutdown(drain=True)


# ---------------------------------------------------------------------------
# Hot reload over the wire: RELOAD RPC + the manifest mtime watcher
# ---------------------------------------------------------------------------
def test_server_hot_reload_rpc_and_watcher(tmp_path):
    write_artifacts(_toy_classifier(seed=7), tmp_path, base="alpha")
    fleet = ClassifierFleet.from_emit_dir(tmp_path, backends="swar",
                                          max_batch=32, deadline_ms=500.0)
    server = FleetServer(fleet, watch_manifest=True, watch_interval_s=0.05)
    host, port = server.start_background()
    try:
        with FleetClient(host, port) as client:
            assert [t["name"] for t in client.tenants()] == ["alpha"]
            # explicit RELOAD round-trip picks up a new tenant (the mtime
            # watcher may legitimately win the race and sync it first, in
            # which case the RPC reconcile is a no-op — either way the
            # tenant must be live afterwards)
            cc_beta = _toy_classifier(F=6, H=4, Cc=3, seed=11)
            write_artifacts(cc_beta, tmp_path, base="beta")
            actions = client.reload()
            assert actions["added"] in ([], ["beta"])
            assert "beta" in {t["name"] for t in client.tenants()}
            x = np.random.default_rng(0).random((16, 6))
            np.testing.assert_array_equal(
                client.classify("beta", x, timeout=60.0),
                CircuitProgram.from_classifier(cc_beta).predict(x))
            # the mtime watcher catches a re-emit on its own
            gen = [t for t in client.tenants()
                   if t["name"] == "alpha"][0]["generation"]
            write_artifacts(_toy_classifier(seed=42), tmp_path, base="alpha")
            deadline = time.monotonic() + 15.0
            while time.monotonic() < deadline:
                rows = {t["name"]: t for t in client.tenants()}
                if rows["alpha"]["generation"] > gen:
                    break
                time.sleep(0.05)
            else:
                pytest.fail("watcher never hot-reloaded the re-emitted "
                            "tenant")
            labels = client.classify("alpha",
                                     np.random.default_rng(1).random((8, 9)),
                                     timeout=60.0)
            assert labels.shape == (8,)
    finally:
        server.stop()
        fleet.shutdown(drain=True)


# ---------------------------------------------------------------------------
# CLI contract: mismatch exits nonzero even without --strict
# ---------------------------------------------------------------------------
def _fake_report(match: bool, slo_miss: int = 0, shed: int = 0,
                 errors: list | None = None) -> dict:
    return {
        "tenants": {"t": {"backend": "swar", "replicas": 1, "dataset": "d",
                          "readings": 4, "labels_match_offline": match,
                          "slo_miss": slo_miss, "n_shed": shed,
                          "worst_latency_ms": 1.0, "req_p50_ms": 1.0,
                          "req_p99_ms": 1.0}},
        "fleet": {"n_readings": 4, "n_batches": 1, "n_slo_miss": slo_miss,
                  "n_shed": shed, "req_p99_ms": 1.0},
        "errors": errors or [],
        "labels_match_offline": match,
        "transport": "inproc",
        "producers": 1,
    }


def test_exit_code_mismatch_fails_without_strict():
    from repro.serve.__main__ import exit_code

    assert exit_code(_fake_report(True), strict=False) == 0
    # regression: a bit-identity mismatch must fail even without --strict
    assert exit_code(_fake_report(False), strict=False) == 1
    assert exit_code(_fake_report(False), strict=True) == 1
    # dispatch errors too
    assert exit_code(_fake_report(True, errors=["boom"]), strict=False) == 1
    # SLO misses and sheds gate only under --strict
    assert exit_code(_fake_report(True, slo_miss=3), strict=False) == 0
    assert exit_code(_fake_report(True, slo_miss=3), strict=True) == 1
    assert exit_code(_fake_report(True, shed=2), strict=False) == 0
    assert exit_code(_fake_report(True, shed=2), strict=True) == 1


def test_replay_cli_exits_nonzero_on_mismatch_without_strict(
        golden_emit_dir, monkeypatch):
    """End-to-end regression for the CLI: fabricate a label mismatch in
    the replay path and check `python -m repro.serve replay` (no
    --strict) returns 1."""
    import repro.serve.__main__ as M

    emit_dir, _ = golden_emit_dir
    monkeypatch.setattr(
        M, "replay_fleet",
        lambda fleet, streams, producers=4, timeout=120.0:
            _fake_report(False))
    rc = M.main(["replay", "--emit-dir", str(emit_dir),
                 "--replay", "all", "--readings", "4", "--producers", "1"])
    assert rc == 1
    # and the legacy bare-flag spelling resolves to the same path
    rc = M.main(["--emit-dir", str(emit_dir),
                 "--replay", "all", "--readings", "4", "--producers", "1"])
    assert rc == 1


# ---------------------------------------------------------------------------
# Protocol v2: batch frames, version negotiation, the 64 MiB cap
# ---------------------------------------------------------------------------
def test_protocol_v2_batch_frames_round_trip():
    rng = np.random.default_rng(1)
    x = rng.random((13, 7))
    rids = np.arange(100, 113, dtype=np.uint64)
    dls = np.full(13, np.nan)
    dls[3] = 12.5
    (payload,) = P.FrameReader().feed(
        P.encode_submit_batch(rids, "tnn_cardio", x, dls))
    msg = P.decode_message(payload)
    assert msg.type == P.MSG_SUBMIT_BATCH and msg.tenant == "tnn_cardio"
    np.testing.assert_array_equal(msg.req_ids, rids)
    np.testing.assert_array_equal(msg.readings, x)   # bit-exact plane
    assert np.isnan(msg.deadlines_ms[0]) and msg.deadlines_ms[3] == 12.5

    labels = (np.arange(13) % 4).astype(np.int32)
    lats = np.linspace(0.5, 2.0, 13)
    (payload,) = P.FrameReader().feed(
        P.encode_result_batch(rids, labels, lats))
    msg = P.decode_message(payload)
    assert msg.type == P.MSG_RESULT_BATCH
    np.testing.assert_array_equal(msg.req_ids, rids)
    np.testing.assert_array_equal(msg.labels, labels)
    np.testing.assert_allclose(msg.latencies_ms, lats)


def test_protocol_version_negotiation():
    assert P.negotiate_version(1) == 1      # a v1 client is served at v1
    assert P.negotiate_version(P.PROTOCOL_VERSION) == P.PROTOCOL_VERSION
    assert P.negotiate_version(99) == P.PROTOCOL_VERSION    # future client
    with pytest.raises(P.ProtocolError):
        P.negotiate_version(0)              # below the supported floor
    # HELLO / WELCOME carry the version on the wire
    assert P.decode_message(P.encode_hello(1)[4:]).version == 1
    assert P.decode_message(P.encode_welcome(2)[4:]).version == 2


if _HAVE_HYPOTHESIS:

    @settings(max_examples=N_EXAMPLES, deadline=None)
    @given(st.lists(st.tuples(st.integers(1, 9), st.integers(1, 6),
                              st.integers(0, 2**32)),
                    min_size=1, max_size=6),
           st.randoms(use_true_random=False))
    def test_batch_frames_survive_arbitrary_chunking(shapes, rnd):
        """SUBMIT_BATCH frames split at random byte boundaries reassemble
        to the exact req_id tables and reading planes, in order."""
        frames, want = [], []
        for k, (b, f, seed) in enumerate(shapes):
            x = np.random.default_rng(seed).random((b, f))
            rids = np.arange(k * 1000, k * 1000 + b, dtype=np.uint64)
            frames.append(P.encode_submit_batch(rids, f"t{k}", x))
            want.append((f"t{k}", rids, x))
        stream = b"".join(frames)
        reader = P.FrameReader()
        out, i = [], 0
        while i < len(stream):
            j = min(len(stream), i + rnd.randint(1, 7))
            out.extend(reader.feed(stream[i:j]))
            i = j
        assert reader.buffered == 0 and len(out) == len(frames)
        for payload, (tenant, rids, x) in zip(out, want):
            msg = P.decode_message(payload)
            assert msg.tenant == tenant
            np.testing.assert_array_equal(msg.req_ids, rids)
            np.testing.assert_array_equal(msg.readings, x)


def test_batch_frame_near_the_64mib_cap_decodes():
    """`batch_rows_per_frame` is the exact fit: its row count lands within
    a whisker of MAX_FRAME and still decodes; one hostile byte past the
    cap is rejected at the framer."""
    F = 4096
    rows = P.batch_rows_per_frame(F)
    frame = P.encode_submit_batch(np.arange(rows, dtype=np.uint64), "t",
                                  np.zeros((rows, F)))
    assert len(frame) - 4 <= P.MAX_FRAME
    assert len(frame) - 4 > 0.95 * P.MAX_FRAME      # actually near the cap
    (payload,) = P.FrameReader().feed(frame)
    msg = P.decode_message(payload)
    assert msg.readings.shape == (rows, F)
    import struct as _struct

    with pytest.raises(P.ProtocolError):
        P.FrameReader().feed(_struct.pack("!I", P.MAX_FRAME + 1))


def test_oversized_batch_gets_clean_error_not_a_hung_connection(
        golden_server):
    """A frame bigger than the cap draws a connection-level ERROR and a
    close — never a silent hang."""
    import socket as _socket
    import struct as _struct

    (host, port), _, _ = golden_server

    def read_frame(s):
        head = b""
        while len(head) < 4:
            head += s.recv(4 - len(head))
        (ln,) = _struct.unpack("!I", head)
        buf = b""
        while len(buf) < ln:
            buf += s.recv(ln - len(buf))
        return buf

    with _socket.create_connection((host, port), timeout=30) as s:
        s.sendall(P.encode_hello())
        assert P.decode_message(read_frame(s)).type == P.MSG_WELCOME
        s.sendall(_struct.pack("!I", P.MAX_FRAME + 1))  # hostile batch size
        msg = P.decode_message(read_frame(s))
        assert msg.type == P.MSG_ERROR and msg.req_id == P.CONN_ERR
        assert s.recv(1) == b""             # and the server hung up


def test_v1_client_against_v2_server_stays_bit_identical(golden_server):
    """Version negotiation: a client pinned to protocol v1 is served at
    v1 (per-reading SUBMIT frames) and still gets offline-exact labels."""
    (host, port), emit_dir, vectors = golden_server
    from repro.compile.artifact import load_manifest

    tenant = sorted(vectors)[0]
    x = vectors[tenant]
    rows = {r["name"]: r for r in load_manifest(emit_dir)}
    want = load_program(
        emit_dir / rows[tenant]["program"]).predict(x).astype(np.int32)
    with FleetClient(host, port, protocol_version=1) as client:
        assert client.protocol_version == 1
        np.testing.assert_array_equal(
            client.classify(tenant, x, timeout=120.0), want)


def test_submit_many_chunks_batch_frames_bit_identical(golden_server):
    """The v2 batch path, forced through many small SUBMIT_BATCH frames
    (tiny max_frame), resolves every row to the offline label."""
    (host, port), emit_dir, vectors = golden_server
    from repro.compile.artifact import load_manifest

    tenant = sorted(vectors)[0]
    x = vectors[tenant]
    rows = {r["name"]: r for r in load_manifest(emit_dir)}
    want = load_program(
        emit_dir / rows[tenant]["program"]).predict(x).astype(np.int32)
    with FleetClient(host, port) as client:
        assert client.protocol_version == P.PROTOCOL_VERSION
        handles = client.submit_many(tenant, x, max_frame=1 << 12)
        got = np.array([h.result(120.0) for h in handles], dtype=np.int32)
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# Batched ingest: fleet fast path, sharded accept loops, UDP, coalescer
# ---------------------------------------------------------------------------
def test_fleet_submit_many_partial_admission_and_identity():
    """One lock acquisition admits the head of the frame up to queue room
    and sheds the tail with a retry hint; admitted rows serve to
    offline-exact labels in arrival order."""
    cc = _toy_classifier()
    prog = CircuitProgram.from_classifier(cc, backend="np")
    ref = CircuitProgram.from_classifier(cc).predict
    spec = TenantSpec(name="t", program=prog, backend="np", max_batch=8,
                      deadline_ms=20_000.0, max_queue=16)
    fleet = ClassifierFleet([spec], warmup=False, autostart=False)
    for rep in fleet._tenant("t").pool.replicas:
        rep.engine.program = _SlowProgram(rep.engine.program, 0.01)
    fleet.start()
    x = np.random.default_rng(5).random((64, 9))
    want = ref(x)
    try:
        reqs, shed_idx, retry_ms = fleet.submit_many("t", x)
        assert len(reqs) + len(shed_idx) == 64
        assert len(shed_idx) >= 64 - 16 > 0 and retry_ms > 0
        # admission is in arrival order: the shed rows are the tail
        np.testing.assert_array_equal(
            shed_idx, np.arange(64 - len(shed_idx), 64))
        for r in reqs:
            r.result(60.0)
        labels = np.array([r.label for r in reqs], dtype=np.int32)
        np.testing.assert_array_equal(labels, want[:len(reqs)])
    finally:
        fleet.shutdown(drain=True)


def test_sharded_server_udp_ingest_and_coalescer():
    """The swarm transports in one sweep: SO_REUSEPORT shards serve
    concurrent connections correctly, the client-side coalescer flushes
    on both size and age, and fire-and-forget UDP datagrams land in the
    server's ingest counters."""
    from repro.serve.client import CoalescingSubmitter, UdpSwarmSender
    from repro.serve.server import FleetServer as _FS

    cc = _toy_classifier()
    prog = CircuitProgram.from_classifier(cc, backend="np")
    ref = CircuitProgram.from_classifier(cc).predict
    spec = TenantSpec(name="t", program=prog, backend="np", max_batch=32,
                      deadline_ms=10_000.0)
    fleet = ClassifierFleet([spec], warmup=False, autostart=False)
    fleet.start()
    server = _FS(fleet, shards=2, udp_port=0)
    host, port = server.start_background()
    x = np.random.default_rng(11).random((96, 9))
    want = ref(x).astype(np.int32)
    try:
        with FleetClient(host, port) as c, FleetClient(host, port) as c2:
            np.testing.assert_array_equal(
                c2.classify("t", x[:32], timeout=60.0), want[:32])
            with CoalescingSubmitter(c, max_rows=16,
                                     max_delay_ms=25.0) as cs:
                pends = [cs.submit("t", x[i]) for i in range(40)]
                got = np.array([p.result(60.0) for p in pends],
                               dtype=np.int32)
            np.testing.assert_array_equal(got, want[:40])

            before = c.stats()["transport"]["udp"]["n_readings"]
            with UdpSwarmSender(host, server.udp_address[1]) as u:
                n = u.send_many("t", x)
                u.send("t", x[0])
            deadline = time.monotonic() + 30
            got_n = 0
            while time.monotonic() < deadline:
                got_n = c.stats()["transport"]["udp"]["n_readings"] - before
                if got_n >= n + 1:
                    break
                time.sleep(0.05)
            assert got_n == n + 1, f"UDP ingest saw {got_n}/{n + 1}"
            assert c.stats()["transport"]["shards"] == 2
    finally:
        server.stop()
        fleet.shutdown(drain=True)


# ---------------------------------------------------------------------------
# Frame completion: one sink call, one loop wake-up, one RESULT_BATCH a frame
# ---------------------------------------------------------------------------
def _read_frame(s) -> bytes:
    import struct as _struct

    def read_n(n):
        buf = b""
        while len(buf) < n:
            chunk = s.recv(n - len(buf))
            assert chunk, "server hung up"
            buf += chunk
        return buf

    (ln,) = _struct.unpack("!I", read_n(4))
    return read_n(ln)


def _sink_readings() -> int:
    return obs.snapshot().get("fleet.complete.sink", {"n": 0})["n"]


@pytest.fixture
def toy_server():
    """One np toy tenant, `max_batch` 256, behind a background server; a
    short deadline flushes a part batch soon."""
    cc = _toy_classifier()
    spec = TenantSpec(name="t", backend="np", max_batch=256,
                      deadline_ms=250.0,
                      program=CircuitProgram.from_classifier(cc,
                                                             backend="np"))
    fleet = ClassifierFleet([spec], warmup=False)
    server = FleetServer(fleet)
    address = server.start_background()
    yield address, fleet, CircuitProgram.from_classifier(cc).predict
    server.stop()
    fleet.shutdown(drain=True)


def _submit_frame_raw(address, x, version, first_rid=1000):
    """Send one SUBMIT_BATCH frame on a raw connection at `version` and
    read back every answer frame until each row has one: returns the
    req_ids sent and the `(message, payload)` of each answer."""
    import socket as _socket

    rids = np.arange(first_rid, first_rid + len(x), dtype=np.uint64)
    frames = []
    with _socket.create_connection(address, timeout=60) as s:
        s.sendall(P.encode_hello(version))
        welcome = P.decode_message(_read_frame(s))
        assert welcome.type == P.MSG_WELCOME and welcome.version == version
        s.sendall(P.encode_submit_batch(rids, "t", x))
        answered = 0
        while answered < len(x):
            payload = _read_frame(s)
            msg = P.decode_message(payload)
            frames.append((msg, payload))
            answered += (len(msg.req_ids) if msg.type == P.MSG_RESULT_BATCH
                         else 1)
    return rids, frames


def test_frame_answers_in_one_result_batch_and_counts_the_sink(toy_server):
    """A 256-row SUBMIT_BATCH served by one dispatch comes back as ONE
    RESULT_BATCH frame (one sink call, one queue item) with the offline
    labels, and `fleet.complete.sink` counts its 256 readings."""
    address, _, ref = toy_server
    x = np.random.default_rng(59).random((256, 9))
    before = _sink_readings()
    rids, frames = _submit_frame_raw(address, x, P.PROTOCOL_VERSION)
    assert [m.type for m, _ in frames] == [P.MSG_RESULT_BATCH]
    (msg, _), = frames
    np.testing.assert_array_equal(msg.req_ids, rids)
    np.testing.assert_array_equal(msg.labels, ref(x))
    assert np.all(msg.latencies_ms > 0)
    assert _sink_readings() - before == 256


def test_v1_connection_gets_one_result_frame_a_reading(toy_server):
    """Held to protocol v1, a frame's completions still leave as one
    RESULT frame a reading, with the offline labels."""
    address, _, ref = toy_server
    x = np.random.default_rng(61).random((40, 9))
    rids, frames = _submit_frame_raw(address, x, 1)
    assert {m.type for m, _ in frames} == {P.MSG_RESULT}
    got = {m.req_id: m.label for m, _ in frames}
    assert sorted(got) == rids.tolist()
    want = ref(x)
    assert [got[int(r)] for r in rids] == [int(v) for v in want]
    for m, payload in frames:   # byte for byte the v1 RESULT encoding
        assert P.frame(payload) == P.encode_result(m.req_id, m.label,
                                                   m.latency_ms)


def test_dispatch_failure_answers_every_frame_row_with_error(toy_server):
    """Every row of a frame whose dispatch fails gets an ERROR frame;
    none is left hanging."""
    from repro.serve.client import FleetClientError

    address, fleet, _ = toy_server

    def boom(x):
        raise RuntimeError("device lost")

    for rep in fleet._tenant("t").pool.replicas:
        rep.engine.classify_batch = boom
    x = np.random.default_rng(67).random((256, 9))
    with FleetClient(*address) as client:
        handles = client.submit_many("t", x)
        for h in handles:
            with pytest.raises(FleetClientError, match="device lost"):
                h.result(60.0)


def test_unary_submit_never_engages_the_frame_sink(toy_server):
    """Unary SUBMIT completes per request: `fleet.complete.sink` stays
    where it was, and the labels are the offline ones."""
    address, _, ref = toy_server
    x = np.random.default_rng(71).random((12, 9))
    before = _sink_readings()
    with FleetClient(*address, protocol_version=1) as client:
        pending = [client.submit("t", row) for row in x]
        got = [p.result(60.0) for p in pending]
    assert got == [int(v) for v in ref(x)]
    assert _sink_readings() == before
