"""Concurrency/soak tier for the multi-tenant serving fleet.

Three layers of pinning:

  * **soak** — N producer threads blast interleaved readings at a
    multi-tenant fleet for a fixed wall-clock budget; every request must be
    answered exactly once, bit-identical to the offline
    `CircuitProgram.predict`, and no request may exceed its deadline by
    more than one dispatch interval (+ CI scheduling slack).
  * **property** — the deadline-driven `MicroBatcher` policy is pure logic
    over an injected clock, so hypothesis drives arbitrary arrival orders,
    batch sizes and budgets through the exact production decision code:
    never reorders within a tenant, never exceeds `max_batch`, drains to
    empty on shutdown.
  * **lifecycle** — manifest round-trips, deadline-triggered partial
    flushes, drain-vs-cancel shutdown, validation errors.

Budget knob: the hypothesis example count follows the repo-wide
REPRO_CONFORMANCE_EXAMPLES (nightly CI raises it).
"""
import os
import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.compile import CircuitProgram, lower_classifier, write_artifacts
from repro.compile.artifact import load_manifest
from repro.core import tnn as T
from repro.serve import ClassifierFleet, MicroBatcher, TenantSpec

N_EXAMPLES = int(os.environ.get("REPRO_CONFORMANCE_EXAMPLES", "20"))

# (features, hidden, classes, rng seed) per toy tenant
TOY_TENANTS = {
    "toy_a": (9, 5, 4, 7),
    "toy_b": (6, 4, 3, 11),
    "toy_c": (12, 6, 5, 13),
}


def _toy_classifier(F, H, Cc, seed):
    rng = np.random.default_rng(seed)
    w1t = rng.integers(-1, 2, size=(F, H)).astype(np.int8)
    w2t = T.balance_zero_counts(rng.normal(size=(H, Cc)), 1 / 3)
    tnn = T.TrainedTNN(w1t=w1t, w2t=w2t, thresholds=np.full(F, 0.5),
                       train_acc=0.0, test_acc=0.0, name=f"toy{seed}")
    return lower_classifier(tnn, *T.exact_netlists(tnn))


@pytest.fixture(scope="module")
def emit_dir(tmp_path_factory):
    """An emit directory holding every toy tenant + its manifest."""
    out = tmp_path_factory.mktemp("fleet_artifacts")
    ccs = {}
    for name, (F, H, Cc, seed) in TOY_TENANTS.items():
        cc = _toy_classifier(F, H, Cc, seed)
        write_artifacts(cc, out, base=name)
        ccs[name] = cc
    return out, ccs


def test_manifest_lists_every_tenant(emit_dir):
    out, ccs = emit_dir
    rows = load_manifest(out)
    assert [r["name"] for r in rows] == sorted(TOY_TENANTS)
    for r in rows:
        F = TOY_TENANTS[r["name"]][0]
        assert r["n_features"] == F
        assert (out / r["program"]).exists()


def test_reemit_replaces_manifest_row(emit_dir, tmp_path):
    cc = _toy_classifier(5, 3, 2, 42)
    for _ in range(2):
        write_artifacts(cc, tmp_path, base="twice")
    rows = load_manifest(tmp_path)
    assert [r["name"] for r in rows] == ["twice"]


def test_fleet_loads_and_routes(emit_dir):
    out, ccs = emit_dir
    fleet = ClassifierFleet.from_emit_dir(out, backends="swar", max_batch=32)
    try:
        assert fleet.tenants == sorted(TOY_TENANTS)
        for name, (F, _, _, _) in TOY_TENANTS.items():
            assert fleet.n_features(name) == F
        with pytest.raises(KeyError):
            fleet.submit("nope", np.zeros(9))
        with pytest.raises(ValueError):
            fleet.submit("toy_a", np.zeros(5))       # wrong feature count
    finally:
        fleet.shutdown(drain=True)


def test_unknown_tenant_selection_and_duplicates(emit_dir):
    out, ccs = emit_dir
    with pytest.raises(KeyError):
        ClassifierFleet.from_emit_dir(out, tenants=["missing"])
    prog = CircuitProgram.from_classifier(ccs["toy_a"])
    spec = TenantSpec(name="dup", program=prog)
    with pytest.raises(ValueError):
        ClassifierFleet([spec, spec], warmup=False, autostart=False)
    with pytest.raises(ValueError):
        ClassifierFleet([TenantSpec(name="x", program=prog,
                                    backend="cuda")],
                        warmup=False, autostart=False)


# ---------------------------------------------------------------------------
# Soak: concurrent producers, multiple tenants, mixed backends
# ---------------------------------------------------------------------------
def test_soak_concurrent_producers_exactly_once_bit_identical(emit_dir):
    out, ccs = emit_dir
    deadline_ms = 150.0
    fleet = ClassifierFleet.from_emit_dir(
        out, backends={"toy_a": "np", "toy_b": "swar", "toy_c": "swar"},
        max_batch=64, deadline_ms=deadline_ms)
    n_producers = 4
    budget_s = 0.6
    pools = {name: np.random.default_rng(i).random((50, spec[0]))
             for i, (name, spec) in enumerate(sorted(TOY_TENANTS.items()))}
    names = sorted(TOY_TENANTS)
    submitted: list[list] = [[] for _ in range(n_producers)]

    def produce(w: int) -> None:
        rng = np.random.default_rng(1000 + w)
        t_end = time.perf_counter() + budget_s
        k = 0
        while time.perf_counter() < t_end:
            name = names[(w + k) % len(names)]           # interleave tenants
            idx = int(rng.integers(0, pools[name].shape[0]))
            req = fleet.submit(name, pools[name][idx])
            submitted[w].append((name, idx, req))
            k += 1
            if k % 7 == 0:                  # vary arrival pattern a little
                time.sleep(0.001)

    threads = [threading.Thread(target=produce, args=(w,))
               for w in range(n_producers)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        fleet.flush(timeout=30)
    finally:
        fleet.shutdown(drain=True)

    flat = [item for per_worker in submitted for item in per_worker]
    assert len(flat) > 0
    assert fleet.errors == []

    # answered exactly once: every handle completed, uids unique, and the
    # engines served exactly as many requests as were submitted
    uids = [req.uid for _, _, req in flat]
    assert len(set(uids)) == len(uids)
    assert all(req.done() and req.label is not None for _, _, req in flat)
    assert fleet.stats.n_requests == len(flat)
    per_tenant = {name: sum(1 for n, _, _ in flat if n == name)
                  for name in names}
    summaries = fleet.stats_summary()["tenants"]
    for name in names:
        assert summaries[name]["n_requests"] == per_tenant[name]

    # bit-identical to the offline program on every backend
    refs = {name: CircuitProgram.from_classifier(ccs[name]).predict(
        pools[name]) for name in names}
    for name, idx, req in flat:
        assert req.label == int(refs[name][idx]), (name, idx)

    # latency: nothing may overshoot its deadline by more than one
    # dispatch interval (worst observed batch) + scheduling slack.  The
    # slack floor is generous on purpose: a single-core CI worker running
    # the full suite has been observed to stall every thread of this
    # process ~1.5 s at a time, which is scheduler noise, not a
    # flush-policy bug — the policy itself is pinned timing-free by the
    # hypothesis tier, so this bound only has to catch a stuck scheduler.
    worst_batch_ms = max(summaries[name]["p99_ms"] for name in names)
    tol_ms = deadline_ms + max(2 * worst_batch_ms, 2_500.0)
    late = [(name, req.latency_ms) for name, _, req in flat
            if req.latency_ms > tol_ms]
    assert not late, f"requests busted deadline+interval: {late[:5]}"


def test_deadline_triggers_partial_flush(emit_dir):
    """A lone request (far below max_batch) must be served by its deadline
    without anyone calling flush — the scheduler's whole point."""
    out, _ = emit_dir
    fleet = ClassifierFleet.from_emit_dir(out, backends="swar",
                                          max_batch=256, deadline_ms=100.0)
    try:
        req = fleet.submit("toy_a", np.zeros(9))
        label = req.result(timeout=10.0)
        assert label is not None and req.latency_ms is not None
        # served once due, not held for max_batch company that never comes
        assert req.latency_ms < 5_000.0
    finally:
        fleet.shutdown(drain=True)


def test_shutdown_drains_backlog(emit_dir):
    out, ccs = emit_dir
    fleet = ClassifierFleet.from_emit_dir(out, backends="swar",
                                          max_batch=128,
                                          deadline_ms=60_000.0)
    x = np.random.default_rng(5).random((40, 9))
    reqs = [fleet.submit("toy_a", row) for row in x]
    fleet.shutdown(drain=True)          # far before any deadline
    ref = CircuitProgram.from_classifier(ccs["toy_a"]).predict(x)
    assert [r.label for r in reqs] == [int(v) for v in ref]
    with pytest.raises(RuntimeError):
        fleet.submit("toy_a", x[0])     # fleet is closed


def test_shutdown_cancel_completes_exceptionally(emit_dir):
    out, _ = emit_dir
    fleet = ClassifierFleet.from_emit_dir(out, backends="swar",
                                          max_batch=128,
                                          deadline_ms=60_000.0)
    req = fleet.submit("toy_b", np.zeros(6))
    fleet.shutdown(drain=False)
    assert req.done() and req.error is not None
    with pytest.raises(RuntimeError):
        req.result(timeout=1.0)


# ---------------------------------------------------------------------------
# Megakernel dispatch mode (fused multi-tenant pallas launches)
# ---------------------------------------------------------------------------
def test_megakernel_fuses_due_tenants_bit_identically(emit_dir):
    """All three toy tenants on the pallas backend, queues pre-loaded
    before the scheduler starts: the first fused pass must carry every
    tenant in ONE multi-program launch, and every label must match the
    offline `CircuitProgram.predict` reference."""
    out, ccs = emit_dir
    fleet = ClassifierFleet.from_emit_dir(
        out, backends="pallas", max_batch=64, deadline_ms=60_000.0,
        megakernel=True, autostart=False, warmup=False)
    rng = np.random.default_rng(17)
    handles = {}
    for name, (F, _, _, _) in TOY_TENANTS.items():
        x = rng.random((48, F))
        handles[name] = (x, [fleet.submit(name, row) for row in x])
    fleet.start()
    try:
        fleet.flush(timeout=60.0)
        for name, (x, reqs) in handles.items():
            ref = CircuitProgram.from_classifier(ccs[name]).predict(x)
            assert [r.result(timeout=60.0) for r in reqs] \
                == [int(v) for v in ref], name
        assert fleet.errors == []
        mk = fleet.stats_summary()["megakernel"]
        assert mk["launches"] >= 1
        assert mk["peak_tenants_per_launch"] == len(TOY_TENANTS), mk
        # per-tenant + fleet accounting both saw the fused traffic
        s = fleet.stats_summary()
        assert s["fleet"]["n_readings"] == 48 * len(TOY_TENANTS)
        for name in TOY_TENANTS:
            assert s["tenants"][name]["n_readings"] == 48
    finally:
        fleet.shutdown(drain=True)


def test_megakernel_only_fuses_pallas_backend(emit_dir):
    """Mixed-backend fleet with megakernel on: swar tenants keep their
    per-tenant dispatch path (and still serve correctly)."""
    out, ccs = emit_dir
    fleet = ClassifierFleet.from_emit_dir(
        out, backends={"toy_a": "pallas", "toy_b": "swar",
                       "toy_c": "pallas"},
        max_batch=64, deadline_ms=60_000.0, megakernel=True,
        autostart=False, warmup=False)
    rng = np.random.default_rng(23)
    handles = {}
    for name, (F, _, _, _) in TOY_TENANTS.items():
        x = rng.random((16, F))
        handles[name] = (x, [fleet.submit(name, row) for row in x])
    fleet.start()
    try:
        fleet.flush(timeout=60.0)
        for name, (x, reqs) in handles.items():
            ref = CircuitProgram.from_classifier(ccs[name]).predict(x)
            assert [r.result(timeout=60.0) for r in reqs] \
                == [int(v) for v in ref], name
        mk = fleet.stats_summary()["megakernel"]
        assert mk["peak_tenants_per_launch"] <= 2   # only the pallas pair
    finally:
        fleet.shutdown(drain=True)


@pytest.mark.parametrize("replicas,megakernel",
                         ((1, False), (2, False), (2, True)))
def test_build_tenant_warms_every_replica(emit_dir, monkeypatch, replicas,
                                          megakernel):
    """Warm-up on load: each replica's engine compiles its batch shape and
    the slowest warm dispatch seeds the tenant's estimate; a fused
    tenant's launches warm with the fleet table instead, so none of its
    engines warms."""
    from repro.kernels import pallas_circuit_sim as PS
    from repro.serve.engine import CircuitServingEngine

    out, _ = emit_dir
    warmed, table_s = [], []
    real_engine, real_table = CircuitServingEngine.warmup, PS.warm_fleet_table

    def engine_warmup(engine):
        dt = real_engine(engine)
        warmed.append((engine, dt))
        return dt

    def table_warmup(table):
        table_s.append(real_table(table))
        return table_s[-1]

    monkeypatch.setattr(CircuitServingEngine, "warmup", engine_warmup)
    monkeypatch.setattr(PS, "warm_fleet_table", table_warmup)
    fleet = ClassifierFleet.from_emit_dir(
        out, backends="pallas", tenants=["toy_a"], max_batch=32,
        replicas=replicas, megakernel=megakernel, autostart=False)
    try:
        t = fleet._tenant("toy_a")
        engines = [r.engine for r in t.pool.replicas]
        assert len(engines) == replicas
        if megakernel:
            assert warmed == [] and len(table_s) == 1
            want = max(1e-4, table_s[0])
        else:
            assert [e for e, _ in warmed] == engines and table_s == []
            want = max([1e-4] + [dt for _, dt in warmed])
        assert t.est_dispatch_s == t.last_dispatch_s == want > 0
    finally:
        fleet.shutdown()


# ---------------------------------------------------------------------------
# Hypothesis: the micro-batcher policy under arbitrary schedules
# ---------------------------------------------------------------------------
try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:

    arrival = st.tuples(
        st.floats(0.0, 50.0, allow_nan=False),       # inter-arrival gap, ms
        st.floats(0.5, 200.0, allow_nan=False),      # deadline budget, ms
    )

    @settings(max_examples=N_EXAMPLES, deadline=None)
    @given(st.integers(1, 8), st.lists(arrival, max_size=64),
           st.floats(0.0, 20.0, allow_nan=False))
    def test_microbatcher_order_size_drain(max_batch, arrivals, est_ms):
        """For arbitrary arrival orders / batch sizes / budgets: arrival
        order is preserved, no batch exceeds max_batch, due() never fires
        while the oldest request still has headroom, and shutdown drains
        to empty."""
        mb = MicroBatcher(max_batch, default_deadline_ms=50.0)
        est_s = est_ms * 1e-3
        now = 0.0
        seq = 0
        popped: list[int] = []
        for gap_ms, deadline_ms in arrivals:
            now += gap_ms * 1e-3
            mb.submit(seq, now, deadline_ms=deadline_ms)
            seq += 1
            while mb.due(now, est_s):
                batch = mb.pop_batch()
                assert 1 <= len(batch) <= max_batch
                popped.extend(e.item for e in batch)
            if len(mb):
                # not due: queue below max_batch and oldest has headroom
                assert len(mb) < max_batch
                assert now + est_s < mb.oldest_due_at
                # the advertised wakeup is exactly when due() flips
                wake = mb.next_due_at(est_s)
                assert wake is not None
                assert mb.due(wake + 1e-9, est_s)
                if wake - 1e-6 > now:
                    assert not mb.due(wake - 1e-6, est_s)
        for batch in mb.drain():                     # shutdown path
            assert 1 <= len(batch) <= max_batch
            popped.extend(e.item for e in batch)
        assert len(mb) == 0
        assert popped == list(range(seq))            # exactly once, in order


# ---------------------------------------------------------------------------
# Frame sinks: submit_many(on_done=) completes a frame's rows in bulk
# ---------------------------------------------------------------------------
class _SinkLog:
    """A frame sink that records every call (from dispatch threads)."""

    def __init__(self):
        self.calls = []
        self._lock = threading.Lock()

    def __call__(self, rows, labels, latencies_ms, error):
        with self._lock:
            self.calls.append((np.array(rows), labels, latencies_ms, error))

    def rows(self) -> np.ndarray:
        return np.concatenate([c[0] for c in self.calls])


def _sink_readings() -> int:
    return obs.snapshot().get("fleet.complete.sink", {"n": 0})["n"]


def _np_fleet(cc, max_batch: int, **kw) -> ClassifierFleet:
    spec = TenantSpec(name="toy_a", backend="np", max_batch=max_batch,
                      deadline_ms=60_000.0,
                      program=CircuitProgram.from_classifier(cc,
                                                             backend="np"))
    return ClassifierFleet([spec], warmup=False, autostart=False, **kw)


@pytest.mark.parametrize("backend,megakernel",
                         [("np", False), ("pallas", True)])
def test_frame_sink_fires_once_for_a_frame_on_one_dispatch(
        emit_dir, backend, megakernel):
    """A 256-row frame served by one dispatch (per-tenant or megakernel)
    calls its sink once with every row, its labels and its latencies;
    `fleet.complete.sink` counts the 256 readings, and each request's
    own handle still resolves."""
    out, ccs = emit_dir
    fleet = ClassifierFleet.from_emit_dir(
        out, backends=backend, tenants=["toy_a"], max_batch=256,
        deadline_ms=60_000.0, megakernel=megakernel, autostart=False,
        warmup=False)
    x = np.random.default_rng(31).random((256, 9))
    want = CircuitProgram.from_classifier(ccs["toy_a"]).predict(x)
    log = _SinkLog()
    before = _sink_readings()
    reqs, shed_idx, _ = fleet.submit_many("toy_a", x, on_done=log)
    assert len(reqs) == 256 and len(shed_idx) == 0
    fleet.start()
    try:
        fleet.flush(timeout=120.0)
        assert len(log.calls) == 1
        rows, labels, lats, error = log.calls[0]
        assert error is None
        np.testing.assert_array_equal(rows, np.arange(256))
        np.testing.assert_array_equal(labels, want)
        assert _sink_readings() - before == 256
        assert [r.result(1.0) for r in reqs] == [int(v) for v in want]
        np.testing.assert_array_equal(lats, [r.latency_ms for r in reqs])
        late = []
        reqs[0].add_done_callback(late.append)  # done already: runs now
        assert late == [reqs[0]]
    finally:
        fleet.shutdown(drain=True)


def test_frame_sink_split_across_dispatches_answers_rows_in_order(
        emit_dir):
    """With `max_batch=100` a 256-row frame takes three dispatches: the
    sink fires three times, every row is answered exactly once and in
    row order, with labels bit-identical to `CircuitProgram.predict`."""
    _, ccs = emit_dir
    fleet = _np_fleet(ccs["toy_a"], max_batch=100)
    x = np.random.default_rng(37).random((256, 9))
    want = CircuitProgram.from_classifier(ccs["toy_a"]).predict(x)
    log = _SinkLog()
    before = _sink_readings()
    fleet.submit_many("toy_a", x, on_done=log)
    fleet.start()
    try:
        fleet.flush(timeout=60.0)
        assert [len(c[0]) for c in log.calls] == [100, 100, 56]
        assert all(c[3] is None for c in log.calls)
        np.testing.assert_array_equal(log.rows(), np.arange(256))
        np.testing.assert_array_equal(
            np.concatenate([c[1] for c in log.calls]), want)
        assert _sink_readings() - before == 256
    finally:
        fleet.shutdown(drain=True)


def test_frame_sinks_sharing_one_batch_each_fire_once(emit_dir):
    """Two frames popped into one batch: each sink fires once, with its
    own rows only."""
    _, ccs = emit_dir
    fleet = _np_fleet(ccs["toy_a"], max_batch=256)
    rng = np.random.default_rng(41)
    ref = CircuitProgram.from_classifier(ccs["toy_a"]).predict
    frames = [rng.random((n, 9)) for n in (64, 40)]
    logs = [_SinkLog() for _ in frames]
    for x, log in zip(frames, logs):
        fleet.submit_many("toy_a", x, on_done=log)
    fleet.start()
    try:
        fleet.flush(timeout=60.0)
        assert fleet.stats_summary()["tenants"]["toy_a"]["n_batches"] == 1
        for x, log in zip(frames, logs):
            assert len(log.calls) == 1
            rows, labels, _, error = log.calls[0]
            assert error is None
            np.testing.assert_array_equal(rows, np.arange(len(x)))
            np.testing.assert_array_equal(labels, ref(x))
    finally:
        fleet.shutdown(drain=True)


def test_frame_sink_dispatch_failure_answers_every_row(emit_dir):
    """A failing dispatch reaches the sink as one error for the rows of
    each batch: every row is answered, none hangs."""
    _, ccs = emit_dir
    fleet = _np_fleet(ccs["toy_a"], max_batch=100)

    def boom(x):
        raise RuntimeError("device lost")

    for rep in fleet._tenant("toy_a").pool.replicas:
        rep.engine.classify_batch = boom
    log = _SinkLog()
    reqs, _, _ = fleet.submit_many(
        "toy_a", np.random.default_rng(43).random((256, 9)), on_done=log)
    fleet.start()
    try:
        fleet.flush(timeout=60.0)
        np.testing.assert_array_equal(log.rows(), np.arange(256))
        for rows, labels, lats, error in log.calls:
            assert labels is None and lats is None
            assert "device lost" in error
        for r in reqs:
            with pytest.raises(RuntimeError, match="device lost"):
                r.result(1.0)
        assert fleet.errors
    finally:
        fleet.shutdown(drain=True)


def test_shutdown_cancel_reaches_a_queued_frame_through_its_sink(emit_dir):
    _, ccs = emit_dir
    fleet = _np_fleet(ccs["toy_a"], max_batch=100)
    log = _SinkLog()
    reqs, _, _ = fleet.submit_many(
        "toy_a", np.random.default_rng(47).random((256, 9)), on_done=log)
    fleet.shutdown(drain=False)
    assert len(log.calls) == 1
    rows, labels, _, error = log.calls[0]
    np.testing.assert_array_equal(rows, np.arange(256))
    assert labels is None and error == "cancelled at shutdown"
    assert all(r.done() and r.error == error for r in reqs)


def test_shadow_observes_every_primary_of_a_sink_completed_frame(emit_dir):
    """Shadow pairing rides per-request callbacks on the primaries; a
    frame completed through its sink still closes every pair."""
    _, ccs = emit_dir
    fleet = _np_fleet(ccs["toy_a"], max_batch=100)
    shadow = TenantSpec(
        name="toy_a_shadow", backend="np", max_batch=100,
        deadline_ms=60_000.0,
        program=CircuitProgram.from_classifier(ccs["toy_a"], backend="np"))
    fleet.deploy_shadow(shadow, "toy_a")
    log = _SinkLog()
    fleet.submit_many("toy_a", np.random.default_rng(53).random((256, 9)),
                      on_done=log)
    fleet.start()
    try:
        fleet.flush(timeout=60.0)
        summary = fleet.retire_shadow("toy_a")
        assert summary["n_mirrored"] == 256
        assert summary["n_pairs"] == summary["n_agree"] == 256
        np.testing.assert_array_equal(log.rows(), np.arange(256))
    finally:
        fleet.shutdown(drain=True)
