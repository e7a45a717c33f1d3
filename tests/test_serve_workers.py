"""Fleet-controller tier: QoS admission, token buckets, autoscaler.

Three layers of pinning:

  * **control law** — `TokenBucket` and `Autoscaler` are pure logic over
    injected clocks/round counters, so grow-after-sustained-pressure,
    shrink-after-idle, cooldown, min/max bounds and shadow immunity are
    all stepped to a decision in a bounded, known number of rounds.
  * **admission** — QoS classes and per-tenant rate limits gate
    `submit`/`submit_many` deterministically (fake fleet clock, workers
    parked): best-effort gives way to backend backlog while guaranteed
    traffic keeps admitting, malformed deadline tables reject the whole
    frame before any state changes, and under live synthetic overload
    guaranteed tenants finish with zero SLO misses while best-effort
    sheds absorb the pressure.
  * **autoscaling end-to-end** — a live fleet with `autoscale_tick()`
    driven manually (interval 0 ⇒ no background thread) grows a hot
    tenant to its ceiling and shrinks it back to the floor once drained,
    with no wall-clock dependence; shadows are never resized.

Hypothesis drives protocol-v2 deadline tables (NaN / scalar / per-row
mixes) end-to-end through encode → decode → `fleet.submit_many`,
asserting tail-shed ordering, shed accounting and `retry_after_ms`
consistency.  Example count follows REPRO_CONFORMANCE_EXAMPLES.
"""
import os
import threading
import time

import numpy as np
import pytest

from repro.compile import CircuitProgram, lower_classifier
from repro.core import tnn as T
from repro.serve import (AutoscaleConfig, Autoscaler, ClassifierFleet,
                         FleetOverloadError, MicroBatcher, TenantSignals,
                         TenantSpec, TokenBucket)
from repro.serve import protocol as P

N_EXAMPLES = int(os.environ.get("REPRO_CONFORMANCE_EXAMPLES", "20"))
F = 9       # toy tenant feature count


def _toy_classifier(seed=7, H=5, Cc=4):
    rng = np.random.default_rng(seed)
    w1t = rng.integers(-1, 2, size=(F, H)).astype(np.int8)
    w2t = T.balance_zero_counts(rng.normal(size=(H, Cc)), 1 / 3)
    tnn = T.TrainedTNN(w1t=w1t, w2t=w2t, thresholds=np.full(F, 0.5),
                       train_acc=0.0, test_acc=0.0, name=f"toy{seed}")
    return lower_classifier(tnn, *T.exact_netlists(tnn))


@pytest.fixture(scope="module")
def prog():
    return CircuitProgram.from_classifier(_toy_classifier(), backend="np")


def _spec(prog, name="toy", **kw):
    kw.setdefault("backend", "np")
    kw.setdefault("max_batch", 8)
    kw.setdefault("deadline_ms", 50.0)
    return TenantSpec(name=name, program=prog, **kw)


class _Clock:
    """Injectable fleet clock; tests advance `t` explicitly."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


class _SlowProgram:
    """Delegating program wrapper: every dispatch costs `delay_s` —
    synthetic overload without timing-sensitive producers."""

    def __init__(self, inner, delay_s):
        self._inner = inner
        self._delay_s = delay_s

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def predict(self, x):
        time.sleep(self._delay_s)
        return self._inner.predict(x)


# ---------------------------------------------------------------------------
# Token bucket: pure clock-injected logic
# ---------------------------------------------------------------------------
def test_token_bucket_grants_refills_and_hints():
    b = TokenBucket(10.0, 5.0, now=0.0)
    assert b.take_upto(3, 0.0) == 3          # starts full
    assert b.take_upto(10, 0.0) == 2         # partial grant, never negative
    assert b.take_upto(1, 0.0) == 0
    assert 0.0 < b.retry_after_s(1, 0.0) <= 0.1 + 1e-9
    assert b.take_upto(1, 0.11) == 1         # refilled at `rate`/s
    assert b.tokens(1e9) == 5.0              # capped at burst
    assert b.take_upto(0, 0.0) == 0
    assert b.retry_after_s(1, 1e9) == 0.0    # already available: no wait
    with pytest.raises(ValueError):
        TokenBucket(0.0, 5.0)
    with pytest.raises(ValueError):
        TokenBucket(1.0, 0.5)


def test_token_bucket_clock_never_runs_backwards():
    b = TokenBucket(1.0, 4.0, now=10.0)
    assert b.take_upto(4, 10.0) == 4
    assert b.take_upto(1, 9.0) == 0          # stale `now` cannot refill
    assert b.take_upto(1, 11.0) == 1


# ---------------------------------------------------------------------------
# Autoscaler: the round-based control law
# ---------------------------------------------------------------------------
def _sig(name, **kw):
    base = dict(pool_size=1, queue_depth=0, inflight=0, shed_delta=0,
                request_delta=0, est_dispatch_ms=0.1, max_batch=32,
                max_queue=64, min_replicas=1, max_replicas=4)
    base.update(kw)
    return TenantSignals(name=name, **base)


def test_autoscaler_grows_after_up_rounds_then_cools_down():
    a = Autoscaler(AutoscaleConfig(up_rounds=2, down_rounds=3,
                                   cooldown_rounds=1))
    assert a.observe([_sig("t", shed_delta=5)]) == []       # round 1 of 2
    acts = a.observe([_sig("t", shed_delta=5)])
    assert [(x.delta, x.reason) for x in acts] == [(1, "pressure")]
    # refractory round: pressure is ignored, counters reset
    assert a.observe([_sig("t", shed_delta=5, pool_size=2)]) == []
    assert a.observe([_sig("t", shed_delta=5, pool_size=2)]) == []
    acts = a.observe([_sig("t", shed_delta=5, pool_size=2)])
    assert acts and acts[0].delta == 1


def test_autoscaler_pressure_sources_queue_and_cost():
    cfg = AutoscaleConfig(up_rounds=1, cooldown_rounds=0, cost_high_ms=5.0)
    a = Autoscaler(cfg)
    # queue past queue_high_frac of capacity counts as pressure
    acts = a.observe([_sig("q", queue_depth=40, max_queue=64)])
    assert acts and acts[0].reason == "pressure"
    # dispatch-cost EMA past cost_high_ms counts as pressure
    acts = a.observe([_sig("c", est_dispatch_ms=9.0)])
    assert [x.name for x in acts] == ["c"]


def test_autoscaler_shrinks_only_after_sustained_idle():
    a = Autoscaler(AutoscaleConfig(up_rounds=1, down_rounds=2,
                                   cooldown_rounds=0))
    # busy-but-not-pressured rounds reset both hysteresis counters
    a.observe([_sig("t", pool_size=2, request_delta=3)])
    assert a.observe([_sig("t", pool_size=2)]) == []        # idle 1 of 2
    a.observe([_sig("t", pool_size=2, request_delta=1)])    # reset
    assert a.observe([_sig("t", pool_size=2)]) == []        # idle 1 of 2
    acts = a.observe([_sig("t", pool_size=2)])
    assert [(x.delta, x.reason) for x in acts] == [(-1, "idle")]


def test_autoscaler_respects_min_max_bounds():
    a = Autoscaler(AutoscaleConfig(up_rounds=1, down_rounds=1,
                                   cooldown_rounds=0))
    # at the ceiling: pressure decides nothing
    assert a.observe([_sig("t", shed_delta=9, pool_size=4,
                           max_replicas=4)]) == []
    # at the floor: idle decides nothing
    assert a.observe([_sig("t", pool_size=2, min_replicas=2)]) == []
    # grow is clamped to the remaining headroom
    a2 = Autoscaler(AutoscaleConfig(up_rounds=1, cooldown_rounds=0,
                                    grow_step=4))
    acts = a2.observe([_sig("t", shed_delta=9, pool_size=3, max_replicas=4)])
    assert [x.delta for x in acts] == [1]


def test_autoscaler_never_scales_shadows_and_drops_vanished_state():
    a = Autoscaler(AutoscaleConfig(up_rounds=1, cooldown_rounds=0))
    for _ in range(4):
        assert a.observe([_sig("sh", shed_delta=99, is_shadow=True)]) == []
    assert a.summary()["tracked"] == []
    a.observe([_sig("t", shed_delta=5)])
    assert a.summary()["tracked"] == ["t"]
    a.observe([])                            # tenant retired between rounds
    assert a.summary()["tracked"] == []


def test_autoscale_config_validates():
    for bad in (dict(up_rounds=0), dict(down_rounds=0),
                dict(cooldown_rounds=-1), dict(grow_step=0),
                dict(queue_high_frac=0.0), dict(queue_high_frac=1.5)):
        with pytest.raises(ValueError):
            AutoscaleConfig(**bad)


# ---------------------------------------------------------------------------
# Admission: rate limits + QoS, deterministic (fake clock, parked workers)
# ---------------------------------------------------------------------------
def test_rate_limit_gates_admission_under_fake_clock(prog):
    clk = _Clock()
    spec = _spec(prog, rate_limit_rps=10.0, rate_burst=4.0, max_queue=None)
    fleet = ClassifierFleet([spec], warmup=False, autostart=False, clock=clk)
    x = np.zeros((6, F))
    reqs, shed, retry = fleet.submit_many("toy", x)
    assert len(reqs) == 4                    # burst grants the head...
    assert shed.tolist() == [4, 5]           # ...and the tail sheds
    assert retry > 0.0
    with pytest.raises(FleetOverloadError) as ei:
        fleet.submit("toy", x[0])            # bucket is dry
    assert ei.value.reason == "rate" and ei.value.retry_after_ms >= 1.0
    clk.t = 0.5                              # 10 rps * 0.5 s = 5, cap 4
    reqs2, shed2, _ = fleet.submit_many("toy", x)
    assert len(reqs2) == 4 and shed2.tolist() == [4, 5]
    s = fleet.stats_summary()
    assert s["tenants"]["toy"]["n_shed"] == 5 == s["fleet"]["n_shed"]
    assert s["tenants"]["toy"]["rate_limit_rps"] == 10.0


def test_best_effort_gives_way_to_backend_backlog(prog):
    gold = _spec(prog, "gold", qos="guaranteed", max_queue=64)
    cheap = _spec(prog, "cheap", qos="best_effort", max_queue=64)
    fleet = ClassifierFleet([gold, cheap], warmup=False, autostart=False,
                            best_effort_backlog=4)
    x = np.zeros(F)
    for _ in range(3):                       # below threshold: both admit
        fleet.submit("gold", x)
    fleet.submit("cheap", x)
    with pytest.raises(FleetOverloadError) as ei:
        fleet.submit("cheap", x)             # backlog hit 4: give way
    assert ei.value.reason == "qos"
    reqs, shed, retry = fleet.submit_many("cheap", np.zeros((3, F)))
    assert reqs == [] and shed.tolist() == [0, 1, 2] and retry > 0
    fleet.submit("gold", x)                  # guaranteed keeps admitting
    s = fleet.stats_summary()
    assert s["tenants"]["cheap"]["n_shed"] == 4
    assert s["tenants"]["gold"]["n_shed"] == 0
    assert s["tenants"]["gold"]["qos"] == "guaranteed"
    assert s["tenants"]["cheap"]["qos"] == "best_effort"


def test_qos_class_and_bound_validation(prog):
    with pytest.raises(ValueError, match="qos"):
        ClassifierFleet([_spec(prog, qos="platinum")], warmup=False,
                        autostart=False)
    with pytest.raises(ValueError, match="min_replicas"):
        ClassifierFleet([_spec(prog, min_replicas=0)], warmup=False,
                        autostart=False)
    with pytest.raises(ValueError, match="max_replicas"):
        ClassifierFleet([_spec(prog, min_replicas=2, max_replicas=1)],
                        warmup=False, autostart=False)


def test_guaranteed_zero_slo_miss_while_best_effort_sheds():
    """Acceptance: under live synthetic overload, best-effort absorbs the
    sheds and every guaranteed request is served in budget."""
    cc = _toy_classifier()
    gprog = CircuitProgram.from_classifier(cc, backend="np")
    ref = CircuitProgram.from_classifier(cc).predict
    bprog = CircuitProgram.from_classifier(_toy_classifier(seed=11),
                                           backend="np")
    deadline_ms = 20_000.0
    gold = TenantSpec(name="gold", program=gprog, backend="np", max_batch=8,
                      deadline_ms=deadline_ms, qos="guaranteed")
    cheap = TenantSpec(name="cheap", program=bprog, backend="np",
                       max_batch=8, deadline_ms=deadline_ms,
                       max_queue=64, qos="best_effort")
    fleet = ClassifierFleet([gold, cheap], warmup=False, autostart=False,
                            best_effort_backlog=4)
    for name in ("gold", "cheap"):
        for rep in fleet._tenant(name).pool.replicas:
            rep.engine.program = _SlowProgram(rep.engine.program, 0.01)
    fleet.start()
    x = np.random.default_rng(7).random(F)
    want = int(ref(x[None, :])[0])
    g_reqs, cheap_sheds = [], 0
    try:
        for _ in range(120):
            g_reqs.append(fleet.submit("gold", x))
            try:
                fleet.submit("cheap", x)
            except FleetOverloadError as exc:
                assert exc.reason in ("qos", "queue")
                assert exc.retry_after_ms >= 1.0
                cheap_sheds += 1
        for r in g_reqs:                     # guaranteed: all served, right
            assert r.result(timeout=120.0) == want
    finally:
        fleet.shutdown(drain=True)
    s = fleet.stats_summary()
    assert cheap_sheds > 0, "overload never shed best-effort traffic"
    assert len(g_reqs) == 120                # guaranteed never shed
    assert s["tenants"]["gold"]["n_shed"] == 0
    assert s["tenants"]["gold"]["n_slo_miss"] == 0
    assert s["tenants"]["cheap"]["n_shed"] == cheap_sheds


# ---------------------------------------------------------------------------
# All-or-nothing frame admission (the validation-ordering regression)
# ---------------------------------------------------------------------------
def test_batcher_validates_whole_deadline_table_before_enqueue():
    mb = MicroBatcher(8, 20.0)
    mb.submit("keep", now=0.0)
    with pytest.raises(ValueError, match="deadline budget must be positive"):
        mb.submit_many(["a", "b", "c"], now=0.0,
                       deadlines_ms=[50.0, 30.0, -1.0])
    # the bad tail row must not leave earlier rows enqueued
    assert len(mb) == 1 and next(iter(mb)).item == "keep"
    entries = mb.submit_many(["a", "b"], now=0.0,
                             deadlines_ms=[float("nan"), 30.0])
    assert [e.deadline_s for e in entries] == pytest.approx([0.020, 0.030])


def test_fleet_submit_many_rejects_malformed_frames_whole(prog):
    fleet = ClassifierFleet([_spec(prog, max_queue=32)], warmup=False,
                            autostart=False)
    x = np.zeros((4, F))
    for bad in ([50.0, -1.0, 30.0, 20.0], 0.0, float("-inf")):
        with pytest.raises(ValueError, match="rejected whole"):
            fleet.submit_many("toy", x, deadlines_ms=bad)
    s = fleet.stats_summary()
    assert s["tenants"]["toy"]["pending"] == 0       # nothing enqueued
    assert s["fleet"]["n_shed"] == 0                 # nothing shed-counted
    reqs, shed, _ = fleet.submit_many("toy", x)
    assert len(reqs) == 4 and shed.size == 0
    assert reqs[0].uid == 0                          # no uids leaked


# ---------------------------------------------------------------------------
# Fleet autoscaling end-to-end: manual ticks, zero wall-clock dependence
# ---------------------------------------------------------------------------
def test_fleet_autoscaler_grows_hot_tenant_and_shrinks_idle(prog):
    cfg = AutoscaleConfig(up_rounds=2, down_rounds=2, cooldown_rounds=0)
    spec = _spec(prog, max_queue=4, replicas=1, max_replicas=3)
    fleet = ClassifierFleet([spec], warmup=False, autoscale=cfg,
                            autoscale_interval_s=0.0)    # no tick thread
    try:
        x = np.random.default_rng(0).normal(size=(64, F))
        for _ in range(2):                   # 64 rows into a 4-deep queue
            fleet.submit_many("toy", x)      # → sheds every round
            fleet.autoscale_tick()
        assert fleet.tenant_replicas("toy") == 2
        events = fleet.autoscale_events
        assert events and events[-1]["reason"] == "pressure"
        assert events[-1]["tenant"] == "toy" and events[-1]["applied"] == 1
        for _ in range(4):                   # two more hot rounds → ceiling
            fleet.submit_many("toy", x)
            fleet.autoscale_tick()
        assert fleet.tenant_replicas("toy") == 3     # capped at max_replicas
        fleet.flush()                        # drain; then idle rounds shrink
        for _ in range(8):
            fleet.autoscale_tick()
        assert fleet.tenant_replicas("toy") == 1     # back to the floor
        assert any(e["reason"] == "idle" for e in fleet.autoscale_events)
        s = fleet.stats_summary()
        assert s["autoscale"]["events"]              # surfaced to operators
        assert s["tenants"]["toy"]["pool_size"] == 1
    finally:
        fleet.shutdown(drain=False)


def test_fleet_autoscaler_never_scales_shadows(prog):
    shadow_prog = CircuitProgram.from_classifier(_toy_classifier(seed=11),
                                                 backend="np")
    cfg = AutoscaleConfig(up_rounds=1, cooldown_rounds=0)
    spec = _spec(prog, max_queue=4, max_replicas=3)
    fleet = ClassifierFleet([spec], warmup=False, autoscale=cfg,
                            autoscale_interval_s=0.0)
    try:
        fleet.deploy_shadow(_spec(shadow_prog, "toy-next", max_queue=4,
                                  max_replicas=3), of="toy")
        x = np.random.default_rng(1).normal(size=(64, F))
        for _ in range(3):                   # mirrored overload every round
            fleet.submit_many("toy", x)
            fleet.autoscale_tick()
        assert fleet.tenant_replicas("toy") == 3     # incumbent grew
        assert fleet._shadows["toy"].pool.size == 1  # shadow untouched
        assert all(e["tenant"] != "toy-next"
                   for e in fleet.autoscale_events)
    finally:
        fleet.shutdown(drain=False)


# ---------------------------------------------------------------------------
# stats_summary: consistent snapshots under concurrent admission
# ---------------------------------------------------------------------------
def test_stats_summary_consistent_under_concurrent_sheds(prog):
    specs = [_spec(prog, f"t{i}", max_queue=8) for i in range(3)]
    fleet = ClassifierFleet(specs, warmup=False)
    stop = threading.Event()

    def blast(name, seed):
        x = np.random.default_rng(seed).normal(size=(32, F))
        while not stop.is_set():
            fleet.submit_many(name, x)       # sheds return, never raise

    threads = [threading.Thread(target=blast, args=(s.name, i), daemon=True)
               for i, s in enumerate(specs)]
    for th in threads:
        th.start()
    try:
        torn = []
        for _ in range(200):
            snap = fleet.stats_summary()
            total = snap["fleet"]["n_shed"]
            per = sum(row["n_shed"] for row in snap["tenants"].values())
            if total != per:
                torn.append((total, per))
            for row in snap["tenants"].values():
                assert row["pending"] <= row["max_queue"]
        assert not torn, f"fleet/tenant shed totals disagreed: {torn[:5]}"
    finally:
        stop.set()
        for th in threads:
            th.join(timeout=10.0)
        fleet.shutdown(drain=False)


# ---------------------------------------------------------------------------
# Hypothesis: protocol-v2 deadline tables end-to-end
# ---------------------------------------------------------------------------
try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:

    _deadline_row = st.one_of(st.just(float("nan")),      # tenant default
                              st.floats(1.0, 1e4, allow_nan=False))
    _tables = st.one_of(st.none(), st.floats(1.0, 1e4, allow_nan=False))
    _E2E_PROG = CircuitProgram.from_classifier(_toy_classifier(),
                                               backend="np")

    @settings(max_examples=N_EXAMPLES, deadline=None)
    @given(st.integers(1, 24), st.integers(1, 16), st.data())
    def test_deadline_tables_end_to_end(B, max_queue, data):
        """Arbitrary v2 deadline tables (NaN/default/per-row mixes, shed
        tails) through decode → `fleet.submit_many`: admitted+shed == B,
        sheds are exactly the frame tail, NaN rows take the tenant
        default budget, shed accounting and the retry hint agree."""
        prog = _E2E_PROG
        dls = data.draw(st.one_of(
            _tables, st.lists(_deadline_row, min_size=B, max_size=B)))
        default_ms = 25.0
        fleet = ClassifierFleet([_spec(prog, max_queue=max_queue,
                                       deadline_ms=default_ms)],
                                warmup=False, autostart=False)
        plane = np.arange(B * F, dtype=np.float64).reshape(B, F)
        frame = P.encode_submit_batch(np.arange(B, dtype=np.uint64), "toy",
                                      plane, deadlines_ms=dls)
        msg = P.decode_message(frame[4:])     # strip the length prefix
        assert msg.tenant == "toy" and msg.readings.shape == (B, F)
        reqs, shed, retry = fleet.submit_many("toy", msg.readings,
                                              msg.deadlines_ms)
        n_admit = len(reqs)
        assert n_admit == min(B, max_queue)
        assert shed.tolist() == list(range(n_admit, B))   # tail, in order
        assert (retry > 0.0) == (n_admit < B)
        table = (np.full(B, np.nan) if dls is None
                 else np.broadcast_to(np.asarray(dls, dtype=np.float64),
                                      (B,)))
        for i, r in enumerate(reqs):
            d = table[i]
            want = default_ms if d != d else d
            assert r.deadline_ms == pytest.approx(want)
            np.testing.assert_array_equal(r.readings, plane[i])
        s = fleet.stats_summary()
        assert s["tenants"]["toy"]["n_shed"] == B - n_admit
        assert s["fleet"]["n_shed"] == B - n_admit
        assert s["tenants"]["toy"]["pending"] == n_admit
