"""The program's spans and counters (`repro.obs`).

* the table's counts and sums stay exact under concurrent writers;
* a fleet behind the socket server counts one decode span per frame and
  one dispatch, fetch and completion span per batch;
* a profiler trace of one dispatch carries the `dispatch.*` spans on the
  host plane, and the traced table holds exactly what was traced;
* the fleet records request latencies on the tenant and the fleet only,
  once a batch, with exact counts.
"""
import glob
import threading

import numpy as np
import pytest

from repro import obs
from repro.compile import lower_classifier, write_artifacts
from repro.core import tnn as T
from repro.serve import ClassifierFleet
from repro.serve.client import FleetClient
from repro.serve.server import FleetServer

MAX_BATCH = 64


def _delta(before: dict, after: dict, name: str) -> dict:
    b = before.get(name, {"n": 0, "s": 0.0})
    a = after.get(name, {"n": 0, "s": 0.0})
    return {"n": a["n"] - b["n"], "s": a["s"] - b["s"]}


def test_table_is_exact_under_eight_threads():
    n_threads, n_each = 8, 500
    before = obs.snapshot()
    go = threading.Barrier(n_threads)

    def work():
        go.wait()
        for _ in range(n_each):
            obs.add("test.obs.counter", 0.25, n=2)
            with obs.span("test.obs.span", worker=1):
                pass

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    after = obs.snapshot()
    counter = _delta(before, after, "test.obs.counter")
    assert counter == {"n": 2 * n_threads * n_each,
                       "s": 0.25 * n_threads * n_each}   # exact in binary
    sp = _delta(before, after, "test.obs.span")
    assert sp["n"] == n_threads * n_each and sp["s"] > 0.0


@pytest.fixture(scope="module")
def toy_fleet(tmp_path_factory):
    """Two toy tenants on the CPU, served by a localhost `FleetServer`."""
    out = tmp_path_factory.mktemp("obs_fleet")
    sizes = {"toy_a": (9, 5, 4), "toy_b": (6, 4, 3)}
    for seed, (name, (F, H, Cc)) in enumerate(sorted(sizes.items())):
        rng = np.random.default_rng(seed)
        tnn = T.TrainedTNN(
            w1t=rng.integers(-1, 2, size=(F, H)).astype(np.int8),
            w2t=T.balance_zero_counts(rng.normal(size=(H, Cc)), 1 / 3),
            thresholds=np.full(F, 0.5), train_acc=0.0, test_acc=0.0,
            name=name)
        write_artifacts(lower_classifier(tnn, *T.exact_netlists(tnn)), out,
                        base=name)
    fleet = ClassifierFleet.from_emit_dir(out, backends="swar",
                                          max_batch=MAX_BATCH,
                                          deadline_ms=5_000.0)
    server = FleetServer(fleet)
    host, port = server.start_background()
    yield fleet, (host, port), {n: s[0] for n, s in sizes.items()}
    server.stop()
    fleet.shutdown(drain=True)


def test_server_counts_frames_and_batches(toy_fleet):
    fleet, (host, port), features = toy_fleet
    rng = np.random.default_rng(5)
    n_frames = 6
    before = obs.snapshot()
    batches0 = fleet.stats.n_batches
    with FleetClient(host, port) as client:     # HELLO is a frame too
        for i in range(n_frames - 1):
            tenant = sorted(features)[i % 2]
            x = rng.random((MAX_BATCH, features[tenant]))
            labels = client.classify(tenant, x, timeout=60.0)
            assert labels.shape == (MAX_BATCH,)
    fleet.flush()
    after = obs.snapshot()
    batches = fleet.stats.n_batches - batches0
    assert batches >= n_frames - 1
    assert _delta(before, after, "serve.frame.decode")["n"] == n_frames
    assert _delta(before, after, "serve.frame.admit")["n"] == n_frames - 1
    for name in ("fleet.dispatch", "dispatch.fetch", "fleet.complete",
                 "fleet.queue_wait"):
        assert _delta(before, after, name)["n"] == batches, name
    assert _delta(before, after, "serve.write")["n"] >= 1


def test_stats_reply_carries_the_span_table(toy_fleet):
    fleet, (host, port), _ = toy_fleet
    spans = fleet.stats_summary()["spans"]
    assert spans["fleet.dispatch"]["n"] >= 1
    with FleetClient(host, port) as client:
        doc = client.stats()
    assert doc["spans"]["serve.frame.decode"]["n"] >= 1
    assert set(doc["spans"]["fleet.dispatch"]) == {"n", "s"}


def test_trace_of_one_dispatch_shows_the_dispatch_spans(toy_fleet, tmp_path):
    import jax
    from jax.profiler import ProfileData

    fleet, _, features = toy_fleet
    x = np.random.default_rng(6).random((MAX_BATCH, features["toy_a"]))
    untraced = obs.snapshot(traced=True)
    reqs, _, _ = fleet.submit_many("toy_a", x)
    fleet.flush()
    assert obs.snapshot(traced=True) == untraced
    with jax.profiler.trace(str(tmp_path)):
        reqs, _, _ = fleet.submit_many("toy_a", x)
        fleet.flush()
        assert all(r.done() for r in reqs)
    traced = obs.snapshot(traced=True)
    assert _delta(untraced, traced, "fleet.dispatch")["n"] == 1
    assert _delta(untraced, traced, "dispatch.fetch")["n"] == 1
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(path[0])
    names = {e.name for plane in pd.planes if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events}
    assert {"fleet.dispatch", "dispatch.gather", "dispatch.binarize",
            "dispatch.pack", "dispatch.launch", "dispatch.fetch",
            "fleet.complete"} <= names


def test_fleet_records_requests_once_on_tenant_and_fleet(toy_fleet):
    fleet, _, features = toy_fleet
    rng = np.random.default_rng(7)
    s0 = fleet.stats_summary()
    n = 0
    for tenant in sorted(features):
        for rows in (MAX_BATCH, 17):
            fleet.submit_many(tenant, rng.random((rows, features[tenant])))
            n += rows
    fleet.submit("toy_b", rng.random(features["toy_b"]))
    fleet.flush()
    s1 = fleet.stats_summary()
    assert s1["fleet"]["n_requests"] - s0["fleet"]["n_requests"] == n + 1
    for tenant in sorted(features):
        got = (s1["tenants"][tenant]["n_requests"]
               - s0["tenants"][tenant]["n_requests"])
        assert got == MAX_BATCH + 17 + (tenant == "toy_b")
        for r in fleet._tenant(tenant).pool.replicas:
            assert r.engine.stats.n_requests == 0
            assert len(r.engine.stats.request_ms) == 0
    assert fleet.stats.request_ms.total_pushed == s1["fleet"]["n_requests"]


@pytest.mark.parametrize("chunks", ((100,), (30, 50, 20), (64, 64, 1)))
def test_record_requests_matches_one_at_a_time(chunks):
    from repro.serve.engine import ServeStats

    rng = np.random.default_rng(8)
    lat = rng.random(sum(chunks)) * 4.0
    dl = np.where(rng.random(lat.size) < 0.2, np.nan, 2.0)
    one, many = ServeStats(window=64), ServeStats(window=64)
    for v, d in zip(lat, dl):
        one.record_request(float(v), None if np.isnan(d) else float(d))
    start = 0
    for c in chunks:
        many.record_requests(lat[start:start + c], dl[start:start + c])
        start += c
    assert many.n_requests == one.n_requests == lat.size
    assert many.n_slo_miss == one.n_slo_miss
    assert many.request_ms.total_pushed == one.request_ms.total_pushed
    np.testing.assert_array_equal(many.request_ms.values(),
                                  one.request_ms.values())
