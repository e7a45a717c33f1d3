"""A 20-tenant classifier zoo through the fused megakernel (CPU size).

Five Table-2 topologies x four seeded draws, emitted into one directory
and served by `ClassifierFleet(megakernel=True)`.  Draw 0 is the golden
classifier of `tests/test_golden.py`; draw k >= 1 is the same
construction from seed tag ``golden:<dataset>:<k>``.  Pinned here:

  * every tenant's fused labels equal per-tenant dispatch and
    `core.tnn.predict_exact`;
  * the fleet's plan table gives a bounded set of launch shapes, warm-up
    compiles them all, and serving arbitrary due subsets afterwards adds
    no executable;
  * an `add_tenant` / `retire_tenant` generation rebuilds the table and
    labels stay exact;
  * the fused-launch counters add up: tenants a launch, and real gate
    steps never above walked ones.
"""
import hashlib

import numpy as np
import pytest

from repro import obs
from repro.compile import lower_classifier, write_artifacts
from repro.compile.artifact import load_program
from repro.core.ternary import (TERNARY_THRESHOLD, abc_binarize,
                                abc_fit_thresholds)
from repro.core.tnn import (TrainedTNN, balance_zero_counts, exact_netlists,
                            predict_exact)
from repro.data.tabular import DATASETS, make_dataset
from repro.kernels import pallas_circuit_sim as PS
from repro.serve import ClassifierFleet, TenantSpec

VARIANTS = 4
N_ROWS = 40             # one full and one partial batch at MAX_BATCH
MAX_BATCH = 32


def zoo_tnn(name: str, k: int) -> TrainedTNN:
    """Draw `k` of dataset `name`: the golden construction, seed tag
    ``golden:<name>`` for k = 0 and ``golden:<name>:<k>`` after."""
    ds = make_dataset(name)
    F, H, Cc = ds.spec.topology
    tag = f"golden:{name}" if k == 0 else f"golden:{name}:{k}"
    digest = hashlib.sha256(tag.encode()).digest()
    rng = np.random.default_rng(int.from_bytes(digest[:8], "little"))
    w1 = rng.normal(0.0, 0.7, size=(F, H))
    w2 = rng.normal(0.0, 0.7, size=(H, Cc))
    w1t = (np.sign(w1) * (np.abs(w1) > TERNARY_THRESHOLD)).astype(np.int8)
    return TrainedTNN(w1t=w1t, w2t=balance_zero_counts(w2, TERNARY_THRESHOLD),
                      thresholds=abc_fit_thresholds(ds.x_train),
                      train_acc=0.0, test_acc=0.0, name=f"{name}_v{k}")


@pytest.fixture(scope="module")
def zoo(tmp_path_factory):
    """The emit directory, and per tenant its readings and exact labels."""
    out = tmp_path_factory.mktemp("zoo20")
    refs = {}
    for name in sorted(DATASETS):
        x = make_dataset(name).x_test[:N_ROWS].astype(np.float64)
        for k in range(VARIANTS):
            tnn = zoo_tnn(name, k)
            write_artifacts(lower_classifier(tnn, *exact_netlists(tnn)), out,
                            base=tnn.name, dataset=name)
            xbin = np.asarray(abc_binarize(x, tnn.thresholds), np.uint8)
            refs[tnn.name] = (x, predict_exact(tnn, xbin))
    return out, refs


@pytest.fixture(scope="module")
def warm_fleet(zoo):
    out, _ = zoo
    fleet = ClassifierFleet.from_emit_dir(
        out, backends="pallas", max_batch=MAX_BATCH, deadline_ms=60_000.0,
        megakernel=True)
    yield fleet
    fleet.shutdown(drain=True)


def _serve(fleet, refs, names):
    handles = {n: fleet.submit_many(n, refs[n][0])[0] for n in names}
    fleet.flush(timeout=300.0)
    return {n: np.array([r.result(timeout=60.0) for r in hs], np.int32)
            for n, hs in handles.items()}


def test_variants_are_distinct_classifiers(zoo):
    _, refs = zoo
    assert len(refs) == len(DATASETS) * VARIANTS
    for name in DATASETS:
        w = [zoo_tnn(name, k).w1t for k in range(VARIANTS)]
        assert all(not np.array_equal(w[0], v) for v in w[1:]), name


def test_fused_labels_match_per_tenant_dispatch_and_exact(zoo, warm_fleet):
    out, refs = zoo
    got = _serve(warm_fleet, refs, sorted(refs))
    for n, (x, want) in refs.items():
        np.testing.assert_array_equal(got[n], want, err_msg=n)
        solo = load_program(out / f"{n}_program.npz", backend="pallas")
        np.testing.assert_array_equal(solo.predict(x), want, err_msg=n)
    assert warm_fleet.errors == []


def test_warm_fleet_serves_any_due_subset_without_compiling(zoo, warm_fleet):
    _, refs = zoo
    mk = warm_fleet.stats_summary()["megakernel"]
    assert mk["launch_shapes"] == [1, 2, 4, 8, 16, 20]
    compiled = PS._fleet_walk._cache_size()
    rng = np.random.default_rng(5)
    names = sorted(refs)
    for size in (1, 3, 7, 13, 20, 2, 17):
        pick = sorted(rng.choice(names, size=size, replace=False))
        got = _serve(warm_fleet, refs, pick)
        for n in pick:
            np.testing.assert_array_equal(got[n], refs[n][1], err_msg=n)
    assert PS._fleet_walk._cache_size() == compiled
    assert warm_fleet.errors == []


def test_add_and_retire_rebuild_the_table(zoo):
    out, refs = zoo
    fleet = ClassifierFleet.from_emit_dir(
        out, backends="pallas", max_batch=MAX_BATCH, deadline_ms=60_000.0,
        megakernel=True, warmup=False, tenants=sorted(refs)[:5])
    try:
        shapes = fleet.stats_summary()["megakernel"]["launch_shapes"]
        assert shapes == [1, 2, 4, 5]
        extra = sorted(refs)[5:8]
        for n in extra:
            fleet.add_tenant(TenantSpec(
                name=n, backend="pallas", max_batch=MAX_BATCH,
                deadline_ms=60_000.0,
                program=load_program(out / f"{n}_program.npz",
                                     backend="pallas")))
        assert fleet.stats_summary()["megakernel"]["launch_shapes"] == \
            [1, 2, 4, 8]
        got = _serve(fleet, refs, sorted(refs)[:8])
        for n, labels in got.items():
            np.testing.assert_array_equal(labels, refs[n][1], err_msg=n)
        for n in sorted(refs)[:3]:
            fleet.retire_tenant(n)
        assert fleet.stats_summary()["megakernel"]["launch_shapes"] == \
            [1, 2, 4, 5]
        got = _serve(fleet, refs, sorted(refs)[3:8])
        for n, labels in got.items():
            np.testing.assert_array_equal(labels, refs[n][1], err_msg=n)
        assert fleet.errors == []
    finally:
        fleet.shutdown(drain=True)


def test_fused_counters_add_up(zoo, warm_fleet):
    _, refs = zoo
    before = obs.snapshot()
    mk0 = warm_fleet.stats_summary()["megakernel"]
    _serve(warm_fleet, refs, sorted(refs))
    after = obs.snapshot()
    mk = warm_fleet.stats_summary()["megakernel"]

    def delta(name):
        return after[name]["n"] - before.get(name, {"n": 0})["n"]

    launches = delta("fleet.fused")
    assert launches == mk["launches"] - mk0["launches"] >= 2
    tenants = delta("fleet.fused.tenants")
    # every tenant's 40 rows: one full and one partial batch of 32
    assert tenants == 2 * len(refs)
    assert 1 <= tenants / launches <= len(refs)
    assert mk["peak_tenants_per_launch"] >= tenants / launches
    real, walked = delta("fleet.fused.gates_real"), \
        delta("fleet.fused.gates_walked")
    assert 0 < real <= walked
    # a batch fills whole words here (32 and 8 readings: one word each),
    # and each slot walks only its own gates
    assert real == walked
    assert 0.0 <= mk["pad_share"] < 1.0
    assert mk["mean_tenants_per_launch"] > 0


def test_launch_buckets():
    assert PS.launch_buckets(1) == (1,)
    assert PS.launch_buckets(5) == (1, 2, 4, 5)
    assert PS.launch_buckets(16) == (1, 2, 4, 8, 16)
    assert PS.launch_buckets(20) == (1, 2, 4, 8, 16, 20)


def test_table_walks_any_slot_order_and_counts_padding():
    """Slots in any order, fewer than the table's rows, padded to the next
    launch shape: each decodes as its program alone; gate steps count
    the unfilled words of a partial plane as padding."""
    from repro.core import circuits as C
    from repro.kernels import circuit_sim as CS

    rng = np.random.default_rng(3)
    shapes = [(5, 0, 2), (3, 17, 3), (9, 40, 4), (4, 9, 1), (7, 25, 2)]
    plans, refs = [], []
    for n_in, n_gates, n_out in shapes:
        pop = C.random_netlist_population(rng, n_in, n_gates, n_out, 1)
        plans.append((pop.op[0], pop.in0[0], pop.in1[0], pop.outputs[0],
                      n_in))
        refs.append(pop)
    table = PS.fleet_table(plans, 2)
    assert table.buckets == (1, 2, 4, 5)
    slots = [4, 0, 2]
    widths = [2, 1, 2]
    planes, want = [], []
    for s, w in zip(slots, widths):
        bits = (rng.random((w * 32, shapes[s][0])) < 0.5).astype(np.uint8)
        planes.append(np.asarray(CS.pack_bits32(bits)))
        want.append(refs[s].eval_uint(C.pack_vectors(bits))[0, : w * 32])
    for got, ref in zip(PS.fleet_walk(table, slots, planes), want):
        np.testing.assert_array_equal(got, ref)
    real, walked = table.gate_steps(slots, [2, 1, 1])
    assert real == 25 * 2 + 0 * 1 + 40 * 1
    assert walked == (25 + 0 + 40) * 2
