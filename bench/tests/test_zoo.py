"""The zoo cell's reference, control and readers (CPU size).

* Draw 0 of each dataset is `reference.seeded_weights(dataset)`, the
  `table2_fleet` tenant, and the four draws are four classifiers.
* The bfloat16 control is rejected on zoo readings: the reference in
  bfloat16 disagrees with float32 on every seed's pools, and put in the
  program's place for a whole window it gives mismatched labels where the
  program gives none.
* The fused-launch readers are the arithmetic their definitions state,
  and return None on a run without the counters (as on a program that
  records none).
* A traced chipless run of the cell is correct and reports all four of
  the cell's per-layer metrics.
"""
import json
import sys

import numpy as np
import pytest

from harness import device as D
from harness import fused as F
from harness import reference as R
from harness import zoo_reference as Z

SEEDS = (31, 32, 33)
CELL = "table2_zoo20.flood"


def _config() -> dict:
    return json.loads((D.BENCH_DIR / "configs" /
                       "table2_zoo20.json").read_text())


@pytest.mark.parametrize("dataset", sorted(R.SPECS))
def test_draw_zero_is_the_fleet_tenant_and_draws_differ(dataset):
    base = R.seeded_weights(dataset)
    zero = Z.seeded_weights(f"{dataset}_v0")
    for a, b in ((base.w1, zero.w1), (base.w2, zero.w2),
                 (base.thresholds, zero.thresholds)):
        np.testing.assert_array_equal(a, b)
    draws = [Z.variant_weights(dataset, k) for k in range(4)]
    for i in range(4):
        for j in range(i + 1, 4):
            assert not (np.array_equal(draws[i].w1, draws[j].w1)
                        and np.array_equal(draws[i].w2, draws[j].w2))


def test_config_names_every_draw():
    cfg = _config()
    names = [t["name"] for t in cfg["tenants"]]
    assert names == [f"{d}_v{k}" for d in sorted(R.SPECS) for k in range(4)]
    for t in cfg["tenants"]:
        assert Z.split(t["name"]) == (t["dataset"], t["variant"])
        assert tuple(t["topology"]) == R.SPECS[t["dataset"]][6]
    with pytest.raises(KeyError):
        Z.split("arrhythmia")


@pytest.mark.parametrize("seed", SEEDS)
def test_bfloat16_reference_differs_on_zoo_readings(seed):
    wrong = 0
    for name in (t["name"] for t in _config()["tenants"]):
        ds = Z.make_dataset(name, seed)
        x = np.concatenate([ds.x_train, ds.x_test])
        w = Z.seeded_weights(name)
        wrong += int((R.tnn_labels(w, x) !=
                      R.tnn_labels(w, x, dtype="bfloat16")).sum())
    assert wrong > 0


@pytest.fixture(scope="module")
def zoo_cell():
    from harness.zoo_cell import ZooCell

    cell = ZooCell(_config())
    cell.setup()
    yield cell
    cell.close()


@pytest.mark.parametrize("seed", SEEDS[:1])
def test_bfloat16_control_fails_zoo(zoo_cell, seed):
    flood = json.loads((D.BENCH_DIR / "traffic" / "flood.json").read_text())
    assert zoo_cell.measure(seed, 1.0, flood, "bfloat16")["mismatched"] > 0
    assert zoo_cell.measure(seed, 1.0, flood)["mismatched"] == 0


TABLE = {"fleet.fused": {"n": 4, "s": 0.04},
         "fleet.fused.tenants": {"n": 30, "s": 0.0},
         "fleet.fused.gates_real": {"n": 900, "s": 0.0},
         "fleet.fused.gates_walked": {"n": 1000, "s": 0.0}}


def test_fused_readers_on_a_synthetic_layer_record():
    run = {"layer": {"spans": TABLE}}
    assert F.fused_tenants(run) == pytest.approx(7.5)
    assert F.fused_pad_pct(run) == pytest.approx(10.0)


@pytest.mark.parametrize("reader", ("fused_tenants", "fused_pad_pct"))
def test_fused_readers_without_counters_read_none(monkeypatch, reader):
    fn = getattr(F, reader)
    per_tenant = {"fleet.dispatch": {"n": 4, "s": 0.1}}
    assert fn({"layer": {"spans": per_tenant}}) is None
    assert fn({"layer": {"spans": {}}}) is None
    monkeypatch.setitem(sys.modules, "repro.obs", None)    # no such module
    assert fn({"layer": {}}) is None


def test_traced_zoo_run_reports_its_metrics(monkeypatch, capsys):
    from chipless import chipless, run_cell

    chipless(monkeypatch)
    line = run_cell(capsys, CELL, seconds=2.0, trace=1)
    assert line["correct"] is True
    got = line["metrics"]
    assert sorted(got) == ["circuit_roofline.zoo", "fused_pad_pct.zoo",
                           "fused_tenants.zoo", "step_mfu.zoo"]
    assert got["fused_tenants.zoo"]["value"] >= 1.0
    assert 0.0 <= got["fused_pad_pct.zoo"]["value"] < 100.0
