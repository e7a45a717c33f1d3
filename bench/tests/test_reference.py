"""The plain references against the program's own host paths (CPU)."""
import numpy as np
import pytest

from harness import reference as R
from harness import work

DATASETS = sorted(R.SPECS)


@pytest.mark.parametrize("name", DATASETS)
def test_dataset_copy_matches_program(name):
    from repro.data.tabular import make_dataset

    for seed in (0, 2**33 + 5):
        a, b = R.make_dataset(name, seed), make_dataset(name, seed)
        np.testing.assert_array_equal(a.x_train, b.x_train)
        np.testing.assert_array_equal(a.y_test, b.y_test)


@pytest.mark.parametrize("name", DATASETS)
def test_labels_match_np_program(name):
    """Reference labels == CircuitProgram(backend="np") on the seeded
    classifier, over training and fresh-seed readings."""
    from harness.fleet_cell import build_emit_dir
    from repro.compile.artifact import load_program

    import tempfile
    from pathlib import Path

    with tempfile.TemporaryDirectory() as d:
        build_emit_dir([name], Path(d))
        prog = load_program(Path(d) / f"{name}_program.npz", backend="np")
    t = R.seeded_weights(name)
    for seed in (0, 7):
        ds = R.make_dataset(name, seed)
        x = np.concatenate([ds.x_train, ds.x_test])
        np.testing.assert_array_equal(R.tnn_labels(t, x),
                                      prog.predict(x.astype(np.float64)))


@pytest.mark.parametrize("name", DATASETS)
def test_seeded_circuit_is_the_golden_one(name):
    """Gate counts of the lowered seeded classifier match tests/golden."""
    import json

    from harness.fleet_cell import build_emit_dir
    from repro.compile.artifact import load_program

    import tempfile
    from pathlib import Path

    cfg = json.loads((Path(R.__file__).parents[1] / "configs"
                      / "table2_fleet.json").read_text())
    want = {t["name"]: t["gates"] for t in cfg["tenants"]}
    with tempfile.TemporaryDirectory() as d:
        build_emit_dir([name], Path(d))
        prog = load_program(Path(d) / f"{name}_program.npz", backend="np")
    assert prog.ir.n_gates == want[name]


def test_arrhythmia_launch_bytes():
    assert work.serving_launch_bytes(274, 256) == 274 * 8 * 4 + 256 * 4
    assert work.serving_window_bytes(274, 256) == 274 * 8 * 4 + 256 * 4


def test_bfloat16_rounding():
    x = np.array([1.0, 1.00390625, 1.005859375, 0.1], dtype=np.float32)
    got = R._bf16(x)
    # 1 + 2^-8 is a tie between 1 and 1 + 2^-7: rounds to even (1.0)
    np.testing.assert_array_equal(got[:3], [1.0, 1.0, 1.0078125])
    assert abs(got[3] - 0.1) < 2**-10


def test_campaign_objectives_match_program():
    """Reference (error rate, area) == the program's serial objective
    (`TNNApproxProblem._eval_one`) on the seeded whitewine problem, for
    the all-exact genome and random ones."""
    from conftest import SMALL_PHASE
    from harness.campaign_cell import (AREA_LIMIT, phase_products,
                                       reference_problem)
    from repro.core.ternary import abc_binarize
    from repro.core.tnn import TNNApproxProblem

    cfg = {"dataset": "whitewine", "phase": dict(SMALL_PHASE)}
    tnn, _, pcc_lib, pc_out = phase_products(cfg)
    ds = R.make_dataset("whitewine")
    prob = TNNApproxProblem(
        tnn=tnn, pcc_lib=pcc_lib, pc_out_lib=pc_out,
        xbin=np.asarray(abc_binarize(ds.x_train, tnn.thresholds)),
        y=ds.y_train)
    rp = reference_problem("whitewine", pcc_lib, pc_out)
    assert rp.hidden_genes == prob.hidden_idx
    assert R.fixed_area(rp.w1, rp.w2) == pytest.approx(
        prob.fixed_cost.area_mm2, rel=AREA_LIMIT)
    dom = prob.domains()
    rng = np.random.default_rng(3)
    genomes = np.concatenate([np.zeros((1, len(dom)), np.int64),
                              rng.integers(0, dom[None, :], (8, len(dom)))])
    ref = R.approx_objectives(rp, genomes)
    got = np.array([prob._eval_one(g) for g in genomes])
    np.testing.assert_array_equal(ref[:, 0], got[:, 0])
    np.testing.assert_allclose(ref[:, 1], got[:, 1], rtol=AREA_LIMIT)
